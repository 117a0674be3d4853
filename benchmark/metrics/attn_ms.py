"""Device milliseconds a step in the attention mixers of a hybrid model
(``benchmark/modules.py``: qkv and output projections, the repetition of
key/value heads, the flash kernels)."""

from benchmark import modules


def applies(config, job) -> bool:
    return "*" in config.get("layers", "")


def read(obs):
    return modules.ms_per_step(obs, "module_s", "attn")
