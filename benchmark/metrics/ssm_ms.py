"""Device milliseconds a step in the Mamba-2 mixers (``benchmark/
modules.py``: every operation lowered from under a ``mamba`` module —
projections, convolution, the chunked scan, the gated norm — forward,
recomputation and backward)."""

from benchmark import modules


def applies(config, job) -> bool:
    return "M" in config.get("layers", "")


def read(obs):
    return modules.ms_per_step(obs, "module_s", "mamba")
