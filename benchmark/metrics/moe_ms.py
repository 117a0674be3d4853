"""Device milliseconds a step in the expert layers (``benchmark/
modules.py``: router, dispatch, the grouped matmuls, the shared expert
and the combine, forward, recomputation and backward)."""

from benchmark import modules


def applies(config, job) -> bool:
    return "E" in config.get("layers", "")


def read(obs):
    return modules.ms_per_step(obs, "module_s", "moe")
