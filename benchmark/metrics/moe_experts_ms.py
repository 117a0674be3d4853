"""Device milliseconds a step in the routed experts alone: what runs
under ``moe/experts`` — the grouped matmuls, the calls that build their
tile metadata and the activation between them."""

from benchmark import modules


def applies(config, job) -> bool:
    return "E" in config.get("layers", "")


def read(obs):
    return modules.ms_per_step(obs, "module_s", "moe/experts")
