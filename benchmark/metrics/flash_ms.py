"""Device milliseconds a step in the Mosaic custom calls (the flash
forward and both backward kernels)."""

from benchmark.metrics import class_seconds, trace_ms_per_step


def applies(config, job) -> bool:
    return "flash" in config["kernels"]


def read(obs):
    return trace_ms_per_step(obs, class_seconds(obs, "mosaic"))
