"""Seconds in the program's ``train_step.lower`` spans from the measured
step's first call to the window's opening: tracing the step and lowering
it to StableHLO (``runtime/compile_cache.aot_compile``), which no cache
shortens — only the program's structure does."""

from benchmark import program_spans as ps


def applies(config, job) -> bool:
    return True


def read(obs):
    return ps.setup_seconds(obs, "train_step.lower")
