"""Seconds in the program's ``train_step.compile`` spans from the
measured step's first call to the window's opening: the store's key,
then the executable loaded from the AOT store or compiled (JAX's
persistent cache may serve that) and stored.  What the caches decide of
``first_step_s``; the rest of it is ``step_lower_s`` and the step's
first run."""

from benchmark import program_spans as ps


def applies(config, job) -> bool:
    return True


def read(obs):
    return ps.setup_seconds(obs, "train_step.compile")
