"""Device milliseconds a step in operations that ``benchmark/phases.py``'s
rules leave unnamed: no scope of the program's, no flax module and no
read through a fusion's computation reaches them — XLA's own copies
between memory spaces (``copy-start`` / ``copy-done``, ``slice-start`` /
``slice-done``), what its reduce-scatter decomposer leaves bare, and, on
a program that lays no scope (a parent commit), everything outside the
model's modules.  What no reader, present or future, can reach by
name.  Applies where the configuration lists a kernel: only such a
step's executable is surely this tree's
(``phases.key_moves_with_names``)."""

from benchmark import phases


def applies(config, job) -> bool:
    return phases.key_moves_with_names(config)


def read(obs):
    if not phases.of_run(obs):
        return None
    return phases.ms_per_step(obs, "unnamed") or 0.0
