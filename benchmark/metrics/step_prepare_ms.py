"""Median over the window of the program's ``train_step.prepare`` span:
``DistributedTrainStep._dispatch`` from its entry to just before the
executable is called — the guard's limit, ``tree_flatten``, the per-call
key (shape, dtype and ``repr(sharding)`` of every leaf) and the look-up
in the step's own LRU.  The host's own work a step before the launch,
whether or not the device then keeps it waiting."""

from benchmark import program_spans as ps
from benchmark.metrics import median_ms


def applies(config, job) -> bool:
    return True


def read(obs):
    return median_ms(ps.seconds_in_window(obs, "train_step.prepare"))
