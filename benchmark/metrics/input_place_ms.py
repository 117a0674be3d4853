"""Median over the window of the program's ``input.place`` span: a pool
worker of ``PrefetchIterator`` in ``place(batch)`` — ``shard_batch`` and
the ``device_put`` it issues."""

from benchmark import program_spans as ps
from benchmark.metrics import median_ms


def applies(config, job) -> bool:
    return job["feed"] == "host"


def read(obs):
    return median_ms(ps.seconds_in_window(obs, "input.place"))
