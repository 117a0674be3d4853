"""Median over the window of the program's ``input.source`` span: the
one feeder thread of ``PrefetchIterator`` in ``next(source)`` — the
dataset making one host batch."""

from benchmark import program_spans as ps
from benchmark.metrics import median_ms


def applies(config, job) -> bool:
    return job["feed"] == "host"


def read(obs):
    return median_ms(ps.seconds_in_window(obs, "input.source"))
