"""Milliseconds a step during which a collective was in flight on the
slowest device (an async one from its ``-start`` to the end of its
``-done``), from the device trace."""

from benchmark.metrics import trace_ms_per_step


def applies(config, job) -> bool:
    return job["chips"] > 1


def read(obs):
    return trace_ms_per_step(obs, obs.trace.get("collective_s"))
