"""The part of ``collective_ms`` during which no other operation ran on
that device: the exchange left exposed."""

from benchmark.metrics import trace_ms_per_step


def applies(config, job) -> bool:
    return job["chips"] > 1


def read(obs):
    return trace_ms_per_step(obs, obs.trace.get("collective_exposed_s"))
