"""Device milliseconds a step with a collective in flight, read where the
compiler may have fused it (``benchmark/exchange.py``): each channel from
the start of the first operation that holds it — the collective, its
``-start`` or ``-done``, a fusion whose computation contains it — to the
end of the last, added up as a union.  Where nothing is fused it is
``collective_ms``."""

from benchmark import exchange


def applies(config, job) -> bool:
    return job["chips"] > 1


def read(obs):
    seconds = exchange.of_run(obs).get("exchange_s")
    return None if seconds is None else seconds * 1e3
