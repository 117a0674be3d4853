"""Device milliseconds a step in operations that hold a collective and
nothing else — a synchronous collective, a ``-start`` or ``-done``, a
fusion whose computation has only the collective and plumbing — while
no other operation runs (``benchmark/exchange.py``): the exchange left
exposed, fused or not.  Where nothing is fused it is
``collective_exposed_ms``; where the compiler runs the exchange in
fusions, that metric reads the synchronous remainder and the difference
is the async chain's start, done and bare steps."""

from benchmark import exchange


def applies(config, job) -> bool:
    return job["chips"] > 1


def read(obs):
    seconds = exchange.of_run(obs).get("exchange_exposed_s")
    return None if seconds is None else seconds * 1e3
