"""Median host time of the un-fenced ``step(params, opt_state, batch)``
call inside the window: the benchmark's own span around it.  While the
device is the bottleneck this includes the time the call waits for a
free slot in the runtime's queue; it is the host's cost a step only
while the host runs ahead."""

from benchmark.metrics import median_ms


def applies(config, job) -> bool:
    return True


def read(obs):
    return median_ms(obs.spans.seconds("dispatch"))
