"""Median time ``next(feed)`` blocked inside the window:
``PrefetchIterator.stall_samples``, the program's own host clock around
its blocking ``get`` (sound: bench.py verdict, ISSUE 22).

Inside a block nothing paces the loop but the feed, so the host drains
the prefetch queue and then takes batches as the pipeline makes them:
this is the pipeline's time to make a batch, less the dispatch that
runs meanwhile — not time the device waited.  It costs the rate once a
block's waits and dispatches together outlast the block's device time
(``device_idle_share`` under ``next_batch`` then says so)."""

from benchmark.metrics import median_ms


def applies(config, job) -> bool:
    return job["feed"] == "host"


def read(obs):
    return median_ms(obs.stall_samples)
