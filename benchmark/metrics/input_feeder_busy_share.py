"""Per cent of the window's blocks that the feeder thread of
``PrefetchIterator`` spent inside ``input.source`` spans.  Batches are made one after another
on that one thread, so at 100% it bounds the rate whatever the workers
and the device do."""

from benchmark import program_spans as ps


def applies(config, job) -> bool:
    return job["feed"] == "host"


def read(obs):
    return ps.busy_share(obs, "input.source")
