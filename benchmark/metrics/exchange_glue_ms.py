"""Device milliseconds a step under the scope ``exchange`` in operations
that hold no collective: the pads, packs, slices and casts that prepare
a reduce-scatter (``exchange/scatter``, the parameters' slabs among
them), what undoes an all-gather (``exchange/gather``) and the loss's
and the guard's plumbing, by ``benchmark/phases.py``'s rules.  The
collectives themselves are the ``exchange_*`` readers', and a fusion
with no path that holds one (XLA's pad + all-reduce + dynamic-slice for
a reduce-scatter) is not under the scope: its time is the wire's as much
as the glue's, and ``exchange_fused_ms`` reads it.  A step that lays no
scope (a parent commit) reads as nothing."""

from benchmark import phases


def applies(config, job) -> bool:
    return job["train_step"].get("mode") == "shard_map"


def read(obs):
    return phases.ms_per_step(obs, "exchange")
