"""Device milliseconds a step in fusions that carry no path anywhere —
not their own, not their computation's — and hold a collective: what
XLA's reduce-scatter decomposer writes without metadata for a leaf it
cannot scatter as it stands, pad + all-reduce + dynamic-slice in one
fusion (``exchange_fused`` in ``benchmark/phases.py``'s rules; 49 of
``zero4``'s 98 leaves).  The wire's time and the glue's in one
operation, which is why neither ``exchange_glue_ms`` (no collective)
nor the ``exchange_*`` readers (a collective as an operation of its
own, or a fusion with a path) count it.  The group rests on no scope of
the program's: a parent commit reads it too.  0 where the step holds no
such fusion."""

from benchmark import phases


def applies(config, job) -> bool:
    return job["train_step"].get("mode") == "shard_map"


def read(obs):
    if not phases.of_run(obs):
        return None
    return phases.ms_per_step(obs, "exchange_fused") or 0.0
