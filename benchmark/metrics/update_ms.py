"""Device milliseconds a step under the scope ``update`` of
``DistributedTrainStep`` (``optimizer.update`` and
``optax.apply_updates``; in the sharded exchange the shard-local update
between ``exchange/scatter`` and ``exchange/gather``), by
``benchmark/phases.py``'s rules: the update that *stands alone*.  On one
chip XLA rides most of a dense model's update in the weight gradients'
fusions, whose root is the matmul's — that time is the module's, not
this reader's; an expert model's update, and any model's on several
chips, stands in fusions of its own.  A step that lays no scope (a
parent commit) reads as nothing.  Applies where the configuration lists
a kernel: only such a step's executable is surely this tree's
(``phases.key_moves_with_names``)."""

from benchmark import phases


def applies(config, job) -> bool:
    return phases.key_moves_with_names(config)


def read(obs):
    return phases.ms_per_step(obs, "update")
