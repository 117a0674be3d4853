"""Model-parallelism strategies over the device mesh.

The reference implements data parallelism only (SURVEY §2.3); its closest
primitive to sequence/expert parallelism is the first-class variable-split
``alltoall`` (``operations.cc:979``, ``nccl_operations.cc:569``) — exactly
what DeepSpeed-Ulysses-style sequence parallelism is built on.  This
package goes from that primitive to the strategies themselves, TPU-first:

* :mod:`~horovod_tpu.parallel.mesh` — multi-axis mesh factory
  (dp/fsdp/pp/ep/sp/tp) laid out so the most communication-intensive axes
  ride ICI neighbors;
* :mod:`~horovod_tpu.parallel.plan` — the declarative
  :class:`~horovod_tpu.parallel.plan.ShardingPlan` (``HOROVOD_PLAN``
  grammar) driving the train step, the exchange scope and checkpoint
  resharding (docs/parallelism.md);
* :mod:`~horovod_tpu.parallel.pipeline` — GPipe and interleaved-1F1B
  pipeline schedules (``lax.scan`` + ``ppermute``, bubbles as masked
  compute);
* :mod:`~horovod_tpu.parallel.ring_attention` — blockwise ring attention
  over a sequence axis (``lax.ppermute`` rotation + online softmax);
* :mod:`~horovod_tpu.parallel.ulysses` — all-to-all sequence↔head
  exchange attention;
* :mod:`~horovod_tpu.parallel.tensor_parallel` — Megatron-style
  column/row-parallel Dense layers with a single ``psum`` per block;
* :mod:`~horovod_tpu.parallel.fsdp` — ZeRO-3-style fully-sharded data
  parallelism by parameter *placement* (GSPMD inserts the
  gather/reduce-scatter), wired into ``DistributedTrainStep`` via
  ``fsdp_axis=``.
"""

from horovod_tpu.parallel.mesh import (
    AXIS_DP,
    AXIS_EP,
    AXIS_FSDP,
    AXIS_PP,
    AXIS_SP,
    AXIS_TP,
    make_parallel_mesh,
)
from horovod_tpu.parallel.expert import expert_parallel_ffn, top1_routing
from horovod_tpu.parallel.fsdp import (
    fsdp_sharding,
    resident_bytes,
    shard_params,
    sharding_specs,
)
from horovod_tpu.parallel.pipeline import (
    bubble_fraction,
    gpipe,
    interleaved_1f1b,
    pipeline_ticks,
)
from horovod_tpu.parallel.plan import ShardingPlan, as_plan
from horovod_tpu.parallel.ring_attention import ring_attention
from horovod_tpu.parallel.ulysses import ulysses_attention
from horovod_tpu.parallel.tensor_parallel import (
    ColumnParallelDense,
    RowParallelDense,
)

__all__ = [
    "make_parallel_mesh",
    "AXIS_DP", "AXIS_FSDP", "AXIS_PP", "AXIS_EP", "AXIS_SP", "AXIS_TP",
    "ShardingPlan", "as_plan",
    "ring_attention", "ulysses_attention", "gpipe", "interleaved_1f1b",
    "pipeline_ticks", "bubble_fraction",
    "expert_parallel_ffn", "top1_routing",
    "ColumnParallelDense", "RowParallelDense",
    "fsdp_sharding", "shard_params", "sharding_specs", "resident_bytes",
]
