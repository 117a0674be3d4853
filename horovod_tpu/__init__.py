"""horovod_tpu: a TPU-native distributed deep-learning training framework.

A ground-up re-design of the capabilities of Horovod v0.19.2 (reference:
``prpankajsingh/horovod``) for TPU hardware on JAX/XLA: the user-facing
contract — ``init()``/``rank()``/``size()``, five collectives with named
tensors and async handles, ``DistributedOptimizer``/gradient-tape
ergonomics, elastic training, a launcher, timeline tracing, autotuning —
rebuilt on SPMD compilation, ``jax.sharding.Mesh`` and XLA collectives
instead of a C++ negotiation thread over NCCL/MPI/Gloo.

Identity model (differs from the reference by design, see
``runtime/state.py``): ``size()`` is the number of *chips* (the
data-parallel degree — scale your LR by it, as reference examples do with
GPU count); ``process_rank()``/``process_count()`` give host-process
identity; ``rank() == 0`` on process 0 so "checkpoint on rank 0" carries
over.

Typical use (mirrors reference README.rst "Usage" 5-step recipe)::

    import horovod_tpu as hvd

    hvd.init()
    step = hvd.DistributedTrainStep(loss_fn, optax.adam(1e-3 * hvd.size()))
    params = hvd.broadcast_variables(params, root_rank=0)
    ...

Reference API parity map: ``horovod/common/basics.py`` (init/rank/size/
probes), ``horovod/torch/mpi_ops.py`` + ``tensorflow/mpi_ops.py``
(collectives), ``torch/optimizer.py`` + ``tensorflow/__init__.py``
(DistributedOptimizer), ``horovod/common/elastic.py`` (elastic State).
"""

from __future__ import annotations

from typing import Optional

from horovod_tpu.ops import (
    Adasum,
    Average,
    Compression,
    Handle,
    HorovodInternalError,
    ReduceOp,
    Sum,
    allgather,
    allgather_async,
    allreduce,
    allreduce_async,
    alltoall,
    alltoall_async,
    barrier,
    broadcast,
    broadcast_async,
    join,
    poll,
    synchronize,
)
from horovod_tpu.runtime import state as _state
from horovod_tpu.runtime.topology import AXIS_DCN, AXIS_ICI, GLOBAL_AXES

__version__ = "0.1.0"


# ---------------------------------------------------------------------------
# basics (reference horovod/common/basics.py)
# ---------------------------------------------------------------------------

def init(ranks: Optional[list] = None, comm=None):
    """Initialize the runtime (reference ``HorovodBasics.init``,
    ``basics.py:33``; C ``horovod_init`` ``operations.cc:679``).

    ``ranks``/``comm`` are accepted for signature parity; process membership
    on TPU comes from the launcher env contract + jax.distributed.
    """
    _state.init(ranks)
    return True


def shutdown():
    """Tear down the runtime (reference ``horovod_shutdown``)."""
    _state.shutdown()


def is_initialized() -> bool:
    return _state.is_initialized()


def start_timeline(file_path: str, mark_cycles: bool = False):
    """Start timeline recording at runtime (reference
    ``horovod_start_timeline``).

    Every process may pass the same (shared) path: non-root ranks record
    to ``<file_path>.<rank>`` so two writers never share a file, and
    :func:`stop_timeline` merges everything back into ``file_path`` on
    rank 0."""
    from horovod_tpu.utils.timeline import Timeline

    st = _state.global_state()
    if st.timeline is not None:
        st.timeline.close()
    if st.process_count > 1 and st.process_rank:
        file_path = f"{file_path}.{st.process_rank}"
    st.timeline = Timeline(file_path, mark_cycles=mark_cycles)


def stop_timeline():
    """Stop recording; in a multi-process world rank 0 then gathers every
    process's events into ONE merged Chrome trace (reference rank-0
    aggregated timeline, ``timeline.cc``)."""
    from horovod_tpu.utils.timeline import aggregate_after_close

    st = _state.global_state()
    if st.timeline is not None:
        fname = getattr(st.timeline, "filename", None)
        origin = getattr(st.timeline, "wall_origin_us", None)
        st.timeline.close()
        st.timeline = None
        if fname:
            aggregate_after_close(fname, origin)


def rank() -> int:
    """Global chip-rank of this process's first device; 0 on process 0."""
    return _state.global_state().rank


def size() -> int:
    """Total number of chips == data-parallel degree."""
    return _state.global_state().size


def local_rank() -> int:
    return _state.global_state().local_rank


def local_size() -> int:
    """Chips driven by this process."""
    return _state.global_state().local_size


def cross_rank() -> int:
    """Slice index of this process (reference CROSS communicator rank)."""
    return _state.global_state().cross_rank


def cross_size() -> int:
    """Number of slices (reference CROSS communicator size)."""
    return _state.global_state().cross_size


def process_rank() -> int:
    return _state.global_state().process_rank


def process_count() -> int:
    return _state.global_state().process_count


def is_homogeneous() -> bool:
    """True when every process drives the same number of chips (reference
    ``horovod_is_homogeneous``; checked in ``mpi_controller.cc:26``)."""
    return _state.global_state().is_homogeneous


def mesh():
    """The global (dcn, ici) runtime mesh for SPMD training."""
    return _state.global_state().mesh


# -- capability probes (reference basics.py:71-233 *_built/enabled) --------

def xla_built() -> bool:
    return True


def tpu_available() -> bool:
    """True when the default backend holds a TPU.  A backend that fails
    to initialise raises — that is an error, not "no TPU"."""
    import jax

    return any(d.platform == "tpu" for d in jax.devices())


def native_built() -> bool:
    """True when the C++ runtime components (timeline writer, rendezvous
    KV store) compiled and loaded."""
    from horovod_tpu import native

    return native.native_built()


def mpi_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def gloo_built() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def mpi_threads_supported() -> bool:
    return False


def current_operations() -> str:
    """Name of the eager data plane in use ("XLA" or "HOST"), selected by
    ``HOROVOD_TPU_OPERATIONS`` / ``--tpu-operations`` — the introspection
    probe for the op-manager priority chain (reference
    ``HOROVOD_CPU_OPERATIONS`` + ``horovod_*_built`` probes,
    ``operations.cc:784``)."""
    from horovod_tpu.ops import op_manager

    return op_manager.current_operations()


def cache_stats() -> dict:
    """Compiled-executable cache counters (reference response-cache
    observability, ``response_cache.{h,cc}``): ``hits``/``misses``
    count the in-memory signature caches (eager negotiation layer and
    each ``DistributedTrainStep``'s executable LRU, bounded by
    ``HOROVOD_CACHE_CAPACITY``).  Whether a step's compile was served
    from the persistent warm-start cache is
    ``DistributedTrainStep.compile_cache_hit``.  ``bench.py`` surfaces
    both in the BENCH JSON."""
    from horovod_tpu.runtime import state as _state

    if not _state.is_initialized():
        return {"hits": 0, "misses": 0}
    return dict(_state.global_state().cache_stats)


# ---------------------------------------------------------------------------
# higher-level API re-exports (populated by submodule imports)
# ---------------------------------------------------------------------------

from horovod_tpu.functions import (  # noqa: E402
    allgather_object,
    broadcast_object,
    broadcast_optimizer_state,
    broadcast_parameters,
    broadcast_variables,
)
from horovod_tpu.optim import (  # noqa: E402
    DistributedAdasumOptimizer,
    DistributedGradientTape,
    DistributedOptimizer,
    DistributedTrainStep,
    SyncBatchNorm,
)
from horovod_tpu import callbacks  # noqa: E402,F401
from horovod_tpu import checkpoint  # noqa: E402,F401
from horovod_tpu import data  # noqa: E402,F401
from horovod_tpu import elastic  # noqa: E402,F401
from horovod_tpu import faults  # noqa: E402,F401
from horovod_tpu import guard  # noqa: E402,F401

__all__ = [
    # basics
    "init", "shutdown", "is_initialized", "rank", "size", "local_rank",
    "local_size", "cross_rank", "cross_size", "process_rank", "process_count",
    "is_homogeneous", "mesh", "start_timeline", "stop_timeline",
    # probes
    "xla_built", "tpu_available", "native_built", "mpi_built", "mpi_enabled", "gloo_built",
    "gloo_enabled", "nccl_built", "ddl_built", "ccl_built", "cuda_built",
    "rocm_built", "mpi_threads_supported", "current_operations",
    "cache_stats",
    # collectives
    "allreduce", "allreduce_async", "allgather", "allgather_async",
    "alltoall", "alltoall_async", "broadcast_async", "barrier",
    "broadcast", "join", "poll", "synchronize",
    "Average", "Sum", "Adasum", "ReduceOp", "Compression", "Handle",
    "HorovodInternalError",
    # axes
    "AXIS_DCN", "AXIS_ICI", "GLOBAL_AXES",
    # functions
    "broadcast_variables", "broadcast_parameters", "broadcast_object",
    "broadcast_optimizer_state", "allgather_object",
    # optimizer layer
    "DistributedOptimizer", "DistributedAdasumOptimizer",
    "DistributedGradientTape", "DistributedTrainStep",
    "SyncBatchNorm",
    # callbacks + checkpoint + data pipeline + elastic + integrity plane
    "callbacks", "checkpoint", "data", "elastic", "guard",
]
