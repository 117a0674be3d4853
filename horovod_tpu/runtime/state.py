"""Process-wide runtime state: the TPU-native ``HorovodGlobalState``.

The reference keeps one ``HorovodGlobalState`` singleton per process
(``horovod/common/global_state.h:42-122``, instantiated at
``operations.cc:114``) owning the background thread, controller, fusion
buffer, timeline and tensor queue.  SPMD compilation removes the
negotiation thread and the tensor queue — XLA schedules collectives inside
the compiled step — but the process singleton survives: it owns the device
mesh, resolved config, timeline, stall watchdog and shutdown flag, and it is
what ``init()``/``shutdown()`` (``operations.cc:679``, ``basics.py:33``)
create and destroy.

Identity semantics (deliberate TPU re-design, documented in README):

* a *worker* in the reference is one process == one GPU; under JAX one
  process drives many chips.  ``rank``/``size`` here are **chip-level** —
  ``size()`` is the data-parallel degree you scale the LR by, exactly as in
  reference examples — while ``process_rank``/``process_count`` give the
  host-process identity.  ``rank() == 0`` iff ``process_rank == 0``, so the
  "checkpoint on rank 0" idiom carries over unchanged.
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import Optional

import jax

from horovod_tpu.runtime.config import Config
from horovod_tpu.runtime import topology
from horovod_tpu.utils import logging as hvd_logging


class NotInitializedError(RuntimeError):
    def __init__(self):
        super().__init__(
            "horovod_tpu has not been initialized; call horovod_tpu.init() first.")


class GlobalState:
    """Singleton runtime object (reference ``HorovodGlobalState``)."""

    def __init__(self, config: Config):
        self.config = config
        self.initialization_done = False
        self.shut_down = False
        self._lock = threading.Lock()

        # populated by initialize()
        self.mesh = None
        self.process_rank = 0
        self.process_count = 1
        self.rank = 0
        self.size = 1
        self.local_rank = 0
        self.local_size = 1
        self.cross_rank = 0
        self.cross_size = 1
        self.is_homogeneous = True

        # aux subsystems, attached lazily to avoid import cycles
        self.timeline = None
        self.stall_inspector = None
        self.parameter_manager = None
        self.elastic_context = None
        # compiled-executable cache counters (the response-cache
        # observability analogue): "hits"/"misses" count the in-memory
        # signature caches (eager negotiation layer + each
        # DistributedTrainStep's executable LRU).  bench.py surfaces
        # them in the BENCH JSON.
        self.cache_stats = {"hits": 0, "misses": 0}
        # warm-start cache root resolved at initialize() (None = disabled)
        self.compile_cache_dir = None
        # telemetry exporters started at initialize() (None = metrics off;
        # the registry itself is process-global, horovod_tpu/telemetry)
        self.telemetry = None

    # -- bootstrap ---------------------------------------------------------

    def initialize(self, ranks: Optional[list] = None) -> None:
        cfg = self.config

        # chaos layer first: if a fault plan is configured it must be
        # live before any instrumented subsystem starts (the plan's own
        # loader logs loudly — an active plan in production is an
        # operator mistake worth shouting about)
        if cfg.fault_plan:
            from horovod_tpu import faults

            faults.load_env_plan()

        # HOROVOD_THREAD_AFFINITY: confine this worker to its core set
        # (reference parse_and_set_affinity, common.cc).  Must run BEFORE
        # any jax.distributed setup — sched_setaffinity is inherited only
        # by threads created afterwards, and the distributed runtime's
        # gRPC/heartbeat threads are exactly what the mask should cover.
        from horovod_tpu.utils.affinity import set_affinity_from_env

        set_affinity_from_env(cfg.local_rank or 0)

        # Multi-process bootstrap: the coordination-service analogue of the
        # reference's gloo rendezvous (gloo_context.cc:71-91).  The launcher
        # sets HOROVOD_COORDINATOR_ADDR + HOROVOD_RANK/SIZE; jax.distributed
        # then wires all processes into one SPMD world.  Elastic runs use
        # the driver-hosted service + survivable client instead (see
        # runtime/distributed.py: worker death must surface as a catchable
        # error, not the stock client's process termination).
        if cfg.coordinator_addr and cfg.size and cfg.size > 1:
            if cfg.elastic_enabled:
                from horovod_tpu.runtime import distributed as hvd_dist

                if not hvd_dist.elastic_client_active():
                    hvd_dist.connect_elastic_client(
                        cfg.coordinator_addr, cfg.size, cfg.rank,
                        heartbeat_timeout=int(os.environ.get(
                            "HOROVOD_ELASTIC_HEARTBEAT_TIMEOUT",
                            hvd_dist.DEFAULT_HEARTBEAT_TIMEOUT_S)))
            elif not jax.distributed.is_initialized():
                jax.distributed.initialize(
                    coordinator_address=cfg.coordinator_addr,
                    num_processes=cfg.size,
                    process_id=cfg.rank,
                )
                hvd_logging.info(
                    "jax.distributed initialized: process %s of %s via %s",
                    cfg.rank, cfg.size, cfg.coordinator_addr)

        self.process_rank = jax.process_index()
        self.process_count = jax.process_count()

        self.mesh = topology.build_mesh(cfg.mesh_shape)
        self.size = topology.mesh_size(self.mesh)

        local = jax.local_device_count()
        self.local_size = local
        self.local_rank = 0
        self.rank = self.process_rank * local  # chip-rank of first local device
        # homogeneity check mirrors MPIController::DoInitialization
        # (mpi_controller.cc:26): all processes must drive equal chip counts
        # for local/cross arithmetic to be meaningful.
        self.is_homogeneous = (self.size == local * self.process_count)

        # cross = slice/host-level (reference CROSS communicator,
        # common.h:113-117).  A process's CROSS identity is the slice its
        # devices live on — NOT its process rank; slices may span several
        # processes.
        self.cross_size = self.mesh.shape[topology.AXIS_DCN]
        sid = getattr(jax.local_devices()[0], "slice_index", None)
        if sid is None:
            # off-TPU there is no slice topology; processes are laid out
            # over the dcn axis in rank order
            sid = (self.process_rank * self.cross_size) // max(
                self.process_count, 1)
        self.cross_rank = min(int(sid), self.cross_size - 1)
        if cfg.cross_rank is not None:
            self.cross_rank = cfg.cross_rank
        if cfg.cross_size is not None:
            self.cross_size = cfg.cross_size

        # warm-start layer: JAX's persistent compilation cache, placed
        # by runtime/compile_cache.py.  Enabled by default — a restarted
        # process (elastic reset, relaunched job) then reuses compiled
        # artifacts instead of recompiling.  Placed by
        # JAX_COMPILATION_CACHE_DIR when set, else beside the package.
        from horovod_tpu.runtime import compile_cache

        self.compile_cache_dir = \
            compile_cache.enable_persistent_cache(config=cfg)
        if self.compile_cache_dir:
            hvd_logging.info("compile cache: %s", self.compile_cache_dir)

        # telemetry plane BEFORE timeline/stall: both render registered
        # gauges (timeline counter rows) and count through the registry
        from horovod_tpu import telemetry

        self.telemetry = telemetry.start_from_config(
            cfg, process_rank=self.process_rank)

        if cfg.timeline_filename:
            self.timeline = _make_timeline(cfg, self.process_rank
                                           if self.process_count > 1 else 0)
        if cfg.stall_check_enabled:
            from horovod_tpu.utils.stall import StallInspector

            self.stall_inspector = StallInspector(
                warning_time_s=cfg.stall_warning_time_seconds,
                shutdown_time_s=cfg.stall_shutdown_time_seconds)
        if cfg.autotune:
            from horovod_tpu.utils.autotune import ParameterManager

            self.parameter_manager = ParameterManager(
                self.config, log_path=cfg.autotune_log)

        self.initialization_done = True
        hvd_logging.info(
            "horovod_tpu initialized: %d chips (%d process(es) x %d local), "
            "mesh dcn=%d ici=%d",
            self.size, self.process_count, local,
            self.mesh.shape[topology.AXIS_DCN],
            self.mesh.shape[topology.AXIS_ICI])

    def shutdown(self) -> None:
        with self._lock:
            if self.shut_down:
                return
            if self.timeline is not None:
                fname = getattr(self.timeline, "filename", None)
                origin = getattr(self.timeline, "wall_origin_us", None)
                self.timeline.close()
                self.timeline = None
                if fname:
                    from horovod_tpu.utils.timeline import \
                        aggregate_after_close

                    aggregate_after_close(fname, origin)
            if self.stall_inspector is not None:
                self.stall_inspector.stop()
            if self.telemetry is not None:
                # final JSONL snapshot + endpoint teardown; the registry
                # itself survives (elastic resets re-init around it)
                self.telemetry.shutdown()
                self.telemetry = None
            self.shut_down = True
            self.initialization_done = False


def _make_timeline(cfg: Config, process_rank: int = 0):
    """Prefer the native lock-free writer (reference timeline.{h,cc} is
    C++); fall back to the Python writer when the toolchain is absent.

    Non-root processes write a per-rank derived path so a shared
    ``HOROVOD_TIMELINE`` never has two writers; ``stop_timeline``'s
    aggregation then merges everything into rank 0's file — the one
    configured path holds the one trace, the reference's UX."""
    filename = cfg.timeline_filename
    if process_rank:
        filename = f"{filename}.{process_rank}"
    if not os.environ.get("HOROVOD_TIMELINE_PYTHON"):
        try:
            from horovod_tpu.native import NativeTimeline

            return NativeTimeline(filename,
                                  mark_cycles=cfg.timeline_mark_cycles)
        except (RuntimeError, OSError):
            pass
    from horovod_tpu.utils.timeline import Timeline

    return Timeline(filename, mark_cycles=cfg.timeline_mark_cycles)


_state: Optional[GlobalState] = None
_state_lock = threading.Lock()


@atexit.register
def _shutdown_at_exit() -> None:
    # one process-wide hook, not one per init() — elastic resets re-init
    # many times (reference registers its background-thread teardown once
    # in InitializeHorovodOnce)
    if _state is not None:
        _state.shutdown()


def init(ranks: Optional[list] = None, config: Optional[Config] = None) -> GlobalState:
    """Create (or return) the singleton; idempotent like ``horovod_init``
    (reference ``operations.cc:620`` InitializeHorovodOnce)."""
    global _state
    with _state_lock:
        if _state is not None and _state.initialization_done:
            return _state
        cfg = config or Config.from_env()
        st = GlobalState(cfg)
        st.initialize(ranks)
        _state = st
        return st


def shutdown() -> None:
    global _state
    with _state_lock:
        if _state is not None:
            _state.shutdown()
            _state = None


def is_initialized() -> bool:
    return _state is not None and _state.initialization_done


def global_state() -> GlobalState:
    if _state is None or not _state.initialization_done:
        raise NotInitializedError()
    return _state
