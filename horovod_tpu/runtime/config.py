"""Runtime configuration from the ``HOROVOD_*`` environment contract.

The reference funnels three config layers (env vars, ``horovodrun`` CLI flags,
runtime autotune) into ``HOROVOD_*`` env vars read by the C++ core
(``horovod/common/utils/env_parser.{h,cc}``, knob names in
``horovod/common/common.h:64-90``).  We keep the same contract and knob names
where they still make sense on TPU, and add TPU-specific ones
(``HOROVOD_TPU_OPERATIONS``, mesh shape overrides).

Knobs that exist purely because of the reference's negotiation machinery
(cycle time, response cache capacity) are kept as accepted-but-advisory
settings: SPMD compilation removes per-tensor negotiation, so they only
influence the eager bucketing layer.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


# The HOROVOD_* env-var registry (reference knob table common.h:64-90):
# every knob the package reads OR sets must be declared here — the
# static analyzer (HVD005, docs/analysis.md) fails on any quoted
# HOROVOD_* literal missing from this set, and the doc-drift guard
# (tests/test_env_knob_docs.py) separately requires each to appear in
# docs/.  One greppable place instead of knobs scattered per-module.
KNOWN_KNOBS = frozenset({
    # -- process identity (set by the launcher)
    "HOROVOD_RANK", "HOROVOD_SIZE", "HOROVOD_LOCAL_RANK",
    "HOROVOD_LOCAL_SIZE", "HOROVOD_CROSS_RANK", "HOROVOD_CROSS_SIZE",
    "HOROVOD_HOSTNAME", "HOROVOD_COORDINATOR_ADDR",
    # -- data plane / fusion
    "HOROVOD_TPU_OPERATIONS", "HOROVOD_FUSION_THRESHOLD",
    "HOROVOD_CYCLE_TIME", "HOROVOD_CACHE_CAPACITY",
    "HOROVOD_HIERARCHICAL_ALLREDUCE", "HOROVOD_HIERARCHICAL_ALLGATHER",
    "HOROVOD_EXCHANGE_BUCKET_BYTES", "HOROVOD_EXCHANGE_HIERARCHY",
    "HOROVOD_EXCHANGE_WIRE_DTYPE", "HOROVOD_EXCHANGE_REDUCTION",
    "HOROVOD_FUSED_COLLECTIVES",
    "HOROVOD_ADASUM_NUM_CHUNKS", "HOROVOD_DEBUG_SPARSE",
    "HOROVOD_TPU_MESH_SHAPE",
    # -- N-level exchange codec map (runtime/topology.py,
    #    docs/calibration.md): "dcn=int8,ici=fp32"-style per-level wire
    #    dtypes for hierarchy=tree meshes
    "HOROVOD_EXCHANGE_LEVEL_CODECS",
    # -- measured hardware model (analysis/cost_model.py,
    #    docs/calibration.md): calibration artifact > preset > builtin
    "HOROVOD_CALIBRATION_PATH", "HOROVOD_HW_PRESET",
    # -- parallelism plan (parallel/plan.py, docs/parallelism.md):
    # the ShardingPlan grammar, e.g. "dp=4,tp=2" or "dp=2,pp=2,v=2"
    "HOROVOD_PLAN",
    # -- MoE expert-parallel dispatch (models/moe.py, parallel/expert.py,
    #    docs/fused_kernels.md "Expert-parallel dispatch")
    "HOROVOD_MOE_FUSED_DISPATCH",
    # -- sequence-parallel ring attention (parallel/ring_attention.py,
    #    ops/pallas_kernels.py, docs/fused_kernels.md "Ring-flash attention")
    "HOROVOD_SP_FUSED_RING", "HOROVOD_SP_LAYOUT",
    # -- warm-start compile cache
    "HOROVOD_COMPILE_CACHE", "HOROVOD_COMPILE_CACHE_DIR",
    # -- input pipeline
    "HOROVOD_PREFETCH_DEPTH", "HOROVOD_INPUT_THREADS",
    # -- autotune
    "HOROVOD_AUTOTUNE", "HOROVOD_AUTOTUNE_LOG",
    "HOROVOD_AUTOTUNE_WARMUP_SAMPLES",
    "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES",
    "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE",
    "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE",
    # -- telemetry plane (horovod_tpu/telemetry, docs/metrics.md)
    "HOROVOD_METRICS", "HOROVOD_METRICS_PORT", "HOROVOD_METRICS_LOG",
    "HOROVOD_METRICS_INTERVAL_S", "HOROVOD_RUN_ID",
    # -- timeline / stall inspector / logging
    "HOROVOD_TIMELINE", "HOROVOD_TIMELINE_MARK_CYCLES",
    "HOROVOD_TIMELINE_PYTHON", "HOROVOD_STALL_CHECK_DISABLE",
    "HOROVOD_STALL_CHECK_TIME_SECONDS",
    "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS",
    "HOROVOD_LOG_LEVEL", "HOROVOD_LOG_HIDE_TIME",
    # -- elastic runtime
    "HOROVOD_ELASTIC", "HOROVOD_ELASTIC_DRIVER_ADDR",
    "HOROVOD_ELASTIC_NOTIFY_ADDR", "HOROVOD_ELASTIC_GENERATION",
    "HOROVOD_ELASTIC_START_TIMEOUT", "HOROVOD_ELASTIC_HEARTBEAT_TIMEOUT",
    "HOROVOD_ELASTIC_HEARTBEAT_INTERVAL",
    "HOROVOD_ELASTIC_HEARTBEAT_SUSPECT_MISSES",
    "HOROVOD_ELASTIC_HEARTBEAT_DEAD_S",
    "HOROVOD_ELASTIC_PROGRESS_TIMEOUT_S",
    "HOROVOD_ELASTIC_DEPART_GRACE_S",
    "HOROVOD_ELASTIC_STRAGGLER_RATIO",
    # -- plan-aware graceful degradation (elastic/degrade.py,
    #    docs/elastic.md "Degraded mode")
    "HOROVOD_DEGRADE", "HOROVOD_DEGRADE_WAIT_S",
    "HOROVOD_DEGRADE_MIN_DATA_EXTENT", "HOROVOD_DEGRADE_PROMOTE",
    # -- serving plane (horovod_tpu/serve, docs/serving.md)
    "HOROVOD_SERVE_QUEUE_DEPTH", "HOROVOD_SERVE_MAX_REQUEUES",
    "HOROVOD_SERVE_MAX_BATCH", "HOROVOD_SERVE_DRAIN_TIMEOUT_S",
    "HOROVOD_SERVE_SCALE_UP_DEPTH", "HOROVOD_SERVE_SCALE_DOWN_DEPTH",
    # -- hvdfleet: tenancy, live weight refresh, closed-loop autoscale
    #    (serve/tenancy.py, serve/refresh.py, serve/autoscale.py)
    "HOROVOD_SERVE_OVERLOAD_FRACTION", "HOROVOD_SERVE_REFRESH_VERIFY",
    "HOROVOD_SERVE_SCALE_HOLD_S", "HOROVOD_SERVE_SCALE_COOLDOWN_S",
    "HOROVOD_SERVE_SCALE_MIN_REPLICAS",
    "HOROVOD_SERVE_SCALE_MAX_REPLICAS",
    # -- perf regression gate (analysis/perf_gate.py, docs/perf_gate.md)
    "HOROVOD_PERF_GATE_TOLERANCE", "HOROVOD_PERF_GATE_OVERLAP_TOLERANCE",
    "HOROVOD_PERF_GATE_WIRE_TOLERANCE",
    "HOROVOD_PERF_GATE_MEMORY_TOLERANCE",
    # -- memory plane (horovod_tpu/memory, docs/memory.md): remat tier,
    #    HBM budget for the plan autotuner, host offload
    "HOROVOD_REMAT_POLICY", "HOROVOD_HBM_BUDGET_BYTES",
    "HOROVOD_OFFLOAD_OPTIMIZER", "HOROVOD_OFFLOAD_DEPTH",
    # -- training-state integrity plane (horovod_tpu/guard,
    #    docs/guardian.md)
    "HOROVOD_GUARD", "HOROVOD_GUARD_POLICY",
    "HOROVOD_GUARD_CHECK_INTERVAL", "HOROVOD_GUARD_ZSCORE",
    "HOROVOD_GUARD_WARMUP_STEPS", "HOROVOD_GUARD_EMA",
    "HOROVOD_GUARD_PREEMPT",
    # -- health / quarantine / retry / chaos
    "HOROVOD_QUARANTINE_BASE_S", "HOROVOD_QUARANTINE_MAX_S",
    "HOROVOD_QUARANTINE_PROBATION_S", "HOROVOD_QUARANTINE_DISABLE",
    "HOROVOD_RETRY_MAX_ATTEMPTS", "HOROVOD_RETRY_BASE_S",
    "HOROVOD_RETRY_MAX_S", "HOROVOD_RETRY_DEADLINE_S",
    "HOROVOD_RETRY_JITTER", "HOROVOD_FAULT_PLAN",
    # -- launcher / runner / spark
    "HOROVOD_CONTROLLER", "HOROVOD_SECRET_KEY", "HOROVOD_RUN_SECRET",
    "HOROVOD_RUN_SERVICE_ADDR", "HOROVOD_THREAD_AFFINITY",
    "HOROVOD_TPU_DISCOVERY_CACHE_TTL",
    "HOROVOD_LSF_ACCELERATORS_PER_NODE", "HOROVOD_LSF_CORES_PER_NODE",
    "HOROVOD_LSF_THREADS_PER_CORE",
    "HOROVOD_SPARK_ELASTIC_RUN_ID", "HOROVOD_SPARK_HOST_HASH",
    "HOROVOD_SPARK_START_TIMEOUT",
})


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {v!r}")


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError:
        raise ValueError(f"{name} must be a float, got {v!r}")


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return v.lower() in ("1", "true", "yes", "on")


def _env_str(name: str, default: str) -> str:
    return os.environ.get(name, default)


@dataclasses.dataclass
class Config:
    """All runtime knobs, resolved once at ``init()`` time.

    Mirrors the env contract in the reference (``common.h:64-90``,
    ``gloo_context.cc:47-55``) plus TPU-mesh additions.
    """

    # -- process identity (set by the launcher; reference gloo_context.cc:47-55)
    rank: Optional[int] = None
    size: Optional[int] = None
    local_rank: Optional[int] = None
    local_size: Optional[int] = None
    cross_rank: Optional[int] = None
    cross_size: Optional[int] = None

    # -- coordination service (jax.distributed)
    coordinator_addr: Optional[str] = None

    # -- data-plane selection; the analogue of HOROVOD_GPU_OPERATIONS=NCCL
    tpu_operations: str = "XLA"

    # -- fusion / bucketing (reference: 64 MiB default, operations.cc:432)
    fusion_threshold_bytes: int = 64 * 1024 * 1024
    cycle_time_ms: float = 5.0   # advisory: eager bucket flush interval
    # bounds the compiled-executable cache (reference response-cache
    # capacity, response_cache.h): the in-memory executable LRU held by
    # each DistributedTrainStep evicts past this many entries
    cache_capacity: int = 1024

    # -- warm-start compile cache (runtime/compile_cache.py): JAX's
    # persistent compilation cache, shared across process restarts and
    # elastic generations
    compile_cache_enabled: bool = True
    compile_cache_dir: Optional[str] = None   # None → compile_cache.default_dir()

    # -- input pipeline (horovod_tpu/data): prefetch queue bound and
    # host-side batch-assembly thread count (docs/data.md tuning notes)
    prefetch_depth: int = 2
    input_threads: int = 2

    # -- hierarchical collectives (ici/dcn mesh split)
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False

    # -- sharded gradient exchange (shard_optimizer_states paths):
    # bucket byte cap and hierarchy mode defaults, overridable per
    # train step; "auto" consults the mesh factorization at build time
    exchange_bucket_bytes: Optional[int] = None
    exchange_hierarchy: str = "auto"
    # low-precision wire codec dtype for the quantized (DCN) exchange
    # hop: "int8" (shared-scale s8, the PR 2 codec) or "fp8_e4m3"
    # (e4m3 floating wire — coarser mantissa, no shared-scale clipping
    # of outlier segments); docs/overlap.md
    exchange_wire_dtype: str = "int8"
    # per-level wire codec map for N-level (tree) meshes, the
    # "dcn=int8,ici=fp32" grammar of topology.parse_level_codecs();
    # None defers to exchange_wire_dtype on the outermost level only
    exchange_level_codecs: Optional[str] = None
    # combine operator of the sharded exchange: "sum" (plain RS), or
    # "adasum" — AdaSum adaptive summation (arXiv 2006.02924) on the
    # OUTERMOST topology level only, the large-batch scale-out
    # operator (docs/adasum.md)
    exchange_reduction: str = "sum"
    # tile-fused matmul⊗collective kernels (docs/fused_kernels.md):
    # "auto" enables on TPU only, "on"/"off" force; a new autotune
    # axis next to bucket bytes + hierarchy
    fused_collectives: str = "auto"

    # -- autotune (reference parameter_manager.h:58-78)
    autotune: bool = False
    autotune_log: Optional[str] = None
    autotune_warmup_samples: int = 3
    autotune_bayes_opt_max_samples: int = 20
    autotune_gaussian_process_noise: float = 0.8
    autotune_steps_per_sample: int = 10

    # -- telemetry plane (horovod_tpu/telemetry, docs/metrics.md):
    # metrics_enabled None = auto (on iff an exporter is configured);
    # port 0 = no Prometheus endpoint; log None = no JSONL snapshots
    metrics_enabled: Optional[bool] = None
    metrics_port: int = 0
    metrics_log: Optional[str] = None
    metrics_interval_s: float = 10.0
    run_id: Optional[str] = None

    # -- timeline (reference operations.cc:417-424)
    timeline_filename: Optional[str] = None
    timeline_mark_cycles: bool = False

    # -- stall inspector (reference stall_inspector.h:73-81)
    stall_check_enabled: bool = True
    stall_warning_time_seconds: float = 60.0
    stall_shutdown_time_seconds: float = 0.0  # 0 = never

    # -- adasum
    adasum_num_chunks: int = 1

    # -- elastic
    elastic_enabled: bool = False

    # -- training-state integrity plane (horovod_tpu/guard,
    # docs/guardian.md): numerics guardian + replica checksums +
    # rollback-and-replay + preemption grace
    guard_enabled: bool = False
    guard_policy: str = "rollback"       # skip_step | rollback | abort
    guard_check_interval: int = 10       # replica-checksum cadence (steps)
    guard_zscore: float = 6.0            # grad-norm spike threshold
    guard_warmup_steps: int = 10         # steps before spike detection arms
    guard_ema: float = 0.99              # EMA decay for the norm baseline
    guard_preempt: bool = True           # SIGTERM graceful-departure handler

    # -- chaos (horovod_tpu/faults): the seeded fault plan, parsed and
    # installed at init() — docs/faults.md for the grammar
    fault_plan: Optional[str] = None

    # -- mesh overrides: "8" or "2,4" → (dcn, ici) axis sizes
    mesh_shape: Optional[str] = None

    # -- parallelism plan (HOROVOD_PLAN, parallel/plan.py): the
    # declarative ShardingPlan grammar ("dp=4,tp=2", "dp=2,pp=2,v=2");
    # None = data-parallel over the runtime mesh, as before.
    # DistributedTrainStep picks this up when no explicit plan/mesh is
    # passed (docs/parallelism.md)
    plan: Optional[str] = None

    # -- memory plane (horovod_tpu/memory, docs/memory.md): remat tier
    # (None = model/step default), HBM budget the plan autotuner must
    # fit (None = device capacity), host offload of the ZeRO optimizer
    # shard + the offload ring depth (2 = double buffering)
    remat_policy: Optional[str] = None
    hbm_budget_bytes: Optional[int] = None
    offload_optimizer: bool = False
    offload_depth: int = 2

    # -- measured hardware model (analysis/cost_model.py,
    # docs/calibration.md): path to a bench --calibrate artifact and/or
    # a named preset ("v5e"/"v5p"/"v4"/"cpu-twin"); precedence is
    # calibration artifact > preset > device_kind preset > v5e
    calibration_path: Optional[str] = None
    hw_preset: Optional[str] = None

    # knobs the user set explicitly must not be autotuned
    # (reference "fixed" flag, operations.cc:436)
    fixed_knobs: frozenset = frozenset()

    @staticmethod
    def from_env() -> "Config":
        fixed = set()

        def mark(name: str, knob: str):
            if os.environ.get(name) not in (None, ""):
                fixed.add(knob)

        mark("HOROVOD_FUSION_THRESHOLD", "fusion_threshold_bytes")
        mark("HOROVOD_CYCLE_TIME", "cycle_time_ms")
        mark("HOROVOD_CACHE_CAPACITY", "cache_capacity")
        mark("HOROVOD_HIERARCHICAL_ALLREDUCE", "hierarchical_allreduce")
        mark("HOROVOD_HIERARCHICAL_ALLGATHER", "hierarchical_allgather")
        mark("HOROVOD_EXCHANGE_BUCKET_BYTES", "exchange_bucket_bytes")
        mark("HOROVOD_EXCHANGE_HIERARCHY", "exchange_hierarchy")
        mark("HOROVOD_EXCHANGE_WIRE_DTYPE", "exchange_wire_dtype")
        mark("HOROVOD_EXCHANGE_LEVEL_CODECS", "exchange_level_codecs")
        mark("HOROVOD_EXCHANGE_REDUCTION", "exchange_reduction")
        mark("HOROVOD_FUSED_COLLECTIVES", "fused_collectives")
        mark("HOROVOD_PLAN", "plan")
        mark("HOROVOD_REMAT_POLICY", "remat_policy")
        mark("HOROVOD_OFFLOAD_OPTIMIZER", "offload_optimizer")

        def opt_int(name: str) -> Optional[int]:
            v = os.environ.get(name)
            return int(v) if v not in (None, "") else None

        # Identity fallback for jsrun/mpirun launches: when the launcher
        # is JSM/PMIx (hvdrun --jsrun), ranks carry PMIX_*/OMPI_* vars
        # instead of the HOROVOD_* env contract (reference: jsrun workers
        # read identity through the MPI controller; js_run.py).
        jsm = None
        if opt_int("HOROVOD_RANK") is None:
            from horovod_tpu.runner.cluster_env import jsm_identity

            jsm = jsm_identity()

        return Config(
            rank=opt_int("HOROVOD_RANK") if jsm is None else jsm["rank"],
            size=opt_int("HOROVOD_SIZE") if jsm is None else jsm["size"],
            local_rank=opt_int("HOROVOD_LOCAL_RANK")
            if jsm is None else jsm["local_rank"],
            local_size=opt_int("HOROVOD_LOCAL_SIZE")
            if jsm is None else jsm["local_size"],
            cross_rank=opt_int("HOROVOD_CROSS_RANK"),
            cross_size=opt_int("HOROVOD_CROSS_SIZE"),
            coordinator_addr=os.environ.get("HOROVOD_COORDINATOR_ADDR"),
            tpu_operations=_env_str("HOROVOD_TPU_OPERATIONS", "XLA").upper(),
            fusion_threshold_bytes=_env_int(
                "HOROVOD_FUSION_THRESHOLD", 64 * 1024 * 1024),
            cycle_time_ms=_env_float("HOROVOD_CYCLE_TIME", 5.0),
            cache_capacity=_env_int("HOROVOD_CACHE_CAPACITY", 1024),
            compile_cache_enabled=_env_bool("HOROVOD_COMPILE_CACHE", True),
            compile_cache_dir=os.environ.get("HOROVOD_COMPILE_CACHE_DIR"),
            prefetch_depth=_env_int("HOROVOD_PREFETCH_DEPTH", 2),
            input_threads=_env_int("HOROVOD_INPUT_THREADS", 2),
            hierarchical_allreduce=_env_bool(
                "HOROVOD_HIERARCHICAL_ALLREDUCE", False),
            hierarchical_allgather=_env_bool(
                "HOROVOD_HIERARCHICAL_ALLGATHER", False),
            exchange_bucket_bytes=opt_int("HOROVOD_EXCHANGE_BUCKET_BYTES"),
            exchange_hierarchy=_env_str(
                "HOROVOD_EXCHANGE_HIERARCHY", "auto").lower(),
            exchange_wire_dtype=_env_str(
                "HOROVOD_EXCHANGE_WIRE_DTYPE", "int8").lower(),
            exchange_level_codecs=(
                os.environ.get("HOROVOD_EXCHANGE_LEVEL_CODECS") or None),
            exchange_reduction=_env_str(
                "HOROVOD_EXCHANGE_REDUCTION", "sum").lower(),
            fused_collectives=_env_str(
                "HOROVOD_FUSED_COLLECTIVES", "auto").lower(),
            autotune=_env_bool("HOROVOD_AUTOTUNE", False),
            autotune_log=os.environ.get("HOROVOD_AUTOTUNE_LOG"),
            autotune_warmup_samples=_env_int("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", 3),
            autotune_bayes_opt_max_samples=_env_int(
                "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", 20),
            autotune_gaussian_process_noise=_env_float(
                "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE", 0.8),
            autotune_steps_per_sample=_env_int(
                "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", 10),
            metrics_enabled=(None if os.environ.get("HOROVOD_METRICS")
                             in (None, "") else
                             _env_bool("HOROVOD_METRICS", False)),
            metrics_port=_env_int("HOROVOD_METRICS_PORT", 0),
            metrics_log=os.environ.get("HOROVOD_METRICS_LOG"),
            metrics_interval_s=_env_float("HOROVOD_METRICS_INTERVAL_S",
                                          10.0),
            run_id=os.environ.get("HOROVOD_RUN_ID"),
            timeline_filename=os.environ.get("HOROVOD_TIMELINE"),
            timeline_mark_cycles=_env_bool("HOROVOD_TIMELINE_MARK_CYCLES", False),
            stall_check_enabled=not _env_bool("HOROVOD_STALL_CHECK_DISABLE", False),
            stall_warning_time_seconds=_env_float(
                "HOROVOD_STALL_CHECK_TIME_SECONDS", 60.0),
            stall_shutdown_time_seconds=_env_float(
                "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", 0.0),
            adasum_num_chunks=_env_int("HOROVOD_ADASUM_NUM_CHUNKS", 1),
            elastic_enabled=_env_bool("HOROVOD_ELASTIC", False),
            guard_enabled=_env_bool("HOROVOD_GUARD", False),
            guard_policy=_env_str("HOROVOD_GUARD_POLICY",
                                  "rollback").lower(),
            guard_check_interval=_env_int("HOROVOD_GUARD_CHECK_INTERVAL",
                                          10),
            guard_zscore=_env_float("HOROVOD_GUARD_ZSCORE", 6.0),
            guard_warmup_steps=_env_int("HOROVOD_GUARD_WARMUP_STEPS", 10),
            guard_ema=_env_float("HOROVOD_GUARD_EMA", 0.99),
            guard_preempt=_env_bool("HOROVOD_GUARD_PREEMPT", True),
            fault_plan=os.environ.get("HOROVOD_FAULT_PLAN"),
            mesh_shape=os.environ.get("HOROVOD_TPU_MESH_SHAPE"),
            plan=os.environ.get("HOROVOD_PLAN"),
            remat_policy=(os.environ.get("HOROVOD_REMAT_POLICY") or
                          None),
            hbm_budget_bytes=opt_int("HOROVOD_HBM_BUDGET_BYTES"),
            offload_optimizer=_env_bool("HOROVOD_OFFLOAD_OPTIMIZER",
                                        False),
            offload_depth=_env_int("HOROVOD_OFFLOAD_DEPTH", 2),
            calibration_path=(
                os.environ.get("HOROVOD_CALIBRATION_PATH") or None),
            hw_preset=(os.environ.get("HOROVOD_HW_PRESET") or None),
            fixed_knobs=frozenset(fixed),
        )
