"""hvdserve: resilient serving plane on the elastic runtime.

The serving plane (docs/serving.md) turns the substrate PRs 3–11 built
— warm-start compile cache, heartbeat/health plane, quarantine-with-decay,
deterministic fault injection, telemetry registry — into a request
path that degrades gracefully instead of dropping or duplicating work:

* :mod:`~horovod_tpu.serve.request` — request/response records; the
  request id is the exactly-once token;
* :mod:`~horovod_tpu.serve.queue` — bounded admission queue:
  deadline-aware shedding + backpressure at the front door, and the
  ``queued → inflight → done`` state machine that makes crash
  re-enqueue exactly-once;
* :mod:`~horovod_tpu.serve.replica` — one serving slot with the
  SERVING → DRAINING → DEPARTED / DEAD lifecycle;
* :mod:`~horovod_tpu.serve.batcher` — continuous batcher packing
  signature-compatible requests into cached executables
  (:class:`~horovod_tpu.serve.batcher.ExecutableCache`);
* :mod:`~horovod_tpu.serve.pool` — replica pool: leases, crash
  recovery, graceful drain via the planned-departure path, and
  hysteresis-damped queue-depth scale signals
  (:class:`~horovod_tpu.serve.pool.ElasticServeBridge`);
* :mod:`~horovod_tpu.serve.smoke` — the seeded sub-second chaos
  scenario hvdci gate 5 runs twice and diffs bit-for-bit.

The **hvdfleet** layer (ISSUE 20) turns the one-model plane into a
multi-tenant fleet:

* :mod:`~horovod_tpu.serve.tenancy` — per-model admission queues
  behind a smooth-weighted-round-robin arbiter with SLO-classed
  overload shedding, plus the :class:`~horovod_tpu.serve.tenancy.
  FleetBatcher` engine loop;
* :mod:`~horovod_tpu.serve.refresh` — live weight refresh without
  drain: double-buffered staging on the host-offload H2D path, atomic
  between-batches flips, fingerprint verify with rollback +
  checkpoint quarantine;
* :mod:`~horovod_tpu.serve.autoscale` — the closed loop over
  ``scale_signal()``: acquire (warm start through the compile cache) /
  release (graceful drain) with cooldown, bounds and death repair;
* :mod:`~horovod_tpu.serve.fleet_smoke` — the seeded 3-model
  enqueue → refresh-mid-load → kill → scale-up → drain scenario hvdci
  gate 11 runs twice and diffs bit-for-bit.

Fault sites: ``serve.batch`` (replica crash mid-batch), ``serve.feed``
(queue-feeder hang), ``serve.drain`` (drain wedged past its window),
``serve.tenant`` (weighted-fair pick), ``serve.refresh`` (flip
attempt — ``corrupt`` must be caught by the fingerprint verify),
``serve.scale`` (autoscale poll).  Metrics: the closed ``hvd_serve_*``
vocabulary in ``analysis/metrics_schema.py SERVE_SERIES``.
"""

from horovod_tpu.serve.autoscale import AutoscaleController
from horovod_tpu.serve.batcher import ContinuousBatcher, ExecutableCache
from horovod_tpu.serve.pool import ElasticServeBridge, ReplicaPool
from horovod_tpu.serve.queue import (
    ADMITTED,
    SHED_DEADLINE,
    SHED_DUPLICATE,
    SHED_FULL,
    SHED_OVERLOAD,
    SHED_REQUEUE_BUDGET,
    AdmissionQueue,
)
from horovod_tpu.serve.refresh import WeightRefresher
from horovod_tpu.serve.tenancy import (
    SLO_CLASSES,
    FleetBatcher,
    MultiTenantQueue,
    SLOClass,
    TenantSpec,
)
from horovod_tpu.serve.replica import (
    DEAD,
    DEPARTED,
    DRAINING,
    SERVING,
    Replica,
)
from horovod_tpu.serve.request import (
    InferenceRequest,
    InferenceResponse,
    payload_signature,
)

__all__ = [
    "ADMITTED", "SHED_DEADLINE", "SHED_DUPLICATE", "SHED_FULL",
    "SHED_OVERLOAD", "SHED_REQUEUE_BUDGET", "AdmissionQueue",
    "AutoscaleController", "ContinuousBatcher", "DEAD", "DEPARTED",
    "DRAINING", "ElasticServeBridge", "ExecutableCache",
    "FleetBatcher", "InferenceRequest", "InferenceResponse",
    "MultiTenantQueue", "Replica", "ReplicaPool", "SERVING",
    "SLOClass", "SLO_CLASSES", "TenantSpec", "WeightRefresher",
    "payload_signature",
]
