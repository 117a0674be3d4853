"""Measured comm/compute overlap for the DP gradient exchange.

The scaling model (``utils/scaling.py``, ``docs/scaling.md``) needs an
``overlap_fraction`` — how much of the gradient collective hides under
backward compute.  Until now that number was *assumed*; this probe
measures it on whatever devices are present, the way the reference
measures rather than models its benchmark tables
(``docs/benchmarks.rst``).

Method — three compiled programs over the same mesh, batch and
parameters:

* **backward-only**: forward + backward, gradients consumed locally
  (no collective);
* **exchange-only**: the bucketed reduce-scatter → allgather exchange
  on gradient-shaped inputs (no model compute);
* **fused**: the real train-step body — backward feeding the exchange
  inside one program, where XLA's latency-hiding scheduler is free to
  interleave them.

If the scheduler achieves nothing, ``t_fused ≈ t_backward +
t_exchange``; if the shorter phase hides completely under the longer,
``t_fused ≈ max(t_backward, t_exchange)``.  The achieved fraction is::

    overlap = (t_backward + t_exchange - t_fused) / min(t_backward,
                                                        t_exchange)

clamped to [0, 1].  Each timing fences on a host fetch of a scalar
(the same discipline as ``bench.py``) and takes the median over
``iters`` calls.  On a 1-chip world the exchange is pure data movement with no
wire, so the fraction is reported but near-meaningless — the probe
exists to be run on real slices, and the bench records it per run so
the scaling table can cite a measured number
(``BENCH_*.json: overlap_fraction``).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.ops import collectives as C
from horovod_tpu.ops.collectives import Average, ReduceOp
from horovod_tpu.runtime import state
from horovod_tpu.runtime.topology import GLOBAL_AXES, resolve_hierarchy
from horovod_tpu.utils import hlo as H

AxisSpec = Union[str, Sequence[str]]


@dataclasses.dataclass
class OverlapReport:
    """One probe run: the three phase timings, the derived overlap, and
    — for the hierarchical exchange — the per-level attribution plus the
    compiled collective structure (which scopes actually exist on the
    wire, straight from the optimized HLO of the exchange program)."""

    backward_s: float
    exchange_s: float
    fused_s: float
    overlap_fraction: float
    world: int
    payload_bytes: int
    hierarchy: str = "flat"
    # exchange time left exposed past backward — the serial tail the
    # tile-fused final-bucket exchange attacks (docs/fused_kernels.md):
    # max(0, fused_s - backward_s); 0 = the wire hid completely
    tail_exchange_s: float = 0.0
    # the final-bucket schedule this probe ran: "on" = tile-granular
    # fused tail, "off" = monolithic last collective
    fused_collectives: str = "off"
    # HLO scan of the exchange program: 1 if its final async RS/AG pair
    # has no compute scheduled between start and done (the serial tail
    # HLO005 flags); 0 when overlapped or when the backend issues
    # synchronously (no async pairs to judge)
    serial_tail_collectives: Optional[int] = None
    # two-level only: the intra-slice (ICI) share of the exchange time
    # and the cross-slice (DCN) remainder — measured, not modeled
    exchange_intra_s: Optional[float] = None
    exchange_cross_s: Optional[float] = None
    # compiled structure of the exchange program: kind → distinct
    # replica-group sizes (two reduce-scatter scopes == two levels)
    rs_scopes: tuple = ()
    ag_scopes: tuple = ()
    grad_sized_allreduces: int = 0
    # per-level wire bytes of the compiled exchange (cost-model
    # attribution over the parsed collectives) — the perf gate diffs
    # these across artifacts (PERF003, docs/perf_gate.md)
    wire_bytes_ici: Optional[int] = None
    wire_bytes_dcn: Optional[int] = None

    def as_bench_fields(self, prefix: str = "") -> dict:
        """The fields ``bench.py`` merges into the bench JSON."""
        fields = {
            f"{prefix}overlap_fraction": round(self.overlap_fraction, 4),
            f"{prefix}overlap_backward_s": round(self.backward_s, 6),
            f"{prefix}overlap_exchange_s": round(self.exchange_s, 6),
            f"{prefix}overlap_fused_s": round(self.fused_s, 6),
            f"{prefix}tail_exchange_s": round(self.tail_exchange_s, 6),
            f"{prefix}exchange_hierarchy": self.hierarchy,
            f"{prefix}fused_collectives": self.fused_collectives,
        }
        if self.serial_tail_collectives is not None:
            fields[f"{prefix}exchange_serial_tail_collectives"] = \
                int(self.serial_tail_collectives)
        if self.exchange_intra_s is not None:
            fields[f"{prefix}overlap_exchange_intra_s"] = \
                round(self.exchange_intra_s, 6)
            fields[f"{prefix}overlap_exchange_cross_s"] = \
                round(self.exchange_cross_s, 6)
        if self.rs_scopes:
            fields[f"{prefix}exchange_rs_scopes"] = list(self.rs_scopes)
            # the count the offline HLO lint (analysis/hlo_lint.py
            # HLO001) checks in saved artifacts: any non-zero value
            # means the sharded exchange regressed to allreduce on the
            # wire of the run that produced this JSON
            fields[f"{prefix}exchange_grad_sized_allreduces"] = \
                int(self.grad_sized_allreduces)
        if self.wire_bytes_ici is not None:
            fields[f"{prefix}exchange_wire_bytes_ici"] = \
                int(self.wire_bytes_ici)
            fields[f"{prefix}exchange_wire_bytes_dcn"] = \
                int(self.wire_bytes_dcn or 0)
        return fields


def _median_time(fn, args, iters: int, warmup: int) -> float:
    for _ in range(warmup):
        out = fn(*args)
        float(np.asarray(jax.device_get(out)))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        float(np.asarray(jax.device_get(out)))   # host fetch = fence
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def measure_overlap(loss_fn: Callable,
                    params,
                    batch,
                    mesh=None,
                    axis: AxisSpec = GLOBAL_AXES,
                    op: ReduceOp = Average,
                    bucket_bytes: Optional[int] = None,
                    hierarchy: str = "auto",
                    fused_collectives: str = "off",
                    iters: int = 5,
                    warmup: int = 2) -> OverlapReport:
    """Measure backward/exchange/fused timings for ``loss_fn`` over the
    (dcn, ici) mesh and return the achieved overlap fraction.

    ``params`` replicated, ``batch`` sharded along ``axis`` — the same
    contract as ``DistributedTrainStep``.  ``bucket_bytes`` buckets the
    exchange exactly as ``exchange_bucket_bytes`` would in the train
    step, and ``hierarchy`` selects its topology exactly as the step's
    knob would (``"auto"`` resolves against the mesh factorization), so
    the probe measures the schedule the step will actually run.

    Two-level runs additionally report (a) per-level timing
    attribution — an intra-slice-only RS/AG program is timed separately
    and the cross-slice remainder is the difference, clamped at zero —
    and (b) the compiled collective *structure* of the exchange program
    (distinct reduce-scatter/all-gather scopes, count of gradient-sized
    all-reduces), parsed from its optimized HLO.  The structure fields
    are what the HLO guard tests pin; the bench JSON carries them so a
    silent topology regression is visible in the run artifact too.

    ``fused_collectives`` selects the final-bucket schedule the probed
    exchange runs (``"on"`` = the tile-granular fused tail,
    docs/fused_kernels.md); the report's ``tail_exchange_s`` — exchange
    time left exposed past backward — is the quantity the fused path
    exists to shrink, and ``bench.py`` emits both paths' numbers."""
    from horovod_tpu.ops.pallas_kernels import resolve_fused_collectives

    mesh = mesh or state.global_state().mesh
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    world = 1
    for a in axes:
        world *= mesh.shape[a]
    mode = resolve_hierarchy(hierarchy,
                             [mesh.shape[a] for a in axes])
    fused_tail = resolve_fused_collectives(fused_collectives)

    shard_map = jax.shard_map
    in_p = (P(), P(axes))

    def grads_of(params, batch):
        _, grads = jax.value_and_grad(loss_fn)(params, batch)
        return grads

    def fingerprint(tree) -> jax.Array:
        leaves = jax.tree_util.tree_leaves(tree)
        return sum(jnp.sum(jnp.abs(x).astype(jnp.float32))
                   for x in leaves)

    def exchange(grads):
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        if mode == "two_level":
            outer, inner = axes
            shards, spec = C.hierarchical_reducescatter(
                leaves, op=op, outer_axis=outer, inner_axis=inner,
                bucket_bytes=bucket_bytes, fused_tail=fused_tail)
            out = C.hierarchical_allgather(shards, spec,
                                           outer_axis=outer,
                                           inner_axis=inner)
        else:
            shards, spec = C.grouped_reducescatter(
                leaves, op=op, axis=axes, bucket_bytes=bucket_bytes,
                fused_tail=fused_tail)
            out = C.grouped_allgather(shards, spec, axis=axes)
        return jax.tree_util.tree_unflatten(treedef, out)

    def intra_exchange(grads):
        # the ICI phase in isolation: RS/AG over the inner axis only —
        # its timing is the intra-slice share of the full exchange
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        shards, spec = C.grouped_reducescatter(
            leaves, op=op, axis=axes[-1], bucket_bytes=bucket_bytes)
        out = C.grouped_allgather(shards, spec, axis=axes[-1])
        return jax.tree_util.tree_unflatten(treedef, out)

    def backward_only(params, batch):
        return fingerprint(grads_of(params, batch))

    def exchange_only(grads):
        return fingerprint(exchange(grads))

    def intra_only(grads):
        return fingerprint(intra_exchange(grads))

    def fused(params, batch):
        return fingerprint(exchange(grads_of(params, batch)))

    bwd = jax.jit(shard_map(backward_only, mesh=mesh, in_specs=in_p,
                            out_specs=P(), check_vma=False))
    fsd = jax.jit(shard_map(fused, mesh=mesh, in_specs=in_p,
                            out_specs=P(), check_vma=False))

    # gradient-shaped input for the exchange-only program: computed
    # once, replicated, so its timing contains zero backward work
    repl = NamedSharding(mesh, P())
    grads = jax.device_put(
        jax.jit(shard_map(grads_of, mesh=mesh, in_specs=in_p,
                          out_specs=P(), check_vma=False))(params, batch),
        repl)
    exc = jax.jit(shard_map(exchange_only, mesh=mesh, in_specs=(P(),),
                            out_specs=P(), check_vma=False))

    # compiled structure of the exchange program (scopes per kind)
    rs_scopes: tuple = ()
    ag_scopes: tuple = ()
    grad_ars = 0
    wire_ici = wire_dcn = None
    serial_tail = None
    payload = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(grads))
    try:
        # the serial-tail scan runs on the FUSED program — that is
        # where backward compute exists to hide the exchange under; an
        # exchange-only module has nothing between start and done by
        # construction
        serial_tail = H.serial_tail_collectives(
            fsd.lower(params, batch).compile().as_text())
    except Exception:      # noqa: BLE001 — structure report is advisory
        pass
    try:
        ops = H.collective_ops(
            exc.lower(grads).compile().as_text())
        scopes = H.scopes_by_kind(ops)
        rs_scopes = scopes.get("reduce-scatter", ())
        ag_scopes = scopes.get("all-gather", ())
        grad_ars = sum(1 for o in ops if o.kind == "all-reduce"
                       and o.bytes >= payload)
        # per-level wire attribution from the compiled collectives —
        # measured structure, not the analytic model, so a
        # de-quantized DCN hop or a de-fused exchange shows up as more
        # bytes in the run's own artifact (perf gate PERF003)
        from horovod_tpu.analysis import cost_model as CM

        n_outer = mesh.shape[axes[0]] if len(axes) == 2 else 1
        levels = CM.collective_wire_by_level(
            ops, n_dcn=n_outer, n_ici=mesh.shape[axes[-1]])
        wire_ici = int(levels["ici"])
        wire_dcn = int(levels["dcn"])
    except Exception:      # noqa: BLE001 — structure report is advisory
        pass

    t_bwd = _median_time(bwd, (params, batch), iters, warmup)
    t_exc = _median_time(exc, (grads,), iters, warmup)
    t_fsd = _median_time(fsd, (params, batch), iters, warmup)

    t_intra = t_cross = None
    if mode == "two_level":
        itr = jax.jit(shard_map(intra_only, mesh=mesh, in_specs=(P(),),
                                out_specs=P(), check_vma=False))
        t_intra = _median_time(itr, (grads,), iters, warmup)
        t_cross = max(0.0, t_exc - t_intra)

    saved = t_bwd + t_exc - t_fsd
    denom = min(t_bwd, t_exc)
    frac = saved / denom if denom > 0 else 0.0
    # the serial tail in time units: whatever the fused program costs
    # beyond backward alone is exchange the schedule failed to hide
    tail_s = max(0.0, t_fsd - t_bwd)
    # registry mirror of the probe's headline numbers (docs/metrics.md):
    # measured per-level exchange time and wire bytes, next to the
    # static model the train step publishes
    from horovod_tpu import telemetry

    if telemetry.enabled():
        tg = telemetry.gauge("hvd_exchange_time_seconds",
                             "measured gradient-exchange time per level")
        tg.set(t_exc, level="total")
        if t_intra is not None:
            tg.set(t_intra, level="ici")
            tg.set(t_cross, level="dcn")
        telemetry.gauge("hvd_overlap_fraction",
                        "measured comm/compute overlap fraction").set(
                            float(np.clip(frac, 0.0, 1.0)))
        telemetry.gauge(
            "hvd_tail_exchange_seconds",
            "exchange time left exposed past backward compute").set(
                tail_s, fused="on" if fused_tail else "off")
        if wire_ici is not None:
            wg = telemetry.gauge(
                "hvd_exchange_measured_wire_bytes",
                "per-level wire bytes of the compiled exchange")
            wg.set(wire_ici, level="ici")
            wg.set(wire_dcn, level="dcn")
    return OverlapReport(
        backward_s=t_bwd, exchange_s=t_exc, fused_s=t_fsd,
        overlap_fraction=float(np.clip(frac, 0.0, 1.0)),
        world=world, payload_bytes=int(payload),
        hierarchy=mode,
        tail_exchange_s=tail_s,
        fused_collectives="on" if fused_tail else "off",
        serial_tail_collectives=serial_tail,
        exchange_intra_s=t_intra, exchange_cross_s=t_cross,
        rs_scopes=rs_scopes, ag_scopes=ag_scopes,
        grad_sized_allreduces=grad_ars,
        wire_bytes_ici=wire_ici, wire_bytes_dcn=wire_dcn)
