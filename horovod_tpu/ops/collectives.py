"""SPMD collective primitives — the TPU data plane.

This is the TPU-native replacement for the reference's op layer
(``horovod/common/ops/``): where ``NCCLAllreduce::Execute``
(``nccl_operations.cc:126``) launches ``ncclAllReduce`` on a side stream,
these functions emit XLA collectives (``lax.psum``/``all_gather``/
``all_to_all``/``ppermute``) *inside* the compiled step, where the compiler
overlaps them with compute — the role the reference's dedicated GPU streams
and event queues played by hand (``gpu_operations.h:51-127``).

Every function here must be called under ``shard_map``/``pmap`` with a bound
axis name.  Defaults reduce over the full (dcn, ici) runtime mesh; passing
``axis=AXIS_ICI`` or ``AXIS_DCN`` reproduces the reference's LOCAL/CROSS
communicator collectives (``common.h:113-117``).

Capability parity (reference collective inventory, ``operations.cc:677-1068``):
allreduce (sum/average/adasum + pre/postscale), allgather (incl. variable
first dim), broadcast, alltoall (with splits), reducescatter, barrier, and
the bitwise AND/OR bitvector reductions the controller uses internally
(``mpi_controller.cc:88-106``).
"""

from __future__ import annotations

import dataclasses
import enum
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.runtime.topology import AXIS_DCN, AXIS_ICI, GLOBAL_AXES

AxisSpec = Union[str, Sequence[str]]


class ReduceOp(enum.IntEnum):
    """Reduction selector (reference ``ReduceOp``: Average=0, Sum=1, Adasum=2
    in ``horovod/torch/mpi_ops.py``; extended with elementwise min/max/product
    which the XLA backend gets for free)."""

    AVERAGE = 0
    SUM = 1
    ADASUM = 2
    MIN = 3
    MAX = 4
    PRODUCT = 5


# Aliases matching the reference Python API surface
Average = ReduceOp.AVERAGE
Sum = ReduceOp.SUM
Adasum = ReduceOp.ADASUM


def axis_size(axis: AxisSpec = GLOBAL_AXES) -> jax.Array:
    if isinstance(axis, str):
        return lax.axis_size(axis)
    n = 1
    for a in axis:
        n *= lax.axis_size(a)
    return n


def axis_index(axis: AxisSpec = GLOBAL_AXES) -> jax.Array:
    """Linearized rank of this shard along ``axis`` (row-major over the
    axis tuple, matching mesh order)."""
    if isinstance(axis, str):
        return lax.axis_index(axis)
    idx = jnp.int32(0)
    for a in axis:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def _scale(x: jax.Array, factor: Optional[float]) -> jax.Array:
    if factor is None or factor == 1.0:
        return x
    # match reference DoAllreduce: scaling in fp32 for low-precision inputs
    # when the factor is not exactly representable (operations.cc:851-866)
    if x.dtype in (jnp.float16, jnp.bfloat16):
        return (x.astype(jnp.float32) * factor).astype(x.dtype)
    return x * factor


def allreduce(x: jax.Array,
              op: ReduceOp = Average,
              axis: AxisSpec = GLOBAL_AXES,
              prescale_factor: Optional[float] = None,
              postscale_factor: Optional[float] = None) -> jax.Array:
    """Allreduce over mesh axis(es) with reference semantics.

    Average divides by the axis size (reference postscale 1/size,
    ``operations.cc:851-854``); Adasum dispatches to the adaptive-summation
    reduction (``ops/adasum/adasum.h``; see ``horovod_tpu.ops.adasum``).
    """
    if op == ReduceOp.ADASUM:
        from horovod_tpu.ops.adasum import adasum_allreduce

        return _scale(adasum_allreduce(_scale(x, prescale_factor), axis=axis),
                      postscale_factor)

    x = _scale(x, prescale_factor)
    if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
        y = lax.psum(x, axis)
        if op == ReduceOp.AVERAGE:
            y = _scale(y, 1.0 / axis_size(axis))
    elif op == ReduceOp.MIN:
        y = lax.pmin(x, axis)
    elif op == ReduceOp.MAX:
        y = lax.pmax(x, axis)
    elif op == ReduceOp.PRODUCT:
        # no product collective in XLA: gather-then-reduce (small tensors
        # only; the reference has no product op at all)
        gathered = x[None]
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        for a in reversed(axes):
            gathered = lax.all_gather(gathered, a, tiled=True)
        y = jnp.prod(gathered, axis=0)
    else:
        raise ValueError(f"unsupported ReduceOp {op}")
    return _scale(y, postscale_factor)


def grouped_allreduce(xs: Sequence[jax.Array],
                      op: ReduceOp = Average,
                      axis: AxisSpec = GLOBAL_AXES,
                      prescale_factor: Optional[float] = None,
                      postscale_factor: Optional[float] = None,
                      quantized_bits: Optional[int] = None) -> list:
    """Fused allreduce of many tensors — Tensor Fusion, compiler-era.

    The reference packs small gradients into one 64 MiB fusion buffer
    (``fusion_buffer_manager.{h,cc}``, ``controller.cc:686 FuseResponses``)
    to amortize per-collective latency.  Under XLA a *grouped* psum of a
    pytree gives the combiner the same opportunity without the double
    memcpy: we flatten-concatenate per dtype and issue one psum per dtype
    group, then split back — one collective per dtype regardless of tensor
    count.

    ``quantized_bits=8`` routes each *float* dtype group through
    :func:`quantized_allreduce` (int8 wire, shared-scale); integer
    groups stay on the exact psum.
    """
    if not xs:
        return []
    if quantized_bits is not None and op not in (ReduceOp.SUM,
                                                 ReduceOp.AVERAGE):
        raise ValueError("quantized_bits supports op=Sum/Average")
    if op == ReduceOp.ADASUM:
        from horovod_tpu.ops.adasum import adasum_grouped_allreduce

        return adasum_grouped_allreduce(
            [_scale(x, prescale_factor) for x in xs], axis=axis)

    groups: dict = {}
    for i, x in enumerate(xs):
        groups.setdefault(x.dtype, []).append(i)
    out: list = [None] * len(xs)
    for dtype, idxs in groups.items():
        flat = jnp.concatenate(
            [jnp.ravel(_scale(xs[i], prescale_factor)) for i in idxs])
        if quantized_bits is not None and \
                jnp.issubdtype(dtype, jnp.floating):
            red = _scale(
                quantized_allreduce(
                    flat, axis=axis, op=op, bits=quantized_bits,
                    segments=tuple(int(xs[i].size) for i in idxs)),
                postscale_factor)
        else:
            red = allreduce(flat, op=op, axis=axis,
                            postscale_factor=postscale_factor)
        offset = 0
        for i in idxs:
            n = xs[i].size
            out[i] = red[offset:offset + n].reshape(xs[i].shape)
            offset += n
    return out


#: Valid wire codecs for the quantized (DCN) exchange hop
#: (``HOROVOD_EXCHANGE_WIRE_DTYPE``): shared-scale int8 (exact int32
#: accumulation, the PR 2 codec) or fp8 e4m3 (floating wire — graceful
#: within-segment dynamic range at a coarser 3-bit mantissa; EQuARX's
#: low-precision-wire argument, arXiv:2506.17615).
WIRE_DTYPES = ("int8", "fp8_e4m3")

#: absmax quantization targets per wire codec: int8 clips at ±127,
#: e4m3's largest finite is ±448
_WIRE_QMAX = {"int8": 127.0, "fp8_e4m3": 448.0}


def _resolve_wire_dtype(wire_dtype: Optional[str]) -> str:
    """Wire codec resolution: explicit argument > runtime config
    (``HOROVOD_EXCHANGE_WIRE_DTYPE``) > int8 default."""
    if wire_dtype is None:
        from horovod_tpu.runtime import state as _rt

        if _rt.is_initialized():
            wire_dtype = getattr(_rt.global_state().config,
                                 "exchange_wire_dtype", "int8")
        else:
            import os

            wire_dtype = os.environ.get(
                "HOROVOD_EXCHANGE_WIRE_DTYPE", "int8").lower() or "int8"
    if wire_dtype not in WIRE_DTYPES:
        raise ValueError(
            f"exchange wire dtype must be one of {WIRE_DTYPES}, got "
            f"{wire_dtype!r}")
    return wire_dtype


#: Reduction operators of the sharded exchange
#: (``HOROVOD_EXCHANGE_REDUCTION``): plain summation, or AdaSum
#: adaptive summation (arXiv 2006.02924) on the OUTERMOST topology
#: level only — orthogonal gradients add, near-parallel gradients
#: average, so a 2-4x larger global batch keeps the small-batch loss
#: trajectory where plain averaging stalls (docs/adasum.md).
REDUCTIONS = ("sum", "adasum")


def _resolve_reduction(reduction: Optional[str]) -> str:
    """Reduction-operator resolution: explicit argument > runtime config
    (``HOROVOD_EXCHANGE_REDUCTION``) > plain-sum default."""
    if reduction is None:
        from horovod_tpu.runtime import state as _rt

        if _rt.is_initialized():
            reduction = getattr(_rt.global_state().config,
                                "exchange_reduction", "sum")
        else:
            import os

            reduction = os.environ.get(
                "HOROVOD_EXCHANGE_REDUCTION", "sum").lower() or "sum"
    if reduction not in REDUCTIONS:
        raise ValueError(
            f"exchange reduction must be one of {REDUCTIONS}, got "
            f"{reduction!r}")
    return reduction


def adasum_pair(a, b, xp=jnp):
    """One pairwise AdaSum combine (arXiv 2006.02924, the reference's
    ``adasum.h`` coefficient rule)::

        a·(1 − ⟨a,b⟩/2‖a‖²) + b·(1 − ⟨a,b⟩/2‖b‖²)

    which is ``a+b`` for orthogonal gradients and the average for
    parallel ones.  Dot/norms accumulate in fp32 regardless of input
    dtype (the reference widens its fp16 path the same way), and a
    zero-norm operand degrades its coefficient to 1 — the plain-sum
    guard, so all-zero gradients pass through exactly.

    ``xp``-generic (jnp or numpy) so the pure-sim smoke gate
    (``analysis/adasum_smoke.py``) and the traced exchange share these
    exact numerics; the eager numpy path additionally counts actual
    zero-norm fallbacks into telemetry (the traced path cannot observe
    data-dependent events at trace time).
    """
    af = a.astype(xp.float32)
    bf = b.astype(xp.float32)
    dot = xp.vdot(af, bf)
    anormsq = xp.vdot(af, af)
    bnormsq = xp.vdot(bf, bf)
    acoeff = xp.where(anormsq >= 1e-30,
                      1.0 - dot / (2.0 * anormsq + 1e-30), 1.0)
    bcoeff = xp.where(bnormsq >= 1e-30,
                      1.0 - dot / (2.0 * bnormsq + 1e-30), 1.0)
    if xp is np:
        fallbacks = int(anormsq < 1e-30) + int(bnormsq < 1e-30)
        if fallbacks:
            from horovod_tpu import telemetry

            telemetry.counter(
                "hvd_adasum_zero_norm_fallbacks_total",
                "zero-norm plain-sum guard activations in adasum_pair"
            ).inc(fallbacks)
    return (acoeff * af + bcoeff * bf).astype(a.dtype)


def quantized_allreduce(x: jax.Array, axis: AxisSpec = GLOBAL_AXES,
                        op: ReduceOp = Average,
                        bits: int = 8,
                        segments: Sequence[int] = (),
                        wire_dtype: Optional[str] = None) -> jax.Array:
    """Average/sum with an int8-quantized wire (EQuARX-style, arXiv
    2506.17615): agree on a shared scale via one ``pmax``, quantize to
    int8, accumulate the psum in int32 (no overflow, exact integer
    summation), dequantize with the shared scale.  Wire cost of the main
    reduction is 1 byte/element vs 4 for fp32; accuracy cost is one
    absmax-scaled rounding, identical on every shard.

    ``segments`` gives per-tensor lengths of a fused flat buffer: each
    segment then gets its *own* shared scale (one small-vector ``pmax``),
    so a small-magnitude gradient fused next to a large one is not
    rounded to zero — the quantization error is bounded per tensor, and
    the wire still carries a single fused int8 psum.

    ``wire_dtype`` selects the codec (default: the runtime's
    ``HOROVOD_EXCHANGE_WIRE_DTYPE``): ``"int8"`` keeps the exact-int32
    accumulation above; ``"fp8_e4m3"`` casts the absmax-scaled values
    to e4m3 on the wire and accumulates in fp32 — a coarser 3-bit
    mantissa, but each element keeps ~2 decimal digits of *relative*
    precision instead of sharing one absolute step across the segment.
    """
    if bits != 8:
        raise ValueError("only 8-bit quantization is supported")
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("quantized_allreduce supports Sum/Average")
    wire = _resolve_wire_dtype(wire_dtype)
    x32 = x.astype(jnp.float32)
    scale = _shared_wire_scale(x32, segments, axis, qmax=_WIRE_QMAX[wire])
    if wire == "fp8_e4m3":
        q8 = jnp.clip(x32 / scale, -448.0, 448.0) \
            .astype(jnp.float8_e4m3fn)
        total = lax.psum(q8.astype(jnp.float32), axis)
        y = total * scale
    else:
        q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
        total = lax.psum(q.astype(jnp.int32), axis)
        y = total.astype(jnp.float32) * scale
    if op == ReduceOp.AVERAGE:
        y = y / axis_size(axis)
    return y.astype(x.dtype)


def _shared_wire_scale(x32: jax.Array, segments: Sequence[int],
                       axis: AxisSpec, qmax: float = 127.0) -> jax.Array:
    """Shared quantization scale(s) for a (fused) flat buffer —
    the codec core of :func:`quantized_allreduce`, reused by
    :func:`quantized_reducescatter`.  One ``pmax`` agrees on the
    per-segment absmax across shards; returns a scalar (no segments)
    or a per-element scale vector (one scale per fused tensor).
    ``qmax`` is the codec's largest representable magnitude (127 for
    int8, 448 for fp8 e4m3)."""
    if segments and len(segments) > 1:
        if x32.ndim != 1 or sum(segments) != x32.shape[0]:
            raise ValueError("segments must partition a flat buffer")
        bounds = np.cumsum([0] + list(segments))
        local_amax = jnp.stack(
            [jnp.max(jnp.abs(x32[bounds[i]:bounds[i + 1]]))
             for i in range(len(segments))])
        scales = lax.pmax(local_amax, axis) / qmax
        scales = jnp.maximum(scales, 1e-30)
        return jnp.repeat(scales, np.asarray(segments),
                          total_repeat_length=x32.shape[0])
    local_amax = jnp.max(jnp.abs(x32))
    scale = lax.pmax(local_amax, axis) / qmax
    return jnp.maximum(scale, 1e-30)


def quantized_reducescatter(x: jax.Array, axis: AxisSpec = GLOBAL_AXES,
                            op: ReduceOp = Average,
                            bits: int = 8,
                            segments: Sequence[int] = (),
                            wire_dtype: Optional[str] = None) -> jax.Array:
    """Reduce-scatter with the low-precision wire of
    :func:`quantized_allreduce` (same shared-scale codec: one ``pmax``
    agrees the scale; int8 wire with exact int32 accumulation, or the
    fp8 e4m3 wire with fp32 accumulation per ``wire_dtype`` /
    ``HOROVOD_EXCHANGE_WIRE_DTYPE``).  ``x`` must be flat with
    length divisible by the axis world size; each shard receives its
    dequantized 1/world slice.  With ``segments``, per-tensor scales
    are used and this shard dequantizes with the scale entries of its
    own slice."""
    if bits != 8:
        raise ValueError("only 8-bit quantization is supported")
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("quantized_reducescatter supports Sum/Average")
    wire = _resolve_wire_dtype(wire_dtype)
    world = axis_size(axis)
    if x.ndim != 1 or x.shape[0] % world:
        raise ValueError(
            f"quantized_reducescatter needs a flat buffer divisible by "
            f"world size {world}, got shape {x.shape}")
    x32 = x.astype(jnp.float32)
    scale = _shared_wire_scale(x32, segments, axis, qmax=_WIRE_QMAX[wire])
    ax = axis if isinstance(axis, str) else tuple(axis)
    if wire == "fp8_e4m3":
        q8 = jnp.clip(x32 / scale, -448.0, 448.0) \
            .astype(jnp.float8_e4m3fn)
        total = lax.psum_scatter(q8.astype(jnp.float32), ax, tiled=True)
    else:
        q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
        total = lax.psum_scatter(q.astype(jnp.int32), ax, tiled=True) \
            .astype(jnp.float32)
    shard = x.shape[0] // world
    if scale.ndim:          # per-segment scales: this shard's slice
        scale = lax.dynamic_slice(scale, (axis_index(axis) * shard,),
                                  (shard,))
    y = total * scale
    if op == ReduceOp.AVERAGE:
        y = y / world
    return y.astype(x.dtype)


def ef_quantized_reducescatter(x: jax.Array,
                               axis: AxisSpec = GLOBAL_AXES,
                               op: ReduceOp = Average,
                               residual: Optional[jax.Array] = None,
                               bits: int = 8,
                               segments: Sequence[int] = (),
                               wire_dtype: Optional[str] = None):
    """:func:`quantized_reducescatter` with error-feedback residuals
    (EF-SGD / 1-bit-Adam lineage): the quantization rounding error of
    step *t* is carried locally and added back to the input of step
    *t+1*, so the bias of the low-precision wire telescopes away
    instead of accumulating into the trajectory.

    Per step, with ``r`` the carried residual::

        e   = x + r                  # error-compensated input (fp32)
        q   = Q(e)                   # shared-scale int8 / fp8 codec
        r'  = e - dQ(q)              # what the wire failed to carry
        out = reduce_scatter(q)      # exact low-precision reduction

    ``dQ(q)`` is this rank's *own* dequantized contribution at full
    buffer length (the codec's exact int32 / fp32 accumulation means
    the reduced sum is the sum of exactly these per-rank values, so
    each rank's residual accounts for precisely its share of the
    total error).  ``op=Average`` scales only the reduced shard; the
    residual stays in per-rank sum-contribution units, matching the
    next step's pre-reduction input.

    Returns ``(shard, new_residual)`` — the dequantized 1/world slice
    (like :func:`quantized_reducescatter`) plus the full-length fp32
    residual to feed back next step.
    """
    if bits != 8:
        raise ValueError("only 8-bit quantization is supported")
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("ef_quantized_reducescatter supports "
                         "Sum/Average")
    wire = _resolve_wire_dtype(wire_dtype)
    world = axis_size(axis)
    if x.ndim != 1 or x.shape[0] % world:
        raise ValueError(
            f"ef_quantized_reducescatter needs a flat buffer divisible "
            f"by world size {world}, got shape {x.shape}")
    x32 = x.astype(jnp.float32)
    if residual is not None:
        x32 = x32 + residual.astype(jnp.float32)
    scale = _shared_wire_scale(x32, segments, axis, qmax=_WIRE_QMAX[wire])
    ax = axis if isinstance(axis, str) else tuple(axis)
    if wire == "fp8_e4m3":
        sent = jnp.clip(x32 / scale, -448.0, 448.0) \
            .astype(jnp.float8_e4m3fn).astype(jnp.float32)
        total = lax.psum_scatter(sent, ax, tiled=True)
    else:
        q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
        sent = q.astype(jnp.float32)
        total = lax.psum_scatter(q.astype(jnp.int32), ax, tiled=True) \
            .astype(jnp.float32)
    new_residual = x32 - sent * scale
    shard = x.shape[0] // world
    if scale.ndim:          # per-segment scales: this shard's slice
        scale = lax.dynamic_slice(scale, (axis_index(axis) * shard,),
                                  (shard,))
    y = total * scale
    if op == ReduceOp.AVERAGE:
        y = y / world
    return y.astype(x.dtype), new_residual


@dataclasses.dataclass(frozen=True)
class ShardGroup:
    """One fused wire buffer of the sharded exchange: the leaves of a
    single (bucket, dtype) cell, concatenated flat and padded to a
    shard-divisible length."""

    key: str                        # "b<bucket>/<dtype>" — shard dict key
    dtype: str                      # jnp dtype name
    indices: Tuple[int, ...]        # original leaf indices, bucket order
    sizes: Tuple[int, ...]          # per-leaf element counts
    shapes: Tuple[Tuple[int, ...], ...]
    padded: int                     # flat length after zero-padding
    shard: int                      # padded // world — this rank's slice


@dataclasses.dataclass(frozen=True)
class FusionSpec:
    """Static reassembly plan for a bucketed sharded exchange.

    Built from leaf shapes only (deterministic across shards — the
    same invariant the eager :class:`~horovod_tpu.ops.bucketing.Bucketer`
    keeps by flushing on program order, here enforced by construction).
    Carries everything :func:`grouped_allgather` needs to reverse
    :func:`grouped_reducescatter`."""

    groups: Tuple[ShardGroup, ...]
    world: int
    num_leaves: int


def make_fusion_spec(leaves: Sequence[jax.Array], world: int,
                     bucket_bytes: Optional[int] = None) -> FusionSpec:
    """Plan the bucketed sharded exchange for ``leaves``.

    Buckets come from :func:`horovod_tpu.ops.bucketing.plan_buckets`
    in reverse-layer order (see there for why); within a bucket the
    leaves split per dtype — mixed-dtype buckets ride as one bucket
    with one wire collective per member dtype, exactly like
    :func:`grouped_allreduce`'s dtype groups.  Each group's flat
    length is padded up to the next multiple of ``world`` so
    ``psum_scatter`` tiles evenly."""
    from horovod_tpu.ops.bucketing import plan_buckets

    nbytes = [x.size * x.dtype.itemsize for x in leaves]
    buckets = plan_buckets(nbytes, bucket_bytes, reverse=True)
    groups: List[ShardGroup] = []
    for b, idxs in enumerate(buckets):
        by_dtype: Dict[str, List[int]] = {}
        for i in idxs:
            by_dtype.setdefault(jnp.dtype(leaves[i].dtype).name,
                                []).append(i)
        for dtype, members in by_dtype.items():
            total = sum(leaves[i].size for i in members)
            padded = -(-max(total, 1) // world) * world
            groups.append(ShardGroup(
                key=f"b{b}/{dtype}", dtype=dtype,
                indices=tuple(members),
                sizes=tuple(int(leaves[i].size) for i in members),
                shapes=tuple(tuple(leaves[i].shape) for i in members),
                padded=padded, shard=padded // world))
    return FusionSpec(groups=tuple(groups), world=world,
                      num_leaves=len(leaves))


def _group_flat(group: ShardGroup, leaves: Sequence[jax.Array],
                prescale: Optional[float] = None) -> jax.Array:
    """Concatenate + zero-pad a group's leaves into its wire buffer."""
    flat = jnp.concatenate(
        [jnp.ravel(_scale(leaves[i], prescale)) for i in group.indices]) \
        if group.indices else jnp.zeros((0,), jnp.dtype(group.dtype))
    pad = group.padded - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


def local_fusion_shards(leaves: Sequence[jax.Array], spec: FusionSpec,
                        axis: AxisSpec = GLOBAL_AXES) -> Dict[str, jax.Array]:
    """This rank's slice of every fused group buffer — no collective,
    just concat + ``dynamic_slice`` at ``rank * shard``.  The sharded
    optimizer uses this to see the *parameter* values co-located with
    the gradient shard it owns."""
    me = axis_index(axis)
    out: Dict[str, jax.Array] = {}
    for g in spec.groups:
        flat = _group_flat(g, leaves)
        out[g.key] = lax.dynamic_slice(flat, (me * g.shard,), (g.shard,))
    return out


#: Tile count of the tile-granular final-bucket exchange
#: (``fused_tail``, docs/fused_kernels.md): the last bucket's wire is
#: split into this many independent sub-collectives so the scheduler
#: can overlap tile k's exchange with the shard-update math consuming
#: tile k-1 — the serial tail the bucketed overlap cannot hide.
FUSED_TAIL_TILES = 4


def _count_fused_tail() -> None:
    from horovod_tpu import telemetry

    telemetry.counter(
        "hvd_pallas_fused_launches_total",
        "tile-fused matmul-collective kernel constructions per kernel"
    ).inc(kernel="tail_reducescatter")


def _tiled_psum_scatter(flat: jax.Array, ax, world: int,
                        tiles: int = FUSED_TAIL_TILES) -> jax.Array:
    """Tile-granular ``psum_scatter`` of one fused flat buffer: the
    per-rank shard splits into ``tiles`` segments, each exchanged by
    its own independent collective, and the reduced shard is their
    concatenation — numerically identical to the monolithic scatter
    (same summation structure per element), but the compiler is free
    to start tile k+1's wire while tile k's output is already being
    consumed.  This is the ZeRO final-bucket form of the tile-fused
    exchange (the matmul⊗collective kernels in
    :mod:`~horovod_tpu.ops.pallas_kernels` are the tensor-parallel
    form)."""
    shard = flat.shape[0] // world
    tiles = max(1, min(int(tiles), shard if shard else 1))
    if tiles == 1 or world == 1:
        return lax.psum_scatter(flat, ax, tiled=True)
    _count_fused_tail()
    x = flat.reshape(world, shard)
    outs = []
    for t in range(tiles):
        lo = t * shard // tiles
        hi = (t + 1) * shard // tiles
        if hi == lo:
            continue
        seg = x[:, lo:hi].reshape(-1)
        outs.append(lax.psum_scatter(seg, ax, tiled=True))
    return jnp.concatenate(outs)


def grouped_reducescatter(xs: Sequence[jax.Array],
                          op: ReduceOp = Sum,
                          axis: AxisSpec = GLOBAL_AXES,
                          prescale_factor: Optional[float] = None,
                          postscale_factor: Optional[float] = None,
                          quantized_bits: Optional[int] = None,
                          bucket_bytes: Optional[int] = None,
                          spec: Optional[FusionSpec] = None,
                          fused_tail: bool = False,
                          residuals: Optional[Dict[str, jax.Array]] = None):
    """Fused reduce-scatter of many tensors — the first half of the
    ZeRO-style rewrite of :func:`grouped_allreduce` (reduce-scatter →
    shard-local math → allgather), with the same fusion machinery:
    per-(bucket, dtype) flat buffers, zero-padding to shard-divisible
    lengths, and the int8 wire of :func:`quantized_allreduce` via
    ``quantized_bits=8``.

    Returns ``(shards, spec)``: ``shards`` maps each
    :class:`ShardGroup` key to this rank's reduced ``(shard,)`` slice;
    ``spec`` is the static plan :func:`grouped_allgather` (or
    :func:`local_fusion_shards`) consumes.  ``bucket_bytes`` splits
    the exchange into reverse-layer-order buckets so XLA can overlap
    each bucket's collective with the rest of backward (see
    :func:`horovod_tpu.ops.bucketing.plan_buckets`); ``None`` keeps
    the monolithic single-bucket exchange.  ``fused_tail=True`` splits
    the LAST group's wire into :data:`FUSED_TAIL_TILES` independent
    sub-collectives (:func:`_tiled_psum_scatter`) — the tile-granular
    form of the final-bucket exchange, which no remaining backward
    work can hide (docs/fused_kernels.md); numerics are identical,
    only the schedule changes.  The quantized wire keeps its
    monolithic shared-scale collective (the codec scale is agreed per
    buffer).

    ``residuals`` (a ``{group key: (padded,) fp32}`` dict) switches the
    quantized groups to the error-feedback codec
    (:func:`ef_quantized_reducescatter`) and changes the return to
    ``(shards, spec, new_residuals)`` — feed ``new_residuals`` back on
    the next call so the wire's rounding bias telescopes away.  Groups
    without a residual entry (non-floating, or quantization off) pass
    through unchanged.

    Degenerate 1-shard worlds reduce to plain identity semantics: the
    "shard" is the whole (padded) buffer and ``psum_scatter`` over a
    size-1 axis is the local value itself.
    """
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("grouped_reducescatter supports op=Sum/Average")
    world = int(axis_size(axis))
    if spec is None:
        spec = make_fusion_spec(xs, world, bucket_bytes)
    elif spec.world != world:
        raise ValueError(
            f"spec was planned for world {spec.world}, axis has {world}")
    ax = axis if isinstance(axis, str) else tuple(axis)
    shards: Dict[str, jax.Array] = {}
    new_residuals: Dict[str, jax.Array] = \
        dict(residuals) if residuals is not None else {}
    for gi, g in enumerate(spec.groups):
        flat = _group_flat(g, xs, prescale_factor)
        floating = jnp.issubdtype(flat.dtype, jnp.floating)
        tail = fused_tail and gi == len(spec.groups) - 1
        if quantized_bits is not None and floating:
            # pad rides the last segment: zeros never raise its absmax
            segs = list(g.sizes)
            segs[-1] += g.padded - sum(g.sizes)
            if residuals is not None and g.key in residuals:
                red, new_residuals[g.key] = ef_quantized_reducescatter(
                    flat, axis=axis, op=op, residual=residuals[g.key],
                    bits=quantized_bits, segments=tuple(segs))
            else:
                red = quantized_reducescatter(flat, axis=axis, op=op,
                                              bits=quantized_bits,
                                              segments=tuple(segs))
        elif tail:
            red = _tiled_psum_scatter(flat, ax, world)
            if op == ReduceOp.AVERAGE and floating:
                red = _scale(red, 1.0 / world)
            elif op == ReduceOp.AVERAGE:
                raise ValueError(
                    "op=Average requires floating dtypes, got "
                    f"{g.dtype}")
        else:
            red = lax.psum_scatter(flat, ax, tiled=True)
            if op == ReduceOp.AVERAGE and floating:
                red = _scale(red, 1.0 / world)
            elif op == ReduceOp.AVERAGE:
                raise ValueError(
                    "op=Average requires floating dtypes, got "
                    f"{g.dtype}")
        shards[g.key] = _scale(red, postscale_factor)
    if residuals is not None:
        return shards, spec, new_residuals
    return shards, spec


def exchange_index_axes(outer_axis: str = AXIS_DCN,
                        inner_axis: str = AXIS_ICI) -> Tuple[str, str]:
    """Axis tuple whose row-major linearization matches the shard
    ownership of :func:`hierarchical_reducescatter`.

    The two-level exchange reduce-scatters over ``inner_axis`` first
    (the intra-slice ICI phase), then over ``outer_axis`` (the
    cross-slice DCN phase), so the rank holding flat-buffer block ``k``
    satisfies ``k = inner_index * outer_size + outer_index`` — row-major
    over ``(inner, outer)``, NOT the mesh's usual ``(outer, inner)``.
    Feed this tuple to :func:`local_fusion_shards` /
    :func:`grouped_allgather` (and :func:`axis_index`) so parameter
    slices and reassembly line up with the hierarchical ownership."""
    return (inner_axis, outer_axis)


@dataclasses.dataclass(frozen=True)
class ExchangeLevel:
    """One level of the N-level tree exchange: the mesh axis (or axis
    tuple, for a degenerate flat level spanning the world) this level's
    collectives scope to, and the wire-codec width on its hop (None =
    full precision).  Levels are ordered INNERMOST first — chip <
    slice < pod < cluster (``runtime/topology.TopologyTree``)."""

    axis: AxisSpec
    quantized_bits: Optional[int] = None


def exchange_levels_from_topology(tree) -> Tuple["ExchangeLevel", ...]:
    """The :class:`ExchangeLevel` sequence of one resolved
    ``runtime/topology.TopologyTree``: each level scopes to its own
    mesh axis at its configured ``wire_bits`` — how the per-level
    codec knob (``HOROVOD_EXCHANGE_LEVEL_CODECS``) reaches the data
    plane."""
    return tuple(ExchangeLevel(axis=lv.axis_spec,
                               quantized_bits=lv.wire_bits)
                 for lv in tree.levels)


def tree_index_axes(levels: Sequence[ExchangeLevel]) -> Tuple[str, ...]:
    """Axis tuple whose row-major linearization matches the shard
    ownership of :func:`tree_reducescatter` — the N-level
    generalization of :func:`exchange_index_axes`.

    Phase ℓ reduce-scatters the block surviving the inner phases, so
    the rank holding flat-buffer block ``k`` satisfies ``k = i₀·(n₁·…)
    + i₁·(n₂·…) + …`` — row-major over the levels innermost-FIRST
    (level 0 is the slowest digit).  Feed this tuple to
    :func:`tree_allgather` / :func:`local_fusion_shards` /
    :func:`axis_index` so slices and reassembly line up."""
    axes: List[str] = []
    for lv in levels:
        ax = lv.axis
        if isinstance(ax, str):
            axes.append(ax)
        else:
            axes.extend(ax)
    return tuple(axes)


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def _count_adasum_exchange(ax) -> None:
    from horovod_tpu import telemetry

    telemetry.counter(
        "hvd_adasum_steps_total",
        "adasum outer-level exchange constructions per level"
    ).inc(level=str(ax))


def _quantized_pair_exchange(x: jax.Array, ax, perm,
                             wire_dtype: Optional[str] = None):
    """One codec-compressed ``ppermute`` round of the adasum schedule.

    The absmax scale is agreed over the whole level with one ``pmax``
    (every rank holds the identical scale), so the XOR partner
    dequantizes the received payload exactly; BOTH sides of the combine
    see dequantized wire values — the pairwise rule stays symmetric, so
    partners compute identical results and the recursive doubling keeps
    its all-ranks-converge property under quantization."""
    wire = _resolve_wire_dtype(wire_dtype)
    x32 = x.astype(jnp.float32)
    scale = _shared_wire_scale(x32, (), ax, qmax=_WIRE_QMAX[wire])
    if wire == "fp8_e4m3":
        q = jnp.clip(x32 / scale, -448.0, 448.0) \
            .astype(jnp.float8_e4m3fn)
    else:
        q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    own = (q.astype(jnp.float32) * scale).astype(x.dtype)
    partner = (lax.ppermute(q, ax, perm=perm).astype(jnp.float32)
               * scale).astype(x.dtype)
    return own, partner


def _adasum_combine(a: jax.Array, b: jax.Array,
                    scalar_axes=()) -> jax.Array:
    """:func:`adasum_pair` with the fp32 dot/norm scalars additionally
    psummed over ``scalar_axes`` — the inner topology levels the fused
    bucket is already scattered across.  Each inner rank holds a
    different segment of the bucket, so the local partial dots only
    become the whole-bucket ⟨a,b⟩/‖a‖²/‖b‖² after the (cheap, scalar,
    intra-slice) reduction; every rank then applies the SAME
    coefficients and the damping is consistent across the bucket's
    segments."""
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    dot = jnp.vdot(af, bf)
    anormsq = jnp.vdot(af, af)
    bnormsq = jnp.vdot(bf, bf)
    if scalar_axes:
        dot = lax.psum(dot, scalar_axes)
        anormsq = lax.psum(anormsq, scalar_axes)
        bnormsq = lax.psum(bnormsq, scalar_axes)
    acoeff = jnp.where(anormsq >= 1e-30,
                       1.0 - dot / (2.0 * anormsq + 1e-30), 1.0)
    bcoeff = jnp.where(bnormsq >= 1e-30,
                       1.0 - dot / (2.0 * bnormsq + 1e-30), 1.0)
    return (acoeff * af + bcoeff * bf).astype(a.dtype)


def _adasum_psum_scatter(block: jax.Array, ax, n: int,
                         bits: Optional[int] = None,
                         wire_dtype: Optional[str] = None,
                         scalar_axes=()) -> jax.Array:
    """Recursive-doubling AdaSum reduce-scatter over one (outermost)
    topology level — the operator analogue of
    ``lax.psum_scatter(tiled=True)``, with :func:`adasum_pair` as the
    combine.  log2(n) XOR-partner ``ppermute`` rounds exchange the full
    surviving block; the dot/norms are whole-bucket per fused
    (bucket, dtype) group — the local partials over this rank's
    surviving segment are psummed over ``scalar_axes`` (the inner
    levels, :func:`_adasum_combine`), so every rank applies identical
    coefficients even though the inner scatter made segment ownership
    rank-dependent.  Every rank then slices its own tiled 1/n shard, so
    ownership matches :func:`tree_index_axes` and :func:`tree_allgather`
    reassembles unchanged.

    ``bits`` runs each round's wire through the shared-scale codec
    (:func:`_quantized_pair_exchange`) — the codec quantizes the wire,
    the operator combines the payload.  Non-power-of-two levels (and
    degenerate axis-tuple levels) gather once and run the identical
    binary tree replicated on every rank, like ``ops/adasum.py``'s
    fallback.  An extent-1 level is the identity scatter.
    """
    if n == 1:
        return lax.psum_scatter(block, ax, tiled=True)
    _count_adasum_exchange(ax)
    shard = block.shape[0] // n
    x = block
    if isinstance(ax, str) and _is_pow2(n):
        for r in range(n.bit_length() - 1):
            dist = 1 << r
            perm = [(i, i ^ dist) for i in range(n)]
            if bits is not None:
                own, partner = _quantized_pair_exchange(
                    x, ax, perm, wire_dtype)
                x = _adasum_combine(own, partner, scalar_axes)
            else:
                x = _adasum_combine(x, lax.ppermute(x, ax, perm=perm),
                                    scalar_axes)
    else:
        stacked = allgather(x, ax, tiled=False).reshape((n,) + x.shape)
        vals = [stacked[i] for i in range(n)]
        while len(vals) > 1:
            nxt = [_adasum_combine(vals[i], vals[i + 1], scalar_axes)
                   for i in range(0, len(vals) - 1, 2)]
            if len(vals) % 2:
                nxt.append(vals[-1])
            vals = nxt
        x = vals[0]
    return lax.dynamic_slice(x, (axis_index(ax) * shard,), (shard,))


def tree_reducescatter(xs: Sequence[jax.Array],
                       levels: Sequence[ExchangeLevel],
                       op: ReduceOp = Sum,
                       prescale_factor: Optional[float] = None,
                       postscale_factor: Optional[float] = None,
                       bucket_bytes: Optional[int] = None,
                       spec: Optional[FusionSpec] = None,
                       fused_tail: bool = False,
                       residuals: Optional[Dict[str, jax.Array]] = None,
                       reduction: str = "sum"):
    """N-level topology-aware reduce-scatter: the reduce phase of the
    tree exchange, composed per level from the resolved topology
    (``runtime/topology.resolve_topology``).  Phase ℓ reduce-scatters
    the block surviving phases 0..ℓ-1 over level ℓ's axis, so level
    ℓ's fabric carries only ``(nℓ−1)/nℓ · B/∏inner`` bytes — the
    hierarchical shrink that makes the slow hops cheap, now at any
    depth.  A 1-level tree is the flat exchange, a 2-level tree is
    exactly :func:`hierarchical_reducescatter` (which delegates here);
    the parity pins in ``tests/test_hierarchy_smoke.py`` and
    ``tests/test_collectives.py`` hold the degeneracies.

    Per-level codec: each :class:`ExchangeLevel` with
    ``quantized_bits`` runs its hop through the shared-scale codec.
    The INNERMOST level's codec gets per-leaf segment scales (its
    input buffer is still whole, so segment boundaries are static) and
    honors ``residuals`` (error feedback, changing the return to
    ``(shards, spec, new_residuals)``); outer levels share one scale
    per block — the inner scatter makes segment boundaries
    rank-dependent, exactly the two-level DCN-hop constraint.
    ``fused_tail`` splits the LAST group's innermost hop into
    :data:`FUSED_TAIL_TILES` sub-collectives (codec wins when both are
    requested, matching :func:`grouped_reducescatter`'s branch order).

    ``reduction="adasum"`` swaps the OUTERMOST level's combine for the
    AdaSum operator (:func:`_adasum_psum_scatter`): plain sum/RS within
    the inner levels where replicas barely diverge, adaptive summation
    on the slow outer hop where they diverge most.  The operator is
    orthogonal to hierarchy and codec — inner-level RS, per-level wire
    codecs, and error-feedback residuals stack unchanged (the codec
    quantizes the wire; the operator combines the payload).  A 1-level
    tree (single-slice world: no outer hop) and an extent-1 outermost
    level degenerate to the bit-identical plain-sum path.  With
    ``op=Average`` the inner levels deliver the inner-replica mean
    (1/inner scale folded in before the outer round) and the final
    1/world divide is skipped — adasum is itself the average-like
    cross-replica combine.

    Ownership is row-major over :func:`tree_index_axes`; reassemble
    with :func:`tree_allgather`.
    """
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("tree_reducescatter supports op=Sum/Average")
    if reduction not in REDUCTIONS:
        raise ValueError(
            f"tree_reducescatter reduction must be one of {REDUCTIONS}, "
            f"got {reduction!r}")
    levels = tuple(levels)
    if not levels:
        raise ValueError("tree_reducescatter needs >= 1 level")
    if residuals is not None and levels[0].quantized_bits is None:
        raise ValueError(
            "residuals carry the innermost hop's codec error "
            "feedback; give levels[0] quantized_bits to enable it")
    sizes = [int(axis_size(lv.axis)) for lv in levels]
    world = 1
    for n in sizes:
        world *= n
    if spec is None:
        spec = make_fusion_spec(xs, world, bucket_bytes)
    elif spec.world != world:
        raise ValueError(
            f"spec was planned for world {spec.world}, the "
            f"{len(levels)}-level tree has {world}")
    # adasum rides the outermost level only, and only when there IS an
    # outer hop to ride: single-level trees and extent-1 outer levels
    # take the plain-sum path bit-identically
    adasum_outer = (reduction == "adasum" and len(levels) >= 2
                    and sizes[-1] > 1)
    adasum_scalar_axes: tuple = ()
    if adasum_outer:
        # the inner levels the bucket is scattered across at the outer
        # hop — the dot/norm partials reduce over these so every rank
        # applies whole-bucket coefficients (_adasum_combine)
        inner_axes = []
        for lv in levels[:-1]:
            if isinstance(lv.axis, str):
                inner_axes.append(lv.axis)
            else:
                inner_axes.extend(lv.axis)
        adasum_scalar_axes = tuple(inner_axes)
    shards: Dict[str, jax.Array] = {}
    new_residuals: Dict[str, jax.Array] = \
        dict(residuals) if residuals is not None else {}
    for gi, g in enumerate(spec.groups):
        block = _group_flat(g, xs, prescale_factor)
        floating = jnp.issubdtype(block.dtype, jnp.floating)
        adasum_done = False
        if op == ReduceOp.AVERAGE and not floating:
            raise ValueError(
                f"op=Average requires floating dtypes, got {g.dtype}")
        for li, lv in enumerate(levels):
            ax = lv.axis if isinstance(lv.axis, str) else tuple(lv.axis)
            bits = lv.quantized_bits
            if li == 0 and bits is not None and floating:
                # innermost hop: whole buffer, static per-leaf segment
                # boundaries — pad rides the last segment (zeros never
                # raise its absmax); EF when the caller carries state
                segs = list(g.sizes)
                segs[-1] += g.padded - sum(g.sizes)
                if residuals is not None and g.key in residuals:
                    block, new_residuals[g.key] = \
                        ef_quantized_reducescatter(
                            block, axis=ax, op=ReduceOp.SUM,
                            residual=residuals[g.key], bits=bits,
                            segments=tuple(segs))
                else:
                    block = quantized_reducescatter(
                        block, axis=ax, op=ReduceOp.SUM, bits=bits,
                        segments=tuple(segs))
            elif li == 0 and fused_tail and gi == len(spec.groups) - 1:
                block = _tiled_psum_scatter(block, ax, sizes[0])
            elif adasum_outer and li == len(levels) - 1 and floating:
                # outermost hop: AdaSum adaptive combine; Average means
                # the inner levels must deliver the inner-replica mean
                # (fold the 1/inner scale in now) and the final 1/world
                # divide is skipped — adasum IS the cross-replica
                # average-like operator
                if op == ReduceOp.AVERAGE:
                    block = _scale(block, float(sizes[li]) / world)
                block = _adasum_psum_scatter(
                    block, ax, sizes[li], bits=bits,
                    scalar_axes=adasum_scalar_axes)
                adasum_done = True
            elif bits is not None and floating:
                # outer hop: the surviving block, one shared scale —
                # segment boundaries are rank-dependent after the
                # inner scatter, so per-leaf scales cannot ride here
                block = quantized_reducescatter(
                    block, axis=ax, op=ReduceOp.SUM, bits=bits)
            else:
                block = lax.psum_scatter(block, ax, tiled=True)
        if op == ReduceOp.AVERAGE and not adasum_done:
            block = _scale(block, 1.0 / world)
        shards[g.key] = _scale(block, postscale_factor)
    if residuals is not None:
        return shards, spec, new_residuals
    return shards, spec


def tree_allgather(shards: Dict[str, jax.Array], spec: FusionSpec,
                   levels: Sequence[ExchangeLevel]) -> list:
    """Reassemble the shards of :func:`tree_reducescatter` — the
    gather phase of the tree exchange, mirrored outermost-first: each
    level's all-gather runs while the buffers are still shrunk by
    every level inside it, so every fabric moves the minimum possible
    bytes (the N-level form of :func:`hierarchical_allgather`).
    Gathering over :func:`tree_index_axes` makes the concatenation
    order row-major over exactly the scatter's ownership
    linearization, so this is its precise inverse."""
    return grouped_allgather(shards, spec, axis=tree_index_axes(levels))


def hierarchical_reducescatter(xs: Sequence[jax.Array],
                               op: ReduceOp = Sum,
                               outer_axis: str = AXIS_DCN,
                               inner_axis: str = AXIS_ICI,
                               prescale_factor: Optional[float] = None,
                               postscale_factor: Optional[float] = None,
                               quantized_bits: Optional[int] = None,
                               bucket_bytes: Optional[int] = None,
                               spec: Optional[FusionSpec] = None,
                               fused_tail: bool = False,
                               quantize_inner: bool = False,
                               inner_residuals: Optional[
                                   Dict[str, jax.Array]] = None,
                               reduction: str = "sum"):
    """Topology-aware two-level reduce-scatter — the reduce phase of the
    hierarchical exchange (reference ``NCCLHierarchicalAllreduce``,
    ``nccl_operations.cc:191-341``: NCCL inside the node, MPI across).

    Phase 1 reduce-scatters each fused group buffer over ``inner_axis``
    (chips within an ICI slice: the cheap torus hop carries the full
    ``(n_ici-1)/n_ici·B``).  Phase 2 reduce-scatters the surviving
    ``1/n_ici`` partial-sum block over ``outer_axis`` — the slow DCN hop
    therefore carries only ``(n_dcn-1)/n_dcn·B/n_ici`` bytes, which is
    the whole point of splitting the levels.  ``quantized_bits=8`` puts
    the int8 shared-scale codec of :func:`quantized_reducescatter` on
    the DCN phase ONLY: wire compression where the fabric is slow, full
    precision where it is already fast (EQuARX's topology-scoped
    compression argument, arXiv:2506.17615).  The codec scale is shared
    per (bucket, dtype, inner-shard) block — per-leaf segment scales
    cannot ride this hop because the inner scatter makes segment
    boundaries rank-dependent (and XLA shapes must be static).

    Returns ``(shards, spec)`` exactly like
    :func:`grouped_reducescatter`, with the one twist that shard
    ownership is linearized row-major over ``(inner, outer)`` — see
    :func:`exchange_index_axes`.  Reassemble with
    :func:`hierarchical_allgather` (cross-slice gather first, then
    intra-slice — each level's traffic stays on its own fabric).

    ``quantize_inner=True`` (requires ``quantized_bits``) additionally
    puts the codec on the ICI phase — double-compressed wire, for
    bandwidth-bound multi-slice runs.  Pass ``inner_residuals``
    (``{group key: (padded,) fp32}``) to run that hop through
    :func:`ef_quantized_reducescatter` so the extra rounding is
    error-fed-back instead of biasing the trajectory; the return then
    becomes ``(shards, spec, new_inner_residuals)``.  Per-leaf segment
    scales *do* ride the inner hop (the input buffer is still whole,
    unlike the DCN phase), so small leaves keep their own codec step.

    ``reduction="adasum"`` puts the AdaSum combine on the DCN phase
    (plain RS stays on ICI) — see :func:`tree_reducescatter`; a size-1
    ``outer_axis`` degenerates it to the bit-identical plain sum.

    Degenerate axes (size-1 dcn on a single slice, or size-1 ici) fall
    through cleanly: a ``psum_scatter`` over a 1-extent axis is the
    local value, so the two-level form equals the flat one.
    """
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("hierarchical_reducescatter supports "
                         "op=Sum/Average")
    if quantize_inner and quantized_bits is None:
        raise ValueError(
            "quantize_inner puts the codec on the ICI phase; pass "
            "quantized_bits=8 to select it")
    if inner_residuals is not None and not quantize_inner:
        raise ValueError(
            "inner_residuals carry the ICI codec's error feedback; "
            "pass quantize_inner=True to enable that hop")
    n_inner = int(lax.axis_size(inner_axis))
    n_outer = int(lax.axis_size(outer_axis))
    world = n_inner * n_outer
    if spec is None:
        spec = make_fusion_spec(xs, world, bucket_bytes)
    elif spec.world != world:
        raise ValueError(
            f"spec was planned for world {spec.world}, mesh "
            f"({outer_axis},{inner_axis}) has {world}")
    # the two-level exchange is the 2-level degenerate tree: ICI is the
    # innermost level (per-leaf segment codec iff quantize_inner, the
    # fused tail), DCN the outer (shared-scale codec iff quantized_bits)
    levels = (ExchangeLevel(inner_axis,
                            quantized_bits if quantize_inner else None),
              ExchangeLevel(outer_axis, quantized_bits))
    return tree_reducescatter(xs, levels, op=op,
                              prescale_factor=prescale_factor,
                              postscale_factor=postscale_factor,
                              spec=spec, fused_tail=fused_tail,
                              residuals=inner_residuals,
                              reduction=reduction)


def hierarchical_allgather(shards: Dict[str, jax.Array], spec: FusionSpec,
                           outer_axis: str = AXIS_DCN,
                           inner_axis: str = AXIS_ICI) -> list:
    """Reassemble the shards of :func:`hierarchical_reducescatter` —
    the gather phase of the two-level exchange, mirrored: all-gather
    across ``outer_axis`` first while the buffers are still 1/world
    sized (the DCN hop moves the minimum possible bytes), then across
    ``inner_axis`` on the fast fabric.  Gathering over the
    ``(inner, outer)`` tuple makes the concatenation order row-major
    over exactly the ownership linearization of the scatter (see
    :func:`exchange_index_axes`), so this is its precise inverse."""
    return tree_allgather(shards, spec,
                          (ExchangeLevel(inner_axis),
                           ExchangeLevel(outer_axis)))


def grouped_allgather(shards: Dict[str, jax.Array], spec: FusionSpec,
                      axis: AxisSpec = GLOBAL_AXES) -> list:
    """Reassemble per-rank group shards into full tensors — the second
    half of the sharded exchange.  All-gathers each group buffer
    (innermost mesh axis first, so concatenation order matches
    :func:`axis_index`'s row-major linearization), strips the padding,
    and splits back into the original leaf order.  The exact inverse
    of :func:`grouped_reducescatter`'s packing."""
    out: list = [None] * spec.num_leaves
    for g in spec.groups:
        flat = allgather(shards[g.key], axis=axis, tiled=True)
        # pin the gathered buffer before it is cut into leaves.  Left
        # free, the TPU compiler rewrites every leaf's slice back
        # through the all-gather's (world, shard) result and spends
        # minutes a buffer on it (PERF.md, PR 21 finding 2: on four
        # v5e chips a two-layer LM's first ZeRO step took 240 s without
        # this and 14 s with it; jax 0.9.0 / libtpu 0.0.34); pinned,
        # the slices read the materialized buffer, which the gather
        # produces either way
        flat = lax.optimization_barrier(flat)
        offset = 0
        for i, n, shape in zip(g.indices, g.sizes, g.shapes):
            out[i] = flat[offset:offset + n].reshape(shape)
            offset += n
    return out


def scatter_dimension(shape: Sequence[int], world: int) -> Optional[int]:
    """The dimension the plain sharded exchange cuts a leaf of ``shape``
    along: its first one that ``world`` divides, None where there is
    none (a scalar, an odd vector) — such a leaf rides the packed
    remainder group."""
    for d, n in enumerate(shape):
        if n and n % world == 0:
            return d
    return None


def leaf_reducescatter(x: jax.Array, dim: int, op: ReduceOp = Sum,
                       axis: AxisSpec = GLOBAL_AXES,
                       prescale_factor: Optional[float] = None,
                       postscale_factor: Optional[float] = None
                       ) -> jax.Array:
    """Reduce-scatter of one tensor in its own shape — the reduce half
    of the plain sharded exchange: this rank's ``1/world`` slab of the
    reduced ``x`` along ``dim`` (:func:`scatter_dimension`), no ravel,
    no buffer.  The collective depends on this one gradient alone, so
    it may start while the backward pass is still producing the
    others."""
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError("leaf_reducescatter supports op=Sum/Average")
    ax = axis if isinstance(axis, str) else tuple(axis)
    red = lax.psum_scatter(_scale(x, prescale_factor), ax,
                           scatter_dimension=dim, tiled=True)
    if op == ReduceOp.AVERAGE:
        if not jnp.issubdtype(x.dtype, jnp.floating):
            raise ValueError(
                f"op=Average requires floating dtypes, got {x.dtype}")
        red = _scale(red, 1.0 / axis_size(axis))
    return _scale(red, postscale_factor)


def leaf_slab(x: jax.Array, dim: int,
              axis: AxisSpec = GLOBAL_AXES) -> jax.Array:
    """This rank's slab of a replicated tensor along ``dim`` — the slab
    :func:`leaf_reducescatter` hands the same rank, so the sharded
    optimizer sees the parameter values co-located with its gradient
    slab.  No collective: reads and writes ``1/world`` of ``x``."""
    rows = x.shape[dim] // int(axis_size(axis))
    return lax.dynamic_slice_in_dim(x, axis_index(axis) * rows, rows,
                                    axis=dim)


def leaf_allgather(x: jax.Array, dim: int,
                   axis: AxisSpec = GLOBAL_AXES) -> jax.Array:
    """Reassemble the slabs of :func:`leaf_reducescatter` along
    ``dim`` — the gather half of the plain sharded exchange, one
    collective over the whole axis tuple (its concatenation order is
    row-major over the tuple, :func:`axis_index`'s)."""
    ax = axis if isinstance(axis, str) else tuple(axis)
    return lax.all_gather(x, ax, axis=dim, tiled=True)


def sparse_allreduce(values: jax.Array, indices: jax.Array,
                     dense_rows: int, axis: AxisSpec = GLOBAL_AXES,
                     op: ReduceOp = Average) -> jax.Array:
    """Sparse (row-indexed) gradient reduction — the reference's
    ``IndexedSlices`` path (``tensorflow/__init__.py:100-110``): sparse
    grads become allgather(values) + allgather(indices) instead of a
    dense allreduce.  Static-shape TPU form: gather both, scatter-add
    into the dense result.  Returns the dense ``(dense_rows, ...)``
    reduced gradient (the ``sparse_as_dense`` output shape).
    """
    world = axis_size(axis)
    all_vals = allgather(values, axis=axis, tiled=False)
    all_idx = allgather(indices, axis=axis, tiled=False)
    all_vals = all_vals.reshape((-1,) + values.shape)
    all_idx = all_idx.reshape((-1,) + indices.shape)
    dense = jnp.zeros((dense_rows,) + values.shape[1:],
                      jnp.promote_types(values.dtype, jnp.float32))
    for s in range(world):
        dense = dense.at[all_idx[s]].add(all_vals[s].astype(dense.dtype))
    if op == ReduceOp.AVERAGE:
        dense = dense / world
    elif op != ReduceOp.SUM:
        raise ValueError("sparse_allreduce supports Sum/Average")
    return dense.astype(values.dtype)


def allgather(x: jax.Array, axis: AxisSpec = GLOBAL_AXES,
              tiled: bool = True) -> jax.Array:
    """Allgather along the first tensor dimension (reference
    ``EnqueueTensorAllgather``, ``operations.cc:903``; same-shape case).

    With ``tiled=True`` the result concatenates shards along dim 0 —
    Horovod's layout.  Variable first-dim gathers (``MPIAllgather`` recvcount
    machinery, ``mpi_operations.cc:96``) are handled by
    :func:`allgather_v`.
    """
    if isinstance(axis, str):
        return lax.all_gather(x, axis, tiled=tiled)
    y = x
    # gather innermost axis first so the final ordering is row-major over
    # the axis tuple, matching axis_index()
    for a in reversed(tuple(axis)):
        y = lax.all_gather(y, a, tiled=tiled)
    return y


def allgather_v(x: jax.Array, valid_count: jax.Array,
                max_count: int, axis: AxisSpec = GLOBAL_AXES):
    """Variable-first-dim allgather.

    Each shard contributes ``valid_count`` ≤ ``max_count`` rows of ``x``
    (padded to ``max_count``).  Returns ``(gathered, counts)`` where
    ``gathered`` is ``(world, max_count, ...)`` and ``counts`` the per-rank
    valid sizes — the displacement bookkeeping of ``AllgatherOp``
    (``collective_operations.h:127-176``) in static-shape form.  Callers
    compact on host or mask in-graph; XLA needs the static bound.
    """
    pad_shape = (max_count,) + x.shape[1:]
    padded = jnp.zeros(pad_shape, x.dtype).at[:x.shape[0]].set(x) \
        if x.shape[0] != max_count else x
    gathered = allgather(padded, axis=axis, tiled=False)
    # non-tiled gather over an axis tuple stacks one leading dim per axis
    # (row-major by construction); flatten them into the world dim
    gathered = gathered.reshape((-1,) + pad_shape)
    counts = allgather(jnp.asarray(valid_count, jnp.int32)[None],
                       axis=axis, tiled=True)
    return gathered, counts


def allgather_v_mask(counts: jax.Array, max_count: int) -> jax.Array:
    """``(world, max_count)`` bool mask of the valid rows in an
    :func:`allgather_v` result — the in-graph masking idiom, provided
    once so call sites don't re-derive it::

        gathered, counts = allgather_v(x, n, max_count)
        mask = allgather_v_mask(counts, max_count)
        total = jnp.sum(jnp.where(mask[..., None], gathered, 0), (0, 1))
    """
    return jnp.arange(max_count)[None, :] < counts[:, None]


def allgather_v_compact(gathered, counts) -> "np.ndarray":
    """Host-side compaction of an :func:`allgather_v` result: drop the
    padding and concatenate every shard's valid rows along dim 0 —
    Horovod's variable allgather output layout (``MPI_Allgatherv``
    displacement packing, ``mpi_operations.cc:96``).  Call *outside*
    jit: the output's first dim is data-dependent.
    """
    g = np.asarray(gathered)
    c = np.asarray(counts).reshape(-1)
    return np.concatenate([g[i, :int(c[i])] for i in range(len(c))],
                          axis=0)


def broadcast(x: jax.Array, root_rank: int = 0,
              axis: AxisSpec = GLOBAL_AXES) -> jax.Array:
    """Broadcast the value held by ``root_rank`` (linearized over ``axis``)
    to every shard (reference ``EnqueueTensorBroadcast``,
    ``operations.cc:928``).

    Implemented as select+psum: contributions from non-root shards are
    zeroed, so the reduction *is* the broadcast.  XLA pattern-matches this
    to a collective-broadcast where profitable.
    """
    me = axis_index(axis)
    contrib = jnp.where(me == root_rank, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis)


def reducescatter(x: jax.Array, op: ReduceOp = Sum,
                  axis: str = AXIS_ICI,
                  scatter_dimension: int = 0) -> jax.Array:
    """Reduce-scatter (the building block of the reference's hierarchical
    allreduce, ``nccl_operations.cc:298``): each shard gets one reduced
    1/world slice along ``scatter_dimension``."""
    y = lax.psum_scatter(x, axis, scatter_dimension=scatter_dimension,
                         tiled=True)
    if op == ReduceOp.AVERAGE:
        y = _scale(y, 1.0 / axis_size(axis))
    return y


def alltoall(x: jax.Array, axis: AxisSpec = GLOBAL_AXES,
             split_axis: int = 0, concat_axis: int = 0) -> jax.Array:
    """Equal-splits alltoall (reference ``EnqueueTensorAlltoall``,
    ``operations.cc:979``; ``NCCLAlltoall`` P2P impl
    ``nccl_operations.cc:569``).  The variable-``splits`` form of the
    reference maps to :func:`alltoall_v`.

    Over an axis *tuple* (the reference's GLOBAL communicator over the
    (dcn, ici) mesh) the exchange decomposes into one per-axis
    ``all_to_all`` per mesh level: with destination ranks linearized
    row-major as ``(s, t)``, exchanging the ``t``-index over ici and the
    ``s``-index over dcn commute and compose to the global permutation
    ``out[s, t] = in_{(s,t)}[p, q]`` — each level's traffic rides that
    level's interconnect (ICI stays on ICI; only the dcn-level exchange
    crosses DCN), which is strictly better than flattening to one big
    ring the way a rank-linearized NCCL alltoall would.
    """
    if isinstance(axis, (tuple, list)) and len(axis) == 1:
        axis = axis[0]
    if isinstance(axis, str):
        return lax.all_to_all(x, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
    axes = tuple(axis)
    sizes = [lax.axis_size(a) for a in axes]
    n = axis_size(axes)
    if x.shape[split_axis] % n:
        raise ValueError(
            f"alltoall split dim {x.shape[split_axis]} not divisible by "
            f"world size {n}")
    chunk = x.shape[split_axis] // n
    lead, tail = x.shape[:split_axis], x.shape[split_axis + 1:]
    # expose one dim per mesh level (row-major, matching axis_index), then
    # exchange each level's index along its own axis
    y = x.reshape(lead + tuple(sizes) + (chunk,) + tail)
    for k, a in enumerate(axes):
        y = lax.all_to_all(y, a, split_axis=split_axis + k,
                           concat_axis=split_axis + k, tiled=True)
    if concat_axis == split_axis:
        return y.reshape(lead + (n * chunk,) + tail)
    # chunks received from the n peers concatenate along a different dim:
    # isolate the peer dim, move it to just before the concat target, merge
    y = y.reshape(lead + (n, chunk) + tail)
    y = jnp.moveaxis(y, split_axis, concat_axis)
    out_shape = list(x.shape)
    out_shape[split_axis] = chunk
    out_shape[concat_axis] *= n
    return y.reshape(out_shape)


def alltoall_v(x: jax.Array, send_counts: jax.Array, max_count: int,
               axis: AxisSpec = AXIS_ICI):
    """Variable-splits alltoall on top of the equal-tile primitive.

    Reference semantics (``AlltoallOp::PrepareOutputAndParams``,
    ``collective_operations.h:206-256``): rank r sends ``send_counts[d]``
    rows to each destination d.  Static-shape formulation: the caller packs
    rows destined to d into slot d of a ``(world, max_count, ...)`` buffer
    (d linearized row-major over an axis tuple, matching ``axis_index``);
    we alltoall the slots and return ``(received, recv_counts)`` — the
    recv-splits negotiation (``mpi_controller.cc:212``) becomes one tiny
    int alltoall.  Works over a single axis or the full (dcn, ici) tuple.
    """
    world = int(axis_size(axis))
    assert x.shape[0] == world and x.shape[1] == max_count, (
        "alltoall_v input must be (world, max_count, ...) slot-packed")
    received = alltoall(x, axis=axis)
    recv_counts = alltoall(jnp.asarray(send_counts, jnp.int32), axis=axis)
    return received, recv_counts


def barrier(axis: AxisSpec = GLOBAL_AXES) -> jax.Array:
    """Cross-shard barrier (reference ``MPIController::Barrier``,
    ``mpi_controller.cc:225``): a scalar psum every shard must reach."""
    return lax.psum(jnp.int32(1), axis)


def _bits(x: jax.Array, nbits: int) -> jax.Array:
    """Unpack an int array into a (..., nbits) {0,1} array.  Arithmetic
    right-shift + ``& 1`` reads every bit position incl. the sign bit."""
    shifts = jnp.arange(nbits, dtype=x.dtype)
    return (x[..., None] >> shifts) & 1


def _pack(bits: jax.Array, dtype) -> jax.Array:
    """Repack (..., nbits) {0,1} bits into ``dtype`` words.  Accumulates in
    the unsigned counterpart so the top (sign) bit packs without overflow,
    then reinterprets into the target dtype."""
    nbits = bits.shape[-1]
    acc = jnp.uint64 if nbits > 32 else jnp.uint32
    shifts = jnp.arange(nbits, dtype=acc)
    packed = jnp.sum(bits.astype(acc) << shifts, axis=-1)
    return lax.convert_element_type(packed, dtype)


def bitwise_and(x: jax.Array, axis: AxisSpec = GLOBAL_AXES,
                nbits: Optional[int] = None) -> jax.Array:
    """Cross-shard bitwise AND of int bitvectors (reference
    ``CrossRankBitwiseAnd``, ``mpi_controller.cc:88`` — the response-cache
    agreement primitive).  A bit survives iff every shard set it, i.e. its
    psum equals the world size — bit-decompose, psum, repack.  All bits of
    the input dtype participate by default (reference operates on full
    64-bit words); pass ``nbits`` to restrict to the low bits."""
    if x.dtype == jnp.bool_:
        return lax.psum(x.astype(jnp.int32), axis) == axis_size(axis)
    nbits = nbits or jnp.iinfo(x.dtype).bits
    n = axis_size(axis)
    counts = lax.psum(_bits(x, nbits).astype(jnp.int32), axis)
    return _pack((counts == n).astype(jnp.int32), x.dtype)


def bitwise_or(x: jax.Array, axis: AxisSpec = GLOBAL_AXES,
               nbits: Optional[int] = None) -> jax.Array:
    """Cross-shard bitwise OR (reference ``CrossRankBitwiseOr``,
    ``mpi_controller.cc:97``): a bit is set iff any shard set it."""
    if x.dtype == jnp.bool_:
        return lax.psum(x.astype(jnp.int32), axis) > 0
    nbits = nbits or jnp.iinfo(x.dtype).bits
    counts = lax.psum(_bits(x, nbits).astype(jnp.int32), axis)
    return _pack((counts > 0).astype(jnp.int32), x.dtype)
