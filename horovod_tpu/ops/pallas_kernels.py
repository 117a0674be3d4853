"""Pallas TPU kernels for the hot ops.

Kernels, mirroring where the reference spends native effort:

* :func:`fused_scale` — the fusion-buffer scale kernel (reference
  ``ops/cuda/cuda_kernels.cu`` ``scale_buffer_k``/``ScaleBufferCudaImpl``):
  one pass over the fused gradient buffer applying the pre/postscale
  factor with an optional wire-dtype cast, saturating VPU lanes instead
  of paying two HBM round-trips for scale-then-cast.
* :func:`flash_attention` — blocked causal attention (the MXU hot loop
  of :mod:`~horovod_tpu.models.transformer`): Q blocks stream against
  K/V blocks held in VMEM with the online-softmax recurrence, never
  materializing the (T, T) score matrix in HBM.
* :func:`matmul_reducescatter` / :func:`allgather_matmul` — tile-fused
  matmul ⊗ collective ops (arXiv:2305.06942, docs/fused_kernels.md):
  the matmul at a tensor-parallel boundary decomposes into per-rank
  tiles streamed around a ``ppermute`` ring, so the exchange of tile
  *k* overlaps the MXU compute of tile *k+1* inside one op and the
  full-width serial collective at the boundary disappears from the
  schedule.  Each tile's dot runs the blocked Pallas matmul kernel on
  TPU (:func:`pallas_matmul`).

All degrade gracefully: off-TPU (or for shapes that don't meet the
tiling contract) they fall back to the identical jnp formulation, and
tests run the kernels in interpreter mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from horovod_tpu.utils import logging as hvd_logging

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
_warned_unblocked: set = set()   # q shapes already reported (flash_attention)


def _on_tpu() -> bool:
    # a backend that fails to initialise is an error, not "no TPU"
    return jax.devices()[0].platform == "tpu"


def _use_kernel(interpret: bool) -> bool:
    """Whether a Pallas kernel runs rather than its jnp formulation: on
    a TPU backend always; elsewhere only in interpreter mode.
    ``interpret`` is test plumbing for the CPU twin — on a TPU it would
    swap the Mosaic kernel for interpreted HLO behind the caller's
    back, so there it raises."""
    if _on_tpu():
        if interpret:
            raise ValueError(
                "interpret=True on a TPU backend: interpreter mode is "
                "CPU test plumbing; on a TPU the Mosaic kernel runs")
        return True
    return interpret


# ---------------------------------------------------------------------------
# fused scale (+ cast)
# ---------------------------------------------------------------------------

def _scale_kernel(x_ref, o_ref, *, factor):
    o_ref[:] = (x_ref[:].astype(jnp.float32) * factor).astype(o_ref.dtype)


def fused_scale(x: jax.Array, factor: float,
                out_dtype: Optional[jnp.dtype] = None,
                interpret: bool = False) -> jax.Array:
    """``x * factor`` cast to ``out_dtype`` in one fused pass (reference
    ``ScaleBufferCudaImpl``, ``cuda_kernels.cu:77``; fp16 half2
    vectorization there ≙ VPU lanes here)."""
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    if not _use_kernel(interpret):
        return (x.astype(jnp.float32) * factor).astype(out_dtype)
    flat = x.reshape(-1)
    # pad to a (8, 128) fp32 tile multiple
    tile = 8 * 128
    n = flat.size
    pad = (-n) % tile
    if pad:
        flat = jnp.pad(flat, (0, pad))
    arr = flat.reshape(-1, 128)
    out = pl.pallas_call(
        functools.partial(_scale_kernel, factor=factor),
        out_shape=jax.ShapeDtypeStruct(arr.shape, out_dtype),
        interpret=interpret,
    )(arr)
    return out.reshape(-1)[:n].reshape(x.shape)


# ---------------------------------------------------------------------------
# flash attention (forward + blockwise backward kernels)
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, block_k: int,
                      causal: bool, scale: float, positions: bool = False):
    # blocks: q (1, BQ, D); k/v (1, T, D); o (1, BQ, D).  With
    # ``positions`` two extra int32 inputs ride along in the lse layout
    # (qpos (1, 8, BQ), kpos (1, 8, T)): GLOBAL sequence positions, so
    # the causal mask stays correct when this kernel consumes a ring
    # shard whose rows are not local-index-contiguous (the sp ring's
    # zigzag layout, :func:`ring_flash_attention`).
    # inputs stay in their native dtype (bf16): the MXU runs bf16 x bf16
    # at full rate with fp32 accumulation via preferred_element_type —
    # casting to fp32 first would forfeit the systolic-array rate
    if positions:
        qpos_ref, kpos_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    q = q_ref[0]                                      # (BQ, D)
    block_q = q.shape[0]
    d = v_ref.shape[-1]           # the output is as wide as v, not as q
    t = k_ref.shape[1]
    qi = pl.program_id(1)
    if positions:
        q_pos = qpos_ref[0, 0][:, None]               # (BQ, 1) global
    else:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)

    def body(kb, carry):
        o, m, l = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
        if causal:
            if positions:
                k_pos = kpos_ref[0, 0, pl.ds(kb * block_k,
                                             block_k)][None, :]
            else:
                k_pos = kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
            mask = q_pos >= k_pos
            s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        # p in the value dtype for the MXU; the o accumulator stays fp32
        o_new = o * corr[:, None] + jnp.dot(
            p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    num_k = t // block_k
    if causal and not positions:
        # skip blocks strictly above the diagonal (their mask is
        # all-false); ceil-divide — flooring would drop the partially
        # live diagonal block whenever block_q is not a block_k multiple.
        # With explicit positions the layout is arbitrary (zigzag), so
        # no diagonal exists to skip — every block runs, masked per row.
        num_k_live = ((qi + 1) * block_q + block_k - 1) // block_k
        num_k = jnp.minimum(num_k, jnp.maximum(num_k_live, 1))
    o0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, num_k, body, (o0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l_safe[:, None]).astype(o_ref.dtype)
    # per-row logsumexp: what the backward needs to rebuild p = exp(s-lse)
    # without re-running the online-softmax recurrence.  Stored with an
    # 8-sublane replication axis — Mosaic requires the last two block
    # dims be (8k, 128k) or full-size (jax's own flash kernel pads its
    # l/m residuals the same way, with 128 lanes)
    lse_ref[0] = jnp.broadcast_to((m + jnp.log(l_safe))[None, :],
                                  lse_ref.shape[1:])


def _bh_layout(*xs):
    """``(b, t, h, d)`` operands as ``(b * h, t, d)``, each at its own
    width ``d``."""
    b, t, h, _ = xs[0].shape
    return tuple(x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])
                 for x in xs)


def _pos_layout(pos):
    """A (t,) position vector in the lse residual layout (1, 8, t):
    int32 replicated over the 8-sublane axis (Mosaic tiling contract —
    same stance as the lse/delta blocks)."""
    t = pos.shape[0]
    return jnp.broadcast_to(pos.astype(jnp.int32)[None, None, :],
                            (1, 8, t))


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               qpos=None, kpos=None):
    b, t, h, d = q.shape
    dv = v.shape[-1]        # q and k are d wide, v and the output dv
    qb, kb, vb = _bh_layout(q, k, v)
    grid = (b * h, t // block_q)
    positions = qpos is not None
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, t, d), lambda bh, qi: (bh, 0, 0)),
        pl.BlockSpec((1, t, dv), lambda bh, qi: (bh, 0, 0)),
    ]
    args = [qb, kb, vb]
    if positions:
        in_specs += [
            pl.BlockSpec((1, 8, block_q), lambda bh, qi: (0, 0, qi)),
            pl.BlockSpec((1, 8, t), lambda bh, qi: (0, 0, 0)),
        ]
        args += [_pos_layout(qpos), _pos_layout(kpos)]
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, block_k=block_k,
                          causal=causal, scale=scale, positions=positions),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, 8, block_q), lambda bh, qi: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 8, t), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    return out.reshape(b, h, t, dv).transpose(0, 2, 1, 3), lse


_NT = (((1,), (1,)), ((), ()))      # a . b^T
_TN = (((0,), (0,)), ((), ()))      # a^T . b

# The one backward call keeps a whole row of Q, dO and dQ in VMEM (the
# reckoning is ``_flash_bwd_vmem_bytes``), so the row it can serve is
# bounded by the chip's VMEM and not by the compiler's default scope of
# 16 MiB: it asks for what its shapes need, up to this much of a v5e's
# 128 MiB.  At bf16 and a head width of 128 seq 32,768 fits (77 MiB)
# and 36,864 does not; a longer row raises at trace time (the sp ring
# shards the sequence first).  XLA keeps activations in the same VMEM
# between operations, so the call asks for no more than it reckons.
_FLASH_BWD_VMEM_CAP = 80 << 20


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *rest, block_q: int, causal: bool, scale: float,
                      positions: bool = False):
    """dQ, dK and dV of one K block against its row's Q blocks
    (FlashAttention-2 backward: dS = P ∘ (dP − delta) with delta =
    rowsum(dO ∘ O)).  Grid ``(rows, K blocks)``, the K-block axis
    sequential: Q and dO stay whole in VMEM and are streamed by block
    inside; for each live pair the scores, the mask and ``p`` are
    rebuilt once from the saved logsumexp and ``dp`` formed once, then
    ``dv += pᵀ·dO``, ``dk += dsᵀ·Q`` and ``dq[Q block] += ds·K`` — five
    products.  The scores are held transposed, (BK, BQ): ``lse`` and
    ``delta`` lie on the lanes as they are stored, and only ``dq``'s
    product contracts over the sublanes.  ``dk`` / ``dv`` add up in
    fp32 across the Q blocks; ``dq`` in the fp32 scratch ``dq_acc``
    (T, D) across the K blocks, zeroed at the row's first and cast out
    at its last; ``scale`` multiplies each fp32 sum once, as it is cast.
    A gradient whose width fills no whole 128-lane tiles
    (:func:`flash_bwd_transposed`) adds up transposed, the width on the
    sublanes, so that its product streams the width through the MXU
    and latches no half-empty tile: ``dkᵀ += Qᵀ·dsᵀ`` and ``dqᵀ[Q
    block] += Kᵀ·ds`` (``dq_acc`` then ``(T / BQ, D, BQ)``), and at
    such a v width ``dvᵀ += dOᵀ·pᵀ``.  ``Qᵀ`` and ``dOᵀ`` are made once
    a row into VMEM scratch at its first K block, a Q block to a
    leading index, ``Kᵀ`` once a grid step, and the sums turned back
    as they are written.
    Causal: Q blocks above the K block's diagonal are not
    visited — except under ``positions`` (global, possibly
    non-contiguous row positions: the sp ring), where no diagonal
    exists and every block runs with its per-row mask."""
    if positions:
        qpos_ref, kpos_ref, dq_ref, dk_ref, dv_ref, dq_acc, *held = rest
    else:
        dq_ref, dk_ref, dv_ref, dq_acc, *held = rest
    f32 = jnp.float32
    k = k_ref[0]                              # (BK, D) native dtype
    v = v_ref[0]                              # (BK, Dv)
    block_k, d = k.shape
    qk_t = flash_bwd_transposed(d)
    v_t = flash_bwd_transposed(v.shape[1])
    qt_ref = held.pop(0) if qk_t else None    # (T / BQ, D, BQ)
    dot_ref = held.pop(0) if v_t else None    # (T / BQ, Dv, BQ)
    t = q_ref.shape[1]
    num_q = t // block_q
    ki = pl.program_id(1)

    def q_rows(qb):
        return pl.ds(pl.multiple_of(qb * block_q, block_q), block_q)

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if qk_t or v_t:
            def hold(qb, c):
                if qk_t:
                    qt_ref[qb] = q_ref[0, q_rows(qb), :].T
                if v_t:
                    dot_ref[qb] = do_ref[0, q_rows(qb), :].T
                return c
            jax.lax.fori_loop(0, num_q, hold, 0)

    if positions:
        k_pos = kpos_ref[0, 0][:, None]       # (BK, 1) global
    else:
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0)
    kt = k.T if qk_t else None                # (D, BK)

    def body(qb, carry):
        dk, dv = carry
        rows = q_rows(qb)
        q_blk = q_ref[0, rows, :]             # (BQ, D)
        do_blk = do_ref[0, rows, :]
        lse_blk = lse_ref[0, 0:1, rows]       # (1, BQ)
        delta_blk = delta_ref[0, 0:1, rows]
        st = jax.lax.dot_general(k, q_blk, _NT,
                                 preferred_element_type=f32) * scale
        if causal:
            if positions:
                q_pos = qpos_ref[0, 0:1, rows]
            else:
                q_pos = qb * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
            mask = q_pos >= k_pos
            st = jnp.where(mask, st, _NEG_INF)
        pt = jnp.exp(st - lse_blk)
        if causal:
            pt = jnp.where(mask, pt, 0.0)
        if v_t:
            dv = dv + jax.lax.dot_general(
                dot_ref[qb], pt.astype(do_blk.dtype), _NT,
                preferred_element_type=f32)
        else:
            dv = dv + jnp.dot(pt.astype(do_blk.dtype), do_blk,
                              preferred_element_type=f32)
        dpt = jax.lax.dot_general(v, do_blk, _NT,
                                  preferred_element_type=f32)
        dst = (pt * (dpt - delta_blk)).astype(q_blk.dtype)
        if qk_t:
            dk = dk + jax.lax.dot_general(qt_ref[qb], dst, _NT,
                                          preferred_element_type=f32)
            dq_acc[qb] += jnp.dot(kt, dst, preferred_element_type=f32)
        else:
            dk = dk + jnp.dot(dst, q_blk, preferred_element_type=f32)
            dq_acc[rows, :] += jax.lax.dot_general(
                dst, k, _TN, preferred_element_type=f32)
        return dk, dv

    start = 0
    if causal and not positions:
        # first Q block that reaches this K block's diagonal
        start = (ki * block_k) // block_q
    dk_shape = k.shape[::-1] if qk_t else k.shape
    dv_shape = v.shape[::-1] if v_t else v.shape
    zeros = jnp.zeros(dk_shape, f32)
    # dv is as wide as v; at equal widths the one constant, as before
    zeros_v = zeros if dv_shape == dk_shape else jnp.zeros(dv_shape, f32)
    dk, dv = jax.lax.fori_loop(start, num_q, body, (zeros, zeros_v))
    dk_ref[0] = ((dk.T if qk_t else dk) * scale).astype(dk_ref.dtype)
    dv_ref[0] = (dv.T if v_t else dv).astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _():
        if qk_t:
            def cast_out(qb, c):
                dq_ref[0, q_rows(qb), :] = (
                    dq_acc[qb].T * scale).astype(dq_ref.dtype)
                return c
            jax.lax.fori_loop(0, num_q, cast_out, 0)
        else:
            dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def flash_lanes(d: int) -> int:
    """The lanes a head width takes in the flash kernels: the kernels are
    given q, k and v as wide as they are, and Mosaic holds a minor
    dimension in whole 128-lane tiles, so a 192-wide q / k is served as
    256 lanes of VMEM, and two passes of the MXU's contraction in the
    score product ``st`` (the backward's other products at such a width
    stream it instead: :func:`flash_bwd_transposed`).  Read on the chip
    at ``[32, 4096, 192 | 128]`` (PERF.md §6) against the score
    product split into 128 + 64 (+1.5% slower) and against q and k
    zero-padded to 256 in HBM (+4%)."""
    return -(-d // 128) * 128


def flash_bwd_transposed(d: int) -> bool:
    """Whether the flash backward adds up a gradient ``d`` wide
    transposed (:func:`_flash_bwd_kernel`): where ``d`` fills no whole
    number of 128-lane tiles, a product whose output is ``d`` wide would
    latch MXU tiles with their last columns empty; transposed, ``d`` is
    the dimension streamed through them.  dq and dk follow the q / k
    width, dv the v width."""
    return d % 128 != 0


def _flash_bwd_vmem_bytes(t: int, d: int, block_q: int, block_k: int,
                          itemsize: int, dv: Optional[int] = None) -> int:
    """VMEM the backward call holds, from its shapes: Q, dO and the dQ
    output as whole rows, double-buffered by the pipeline; dQ's fp32
    accumulator; lse, delta and the two position rows in their
    8-sublane layout; the K, V, dK, dV blocks; and room for three
    (BK, BQ) fp32 temporaries of one block pair with the fp32 dK / dV
    sums.  ``d`` is the width of q, k and their gradients, ``dv`` that
    of v, dO and dV (``d`` where not given).  What is held as given is
    counted in the whole 128-lane tiles VMEM holds it in (192 takes the
    room of 256); what is held transposed (:func:`flash_bwd_transposed`:
    dQ's accumulator, the dK / dV sums, and the scratch copies Qᵀ, dOᵀ
    and Kᵀ) in 16-row sublane tiles, the width as it is.  An upper
    bound: bisecting ``vmem_limit_bytes`` off the chip, ``[32, 8192,
    128]`` bf16 compiles from 19.6 MiB where this says 23, ``[8, 16384,
    128]`` from 35.5 where this says 41."""
    dv = d if dv is None else dv
    qk_t, v_t = flash_bwd_transposed(d), flash_bwd_transposed(dv)
    d_l, dv_l = flash_lanes(d), flash_lanes(dv)
    # a width as the sums take it: on the sublanes where transposed
    d_s = -(-d // 16) * 16 if qk_t else d_l
    dv_s = -(-dv // 16) * 16 if v_t else dv_l
    rows = (4 * d_l + 2 * dv_l) * t * itemsize + t * d_s * 4 \
        + 4 * 2 * 8 * t * 4
    if qk_t:        # Qᵀ, and Kᵀ of one block
        rows += (t + block_k) * d_s * itemsize
    if v_t:         # dOᵀ
        rows += t * dv_s * itemsize
    blocks = 2 * 2 * block_k * (d_l + dv_l) * itemsize
    pair = 3 * block_q * block_k * 4 \
        + 2 * max(block_q, block_k) * (d_s + dv_s) * 4
    return rows + blocks + pair


def _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_k,
               interpret, qpos=None, kpos=None, delta=None):
    """FlashAttention-2 blockwise backward, one kernel
    (:func:`_flash_bwd_kernel`).  ``lse``/``delta`` may be GLOBAL
    quantities (the sp ring: softmax over the whole ring's keys) — the
    FA2 decomposition is exact per K/V block given the global
    logsumexp, which is what lets :func:`ring_flash_attention` reuse
    the kernel per visiting block.  ``delta`` defaults to
    rowsum(dO ∘ O) of the given out/g; pass a precomputed ``(b·h, t)``
    row-sum to avoid recomputing it once per ring step."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    dv = v.shape[-1]        # q, k, dq, dk are d wide; v, dO, dv are dv
    need = _flash_bwd_vmem_bytes(t, d, block_q, block_k, q.dtype.itemsize,
                                 dv)
    if need > _FLASH_BWD_VMEM_CAP:
        raise ValueError(
            f"flash attention backward: q{tuple(q.shape)} {q.dtype} keeps "
            f"a whole row of Q, dO and dQ in VMEM, {need >> 20} MiB at "
            f"seq {t}; the kernel serves up to "
            f"{_FLASH_BWD_VMEM_CAP >> 20} MiB — shard the sequence "
            f"(attention_impl='ring')")
    qb, kb, vb = _bh_layout(q, k, v)
    do = g.transpose(0, 2, 1, 3).reshape(b * h, t, dv)
    positions = qpos is not None
    if delta is None:
        ob = out.transpose(0, 2, 1, 3).reshape(b * h, t, dv)
        # delta = rowsum(dO ∘ O): tiny elementwise pass, XLA fuses it
        delta = (do.astype(jnp.float32) *
                 ob.astype(jnp.float32)).sum(-1)
    # replicated to the same 8-sublane layout as lse (tiling contract)
    delta = jnp.broadcast_to(delta[:, None, :], (b * h, 8, t))

    def row(bh, ki):
        return (bh, 0, 0)

    def blk(bh, ki):
        return (bh, ki, 0)

    in_specs = [
        pl.BlockSpec((1, t, d), row),
        pl.BlockSpec((1, block_k, d), blk),
        pl.BlockSpec((1, block_k, dv), blk),
        pl.BlockSpec((1, t, dv), row),
        pl.BlockSpec((1, 8, t), row),
        pl.BlockSpec((1, 8, t), row),
    ]
    args = [qb, kb, vb, do, lse, delta]
    if positions:
        in_specs += [
            pl.BlockSpec((1, 8, t), lambda bh, ki: (0, 0, 0)),
            pl.BlockSpec((1, 8, block_k), lambda bh, ki: (0, 0, ki)),
        ]
        args += [_pos_layout(qpos), _pos_layout(kpos)]
    # dQ's fp32 sum, then Qᵀ and dOᵀ a Q block to a leading index where
    # their gradients add up transposed
    num_q, qk_t = t // block_q, flash_bwd_transposed(d)
    scratch = [pltpu.VMEM((num_q, d, block_q) if qk_t else (t, d),
                          jnp.float32)]
    if qk_t:
        scratch.append(pltpu.VMEM((num_q, d, block_q), q.dtype))
    if flash_bwd_transposed(dv):
        scratch.append(pltpu.VMEM((num_q, dv, block_q), do.dtype))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, block_q=block_q,
                          causal=causal, scale=scale, positions=positions),
        grid=(b * h, t // block_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, t, d), row),
            pl.BlockSpec((1, block_k, d), blk),
            pl.BlockSpec((1, block_k, dv), blk),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, t, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, t, dv), v.dtype),
        ],
        scratch_shapes=scratch,
        # never under the compiler's default scope of 16 MiB
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(need, 16 << 20)),
        interpret=interpret,
        name="flash_bwd",
    )(*args)

    def from_bh(x):
        return x.reshape(b, h, t, x.shape[-1]).transpose(0, 2, 1, 3)

    return from_bh(dq), from_bh(dk), from_bh(dv)


def fit_flash_block(t: int, requested: int) -> Optional[int]:
    """Largest flash block ≤ ``requested`` that divides ``t`` — a seq
    len that is a multiple of 128 but not of the (large) default must
    shrink the block, not fall back to the dense O(T²) path.  Sequences
    shorter than one tile run as a single block (small-shape tests and
    probes); other non-128-multiples return ``None`` (the caller's
    dense/jnp fallback) — sub-tile blocks on real bf16 inputs are
    Mosaic-lowering risk.  Shared by :func:`flash_attention` and the
    :func:`ring_flash_attention` dispatch in
    :mod:`~horovod_tpu.parallel.ring_attention`."""
    if t <= 128:
        b = min(requested, t)
        if t % b == 0:
            return b
        # ragged small seq: a single whole-sequence block if it
        # tiles, else the dense fallback
        return t if t % 8 == 0 else None
    for cand in (requested, 512, 256, 128):
        if cand <= t and t % cand == 0:
            return cand
    return None


def flash_forward_saveable(prim, *_, **params) -> bool:
    """A ``jax.checkpoint`` policy that saves the results of the flash
    forward call (``flash_fwd``): the output and the per-row logsumexp,
    what the backward reads besides q, k and v.  A policy on the call
    itself, and no ``checkpoint_name`` on its results: two names of two
    shapes would each lower to an out-of-line function of their own and
    renumber every later one of the module, in steps that keep nothing
    too (a ``TransformerLM``'s)."""
    return prim.name == "pallas_call" and params.get("name") == "flash_fwd"


def flash_runs_kernel(t: int, block: int, interpret: bool) -> bool:
    """Whether :func:`flash_attention` over a ``t``-long sequence at
    ``block`` runs its kernels (and not the dense jnp fall-back)."""
    return _use_kernel(interpret) and fit_flash_block(t, block) is not None


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """Blocked attention over ``(batch, seq, heads, head_dim)`` inputs.
    ``q`` and ``k`` share a width, ``v`` may have another (latent
    attention: 192 and 128): the output and ``dv`` are as wide as ``v``,
    ``dq`` and ``dk`` as ``q``; ``scale`` defaults to the q / k width's
    inverse root.

    Runs the dense jnp formulation off-TPU (unless ``interpret``) and —
    with a warning naming the shape — when ``seq`` fits no block
    (:func:`fit_flash_block`).  Differentiable end-to-end in Pallas, a
    forward call (``flash_fwd``) and one backward call (``flash_bwd``):
    the forward saves per-row logsumexp; the backward
    (:func:`_flash_bwd_kernel`) takes a K block at a time against its
    row's Q / dO blocks, rebuilds the scores and ``p`` once a block
    pair and forms dQ, dK and dV from them in five products, dQ summed
    in fp32 in VMEM over the row's K blocks — the (T, T) score matrix
    never exists in HBM in either direction.  The backward keeps a row
    of Q, dO and dQ in VMEM and asks for the VMEM its shapes need; a
    row beyond ``_FLASH_BWD_VMEM_CAP`` raises when it is traced.  A
    rematerialised block whose policy takes in
    :func:`flash_forward_saveable` keeps the forward call's two results,
    so that its backward runs no second ``flash_fwd`` (``models/hybrid``'s
    attention blocks).  A span open while this is traced
    (``train_step.lower``) is told ``flash_bwd_calls_per_layer``,
    ``flash_bwd_products_per_pair`` and ``flash_bwd_transposed_grads``
    (how many of dq, dk, dv the backward adds up transposed:
    :func:`flash_bwd_transposed`).
    """
    from horovod_tpu import telemetry
    from horovod_tpu.parallel.ring_attention import reference_attention

    b, t, h, d = q.shape
    scale = d ** -0.5 if scale is None else scale

    block_q = fit_flash_block(t, block_q)
    block_k = fit_flash_block(t, block_k)
    if not _use_kernel(interpret):
        return reference_attention(q, k, v, causal=causal, scale=scale)
    if block_q is None or block_k is None:
        if q.shape not in _warned_unblocked:
            # trace time, once a shape: silent O(T^2) is what the
            # kernel exists to avoid
            _warned_unblocked.add(q.shape)
            hvd_logging.warning(
                "flash_attention: seq %d of q%s fits no flash block "
                "(a multiple of 128, or <= 128 and of 8); running the "
                "dense O(T^2) jnp attention instead of the kernel",
                t, tuple(q.shape))
        return reference_attention(q, k, v, causal=causal, scale=scale)
    telemetry.annotate(
        flash_bwd_calls_per_layer=1, flash_bwd_products_per_pair=5,
        flash_bwd_transposed_grads=2 * flash_bwd_transposed(d)
        + flash_bwd_transposed(v.shape[-1]))

    @jax.custom_vjp
    def _attn(q, k, v):
        out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                            interpret)
        return out

    def _fwd(q, k, v):
        out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                              interpret)
        return out, (q, k, v, out, lse)

    def _bwd(res, g):
        q, k, v, out, lse = res
        return _flash_bwd(q, k, v, out, lse, g, causal, scale,
                          block_q, block_k, interpret)

    _attn.defvjp(_fwd, _bwd)
    return _attn(q, k, v)


# ---------------------------------------------------------------------------
# fused bottleneck-segment backward (conv3x3 + inference-BN + relu)
# ---------------------------------------------------------------------------
#
# ResNet's measured gap (PERF_NOTES.md): the XLA backward spends ~35% of
# the step in VPU-bound BN dgamma/dbeta convert+reduce fusions that
# re-stream the gradient/activation tensors from HBM after the conv
# backward already read them.  This kernel computes the WHOLE backward
# of the block segment  b = relu(bn(conv3x3(a)))  (inference-mode BN —
# frozen running stats, the synthetic-bench training configuration) in
# one pass:
#
#   dz      = db * (b > 0)                  (relu)
#   dbeta  += sum(dz);  dgamma += sum(dz * yhat)      (BN param grads)
#   dy      = dz * gamma/sigma                        (BN input grad)
#   dW[tap] += a_shifted^T @ dy             (9 tap matmuls, MXU)
#   da      = sum_tap dy_shifted @ W[tap]^T (9 tap matmuls, MXU)
#
# so db/b/a cross HBM exactly once and the channel reductions ride the
# VMEM tiles the matmuls already hold.  The reference has no analogue —
# cuDNN owns its conv backward — this is the "fuse across the block
# boundary" lever the round-4 review left on the table.

def _cbr_bwd_kernel(db_ref, b_ref, ap_ref, w_ref, beta_ref, gamma_ref,
                    seff_ref, da_ref, dw_ref, dgamma_ref, dbeta_ref,
                    dypad_ref, *, hh: int, ww: int):
    """Grid is (batch_tiles,) with the 9-tap loop unrolled in the body.

    Accumulator layout constraint: Pallas TPU output windows are only
    defined across CONSECUTIVE same-index grid steps, so every
    accumulated output (dW, dgamma, dbeta) must keep a constant block
    index over the whole grid — a tap-in-the-grid variant (dW blocked
    per tap, revisited once per tile) silently accumulates into stale
    buffers on hardware.  The price of the unrolled body is Mosaic
    stack pressure (~48 B/tile element live), paid for with a smaller
    batch tile (see the caller's budget)."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        dgamma_ref[...] = jnp.zeros_like(dgamma_ref)
        dbeta_ref[...] = jnp.zeros_like(dbeta_ref)

    db = db_ref[...].astype(jnp.float32)          # (nb, H, W, C)
    b = b_ref[...].astype(jnp.float32)
    beta = beta_ref[0]                            # (C,)
    gamma = gamma_ref[0]
    seff = seff_ref[0]

    dz = jnp.where(b > 0, db, 0.0)
    dbeta_ref[...] += jnp.broadcast_to(
        dz.sum((0, 1, 2))[None, :], dbeta_ref.shape)
    # yhat = (z - beta)/gamma; on active lanes z == b, on inactive ones
    # dz == 0 annihilates the (wrong) yhat — no mask needed.  gamma==0
    # destroys the information needed to recover yhat from the relu
    # output at all (z is constant beta), so the safe divide pins that
    # channel's dgamma to 0 instead of NaN (docstring caveat in
    # fused_conv_bn_relu).
    gamma_safe = jnp.where(jnp.abs(gamma) < 1e-12, 1.0, gamma)
    dgamma_ref[...] += jnp.broadcast_to(
        (dz * ((b - beta) / gamma_safe)).sum((0, 1, 2))[None, :],
        dgamma_ref.shape)

    dy = (dz * seff).astype(db_ref.dtype)         # conv-output grad
    nb, h, w, c = dy.shape
    rows = nb * h * w
    dy2 = dy.reshape(rows, c)

    # dW[tap] += a_pad[:, kh:kh+H, kw:kw+W]^T @ dy   (contract rows)
    for kh in range(3):
        for kw in range(3):
            a_tap = ap_ref[:, kh:kh + hh, kw:kw + ww, :] \
                .reshape(rows, ap_ref.shape[-1])
            dw_ref[3 * kh + kw] += jax.lax.dot_general(
                a_tap, dy2, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    # da = sum_tap dy_pad[:, 2-kh : 2-kh+H, 2-kw : 2-kw+W] @ W[tap]^T
    dypad_ref[...] = jnp.zeros_like(dypad_ref)
    dypad_ref[:, 1:hh + 1, 1:ww + 1, :] = dy
    acc = None
    for kh in range(3):
        for kw in range(3):
            d_tap = dypad_ref[:, 2 - kh:2 - kh + hh,
                              2 - kw:2 - kw + ww, :].reshape(rows, c)
            part = jax.lax.dot_general(
                d_tap, w_ref[3 * kh + kw], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part
    da_ref[...] = acc.reshape(da_ref.shape).astype(da_ref.dtype)


def _cbr_bwd_reference(db, b, a, w, gamma, beta, scale_eff):
    """jnp oracle of the fused backward (also the off-TPU fallback):
    relu/BN grads by hand, conv grads through jax.vjp of the forward
    conv — exactly what XLA autodiff produces, unfused."""
    f32 = jnp.float32
    dz = jnp.where(b > 0, db.astype(f32), 0.0)
    dbeta = dz.sum((0, 1, 2))
    gamma_safe = jnp.where(jnp.abs(gamma) < 1e-12, 1.0, gamma)
    dgamma = (dz * ((b.astype(f32) - beta) / gamma_safe)).sum((0, 1, 2))
    dy = (dz * scale_eff).astype(a.dtype)
    dn = jax.lax.conv_dimension_numbers(a.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))

    def conv(a_, w_):
        return jax.lax.conv_general_dilated(
            a_, w_, (1, 1), "SAME", dimension_numbers=dn)

    _, vjp = jax.vjp(conv, a, w.astype(a.dtype))
    da, dw = vjp(dy)
    return da, dw.astype(f32), dgamma, dbeta


def fused_conv_bn_relu_bwd(db, b, a, w, gamma, beta, scale_eff,
                           interpret: bool = False):
    """Backward of ``relu(bn_inference(conv3x3_same(a, w)))``.

    Returns ``(da, dw, dgamma, dbeta)``.  One fused pass on TPU (see
    the kernel above); jnp fallback elsewhere or for shapes outside the
    tiling contract (stride-1 SAME 3x3, channels a lane multiple).
    """
    n, hh, ww, cin = a.shape
    c = w.shape[-1]
    # the dW accumulator (9*Cin*C fp32) lives in VMEM for the whole
    # grid; past 256x256 channels it plus the tiles exceeds the ~16 MB
    # scoped-vmem budget (measured: 512x512 OOMs at 19.3 MB), so wide
    # segments keep the XLA path — the dominant stages (PERF_NOTES
    # profile) are the 128/256-channel ones anyway
    dw_bytes = 9 * cin * c * 4
    usable = _use_kernel(interpret) and w.shape[:2] == (3, 3) and \
        c % 128 == 0 and cin % 128 == 0 and db.shape == b.shape and \
        db.shape[:3] == (n, hh, ww) and dw_bytes <= 2_400_000
    if not usable:
        return _cbr_bwd_reference(db, b, a, w, gamma, beta, scale_eff)

    from jax.experimental.pallas import tpu as pltpu

    # batch tile: keep dW + the per-tile working set within the 16 MB
    # scoped-vmem budget.  The unrolled 9-tap body keeps ~48 B of live
    # temporaries per tile element on the Mosaic stack (measured:
    # 21.3 MB at nb=8, 14x14x256); nb must divide N
    tile_budget = max(10e6 - dw_bytes, 1e6)
    target = max(1, int(tile_budget // (hh * ww * max(c, cin) * 48)))
    nb = 1
    while nb * 2 <= min(target, n) and n % (nb * 2) == 0:
        nb *= 2
    grid = (n // nb,)

    a_pad = jnp.pad(a, ((0, 0), (1, 1), (1, 1), (0, 0)))
    w9 = w.astype(jnp.float32).reshape(9, cin, c)
    rep = (8, c)
    gamma8 = jnp.broadcast_to(gamma.astype(jnp.float32)[None, :], rep)
    beta8 = jnp.broadcast_to(beta.astype(jnp.float32)[None, :], rep)
    seff8 = jnp.broadcast_to(scale_eff.astype(jnp.float32)[None, :], rep)

    da, dw, dgamma8, dbeta8 = pl.pallas_call(
        functools.partial(_cbr_bwd_kernel, hh=hh, ww=ww),
        grid=grid,
        in_specs=[
            pl.BlockSpec((nb, hh, ww, c), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((nb, hh, ww, c), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((nb, hh + 2, ww + 2, cin),
                         lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((9, cin, c), lambda i: (0, 0, 0)),
            pl.BlockSpec(rep, lambda i: (0, 0)),
            pl.BlockSpec(rep, lambda i: (0, 0)),
            pl.BlockSpec(rep, lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((nb, hh, ww, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((9, cin, c), lambda i: (0, 0, 0)),
            pl.BlockSpec(rep, lambda i: (0, 0)),
            pl.BlockSpec(rep, lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(a.shape, a.dtype),
            jax.ShapeDtypeStruct((9, cin, c), jnp.float32),
            jax.ShapeDtypeStruct(rep, jnp.float32),
            jax.ShapeDtypeStruct(rep, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nb, hh + 2, ww + 2, c), db.dtype),
        ],
        interpret=interpret,
    )(db, b, a_pad, w9, beta8, gamma8, seff8)
    return da, dw.reshape(w.shape), dgamma8[0], dbeta8[0]


def fused_conv_bn_relu(a, w, gamma, beta, mean, var,
                       eps: float = 1e-5, interpret: bool = False):
    """``relu(bn_inference(conv3x3_same(a, w)))`` with the one-pass
    fused backward above wired in via custom_vjp.  The forward stays
    plain XLA (its conv+affine+relu already fuse optimally); only the
    backward — where XLA re-streams tensors for the channel reductions
    — is replaced.  ``mean``/``var`` are frozen running stats and get
    zero gradients (they are buffers, not parameters).

    Caveat: dgamma is reconstructed from the relu output as
    ``sum(dz * (z - beta)/gamma)`` — only the relu output is saved, so
    a channel whose ``gamma`` reaches exactly 0 has no recoverable
    normalized activation and its dgamma is pinned to 0 (instead of
    NaN).  Autodiff of the unfused segment (which saves the conv
    output) stays exact there; don't enable the fused path if BN
    scales are expected to cross zero."""

    @jax.custom_vjp
    def _run(a, w, gamma, beta, mean, var):
        return _fwd(a, w, gamma, beta, mean, var)[0]

    def _fwd(a, w, gamma, beta, mean, var):
        dn = jax.lax.conv_dimension_numbers(a.shape, w.shape,
                                            ("NHWC", "HWIO", "NHWC"))
        y = jax.lax.conv_general_dilated(a, w.astype(a.dtype), (1, 1),
                                         "SAME", dimension_numbers=dn)
        scale_eff = (gamma / jnp.sqrt(var + eps)).astype(jnp.float32)
        z = y.astype(jnp.float32) * scale_eff + \
            (beta - mean * scale_eff)
        out = jnp.maximum(z, 0.0).astype(a.dtype)
        return out, (a, w, out, gamma, beta, scale_eff, mean, var)

    def _bwd(res, db):
        a, w, out, gamma, beta, scale_eff, mean, var = res
        da, dw, dgamma, dbeta = fused_conv_bn_relu_bwd(
            db, out, a, w, gamma.astype(jnp.float32),
            beta.astype(jnp.float32), scale_eff, interpret=interpret)
        return (da, dw.astype(w.dtype), dgamma.astype(gamma.dtype),
                dbeta.astype(beta.dtype), jnp.zeros_like(mean),
                jnp.zeros_like(var))

    _run.defvjp(_fwd, _bwd)
    return _run(a, w, gamma, beta, mean, var)


# ---------------------------------------------------------------------------
# tile-fused matmul ⊗ collective kernels
# ---------------------------------------------------------------------------
#
# Bucketed async RS/AG overlap (PR 1-2) hides the gradient exchange
# behind backward compute — except at the boundaries where no compute
# remains: the LAST bucket's exchange, and the collective every
# tensor-parallel matmul pays at the row/column boundary.  These ops
# close that tail the way "Optimizing Distributed ML Communication with
# Fused Computation-Collective Operations" (arXiv:2305.06942) does:
# decompose the matmul along the sharded dimension into one tile per
# rank and stream the tiles around a ppermute ring, so the wire
# transfer of tile k runs concurrently with the MXU compute of tile
# k+1 *inside one op* — the serial full-width collective disappears
# from the schedule (the HLO guard pins exactly this: ring
# collective-permutes, no boundary-wide reduce-scatter/all-gather).
# Each tile's dot runs the blocked Pallas matmul on TPU; off-TPU the
# tile dot is the identical jnp formulation, so the ring is still the
# compiled structure tier-1 asserts on the CPU mesh.

#: Valid values of the ``fused_collectives`` knob
#: (``HOROVOD_FUSED_COLLECTIVES``, docs/fused_kernels.md).
FUSED_COLLECTIVES_MODES = ("auto", "on", "off")


def resolve_fused_collectives(mode: str = "auto") -> bool:
    """Resolve the ``fused_collectives="auto"|"on"|"off"`` knob.

    ``"auto"`` enables the tile-fused path exactly when a TPU backend
    is present — the ring's per-hop latency is what the ICI fabric
    hides; on the CPU twin the fused path is opt-in (``"on"``) so the
    structural tests and probes can exercise it deliberately.
    """
    if mode not in FUSED_COLLECTIVES_MODES:
        raise ValueError(
            f"fused_collectives must be one of {FUSED_COLLECTIVES_MODES},"
            f" got {mode!r}")
    if mode == "on":
        return True
    if mode == "off":
        return False
    return _on_tpu()


def _count_fused_launch(kernel: str) -> None:
    """hvd_pallas_fused_launches_total{kernel}: one count per fused-path
    construction (trace time — the in-graph op then runs every step;
    docs/metrics.md notes the trace-time semantics)."""
    from horovod_tpu import telemetry

    telemetry.counter(
        "hvd_pallas_fused_launches_total",
        "tile-fused matmul-collective kernel constructions per kernel"
    ).inc(kernel=kernel)


def _fit_mm_block(dim: int, candidates) -> Optional[int]:
    for c in candidates:
        if c <= dim and dim % c == 0:
            return c
    return None


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref):
    # grid (i, j, kk): the K axis is innermost and sequential, so the
    # (bm, bn) output window stays put while its fp32 accumulator
    # collects the K blocks.  bf16 inputs ride the MXU at full rate
    # with fp32 accumulation via preferred_element_type (same stance
    # as the flash kernels)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def pallas_matmul(x: jax.Array, w: jax.Array,
                  out_dtype=None,
                  interpret: bool = False) -> jax.Array:
    """``x @ w`` as a blocked Pallas kernel (fp32 MXU accumulation).

    Tiling contract: ``x`` is ``(m, k)``, ``w`` ``(k, n)`` with
    ``m % 8 == 0`` and ``k, n % 128 == 0`` (fp32 sublane/lane tiles);
    anything else — or no TPU and not interpret mode — runs the
    identical ``jnp.dot`` formulation.  All three dims are blocked (at
    most 512 each, K sequential into an fp32 VMEM accumulator), so the
    working set is a few MB whatever ``k`` is.  Differentiable: the
    VJP is two more calls of this kernel (``g @ wᵀ`` and ``xᵀ @ g``,
    the cotangent cast to the operand dtype as the MXU's default
    precision would).  This is the per-tile compute of the fused
    collective ops below.
    """
    from jax.experimental.pallas import tpu as pltpu

    out_dtype = jnp.dtype(out_dtype or jnp.result_type(x.dtype, w.dtype))
    m, k = x.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(f"pallas_matmul shapes {x.shape} @ {w.shape}")
    bm = _fit_mm_block(m, (512, 256, 128, 64, 32, 16, 8))
    bn = _fit_mm_block(n, (512, 256, 128))
    bk = _fit_mm_block(k, (512, 256, 128))
    if not _use_kernel(interpret) or None in (bm, bn, bk):
        return jnp.dot(x, w, preferred_element_type=jnp.float32
                       ).astype(out_dtype)

    def call(x, w):
        return pl.pallas_call(
            _mm_kernel,
            grid=(m // bm, n // bn, k // bk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(x, w)

    @jax.custom_vjp
    def _mm(x, w):
        return call(x, w)

    def _fwd(x, w):
        return call(x, w), (x, w)

    def _bwd(res, g):
        x, w = res
        dx = pallas_matmul(g.astype(w.dtype), w.T, out_dtype=x.dtype,
                           interpret=interpret)
        dw = pallas_matmul(x.T, g.astype(x.dtype), out_dtype=w.dtype,
                           interpret=interpret)
        return dx, dw

    _mm.defvjp(_fwd, _bwd)
    return _mm(x, w)


def matmul_reducescatter(x: jax.Array, w: jax.Array, axis: str,
                         fused: bool = True,
                         interpret: bool = False) -> jax.Array:
    """Fused ``psum_scatter(x @ w)`` over mesh axis ``axis`` — the
    row-parallel boundary op.

    ``x`` is ``(m, k)`` with ``m`` divisible by the axis size, ``w``
    this rank's ``(k, n)`` contraction shard; returns the reduced
    ``(m/world, n)`` row block this rank owns (identical semantics to
    ``lax.psum_scatter(x @ w, axis, scatter_dimension=0, tiled=True)``,
    row blocks rank-major).

    Fused schedule: the output rows split into one tile per rank; each
    ring step computes ONE tile's partial product (Pallas matmul on
    TPU) while the accumulated partial for the previous tile crosses
    the wire via ``ppermute`` — after ``world-1`` hops every rank holds
    its fully-reduced tile without any boundary-wide collective.  The
    partials accumulate in fp32 regardless of input dtype.
    ``fused=False`` (or a size-1 axis) keeps the unfused formulation.
    """
    from jax import lax

    if x.ndim != 2 or w.ndim != 2:
        raise ValueError(
            f"matmul_reducescatter takes 2-D operands, got {x.shape} @ "
            f"{w.shape} (flatten leading dims first)")
    world = int(lax.axis_size(axis))
    m = x.shape[0]
    if m % world:
        raise ValueError(
            f"matmul_reducescatter rows {m} not divisible by axis "
            f"{axis!r} size {world}")
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if not fused or world == 1:
        y = pallas_matmul(x, w, interpret=interpret)
        if world == 1:
            return y
        return lax.psum_scatter(y, axis, scatter_dimension=0, tiled=True)
    _count_fused_launch("matmul_reducescatter")
    me = lax.axis_index(axis)
    tiles = x.reshape(world, m // world, x.shape[1])
    perm = [(i, (i + 1) % world) for i in range(world)]
    # start at tile (me-1) so that after world-1 send-right hops each
    # rank ends holding its OWN fully-reduced tile (ownership matches
    # psum_scatter's rank-major row blocks)
    idx0 = (me + world - 1) % world
    acc = pallas_matmul(jnp.take(tiles, idx0, axis=0), w,
                        out_dtype=jnp.float32, interpret=interpret)
    for s in range(1, world):
        # the ppermute and the tile matmul are data-independent: the
        # scheduler overlaps tile k's wire hop with tile k+1's compute
        acc = lax.ppermute(acc, axis, perm)
        idx = (me + world - 1 - s) % world
        acc = acc + pallas_matmul(jnp.take(tiles, idx, axis=0), w,
                                  out_dtype=jnp.float32,
                                  interpret=interpret)
    return acc.astype(out_dtype)


_warned_ungrouped: set = set()   # lhs shapes already reported


def _grouped_block(dim: int) -> int:
    """The k or n block of :func:`grouped_matmul`: 384 where it divides
    the dimension, else up to 1024 with a ragged last block (on a v5e,
    (rows, 2688, 1856) and back: 14.0 ms a forward and backward of an
    expert FFN over 24,576 rows against 16.9 at 384 throughout —
    PERF.md, PR 28)."""
    return 384 if dim % 384 == 0 else min(1024, -(-dim // 128) * 128)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   interpret: bool = False) -> jax.Array:
    """``lhs[rows of group g] @ rhs[g]`` for rows sorted by group.

    ``lhs`` (m, k), ``rhs`` (groups, k, n), ``group_sizes`` (groups,)
    int32 with ``sum <= m``; returns (m, n) in ``lhs.dtype``, added up
    in fp32; rows past the last group are not defined.  On a TPU the
    Pallas grouped matmul that ships with jax (megablox ``gmm``, with
    its ``tgmm`` for the weight gradient) at tiles of 512 rows (256 or
    128 where those divide ``m``) by :func:`_grouped_block` of k and n:
    a tile is visited once a group that has rows in it, so its work
    follows ``group_sizes``, not ``m``.  (XLA:TPU's own lowering of
    ``jax.lax.ragged_dot`` is a kernel of 128-wide tiles that reached
    3.9% of its roofline at these shapes: PERF.md, PR 28.)  Off a TPU,
    or where ``m`` fits no row block, ``jax.lax.ragged_dot``."""
    m, k = lhs.shape
    rows = next((b for b in (512, 256, 128) if m % b == 0), None)
    if not _use_kernel(interpret) or rows is None:
        if rows is None and _on_tpu() and lhs.shape not in _warned_ungrouped:
            _warned_ungrouped.add(lhs.shape)
            hvd_logging.warning(
                "grouped_matmul: %d rows of lhs%s are no multiple of 128; "
                "running jax.lax.ragged_dot instead of the kernel",
                m, tuple(lhs.shape))
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype,
               (rows, _grouped_block(k), _grouped_block(rhs.shape[2])),
               interpret=interpret)


def expert_chunk_mlp(chunk: jax.Array, w1: jax.Array, w2: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """Per-expert gelu MLP over one ``(e_local, slots, d)`` token chunk
    — the per-tile compute of :func:`expert_alltoall_ffn`.  Each
    expert's two dots run the blocked Pallas matmul on TPU
    (:func:`pallas_matmul`; off-contract shapes fall back to the
    identical jnp dot inside it), batched by a Python loop over the
    (small) local expert count so every dot is a 2-D MXU tile."""
    outs = []
    for ei in range(chunk.shape[0]):
        h = pallas_matmul(chunk[ei], w1[ei], interpret=interpret)
        outs.append(pallas_matmul(jax.nn.gelu(h), w2[ei],
                                  out_dtype=chunk.dtype,
                                  interpret=interpret))
    return jnp.stack(outs)


def expert_alltoall_ffn(dispatch: jax.Array, expert_fn,
                        axis: str, fused: bool = True,
                        interpret: bool = False) -> jax.Array:
    """Fused ``a2a ⊗ expert-matmul``: the MoE dispatch→expert→combine
    exchange over mesh axis ``axis`` with the token movement streamed
    around a ``ppermute`` ring instead of two boundary-wide
    ``all_to_all``\\ s.

    ``dispatch`` is this rank's ``(world, e_local, capacity, d)``
    routed-token buffer (dim 0 = destination expert rank, the layout
    :func:`~horovod_tpu.parallel.expert.expert_parallel_ffn` builds);
    ``expert_fn`` applies this rank's local experts to an
    ``(e_local, slots, d)`` token buffer and MUST be token-wise (each
    slot independent — true of any per-token MLP): the fused schedule
    computes it per source-rank tile, the unfused one over the whole
    ``world·capacity`` buffer, and only a slot-independent body makes
    the two identical.  Returns the combined ``(world, e_local,
    capacity, d)`` expert outputs back at the origin rank, dim 0 = the
    expert rank that computed them — exactly the unfused formulation::

        received = lax.all_to_all(dispatch, axis, 0, 0)
        outputs  = expert_fn(received … reshaped)
        combined = lax.all_to_all(outputs …)

    Fused schedule: hop ``s`` moves ONE ``(e_local, capacity, d)``
    token tile to expert rank ``me+s`` while the tile that arrived at
    hop ``s-1`` is inside its expert matmul, and each tile's outputs
    ride the inverse permute home as soon as they exist — expert
    ``k+1``'s tokens are in flight while expert ``k``'s matmul
    computes, and the boundary-wide all-to-all disappears from the
    schedule (the HLO guard pins ``2·(world−1)`` collective-permutes,
    zero all-to-all).  Differentiable end-to-end: every op is a lax
    primitive with a transpose (the grads run the ring backwards).
    ``fused=False`` keeps the unfused all_to_all formulation — the
    numerics oracle and the off-contract fallback.
    """
    from jax import lax

    if dispatch.ndim != 4:
        raise ValueError(
            f"expert_alltoall_ffn takes a (world, e_local, capacity, d) "
            f"dispatch buffer, got shape {dispatch.shape}")
    world = int(lax.axis_size(axis))
    if dispatch.shape[0] != world:
        raise ValueError(
            f"dispatch dim 0 is {dispatch.shape[0]} but axis {axis!r} "
            f"has size {world}")
    _, e_local, capacity, d = dispatch.shape
    if not fused or world == 1:
        if world == 1:
            return expert_fn(dispatch[0])[None]
        received = lax.all_to_all(dispatch, axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        buffers = received.transpose(1, 0, 2, 3).reshape(
            e_local, world * capacity, d)
        outputs = expert_fn(buffers)
        outputs = outputs.reshape(e_local, world, capacity, d) \
            .transpose(1, 0, 2, 3)
        return lax.all_to_all(outputs, axis, split_axis=0,
                              concat_axis=0, tiled=False)
    _count_fused_launch("a2a_matmul")
    me = lax.axis_index(axis)
    # tile for my own experts never touches the wire: compute first so
    # its matmul overlaps hop 1's transfer
    chunks = [expert_fn(jnp.take(dispatch, me, axis=0))]
    for s in range(1, world):
        fwd = [(i, (i + s) % world) for i in range(world)]
        bwd = [(i, (i - s) % world) for i in range(world)]
        # hop s: send the tile destined for rank me+s; what arrives is
        # rank me-s's tile for MY experts.  The sends are mutually
        # data-independent, so tile s+1's wire overlaps tile s's dot.
        got = lax.ppermute(
            jnp.take(dispatch, (me + s) % world, axis=0), axis, fwd)
        # the outputs ride the inverse permute home immediately —
        # rank p receives its own tokens' results from rank p+s
        chunks.append(lax.ppermute(expert_fn(got), axis, bwd))
    # chunks[s] holds my tokens' outputs from expert rank (me+s):
    # rotate shift-major -> rank-major so dim 0 matches the unfused
    # all_to_all's source-rank ordering
    return jnp.roll(jnp.stack(chunks), me, axis=0)


def allgather_matmul(x: jax.Array, w: jax.Array, axis: str,
                     fused: bool = True,
                     interpret: bool = False) -> jax.Array:
    """Fused ``all_gather(x) @ w`` over mesh axis ``axis`` — the
    column-parallel boundary op.

    ``x`` is this rank's ``(m_local, k)`` row shard (rank-major),
    ``w`` the ``(k, n)`` kernel (typically a column shard); returns the
    full ``(world·m_local, n)`` product, identical to
    ``jnp.dot(lax.all_gather(x, axis, tiled=True), w)``.

    Fused schedule: each ring step multiplies the row shard currently
    held (Pallas matmul on TPU) while the next shard arrives via
    ``ppermute`` — the gather never materializes as a boundary-wide
    all-gather and the wire hides under the MXU.
    """
    from jax import lax

    if x.ndim != 2 or w.ndim != 2:
        raise ValueError(
            f"allgather_matmul takes 2-D operands, got {x.shape} @ "
            f"{w.shape} (flatten leading dims first)")
    world = int(lax.axis_size(axis))
    if not fused or world == 1:
        y = lax.all_gather(x, axis, tiled=True) if world > 1 else x
        return pallas_matmul(y, w, interpret=interpret)
    _count_fused_launch("allgather_matmul")
    me = lax.axis_index(axis)
    m_local = x.shape[0]
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    out = jnp.zeros((world * m_local, w.shape[1]), out_dtype)
    cur = x
    # send left = receive from the right neighbor: after s hops this
    # rank holds shard (me + s) % world
    perm = [(i, (i - 1) % world) for i in range(world)]
    for s in range(world):
        src = (me + s) % world
        part = pallas_matmul(cur, w, out_dtype=out_dtype,
                             interpret=interpret)
        out = lax.dynamic_update_slice(out, part, (src * m_local, 0))
        if s < world - 1:
            cur = lax.ppermute(cur, axis, perm)
    return out


# ---------------------------------------------------------------------------
# ring-flash attention: the sp ring fused with the flash kernels
# ---------------------------------------------------------------------------
#
# The naive jnp ring (parallel/ring_attention.py) materializes a full
# (b, h, tq, tk) fp32 score tensor per visiting block and leaves each
# ppermute serial between steps.  Here every visiting K/V block runs
# the Pallas flash kernels instead — the online-softmax partials merge
# across ring steps in log-space, so no per-block score tensor exists
# and nothing upcasts to fp32 beyond the flash accumulator — while the
# NEXT block's ppermute is issued before the current block's kernel
# (data-independent sends, the same double-buffering contract as
# expert_alltoall_ffn's dispatch ring).  docs/fused_kernels.md
# "Ring-flash attention".

#: Sequence layouts the sp ring understands (``HOROVOD_SP_LAYOUT``).
RING_LAYOUTS = ("contiguous", "zigzag")


def ring_layout_positions(rank, world: int, seq_local: int,
                          layout: str) -> jax.Array:
    """Global sequence positions shard ``rank`` holds under ``layout``.

    ``contiguous``: shard r is global chunk r of ``world`` chunks.
    ``zigzag``: shard r holds chunks ``(r, 2·world−1−r)`` of ``2·world``
    equal chunks — pairing an early (causally busy) chunk with a late
    one so the causal mask load-balances across ranks, and no causal
    ring step is ever fully masked: the low chunk of any rank precedes
    the high chunk of every rank, so every (q shard, k/v shard) pair
    has at least one allowed position.  ``rank`` may be a traced
    ``lax.axis_index``.
    """
    if layout not in RING_LAYOUTS:
        raise ValueError(
            f"sp layout must be one of {RING_LAYOUTS}, got {layout!r}")
    if layout == "contiguous":
        return rank * seq_local + jnp.arange(seq_local, dtype=jnp.int32)
    if seq_local % 2:
        raise ValueError(
            f"zigzag layout needs an even per-shard seq, got {seq_local}")
    half = seq_local // 2
    ar = jnp.arange(half, dtype=jnp.int32)
    return jnp.concatenate(
        [rank * half + ar, (2 * world - 1 - rank) * half + ar])


def zigzag_sequence_indices(world: int, seq_global: int) -> jax.Array:
    """Permutation σ with ``x_zigzag = x[σ]`` along the sequence dim.

    Contiguous (rank-major) sharding of the permuted sequence hands
    shard r exactly its zigzag chunks ``(r, 2·world−1−r)`` — the
    host-side pre-pass that makes the zigzag layout a pure relabeling
    (undo on outputs with ``jnp.argsort`` of the same indices)."""
    if seq_global % (2 * world):
        raise ValueError(
            f"zigzag needs seq divisible by 2·world={2 * world}, "
            f"got {seq_global}")
    half = seq_global // (2 * world)
    idx = []
    for r in range(world):
        idx.extend(range(r * half, (r + 1) * half))
        idx.extend(range((2 * world - 1 - r) * half,
                         (2 * world - r) * half))
    return jnp.asarray(idx, dtype=jnp.int32)


def ring_step_schedule(world: int, causal: bool = False,
                       layout: str = "contiguous") -> dict:
    """Static kernel-launch schedule of the sp ring — pure Python.

    A causal (rank, step) pair whose visiting K/V block lies entirely
    in the query shard's future launches no kernel (the runtime skip in
    :func:`ring_flash_attention`).  Chunk-level comparison is exact:
    the whole step is masked iff ``max(q chunk) < min(k/v chunk)``.
    Under ``contiguous`` that skips ``world·(world−1)/2`` of the
    ``world²`` launches — all stacked on the low ranks; ``zigzag``
    skips none because no pair is ever fully masked, and the *partial*
    mask work balances across ranks instead.  The cost model and the
    zigzag acceptance pin both read this."""
    if layout not in RING_LAYOUTS:
        raise ValueError(
            f"sp layout must be one of {RING_LAYOUTS}, got {layout!r}")

    def chunks(r):
        return (r,) if layout == "contiguous" else (r, 2 * world - 1 - r)

    skipped = []
    for r in range(world):
        n = 0
        if causal:
            qmax = max(chunks(r))
            for s in range(world):
                kmin = min(chunks((r - s) % world))
                if qmax < kmin:
                    n += 1
        skipped.append(n)
    total = sum(skipped)
    return {
        "world": world, "causal": causal, "layout": layout,
        "steps_per_rank": world,
        "launches": world * world - total,
        "skipped": total,
        "skipped_by_rank": tuple(skipped),
    }


def ring_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         axis_name: str, causal: bool = False,
                         scale: Optional[float] = None,
                         layout: str = "contiguous",
                         block_q: int = 512, block_k: int = 512,
                         interpret: bool = False) -> jax.Array:
    """Fused sp-ring ⊗ flash attention over mesh axis ``axis_name``.

    Same contract as :func:`~horovod_tpu.parallel.ring_attention.
    ring_attention` — call inside ``shard_map`` with ``(batch,
    seq_local, heads, head_dim)`` shards, returns the exact softmax
    attention over the full global sequence — but each visiting K/V
    block is consumed by the Pallas flash kernels and the per-step
    normalized partials ``(out_s, lse_s)`` merge in log-space::

        lse  = logaddexp(lse, lse_s)
        out  = out·exp(lse_prev − lse) + out_s·exp(lse_s − lse)

    initialized at the finite ``_NEG_INF`` sentinel, so a fully-masked
    partial contributes ``exp(−huge) == 0`` exactly and the accumulator
    can never emit NaN.  The next block's ``ppermute`` is issued before
    the current block's kernel — the sends are data-independent, so the
    scheduler double-buffers the wire behind the MXU (the same contract
    as ``expert_alltoall_ffn``; on the synchronous CPU twin this pins
    structure, the overlap itself is a TPU quantity).

    Causal masking compares GLOBAL positions that travel around the
    ring with their blocks, so it composes with the ``zigzag`` layout;
    a causal ring step whose visiting block is entirely in the future
    skips its kernel launch via ``lax.cond`` (identity carry — the
    schedule is in :func:`ring_step_schedule`).

    Differentiable via ``custom_vjp``: FA2's blockwise backward is
    exact given the GLOBAL logsumexp and delta, so the backward replays
    the ring with each block's dK/dV accumulator traveling WITH the
    block — after ``world`` hops every accumulator is home and
    complete.

    Raises for shards off the flash tiling contract (unequal q/k/v
    shapes, non-tiling ``seq_local``, odd ``seq_local`` under zigzag)
    — the dispatch in ``parallel/ring_attention.py`` checks first and
    keeps the jnp formulation for those.
    """
    from jax import lax

    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(
            f"ring_flash_attention needs equal q/k/v shard shapes, got "
            f"{q.shape}/{k.shape}/{v.shape}")
    b, t, h, d = q.shape
    world = int(lax.axis_size(axis_name))
    scale = d ** -0.5 if scale is None else scale
    bq = fit_flash_block(t, block_q)
    bk = fit_flash_block(t, block_k)
    if bq is None or bk is None:
        raise ValueError(
            f"seq_local {t} does not fit the flash tiling contract; "
            f"use the jnp ring (parallel.ring_attention) instead")
    # validates layout, and zigzag's even-seq requirement (rank 0 is
    # representative; the traced per-rank positions are rebuilt inside
    # the vjp halves so no tracer is closed over across them)
    ring_layout_positions(0, world, t, layout)
    _count_fused_launch("ring_flash_attention")
    perm = [(i, (i + 1) % world) for i in range(world)]
    bh = b * h

    def _positions():
        me = lax.axis_index(axis_name)
        qpos = ring_layout_positions(me, world, t, layout)
        return qpos, jnp.max(qpos)

    def _to_o(w_row):
        # (bh, t) row weight -> broadcastable over (b, t, h, d)
        return w_row.reshape(b, h, t).transpose(0, 2, 1)[..., None]

    def _merge(out_acc, lse_acc, out_b, lse_b):
        # log-space merge of normalized flash partials.  All-finite by
        # construction: the sentinel is finite, logaddexp of finite
        # inputs is finite, and exp(_NEG_INF − anything) == 0 exactly.
        lse_new = jnp.logaddexp(lse_acc, lse_b)
        out_new = (out_acc * _to_o(jnp.exp(lse_acc - lse_new)) +
                   out_b.astype(jnp.float32) *
                   _to_o(jnp.exp(lse_b - lse_new)))
        return out_new, lse_new

    def _fwd_ring(q, k, v):
        qpos, q_max = _positions()
        out_acc = jnp.zeros((b, t, h, d), jnp.float32)
        lse_acc = jnp.full((bh, t), _NEG_INF, jnp.float32)
        k_cur, v_cur, kpos_cur = k, v, qpos
        for s in range(world):
            nxt = None
            if s < world - 1:
                # double-buffer: the hop is data-independent of this
                # step's kernel, so the wire flies behind the MXU
                nxt = lax.ppermute((k_cur, v_cur, kpos_cur),
                                   axis_name, perm)

            def live(args):
                o_acc, l_acc, k_c, v_c, kp = args
                out_b, lse_b = _flash_fwd(
                    q, k_c, v_c, causal, scale, bq, bk, interpret,
                    qpos=qpos if causal else None,
                    kpos=kp if causal else None)
                return _merge(o_acc, l_acc, out_b, lse_b[:, 0, :])

            args = (out_acc, lse_acc, k_cur, v_cur, kpos_cur)
            if causal:
                # a block entirely in the future launches no kernel;
                # the identity carry doubles as the lse=-inf NaN guard
                out_acc, lse_acc = lax.cond(
                    q_max < jnp.min(kpos_cur),
                    lambda a: (a[0], a[1]), live, args)
            else:
                out_acc, lse_acc = live(args)
            if nxt is not None:
                k_cur, v_cur, kpos_cur = nxt
        return out_acc.astype(q.dtype), lse_acc

    def _bwd_ring(res, g):
        q, k, v, out, lse_g = res
        qpos, q_max = _positions()
        gb = g.transpose(0, 2, 1, 3).reshape(bh, t, d).astype(jnp.float32)
        ob = out.transpose(0, 2, 1, 3).reshape(bh, t, d) \
            .astype(jnp.float32)
        delta = (gb * ob).sum(-1)                       # (bh, t) global
        lse8 = jnp.broadcast_to(lse_g[:, None, :], (bh, 8, t))
        dq_acc = jnp.zeros((b, t, h, d), jnp.float32)
        # the visiting block's dK/dV accumulate where the block IS and
        # travel with it: after `world` hops each is home, complete
        dk_cur = jnp.zeros((b, t, h, d), jnp.float32)
        dv_cur = jnp.zeros((b, t, h, d), jnp.float32)
        k_cur, v_cur, kpos_cur = k, v, qpos
        for s in range(world):
            nxt = None
            if s < world - 1:
                nxt = lax.ppermute((k_cur, v_cur, kpos_cur),
                                   axis_name, perm)

            def live(args):
                dq_a, dk_c, dv_c, k_c, v_c, kp = args
                dq_b, dk_b, dv_b = _flash_bwd(
                    q, k_c, v_c, out, lse8, g, causal, scale, bq, bk,
                    interpret, qpos=qpos if causal else None,
                    kpos=kp if causal else None, delta=delta)
                return (dq_a + dq_b.astype(jnp.float32),
                        dk_c + dk_b.astype(jnp.float32),
                        dv_c + dv_b.astype(jnp.float32))

            args = (dq_acc, dk_cur, dv_cur, k_cur, v_cur, kpos_cur)
            if causal:
                dq_acc, dk_cur, dv_cur = lax.cond(
                    q_max < jnp.min(kpos_cur),
                    lambda a: (a[0], a[1], a[2]), live, args)
            else:
                dq_acc, dk_cur, dv_cur = live(args)
            # the accumulators hop with their block every step — the
            # world-th hop is the homecoming
            dk_cur, dv_cur = lax.ppermute((dk_cur, dv_cur),
                                          axis_name, perm)
            if nxt is not None:
                k_cur, v_cur, kpos_cur = nxt
        return (dq_acc.astype(q.dtype), dk_cur.astype(k.dtype),
                dv_cur.astype(v.dtype))

    @jax.custom_vjp
    def _attn(q, k, v):
        out, _ = _fwd_ring(q, k, v)
        return out

    def _fwd(q, k, v):
        out, lse_g = _fwd_ring(q, k, v)
        return out, (q, k, v, out, lse_g)

    _attn.defvjp(_fwd, _bwd_ring)
    return _attn(q, k, v)


# ---------------------------------------------------------------------------
# the Mamba-2 chunked scan (SSD)
# ---------------------------------------------------------------------------
#
# docs/fused_kernels.md.  ``ssd_chunked`` is the jax.numpy form: every
# product an einsum, so the (chunk x chunk) decay matrix of each head,
# its product with C.B^T, the fp32 ``x dt`` and the (chunk, G, R, Q, P)
# copies of ``x`` all cross HBM, forward and again in the backward pass.
# ``ssd_scan`` runs the same arithmetic as two Mosaic kernels under one
# ``custom_vjp``: a program is one (batch row, group, chunk) — or, where
# a group's R heads are more than one call's VMEM holds
# (:func:`ssd_head_block`), one (batch row, group, block of Rb heads,
# chunk) — the chunk axis innermost and sequential, the running state of
# the heads in hand (Rb P x N fp32) carried in VMEM scratch from chunk to
# chunk — forward in the forward kernel, its cotangent backward in the
# backward kernel — and nothing chunk x chunk ever leaves VMEM.  Operands
# have time on the lanes, a head's P values on the sublanes.  (This section stays last in
# the file: a Mosaic call's serialized body carries its source lines,
# so code added above the flash kernels would move the compile-cache
# key of every step that holds them.)

def ssd_chunked(x, dt, a, b, c, chunk: int, dtype=jnp.float32):
    """The Mamba-2 recurrence in its chunked (SSD) form.

    ``h_t = exp(dt_t a) h_{t-1} + dt_t b_t (x) x_t``, ``y_t = c_t . h_t``
    from a zero state, for ``x`` (B, T, H, P), ``dt`` (B, T, H) fp32 and
    non-negative, ``a`` (H,) fp32 and negative, ``b`` and ``c``
    (B, T, G, N) with head ``h`` reading group ``h // (H // G)``.
    Within a chunk of ``chunk`` steps the outputs are one masked
    (chunk x chunk) product; each chunk's closing state is carried to
    the chunks after it by their summed decays.  The decays are kept in
    fp32; the products take ``dtype`` operands and add up in fp32.
    ``T`` need be no multiple of ``chunk``.  Returns (B, T, H, P) fp32.
    """
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    pad = -t % chunk
    if pad:     # dt = 0: the state neither decays nor takes anything in
        x, dt, b, c = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // chunk
    f32 = jnp.float32
    # (B, nc, G, R, Q[, P]) and (B, nc, G, Q, N)
    dt = dt.reshape(bsz, nc, chunk, g, r).transpose(0, 1, 3, 4, 2)
    xdt = x.reshape(bsz, nc, chunk, g, r, p).transpose(0, 1, 3, 4, 2, 5) \
        .astype(f32) * dt[..., None]
    b = b.reshape(bsz, nc, chunk, g, n).transpose(0, 1, 3, 2, 4).astype(dtype)
    c = c.reshape(bsz, nc, chunk, g, n).transpose(0, 1, 3, 2, 4).astype(dtype)
    cum = jnp.cumsum(dt * a.reshape(g, r)[..., None], axis=-1)   # log decay

    # inside a chunk: y_l += sum_{s<=l} (c_l . b_s) exp(cum_l - cum_s) xdt_s
    cb = jnp.einsum("zcgln,zcgsn->zcgls", c, b, preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    y = jnp.einsum("zcgrls,zcgrsp->zcgrlp",
                   (cb[:, :, :, None] * decay).astype(dtype),
                   xdt.astype(dtype), preferred_element_type=f32)

    # each chunk's closing state, had it started from zero
    to_end = jnp.exp(cum[..., -1:] - cum)
    states = jnp.einsum("zcgrsp,zcgsn->zcgrpn",
                        (xdt * to_end[..., None]).astype(dtype), b,
                        preferred_element_type=f32)
    # carried: the state chunk k starts from is the sum over j < k of
    # state_j decayed by the chunks between them
    total = jnp.cumsum(cum[..., -1], axis=1)            # (B, nc, G, R)
    before = total - cum[..., -1]                       # exclusive
    between = before[:, :, None] - total[:, None, :]    # [k, j]
    earlier = jnp.tril(jnp.ones((nc, nc), bool), -1)[None, :, :, None, None]
    carry = jnp.exp(jnp.where(earlier, between, -jnp.inf))
    start = jnp.einsum("zkjgr,zjgrpn->zkgrpn", carry, states,
                       preferred_element_type=f32)
    y = y + jnp.einsum("zcgln,zcgrpn->zcgrlp", c, start.astype(dtype),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(bsz, t + pad, h, p)
    return y[:, :t]


def _ssd_head(r, p: int, cumr_ref, cumc, causal):
    """Of head ``r`` (traced) of the group in hand: its ``p`` rows of a
    (R P, .) operand, its row of a (R, Q) one, its log-decay prefix as a
    row (1, Q), the prefix's last entry — the chunk's whole decay —
    (1, 1), ``exp(cum_l - cum_s)`` for ``s <= l``, else 0, as (l, s):
    the (Q, Q) matrix that never leaves VMEM, and its column of a
    (Q, R) operand as a mask."""
    q = cumc.shape[0]
    rows, row = pl.ds(pl.multiple_of(r * p, p), p), pl.ds(r, 1)
    cum = cumr_ref[0, 0, row, :]
    mine = jax.lax.broadcasted_iota(jnp.int32, cumc.shape, 1) == r
    col = jnp.sum(jnp.where(mine, cumc, 0), axis=1, keepdims=True)
    decay = jnp.exp(jnp.where(causal, col - cum, -jnp.inf))
    return rows, row, cum, col[q - 1:q, :], decay, mine


def _ssd_fwd_kernel(x_ref, dt_ref, cumr_ref, cumc_ref, b_ref, c_ref,
                    y_ref, start_ref, state_ref, *, heads: int, p: int,
                    chunk_axis: int = 2):
    """One chunk of ``heads`` heads of one group (all of them, or one
    block), time on the lanes: their ``y`` (R P, Q), the state they start
    the chunk from (kept for the backward pass) and, in ``state_ref``,
    the state they leave it with.  ``chunk_axis``: the grid's last."""
    f32, dtype = jnp.float32, x_ref.dtype
    q = x_ref.shape[2]

    @pl.when(pl.program_id(chunk_axis) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    bt, ct = b_ref[0], c_ref[0]                               # (N, Q)
    cumc = cumc_ref[0, 0]                                     # (Q, R)
    cb = jnp.dot(ct.T, bt, preferred_element_type=f32)        # (l, s)
    causal = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)

    def head(r, carry):
        rows, row, cum, total, decay, _ = _ssd_head(r, p, cumr_ref, cumc,
                                                    causal)
        xdt = x_ref[0, rows, :].astype(f32) * dt_ref[0, 0, row, :]
        state = state_ref[rows, :]                            # (P, N)
        start_ref[0, 0, 0, rows, :] = state
        y_ref[0, rows, :] = jax.lax.dot_general(
            xdt.astype(dtype), (cb * decay).astype(dtype), _NT,
            preferred_element_type=f32) \
            + jnp.exp(cum) * jnp.dot(state.astype(dtype), ct,
                                     preferred_element_type=f32)
        # (Mosaic broadcasts a (1, 1) along one axis at a time)
        closing = (xdt * jnp.exp(jnp.broadcast_to(total, (1, q)) - cum)) \
            .astype(dtype)
        state_ref[rows, :] = \
            state * jnp.exp(jnp.broadcast_to(total, (p, 1))) \
            + jax.lax.dot_general(closing, bt, _NT,
                                  preferred_element_type=f32)
        return carry

    # the body is traced once and unrolled when it is lowered: written
    # as a Python loop its 8 copies cost a model's ``init`` 4 s of
    # tracing; left rolled the kernel ran half as long again (PERF.md,
    # PR 29)
    jax.lax.fori_loop(0, heads, head, 0, unroll=True)


def _ssd_bwd_kernel(x_ref, dt_ref, cumr_ref, cumc_ref, b_ref, c_ref,
                    start_ref, dy_ref, dx_ref, ddt_ref, dcumr_ref,
                    dcumc_ref, db_ref, dc_ref, dstate_ref, dsb_ref,
                    closing_ref, dz_ref, *, heads: int, p: int,
                    chunk_axis: int = 2):
    """The forward kernel's chunk differentiated, the chunks taken last
    to first: ``dstate_ref`` carries the cotangent of the state a chunk
    leaves.  ``C B^T``, the decays and their product are recomputed.  The
    log-decay prefix takes its cotangent by position in both layouts it
    was read in: ``dcumc`` (time on the sublanes) the sums over ``s`` of
    the (l, s) part, ``dcumr`` (time on the lanes) everything else.
    ``db`` / ``dc`` are the sums over the heads in hand: a group's, or
    one head block's share of it."""
    f32, dtype = jnp.float32, x_ref.dtype
    q = x_ref.shape[2]

    @pl.when(pl.program_id(chunk_axis) == 0)    # nothing reads the last state
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    bt, ct = b_ref[0], c_ref[0]
    cm = ct.T                                                 # (l, N)
    cumc = cumc_ref[0, 0]
    cb = jnp.dot(cm, bt, preferred_element_type=f32)
    causal = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    dsb_ref[...] = dstate_ref[...].astype(dtype)

    def over_rows(v):
        return jnp.sum(v, axis=0, keepdims=True)

    def head(r, carry):
        dcb, dcumc = carry
        rows, row, cum, total, decay, mine = _ssd_head(r, p, cumr_ref,
                                                       cumc, causal)
        dt = dt_ref[0, 0, row, :]
        from_start = jnp.exp(cum)
        to_end = jnp.exp(jnp.broadcast_to(total, (1, q)) - cum)
        x = x_ref[0, rows, :].astype(f32)
        xdt = x * dt
        dy = dy_ref[0, rows, :]                               # (P, l)
        dyb = dy.astype(dtype)
        state, dstate = start_ref[0, 0, 0, rows, :], dstate_ref[rows, :]
        # inside the chunk, y^T += xdt^T . (C B^T o decay)^T
        m = cb * decay
        dm = jnp.dot(dyb.T, xdt.astype(dtype), preferred_element_type=f32)
        dcb += dm * decay
        dm = dm * m
        # the state the chunk leaves, exp(total) state + closing^T . B
        closing_ref[rows, :] = (xdt * to_end).astype(dtype)
        dxdt = to_end * jnp.dot(dsb_ref[rows, :], bt,
                                preferred_element_type=f32)
        closed = dxdt * xdt             # through exp(total - cum)
        dtotal = jnp.sum(over_rows(closed), axis=1, keepdims=True) \
            + jnp.exp(total) * jnp.sum(over_rows(dstate * state), axis=1,
                                       keepdims=True)
        # the carried-in part, y^T += exp(cum) state . C^T
        dz = (dy * from_start).astype(dtype)
        dz_ref[rows, :] = dz
        carried = jnp.dot(state.astype(dtype), ct,
                          preferred_element_type=f32)
        dcumr_ref[0, 0, row, :] = \
            over_rows(dy * carried) * from_start - over_rows(closed) \
            - over_rows(dm) + jnp.where(
                last, jnp.broadcast_to(dtotal, (1, q)), 0)
        dcumc = jnp.where(mine, jnp.sum(dm, axis=1, keepdims=True), dcumc)
        dxdt += jnp.dot(dyb, m.astype(dtype), preferred_element_type=f32)
        dx_ref[0, rows, :] = (dxdt * dt).astype(dx_ref.dtype)
        ddt_ref[0, 0, row, :] = over_rows(dxdt * x)
        dstate_ref[rows, :] = \
            dstate * jnp.exp(jnp.broadcast_to(total, (p, 1))) \
            + jnp.dot(dz, cm, preferred_element_type=f32)
        return dcb, dcumc

    dcb, dcumc = jax.lax.fori_loop(
        0, heads, head, (jnp.zeros((q, q), f32), jnp.zeros_like(cumc)),
        unroll=True)
    dcbb = dcb.astype(dtype)
    dc_ref[0] = (jnp.dot(start_ref[0, 0, 0].astype(dtype).T, dz_ref[...],
                         preferred_element_type=f32)
                 + jax.lax.dot_general(bt, dcbb, _NT,
                                       preferred_element_type=f32)) \
        .astype(dc_ref.dtype)
    db_ref[0] = (jnp.dot(dsb_ref[...].T, closing_ref[...],
                         preferred_element_type=f32)
                 + jnp.dot(ct, dcbb, preferred_element_type=f32)) \
        .astype(db_ref.dtype)
    dcumc_ref[0, 0] = dcumc


# Mosaic's default scope of VMEM on a v5e.  A scan call takes as many of
# a group's heads a grid step as it reckons fit it, and asks for more
# only where the fewest it can take do not.  Read on the chip at
# [1, 8192, 64 x 64], one group, state 128, chunk 256, forward | forward
# + backward ms (PERF.md, PR 34): 8 heads a step 0.566 | 1.892, 16 —
# what fits — 0.501 | 1.763, 32 (23 MiB) 0.473 | 1.826, all 64 (41 MiB,
# six times the compile) 0.467 | 1.710
_MOSAIC_VMEM_SCOPE = 16 << 20


def _ssd_vmem_bytes(rb: int, p: int, n: int, chunk: int,
                    itemsize: int) -> int:
    """VMEM the backward call — the larger of the two — holds for a block
    of ``rb`` heads, from its shapes: ``x``, the fp32 ``dy``, ``dx`` and
    the chunk's starting state as (rb p, .) blocks, ``b``, ``c``, ``db``,
    ``dc``, the four (rb, Q) rows in whole 8-sublane tiles and the two
    (Q, rb) columns in whole 128-lane tiles, each double-buffered by the
    pipeline; the four scratches; two (p, Q) fp32 temporaries for every
    head of the unrolled body; and eighteen (Q, Q) fp32 ones (``C B^T``,
    a head's decays, their product and the cotangents of each, while the
    scheduler overlaps a few heads' bodies).  An upper bound: compiled
    for a v5e, (rb, Q) = (32, 256) allocates 23.3 MiB where this says
    23.5, (64, 256) 40.6 for 41.3, (64, 128) 21.9 for 23.0."""
    rows = rb * p
    blocks = rows * chunk * (2 * itemsize + 4) + rows * n * 4 \
        + 4 * n * chunk * itemsize + 4 * -(-rb // 8) * 8 * chunk * 4 \
        + 2 * chunk * -(-rb // 128) * 128 * 4
    scratch = rows * n * (4 + itemsize) + 2 * rows * chunk * itemsize
    return 2 * blocks + scratch + 2 * rows * chunk * 4 \
        + 18 * chunk * chunk * 4


def ssd_head_block(r: int, p: int, n: int, chunk: int, itemsize: int) -> int:
    """Heads of a group one scan call takes at a grid step: the most —
    all ``r``, or a divisor of ``r`` that is a multiple of 8, the fp32
    sublane tile of the (heads, time) rows — whose reckoned VMEM
    (:func:`_ssd_vmem_bytes`) fits Mosaic's default scope; the fewest
    where none does (the call then asks for what it reckons)."""
    fits = [d for d in range(r, 0, -1)
            if r % d == 0 and (d == r or d % 8 == 0)]
    return next((d for d in fits if _ssd_vmem_bytes(
        d, p, n, chunk, itemsize) <= _MOSAIC_VMEM_SCOPE), fits[-1])


def _ssd_calls(bsz: int, t: int, g: int, r: int, p: int, n: int,
               chunk: int, dtype, interpret: bool, rb: int):
    """The forward and the backward ``pallas_call`` over operands with
    time on the lanes (:func:`ssd_scan`): ``x`` (B, H P, T) — a group's
    heads are R P adjacent rows of it —, ``b``, ``c`` (B, G N, T), ``dt``
    and the log-decay prefix (B, G, R, T), the prefix again with time on
    the sublanes (B, G, T, R), the chunks' starting states
    (B, G, T/Q, R P, N).

    ``rb`` heads a grid step (``r``: the whole group, grid (B, G, T/Q)).
    With ``nb = r // rb`` > 1 the grid is (B, G, nb, T/Q) — the chunk
    axis still last and sequential, the carried state a head block's —,
    ``C^T B`` of a chunk is rebuilt a head block, the time-on-sublanes
    prefix and its cotangent are (B, G nb, T, rb) (a block's last
    dimension is then the array's), and ``db`` / ``dc`` come back as one
    share a head block, (B, G nb N, T), for the caller to add up."""
    from jax.experimental.pallas import tpu as pltpu

    nb, nc, f32 = r // rb, t // chunk, jnp.float32
    need = _ssd_vmem_bytes(rb, p, n, chunk, jnp.dtype(dtype).itemsize)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (2 + (nb > 1)) + ("arbitrary",),
        vmem_limit_bytes=need if need > _MOSAIC_VMEM_SCOPE else None)
    grid = (bsz, g, nc) if nb == 1 else (bsz, g, nb, nc)

    def of_group(i, j):     # head block j of group i, where both share an axis
        return i if nb == 1 else i * nb + j

    def specs(chunk_of):
        def at(index):      # index(z, i, j, k); one block a group: no j
            if nb == 1:
                return lambda z, i, k: index(z, i, 0, chunk_of(k))
            return lambda z, i, j, k: index(z, i, j, chunk_of(k))

        def heads(z, i, j, k):
            return z, of_group(i, j), k

        def group(z, i, j, k):
            return z, i, k

        def wide(rows, index=heads):    # (B, . x rows, T)
            return pl.BlockSpec((1, rows, chunk), at(index))
        row = pl.BlockSpec((1, 1, rb, chunk),
                           at(lambda z, i, j, k: (z, i, j, k)))
        col = pl.BlockSpec((1, 1, chunk, rb),
                           at(lambda z, i, j, k: (z, of_group(i, j), k, 0)))
        state = pl.BlockSpec((1, 1, 1, rb * p, n),
                             at(lambda z, i, j, k: (z, i, k, j, 0)))
        return wide, group, row, col, state

    def shape(*dims, dtype=f32):
        return jax.ShapeDtypeStruct(dims, dtype)

    kernel = dict(heads=rb, p=p) if nb == 1 \
        else dict(heads=rb, p=p, chunk_axis=3)
    wide, group, row, col, state = specs(lambda k: k)
    fwd = pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, **kernel),
        grid=grid,
        in_specs=[wide(rb * p), row, row, col, wide(n, group),
                  wide(n, group)],
        out_specs=[wide(rb * p), state],
        out_shape=[shape(bsz, g * r * p, t), shape(bsz, g, nc, r * p, n)],
        scratch_shapes=[pltpu.VMEM((rb * p, n), f32)],
        compiler_params=params, interpret=interpret, name="ssd_fwd")
    wide, group, row, col, state = specs(lambda k: nc - 1 - k)
    bwd = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, **kernel),
        grid=grid,
        in_specs=[wide(rb * p), row, row, col, wide(n, group),
                  wide(n, group), state, wide(rb * p)],
        out_specs=[wide(rb * p), row, row, col, wide(n), wide(n)],
        out_shape=[shape(bsz, g * r * p, t, dtype=dtype),
                   shape(bsz, g, r, t), shape(bsz, g, r, t),
                   shape(bsz, g * nb, t, rb),
                   shape(bsz, g * nb * n, t, dtype=dtype),
                   shape(bsz, g * nb * n, t, dtype=dtype)],
        scratch_shapes=[pltpu.VMEM((rb * p, n), f32),
                        pltpu.VMEM((rb * p, n), dtype),
                        pltpu.VMEM((rb * p, chunk), dtype),
                        pltpu.VMEM((rb * p, chunk), dtype)],
        compiler_params=params, interpret=interpret, name="ssd_bwd")
    return fwd, bwd


def ssd_runs_kernels(t: int, heads: int, p: int, groups: int, n: int,
                     chunk: int, interpret: bool = False) -> bool:
    """Whether :func:`ssd_scan` runs its kernels: the file's rule
    (:func:`_use_kernel`) and shapes that tile — whole chunks, ``chunk``
    (time, on the lanes) and the state width multiples of 128, a head's
    ``P`` rows a multiple of 16 (a bf16 sublane tile).  Any number of
    heads a group tiles: they are taken whole or in blocks of a multiple
    of 8 (:func:`ssd_head_block`)."""
    return (_use_kernel(interpret) and t % chunk == 0 and chunk % 128 == 0
            and n % 128 == 0 and heads % groups == 0 and p % 16 == 0)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int, *, interpret: bool = False,
             head_block: Optional[int] = None) -> jax.Array:
    """:func:`ssd_chunked` with its contract — ``x`` (B, T, H, P), ``dt``
    (B, T, H) fp32 >= 0, ``a`` (H,) fp32 < 0, ``b`` and ``c``
    (B, T, G, N); (B, T, H, P) fp32 from a zero state — as Mosaic
    kernels, forward and backward: on a TPU (elsewhere in interpreter
    mode) for shapes that tile (:func:`ssd_runs_kernels`), else
    ``ssd_chunked`` itself.  The products take ``x.dtype`` operands and
    add up in fp32, rounded where ``ssd_chunked`` rounds them; the
    decays, their prefix sums, the carried state and its cotangent are
    fp32 throughout.

    The kernels take their operands with time on the lanes, (B, H P, T):
    the layout XLA gives the Mamba mixer's activations on a TPU when
    left to itself (T is a multiple of 128 where 2 H P + 2 G N + H is
    not), so the transpositions here cost nothing there.

    A group's ``H / G`` heads are taken ``head_block`` a grid step
    (:func:`ssd_head_block` of the shapes where not given: all of them
    where they fit, as eight 64-wide heads do), and the gradients of the
    group's ``b`` and ``c`` are the sums of its head blocks' shares,
    added up here in fp32."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    if not ssd_runs_kernels(t, h, p, g, n, chunk, interpret):
        return ssd_chunked(x, dt, a, b, c, chunk, x.dtype)
    r, nc, f32 = h // g, t // chunk, jnp.float32
    rb = head_block or ssd_head_block(r, p, n, chunk, x.dtype.itemsize)
    if r % rb:
        raise ValueError(f"head_block {rb} does not divide the {r} heads "
                         f"of a group")
    nb = r // rb
    fwd, bwd = _ssd_calls(bsz, t, g, r, p, n, chunk, x.dtype, interpret, rb)

    @jax.custom_vjp
    def scan(x, dt, cumr, cumc, b, c):
        return fwd(x, dt, cumr, cumc, b, c)[0]

    def scan_fwd(x, dt, cumr, cumc, b, c):
        y, start = fwd(x, dt, cumr, cumc, b, c)
        return y, (x, dt, cumr, cumc, b, c, start)

    def scan_bwd(res, dy):
        if nb == 1:
            return tuple(bwd(*res, dy))
        *others, db, dc = bwd(*res, dy)
        return (*others, *(
            jnp.sum(v.reshape(bsz, g, nb, n, t).astype(f32), axis=2)
            .reshape(bsz, g * n, t).astype(v.dtype) for v in (db, dc)))

    scan.defvjp(scan_fwd, scan_bwd)

    def time_last(v):           # (B, T, ...) -> (B, prod(...), T)
        return v.reshape(bsz, t, -1).transpose(0, 2, 1)
    dt = time_last(dt.astype(f32)).reshape(bsz, g, r, t)
    # the log-decay prefix of each chunk as a product with a triangle at
    # full precision (jnp.cumsum is a reduce_window on a TPU: 4.9 ms a
    # step of the benchmark's cell, PERF.md PR 29)
    cumr = jnp.einsum(
        "zgrcs,ls->zgrcl",
        (dt * a.reshape(g, r, 1)).reshape(bsz, g, r, nc, chunk),
        jnp.tril(jnp.ones((chunk, chunk), f32)),
        precision=jax.lax.Precision.HIGHEST).reshape(bsz, g, r, t)

    def time_on_sublanes(v):    # (B, G, T, R); by head block (B, G nb, T, rb)
        if nb == 1:
            return v.transpose(0, 1, 3, 2)
        return v.reshape(bsz, g, nb, rb, t).transpose(0, 1, 2, 4, 3) \
            .reshape(bsz, g * nb, t, rb)
    y = scan(time_last(x), dt, cumr, time_on_sublanes(cumr),
             time_last(b.astype(x.dtype)), time_last(c.astype(x.dtype)))
    return y.transpose(0, 2, 1).reshape(bsz, t, h, p)


# ---------------------------------------------------------------------------
# the two sides of a hyper-connection
# ---------------------------------------------------------------------------
#
# docs/fused_kernels.md.  ``hc_read_reference`` / ``hc_write_reference``
# are the jax.numpy form: under XLA the backward pass re-reads the n
# streams of a token once a coefficient and Sinkhorn's unrolled rounds
# are some hundred small operations a layer.  ``hc_read`` / ``hc_write``
# run the same arithmetic as one ``custom_vjp`` a side whose forward and
# backward are one pass each over the streams: a program is one block of
# tokens with all n streams of it in VMEM, the channels walked 512 lanes
# at a time; the coefficients live with tokens on the lanes, as
# (8, tokens) slabs — a row of the mixing matrix a slab —, and are
# turned once a block for the per-token products.  (Appended after the
# scan, for its reason: code above a kernel moves that kernel's cache
# key.)

_HC_ROWS = 128      # of a coefficient matrix in VMEM: a lane tile, turned


def sinkhorn(logits, iters: int, eps: float):
    """``exp(logits)`` made (nearly) doubly stochastic by ``iters``
    Sinkhorn-Knopp rounds: divide each row by its sum + ``eps``, then
    each column by its sum + ``eps``.  ``logits``: (n, n, ...) — rows,
    columns, and whatever the matrices are batched over behind them, so
    that on a TPU the batch and not ``n`` lies on the lanes."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def _hc_stream(xs, j: int, c: int):
    """Stream ``j`` of ``xs`` in fp32 — sliced first, so that no fp32
    copy of all the streams is asked for."""
    return xs[..., j * c:(j + 1) * c].astype(jnp.float32)


def hc_read_reference(xs, scale, phi, gates, b_pre, b_post, b_res, *,
                      norm_eps: float, clamp, iters: int, eps: float):
    """The read side of a hyper-connection in ``jax.numpy``
    (:func:`hc_read` has the contract).  ``u Phi = (X (scale Phi)) /
    rms(X)``: the streams enter the matmul as they are (operands in
    their own type, the sum over ``n c`` in fp32), and no normed copy of
    them exists."""
    f32 = jnp.float32
    n = b_pre.shape[0]
    c = xs.shape[-1] // n
    inv_rms = jax.lax.rsqrt(jnp.mean(jnp.square(xs.astype(f32)), axis=-1)
                            + norm_eps)
    a = jax.lax.dot_general(xs, (scale[:, None] * phi).astype(xs.dtype),
                            (((2,), (0,)), ((), ())),
                            preferred_element_type=f32) * inv_rms[..., None]
    a = jnp.moveaxis(a, -1, 0)              # (n (n + 2), batch, seq)
    pre = jax.nn.sigmoid(gates[0] * a[:n] + b_pre[:, None, None])
    post = 2.0 * jax.nn.sigmoid(gates[1] * a[n:2 * n]
                                + b_post[:, None, None])
    res = gates[2] * a[2 * n:].reshape((n, n) + a.shape[1:]) \
        + b_res[:, :, None, None]
    mix = sinkhorn(jnp.clip(res, *clamp), iters, eps)
    x_in = sum(pre[j][..., None] * _hc_stream(xs, j, c) for j in range(n))
    return x_in.astype(xs.dtype), (post, mix)


def hc_write_reference(xs, post, mix, y):
    """The write side in ``jax.numpy`` (:func:`hc_write`): stream ``i``
    of the result ``sum_j mix[i, j] X_j + post[i] y``, summed in fp32 and
    cast stream by stream."""
    n, c = post.shape[0], y.shape[-1]
    y32 = y.astype(jnp.float32)
    return jnp.concatenate(
        [(sum(mix[i, j][..., None] * _hc_stream(xs, j, c) for j in range(n))
          + post[i][..., None] * y32).astype(xs.dtype)
         for i in range(n)], axis=-1)


def _hc_stack(v, n: int):
    """(n (n + 2), ...) — ``pre``, ``post``, the mixing matrix row by
    row, as ``phi``'s columns lie — to the kernels' (128, ...): every n
    of them at the head of a slab of 8 rows (``pre`` at 0, ``post`` at
    8, row ``i`` of the matrix at 16 + 8 i), zeros between and behind."""
    tail = v.shape[1:]
    behind = [(0, 0)] * len(tail)
    slabs = jnp.concatenate([v[:2 * n].reshape((2, n) + tail),
                             v[2 * n:].reshape((n, n) + tail)])
    slabs = jnp.pad(slabs, [(0, 0), (0, 8 - n)] + behind)
    return jnp.pad(slabs.reshape((8 * (n + 2),) + tail),
                   [(0, _HC_ROWS - 8 * (n + 2))] + behind)


def _hc_coefficients(logits, n: int, clamp, iters: int, eps: float):
    """(128, T) logits in :func:`_hc_stack`'s rows, tokens on the lanes,
    to the coefficients in the same rows: the two logistics, and the
    mixing matrix's rows as n (8, T) slabs — a row's sum is a sum over
    sublanes, a column's the sum of the slabs — through ``exp`` of the
    clipped logits and the Sinkhorn rounds.  fp32; rows past ``n`` of a
    slab are 0 and stay 0."""
    t = logits.shape[1]
    live = jax.lax.broadcasted_iota(jnp.int32, (8, t), 0) < n
    pre = jnp.where(live, jax.nn.sigmoid(logits[0:8]), 0.0)
    post = jnp.where(live, 2.0 * jax.nn.sigmoid(logits[8:16]), 0.0)
    m = [jnp.where(live, jnp.exp(jnp.clip(
        logits[16 + 8 * i:24 + 8 * i], *clamp)), 0.0) for i in range(n)]
    # a slab's idle rows are 0 over 1, not 0 over eps: a compiler that
    # folds the 2 iters divisors into one product would read 0 / 0 there
    idle = jnp.where(live, eps, 1.0)
    for _ in range(iters):
        m = [v / (jnp.sum(v, axis=0, keepdims=True) + eps) for v in m]
        total = functools.reduce(jnp.add, m)
        m = [v / (total + idle) for v in m]
    return jnp.concatenate(
        [pre, post, *m,
         jnp.zeros((_HC_ROWS - 8 * (n + 2), t), jnp.float32)], axis=0)


def _hc_lanes(k, w: int):
    return pl.ds(pl.multiple_of(k * w, w), w)


def _hc_folded(v):
    """(T, w) to (T, 128): the sum of its lane tiles — partial row sums,
    which one reduction over lanes a block, not a chunk, finishes."""
    return functools.reduce(jnp.add, (
        v[:, q * 128:(q + 1) * 128] for q in range(v.shape[1] // 128)))


def _hc_normed(x_ref, w_ref, *, n: int, c: int, w: int, norm_eps: float,
               other_ref=None):
    """One walk over a block's streams, (T, n c) in VMEM, ``w`` lanes at
    a time: ``X . w^T`` (T, 128) summed in fp32 from operands as they
    are, and the rows' inverse RMS (T, 1) from their squares in fp32.
    With ``other_ref``, a (T, c) block: beside them, in lane ``j`` of a
    (T, 128) array, the row sums of its product with stream ``j``."""
    f32 = jnp.float32
    t = x_ref.shape[0]
    zero = jnp.zeros((t, 128), f32)

    def chunk(k, carry):
        a, squares, sums = carry
        other = None if other_ref is None \
            else other_ref[:, _hc_lanes(k, w)].astype(f32)
        for j in range(n):
            lanes = _hc_lanes(j * (c // w) + k, w)
            x = x_ref[:, lanes]
            a += jax.lax.dot_general(x, w_ref[:, lanes], _NT,
                                     preferred_element_type=f32)
            x = x.astype(f32)
            squares += _hc_folded(x * x)
            if other is not None:
                sums = sums[:j] + (sums[j] + _hc_folded(other * x),) \
                    + sums[j + 1:]
        return a, squares, sums

    a, squares, sums = jax.lax.fori_loop(
        0, c // w, chunk,
        (jnp.zeros((t, _HC_ROWS), f32), zero,
         (zero,) * (0 if other_ref is None else n)))
    inv_rms = jax.lax.rsqrt(
        jnp.sum(squares, axis=-1, keepdims=True) / (n * c) + norm_eps)
    return a, inv_rms, _hc_in_lanes(dict(enumerate(sums)), t)


def _hc_in_lanes(sums, t: int):
    """(T, 128): in lane ``q`` the sum over lanes of ``sums[q]`` (T, 128),
    0 in the others."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (t, _HC_ROWS), 1)
    out = jnp.zeros((t, _HC_ROWS), jnp.float32)
    for q, v in sums.items():
        out = jnp.where(lane == q, jnp.sum(v, axis=-1, keepdims=True), out)
    return out


def _hc_read_fwd_kernel(x_ref, w_ref, gb_ref, xin_ref, post_ref, mix_ref,
                        turned_ref, *, n: int, c: int, w: int,
                        norm_eps: float, coefficients):
    """A block of tokens: the mixer's input (T, c), ``h_post`` (n, T)
    and the mixing matrix (n, n, T), from the block's streams read from
    HBM once.  Nothing of the streams' size is fp32 outside a lane
    chunk."""
    f32 = jnp.float32
    a, inv_rms, _ = _hc_normed(x_ref, w_ref, n=n, c=c, w=w,
                               norm_eps=norm_eps)
    coef = coefficients(gb_ref[:, 0:1] * (a * inv_rms).T + gb_ref[:, 1:2])
    post_ref[...] = coef[8:8 + n]
    for i in range(n):
        mix_ref[i] = coef[16 + 8 * i:16 + 8 * i + n]
    turned_ref[...] = coef.T                # h_pre[j]: column j, (T, 1)

    def chunk(k, carry):
        xin_ref[:, _hc_lanes(k, w)] = functools.reduce(jnp.add, (
            turned_ref[:, j:j + 1]
            * x_ref[:, _hc_lanes(j * (c // w) + k, w)].astype(f32)
            for j in range(n))).astype(xin_ref.dtype)
        return carry

    jax.lax.fori_loop(0, c // w, chunk, 0)


def _hc_read_bwd_kernel(x_ref, w_ref, gb_ref, dxin_ref, dpost_ref, dmix_ref,
                        dx_ref, dw_ref, dlogits_ref, normed_ref,
                        rows_ref, turned_ref, da_ref, dat_ref, *, n: int,
                        c: int, w: int, norm_eps: float, coefficients):
    """The read side differentiated, a block of tokens: the coefficients
    rebuilt in VMEM from the streams, their chain — Sinkhorn's rounds,
    ``exp``, the clip, the logistics — taken back by ``jax.vjp`` of the
    same function on the same (8, T) slabs, so no round's intermediate
    leaves VMEM; then ``dX_j = da . w + k X_j + h_pre[j] dx_in`` (``k``
    the norm's own term) and ``dw += da^T . X``, which adds up in VMEM
    across the grid.  The logits' cotangent and the normed products go
    out as (128, tokens) fp32 for the gates' and biases' sums."""
    f32 = jnp.float32
    live = dw_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    a, inv_rms, dpre = _hc_normed(x_ref, w_ref, n=n, c=c, w=w,
                                  norm_eps=norm_eps, other_ref=dxin_ref)
    normed = a * inv_rms                                    # (T, 128)
    gate = gb_ref[:, 0:1]
    coef, back = jax.vjp(coefficients, gate * normed.T + gb_ref[:, 1:2])
    rows_ref[...] = dpre.T          # the cotangents, in the stack's rows
    rows_ref[8:8 + n, :] = dpost_ref[...]
    for i in range(n):
        rows_ref[16 + 8 * i:16 + 8 * i + n, :] = dmix_ref[i]
    dlogits, = back(rows_ref[...])
    dlogits_ref[...] = dlogits
    normed_ref[...] = normed.T
    dnormed = (gate * dlogits).T                            # (T, 128)
    # through a / rms: the products' own cotangent, and the norm's term
    # -(sum_r dnormed_r normed_r) inv_rms^2 / (n c) times the row
    da = dnormed * inv_rms
    own = -jnp.sum(dnormed * normed, axis=-1, keepdims=True) \
        * inv_rms * inv_rms / (n * c)
    da_ref[...] = da.astype(da_ref.dtype)
    dat_ref[...] = da.T[:live].astype(dat_ref.dtype)
    turned_ref[...] = coef.T

    def chunk(k, carry):
        dxin = dxin_ref[:, _hc_lanes(k, w)].astype(f32)
        for j in range(n):
            lanes = _hc_lanes(j * (c // w) + k, w)
            x = x_ref[:, lanes]
            dx_ref[:, lanes] = (
                jnp.dot(da_ref[...], w_ref[:, lanes],
                        preferred_element_type=f32)
                + own * x.astype(f32)
                + turned_ref[:, j:j + 1] * dxin).astype(dx_ref.dtype)
            dw_ref[:, lanes] += jnp.dot(dat_ref[...], x,
                                        preferred_element_type=f32)
        return carry

    jax.lax.fori_loop(0, c // w, chunk, 0)


def _hc_turn(post_ref, mix_ref, rows_ref, turned_ref, n: int):
    """``h_post`` (n, T) and the mixing matrix (n, n, T) into the
    columns of ``turned_ref`` (T, 128): ``H_res[i, j]`` at 8 i + j,
    ``h_post[i]`` at 8 n + i.  Rows of ``rows_ref`` never written are
    never read as columns."""
    for i in range(n):
        rows_ref[8 * i:8 * i + n, :] = mix_ref[i]
    rows_ref[8 * n:8 * n + n, :] = post_ref[...]
    turned_ref[...] = rows_ref[...].T


def _hc_write_fwd_kernel(x_ref, y_ref, post_ref, mix_ref, out_ref,
                         rows_ref, turned_ref, *, n: int, c: int, w: int):
    """A block of tokens: ``X'_i = sum_j H_res[i, j] X_j + h_post[i] y``
    into stream ``i``'s lanes of the one output block, summed in fp32."""
    f32 = jnp.float32
    _hc_turn(post_ref, mix_ref, rows_ref, turned_ref, n)

    def chunk(k, carry):
        y = y_ref[:, _hc_lanes(k, w)].astype(f32)
        xs = [x_ref[:, _hc_lanes(j * (c // w) + k, w)].astype(f32)
              for j in range(n)]
        for i in range(n):
            mixed = functools.reduce(jnp.add, (
                turned_ref[:, 8 * i + j:8 * i + j + 1] * xs[j]
                for j in range(n)))
            out_ref[:, _hc_lanes(i * (c // w) + k, w)] = (
                mixed + turned_ref[:, 8 * n + i:8 * n + i + 1] * y
            ).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, c // w, chunk, 0)


def _hc_write_bwd_kernel(x_ref, y_ref, post_ref, mix_ref, g_ref, dx_ref,
                         dy_ref, dpost_ref, dmix_ref, rows_ref, turned_ref,
                         *, n: int, c: int, w: int):
    """The write side differentiated, a block of tokens in one pass:
    ``dX_j = sum_i H_res[i, j] g_i``, ``dy = sum_i h_post[i] g_i``, and
    the n n + n coefficient gradients ``<g_i, X_j>``, ``<g_i, y>`` as
    row sums in fp32 of the blocks in hand (a lane tile of partial sums
    each, reduced once a block)."""
    f32 = jnp.float32
    t = x_ref.shape[0]
    _hc_turn(post_ref, mix_ref, rows_ref, turned_ref, n)

    def chunk(k, sums):
        y = y_ref[:, _hc_lanes(k, w)].astype(f32)
        xs = [x_ref[:, _hc_lanes(j * (c // w) + k, w)].astype(f32)
              for j in range(n)]
        gs = [g_ref[:, _hc_lanes(i * (c // w) + k, w)].astype(f32)
              for i in range(n)]
        for j in range(n):
            dx_ref[:, _hc_lanes(j * (c // w) + k, w)] = functools.reduce(
                jnp.add, (turned_ref[:, 8 * i + j:8 * i + j + 1] * gs[i]
                          for i in range(n))).astype(dx_ref.dtype)
        dy_ref[:, _hc_lanes(k, w)] = functools.reduce(jnp.add, (
            turned_ref[:, 8 * n + i:8 * n + i + 1] * gs[i]
            for i in range(n))).astype(dy_ref.dtype)
        return tuple(
            s + _hc_folded(gs[q // (n + 1)] * (xs + [y])[q % (n + 1)])
            for q, s in enumerate(sums))

    sums = jax.lax.fori_loop(
        0, c // w, chunk,
        tuple(jnp.zeros((t, 128), f32) for _ in range(n * (n + 1))))
    # <g_i, X_j> to column 8 i + j, <g_i, y> to 8 n + i, and turned
    rows = _hc_in_lanes({
        8 * n + q // (n + 1) if q % (n + 1) == n
        else 8 * (q // (n + 1)) + q % (n + 1): s
        for q, s in enumerate(sums)}, t).T
    dpost_ref[...] = rows[8 * n:8 * n + n]
    for i in range(n):
        dmix_ref[i] = rows[8 * i:8 * i + n]


def hc_token_block(seq: int) -> Optional[int]:
    """Tokens a grid step of the hyper-connection's kernels: 256 where
    they divide the sequence, else 128, else none."""
    return next((t for t in (256, 128) if seq % t == 0), None)


def _hc_vmem_bytes(t: int, n: int, c: int, itemsize: int, wide: int,
                   narrow: int, matrix: bool) -> int:
    """VMEM one of the four calls holds for a block of ``t`` tokens,
    from its shapes: ``wide`` (t, n c) and ``narrow`` (t, c) blocks in
    and out and, with ``matrix``, the read side's (128, n c) matrix and
    the fp32 gradient of its live rows, each double-buffered by the
    pipeline; 12 MiB for the coefficient rows, the scratches and the
    fp32 temporaries of a lane chunk.  An upper bound: compiled for a
    v5e at (256, 4, 3584) bf16 the write side's backward — three wide
    blocks, two narrow — allocates 57.2 MiB where this says 61.0."""
    k = n * c
    blocks = (wide * k + narrow * c) * t * itemsize \
        + matrix * k * (_HC_ROWS * itemsize + 8 * (n + 2) * 4)
    return 2 * blocks + (12 << 20)


def hc_runs_kernels(seq: int, n: int, c: int,
                    interpret: bool = False) -> bool:
    """Whether :func:`hc_read` / :func:`hc_write` run their kernels: the
    file's rule (:func:`_use_kernel`) and shapes that tile — a stream's
    width a multiple of 128 (its lanes), at most 8 streams (a slab of
    coefficient rows), a token block (:func:`hc_token_block`) that
    divides the sequence."""
    return (_use_kernel(interpret) and c % 128 == 0 and 1 <= n <= 8
            and hc_token_block(seq) is not None)


def _hc_specs(t: int, n: int, c: int, dtype, interpret: bool):
    """What the four calls share: the block of a (tokens, n c) array
    (``wide``), of a (tokens, c) one (``narrow``), of ``h_post``
    (n, tokens), of the mixing matrix (n, n, tokens) and of a (128,
    tokens) coefficient matrix (``rows``); an array taken ``whole``; an
    output's ``shape``; ``params``, the keywords of a call that holds so
    many wide and narrow blocks; the (128, t) and (t, 128) fp32
    ``scratch``."""
    import types

    from jax.experimental.pallas import tpu as pltpu

    def params(semantics, wide, narrow, matrix=False):
        need = _hc_vmem_bytes(t, n, c, jnp.dtype(dtype).itemsize, wide,
                              narrow, matrix)
        return dict(
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(semantics,),
                vmem_limit_bytes=need if need > _MOSAIC_VMEM_SCOPE else None),
            interpret=interpret)

    return types.SimpleNamespace(
        wide=pl.BlockSpec((t, n * c), lambda z: (z, 0)),
        narrow=pl.BlockSpec((t, c), lambda z: (z, 0)),
        post=pl.BlockSpec((n, t), lambda z: (0, z)),
        mix=pl.BlockSpec((n, n, t), lambda z: (0, 0, z)),
        rows=pl.BlockSpec((_HC_ROWS, t), lambda z: (0, z)),
        whole=lambda *dims: pl.BlockSpec(dims, lambda z: (0,) * len(dims)),
        shape=lambda *dims, dtype=dtype: jax.ShapeDtypeStruct(dims, dtype),
        params=params,
        scratch=[pltpu.VMEM((_HC_ROWS, t), jnp.float32),
                 pltpu.VMEM((t, _HC_ROWS), jnp.float32)])


def _hc_chunk(c: int) -> int:
    """Lanes of a stream a trip of a kernel's walk takes: 512 where they
    divide it (the write side's forward alone reads 0.43 ms at 512 and
    0.65 at 128, PERF.md PR 35)."""
    return next(v for v in (512, 256, 128) if c % v == 0)


# cached: a model's sublayers share their calls, and a call traced once
# is not traced again (ten sublayers' kernels, Sinkhorn's unrolled
# rounds and their vjp among them, are 4 s of a step's lowering)
@functools.lru_cache(maxsize=None)
def _hc_read_calls(tokens: int, t: int, n: int, c: int, dtype,
                   interpret: bool, norm_eps: float, clamp, iters: int,
                   eps: float):
    """The read side's forward and backward ``pallas_call`` over
    token-major operands: the streams (tokens, n c), the mixer's input
    (tokens, c), ``h_post`` (n, tokens) and the mixing matrix (n, n,
    tokens) fp32, the matrix (128, n c) and the gates and biases (128,
    2) in :func:`_hc_stack`'s rows.  Grid: the token blocks — in order
    in the backward call, whose matrix gradient adds up across them."""
    from jax.experimental.pallas import tpu as pltpu

    f32, k, live = jnp.float32, n * c, 8 * (n + 2)
    s = _hc_specs(t, n, c, dtype, interpret)
    kernel = dict(n=n, c=c, w=_hc_chunk(c), norm_eps=norm_eps,
                  coefficients=functools.partial(
                      _hc_coefficients, n=n, clamp=clamp, iters=iters,
                      eps=eps))
    operands = [s.wide, s.whole(_HC_ROWS, k), s.whole(_HC_ROWS, 2)]
    fwd = pl.pallas_call(
        functools.partial(_hc_read_fwd_kernel, **kernel),
        grid=(tokens // t,), in_specs=operands,
        out_specs=[s.narrow, s.post, s.mix],
        out_shape=[s.shape(tokens, c), s.shape(n, tokens, dtype=f32),
                   s.shape(n, n, tokens, dtype=f32)],
        scratch_shapes=s.scratch[1:], name="hc_read_fwd",
        **s.params("parallel", 1, 1, True))
    bwd = pl.pallas_call(
        functools.partial(_hc_read_bwd_kernel, **kernel),
        grid=(tokens // t,),
        in_specs=operands + [s.narrow, s.post, s.mix],
        out_specs=[s.wide, s.whole(live, k), s.rows, s.rows],
        out_shape=[s.shape(tokens, k), s.shape(live, k, dtype=f32),
                   s.shape(_HC_ROWS, tokens, dtype=f32),
                   s.shape(_HC_ROWS, tokens, dtype=f32)],
        scratch_shapes=s.scratch + [pltpu.VMEM((t, _HC_ROWS), dtype),
                                    pltpu.VMEM((live, t), dtype)],
        name="hc_read_bwd", **s.params("arbitrary", 2, 1, True))
    return fwd, bwd


@functools.lru_cache(maxsize=None)
def _hc_write_calls(tokens: int, t: int, n: int, c: int, dtype,
                    interpret: bool):
    """The write side's forward and backward ``pallas_call`` over the
    same operands and the mixer's output (tokens, c)."""
    f32, k = jnp.float32, n * c
    s = _hc_specs(t, n, c, dtype, interpret)
    kernel = dict(n=n, c=c, w=_hc_chunk(c))
    operands = [s.wide, s.narrow, s.post, s.mix]
    fwd = pl.pallas_call(
        functools.partial(_hc_write_fwd_kernel, **kernel),
        grid=(tokens // t,), in_specs=operands, out_specs=s.wide,
        out_shape=s.shape(tokens, k), scratch_shapes=s.scratch,
        name="hc_write_fwd", **s.params("parallel", 2, 1))
    bwd = pl.pallas_call(
        functools.partial(_hc_write_bwd_kernel, **kernel),
        grid=(tokens // t,), in_specs=operands + [s.wide],
        out_specs=operands,
        out_shape=[s.shape(tokens, k), s.shape(tokens, c),
                   s.shape(n, tokens, dtype=f32),
                   s.shape(n, n, tokens, dtype=f32)],
        scratch_shapes=s.scratch, name="hc_write_bwd",
        **s.params("parallel", 3, 2))
    return fwd, bwd


def hc_read(xs, scale, phi, gates, b_pre, b_post, b_res, *,
            norm_eps: float, clamp, iters: int, eps: float,
            interpret: bool = False, token_block: Optional[int] = None):
    """The read side of a manifold-constrained hyper-connection.

    ``xs`` (B, S, n c): a token's n residual streams side by side.
    From ``u = RMSNorm(vec(X))`` (``scale``, (n c,)) and ``a = u phi``
    (``phi`` (n c, n (n + 2)), columns ``pre``, ``post``, the mixing
    matrix row by row): ``h_pre = sigmoid(gates[0] a_pre + b_pre)``,
    ``h_post = 2 sigmoid(gates[1] a_post + b_post)``, ``H_res =
    Sinkhorn(clip(gates[2] mat(a_res) + b_res))`` (:func:`sinkhorn`).
    Returns the mixer's input ``sum_j h_pre[j] X_j`` (B, S, c) in
    ``xs.dtype`` and ``(h_post (n, B, S), H_res (n, n, B, S))`` fp32.

    On a TPU (elsewhere in interpreter mode) for shapes that tile
    (:func:`hc_runs_kernels`) one Mosaic call forward, ``hc_read_fwd``,
    and one backward, ``hc_read_bwd``, under a ``custom_vjp`` whose
    residuals are its inputs: each reads a block of ``token_block``
    tokens' streams from HBM once, and neither an fp32 nor a normed copy
    of the streams, nor any of Sinkhorn's rounds, is written there.
    Else :func:`hc_read_reference`.  The norm, the coefficients,
    Sinkhorn and the sums over streams are fp32; ``X (scale phi)`` takes
    operands in ``xs.dtype`` and adds up in fp32, and so do the two
    products of its backward, rounded where the reference rounds them.
    """
    bsz, seq, k = xs.shape
    n = b_pre.shape[0]
    c = k // n
    if not hc_runs_kernels(seq, n, c, interpret):
        return hc_read_reference(xs, scale, phi, gates, b_pre, b_post,
                                 b_res, norm_eps=norm_eps, clamp=clamp,
                                 iters=iters, eps=eps)
    f32, tokens = jnp.float32, bsz * seq
    live = 8 * (n + 2)
    read_fwd, read_bwd = _hc_read_calls(
        tokens, token_block or hc_token_block(seq), n, c, xs.dtype,
        interpret, float(norm_eps), tuple(clamp), iters, float(eps))

    @jax.custom_vjp
    def read(x, w, gb):
        return tuple(read_fwd(x, w, gb))

    def fwd(x, w, gb):
        return tuple(read_fwd(x, w, gb)), (x, w, gb)

    def bwd(res, cotangents):
        dx, dw, dlogits, normed = read_bwd(*res, *cotangents)
        dw = jnp.pad(dw, [(0, _HC_ROWS - live), (0, 0)])
        dgb = jnp.stack([jnp.sum(dlogits * normed, axis=1),
                         jnp.sum(dlogits, axis=1)], axis=1)
        return dx, dw.astype(res[1].dtype), dgb

    read.defvjp(fwd, bwd)
    w = _hc_stack((scale[:, None] * phi).astype(xs.dtype).T, n)
    gb = _hc_stack(jnp.stack(
        [jnp.concatenate([jnp.broadcast_to(gates[i], (m,))
                          for i, m in enumerate((n, n, n * n))]),
         jnp.concatenate([b_pre, b_post, b_res.reshape(-1)])],
        axis=1).astype(f32), n)
    x_in, post, mix = read(xs.reshape(tokens, k), w, gb)
    return x_in.reshape(bsz, seq, c), (post.reshape(n, bsz, seq),
                                       mix.reshape(n, n, bsz, seq))


def hc_write(xs, post, mix, y, *, interpret: bool = False,
             token_block: Optional[int] = None):
    """The write side: ``X' = H_res X + h_post^T y`` — stream ``i`` of
    the result ``sum_j mix[i, j] X_j + post[i] y``, summed in fp32 and
    cast once — for ``xs`` (B, S, n c), ``y`` (B, S, c), ``post``
    (n, B, S) and ``mix`` (n, n, B, S) fp32.

    For shapes that tile (:func:`hc_runs_kernels`) one Mosaic call
    forward, ``hc_write_fwd`` — every stream written into its lanes of
    the one result, no concatenation —, and one backward,
    ``hc_write_bwd``: ``dX``, ``dy`` and the n n + n coefficient
    gradients as fp32 row sums from one reading of ``xs``, ``y`` and the
    result's cotangent.  The ``custom_vjp``'s residuals are its inputs,
    so a rematerialised block's backward does not run the forward again.
    Else :func:`hc_write_reference`."""
    bsz, seq, k = xs.shape
    n, c = post.shape[0], y.shape[-1]
    if not hc_runs_kernels(seq, n, c, interpret):
        return hc_write_reference(xs, post, mix, y)
    tokens = bsz * seq
    write_fwd, write_bwd = _hc_write_calls(
        tokens, token_block or hc_token_block(seq), n, c, xs.dtype,
        interpret)

    @jax.custom_vjp
    def write(x, y, post, mix):
        return write_fwd(x, y, post, mix)

    def fwd(x, y, post, mix):
        return write_fwd(x, y, post, mix), (x, y, post, mix)

    def bwd(res, g):
        return tuple(write_bwd(*res, g))

    write.defvjp(fwd, bwd)
    return write(xs.reshape(tokens, k), y.reshape(tokens, c),
                 post.reshape(n, tokens), mix.reshape(n, n, tokens)) \
        .reshape(bsz, seq, k)


# ---------------------------------------------------------------------------
# the Mamba-2 mixer's elementwise passes
# ---------------------------------------------------------------------------
#
# docs/fused_kernels.md.  Between ``in_proj`` and ``out_proj`` the mixer
# is the scan and two elementwise passes: the causal depthwise
# convolution with SiLU over ``xBC``, and ``(y + D x) silu(z)`` under an
# RMSNorm in groups.  Under XLA the convolution is a padded copy, four
# shifted slices and their transposes, and the norm's backward is split
# over two fusions.  ``mamba_conv`` and ``mamba_gated_norm`` run the same
# arithmetic as one ``custom_vjp`` each whose forward and backward are
# one pass over operands with time on the lanes, as the scan takes them:
# the ``xBC`` and ``z`` rows are read out of ``in_proj``'s result
# (B, F, T) through the ``BlockSpec``, the shift in time is a lane
# rotation, and the norm's sum over a group's channels runs down the
# sublanes.  (Appended last, for the scan's reason: code above a kernel
# moves that kernel's cache key.)

_MAMBA_TAPS = 4         # the convolution's width the kernels are written for
_MAMBA_HALO = 128       # lanes of the tile before (after) a block also reads
# lanes a trip of the convolution's walks takes.  Read on the chip at
# (128, 8192) blocks, C = 6,144 (PERF.md PR 37): forward 0.49 ms at 512,
# 0.44 at 1,024, 0.42 at 2,048, 0.50 at 8,192; backward 1.00 at 512, 0.88
# at 2,048, 0.71 at 8,192 (the whole tile: no loop)
_MAMBA_CHUNK = 2048
_MAMBA_BWD_CHUNK = 8192


def _mamba_lanes(q, w: int, extra: int = 0):
    return pl.ds(pl.multiple_of(q * w, 128), w + extra)


def _mamba_shifted(ext, s: int, w: int, ahead: bool = False):
    """Of ``ext`` (R, 128 + w) — 128 lanes of halo, then ``w`` lanes —
    the ``w`` lanes moved ``s`` steps back in time (lane ``t`` reads
    ``t - s``); with ``ahead``, of ``ext`` (R, w + 128) — ``w`` lanes,
    then the halo — moved ``s`` steps on (lane ``t`` reads ``t + s``)."""
    from jax.experimental.pallas import tpu as pltpu

    if ahead:
        return pltpu.roll(ext, ext.shape[1] - s, 1)[:, :w]
    return pltpu.roll(ext, s, 1)[:, _MAMBA_HALO:]


def _mamba_fill(ext_ref, at: int, halo_ref, live):
    """128 lanes of ``ext_ref`` from ``at``: the halo block where it is
    ``live`` (a tile of the sequence), zeros beyond the sequence's end."""
    lanes = slice(at, at + _MAMBA_HALO)

    @pl.when(live)
    def _():
        ext_ref[:, lanes] = halo_ref[0]

    @pl.when(jnp.logical_not(live))
    def _():
        ext_ref[:, lanes] = jnp.zeros(
            (ext_ref.shape[0], _MAMBA_HALO), ext_ref.dtype)


def _mamba_pre(ext, taps, bias, w: int):
    """The convolution before its SiLU over ``w`` lanes, fp32, from
    ``ext`` (R, 128 + w), and the four shifted inputs it summed."""
    shifted = [_mamba_shifted(ext, _MAMBA_TAPS - 1 - j, w)
               for j in range(_MAMBA_TAPS - 1)] + [ext[:, _MAMBA_HALO:]]
    pre = bias + functools.reduce(
        jnp.add, (tap * x for tap, x in zip(taps, shifted)))
    return pre, shifted


def _mamba_sigmoid(v):
    """``1 / (1 + exp(-v))`` as ``(1 + tanh(v / 2)) / 2``: one pass of
    the transcendental unit and two of the VPU, no division (read on
    the chip: the convolution's forward 0.61 ms with the division,
    0.49 without, PERF.md PR 37)."""
    return 0.5 + 0.5 * jnp.tanh(0.5 * v)


def _mamba_conv_fwd_kernel(cur_ref, prev_ref, wb_ref, x_ref, b_ref, c_ref,
                           ext_ref, *, nx: int, nb: int, w: int):
    """A tile of ``xBC``'s channels by a tile of time: ``silu(bias +
    sum_j w_j xBC[t - 3 + j])`` in fp32, rounded once, into the one of
    the three results the channels belong to."""
    f32 = jnp.float32
    i, k = pl.program_id(1), pl.program_id(2)
    bt = cur_ref.shape[2]
    _mamba_fill(ext_ref, 0, prev_ref, k > 0)
    ext_ref[:, _MAMBA_HALO:] = cur_ref[0]
    taps = [wb_ref[:, j:j + 1] for j in range(_MAMBA_TAPS)]
    bias = wb_ref[:, _MAMBA_TAPS:_MAMBA_TAPS + 1]

    def chunk(q, carry):
        pre, _ = _mamba_pre(
            ext_ref[:, _mamba_lanes(q, w, _MAMBA_HALO)].astype(f32), taps,
            bias, w)
        half = 0.5 * pre        # silu(pre) = pre sigmoid(pre)
        act = (half + half * jnp.tanh(half)).astype(x_ref.dtype)
        lanes = _mamba_lanes(q, w)

        @pl.when(i < nx)
        def _():
            x_ref[0, :, lanes] = act

        @pl.when(jnp.logical_and(i >= nx, i < nx + nb))
        def _():
            b_ref[0, :, lanes] = act

        @pl.when(i >= nx + nb)
        def _():
            c_ref[0, :, lanes] = act
        return carry

    jax.lax.fori_loop(0, bt // w, chunk, 0)


def _mamba_conv_bwd_kernel(cur_ref, prev_ref, next_ref, wb_ref, dx_ref,
                           dxn_ref, db_ref, dbn_ref, dc_ref, dcn_ref,
                           dxbc_ref, dwb_ref, ext_ref, dy_ref, dpre_ref, *,
                           nx: int, nb: int, w: int, last: int):
    """The forward's tile differentiated in one pass: the convolution
    before its SiLU rebuilt over the tile and the 128 lanes after it,
    its cotangent ``dpre``, ``dxBC[t] = sum_j w_j dpre[t + 3 - j]``, and
    the taps' and the bias's gradients as (R, 128) partial sums over
    time in fp32, added up over the tiles of a row."""
    f32 = jnp.float32
    i, k = pl.program_id(1), pl.program_id(2)
    bt = cur_ref.shape[2]
    _mamba_fill(ext_ref, 0, prev_ref, k > 0)
    ext_ref[:, _MAMBA_HALO:_MAMBA_HALO + bt] = cur_ref[0]
    _mamba_fill(ext_ref, _MAMBA_HALO + bt, next_ref, k < last)
    for own, (ref, halo) in zip(
            (i < nx, jnp.logical_and(i >= nx, i < nx + nb), i >= nx + nb),
            ((dx_ref, dxn_ref), (db_ref, dbn_ref), (dc_ref, dcn_ref))):
        @pl.when(own)
        def _(ref=ref, halo=halo):
            dy_ref[:, :bt] = ref[0]
            _mamba_fill(dy_ref, bt, halo, k < last)
    taps = [wb_ref[:, j:j + 1] for j in range(_MAMBA_TAPS)]
    bias = wb_ref[:, _MAMBA_TAPS:_MAMBA_TAPS + 1]

    @pl.when(k == 0)
    def _():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    def dpre_of(at, width):     # over lanes [at, at + width) of the tile
        pre, shifted = _mamba_pre(
            ext_ref[:, pl.ds(at, width + _MAMBA_HALO)].astype(f32), taps,
            bias, width)
        sig = _mamba_sigmoid(pre)
        return dy_ref[:, pl.ds(at, width)].astype(f32) \
            * (sig * (1.0 + pre * (1.0 - sig))), shifted

    def rebuilt(q, carry):
        at = pl.multiple_of(q * w, 128)
        dpre, shifted = dpre_of(at, w)
        dpre_ref[:, pl.ds(at, w)] = dpre
        for j, x in enumerate(shifted):
            dwb_ref[0, j] += _hc_folded(dpre * x)
        dwb_ref[0, _MAMBA_TAPS] += _hc_folded(dpre)
        return carry

    jax.lax.fori_loop(0, bt // w, rebuilt, 0)
    dpre_ref[:, bt:] = dpre_of(bt, _MAMBA_HALO)[0]

    def transposed(q, carry):
        ext = dpre_ref[:, _mamba_lanes(q, w, _MAMBA_HALO)]
        dxbc_ref[0, :, _mamba_lanes(q, w)] = functools.reduce(jnp.add, (
            taps[_MAMBA_TAPS - 1 - s] * (
                _mamba_shifted(ext, s, w, ahead=True) if s else ext[:, :w])
            for s in range(_MAMBA_TAPS))).astype(dxbc_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bt // w, transposed, 0)


def _mamba_gate(r, p: int, base, d_ref, y_ref, x_ref, z_ref):
    """Head ``r`` of the group in hand: its rows, ``u = y + D x``, the
    gate ``silu(z)``, ``sigmoid(z)`` and ``z``, fp32."""
    f32 = jnp.float32
    rows = pl.ds(pl.multiple_of(r * p, p), p)
    x = x_ref[0, rows, :].astype(f32)
    z = z_ref[0, rows, :].astype(f32)
    sig = _mamba_sigmoid(z)
    return rows, x, y_ref[0, rows, :] + d_ref[base + r] * x, z * sig, sig, z


def _mamba_norm_fwd_kernel(d_ref, y_ref, x_ref, z_ref, w_ref, o_ref, *,
                           p: int, eps: float):
    """All channels of one group by a tile of time: ``v = (y + D x)
    silu(z)``, ``v rsqrt(mean_c v^2 + eps) scale``, fp32, rounded once.
    Two walks over the block in VMEM, a head at a time: the squares'
    sum down the sublanes, then the result."""
    f32 = jnp.float32
    rows, bt = y_ref.shape[1:]
    heads = rows // p
    base = pl.program_id(1) * heads

    def squares(r, acc):
        _, _, u, gate, _, _ = _mamba_gate(r, p, base, d_ref, y_ref, x_ref,
                                          z_ref)
        v = u * gate
        return acc + jnp.sum(v * v, axis=0, keepdims=True)

    inv = jax.lax.rsqrt(jax.lax.fori_loop(
        0, heads, squares, jnp.zeros((1, bt), f32)) / rows + eps)

    def write(r, carry):
        at, _, u, gate, _, _ = _mamba_gate(r, p, base, d_ref, y_ref, x_ref,
                                           z_ref)
        o_ref[0, at, :] = (u * gate * inv * w_ref[at, :]).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, heads, write, 0)


def _mamba_norm_bwd_kernel(d_ref, y_ref, x_ref, z_ref, w_ref, g_ref, dy_ref,
                           dx_ref, dz_ref, dd_ref, dw_ref, *, p: int,
                           eps: float):
    """The forward's block differentiated in one pass: ``dy`` (the
    scan's cotangent, fp32, written here once), the skip's ``dx``,
    ``dz``, and ``dD`` (a row a head) and ``dscale`` (a row a channel)
    as 128-lane partial sums over time in fp32, added up over the tiles
    of a row."""
    f32 = jnp.float32
    rows, bt = y_ref.shape[1:]
    heads = rows // p
    base = pl.program_id(1) * heads

    @pl.when(pl.program_id(2) == 0)
    def _():
        dd_ref[...] = jnp.zeros_like(dd_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def sums(r, acc):
        at, _, u, gate, _, _ = _mamba_gate(r, p, base, d_ref, y_ref, x_ref,
                                           z_ref)
        v = u * gate
        gw = g_ref[0, at, :].astype(f32) * w_ref[at, :]
        return (acc[0] + jnp.sum(v * v, axis=0, keepdims=True),
                acc[1] + jnp.sum(gw * v, axis=0, keepdims=True))

    zero = jnp.zeros((1, bt), f32)
    squares, along = jax.lax.fori_loop(0, heads, sums, (zero, zero))
    inv = jax.lax.rsqrt(squares / rows + eps)
    back = inv * inv * inv * along / rows

    def write(r, carry):
        at, x, u, gate, sig, z = _mamba_gate(r, p, base, d_ref, y_ref,
                                             x_ref, z_ref)
        v = u * gate
        g = g_ref[0, at, :].astype(f32)
        dv = g * w_ref[at, :] * inv - v * back
        du = dv * gate
        dy_ref[0, at, :] = du
        dx_ref[0, at, :] = (du * d_ref[base + r]).astype(dx_ref.dtype)
        dz_ref[0, at, :] = (dv * u * (sig * (1.0 + z * (1.0 - sig)))) \
            .astype(dz_ref.dtype)
        dd_ref[0, pl.ds(r, 1), :] += _hc_folded(
            jnp.sum(du * x, axis=0, keepdims=True))
        dw_ref[0, at, :] += _hc_folded(g * v * inv)
        return carry

    jax.lax.fori_loop(0, heads, write, 0)


def mamba_conv_tile(t: int, inner: int, state_cols: int,
                    itemsize: int) -> Optional[tuple]:
    """(channels, time) of a block of the convolution's kernels: the
    longest tile of time up to 8,192 lanes that divides the sequence —
    whole rows where the sequence is no longer: no halo read, the fewest
    grid steps (read on the chip at 8,192: 0.49 ms forward at (256,
    2048), 0.44 at (128, 8192), PERF.md PR 37) — and the most channels,
    a divisor of ``x``'s and of ``B``'s (``C``'s) width, that keep a
    block at 2 MiB; none where nothing tiles."""
    bt = next((v for v in (8192, 4096, 2048, 1024, 512, 256, 128)
               if t % v == 0), None)
    if bt is None:
        return None
    bc = next((v for v in (512, 256, 128)
               if inner % v == 0 and state_cols % v == 0
               and v * bt * itemsize <= 2 << 20), None)
    return bc and (bc, bt)


def mamba_norm_tile(t: int, rows: int) -> Optional[int]:
    """Lanes of time of a block of the gated norm's kernels, whose rows
    are all channels of one group: the longest up to 2,048 that divides
    the sequence and keeps the fp32 ``y`` block at 4 MiB."""
    return next((v for v in (2048, 1024, 512, 256, 128)
                 if t % v == 0 and rows * v * 4 <= 4 << 20), None)


def mamba_runs_kernels(t: int, heads: int, p: int, groups: int, n: int,
                       taps: int, dtype, interpret: bool = False) -> bool:
    """Whether :func:`mamba_conv` / :func:`mamba_gated_norm` run their
    kernels: the file's rule (:func:`_use_kernel`) and shapes that tile —
    the convolution's width the kernels' four, ``x``'s and ``B``'s
    widths multiples of a channel tile (so ``xBC`` starts on a block of
    ``in_proj``'s result), a head's ``P`` rows whole sublane tiles, a
    tile of time that divides the sequence."""
    inner, itemsize = heads * p, jnp.dtype(dtype).itemsize
    return bool(
        _use_kernel(interpret) and taps == _MAMBA_TAPS
        and heads % groups == 0 and p % (32 // itemsize) == 0
        and mamba_conv_tile(t, inner, groups * n, itemsize)
        and mamba_norm_tile(t, inner // groups))


def _mamba_params(semantics, need: int, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=need if need > _MOSAIC_VMEM_SCOPE else None),
        interpret=interpret)


def _mamba_conv_vmem_bytes(bc: int, bt: int, itemsize: int) -> int:
    """VMEM the convolution's backward call — the larger — holds: six
    (bc, bt) blocks and five halos in and out and the taps' (5, bc, 128)
    fp32 sums, double-buffered; the three scratches; four fp32
    temporaries of a walk's trip and 4 MiB.  An upper bound: compiled
    for a v5e at (128, 8192) bf16 the call allocates 27.2 MiB where this
    says 54."""
    halo = bc * _MAMBA_HALO * itemsize
    blocks = 6 * bc * bt * itemsize + 5 * halo \
        + (_MAMBA_TAPS + 1) * bc * 128 * 4
    scratch = 2 * (bc * bt * itemsize + 2 * halo) + bc * (bt + 128) * 4
    return 2 * blocks + scratch \
        + 4 * bc * (min(bt, _MAMBA_BWD_CHUNK) + 128) * 4 + (4 << 20)


# cached: a model's layers share their calls, and a call traced once is
# not traced again
@functools.lru_cache(maxsize=None)
def _mamba_conv_calls(bsz: int, t: int, f: int, inner: int,
                      state_cols: int, dtype, interpret: bool):
    """The convolution's forward and backward ``pallas_call`` over
    ``in_proj``'s result with time on the lanes, (B, F, T) — its rows
    ``[z | xBC | dt]``, ``xBC`` = ``[x | B | C]`` from row ``inner`` —
    and the taps and bias as (C, 8) fp32 columns.  Grid (B, C / bc,
    T / bt), time innermost: a block reads its tile, the 128 lanes
    before it (after it too, backward) and writes ``x`` (B, inner, T),
    ``B`` or ``C`` (B, G N, T) — the results its channels do not belong
    to keep the block they hold, whose index does not move."""
    from jax.experimental.pallas import tpu as pltpu

    f32, itemsize = jnp.float32, jnp.dtype(dtype).itemsize
    bc, bt = mamba_conv_tile(t, inner, state_cols, itemsize)
    c = inner + 2 * state_cols
    # x is as wide as z: xBC starts nx blocks into in_proj's rows
    nx, nb = inner // bc, state_cols // bc
    nk, per = t // bt, bt // _MAMBA_HALO
    kernel = dict(nx=nx, nb=nb, w=min(bt, _MAMBA_CHUNK))
    grid = (bsz, c // bc, nk)

    def after(k):           # the 128-lane block that follows tile k
        return jnp.minimum((k + 1) * per, t // _MAMBA_HALO - 1)

    def tile(first):        # (bc, bt) of an array, from its block ``first``
        return pl.BlockSpec((1, bc, bt), lambda z, i, k: (z, first + i, k))

    own = tile(nx)
    own_before = pl.BlockSpec(
        (1, bc, _MAMBA_HALO),
        lambda z, i, k: (z, nx + i, jnp.maximum(k * per - 1, 0)))
    own_after = pl.BlockSpec(
        (1, bc, _MAMBA_HALO), lambda z, i, k: (z, nx + i, after(k)))

    def held(lo, count):
        """Of one of the three arrays ``xBC`` splits into, the tile and
        the halo after it: its own block while the grid walks its
        channels, before them its first and after them its last — an
        index that does not move, so nothing is fetched or written back
        for the other two's steps."""
        def index(z, i, k):
            mine = jnp.logical_and(i >= lo, i < lo + count)
            row = jnp.clip(i - lo, 0, count - 1)
            return z, row, jnp.where(
                mine, k, jnp.where(i < lo, 0, nk - 1))

        def halo(z, i, k):
            z, row, k = index(z, i, k)
            return z, row, after(k)
        return (pl.BlockSpec((1, bc, bt), index),
                pl.BlockSpec((1, bc, _MAMBA_HALO), halo))

    parts = [held(0, nx), held(nx, nb), held(nx + nb, nb)]
    wb = pl.BlockSpec((bc, 8), lambda z, i, k: (i, 0))
    need = _mamba_conv_vmem_bytes(bc, bt, itemsize)
    params = _mamba_params(("parallel", "arbitrary", "arbitrary"), need,
                           interpret)

    def shape(*dims, dtype=dtype):
        return jax.ShapeDtypeStruct(dims, dtype)

    fwd = pl.pallas_call(
        functools.partial(_mamba_conv_fwd_kernel, **kernel), grid=grid,
        in_specs=[own, own_before, wb],
        out_specs=[tiles for tiles, _ in parts],
        out_shape=[shape(bsz, inner, t), shape(bsz, state_cols, t),
                   shape(bsz, state_cols, t)],
        scratch_shapes=[pltpu.VMEM((bc, _MAMBA_HALO + bt), dtype)],
        name="mamba_conv_fwd", **params)
    bwd = pl.pallas_call(
        functools.partial(_mamba_conv_bwd_kernel, last=nk - 1,
                          **dict(kernel, w=min(bt, _MAMBA_BWD_CHUNK))),
        grid=grid,
        in_specs=[own, own_before, own_after, wb]
        + [spec for pair in parts for spec in pair],
        out_specs=[tile(0),
                   pl.BlockSpec((1, _MAMBA_TAPS + 1, bc, 128),
                                lambda z, i, k: (z, 0, i, 0))],
        out_shape=[shape(bsz, c, t),
                   shape(bsz, _MAMBA_TAPS + 1, c, 128, dtype=f32)],
        scratch_shapes=[
            pltpu.VMEM((bc, bt + 2 * _MAMBA_HALO), dtype),
            pltpu.VMEM((bc, bt + _MAMBA_HALO), dtype),
            pltpu.VMEM((bc, bt + _MAMBA_HALO), f32)],
        name="mamba_conv_bwd", **params)
    return fwd, bwd


def mamba_conv(zt: jax.Array, conv_w: jax.Array, conv_b: jax.Array, *,
               inner: int, state_cols: int, interpret: bool = False):
    """The Mamba-2 mixer's causal depthwise convolution and SiLU over
    ``xBC``, time on the lanes.

    ``zt`` (B, F, T): ``in_proj``'s result transposed — on a TPU the
    layout XLA gives it, so no copy —, rows ``[z | xBC | dt]`` with
    ``xBC`` = ``[x | B | C]`` (``inner`` + 2 ``state_cols`` rows from
    row ``inner``); ``conv_w`` (4, C) and ``conv_b`` (C,) fp32.  Returns
    ``x`` (B, inner, T), ``B`` and ``C`` (B, state_cols, T) in
    ``zt.dtype``: ``silu(conv_b + sum_j conv_w[j] xBC[t - 3 + j])``
    with zeros before the sequence, in fp32, rounded once.

    One Mosaic call forward, ``mamba_conv_fwd``, and one backward,
    ``mamba_conv_bwd``, under a ``custom_vjp`` whose residuals are its
    inputs (:func:`_mamba_conv_calls`); the cotangent of ``zt`` is zero
    outside ``xBC``'s rows.  For shapes :func:`mamba_runs_kernels`
    takes."""
    bsz, f, t = zt.shape
    c = inner + 2 * state_cols
    fwd, bwd = _mamba_conv_calls(bsz, t, f, inner, state_cols, zt.dtype,
                                 interpret)

    @jax.custom_vjp
    def conv(zt, wb):
        return tuple(fwd(zt, zt, wb))

    def conv_fwd(zt, wb):
        return tuple(fwd(zt, zt, wb)), (zt, wb)

    def conv_bwd(res, cotangents):
        zt, wb = res
        dx, db, dc = cotangents
        dxbc, dwb = bwd(zt, zt, zt, wb, dx, dx, db, db, dc, dc)
        return (jnp.pad(dxbc, [(0, 0), (inner, f - inner - c), (0, 0)]),
                columns(jnp.sum(dwb, axis=(0, 3)).T))

    def columns(v):         # (C, 5) to the (C, 8) operand
        return jnp.pad(v, [(0, 0), (0, 8 - v.shape[1])])

    conv.defvjp(conv_fwd, conv_bwd)
    return conv(zt, columns(jnp.concatenate(
        [conv_w.T, conv_b[:, None]], axis=1).astype(jnp.float32)))


def _mamba_norm_vmem_bytes(rows: int, bt: int, itemsize: int) -> int:
    """VMEM the gated norm's backward call — the larger — holds: the
    fp32 ``y`` and ``dy`` blocks, five blocks in the compute type, the
    scale's column and the channels' sums padded to 128 lanes, each
    double-buffered; 8 MiB for a head's fp32 temporaries.  An upper
    bound: compiled for a v5e at (512, 2048) and at (4096, 256) bf16 the
    call allocates 36.0 MiB where this says 45 and 52."""
    blocks = rows * bt * (2 * 4 + 5 * itemsize) + 2 * rows * 128 * 4
    return 2 * blocks + (8 << 20)


@functools.lru_cache(maxsize=None)
def _mamba_norm_calls(bsz: int, t: int, heads: int, p: int, groups: int,
                      dtype, interpret: bool, eps: float):
    """The gated norm's forward and backward ``pallas_call``: ``y``
    (B, inner, T) fp32, ``x`` (B, inner, T), ``in_proj``'s result
    (B, F, T) whose first ``inner`` rows are ``z``, ``D`` (H,) in SMEM,
    the scale as an (inner, 1) column.  Grid (B, G, T / bt), time
    innermost; a block is all channels of one group."""
    from jax.experimental.pallas import tpu as pltpu

    f32, inner = jnp.float32, heads * p
    rows = inner // groups
    bt = mamba_norm_tile(t, rows)
    grid = (bsz, groups, t // bt)
    block = pl.BlockSpec((1, rows, bt), lambda z, i, k: (z, i, k))
    column = pl.BlockSpec((rows, 1), lambda z, i, k: (i, 0))
    skip = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = dict(p=p, eps=eps)
    need = _mamba_norm_vmem_bytes(rows, bt, jnp.dtype(dtype).itemsize)

    def shape(*dims, dtype=dtype):
        return jax.ShapeDtypeStruct(dims, dtype)

    fwd = pl.pallas_call(
        functools.partial(_mamba_norm_fwd_kernel, **kernel), grid=grid,
        in_specs=[skip, block, block, block, column], out_specs=block,
        out_shape=shape(bsz, inner, t), name="mamba_gated_norm_fwd",
        **_mamba_params(("parallel",) * 3, need, interpret))
    bwd = pl.pallas_call(
        functools.partial(_mamba_norm_bwd_kernel, **kernel), grid=grid,
        in_specs=[skip, block, block, block, column, block],
        out_specs=[block, block, block,
                   pl.BlockSpec((1, heads // groups, 128),
                                lambda z, i, k: (z, i, 0)),
                   pl.BlockSpec((1, rows, 128), lambda z, i, k: (z, i, 0))],
        out_shape=[shape(bsz, inner, t, dtype=f32), shape(bsz, inner, t),
                   shape(bsz, inner, t),
                   shape(bsz, heads, 128, dtype=f32),
                   shape(bsz, inner, 128, dtype=f32)],
        name="mamba_gated_norm_bwd",
        **_mamba_params(("parallel", "parallel", "arbitrary"), need,
                        interpret))
    return fwd, bwd


def mamba_gated_norm(y: jax.Array, x: jax.Array, zt: jax.Array,
                     d_skip: jax.Array, norm_scale: jax.Array, *,
                     groups: int, eps: float, interpret: bool = False):
    """The Mamba-2 mixer's ``D``-skip, gate and grouped RMSNorm, time on
    the lanes: ``v = (y + D x) silu(z)``, ``v rsqrt(mean v^2 + eps)
    norm_scale`` with the mean over the channels of each of ``groups``
    groups, fp32 throughout, cast to ``x.dtype``.

    ``y`` (B, inner, T) fp32 as the scan returns it, ``x`` (B, inner, T)
    as :func:`mamba_conv` does, ``zt`` (B, F, T) ``in_proj``'s result
    transposed, whose first ``inner`` rows are ``z``; ``d_skip`` (H,)
    and ``norm_scale`` (inner,) fp32.  Returns (B, inner, T).

    One Mosaic call forward, ``mamba_gated_norm_fwd``, and one backward,
    ``mamba_gated_norm_bwd`` — the scan's fp32 cotangent, the skip's
    ``dx``, ``dz``, ``dD`` and ``dnorm_scale`` from one reading — under
    a ``custom_vjp`` whose residuals are its inputs; the cotangent of
    ``zt`` is zero below ``z``'s rows.  For shapes
    :func:`mamba_runs_kernels` takes."""
    bsz, inner, t = y.shape
    heads, f = d_skip.shape[0], zt.shape[1]
    fwd, bwd = _mamba_norm_calls(bsz, t, heads, inner // heads, groups,
                                 x.dtype, interpret, float(eps))

    @jax.custom_vjp
    def norm(y, x, zt, d, w):
        return fwd(d, y, x, zt, w)

    def norm_fwd(y, x, zt, d, w):
        return fwd(d, y, x, zt, w), (y, x, zt, d, w)

    def norm_bwd(res, g):
        y, x, zt, d, w = res
        dy, dx, dz, dd, dw = bwd(d, y, x, zt, w, g)
        return (dy, dx, jnp.pad(dz, [(0, 0), (0, f - inner), (0, 0)]),
                jnp.sum(dd, axis=(0, 2)),
                jnp.sum(dw, axis=(0, 2))[:, None])

    norm.defvjp(norm_fwd, norm_bwd)
    return norm(y, x, zt, d_skip.astype(jnp.float32),
                norm_scale.astype(jnp.float32)[:, None])


# ---------------------------------------------------------------------------
# the expert layer's rows-to-tokens sum (held_expert_ffn's combine and the
# transpose of its dispatch)
# ---------------------------------------------------------------------------

_ROW_SUM_GRANULE = 16   # buffer rows a copy fetches: a packed bf16 tile
_ROW_SUM_TRIP = 128     # buffer rows a product takes: the MXU's depth
_ROW_SUM_ABSENT = -(1 << 24)    # ``pos`` of a token with no row in a run


def _row_sum_hold(tt: int, top_k: int, groups: int) -> int:
    """Buffer rows a tile of ``tt`` tokens can own, as the kernel holds
    them: a token's rows are at most ``top_k`` (or ``groups``), and a
    run's stretch reaches into a granule at either end."""
    hold = tt * min(top_k, groups) + 2 * _ROW_SUM_GRANULE * groups
    return -(-hold // _ROW_SUM_TRIP) * _ROW_SUM_TRIP


def _row_sum_vmem_bytes(tt: int, d: int, top_k: int, groups: int,
                        itemsize: int) -> int:
    """VMEM a call holds: two tiles' rows (one summed, one on its way),
    the fp32 sums and a trip's product, the result's block
    double-buffered, a trip's rows and weights, the tokens' (groups, tt)
    blocks, and 2 MiB.  An upper bound."""
    return 2 * _row_sum_hold(tt, top_k, groups) * d * itemsize \
        + 2 * tt * d * 4 + 2 * tt * d * itemsize \
        + _ROW_SUM_TRIP * (d * itemsize + tt * 8) + 4 * groups * tt * 4 \
        + (2 << 20)


def moe_row_sum_tile(tokens: int, d: int, top_k: int, groups: int,
                     itemsize: int) -> Optional[int]:
    """Tokens of a grid step of :func:`moe_row_sum`: 256 where they
    divide the step's and the call fits half a v5e's VMEM, else 128;
    none where ``d`` is no whole lane tiles or neither does.  Read on
    the chip at 4,096 x 3,584 and 8,192 x 2,688 bf16, 3.3k rows landed,
    ms a call, the runs unrolled in the body (PERF.md, PR 39): 256
    tokens by trips of 128 rows 0.113 | 0.140, by 256 rows 0.136 |
    0.140, 128 tokens 0.125 | 0.177 (and its plan three times as dear),
    512 tokens 0.154 | 0.190; without the next tile's rows on their way
    0.224 | 0.253.  The rolled body that ships: 0.129 | 0.165."""
    if d % 128:
        return None
    return next((tt for tt in (256, 128) if tokens % tt == 0
                 and _row_sum_vmem_bytes(tt, d, top_k, groups, itemsize)
                 <= 64 << 20), None)


def moe_row_sum_runs_kernel(tokens: int, d: int, top_k: int, groups: int,
                            dtype, interpret: bool = False) -> bool:
    """Whether :func:`moe_row_sum` runs its kernel: the file's rule
    (:func:`_use_kernel`) and shapes that tile
    (:func:`moe_row_sum_tile`)."""
    return bool(_use_kernel(interpret) and moe_row_sum_tile(
        tokens, d, top_k, groups, jnp.dtype(dtype).itemsize))


def _earlier(n: int) -> jax.Array:
    """(n, n) bool: at ``[i, j]`` whether ``j`` comes before ``i``."""
    at = jnp.arange(n)
    return at[None, :] < at[:, None]


def moe_row_sum_plan(local: jax.Array, group_sizes: jax.Array,
                     weights: jax.Array, tile: int):
    """Where each tile of tokens finds its rows in a buffer sorted by
    :func:`~horovod_tpu.parallel.expert.held_assignments`.

    ``local`` (tokens * top_k,): the held expert each flat assignment
    lands on, ``groups`` where it lands on none; ``group_sizes``
    (groups,); ``weights`` (tokens, top_k).  The sort is stable, so
    inside expert ``g``'s run the rows ascend by token and a token occurs
    at most once (top-k picks distinct experts): a tile owns one
    contiguous stretch of each run, which starts where the run does plus
    the run's assignments among earlier tokens.

    Returns ``(starts, pos, wt)``: ``starts`` ((tokens / tile + 1) *
    groups,) int32, at ``i * groups + g`` the buffer row where tile
    ``i``'s stretch of run ``g`` starts (and tile ``i - 1``'s ends);
    ``pos`` (groups, tokens) int32, a token's place in its tile's stretch
    of run ``g``, far below zero where it has no row there; ``wt``
    (groups, tokens), the weight of the token's choice that is held
    expert ``g``, zero where none is."""
    groups = group_sizes.shape[0]
    tokens, top_k = weights.shape
    hit = local.reshape(tokens, 1, top_k) \
        == jnp.arange(groups, dtype=local.dtype)[None, :, None]
    member = jnp.any(hit, axis=-1).reshape(tokens // tile, tile, groups)
    # how many came before, as sums under a mask and, inside a tile, one
    # small product (0 / 1 operands: exact) — not ``jnp.cumsum``, whose
    # reduce-window XLA rewrites into operations that carry no name, so
    # that no reader of the step by scope would find them
    earlier = _earlier(tile)
    rank = jnp.einsum("st,ntg->nsg", earlier.astype(jnp.float32),
                      member.astype(jnp.float32)).astype(jnp.int32)
    counts = jnp.sum(member, axis=1, dtype=jnp.int32)
    sizes = group_sizes.astype(jnp.int32)
    offset = jnp.sum(jnp.where(_earlier(groups), sizes[None, :], 0), axis=1)
    before = jnp.sum(jnp.where(_earlier(tokens // tile)[:, :, None],
                               counts[None], 0), axis=1)
    starts = jnp.concatenate([offset[None] + before,
                              (offset + sizes)[None]])
    pos = jnp.where(member, rank, _ROW_SUM_ABSENT).reshape(tokens, groups)
    wt = jnp.sum(jnp.where(hit, weights[:, None, :], 0.0), axis=-1)
    return starts.reshape(-1), pos.T.astype(jnp.int32), wt.T


def _row_sum_kernel(starts_ref, pos_ref, wt_ref, rows_ref, out_ref, buf_ref,
                    acc_ref, edge_ref, sem, *, groups: int):
    """One tile of tokens.  Its stretch of every run is fetched from HBM
    a granule a copy into one half of ``buf_ref``, the stretches one
    after another — the step before started those copies, and this one
    starts the next tile's into the other half before it waits for its
    own.  Then, 128 buffer rows a trip: the (128, tokens) matrix that
    holds a token's weight where the row is its own, times the rows, on
    the MXU, added in fp32; rounded once at the end.  Rows that are no
    one's — a granule's edges, what an earlier step left in the buffer —
    are zeroed by select first: they may hold anything.  The runs are
    walked by loops, not unrolled: the body is traced and lowered once a
    buffer size and side of every expert layer, and a start pays it."""
    from jax.experimental.pallas import tpu as pltpu

    gr, kb = _ROW_SUM_GRANULE, _ROW_SUM_TRIP
    i = pl.program_id(0)
    tt = out_ref.shape[0]
    slot = i % 2

    def granule(ref, at):
        return ref.at[pl.ds(pl.multiple_of(at * gr, gr), gr)]

    def stretches(tile, fetch_into=None):
        """The granules ``tile``'s stretches take together.  With
        ``fetch_into`` the copies into that half are started; without,
        ``edge_ref`` gets the first and last (exclusive) buffer row of
        each run's stretch."""
        def run(g, taken):
            lo = starts_ref[tile * groups + g]
            hi = starts_ref[(tile + 1) * groups + g]
            head = lo // gr
            n = jnp.where(hi > lo, (hi + gr - 1) // gr - head, 0)
            if fetch_into is None:
                edge_ref[0, g] = taken * gr + lo - head * gr
                edge_ref[1, g] = taken * gr + hi - head * gr
            else:
                def fetch(k, carry):
                    pltpu.make_async_copy(
                        granule(rows_ref, head + k),
                        granule(buf_ref.at[fetch_into], taken + k),
                        sem.at[fetch_into]).start()
                    return carry

                jax.lax.fori_loop(0, n, fetch, 0)
            return taken + n

        return jax.lax.fori_loop(0, groups, run, jnp.int32(0))

    @pl.when(i == 0)
    def _():
        stretches(i, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        stretches(i + 1, 1 - slot)

    taken = stretches(i)

    def landed(k, carry):       # one wait a copy, each of a granule
        pltpu.make_async_copy(granule(rows_ref, 0),
                              granule(buf_ref.at[slot], 0),
                              sem.at[slot]).wait()
        return carry

    jax.lax.fori_loop(0, taken, landed, 0)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def trip(b, carry):
        at = pl.multiple_of(b * kb, kb)
        row = at + jax.lax.broadcasted_iota(jnp.int32, (kb, 1), 0)
        cell = at + jax.lax.broadcasted_iota(jnp.int32, (kb, tt), 0)

        def run(g, carry):
            own, weight = carry
            first, last = edge_ref[0, g], edge_ref[1, g]
            mine = (row >= first) & (row < last)
            hit = pos_ref[pl.ds(g, 1), :] + first == cell
            return (own + mine.astype(jnp.float32),
                    weight + jnp.where(hit, wt_ref[pl.ds(g, 1), :], 0.0))

        own, weight = jax.lax.fori_loop(
            0, groups, run, (jnp.zeros((kb, 1), jnp.float32),
                             jnp.zeros((kb, tt), jnp.float32)))
        chunk = jnp.where(own > 0, buf_ref[slot, pl.ds(at, kb), :], 0)
        acc_ref[...] += jax.lax.dot_general(
            weight.astype(chunk.dtype), chunk, _TN,
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, (taken * gr + kb - 1) // kb, trip, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


# cached: a model's layers and a layer's buffers share their calls
@functools.lru_cache(maxsize=None)
def _row_sum_call(tokens: int, d: int, groups: int, top_k: int, dtype,
                  interpret: bool):
    """The ``pallas_call``: ``starts`` by scalar prefetch, ``pos`` and
    ``wt`` (groups, tokens) a (groups, tt) block a step, the rows left
    where they are (HBM, any number of them), the result (tokens, d) a
    (tt, d) block a step.  The steps run in order: each fetches for the
    next."""
    from jax.experimental.pallas import tpu as pltpu

    itemsize = jnp.dtype(dtype).itemsize
    tt = moe_row_sum_tile(tokens, d, top_k, groups, itemsize)
    need = _row_sum_vmem_bytes(tt, d, top_k, groups, itemsize)
    block = pl.BlockSpec((groups, tt), lambda i, starts: (0, i))
    return pl.pallas_call(
        functools.partial(_row_sum_kernel, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tokens // tt,),
            in_specs=[block, block, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tt, d), lambda i, starts: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, _row_sum_hold(tt, top_k, groups), d), dtype),
                pltpu.VMEM((tt, d), jnp.float32),
                pltpu.SMEM((2, groups), jnp.int32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((tokens, d), dtype),
        name="moe_row_sum",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need if need > _MOSAIC_VMEM_SCOPE else None),
        interpret=interpret)


def moe_row_sum(rows: jax.Array, starts: jax.Array, pos: jax.Array,
                wt: jax.Array, *, top_k: int,
                interpret: bool = False) -> jax.Array:
    """The weighted sum of a sorted buffer's rows into their tokens:
    ``out[t] = sum over runs g where t has a row of wt[g, t] * that
    row``, in one pass over the rows that landed, with no scatter.

    ``rows`` (cap, d): the buffer
    :func:`~horovod_tpu.parallel.expert.held_expert_ffn` sorts by held
    expert, any ``cap`` that holds the rows that landed; what stands
    past them is never read into a token.  ``starts`` and ``pos`` from
    :func:`moe_row_sum_plan` at :func:`moe_row_sum_tile`'s tile; ``wt``
    (groups, tokens): a token's weight in run ``g`` (rounded to
    ``rows.dtype`` as a factor of the product).  Returns (tokens, d) in
    ``rows.dtype``, added in fp32 and rounded once.

    One Mosaic call, ``moe_row_sum`` (:func:`_row_sum_kernel`).  It
    leans on the stable sort: inside a run the rows ascend by token and
    no token repeats, so a tile of tokens owns one contiguous stretch of
    each run and ``pos`` says which of its rows is whose.  For shapes
    :func:`moe_row_sum_runs_kernel` takes."""
    groups, tokens = pos.shape
    call = _row_sum_call(tokens, rows.shape[1], groups, top_k, rows.dtype,
                         interpret)
    return call(starts, pos, wt.astype(jnp.float32), rows)
