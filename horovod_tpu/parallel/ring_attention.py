"""Ring attention: exact long-context attention over a sequence axis.

Q stays put; K/V blocks rotate around the mesh axis with
``lax.ppermute`` while each shard folds the visiting block into a
numerically-stable online-softmax accumulator (the blockwise/flash
recurrence).  After ``world`` steps every query has attended to the full
global sequence, using only neighbor exchanges that ride the ICI torus —
no shard ever materializes the full K/V or the (T, T) score matrix, so
context length scales linearly with the number of chips.

Two formulations share this contract:

* the **fused** path (:func:`~horovod_tpu.ops.pallas_kernels.
  ring_flash_attention`) consumes each visiting K/V block with the
  Pallas flash kernels — no per-block score tensor, the next hop's
  ``ppermute`` double-buffered behind the current block's compute —
  gated by :func:`~horovod_tpu.ops.pallas_kernels.
  resolve_fused_collectives` (``HOROVOD_SP_FUSED_RING``, falling back
  to ``HOROVOD_FUSED_COLLECTIVES``);
* the **jnp** fallback below, the identical online-softmax math in
  plain jnp, kept for shards off the flash tiling contract and for
  CPU-twin oracles.

Both understand the ``contiguous`` and ``zigzag`` sequence layouts
(``HOROVOD_SP_LAYOUT``): under zigzag each shard holds an early and a
late chunk of the global sequence so causal mask work load-balances
across ranks (docs/fused_kernels.md "Ring-flash attention").

This is an extension beyond the reference (SURVEY §5.7: sequence
parallelism is absent there; its ``alltoall`` primitive is the closest
building block — see :mod:`~horovod_tpu.parallel.ulysses` for the
alltoall formulation).

Call inside ``shard_map`` with the sequence dimension sharded over
``axis_name``.  Differentiable by construction: the jnp path's autodiff
flows through the scan and ``ppermute`` (whose transpose is the inverse
rotation), and the fused path carries its own ``custom_vjp`` ring.
"""

from __future__ import annotations

import os
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _resolve_fused(fused: Union[bool, str, None]) -> bool:
    """Normalize the ``fused`` knob to a bool.

    ``None`` reads ``HOROVOD_SP_FUSED_RING`` then
    ``HOROVOD_FUSED_COLLECTIVES`` (default ``auto`` = TPU only); a bool
    passes through; a mode string goes to ``resolve_fused_collectives``.
    """
    from horovod_tpu.ops.pallas_kernels import resolve_fused_collectives

    if isinstance(fused, bool):
        return fused
    if fused is None:
        fused = os.environ.get(
            "HOROVOD_SP_FUSED_RING",
            os.environ.get("HOROVOD_FUSED_COLLECTIVES", "auto"))
    return resolve_fused_collectives(fused)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, causal: bool = False,
                   scale: Optional[float] = None,
                   fused: Union[bool, str, None] = None,
                   layout: Optional[str] = None,
                   block_q: int = 512, block_k: int = 512,
                   interpret: bool = False) -> jax.Array:
    """Exact attention with K/V ring-rotated over ``axis_name``.

    Args:
      q, k, v: per-shard blocks ``(batch, seq_local, heads, head_dim)``;
        the global sequence is the concatenation of shards in axis order
        (chunk order under ``layout="zigzag"`` — see
        :func:`~horovod_tpu.ops.pallas_kernels.ring_layout_positions`).
      axis_name: mesh axis the sequence is sharded over.
      causal: apply a causal mask in *global* sequence positions.
      scale: score scale; default ``head_dim ** -0.5``.
      fused: ``True``/``False``, an ``"auto"|"on"|"off"`` mode string,
        or ``None`` to read ``HOROVOD_SP_FUSED_RING`` (fallback
        ``HOROVOD_FUSED_COLLECTIVES``, default ``auto``).  Even when
        resolved on, shards off the flash tiling contract silently take
        the jnp formulation — same numerics, same ring wire.
      layout: ``"contiguous"`` (default; env ``HOROVOD_SP_LAYOUT``) or
        ``"zigzag"``.
      block_q, block_k: flash tile sizes for the fused path.
      interpret: run the fused path's Pallas kernels in interpreter
        mode (CPU tests).

    Returns:
      Attention output ``(batch, seq_local, heads, head_dim)``, the exact
      softmax attention over the full global sequence.
    """
    from horovod_tpu.ops import pallas_kernels as _pk

    if layout is None:
        layout = os.environ.get("HOROVOD_SP_LAYOUT", "contiguous")
    if layout not in _pk.RING_LAYOUTS:
        raise ValueError(
            f"sp layout must be one of {_pk.RING_LAYOUTS}, got {layout!r}")

    world = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = d ** -0.5 if scale is None else scale

    fits = (tq == tk and k.shape == q.shape and v.shape == q.shape
            and _pk.fit_flash_block(tq, block_q) is not None
            and _pk.fit_flash_block(tk, block_k) is not None
            and not (layout == "zigzag" and tq % 2))
    if fits and _resolve_fused(fused) and _pk._use_kernel(interpret):
        return _pk.ring_flash_attention(
            q, k, v, axis_name, causal=causal, scale=scale,
            layout=layout, block_q=block_q, block_k=block_k,
            interpret=interpret)

    qf = q.astype(jnp.float32)
    # send K/V to the next shard: after s steps we hold the block that
    # started at shard (my_idx - s) % world
    perm = [(i, (i + 1) % world) for i in range(world)]

    q_pos = _pk.ring_layout_positions(my_idx, world, tq, layout)
    kpos0 = (q_pos if tq == tk
             else _pk.ring_layout_positions(my_idx, world, tk, layout))

    def step(carry, _):
        o, m, l, k_cur, v_cur, kp_cur = carry
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf,
                            k_cur.astype(jnp.float32)) * scale
        if causal:
            # global positions travel with the block (layout-aware)
            allowed = q_pos[:, None] >= kp_cur[None, :]        # (tq, tk)
            scores = jnp.where(allowed[None, None], scores, _NEG_INF)
            allowed_f = allowed.astype(jnp.float32)[None, None]
        else:
            allowed_f = jnp.float32(1.0)
        m_new = jnp.maximum(m, scores.max(axis=-1))           # (b, h, tq)
        # multiply by the mask so fully-masked blocks contribute exactly 0
        # even while m_new is still at the -inf sentinel
        p = jnp.exp(scores - m_new[..., None]) * allowed_f    # (b, h, tq, tk)
        corr = jnp.exp(m - m_new)                             # (b, h, tq)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32))
        o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
        k_nxt, v_nxt, kp_nxt = lax.ppermute((k_cur, v_cur, kp_cur),
                                            axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt, kp_nxt), None

    o0 = jnp.zeros((b, tq, h, d), jnp.float32)
    m0 = jnp.full((b, h, tq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    (o, m, l, _, _, _), _ = lax.scan(step, (o0, m0, l0, k, v, kpos0),
                                     jnp.arange(world))
    denom = jnp.maximum(l, jnp.float32(1e-30)).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False,
                        scale: Optional[float] = None) -> jax.Array:
    """Plain single-device softmax attention (the numerics oracle for
    ring/ulysses tests, and the local attention inside Ulysses)."""
    d = q.shape[-1]
    scale = d ** -0.5 if scale is None else scale
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        allowed = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        scores = jnp.where(allowed[None, None], scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
