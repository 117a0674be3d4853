"""Decoder-only transformer LM — the long-context flagship.

TPU-first design choices:

* matmul-heavy blocks sized for the MXU, bf16 compute / fp32 params;
* RMSNorm + rotary embeddings (no learned position table to shard);
* tensor parallelism by annotation:
  :class:`~horovod_tpu.parallel.tensor_parallel.ColumnParallelDense` /
  ``RowParallelDense`` carry kernel partition specs, so under ``jit``
  over a mesh with a ``tp`` axis XLA places one reduction per block;
* sequence parallelism by construction: ``attention_impl="ring"`` or
  ``"ulysses"`` wraps the attention core in ``shard_map`` over the
  ``sp`` axis (ring ppermute / all_to_all head exchange), enabling
  contexts that exceed one chip's HBM;
* ``remat`` applies ``jax.checkpoint`` per block — recompute activations
  in backward instead of holding them in HBM.

The reference has no model zoo beyond examples; this plays the role of
its ResNet-50 benchmark flagship (``examples/tensorflow2_synthetic_benchmark.py``)
for the long-context/LLM regime.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel.mesh import AXIS_SP, AXIS_TP
from horovod_tpu.parallel.ring_attention import (
    reference_attention,
    ring_attention,
)
from horovod_tpu.parallel.tensor_parallel import (
    ColumnParallelDense,
    RowParallelDense,
)
from horovod_tpu.parallel.ulysses import ulysses_attention
from horovod_tpu.utils import logging as hvd_logging


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 32_000
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    attention_impl: str = "dense"       # dense | flash | ring | ulysses
    flash_block: int = 512              # flash q/k block (512 = round-4
                                        # measured winner; autotunable)
    causal: bool = True                 # False: bidirectional (ViT/BERT)
    sp_axis: str = AXIS_SP
    tp_axis: str = AXIS_TP
    remat: bool = False
    # per-block remat tier (none|dots|full|offload) — overrides the
    # boolean when set; resolution order and the memory/recompute
    # trade of each tier: memory/remat.py, docs/memory.md
    remat_policy: Optional[str] = None
    # tile-fused matmul⊗collective kernels at the tp boundaries
    # (HOROVOD_FUSED_COLLECTIVES, docs/fused_kernels.md) — consumed by
    # :func:`fused_tp_apply`, the explicit shard_map execution mode,
    # and by the ring attention dispatch (``attention_impl="ring"``:
    # "auto" defers to HOROVOD_SP_FUSED_RING / HOROVOD_FUSED_COLLECTIVES
    # so env knobs stay live; "on"/"off" here wins).  The GSPMD modules
    # below ignore it (XLA owns their collectives)
    fused_collectives: str = "auto"     # auto | on | off
    # sp sequence layout for the ring path — None defers to
    # HOROVOD_SP_LAYOUT (default "contiguous"); "zigzag" load-balances
    # the causal mask across ranks (docs/fused_kernels.md)
    sp_layout: Optional[str] = None     # None | contiguous | zigzag
    # run the flash/ring-flash Pallas kernels in interpreter mode so
    # the CPU twin exercises the REAL blocked memory behavior instead
    # of the dense jnp fallback (which materializes the (T, T) scores
    # the kernels exist to avoid) — bench/test plumbing, never on-TPU
    flash_interpret: bool = False
    # grouped-query attention: key/value heads, each shared by
    # num_heads // num_kv_heads query heads (None: one a query head)
    num_kv_heads: Optional[int] = None
    # a head's width where it is not d_model // num_heads (the
    # projections are then num_heads * head_width wide, not d_model)
    head_width: Optional[int] = None
    rotary: bool = True                 # False: no positional term
    # the softmax scale where it is not head_dim ** -0.5 (a muP model's
    # attention multiplier), on the dense, flash and ring paths
    attention_scale: Optional[float] = None

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    def tpu_efficiency_hints(self) -> list:
        """Measured-on-v5e shape advice (PERF_NOTES.md round 4): the MXU
        is a 128x128 systolic array, and head_dim 64 configs measured
        12-13 MFU points below head_dim 128 at every model size.
        Returns human-readable hints (empty = no issues)."""
        hints = []
        if self.d_model % 128:
            hints.append(
                f"d_model {self.d_model} is not a multiple of 128; "
                f"matmul tiles will be padded")
        elif self.head_dim < 128:
            # suggest only divisors of d_model so the advised config is
            # always constructible; d_model % 128 == 0 guarantees one
            suggestion = next(h for h in range(self.d_model // 128, 0, -1)
                              if self.d_model % h == 0)
            hints.append(
                f"head_dim {self.head_dim} < 128 underfills the MXU "
                f"(128-lane systolic array): fewer, wider heads measured "
                f"+12-13 MFU points on v5e (PERF_NOTES.md); consider "
                f"num_heads={suggestion}")
        return hints


def yarn_frequencies(dim: int, base: float, factor: float,
                     original_max: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0) -> jax.Array:
    """YaRN's ``dim // 2`` rotation frequencies (Peng et al. 2023, as
    the DeepSeek-family modelling code computes them): pair ``i`` of
    plain RoPE turns at ``base ** (-2i / dim)``; pairs that complete
    more than ``beta_fast`` turns inside the original context keep that
    frequency, pairs that complete fewer than ``beta_slow`` are slowed
    by ``factor``, and between the two pair indices (floor and ceiling
    of where those counts fall, clipped to the pairs there are) the two
    are blended linearly."""
    def pair_that_turns(times: float) -> float:
        return dim * math.log(original_max / (times * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair_that_turns(beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(beta_slow)), dim - 1)
    plain = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    slowed = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                      / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * slowed + plain * (1.0 - slowed)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """``0.1 * mscale * ln(factor) + 1`` (1 where nothing is scaled)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_embedding(x: jax.Array, positions: jax.Array,
                     base: float = 10_000.0,
                     inv_freq: Optional[jax.Array] = None) -> jax.Array:
    """Rotate pairs of head dims by position-dependent angles (RoPE).
    ``x``: (b, t, h, d); ``positions``: (t,) global positions — under
    sequence parallelism each shard passes its global offsets.
    ``inv_freq``: the ``d // 2`` frequencies, where they are not plain
    RoPE's at ``base`` (:func:`yarn_frequencies`)."""
    d = x.shape[-1]
    if inv_freq is None:
        inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32)
                                   / d))
    angles = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class RMSNorm(nn.Module):
    epsilon: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.epsilon)
        return (y * scale).astype(x.dtype)


_warned_replicated: set = set()   # (q shape, axes left out) already reported


def _over_ambient_mesh(kernel, q, k, v, cfg):
    """Run an attention ``kernel`` on ``(batch, seq, heads, head_dim)``
    operands wherever a mesh is ambient (``cfg``: whatever names the
    model's ``tp_axis`` and ``sp_axis``).

    A Mosaic call has no GSPMD partitioning rule: outside ``shard_map``
    jax refuses to lower one for more than one device ("Mosaic kernels
    cannot be automatically partitioned").  Attention is independent
    per (batch row, head), so under an ambient mesh —
    ``DistributedTrainStep``'s pjit mode, ``jax.set_mesh`` — the kernel
    runs in a ``shard_map`` over every axis of it: heads over the
    model's tp axis, batch rows over the axes that are not the model's
    own (replica axes, from this model's point of view), each where
    its extent divides the dimension.  An axis left out makes its
    devices gather the operands and run the kernel over the same rows
    — right result, replicated work — so it is reported, once a shape.
    With no mesh, one device, or inside an enclosing ``shard_map``
    (axes already Manual) the kernel is called as it is."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty or am.size == 1 or am.manual_axes:
        return kernel(q, k, v)
    b, _, h, _ = q.shape
    tp_extent = am.shape.get(cfg.tp_axis)
    tp = cfg.tp_axis if tp_extent and h % tp_extent == 0 else None
    batch_axes, extent = [], 1
    for name, n in am.shape.items():
        if name not in (cfg.tp_axis, cfg.sp_axis) \
                and b % (extent * n) == 0:
            batch_axes.append(name)
            extent *= n
    left_out = tuple(name for name, n in am.shape.items()
                     if n > 1 and name != tp and name not in batch_axes)
    if left_out and (q.shape, left_out) not in _warned_replicated:
        _warned_replicated.add((q.shape, left_out))
        hvd_logging.warning(
            "attention kernel on q%s (batch, seq, heads, head_dim): mesh "
            "axes %s of %s divide neither its batch nor (tp) its heads; "
            "every device along them runs the kernel over the same rows",
            tuple(q.shape), left_out, dict(am.shape))
    spec = P(tuple(batch_axes) or None, None, tp, None)
    return jax.shard_map(kernel, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h, kv, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        if h % kv:
            raise ValueError(f"num_heads {h} is no multiple of "
                             f"num_kv_heads {kv}")
        # fused QKV projection, column-parallel over tp (heads shard)
        qkv = ColumnParallelDense((h + 2 * kv) * d, axis=cfg.tp_axis,
                                  use_bias=False, dtype=cfg.dtype,
                                  name="qkv")(x)
        q, k, v = jnp.split(qkv, [h * d, (h + kv) * d], axis=-1)
        q = q.reshape(x.shape[:2] + (h, d))
        k, v = (t.reshape(x.shape[:2] + (kv, d)) for t in (k, v))
        if cfg.rotary:
            q = rotary_embedding(q, positions)
            k = rotary_embedding(k, positions)
        if kv != h:
            # query head i reads key/value head i // (h // kv): the
            # kernels below take equal head counts
            k, v = (jnp.repeat(t, h // kv, axis=2) for t in (k, v))

        if cfg.attention_impl == "dense":
            o = reference_attention(q, k, v, causal=cfg.causal,
                                    scale=cfg.attention_scale)
        elif cfg.attention_impl == "flash":
            from horovod_tpu.ops.pallas_kernels import flash_attention

            o = _over_ambient_mesh(
                partial(flash_attention, causal=cfg.causal,
                        scale=cfg.attention_scale,
                        block_q=cfg.flash_block, block_k=cfg.flash_block,
                        interpret=cfg.flash_interpret),
                q, k, v, cfg)
        elif cfg.attention_impl == "ring":
            # "auto" stays None so the HOROVOD_SP_* env knobs resolve
            # inside the dispatch; an explicit config "on"/"off" wins
            o = ring_attention(
                q, k, v, cfg.sp_axis, causal=cfg.causal,
                scale=cfg.attention_scale,
                fused=(None if cfg.fused_collectives == "auto"
                       else cfg.fused_collectives),
                layout=cfg.sp_layout,
                block_q=cfg.flash_block, block_k=cfg.flash_block,
                interpret=cfg.flash_interpret)
        elif cfg.attention_impl == "ulysses":
            if cfg.attention_scale is not None:
                raise ValueError("attention_scale is read by the dense, "
                                 "flash and ring paths, not by ulysses")
            o = ulysses_attention(q, k, v, cfg.sp_axis, causal=cfg.causal)
        else:
            raise ValueError(
                f"unknown attention_impl {cfg.attention_impl!r}")
        o = o.reshape(x.shape[:2] + (h * d,))
        # output projection, row-parallel: closes the block's tp reduction
        return RowParallelDense(cfg.d_model, axis=cfg.tp_axis,
                                use_bias=False, dtype=cfg.dtype,
                                name="proj")(o)


@dataclasses.dataclass
class LatentAttentionConfig:
    """Multi-head latent attention (DeepSeek-V2's MLA) in its training
    form: queries and keys/values come up from low-rank latents, a head's
    query and key are ``nope_dim + rope_dim`` wide, its value ``v_dim``."""

    d_model: int = 3584
    num_heads: int = 32
    q_rank: int = 768
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    norm_eps: float = 1e-6
    rope_base: float = 10_000.0
    # YaRN: {"factor", "original_max_position_embeddings", "beta_fast",
    # "beta_slow", "mscale", "mscale_all_dim"}; None: plain RoPE
    rope_scaling: Optional[dict] = None
    dtype: Any = jnp.bfloat16
    attention_impl: str = "dense"       # dense | flash
    flash_block: int = 512
    flash_interpret: bool = False
    sp_axis: str = AXIS_SP
    tp_axis: str = AXIS_TP

    def __post_init__(self):
        y = self.rope_scaling
        if y and y["mscale"] != y["mscale_all_dim"]:
            raise ValueError(
                "rope_scaling: YaRN scales cos and sin by mscale(factor, "
                "mscale) / mscale(factor, mscale_all_dim); this module "
                f"runs the ratio 1 only, got {y}")

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    def inv_freq(self) -> Optional[jax.Array]:
        y = self.rope_scaling
        if not y:
            return None
        return yarn_frequencies(
            self.rope_dim, self.rope_base, y["factor"],
            y["original_max_position_embeddings"], y["beta_fast"],
            y["beta_slow"])

    def softmax_scale(self) -> float:
        """``qk_dim ** -0.5``, times YaRN's ``mscale(factor,
        mscale_all_dim)`` squared where the frequencies are scaled."""
        y = self.rope_scaling
        m = yarn_mscale(y["factor"], y["mscale_all_dim"]) if y else 1.0
        return self.qk_dim ** -0.5 * m * m


class LatentAttention(nn.Module):
    """``c_q = RMSNorm(x W_dq)``, ``q = c_q W_uq`` → heads × [nope |
    rope]; ``[c_kv | k_r] = x W_dkv``, ``[k_nope | v] = RMSNorm(c_kv)
    W_ukv``; the rope parts rotated (pairs ``(2i, 2i+1)``), the one
    rotary key a token read by every head; ``softmax(q kᵀ s) v W_o``
    with q and k wider than v.  No bias anywhere.  The up-projections
    shard over tp by heads, the latents are whole on every rank."""

    cfg: LatentAttentionConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h, nope, rope, dv = (cfg.num_heads, cfg.nope_dim, cfg.rope_dim,
                             cfg.v_dim)
        lead = x.shape[:2]

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                            name=name)

        def up(features, name):
            return ColumnParallelDense(features, axis=cfg.tp_axis,
                                       use_bias=False, dtype=cfg.dtype,
                                       name=name)

        c_q = RMSNorm(epsilon=cfg.norm_eps, name="q_a_norm")(
            dense(cfg.q_rank, "q_a")(x))
        q = up(h * (nope + rope), "q_b")(c_q).reshape(lead + (h, nope + rope))
        c_kv, k_r = jnp.split(dense(cfg.kv_rank + rope, "kv_a")(x),
                              [cfg.kv_rank], axis=-1)
        c_kv = RMSNorm(epsilon=cfg.norm_eps, name="kv_a_norm")(c_kv)
        k_nope, v = jnp.split(
            up(h * (nope + dv), "kv_b")(c_kv).reshape(lead + (h, nope + dv)),
            [nope], axis=-1)
        inv_freq = cfg.inv_freq()
        q_r = rotary_embedding(q[..., nope:], positions, cfg.rope_base,
                               inv_freq)
        k_r = rotary_embedding(k_r[:, :, None, :], positions, cfg.rope_base,
                               inv_freq)
        q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r, lead + (h, rope))], axis=-1)

        scale = cfg.softmax_scale()
        if cfg.attention_impl == "dense":
            o = reference_attention(q, k, v, causal=True, scale=scale)
        elif cfg.attention_impl == "flash":
            from horovod_tpu.ops.pallas_kernels import flash_attention

            o = _over_ambient_mesh(
                partial(flash_attention, causal=True, scale=scale,
                        block_q=cfg.flash_block, block_k=cfg.flash_block,
                        interpret=cfg.flash_interpret),
                q, k, v, cfg)
        else:
            raise ValueError(f"latent attention runs dense or flash, got "
                             f"attention_impl {cfg.attention_impl!r}")
        return RowParallelDense(cfg.d_model, axis=cfg.tp_axis,
                                use_bias=False, dtype=cfg.dtype,
                                name="o")(o.reshape(lead + (h * dv,)))


class MlpBlock(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = ColumnParallelDense(cfg.d_ff, axis=cfg.tp_axis, use_bias=False,
                                dtype=cfg.dtype, name="wi")(x)
        h = nn.gelu(h)
        return RowParallelDense(cfg.d_model, axis=cfg.tp_axis,
                                use_bias=False, dtype=cfg.dtype,
                                name="wo")(h)


class Block(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        x = x + Attention(self.cfg, name="attn")(
            RMSNorm(name="ln1")(x), positions)
        x = x + MlpBlock(self.cfg, name="mlp")(RMSNorm(name="ln2")(x))
        return x


_hinted_shapes: set = set()   # perf hints emitted once per shape


class TransformerLM(nn.Module):
    """``apply(variables, tokens, positions=None) -> logits``.

    ``tokens``: (batch, seq_local) int32.  ``positions``: (seq_local,)
    global positions; defaults to ``arange`` (correct without sequence
    parallelism — under SP pass each shard's global offsets).

    Execution modes: under ``jit`` with an ambient mesh
    (``jax.set_mesh``; ``DistributedTrainStep``'s pjit mode) the
    tp-annotated kernels shard automatically (GSPMD).  Under
    ``shard_map`` (required for ``attention_impl="ring"``/``"ulysses"``)
    the same variables run as ``init`` returns them: the tp modules
    skip their sharding constraints where the axes are Manual.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions: Optional[jax.Array] = None):
        cfg = self.cfg
        shape_key = (cfg.d_model, cfg.num_heads)
        if shape_key not in _hinted_shapes:     # once per process, cheap
            import horovod_tpu

            # only mark hinted once a TPU was actually present — a CPU
            # trace before hvd.init() must not suppress the hint forever
            if horovod_tpu.tpu_available():
                _hinted_shapes.add(shape_key)
                for hint in cfg.tpu_efficiency_hints():
                    hvd_logging.info("TransformerLM perf hint: %s", hint)
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        emb = nn.Embed(cfg.vocab_size, cfg.d_model,
                       dtype=cfg.dtype,
                       embedding_init=nn.initializers.normal(0.02),
                       name="embed")
        x = emb(tokens)
        from horovod_tpu.memory.remat import remat_block, \
            resolve_remat_policy

        block = remat_block(
            Block, resolve_remat_policy(cfg.remat_policy, cfg.remat))
        for i in range(cfg.num_layers):
            x = block(cfg, name=f"layer_{i}")(x, positions)
        # the scope ``head`` (docs/metrics.md): final norm and logits
        with jax.named_scope("head"):
            x = RMSNorm(name="ln_f")(x)
            # tied output head: logits in fp32 for a stable softmax
            return emb.attend(x.astype(jnp.float32))


def lm_loss(variables, model: TransformerLM, tokens: jax.Array,
            positions: Optional[jax.Array] = None) -> jax.Array:
    """Next-token cross-entropy (mean over the local shard)."""
    logits = model.apply(variables, tokens[:, :-1],
                         positions[:-1] if positions is not None else None)
    import optax

    with jax.named_scope("loss"):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]).mean()


# ---------------------------------------------------------------------------
# tile-fused sequence-parallel execution mode (docs/fused_kernels.md)
# ---------------------------------------------------------------------------

def _rms(x, scale, epsilon=1e-6):
    """RMSNorm as a function of the unboxed ``scale`` param — the exact
    math of :class:`RMSNorm` (per-token, so it runs on token shards)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + epsilon)
    return (y * scale).astype(x.dtype)


def fused_tp_apply(variables, cfg: TransformerConfig, tokens: jax.Array,
                   positions: Optional[jax.Array] = None,
                   fused: Optional[bool] = None,
                   interpret: bool = False) -> jax.Array:
    """TransformerLM forward with tile-fused collectives at every
    tensor-parallel boundary — the explicit shard_map twin of
    ``TransformerLM.apply``.

    Run inside ``shard_map`` over ``cfg.tp_axis`` with replicated
    variables, as ``init`` returns them or unboxed; returns the same
    logits as the GSPMD ``apply``.  Where the annotated modules close
    each block with one boundary-wide psum, this path restructures to
    Megatron-SP: activations stay **token-sharded** between blocks
    (RMSNorm and residuals are per-token), each column boundary gathers
    tokens *inside* the matmul
    (:func:`~horovod_tpu.parallel.tensor_parallel.column_parallel_dense_ag`)
    and each row boundary reduce-scatters them back
    (:func:`~horovod_tpu.parallel.tensor_parallel.row_parallel_dense_rs`)
    — tile k's wire hides under tile k+1's MXU compute, so no serial
    full-width collective survives at any parallelism boundary (the
    HLO guard pins ring permutes, zero all-reduces).  The one
    remaining gather is the final-logits all-gather after ``ln_f``.

    Shape contract: ``seq % tp``, ``num_heads % tp`` and
    ``d_ff % tp`` must be 0.  ``fused=None`` resolves
    ``cfg.fused_collectives`` (``"auto"`` = TPU only); ``fused=False``
    keeps the same SP structure with unfused boundary collectives —
    the numerics-pinning baseline.
    """
    from jax import lax

    from horovod_tpu.ops.pallas_kernels import resolve_fused_collectives
    from horovod_tpu.parallel.tensor_parallel import (
        param_value,
        column_parallel_dense_ag,
        row_parallel_dense_rs,
    )

    if cfg.kv_heads != cfg.num_heads or not cfg.rotary \
            or cfg.num_heads * cfg.head_dim != cfg.d_model \
            or cfg.attention_scale is not None:
        raise ValueError(
            "fused_tp_apply runs equal head counts of width d_model / "
            "num_heads with rotary positions and the width's own softmax "
            "scale only")
    if cfg.attention_impl not in ("dense", "flash"):
        raise ValueError(
            f"fused_tp_apply supports attention_impl dense|flash, got "
            f"{cfg.attention_impl!r} (ring/ulysses already own their "
            f"sequence axis)")
    if fused is None:
        fused = resolve_fused_collectives(cfg.fused_collectives)
    params = jax.tree_util.tree_map(
        param_value, variables.get("params", variables),
        is_leaf=lambda x: isinstance(x, nn.Partitioned))
    axis = cfg.tp_axis
    w = int(jax.lax.axis_size(axis))
    me = lax.axis_index(axis)
    b, t = tokens.shape
    d, heads = cfg.d_model, cfg.num_heads
    if t % w or heads % w or cfg.d_ff % w:
        raise ValueError(
            f"fused_tp_apply needs seq ({t}), num_heads ({heads}) and "
            f"d_ff ({cfg.d_ff}) divisible by the {axis!r} extent {w}")
    t_loc, d_loc, f_loc = t // w, d // w, cfg.d_ff // w
    h_loc, hd = heads // w, cfg.head_dim
    if positions is None:
        positions = jnp.arange(t)

    def col_shard(kernel, width):
        return lax.dynamic_slice_in_dim(kernel, me * width, width, axis=1)

    def row_shard(kernel, width):
        return lax.dynamic_slice_in_dim(kernel, me * width, width, axis=0)

    def to_rank_major(full):
        """(b, t, f) natural tokens → (w·b·t_loc, f) rank-major rows —
        the layout matmul_reducescatter scatters over."""
        f = full.shape[-1]
        return full.reshape(b, w, t_loc, f).transpose(1, 0, 2, 3) \
            .reshape(w * b * t_loc, f)

    def from_gathered(rows, f):
        """(w·b·t_loc, f) rank-major gather output → (b, t, f) natural."""
        return rows.reshape(w, b, t_loc, f).transpose(1, 0, 2, 3) \
            .reshape(b, t, f)

    def shard2d(x_shard):
        return x_shard.reshape(b * t_loc, x_shard.shape[-1])

    emb = params["embed"]["embedding"]
    x = jnp.take(emb.astype(cfg.dtype), tokens, axis=0)   # (b, t, d)
    # token-shard the residual stream: rank r owns tokens
    # [r·t_loc, (r+1)·t_loc) of every batch row
    x_shard = lax.dynamic_slice_in_dim(x, me * t_loc, t_loc, axis=1)

    for i in range(cfg.num_layers):
        layer = params[f"layer_{i}"]
        # -- attention: AG⊗qkv-matmul → core → proj-matmul⊗RS
        h = _rms(x_shard, layer["ln1"]["scale"])
        qkv_k = layer["attn"]["qkv"]["kernel"].astype(cfg.dtype)
        # per-matrix column shards: a contiguous slice of the fused
        # (d, 3d) kernel would span only one of q/k/v at tp > 3
        wq, wk, wv = (qkv_k[:, j * d:(j + 1) * d] for j in range(3))
        wqkv = jnp.concatenate(
            [col_shard(m, d_loc) for m in (wq, wk, wv)], axis=1)
        qkv = column_parallel_dense_ag(
            shard2d(h).astype(cfg.dtype), wqkv, axis=axis, fused=fused,
            interpret=interpret)
        q, k, v = jnp.split(from_gathered(qkv, 3 * d_loc), 3, axis=-1)
        shape = (b, t, h_loc, hd)
        q, k, v = (a.reshape(shape) for a in (q, k, v))
        q = rotary_embedding(q, positions)
        k = rotary_embedding(k, positions)
        if cfg.attention_impl == "flash":
            from horovod_tpu.ops.pallas_kernels import flash_attention

            o = flash_attention(q, k, v, causal=cfg.causal,
                                block_q=cfg.flash_block,
                                block_k=cfg.flash_block)
        else:
            o = reference_attention(q, k, v, causal=cfg.causal)
        o = o.reshape(b, t, h_loc * hd)
        proj_k = layer["attn"]["proj"]["kernel"].astype(cfg.dtype)
        y = row_parallel_dense_rs(
            to_rank_major(o).astype(cfg.dtype),
            row_shard(proj_k, d_loc), axis=axis, fused=fused,
            interpret=interpret)
        x_shard = x_shard + y.reshape(b, t_loc, d)

        # -- MLP: AG⊗wi-matmul → gelu → wo-matmul⊗RS.  The activation
        # stays rank-major between the two boundaries — gelu is
        # elementwise, so no natural-order round trip is needed
        h = _rms(x_shard, layer["ln2"]["scale"])
        wi = col_shard(layer["mlp"]["wi"]["kernel"].astype(cfg.dtype),
                       f_loc)
        wo = row_shard(layer["mlp"]["wo"]["kernel"].astype(cfg.dtype),
                       f_loc)
        hh = column_parallel_dense_ag(
            shard2d(h).astype(cfg.dtype), wi, axis=axis, fused=fused,
            interpret=interpret)
        hh = nn.gelu(hh)
        y = row_parallel_dense_rs(hh.astype(cfg.dtype), wo, axis=axis,
                                  fused=fused, interpret=interpret)
        x_shard = x_shard + y.reshape(b, t_loc, d)

    x_shard = _rms(x_shard, params["ln_f"]["scale"])
    # the one boundary-wide gather left: reassemble tokens for the tied
    # logits head (rank-major chunks → natural order)
    full = lax.all_gather(x_shard, axis, tiled=False)    # (w, b, t_loc, d)
    x = full.transpose(1, 0, 2, 3).reshape(b, t, d)
    # tied head, exactly flax Embed.attend's promotion: both operands
    # to cfg.dtype (promote_dtype(dtype=self.dtype)) before the dot
    query = x.astype(jnp.float32)
    if cfg.dtype is not None:
        query = query.astype(cfg.dtype)
        emb = emb.astype(cfg.dtype)
    return jnp.dot(query, emb.T)
