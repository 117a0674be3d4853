"""Switch-style Mixture-of-Experts transformer LM.

Completes the model family around the expert-parallel machinery
(:mod:`horovod_tpu.parallel.expert` — the TPU formulation of the
reference's variable-split alltoall, ``operations.cc:979``, as an MoE
dispatch plane): :class:`SwitchFFN` replaces every second block's MLP
with top-1-routed experts, and :class:`MoETransformerLM` stacks them on
the same attention/RMSNorm/RoPE machinery as
:class:`~horovod_tpu.models.transformer.TransformerLM`.

TPU-first choices, same stance as the rest of the zoo:

* static capacity buckets (no dynamic shapes under jit; over-capacity
  tokens drop, the Switch-Transformer policy);
* expert FFNs run as ONE batched einsum over ``(E, C, d)`` buffers —
  the MXU sees a single large contraction, not per-expert dispatches;
* two execution modes sharing the router and parameters: *local*
  (every device holds all experts — single chip, or experts replicated
  under pure DP) and *expert-parallel* (``ep_axis`` set, call under
  ``shard_map``: experts sharded, tokens moved by ``all_to_all`` via
  :func:`~horovod_tpu.parallel.expert.expert_parallel_ffn` — or by the
  tile-fused ``a2a ⊗ expert-matmul`` ppermute ring when
  ``fused_dispatch`` / ``HOROVOD_MOE_FUSED_DISPATCH`` resolves on,
  overlapping each hop's wire with the previous tile's expert matmul);
* the Switch load-balancing auxiliary loss is sowed under
  ``intermediates/moe_aux_loss`` so training loops can add
  ``aux_weight * mean(aux)`` without threading extra outputs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import (
    Attention,
    RMSNorm,
    TransformerConfig,
)
from horovod_tpu.parallel.expert import expert_parallel_ffn, top1_routing


@dataclasses.dataclass
class MoEConfig:
    vocab_size: int = 32_000
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    attention_impl: str = "dense"
    flash_block: int = 512
    causal: bool = True
    num_experts: int = 8
    capacity_factor: float = 1.25
    moe_every: int = 2              # every Nth block is MoE (Switch: 2)
    ep_axis: Optional[str] = None   # None: local experts; "ep": sharded
    fused_dispatch: Optional[str] = None  # auto|on|off; None -> env knob
    remat: bool = False
    remat_policy: Optional[str] = None  # none|dots|full|offload

    def resolved_fused_dispatch(self) -> str:
        """The ``fused_dispatch`` mode with the
        ``HOROVOD_MOE_FUSED_DISPATCH`` env-knob fallback applied
        (default ``"auto"`` = TPU-only, docs/fused_kernels.md)."""
        import os
        return (self.fused_dispatch
                or os.environ.get("HOROVOD_MOE_FUSED_DISPATCH")
                or "auto").lower()

    def transformer(self) -> TransformerConfig:
        return TransformerConfig(
            vocab_size=self.vocab_size, num_layers=self.num_layers,
            num_heads=self.num_heads, d_model=self.d_model,
            d_ff=self.d_ff, max_seq_len=self.max_seq_len,
            dtype=self.dtype, attention_impl=self.attention_impl,
            flash_block=self.flash_block, causal=self.causal,
            remat=self.remat, remat_policy=self.remat_policy)


class SwitchFFN(nn.Module):
    """Top-1-routed expert FFN (gelu MLP experts).

    ``(B, T, D) -> (B, T, D)``; sows ``moe_aux_loss`` (Switch aux:
    ``E * sum_e fraction_e * prob_e``, minimized at uniform routing),
    ``moe_expert_fraction`` (per-expert routed-token share, the
    utilization vector) and ``moe_drop_fraction`` under
    ``intermediates``.
    """

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, d = x.shape
        e = cfg.num_experts
        gate_kernel = self.param(
            "gate", nn.initializers.normal(0.02), (d, e), jnp.float32)
        w1 = self.param("w1", nn.initializers.lecun_normal(),
                        (e, d, cfg.d_ff), jnp.float32)
        w2 = self.param("w2", nn.initializers.lecun_normal(),
                        (e, cfg.d_ff, d), jnp.float32)
        tokens = x.reshape(b * t, d)

        # Switch aux loss from the router view (identical in both
        # modes; fp32 for a stable softmax)
        scores = tokens.astype(jnp.float32) @ gate_kernel
        probs = jax.nn.softmax(scores, axis=-1)
        chosen = jax.nn.one_hot(jnp.argmax(probs, axis=-1), e,
                                dtype=jnp.float32)
        aux = e * jnp.sum(chosen.mean(0) * probs.mean(0))
        self.sow("intermediates", "moe_aux_loss", aux)
        # per-expert routing share (router view, both modes): the
        # fraction of tokens argmax-assigned to each expert BEFORE
        # capacity drops — uniform is 1/E; the bench emits this so an
        # imbalanced router (and the drops it causes) is visible in
        # the artifact instead of silently inflating active-FLOP MFU
        self.sow("intermediates", "moe_expert_fraction", chosen.mean(0))

        w1c = w1.astype(cfg.dtype)
        w2c = w2.astype(cfg.dtype)

        def expert_mlp(buffers, w1_, w2_):
            """(E?, S, d) -> (E?, S, d): one batched MXU contraction
            per layer across however many experts are present — the
            ONE expert body both modes share (local and EP must never
            diverge in what an expert computes)."""
            h = jnp.einsum("esd,edf->esf", buffers, w1_)
            return jnp.einsum("esf,efd->esd", nn.gelu(h), w2_)

        if cfg.ep_axis is not None:
            # expert-parallel: must be traced inside shard_map with the
            # axis bound.  Each shard applies ITS slice of the experts.
            from jax import lax

            def expert_fn(buffers):
                world = lax.axis_size(cfg.ep_axis)
                e_local = e // world
                idx = lax.axis_index(cfg.ep_axis)
                w1l = lax.dynamic_slice_in_dim(w1c, idx * e_local,
                                               e_local, 0)
                w2l = lax.dynamic_slice_in_dim(w2c, idx * e_local,
                                               e_local, 0)
                return expert_mlp(buffers, w1l, w2l)

            # scores= hands the fp32 routing used for the aux loss to
            # the dispatch plane: the accounted routing IS the
            # dispatched routing, in any compute dtype
            from horovod_tpu.ops.pallas_kernels import \
                resolve_fused_collectives

            fused = resolve_fused_collectives(
                cfg.resolved_fused_dispatch())
            y, dropped = expert_parallel_ffn(
                tokens.astype(cfg.dtype), gate_kernel,
                expert_fn, e, capacity_factor=cfg.capacity_factor,
                axis=cfg.ep_axis, scores=scores, fused=fused)
        else:
            # local mode: same dispatch/combine as the parallel path
            # minus the all_to_alls — numerics are mode-invariant
            capacity = int(max(1, -(-cfg.capacity_factor *
                                    tokens.shape[0] // e)))
            expert_idx, slot, keep, gate = top1_routing(scores, capacity)
            xt = tokens.astype(cfg.dtype)
            dispatch = jnp.zeros((e, capacity, d), cfg.dtype)
            safe_slot = jnp.where(keep, slot, 0)
            dispatch = dispatch.at[expert_idx, safe_slot].add(
                jnp.where(keep[:, None], xt, 0))
            out = expert_mlp(dispatch, w1c, w2c)
            y = out[expert_idx, safe_slot]
            y = jnp.where(keep[:, None],
                          y * gate[:, None].astype(y.dtype), 0)
            dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
        self.sow("intermediates", "moe_drop_fraction", dropped)
        return y.reshape(b, t, d).astype(cfg.dtype)


class MoEBlock(nn.Module):
    cfg: MoEConfig

    @nn.compact
    def __call__(self, x, positions):
        tcfg = self.cfg.transformer()
        x = x + Attention(tcfg, name="attn")(
            RMSNorm(name="ln1")(x), positions)
        return x + SwitchFFN(self.cfg, name="moe")(
            RMSNorm(name="ln2")(x))


class MoETransformerLM(nn.Module):
    """``apply(variables, tokens) -> logits``; every
    ``cfg.moe_every``-th block routes through experts, the rest are the
    dense :class:`~horovod_tpu.models.transformer.Block` MLPs.  Collect
    the aux losses with ``mutable=["intermediates"]`` and add
    ``aux_weight * mean(moe_aux_loss values)`` to the task loss.

    With ``ep_axis`` set, call under ``shard_map`` — the params run
    as ``init`` returns them, same as TransformerLM's ring/ulysses
    modes; init with an ``ep_axis=None`` twin (identical param tree,
    no bound axis needed).  See ``examples/moe_lm_example.py``."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, tokens, positions: Optional[jax.Array] = None):
        from horovod_tpu.models.transformer import Block

        cfg = self.cfg
        tcfg = cfg.transformer()
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        emb = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                       embedding_init=nn.initializers.normal(0.02),
                       name="embed")
        x = emb(tokens)
        from horovod_tpu.memory.remat import remat_block, \
            resolve_remat_policy

        policy = resolve_remat_policy(cfg.remat_policy, cfg.remat)
        for i in range(cfg.num_layers):
            moe = cfg.moe_every and (i + 1) % cfg.moe_every == 0
            cls = remat_block(MoEBlock if moe else Block, policy)
            x = cls(cfg if moe else tcfg, name=f"layer_{i}")(x, positions)
        x = RMSNorm(name="ln_f")(x)
        return emb.attend(x.astype(jnp.float32))


def moe_aux_loss(intermediates) -> jax.Array:
    """Mean of the sowed Switch aux losses (0 when none present)."""
    leaves = [v for path, v in
              jax.tree_util.tree_flatten_with_path(intermediates)[0]
              if any(getattr(p, "key", "") == "moe_aux_loss"
                     for p in path)]
    if not leaves:
        return jnp.zeros(())
    return jnp.mean(jnp.stack([jnp.asarray(l, jnp.float32).mean()
                               for l in leaves]))
