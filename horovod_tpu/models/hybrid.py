"""Hybrid decoder LM: Mamba-2, routed-expert and attention layers chosen
per layer by a pattern string (the Nemotron-H family's layout).

Every layer is one mixer and a residual, ``x + mixer(RMSNorm(x))``; the
pattern names the mixer: ``M`` a Mamba-2 state-space mixer
(:class:`Mamba2Mixer`, the chunked SSD form: Mosaic kernels on a TPU,
``jax.numpy`` einsums elsewhere — ``ops/pallas_kernels.ssd_scan``),
``E`` a routed-expert layer (:class:`ExpertMixer`: sigmoid scores, a
selection bias, top-k, a shared expert, ReLU² experts, dropless, told
which experts it holds), ``*`` grouped-query attention (the
:class:`~horovod_tpu.models.transformer.Attention` every other LM here
runs, without rotary positions).  The head is untied; ``vocab_size``
may be a slice of the published vocabulary.

``experts_held`` is the half-open range of expert ids this rank holds
of ``num_experts`` (docs/hybrid.md): the router is ``num_experts`` wide
whatever is held, parameters exist for the held experts only, and the
layer adds its own experts' part of the result — what the absent
experts would add is another rank's, and no code stands in for it.

Each mixer runs under its module's name (``mamba``, ``moe``, ``attn``;
below them ``ssd``, ``router``, ``dispatch``, ``experts``, ``shared``,
``combine``), so the compiled step's ``op_name`` paths carry the kind.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import telemetry
from horovod_tpu.models.transformer import (
    Attention,
    RMSNorm,
    TransformerConfig,
)
from horovod_tpu.ops.pallas_kernels import (
    ssd_chunked,    # noqa: F401 — the scan's jax.numpy form, as before
    ssd_runs_kernels,
    ssd_scan,
)
from horovod_tpu.parallel.expert import (
    held_assignments,
    held_expert_ffn,
    topk_routing,
)

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


@dataclasses.dataclass
class HybridConfig:
    vocab_size: int = 16_384
    pattern: str = "EMEMEMEM*"          # one mixer a layer: M, E or *
    d_model: int = 2688
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # * attention (grouped-query, no positional term)
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    attention_impl: str = "dense"       # dense | flash
    flash_block: int = 512
    flash_interpret: bool = False       # the Pallas kernels interpreted
                                        # (CPU test plumbing)
    # M Mamba-2: inner width mamba_heads * mamba_head_dim
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # E routed experts
    num_experts: int = 128              # the router's width
    experts_held: tuple = (0, 8)        # [lo, hi) of the ids held here
    top_k: int = 6
    expert_width: int = 1856
    shared_width: int = 3712
    routed_scale: float = 2.5
    # False: the router's matrix takes no gradient (docs/hybrid.md: a
    # rank that holds a share of the experts, trained alone, has only
    # its own experts' terms of that gradient)
    train_router: bool = True
    # none|dots|full|offload, of the Mamba and attention blocks; an expert
    # block keeps its grouped matmuls' results and little else either way
    remat_policy: Optional[str] = None

    def __post_init__(self):
        unknown = set(self.pattern) - set(KINDS)
        if unknown or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: a layer is one "
                             f"of {sorted(KINDS)}")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is no "
                             f"range of the {self.num_experts} experts")
        if self.mamba_heads % self.mamba_groups:
            raise ValueError("mamba_heads must be a multiple of "
                             "mamba_groups")

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    def attention(self) -> TransformerConfig:
        return TransformerConfig(
            vocab_size=self.vocab_size, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, head_width=self.head_dim,
            rotary=False, d_model=self.d_model, dtype=self.dtype,
            attention_impl=self.attention_impl,
            flash_block=self.flash_block,
            flash_interpret=self.flash_interpret)


def _dense(features: int, cfg: HybridConfig, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype, name=name)


# ---------------------------------------------------------------------------
# M: Mamba-2
# ---------------------------------------------------------------------------

def _dt_bias_init(cfg: HybridConfig):
    """Inverse softplus of time steps drawn log-uniformly from
    [time_step_min, time_step_max] (the published initialisation)."""
    def init(key, shape, dtype=jnp.float32):
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (hi - lo) + lo)
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


class Mamba2Mixer(nn.Module):
    """``[z | xBC | dt] = in_proj(u)``; a causal depthwise convolution
    and SiLU over ``xBC``; the state-space recurrence over ``x`` with
    ``B``, ``C`` shared by the heads of a group; ``+ D x``; a gated
    RMSNorm in groups; ``out_proj``."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        h, p, g, n = (cfg.mamba_heads, cfg.mamba_head_dim,
                      cfg.mamba_groups, cfg.ssm_state)
        inner, k = cfg.mamba_inner, cfg.conv_kernel
        bsz, t, _ = u.shape
        f32 = jnp.float32
        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (k, inner + 2 * g * n), f32)
        conv_b = self.param("conv_bias", nn.initializers.zeros_init(),
                            (inner + 2 * g * n,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (h,), f32)
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(
                jnp.arange(1, shape[0] + 1, dtype=f32)), (h,))
        d_skip = self.param("D", nn.initializers.ones_init(), (h,), f32)
        norm_scale = self.param("norm_scale", nn.initializers.ones_init(),
                                (inner,), f32)

        zxbcdt = _dense(2 * inner + 2 * g * n + h, cfg, "in_proj")(u)
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * g * n],
                               axis=-1)
        with jax.named_scope("conv"):
            padded = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
            xbc = sum(padded[:, i:i + t] * conv_w[i].astype(cfg.dtype)
                      for i in range(k)) + conv_b.astype(cfg.dtype)
            xbc = nn.silu(xbc)
        x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
        x = x.reshape(bsz, t, h, p)
        with jax.named_scope("ssd"):
            y = ssd_scan(
                x, jax.nn.softplus(dt.astype(f32) + dt_bias),
                -jnp.exp(a_log), b.reshape(bsz, t, g, n),
                c.reshape(bsz, t, g, n), cfg.chunk,
                interpret=cfg.flash_interpret)
            y = y + d_skip[:, None] * x.astype(f32)
        with jax.named_scope("gated_norm"):
            y = y.reshape(bsz, t, inner) * nn.silu(z.astype(f32))
            y = y.reshape(bsz, t, g, inner // g)
            y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + cfg.norm_eps)
            y = (y.reshape(bsz, t, inner) * norm_scale).astype(cfg.dtype)
        return _dense(cfg.d_model, cfg, "out_proj")(y)


# ---------------------------------------------------------------------------
# E: routed experts
# ---------------------------------------------------------------------------

def _relu2(x):
    return jnp.square(nn.relu(x))


def _held_experts(params, rows, group_sizes, interpret: bool = False):
    """``down_g . relu(up_g . row)^2`` for the rows of each held expert:
    two grouped matmuls over the sorted buffer."""
    from jax.ad_checkpoint import checkpoint_name

    from horovod_tpu.ops.pallas_kernels import grouped_matmul

    up, down = params
    hidden = checkpoint_name(
        grouped_matmul(rows, up, group_sizes, interpret=interpret),
        "grouped_matmul")
    return checkpoint_name(
        grouped_matmul(_relu2(hidden), down, group_sizes,
                       interpret=interpret), "grouped_matmul")


class ExpertMixer(nn.Module):
    """The shared expert's output for every token, plus the part of the
    routed experts' output that the experts held here give."""

    cfg: HybridConfig

    def _route(self, tokens):
        cfg = self.cfg
        router = self.param("router", nn.initializers.normal(0.02),
                            (cfg.d_model, cfg.num_experts), jnp.float32)
        if not cfg.train_router:    # scores still carry gradient to the input
            router = lax.stop_gradient(router)
        # the selection bias takes no gradient and stays where it is
        # initialised: its load-balancing update needs an auxiliary
        # output the step does not have
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (cfg.num_experts,), jnp.float32)
        with jax.named_scope("router"):
            # fp32 and all of it: a near-tie between the sixth and the
            # seventh score must not be decided by a rounding
            scores = jnp.dot(tokens.astype(jnp.float32), router,
                             precision=lax.Precision.HIGHEST)
            return topk_routing(scores, bias, cfg.top_k, cfg.routed_scale)

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        lo, hi = cfg.experts_held
        bsz, t, d = u.shape
        tokens = u.reshape(bsz * t, d)
        expert_idx, weights = self._route(tokens)
        if self.is_mutable_collection("intermediates"):   # expert_load
            self.sow("intermediates", "held_load",
                     held_assignments(expert_idx, cfg.experts_held)[1])
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        up = self.param("experts_up", init,
                        (hi - lo, d, cfg.expert_width), jnp.float32)
        down = self.param("experts_down", init,
                          (hi - lo, cfg.expert_width, d), jnp.float32)
        routed = held_expert_ffn(
            tokens, expert_idx, weights, cfg.experts_held,
            functools.partial(_held_experts,
                              interpret=cfg.flash_interpret),
            (up.astype(cfg.dtype), down.astype(cfg.dtype)))
        with jax.named_scope("shared"):
            shared = _dense(d, cfg, "shared_down")(
                _relu2(_dense(cfg.shared_width, cfg, "shared_up")(u)))
        return shared + routed.reshape(bsz, t, d).astype(cfg.dtype)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class HybridBlock(nn.Module):
    """``x + mixer(RMSNorm(x))``, the mixer named by its kind."""

    cfg: HybridConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        u = RMSNorm(epsilon=cfg.norm_eps, name="norm")(x)
        name = KINDS[self.kind]
        if self.kind == "M":
            y = Mamba2Mixer(cfg, name=name)(u)
        elif self.kind == "E":
            y = ExpertMixer(cfg, name=name)(u)
        else:
            y = Attention(cfg.attention(), name=name)(u, positions)
        return x + y


class HybridLM(nn.Module):
    """``apply(variables, tokens) -> logits`` (fp32, over the
    vocabulary slice)."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, tokens):
        from horovod_tpu.memory.remat import remat_block, \
            resolve_remat_policy

        cfg = self.cfg
        _note_shapes(cfg, tokens.shape)
        positions = jnp.arange(tokens.shape[1])
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                     embedding_init=nn.initializers.normal(0.02),
                     name="embed")(tokens)
        block = remat_block(HybridBlock,
                            resolve_remat_policy(cfg.remat_policy, None))
        for i, kind in enumerate(cfg.pattern):
            # an expert block is not rematerialised as a block: its routed
            # part rematerialises itself around the grouped matmuls'
            # results (held_expert_ffn), so those run once forward
            layer = HybridBlock if kind == "E" else block
            x = layer(cfg, kind, name=f"layer_{i}")(x, positions)
        x = RMSNorm(epsilon=cfg.norm_eps, name="ln_f")(x)
        head = self.param("head", nn.initializers.lecun_normal(),
                          (cfg.d_model, cfg.vocab_size), jnp.float32)
        # bf16 operands, fp32 logits: the softmax is taken from them
        return lax.dot_general(x, head.astype(cfg.dtype),
                               (((2,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def hybrid_lm_loss(model: HybridLM, variables, batch) -> jax.Array:
    """Next-token cross-entropy over the vocabulary slice, in fp32, of
    ``batch["inputs"]`` against ``batch["labels"]`` (mean of the shard)."""
    import optax

    logits = model.apply(variables, batch["inputs"])
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["labels"]).mean()


def expert_load(model: HybridLM, variables, tokens) -> dict:
    """The routing probe: one forward pass, and for the expert layers in
    order ``{"local_share": (layers,), "max_over_mean": (layers,)}`` —
    the share of the step's ``tokens * top_k`` assignments that land on
    the experts held here, and the largest held expert's load over
    their mean.  Jittable (``jax.jit(partial(expert_load, model))``);
    the train step itself records nothing."""
    _, state = model.apply(variables, tokens, mutable=["intermediates"])
    total = tokens.shape[0] * tokens.shape[1] * model.cfg.top_k
    counts = jnp.stack([
        state["intermediates"][f"layer_{i}"]["moe"]["held_load"][0]
        for i, kind in enumerate(model.cfg.pattern) if kind == "E"]
    ).astype(jnp.float32)
    return {"local_share": jnp.sum(counts, axis=1) / total,
            "max_over_mean": jnp.max(counts, axis=1)
            / jnp.maximum(jnp.mean(counts, axis=1), 1e-9)}


def _note_shapes(cfg: HybridConfig, shape) -> None:
    """At trace time: the step's shape facts as gauges, and as attributes
    of the span the trace runs under (``train_step.lower``, which hands
    them to ``train_step.compile``)."""
    from horovod_tpu.memory.remat import resolve_remat_policy

    tokens = int(shape[0]) * int(shape[1])
    lo, hi = cfg.experts_held
    mosaic = ssd_runs_kernels(
        int(shape[1]), cfg.mamba_heads, cfg.mamba_head_dim,
        cfg.mamba_groups, cfg.ssm_state, cfg.chunk, cfg.flash_interpret)
    # forward and backward, and the forward again where the block is
    # rematerialised
    recomputed = resolve_remat_policy(cfg.remat_policy, None) != "none"
    facts = {
        "hybrid_pattern": cfg.pattern,
        "experts_held": hi - lo,
        "tokens_per_step": tokens,
        "assignments_per_step": tokens * cfg.top_k,
        "expert_buffer_rows": tokens * cfg.top_k,   # any routing fits
        "ssd_chunks_per_sequence": -(-int(shape[1]) // cfg.chunk),
        "ssd_impl": "mosaic" if mosaic else "einsum",
        "ssd_kernel_calls_per_layer": (2 + recomputed) if mosaic else 0,
    }
    telemetry.annotate(**facts)
    # gauges record only while telemetry is enabled, as every handle
    for name in ("experts_held", "tokens_per_step", "assignments_per_step",
                 "expert_buffer_rows", "ssd_chunks_per_sequence",
                 "ssd_kernel_calls_per_layer"):
        telemetry.gauge(f"hvd_hybrid_{name}",
                        "set when a HybridLM step is traced").set(facts[name])
    for impl in ("mosaic", "einsum"):       # 1 on the one that runs
        telemetry.gauge("hvd_hybrid_ssd_impl",
                        "set when a HybridLM step is traced").set(
                            int(impl == facts["ssd_impl"]), impl=impl)
