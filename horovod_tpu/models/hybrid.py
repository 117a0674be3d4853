"""Hybrid decoder LM: Mamba-2, routed-expert, attention and gated-MLP
layers chosen per layer by a pattern string (the Nemotron-H family's
layout; with ``residual="hc"`` and latent attention, the DeepSeek-V3
family's under manifold-constrained hyper-connections).

Every layer is one mixer and a residual connector round it — plain,
``x + mixer(RMSNorm(x))``, or a hyper-connection over ``hc_streams``
residual streams (:class:`HyperConnection`).  The
pattern names the mixer: ``M`` a Mamba-2 state-space mixer
(:class:`Mamba2Mixer`, the chunked SSD form: Mosaic kernels on a TPU,
``jax.numpy`` einsums elsewhere — ``ops/pallas_kernels.ssd_scan``),
``E`` a routed-expert layer (:class:`ExpertMixer`: sigmoid scores, a
selection bias, top-k, a shared expert, ReLU² or SwiGLU experts,
dropless, told which experts it holds), ``*`` attention — grouped-query
(the :class:`~horovod_tpu.models.transformer.Attention` every other LM
here runs, without rotary positions) or latent
(:class:`~horovod_tpu.models.transformer.LatentAttention`) by
``attention_kind`` —, ``D`` a dense SwiGLU MLP (:class:`GatedMlp`).  The
head is untied, or with ``tie_head`` the embedding read again;
``vocab_size`` may be a slice of the published vocabulary.  A muP
model's scalars (the Granite family's): ``embedding_scale`` multiplies
the embedding, ``residual_scale`` every mixer's output before the plain
add, ``logits_divisor`` divides the logits, ``attention_scale`` is the
softmax scale where it is not the head width's inverse root.

``experts_held`` is the half-open range of expert ids this rank holds
of ``num_experts`` (docs/hybrid.md): the router is ``num_experts`` wide
whatever is held, parameters exist for the held experts only, and the
layer adds its own experts' part of the result — what the absent
experts would add is another rank's, and no code stands in for it.

Each mixer runs under its module's name (``mamba``, ``moe``, ``attn``,
``mlp``; below them ``ssd``, ``router``, ``dispatch``, ``experts``,
``shared``, ``combine``) and the hyper-connection under ``hc``, so the
compiled step's ``op_name`` paths carry the kind.
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
    LatentAttention,
    LatentAttentionConfig,
    RMSNorm,
    TransformerConfig,
)
from horovod_tpu.ops import pallas_kernels
from horovod_tpu.ops.pallas_kernels import (
    hc_read,
    hc_runs_kernels,
    mamba_conv,
    mamba_gated_norm,
    mamba_runs_kernels,
    moe_row_sum_runs_kernel,
    sinkhorn,       # noqa: F401 — the mixing matrix's rounds, as before
    ssd_chunked,    # noqa: F401 — the scan's jax.numpy form, as before
    ssd_runs_kernels,
    ssd_scan,
)
from horovod_tpu.parallel.expert import (
    held_assignments,
    held_expert_ffn,
    topk_routing,
)

KINDS = {"M": "mamba", "E": "moe", "*": "attn", "D": "mlp"}


@dataclasses.dataclass
class HybridConfig:
    vocab_size: int = 16_384
    pattern: str = "EMEMEMEM*"          # one mixer a layer: M, E, * or D
    d_model: int = 2688
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # the connector round every mixer: "add", x + mixer(RMSNorm(x)), or
    # "hc", hc_streams residual streams a token (HyperConnection)
    residual: str = "add"
    hc_streams: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple = (-30.0, 30.0)     # of the mixing logits
    # * attention: "gqa" (grouped-query, no positional term) or "latent"
    attention_kind: str = "gqa"
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # latent attention (LatentAttentionConfig names the parts)
    q_rank: int = 768
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rope_base: float = 10_000.0
    rope_scaling: Optional[dict] = None
    # D dense SwiGLU MLP
    mlp_width: int = 9216
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
    # "relu2": down(relu(up x)^2), two matrices an expert; "swiglu":
    # down(silu(gate x) * up x), three — the shared expert alike
    expert_act: str = "relu2"
    # False: the router's matrix takes no gradient (docs/hybrid.md: a
    # rank that holds a share of the experts, trained alone, has only
    # its own experts' terms of that gradient)
    train_router: bool = True
    # none|dots|full|offload, of the Mamba and attention blocks; an expert
    # block keeps its grouped matmuls' results and little else either way
    remat_policy: Optional[str] = None
    # of the dense MLP (D) blocks where it is another (None: remat_policy):
    # "dots" keeps an MLP's three matmul results and recomputes only what
    # lies between them, where memory has the room
    mlp_remat_policy: Optional[str] = None
    # muP scalars: h0 = embedding_scale E[tokens]; x + residual_scale
    # mixer(norm(x)) (the plain residual only); logits / logits_divisor;
    # gqa softmax scale (None: head_dim ** -0.5)
    embedding_scale: float = 1.0
    residual_scale: float = 1.0
    logits_divisor: float = 1.0
    attention_scale: Optional[float] = None
    tie_head: bool = False              # the head is the embedding's leaf

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
        for field, allowed in (("residual", ("add", "hc")),
                               ("attention_kind", ("gqa", "latent")),
                               ("expert_act", ("relu2", "swiglu"))):
            if getattr(self, field) not in allowed:
                raise ValueError(f"{field} {getattr(self, field)!r}: one "
                                 f"of {allowed}")
        if self.residual == "hc" and self.residual_scale != 1.0:
            raise ValueError(
                f"residual_scale {self.residual_scale} scales the plain "
                f"residual's branch; a hyper-connection weighs its own "
                f"(h_post)")
        if self.attention_kind == "latent" \
                and self.attention_scale is not None:
            raise ValueError("attention_scale is grouped-query attention's; "
                             "latent attention derives its own softmax scale")

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    def attention(self):
        if self.attention_kind == "latent":
            return LatentAttentionConfig(
                d_model=self.d_model, num_heads=self.num_heads,
                q_rank=self.q_rank, kv_rank=self.kv_rank,
                nope_dim=self.nope_dim, rope_dim=self.rope_dim,
                v_dim=self.v_dim, norm_eps=self.norm_eps,
                rope_base=self.rope_base, rope_scaling=self.rope_scaling,
                dtype=self.dtype, attention_impl=self.attention_impl,
                flash_block=self.flash_block,
                flash_interpret=self.flash_interpret)
        return TransformerConfig(
            vocab_size=self.vocab_size, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, head_width=self.head_dim,
            rotary=False, attention_scale=self.attention_scale,
            d_model=self.d_model, dtype=self.dtype,
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


def _mamba_fused(cfg: HybridConfig, zxbcdt, conv_w, conv_b, dt_bias, a_log,
                 d_skip, norm_scale):
    """The path between the projections with the elementwise passes
    as kernels (``ops/pallas_kernels.mamba_conv`` /
    ``mamba_gated_norm``): everything with time on the lanes, as
    the scan's kernels take it, so ``z`` and ``xBC`` are read out of
    ``in_proj``'s result where it lies and the transposes round the
    scan cancel."""
    h, p, g, n = (cfg.mamba_heads, cfg.mamba_head_dim,
                  cfg.mamba_groups, cfg.ssm_state)
    bsz, t, _ = zxbcdt.shape
    f32 = jnp.float32

    def time_first(v):      # (B, rows, T) -> (B, T, rows)
        return v.transpose(0, 2, 1)
    zt = time_first(zxbcdt)
    with jax.named_scope("conv"):
        x, b, c = mamba_conv(zt, conv_w, conv_b, inner=h * p,
                             state_cols=g * n,
                             interpret=cfg.flash_interpret)
    with jax.named_scope("ssd"):
        y = ssd_scan(
            time_first(x).reshape(bsz, t, h, p),
            jax.nn.softplus(zxbcdt[..., -h:].astype(f32) + dt_bias),
            -jnp.exp(a_log), time_first(b).reshape(bsz, t, g, n),
            time_first(c).reshape(bsz, t, g, n), cfg.chunk,
            interpret=cfg.flash_interpret)
    with jax.named_scope("gated_norm"):
        y = mamba_gated_norm(
            time_first(y.reshape(bsz, t, h * p)), x, zt, d_skip,
            norm_scale, groups=g, eps=cfg.norm_eps,
            interpret=cfg.flash_interpret)
    return time_first(y)


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
        if mamba_runs_kernels(t, h, p, g, n, k, cfg.dtype,
                              cfg.flash_interpret):
            y = _mamba_fused(cfg, zxbcdt, conv_w, conv_b, dt_bias, a_log,
                             d_skip, norm_scale)
            return _dense(cfg.d_model, cfg, "out_proj")(y)
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


def _swiglu(gate_up):
    """``silu(gate) * up`` of ``[gate | up]`` side by side."""
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return nn.silu(gate) * up


def _held_experts(params, rows, group_sizes, interpret: bool = False,
                  act=_relu2):
    """``down_g . act(up_g . row)`` for the rows of each held expert:
    two grouped matmuls over the sorted buffer.  ``act`` is ReLU², or
    SwiGLU over an ``up`` that holds ``[gate | up]`` side by side."""
    from jax.ad_checkpoint import checkpoint_name

    from horovod_tpu.ops.pallas_kernels import grouped_matmul

    up, down = params
    hidden = checkpoint_name(
        grouped_matmul(rows, up, group_sizes, interpret=interpret),
        "grouped_matmul")
    return checkpoint_name(
        grouped_matmul(act(hidden), down, group_sizes,
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
        gated = cfg.expert_act == "swiglu"
        up = self.param("experts_up", init,
                        (hi - lo, d, cfg.expert_width), jnp.float32)
        if gated:   # [gate | up] side by side: one grouped matmul
            up = jnp.concatenate(
                [self.param("experts_gate", init,
                            (hi - lo, d, cfg.expert_width), jnp.float32),
                 up], axis=-1)
        down = self.param("experts_down", init,
                          (hi - lo, cfg.expert_width, d), jnp.float32)
        routed = held_expert_ffn(
            tokens, expert_idx, weights, cfg.experts_held,
            functools.partial(_held_experts, interpret=cfg.flash_interpret,
                              act=_swiglu if gated else _relu2),
            (up.astype(cfg.dtype), down.astype(cfg.dtype)),
            interpret=cfg.flash_interpret)
        with jax.named_scope("shared"):
            hidden = _dense(cfg.shared_width, cfg, "shared_up")(u)
            if gated:
                hidden = nn.silu(_dense(cfg.shared_width, cfg,
                                        "shared_gate")(u)) * hidden
            else:
                hidden = _relu2(hidden)
            shared = _dense(d, cfg, "shared_down")(hidden)
        return shared + routed.reshape(bsz, t, d).astype(cfg.dtype)


# ---------------------------------------------------------------------------
# D: dense gated MLP
# ---------------------------------------------------------------------------

def _silu_gate_bwd(g, p, dh):
    """``h = silu(g) p`` again and its two cotangents ``d_g = dh p
    silu'(g)``, ``d_p = dh silu(g)`` from one reading of ``g``, ``p``,
    ``dh``: fp32 arithmetic, one logistic shared by the three, each
    rounded once to the operands' type."""
    f32 = jnp.float32
    gf, pf, dhf = g.astype(f32), p.astype(f32), dh.astype(f32)
    s = jax.nn.sigmoid(gf)
    act = gf * s
    return ((act * pf).astype(g.dtype),
            (dhf * pf * (s * (1.0 + gf * (1.0 - s)))).astype(g.dtype),
            (dhf * act).astype(g.dtype))


def _project(x, w):
    """``x w`` over ``x``'s last axis, as ``nn.Dense`` multiplies."""
    return lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())))


def _project_back(dy, w):
    """``dy w^T``: the cotangent of :func:`_project`'s ``x``."""
    return lax.dot_general(dy, w, (((dy.ndim - 1,), (1,)), ((), ())))


def _weight_cotangent(x, dy):
    """``x^T dy`` summed over every leading axis: the cotangent of
    :func:`_project`'s ``w``."""
    lead = tuple(range(x.ndim - 1))
    return lax.dot_general(x, dy, ((lead, lead), ((), ())))


@jax.custom_vjp
def gated_mlp(u, w_gate, w_up, w_down):
    """``(silu(u w_gate) * (u w_up)) w_down`` on operands of one type
    (the compute type: the kernels are cast before the call, so a
    kernel's cotangent leaves in that type and the cast's transpose
    widens it), with the backward written out (docs/fused_kernels.md).

    Forward as three ``nn.Dense`` and ``nn.silu`` made it (``silu(g) p``
    in the operands' type: XLA keeps it a prologue of ``down``'s matmul,
    or, where a rematerialised block keeps neither ``g`` nor ``p``, the
    epilogue of ``up``'s).  Residuals: ``u``, ``g = u w_gate``, ``p = u
    w_up`` and the three kernels — what ``jax.checkpoint``'s
    ``dots_saveable`` keeps of the sublayer anyway.  Backward, given ``dy``: ``dh = dy w_down^T``; one
    elementwise pass over ``g``, ``p``, ``dh`` that makes ``h``,
    ``d_g``, ``d_p`` (:func:`_silu_gate_bwd`), its three results behind
    ``lax.optimization_barrier`` — so they stand in HBM, made once: XLA
    may fuse none of it into a matmul that reads them, nor make it
    again for each of their five readers —; then ``h^T dy``, ``u^T
    d_g``, ``u^T d_p`` and ``d_g w_gate^T + d_p w_up^T``.  Left to
    autodiff five of the six matmuls carry the logistic, its derivative
    and the products as prologues of their fusions, where the MXU waits
    on the VPU (PERF.md, PR 38).  Before the pass nothing is held apart:
    on a TPU XLA makes it the epilogue of ``dh``'s matmul, and ``dh``
    itself never stands in HBM (read faster than the pass standing
    alone).  The scopes ``gate``, ``up``, ``down`` name the matmuls
    forward and backward as ``nn.Dense``'s names did."""
    return _gated_mlp_fwd(u, w_gate, w_up, w_down)[0]


def _gated_mlp_fwd(u, w_gate, w_up, w_down):
    with jax.named_scope("gate"):
        g = _project(u, w_gate)
    with jax.named_scope("up"):
        p = _project(u, w_up)
    with jax.named_scope("down"):
        y = _project(nn.silu(g) * p, w_down)
    return y, (u, g, p, w_gate, w_up, w_down)


def _gated_mlp_bwd(residuals, dy):
    u, g, p, w_gate, w_up, w_down = residuals
    with jax.named_scope("down"):
        dh = _project_back(dy, w_down)
    with jax.named_scope("swiglu"):
        h, d_g, d_p = lax.optimization_barrier(_silu_gate_bwd(g, p, dh))
    with jax.named_scope("down"):
        dw_down = _weight_cotangent(h, dy)
    with jax.named_scope("gate"):
        dw_gate = _weight_cotangent(u, d_g)
        du = _project_back(d_g, w_gate)
    with jax.named_scope("up"):
        dw_up = _weight_cotangent(u, d_p)
        du = du + _project_back(d_p, w_up)
    return du, dw_gate, dw_up, dw_down


gated_mlp.defvjp(_gated_mlp_fwd, _gated_mlp_bwd)


class _Kernel(nn.Module):
    """``nn.Dense``'s one leaf without its matmul: ``kernel`` (fp32,
    LeCun-normal, under this module's name as under a ``Dense``'s), cast
    to the compute type."""

    shape: tuple
    dtype: Any

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          self.shape, jnp.float32).astype(self.dtype)


class GatedMlp(nn.Module):
    """``down(silu(gate u) * up u)``: :func:`gated_mlp` on the leaves
    ``gate/kernel``, ``up/kernel``, ``down/kernel``."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        wide = (cfg.d_model, cfg.mlp_width)
        return gated_mlp(
            u.astype(cfg.dtype), _Kernel(wide, cfg.dtype, name="gate")(),
            _Kernel(wide, cfg.dtype, name="up")(),
            _Kernel(wide[::-1], cfg.dtype, name="down")())


# ---------------------------------------------------------------------------
# the residual connector
# ---------------------------------------------------------------------------

class HyperConnection(nn.Module):
    """The read side of a manifold-constrained hyper-connection
    (arXiv 2512.24880) round one mixer.  A token's state is ``n =
    hc_streams`` residual streams, carried side by side as one
    ``(batch, seq, n * d_model)`` array in the compute type.  From
    ``u = RMSNorm(vec(X))``: ``h_pre = sigmoid(g_pre u Phi_pre +
    b_pre)`` (n), ``h_post = 2 sigmoid(g_post u Phi_post + b_post)``
    (n), ``H_res = Sinkhorn(clip(g_res mat(u Phi_res) + B_res))``
    (n x n).  Returns the mixer's input ``h_pre X`` and ``(h_post,
    H_res)`` for :func:`hc_write`.  The three ``Phi`` are one matrix,
    ``phi`` (columns: pre, post, res row by row); coefficients, Sinkhorn
    and the sums over streams are fp32.

    The arithmetic is ``ops/pallas_kernels.hc_read``: where the shapes
    tile (``d_model`` a multiple of 128, a token block of 256 or 128
    that divides the sequence) one Mosaic call forward, ``hc_read_fwd``,
    and one backward, ``hc_read_bwd``, each reading a token block's
    streams from HBM once — on a TPU; interpreted where
    ``flash_interpret`` says — and the ``jax.numpy`` form
    (``hc_read_reference``) elsewhere.  In the kernels the norm, ``X
    (scale Phi)``, the logistics, ``exp`` and Sinkhorn's rounds run in
    VMEM on a block: no fp32 or normed copy of the streams and no
    round's intermediate is ever written to HBM, forward or backward.

    Initial values (no published ones): ``phi`` N(0, 0.02), gates 0.01,
    ``b_pre = b_post = 0`` (``h_pre`` 1/2, ``h_post`` 1), ``B_res = 3
    I`` (``H_res`` ≈ 0.87 I + 0.043; 20 rounds then leave rows and
    columns within 1e-4 of 1 — from 4 I, whose limit lies nearer a
    permutation, they leave 2e-3): with the streams starting as n copies
    of the embedding, the layer starts out as a plain pre-norm residual
    up to the gates' 0.01."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, xs):
        cfg = self.cfg
        n, c, f32 = cfg.hc_streams, cfg.d_model, jnp.float32
        scale = self.param("norm_scale", nn.initializers.ones_init(),
                           (n * c,), f32)
        phi = self.param("phi", nn.initializers.normal(0.02),
                         (n * c, n * (n + 2)), f32)
        gates = self.param("gates", nn.initializers.constant(0.01), (3,), f32)
        b_pre = self.param("b_pre", nn.initializers.zeros_init(), (n,), f32)
        b_post = self.param("b_post", nn.initializers.zeros_init(), (n,), f32)
        b_res = self.param("b_res", lambda key, shape: 3.0 * jnp.eye(n), (n, n))
        return hc_read(xs, scale, phi, gates, b_pre, b_post, b_res,
                       norm_eps=cfg.norm_eps, clamp=cfg.hc_clamp,
                       iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
                       interpret=cfg.flash_interpret)


def hc_write(xs, coefficients, y, interpret: bool = False):
    """The write side: ``X' = H_res X + h_post^T y``, stream ``i`` of
    the result ``sum_j H_res[i, j] X_j + h_post[i] y``, summed in fp32
    and cast stream by stream (``ops/pallas_kernels.hc_write``).  Where
    the shapes tile, one Mosaic call forward (``hc_write_fwd``: every
    stream written into the one result, no concatenation) and one
    backward (``hc_write_bwd``: ``dX``, ``dy`` and the n n + n
    coefficient gradients as fp32 row sums, from one reading of the
    streams); its residuals are its inputs, so a rematerialised block
    does not run it again for its own backward."""
    with jax.named_scope("hc"):
        return pallas_kernels.hc_write(xs, *coefficients, y,
                                       interpret=interpret)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class HybridBlock(nn.Module):
    """``x + residual_scale * mixer(RMSNorm(x))``, the mixer named by its
    kind — or, with ``residual="hc"``, the same mixer and norm between
    the two sides of a hyper-connection.  ``remat_connector`` (a policy of
    ``memory/remat.py``): rematerialise the two sides of the
    hyper-connection on their own, for a block that is not
    rematerialised whole."""

    cfg: HybridConfig
    kind: str
    remat_connector: str = "none"

    @nn.compact
    def __call__(self, x, positions):
        from horovod_tpu.memory.remat import remat_block, remat_fn

        cfg = self.cfg
        write = None
        if cfg.residual == "hc":
            streams, write = x, remat_fn(functools.partial(
                hc_write, interpret=cfg.flash_interpret),
                self.remat_connector)
            x, coefficients = remat_block(
                HyperConnection, self.remat_connector)(cfg, name="hc")(x)
        u = RMSNorm(epsilon=cfg.norm_eps, name="norm")(x)
        name = KINDS[self.kind]
        if self.kind == "M":
            y = Mamba2Mixer(cfg, name=name)(u)
        elif self.kind == "E":
            y = ExpertMixer(cfg, name=name)(u)
        elif self.kind == "D":
            y = GatedMlp(cfg, name=name)(u)
        elif cfg.attention_kind == "latent":
            y = LatentAttention(cfg.attention(), name=name)(u, positions)
        else:
            y = Attention(cfg.attention(), name=name)(u, positions)
        if write is not None:
            return write(streams, coefficients, y)
        if cfg.residual_scale != 1.0:
            y = y * cfg.residual_scale
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
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                         embedding_init=nn.initializers.normal(0.02),
                         name="embed")
        x = embed(tokens)
        if cfg.embedding_scale != 1.0:
            x = x * cfg.embedding_scale
        policy = resolve_remat_policy(cfg.remat_policy, None)
        block = remat_block(HybridBlock, policy)
        mlp_block = block if cfg.mlp_remat_policy is None else remat_block(
            HybridBlock, resolve_remat_policy(cfg.mlp_remat_policy, None))
        hc = cfg.residual == "hc"
        if hc:      # every stream starts as the embedding
            x = jnp.tile(x, (1, 1, cfg.hc_streams))
        for i, kind in enumerate(cfg.pattern):
            # an expert block is not rematerialised as a block: its routed
            # part rematerialises itself around the grouped matmuls'
            # results (held_expert_ffn), so those run once forward — and
            # the two sides of its hyper-connection do so on their own
            if kind == "E":
                x = HybridBlock(cfg, kind, policy,
                                name=f"layer_{i}")(x, positions)
            else:
                x = (mlp_block if kind == "D" else block)(
                    cfg, kind, name=f"layer_{i}")(x, positions)
        if hc:      # the streams are summed, in fp32, each sliced first
            x = sum(x[..., j * cfg.d_model:(j + 1) * cfg.d_model]
                    .astype(jnp.float32)
                    for j in range(cfg.hc_streams)).astype(cfg.dtype)
        # the scope ``head`` (docs/metrics.md): final norm, logits and
        # their divisor
        with jax.named_scope("head"):
            x = RMSNorm(epsilon=cfg.norm_eps, name="ln_f")(x)
            # bf16 operands, fp32 logits: the softmax is taken from them
            if cfg.tie_head:    # the embedding's leaf, (vocab, d_model)
                logits = lax.dot_general(
                    x, embed.embedding.astype(cfg.dtype),
                    (((2,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            else:
                head = self.param("head", nn.initializers.lecun_normal(),
                                  (cfg.d_model, cfg.vocab_size),
                                  jnp.float32)
                logits = lax.dot_general(x, head.astype(cfg.dtype),
                                         (((2,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
            if cfg.logits_divisor != 1.0:
                logits = logits / cfg.logits_divisor
        return logits


def hybrid_lm_loss(model: HybridLM, variables, batch) -> jax.Array:
    """Next-token cross-entropy over the vocabulary slice, in fp32, of
    ``batch["inputs"]`` against ``batch["labels"]`` (mean of the shard)."""
    import optax

    logits = model.apply(variables, batch["inputs"])
    with jax.named_scope("loss"):
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
    from horovod_tpu.ops.pallas_kernels import flash_lanes, ssd_head_block

    tokens = int(shape[0]) * int(shape[1])
    lo, hi = cfg.experts_held
    latent = cfg.attention_kind == "latent"
    qk_width = cfg.nope_dim + cfg.rope_dim if latent else cfg.head_dim
    facts = {
        "hybrid_pattern": cfg.pattern,
        "experts_held": hi - lo,
        "tokens_per_step": tokens,
        "assignments_per_step": tokens * cfg.top_k,
        "expert_buffer_rows": tokens * cfg.top_k,   # any routing fits
        # a token's residual streams (1: a plain residual) and the rounds
        # that make their mixing matrix doubly stochastic
        "hc_streams": cfg.hc_streams if cfg.residual == "hc" else 1,
        "hc_sinkhorn_iters":
            cfg.hc_sinkhorn_iters if cfg.residual == "hc" else 0,
        # the hyper-connections whose two sides run as Mosaic kernels
        # (hc_read / hc_write): every one where the shapes tile, or none
        "hc_fused_sublayers":
            len(cfg.pattern) if cfg.residual == "hc" and hc_runs_kernels(
                int(shape[1]), cfg.hc_streams, cfg.d_model,
                cfg.flash_interpret) else 0,
        # the expert layers whose rows-to-tokens sums (the combine, the
        # dispatch's transpose) run as the Mosaic kernel moe_row_sum:
        # every one where the shapes tile, or none
        "moe_row_sum_layers":
            cfg.pattern.count("E") if moe_row_sum_runs_kernel(
                tokens, cfg.d_model, cfg.top_k, hi - lo, cfg.dtype,
                cfg.flash_interpret) else 0,
        "attn_qk_width": qk_width,
        "attn_v_width": cfg.v_dim if latent else cfg.head_dim,
        # the lanes the flash kernels serve the q / k width in
        "flash_qk_lanes": flash_lanes(qk_width),
        # grouped-query attention's head and softmax scale, the plain
        # residual's branch scale, whether the head is the embedding
        "attn_head_width": qk_width,
        "attn_scale": float(
            cfg.attention().softmax_scale() if latent
            else cfg.attention_scale or cfg.head_dim ** -0.5),
        "residual_scale": float(cfg.residual_scale),
        "tied_head": int(cfg.tie_head),
    }
    if "M" in cfg.pattern:      # the scan's facts, where a scan runs
        mosaic = ssd_runs_kernels(
            int(shape[1]), cfg.mamba_heads, cfg.mamba_head_dim,
            cfg.mamba_groups, cfg.ssm_state, cfg.chunk, cfg.flash_interpret)
        # forward and backward, and the forward again where the block is
        # rematerialised
        recomputed = resolve_remat_policy(cfg.remat_policy, None) != "none"
        per_group = cfg.mamba_heads // cfg.mamba_groups
        facts.update(
            mamba_groups=cfg.mamba_groups, ssd_heads_per_group=per_group,
            # heads a grid step of the scan's kernels (0: no kernels run)
            ssd_head_block=ssd_head_block(
                per_group, cfg.mamba_head_dim, cfg.ssm_state, cfg.chunk,
                jnp.dtype(cfg.dtype).itemsize) if mosaic else 0,
            ssd_chunk=cfg.chunk,
            ssd_chunks_per_sequence=-(-int(shape[1]) // cfg.chunk),
            ssd_impl="mosaic" if mosaic else "einsum",
            ssd_kernel_calls_per_layer=(2 + recomputed) if mosaic else 0,
            # the Mamba layers whose convolution and gated norm run as
            # Mosaic kernels (mamba_conv / mamba_gated_norm): every one
            # where the shapes tile, or none
            mamba_fused_layers=cfg.pattern.count("M") if mamba_runs_kernels(
                int(shape[1]), cfg.mamba_heads, cfg.mamba_head_dim,
                cfg.mamba_groups, cfg.ssm_state, cfg.conv_kernel, cfg.dtype,
                cfg.flash_interpret) else 0,
            mamba_conv_channels=cfg.mamba_inner
            + 2 * cfg.mamba_groups * cfg.ssm_state)
        for impl in ("mosaic", "einsum"):       # 1 on the one that runs
            telemetry.gauge("hvd_hybrid_ssd_impl",
                            "set when a HybridLM step is traced").set(
                                int(impl == facts["ssd_impl"]), impl=impl)
    telemetry.annotate(**facts)
    # gauges record only while telemetry is enabled, as every handle
    for name, value in facts.items():
        if isinstance(value, (int, float)):
            telemetry.gauge(f"hvd_hybrid_{name}",
                            "set when a HybridLM step is traced").set(value)
