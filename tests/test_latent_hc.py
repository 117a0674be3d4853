"""Latent attention on the two-width flash kernel, the hyper-connection
and the SwiGLU held experts: each against plain ``jax.numpy`` at a small
size, seeded random weights, on the CPU (kernels interpreted)."""

import functools
import math
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import HybridConfig, HybridLM, hybrid_lm_loss
from horovod_tpu import telemetry
from horovod_tpu.models.hybrid import (
    ExpertMixer,
    HyperConnection,
    hc_write,
    sinkhorn,
)
from horovod_tpu.models.transformer import (
    LatentAttention,
    LatentAttentionConfig,
    rotary_embedding,
    yarn_frequencies,
    yarn_mscale,
)
from horovod_tpu.ops import pallas_kernels as pk
from horovod_tpu.parallel.ring_attention import reference_attention

YARN = {"factor": 64, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


def tiny(**kw):
    base = dict(
        vocab_size=256, pattern="*D*E", d_model=32, norm_eps=1e-6,
        dtype=jnp.float32, residual="hc", attention_kind="latent",
        num_heads=2, q_rank=12, kv_rank=8, nope_dim=16, rope_dim=8,
        v_dim=16, rope_scaling=YARN, mlp_width=48, num_experts=8,
        experts_held=(2, 6), top_k=3, expert_width=16, shared_width=16,
        routed_scale=2.0, expert_act="swiglu")
    base.update(kw)
    return HybridConfig(**base)


# ---------------------------------------------------------------------------
# the flash kernel at two widths
# ---------------------------------------------------------------------------

def _qkvg(d, dv, t=256, h=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (1, t, h, d)),
            jax.random.normal(keys[1], (1, t, h, d)),
            jax.random.normal(keys[2], (1, t, h, dv)),
            jax.random.normal(keys[3], (1, t, h, dv)))


@pytest.mark.parametrize("d,dv", [(48, 32), (24, 16), (16, 48), (32, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_with_unequal_widths_is_dense_attention(d, dv, causal):
    """q and k ``d`` wide, v ``dv``: the output and dv are v-wide, dq and
    dk q-wide, and all four agree with dense attention at a scale that
    is not ``d ** -0.5``."""
    q, k, v, g = _qkvg(d, dv)
    scale = 0.31

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, causal=causal, scale=scale,
                                  block_q=128, block_k=128, interpret=True)

    def dense(q, k, v):
        return reference_attention(q, k, v, causal=causal, scale=scale)

    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(dense, q, k, v)
    assert out.shape == (1, 256, 2, dv)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for got, ref, like in zip(vjp(g), want_vjp(g), (q, k, v)):
        assert got.shape == like.shape
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_the_backward_asks_for_the_vmem_its_two_widths_need():
    """At equal widths the one-width reckoning, to the byte (it is a
    compiler parameter of the call: the lowered program holds it); a
    second width adds its own, in whole 128-lane tiles where it is held
    as given, as it is where its gradient adds up transposed."""
    for t, d in ((1024, 128), (4096, 128), (8192, 128), (32768, 128)):
        one_width = 6 * t * d * 2 + t * d * 4 + 4 * 2 * 8 * t * 4 \
            + 4 * 2 * 512 * d * 2 + 3 * 512 * 512 * 4 + 4 * 512 * d * 4
        assert pk._flash_bwd_vmem_bytes(t, d, 512, 512, 2) == one_width
        assert pk._flash_bwd_vmem_bytes(t, d, 512, 512, 2, d) == one_width
    # 192 takes the room of 256 lanes in q, dq whole rows and their
    # blocks; dq's fp32 sum, q transposed (a row) and k transposed (a
    # block) 192 rows of sublanes; the fp32 dk sum 192 rows, dv's 128
    assert pk.flash_lanes(192) == 256 and pk.flash_lanes(128) == 128
    t = 4096
    two_widths = (4 * 256 + 2 * 128) * t * 2 + t * 192 * 4 \
        + 4 * 2 * 8 * t * 4 + (t + 512) * 192 * 2 \
        + 4 * 512 * (256 + 128) * 2 + 3 * 512 * 512 * 4 \
        + 2 * 512 * (192 + 128) * 4
    assert pk._flash_bwd_vmem_bytes(t, 192, 512, 512, 2, 128) == two_widths
    # q transposed costs more than dq's sum gives back at 192 lanes
    assert pk._flash_bwd_vmem_bytes(t, 256, 512, 512, 2, 128) \
        < two_widths < 24 << 20
    # 64 and 64: every gradient transposed, dO too
    narrow = (4 * 128 + 2 * 128) * t * 2 + t * 64 * 4 \
        + 4 * 2 * 8 * t * 4 + (t + 512) * 64 * 2 + t * 64 * 2 \
        + 4 * 512 * 256 * 2 + 3 * 512 * 512 * 4 + 2 * 512 * 128 * 4
    assert pk._flash_bwd_vmem_bytes(t, 64, 512, 512, 2) == narrow \
        < pk._flash_bwd_vmem_bytes(t, 128, 512, 512, 2)


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def test_yarn_frequencies_against_hand_computed_values():
    """dim 64, base 1e4, factor 64, original context 4096: pairs 0-10
    keep plain RoPE's frequency (they turn more than 32 times in 4,096
    positions: 64 ln(4096 / 64 pi) / (2 ln 1e4) = 10.47), pairs 23-31
    are slowed 64-fold (fewer than one turn: 22.51, rounded up), pairs
    11-22 blend linearly."""
    f = np.asarray(yarn_frequencies(64, 10_000.0, 64, 4096, 32, 1))
    plain = 10_000.0 ** (-np.arange(32) / 32.0)
    assert f.shape == (32,)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 64, rtol=1e-6)
    # pair 16 is 6/13 of the way from pair 10 to pair 23
    np.testing.assert_allclose(
        f[16], plain[16] * (7 / 13) + plain[16] / 64 * (6 / 13), rtol=1e-6)
    np.testing.assert_allclose(f[16], 0.01 * (7 / 13 + 6 / 13 / 64),
                               rtol=1e-5)
    assert np.all(np.diff(f) < 0)
    assert yarn_mscale(64, 1) == pytest.approx(1.4158883, rel=1e-6)
    assert yarn_mscale(1, 1) == 1.0
    cfg = LatentAttentionConfig(rope_scaling=YARN)
    assert cfg.softmax_scale() == pytest.approx(
        192 ** -0.5 * 1.4158883 ** 2, rel=1e-6)
    with pytest.raises(ValueError, match="ratio 1 only"):
        LatentAttentionConfig(rope_scaling=dict(YARN, mscale=0.7))
    assert LatentAttentionConfig().softmax_scale() == 192 ** -0.5
    # the default frequencies are today's
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    np.testing.assert_array_equal(
        rotary_embedding(x, jnp.arange(8)),
        rotary_embedding(x, jnp.arange(8), inv_freq=1.0 / (
            10_000.0 ** (jnp.arange(0, 16, 2, dtype=jnp.float32) / 16))))


def _latent_by_hand(p, x, cfg):
    """The module's equations with numpy-style indexing, one head at a
    time, angles from ``yarn_frequencies``."""
    def rms(v, scale):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True)
                           + cfg.norm_eps) * scale

    def rotate(v, pos):                      # v: (rope,)
        f = np.asarray(cfg.inv_freq())
        out = np.empty_like(v)
        for i in range(len(f)):
            c, s = math.cos(pos * f[i]), math.sin(pos * f[i])
            out[2 * i] = v[2 * i] * c - v[2 * i + 1] * s
            out[2 * i + 1] = v[2 * i] * s + v[2 * i + 1] * c
        return out

    x = np.asarray(x, np.float64)
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    t = x.shape[0]
    nope, rope, dv, h = cfg.nope_dim, cfg.rope_dim, cfg.v_dim, cfg.num_heads
    c_q = rms(x @ p["q_a"]["kernel"], p["q_a_norm"]["scale"])
    q = (c_q @ p["q_b"]["kernel"]).reshape(t, h, nope + rope)
    down = x @ p["kv_a"]["kernel"]
    c_kv = rms(down[:, :cfg.kv_rank], p["kv_a_norm"]["scale"])
    kv = (c_kv @ p["kv_b"]["kernel"]).reshape(t, h, nope + dv)
    k_r = np.stack([rotate(down[i, cfg.kv_rank:], i) for i in range(t)])
    out = np.zeros((t, h, dv))
    for head in range(h):
        qh = np.concatenate(
            [q[:, head, :nope],
             np.stack([rotate(q[i, head, nope:], i) for i in range(t)])], 1)
        kh = np.concatenate([kv[:, head, :nope], k_r], 1)
        s = qh @ kh.T * cfg.softmax_scale()
        s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        out[:, head] = (w / w.sum(-1, keepdims=True)) @ kv[:, head, nope:]
    return out.reshape(t, h * dv) @ p["o"]["kernel"]


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_latent_attention_is_its_equations(impl):
    cfg = LatentAttentionConfig(
        d_model=32, num_heads=2, q_rank=12, kv_rank=8, nope_dim=16,
        rope_dim=8, v_dim=16, rope_scaling=YARN, dtype=jnp.float32,
        attention_impl=impl, flash_block=128, flash_interpret=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 32))
    module = LatentAttention(cfg)
    variables = module.init(jax.random.PRNGKey(2), x, jnp.arange(16))
    params = nn.meta.unbox(variables)["params"]
    assert set(params) == {"q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
                           "kv_b", "o"}
    assert params["q_b"]["kernel"].shape == (12, 2 * 24)
    assert params["kv_a"]["kernel"].shape == (32, 8 + 8)
    assert params["kv_b"]["kernel"].shape == (8, 2 * 32)
    assert params["o"]["kernel"].shape == (2 * 16, 32)
    got = module.apply(variables, x, jnp.arange(16))
    np.testing.assert_allclose(got[0], _latent_by_hand(params, x[0], cfg),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# the hyper-connection
# ---------------------------------------------------------------------------

def test_sinkhorn_makes_rows_and_columns_sum_to_one():
    """At the logits a layer starts from (its ``B_res`` of 3 I) with a
    token's part ten times the initial one; (n, n, batch, seq)."""
    logits = 3.0 * jnp.eye(4)[:, :, None, None] \
        + 0.25 * jax.random.normal(jax.random.PRNGKey(0), (4, 4, 2, 64))
    m = sinkhorn(logits, 20, 1e-6)
    assert m.dtype == jnp.float32 and bool(jnp.all(m > 0))
    np.testing.assert_allclose(jnp.sum(m, axis=0), 1.0, atol=1e-3)
    np.testing.assert_allclose(jnp.sum(m, axis=1), 1.0, atol=1e-3)
    # one round is not enough: the 20 are doing something
    rough = sinkhorn(logits, 1, 1e-6)
    assert float(jnp.max(jnp.abs(jnp.sum(rough, axis=1) - 1.0))) > 3e-3


def _hc_by_hand(p, xs, y_of, cfg):
    """``X' = H_res X + h_post^T F(h_pre X)`` on (batch, seq, n, width)."""
    n, c = cfg.hc_streams, cfg.d_model
    x = xs.reshape(xs.shape[:2] + (n, c))
    u = xs * jax.lax.rsqrt(jnp.mean(xs * xs, -1, keepdims=True)
                           + cfg.norm_eps) * p["norm_scale"]
    a = u @ p["phi"]
    h_pre = jax.nn.sigmoid(p["gates"][0] * a[..., :n] + p["b_pre"])
    h_post = 2 * jax.nn.sigmoid(p["gates"][1] * a[..., n:2 * n]
                                + p["b_post"])
    m = jnp.exp(jnp.clip(p["gates"][2] * a[..., 2 * n:].reshape(
        a.shape[:2] + (n, n)) + p["b_res"], *cfg.hc_clamp))
    for _ in range(cfg.hc_sinkhorn_iters):
        m = m / (m.sum(-1, keepdims=True) + cfg.hc_eps)
        m = m / (m.sum(-2, keepdims=True) + cfg.hc_eps)
    y = y_of(jnp.einsum("btj,btjc->btc", h_pre, x))
    out = jnp.einsum("btij,btjc->btic", m, x) \
        + h_post[..., None] * y[:, :, None, :]
    return out.reshape(xs.shape)


def test_hyper_connection_layer_output_and_gradients():
    cfg = tiny()
    n, c = cfg.hc_streams, cfg.d_model
    keys = jax.random.split(jax.random.PRNGKey(3), 8)
    xs = jax.random.normal(keys[0], (2, 12, n * c))
    w = jax.random.normal(keys[1], (c, c)) * 0.3
    module = HyperConnection(cfg)
    params = module.init(keys[2], xs)["params"]
    # away from the initial values, where the streams would stay copies
    params = dict(
        params, gates=jnp.asarray([0.7, -0.5, 0.9]),
        phi=jax.random.normal(keys[3], params["phi"].shape) * 0.05,
        b_pre=jax.random.normal(keys[4], (n,)),
        b_post=jax.random.normal(keys[5], (n,)),
        b_res=params["b_res"] + jax.random.normal(keys[6], (n, n)))
    cot = jax.random.normal(keys[7], xs.shape)

    def system(p, xs, w):
        x_in, coefficients = module.apply({"params": p}, xs)
        return jnp.sum(hc_write(xs, coefficients, jnp.tanh(x_in @ w)) * cot)

    def plain(p, xs, w):
        return jnp.sum(_hc_by_hand(p, xs, lambda v: jnp.tanh(v @ w), cfg)
                       * cot)

    got, grads = jax.jit(jax.value_and_grad(system, (0, 1, 2)))(
        params, xs, w)
    want, want_grads = jax.value_and_grad(plain, (0, 1, 2))(params, xs, w)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, r in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5)


def test_hyper_connection_coefficients_stay_float32_under_bfloat16():
    """Streams and the mixer's input in the compute type; coefficients,
    Sinkhorn and the sums over streams in fp32 — every ``exp`` and every
    division of the traced read side is an fp32 operation."""
    cfg = tiny(dtype=jnp.bfloat16)
    xs = jax.random.normal(jax.random.PRNGKey(0),
                           (1, 8, cfg.hc_streams * cfg.d_model),
                           jnp.bfloat16)
    module = HyperConnection(cfg)
    variables = module.init(jax.random.PRNGKey(1), xs)
    x_in, (post, mix) = module.apply(variables, xs)
    assert x_in.dtype == jnp.bfloat16
    assert post.dtype == mix.dtype == jnp.float32
    assert mix.shape == (4, 4, 1, 8) and post.shape == (4, 1, 8)
    np.testing.assert_allclose(jnp.sum(mix, axis=0), 1.0, atol=1e-3)
    np.testing.assert_allclose(jnp.sum(mix, axis=1), 1.0, atol=1e-3)
    assert hc_write(xs, (post, mix), x_in).dtype == jnp.bfloat16
    jaxpr = jax.make_jaxpr(lambda v, x: module.apply(v, x))(variables, xs)
    rounds = [e for e in jaxpr.jaxpr.eqns
              if e.primitive.name in ("exp", "div", "logistic")]
    assert len(rounds) >= 2 * cfg.hc_sinkhorn_iters + 3
    assert all(e.outvars[0].aval.dtype == jnp.float32 for e in rounds)
    # at the initial values a layer is a plain pre-norm residual up to
    # the gates' 0.01: h_pre 1/2, h_post 1, H_res near the identity
    np.testing.assert_allclose(post, 1.0, atol=0.02)
    np.testing.assert_allclose(mix[0, 0], 0.87, atol=0.01)


# the two sides as kernels (interpreted): ops/pallas_kernels.hc_read and
# hc_write against their jax.numpy forms

HC = dict(norm_eps=1e-6, clamp=(-30.0, 30.0), iters=20, eps=1e-6)
HC_PARAMS = ("norm_scale", "phi", "gates", "b_pre", "b_post", "b_res")


def _hc_case(n, c, batch, seq, dtype=jnp.float32, seed=0):
    """Streams that differ and parameters off their initial values
    (gates 1, ``B_res`` ~ N(0, 1)): from the model's own a transposed
    mixing matrix reads the same (PERF.md, PR 32)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    k = n * c
    xs = (jax.random.normal(keys[0], (batch, seq, k))
          * (1.0 + 0.5 * jax.random.normal(keys[1], (k,)))).astype(dtype)
    y = jax.random.normal(keys[2], (batch, seq, c)).astype(dtype)
    params = {
        "norm_scale": 1.0 + 0.1 * jax.random.normal(keys[3], (k,)),
        "phi": 0.05 * jax.random.normal(keys[4], (k, n * (n + 2))),
        "gates": jnp.ones((3,)),
        "b_pre": jax.random.normal(keys[5], (n,)),
        "b_post": jax.random.normal(keys[6], (n,)),
        "b_res": jax.random.normal(keys[7], (n, n))}
    cot = jax.random.normal(keys[8], (batch, seq, k))
    return xs, y, params, cot


def _hc_system(read, write, cot):
    """``sum(cot . write(X, read(X), tanh(x_in) + y))`` and what both
    sides return."""
    def system(params, xs, y):
        x_in, (post, mix) = read(xs, *(params[k] for k in HC_PARAMS), **HC)
        out = write(xs, post, mix,
                    (jnp.tanh(x_in.astype(jnp.float32))
                     + y.astype(jnp.float32)).astype(xs.dtype))
        return jnp.sum(out.astype(jnp.float32) * cot), (x_in, post, mix, out)
    return system


def _hc_both(n, c, batch, seq, block, dtype=jnp.float32, write=None):
    xs, y, params, cot = _hc_case(n, c, batch, seq, dtype)
    fused = _hc_system(
        functools.partial(pk.hc_read, interpret=True, token_block=block),
        write or functools.partial(pk.hc_write, interpret=True,
                                   token_block=block), cot)
    plain = _hc_system(pk.hc_read_reference, pk.hc_write_reference, cot)
    return [jax.jit(jax.value_and_grad(f, (0, 1, 2), has_aux=True))(
        params, xs, y) for f in (fused, plain)]


def _rel(got, want):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("n,batch,seq,block", [
    (4, 1, 256, 256),       # one block of tokens
    (4, 1, 256, 128),       # two: the matrix's gradient adds up across
    (2, 2, 128, 128),       # two streams, a block a batch row
    (2, 1, 384, 128),       # three
])
def test_the_fused_hyper_connection_is_its_jax_numpy_form(n, batch, seq,
                                                          block):
    """fp32, off the initial values: the mixer's input, ``h_post``, the
    mixing matrix, the streams written, and the gradient of every input
    and parameter."""
    ((got, outs), grads), ((want, ref_outs), ref_grads) = _hc_both(
        n, 128, batch, seq, block)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, o, r in zip(("x_in", "post", "mix", "out"), outs, ref_outs):
        assert o.shape == r.shape and o.dtype == r.dtype, name
        np.testing.assert_allclose(o, r, rtol=2e-5, atol=2e-5, err_msg=name)
    assert outs[2].shape == (n, n, batch, seq)
    assert set(grads[0]) == set(HC_PARAMS)
    for name in HC_PARAMS:
        assert _rel(grads[0][name], ref_grads[0][name]) < 2e-5, name
    assert _rel(grads[1], ref_grads[1]) < 2e-5      # the streams'
    assert _rel(grads[2], ref_grads[2]) < 2e-5      # the mixer output's


def test_a_transposed_mixing_matrix_fails_the_same_comparison():
    """The comparison above is not blind to the matrix's arrangement:
    the kernels' write side given ``H_res`` transposed misses by five
    orders of magnitude more than the limit."""
    def transposed(xs, post, mix, y):
        return pk.hc_write(xs, post, jnp.swapaxes(mix, 0, 1), y,
                           interpret=True)

    ((_, outs), grads), ((_, ref_outs), ref_grads) = _hc_both(
        4, 128, 1, 256, 256, write=transposed)
    np.testing.assert_allclose(outs[2], ref_outs[2], rtol=2e-5, atol=2e-5)
    assert _rel(outs[3], ref_outs[3]) > 0.1
    assert _rel(grads[1], ref_grads[1]) > 0.1
    assert _rel(grads[0]["b_res"], ref_grads[0]["b_res"]) > 0.1


def test_the_fused_hyper_connection_under_bfloat16_rounds_as_its_form():
    """bf16 streams: outputs in the streams' type, coefficients fp32;
    the kernels differ from the jax.numpy form by no more than that form
    differs from itself in fp32 — the streams' own rounding."""
    ((_, outs), grads), ((_, ref_outs), ref_grads) = _hc_both(
        4, 128, 1, 256, 128, jnp.bfloat16)
    (_, exact_outs), exact_grads = _hc_both(4, 128, 1, 256, 128)[1]
    assert outs[0].dtype == outs[3].dtype == jnp.bfloat16
    assert outs[1].dtype == outs[2].dtype == jnp.float32
    assert grads[1].dtype == grads[2].dtype == jnp.bfloat16
    for o, r in zip(outs, ref_outs):
        assert _rel(o, r) < 4e-3
    for name in HC_PARAMS:
        room = 2 * _rel(ref_grads[0][name], exact_grads[0][name]) + 1e-3
        assert _rel(grads[0][name], ref_grads[0][name]) < room, name
    for i in (1, 2):
        room = 2 * _rel(ref_grads[i], exact_grads[i]) + 1e-3
        assert _rel(grads[i], ref_grads[i]) < room


@pytest.mark.parametrize("c,seq,interpret", [
    (96, 128, True),        # a stream is no whole number of lane tiles
    (128, 120, True),       # no token block divides the sequence
    (128, 128, False),      # no TPU, no interpreter
])
def test_shapes_that_do_not_tile_take_the_jax_numpy_form(c, seq, interpret):
    """Equal results, and no kernel in the traced program."""
    xs, y, params, _ = _hc_case(2, c, 1, seq)
    assert not pk.hc_runs_kernels(seq, 2, c, interpret)
    args = [params[k] for k in HC_PARAMS]

    def both(xs, y):
        x_in, (post, mix) = pk.hc_read(xs, *args, **HC, interpret=interpret)
        return x_in, post, mix, pk.hc_write(xs, post, mix, y,
                                            interpret=interpret)

    assert "pallas_call" not in str(jax.make_jaxpr(both)(xs, y))
    x_in, (post, mix) = pk.hc_read_reference(xs, *args, **HC)
    for got, want in zip(both(xs, y), (
            x_in, post, mix, pk.hc_write_reference(xs, post, mix, y))):
        np.testing.assert_array_equal(got, want)
    assert pk.hc_runs_kernels(128, 2, 128, True)
    assert not pk.hc_runs_kernels(128, 9, 128, True)    # a slab is 8 rows


def test_a_fused_layer_is_four_named_calls_whose_residuals_are_its_inputs():
    """One Mosaic call a side and pass; under ``jax.checkpoint`` the
    backward runs the read side's forward again (the mixer needs its
    input) and never the write side's."""
    xs, y, params, cot = _hc_case(4, 128, 1, 128)
    system = _hc_system(
        functools.partial(pk.hc_read, interpret=True),
        functools.partial(pk.hc_write, interpret=True), cot)

    def names(f):
        text = str(jax.make_jaxpr(f)(params, xs, y))
        return sorted(re.findall(r"name=(hc_\w+)", text))

    assert names(lambda *a: system(*a)[0]) == ["hc_read_fwd", "hc_write_fwd"]
    assert names(jax.grad(lambda *a: system(*a)[0])) == [
        "hc_read_bwd", "hc_read_fwd", "hc_write_bwd", "hc_write_fwd"]
    assert names(jax.grad(jax.checkpoint(lambda *a: system(*a)[0]))) == [
        "hc_read_bwd", "hc_read_fwd", "hc_read_fwd", "hc_write_bwd",
        "hc_write_fwd"]


def test_the_model_s_layer_runs_the_kernels_where_its_shapes_tile():
    """``HyperConnection`` / ``hc_write`` at a tiling width through the
    kernels (interpreted) equal the same layer through the jax.numpy
    form, values and parameter gradients; every call under ``hc``."""
    cfg = tiny(d_model=128, flash_interpret=True)
    plain_cfg = tiny(d_model=128)
    xs, y, _, cot = _hc_case(4, 128, 1, 128)
    module = HyperConnection(cfg)
    params = module.init(jax.random.PRNGKey(0), xs)["params"]
    params = dict(params, gates=jnp.ones((3,)), b_res=jax.random.normal(
        jax.random.PRNGKey(1), (4, 4)))

    def system(cfg, p, xs):
        x_in, coefficients = HyperConnection(cfg).apply({"params": p}, xs)
        return jnp.sum(hc_write(xs, coefficients, jnp.tanh(x_in) + y,
                                interpret=cfg.flash_interpret) * cot)

    got, grads = jax.value_and_grad(functools.partial(system, cfg))(params, xs)
    want, ref = jax.value_and_grad(functools.partial(system, plain_cfg))(
        params, xs)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name in params:
        assert _rel(grads[name], ref[name]) < 2e-5, name
    text = str(jax.make_jaxpr(jax.grad(functools.partial(system, cfg)))(
        params, xs))
    assert len(re.findall(r"name=hc_\w+", text)) == 4
    assert "pallas_call" not in str(jax.make_jaxpr(
        functools.partial(system, plain_cfg))(params, xs))


# ---------------------------------------------------------------------------
# SwiGLU experts, held and dropless
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("crowd", [0.0, 4.0, 50.0])
def test_swiglu_experts_drop_nothing_at_any_imbalance(crowd):
    """The router pushed towards the held experts until every assignment
    lands on them: the layer equals a loop over the held experts with
    0/1 masks, values and gradients."""
    cfg = tiny(pattern="E", experts_held=(2, 6))
    d, lo, hi = cfg.d_model, *cfg.experts_held
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 40, d))
    module = ExpertMixer(cfg)
    params = module.init(jax.random.PRNGKey(1), u)["params"]
    assert set(params) == {"router", "bias", "experts_gate", "experts_up",
                           "experts_down", "shared_gate", "shared_up",
                           "shared_down"}
    params = dict(params, bias=jnp.zeros((8,)).at[lo:hi].set(crowd))

    def plain(p, u):
        s = jax.nn.sigmoid(u @ p["router"])
        _, chosen = jax.lax.top_k(s + p["bias"], cfg.top_k)
        picked = jnp.take_along_axis(s, chosen, -1)
        weights = picked / picked.sum(-1, keepdims=True) * cfg.routed_scale
        y = (jax.nn.silu(u @ p["shared_gate"]["kernel"])
             * (u @ p["shared_up"]["kernel"])) @ p["shared_down"]["kernel"]
        for e in range(lo, hi):
            w = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
            y = y + w[..., None] * (
                (jax.nn.silu(u @ p["experts_gate"][e - lo])
                 * (u @ p["experts_up"][e - lo])) @ p["experts_down"][e - lo])
        return y, chosen

    want, chosen = plain(params, u)
    landed = int(jnp.sum((chosen >= lo) & (chosen < hi)))
    assert landed == 2 * 40 * 3 if crowd else landed < 2 * 40 * 3
    np.testing.assert_allclose(
        jax.jit(lambda p, u: module.apply({"params": p}, u))(params, u),
        want, rtol=2e-4, atol=2e-5)
    cot = jax.random.normal(jax.random.PRNGKey(2), u.shape)
    got = jax.jit(jax.grad(lambda p, u: jnp.sum(
        module.apply({"params": p}, u) * cot), (0, 1)))(params, u)
    ref = jax.grad(lambda p, u: jnp.sum(plain(p, u)[0] * cot), (0, 1))(
        params, u)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(g, r, rtol=2e-3, atol=2e-4)


def test_a_swiglu_layer_is_two_grouped_matmuls_a_pass():
    """``[gate | up]`` side by side through one grouped matmul, ``down``
    through another: per buffer size one ``ragged_dot`` pair forward."""
    cfg = tiny(pattern="E")
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 16, cfg.d_model))
    module = ExpertMixer(cfg)
    variables = module.init(jax.random.PRNGKey(1), u)
    text = str(jax.make_jaxpr(lambda v, u: module.apply(v, u))(variables, u))
    sizes = 3       # one row a token, two, top_k
    assert len(re.findall(r"= ragged_dot\w*\[", text)) == 2 * sizes


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("remat,d_model", [
    (None, 32), ("full", 32),
    ("full", 128),      # the hyper-connections' kernels, interpreted
])
def test_model_trains_a_step_whatever_is_rematerialised(remat, d_model):
    cfg = tiny(remat_policy=remat, train_router=False, d_model=d_model,
               attention_impl="flash", flash_block=128, flash_interpret=True)
    model = HybridLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 128), 0, 256)
    variables = model.init(jax.random.PRNGKey(1), tokens)
    loss, grads = jax.jit(jax.value_and_grad(
        functools.partial(hybrid_lm_loss, model)))(
            variables, {"inputs": tokens, "labels": tokens})
    assert np.isfinite(float(loss))
    plain = nn.meta.unbox(grads)["params"]
    assert not np.any(plain["layer_3"]["moe"]["router"])
    assert not np.any(plain["layer_3"]["moe"]["bias"])
    for i in range(4):
        assert np.any(plain[f"layer_{i}"]["hc"]["phi"])
    dense = HybridLM(tiny(remat_policy=None, train_router=False,
                          d_model=d_model))
    want, want_grads = jax.jit(jax.value_and_grad(
        functools.partial(hybrid_lm_loss, dense)))(
            variables, {"inputs": tokens, "labels": tokens})
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    # the connector's gradients are the jax.numpy form's, whichever runs
    for i in range(4):
        for name, got in plain[f"layer_{i}"]["hc"].items():
            ref = nn.meta.unbox(want_grads)["params"][f"layer_{i}"]["hc"][name]
            np.testing.assert_allclose(got, ref, rtol=2e-3, atol=1e-6,
                                       err_msg=f"layer_{i} {name}")


def test_a_plain_residual_model_holds_no_connector_parameters():
    cfg = HybridConfig(
        vocab_size=64, pattern="E*", d_model=32, num_heads=2, num_kv_heads=1,
        head_dim=16, num_experts=8, experts_held=(0, 4), top_k=2,
        expert_width=16, shared_width=16, dtype=jnp.float32)
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = HybridLM(cfg).init(jax.random.PRNGKey(0), tokens)["params"]
    assert set(params["layer_0"]) == {"norm", "moe"}
    assert set(params["layer_0"]["moe"]) == {
        "router", "bias", "experts_up", "experts_down", "shared_up",
        "shared_down"}
    assert set(params["layer_1"]) == {"norm", "attn"}


def test_config_refuses_kinds_it_does_not_know():
    for field in ("residual", "attention_kind", "expert_act"):
        with pytest.raises(ValueError, match=field):
            tiny(**{field: "other"})
    with pytest.raises(ValueError, match="pattern"):
        tiny(pattern="*X")


def test_a_traced_step_names_its_streams_and_widths_to_the_compile_span():
    cfg = tiny()
    model = HybridLM(cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    with telemetry.span("train_step.lower") as span:
        jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    for key, value in (("hc_streams", 4), ("hc_sinkhorn_iters", 20),
                       ("hc_fused_sublayers", 0),   # d_model 32: no lanes
                       ("attn_qk_width", 24), ("attn_v_width", 16),
                       ("flash_qk_lanes", 128), ("experts_held", 4),
                       ("hybrid_pattern", "*D*E")):
        assert span.attrs[key] == value, key
    # no Mamba layer, no scan: its facts are a pattern's with an M
    assert not [key for key in span.attrs if key.startswith("ssd_")]
    plain = HybridConfig(vocab_size=64, pattern="*", d_model=32, num_heads=2,
                         num_kv_heads=1, head_dim=16, dtype=jnp.float32)
    with telemetry.span("train_step.lower") as span:
        jax.eval_shape(HybridLM(plain).init, jax.random.PRNGKey(0), tokens)
    assert span.attrs["hc_streams"] == 1
    assert span.attrs["hc_sinkhorn_iters"] == 0
    assert span.attrs["hc_fused_sublayers"] == 0
    assert span.attrs["attn_qk_width"] == span.attrs["attn_v_width"] == 16


@pytest.mark.parametrize("d_model,seq,interpret,fused", [
    (128, 128, True, 4),    # lanes and a token block: every sublayer
    (128, 96, True, 0),     # no token block divides the sequence
    (32, 128, True, 0),     # a stream narrower than a lane tile
    (128, 128, False, 0),   # no TPU and no interpreter: the jax.numpy form
])
def test_the_step_counts_its_fused_hyper_connections(d_model, seq, interpret,
                                                     fused):
    """``hc_fused_sublayers`` on the compile span and as a gauge: the
    hyper-connections whose two sides run as kernels, from what the
    trace can observe."""
    cfg = tiny(d_model=d_model, flash_interpret=interpret)
    tokens = jnp.zeros((1, seq), jnp.int32)
    was_on = telemetry.enabled()
    telemetry.enable()
    try:
        with telemetry.span("train_step.lower") as span:
            jax.eval_shape(HybridLM(cfg).init, jax.random.PRNGKey(0), tokens)
        assert span.attrs["hc_fused_sublayers"] == fused
        assert span.attrs["hc_streams"] == 4
        assert telemetry.value("hvd_hybrid_hc_fused_sublayers") == fused
    finally:
        if not was_on:
            telemetry.disable()


def test_the_swiglu_router_runs_in_float32_at_the_highest_precision():
    cfg = tiny(pattern="E", dtype=jnp.bfloat16)
    u = jnp.ones((1, 8, cfg.d_model), jnp.bfloat16)
    module = ExpertMixer(cfg)
    variables = module.init(jax.random.PRNGKey(0), u)
    jaxpr = jax.make_jaxpr(lambda v, u: module.apply(v, u))(variables, u)

    def dots(j):
        for e in j.eqns:
            if e.primitive.name == "dot_general":
                yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from dots(sub)

    router = [e for e in dots(jaxpr.jaxpr)
              if e.outvars[0].aval.shape[-1] == cfg.num_experts]
    assert len(router) == 1
    assert router[0].invars[0].aval.dtype == jnp.float32
    assert router[0].params["precision"] is not None
    assert "HIGHEST" in str(router[0].params["precision"])
