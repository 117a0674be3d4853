"""The Mamba-2 mixer's two elementwise kernels against the ``jax.numpy``
lines they stand in for: ``mamba_conv`` (causal depthwise convolution +
SiLU over ``xBC``) and ``mamba_gated_norm`` (``D``-skip, gate, RMSNorm in
groups), interpreted on the CPU, values and every gradient in fp32, at
both benchmark configurations' channel counts and groups with the
sequence cut; the rule that selects them; the fall-back as the program
it was; and a cut ``HybridLM`` of each configuration with the kernels
against without."""

import functools
import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.models import HybridConfig, HybridLM, hybrid_lm_loss
from horovod_tpu.models import hybrid
from horovod_tpu.ops import pallas_kernels as pk

f32 = jnp.float32

# (heads, head width, groups, state): C = 6,144 in 8 groups
# (nemotron3nano), C = 4,352 in one (granite4hmicro)
SHAPES = {"nemotron3nano": (64, 64, 8, 128),
          "granite4hmicro": (64, 64, 1, 128)}


# -- today's jax.numpy lines (models/hybrid.Mamba2Mixer's fall-back) ----------

def conv_lines(xbc, conv_w, conv_b, dtype):
    k, t = conv_w.shape[0], xbc.shape[1]
    padded = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    xbc = sum(padded[:, i:i + t] * conv_w[i].astype(dtype)
              for i in range(k)) + conv_b.astype(dtype)
    return nn.silu(xbc)


def gated_norm_lines(y, x, z, d_skip, norm_scale, g, eps, dtype):
    bsz, t, inner = z.shape
    h = d_skip.shape[0]
    y = y.reshape(bsz, t, h, -1) \
        + d_skip[:, None] * x.reshape(bsz, t, h, -1).astype(f32)
    y = y.reshape(bsz, t, inner) * nn.silu(z.astype(f32))
    y = y.reshape(bsz, t, g, inner // g)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return (y.reshape(bsz, t, inner) * norm_scale).astype(dtype)


def operands(name, bsz, t, dtype=f32, seed=0):
    h, p, g, n = SHAPES[name]
    inner, c = h * p, h * p + 2 * g * n
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    return dict(
        zx=jax.random.normal(ks[0], (bsz, t, 2 * inner + 2 * g * n + h),
                             f32).astype(dtype),
        w=0.5 * jax.random.normal(ks[1], (4, c)),
        b=0.1 * jax.random.normal(ks[2], (c,)),
        d=1.0 + jax.random.normal(ks[3], (h,)),
        scale=1.0 + 0.1 * jax.random.normal(ks[4], (inner,)),
        y=jax.random.normal(ks[5], (bsz, t, inner), f32),
        x=jax.random.normal(ks[6], (bsz, t, inner), f32).astype(dtype),
        ct_conv=jax.random.normal(ks[7], (bsz, t, c), f32).astype(dtype),
        ct_norm=jax.random.normal(ks[8], (bsz, t, inner), f32).astype(dtype))


def time_last(v):
    return v.transpose(0, 2, 1)


def close(got, want, names, rtol=2e-5):
    for name, u, v in zip(names, got, want):
        scale = float(jnp.max(jnp.abs(v)))
        np.testing.assert_allclose(u, v, rtol=rtol, atol=rtol * scale,
                                   err_msg=name)


# t = 384: three tiles of 128 lanes (two edges for the halo to cross);
# 256: one tile, the whole sequence in a block
@pytest.mark.parametrize("name,bsz,t", [
    ("nemotron3nano", 1, 384), ("granite4hmicro", 2, 384),
    ("granite4hmicro", 1, 256)])
def test_the_convolution_and_its_gradients(name, bsz, t):
    h, p, g, n = SHAPES[name]
    inner, gn = h * p, g * n
    c = inner + 2 * gn
    o = operands(name, bsz, t)
    assert pk.mamba_runs_kernels(t, h, p, g, n, 4, f32, interpret=True)
    assert pk.mamba_conv_tile(t, inner, gn, 4)[1] == (128 if t == 384 else t)

    def kernels(zx, w, b):
        return time_last(jnp.concatenate(pk.mamba_conv(
            time_last(zx), w, b, inner=inner, state_cols=gn,
            interpret=True), axis=1))

    def lines(zx, w, b):
        return conv_lines(zx[..., inner:inner + c], w, b, f32)

    got, back = jax.vjp(kernels, o["zx"], o["w"], o["b"])
    want, back_lines = jax.vjp(lines, o["zx"], o["w"], o["b"])
    close([got], [want], ["xBC"])
    # the first three tokens see the zeros before the sequence
    close([got[:, :3]], [want[:, :3]], ["xBC[:3]"])
    close(back(o["ct_conv"]), back_lines(o["ct_conv"]),
          ["dxBC (in in_proj's result)", "dw", "db"])
    # nothing of the cotangent lands on z's or dt's columns
    dzx = back(o["ct_conv"])[0]
    assert not np.any(dzx[..., :inner]) and not np.any(dzx[..., inner + c:])


@pytest.mark.parametrize("name,bsz,t", [
    ("nemotron3nano", 2, 384), ("granite4hmicro", 1, 384)])
def test_the_gated_norm_and_its_gradients(name, bsz, t):
    h, p, g, n = SHAPES[name]
    inner = h * p
    o = operands(name, bsz, t, seed=1)
    assert pk.mamba_norm_tile(t, inner // g) == 128

    def kernels(y, x, zx, d, scale):
        return time_last(pk.mamba_gated_norm(
            time_last(y), time_last(x), time_last(zx), d, scale, groups=g,
            eps=1e-5, interpret=True))

    def lines(y, x, zx, d, scale):
        return gated_norm_lines(y, x, zx[..., :inner], d, scale, g, 1e-5,
                                f32)

    args = (o["y"], o["x"], o["zx"], o["d"], o["scale"])
    got, back = jax.vjp(kernels, *args)
    want, back_lines = jax.vjp(lines, *args)
    close([got], [want], ["out"])
    close(back(o["ct_norm"]), back_lines(o["ct_norm"]),
          ["dy", "dx", "dz (in in_proj's result)", "dD", "dnorm_scale"],
          rtol=5e-5)
    assert not np.any(back(o["ct_norm"])[2][..., inner:])


def test_bf16_operands_are_rounded_once():
    """In the compute type of the cells the kernels' arithmetic is fp32
    and the result is rounded once: no farther from the fp32 lines than
    the bf16 lines are (which round every product and sum)."""
    name, t = "granite4hmicro", 256
    h, p, g, n = SHAPES[name]
    inner, gn = h * p, g * n
    c = inner + 2 * gn
    o = operands(name, 1, t, jnp.bfloat16, seed=2)
    exact = conv_lines(o["zx"][..., inner:inner + c].astype(f32), o["w"],
                       o["b"], f32)
    rounded = conv_lines(o["zx"][..., inner:inner + c], o["w"], o["b"],
                         jnp.bfloat16)
    got = time_last(jnp.concatenate(pk.mamba_conv(
        time_last(o["zx"]), o["w"], o["b"], inner=inner, state_cols=gn,
        interpret=True), axis=1))
    assert got.dtype == jnp.bfloat16

    def off(v):
        return float(jnp.max(jnp.abs(v.astype(f32) - exact)))
    assert off(got) <= off(rounded)
    assert off(got) <= 2 ** -8 * float(jnp.max(jnp.abs(exact)))


@pytest.mark.parametrize("t,heads,p,groups,n,taps,dtype,want", [
    (8192, 64, 64, 8, 128, 4, jnp.bfloat16, True),      # nemotron3nano
    (8192, 64, 64, 1, 128, 4, jnp.bfloat16, True),      # granite4hmicro
    (384, 64, 64, 8, 128, 4, f32, True),
    (8192, 64, 64, 8, 128, 3, jnp.bfloat16, False),     # another width
    (8200, 64, 64, 8, 128, 4, jnp.bfloat16, False),     # no tile of time
    (8192, 64, 64, 1, 16, 4, jnp.bfloat16, False),      # B under a tile
    (8192, 8, 8, 1, 128, 4, jnp.bfloat16, False),       # so is a head
])
def test_the_rule_that_selects_the_kernels(t, heads, p, groups, n, taps,
                                           dtype, want):
    assert pk.mamba_runs_kernels(t, heads, p, groups, n, taps, dtype,
                                 interpret=True) is want
    # off a TPU and not interpreted: the jax.numpy lines, whatever tiles
    assert not pk.mamba_runs_kernels(t, heads, p, groups, n, taps, dtype)


def test_the_tiles_follow_the_shapes():
    # both cells: whole rows of 8,192 lanes, 128 channels a block (B and
    # C are 128 wide on granite4hmicro); the norm's block is a group
    assert pk.mamba_conv_tile(8192, 4096, 1024, 2) == (128, 8192)
    assert pk.mamba_conv_tile(8192, 4096, 128, 2) == (128, 8192)
    assert pk.mamba_conv_tile(16384, 4096, 1024, 2) == (128, 8192)
    assert pk.mamba_conv_tile(1024, 4096, 1024, 2) == (512, 1024)
    assert pk.mamba_norm_tile(8192, 512) == 2048
    assert pk.mamba_norm_tile(8192, 4096) == 256
    assert pk.mamba_conv_tile(100, 4096, 1024, 2) is None
    assert pk.mamba_norm_tile(100, 512) is None


def small(**kw):
    base = dict(vocab_size=64, pattern="M", d_model=32, dtype=f32,
                num_heads=4, num_kv_heads=2, head_dim=8, mamba_heads=4,
                mamba_head_dim=16, mamba_groups=2, ssm_state=16, chunk=8)
    base.update(kw)
    return HybridConfig(**base)


# sha256 of the lowered (StableHLO) forward of ``small()`` on the CPU as
# the commit before the kernels lowered it: where the rule says no, the
# mixer is the program it was, line for line
FALL_BACK = \
    "722d7e2732dc83a29e2ecce49fb54570aa0f84aeb6598ef09579a7adc51a3829"


def test_where_the_rule_says_no_the_mixer_is_the_program_it_was():
    model = HybridLM(small())
    tokens = jnp.zeros((2, 32), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    text = jax.jit(model.apply).lower(variables, tokens).as_text()
    assert "pallas_call" not in text and "mamba_conv" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == FALL_BACK
    # and its values are the lines' (one Mamba layer, by hand)
    cfg = small()
    mixer = hybrid.Mamba2Mixer(cfg)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    params = mixer.init(jax.random.PRNGKey(2), u)
    q = params["params"]
    h, p, g, n = 4, 16, 2, 16
    inner = h * p
    zx = u @ q["in_proj"]["kernel"]
    z, xbc, dt = jnp.split(zx, [inner, 2 * inner + 2 * g * n], axis=-1)
    xbc = conv_lines(xbc, q["conv_kernel"], q["conv_bias"], f32)
    x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    y = pk.ssd_chunked(x.reshape(2, 32, h, p),
                       jax.nn.softplus(dt + q["dt_bias"]),
                       -jnp.exp(q["A_log"]), b.reshape(2, 32, g, n),
                       c.reshape(2, 32, g, n), cfg.chunk)
    y = gated_norm_lines(y.reshape(2, 32, inner), x, z, q["D"],
                         q["norm_scale"], g, cfg.norm_eps, f32)
    np.testing.assert_array_equal(mixer.apply(params, u),
                                  y @ q["out_proj"]["kernel"])


@pytest.mark.parametrize("name,chunk", [("nemotron3nano", 128),
                                        ("granite4hmicro", 256)])
def test_a_cut_model_with_the_kernels_against_without(name, chunk,
                                                      monkeypatch):
    """One Mamba layer at the configuration's Mamba widths (64 heads of
    64, its groups, state 128, its chunk) in a narrow model, inside a
    rematerialised block as the cells run it: logits and every
    parameter's gradient with the two kernels interpreted against the
    ``jax.numpy`` lines, the scan's kernels interpreted on both sides."""
    h, p, g, n = SHAPES[name]
    cfg = small(d_model=64, mamba_heads=h, mamba_head_dim=p, mamba_groups=g,
                ssm_state=n, chunk=chunk, remat_policy="full",
                flash_interpret=True)
    model = HybridLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 257), 0, 64)
    batch = {"inputs": tokens[:, :-1], "labels": tokens[:, 1:]}
    variables = model.init(jax.random.PRNGKey(1), batch["inputs"])
    # off their initial values: D, the norm's scale and the bias count
    variables = jax.tree_util.tree_map_with_path(
        lambda path, v: v + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(jax.tree_util.keystr(path))), v.shape)
        if path[-1].key in ("D", "norm_scale", "conv_bias") else v,
        variables)

    def both():
        step = jax.jit(jax.value_and_grad(
            functools.partial(hybrid_lm_loss, model)))
        return (str(jax.make_jaxpr(step)(variables, batch)),
                jax.jit(model.apply)(variables, batch["inputs"]),
                step(variables, batch))

    jaxpr, logits, (loss, grads) = both()
    for call in ("mamba_conv_fwd", "mamba_conv_bwd", "mamba_gated_norm_fwd",
                 "mamba_gated_norm_bwd", "ssd_fwd", "ssd_bwd"):
        assert call in jaxpr, call
    monkeypatch.setattr(hybrid, "mamba_runs_kernels", lambda *a, **k: False)
    jaxpr, want_logits, (want_loss, want) = both()
    assert "mamba_conv" not in jaxpr and "ssd_fwd" in jaxpr
    np.testing.assert_allclose(logits, want_logits, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for (path, u), v in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            u, v, rtol=1e-3, atol=1e-5 * float(jnp.max(jnp.abs(v))) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("seq,interpret,want", [
    (256, True, 2),     # the shapes tile and the kernels are interpreted
    (200, True, 0),     # no tile of time divides the sequence
    (256, False, 0),    # off a TPU, not interpreted: the jax.numpy lines
])
def test_a_traced_model_says_how_many_layers_run_the_kernels(
        seq, interpret, want):
    """``mamba_fused_layers`` (every Mamba layer or none) and
    ``mamba_conv_channels`` as ``hvd_hybrid_*`` gauges, set when the
    model is traced (the spans' attributes come from the same facts:
    tests/test_mamba_offchip_compile.py reads them off a lowering)."""
    from horovod_tpu import telemetry

    was_on = telemetry.enabled()
    telemetry.enable()
    try:
        cfg = small(pattern="M*M", mamba_heads=2, mamba_head_dim=64,
                    mamba_groups=1, ssm_state=128, chunk=128,
                    flash_interpret=interpret)
        jax.eval_shape(HybridLM(cfg).init, jax.random.PRNGKey(0),
                       jnp.zeros((1, seq), jnp.int32))
        assert telemetry.value("hvd_hybrid_mamba_fused_layers") == want
        assert telemetry.value("hvd_hybrid_mamba_conv_channels") \
            == 2 * 64 + 2 * 128
    finally:
        if not was_on:
            telemetry.disable()
