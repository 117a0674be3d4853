"""What granite-4.0-h-micro asked of the program: the scan's kernels with
a group's heads in blocks, flash at a 64-wide head with an explicit
softmax scale, the muP scalars and the tied head of ``HybridLM`` — and
that at their defaults the new fields leave every other configuration's
program as it was.  (System against the plain reference:
tests/benchmark_harness/test_benchmark_granite.py.)"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import telemetry
from horovod_tpu.models import (
    HybridConfig,
    HybridLM,
    TransformerConfig,
    TransformerLM,
    hybrid_lm_loss,
)
from horovod_tpu.models.transformer import Attention
from horovod_tpu.ops import pallas_kernels
from horovod_tpu.ops.pallas_kernels import (
    flash_attention,
    ssd_chunked,
    ssd_head_block,
    ssd_scan,
)
from horovod_tpu.parallel.ring_attention import reference_attention
from test_hybrid import recurrence  # the recurrence one step at a time


def scan_operands(heads: int, groups: int, seq: int = 256, seed: int = 0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(keys[0], (1, seq, heads, 16)),
            0.1 * jax.nn.softplus(jax.random.normal(keys[1], (1, seq, heads))),
            -jnp.exp(jax.random.normal(keys[2], (heads,))),
            jax.random.normal(keys[3], (1, seq, groups, 128)),
            jax.random.normal(keys[4], (1, seq, groups, 128)))


def _scalar(f):
    return lambda *args: jnp.sum(jnp.sin(f(*args)))


@pytest.mark.parametrize("heads,groups,block", [
    (16, 1, 8), (8, 1, 4), (16, 2, 4), (16, 1, 16)])
def test_head_blocks_give_the_scan_of_the_whole_group(heads, groups, block):
    """One B/C group (and two) with more heads than a call takes at a
    grid step, kernels interpreted, two chunks of 128: values and every
    gradient — ``db`` / ``dc`` summed over the head blocks among them —
    against the einsum form and against the recurrence one step at a
    time."""
    args = scan_operands(heads, groups)
    blocked = functools.partial(ssd_scan, chunk=128, interpret=True,
                                head_block=block)
    einsum = functools.partial(ssd_chunked, chunk=128)
    assert "ssd_fwd" in str(jax.make_jaxpr(blocked)(*args))
    got = blocked(*args)
    np.testing.assert_allclose(got, einsum(*args), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got, recurrence(*args), rtol=2e-4, atol=2e-4)
    grads = jax.grad(_scalar(blocked), argnums=range(5))(*args)
    for want_of in (einsum, recurrence):
        want = jax.grad(_scalar(want_of), argnums=range(5))(*args)
        for u, v in zip(grads, want):
            np.testing.assert_allclose(
                u, v, rtol=2e-3, atol=2e-5 * float(jnp.max(jnp.abs(v))))


def _grids(fn, *args):
    """The grid of every ``pallas_call`` in ``fn``'s jaxpr, by name."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"] if "name" in eqn.params \
                    else eqn.params["name_and_src_info"].name
                found[name] = tuple(eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_eight_heads_a_group_keep_the_grid_they_had():
    """(batch, groups, chunks) where a group's heads fit one step — the
    calls ``nemotron3nano`` compiles — and one more axis, the chunk axis
    still last, where they are taken in blocks."""
    whole = scan_operands(16, 2)        # 8 heads a group
    both = jax.grad(_scalar(functools.partial(
        ssd_scan, chunk=128, interpret=True)), argnums=range(5))
    assert _grids(both, *whole) == {"ssd_fwd": (1, 2, 2),
                                    "ssd_bwd": (1, 2, 2)}
    blocked = jax.grad(_scalar(functools.partial(
        ssd_scan, chunk=128, interpret=True, head_block=8)),
        argnums=range(5))
    assert _grids(blocked, *scan_operands(16, 1)) == {
        "ssd_fwd": (1, 1, 2, 2), "ssd_bwd": (1, 1, 2, 2)}
    with pytest.raises(ValueError, match="does not divide"):
        ssd_scan(*scan_operands(8, 1), 128, interpret=True, head_block=3)


def test_the_head_block_is_a_function_of_the_shapes():
    """All of a group's heads where they fit what a call may hold (eight
    64-wide heads at any chunk: ``nemotron3nano``), else the largest
    divisor that is a multiple of 8 and fits; what a call reckons grows
    with the block, and past Mosaic's default scope it is asked for."""
    assert ssd_head_block(8, 64, 128, 128, 2) == 8
    assert ssd_head_block(8, 64, 128, 256, 2) == 8
    assert ssd_head_block(2, 64, 128, 128, 4) == 2
    assert ssd_head_block(64, 64, 128, 256, 2) == 16
    assert ssd_head_block(64, 64, 128, 128, 2) == 32
    picked = ssd_head_block(64, 64, 128, 256, 2)
    need = [pallas_kernels._ssd_vmem_bytes(rb, 64, 128, 256, 2)
            for rb in (8, 16, 32, 64)]
    assert need == sorted(need)
    assert need[-1] > pallas_kernels._MOSAIC_VMEM_SCOPE
    assert pallas_kernels._ssd_vmem_bytes(picked, 64, 128, 256, 2) \
        <= pallas_kernels._MOSAIC_VMEM_SCOPE
    # nothing fits: the fewest heads a step, not an error
    assert ssd_head_block(64, 1024, 128, 512, 4) == 8


@pytest.mark.parametrize("scale", [1 / 64, None])
def test_flash_at_a_64_wide_head_with_an_explicit_scale(scale):
    """[1, 256, 4, 64] through ``flash_fwd`` / ``flash_bwd`` interpreted,
    two blocks a row: values and gradients against the dense form at the
    same scale — 1/64, which is not the width's inverse root, and the
    default, which is."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(key, (1, 256, 4, 64)) for key in keys)
    flash = functools.partial(flash_attention, causal=True, scale=scale,
                              block_q=128, block_k=128, interpret=True)
    dense = functools.partial(reference_attention, causal=True, scale=scale)
    text = str(jax.make_jaxpr(jax.grad(_scalar(flash), argnums=(0, 1, 2)))(
        q, k, v))
    assert "flash_fwd" in text and "flash_bwd" in text
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(_scalar(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_scalar(dense), argnums=(0, 1, 2))(q, k, v)
    for u, w in zip(got, want):
        np.testing.assert_allclose(u, w, rtol=2e-4, atol=2e-5)
    if scale is not None:       # and the scale is read, not the default
        assert not np.allclose(flash(q, k, v),
                               reference_attention(q, k, v, causal=True))


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_attention_reads_its_scale_on_every_implementation(impl):
    cfg = dict(num_heads=4, num_kv_heads=2, head_width=16, d_model=32,
               dtype=jnp.float32, rotary=False, attention_impl=impl,
               flash_block=128, flash_interpret=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 32))
    pos = jnp.arange(128)
    plain = Attention(TransformerConfig(**cfg))
    variables = plain.init(jax.random.PRNGKey(1), x, pos)
    spelt = Attention(TransformerConfig(attention_scale=16 ** -0.5, **cfg))
    other = Attention(TransformerConfig(attention_scale=1 / 16, **cfg))
    np.testing.assert_allclose(spelt.apply(variables, x, pos),
                               plain.apply(variables, x, pos), rtol=1e-6)
    assert not np.allclose(other.apply(variables, x, pos),
                           plain.apply(variables, x, pos), atol=1e-4)
    with pytest.raises(ValueError, match="not by ulysses"):
        Attention(TransformerConfig(
            **{**cfg, "attention_impl": "ulysses"},
            attention_scale=0.1)).apply(variables, x, pos)


def tiny(**kw):
    base = dict(vocab_size=64, pattern="MD*D", d_model=32, dtype=jnp.float32,
                num_heads=4, num_kv_heads=2, head_dim=8, mamba_heads=8,
                mamba_head_dim=8, mamba_groups=1, ssm_state=16, chunk=8,
                mlp_width=48)
    base.update(kw)
    return HybridConfig(**base)


MUP = dict(embedding_scale=12.0, residual_scale=0.22, logits_divisor=8.0,
           attention_scale=1 / 8, tie_head=True)


def _lowered(model, tokens):
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    return jax.jit(model.apply).lower(variables, tokens).as_text()


def test_at_their_defaults_the_new_fields_leave_the_programs_as_they_were():
    """The new fields at their defaults, spelt out, and the softmax scale
    given as the width's own inverse root: the same lowered text — no
    multiplication by 1, no division by 1, no second head — for a hybrid
    with a plain residual, one with hyper-connections and latent
    attention, and the decoder (whose compile-cache key must not move).
    Any of the scalars off its default is another program."""
    tokens = jnp.zeros((2, 16), jnp.int32)
    spelt = dict(embedding_scale=1.0, residual_scale=1.0, logits_divisor=1.0,
                 attention_scale=None, tie_head=False)
    for base in (dict(pattern="EM*D"),
                 dict(pattern="*D*E", residual="hc", attention_kind="latent",
                      q_rank=24, kv_rank=16, nope_dim=16, rope_dim=8,
                      v_dim=16, expert_act="swiglu")):
        base = dict(base, num_experts=16, experts_held=(4, 8), top_k=3,
                    expert_width=24, shared_width=48)
        plain = _lowered(HybridLM(tiny(**base)), tokens)
        assert _lowered(HybridLM(tiny(**base, **spelt)), tokens) == plain
        if base.get("residual") != "hc":
            assert _lowered(HybridLM(tiny(
                **base, attention_scale=8 ** -0.5)), tokens) == plain
            for name, value in MUP.items():
                assert _lowered(HybridLM(tiny(**base, **{name: value})),
                                tokens) != plain, name
    decoder = dict(vocab_size=64, num_layers=1, num_heads=4, d_model=32,
                   d_ff=64, dtype=jnp.float32)
    plain = _lowered(TransformerLM(TransformerConfig(**decoder)), tokens)
    assert _lowered(TransformerLM(TransformerConfig(
        **decoder, attention_scale=None)), tokens) == plain
    assert _lowered(TransformerLM(TransformerConfig(
        **decoder, attention_scale=8 ** -0.5)), tokens) == plain


def test_config_refuses_scalars_it_would_not_read():
    with pytest.raises(ValueError, match="residual_scale"):
        tiny(residual="hc", residual_scale=0.22)
    tiny(residual="hc", residual_scale=1.0)
    with pytest.raises(ValueError, match="attention_scale"):
        tiny(attention_kind="latent", attention_scale=0.1)


def test_the_scalars_and_the_tied_head_are_the_equations():
    """``h0 = 12 E[t]``; ``h <- h + 0.22 mixer(N(h))``; ``logits = N(h)
    E^T / 8`` with no head leaf: checked against the model run with the
    scalars at 1 and an untied head that is the embedding transposed, on
    inputs rescaled by hand where that is possible (the embedding and
    the logits), and by the branch scale's linearity at one sublayer."""
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64)
    tied = HybridLM(tiny(**MUP))
    variables = tied.init(jax.random.PRNGKey(1), tokens)
    params = variables["params"]
    assert "head" not in params and set(params) == {
        "embed", "ln_f", "layer_0", "layer_1", "layer_2", "layer_3"}
    # the embedding scale is a scaled embedding under an untied head
    untied = HybridLM(tiny(**{**MUP, "embedding_scale": 1.0,
                              "logits_divisor": 1.0, "tie_head": False}))
    by_hand = {"params": {
        **params, "embed": {"embedding": 12.0 * params["embed"]["embedding"]},
        "head": params["embed"]["embedding"].T}}
    np.testing.assert_allclose(
        tied.apply(variables, tokens),
        untied.apply(by_hand, tokens) / 8.0, rtol=2e-5, atol=2e-6)
    # one MLP sublayer: x + 0.22 f(N(x)) against x + f(N(x))
    one = dict(pattern="D", embedding_scale=1.0, logits_divisor=1.0)
    scaled = HybridLM(tiny(**{**MUP, **one}))
    plain = HybridLM(tiny(**{**MUP, **one, "residual_scale": 1.0}))
    v1 = scaled.init(jax.random.PRNGKey(2), tokens)
    x0 = v1["params"]["embed"]["embedding"][tokens]

    def stream(model):      # the residual stream before the final norm
        _, state = model.apply(
            v1, tokens, capture_intermediates=lambda m, _: m.name == "layer_0",
            mutable=["intermediates"])
        return state["intermediates"]["layer_0"]["__call__"][0]
    np.testing.assert_allclose(
        stream(scaled) - x0, 0.22 * (stream(plain) - x0),
        rtol=1e-5, atol=1e-6)


def test_a_traced_step_names_the_new_shape_facts(hvd_runtime, monkeypatch):
    """``mamba_groups``, heads a group, the head block and the chunk of
    the scan, the attention head's width and lanes, the softmax and
    branch scales and whether the head is tied: on ``train_step.compile``
    and in ``hvd_hybrid_*`` gauges."""
    import optax

    hvd = hvd_runtime
    was_on = telemetry.enabled()
    telemetry.enable()
    monkeypatch.setattr(pallas_kernels, "ssd_head_block",
                        lambda r, *shape: 4)
    model = HybridLM(tiny(
        **MUP, head_dim=64, mamba_head_dim=16, ssm_state=128, chunk=128,
        attention_impl="flash", flash_block=128, flash_interpret=True))
    tokens = np.zeros((8, 129), np.int32)
    step = hvd.DistributedTrainStep(
        functools.partial(hybrid_lm_loss, model), optax.sgd(0.1))
    params, opt_state = step.init(
        model.init(jax.random.PRNGKey(0), tokens[:1, :-1]))
    since = telemetry.spans._now()
    batch = step.shard_batch({"inputs": tokens[:, :-1],
                              "labels": tokens[:, 1:]})
    jax.block_until_ready(step(params, opt_state, batch))
    attrs = [s for s in telemetry.spans.snapshot(since=since)
             if s.name == "train_step.compile"][-1].attrs
    want = {"mamba_groups": 1, "ssd_heads_per_group": 8, "ssd_head_block": 4,
            "ssd_chunk": 128, "ssd_impl": "mosaic", "attn_head_width": 64,
            "flash_qk_lanes": 128, "attn_scale": 1 / 8,
            "residual_scale": 0.22, "tied_head": 1}
    assert {k: attrs[k] for k in want} == want
    for name in ("mamba_groups", "ssd_heads_per_group", "ssd_head_block",
                 "ssd_chunk", "attn_head_width", "flash_qk_lanes",
                 "attn_scale", "residual_scale", "tied_head"):
        assert telemetry.value(f"hvd_hybrid_{name}") == want[name], name
    if not was_on:
        telemetry.disable()


def test_the_mlp_blocks_take_a_remat_policy_of_their_own():
    """``mlp_remat_policy`` rematerialises the ``D`` blocks by another
    policy than the mixers' (``None``: the same program as before the
    field): the same loss and gradients, one ``checkpoint`` a mixer block
    and none for the MLPs."""
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 17), 0, 64)
    batch = {"inputs": tokens[:, :-1], "labels": tokens[:, 1:]}
    whole = HybridLM(tiny(remat_policy="full"))
    mixed = HybridLM(tiny(remat_policy="full", mlp_remat_policy="none"))
    variables = whole.init(jax.random.PRNGKey(1), batch["inputs"])

    def step(model):
        return jax.value_and_grad(functools.partial(hybrid_lm_loss, model))
    count = {name: str(jax.make_jaxpr(step(model))(variables, batch))
             .count("remat2[") for name, model in
             (("whole", whole), ("mixed", mixed))}
    assert count == {"whole": 4, "mixed": 2}
    want_loss, want = step(whole)(variables, batch)
    got_loss, got = step(mixed)(variables, batch)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    for u, v in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(u, v, rtol=1e-4, atol=1e-6)
    tokens16 = jnp.zeros((2, 16), jnp.int32)
    assert _lowered(HybridLM(tiny(remat_policy="full",
                                  mlp_remat_policy=None)), tokens16) \
        == _lowered(whole, tokens16)
    with pytest.raises(ValueError):
        HybridLM(tiny(mlp_remat_policy="some")).init(
            jax.random.PRNGKey(0), tokens16)
