"""What a hybrid's rematerialised blocks keep for the backward pass, on
the CPU at a small size (flash interpreted): an attention block keeps
flash's output and row statistics, so its backward runs no second
``flash_fwd`` and trains as the block that keeps nothing; the expert
layer's three buffers keep their grouped matmuls' results in pieces of
one size, so that they share one set of residual slots; and what a
traced model says of both."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import telemetry
from horovod_tpu.memory import remat
from horovod_tpu.models import HybridConfig, HybridLM, hybrid_lm_loss
from horovod_tpu.models.hybrid import _held_experts, _relu2, _swiglu
from horovod_tpu.parallel.expert import held_expert_ffn, topk_routing

f32 = jnp.float32


def small(**kw):
    base = dict(vocab_size=64, pattern="*E", d_model=32, dtype=f32,
                num_heads=4, num_kv_heads=2, head_dim=16, num_experts=16,
                experts_held=(4, 12), top_k=4, expert_width=24,
                shared_width=48, attention_impl="flash", flash_block=128,
                flash_interpret=True)
    base.update(kw)
    return HybridConfig(**base)


def latent(**kw):
    """Latent attention inside hyper-connections, SwiGLU experts."""
    return small(pattern="*D*E", residual="hc", attention_kind="latent",
                 num_heads=2, q_rank=12, kv_rank=8, nope_dim=16, rope_dim=8,
                 v_dim=16, mlp_width=48, expert_act="swiglu", **kw)


def with_mtp(**kw):
    """A multi-token-prediction module of one attention and one expert
    sublayer beside the main layers."""
    return small(mtp_layers=1, mtp_pattern="*E", **kw)


def _step(model, variables, batch):
    """The loss and its gradients, and the flash calls the step's jaxpr
    holds: ``(flash_fwd, flash_bwd)``."""
    traced = jax.jit(jax.value_and_grad(functools.partial(
        hybrid_lm_loss, model))).trace(variables, batch)
    text = str(traced.jaxpr)
    return (traced.lower().compile()(variables, batch),
            (text.count("name=flash_fwd"), text.count("name=flash_bwd")))


@pytest.mark.parametrize("make,policy", [
    (small, "full"), (small, "dots"), (latent, "full"), (with_mtp, "full")])
def test_kept_flash_residuals_train_as_the_recomputed_ones(
        monkeypatch, make, policy):
    """The same parameters through the step as it is (an attention block
    keeps flash's output and logsumexp) and through the step whose
    attention blocks keep only what their tier keeps (the forward kernel
    again inside the backward): one ``flash_fwd`` an attention sublayer
    against two, and the same loss and gradients to fp32 round-off."""
    cfg = make(remat_policy=policy)
    attention = (cfg.pattern + cfg.mtp_pattern).count("*")
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 129), 0, 64)
    batch = {"inputs": tokens[:, :-1], "labels": tokens[:, 1:]}
    model = HybridLM(cfg)
    variables = model.init(jax.random.PRNGKey(1), batch["inputs"])
    (got_loss, got), calls = _step(model, variables, batch)
    assert calls == (attention, attention)

    block = remat.remat_block
    monkeypatch.setattr(remat, "remat_block",
                        lambda cls, policy, also_save=None: block(cls, policy))
    (want_loss, want), calls = _step(HybridLM(make(remat_policy=policy)),
                                     variables, batch)
    assert calls == (2 * attention, attention)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    # round-off at the gradient's own scale: a leaf that is a sum of
    # terms which cancel (a bias's) rounds at its terms' scale, not its own
    scale = max(float(jnp.max(jnp.abs(v)))
                for v in jax.tree_util.tree_leaves(want))
    for (path, u), v in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(u, v, rtol=1e-5, atol=1e-6 * scale,
                                   err_msg=jax.tree_util.keystr(path))


TOKENS, D, WIDTH, TOP_K, HELD = 64, 32, 24, 4, (4, 12)


def _routed(tier: int):
    """Routing whose count of held assignments takes buffer ``tier``: a
    token's choices are the held experts boosted (1, 2 or all of them)
    and otherwise experts held elsewhere."""
    scores = jax.random.normal(jax.random.PRNGKey(tier), (TOKENS, 16))
    boosted = (1, 2, TOP_K)[tier]
    scores = scores.at[:, HELD[0]:HELD[1]].add(-50.0)
    scores = scores.at[:, HELD[0]:HELD[0] + boosted].add(100.0)
    idx, w = topk_routing(scores, jnp.zeros(16), TOP_K, 2.0)
    landed = int(jnp.sum((idx >= HELD[0]) & (idx < HELD[1])))
    assert landed == boosted * TOKENS
    return idx, w


def _experts(piece, gated=False):
    """The model's grouped function with its results kept in pieces of
    ``piece`` rows; ``None``, whole (the form before the pieces)."""
    def grouped(params, rows, group_sizes):
        return _held_experts(params, rows, group_sizes,
                             piece=piece or rows.shape[0],
                             act=_swiglu if gated else _relu2)
    return grouped


def _operands(gated=False):
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    groups = HELD[1] - HELD[0]
    x = jax.random.normal(keys[0], (TOKENS, D))
    params = (jax.random.normal(keys[1], (groups, D, WIDTH * (1 + gated)))
              * D ** -0.5,
              jax.random.normal(keys[2], (groups, WIDTH, D)) * WIDTH ** -0.5)
    return x, params


def _kept_rows(fn, *args):
    """``{width: rows}`` of the residuals ``jax.linearize`` keeps that
    are two-dimensional arrays."""
    _, linear = jax.linearize(fn, *args)
    rows = {}
    for leaf in jax.tree_util.tree_leaves(linear):
        if leaf.ndim == 2:
            rows[leaf.shape[1]] = rows.get(leaf.shape[1], 0) + leaf.shape[0]
    return rows


@pytest.mark.parametrize("gated", [False, True])
def test_the_three_buffers_keep_one_set_of_slots(gated):
    """Kept whole, the buffers of one, two and ``top_k`` rows a token
    hold a residual slot each, the sum of their rows; kept in pieces of
    ``tokens`` rows, one set: the largest buffer's rows."""
    x, params = _operands(gated)
    idx, w = _routed(0)
    caps = [TOKENS * m for m in (1, 2, TOP_K)]
    rows = {form: _kept_rows(
        lambda x, w, p: held_expert_ffn(x, idx, w, HELD,
                                        _experts(piece, gated), p),
        x, w, params)
        for form, piece in (("pieces", TOKENS), ("whole", None))}
    hidden = WIDTH * (1 + gated)
    assert rows["pieces"][hidden] == max(caps)
    assert rows["whole"][hidden] == sum(caps)
    # the second matmul's results beside the step's own (tokens, d) input
    assert rows["whole"][D] - rows["pieces"][D] == sum(caps) - max(caps)


@pytest.mark.parametrize("tier", [0, 1, 2])
@pytest.mark.parametrize("gated", [False, True])
def test_pieces_give_the_gradients_of_the_whole_results(tier, gated):
    """At each buffer the routing can take: the layer's value and its
    gradients with respect to the tokens, the weights and the experts
    are those of the form that keeps each result whole."""
    x, params = _operands(gated)
    idx, w = _routed(tier)
    cot = jax.random.normal(jax.random.PRNGKey(9), (TOKENS, D))

    def scalar(piece, x, w, params):
        return jnp.sum(held_expert_ffn(x, idx, w, HELD,
                                       _experts(piece, gated), params) * cot)

    got, want = (jax.jit(jax.value_and_grad(functools.partial(scalar, piece),
                                            argnums=(0, 1, 2)))(x, w, params)
                 for piece in (TOKENS, None))
    for u, v in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(u, v, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cfg,flash,rows", [
    (small(remat_policy="full"), 1, 128 * 4),
    (small(remat_policy="dots", pattern="*E*"), 2, 128 * 4),
    (latent(remat_policy="full"), 2, 128 * 4),
    (with_mtp(remat_policy="full"), 2, 128 * 4),
    # nothing rematerialised: nothing kept; no expert layer: no rows
    (small(pattern="*E*"), 0, 128 * 4),
    (small(remat_policy="full", pattern="*D"), 1, 0),
    # no flash kernel on this backend: the dense form keeps nothing named
    (small(remat_policy="full", flash_interpret=False), 0, 128 * 4),
])
def test_a_traced_model_says_what_its_blocks_keep(cfg, flash, rows):
    model = HybridLM(cfg)
    tokens = jnp.zeros((1, 128), jnp.int32)
    was_on = telemetry.enabled()
    telemetry.enable()
    try:
        since = telemetry.spans._now()
        with telemetry.span("probe"):
            jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
        said = [s for s in telemetry.spans.snapshot(since=since)
                if s.name == "probe"][-1].attrs
        assert (said["flash_kept_layers"], said["moe_kept_rows"]) \
            == (flash, rows)
        assert telemetry.value("hvd_hybrid_flash_kept_layers") == flash
        assert telemetry.value("hvd_hybrid_moe_kept_rows") == rows
    finally:
        if not was_on:
            telemetry.disable()
