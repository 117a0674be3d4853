"""``models/hybrid.gated_mlp``: the dense gated MLP as one function with
its backward written out — against autodiff of the plain ``jax.numpy``
form (value and all four cotangents, float32 and bfloat16), under
``jax.checkpoint`` (which matmuls a policy keeps and which it makes
again), what the backward materialises, ``HybridLM`` with ``D`` sublayers
against the benchmark's accepted plain references, and the parameter
tree, leaf for leaf what ``nn.Dense`` made of it."""

import dataclasses
import functools
import sys
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import cells  # noqa: E402

from horovod_tpu.models import HybridConfig, HybridLM  # noqa: E402
from horovod_tpu.models.hybrid import GatedMlp, gated_mlp  # noqa: E402

f32, bf16 = jnp.float32, jnp.bfloat16


def plain(u, w_gate, w_up, w_down):
    """What ``GatedMlp`` was: three ``nn.Dense`` and ``nn.silu``."""
    return (nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def operands(dtype, seed=0, rows=(2, 24), d=32, width=64):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    u = jax.random.normal(keys[0], rows + (d,))
    w_gate = jax.random.normal(keys[1], (d, width)) * d ** -0.5
    w_up = jax.random.normal(keys[2], (d, width)) * d ** -0.5
    w_down = jax.random.normal(keys[3], (width, d)) * width ** -0.5
    dy = jax.random.normal(keys[4], rows + (d,))
    return tuple(x.astype(dtype) for x in (u, w_gate, w_up, w_down)), \
        dy.astype(dtype)


def value_and_cotangents(fn, args, dy):
    out, vjp = jax.vjp(fn, *args)
    return (out,) + vjp(dy)


def rel_l2(got, want):
    got, want = got.astype(f32), want.astype(f32)
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


NAMES = ("y", "du", "dw_gate", "dw_up", "dw_down")


def test_float32_value_and_cotangents_are_autodiffs():
    args, dy = operands(f32)
    got = value_and_cotangents(gated_mlp, args, dy)
    want = value_and_cotangents(plain, args, dy)
    for name, u, v in zip(NAMES, got, want):
        assert u.shape == v.shape and u.dtype == v.dtype, name
        assert rel_l2(u, v) < 2e-6, name


def test_bfloat16_rounds_where_autodiff_rounds():
    """bf16 operands: ``h``, ``d_g``, ``d_p`` are rounded once each from
    fp32 arithmetic, the matmuls add up in fp32 — so against the float32
    form on the same (bf16) operands every result is as near as
    autodiff's bf16 program is, and within bf16's few parts in a
    thousand."""
    args, dy = operands(bf16, seed=3, rows=(2, 64), d=64, width=128)
    got = value_and_cotangents(gated_mlp, args, dy)
    auto = value_and_cotangents(plain, args, dy)
    truth = value_and_cotangents(
        plain, tuple(a.astype(f32) for a in args), dy.astype(f32))
    for name, u, a, t in zip(NAMES, got, auto, truth):
        assert u.dtype == bf16 and u.shape == t.shape, name
        assert rel_l2(u, t) < 8e-3, (name, rel_l2(u, t))
        assert rel_l2(u, t) < 1.5 * rel_l2(a, t) + 1e-3, name


def dot_generals(jaxpr) -> int:
    """Every ``dot_general`` of a jaxpr, those of its sub-jaxprs (a
    ``checkpoint``'s recomputation, a ``custom_vjp``'s rules) too."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "dot_general"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += dot_generals(sub)
    return n


@pytest.mark.parametrize("policy,forward,backward", [
    ("none", 3, 6), ("dots", 3, 6), ("full", 3, 8)])
def test_under_checkpoint_the_policy_sees_the_forward_rules_matmuls(
        policy, forward, backward):
    """Forward three matmuls, backward six.  ``dots_saveable`` keeps
    ``g`` and ``p`` (the forward rule's matmuls are the ones the policy
    sees) and nothing is made again; with no policy the block's forward
    is made again once — ``gate`` and ``up``, ``down``'s result being
    nobody's residual — exactly as for the plain form."""
    from horovod_tpu.memory.remat import remat_fn

    args, dy = operands(f32)

    def loss(fn):
        return lambda *a: jnp.sum(remat_fn(fn, policy)(*a) * dy)

    assert dot_generals(jax.make_jaxpr(loss(gated_mlp))(*args).jaxpr) \
        == forward
    grad = jax.grad(loss(gated_mlp), argnums=range(4))
    assert dot_generals(jax.make_jaxpr(grad)(*args).jaxpr) \
        == forward + backward
    assert dot_generals(jax.make_jaxpr(jax.grad(
        loss(plain), argnums=range(4)))(*args).jaxpr) == forward + backward
    for u, v in zip(grad(*args),
                    jax.grad(loss(plain), argnums=range(4))(*args)):
        assert rel_l2(u, v) < 2e-6


def test_the_backward_makes_its_elementwise_tensors_once():
    """One logistic in the backward, shared by ``h``, ``d_g`` and
    ``d_p``, whose three results — in the operands' type — stand behind
    one ``optimization_barrier`` between ``dh``'s matmul and the five
    that read them; the residuals are ``u``, ``g``, ``p`` and the
    kernels."""
    args, dy = operands(bf16)
    _, vjp = jax.vjp(gated_mlp, *args)
    backward = jax.make_jaxpr(vjp)(dy).jaxpr
    names = [eqn.primitive.name for eqn in backward.eqns]
    assert names.count("logistic") == 1 and names.count("exp") == 0
    assert names.count("dot_general") == 6
    pass_ = [eqn for eqn in backward.eqns
             if eqn.primitive.name == "optimization_barrier"
             and len(eqn.outvars) == 3][0]
    assert [v.aval.dtype for v in pass_.outvars] == [bf16] * 3
    assert {v.aval.shape for v in pass_.outvars} == {(2, 24, 64)}
    # the matmul before the pass, the five after it
    at = backward.eqns.index(pass_)
    assert [n for n in names[:at] if n == "dot_general"] == ["dot_general"]
    assert names[at + 1:].count("dot_general") == 5
    residuals = sorted((tuple(v.aval.shape), str(v.aval.dtype))
                       for v in backward.constvars)
    assert residuals == sorted(
        [((2, 24, 32), "bfloat16")] + [((2, 24, 64), "bfloat16")] * 2
        + [((32, 64), "bfloat16")] * 2 + [((64, 32), "bfloat16")])


class DenseMlp(nn.Module):
    """``GatedMlp`` as it was before :func:`gated_mlp`."""

    cfg: HybridConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                            name=name)
        return dense(cfg.d_model, "down")(
            nn.silu(dense(cfg.mlp_width, "gate")(u))
            * dense(cfg.mlp_width, "up")(u))


@pytest.mark.parametrize("dtype", [f32, bf16])
def test_the_parameter_tree_is_what_three_dense_layers_made(dtype):
    cfg = HybridConfig(vocab_size=64, pattern="D", d_model=32, dtype=dtype,
                       mlp_width=48)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32)).astype(dtype)
    key = jax.random.PRNGKey(7)
    got = GatedMlp(cfg).init(key, u)
    want = DenseMlp(cfg).init(key, u)
    assert jax.tree_util.tree_structure(got) \
        == jax.tree_util.tree_structure(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype == f32, jax.tree_util.keystr(path)
        np.testing.assert_array_equal(a, b)
    assert {jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_leaves_with_path(got)} == {
        "['params']['gate']['kernel']", "['params']['up']['kernel']",
        "['params']['down']['kernel']"}
    tol = 2e-6 if dtype == f32 else 2e-2
    np.testing.assert_allclose(
        GatedMlp(cfg).apply(got, u).astype(f32),
        DenseMlp(cfg).apply(want, u).astype(f32), rtol=tol, atol=tol)
    # in the model: under the block's ``mlp``
    model = HybridLM(dataclasses.replace(cfg, pattern="*D", num_heads=4,
                                        num_kv_heads=2, head_dim=8))
    shapes = jax.eval_shape(model.init, key, jnp.zeros((2, 8), jnp.int32))
    assert {k: v["kernel"].shape
            for k, v in shapes["params"]["layer_1"]["mlp"].items()} == {
        "gate": (32, 48), "up": (32, 48), "down": (48, 32)}


def _granite(pattern, seq):
    from benchmark.configs import granite4hmicro as config

    tiny = cells.rehearsal(cells.load_json(
        REPO, "benchmark/configs/granite4hmicro.json"))
    model = config._model(tiny, pattern)
    init = config.off_seed(config._init(model, seq))
    return config, tiny, model, nn.meta.unbox(init(jax.random.PRNGKey(0)))


def _xing4(pattern, seq):
    from benchmark.configs import xing4 as config

    tiny = cells.rehearsal(cells.load_json(
        REPO, "benchmark/configs/xing4.json"))
    model = config._model(tiny, pattern)
    variables = nn.meta.unbox(config._init(model, seq)(jax.random.PRNGKey(0)))
    # off the initial values, at which the streams are copies of one
    # another and the hyper-connection has nothing to mix
    keys = iter(jax.random.split(jax.random.PRNGKey(9), 16))
    for name, layer in variables["params"].items():
        if name.startswith("layer_"):
            hc = layer["hc"]
            hc["gates"] = jnp.asarray([0.6, -0.4, 0.8])
            hc["phi"] = 0.05 * jax.random.normal(next(keys), hc["phi"].shape)
            hc["b_res"] = hc["b_res"] + jax.random.normal(
                next(keys), hc["b_res"].shape)
    return config, tiny, model, variables


@pytest.mark.parametrize("family,pattern,seq", [
    (_granite, "MD*D", 128), (_xing4, "*D", 48), (_xing4, "D", 48)])
def test_hybrid_lm_with_dense_sublayers_agrees_with_the_plain_reference(
        family, pattern, seq):
    """The accepted plain references (``benchmark/configs``: float32
    ``jax.numpy``, nothing of the program) at the rehearsal's sizes:
    ``MD*D`` with the plain residual under ``mlp_remat_policy`` ``dots``,
    ``*D`` and ``D`` inside hyper-connections rematerialised whole — the
    loss and the gradient, at the limits the harness's own tests hold
    them to."""
    config, tiny, model, variables = family(pattern, seq)
    batch = config._sampler(tiny, seq, 0)(np.random.default_rng(0), 2)
    loss, grads = jax.jit(jax.value_and_grad(config._loss_fn(model)))(
        variables, batch)
    want_loss, want = jax.jit(jax.value_and_grad(functools.partial(
        config.reference_loss, config=tiny, pattern=pattern)))(
            variables, batch)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    # granite4hmicro's test: leaf by leaf; xing4's: the whole gradient
    # (its ``b_pre`` is a sum that cancels to 1e-6)
    leaves = list(zip(jax.tree_util.tree_leaves_with_path(grads),
                      jax.tree_util.tree_leaves(want)))
    if family is _granite:
        for (path, got), ref in leaves:
            norm = float(jnp.linalg.norm(ref.ravel()))
            assert norm > 0, jax.tree_util.keystr(path)
            assert float(jnp.linalg.norm((got - ref).ravel())) / norm \
                < 1e-3, jax.tree_util.keystr(path)
    else:
        norm = np.sqrt(sum(float(jnp.sum(r * r)) for _, r in leaves))
        diff = np.sqrt(sum(float(jnp.sum((g - r) ** 2))
                           for (_, g), r in leaves))
        assert norm > 0 and diff / norm < 1e-4
