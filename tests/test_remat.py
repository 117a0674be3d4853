"""Remat policy compiler (memory/remat.py, docs/memory.md): the
per-block ``none|dots|full|offload`` tiers must be numerics-neutral —
same logits AND same grads as the un-remat model on all three flagship
architectures — and the resolution precedence (explicit > env > legacy
bool) must hold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.memory.remat import (
    REMAT_POLICIES,
    checkpoint_policy,
    remat_block,
    remat_fn,
    resolve_remat_policy,
)
from horovod_tpu.models import (
    MoEConfig,
    MoETransformerLM,
    TransformerConfig,
    TransformerLM,
    lm_loss,
)

POLICIES = ("dots", "full", "offload")


def tf_cfg(**kw):
    base = dict(vocab_size=128, num_layers=2, num_heads=4, d_model=32,
                d_ff=64, max_seq_len=64, dtype=jnp.float32)
    base.update(kw)
    return TransformerConfig(**base)


def moe_cfg(**kw):
    base = dict(vocab_size=64, num_layers=2, num_heads=2, d_model=32,
                d_ff=64, max_seq_len=16, dtype=jnp.float32,
                num_experts=4, capacity_factor=8.0, moe_every=2)
    base.update(kw)
    return MoEConfig(**base)


def assert_trees_close(a, b, **tol):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **tol)


class TestResolution:
    def test_default_is_none(self):
        assert resolve_remat_policy() == "none"

    def test_legacy_bool(self):
        assert resolve_remat_policy(remat=True) == "full"
        assert resolve_remat_policy(remat=False) == "none"

    def test_string_through_legacy_slot_is_explicit(self):
        assert resolve_remat_policy(remat="dots") == "dots"

    def test_env_beats_legacy_bool(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_REMAT_POLICY", "dots")
        assert resolve_remat_policy(remat=True) == "dots"
        assert resolve_remat_policy() == "dots"

    def test_explicit_beats_env(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_REMAT_POLICY", "dots")
        assert resolve_remat_policy("full") == "full"
        assert resolve_remat_policy(remat="offload") == "offload"

    def test_unknown_policy_refuses(self, monkeypatch):
        with pytest.raises(ValueError, match="unknown remat policy"):
            resolve_remat_policy("sometimes")
        monkeypatch.setenv("HOROVOD_REMAT_POLICY", "frobnicate")
        with pytest.raises(ValueError, match="unknown remat policy"):
            resolve_remat_policy()

    def test_vocabulary_mirrored_in_cost_model(self):
        from horovod_tpu.analysis import cost_model as CM

        assert tuple(sorted(REMAT_POLICIES)) == \
            tuple(sorted(CM.REMAT_ACTIVATION_FRACTION))
        assert tuple(sorted(REMAT_POLICIES)) == \
            tuple(sorted(CM.REMAT_RECOMPUTE_OVERHEAD))


class TestWrappers:
    def test_none_is_identity(self):
        class Sentinel:
            pass

        assert remat_block(Sentinel, "none") is Sentinel
        fn = lambda x: x  # noqa: E731
        assert remat_fn(fn, "none") is fn

    def test_checkpoint_policy_tiers(self):
        # none/full need no policy argument; dots names the saveable
        # set; offload constructs (or degrades to dots on CPU XLA /
        # old JAX) — never raises
        assert checkpoint_policy("none") is None
        assert checkpoint_policy("full") is None
        assert checkpoint_policy("dots") is not None
        assert checkpoint_policy("offload") is not None

    def test_remat_fn_parity(self):
        def f(x):
            return jnp.sum(jnp.tanh(x @ x.T))

        x = jax.random.normal(jax.random.PRNGKey(0), (8, 8))
        base = jax.grad(f)(x)
        for policy in POLICIES:
            # offload's TransferToMemoryKind is jit-only by contract
            g = jax.jit(jax.grad(remat_fn(f, policy)))(x)
            assert_trees_close(base, g, rtol=1e-6, atol=1e-6)


class TestModelParity:
    """Every policy tier computes the same function — logits and
    grads — as the plain block; only the liveness profile may differ.
    All applies run under jit: ``offload``'s host memory-kind
    transfers are jit-only by JAX contract."""

    @pytest.mark.parametrize("policy", POLICIES)
    def test_transformer(self, policy):
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32),
                                    0, 128)
        base = TransformerLM(tf_cfg())
        variables = base.init(jax.random.PRNGKey(1), tokens)
        model = TransformerLM(tf_cfg(remat_policy=policy))

        np.testing.assert_allclose(
            np.asarray(jax.jit(base.apply)(variables, tokens)),
            np.asarray(jax.jit(model.apply)(variables, tokens)),
            rtol=1e-5, atol=1e-5)
        g0 = jax.jit(lambda v: jax.grad(lm_loss)(v, base, tokens))(
            variables)
        g1 = jax.jit(lambda v: jax.grad(lm_loss)(v, model, tokens))(
            variables)
        assert_trees_close(g0, g1, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("policy", POLICIES)
    def test_vit(self, policy):
        from horovod_tpu.models import ViTConfig, VisionTransformer

        kw = dict(image_size=16, patch_size=8, num_classes=4,
                  num_layers=2, num_heads=2, d_model=32, d_ff=64,
                  dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 3))
        base = VisionTransformer(ViTConfig(**kw))
        variables = base.init(jax.random.PRNGKey(1), x)
        model = VisionTransformer(ViTConfig(remat_policy=policy, **kw))

        np.testing.assert_allclose(
            np.asarray(jax.jit(base.apply)(variables, x)),
            np.asarray(jax.jit(model.apply)(variables, x)),
            rtol=1e-5, atol=1e-5)

        def grad_for(m):
            return jax.jit(jax.grad(
                lambda v: jnp.sum(m.apply(v, x) ** 2)))(variables)

        assert_trees_close(grad_for(base), grad_for(model),
                           rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("policy", POLICIES)
    def test_moe(self, policy):
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16),
                                    0, 64)
        base = MoETransformerLM(moe_cfg())
        variables = base.init(jax.random.PRNGKey(1), tokens)
        model = MoETransformerLM(moe_cfg(remat_policy=policy))

        np.testing.assert_allclose(
            np.asarray(jax.jit(base.apply)(variables, tokens)),
            np.asarray(jax.jit(model.apply)(variables, tokens)),
            rtol=1e-5, atol=1e-5)

        def grad_for(m):
            return jax.jit(jax.grad(
                lambda v: jnp.sum(m.apply(v, tokens) ** 2)))(variables)

        assert_trees_close(grad_for(base), grad_for(model),
                           rtol=2e-5, atol=1e-5)

    def test_env_policy_reaches_the_block(self, monkeypatch):
        """HOROVOD_REMAT_POLICY steers an un-flagged model — same
        numbers, resolved at apply time."""
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32),
                                    0, 128)
        base = TransformerLM(tf_cfg())
        variables = base.init(jax.random.PRNGKey(1), tokens)
        expected = np.asarray(base.apply(variables, tokens))
        monkeypatch.setenv("HOROVOD_REMAT_POLICY", "full")
        np.testing.assert_allclose(
            np.asarray(TransformerLM(tf_cfg()).apply(variables, tokens)),
            expected, rtol=1e-5, atol=1e-5)


class TestTrainStepPolicy:
    """The resolved policy is a property of the step."""

    def _step(self, **kw):
        import optax

        import horovod_tpu as hvd

        hvd.init()

        def loss_fn(params, batch):
            return jnp.mean((batch["x"] @ params["w"]) ** 2)

        return hvd.DistributedTrainStep(loss_fn, optax.sgd(0.1), **kw)

    def test_policy_string(self):
        assert self._step(remat="dots").remat_policy == "dots"

    def test_legacy_bool_and_default(self):
        assert self._step(remat=True).remat_policy == "full"
        assert self._step().remat_policy == "none"

    def test_env_policy_reaches_the_step(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_REMAT_POLICY", "dots")
        assert self._step(remat=True).remat_policy == "dots"

    def test_remat_step_trains_identically(self):
        """One seeded step at remat=full equals the plain step —
        the policy changes liveness, never numbers."""
        import optax

        import horovod_tpu as hvd

        hvd.init()

        def loss_fn(params, batch):
            h = jnp.tanh(batch["x"] @ params["w1"])
            return jnp.mean((h @ params["w2"] - batch["y"]) ** 2)

        rng = np.random.RandomState(0)
        variables = {"w1": jnp.asarray(rng.randn(8, 16), jnp.float32),
                     "w2": jnp.asarray(rng.randn(16, 4), jnp.float32)}
        x = jnp.asarray(np.random.RandomState(1).randn(8, 8),
                        jnp.float32)
        y = jnp.asarray(np.random.RandomState(2).randn(8, 4),
                        jnp.float32)
        losses = {}
        for remat in (False, "full"):
            step = hvd.DistributedTrainStep(loss_fn, optax.sgd(0.1),
                                            remat=remat)
            # the step donates its state buffers — fresh copies per run
            params, opt = step.init(
                jax.tree_util.tree_map(jnp.array, variables))
            batch = step.shard_batch({"x": x, "y": y})
            for _ in range(3):
                params, opt, loss = step(params, opt, batch)
            losses[remat] = float(loss)
        assert losses[False] == losses["full"]
