"""Transformer LM: dense vs ring/ulysses parity, TP under GSPMD, loss.

The distributed-attention variants must produce the same logits as the
dense single-device model — same oracle pattern as test_parallel.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import TransformerConfig, TransformerLM, lm_loss
from horovod_tpu.parallel import make_parallel_mesh


def small_cfg(**kw):
    defaults = dict(vocab_size=128, num_layers=2, num_heads=4, d_model=32,
                    d_ff=64, max_seq_len=64, dtype=jnp.float32)
    defaults.update(kw)
    return TransformerConfig(**defaults)


def make_tokens(b=2, t=32, vocab=128, seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, t), 0, vocab)


class TestDense:
    def test_forward_shapes_and_loss(self):
        cfg = small_cfg()
        model = TransformerLM(cfg)
        tokens = make_tokens()
        variables = model.init(jax.random.PRNGKey(0), tokens)
        logits = model.apply(variables, tokens)
        assert logits.shape == (2, 32, 128)
        assert logits.dtype == jnp.float32
        loss = lm_loss(variables, model, tokens)
        assert np.isfinite(float(loss))
        assert float(loss) == pytest.approx(np.log(128), rel=0.2)

    def test_remat_matches(self):
        tokens = make_tokens()
        m1 = TransformerLM(small_cfg())
        m2 = TransformerLM(small_cfg(remat=True))
        v = m1.init(jax.random.PRNGKey(0), tokens)
        np.testing.assert_allclose(
            np.asarray(m1.apply(v, tokens)), np.asarray(m2.apply(v, tokens)),
            rtol=1e-5, atol=1e-5)


class TestSequenceParallel:
    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_matches_dense(self, impl):
        # ulysses shards heads over the 8-way sp axis -> needs 8 heads
        heads = 8 if impl == "ulysses" else 4
        tokens = make_tokens(b=2, t=32)
        dense = TransformerLM(small_cfg(num_heads=heads))
        variables = dense.init(jax.random.PRNGKey(0), tokens)
        expected = dense.apply(variables, tokens)

        sp_model = TransformerLM(small_cfg(num_heads=heads,
                                           attention_impl=impl))
        mesh = make_parallel_mesh(sp=8, devices=jax.devices("cpu")[:8])
        t_local = 32 // 8
        # shard_map is manual-mesh: strip GSPMD partitioning boxes
        import flax.core.meta as meta

        variables = meta.unbox(variables)

        def f(variables, tokens_local):
            offset = lax.axis_index("sp") * t_local
            positions = offset + jnp.arange(t_local)
            return sp_model.apply(variables, tokens_local,
                                  positions=positions)

        out = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P(), P(None, "sp")),
            out_specs=P(None, "sp", None), check_vma=False))(
                variables, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=3e-4, atol=3e-4)


class TestTensorParallelGSPMD:
    def test_tp_matches_dense(self):
        tokens = make_tokens()
        model = TransformerLM(small_cfg())
        variables = model.init(jax.random.PRNGKey(0), tokens)
        expected = model.apply(variables, tokens)

        mesh = make_parallel_mesh(tp=8, devices=jax.devices("cpu")[:8])
        with jax.set_mesh(mesh):
            out = jax.jit(model.apply)(variables, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=1e-4, atol=1e-4)


class TestTpuEfficiencyHints:
    def test_head_dim_hint(self):
        from horovod_tpu.models import TransformerConfig

        cfg = TransformerConfig(d_model=1024, num_heads=16)  # head_dim 64
        hints = cfg.tpu_efficiency_hints()
        assert any("head_dim 64" in h and "num_heads=8" in h
                   for h in hints), hints

    def test_clean_config_no_hints(self):
        from horovod_tpu.models import TransformerConfig

        cfg = TransformerConfig(d_model=2048, num_heads=16)  # head_dim 128
        assert cfg.tpu_efficiency_hints() == []

    def test_non_multiple_d_model(self):
        from horovod_tpu.models import TransformerConfig

        cfg = TransformerConfig(d_model=1000, num_heads=8)
        hints = cfg.tpu_efficiency_hints()
        assert any("multiple of 128" in h for h in hints)
        # no head-count suggestion when padding is the first problem
        assert not any("num_heads=" in h for h in hints)

    def test_suggestion_is_always_a_divisor(self):
        from horovod_tpu.models import TransformerConfig

        import re
        for d in (256, 768, 1024, 1280, 1536, 2048, 4096):
            heads = max(d // 64, 2)
            if d % heads:
                continue
            cfg = TransformerConfig(d_model=d, num_heads=heads)
            for h in cfg.tpu_efficiency_hints():
                m = re.search(r"num_heads=(\d+)", h)
                if m:
                    n = int(m.group(1))
                    assert d % n == 0 and d // n >= 128, (d, n)


class TestFusedTpApply:
    """Tile-fused sequence-parallel execution (ISSUE 9): fused_tp_apply
    under shard_map over tp must reproduce the GSPMD apply's logits —
    the numerics pin of the matmul⊗collective kernels in their
    transformer wiring."""

    def _cfg(self, **kw):
        return small_cfg(num_heads=8, d_model=64, d_ff=128,
                         fused_collectives="on", **kw)

    def _run(self, cfg, variables, tokens, **apply_kw):
        import flax.core.meta as meta

        from horovod_tpu.models.transformer import fused_tp_apply

        mesh = make_parallel_mesh(tp=8, devices=jax.devices("cpu")[:8])
        unboxed = meta.unbox(variables)

        def f(v, toks):
            return fused_tp_apply(v, cfg, toks, **apply_kw)

        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False))(unboxed, tokens)

    @pytest.mark.parametrize("impl", ["dense", "flash"])
    def test_matches_gspmd_apply(self, impl):
        cfg = self._cfg(attention_impl=impl)
        model = TransformerLM(cfg)
        tokens = make_tokens(b=2, t=32)
        variables = model.init(jax.random.PRNGKey(0), tokens)
        expected = model.apply(variables, tokens)
        out = self._run(cfg, variables, tokens)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(expected),
                                   rtol=3e-4, atol=3e-4)

    def test_unfused_sp_twin_matches_too(self):
        """fused=False keeps the same Megatron-SP structure with plain
        collectives — the graceful-degradation baseline the fused path
        is pinned against."""
        cfg = self._cfg()
        model = TransformerLM(cfg)
        tokens = make_tokens(b=2, t=32)
        variables = model.init(jax.random.PRNGKey(1), tokens)
        expected = model.apply(variables, tokens)
        fused = self._run(cfg, variables, tokens, fused=True)
        unfused = self._run(cfg, variables, tokens, fused=False)
        np.testing.assert_allclose(np.asarray(fused),
                                   np.asarray(unfused),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(unfused),
                                   np.asarray(expected),
                                   rtol=3e-4, atol=3e-4)

    def test_divisibility_validation(self):
        cfg = self._cfg()
        model = TransformerLM(cfg)
        tokens = make_tokens(b=1, t=28)      # 28 % 8 != 0
        variables = model.init(jax.random.PRNGKey(0), tokens)
        with pytest.raises(ValueError, match="divisible"):
            self._run(cfg, variables, tokens)

    def test_rejects_sequence_parallel_attention(self):
        cfg = self._cfg(attention_impl="ring")
        model = TransformerLM(self._cfg())
        tokens = make_tokens(b=1, t=32)
        variables = model.init(jax.random.PRNGKey(0), tokens)
        with pytest.raises(ValueError, match="attention_impl"):
            self._run(cfg, variables, tokens)

    def test_fused_kernel_grads_match_unfused(self):
        """The ring kernels must stay differentiable (training wiring
        depends on it): per-rank grads through the fused ops equal the
        grads through their unfused formulations inside the SAME
        shard_map program — the transpose of the ring is the transpose
        of the collective it replaces."""
        from horovod_tpu.ops.pallas_kernels import (
            allgather_matmul,
            matmul_reducescatter,
        )

        mesh = make_parallel_mesh(tp=8, devices=jax.devices("cpu")[:8])
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(64, 16), jnp.float32)
        w = jnp.asarray(rng.randn(16, 8), jnp.float32)
        xs = jnp.asarray(rng.randn(4, 16), jnp.float32)

        def grads(fused):
            def loss(x, w, xs):
                a = jnp.sum(matmul_reducescatter(x, w, "tp",
                                                 fused=fused) ** 2)
                b = jnp.sum(allgather_matmul(xs, w, "tp",
                                             fused=fused) ** 2)
                return a + b

            return jax.jit(jax.shard_map(
                jax.grad(loss, argnums=(0, 1, 2)), mesh=mesh,
                in_specs=(P(), P(), P()), out_specs=P(),
                check_vma=False))(x, w, xs)

        for gf, gu, name in zip(grads(True), grads(False),
                                ("dx", "dw", "dxs")):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gu),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=name)

    def test_grads_flow_through_fused_apply(self):
        """End-to-end differentiability smoke: the fused SP forward
        backprops to every parameter leaf with finite values."""
        import flax.core.meta as meta
        import optax

        from horovod_tpu.models.transformer import fused_tp_apply

        cfg = self._cfg()
        model = TransformerLM(cfg)
        tokens = make_tokens(b=2, t=32)
        variables = meta.unbox(model.init(jax.random.PRNGKey(0),
                                          tokens))
        mesh = make_parallel_mesh(tp=8, devices=jax.devices("cpu")[:8])

        def loss_fused(v, toks):
            logits = fused_tp_apply(v, cfg, toks)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], toks[:, 1:]).mean()

        g = jax.jit(jax.shard_map(
            jax.grad(loss_fused), mesh=mesh, in_specs=(P(), P()),
            out_specs=P(), check_vma=False))(variables, tokens)
        leaves = jax.tree_util.tree_leaves(g)
        assert leaves and all(
            np.isfinite(np.asarray(x)).all() for x in leaves)
        # the loss actually depends on the weights through the fused
        # path: at least the block kernels carry non-zero gradient
        assert any(float(jnp.max(jnp.abs(x))) > 0 for x in leaves)


class TestBoxedParamsThroughTrainStep:
    """The params ``model.init`` returns — kernels boxed in
    ``nn.Partitioned`` — go through every ``DistributedTrainStep`` mode
    as they are.  flax's own unboxing applies the boxed ``tp`` spec as
    a sharding constraint wherever a mesh is bound, which inside the
    shard_map step (axes Manual, and no ``tp`` on the runtime mesh)
    raised; the tp modules unbox without it (parallel/tensor_parallel
    ``param_value``)."""

    def _losses(self, hvd, **step_kw):
        import flax.linen as nn
        import optax

        model = TransformerLM(small_cfg())
        tokens = make_tokens(b=8, t=32)
        variables = model.init(jax.random.PRNGKey(0), tokens)
        assert any(isinstance(x, nn.Partitioned)
                   for x in jax.tree_util.tree_leaves(
                       variables,
                       is_leaf=lambda x: isinstance(x, nn.Partitioned)))

        def loss_fn(params, batch):
            return lm_loss(params, model, batch)

        step = hvd.DistributedTrainStep(loss_fn, optax.adamw(1e-2),
                                        **step_kw)
        params, opt_state = step.init(variables)
        batch = step.shard_batch(np.asarray(tokens))
        losses = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
        return losses

    @pytest.mark.parametrize("shard_opt", [False, True])
    def test_shard_map_accepts_boxed_params_and_matches_pjit(
            self, hvd_runtime, shard_opt):
        want = self._losses(hvd_runtime)
        got = self._losses(hvd_runtime, mode="shard_map",
                           shard_optimizer_states=shard_opt)
        assert want[-1] < want[0]
        np.testing.assert_allclose(got, want, rtol=1e-5)


class TestKernelOverAmbientMesh:
    """``attention_impl="flash"`` under an ambient mesh runs the kernel
    in a ``shard_map`` over the mesh's axes.  An axis whose extent
    divides neither the batch nor (tp) the heads is left out — its
    devices repeat the work — and that is said once a shape."""

    def _apply(self, monkeypatch, b, heads, **mesh_kw):
        from horovod_tpu.models import transformer as tr

        calls = []
        monkeypatch.setattr(tr.hvd_logging, "warning",
                            lambda msg, *a: calls.append(msg % a))
        monkeypatch.setattr(tr, "_warned_replicated", set())
        tokens = make_tokens(b=b, t=32)
        cfg = dict(num_heads=heads, flash_interpret=True)
        dense = TransformerLM(small_cfg(**cfg))
        variables = dense.init(jax.random.PRNGKey(0), tokens)
        expected = dense.apply(variables, tokens)
        model = TransformerLM(small_cfg(attention_impl="flash", **cfg))
        mesh = make_parallel_mesh(devices=jax.devices("cpu")[:8], **mesh_kw)
        with jax.set_mesh(mesh):
            for _ in range(2):
                out = jax.jit(model.apply)(variables, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=1e-4, atol=1e-4)
        return [c for c in calls if c.startswith("attention kernel")]

    def test_every_axis_used_is_silent(self, monkeypatch):
        assert self._apply(monkeypatch, b=4, heads=4, tp=2) == []

    def test_axis_left_out_is_reported_once(self, monkeypatch):
        # batch 2 over dp=4, 1 head over tp=2: both axes are left out
        calls = self._apply(monkeypatch, b=2, heads=1, tp=2)
        assert len(calls) == 1, calls
        assert "(2, 32, 1," in calls[0] and "'dp', 'tp'" in calls[0], calls


@pytest.mark.parametrize("impl,told", [("flash", True), ("dense", False)])
def test_a_traced_flash_step_names_its_backward_to_the_compile_span(
        hvd_runtime, impl, told):
    """The counter that says the mechanism is what ran: a step whose
    attention is the flash kernel tells the span it is traced under
    (``train_step.lower``, which hands its attributes to
    ``train_step.compile``) that the backward is one call a layer of
    five products a block pair; a dense step says nothing."""
    import optax

    from horovod_tpu import telemetry

    hvd = hvd_runtime
    was_on = telemetry.enabled()
    telemetry.enable()
    try:
        model = TransformerLM(small_cfg(attention_impl=impl,
                                        flash_interpret=True))
        tokens = np.asarray(make_tokens(b=8, t=33))
        step = hvd.DistributedTrainStep(
            lambda p, b: lm_loss(p, model, b["tokens"]), optax.sgd(0.1))
        params, opt_state = step.init(
            model.init(jax.random.PRNGKey(0), tokens[:1, :-1]))
        since = telemetry.spans._now()
        jax.block_until_ready(step(params, opt_state,
                                   step.shard_batch({"tokens": tokens})))
        (compiled,) = [s for s in telemetry.spans.snapshot(since=since)
                       if s.name == "train_step.compile"]
        attrs = compiled.attrs or {}
        if told:
            assert attrs["flash_bwd_calls_per_layer"] == 1
            assert attrs["flash_bwd_products_per_pair"] == 5
            # 8-wide heads: dq, dk and dv all add up transposed
            assert attrs["flash_bwd_transposed_grads"] == 3
        else:
            assert not any(k.startswith("flash_bwd") for k in attrs)
    finally:
        if not was_on:
            telemetry.disable()
