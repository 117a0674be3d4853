"""Parallelism strategies on the virtual 8-device CPU mesh.

Numerics oracle pattern (reference ``test_adasum_*`` style): every
distributed attention/matmul is checked against its dense single-device
counterpart to machine tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.parallel import (
    ColumnParallelDense,
    RowParallelDense,
    make_parallel_mesh,
    ring_attention,
    ulysses_attention,
)
from horovod_tpu.parallel.ring_attention import reference_attention
from horovod_tpu.parallel.tensor_parallel import (
    column_parallel_dense,
    row_parallel_dense,
)

N = 8


def sp_mesh(sp=8):
    return make_parallel_mesh(sp=sp, devices=jax.devices("cpu")[:8])


def make_qkv(b=2, t=32, h=4, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = make_qkv()
        mesh = sp_mesh()

        def f(q, k, v):
            return ring_attention(q, k, v, "sp", causal=causal)

        spec = P(None, "sp", None, None)
        out = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False))(q, k, v)
        expected = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    def test_grad_matches_dense(self):
        q, k, v = make_qkv(b=1, t=16, h=2, d=8)
        mesh = sp_mesh()
        spec = P(None, "sp", None, None)

        def ring_loss(q, k, v):
            smapped = jax.shard_map(
                lambda q_, k_, v_: ring_attention(q_, k_, v_, "sp",
                                                  causal=True),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False)
            return jnp.sum(smapped(q, k, v) ** 2)

        def dense_loss(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
        g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for gr, gd in zip(g_ring, g_dense):
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                       rtol=1e-4, atol=1e-4)

    def test_long_context_block_memory(self):
        """Each shard only ever holds 1/world of K/V (the point of ring
        attention): shapes inside the step are (b, t/world, h, d)."""
        q, k, v = make_qkv(t=64)
        mesh = sp_mesh()
        spec = P(None, "sp", None, None)

        def f(q, k, v):
            assert q.shape[1] == 64 // N   # local block only
            return ring_attention(q, k, v, "sp")

        jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                              out_specs=spec, check_vma=False))(q, k, v)


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = make_qkv(h=8)   # heads divisible by world
        mesh = sp_mesh()
        spec = P(None, "sp", None, None)

        def f(q, k, v):
            return ulysses_attention(q, k, v, "sp", causal=causal)

        out = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False))(q, k, v)
        expected = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)

    def test_head_divisibility_error(self):
        q, k, v = make_qkv(h=6)
        mesh = sp_mesh()
        spec = P(None, "sp", None, None)
        with pytest.raises(ValueError, match="divisible"):
            jax.jit(jax.shard_map(
                lambda q_, k_, v_: ulysses_attention(q_, k_, v_, "sp"),
                mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                check_vma=False))(q, k, v)


class TestTensorParallel:
    def test_column_then_row_matches_dense(self):
        """Classic TP MLP: column-parallel → gelu → row-parallel with one
        psum equals the dense computation."""
        mesh = make_parallel_mesh(tp=8, devices=jax.devices("cpu")[:8])
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (4, 32), jnp.float32)
        w1 = jax.random.normal(jax.random.fold_in(key, 1), (32, 64)) * 0.1
        w2 = jax.random.normal(jax.random.fold_in(key, 2), (64, 32)) * 0.1

        def f(x, w1, w2):
            h = column_parallel_dense(x, w1)     # w1 sharded (in, out/tp)
            h = jax.nn.gelu(h)
            return row_parallel_dense(h, w2, axis="tp")

        out = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=(P(), P(None, "tp"), P("tp", None)),
            out_specs=P(), check_vma=False))(x, w1, w2)
        expected = jax.nn.gelu(x @ w1) @ w2
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=1e-5, atol=1e-5)

    def test_pjit_modules_match_dense(self):
        """GSPMD path: partitioned flax modules under jit over a tp mesh
        produce the same numbers as unsharded execution."""
        mesh = make_parallel_mesh(tp=8, devices=jax.devices("cpu")[:8])
        import flax.linen as nn

        class TpMlp(nn.Module):
            @nn.compact
            def __call__(self, x):
                h = ColumnParallelDense(64, axis="tp")(x)
                h = nn.gelu(h)
                return RowParallelDense(32, axis="tp")(h)

        model = TpMlp()
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32), jnp.float32)
        variables = model.init(jax.random.PRNGKey(1), x)
        dense_out = model.apply(variables, x)

        with jax.set_mesh(mesh):
            sharded_out = jax.jit(model.apply)(variables, x)
        np.testing.assert_allclose(np.asarray(sharded_out),
                                   np.asarray(dense_out),
                                   rtol=1e-5, atol=1e-5)


class TestAmbientMeshDetection:
    """_constrainable_axes and the no-mesh warning (ADVICE round 5):
    partitioned modules silently replicate without an ambient mesh, so
    the first such execution must say so.  One mechanism detects the
    mesh — the public ``jax.sharding.get_abstract_mesh()``, which
    ``jax.set_mesh`` installs and ``shard_map`` binds with its axes
    Manual."""

    def _fresh(self):
        from horovod_tpu.parallel import tensor_parallel as tp

        tp._warned_no_ambient_mesh = False
        return tp

    def test_ambient_mesh_detection(self):
        tp = self._fresh()
        # outside any context there is no mesh -> no constrainable axes
        assert tp._constrainable_axes() is None
        mesh = make_parallel_mesh(tp=8, devices=jax.devices("cpu")[:8])
        with jax.set_mesh(mesh):
            assert "tp" in tp._constrainable_axes()
        seen = []

        def body(x):
            seen.append(tp._constrainable_axes())
            return x

        jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("tp"),
                              out_specs=P("tp")))(jnp.ones((8,)))
        # inside shard_map every axis is Manual: nothing to constrain
        assert seen == [set()]

    def _capture_warnings(self, tp, monkeypatch):
        # the hvd logger sets propagate=False, so caplog can't see it;
        # intercept at the module seam instead
        calls = []
        monkeypatch.setattr(
            tp.hvd_logging, "warning",
            lambda msg, *a: calls.append(msg % a if a else msg))
        return calls

    def test_warns_once_without_mesh(self, monkeypatch):
        tp = self._fresh()
        calls = self._capture_warnings(tp, monkeypatch)
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32), jnp.float32)
        model = ColumnParallelDense(64, axis="tp")
        variables = model.init(jax.random.PRNGKey(1), x)  # 1st execution
        model.apply(variables, x)
        model.apply(variables, x)
        hits = [c for c in calls if "no ambient mesh" in c]
        assert len(hits) == 1, calls
        assert "REPLICATED" in hits[0] and "'tp'" in hits[0]

    def test_no_warning_under_mesh(self, monkeypatch):
        tp = self._fresh()
        calls = self._capture_warnings(tp, monkeypatch)
        mesh = make_parallel_mesh(tp=8, devices=jax.devices("cpu")[:8])
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32), jnp.float32)
        model = ColumnParallelDense(64, axis="tp")
        with jax.set_mesh(mesh):
            variables = model.init(jax.random.PRNGKey(1), x)
            jax.jit(model.apply)(variables, x)
        assert not [c for c in calls if "no ambient mesh" in c]
        assert not tp._warned_no_ambient_mesh


class TestMeshFactory:
    def test_infers_dp(self):
        mesh = make_parallel_mesh(tp=2, sp=2,
                                  devices=jax.devices("cpu")[:8])
        assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 2
        assert mesh.shape["sp"] == 2 and mesh.shape["pp"] == 1

    def test_bad_factorization(self):
        with pytest.raises(ValueError, match="divisible"):
            make_parallel_mesh(tp=3, devices=jax.devices("cpu")[:8])


class TestFSDP:
    """ZeRO-3-style fully-sharded data parallelism by placement
    (parallel/fsdp.py + DistributedTrainStep(fsdp_axis=...))."""

    def _mesh(self):
        devs = np.asarray(jax.devices("cpu")[:8]).reshape(2, 4)
        return Mesh(devs, ("dcn", "ici"))

    def test_sharding_rule(self):
        from horovod_tpu.parallel import fsdp

        mesh = self._mesh()
        # big matrix: largest divisible dim partitioned over ici (4)
        s = fsdp.fsdp_sharding((256, 128), mesh, "ici")
        assert s.spec == P("ici", None)
        s = fsdp.fsdp_sharding((128, 256), mesh, "ici")
        assert s.spec == P(None, "ici")
        # small leaf stays replicated
        assert fsdp.fsdp_sharding((64,), mesh, "ici").spec == P()
        # indivisible largest dim: falls to a divisible one
        s = fsdp.fsdp_sharding((254, 130), mesh, "ici",
                               min_weight_size=1)
        assert s.spec == P()  # neither 254 nor 130 divisible by 4

    def test_train_step_fsdp_matches_replicated(self):
        import optax

        import horovod_tpu as hvd
        from horovod_tpu.parallel import fsdp

        def loss_fn(params, batch):
            h = jax.nn.relu(batch["x"] @ params["w1"])
            return jnp.mean((h @ params["w2"] - batch["y"]) ** 2)

        rng = np.random.RandomState(0)
        w1 = rng.randn(64, 256).astype(np.float32) * 0.05
        w2 = rng.randn(256, 8).astype(np.float32) * 0.05
        xb = rng.randn(32, 64).astype(np.float32)
        yb = rng.randn(32, 8).astype(np.float32)

        hvd.init()
        results = {}
        for fsdp_axis in (None, "ici"):
            kw = {"fsdp_axis": "ici", "fsdp_min_weight_size": 1} \
                if fsdp_axis else {}
            step = hvd.DistributedTrainStep(
                loss_fn, optax.adam(1e-2), mode="pjit", **kw)
            params, opt_state = step.init({"w1": jnp.asarray(w1),
                                           "w2": jnp.asarray(w2)})
            if fsdp_axis:
                # parameters and adam state actually live sharded
                assert params["w1"].sharding.spec == P(None, "ici")
                mu = jax.tree_util.tree_leaves(opt_state)
                specs = [str(getattr(m.sharding, "spec", "")) for m in mu]
                assert any("ici" in sp for sp in specs), specs
                # resident bytes shrink ~4x for the sharded leaves
                repl_bytes = sum(v.size * 4 for v in (w1, w2))
                assert fsdp.resident_bytes(params) <= repl_bytes // 2
            batch = step.shard_batch({"x": jnp.asarray(xb),
                                      "y": jnp.asarray(yb)})
            for _ in range(3):
                params, opt_state, loss = step(params, opt_state, batch)
            results[fsdp_axis] = (
                np.asarray(jax.device_get(params["w1"])),
                np.asarray(jax.device_get(params["w2"])),
                float(loss))

        # FSDP is a placement change, not an algorithm change
        np.testing.assert_allclose(results[None][0], results["ici"][0],
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(results[None][1], results["ici"][1],
                                   rtol=2e-5, atol=1e-6)
        assert abs(results[None][2] - results["ici"][2]) < 1e-5

    def test_mode_guard(self):
        import optax

        import horovod_tpu as hvd

        hvd.init()
        with pytest.raises(ValueError, match="pjit"):
            hvd.DistributedTrainStep(lambda p, b: 0.0, optax.sgd(0.1),
                                     mode="shard_map", fsdp_axis="ici")
        with pytest.raises(ValueError, match="axis"):
            hvd.DistributedTrainStep(lambda p, b: 0.0, optax.sgd(0.1),
                                     mode="pjit", fsdp_axis="nope")
