"""DistributedOptimizer / DistributedTrainStep end-to-end on a tiny MLP.

Mirrors the reference's optimizer-layer tests (``test_torch.py``
DistributedOptimizer cases): train a small model data-parallel and assert
(a) the pjit and shard_map paths agree, (b) loss decreases, (c)
backward_passes_per_step accumulation and join_step masking behave.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops import collectives as C
from horovod_tpu.optim.train_step import join_step
from horovod_tpu.runtime.topology import GLOBAL_AXES


@pytest.fixture(autouse=True)
def runtime():
    hvd.init()
    yield


def make_params(key):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (4, 16)) * 0.1,
        "b1": jnp.zeros((16,)),
        "w2": jax.random.normal(k2, (16, 1)) * 0.1,
        "b2": jnp.zeros((1,)),
    }


def loss_fn(params, batch):
    x, y = batch["x"], batch["y"]
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    pred = h @ params["w2"] + params["b2"]
    return jnp.mean((pred - y) ** 2)


def make_batch(n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype(np.float32)
    y = (x.sum(axis=1, keepdims=True) > 0).astype(np.float32)
    return {"x": jnp.asarray(x), "y": jnp.asarray(y)}


class TestDistributedTrainStep:
    def test_loss_decreases_pjit(self):
        step = hvd.DistributedTrainStep(loss_fn, optax.adam(1e-2))
        params, opt_state = step.init(make_params(jax.random.PRNGKey(0)))
        batch = step.shard_batch(make_batch())
        losses = []
        for _ in range(30):
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5

    def test_shard_map_matches_pjit(self):
        params0 = make_params(jax.random.PRNGKey(1))
        batch = make_batch()

        outs = {}
        for mode in ("pjit", "shard_map"):
            step = hvd.DistributedTrainStep(loss_fn, optax.sgd(0.1),
                                            mode=mode, donate=False)
            params, opt_state = step.init(params0)
            b = step.shard_batch(batch)
            for _ in range(5):
                params, opt_state, loss = step(params, opt_state, b)
            outs[mode] = (jax.device_get(params), float(loss))

        for k in outs["pjit"][0]:
            np.testing.assert_allclose(
                np.asarray(outs["pjit"][0][k]),
                np.asarray(outs["shard_map"][0][k]), rtol=1e-4, atol=1e-6)
        assert abs(outs["pjit"][1] - outs["shard_map"][1]) < 1e-4

    def test_steps_per_call_matches_sequential(self):
        """k scanned steps in one program == k sequential calls (the
        Keras steps_per_execution analogue), for both modes."""
        params0 = make_params(jax.random.PRNGKey(2))
        batch = make_batch()
        for mode in ("pjit", "shard_map"):
            seq = hvd.DistributedTrainStep(loss_fn, optax.sgd(0.1),
                                           mode=mode, donate=False)
            p, o = seq.init(params0)
            b = seq.shard_batch(batch)
            for _ in range(4):
                p, o, loss_seq = seq(p, o, b)

            fused = hvd.DistributedTrainStep(loss_fn, optax.sgd(0.1),
                                             mode=mode, donate=False,
                                             steps_per_call=4)
            fp, fo = fused.init(params0)
            fp, fo, loss_fused = fused(fp, fo, fused.shard_batch(batch))
            for k in p:
                np.testing.assert_allclose(np.asarray(p[k]),
                                           np.asarray(fp[k]),
                                           rtol=1e-5, atol=1e-6)
            assert abs(float(loss_seq) - float(loss_fused)) < 1e-5

    def test_steps_per_call_validation(self):
        with pytest.raises(ValueError, match="steps_per_call"):
            hvd.DistributedTrainStep(loss_fn, optax.sgd(0.1),
                                     steps_per_call=0)

    def test_compiler_options_path(self):
        """compiler_options reach the step's one lower/compile path;
        results match the default and the compile is cached per
        signature."""
        params0 = make_params(jax.random.PRNGKey(3))
        batch = make_batch()
        ref = hvd.DistributedTrainStep(loss_fn, optax.sgd(0.1),
                                       donate=False)
        p, o = ref.init(params0)
        b = ref.shard_batch(batch)
        p, o, loss_ref = ref(p, o, b)

        opt = hvd.DistributedTrainStep(loss_fn, optax.sgd(0.1),
                                       donate=False,
                                       compiler_options={})
        cp, co = opt.init(params0)
        cp, co, loss_opt = opt(cp, co, opt.shard_batch(batch))
        assert abs(float(loss_ref) - float(loss_opt)) < 1e-6
        assert len(opt._compiled_cache) == 1
        opt(cp, co, opt.shard_batch(batch))
        assert len(opt._compiled_cache) == 1

    def test_adasum_mode_runs(self):
        step = hvd.DistributedTrainStep(loss_fn, optax.sgd(0.05),
                                        mode="shard_map", op=hvd.Adasum)
        params, opt_state = step.init(make_params(jax.random.PRNGKey(2)))
        batch = step.shard_batch(make_batch())
        losses = []
        for _ in range(20):
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_compression_mode_runs(self):
        step = hvd.DistributedTrainStep(loss_fn, optax.sgd(0.1),
                                        mode="shard_map",
                                        compression=hvd.Compression.bf16)
        params, opt_state = step.init(make_params(jax.random.PRNGKey(3)))
        batch = step.shard_batch(make_batch())
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, batch)
        assert np.isfinite(float(loss))


class TestDistributedOptimizerTransform:
    def test_backward_passes_per_step(self):
        opt = hvd.DistributedOptimizer(optax.sgd(1.0), mode="pjit",
                                       backward_passes_per_step=2)
        params = {"w": jnp.ones((2,))}
        st = opt.init(params)
        g = {"w": jnp.full((2,), 0.5)}
        # first micro-step: no update applied yet
        upd, st = opt.update(g, st, params)
        np.testing.assert_allclose(np.asarray(upd["w"]), 0.0)
        # second: averaged accumulated gradient applied
        upd, st = opt.update(g, st, params)
        np.testing.assert_allclose(np.asarray(upd["w"]), -0.5)

    def test_process_mode_single(self):
        opt = hvd.DistributedOptimizer(optax.sgd(1.0), mode="process")
        params = {"w": jnp.ones((2,))}
        st = opt.init(params)
        upd, st = opt.update({"w": jnp.full((2,), 0.25)}, st, params)
        np.testing.assert_allclose(np.asarray(upd["w"]), -0.25)


class TestGradientTape:
    def test_tape_single_process(self):
        tape = hvd.DistributedGradientTape(jax.grad(loss_fn))
        params = make_params(jax.random.PRNGKey(4))
        grads = tape.gradient(params, make_batch(16))
        ref = jax.grad(loss_fn)(params, make_batch(16))
        for k in ref:
            np.testing.assert_allclose(np.asarray(grads[k]),
                                       np.asarray(ref[k]), rtol=1e-5)


class TestJoinStep:
    def test_ragged_masking(self):
        """Shards 5,6,7 are out of data: average over 5 contributors only
        (reference join zero-filling, controller.cc:263-274)."""
        devs = np.asarray(jax.devices("cpu")[:8]).reshape(2, 4)
        mesh = Mesh(devs, GLOBAL_AXES)

        def f():
            r = C.axis_index(GLOBAL_AXES)
            has_data = r < 5
            grads = {"g": jnp.full((3,), r + 1.0, jnp.float32)}
            out = join_step(grads, has_data)
            return out["g"][None]

        out = np.asarray(jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(), out_specs=P(GLOBAL_AXES),
            check_vma=False))())
        expected = sum(range(1, 6)) / 5.0
        np.testing.assert_allclose(out, expected, rtol=1e-6)


class TestSparseGradientRouting:
    """sparse_params routes embedding-style leaves through the row-sparse
    allgather path (reference IndexedSlices handling,
    ``tensorflow/__init__.py:100-110``); result must match the dense
    reduction exactly."""

    V, D = 32, 4  # embedding table

    def _emb_setup(self):
        rng = np.random.RandomState(3)
        emb = rng.randn(self.V, self.D).astype(np.float32)
        w = rng.randn(self.D, 2).astype(np.float32)
        # per-shard token ids: few unique rows touched per shard
        tokens = rng.randint(0, self.V, (8, 4)).astype(np.int32)
        return emb, w, tokens

    def _grads(self, params, tokens_shard):
        def loss(p):
            h = p["emb"][tokens_shard]          # (4, D) lookup
            return jnp.sum((h @ p["w"]) ** 2)

        return jax.grad(loss)(params)

    def _run(self, sparse_params):
        emb, w, tokens = self._emb_setup()
        tx = hvd.DistributedOptimizer(optax.sgd(1.0), op=C.Average,
                                      axis=GLOBAL_AXES,
                                      sparse_params=sparse_params)

        def f():
            r = C.axis_index(GLOBAL_AXES)
            params = {"emb": jnp.asarray(emb), "w": jnp.asarray(w)}
            g = self._grads(params, jnp.asarray(tokens)[r])
            state = tx.init(params)
            updates, _ = tx.update(g, state, params)
            return updates["emb"][None], updates["w"][None]

        devs = np.asarray(jax.devices("cpu")[:8]).reshape(2, 4)
        ge, gw = jax.jit(jax.shard_map(
            f, mesh=Mesh(devs, GLOBAL_AXES), in_specs=(),
            out_specs=(P(GLOBAL_AXES), P(GLOBAL_AXES)),
            check_vma=False))()
        return np.asarray(ge), np.asarray(gw)

    def test_matches_dense(self):
        # max_rows=4 unique tokens per shard is a tight-but-safe bound
        # (4 lookups/shard); dense leaf "w" stays on the fused path
        se, sw = self._run({"emb": 4})
        de, dw = self._run(None)
        np.testing.assert_allclose(se, de, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(sw, dw, rtol=1e-5, atol=1e-6)

    def test_loose_bound_fill_slots(self):
        # max_rows far above the touched-row count: fill slots must
        # contribute nothing
        se, _ = self._run({"emb": 16})
        de, _ = self._run(None)
        np.testing.assert_allclose(se, de, rtol=1e-5, atol=1e-6)

    def test_train_step_end_to_end(self):
        emb, w, tokens = self._emb_setup()

        def loss_fn_(params, batch):
            h = params["emb"][batch["t"]]
            return jnp.mean((h @ params["w"]) ** 2)

        outs = []
        for sp in ({"emb": 8}, None):
            step = hvd.DistributedTrainStep(
                loss_fn_, optax.sgd(0.1), mode="shard_map",
                sparse_params=sp)
            params, opt_state = step.init(
                {"emb": jnp.asarray(emb), "w": jnp.asarray(w)})
            batch = step.shard_batch({"t": jnp.asarray(tokens)})
            params, opt_state, loss = step(params, opt_state, batch)
            outs.append(jax.tree_util.tree_map(np.asarray, params))
        np.testing.assert_allclose(outs[0]["emb"], outs[1]["emb"],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(outs[0]["w"], outs[1]["w"],
                                   rtol=1e-5, atol=1e-6)

    def test_mode_guards(self):
        with pytest.raises(ValueError, match="shard_map"):
            hvd.DistributedOptimizer(optax.sgd(0.1), mode="pjit",
                                     sparse_params={"emb": 8})
        with pytest.raises(ValueError, match="shard_map"):
            hvd.DistributedTrainStep(lambda p, b: 0.0, optax.sgd(0.1),
                                     mode="pjit", sparse_params={"emb": 8})


class TestInt8WireReduction:
    """Compression.int8 routes the gradient reduction through the
    shared-scale quantized psum (EQuARX-style int8 wire)."""

    def test_grouped_close_to_exact(self):
        rng = np.random.RandomState(5)
        data = rng.randn(8, 64).astype(np.float32)

        def f(quant):
            def inner():
                r = C.axis_index(GLOBAL_AXES)
                xs = [jnp.asarray(data)[r], jnp.asarray(data)[r] * 2.0]
                out = C.grouped_allreduce(
                    xs, op=C.Average,
                    quantized_bits=8 if quant else None)
                return out[0][None], out[1][None]

            devs = np.asarray(jax.devices("cpu")[:8]).reshape(2, 4)
            return jax.jit(jax.shard_map(
                inner, mesh=Mesh(devs, GLOBAL_AXES), in_specs=(),
                out_specs=(P(GLOBAL_AXES), P(GLOBAL_AXES)),
                check_vma=False))()

        q0, q1 = map(np.asarray, f(True))
        e0, e1 = map(np.asarray, f(False))
        # one absmax-scaled rounding of error: |err| <= amax/127 per group
        assert np.max(np.abs(q0 - e0)) <= np.abs(data).max() * 2 * 3 / 127
        assert np.max(np.abs(q1 - e1)) <= np.abs(data).max() * 2 * 3 / 127
        assert np.max(np.abs(q0 - e0)) > 0  # quantization actually engaged

    def test_int_dtype_group_stays_exact(self):
        def inner():
            r = C.axis_index(GLOBAL_AXES)
            xs = [jnp.full((4,), r + 1, jnp.int32)]
            return C.grouped_allreduce(xs, op=C.Sum, quantized_bits=8)[0][None]

        devs = np.asarray(jax.devices("cpu")[:8]).reshape(2, 4)
        out = np.asarray(jax.jit(jax.shard_map(
            inner, mesh=Mesh(devs, GLOBAL_AXES), in_specs=(),
            out_specs=P(GLOBAL_AXES), check_vma=False))())
        np.testing.assert_array_equal(out, sum(range(1, 9)))

    def test_convergence_smoke(self):
        """MNIST-shaped classification to target loss on the 8-device
        mesh with the int8 gradient wire (the knob's end-to-end proof)."""
        rng = np.random.RandomState(0)
        # separable synthetic 10-class problem
        centers = rng.randn(10, 16).astype(np.float32) * 3
        labels = rng.randint(0, 10, 512)
        feats = centers[labels] + rng.randn(512, 16).astype(np.float32) * .3

        def loss_fn(params, batch):
            h = jax.nn.relu(batch["x"] @ params["w1"] + params["b1"])
            logits = h @ params["w2"] + params["b2"]
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["y"]).mean()

        step = hvd.DistributedTrainStep(
            loss_fn, optax.adam(5e-3), mode="shard_map",
            compression=hvd.Compression.int8)
        k = jax.random.PRNGKey(0)
        params, opt_state = step.init({
            "w1": jax.random.normal(k, (16, 32)) * 0.1,
            "b1": jnp.zeros((32,)),
            "w2": jax.random.normal(jax.random.fold_in(k, 1), (32, 10)) * .1,
            "b2": jnp.zeros((10,)),
        })
        first = None
        for i in range(60):
            sl = slice((i * 64) % 448, (i * 64) % 448 + 64)
            batch = step.shard_batch({"x": jnp.asarray(feats[sl]),
                                      "y": jnp.asarray(labels[sl])})
            params, opt_state, loss = step(params, opt_state, batch)
            if first is None:
                first = float(loss)
        assert float(loss) < 0.1 < first, (first, float(loss))

    def test_eager_rejects_marker(self):
        with pytest.raises(ValueError, match="in-jit"):
            hvd.allreduce(jnp.ones((4,)), compression=hvd.Compression.int8)

    def test_per_segment_scales(self):
        """A tiny-magnitude gradient fused next to a large one must keep
        its own quantization scale (not round to zero)."""
        rng = np.random.RandomState(9)
        big = rng.randn(8, 32).astype(np.float32)          # ~1.0 scale
        small = rng.randn(8, 32).astype(np.float32) * 1e-4

        def inner():
            r = C.axis_index(GLOBAL_AXES)
            out = C.grouped_allreduce(
                [jnp.asarray(big)[r], jnp.asarray(small)[r]],
                op=C.Average, quantized_bits=8)
            return out[0][None], out[1][None]

        devs = np.asarray(jax.devices("cpu")[:8]).reshape(2, 4)
        qb, qs = map(np.asarray, jax.jit(jax.shard_map(
            inner, mesh=Mesh(devs, GLOBAL_AXES), in_specs=(),
            out_specs=(P(GLOBAL_AXES), P(GLOBAL_AXES)),
            check_vma=False))())
        exact_small = small.mean(axis=0)
        # with a group-wide scale the small tensor would quantize to all
        # zeros; per-segment scales keep its relative error bounded
        assert np.any(qs != 0)
        np.testing.assert_allclose(qs[0], exact_small,
                                   atol=np.abs(small).max() * 3 / 127)

    def test_sparse_match_is_component_wise(self):
        from horovod_tpu.optim.optimizer import _match_sparse
        import jax.tree_util as jtu

        paths = jtu.tree_flatten_with_path(
            {"member": 1, "emb": 2, "enc": {"emb": 3}})[0]
        by_name = {"/".join(
            str(getattr(e, "key", e)) for e in p): p for p, _ in paths}
        assert _match_sparse(by_name["member"], {"emb": 8}) is None
        assert _match_sparse(by_name["emb"], {"emb": 8}) == 8
        assert _match_sparse(by_name["enc/emb"], {"emb": 8}) == 8
        assert _match_sparse(by_name["enc/emb"], {"enc/emb": 4}) == 4
        assert _match_sparse(by_name["emb"], {"enc/emb": 4}) is None

    def test_op_none_sparse_params_raises(self):
        with pytest.raises(ValueError, match="sparse_params"):
            hvd.DistributedTrainStep(lambda p, b: 0.0, optax.sgd(0.1),
                                     mode="shard_map", op=None,
                                     sparse_params={"emb": 8})


class TestShardedOptimizerStates:
    """shard_optimizer_states=True (reduce-scatter → shard-local update
    → allgather) must produce the same parameters as the allreduce path
    within dtype tolerance — the ZeRO-style decomposition changes the
    schedule and the per-rank memory, never the math (ISSUE 1
    acceptance criterion)."""

    def _train(self, shard, steps=8, bucket_bytes=None, opt=None,
               compression=None):
        step = hvd.DistributedTrainStep(
            loss_fn, opt or optax.adamw(1e-2), mode="shard_map",
            donate=False, shard_optimizer_states=shard,
            compression=compression,
            exchange_bucket_bytes=bucket_bytes if shard else None)
        params, opt_state = step.init(make_params(jax.random.PRNGKey(7)))
        batch = step.shard_batch(make_batch())
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, batch)
        return jax.device_get(params), float(loss)

    def test_matches_allreduce_path(self):
        sharded, loss_s = self._train(True)
        dense, loss_d = self._train(False)
        for k in dense:
            np.testing.assert_allclose(np.asarray(sharded[k]),
                                       np.asarray(dense[k]),
                                       rtol=1e-5, atol=1e-6)
        assert abs(loss_s - loss_d) < 1e-5

    def test_bucketed_exchange_matches(self):
        """Splitting the exchange into reverse-layer-order buckets
        reorders collectives but not values: tiny cap forces one
        bucket per leaf for this 4-leaf MLP."""
        bucketed, _ = self._train(True, bucket_bytes=64)
        dense, _ = self._train(False)
        for k in dense:
            np.testing.assert_allclose(np.asarray(bucketed[k]),
                                       np.asarray(dense[k]),
                                       rtol=1e-5, atol=1e-6)

    def test_sgd_momentum_matches_exactly(self):
        """Momentum state lives sharded; elementwise trace math must
        commute with the shard slicing bit-for-bit-ish."""
        opt = optax.sgd(0.05, momentum=0.9)
        sharded, _ = self._train(True, opt=opt)
        dense, _ = self._train(False, opt=opt)
        for k in dense:
            np.testing.assert_allclose(np.asarray(sharded[k]),
                                       np.asarray(dense[k]),
                                       rtol=1e-6, atol=1e-7)

    def test_int8_wire_close_to_exact(self):
        """Compression.int8 rides the sharded exchange through
        quantized_reducescatter — same shared-scale codec, so the
        error bound matches the allreduce wire's."""
        sharded, loss = self._train(True, steps=3,
                                    compression=hvd.Compression.int8)
        assert np.isfinite(loss)
        dense, _ = self._train(False, steps=3)
        for k in dense:
            # int8 rounding compounds through adam's normalizer; bound
            # the drift absolutely (params are O(0.1)), not relatively
            np.testing.assert_allclose(np.asarray(sharded[k]),
                                       np.asarray(dense[k]), atol=0.02)

    def test_optimizer_factory_matches_allreduce(self):
        """DistributedOptimizer(shard_optimizer_states=True) inside
        shard_map: one update equals the allreduce-then-update path."""
        data = np.linspace(-1, 1, 8 * 12).reshape(8, 12).astype(np.float32)

        def f(shard):
            def inner():
                r = C.axis_index(GLOBAL_AXES)
                tx = hvd.DistributedOptimizer(
                    optax.adam(0.1), shard_optimizer_states=shard)
                params = {"a": jnp.ones((8,)), "b": jnp.zeros((4,))}
                g = {"a": jnp.asarray(data)[r, :8],
                     "b": jnp.asarray(data)[r, 8:]}
                u, _ = tx.update(g, tx.init(params), params)
                return u["a"][None], u["b"][None]

            devs = np.asarray(jax.devices("cpu")[:8]).reshape(2, 4)
            return map(np.asarray, jax.jit(jax.shard_map(
                inner, mesh=Mesh(devs, GLOBAL_AXES), in_specs=(),
                out_specs=(P(GLOBAL_AXES), P(GLOBAL_AXES)),
                check_vma=False))())

        sa, sb = f(True)
        da, db = f(False)
        np.testing.assert_allclose(sa, da, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(sb, db, rtol=1e-5, atol=1e-6)

    def test_validation_guards(self):
        with pytest.raises(ValueError, match="shard_map"):
            hvd.DistributedOptimizer(optax.sgd(0.1), mode="pjit",
                                     shard_optimizer_states=True)
        with pytest.raises(ValueError, match="shard_optimizer_states"):
            hvd.DistributedOptimizer(optax.sgd(0.1),
                                     exchange_bucket_bytes=1 << 20)
        with pytest.raises(ValueError, match="sparse_params"):
            hvd.DistributedOptimizer(optax.sgd(0.1),
                                     shard_optimizer_states=True,
                                     sparse_params={"emb": 8})
        with pytest.raises(ValueError, match="shard_map"):
            hvd.DistributedTrainStep(loss_fn, optax.sgd(0.1),
                                     mode="pjit",
                                     shard_optimizer_states=True)
        with pytest.raises(ValueError, match="shard_optimizer_states"):
            hvd.DistributedTrainStep(loss_fn, optax.sgd(0.1),
                                     mode="shard_map",
                                     exchange_bucket_bytes=1 << 20)


class TestLeafwiseShardedExchange:
    """The plain sharded exchange (one-level topology, no codec, plain
    sum, no explicit bucket size, the tail tiling not asked for by
    name) runs leaf by leaf: every gradient reduce-scattered, updated
    and gathered in its own shape along its first dimension the world
    divides; only leaves no dimension of which it divides ride a packed
    remainder group.  Same parameters as the all-reduce path, a state
    of slabs, and no buffer of the whole model anywhere."""

    W = 8

    @staticmethod
    def _params(key):
        ks = jax.random.split(key, 4)
        return {
            "w1": jax.random.normal(ks[0], (4, 16)) * 0.1,   # cut on dim 1
            "b1": jnp.zeros((16,)),                          # cut on dim 0
            "w2": jax.random.normal(ks[1], (16, 3)) * 0.1,   # cut on dim 0
            "b2": jnp.zeros((3,)),                           # cannot be cut
            "s": jnp.ones(()),                               # nor a scalar
        }

    @staticmethod
    def _loss(params, batch):
        h = jnp.tanh(batch["x"] @ params["w1"] + params["b1"])
        pred = (h @ params["w2"] + params["b2"]).sum(-1, keepdims=True)
        return jnp.mean((params["s"] * pred - batch["y"]) ** 2)

    def _train(self, shard, steps=8, opt=None, params=None, **kw):
        step = hvd.DistributedTrainStep(
            self._loss, opt or optax.adamw(1e-2), mode="shard_map",
            donate=False, shard_optimizer_states=shard, **kw)
        params, opt_state = step.init(
            params or self._params(jax.random.PRNGKey(7)))
        batch = step.shard_batch(make_batch())
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, batch)
        return jax.device_get(params), float(loss), opt_state, step

    def test_mixed_tree_matches_allreduce_path(self):
        """A tree that mixes cuttable leaves (one of them on dimension
        1: its dimension 0 is 4, the world 8) with two that cannot be
        cut lands on the all-reduce path's parameters after 8 AdamW
        steps."""
        sharded, loss_s, _, step = self._train(True, hierarchy="flat")
        dense, loss_d, _, _ = self._train(False)
        assert step.exchange_hierarchy == "flat"
        for k in dense:
            np.testing.assert_allclose(np.asarray(sharded[k]),
                                       np.asarray(dense[k]),
                                       rtol=1e-5, atol=1e-6, err_msg=k)
        assert abs(loss_s - loss_d) < 1e-5

    def test_state_is_slabs_in_leaf_shape(self):
        """m and v of a cuttable leaf hold 1/world of its elements in
        its own rank; an uncuttable leaf leaves an empty placeholder at
        its place and rides the remainder group; nothing in the state
        has the whole model's length."""
        _, _, opt_state, _ = self._train(True, steps=1, hierarchy="flat")
        adam = opt_state.inner[0]
        tree, rest = adam.mu
        shapes = {k: tuple(v.shape) for k, v in tree.items()}
        assert shapes == {"w1": (4, 2), "b1": (2,), "w2": (2, 3),
                          "b2": (0,), "s": (0,)}
        # the remainder: (3,) + () = 4 elements, padded to 8, one a rank
        assert {k: tuple(v.shape) for k, v in rest.items()} == \
            {"b0/float32": (1,)}
        params = self._params(jax.random.PRNGKey(7))
        total = sum(x.size for x in jax.tree_util.tree_leaves(params))
        assert all(x.size <= 64 // self.W for x in
                   jax.tree_util.tree_leaves(opt_state)), total
        assert opt_state.residuals is None

    def test_all_cuttable_state_mirrors_the_params_tree(self):
        """With every leaf cuttable the wrapped optimizer's state is
        its state over the caller's own tree, each leaf its slab — no
        group keys, no remainder."""
        params = {"w": jnp.ones((16, 4)), "b": jnp.ones((8,))}

        def loss(p, batch):
            return jnp.mean((batch["x"] @ p["w"].T[:, :8] * p["b"]
                             - batch["y"]) ** 2)

        step = hvd.DistributedTrainStep(
            loss, optax.adamw(1e-2), mode="shard_map", donate=False,
            shard_optimizer_states=True, hierarchy="flat")
        _, opt_state = step.init(params)
        mu = opt_state.inner[0].mu
        assert {k: tuple(v.shape) for k, v in mu.items()} == \
            {"w": (2, 4), "b": (1,)}

    def test_sgd_momentum_matches_exactly(self):
        opt = optax.sgd(0.05, momentum=0.9)
        sharded, _, _, _ = self._train(True, opt=opt, hierarchy="flat")
        dense, _, _, _ = self._train(False, opt=opt)
        for k in dense:
            np.testing.assert_allclose(np.asarray(sharded[k]),
                                       np.asarray(dense[k]),
                                       rtol=1e-6, atol=1e-7, err_msg=k)

    def test_optimizer_factory_inside_shard_map(self):
        """DistributedOptimizer(shard_optimizer_states=True,
        hierarchy="flat") inside a user's shard_map: the (8,) leaf is
        exchanged in its own shape, the (4,) leaf on 8 devices rides
        the remainder group, and one update equals the
        allreduce-then-update path."""
        data = np.linspace(-1, 1, 8 * 12).reshape(8, 12).astype(np.float32)

        def f(shard):
            def inner():
                r = C.axis_index(GLOBAL_AXES)
                tx = hvd.DistributedOptimizer(
                    optax.adam(0.1), shard_optimizer_states=shard,
                    **({"hierarchy": "flat"} if shard else {}))
                params = {"a": jnp.ones((8,)), "b": jnp.zeros((4,))}
                g = {"a": jnp.asarray(data)[r, :8],
                     "b": jnp.asarray(data)[r, 8:]}
                state = tx.init(params)
                if shard:
                    tree, rest = state.inner[0].mu
                    assert tree["a"].shape == (1,)
                    assert tree["b"].shape == (0,)
                    assert rest["b0/float32"].shape == (1,)
                u, _ = tx.update(g, state, params)
                return u["a"][None], u["b"][None]

            devs = np.asarray(jax.devices("cpu")[:8]).reshape(2, 4)
            return map(np.asarray, jax.jit(jax.shard_map(
                inner, mesh=Mesh(devs, GLOBAL_AXES), in_specs=(),
                out_specs=(P(GLOBAL_AXES), P(GLOBAL_AXES)),
                check_vma=False))())

        sa, sb = f(True)
        da, db = f(False)
        np.testing.assert_allclose(sa, da, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(sb, db, rtol=1e-5, atol=1e-6)

    def test_prescale_and_postscale_ride_the_leaf_exchange(self):
        """gradient_predivide_factor splits the averaging round the
        per-leaf scatter exactly as round the packed one."""
        data = np.linspace(-2, 2, 8 * 16).reshape(8, 16).astype(np.float32)

        def f(**kw):
            def inner():
                r = C.axis_index(GLOBAL_AXES)
                tx = hvd.DistributedOptimizer(optax.sgd(1.0), **kw)
                params = {"a": jnp.zeros((16,))}
                u, _ = tx.update({"a": jnp.asarray(data)[r]},
                                 tx.init(params), params)
                return u["a"][None]

            devs = np.asarray(jax.devices("cpu")[:8]).reshape(2, 4)
            return np.asarray(jax.jit(jax.shard_map(
                inner, mesh=Mesh(devs, GLOBAL_AXES), in_specs=(),
                out_specs=P(GLOBAL_AXES), check_vma=False))())

        plain = f()
        split = f(shard_optimizer_states=True, hierarchy="flat",
                  gradient_predivide_factor=4.0)
        np.testing.assert_allclose(split, plain, rtol=1e-6, atol=1e-7)

    def test_scatter_dimension_rule(self):
        assert C.scatter_dimension((32000, 2048), 4) == 0
        assert C.scatter_dimension((6, 8), 4) == 1
        assert C.scatter_dimension((6, 8), 2) == 0
        assert C.scatter_dimension((3, 5), 2) is None
        assert C.scatter_dimension((), 2) is None
        assert C.scatter_dimension((0, 4), 4) == 1
        assert C.scatter_dimension((7,), 1) == 0


class TestGradientPredivide:
    def test_split_average_matches_plain(self):
        """gradient_predivide_factor splits the averaging across the sum
        (reference torch/optimizer.py:119-123): result identical to the
        plain average up to fp rounding."""
        data = np.linspace(-2, 2, 8 * 6).reshape(8, 6).astype(np.float32)

        def f(factor):
            def inner():
                r = C.axis_index(GLOBAL_AXES)
                tx = hvd.DistributedOptimizer(
                    optax.sgd(1.0), gradient_predivide_factor=factor)
                params = {"p": jnp.zeros(6)}
                u, _ = tx.update({"p": jnp.asarray(data)[r]},
                                 tx.init(params), params)
                return u["p"][None]

            devs = np.asarray(jax.devices("cpu")[:8]).reshape(2, 4)
            return np.asarray(jax.jit(jax.shard_map(
                inner, mesh=Mesh(devs, GLOBAL_AXES), in_specs=(),
                out_specs=P(GLOBAL_AXES), check_vma=False))())

        np.testing.assert_allclose(f(4.0)[0], f(1.0)[0], rtol=1e-5)
        np.testing.assert_allclose(f(1.0)[0], -data.mean(axis=0),
                                   rtol=1e-5)

    def test_guards(self):
        with pytest.raises(ValueError, match="op=Average"):
            hvd.DistributedOptimizer(optax.sgd(1.0), op=C.Sum,
                                     gradient_predivide_factor=2.0)
        with pytest.raises(ValueError, match="not both"):
            hvd.DistributedOptimizer(optax.sgd(1.0),
                                     gradient_predivide_factor=2.0,
                                     prescale_factor=0.5)
