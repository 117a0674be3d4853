"""The sharding-plan compiler: one ShardingPlan drives mesh layout,
batch sharding, the gradient exchange, FSDP placement and checkpoint
resharding (docs/parallelism.md).

Acceptance pins: a DP×TP plan-compiled step is bit-for-bit the step
built from the equivalent explicit GSPMD mesh; a plan-scoped dp×fsdp
sharded exchange matches the hand-axed baseline; checkpoint restore
reshards across data-extent plan changes and refuses model-extent
ones."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel import ShardingPlan, as_plan, make_parallel_mesh
from horovod_tpu.runtime import state as rt_state


@pytest.fixture(autouse=True)
def runtime():
    hvd.init()
    yield
    hvd.shutdown()


class TestPlanGrammar:
    def test_parse_resolve_round_trip(self):
        plan = ShardingPlan.from_string("dp=4,tp=2")
        assert (plan.dp, plan.tp, plan.pp) == (4, 2, 1)
        assert plan.to_string() == "dp=4,tp=2"
        assert ShardingPlan.from_string(plan.to_string()) == plan

    def test_dp_inferred_on_resolve(self):
        plan = ShardingPlan.from_string("tp=2,fsdp=2")
        assert plan.dp is None
        resolved = plan.resolve(8)
        assert resolved.dp == 2 and resolved.total == 8

    def test_canonical_order_and_v(self):
        plan = ShardingPlan.from_string("v=2,pp=2,tp=2,dp=2")
        assert plan.to_string() == "dp=2,pp=2,tp=2,v=2"

    def test_unresolved_to_string(self):
        plan = ShardingPlan.from_string("tp=2")
        with pytest.raises(ValueError, match="resolve"):
            plan.to_string()
        assert plan.to_string(allow_unresolved=True) == "dp=?,tp=2"

    def test_axis_split(self):
        plan = ShardingPlan(dp=2, fsdp=2, tp=2)
        assert plan.data_axes == ("dp", "fsdp")
        assert plan.model_axes == ("tp",)
        # fully model-parallel: exchange rides a size-1 dp axis
        assert ShardingPlan(dp=1, tp=8).data_axes == ("dp",)

    def test_grammar_errors(self):
        with pytest.raises(ValueError, match="bad plan term"):
            ShardingPlan.from_string("dp:4")
        with pytest.raises(ValueError, match="bad plan term"):
            ShardingPlan.from_string("zz=2")
        with pytest.raises(ValueError, match="duplicate"):
            ShardingPlan.from_string("dp=2,dp=4")
        with pytest.raises(ValueError, match="positive"):
            ShardingPlan.from_string("tp=0")
        with pytest.raises(ValueError, match="positive"):
            ShardingPlan.from_string("dp=two")
        with pytest.raises(ValueError, match="empty plan"):
            ShardingPlan.from_string("  ")
        with pytest.raises(ValueError, match="pp=1"):
            ShardingPlan.from_string("dp=4,v=2")
        with pytest.raises(ValueError, match="covers"):
            ShardingPlan.from_string("dp=3").resolve(8)
        with pytest.raises(ValueError, match="divisible"):
            ShardingPlan.from_string("tp=3").resolve(8)

    def test_from_env(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_PLAN", raising=False)
        assert ShardingPlan.from_env() is None
        monkeypatch.setenv("HOROVOD_PLAN", "dp=2,fsdp=4")
        assert ShardingPlan.from_env() == ShardingPlan(dp=2, fsdp=4)

    def test_as_plan_coercion(self):
        plan = ShardingPlan(dp=8)
        assert as_plan(plan) is plan
        assert as_plan("dp=8") == plan
        assert as_plan(None) is None
        with pytest.raises(TypeError, match="ShardingPlan"):
            as_plan(8)


class TestPlanMesh:
    def test_build_mesh_carries_extents(self):
        plan = ShardingPlan.from_string("dp=2,tp=4").resolve(8)
        mesh = plan.build_mesh(devices=jax.devices("cpu")[:8])
        assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
        assert mesh.shape["fsdp"] == 1 and mesh.size == 8
        assert plan.matches_mesh(mesh)

    def test_matches_mesh_rejects_other_factorization(self):
        plan = ShardingPlan.from_string("dp=2,tp=4").resolve(8)
        other = make_parallel_mesh(tp=8, devices=jax.devices("cpu")[:8])
        assert not plan.matches_mesh(other)


def _tp_loss(model):
    def loss_fn(params, batch):
        pred = model.apply(params, batch["x"])
        return jnp.mean((pred - batch["y"]) ** 2)
    return loss_fn


class TestPlanTrainStep:
    """One plan drives the step: mesh, batch sharding, exchange scope,
    and FSDP placement."""

    def _tp_model(self):
        import flax.linen as nn

        from horovod_tpu.parallel import (
            ColumnParallelDense,
            RowParallelDense,
        )

        class TpMlp(nn.Module):
            @nn.compact
            def __call__(self, x):
                h = ColumnParallelDense(64, axis="tp")(x)
                h = nn.gelu(h)
                return RowParallelDense(32, axis="tp")(h)

        return TpMlp()

    def _data(self):
        rng = np.random.RandomState(0)
        return {"x": jnp.asarray(rng.randn(16, 32), jnp.float32),
                "y": jnp.asarray(rng.randn(16, 32), jnp.float32)}

    def test_dp_tp_plan_bit_identical_to_explicit_gspmd(self):
        """The tentpole pin: DistributedTrainStep(plan="dp=2,tp=4")
        compiles the SAME program as the hand-assembled GSPMD path
        (explicit make_parallel_mesh + data_axes) — parameters match
        bit for bit after training, and so do the logits."""
        model = self._tp_model()
        loss_fn = _tp_loss(model)
        batch = self._data()
        variables = model.init(jax.random.PRNGKey(1), batch["x"])

        def train(**kw):
            step = hvd.DistributedTrainStep(
                loss_fn, optax.adam(1e-2), mode="pjit", donate=False,
                **kw)
            params, opt_state = step.init(variables)
            b = step.shard_batch(batch)
            for _ in range(3):
                params, opt_state, loss = step(params, opt_state, b)
            logits = model.apply(jax.device_get(params), batch["x"])
            return jax.device_get(params), np.asarray(logits), float(loss)

        p_plan, logits_plan, l_plan = train(plan="dp=2,tp=4")
        p_ref, logits_ref, l_ref = train(
            mesh=make_parallel_mesh(dp=2, tp=4,
                                    devices=jax.devices("cpu")[:8]),
            data_axes=("dp",))
        flat_plan = jax.tree_util.tree_leaves(p_plan)
        flat_ref = jax.tree_util.tree_leaves(p_ref)
        for a, b in zip(flat_plan, flat_ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(logits_plan, logits_ref)
        assert l_plan == l_ref

    def test_plan_step_records_resolved_plan(self):
        model = self._tp_model()
        step = hvd.DistributedTrainStep(
            _tp_loss(model), optax.adam(1e-2), mode="pjit",
            plan="tp=4")           # dp inferred from the device count
        assert step.plan.to_string() == "dp=2,tp=4"
        assert step._mesh.shape["tp"] == 4

    def test_plan_fsdp_extent_turns_on_placement(self):
        """fsdp>1 under pjit auto-sets fsdp_axis="fsdp": parameters
        live sharded, and the trajectory matches the replicated step
        (FSDP is a placement change, not an algorithm change)."""
        def loss_fn(params, batch):
            h = jax.nn.relu(batch["x"] @ params["w1"])
            return jnp.mean((h @ params["w2"] - batch["y"]) ** 2)

        rng = np.random.RandomState(0)
        w = {"w1": jnp.asarray(rng.randn(64, 256) * 0.05, jnp.float32),
             "w2": jnp.asarray(rng.randn(256, 8) * 0.05, jnp.float32)}
        batch = {"x": jnp.asarray(rng.randn(32, 64), jnp.float32),
                 "y": jnp.asarray(rng.randn(32, 8), jnp.float32)}

        def train(**kw):
            step = hvd.DistributedTrainStep(
                loss_fn, optax.adam(1e-2), mode="pjit", donate=False,
                **kw)
            params, opt_state = step.init(dict(w))
            if kw.get("plan"):
                assert step._fsdp_axis == "fsdp"
                assert params["w1"].sharding.spec == P(None, "fsdp")
            b = step.shard_batch(batch)
            for _ in range(3):
                params, opt_state, _ = step(params, opt_state, b)
            return jax.device_get(params)

        sharded = train(plan="dp=2,fsdp=4", fsdp_min_weight_size=1)
        repl = train()
        for k in repl:
            np.testing.assert_allclose(np.asarray(sharded[k]),
                                       np.asarray(repl[k]),
                                       rtol=2e-5, atol=1e-6)

    def test_plan_scoped_sharded_exchange_matches_baseline(self):
        """shard_map + shard_optimizer_states under a dp×fsdp plan:
        the ZeRO exchange runs over the plan's data axes and lands on
        the same parameters as the GLOBAL_AXES baseline."""
        def loss_fn(params, batch):
            pred = jnp.tanh(batch["x"] @ params["w"]) @ params["v"]
            return jnp.mean((pred - batch["y"]) ** 2)

        rng = np.random.RandomState(1)
        w = {"w": jnp.asarray(rng.randn(4, 16) * 0.1, jnp.float32),
             "v": jnp.asarray(rng.randn(16, 1) * 0.1, jnp.float32)}
        batch = {"x": jnp.asarray(rng.randn(64, 4), jnp.float32),
                 "y": jnp.asarray(rng.randn(64, 1), jnp.float32)}

        def train(**kw):
            step = hvd.DistributedTrainStep(
                loss_fn, optax.adamw(1e-2), mode="shard_map",
                donate=False, shard_optimizer_states=True, **kw)
            params, opt_state = step.init(dict(w))
            b = step.shard_batch(batch)
            for _ in range(6):
                params, opt_state, loss = step(params, opt_state, b)
            return jax.device_get(params), float(loss), step

        planned, loss_p, step = train(plan="dp=2,fsdp=4")
        assert step.plan.data_axes == ("dp", "fsdp")
        # auto hierarchy resolves two_level on the (2, 4) data extents
        assert step.exchange_hierarchy == "two_level"
        base, loss_b, _ = train()          # GLOBAL_AXES on runtime mesh
        for k in base:
            np.testing.assert_allclose(np.asarray(planned[k]),
                                       np.asarray(base[k]),
                                       rtol=1e-5, atol=1e-6)
        assert abs(loss_p - loss_b) < 1e-5

    def test_plan_rejections(self):
        loss = lambda p, b: 0.0                      # noqa: E731
        with pytest.raises(ValueError, match="pp>1"):
            hvd.DistributedTrainStep(loss, optax.sgd(0.1), mode="pjit",
                                     plan="dp=4,pp=2")
        with pytest.raises(ValueError, match="model axes"):
            hvd.DistributedTrainStep(loss, optax.sgd(0.1),
                                     mode="shard_map", plan="dp=4,tp=2")
        with pytest.raises(ValueError, match="does not match"):
            hvd.DistributedTrainStep(
                loss, optax.sgd(0.1), mode="pjit", plan="dp=8",
                mesh=make_parallel_mesh(tp=8,
                                        devices=jax.devices("cpu")[:8]))
        with pytest.raises(ValueError, match="conflicts with plan"):
            hvd.DistributedTrainStep(loss, optax.sgd(0.1), mode="pjit",
                                     plan="dp=2,fsdp=4",
                                     data_axes=("dp",))

    def test_config_plan_fallback(self):
        """HOROVOD_PLAN reaches the step through the runtime config
        when no explicit plan is passed."""
        cfg = rt_state.global_state().config
        old = cfg.plan
        cfg.plan = "dp=8"
        try:
            step = hvd.DistributedTrainStep(
                lambda p, b: jnp.sum(p["w"] ** 2), optax.sgd(0.1),
                mode="shard_map")
            assert step.plan is not None
            assert step.plan.to_string() == "dp=8"
        finally:
            cfg.plan = old


class TestPlanCheckpoint:
    """Plan-aware sharded save/restore: data-extent changes reshard,
    model-extent changes refuse (docs/parallelism.md)."""

    def _save(self, tmp_path, world=8, plan="dp=8"):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        full = np.arange(world * 3, dtype=np.float32)
        for r in range(world):
            ckpt.save_sharded(0, {"m": full[r * 3:(r + 1) * 3]}, r,
                              world, plan=plan)
            ckpt.wait()
        return ckpt, full

    def test_data_extent_change_reshards(self, tmp_path):
        ckpt, full = self._save(tmp_path, plan="dp=8")
        # same shard count, different dp×fsdp split: plain round trip
        out = ckpt.restore_sharded({"m": np.zeros(3, np.float32)}, 1, 8,
                                   plan="dp=4,fsdp=2")
        np.testing.assert_array_equal(out["m"], full[3:6])
        # smaller data extent: reshards like a world-size change
        out = ckpt.restore_sharded({"m": np.zeros(6, np.float32)}, 0, 4,
                                   plan="dp=2,fsdp=2")
        np.testing.assert_array_equal(out["m"], full[:6])

    def test_model_extent_change_refuses(self, tmp_path):
        ckpt, _ = self._save(tmp_path, plan="dp=8")
        with pytest.raises(ValueError, match="model-parallel extents"):
            ckpt.restore_sharded({"m": np.zeros(6, np.float32)}, 0, 4,
                                 plan="dp=4,tp=2")

    def test_plan_shard_count_consistency(self, tmp_path):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        with pytest.raises(ValueError, match="shard_count"):
            ckpt.save_sharded(0, {"m": np.ones(3, np.float32)}, 0, 8,
                              plan="dp=4")

    def test_legacy_and_planless_restores_pass(self, tmp_path):
        # plan recorded at save, none given at restore — and vice versa
        ckpt, full = self._save(tmp_path, plan="dp=8")
        out = ckpt.restore_sharded({"m": np.zeros(3, np.float32)}, 0, 8)
        np.testing.assert_array_equal(out["m"], full[:3])
        ckpt2, full2 = self._save(tmp_path / "b", plan=None)
        out = ckpt2.restore_sharded({"m": np.zeros(3, np.float32)}, 2, 8,
                                    plan="dp=8")
        np.testing.assert_array_equal(out["m"], full2[6:9])
