"""Warm-start compile cache: key contract, AOT round-trip, LRU bounds,
and the transparent DistributedTrainStep integration (docs/warmstart.md).
"""

import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.runtime import compile_cache, state as rt_state


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """Isolated cache root, active for both env- and config-resolution,
    with a freshly-initialized runtime."""
    d = str(tmp_path / "cc")
    monkeypatch.setenv("HOROVOD_COMPILE_CACHE_DIR", d)
    hvd.shutdown()
    hvd.init()
    yield d
    hvd.shutdown()


class TestKey:
    def test_deterministic(self):
        k1 = compile_cache.executable_key("module @m {}", {"a": 1})
        k2 = compile_cache.executable_key("module @m {}", {"a": 1})
        assert k1 == k2

    def test_sensitive_to_module_extras_and_options(self):
        base = compile_cache.executable_key("module @m {}", {"a": 1})
        assert compile_cache.executable_key("module @n {}", {"a": 1}) != base
        assert compile_cache.executable_key("module @m {}", {"a": 2}) != base
        assert compile_cache.executable_key(
            "module @m {}", {"a": 1},
            compiler_options={"xla_flag": "true"}) != base


class TestResolveDir:
    def test_env_disable(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_COMPILE_CACHE", "0")
        hvd.shutdown()   # fall back to raw-env resolution
        assert compile_cache.resolve_dir() is None

    def test_config_disable(self, cache_dir):
        cfg = rt_state.global_state().config
        import dataclasses

        off = dataclasses.replace(cfg, compile_cache_enabled=False)
        assert compile_cache.resolve_dir(off) is None

    def test_env_dir_wins(self, cache_dir):
        assert compile_cache.resolve_dir() == cache_dir

    def test_persistent_xla_cache_wired_at_init(self, cache_dir):
        assert rt_state.global_state().compile_cache_dir == cache_dir
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(cache_dir, "xla")


class TestPlacement:
    """Where the cache lives (docs/warmstart.md): JAX's own variable
    first — JAX reads it itself, the code sets nothing — then the
    HOROVOD knob, then a fixed path beside the package; never $HOME."""

    def test_default_is_fixed_and_in_checkout(self, monkeypatch):
        monkeypatch.setenv("HOME", "/nonexistent-home")
        monkeypatch.setenv("XDG_CACHE_HOME", "/nonexistent-xdg")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compile_cache.default_dir() == \
            os.path.join(repo, ".compile_cache")
        assert compile_cache.default_dir() == compile_cache.default_dir()

    def test_jax_variable_places_everything(self, tmp_path, monkeypatch):
        jax_dir = str(tmp_path / "from_outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", jax_dir)
        monkeypatch.setenv("HOROVOD_COMPILE_CACHE_DIR",
                           str(tmp_path / "loses"))
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: updates.append(a))
        hvd.shutdown()
        hvd.init()
        try:
            assert compile_cache.resolve_dir() == jax_dir
            assert rt_state.global_state().compile_cache_dir == jax_dir
            # JAX reads its variable itself: nothing set in code
            assert not [u for u in updates
                        if u[0] == "jax_compilation_cache_dir"], updates
            f = jax.jit(lambda x: x + 2)
            compile_cache.aot_compile(f, (jnp.ones(4),))
            assert os.listdir(os.path.join(jax_dir, "aot"))
            assert not (tmp_path / "loses").exists()
        finally:
            hvd.shutdown()


class TestAotRoundTrip:
    def test_loaded_executable_runs_on_its_own_devices(self, cache_dir):
        """A program compiled for ONE of the eight devices comes back
        onto that device (jax 0.9's deserialize_and_load otherwise
        spreads it over the whole backend and the call fails expecting
        eight shards), and one compiled over the mesh comes back over
        the mesh."""
        dev = jax.devices()[3]
        x = jax.device_put(jnp.arange(8.0), dev)
        f = jax.jit(lambda x: x * 3)
        compile_cache.aot_compile(f, (x,), directory=cache_dir)
        loaded, hit = compile_cache.aot_compile(f, (x,),
                                                directory=cache_dir)
        assert hit is True
        out = loaded(x)
        assert out.devices() == {dev}
        np.testing.assert_allclose(np.asarray(out), np.arange(8.0) * 3)

        # a mesh that is not in jax.devices() order: the assignment
        # order is the mesh's, and a shard must come back where it was
        order = [3, 1, 2, 0, 7, 6, 5, 4]
        mesh = Mesh(np.array(jax.devices())[order], ("x",))
        xs = jax.device_put(jnp.arange(8.0), NamedSharding(mesh, P("x")))
        compile_cache.aot_compile(f, (xs,), directory=cache_dir)
        loaded, hit = compile_cache.aot_compile(f, (xs,),
                                                directory=cache_dir)
        assert hit is True
        out = loaded(xs)
        assert [s.device.id for s in out.addressable_shards] == \
            [s.device.id for s in xs.addressable_shards]
        np.testing.assert_allclose(np.asarray(out), np.arange(8.0) * 3)

    def test_stored_entry_failing_its_first_call_is_replaced(
            self, cache_dir):
        """A stored entry that loads but cannot run must not sink a run
        a cold start would have passed: it is evicted, the lowered
        program compiled fresh, and the call answered."""
        f = jax.jit(lambda x: x * 2 + 1)
        args = (jnp.arange(8, dtype=jnp.float32),)
        compile_cache.aot_compile(f, args, directory=cache_dir)
        loaded, hit = compile_cache.aot_compile(f, args,
                                                directory=cache_dir)
        assert hit is True

        def rejects(*a):
            raise RuntimeError("INVALID_ARGUMENT: expected 8 shards")

        loaded._compiled = rejects
        np.testing.assert_allclose(np.asarray(loaded(*args)),
                                   np.arange(8) * 2 + 1)
        assert compile_cache.entry_count(cache_dir) == 0   # evicted
        np.testing.assert_allclose(np.asarray(loaded(*args)),
                                   np.arange(8) * 2 + 1)   # and stays good

    def test_miss_store_hit(self, cache_dir):
        f = jax.jit(lambda x: x * 2 + 1)
        args = (jnp.arange(8, dtype=jnp.float32),)
        c1, hit1 = compile_cache.aot_compile(f, args, extras={"t": 1},
                                             directory=cache_dir)
        assert hit1 is False
        assert compile_cache.entry_count(cache_dir) == 1
        c2, hit2 = compile_cache.aot_compile(f, args, extras={"t": 1},
                                             directory=cache_dir)
        assert hit2 is True
        np.testing.assert_allclose(np.asarray(c1(*args)),
                                   np.asarray(c2(*args)))

    def test_disabled_compiles_plain(self, cache_dir):
        f = jax.jit(lambda x: x + 1)
        args = (jnp.ones(4),)
        compiled, hit = compile_cache.aot_compile(f, args, directory=None)
        assert hit is False
        assert compile_cache.entry_count(cache_dir) == 0
        np.testing.assert_allclose(np.asarray(compiled(*args)), 2.0)

    def test_stats_counters_flow_to_runtime(self, cache_dir):
        f = jax.jit(lambda x: x - 3)
        args = (jnp.ones(4),)
        before = hvd.cache_stats()
        compile_cache.aot_compile(f, args, directory=cache_dir)
        compile_cache.aot_compile(f, args, directory=cache_dir)
        after = hvd.cache_stats()
        assert after["aot_disk_misses"] == before["aot_disk_misses"] + 1
        assert after["aot_disk_hits"] == before["aot_disk_hits"] + 1

    def test_corrupt_entry_recovers(self, cache_dir):
        f = jax.jit(lambda x: x * 5)
        args = (jnp.ones(4),)
        compile_cache.aot_compile(f, args, directory=cache_dir)
        aot = os.path.join(cache_dir, "aot")
        (entry,) = os.listdir(aot)
        with open(os.path.join(aot, entry), "wb") as fh:
            fh.write(b"not a pickle")
        compiled, hit = compile_cache.aot_compile(f, args,
                                                  directory=cache_dir)
        assert hit is False            # corrupted entry fell back
        np.testing.assert_allclose(np.asarray(compiled(*args)), 5.0)

    def test_incompatible_payload_is_evicted_then_rewritten(
            self, cache_dir):
        f = jax.jit(lambda x: x * 7)
        args = (jnp.ones(4),)
        compile_cache.aot_compile(f, args, directory=cache_dir)
        aot = os.path.join(cache_dir, "aot")
        (entry,) = os.listdir(aot)
        # well-formed pickle, wrong schema — the deserialize raises
        with open(os.path.join(aot, entry), "wb") as fh:
            pickle.dump({"serialized": b"xx", "in_tree": None,
                         "out_tree": None}, fh)
        _, hit = compile_cache.aot_compile(f, args, directory=cache_dir)
        assert hit is False
        _, hit = compile_cache.aot_compile(f, args, directory=cache_dir)
        assert hit is True             # rewritten entry loads again


class TestLruEviction:
    def test_prune_keeps_most_recent(self, cache_dir):
        fns = [jax.jit(lambda x, k=k: x + k) for k in range(4)]
        args = (jnp.ones(4),)
        for f in fns:
            compile_cache.aot_compile(f, args, directory=cache_dir,
                                      capacity=2)
        assert compile_cache.entry_count(cache_dir) == 2
        # the survivors are the two most recently stored
        _, hit = compile_cache.aot_compile(fns[-1], args,
                                           directory=cache_dir, capacity=2)
        assert hit is True
        _, hit = compile_cache.aot_compile(fns[0], args,
                                           directory=cache_dir, capacity=2)
        assert hit is False


def _loss(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _make_step(**kw):
    return hvd.DistributedTrainStep(_loss, optax.adamw(1e-3), **kw)


class TestTrainStepIntegration:
    def _run_once(self, step):
        p, o = step.init({"w": jnp.ones((8, 4))})
        batch = step.shard_batch({"x": jnp.ones((16, 8)),
                                  "y": jnp.zeros((16, 4))})
        return step(p, o, batch)

    def test_cold_then_warm_across_step_objects(self, cache_dir):
        step = _make_step()
        p1, _, l1 = self._run_once(step)
        assert step.compile_cache_hit is False
        assert compile_cache.entry_count(cache_dir) == 1

        step2 = _make_step()
        p2, _, l2 = self._run_once(step2)
        assert step2.compile_cache_hit is True
        assert float(l1) == pytest.approx(float(l2))
        np.testing.assert_allclose(np.asarray(p1["w"]),
                                   np.asarray(p2["w"]))

    def test_sharded_exchange_step_round_trips(self, cache_dir):
        kw = dict(mode="shard_map", shard_optimizer_states=True,
                  exchange_bucket_bytes=1 << 20)
        p1, _, _ = self._run_once(_make_step(**kw))
        step2 = _make_step(**kw)
        p2, _, _ = self._run_once(step2)
        assert step2.compile_cache_hit is True
        np.testing.assert_allclose(np.asarray(p1["w"]),
                                   np.asarray(p2["w"]))

    def test_in_memory_lru_bounded_by_cache_capacity(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("HOROVOD_COMPILE_CACHE_DIR",
                           str(tmp_path / "cc2"))
        monkeypatch.setenv("HOROVOD_CACHE_CAPACITY", "1")
        hvd.shutdown()
        hvd.init()
        try:
            step = _make_step()
            assert step._compiled_cache_max == 1
            p, o = step.init({"w": jnp.ones((8, 4))})
            mk = lambda n: step.shard_batch(    # noqa: E731
                {"x": jnp.ones((n, 8)), "y": jnp.zeros((n, 4))})
            before = hvd.cache_stats()
            p, o, _ = step(p, o, mk(16))
            p, o, _ = step(p, o, mk(24))   # new signature evicts the first
            assert len(step._compiled_cache) == 1
            p, o, _ = step(p, o, mk(24))   # in-memory hit
            after = hvd.cache_stats()
            assert after["misses"] == before["misses"] + 2
            assert after["hits"] == before["hits"] + 1
        finally:
            hvd.shutdown()

    def test_cache_disabled_keeps_plain_jit_path(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("HOROVOD_COMPILE_CACHE", "0")
        hvd.shutdown()
        hvd.init()
        try:
            step = _make_step()
            assert step._persistent_root is None
            self._run_once(step)
            assert step.compile_cache_hit is None
        finally:
            hvd.shutdown()


class TestFusedCollectivesKey:
    """ISSUE 9 satellite: the fused-collectives knob is an AOT-key
    field — a warm start must never serve a fused executable to an
    unfused config (or vice versa)."""

    def test_key_differs_on_fused_field(self):
        base = compile_cache.executable_key(
            "module @m {}", {"fused_collectives": "off"})
        assert compile_cache.executable_key(
            "module @m {}", {"fused_collectives": "on"}) != base

    def test_step_extras_carry_resolved_mode(self, cache_dir):
        import optax

        def loss_fn(params, batch):
            return jnp.sum((batch @ params) ** 2)

        def build(fused):
            return hvd.DistributedTrainStep(
                loss_fn, optax.sgd(0.1), mode="shard_map",
                shard_optimizer_states=True, hierarchy="flat",
                fused_collectives=fused)

        on, off = build("on"), build("off")
        assert on._aot_extras()["fused_collectives"] == "on"
        assert off._aot_extras()["fused_collectives"] == "off"
        # "auto" resolves off on this CPU twin and keys like "off"
        auto = build("auto")
        assert auto._aot_extras()["fused_collectives"] == "off"
        k_on = compile_cache.executable_key("module @m {}",
                                            on._aot_extras())
        k_off = compile_cache.executable_key("module @m {}",
                                             off._aot_extras())
        k_auto = compile_cache.executable_key("module @m {}",
                                              auto._aot_extras())
        assert k_on != k_off
        assert k_auto == k_off


class TestPlanKey:
    """ISSUE 13 tentpole pin: the sharding plan is an AOT-key field —
    a plan change is an executable-cache miss, so a warm start never
    serves a program compiled for a different parallelism layout."""

    def test_key_differs_on_plan_field(self):
        base = compile_cache.executable_key("module @m {}",
                                            {"plan": "dp=8"})
        assert compile_cache.executable_key(
            "module @m {}", {"plan": "dp=4,fsdp=2"}) != base
        assert compile_cache.executable_key(
            "module @m {}", {"plan": None}) != base

    def test_step_extras_carry_canonical_plan(self, cache_dir):
        step = _make_step(mode="shard_map", plan="dp=8")
        assert step._aot_extras()["plan"] == "dp=8"
        bare = _make_step()
        assert bare._aot_extras()["plan"] is None
        k_plan = compile_cache.executable_key("module @m {}",
                                              step._aot_extras())
        k_bare = compile_cache.executable_key("module @m {}",
                                              bare._aot_extras())
        assert k_plan != k_bare

    def test_error_feedback_is_a_key_field(self, cache_dir):
        """The EF satellite rides the same contract: a residual-
        carrying executable must not serve an uncompensated config."""
        def build(ef):
            return hvd.DistributedTrainStep(
                _loss, optax.sgd(0.1), mode="shard_map",
                shard_optimizer_states=True,
                compression=hvd.Compression.int8, error_feedback=ef)

        on, off = build(True), build(False)
        assert on._aot_extras()["error_feedback"] is True
        assert compile_cache.executable_key(
            "module @m {}", on._aot_extras()) != \
            compile_cache.executable_key("module @m {}",
                                         off._aot_extras())


class TestReductionKey:
    """ISSUE 19: the exchange's reduction operator is an AOT-key
    field — an adasum program runs a different outer-level schedule
    (pairwise doubling + psum'd dot/norm scalars), so a warm start
    must never serve it to a plain-sum config or vice versa."""

    def test_key_differs_on_reduction_field(self):
        base = compile_cache.executable_key("module @m {}",
                                            {"reduction": "sum"})
        assert compile_cache.executable_key(
            "module @m {}", {"reduction": "adasum"}) != base
        assert compile_cache.executable_key(
            "module @m {}", {"reduction": None}) != base

    def test_step_extras_carry_resolved_reduction(self, cache_dir):
        step = _make_step(mode="shard_map",
                          shard_optimizer_states=True,
                          reduction="adasum")
        assert step._aot_extras()["reduction"] == "adasum"
        plain = _make_step(mode="shard_map",
                           shard_optimizer_states=True)
        assert plain._aot_extras()["reduction"] == "sum"
        assert compile_cache.executable_key(
            "module @m {}", step._aot_extras()) != \
            compile_cache.executable_key("module @m {}",
                                         plain._aot_extras())
        # no sharded exchange → the knob has nothing to steer
        bare = _make_step()
        assert bare._aot_extras()["reduction"] is None

    def test_env_knob_reaches_the_key(self, cache_dir, monkeypatch):
        monkeypatch.setenv("HOROVOD_EXCHANGE_REDUCTION", "adasum")
        step = _make_step(mode="shard_map",
                          shard_optimizer_states=True)
        assert step._aot_extras()["reduction"] == "adasum"

    def test_replicated_path_rejects_the_knob(self, cache_dir):
        with pytest.raises(ValueError, match="shard_optimizer_states"):
            _make_step(mode="shard_map", reduction="adasum")


class TestMoeRoutingKey:
    """ISSUE 16: the MoE dispatch schedule and capacity factor are
    AOT-key fields — a warm start must never serve a fused-ring
    executable (or a different capacity bucketing) to a config that
    asked for the unfused all_to_all formulation."""

    def test_key_differs_on_moe_fields(self):
        base = compile_cache.executable_key(
            "module @m {}",
            {"moe_fused": None, "moe_capacity_factor": None})
        assert compile_cache.executable_key(
            "module @m {}",
            {"moe_fused": "on", "moe_capacity_factor": None}) != base
        assert compile_cache.executable_key(
            "module @m {}",
            {"moe_fused": None, "moe_capacity_factor": 1.5}) != base

    def test_step_extras_carry_resolved_dispatch(self, cache_dir):
        step = _make_step(mode="shard_map", moe_fused="on",
                          moe_capacity_factor=1.5)
        ex = step._aot_extras()
        assert ex["moe_fused"] == "on"
        assert ex["moe_capacity_factor"] == 1.5
        bare = _make_step(mode="shard_map")
        assert bare._aot_extras()["moe_fused"] is None
        assert bare._aot_extras()["moe_capacity_factor"] is None
        assert compile_cache.executable_key(
            "module @m {}", ex) != compile_cache.executable_key(
            "module @m {}", bare._aot_extras())
        # "auto" resolves through resolve_fused_collectives — off on
        # this CPU twin, so it keys like an explicit "off"
        auto = _make_step(mode="shard_map", moe_fused="auto")
        assert auto._aot_extras()["moe_fused"] == "off"

    def test_env_knobs_reach_the_key(self, cache_dir, monkeypatch):
        monkeypatch.setenv("HOROVOD_MOE_FUSED_DISPATCH", "on")
        monkeypatch.setenv("HOROVOD_MOE_CAPACITY_FACTOR", "2.0")
        step = _make_step(mode="shard_map")
        ex = step._aot_extras()
        assert ex["moe_fused"] == "on"
        assert ex["moe_capacity_factor"] == 2.0
