"""Warm start through one cache — JAX's persistent compilation cache,
placed by runtime/compile_cache.py — and the one way from a
DistributedTrainStep to an executable (docs/warmstart.md).
"""

import json
import os
import subprocess
import sys
import time
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import horovod_tpu as hvd
from horovod_tpu.runtime import compile_cache, state as rt_state
from horovod_tpu.telemetry import spans

REPO = Path(__file__).resolve().parent.parent


def _files(root) -> set:
    return {os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs}


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

    def test_jax_cache_placed_at_init(self, cache_dir):
        assert rt_state.global_state().compile_cache_dir == cache_dir
        assert jax.config.jax_compilation_cache_dir == cache_dir
        assert compile_cache.active()


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
            # JAX reads its variable itself: nothing set in code, no
            # directory made
            assert not [u for u in updates
                        if u[0] == "jax_compilation_cache_dir"], updates
            assert not (tmp_path / "loses").exists()
        finally:
            hvd.shutdown()


def _loss(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _make_step(**kw):
    return hvd.DistributedTrainStep(_loss, optax.adamw(1e-3), **kw)


def _run_once(step):
    p, o = step.init({"w": jnp.ones((8, 4))})
    batch = step.shard_batch({"x": jnp.ones((16, 8)),
                              "y": jnp.zeros((16, 4))})
    return step(p, o, batch)


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[3:4]).reshape(1, 1), ("dcn", "ici"))


ZERO = dict(mode="shard_map", shard_optimizer_states=True)


class TestTrainStepWarmStart:
    @pytest.mark.parametrize("kw", [
        pytest.param({}, id="replicated"),
        pytest.param(dict(ZERO, exchange_bucket_bytes=1 << 20),
                     id="sharded_exchange")])
    def test_cold_then_warm_across_step_objects(self, cache_dir, kw):
        step = _make_step(**kw)
        assert step.compile_cache_hit is None        # not compiled yet
        t0 = time.perf_counter()
        p1, _, l1 = _run_once(step)
        assert step.compile_cache_hit is False
        written = _files(cache_dir)
        assert written

        step2 = _make_step(**kw)
        p2, _, l2 = _run_once(step2)
        assert step2.compile_cache_hit is True
        assert [s.attrs["hit"] for s in spans.snapshot(since=t0)
                if s.name == "train_step.compile"] == [False, True]
        assert _files(cache_dir) == written          # a hit writes nothing
        assert float(l1) == float(l2)
        np.testing.assert_array_equal(np.asarray(p1["w"]),
                                      np.asarray(p2["w"]))

    def test_cold_then_warm_across_processes(self, tmp_path):
        """The same program in two processes, one cache directory: the
        second is served the first's executable."""
        script = (
            "import json, jax, jax.numpy as jnp, optax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "jax.config.update("
            "'jax_persistent_cache_min_compile_time_secs', 0)\n"
            "import horovod_tpu as hvd\n"
            "hvd.init()\n"
            "step = hvd.DistributedTrainStep(\n"
            "    lambda p, b: jnp.mean((b['x'] @ p['w'] - b['y']) ** 2),\n"
            "    optax.adamw(1e-3))\n"
            "p, o = step.init({'w': jnp.ones((8, 4))})\n"
            "b = step.shard_batch({'x': jnp.ones((16, 8)),\n"
            "                      'y': jnp.zeros((16, 4))})\n"
            "loss = step(p, o, b)[2]\n"
            "print(json.dumps({'hit': step.compile_cache_hit,\n"
            "                  'loss': float(loss)}))\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   HOROVOD_COMPILE_CACHE_DIR=str(tmp_path / "cc"),
                   PYTHONPATH=str(REPO))
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        said = []
        for _ in range(2):
            proc = subprocess.run([sys.executable, "-c", script], env=env,
                                  capture_output=True, text=True,
                                  timeout=300)
            assert proc.returncode == 0, proc.stderr[-3000:]
            said.append(json.loads(proc.stdout.splitlines()[-1]))
        assert [s["hit"] for s in said] == [False, True]
        assert said[0]["loss"] == said[1]["loss"]

    def test_one_device_step_served_warm_runs_on_its_own_device(
            self, cache_dir):
        """A step compiled for ONE of the eight devices, served from
        the cache, runs on that device (a loaded executable spread over
        the whole backend fails at the call expecting eight shards)."""
        dev = jax.devices()[3]
        _run_once(_make_step(mesh=_one_device_mesh()))
        step = _make_step(mesh=_one_device_mesh())
        params, _, loss = _run_once(step)
        assert step.compile_cache_hit is True
        assert params["w"].devices() == loss.devices() == {dev}

    def test_truncated_entry_degrades_to_a_compile(self, cache_dir):
        _, _, cold = _run_once(_make_step())
        for path in _files(cache_dir):
            with open(path, "r+b") as f:
                f.truncate(os.path.getsize(path) // 2)
        step = _make_step()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            _, _, loss = _run_once(step)
        assert step.compile_cache_hit is False
        assert any("persistent compilation cache" in str(w.message)
                   for w in caught), [str(w.message) for w in caught]
        assert float(loss) == float(cold)

    def test_compiled_text_is_the_running_executables(self, cache_dir):
        """One way to an executable: the text after a call compiles
        nothing and lowers nothing — it is what ran."""
        step = _make_step()
        p, o = step.init({"w": jnp.ones((8, 4))})
        batch = step.shard_batch({"x": jnp.ones((16, 8)),
                                  "y": jnp.zeros((16, 4))})
        t0 = time.perf_counter()
        p, o, _ = step(p, o, batch)
        text = step.compiled_text(p, o, batch)
        names = [s.name for s in spans.snapshot(since=t0)]
        assert names.count("train_step.lower") == 1
        assert names.count("train_step.compile") == 1
        (running,) = step._compiled_cache.values()
        assert text == running.as_text()
        assert "all-reduce" in text

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
            assert after == {"hits": before["hits"] + 1,
                             "misses": before["misses"] + 2}
        finally:
            hvd.shutdown()

    def test_cache_disabled_is_the_same_one_path(
            self, cache_dir, monkeypatch):
        monkeypatch.setenv("HOROVOD_COMPILE_CACHE", "0")
        hvd.shutdown()
        hvd.init()
        assert rt_state.global_state().compile_cache_dir is None
        assert not compile_cache.active()
        step = _make_step()
        t0 = time.perf_counter()
        _run_once(step)
        assert step.compile_cache_hit is None
        assert len(step._compiled_cache) == 1
        assert [s.attrs["hit"] for s in spans.snapshot(since=t0)
                if s.name == "train_step.compile"] == [False]
        assert not _files(cache_dir)


# ---------------------------------------------------------------------------
# What the store's key fields only declared: two values of a knob are two
# programs (or two option sets), so JAX's key cannot serve one to the other.
# ---------------------------------------------------------------------------

def _mlp_loss(params, batch):
    h = jnp.tanh(batch["x"] @ params["w"] + params["b"])
    return jnp.mean((h - batch["y"]) ** 2)


def _elementwise_loss(params, batch):
    return jnp.mean((batch["x"] * params["w"] - batch["y"]) ** 2)


# (loss, optimizer, params, batch); the batch is (rows, tokens, features)
MODELS = {
    "mlp": (_mlp_loss, optax.adamw(1e-3),
            {"w": (8, 4), "b": (4,)}, {"x": (16, 2, 8), "y": (16, 2, 4)}),
    # a batch leaf shaped as the one output left to alias, on one device:
    # elsewhere a donated batch aliases nothing and changes nothing
    "elementwise": (_elementwise_loss, optax.sgd(1e-3),
                    {"w": (16, 4)}, {"x": (16, 4), "y": (16, 4)}),
}


class _Guard:
    def current_limit(self):
        return 1e9

    def observe(self, norm, limit):
        pass


def _program(model, **kw):
    """What decides JAX's cache key for a step built with ``kw``: the
    lowered module and the compile options."""
    loss, optimizer, params, batch = MODELS[model]
    step = hvd.DistributedTrainStep(loss, optimizer, **kw)
    p, o = step.init({k: jnp.ones(v) for k, v in params.items()})
    args = (p, o, step.shard_batch({k: jnp.ones(v)
                                    for k, v in batch.items()}))
    if kw.get("guard") is not None:
        args += (np.float32(1e9),)
    with step._ambient_mesh():
        return step._step.lower(*args).as_text(), step._compiler_options


@pytest.mark.parametrize("base, knob, one, other, model", [
    pytest.param(dict(ZERO, hierarchy="flat"), "fused_collectives",
                 "off", "on", "mlp", id="fused_collectives"),
    pytest.param(dict(ZERO, compression=hvd.Compression.int8),
                 "error_feedback", False, True, "mlp", id="error_feedback"),
    pytest.param(ZERO, "reduction", "sum", "adasum", "mlp", id="reduction"),
    pytest.param(ZERO, "hierarchy", "flat", "two_level", "mlp",
                 id="hierarchy"),
    pytest.param(ZERO, "exchange_bucket_bytes", None, 64, "mlp",
                 id="exchange_bucket_bytes"),
    pytest.param({}, "remat", False, "dots", "mlp", id="remat"),
    pytest.param(dict(mode="shard_map"), "plan", "dp=8", "dp=4,sp=2", "mlp",
                 id="sp"),
    pytest.param({}, "donate", True, False, "mlp", id="donate"),
    pytest.param(lambda: dict(donate=False, mesh=_one_device_mesh()),
                 "donate_batch", False, True, "elementwise",
                 id="donate_batch"),
    pytest.param({}, "guard", None, _Guard(), "mlp", id="guard"),
    pytest.param({}, "steps_per_call", 1, 2, "mlp", id="steps_per_call"),
    pytest.param({}, "mode", "pjit", "shard_map", "mlp", id="mode"),
    pytest.param({}, "plan", "dp=8", "dp=4,fsdp=2", "mlp", id="plan"),
    pytest.param({}, "fsdp_axis", None, "ici", "mlp", id="fsdp_axis"),
    pytest.param(dict(mode="shard_map"), "compression", None,
                 hvd.Compression.int8, "mlp", id="compression"),
    pytest.param(dict(mode="shard_map"), "op", hvd.Sum, hvd.Average, "mlp",
                 id="op"),
    pytest.param({}, "compiler_options", None,
                 {"xla_embed_ir_in_executable": "true"}, "mlp",
                 id="compiler_options"),
])
def test_a_knob_is_another_program(hvd_runtime, base, knob, one, other,
                                   model):
    base = base() if callable(base) else base
    first = _program(model, **base, **{knob: one})
    # the same knobs twice are one program: what differs below is the
    # knob's doing, not a name or an address in the text
    assert _program(model, **base, **{knob: one}) == first
    assert _program(model, **base, **{knob: other}) != first


class TestResolvedKnobs:
    """What a step resolved its knobs to, on its public properties."""

    def test_fused_collectives_resolved_mode(self, hvd_runtime):
        def build(fused):
            return _make_step(**ZERO, hierarchy="flat",
                              fused_collectives=fused)

        assert build("on").fused_collectives == "on"
        assert build("off").fused_collectives == "off"
        # "auto" resolves off on this CPU twin
        assert build("auto").fused_collectives == "off"

    def test_canonical_plan(self, hvd_runtime):
        step = _make_step(mode="shard_map", plan="dp=8")
        assert step.plan.to_string() == "dp=8"
        assert _make_step().plan is None

    def test_resolved_reduction(self, hvd_runtime):
        assert _make_step(**ZERO, reduction="adasum").reduction == "adasum"
        assert _make_step(**ZERO).reduction == "sum"
        # no sharded exchange → the knob has nothing to steer
        assert _make_step().reduction is None

    def test_reduction_env_knob_reaches_the_step(self, hvd_runtime,
                                                 monkeypatch):
        monkeypatch.setenv("HOROVOD_EXCHANGE_REDUCTION", "adasum")
        assert _make_step(**ZERO).reduction == "adasum"

    def test_replicated_path_rejects_the_reduction_knob(self, hvd_runtime):
        with pytest.raises(ValueError, match="shard_optimizer_states"):
            _make_step(mode="shard_map", reduction="adasum")
