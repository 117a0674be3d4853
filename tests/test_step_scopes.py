"""The scopes the program lays inside the compiled step
(docs/metrics.md "Scopes inside the compiled step"): ``loss_fn``,
``exchange`` (``exchange/scatter``, ``exchange/gather``), ``guard`` and
``update`` from ``DistributedTrainStep`` and the optimizers, ``head`` and
``loss`` from the models — in the compiled text where the step's mode
enters them and not where it does not, on the compile span as
``step_scopes``, and nowhere in the lowered program itself: a scope is
metadata, so the text without locations is the same byte for byte with
``jax.named_scope`` made a no-op.  CPU, the 8-device virtual mesh."""

import contextlib
import functools
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import phases  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu import telemetry  # noqa: E402
from horovod_tpu.models import (  # noqa: E402
    HybridConfig,
    HybridLM,
    TransformerConfig,
    TransformerLM,
    hybrid_lm_loss,
)
from horovod_tpu.models.transformer import lm_loss  # noqa: E402


class _Guard:
    """The least a guardian is: a limit, and somewhere to tell the norm."""

    def current_limit(self):
        return np.inf

    def observe(self, gnorm, limit):
        pass


def _loss(params, batch):
    return jnp.mean((batch["x"] @ params["w"] + params["b"] - batch["y"]) ** 2)


def _arguments(step):
    params, opt_state = step.init({"w": jnp.ones((16, 8)),
                                   "b": jnp.zeros((8,))})
    batch = step.shard_batch({"x": np.ones((16, 16), np.float32),
                              "y": np.zeros((16, 8), np.float32)})
    return params, opt_state, batch


def _paths(text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', text))


def _holds(paths: set, scope: str) -> bool:
    """Whether some operation stands under ``scope``: a step of its path
    (two in a row for ``exchange/scatter``) as the readers take a path
    apart — JAX writes a scope entered under a transformation as
    ``jvp(loss)`` or ``transpose(jvp(loss))``."""
    want = scope.split("/")
    for path in paths:
        found = phases.steps(path)
        if any(found[i:i + len(want)] == want for i in range(len(found))):
            return True
    return False


ZERO = dict(mode="shard_map", shard_optimizer_states=True)
# the step's options -> the scopes it enters (the rest it must not)
MODES = {
    "pjit": (dict(), "loss_fn,update"),
    "pjit_guard": (dict(guard=_Guard()), "loss_fn,guard,update"),
    "pjit_scanned": (dict(steps_per_call=3), "loss_fn,update"),
    "shard_map": (dict(mode="shard_map"), "loss_fn,exchange,update"),
    "shard_map_guard": (dict(mode="shard_map", guard=_Guard()),
                        "loss_fn,exchange,guard,update"),
    "shard_map_scanned": (dict(mode="shard_map", steps_per_call=2),
                          "loss_fn,exchange,update"),
    "zero_leafwise": (dict(ZERO, hierarchy="flat"),
                      "loss_fn,exchange,update"),
    "zero_leafwise_guard": (dict(ZERO, hierarchy="flat", guard=_Guard()),
                            "loss_fn,exchange,guard,update"),
    "zero_packed": (dict(ZERO, hierarchy="flat",
                         exchange_bucket_bytes=1 << 20),
                    "loss_fn,exchange,update"),
    "zero_two_level": (dict(ZERO), "loss_fn,exchange,update"),
}
SCOPES = ("loss_fn", "exchange", "guard", "update")


@pytest.mark.parametrize("mode", sorted(MODES))
def test_a_step_holds_the_scopes_its_mode_enters_and_no_other(
        hvd_runtime, mode):
    options, entered = MODES[mode]
    step = hvd.DistributedTrainStep(_loss, optax.adam(1e-2), **options)
    args = _arguments(step)
    since = telemetry.spans._now()
    paths = _paths(step.compiled_text(*args))
    for scope in SCOPES:
        assert _holds(paths, scope) == (scope in entered.split(",")), scope
    sharded = options.get("shard_optimizer_states", False)
    for part in ("exchange/scatter", "exchange/gather"):
        assert _holds(paths, part) == sharded, part
        # inside the step's update: the innermost of the two wins
        assert _holds(paths, f"update/{part}") == sharded, part
    # the step says which it laid, where the flash and hybrid facts are
    lowering, compiling = (
        [s for s in telemetry.spans.snapshot(since=since)
         if s.name == name][-1]
        for name in ("train_step.lower", "train_step.compile"))
    assert lowering.attrs["step_scopes"] == entered
    assert compiling.attrs["step_scopes"] == entered
    # the backward of the loss keeps the scope, under transpose(...)
    assert any("loss_fn" in p and "transpose(" in p for p in paths)
    # and the step trains as it did
    params, opt_state, loss = step(*args)
    assert np.isfinite(float(loss))


def test_a_distributed_optimizer_outside_the_step_is_named_too(hvd_runtime):
    """``distributed_gradients`` lays ``exchange`` itself."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(2, 4),
                             ("dcn", "ici"))
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))

    def per_device(params, batch):
        grads = jax.grad(_loss)(params, batch)
        updates, _ = opt.update(grads, opt.init(params), params)
        return optax.apply_updates(params, updates)

    fn = jax.jit(jax.shard_map(
        per_device, mesh=mesh, in_specs=(P(), P(("dcn", "ici"))),
        out_specs=P(), check_vma=False))
    text = fn.lower({"w": jnp.ones((16, 8)), "b": jnp.zeros((8,))},
                    {"x": jnp.ones((16, 16)), "y": jnp.zeros((16, 8))}) \
        .compile().as_text()
    assert _holds(_paths(text), "exchange")


def _lm():
    cfg = TransformerConfig(vocab_size=64, num_layers=1, num_heads=2,
                            d_model=16, d_ff=32, max_seq_len=8)
    model = TransformerLM(cfg)
    return (lambda params, batch: lm_loss(params, model, batch),
            model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)),
            np.zeros((8, 9), np.int32))


def _hybrid(**kw):
    base = dict(vocab_size=64, pattern="MD*D", d_model=32, dtype=jnp.float32,
                num_heads=4, num_kv_heads=2, head_dim=8, mamba_heads=8,
                mamba_head_dim=8, mamba_groups=1, ssm_state=16, chunk=8,
                mlp_width=48)
    model = HybridLM(HybridConfig(**dict(base, **kw)))
    tokens = np.zeros((8, 16), np.int32)
    return (functools.partial(hybrid_lm_loss, model),
            model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)),
            {"inputs": tokens, "labels": tokens})


MODELS = {
    "transformer_lm": _lm,
    "hybrid_lm_untied": _hybrid,
    "hybrid_lm_tied": functools.partial(_hybrid, tie_head=True,
                                        logits_divisor=8.0),
}


@pytest.mark.parametrize("model", sorted(MODELS))
def test_a_language_model_s_step_holds_head_and_loss(hvd_runtime, model):
    loss_fn, params, tokens = MODELS[model]()
    step = hvd.DistributedTrainStep(loss_fn, optax.adamw(1e-3))
    params, opt_state = step.init(params)
    paths = _paths(step.compiled_text(params, opt_state,
                                      step.shard_batch(tokens)))
    for scope in ("head", "loss"):
        forward = [p for p in paths if _holds({p}, scope)
                   and "transpose(" not in p]
        backward = [p for p in paths if _holds({p}, scope)
                    and "transpose(" in p]
        assert forward and backward, scope
    # the logits' matmul, tied or not, forward and backward, and the
    # final norm stand under head; the layers do not
    assert any(p.endswith("dot_general") and _holds({p}, "head")
               and "transpose(" in p for p in paths)
    assert any(_holds({p}, "head/ln_f") for p in paths)
    assert not any(_holds({p}, "head") and "layer_0" in p for p in paths)
    # both under the step's loss_fn, beside flax's own names
    assert all(_holds({p}, "loss_fn") for p in paths if _holds({p}, "head"))
    assert any(_holds({p}, "embed") for p in paths)


@pytest.mark.parametrize("mode", ["pjit_guard", "shard_map",
                                  "zero_leafwise", "zero_packed"])
def test_the_lowered_program_is_the_same_without_the_scopes(
        hvd_runtime, monkeypatch, mode):
    """``lower(...).as_text()`` prints no locations: with the scopes
    entered and with ``jax.named_scope`` a no-op it is the same text."""
    options, _ = MODES[mode]

    def lowered():
        step = hvd.DistributedTrainStep(_loss, optax.adam(1e-2), **options)
        args = _arguments(step)
        if step._guard is not None:
            args += (np.float32(np.inf),)
        with step._ambient_mesh():
            return step._step.lower(*args).as_text()

    with_scopes = lowered()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert lowered() == with_scopes
    assert "loss_fn" not in with_scopes and "exchange" not in with_scopes


def test_a_model_lowers_to_the_same_text_without_head_and_loss(monkeypatch):
    loss_fn, params, batch = _hybrid(tie_head=True, logits_divisor=8.0)

    def lowered():
        return jax.jit(jax.value_and_grad(loss_fn)).lower(
            params, batch).as_text()

    with_scopes = lowered()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert lowered() == with_scopes
