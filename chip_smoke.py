#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the training path starts on the chip.

Drives the main path a user calls — ``hvd.init()`` →
``DistributedTrainStep`` → ``step.init`` → ``step.shard_batch`` →
``step(...)`` — once, in one process, on every chip that process sees, at
the full size of the repo's flagship LM (870.9M parameters: 16 layers,
d_model 2048, 16 heads of 128, d_ff 8192, vocab 32,000, seq 1024, batch 6
a chip, bf16 compute, flash attention with 512 blocks, ``adamw(3e-4)``,
``steps_per_call=1``).  Weights are random from a seed; there is no
checkpoint and no network.

Phases:

* ``train_pjit`` — always: the default step (``mode="pjit"``) over all
  devices; ten optimizer steps on one repeated batch, three on fresh
  host batches.
* with four devices, also ``train_zero`` (``mode="shard_map",
  shard_optimizer_states=True`` — the ZeRO RS → update → AG exchange) and
  one forward+backward each, at LM width and two layers, through the
  paths ``auto`` turns on only on a TPU: ``ring_flash_sp4`` (ring
  attention under ``plan="sp=4"`` at seq 4096) and ``fused_tp4``
  (``fused_tp_apply`` under ``tp=4`` with ``jax.grad``), each checked
  against its jnp formulation on the same inputs.

It fails — non-zero exit, the check named — when a loss is not finite,
the loss on the repeated batch does not fall, the compiled step holds no
Mosaic custom call (flash gave way to dense attention), anything
compiles after the first step, the batch or the state is not spread over
every device, or a phase raises.  It prints set-up facts (seconds to
first step, wall time a step, peak bytes a device) for ``CHANGES.md``;
they are not performance records, and it prints no utilisation.

Without a TPU it exits non-zero and prints no result.  On a chip the
last line of standard output is one JSON object with exactly two keys,
``{"ok": true|false, "device": {"platform": ..., "kind": ..., "count":
...}}``, the device as JAX reports it; the exit code is 0 iff ``ok``.
The per-phase facts are the ``chip_smoke: summary {...}`` line before it.

``--expect-warm`` additionally fails unless the train step was served
from the compile cache and wrote nothing new into the cache directory (a
second run against the same cache directory).
``--cpu-rehearsal`` is for debugging the script itself off the chip: a
tiny model with the kernels in interpreter mode, labelled as such, and
it prints no result either.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

MOSAIC_CALL = "tpu_custom_call"
REPEATED_STEPS = 10
FRESH_STEPS = 3
# bf16 compute, fp32 accumulation; reduction orders differ between the
# formulations compared.  Set beforehand from the dtype (bf16 has 8
# mantissa bits, ~4e-3 relative a rounding), not fitted to a run.
LOSS_RTOL = 1e-2
GRAD_REL_L2 = 5e-2


class CheckFailed(Exception):
    """A named smoke check did not hold."""

    def __init__(self, check: str, detail: str):
        super().__init__(f"{check}: {detail}")
        self.check = check


def check(name: str, ok: bool, detail: str) -> None:
    if not ok:
        raise CheckFailed(name, detail)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ---------------------------------------------------------------------------
# observation helpers
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts backend compilations through ``jax.monitoring`` — the
    listener sees every XLA compile of the process, cache hit or not."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.count += 1


def bytes_per_device(tree) -> dict:
    """Bytes each device holds of ``tree`` (sum over addressable shards)."""
    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) + \
                shard.data.nbytes
    return out


def memory_per_device(key: str) -> dict:
    """``memory_stats()[key]`` of every device (the CPU backend of the
    rehearsal keeps no stats: None there)."""
    stats = {d.id: d.memory_stats() for d in jax.devices()}
    return {i: s[key] if s else None for i, s in stats.items()}


def cache_files(root) -> int:
    if root is None or not os.path.isdir(root):
        return 0
    return sum(len(files) for _, _, files in os.walk(root))


def rel_l2(tree_a, tree_b) -> float:
    """Relative L2 distance of two gradient pytrees, in fp32."""
    num = den = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(tree_a),
                    jax.tree_util.tree_leaves(tree_b)):
        a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
        num += float(np.sum((a32 - b32) ** 2))
        den += float(np.sum(b32 ** 2))
    return math.sqrt(num / den)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    d_model: int
    heads: int
    d_ff: int
    vocab: int
    seq: int
    batch_per_chip: int
    dtype: object
    interpret: bool

    def config(self, **overrides):
        from horovod_tpu.models import TransformerConfig

        kw = dict(vocab_size=self.vocab, num_layers=self.layers,
                  num_heads=self.heads, d_model=self.d_model,
                  d_ff=self.d_ff, max_seq_len=self.seq, dtype=self.dtype,
                  attention_impl="flash", flash_block=512,
                  flash_interpret=self.interpret)
        kw.update(overrides)
        return TransformerConfig(**kw)


FLAGSHIP = Sizes(layers=16, d_model=2048, heads=16, d_ff=8192, vocab=32_000,
                 seq=1024, batch_per_chip=6, dtype=jnp.bfloat16,
                 interpret=False)
# --cpu-rehearsal only: debugs this script, measures nothing
REHEARSAL = Sizes(layers=2, d_model=128, heads=4, d_ff=256, vocab=512,
                  seq=128, batch_per_chip=2, dtype=jnp.float32,
                  interpret=True)


def lm_loss_fn(model):
    def loss_fn(params, batch):
        logits = model.apply(params, batch["inputs"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["labels"]).mean()
    return loss_fn


def host_batch(rng, rows: int, seq: int, vocab: int) -> dict:
    raw = rng.randint(0, vocab, (rows, seq + 1))
    return {"inputs": raw[:, :-1].astype(np.int32),
            "labels": raw[:, 1:].astype(np.int32)}


# ---------------------------------------------------------------------------
# training phases
# ---------------------------------------------------------------------------

def train_phase(name: str, hvd, sizes: Sizes, compiles: CompileCounter,
                expect_warm: bool, **step_kwargs) -> dict:
    """The main path, once: build the step, init, shard, step."""
    from horovod_tpu.models import TransformerLM
    from horovod_tpu.runtime import compile_cache
    from horovod_tpu.utils import hlo

    n = hvd.size()
    devices = jax.devices()
    model = TransformerLM(sizes.config())
    step = hvd.DistributedTrainStep(lm_loss_fn(model), optax.adamw(3e-4),
                                    **step_kwargs)
    # params exactly as model.init returns them (boxed nn.Partitioned)
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, sizes.seq), jnp.int32))
    nparams = sum(x.size for x in jax.tree_util.tree_leaves(variables))
    params, opt_state = step.init(variables)
    del variables
    rng = np.random.RandomState(0)
    rows = sizes.batch_per_chip * n
    batch = step.shard_batch(host_batch(rng, rows, sizes.seq, sizes.vocab))
    say(f"[{name}] {nparams / 1e6:.1f}M parameters, {n} device(s), "
        f"global batch {rows} x seq {sizes.seq}, step kwargs {step_kwargs}")

    shard_devices = {s.device.id for s in batch["inputs"].addressable_shards}
    check(f"{name}.batch_spread", len(shard_devices) == n,
          f"batch shards live on devices {sorted(shard_devices)}, "
          f"expected all {n}")
    check(f"{name}.batch_rows",
          batch["inputs"].addressable_shards[0].data.shape[0]
          == sizes.batch_per_chip,
          f"a device holds "
          f"{batch['inputs'].addressable_shards[0].data.shape[0]} rows, "
          f"expected {sizes.batch_per_chip}")
    opt_bytes = bytes_per_device(opt_state)
    param_bytes = bytes_per_device(params)

    cache_root = compile_cache.resolve_dir()
    files_before = cache_files(cache_root)
    t0 = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    first_step_s = time.perf_counter() - t0
    losses = [float(loss)]
    new_files = cache_files(cache_root) - files_before
    cache_hit = step.compile_cache_hit
    say(f"[{name}] first step (trace + compile + run) {first_step_s:.1f}s, "
        f"loss {losses[0]:.4f}; compile cache hit: {cache_hit}; "
        f"{new_files} new file(s) under {cache_root}")
    if expect_warm:
        # both, not either: a cache that stopped writing must not pass
        # as warm on the strength of an untouched directory
        check(f"{name}.served_from_cache",
              cache_hit is True and new_files == 0,
              f"--expect-warm, but the step was not served from the "
              f"cache: hit {cache_hit}, {new_files} new cache files "
              f"in {cache_root}")

    # the compiled program's text: the executable that just ran, what
    # actually runs, not what was asked for
    text = step.compiled_text(params, opt_state, batch)
    mosaic_calls = [ln for ln in text.splitlines() if MOSAIC_CALL in ln]
    if not sizes.interpret:
        check(f"{name}.mosaic_call", bool(mosaic_calls),
              "the compiled step holds no Mosaic custom call: flash "
              "attention gave way to the dense jnp attention")
        # flash_fwd's first operand is q as (batch*heads, seq, head_dim):
        # per device it must be this device's rows only
        bh = sizes.batch_per_chip * sizes.heads
        want = f"[{bh},{sizes.seq},{sizes.d_model // sizes.heads}]"
        check(f"{name}.mosaic_operands_per_chip",
              all(want in ln for ln in mosaic_calls),
              f"a Mosaic call's operands are not the per-chip "
              f"{want} (batch {sizes.batch_per_chip} x {sizes.heads} "
              f"heads): {mosaic_calls[0][:300]}")
    ops = hlo.collective_ops(text)
    kinds = hlo.count_by_kind(ops)
    reduced_bytes = {k: sum(o.bytes for o in ops
                            if o.kind == k and o.group_size == n)
                     for k in ("reduce-scatter", "all-reduce", "all-gather")}
    # a line an occurrence: an async collective fusion clones its
    # all-reduce into every step's computation, so the lines over-read
    # the replicated step's exchange; the channels do not
    exchange = hlo.exchange_counts(text, n)
    say(f"[{name}] compiled step: {len(mosaic_calls)} Mosaic call(s), "
        f"collectives {kinds}, bytes over {n} replicas {reduced_bytes}; "
        f"a channel once: {exchange}")

    compiled_before = compiles.count
    step_s = []
    for _ in range(REPEATED_STEPS - 1):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        jax.block_until_ready(loss)
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    fresh_losses = []
    for _ in range(FRESH_STEPS):
        fresh = step.shard_batch(
            host_batch(rng, rows, sizes.seq, sizes.vocab))
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, fresh)
        jax.block_until_ready(loss)
        step_s.append(time.perf_counter() - t0)
        fresh_losses.append(float(loss))
    check(f"{name}.no_recompile", compiles.count == compiled_before,
          f"{compiles.count - compiled_before} compilation(s) after the "
          f"first step")
    check(f"{name}.finite_loss",
          all(math.isfinite(x) for x in losses + fresh_losses),
          f"losses {losses} / fresh {fresh_losses}")
    check(f"{name}.loss_falls", losses[-1] < losses[0],
          f"loss on the repeated batch went {losses[0]:.4f} -> "
          f"{losses[-1]:.4f} over {REPEATED_STEPS} steps")

    # what a device holds: live arrays (bytes_in_use) plus the region the
    # loaded executable keeps for its temporaries (bytes_reserved) —
    # peak_bytes_in_use counts only the former, over the process's life
    in_use = memory_per_device("bytes_in_use")
    reserved = memory_per_device("bytes_reserved")
    peak = memory_per_device("peak_bytes_in_use")
    limit = memory_per_device("bytes_limit")
    if not sizes.interpret:
        check(f"{name}.every_device_holds_state",
              all(in_use[d.id] >= param_bytes[d.id] for d in devices),
              f"bytes in use {in_use} vs parameter bytes a device "
              f"{param_bytes}")
        check(f"{name}.fits",
              all(peak[i] < limit[i] and in_use[i] + reserved[i] < limit[i]
                  for i in peak),
              f"peak {peak}, in use {in_use} + reserved {reserved} vs "
              f"limit {limit}")
    say(f"[{name}] losses {losses[0]:.4f} -> {losses[-1]:.4f} (repeated), "
        f"fresh {[round(x, 4) for x in fresh_losses]}; wall a step "
        f"median {np.median(step_s) * 1e3:.0f} ms (fenced); peak bytes in "
        f"use a device {peak}; bytes reserved for the executable "
        f"{reserved}; optimizer-state bytes a device {opt_bytes}")
    return {
        "phase": name, "parameters": int(nparams), "devices": n,
        "first_step_s": round(first_step_s, 2),
        "compile_cache_hit": cache_hit, "new_cache_files": new_files,
        "cache_dir": cache_root,
        "step_wall_ms_median": round(float(np.median(step_s)) * 1e3, 1),
        "first_loss": losses[0], "last_loss": losses[-1],
        "fresh_losses": fresh_losses,
        "mosaic_calls": len(mosaic_calls), "collectives": kinds,
        "collective_bytes_over_all_replicas": reduced_bytes,
        "exchange_a_channel_once": exchange,
        "dynamic_slices": text.count(" dynamic-slice("),
        "peak_bytes_in_use": peak, "bytes_in_use": in_use,
        "bytes_reserved": reserved,
        "optimizer_state_bytes": opt_bytes,
    }


def check_zero_against_pjit(zero: dict, pjit: dict, n: int) -> None:
    """The ZeRO exchange as compiled: the gradients reduced over all
    ``n`` replicas with each keeping 1/n, the updates all-gathered back.
    The framework emits one ``psum_scatter`` a leaf; whether the
    compiler keeps one as a reduce-scatter or lowers it as all-reduce +
    dynamic-slice is its choice — recorded, because the second may move
    twice the bytes."""
    grad_bytes = 4 * zero["parameters"]
    over_n = zero["collective_bytes_over_all_replicas"]
    if over_n["reduce-scatter"] >= grad_bytes // n:
        zero["gradient_reduction_as_compiled"] = "reduce-scatter"
    elif over_n["all-reduce"] >= grad_bytes and zero["dynamic_slices"]:
        zero["gradient_reduction_as_compiled"] = \
            "all-reduce + dynamic-slice"
    elif n * over_n["reduce-scatter"] + over_n["all-reduce"] >= grad_bytes \
            and zero["dynamic_slices"]:
        # leaf by leaf the compiler chooses a leaf at a time
        zero["gradient_reduction_as_compiled"] = \
            "reduce-scatter for some leaves, all-reduce + " \
            "dynamic-slice for the others"
    else:
        raise CheckFailed(
            "train_zero.gradient_reduction",
            f"no reduction of the {grad_bytes}-byte gradient over {n} "
            f"replicas in the compiled step: {zero['collectives']}, "
            f"bytes {over_n}")
    say(f"[train_zero] psum_scatter of the gradients compiled as "
        f"{zero['gradient_reduction_as_compiled']}")
    check("train_zero.update_allgather", over_n["all-gather"] >= grad_bytes,
          f"no all-gather of the {grad_bytes}-byte update over {n} "
          f"replicas: {zero['collectives']}, bytes {over_n}")
    check("train_zero.first_loss_matches_pjit",
          abs(zero["first_loss"] - pjit["first_loss"])
          <= LOSS_RTOL * abs(pjit["first_loss"]),
          f"first-step loss {zero['first_loss']} vs pjit's "
          f"{pjit['first_loss']}")
    for dev, b in zero["optimizer_state_bytes"].items():
        ratio = b / pjit["optimizer_state_bytes"][dev]
        check("train_zero.optimizer_state_sharded",
              abs(ratio - 1 / n) < 0.05,
              f"device {dev} holds {ratio:.3f} of pjit's optimizer "
              f"state, expected ~1/{n}")


# ---------------------------------------------------------------------------
# one forward+backward through the paths `auto` enables only on a TPU
# ---------------------------------------------------------------------------

def _value_and_grads(fn, *args):
    loss, grads = fn(*args)
    jax.block_until_ready(grads)
    return float(loss), grads


def ring_flash_phase(sizes: Sizes, n: int) -> dict:
    """``attention_impl="ring"`` under ``plan="sp=n"``: ring-flash (the
    fused default on TPU) against the jnp ring on the same inputs."""
    from jax import lax

    from horovod_tpu.models import TransformerLM
    from horovod_tpu.parallel.plan import ShardingPlan

    name = f"ring_flash_sp{n}"
    seq = sizes.seq * n
    mesh = ShardingPlan.from_string(f"sp={n}").resolve(n).build_mesh()
    init_model = TransformerLM(sizes.config(attention_impl="dense",
                                            max_seq_len=seq))
    variables = jax.jit(init_model.init)(
        jax.random.PRNGKey(1), jnp.zeros((1, seq), jnp.int32))
    batch = host_batch(np.random.RandomState(1), 1, seq, sizes.vocab)

    def build(fused: str):
        model = TransformerLM(sizes.config(
            attention_impl="ring", fused_collectives=fused,
            max_seq_len=seq))

        def per_shard(params, inputs, labels):
            def loss_fn(params):
                t_local = inputs.shape[1]
                positions = lax.axis_index("sp") * t_local + \
                    jnp.arange(t_local)
                logits = model.apply(params, inputs, positions)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels).mean()

            loss, grads = jax.value_and_grad(loss_fn)(params)
            return lax.pmean(loss, "sp"), lax.pmean(grads, "sp")

        return jax.jit(jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(), P(None, "sp"), P(None, "sp")),
            out_specs=(P(), P()), check_vma=False))

    # "auto" is what a user gets: on a TPU it must select ring-flash
    fused = build("on" if sizes.interpret else "auto")
    args = jax.device_put((variables, batch["inputs"], batch["labels"]),
                          NamedSharding(mesh, P()))
    text = fused.lower(*args).compile().as_text()
    mosaic = text.count(MOSAIC_CALL)
    if not sizes.interpret:
        check(f"{name}.mosaic_call", mosaic > 0,
              "fused_collectives='auto' compiled no Mosaic call: the "
              "ring took the jnp formulation")
    loss_f, grads_f = _value_and_grads(fused, *args)
    loss_r, grads_r = _value_and_grads(build("off"), *args)
    err = rel_l2(grads_f, grads_r)
    say(f"[{name}] seq {seq}: loss {loss_f:.5f} vs jnp ring "
        f"{loss_r:.5f}; gradient rel-L2 {err:.2e}; {mosaic} Mosaic "
        f"call(s)")
    check(f"{name}.loss", math.isfinite(loss_f) and
          abs(loss_f - loss_r) <= LOSS_RTOL * abs(loss_r),
          f"loss {loss_f} vs the jnp ring's {loss_r}")
    check(f"{name}.grad", err < GRAD_REL_L2,
          f"gradient rel-L2 {err} vs the jnp ring")
    return {"phase": name, "seq": seq, "loss": loss_f,
            "reference_loss": loss_r, "grad_rel_l2": err,
            "mosaic_calls": mosaic,
            "peak_bytes_in_use": memory_per_device("peak_bytes_in_use")}


def fused_tp_phase(sizes: Sizes, n: int) -> dict:
    """``fused_tp_apply`` under ``tp=n`` with ``jax.grad`` (matmul ⊗
    collective rings on ``pallas_matmul``) against ``model.apply`` in
    plain jnp on the same inputs."""
    from horovod_tpu.models import TransformerLM
    from horovod_tpu.models.transformer import fused_tp_apply
    from horovod_tpu.parallel.plan import ShardingPlan

    name = f"fused_tp{n}"
    mesh = ShardingPlan.from_string(f"tp={n}").resolve(n).build_mesh()
    cfg = sizes.config()
    ref_model = TransformerLM(sizes.config(attention_impl="dense"))
    variables = jax.jit(ref_model.init)(
        jax.random.PRNGKey(2), jnp.zeros((1, sizes.seq), jnp.int32))
    batch = host_batch(np.random.RandomState(2), 2, sizes.seq, sizes.vocab)

    def ce(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels).mean()

    # None resolves cfg.fused_collectives="auto" — what a user gets,
    # which on a TPU must mean on
    fused_arg = True if sizes.interpret else None

    def per_shard(params, inputs, labels):
        loss, grads = jax.value_and_grad(lambda p: ce(
            fused_tp_apply(p, cfg, inputs, fused=fused_arg,
                           interpret=sizes.interpret), labels))(params)
        # every rank seeds the same (replicated) loss and backpropagates
        # only through its own slice of the work, the ring transposes
        # carrying the rest: the ranks' gradients sum to tp x the
        # gradient, so their mean is the gradient
        return loss, jax.lax.pmean(grads, cfg.tp_axis)

    fused = jax.jit(jax.shard_map(
        per_shard, mesh=mesh, in_specs=(P(), P(), P()),
        out_specs=(P(), P()), check_vma=False))
    reference = jax.jit(jax.value_and_grad(
        lambda p, inputs, labels: ce(ref_model.apply(p, inputs), labels)))
    args = (variables, batch["inputs"], batch["labels"])
    mesh_args = jax.device_put(args, NamedSharding(mesh, P()))
    text = fused.lower(*mesh_args).compile().as_text()
    mosaic = text.count(MOSAIC_CALL)
    if not sizes.interpret:
        check(f"{name}.mosaic_call", mosaic > 0,
              "fused_tp_apply compiled no Mosaic call: pallas_matmul "
              "took the jnp formulation")
    loss_f, grads_f = _value_and_grads(fused, *mesh_args)
    loss_r, grads_r = _value_and_grads(reference, *args)
    err = rel_l2(grads_f, grads_r)
    say(f"[{name}] loss {loss_f:.5f} vs jnp {loss_r:.5f}; gradient "
        f"rel-L2 {err:.2e}; {mosaic} Mosaic call(s)")
    check(f"{name}.loss", math.isfinite(loss_f) and
          abs(loss_f - loss_r) <= LOSS_RTOL * abs(loss_r),
          f"loss {loss_f} vs jnp {loss_r}")
    check(f"{name}.grad", err < GRAD_REL_L2,
          f"gradient rel-L2 {err} vs jnp")
    return {"phase": name, "loss": loss_f,
            "reference_loss": loss_r, "grad_rel_l2": err,
            "mosaic_calls": mosaic,
            "peak_bytes_in_use": memory_per_device("peak_bytes_in_use")}


# ---------------------------------------------------------------------------

def run_phases(sizes: Sizes, expect_warm: bool) -> list:
    import horovod_tpu as hvd

    compiles = CompileCounter()
    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()
    say(f"hvd.init(): {n} chip(s), mesh {dict(mesh.shape)}, devices in "
        f"mesh order {[d.id for d in mesh.devices.flat]}")
    check("init.mesh", dict(mesh.shape) == {"dcn": 1, "ici": n}
          and list(mesh.devices.flat) == jax.devices(),
          f"expected mesh dcn=1 x ici={n} over jax.devices() in list "
          f"order, got {dict(mesh.shape)}")
    phases = [train_phase("train_pjit", hvd, sizes, compiles, expect_warm)]
    if n == 4:
        phases.append(train_phase(
            "train_zero", hvd, sizes, compiles, expect_warm,
            mode="shard_map", shard_optimizer_states=True))
        check_zero_against_pjit(phases[1], phases[0], n)
        narrow = dataclasses.replace(sizes, layers=2)
        phases.append(ring_flash_phase(narrow, n))
        phases.append(fused_tp_phase(narrow, n))
    hvd.shutdown()
    return phases


def result_line(ok: bool, device: dict) -> str:
    """The last line of standard output: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``) — the checker takes no other
    key, so everything else goes on the ``summary`` line before it."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--expect-warm", action="store_true",
                        help="fail unless the step came from the compile "
                             "cache (second run, same cache directory)")
    parser.add_argument("--cpu-rehearsal", action="store_true",
                        help="debug this script off the chip: tiny model, "
                             "interpreter-mode kernels, no result")
    args = parser.parse_args(argv)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"chip_smoke: jax {jax.__version__}, platform {dev.platform}, "
          f"device_kind {dev.device_kind!r}, {device['count']} device(s)",
          flush=True)
    if args.cpu_rehearsal:
        say("CPU rehearsal — a tiny model with interpreter-mode kernels; "
            "this debugs the script and says nothing about any chip")
        check("rehearsal.platform", dev.platform == "cpu",
              f"--cpu-rehearsal on platform {dev.platform!r}")
        run_phases(REHEARSAL, args.expect_warm)
        say("CPU rehearsal finished; no result is printed off the chip")
        return 0
    if dev.platform != "tpu":
        print(f"chip_smoke: platform is {dev.platform!r}, not 'tpu' — "
              f"this script does not train on the CPU", file=sys.stderr)
        return 2

    # outside the reporting boundary below: without the repository around
    # it this script has nothing to run, and prints no result
    import horovod_tpu  # noqa: F401

    t0 = time.perf_counter()
    summary = {"ok": False, "device": device}
    try:
        summary["phases"] = run_phases(FLAGSHIP, args.expect_warm)
        summary["ok"] = True
    except Exception as e:  # noqa: BLE001 — the boundary: report, then fail
        traceback.print_exc()
        summary["failed_check"] = getattr(e, "check", type(e).__name__)
        summary["error"] = str(e)[:2000]
    summary["wall_s"] = round(time.perf_counter() - t0, 1)
    say(f"summary {json.dumps(summary)}")
    print(result_line(summary["ok"], device), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
