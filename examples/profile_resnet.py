"""Per-op device profile of the ResNet-50 bench step (PERF_NOTES tables).

Captures a ``jax.profiler`` trace of the exact ``bench.py`` train step
on the real chip and prints exclusive per-op device times — the "XLA
Ops" line of the xplane proto (parsed with the proto bundled in
``tensorflow.tsl``; no tensorboard UI needed), with nested event
durations subtracted from their parents so wrapper events (the step
``while``, the jit module) and async copy spans don't double count.

Usage::

    python examples/profile_resnet.py --top 30 [--steps-per-call 4]
        [--no-lhs] [--no-space-to-depth]
"""

import argparse
import collections
import glob
import os
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import optax


def build_step(batch_size, image_size, steps_per_call, lhs, s2d):
    import horovod_tpu as hvd
    from horovod_tpu.models.resnet import ResNet50

    hvd.init()
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                     space_to_depth=s2d,
                     fused_bwd=bool(int(os.environ.get(
                         "HOROVOD_PROFILE_FUSED_BWD", "0"))))

    def loss_fn(params, batch):
        logits = model.apply(params, batch["x"], train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    opts = {"xla_tpu_enable_latency_hiding_scheduler": "true"} if lhs \
        else None
    step = hvd.DistributedTrainStep(
        loss_fn, optax.sgd(0.01, momentum=0.9),
        steps_per_call=steps_per_call, compiler_options=opts)
    x0 = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
    # jit the init: eagerly it is hundreds of per-op dispatches
    params, opt_state = step.init(jax.jit(
        lambda k: model.init(k, x0, train=False))(jax.random.PRNGKey(0)))
    rng = np.random.RandomState(0)
    batch = step.shard_batch({
        "x": jnp.asarray(rng.rand(batch_size, image_size, image_size, 3),
                         jnp.float32),
        "y": jnp.asarray(rng.randint(0, 1000, (batch_size,)), jnp.int32),
    })
    return step, params, opt_state, batch


def exclusive_op_times(trace_dir):
    """{op name: self ps} from the device "XLA Ops" line, with child
    durations subtracted from enclosing events via an interval stack."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(open(paths[-1], "rb").read())
    self_ps: dict = collections.defaultdict(float)
    for plane in xs.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        ev_meta = dict(plane.event_metadata.items())
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            evs = sorted(
                (e.offset_ps, e.offset_ps + e.duration_ps, e.metadata_id)
                for e in line.events)
            stack = []
            for s, t, mid in evs:
                while stack and stack[-1][1] <= s:
                    stack.pop()
                name = ev_meta[mid].name if mid in ev_meta else "?"
                if stack:
                    self_ps[stack[-1][2]] -= (t - s)
                self_ps[name] += (t - s)
                stack.append((s, t, name))
    return self_ps


def op_kind(name: str) -> str:
    m = re.match(r"%?([a-zA-Z_\-]+)", name.split(" = ")[0])
    return m.group(1) if m else name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--steps-per-call", type=int, default=4)
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--no-lhs", action="store_true")
    ap.add_argument("--space-to-depth", action="store_true", default=True)
    ap.add_argument("--no-space-to-depth", dest="space_to_depth",
                    action="store_false")
    ap.add_argument("--trace-dir", default=None)
    args = ap.parse_args()

    step, params, opt_state, batch = build_step(
        args.batch_size, args.image_size, args.steps_per_call,
        not args.no_lhs, args.space_to_depth)
    p, o, loss = step(params, opt_state, batch)       # compile + warm
    float(loss)

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="rn50prof_")
    with jax.profiler.trace(trace_dir):
        p, o, loss = step(p, o, batch)
        float(loss)
    print(f"trace: {trace_dir}")

    self_ps = exclusive_op_times(trace_dir)
    nsteps = args.steps_per_call
    total_ms = sum(self_ps.values()) / 1e9 / nsteps
    print(f"device exclusive op time: {total_ms:.2f} ms/step "
          f"({len(self_ps)} distinct ops, {nsteps} steps traced)")

    by_kind = collections.defaultdict(float)
    for name, ps in self_ps.items():
        by_kind[op_kind(name)] += ps
    print("\n-- by op class (ms/step) --")
    for k, v in sorted(by_kind.items(), key=lambda kv: -kv[1])[:12]:
        ms = v / 1e9 / nsteps
        if ms >= 0.005:
            print(f"{k:36s} {ms:8.2f}  {ms / total_ms * 100:5.1f}%")

    print(f"\n-- top {args.top} ops (self ms/step) --")
    ranked = sorted(self_ps.items(), key=lambda kv: -kv[1])
    for name, ps in ranked[:args.top]:
        ms = ps / 1e9 / nsteps
        print(f"{name[:84]:84s} {ms:7.3f}")


if __name__ == "__main__":
    main()
