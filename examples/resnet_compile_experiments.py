"""A/B compiler-option experiments on the ResNet-50 bench step.

Per-compile ``compiler_options`` through
``jit(...).lower(...).compile()`` reach the TPU compiler, so the
latency-hiding scheduler / fusion / vmem knobs are testable one compile
at a time.  This harness times the exact ``bench.py`` train step under
each option set and prints a ms/step table (median of iters, loss-fetch
fenced).

Usage::

    python examples/resnet_compile_experiments.py \
        --set lhs=xla_tpu_enable_latency_hiding_scheduler:true \
        --set vmem=xla_tpu_scoped_vmem_limit_kib:65536 ...

Each ``--set name=opt:val[,opt:val...]`` adds one experiment; the
baseline (no options) always runs first.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax


def build_step(batch_size=256, image_size=224):
    import horovod_tpu as hvd
    from horovod_tpu.models.resnet import ResNet50

    hvd.init()
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)

    def loss_fn(params, batch):
        logits = model.apply(params, batch["x"], train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    opt = optax.sgd(0.01, momentum=0.9)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    x0 = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
    # jit the init: run eagerly it is hundreds of per-op dispatches
    params = jax.jit(lambda k: model.init(k, x0, train=False))(
        jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    # host copies so donation inside time_variant can't consume them
    params = jax.tree_util.tree_map(np.asarray, params)
    opt_state = jax.tree_util.tree_map(np.asarray, opt_state)
    rng = np.random.RandomState(0)
    batch = {
        "x": jnp.asarray(rng.rand(batch_size, image_size, image_size, 3),
                         jnp.float32),
        "y": jnp.asarray(rng.randint(0, 1000, (batch_size,)), jnp.int32),
    }
    return step, params, opt_state, batch


def time_variant(step, params, opt_state, batch, options, iters=4,
                 steps_per_iter=10):
    # params/opt_state arrive as host trees: the step donates its
    # arguments (like bench.py), so each variant starts from fresh
    # device buffers
    p = jax.tree_util.tree_map(jnp.asarray, params)
    o = jax.tree_util.tree_map(jnp.asarray, opt_state)
    lowered = jax.jit(step, donate_argnums=(0, 1)).lower(p, o, batch)
    t0 = time.perf_counter()
    compiled = lowered.compile(compiler_options=options or None)
    compile_s = time.perf_counter() - t0
    p, o, loss = compiled(p, o, batch)
    float(loss)                      # fence (see PERF_NOTES.md)
    rates = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(steps_per_iter):
            p, o, loss = compiled(p, o, batch)
        float(loss)
        rates.append((time.perf_counter() - t0) / steps_per_iter)
    del p, o
    return float(np.median(rates)) * 1e3, compile_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--set", action="append", default=[],
                    help="name=opt:val[,opt:val...]")
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--iters", type=int, default=4)
    args = ap.parse_args()

    experiments = [("baseline", {})]
    for spec in args.set:
        name, body = spec.split("=", 1)
        opts = {}
        for kv in body.split(","):
            k, v = kv.split(":", 1)
            opts[k] = v
        experiments.append((name, opts))

    step, params, opt_state, batch = build_step(args.batch_size)
    bs = batch["y"].shape[0]
    print(f"{'variant':24s} {'ms/step':>9s} {'img/s':>8s} {'compile':>8s}")
    for name, opts in experiments:
        try:
            ms, comp = time_variant(step, params, opt_state, batch, opts,
                                    iters=args.iters)
            print(f"{name:24s} {ms:9.2f} {bs / ms * 1e3:8.1f} {comp:7.1f}s",
                  flush=True)
        except Exception as e:
            print(f"{name:24s} FAILED: {str(e)[:140]}", flush=True)


if __name__ == "__main__":
    main()
