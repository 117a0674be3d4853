"""Synthetic transformer-LM training benchmark — the long-context flagship.

Counterpart of the reference's synthetic benchmarks for the LLM regime:
trains :class:`horovod_tpu.models.TransformerLM` on random tokens and
prints tokens/sec.  ``--attention ring`` shards the sequence over the
``sp`` mesh axis (K/V ppermute ring), letting context length scale with
chips; ``--tp`` shards the matmuls.

Usage::

    python examples/transformer_lm_benchmark.py --platform cpu \
        --attention ring --sp 4 --seq-len 512
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--num-layers", type=int, default=4)
    p.add_argument("--d-model", type=int, default=512)
    p.add_argument("--num-heads", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=1024,
                   help="global sequence length")
    p.add_argument("--batch-size", type=int, default=8,
                   help="global batch size")
    p.add_argument("--vocab-size", type=int, default=32000)
    p.add_argument("--attention", default="dense",
                   choices=["dense", "flash", "ring", "ulysses"])
    p.add_argument("--sp", type=int, default=1, help="sequence-parallel degree")
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel degree")
    p.add_argument("--num-iters", type=int, default=5)
    p.add_argument("--num-batches-per-iter", type=int, default=5)
    p.add_argument("--remat", action="store_true")
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--platform", default=None)
    return p.parse_args()


def main():
    args = parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    import horovod_tpu as hvd
    from horovod_tpu.models import TransformerConfig, TransformerLM
    from horovod_tpu.parallel import make_parallel_mesh

    hvd.init()
    n = hvd.size()
    dp = n // (args.sp * args.tp)
    mesh = make_parallel_mesh(dp=dp, sp=args.sp, tp=args.tp)

    cfg = TransformerConfig(
        vocab_size=args.vocab_size, num_layers=args.num_layers,
        num_heads=args.num_heads, d_model=args.d_model,
        d_ff=4 * args.d_model, max_seq_len=args.seq_len,
        dtype=jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32,
        attention_impl=args.attention, remat=args.remat)
    model = TransformerLM(cfg)

    t_local = args.seq_len // max(args.sp, 1)

    # the next-token shift happens ONCE globally (inputs = tokens[:-1],
    # labels = tokens[1:]) and both sides are sharded over sp — a
    # per-shard shift would drop one token per shard, not one globally
    def loss_fn(variables, inputs, labels):
        offset = lax.axis_index("sp") * t_local if args.sp > 1 else 0
        positions = offset + jnp.arange(inputs.shape[1])
        logits = model.apply(variables, inputs, positions=positions)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return lax.pmean(lax.pmean(loss, "dp"), "sp") \
            if args.sp > 1 else lax.pmean(loss, "dp")

    opt = optax.adamw(3e-4)

    def train_step(variables, opt_state, inputs, labels):
        loss, grads = jax.value_and_grad(loss_fn)(variables, inputs, labels)
        grads = jax.tree_util.tree_map(
            lambda g: lax.pmean(lax.pmean(g, "dp"), "sp") if args.sp > 1
            else lax.pmean(g, "dp"), grads)
        updates, opt_state = opt.update(grads, opt_state, variables)
        return optax.apply_updates(variables, updates), opt_state, loss

    # init outside the mesh with a dense-attention twin (identical param
    # tree); the distributed attention only exists inside shard_map
    init_model = TransformerLM(
        dataclasses.replace(cfg, attention_impl="dense"))
    tokens0 = jnp.zeros((args.batch_size, max(t_local, 2)), jnp.int32)
    variables = init_model.init(jax.random.PRNGKey(0), tokens0)
    opt_state = opt.init(variables)

    tok_spec = P("dp", "sp") if args.sp > 1 else P("dp", None)
    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), tok_spec, tok_spec),
        out_specs=(P(), P(), P()),
        check_vma=False), donate_argnums=(0, 1))

    rng = np.random.RandomState(0)
    raw = jnp.asarray(rng.randint(
        0, args.vocab_size, (args.batch_size, args.seq_len + 1)), jnp.int32)
    sharding = NamedSharding(mesh, tok_spec)
    inputs = jax.device_put(raw[:, :-1], sharding)
    labels = jax.device_put(raw[:, 1:], sharding)

    if hvd.rank() == 0:
        nparams = sum(x.size for x in jax.tree_util.tree_leaves(variables))
        print(f"TransformerLM: {nparams / 1e6:.1f}M params, "
              f"seq {args.seq_len}, batch {args.batch_size}, "
              f"mesh dp={dp} sp={args.sp} tp={args.tp}, "
              f"attention={args.attention}")

    t0 = time.perf_counter()
    variables, opt_state, loss = step(variables, opt_state, inputs, labels)
    float(loss)     # fence: the host fetch waits for the step
    if hvd.rank() == 0:
        print(f"Warmup (incl. compile): {time.perf_counter() - t0:.1f}s, "
              f"loss={float(loss):.4f}")

    tokens_per_batch = args.batch_size * args.seq_len
    rates = []
    for it in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            variables, opt_state, loss = step(variables, opt_state, inputs, labels)
        float(loss)
        dt = time.perf_counter() - t0
        rates.append(tokens_per_batch * args.num_batches_per_iter / dt)
        if hvd.rank() == 0:
            print(f"Iter #{it}: {rates[-1]:.0f} tokens/sec")

    if hvd.rank() == 0:
        print(f"Mean: {np.mean(rates):.0f} +- {1.96 * np.std(rates):.0f} "
              f"tokens/sec; final loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
