"""Switch-MoE LM training — local experts or expert-parallel dispatch.

Two modes over identical parameters:

* default: every device holds all experts (single chip / pure DP);
* ``--ep N``: experts sharded over an ``ep`` mesh axis, tokens moved by
  ``all_to_all`` (``parallel/expert.py``), run under ``shard_map``.

Usage::

    python examples/moe_lm_example.py --platform cpu                # local
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/moe_lm_example.py --platform cpu --ep 8     # EP
"""

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=32)
    p.add_argument("--experts", type=int, default=8)
    p.add_argument("--ep", type=int, default=0,
                   help="expert-parallel over an ep mesh of this size "
                        "(0 = local experts)")
    p.add_argument("--aux-weight", type=float, default=0.01)
    p.add_argument("--platform", default=None)
    return p.parse_args()


def main():
    args = parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from horovod_tpu.models import MoEConfig, MoETransformerLM, moe_aux_loss
    from horovod_tpu.parallel.mesh import make_parallel_mesh

    cfg = MoEConfig(vocab_size=256, num_layers=2, num_heads=4,
                    d_model=64, d_ff=128, max_seq_len=args.seq_len,
                    dtype=jnp.float32, num_experts=args.experts,
                    capacity_factor=2.0, moe_every=2,
                    ep_axis="ep" if args.ep else None)
    model = MoETransformerLM(cfg)

    rng = np.random.RandomState(0)
    data = rng.randint(0, cfg.vocab_size,
                       (args.batch_size, args.seq_len + 1))
    x = jnp.asarray(data[:, :-1], jnp.int32)
    y = jnp.asarray(data[:, 1:], jnp.int32)

    # init with the local-mode twin (identical params, no bound axis)
    init_model = MoETransformerLM(dataclasses.replace(cfg, ep_axis=None))
    variables = jax.jit(init_model.init)(jax.random.PRNGKey(0), x[:1])
    params = variables["params"]
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)

    def loss_fn(params, x, y):
        logits, state = model.apply({"params": params}, x,
                                    mutable=["intermediates"])
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return ce + args.aux_weight * moe_aux_loss(state["intermediates"])

    def train_step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    if args.ep:
        from jax.sharding import PartitionSpec as P

        mesh = make_parallel_mesh(ep=args.ep)

        def sharded_step(params, opt_state, x, y):
            loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
            # experts see only their token shard: average grads/loss
            # across the ep axis so every shard applies one update
            grads = jax.lax.pmean(grads, "ep")
            loss = jax.lax.pmean(loss, "ep")
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, \
                loss[None]

        step = jax.jit(jax.shard_map(
            sharded_step, mesh=mesh,
            in_specs=(P(), P(), P("ep"), P("ep")),
            out_specs=(P(), P(), P()), check_vma=False))
        print(f"expert-parallel over ep={args.ep} "
              f"({cfg.num_experts} experts, "
              f"{cfg.num_experts // args.ep} per shard)")
    else:
        step = jax.jit(train_step)
        print(f"local mode ({cfg.num_experts} experts resident)")

    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, x, y)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  loss {float(jnp.asarray(loss).mean()):.4f}")


if __name__ == "__main__":
    main()
