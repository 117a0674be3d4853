"""Hybrid Mamba-2 / routed-expert / GQA LM through DistributedTrainStep.

A tiny preset of ``horovod_tpu.models.HybridLM`` (docs/hybrid.md): the
pattern string picks one mixer a layer, and ``experts_held`` says which
of the router's experts this rank holds — here ids 4-7 of 16, so most
assignments are another rank's and add nothing.  Data-parallel over
whatever devices there are.

    python examples/hybrid_lm_example.py --platform cpu --steps 20
"""

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--platform", default=None)
    args = p.parse_args(argv)
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    import horovod_tpu as hvd
    from horovod_tpu.models import HybridConfig, HybridLM, expert_load, \
        hybrid_lm_loss

    hvd.init()
    cfg = HybridConfig(
        vocab_size=256, pattern="EMEM*", d_model=64, dtype=jnp.float32,
        num_heads=4, num_kv_heads=2, head_dim=32, mamba_heads=4,
        mamba_head_dim=16, mamba_groups=2, ssm_state=16, chunk=16,
        num_experts=16, experts_held=(4, 8), top_k=3, expert_width=48,
        shared_width=96, remat_policy="full",
        # one rank alone has a quarter of the router's gradient
        train_router=False)
    model = HybridLM(cfg)
    step = hvd.DistributedTrainStep(functools.partial(hybrid_lm_loss, model),
                                    optax.adamw(3e-3))
    rows = 2 * hvd.size()
    # a skewed unigram, so there is something to learn
    data = np.random.default_rng(0).zipf(1.5, (rows, args.seq_len + 1)) % 256
    batch = {"inputs": data[:, :-1].astype(np.int32),
             "labels": data[:, 1:].astype(np.int32)}
    params, opt_state = step.init(
        model.init(jax.random.PRNGKey(0), batch["inputs"][:1]))
    load = jax.jit(functools.partial(expert_load, model))(
        params, batch["inputs"])
    print(f"share of assignments on the held experts, a layer: "
          f"{np.round(np.asarray(load['local_share']), 3)}")
    placed = step.shard_batch(batch)
    losses = []
    for _ in range(args.steps):
        params, opt_state, loss = step(params, opt_state, placed)
        losses.append(float(loss))
    print(f"loss {losses[0]:.4f} -> {losses[-1]:.4f} in {args.steps} steps")
    hvd.shutdown()
    return losses


if __name__ == "__main__":
    main()
