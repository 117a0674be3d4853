"""Eager-collective bandwidth microbenchmark over real worker processes.

Companion to the O(data) data-movement contract in
:mod:`horovod_tpu.ops.eager` (``_allgather_rows``/``_alltoall_rows``):
launches ``--np`` localhost processes through the programmatic runner and
reports per-collective effective bandwidth.  The reference benchmarks its
wire ops the same way (synthetic tensors, localhost multi-process).

Usage::

    python examples/eager_bandwidth_bench.py --np 2 --mb 64
    python examples/eager_bandwidth_bench.py --np 1 --device   # real chip

``--device`` keeps the default backend (the TPU, where there is one)
and runs in-process, measuring the *per-eager-call* cost on device —
each flush is its own dispatched program, so this is dominated by
dispatch latency.  The
printed ``in_jit`` row times the same reduction arithmetic fused inside
one compiled step, the cost the in-graph plane
(``DistributedTrainStep``/``ops.collectives``) pays instead.
"""

import argparse
import time


def worker(nbytes: int, iters: int, device: bool = False):
    import jax

    if not device:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    # round down to a world-size multiple so the split-less alltoall is legal
    n = (nbytes // 4) // hvd.size() * hvd.size()
    x = jnp.asarray(np.random.RandomState(hvd.rank()).rand(n), jnp.float32)

    out = {}

    def timed(fn, label):
        fn(x, name=f"{label}_warm")
        t0 = time.perf_counter()
        for i in range(iters):
            fn(x, name=f"{label}_{i}")
        return (time.perf_counter() - t0) / iters

    ar_s = timed(hvd.allreduce, "ar")
    out["allreduce_sync_ms_per_call"] = ar_s * 1e3
    out["allreduce_MBps"] = nbytes / ar_s / 1e6
    out["allgather_MBps"] = (nbytes * hvd.size()
                             / timed(hvd.allgather, "ag") / 1e6)
    out["alltoall_MBps"] = nbytes / timed(hvd.alltoall, "a2a") / 1e6

    def burst(r, tag):
        """Issue ``r`` async allreduces, then synchronize the batch."""
        t0 = time.perf_counter()
        handles = [hvd.allreduce_async(x, name=f"b{tag}_{i}")
                   for i in range(r)]
        for h in handles:
            hvd.synchronize(h)
        return time.perf_counter() - t0

    # marginal per-call cost by slope fit: any single burst pays a
    # fixed fence cost, so difference two burst sizes instead of
    # trusting one
    burst(2, "w")
    r1, r3 = iters, 3 * iters
    out["allreduce_async_ms_per_call"] =         (burst(r3, "3") - burst(r1, "1")) / (r3 - r1) * 1e3

    # the same arithmetic fused in one compiled program: what the
    # in-graph plane pays per reduction instead of a per-call dispatch
    scale = 1.0 / hvd.size()
    fused = jax.jit(lambda v: v * scale)

    def jit_burst(r):
        t0 = time.perf_counter()
        for _ in range(r):
            y = fused(x)
        jax.block_until_ready(y)
        return time.perf_counter() - t0

    jit_burst(2)
    out["in_jit_ms_per_call"] =         (jit_burst(r3) - jit_burst(r1)) / (r3 - r1) * 1e3

    hvd.shutdown()
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--np", type=int, default=2)
    p.add_argument("--mb", type=int, default=16, help="payload megabytes")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--device", action="store_true",
                   help="keep the default backend (real TPU) and run "
                        "in-process; requires --np 1")
    args = p.parse_args()

    if args.device:
        if args.np != 1:
            raise SystemExit("--device measures the single-chip eager "
                             "path; use --np 1")
        r0 = worker(args.mb * 1024 * 1024, args.iters, device=True)
    else:
        from horovod_tpu.runner import run

        results = run(worker, args=(args.mb * 1024 * 1024, args.iters),
                      np=args.np)
        r0 = results[0]
    for k, v in r0.items():
        unit = "ms" if k.endswith("ms_per_call") else "MB/s"
        print(f"{k}: {v:,.2f} {unit}")


if __name__ == "__main__":
    main()
