"""Measured H2D/compute overlap for the input feed.

The input-pipeline claim is that batch ``k+1``'s host→device transfer
hides under batch ``k``'s compute.  Host→device copies never appear in
HLO, so unlike the gradient exchange (``utils/overlap_probe.py``, whose
collectives are pinned in the compiled program) the input claim must be
verified by *timing the transfer against an in-flight step* — the
timeline view, reduced to three numbers:

* ``put_s`` — placing one host batch on the device(s), fenced;
* ``step_s`` — one train-step call on an already-resident batch,
  fenced on a host fetch of its scalar (the bench discipline);
* ``both_s`` — dispatch the step, then immediately issue the *next*
  batch's placement while the step is in flight, fence both.

If the runtime serializes them, ``both ≈ step + put``; if the transfer
fully hides, ``both ≈ max(step, put)``.  The achieved fraction is::

    h2d_overlap = (step_s + put_s - both_s) / min(step_s, put_s)

clamped to [0, 1] — the same estimator the exchange probe uses, so the
two overlap numbers in a BENCH artifact are directly comparable.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import numpy as np


@dataclasses.dataclass
class H2dReport:
    put_s: float
    step_s: float
    both_s: float
    overlap_fraction: float

    def as_bench_fields(self, prefix: str = "") -> dict:
        return {
            f"{prefix}h2d_overlap_fraction": round(self.overlap_fraction,
                                                   4),
            f"{prefix}h2d_put_s": round(self.put_s, 6),
            f"{prefix}h2d_step_s": round(self.step_s, 6),
        }


def fence_batch(batch) -> None:
    """Wait for a placed batch's transfer: host-fetch one element of
    one leaf (completes only after the copy lands on device)."""
    leaf = jax.tree_util.tree_leaves(batch)[0]
    np.asarray(jax.device_get(leaf.ravel()[:1]))


def measure_h2d_overlap(run_step: Callable, make_batch: Callable,
                        place: Callable, iters: int = 3,
                        warmup: int = 1) -> H2dReport:
    """Time the three phases and return the achieved overlap.

    ``make_batch() -> host batch`` (fresh each call — the probe feeds
    the step real, distinct batches so donation-enabled steps stay
    legal); ``place(host) -> device batch``; ``run_step(device_batch)
    -> fetchable scalar`` (own the train state internally — the probe
    treats the step as a black box)."""
    def t_put():
        b = make_batch()
        t0 = time.perf_counter()
        fence_batch(place(b))
        return time.perf_counter() - t0

    def t_step():
        b = place(make_batch())
        fence_batch(b)
        t0 = time.perf_counter()
        float(np.asarray(jax.device_get(run_step(b))))
        return time.perf_counter() - t0

    def t_both():
        b = place(make_batch())
        fence_batch(b)
        nxt = make_batch()
        t0 = time.perf_counter()
        out = run_step(b)            # async dispatch
        placed = place(nxt)          # H2D issued while the step flies
        fence_batch(placed)
        float(np.asarray(jax.device_get(out)))
        return time.perf_counter() - t0

    def median(fn):
        for _ in range(warmup):
            fn()
        return float(np.median([fn() for _ in range(iters)]))

    put_s, step_s, both_s = median(t_put), median(t_step), median(t_both)
    denom = min(put_s, step_s)
    frac = (put_s + step_s - both_s) / denom if denom > 0 else 0.0
    return H2dReport(put_s=put_s, step_s=step_s, both_s=both_s,
                     overlap_fraction=float(np.clip(frac, 0.0, 1.0)))
