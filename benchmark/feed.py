"""The one generator of training input, driven by the job file.

A configuration supplies ``sample(rng, n)`` (``n`` host samples drawn
from its seeded distribution); the job says how they reach the step:

* ``"feed": "host"`` — ``dataset_samples_per_chip`` samples stay on the
  host and every step's global batch travels the program's own pipeline,
  ``ShardedDataset`` → ``PrefetchIterator`` → ``step.shard_batch``, at
  its defaults;
* ``"feed": "resident"`` — ``ring_batches`` distinct global batches are
  placed on the device once and handed out in turn: the input layer is
  bypassed.

Either way a batch is new to the step that gets it (no step sees the
batch of the step before), and the same seed gives the same batches.
"""

from __future__ import annotations

import itertools

import jax
import numpy as np


class ResidentRing:
    """``ring_batches`` device batches, in turn, for ever."""

    stall_samples: tuple = ()          # it never waits: nothing to read

    def __init__(self, batches: list):
        self._cycle = itertools.cycle(batches)

    def __next__(self):
        return next(self._cycle)

    def close(self) -> None:
        pass


def make_feed(job: dict, sample, step, seed: int, global_batch: int):
    """The cell's batch iterator: ``next(feed)`` is a device batch in the
    step's sharding; ``feed.stall_samples`` holds the seconds each
    ``next`` blocked (host feed); ``feed.close()`` stops its threads."""
    rng = np.random.default_rng([seed, 1])
    chips = job["chips"]
    if job["feed"] == "resident":
        data = sample(rng, job["ring_batches"] * global_batch)
        batches = [step.shard_batch(jax.tree_util.tree_map(
            lambda a: a[i * global_batch:(i + 1) * global_batch], data))
            for i in range(job["ring_batches"])]
        return ResidentRing(batches)
    if job["feed"] == "host":
        from horovod_tpu.data import (
            ArraySource,
            PrefetchIterator,
            ShardedDataset,
        )

        data = sample(rng, job["dataset_samples_per_chip"] * chips)
        # one process feeds the whole mesh: one reading rank, global
        # batches (ShardedDataset's batch_size is per rank)
        dataset = ShardedDataset(ArraySource(data), batch_size=global_batch,
                                 rank=0, world=1, seed=seed)
        return PrefetchIterator(dataset.iter_epochs(),
                                place=step.shard_batch, name="benchmark")
    raise ValueError(f"job feed must be 'host' or 'resident', got "
                     f"{job['feed']!r}")
