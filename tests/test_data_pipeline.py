"""Tier-1 input-pipeline suite (ISSUE 4): ShardedDataset sharding /
coverage / elastic-reshard invariants, PrefetchIterator determinism,
backpressure, exception propagation and leak-free shutdown, the train
step's donated input slot, and the runtime knobs — all CPU-runnable.
"""

import threading
import time

import numpy as np
import pytest

from horovod_tpu.data import (
    ArraySource,
    ParquetSource,
    PrefetchIterator,
    ShardedDataset,
    broadcast_seed,
    close_all_pipelines,
    default_input_threads,
    default_prefetch_depth,
)


def _dataset(n, batch, world, rank=0, seed=7, shuffle=True, data=None):
    if data is None:
        data = {"x": np.arange(n, dtype=np.int64),
                "y": np.arange(n, dtype=np.int64) * 10}
    return ShardedDataset(ArraySource(data), batch_size=batch, rank=rank,
                          world=world, seed=seed, shuffle=shuffle)


def _consume_indices(ds, epoch, start_sample=0, steps=None):
    out = []
    for k, idx in enumerate(ds.epoch_indices(epoch, start_sample)):
        if steps is not None and k >= steps:
            break
        out.append(idx)
    return out


class TestShardedDataset:
    def test_disjoint_shards_exact_coverage(self):
        """Every rank's per-epoch blocks are disjoint and their union
        is exactly the sample set — the no-duplicate, no-hole
        contract."""
        world, n, b = 4, 64, 4
        all_idx = []
        for r in range(world):
            ds = _dataset(n, b, world, rank=r)
            blocks = _consume_indices(ds, epoch=0)
            assert all(len(blk) == b for blk in blocks)
            all_idx.append(np.concatenate(blocks))
        for r in range(world):
            for s in range(r + 1, world):
                assert not set(all_idx[r]) & set(all_idx[s])
        assert sorted(np.concatenate(all_idx)) == list(range(n))

    def test_drop_remainder_zero_tail(self):
        """No ragged tail ever: with n not divisible by world*batch the
        final partial chunk is dropped, every batch stays full."""
        ds = _dataset(n=70, batch=4, world=2)
        blocks = _consume_indices(ds, epoch=0)
        assert ds.steps_per_epoch == 8          # 70 // 8
        assert len(blocks) == 8
        assert all(len(blk) == 4 for blk in blocks)

    def test_same_seed_same_order_across_ranks_and_epochs(self):
        a = _dataset(48, 4, 2, rank=0, seed=3)
        b = _dataset(48, 4, 2, rank=1, seed=3)
        # both ranks derive the identical global order: rank 1's block
        # at step k is the continuation of rank 0's
        for ia, ib in zip(a.epoch_indices(2), b.epoch_indices(2)):
            assert not set(ia) & set(ib)
        # deterministic: a rebuilt dataset replays the same order
        again = _dataset(48, 4, 2, rank=0, seed=3)
        for x, y in zip(a.epoch_indices(5), again.epoch_indices(5)):
            assert np.array_equal(x, y)
        # different epochs shuffle differently; different seeds too
        e0 = np.concatenate(_consume_indices(a, 0))
        e1 = np.concatenate(_consume_indices(a, 1))
        assert not np.array_equal(e0, e1)
        other = _dataset(48, 4, 2, rank=0, seed=4)
        assert not np.array_equal(
            e0, np.concatenate(_consume_indices(other, 0)))

    def test_no_shuffle_is_contiguous_ranges(self):
        """shuffle=False: each block is a literal index range — what
        maps onto the store's range reads."""
        ds = _dataset(32, 4, 2, rank=1, shuffle=False)
        for k, blk in enumerate(ds.epoch_indices(0)):
            lo = k * 8 + 4
            assert np.array_equal(blk, np.arange(lo, lo + 4))

    def test_rank_materializes_only_its_fraction(self):
        """The no-full-copy guarantee: one rank's epoch fetches ~1/N of
        the rows through the source, never the dataset."""
        n, world = 96, 4
        src = ArraySource({"x": np.arange(n)})
        ds = ShardedDataset(src, batch_size=4, rank=2, world=world,
                            seed=1)
        for batch in ds.epoch(0):
            assert len(batch["x"]) == 4
        assert src.rows_fetched == n // world

    def test_elastic_reshard_2_to_4_no_replay_no_dup(self):
        """The acceptance invariant: consume part of an epoch at world
        2, commit the position, reshard to world 4, finish the epoch —
        union of all consumed samples is exact, nothing twice."""
        n, b, seed = 64, 2, 11
        gen1 = [_dataset(n, b, 2, rank=r, seed=seed) for r in range(2)]
        steps_before = 6
        consumed = [np.concatenate(_consume_indices(d, 0, steps=steps_before))
                    for d in gen1]
        pos = gen1[0].position_after(steps_before)      # 6 * 2 * 2 = 24
        st = gen1[0].state_dict(epoch=0, step=steps_before)
        # new generation: same source/seed, world 4 — via reshard()
        gen2 = [gen1[0].reshard(rank=r, world=4) for r in range(4)]
        epoch, resume = gen2[0].load_position(st)
        assert (epoch, resume) == (0, pos)
        for d in gen2:
            consumed.append(
                np.concatenate(_consume_indices(d, epoch, resume)))
        flat = np.concatenate(consumed)
        assert len(flat) == len(set(flat.tolist())), "a sample replayed"
        assert sorted(flat) == list(range(n)), "coverage hole"

    def test_position_is_world_size_independent(self):
        d2 = _dataset(64, 4, 2)
        d4 = _dataset(64, 4, 4)
        # 4 steps at world 2 == 2 steps at world 4: same global position
        assert d2.position_after(4) == d4.position_after(2)

    def test_load_position_checks_seed(self):
        ds = _dataset(32, 4, 2, seed=5)
        st = ds.state_dict(epoch=1, step=2)
        other = _dataset(32, 4, 2, seed=6)
        with pytest.raises(ValueError, match="seed"):
            other.load_position(st)

    def test_iter_epochs_rolls_over(self):
        ds = _dataset(16, 4, 2, rank=0)     # 2 steps/epoch
        it = ds.iter_epochs()
        batches = [next(it) for _ in range(5)]   # crosses 2 epochs
        assert all(len(b["x"]) == 4 for b in batches)

    def test_validation(self):
        with pytest.raises(ValueError, match="batch_size"):
            _dataset(16, 0, 1)
        with pytest.raises(ValueError, match="rank"):
            _dataset(16, 4, 2, rank=2)
        with pytest.raises(ValueError, match="length"):
            ArraySource({"x": np.arange(4), "y": np.arange(5)})

    def test_broadcast_seed_local(self):
        assert broadcast_seed(123) == 123
        s = broadcast_seed()
        assert isinstance(s, int) and s >= 0


class TestParquetSource:
    @pytest.fixture
    def store_dir(self, tmp_path):
        import pandas as pd

        from horovod_tpu.spark.store import LocalStore

        store = LocalStore(str(tmp_path))
        df = pd.DataFrame({"x": np.arange(40, dtype=np.int64),
                           "y": np.arange(40, dtype=np.int64) * 3})
        path = store.get_train_data_path("rr")
        store.write_dataframe(df, path, rows_per_group=5)
        return path

    def test_shard_reads_only_its_groups(self, store_dir):
        src = ParquetSource(store_dir)
        assert len(src) == 40
        ds = ShardedDataset(src, batch_size=5, rank=0, world=2,
                            seed=0, shuffle=False)
        got = [b for b in ds.epoch(0)]
        assert len(got) == 4                       # 40 / (2*5)
        # rank 0 reads rows [0,5)+[10,15)+... = 20 rows; group-pruned
        # IO touches exactly the groups those ranges live in
        assert src.rows_fetched == 20
        assert np.concatenate(
            [np.asarray(b["x"]) for b in got]).tolist() == \
            [i for k in range(4) for i in range(k * 10, k * 10 + 5)]

    def test_shuffled_shard_stays_fractional(self, store_dir):
        src = ParquetSource(store_dir)
        ds = ShardedDataset(src, batch_size=5, rank=1, world=2, seed=9)
        rows = sum(len(b) for b in ds.epoch(0))
        assert rows == 20
        # shuffled gathers may touch extra groups, but each take
        # materializes only the groups its 5 indices land in (<= 5
        # groups of 5 rows), never the whole dataset per batch
        assert src.rows_fetched <= 4 * 25


def _ints(n):
    for i in range(n):
        yield np.full((2,), i, dtype=np.int64)


class TestPrefetchIterator:
    def test_order_and_determinism_at_any_depth(self):
        """Same source ⇒ same batch order no matter the depth/threads
        — prefetching must never reorder the stream."""
        outs = []
        for depth, threads in ((1, 1), (2, 2), (8, 4)):
            with PrefetchIterator(_ints(20), depth=depth,
                                  threads=threads) as feed:
                outs.append([int(b[0]) for b in feed])
        assert outs[0] == list(range(20))
        assert outs[0] == outs[1] == outs[2]

    def test_sharded_batches_identical_through_any_depth(self):
        """The satellite contract verbatim: same seed ⇒ same batches
        at prefetch depth 1 and 8."""
        def run(depth):
            ds = _dataset(48, 4, 2, rank=0, seed=13)
            with PrefetchIterator(ds.epoch(0), depth=depth) as feed:
                return [np.asarray(b["x"]) for b in feed]

        a, b = run(1), run(8)
        assert len(a) == 6
        assert all(np.array_equal(x, y) for x, y in zip(a, b))

    def test_place_runs_on_worker_threads(self):
        seen = set()

        def place(x):
            seen.add(threading.current_thread().name)
            return x * 2

        with PrefetchIterator(_ints(8), place=place, depth=2) as feed:
            got = [int(b[0]) for b in feed]
        assert got == [2 * i for i in range(8)]
        assert all(name.startswith("hvd-input") for name in seen)

    def test_bounded_queue_backpressure(self):
        """A slow consumer must cap how far the feeder runs ahead:
        at most depth + 1 items pulled beyond what was consumed."""
        pulled = []

        def src():
            for i in range(100):
                pulled.append(i)
                yield i

        feed = PrefetchIterator(src(), depth=3, threads=1)
        try:
            for consumed in range(1, 6):
                next(feed)
                time.sleep(0.05)       # let the feeder run ahead
                assert len(pulled) <= consumed + 3 + 1, \
                    f"feeder ran {len(pulled) - consumed} ahead"
        finally:
            feed.close()

    def test_source_exception_propagates(self):
        def src():
            yield np.zeros(1)
            yield np.zeros(1)
            raise RuntimeError("upstream reader died")

        feed = PrefetchIterator(src(), depth=2)
        next(feed), next(feed)
        with pytest.raises(RuntimeError, match="upstream reader died"):
            next(feed)
        assert feed.closed

    def test_place_exception_propagates(self):
        def place(x):
            if int(x[0]) == 2:
                raise ValueError("bad batch assembly")
            return x

        feed = PrefetchIterator(_ints(6), place=place, depth=2)
        with pytest.raises(ValueError, match="bad batch assembly"):
            for _ in range(6):
                next(feed)
        assert feed.closed

    def _input_threads(self):
        return [t for t in threading.enumerate()
                if t.name.startswith("hvd-input") and t.is_alive()]

    def test_shutdown_without_leak(self):
        feed = PrefetchIterator(_ints(50), depth=2, threads=3,
                                name="leakcheck")
        next(feed)
        assert self._input_threads()
        feed.close()
        assert not self._input_threads(), \
            "threads survived close()"
        feed.close()      # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            next(feed)

    def test_close_unblocks_parked_feeder(self):
        """close() while the feeder is blocked on a full queue must
        return promptly and leave nothing running."""
        feed = PrefetchIterator(_ints(1000), depth=1, threads=1)
        time.sleep(0.1)                      # feeder parks on put()
        t0 = time.perf_counter()
        feed.close()
        assert time.perf_counter() - t0 < 2.0
        assert not self._input_threads()

    def test_close_during_inflight_worker_exception(self):
        """The documented contract (prefetch.py): a deferred worker
        exception is raised only from iteration — close() on an
        iterator whose feeder/pool already hit an error must return
        cleanly AND leak-free, dropping the pending error."""
        import queue as queue_mod

        gate = threading.Event()

        def src():
            yield np.zeros(1)
            gate.wait(5.0)               # let the consumer take batch 0
            raise RuntimeError("in-flight source failure")

        feed = PrefetchIterator(src(), depth=2, name="inflightclose")
        next(feed)                        # batch 0 consumed
        gate.set()
        # wait until the failure is actually queued (in-flight, undelivered)
        deadline = time.monotonic() + 5.0
        while feed._queue.empty() and time.monotonic() < deadline:
            time.sleep(0.01)
        feed.close()                      # must NOT raise the deferred error
        assert feed.closed
        assert not self._input_threads(), "threads survived close()"
        with pytest.raises(queue_mod.Empty):
            feed._queue.get_nowait()      # error sentinel was drained

    def test_close_during_inflight_place_exception(self):
        """Same contract for an assembly (place) failure pending in the
        worker pool: close() swallows it, threads exit."""
        def place(x):
            if int(x[0]) >= 1:
                raise ValueError("bad assembly in flight")
            return x

        feed = PrefetchIterator(_ints(10), place=place, depth=3,
                                name="placeclose")
        next(feed)                        # batch 0 was fine
        time.sleep(0.1)                   # failing futures queue up
        feed.close()                      # no raise
        assert feed.closed
        assert not self._input_threads()

    def test_exhaustion_closes(self):
        feed = PrefetchIterator(_ints(3), depth=4)
        assert [int(b[0]) for b in feed] == [0, 1, 2]
        assert feed.closed
        with pytest.raises(StopIteration):
            next(feed)

    def test_stall_accounting(self):
        def slow():
            for i in range(3):
                time.sleep(0.03)
                yield i

        with PrefetchIterator(slow(), depth=2) as feed:
            list(feed)
            assert feed.batches == 3
            assert feed.stall_s > 0.0

    def test_close_all_pipelines(self):
        feeds = [PrefetchIterator(_ints(100), depth=1, threads=1)
                 for _ in range(3)]
        feeds[0].close()
        assert close_all_pipelines() == 2
        assert all(f.closed for f in feeds)
        assert not self._input_threads()

    def test_depth_validation(self):
        with pytest.raises(ValueError, match="depth"):
            PrefetchIterator(_ints(1), depth=0)
        with pytest.raises(ValueError, match="threads"):
            PrefetchIterator(_ints(1), threads=0)


class TestKnobs:
    def test_env_defaults(self, monkeypatch):
        import horovod_tpu as hvd

        # a direct env read is the contract *before* init; another
        # file's test on this worker may have left a runtime up
        hvd.shutdown()
        monkeypatch.setenv("HOROVOD_PREFETCH_DEPTH", "5")
        monkeypatch.setenv("HOROVOD_INPUT_THREADS", "3")
        assert default_prefetch_depth() == 5
        assert default_input_threads() == 3

    def test_config_fields(self, monkeypatch):
        from horovod_tpu.runtime.config import Config

        monkeypatch.setenv("HOROVOD_PREFETCH_DEPTH", "7")
        monkeypatch.setenv("HOROVOD_INPUT_THREADS", "4")
        cfg = Config.from_env()
        assert cfg.prefetch_depth == 7
        assert cfg.input_threads == 4
        monkeypatch.delenv("HOROVOD_PREFETCH_DEPTH")
        monkeypatch.delenv("HOROVOD_INPUT_THREADS")
        cfg = Config.from_env()
        assert cfg.prefetch_depth == 2
        assert cfg.input_threads == 2


class TestDonatedInputSlot:
    def test_pipeline_fed_step_with_donated_batch(self, hvd_runtime):
        """End-to-end: ShardedDataset -> PrefetchIterator (place =
        shard_batch) -> DistributedTrainStep(donate_batch=True).  Every
        call gets fresh buffers, so the donated input slot is legal and
        the loop trains."""
        import jax.numpy as jnp
        import optax

        hvd = hvd_runtime

        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"]
            return jnp.mean((pred - batch["y"]) ** 2)

        step = hvd.DistributedTrainStep(loss_fn, optax.sgd(0.05),
                                        donate_batch=True)
        assert step.donates_batch
        from jax.sharding import NamedSharding

        assert isinstance(step.batch_sharding, NamedSharding)
        params, opt = step.init(
            {"w": np.zeros((4, 1), np.float32)})
        rng = np.random.RandomState(0)
        w_true = rng.randn(4, 1).astype(np.float32)
        n = 128
        x = rng.randn(n, 4).astype(np.float32)
        data = {"x": x, "y": x @ w_true}
        ds = ShardedDataset(ArraySource(data), batch_size=16, rank=0,
                            world=1, seed=0)
        losses = []
        with PrefetchIterator(ds.iter_epochs(), place=step.shard_batch,
                              depth=2) as feed:
            for _ in range(24):
                params, opt, loss = step(params, opt, next(feed))
                losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, \
            f"no learning through the pipeline: {losses[0]} -> " \
            f"{losses[-1]}"

    def test_donated_batch_is_a_property_of_the_step(self, hvd_runtime):
        hvd = hvd_runtime
        import jax.numpy as jnp
        import optax

        step = hvd.DistributedTrainStep(
            lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2),
            optax.sgd(0.1), donate_batch=True)
        assert step.donates_batch is True
