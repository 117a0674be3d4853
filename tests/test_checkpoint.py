"""Checkpointer: save/restore round-trip, retention, latest-step, the
async writer contract, and sharded (ZeRO) save/restore across world
sizes (docs/warmstart.md)."""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.ops import collectives as C


def make_state(v=1.0):
    return {"params": {"w": jnp.full((4, 4), v), "b": jnp.zeros((4,))},
            "step": int(v)}


class TestCheckpointer:
    @pytest.mark.parametrize("use_orbax", [False, None])
    def test_roundtrip(self, tmp_path, use_orbax):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=use_orbax)
        state = make_state(3.0)
        assert ckpt.save(0, state)
        restored = ckpt.restore(make_state(0.0))
        np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 3.0)
        assert restored["step"] == 3

    def test_latest_and_retention(self, tmp_path):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           max_to_keep=2, use_orbax=False)
        for s in range(5):
            ckpt.save(s, make_state(float(s)))
        assert ckpt.latest_step() == 4
        assert sorted(ckpt.all_steps()) == [3, 4]
        restored = ckpt.restore(make_state(0.0))
        np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 4.0)

    def test_restore_and_broadcast_single_process(self, tmp_path):
        hvd.init()
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        ckpt.save(7, make_state(7.0))
        restored = ckpt.restore_and_broadcast(make_state(0.0))
        np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 7.0)

    def test_missing_checkpoint_raises(self, tmp_path):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "none"),
                                           use_orbax=False)
        with pytest.raises(FileNotFoundError):
            ckpt.restore(make_state())


class TestAsyncSave:
    def test_roundtrip_through_background_writer(self, tmp_path):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False,
                                           async_save=True)
        assert ckpt.save(0, make_state(9.0))
        ckpt.wait()
        assert ckpt.last_stall_s is not None   # the D2H cut was timed
        assert ckpt.last_write_s is not None   # the background write too
        restored = ckpt.restore(make_state(0.0))
        np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 9.0)

    def test_reads_see_pending_write(self, tmp_path):
        # read-your-writes: restore()/all_steps() barrier on the writer,
        # so a save followed immediately by a read never misses
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        ckpt.save(3, make_state(3.0))
        assert ckpt.latest_step() == 3
        restored = ckpt.restore(make_state(0.0))
        assert restored["step"] == 3

    def test_save_stalls_only_for_the_copy(self, tmp_path, monkeypatch):
        # slow the background serialization down; save() must still
        # return fast (it blocks only for the host copy), and wait()
        # must block until the write finished
        import horovod_tpu.checkpoint as ckpt_mod

        real = ckpt_mod._atomic_write
        started = threading.Event()

        def slow_write(path, payload):
            started.set()
            time.sleep(0.3)
            real(path, payload)

        monkeypatch.setattr(ckpt_mod, "_atomic_write", slow_write)
        ckpt = ckpt_mod.Checkpointer(str(tmp_path / "ck"),
                                     use_orbax=False)
        t0 = time.perf_counter()
        ckpt.save(0, make_state(1.0))
        stall = time.perf_counter() - t0
        assert started.wait(5.0)
        assert stall < 0.25            # the 0.3 s write is off the clock
        t0 = time.perf_counter()
        ckpt.wait()
        assert time.perf_counter() - t0 > 0.05   # wait() really blocked
        assert ckpt.last_write_s >= 0.3

    def test_writer_error_surfaces_at_wait(self, tmp_path):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        # lambdas survive the host copy but cannot pickle
        ckpt.save(0, {"fn": lambda: None})
        with pytest.raises(Exception):
            ckpt.wait()
        # STICKY: every subsequent save/wait/close path re-raises until
        # the caller acknowledges — a lost checkpoint must not be
        # discoverable only by whoever hit the barrier first
        with pytest.raises(Exception):
            ckpt.wait()
        with pytest.raises(Exception):
            ckpt.save(1, make_state(2.0))
        with pytest.raises(Exception):
            ckpt.close()
        assert ckpt.clear_error() is not None
        # acknowledged: the next save/wait cycle is clean
        ckpt.save(1, make_state(2.0))
        ckpt.wait()
        assert ckpt.latest_step() == 1

    def test_failing_write_leaves_no_visible_half_step(self, tmp_path,
                                                       monkeypatch):
        # the write dies mid-stream (tmp written, never renamed): no
        # reader may ever see the step, and the error must surface
        import horovod_tpu.checkpoint as ckpt_mod

        monkeypatch.setenv("HOROVOD_RETRY_MAX_ATTEMPTS", "1")

        def dying_write(path, payload):
            d = os.path.dirname(path)
            with open(os.path.join(d, ".tmp.state.pkl.999"), "wb") as f:
                f.write(b"torso")
            raise OSError("disk pulled mid-write")

        monkeypatch.setattr(ckpt_mod, "_atomic_write", dying_write)
        root = tmp_path / "ck"
        ckpt = ckpt_mod.Checkpointer(str(root), use_orbax=False)
        ckpt.save(3, make_state(1.0))
        with pytest.raises(OSError, match="disk pulled"):
            ckpt.wait()
        ckpt.clear_error()
        assert ckpt.all_steps() == []          # half-step invisible
        with pytest.raises(FileNotFoundError):
            ckpt.restore(make_state(0.0))
        # the torso exists on disk but only as an ignored tmp dropping
        assert os.listdir(root / "step_3") == [".tmp.state.pkl.999"]

    def test_transient_write_error_is_retried(self, tmp_path,
                                              monkeypatch):
        # one ENOSPC-style hiccup, then success: the writer-thread retry
        # absorbs it and the checkpoint lands durably with no error
        import horovod_tpu.checkpoint as ckpt_mod

        real = ckpt_mod._atomic_write
        calls = []

        def flaky_write(path, payload):
            calls.append(path)
            if len(calls) == 1:
                raise OSError("transient")
            real(path, payload)

        monkeypatch.setenv("HOROVOD_RETRY_BASE_S", "0.01")
        monkeypatch.setattr(ckpt_mod, "_atomic_write", flaky_write)
        ckpt = ckpt_mod.Checkpointer(str(tmp_path / "ck"),
                                     use_orbax=False)
        ckpt.save(0, make_state(6.0))
        ckpt.wait()                            # no raise: retry recovered
        assert len(calls) == 2
        restored = ckpt.restore(make_state(0.0))
        np.testing.assert_allclose(
            np.asarray(restored["params"]["w"]), 6.0)

    def test_close_is_final_barrier(self, tmp_path):
        root = tmp_path / "ck"
        ckpt = hvd.checkpoint.Checkpointer(str(root), use_orbax=False)
        ckpt.save(0, make_state(2.0))
        ckpt.close()                           # joins + surfaces errors
        assert os.path.exists(root / "step_0" / "state.pkl")

    def test_snapshot_owns_host_arrays(self, tmp_path, monkeypatch):
        # the immune-after-return contract must hold for numpy leaves
        # too: mutating the caller's host arrays after save() returns
        # must not tear the background pickle
        import horovod_tpu.checkpoint as ckpt_mod

        real = ckpt_mod._atomic_write
        gate = threading.Event()

        def gated_write(path, payload):
            gate.wait(5.0)
            real(path, payload)

        monkeypatch.setattr(ckpt_mod, "_atomic_write", gated_write)
        ckpt = ckpt_mod.Checkpointer(str(tmp_path / "ck"),
                                     use_orbax=False)
        state = {"w": np.full((4,), 1.0, np.float32)}
        ckpt.save(0, state)
        state["w"][:] = -99.0          # caller reuses its buffer
        gate.set()
        ckpt.wait()
        restored = ckpt.restore({"w": np.zeros((4,), np.float32)})
        np.testing.assert_allclose(restored["w"], 1.0)

    def test_no_tmp_droppings_and_atomic_layout(self, tmp_path):
        root = tmp_path / "ck"
        ckpt = hvd.checkpoint.Checkpointer(str(root), use_orbax=False)
        ckpt.save(0, make_state(1.0))
        ckpt.wait()
        files = os.listdir(root / "step_0")
        assert files == ["state.pkl"]

    def test_crashed_partial_write_is_invisible(self, tmp_path):
        root = tmp_path / "ck"
        ckpt = hvd.checkpoint.Checkpointer(str(root), use_orbax=False)
        ckpt.save(0, make_state(1.0))
        ckpt.wait()
        # simulate a crash mid-write of step 1: tmp file exists, no rename
        os.makedirs(root / "step_1", exist_ok=True)
        with open(root / "step_1" / ".tmp.state.pkl.999", "wb") as f:
            f.write(b"partial")
        assert ckpt.all_steps() == [0]   # the torso never surfaces
        restored = ckpt.restore(make_state(0.0))
        np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 1.0)

    def test_bfloat16_leaves_roundtrip(self, tmp_path):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        state = {"w": jnp.full((4, 2), 1.5, jnp.bfloat16),
                 "nu": jnp.arange(6, dtype=jnp.bfloat16)}
        ckpt.save(0, state)
        restored = ckpt.restore(state)
        assert restored["w"].dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(restored["w"], np.float32), 1.5)
        np.testing.assert_allclose(
            np.asarray(restored["nu"], np.float32), np.arange(6))

    def test_sync_mode_is_durable_on_return(self, tmp_path):
        root = tmp_path / "ck"
        ckpt = hvd.checkpoint.Checkpointer(str(root), use_orbax=False,
                                           async_save=False)
        ckpt.save(0, make_state(4.0))
        # no wait(): the file is already there
        assert os.path.exists(root / "step_0" / "state.pkl")


def _shard_trees(leaves, world):
    """Per-rank ZeRO state trees for ``leaves``: the fusion spec's flat
    buffer (concat + zero-pad to a world multiple), sliced per rank —
    exactly the shape ``sharded_distributed_update`` keeps per rank."""
    spec = C.make_fusion_spec(leaves, world)
    flats = {}
    for g in spec.groups:
        flat = np.concatenate(
            [np.ravel(np.asarray(leaves[i])) for i in g.indices])
        flats[g.key] = np.concatenate(
            [flat, np.zeros(g.padded - flat.size, flat.dtype)])
    trees = []
    for r in range(world):
        trees.append({k: {"m": v[r * (v.size // world):
                                 (r + 1) * (v.size // world)],
                          "count": np.int32(7)}
                      for k, v in flats.items()})
    return spec, flats, trees


class TestShardedCheckpoint:
    LEAVES = [np.arange(10, dtype=np.float32),
              np.arange(6, dtype=np.float32).reshape(2, 3) + 100.0]

    def _save_all(self, tmp_path, world):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        spec, flats, trees = _shard_trees(self.LEAVES, world)
        for r, tree in enumerate(trees):
            ckpt.save_sharded(0, tree, r, world)
            ckpt.wait()
        return ckpt, spec, flats, trees

    def test_same_world_roundtrip(self, tmp_path):
        world = 4
        ckpt, spec, flats, trees = self._save_all(tmp_path, world)
        for r in range(world):
            target = {k: {"m": np.zeros_like(v["m"]),
                          "count": np.int32(0)}
                      for k, v in trees[r].items()}
            out = ckpt.restore_sharded(target, r, world)
            for k in trees[r]:
                np.testing.assert_array_equal(out[k]["m"],
                                              trees[r][k]["m"])
                assert out[k]["count"] == 7

    @pytest.mark.parametrize("new_world", [2, 8, 3])
    def test_resharded_restore(self, tmp_path, new_world):
        # save at world 4, restore at 2 / 8 / 3 (the non-dividing case
        # exercises pad-trim): every new shard must equal the slice of
        # the re-padded full flat buffer
        ckpt, spec, flats, _ = self._save_all(tmp_path, world=4)
        new_spec = C.make_fusion_spec(self.LEAVES, new_world)
        for g in new_spec.groups:
            full = flats[g.key]          # old padded buffer
            if g.padded >= full.size:
                full = np.concatenate(
                    [full, np.zeros(g.padded - full.size, full.dtype)])
            else:
                full = full[:g.padded]
            for r in range(new_world):
                target = {k2.key: {"m": np.zeros((k2.shard,), np.float32),
                                   "count": np.int32(0)}
                          for k2 in new_spec.groups}
                out = ckpt.restore_sharded(target, r, new_world)
                np.testing.assert_array_equal(
                    out[g.key]["m"],
                    full[r * g.shard:(r + 1) * g.shard])
                assert out[g.key]["count"] == 7   # scalar: rank 0 wins

    def test_plain_restore_of_sharded_step_raises_clear_error(
            self, tmp_path):
        # restore() must not fall through to the orbax branch (confusing
        # path error / ImportError) when the step holds only shard files
        ckpt, _, _, trees = self._save_all(tmp_path, world=4)
        with pytest.raises(ValueError, match="restore_sharded"):
            ckpt.restore(trees[0])

    def test_trimming_nonzero_state_raises(self, tmp_path):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        # 12-long buffer, all non-zero — restoring into 2 shards of 5
        # (10 < 12) would silently drop real state
        for r in range(4):
            ckpt.save_sharded(0, {"m": np.ones(3, np.float32)}, r, 4)
            ckpt.wait()
        with pytest.raises(ValueError, match="non-zero state"):
            ckpt.restore_sharded({"m": np.zeros(5, np.float32)}, 0, 2)

    def test_incomplete_shard_set_raises(self, tmp_path):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        ckpt.save_sharded(0, {"m": np.ones(3, np.float32)}, 0, 4)
        ckpt.wait()
        ckpt.save_sharded(0, {"m": np.ones(3, np.float32)}, 2, 4)
        ckpt.wait()
        with pytest.raises(FileNotFoundError, match=r"missing shard"):
            ckpt.restore_sharded({"m": np.zeros(3, np.float32)}, 0, 4)

    def test_mixed_world_overwrite_raises(self, tmp_path):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        for r in range(2):
            ckpt.save_sharded(0, {"m": np.ones(4, np.float32)}, r, 2)
            ckpt.wait()
        ckpt.save_sharded(0, {"m": np.ones(2, np.float32)}, 3, 4)
        ckpt.wait()
        with pytest.raises(ValueError, match="mixed shard_count"):
            ckpt.restore_sharded({"m": np.zeros(4, np.float32)}, 0, 2)

    def test_real_sharded_optimizer_state_reshards(self, tmp_path):
        """End-to-end: the per-rank state of sharded_distributed_update
        (optax.adam over fusion-template shards) saved at world 4 and
        restored at world 8 slices identically to re-running the spec
        math at world 8."""
        from horovod_tpu.optim.optimizer import sharded_distributed_update

        params = {"w": jnp.arange(24, dtype=jnp.float32).reshape(4, 6),
                  "b": jnp.arange(5, dtype=jnp.float32)}
        leaves = jax.tree_util.tree_leaves(params)
        # the packed layout, asked for by what keeps it: an explicit
        # bucket size that holds both leaves in one group (the plain
        # call's state is slabs in leaf shape: the tests below)
        opt4 = sharded_distributed_update(optax.adam(1e-2), world=4,
                                          bucket_bytes=1 << 20)
        state4 = opt4.init(params)
        # populate each rank's mu with its slice of a known full buffer
        spec4 = C.make_fusion_spec(leaves, 4)
        full = {g.key: np.arange(g.padded, dtype=np.float32) + 1.0
                for g in spec4.groups}
        # zero the fusion padding: the re-shard contract's tail invariant
        total = {g.key: sum(g.sizes) for g in spec4.groups}
        for k in full:
            full[k][total[k]:] = 0.0
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        for r in range(4):
            rank_state = jax.tree_util.tree_map(np.asarray, state4)
            mu = {g.key: full[g.key][r * g.shard:(r + 1) * g.shard]
                  for g in spec4.groups}
            rank_state = (rank_state.inner[0]._replace(
                mu=mu, nu=jax.tree_util.tree_map(np.zeros_like, mu)),
                rank_state.inner[1])
            ckpt.save_sharded(0, rank_state, r, 4)
            ckpt.wait()
        opt8 = sharded_distributed_update(optax.adam(1e-2), world=8,
                                          bucket_bytes=1 << 20)
        spec8 = C.make_fusion_spec(leaves, 8)
        template = jax.tree_util.tree_map(np.asarray, opt8.init(params))
        template = (template.inner[0], template.inner[1])
        for r in (0, 5, 7):
            out = ckpt.restore_sharded(template, r, 8)
            for g in spec8.groups:
                want = full[g.key]
                if g.padded > want.size:
                    want = np.concatenate(
                        [want, np.zeros(g.padded - want.size,
                                        want.dtype)])
                else:
                    want = want[:g.padded]
                np.testing.assert_array_equal(
                    out[0].mu[g.key],
                    want[r * g.shard:(r + 1) * g.shard])


    @staticmethod
    def _slab(full, world, rank):
        """This rank's slab of ``full`` as the plain exchange cuts it."""
        d = C.scatter_dimension(full.shape, world)
        rows = full.shape[d] // world
        return np.take(full, range(rank * rows, (rank + 1) * rows), axis=d)

    def _save_leafwise(self, tmp_path, params, full, world):
        """Save, rank by rank, a real plain-path state of
        sharded_distributed_update whose mu holds each rank's slab of
        the known ``full`` arrays."""
        from horovod_tpu.optim.optimizer import sharded_distributed_update

        opt = sharded_distributed_update(optax.adam(1e-2), world=world,
                                         hierarchy="flat")
        state = jax.tree_util.tree_map(np.asarray, opt.init(params))
        assert {k: v.shape for k, v in state.inner[0].mu.items()} == \
            {k: self._slab(v, world, 0).shape for k, v in full.items()}
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        for r in range(world):
            mu = {k: self._slab(v, world, r) for k, v in full.items()}
            ckpt.save_sharded(0, (state.inner[0]._replace(
                mu=mu, nu=jax.tree_util.tree_map(np.zeros_like, mu)),
                state.inner[1]), r, world)
            ckpt.wait()
        return ckpt

    @pytest.mark.parametrize("world", [2, 8])
    def test_leafwise_slabs_reshard_across_worlds(self, tmp_path, world):
        """The plain exchange's state is slabs in leaf shape: saved at
        world 4, a leaf's slabs restore at world 2 and 8 by
        concatenation along the one dimension in which saved and target
        shapes differ — dimension 0 of ``w``, dimension 1 of ``x``
        (whose dimension 0, 6, neither 4 nor 8 divides), the only one
        of ``v``."""
        from horovod_tpu.optim.optimizer import sharded_distributed_update

        params = {"w": jnp.zeros((8, 6)), "v": jnp.zeros((16,)),
                  "x": jnp.zeros((6, 8))}
        if world == 2:
            # world 2 divides x's dimension 0: it would cut another
            # dimension than world 4 did (refused: the test below)
            del params["x"]
        full = {k: np.arange(v.size, dtype=np.float32).reshape(v.shape)
                + 1.0 for k, v in params.items()}
        ckpt = self._save_leafwise(tmp_path, params, full, 4)
        opt = sharded_distributed_update(optax.adam(1e-2), world=world,
                                         hierarchy="flat")
        template = jax.tree_util.tree_map(np.asarray, opt.init(params))
        template = (template.inner[0], template.inner[1])
        for r in range(world):
            out = ckpt.restore_sharded(template, r, world)
            for k in params:
                np.testing.assert_array_equal(
                    out[0].mu[k], self._slab(full[k], world, r), err_msg=k)
                assert out[0].mu[k].shape == template[0].mu[k].shape

    def test_leafwise_reshard_refuses_another_scatter_dimension(
            self, tmp_path):
        """World 4 cuts a (6, 8) leaf along dimension 1, world 2 along
        dimension 0: the saved slabs cannot be the target's, and the
        restore says which leaf and both shapes instead of slicing
        something."""
        from horovod_tpu.optim.optimizer import sharded_distributed_update

        params = {"x": jnp.zeros((6, 8))}
        full = {"x": np.arange(48, dtype=np.float32).reshape(6, 8)}
        ckpt = self._save_leafwise(tmp_path, params, full, 4)
        opt = sharded_distributed_update(optax.adam(1e-2), world=2,
                                         hierarchy="flat")
        template = jax.tree_util.tree_map(np.asarray, opt.init(params))
        template = (template.inner[0], template.inner[1])
        with pytest.raises(ValueError) as e:
            ckpt.restore_sharded(template, 0, 2)
        msg = str(e.value)
        assert "mu" in msg and "'x'" in msg, msg
        assert "(6, 2)" in msg and "(3, 8)" in msg, msg

    def test_leafwise_same_world_restores_own_slab(self, tmp_path):
        params = {"w": jnp.zeros((8, 6))}
        full = {"w": np.arange(48, dtype=np.float32).reshape(8, 6)}
        ckpt = self._save_leafwise(tmp_path, params, full, 4)
        from horovod_tpu.optim.optimizer import sharded_distributed_update

        opt = sharded_distributed_update(optax.adam(1e-2), world=4,
                                         hierarchy="flat")
        template = jax.tree_util.tree_map(np.asarray, opt.init(params))
        out = ckpt.restore_sharded(
            (template.inner[0], template.inner[1]), 3, 4)
        np.testing.assert_array_equal(out[0].mu["w"], full["w"][6:8])


class TestElasticStateThroughAsyncCheckpoint:
    def test_commit_persists_and_cold_restores(self, tmp_path):
        hvd.init()
        try:
            ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                               use_orbax=False)
            state = hvd.elastic.TpuState(
                params={"w": jnp.ones((2, 2))},
                opt_state={"mu": jnp.zeros((2, 2))},
                epoch=0, checkpointer=ckpt)
            state.params = {"w": jnp.full((2, 2), 5.0)}
            state.epoch = 3
            state.commit()
            state.wait()
            # a brand-new process (no in-memory commit): restore from disk
            cold = hvd.elastic.TpuState(
                params={"w": jnp.zeros((2, 2))},
                opt_state={"mu": jnp.zeros((2, 2))},
                epoch=0, checkpointer=ckpt)
            assert cold.restore_from_checkpoint() is True
            np.testing.assert_allclose(np.asarray(cold.params["w"]), 5.0)
            assert cold.epoch == 3
        finally:
            hvd.shutdown()

    def test_checkpoint_every_skips_intermediate_commits(self, tmp_path):
        hvd.init()
        try:
            ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                               use_orbax=False,
                                               max_to_keep=10)
            state = hvd.elastic.TpuState(
                params={"w": jnp.ones(2)}, epoch=0,
                checkpointer=ckpt, checkpoint_every=2)
            for _ in range(4):
                state.commit()
            state.wait()
            assert ckpt.all_steps() == [2, 4]
        finally:
            hvd.shutdown()

    def test_commit_counter_resumes_from_restored_step(self, tmp_path):
        # Regression: after a cold restore from durable step N, further
        # commits must continue at N+1, N+2, ... — restarting from 1
        # would make keep-highest retention GC the fresh steps while
        # latest_step() kept answering the stale pre-crash one, so a
        # second crash would lose all post-restart progress.
        hvd.init()
        try:
            ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                               use_orbax=False,
                                               max_to_keep=2)
            state = hvd.elastic.TpuState(params={"w": jnp.ones(2)},
                                         epoch=0, checkpointer=ckpt)
            for e in range(5):
                state.epoch = e
                state.commit()
            state.wait()
            assert ckpt.latest_step() == 5

            cold = hvd.elastic.TpuState(params={"w": jnp.zeros(2)},
                                        epoch=0, checkpointer=ckpt)
            assert cold.restore_from_checkpoint() is True
            assert cold.epoch == 4
            cold.epoch = 9
            cold.commit()                # must persist as step 6, not 1
            cold.wait()
            assert ckpt.latest_step() == 6
            assert ckpt.all_steps() == [5, 6]   # retention kept the new one

            second = hvd.elastic.TpuState(params={"w": jnp.zeros(2)},
                                          epoch=0, checkpointer=ckpt)
            assert second.restore_from_checkpoint() is True
            assert second.epoch == 9     # post-restart progress survived
        finally:
            hvd.shutdown()

    def test_no_checkpointer_is_memory_only(self):
        hvd.init()
        try:
            state = hvd.elastic.TpuState(params={"w": jnp.ones(2)})
            state.commit()
            state.wait()                 # no-op barrier
            assert state.restore_from_checkpoint() is False
        finally:
            hvd.shutdown()


class TestPinAgainstRetention:
    """The guardian's rollback target must survive retention GC
    (docs/guardian.md): ``pin`` exempts a step until ``unpin``."""

    def test_pinned_step_survives_gc(self, tmp_path):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           max_to_keep=2, use_orbax=False)
        ckpt.save(0, make_state(0.0))
        ckpt.pin(0)
        for s in range(1, 6):                 # push far past max_to_keep
            ckpt.save(s, make_state(float(s)))
        assert 0 in ckpt.all_steps()          # the pin held
        assert sorted(ckpt.all_steps()) == [0, 4, 5]
        # the pinned step is still restorable, not a husk
        restored = ckpt.restore(make_state(9.0), step=0)
        np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 0.0)

    def test_unpin_rejoins_retention(self, tmp_path):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           max_to_keep=2, use_orbax=False)
        ckpt.save(0, make_state(0.0))
        ckpt.pin(0)
        for s in range(1, 4):
            ckpt.save(s, make_state(float(s)))
        assert 0 in ckpt.all_steps()
        ckpt.unpin(0)
        ckpt.save(4, make_state(4.0))         # next GC pass reaps it
        assert 0 not in ckpt.all_steps()
        assert ckpt.pinned_steps() == []

    def test_pinned_steps_reports(self, tmp_path):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        ckpt.pin(3)
        ckpt.pin(7)
        assert ckpt.pinned_steps() == [3, 7]
        ckpt.unpin(3)
        assert ckpt.pinned_steps() == [7]


class TestPlanReshard:
    """Plan-stamped sharded checkpoints across sp (ISSUE 17 satellite):
    sp shards *activations*, so for the saved parameter/optimizer state
    it is data-free — a dp=2,sp=2 checkpoint restores onto dp=4 or
    dp=1,sp=4 as a plain reshard, while a model-extent (pp/ep/tp)
    change refuses with a clear error (docs/parallelism.md)."""

    LEAVES = [np.arange(10, dtype=np.float32),
              np.arange(6, dtype=np.float32).reshape(2, 3) + 100.0]

    def _save_all(self, tmp_path, world, plan):
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        spec, flats, trees = _shard_trees(self.LEAVES, world)
        for r, tree in enumerate(trees):
            ckpt.save_sharded(0, tree, r, world, plan=plan)
            ckpt.wait()
        return ckpt, trees

    @pytest.mark.parametrize("new_plan", ["dp=4", "dp=1,sp=4",
                                          "dp=2,sp=2", "dp=2,fsdp=2"])
    def test_sp_restores_across_data_factorizations(self, tmp_path,
                                                    new_plan):
        ckpt, trees = self._save_all(tmp_path, 4, plan="dp=2,sp=2")
        for r in range(4):
            target = {k: {"m": np.zeros_like(v["m"]),
                          "count": np.int32(0)}
                      for k, v in trees[r].items()}
            out = ckpt.restore_sharded(target, r, 4, plan=new_plan)
            for k in trees[r]:
                np.testing.assert_array_equal(out[k]["m"],
                                              trees[r][k]["m"])

    def test_sp_checkpoint_reshards_to_wider_world(self, tmp_path):
        # dp=2,sp=2 (4 shards) -> dp=8 (8 shards): sp folds into the
        # data extent and the flat buffer re-slices like any world
        # change
        ckpt, _ = self._save_all(tmp_path, 4, plan="dp=2,sp=2")
        spec8 = C.make_fusion_spec(self.LEAVES, 8)
        _, flats, _ = _shard_trees(self.LEAVES, 4)
        for g in spec8.groups:
            full = flats[g.key]
            if g.padded >= full.size:
                full = np.concatenate(
                    [full, np.zeros(g.padded - full.size, full.dtype)])
            else:
                full = full[:g.padded]
            for r in (0, 7):
                target = {k2.key: {"m": np.zeros((k2.shard,),
                                                 np.float32),
                                   "count": np.int32(0)}
                          for k2 in spec8.groups}
                out = ckpt.restore_sharded(target, r, 8, plan="dp=8")
                np.testing.assert_array_equal(
                    out[g.key]["m"],
                    full[r * g.shard:(r + 1) * g.shard])

    def test_model_extent_change_refuses(self, tmp_path):
        ckpt, trees = self._save_all(tmp_path, 4, plan="dp=2,sp=2")
        target = {k: {"m": np.zeros_like(v["m"]), "count": np.int32(0)}
                  for k, v in trees[0].items()}
        with pytest.raises(ValueError, match="pp/ep/tp"):
            ckpt.restore_sharded(target, 0, 4, plan="dp=4,tp=2")

    def test_plan_shard_count_mismatch_is_a_clear_error(self, tmp_path):
        # a dp=2,sp=2 plan shards the exchange over 4 ranks; stamping
        # it onto an 8-way save would write a lie into the checkpoint
        ckpt = hvd.checkpoint.Checkpointer(str(tmp_path / "ck"),
                                           use_orbax=False)
        with pytest.raises(ValueError, match=r"dp\*fsdp\*sp"):
            ckpt.save_sharded(0, {"m": np.ones(3, np.float32)}, 0, 8,
                              plan="dp=2,sp=2")

    def test_unstamped_checkpoint_restores_under_any_plan(self,
                                                          tmp_path):
        # pre-ISSUE-17 checkpoints carry no plan; restore must not
        # invent a refusal
        ckpt, trees = self._save_all(tmp_path, 4, plan=None)
        target = {k: {"m": np.zeros_like(v["m"]), "count": np.int32(0)}
                  for k, v in trees[0].items()}
        out = ckpt.restore_sharded(target, 0, 4, plan="dp=1,sp=4")
        for k in trees[0]:
            np.testing.assert_array_equal(out[k]["m"],
                                          trees[0][k]["m"])
