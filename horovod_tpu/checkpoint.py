"""Checkpoint/resume: rank-0-save + broadcast-restore, off the train clock.

Reference (SURVEY §5.4): Horovod ships no checkpoint format; its
examples save on rank 0 only and restore with
``broadcast_variables``/``broadcast_optimizer_state``
(``examples/tensorflow2_keras_mnist.py``, ``tensorflow/functions.py:47``,
``torch/functions.py:30,62``).  This module packages that pattern with
an orbax backend (the TPU-native checkpoint store) and a msgpack/numpy
fallback — and, since the warm-start PR, takes serialization off the
training clock:

**Async snapshotting** (default): ``save()`` blocks only for the
device→host copy — the consistent cut; the arrays the train loop will
donate next step are copied out before ``save()`` returns — then
pickling, fsync and retention run on a background writer thread.
``wait()`` is the barrier: it re-raises writer errors, and ``save()``
calls it first so two writes never interleave (at steady state the
previous write has long finished and the barrier is free).

**Crash consistency**: a checkpoint file becomes visible only via
atomic ``os.replace`` after its bytes are fsynced, and the directory
entry is fsynced after the rename; a crash mid-write leaves only
``*.tmp*`` files, which every reader ignores and the next writer
removes.  The previous checkpoint is never touched until the new one
is durable (retention runs after the rename).

**Sharded (ZeRO) optimizer state** (PR 1 ``shard_optimizer_states``):
each rank owns 1/N of the flat fused state, so the rank-0-only rule
doesn't apply — :meth:`save_sharded` has every rank write its own
shard file and :meth:`restore_sharded` reassembles the full flat
buffer and re-slices it for the restoring world size, which may
differ (elastic resize).  The zero-padding the fusion spec adds is
preserved by construction (padded gradient tails are zero, so padded
state tails stay zero), so trimming/re-padding at a new world size is
exact.  See docs/warmstart.md.

::

    ckpt = hvd.checkpoint.Checkpointer("/tmp/run1")
    ckpt.save(step, {"params": params, "opt_state": opt_state})   # rank 0
    ckpt.wait()                                                   # barrier
    state = ckpt.restore_and_broadcast({"params": params, ...})   # all
"""

from __future__ import annotations

import os
import pickle
import threading
import time
from typing import Any, Optional

import jax
import numpy as np

from horovod_tpu import faults, telemetry
from horovod_tpu import functions as F
from horovod_tpu.utils import logging as hvd_logging

# save-plane telemetry (docs/metrics.md): dispatch counts, the blocking
# D2H-cut stall, the background write duration, and sticky writer errors
_TEL_SAVES = telemetry.counter(
    "hvd_checkpoint_saves_total", "checkpoint saves dispatched")
_TEL_STALL = telemetry.histogram(
    "hvd_checkpoint_stall_seconds",
    "train-loop blocking time of a save (the D2H consistent cut)")
_TEL_WRITE = telemetry.histogram(
    "hvd_checkpoint_write_seconds",
    "end-to-end background write duration (pickle+fsync+rename)")
_TEL_ERRORS = telemetry.counter(
    "hvd_checkpoint_writer_errors_total",
    "checkpoint writer-thread failures (sticky until clear_error)")


def _is_root() -> bool:
    return jax.process_index() == 0


def _host_copy(state: Any) -> Any:
    """The consistent cut: synchronous copy of every array leaf into
    host memory the snapshot OWNS.  After this returns, the snapshot is
    immune — the caller may overwrite its device buffers *and* its host
    arrays in place while the background writer pickles."""

    def _leaf(x):
        if isinstance(x, np.ndarray):
            # np.asarray would be a zero-copy alias here, breaking the
            # immune-after-return contract for host-resident state
            return x.copy()
        if hasattr(x, "shape"):
            a = np.asarray(x)
            # __array__ can be zero-copy too (CPU-backed jax arrays):
            # keep only memory we own
            return a if a.base is None and a.flags.owndata else a.copy()
        return x

    return jax.tree_util.tree_map(_leaf, state)


def _io_retry():
    """Writer-thread retry policy for transient storage errors (NFS
    hiccups, momentary ENOSPC): short exponential backoff under the
    unified ``HOROVOD_RETRY_*`` knobs, OSError only — a pickling error
    is a bug and must surface on the first attempt."""
    from horovod_tpu.runtime.retry import RetryPolicy

    return RetryPolicy(retry_on=(OSError,), name="checkpoint-io")


def _atomic_write(path: str, payload: Any) -> None:
    """Pickle ``payload`` to ``path`` durably: tmp file → fsync →
    atomic rename → fsync of the directory entry."""
    d = os.path.dirname(path)
    tmp = os.path.join(d, f".tmp.{os.path.basename(path)}.{os.getpid()}")
    with open(tmp, "wb") as f:
        pickle.dump(payload, f, protocol=pickle.HIGHEST_PROTOCOL)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    try:
        dirfd = os.open(d, os.O_RDONLY)
        try:
            os.fsync(dirfd)
        finally:
            os.close(dirfd)
    except OSError:  # pragma: no cover - exotic filesystems
        pass


class Checkpointer:
    """Directory-per-step checkpoints with an async writer thread.

    Replicated state is written by rank 0 only (the reference's
    "checkpoint on rank 0" rule); sharded state is written by every
    rank through :meth:`save_sharded`.  Uses orbax when available
    (``use_orbax=None`` autodetects); the fallback serializes the
    pytree's numpy leaves with pickle — same layout, no extra deps.

    ``async_save=False`` restores the old fully-synchronous behavior
    (save returns only when bytes are durable) — what the bench's
    ``checkpoint_sync_s`` reference number measures.
    """

    def __init__(self, directory: str, max_to_keep: int = 3,
                 use_orbax: Optional[bool] = None,
                 async_save: bool = True):
        self._dir = os.path.abspath(directory)
        self._max_to_keep = max_to_keep
        self._async = async_save
        self._writer: Optional[threading.Thread] = None
        # guards _writer_error: written by the writer thread, consumed
        # by wait()/clear_error() on the caller's thread.  wait()'s
        # join() already orders the steady-state handoff, but
        # clear_error() has no such edge — without the lock it can race
        # a writer failing mid-flight and acknowledge an error it never
        # returned to the caller.
        self._error_lock = threading.Lock()
        self._writer_error: Optional[BaseException] = None
        # steps pinned against retention (guardian "last-good" rollback
        # targets, docs/guardian.md).  Written by the caller thread,
        # read by _gc() on the writer thread — lock-guarded.
        self._pin_lock = threading.Lock()
        self._pins: set = set()
        # observability for the bench probe: the train-loop blocking
        # time of the last save (D2H cut only, async) and the last
        # end-to-end write duration (background, after wait())
        self.last_stall_s: Optional[float] = None
        self.last_write_s: Optional[float] = None
        if use_orbax is None:
            try:
                import orbax.checkpoint  # noqa: F401

                # orbax's CheckpointManager is collective: __init__ and
                # save() run global barriers over jax.distributed, which
                # deadlocks against this class's rank-0-only contract
                # (the reference's "checkpoint on rank 0" rule).  Use
                # orbax single-process; the pickle layout multi-process.
                use_orbax = jax.process_count() == 1
            except ImportError:
                use_orbax = False
        elif use_orbax and jax.process_count() > 1:
            raise ValueError(
                "use_orbax=True is not supported in multi-process runs: "
                "orbax's CheckpointManager is collective (global barriers "
                "in __init__/save) and this Checkpointer writes on rank 0 "
                "only — the job would deadlock at the first save. Leave "
                "use_orbax unset (the pickle layout is chosen "
                "automatically; reads remain layout-agnostic).")
        self._use_orbax = use_orbax
        self._manager = None
        os.makedirs(self._dir, exist_ok=True)
        if use_orbax and _is_root():
            import orbax.checkpoint as ocp

            self._manager = ocp.CheckpointManager(
                self._dir,
                options=ocp.CheckpointManagerOptions(
                    max_to_keep=max_to_keep, create=True))

    # -- async writer machinery ---------------------------------------------

    def wait(self) -> None:
        """Barrier: block until the pending background write (if any)
        is durable; re-raise any error it hit.  ``save()`` runs this
        first, so callers that never touch ``wait()`` still get the
        one-outstanding-write guarantee.

        A writer error is STICKY: every subsequent ``save()``/
        ``wait()``/``close()`` re-raises it until :meth:`clear_error`
        acknowledges it — a lost checkpoint must not be discoverable
        only by the one caller that happened to hit the barrier first
        (and silently absorbed by everyone after)."""
        w = self._writer
        if w is not None:
            w.join()
            self._writer = None
        with self._error_lock:
            err = self._writer_error
        if err is not None:
            raise err

    def clear_error(self) -> Optional[BaseException]:
        """Acknowledge (and return) the sticky writer error, unblocking
        further saves — the caller has decided how to proceed (retry
        the save, fail over to another directory, abort)."""
        with self._error_lock:
            err, self._writer_error = self._writer_error, None
        return err

    def close(self) -> None:
        """Final barrier: join any pending write and surface its error.
        A process that saves last and exits without ``wait()`` would
        otherwise swallow a failed final checkpoint (the non-daemon
        writer thread completes at interpreter shutdown, but nobody
        reads its error)."""
        self.wait()

    def _dispatch(self, fn) -> None:
        """Run ``fn`` on the writer thread (async) or inline (sync)."""

        def run():
            t0 = time.perf_counter()
            try:
                faults.inject("checkpoint.write")   # chaos hook
                fn()
            except BaseException as e:  # noqa: BLE001 — surfaced at wait()
                _TEL_ERRORS.inc()
                with self._error_lock:
                    self._writer_error = e
            finally:
                self.last_write_s = time.perf_counter() - t0
                _TEL_WRITE.observe(self.last_write_s)

        if not self._async:
            run()
            with self._error_lock:
                # synchronous surfacing: the caller sees the error right
                # here, so it is consumed rather than left sticky
                err, self._writer_error = self._writer_error, None
            if err is not None:
                raise err
            return
        # non-daemon: a process exiting right after save() (last epoch,
        # worker retirement) joins the writer at interpreter shutdown
        # instead of truncating the write — durability over exit speed
        self._writer = threading.Thread(
            target=run, daemon=False, name="hvd_tpu_ckpt_writer")
        self._writer.start()

    # -- write --------------------------------------------------------------

    def save(self, step: int, state: Any) -> bool:
        """Write a checkpoint on rank 0; no-op elsewhere (the reference's
        "checkpoint on rank 0 only" rule).  Blocks only for the D2H
        copy when ``async_save`` (the default); durability is reached
        in the background and checkable via :meth:`wait`."""
        if not _is_root():
            return False
        self.wait()                       # one outstanding write, ever
        t0 = time.perf_counter()
        host_state = _host_copy(state)    # the consistent cut
        self.last_stall_s = time.perf_counter() - t0
        _TEL_SAVES.inc()
        _TEL_STALL.observe(self.last_stall_s)

        if self._manager is not None:
            def write():
                import orbax.checkpoint as ocp

                self._manager.save(step,
                                   args=ocp.args.StandardSave(host_state))
                self._manager.wait_until_finished()
                hvd_logging.info("checkpoint: saved step %d to %s",
                                 step, self._dir)
        else:
            def write():
                path = os.path.join(self._dir, f"step_{step}")
                os.makedirs(path, exist_ok=True)
                _io_retry().call(_atomic_write,
                                 os.path.join(path, "state.pkl"),
                                 host_state)
                self._gc()
                hvd_logging.info("checkpoint: saved step %d to %s",
                                 step, self._dir)

        self._dispatch(write)
        return True

    def save_sharded(self, step: int, shard_state: Any,
                     shard_rank: int, shard_count: int,
                     plan: Any = None) -> bool:
        """Write THIS rank's 1/N shard of a sharded (ZeRO) state tree.

        Every rank calls this with its own ``shard_state`` — the
        per-rank optimizer state of ``shard_optimizer_states=True``
        (flat ``(shard,)`` leaves keyed by fusion group).  Same async
        contract as :meth:`save`: blocks for the D2H copy only.  The
        step is complete once all ``shard_count`` files exist —
        :meth:`restore_sharded` verifies that.

        ``plan`` (a :class:`~horovod_tpu.parallel.plan.ShardingPlan` or
        grammar string) stamps the parallelism plan the state was
        trained under into every shard payload, letting
        :meth:`restore_sharded` reshard across *plan* changes — the
        data extent (dp×fsdp) may change freely, and so may ``sp``:
        sequence parallelism shards *activations*, not parameters, so
        for the saved state sp is data-free and the flat-buffer reshard
        covers it.  A changed model-parallel factorization (pp/ep/tp)
        is refused there instead of silently mis-slicing
        (docs/parallelism.md)."""
        if not 0 <= shard_rank < shard_count:
            raise ValueError(
                f"shard_rank {shard_rank} out of range for "
                f"shard_count {shard_count}")
        plan_str = _canonical_plan(plan, shard_count)
        self.wait()
        t0 = time.perf_counter()
        host_state = _host_copy(shard_state)
        self.last_stall_s = time.perf_counter() - t0
        _TEL_SAVES.inc()
        _TEL_STALL.observe(self.last_stall_s)

        def write():
            path = os.path.join(self._dir, f"step_{step}")
            os.makedirs(path, exist_ok=True)
            payload = {"shard_rank": shard_rank,
                       "shard_count": shard_count,
                       "state": host_state}
            if plan_str is not None:
                payload["plan"] = plan_str
            _io_retry().call(
                _atomic_write,
                os.path.join(path, _shard_name(shard_rank, shard_count)),
                payload)
            hvd_logging.info(
                "checkpoint: saved shard %d/%d of step %d to %s",
                shard_rank, shard_count, step, self._dir)

        self._dispatch(write)
        return True

    def pin(self, step: int) -> None:
        """Exempt ``step`` from retention until :meth:`unpin`.

        The guardian's rollback contract (docs/guardian.md): between
        anomaly detection and restore, further saves may push the
        last-good step past ``max_to_keep`` — a pinned step can never be
        reaped in that window.  Pins cover the pickle layout (the
        multi-process production writer); the orbax manager owns its own
        retention."""
        with self._pin_lock:
            self._pins.add(int(step))

    def unpin(self, step: int) -> None:
        """Release a :meth:`pin`; the step rejoins normal retention on
        the next save's GC pass."""
        with self._pin_lock:
            self._pins.discard(int(step))

    def pinned_steps(self) -> list:
        with self._pin_lock:
            return sorted(self._pins)

    def _gc(self) -> None:
        # rank retention over the pickle layout only — mixing in orbax
        # step numbers could delete a just-written pickle step while
        # never pruning the (manager-owned) orbax dirs
        steps = sorted(self._pickle_steps())
        with self._pin_lock:
            pins = set(self._pins)
        for s in steps[:-self._max_to_keep]:
            if s in pins:     # a rollback target is never reaped
                continue
            import shutil

            shutil.rmtree(os.path.join(self._dir, f"step_{s}"),
                          ignore_errors=True)

    # -- read ---------------------------------------------------------------

    def all_steps(self) -> list:
        """Steps present on disk, in EITHER layout.  The write format
        depends on availability and process count, but a run resumed or
        evaluated with a different process count must still find its
        existing checkpoints — reads are layout-agnostic.  Only steps
        with at least one finalized (non-tmp) payload file count, so a
        crash mid-first-write never surfaces an empty step."""
        self.wait()   # read-your-writes: surface our own pending save
        if not os.path.isdir(self._dir):
            return []
        steps = set(self._pickle_steps())
        if self._manager is not None:
            steps.update(int(s) for s in self._manager.all_steps())
        else:
            # Non-root ranks / pickle writers still list orbax-finalized
            # steps (checkpoint_steps only reports finalized ones, so a
            # reader can never pick a step rank 0 is mid-writing).
            try:
                from orbax.checkpoint import utils as ocp_utils

                # only steps living in orbax's plain-digit layout: the
                # pickle layout's step_N dirs must not round-trip through
                # orbax's scanner, which would resurface an incomplete
                # (crash-torso) pickle step _pickle_steps just filtered
                steps.update(
                    int(s) for s in ocp_utils.checkpoint_steps(self._dir)
                    if os.path.isdir(os.path.join(self._dir, str(int(s)))))
            except ImportError:
                pass
        return sorted(steps)

    def _pickle_steps(self) -> list:
        out = []
        for d in os.listdir(self._dir):
            if not (d.startswith("step_") and d.split("_", 1)[1].isdigit()):
                continue
            full = os.path.join(self._dir, d)
            try:
                final = [n for n in os.listdir(full)
                         if n.endswith(".pkl") and not n.startswith(".tmp")]
            except NotADirectoryError:
                continue
            if final:
                out.append(int(d.split("_", 1)[1]))
        return out

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return max(steps) if steps else None

    def restore(self, target: Any, step: Optional[int] = None) -> Any:
        """Load a checkpoint on this process (every rank reads — use
        :meth:`restore_and_broadcast` for the read-once pattern)."""
        self.wait()
        if step is None:
            step = self._resolve_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self._dir}")
        # layout-agnostic: read whichever format holds this step
        step_dir = os.path.join(self._dir, f"step_{step}")
        pkl = os.path.join(step_dir, "state.pkl")
        if os.path.exists(pkl):
            with open(pkl, "rb") as f:
                return pickle.load(f)
        if os.path.isdir(step_dir) and any(
                n.startswith("shard_") and n.endswith(".pkl")
                for n in os.listdir(step_dir)):
            # don't fall through to orbax: the step exists but holds
            # per-rank shard files, which only restore_sharded can read
            raise ValueError(
                f"step {step} in {self._dir} was written by "
                f"save_sharded() (per-rank shard files, no replicated "
                f"state.pkl) — use restore_sharded(target, shard_rank, "
                f"shard_count) to read it")
        if step not in self.all_steps():
            raise FileNotFoundError(
                f"no checkpoint for step {step} in {self._dir} "
                f"(available: {self.all_steps()})")
        import orbax.checkpoint as ocp

        host_target = _host_copy(target)
        if self._manager is not None and \
                step in set(self._manager.all_steps()):
            return self._manager.restore(
                step, args=ocp.args.StandardRestore(host_target))
        # Non-root / cross-layout: plain per-host read of the shared
        # directory; no cross-host coordination needed for a restore.
        # Layout is the manager's: <dir>/<step>/default.
        return ocp.StandardCheckpointer().restore(
            os.path.join(self._dir, str(step), "default"), host_target)

    def saved_plan(self, step: Optional[int] = None) -> Optional[str]:
        """The parallelism plan stamped into ``step``'s sharded
        checkpoint (``save_sharded`` ``plan=``), or None when the step
        holds no shard files or an unstamped legacy one.  The degrade
        resolver reads this before a transition: the restoring plan's
        model extent must match the stamp or ``restore_sharded`` will
        refuse (elastic/degrade.py, docs/elastic.md)."""
        self.wait()
        if step is None:
            step = self.latest_step()
        if step is None:
            return None
        path = os.path.join(self._dir, f"step_{step}")
        try:
            shards = _load_shards(path)
        except (FileNotFoundError, ValueError):
            return None
        return shards[0].get("plan")

    def restore_sharded(self, target: Any, shard_rank: int,
                        shard_count: int,
                        step: Optional[int] = None,
                        plan: Any = None) -> Any:
        """Rebuild THIS rank's shard of a sharded state saved at any
        world size — or under any *plan* with the same model-parallel
        factorization.

        The saved shards concatenate back into the full flat buffer
        (padded to the *saving* world's multiple); ``target``'s leaf
        shapes define the *restoring* world's shard sizes, so the
        buffer is re-padded (or pad-trimmed — the tail is zeros by the
        fusion-spec invariant) to ``shard * shard_count`` and re-sliced
        at ``shard_rank``.  Scalar leaves (optimizer step counters) are
        replicated across shards; the saving rank 0's value wins.

        ``plan`` names the *restoring* run's plan.  When the checkpoint
        carries a saved plan (:meth:`save_sharded` ``plan=``), the
        model-parallel extents (pp/ep/tp) must match — those change
        the parameter tensors themselves, which no flat-buffer reshard
        can fix — while the data extent (dp×fsdp) *and* the sp extent
        reshard exactly like a world-size change: sp shards the
        sequence (activations), so every sp rank holds the same
        parameter/optimizer values and the exchange treats sp as one
        more data axis (docs/parallelism.md)."""
        self.wait()
        if step is None:
            step = self._resolve_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self._dir}")
        path = os.path.join(self._dir, f"step_{step}")
        shards = _load_shards(path)
        plan_str = _canonical_plan(plan, shard_count)
        saved_plan = shards[0].get("plan")
        if saved_plan is not None and plan_str is not None:
            _check_plan_reshard(saved_plan, plan_str, path)
        saved_trees = [s["state"] for s in shards]
        t_paths, treedef = jax.tree_util.tree_flatten_with_path(target)
        t_leaves = [leaf for _, leaf in t_paths]
        shard_leaves = [jax.tree_util.tree_flatten(t)[0]
                        for t in saved_trees]
        if any(len(sl) != len(t_leaves) for sl in shard_leaves):
            raise ValueError(
                f"sharded checkpoint at {path} has a different tree "
                f"structure than the restore target")
        out = []
        for i, t in enumerate(t_leaves):
            saved = [sl[i] for sl in shard_leaves]
            out.append(_reshard_leaf(
                t, saved, shard_rank, shard_count,
                name=jax.tree_util.keystr(t_paths[i][0])))
        return jax.tree_util.tree_unflatten(treedef, out)

    def _resolve_step(self) -> Optional[int]:
        """Pick the latest step, agreed across ranks.

        Collective when multi-process (every rank must call it): root's
        directory listing is broadcast, because per-rank listings can lag
        on shared filesystems and ranks silently restoring different steps
        is worse than any error.
        """
        if jax.process_count() == 1:
            return self.latest_step()
        from horovod_tpu.ops import eager

        mine = self.latest_step() if _is_root() else None
        step = int(eager.broadcast(
            np.asarray([-1 if mine is None else mine], np.int32),
            root_rank=0, name="ckpt_latest_step")[0])
        return None if step < 0 else step

    def restore_and_broadcast(self, target: Any,
                              step: Optional[int] = None,
                              root_rank: int = 0) -> Any:
        """Rank 0 reads from storage, everyone else receives via broadcast
        (reference restore + ``broadcast_variables`` recipe) — one storage
        read per job instead of N."""
        if jax.process_count() == 1:
            return self.restore(target, step)
        # resolve the step on ALL ranks first: restore() below runs on root
        # only, so its internal collective resolution must not trigger
        if step is None:
            step = self._resolve_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self._dir}")
        if _is_root():
            state = self.restore(target, step)
        else:
            state = target
        return F.broadcast_variables(state, root_rank=root_rank,
                                     name="checkpoint_restore")


def _shard_name(rank: int, count: int) -> str:
    return f"shard_{rank}_of_{count}.pkl"


def _canonical_plan(plan: Any, shard_count: int) -> Optional[str]:
    """Canonical plan string for shard payloads, validated against the
    exchange width: the sharded state shards over the plan's data axes,
    so a plan whose dp×fsdp disagrees with ``shard_count`` would stamp
    a lie into the checkpoint."""
    if plan is None:
        return None
    from horovod_tpu.parallel.plan import as_plan

    p = as_plan(plan)
    if p.dp is not None:
        # sp counts: sequence parallelism shards activations, not
        # parameters, so the sharded state spreads over dp×fsdp×sp
        # ranks (sp joined the exchange scope in the train step)
        data_extent = p.dp * p.fsdp * p.sp
        if data_extent != shard_count:
            raise ValueError(
                f"plan {p.to_string()} shards the exchange over "
                f"dp*fsdp*sp={data_extent} ranks, but shard_count is "
                f"{shard_count}")
    return p.to_string(allow_unresolved=True)


def _check_plan_reshard(saved: str, restoring: str, path: str) -> None:
    """Refuse cross-plan restores that change the model-parallel
    factorization: pp/ep/tp extents reshape the parameter tensors
    themselves, so the flat-buffer reshard of :func:`_reshard_leaf`
    would slice garbage.  Data-extent (dp/fsdp), ``sp`` (sequence
    parallelism shards activations — parameters are identical on every
    sp rank, so for the saved state sp is just more data extent) and
    virtual-stage changes reshard fine."""
    from horovod_tpu.parallel.plan import ShardingPlan

    sp = ShardingPlan.from_string(saved.replace("dp=?", "dp=1")
                                  if "dp=?" in saved else saved)
    rp = ShardingPlan.from_string(restoring.replace("dp=?", "dp=1")
                                  if "dp=?" in restoring else restoring)
    model_axes = ("pp", "ep", "tp")
    mismatch = [ax for ax in model_axes
                if getattr(sp, ax) != getattr(rp, ax)]
    if mismatch:
        raise ValueError(
            f"sharded checkpoint in {path} was saved under plan "
            f"{saved!r} but the restore runs plan {restoring!r}: "
            f"model-parallel extents differ on {mismatch} — resharding "
            f"only covers data-extent (dp/fsdp/sp) changes; "
            f"re-partition the model to change pp/ep/tp")


def _load_shards(path: str) -> list:
    """All shard payloads of one step, ordered by shard rank; validates
    the set is complete and from one world size."""
    if not os.path.isdir(path):
        raise FileNotFoundError(f"no checkpoint directory {path}")
    names = [n for n in os.listdir(path)
             if n.startswith("shard_") and n.endswith(".pkl")
             and not n.startswith(".tmp")]
    if not names:
        raise FileNotFoundError(f"no shard files in {path}")
    payloads = []
    for n in sorted(names):
        with open(os.path.join(path, n), "rb") as f:
            payloads.append(pickle.load(f))
    counts = {p["shard_count"] for p in payloads}
    if len(counts) != 1:
        raise ValueError(
            f"mixed shard_count values {sorted(counts)} in {path} — "
            f"partial overwrite from two world sizes?")
    count = counts.pop()
    ranks = sorted(p["shard_rank"] for p in payloads)
    if ranks != list(range(count)):
        missing = sorted(set(range(count)) - set(ranks))
        raise FileNotFoundError(
            f"incomplete sharded checkpoint in {path}: missing shard(s) "
            f"{missing} of {count}")
    payloads.sort(key=lambda p: p["shard_rank"])
    return payloads


def _reshard_leaf(target, saved: list, shard_rank: int, shard_count: int,
                  name: str = "leaf"):
    """One leaf's re-shard.  A 1-D leaf (a packed group's slice, or the
    slab of a vector): concat the saved per-rank pieces, fix the padded
    length to the restoring world's, slice this rank's piece.  A slab
    of higher rank (the plain exchange cuts a leaf along one of its own
    dimensions): concat along the one dimension in which saved and
    target shapes differ, slice this rank's rows of it — and refuse,
    naming the leaf, where they are not cut along one common
    dimension (another world may cut another one)."""
    if not hasattr(target, "shape") or np.ndim(target) == 0:
        # replicated scalar (e.g. optax count): saving rank 0's value
        return saved[0]
    t_shape = tuple(np.shape(target))
    s0 = np.asarray(saved[0])
    if tuple(s0.shape) == t_shape and len(saved) == shard_count:
        # same world size: this rank's own shard, no reassembly
        return saved[shard_rank]
    if s0.ndim > 1 or len(t_shape) > 1:
        return _reshard_slab(t_shape, saved, shard_rank, shard_count, name)
    full = np.concatenate([np.asarray(s) for s in saved])
    new_padded = t_shape[0] * shard_count
    if new_padded < full.shape[0]:
        # the fusion spec pads with zeros and padded gradient tails are
        # zero, so state tails are zero — trimming drops only padding
        tail = full[new_padded:]
        if np.any(tail != 0):
            raise ValueError(
                "re-shard would trim non-zero state: the restore "
                f"target's padded length {new_padded} is shorter than "
                f"the saved buffer {full.shape[0]} and the excess is "
                "not fusion padding")
        full = full[:new_padded]
    elif new_padded > full.shape[0]:
        full = np.concatenate([
            full, np.zeros((new_padded - full.shape[0],), full.dtype)])
    shard = full.shape[0] // shard_count
    return full[shard_rank * shard:(shard_rank + 1) * shard]


def _reshard_slab(t_shape: tuple, saved: list, shard_rank: int,
                  shard_count: int, name: str):
    """Re-shard the slabs of one leaf of rank > 1 (see
    :func:`_reshard_leaf`): no padding exists on this path, so the
    saved slabs must tile exactly what the restoring world's do."""
    s_shape = tuple(np.shape(saved[0]))
    differ = [d for d in range(min(len(s_shape), len(t_shape)))
              if s_shape[d] != t_shape[d]]
    if len(s_shape) != len(t_shape) or len(differ) != 1 or \
            s_shape[differ[0]] * len(saved) != \
            t_shape[differ[0]] * shard_count:
        raise ValueError(
            f"cannot re-shard {name}: its saved slabs have shape "
            f"{s_shape} (world {len(saved)}) and the restore target "
            f"asks for {t_shape} (world {shard_count}) — the two worlds "
            f"do not cut this leaf along one common dimension "
            f"(shard_optimizer_states cuts a leaf along its first "
            f"dimension the world divides); restore at a world that "
            f"cuts it as the saving one did, or from a replicated "
            f"checkpoint")
    d = differ[0]
    full = np.concatenate([np.asarray(s) for s in saved], axis=d)
    rows = t_shape[d]
    return full[(slice(None),) * d
                + (slice(shard_rank * rows, (shard_rank + 1) * rows),)]
