"""Framework-independent elastic state + retry loop.

Reference: ``horovod/common/elastic.py`` — ``State`` (commit/
check_host_updates:60-93), ``ObjectState:112``, ``run_fn`` retry loop
(:147-168); TF/torch specializations in ``tensorflow/elastic.py`` /
``torch/elastic.py``.  ``TpuState`` is the JAX specialization: model
params + optimizer state are pytrees, so save/restore is a host-side
pytree copy and ``sync()`` is a ``broadcast_variables`` from rank 0.
"""

from __future__ import annotations

import copy
import queue
import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from horovod_tpu.exceptions import HorovodInternalError, HostsUpdatedInterrupt
from horovod_tpu.utils import logging as hvd_logging


class State:
    """Base elastic state (reference ``common/elastic.py:State``).

    Subclasses implement ``save``/``restore``/``sync``.  ``commit()``
    persists a known-good snapshot and then checks for host changes;
    ``check_host_updates()`` alone is the cheap between-batch probe.
    """

    def __init__(self, **kwargs):
        self._host_messages: "queue.Queue" = queue.Queue()
        self._last_updated_timestamp = 0
        self._reset_callbacks = []

    def register_reset_callbacks(self, callbacks) -> None:
        self._reset_callbacks.extend(callbacks)

    def on_reset(self) -> None:
        self._host_messages = queue.Queue()
        self.reset()
        for callback in self._reset_callbacks:
            callback()

    def on_hosts_updated(self, timestamp, update_res=None) -> None:
        """Called by the worker notification service when the driver reports
        a host-set change (reference ``elastic.py:54``)."""
        self._host_messages.put((timestamp, update_res))

    def commit(self) -> None:
        from horovod_tpu import faults

        faults.inject("worker.commit")   # chaos hook: crash/hang at step k
        self.save()
        self.check_host_updates()

    def check_host_updates(self) -> None:
        """Raise ``HostsUpdatedInterrupt`` if new hosts arrived/left; all
        workers agree on the decision via a max-allreduce of the newest
        timestamp they saw (reference ``elastic.py:70-93``)."""
        last_updated_timestamp = prev_timestamp = self._last_updated_timestamp
        all_update_res = 0
        while not self._host_messages.empty():
            timestamp, update_res = self._host_messages.get()
            if timestamp > last_updated_timestamp:
                last_updated_timestamp = timestamp
                if update_res:
                    all_update_res |= int(update_res)

        # coordinate the view across workers so everyone interrupts together
        prev_timestamp, last_updated_timestamp, all_update_res = \
            self._sync_host_updates(prev_timestamp, last_updated_timestamp,
                                    all_update_res)

        if last_updated_timestamp > prev_timestamp:
            self._last_updated_timestamp = last_updated_timestamp
            raise HostsUpdatedInterrupt(all_update_res == 0)

    def _sync_host_updates(self, prev_ts, last_ts, update_res):
        from horovod_tpu.ops import eager

        if eager.process_mesh().devices.size == 1:
            return prev_ts, last_ts, update_res
        # Rank 0's (prev, last, res) triple is the global truth — the
        # reference broadcasts all three (``elastic.py:84-88``) so the
        # raise decision is all-or-none.  A max-allreduce of each rank's
        # own view deadlocks a freshly-joined worker: its prev is 0 while
        # a survivor's prev already covers the update, so only the new
        # worker would interrupt and wait for a generation that never
        # comes.  int64 goes through the int32-pair-safe metadata
        # exchange (microsecond timestamps overflow int32).  The
        # ``hostsync`` negotiation keeps the wire aligned when some
        # process sits in a join() service loop — it emulates the
        # follow-up 3-word exchange with zeros (and zeros from a joined
        # rank 0 simply mean "no interrupt", which is right: a joined
        # rank has left the training loop).
        eager._negotiate({"kind": "hostsync", "sig": "hostsync"})
        allv = eager._allgather_host_metadata(
            np.asarray([prev_ts, last_ts, update_res], np.int64))
        return int(allv[0, 0]), int(allv[0, 1]), int(allv[0, 2])

    # -- to implement -------------------------------------------------------

    def save(self) -> None:
        raise NotImplementedError

    def restore(self) -> None:
        raise NotImplementedError

    def sync(self) -> None:
        raise NotImplementedError

    def reset(self) -> None:
        pass


class ObjectState(State):
    """Elastic state for arbitrary picklable attributes (reference
    ``elastic.py:112``): everything passed as kwargs becomes a synced,
    commit/restorable attribute."""

    def __init__(self, bcast_object: Optional[Callable] = None, **kwargs):
        if bcast_object is None:
            from horovod_tpu.functions import broadcast_object

            bcast_object = broadcast_object
        self._bcast_object = bcast_object
        self._saved_state: Dict[str, Any] = kwargs
        for k, v in kwargs.items():
            setattr(self, k, v)
        super().__init__()

    def save(self) -> None:
        new_state = {}
        for attr in self._saved_state.keys():
            new_state[attr] = copy.deepcopy(getattr(self, attr))
        self._saved_state = new_state

    def restore(self) -> None:
        for attr, value in self._saved_state.items():
            setattr(self, attr, copy.deepcopy(value))

    def sync(self) -> None:
        if self._saved_state:
            synced = self._bcast_object(self._saved_state, root_rank=0)
            for attr, value in synced.items():
                setattr(self, attr, value)
                self._saved_state[attr] = copy.deepcopy(value)


class TpuState(ObjectState):
    """JAX/TPU elastic state: pytree model+optimizer state with host-side
    snapshots (the analogue of ``TensorFlowKerasState`` /
    ``TorchState``).

    ``params``/``opt_state`` (and any extra kwargs) are committed as numpy
    host copies — cheap, device-memory-free snapshots — and restored /
    rank-0-broadcast as pytrees.

    ``checkpointer`` (a :class:`horovod_tpu.checkpoint.Checkpointer`)
    additionally persists every Nth commit (``checkpoint_every``,
    default 1) to durable storage through the async writer: the train
    loop still stalls only for the host copy ``commit()`` makes anyway
    — the numpy snapshot is handed to the background thread as-is — so
    a process-loss restart (every previously-assigned host gone, the
    case in-memory commits cannot survive) resumes from disk via
    :meth:`restore_from_checkpoint` instead of losing the run.
    """

    def __init__(self, params=None, opt_state=None, checkpointer=None,
                 checkpoint_every: int = 1, **kwargs):
        self._checkpointer = checkpointer
        self._checkpoint_every = max(int(checkpoint_every), 1)
        self._commit_count = 0
        super().__init__(params=params, opt_state=opt_state, **kwargs)

    def save(self) -> None:
        new_state = {}
        for attr in self._saved_state.keys():
            val = getattr(self, attr)
            new_state[attr] = jax.tree_util.tree_map(
                lambda x: np.asarray(x) if hasattr(x, "shape") else
                copy.deepcopy(x), val)
        self._saved_state = new_state
        self._commit_count += 1
        # progress export: the commit count rides the worker's heartbeats
        # so the driver's hung-rank watchdog sees training advance
        from horovod_tpu.elastic import worker as elastic_worker

        elastic_worker.report_step(self._commit_count)
        from horovod_tpu import telemetry

        telemetry.counter("hvd_elastic_commits_total",
                          "elastic state commits").inc()
        # gauge (not counter): overwritten per commit, so a crash leaves
        # the last durable-loop value for restore's steps_lost diff
        telemetry.gauge("hvd_elastic_steps_committed",
                        "highest committed elastic step").set(
                            self._commit_count)
        telemetry.run_context().advance(step=self._commit_count)
        if self._checkpointer is not None and \
                self._commit_count % self._checkpoint_every == 0:
            # the leaves are already host numpy arrays, so the
            # checkpointer's cut costs only a host memcpy (it copies
            # numpy leaves to own its snapshot) plus thread dispatch —
            # serialization and fsync run behind the loop (checkpoint.py)
            self._checkpointer.save(self._commit_count, self._saved_state)

    def wait(self) -> None:
        """Barrier on the async checkpoint writer (no-op without one)."""
        if self._checkpointer is not None:
            self._checkpointer.wait()

    def priority_commit(self) -> int:
        """A commit that bypasses ``checkpoint_every`` — the degrade
        transition's drain leg (and the preemption-grace ``commit_fn``;
        guard/preempt.py): whatever the interval, THIS commit reaches
        durable storage, so the post-reshard restore replays zero
        steps from the drain point.  Uses :meth:`save`, not
        :meth:`commit`: the world is already changing, so the
        host-update check would raise mid-drain.  Returns the
        committed step; blocks until the writer has it durable."""
        every, self._checkpoint_every = self._checkpoint_every, 1
        try:
            self.save()
        finally:
            self._checkpoint_every = every
        self.wait()
        return self._commit_count

    def restore_from_checkpoint(self, step=None) -> bool:
        """Load the latest (or ``step``-th) durable commit into this
        state's attributes — the cold-restart path when no surviving
        worker holds an in-memory commit.  Returns False when the
        checkpointer has nothing."""
        if self._checkpointer is None:
            return False
        t0 = time.perf_counter()
        if step is None:
            # resolve once (collective when multi-process) so the step is
            # known here, not just inside restore(): the commit counter
            # must continue from it
            step = self._checkpointer._resolve_step()
            if step is None:
                return False
        saved = self._checkpointer.restore(self._saved_state, step=step)
        self._saved_state = saved
        # Continue the step sequence from the restored commit: leaving
        # _commit_count at 0 would make post-restore saves re-use step
        # numbers 1, 2, ... — the checkpointer's keep-highest retention
        # would then GC the fresh low-numbered steps while latest_step()
        # kept answering the stale pre-crash one, so a second crash would
        # lose everything since the first restart.
        self._commit_count = int(step)
        from horovod_tpu.elastic import worker as elastic_worker

        elastic_worker.report_step(self._commit_count)
        self.restore()
        # recovery telemetry (docs/metrics.md): restore latency, the
        # restored step, and steps_lost diffed against the last
        # committed-step gauge — the structured record bench.py --chaos
        # reads instead of re-deriving these from timing locals
        from horovod_tpu import telemetry

        if telemetry.enabled():
            committed = telemetry.value("hvd_elastic_steps_committed")
            telemetry.gauge("hvd_elastic_restore_seconds",
                            "durable-checkpoint restore latency").set(
                                time.perf_counter() - t0)
            telemetry.gauge("hvd_elastic_restored_step",
                            "step the state restored to").set(
                                self._commit_count)
            telemetry.gauge(
                "hvd_elastic_steps_lost",
                "committed-but-not-durable steps lost by the restore"
            ).set(max(int(committed) - self._commit_count, 0))
        return True

    def restore(self) -> None:
        for attr, value in self._saved_state.items():
            setattr(self, attr, value)

    def sync(self) -> None:
        from horovod_tpu.functions import broadcast_variables

        for attr in list(self._saved_state.keys()):
            val = getattr(self, attr)
            if val is None:
                continue
            is_tree = any(hasattr(l, "shape")
                          for l in jax.tree_util.tree_leaves(val))
            if is_tree:
                synced = broadcast_variables(val, root_rank=0,
                                             name=f"elastic.sync.{attr}")
            else:
                synced = self._bcast_object(val, root_rank=0,
                                            name=f"elastic.sync.{attr}")
            setattr(self, attr, synced)
        self.save()


def run(func: Callable) -> Callable:
    """Elastic run decorator (reference ``run_fn``, ``elastic.py:147-168``)::

        @hvd.elastic.run
        def train(state, ...):
            ...

        train(state)

    Loop: notification init → ``state.sync()`` → ``func(state)``; on
    ``HorovodInternalError`` restore committed state, on
    ``HostsUpdatedInterrupt`` continue with live state; then ``reset()``
    (runtime re-init over the new world) and retry.
    """

    def wrapper(state: State, *args, **kwargs):
        from horovod_tpu.elastic.worker import init_notification_manager

        notification_manager = init_notification_manager()
        if notification_manager is not None:
            notification_manager.register_listener(state)

        skip_sync = False
        try:
            while True:
                if not skip_sync:
                    state.sync()
                try:
                    return func(state, *args, **kwargs)
                except HorovodInternalError:
                    hvd_logging.warning(
                        "elastic: collective failure — restoring last "
                        "committed state and re-initializing")
                    state.restore()
                    skip_sync = False
                except HostsUpdatedInterrupt as e:
                    hvd_logging.info(
                        "elastic: host set changed — re-initializing")
                    skip_sync = e.skip_sync
                _reset()
                state.on_reset()
        finally:
            if notification_manager is not None:
                notification_manager.remove_listener(state)

    return wrapper


def _reset() -> None:
    """Tear down and re-initialize the runtime for a changed world.

    The TPU-specific fidelity point (SURVEY §7 hard part #1): XLA programs
    are compiled for a fixed mesh, so a world change means shutdown,
    re-rendezvous via jax.distributed, mesh rebuild, and recompilation of
    every jitted collective — accomplished by clearing the compiled-fn
    caches so first use recompiles against the new mesh.
    """
    from horovod_tpu.elastic.worker import refresh_assignment_from_driver
    from horovod_tpu.ops import eager
    from horovod_tpu.runtime import state as rt_state

    # input pipelines first: their queues hold device batches pinning
    # buffers (and threads issuing device_puts) against the OLD world's
    # backend — they must drain before the client is torn down.  The
    # training fn rebuilds its feed after reset, re-seeded at the
    # restored (epoch, sample position): ShardedDataset positions are
    # world-size independent, so the resharded dataset replays nothing
    # (docs/data.md "Elastic resume").
    from horovod_tpu import data as hvd_data

    n_closed = hvd_data.close_all_pipelines()
    if n_closed:
        hvd_logging.info(
            "elastic: closed %d input pipeline(s) for reset", n_closed)
    rt_state.shutdown()
    # under an elastic launcher: pull the new rank/size/coordinator from
    # the driver's rendezvous before re-initializing
    refresh_assignment_from_driver()
    # leave the old coordination-service world: without this,
    # jax.distributed stays initialized, GlobalState.initialize skips the
    # re-rendezvous, and the rebuilt mesh would still contain dead peers
    from horovod_tpu.runtime import distributed as hvd_dist

    if hvd_dist.elastic_client_active():
        # driver-hosted service: detach without the shutdown barrier
        # (dead peers would block it)
        hvd_dist.disconnect_elastic_client()
    else:
        try:
            if jax.distributed.is_initialized():
                jax.distributed.shutdown()
        except Exception as e:  # pragma: no cover - backend teardown
            hvd_logging.warning(
                "elastic: jax.distributed.shutdown failed: %s", e)
    # The live PJRT client was built against the OLD distributed world (its
    # cross-process collectives hold dead peer connections); re-initializing
    # jax.distributed alone is not enough — the backend must be rebuilt so
    # the new world's client is constructed on first use.
    try:
        import jax.extend.backend as _jeb

        _jeb.clear_backends()
    except Exception as e:  # pragma: no cover - version-dependent API
        hvd_logging.warning("elastic: clear_backends failed: %s", e)
    eager._reset_mesh_cache()   # drops all mesh-capturing eager caches
    jax.clear_caches()   # compiled programs hold the old mesh's devices
    st = rt_state.init()
    # Warm start: clear_backends/clear_caches dropped every in-memory
    # executable, but the persistent compile cache (runtime/compile_cache)
    # survives on disk — init() re-asserted the cache dir, and the
    # rebuilt DistributedTrainStep's first compile goes through it, so
    # a generation whose (mesh, model, knobs) was ever compiled before
    # restarts in seconds instead of re-paying the full XLA pipeline
    # (docs/warmstart.md).
    if st.compile_cache_dir:
        hvd_logging.info(
            "elastic: warm-start cache ready at %s — recompiles for a "
            "previously-seen world are disk loads", st.compile_cache_dir)
