"""Host-side prefetching + eager device placement for the input feed.

``DistributedTrainStep`` hides the gradient exchange under backward
compute (PR 1–2) and the warm-start cache hides compile cost (PR 3);
the last unhidden serial cost is the input feed — host batch assembly
and the host→device transfer both sat on the critical path between
steps.  :class:`PrefetchIterator` takes them off it:

* a feeder thread pulls host batches from the source iterator (sources
  are rarely thread-safe, so exactly one thread touches the iterator —
  order is preserved by construction);
* each batch's *assembly* — the ``place`` callable, typically
  ``step.shard_batch`` / ``shard_local_batch`` / a ``jax.device_put``
  onto the step's ``NamedSharding`` — runs on a small thread pool
  (``HOROVOD_INPUT_THREADS``), so the H2D transfer for batch ``k+1``
  is *issued* while batch ``k`` computes (double-buffering; JAX
  transfers are async, the pool just gets them dispatched early);
* a bounded queue (``HOROVOD_PREFETCH_DEPTH``) applies backpressure:
  the feeder pulls at most ``depth + 1`` items beyond what the
  consumer took, so host memory holds a bounded number of in-flight
  batches no matter how slow the step is;
* exceptions from the source or from ``place`` surface at ``next()``
  — never silently swallowed on a worker thread;
* ``close()`` is idempotent, unblocks a parked feeder, joins every
  thread and leaves nothing running (the shutdown-without-leak tests
  pin this); iterators also close themselves on exhaustion.

Donation-safe handoff: every batch out of ``next()`` is a fresh set of
arrays (``place`` makes new device buffers per batch), so feeding a
``DistributedTrainStep(donate_batch=True)`` is safe — the step may
donate the input buffers; nothing else aliases them.

Elastic: live iterators register in a process-wide set;
:func:`close_all` tears them all down — ``elastic._reset`` calls it
before rebuilding the backend, because queued device batches pin
buffers of the *old* world's client.  After reset, re-seed the dataset
at the restored step (``ShardedDataset.reshard`` + ``epoch(e,
start_sample=p)``) and build a fresh iterator.
"""

from __future__ import annotations

import itertools
import queue
import threading
import weakref
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable, Optional

from horovod_tpu import faults, telemetry
from horovod_tpu.runtime.config import _env_int

_LIVE: "weakref.WeakSet[PrefetchIterator]" = weakref.WeakSet()
_THREAD_PREFIX = "hvd-input"

_DEFAULT_DEPTH = 2
_DEFAULT_THREADS = 2


def _config_default(attr: str, env: str, fallback: int) -> int:
    """Knob resolution: runtime config when initialized (the env
    contract resolved at init()), a direct env read before init, the
    built-in default last."""
    from horovod_tpu.runtime import state

    if state.is_initialized():
        return int(getattr(state.global_state().config, attr))
    return _env_int(env, fallback)


def default_prefetch_depth() -> int:
    return max(_config_default("prefetch_depth", "HOROVOD_PREFETCH_DEPTH",
                               _DEFAULT_DEPTH), 1)


def default_input_threads() -> int:
    return max(_config_default("input_threads", "HOROVOD_INPUT_THREADS",
                               _DEFAULT_THREADS), 1)


class _End:
    """Queue sentinel: normal exhaustion, or a carried source error."""

    __slots__ = ("error",)

    def __init__(self, error: Optional[BaseException] = None):
        self.error = error


class PrefetchIterator:
    """Bounded, ordered, background-assembled batch iterator.

    ::

        feed = PrefetchIterator(dataset.iter_epochs(),
                                place=step.shard_batch)
        for batch in feed:            # or: batch = next(feed)
            params, opt, loss = step(params, opt, batch)
        feed.close()                  # or use as a context manager

    ``source`` is any iterable of host batches; ``place`` (optional)
    maps a host batch to its device placement and runs on the worker
    pool.  ``depth`` bounds the prefetch queue; ``threads`` sizes the
    assembly pool.  Both default to the runtime knobs.

    Instrumentation (what ``bench.py`` emits): ``stall_s`` accumulates
    wall time ``next()`` spent *blocked* waiting for a batch — the
    input stall the pipeline exists to eliminate — ``stall_samples``
    keeps the per-delivery values (medians over a window stay robust
    to one-off wakeup spikes, the ``median_rate`` discipline), and
    ``batches`` counts deliveries.  The flight recorder
    (``telemetry/spans.py``) holds the same wait as an ``input.wait``
    span, beside the feeder's ``input.source`` and the workers'
    ``input.place`` of the same batch ordinal.
    """

    def __init__(self, source: Iterable, place: Optional[Callable] = None,
                 depth: Optional[int] = None,
                 threads: Optional[int] = None,
                 name: str = "feed"):
        self._source = iter(source)
        self._place = place
        self.depth = int(depth) if depth is not None \
            else default_prefetch_depth()
        if self.depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got "
                             f"{self.depth}")
        self._threads = int(threads) if threads is not None \
            else default_input_threads()
        if self._threads < 1:
            raise ValueError(f"input threads must be >= 1, got "
                             f"{self._threads}")
        self._queue: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._closed = False
        self._exhausted = False
        self._lock = threading.Lock()
        self.stall_s = 0.0
        self.stall_samples: list = []
        self.batches = 0
        # telemetry (docs/metrics.md): queue depth is the gauge the
        # serving plane's autoscaling story scrapes; stall time is the
        # input plane's contract number
        self._tel_batches = telemetry.counter(
            "hvd_input_batches_total",
            "batches delivered by the input pipeline").labels(
                pipeline=name)
        self._tel_stall = telemetry.histogram(
            "hvd_input_stall_seconds",
            "time next() blocked waiting for a batch").labels(
                pipeline=name)
        self._tel_depth = telemetry.gauge(
            "hvd_input_queue_depth",
            "prefetch queue occupancy at delivery").labels(pipeline=name)
        self._pool = ThreadPoolExecutor(
            max_workers=self._threads,
            thread_name_prefix=f"{_THREAD_PREFIX}-{name}")
        self._feeder = threading.Thread(
            target=self._feed, name=f"{_THREAD_PREFIX}-{name}-feeder",
            daemon=True)
        self._feeder.start()
        _LIVE.add(self)

    # -- feeder side -------------------------------------------------------

    def _assemble(self, item, seq, source):
        if self._place is None:
            return item
        # the feeder's input.source of this batch caused this span
        with telemetry.span("input.place", seq=seq, parent=source):
            return self._place(item)

    def _feed(self) -> None:
        try:
            for seq in itertools.count():
                if self._stop.is_set():
                    return
                # chaos hook: a raise here surfaces at next() via the
                # _End sentinel (the documented worker-exception path);
                # a delay models a slow source
                faults.inject("data.feed")
                try:
                    with telemetry.span("input.source", seq=seq) as made:
                        item = next(self._source)
                except StopIteration:
                    self._put(_End())
                    return
                # submit BEFORE the (possibly blocking) queue put: the
                # H2D/device_put dispatch is exactly the work that must
                # start early, and the put is where backpressure parks
                # the feeder — at most depth+1 items are ever pulled
                # beyond what the consumer consumed
                self._put(self._pool.submit(self._assemble, item, seq,
                                            made.id))
        except BaseException as e:  # noqa: BLE001 — carried to next()
            self._put(_End(e))

    def _put(self, obj) -> None:
        while not self._stop.is_set():
            try:
                self._queue.put(obj, timeout=0.1)
                return
            except queue.Full:
                continue

    # -- consumer side -----------------------------------------------------

    def __iter__(self) -> "PrefetchIterator":
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration
        if self._closed:
            raise RuntimeError("PrefetchIterator is closed")
        with telemetry.span("input.wait", seq=self.batches) as wait:
            got = self._queue.get()
            if isinstance(got, _End):
                self._exhausted = True
                self.close()
                if got.error is not None:
                    raise got.error
                raise StopIteration
            try:
                batch = got.result()
            except BaseException:
                self.close()
                raise
            depth = self._queue.qsize()
            wait.attrs = {"depth": depth}
        dt = wait.seconds
        self.stall_s += dt
        self.stall_samples.append(dt)
        self.batches += 1
        self._tel_batches.inc()
        self._tel_stall.observe(dt)
        self._tel_depth.set(depth)
        return batch

    def close(self) -> None:
        """Tear down feeder + pool; idempotent, leak-free.  Queued
        batches are dropped (their device buffers released) — an
        elastic reset must not carry arrays of the old world across."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._stop.set()
        # the feeder may be parked in _put; it polls _stop every 100 ms,
        # and draining the queue lets it exit immediately instead
        while self._feeder.is_alive():
            try:
                while True:
                    got = self._queue.get_nowait()
                    if not isinstance(got, _End):
                        got.cancel()
            except queue.Empty:
                pass
            self._feeder.join(timeout=0.05)
        self._pool.shutdown(wait=True)
        while True:     # anything the feeder enqueued while draining
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        _LIVE.discard(self)

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "PrefetchIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # pragma: no cover - GC timing dependent
        try:
            self.close()
        except Exception:
            pass


def close_all() -> int:
    """Close every live :class:`PrefetchIterator` in the process —
    the elastic ``_reset`` hook (queued batches hold device buffers of
    the torn-down world).  Returns how many were closed."""
    closed = 0
    for it in list(_LIVE):
        if not it.closed:
            it.close()
            closed += 1
    return closed
