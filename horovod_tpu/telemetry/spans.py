"""The flight recorder: a bounded ring of finished host spans.

The registry (``registry.py``) holds values; this holds *intervals* —
when the train step's host side, the compile path and the input
pipeline each began and ended, on which thread, for which step or
batch::

    with telemetry.span("train_step.call", seq=n) as call:
        ...
    call.seconds                      # the duration, span recorded or not

A finished span is one :class:`Span` ``(id, parent, name, start, end,
thread, seq, attrs)``.  ``start``/``end`` are ``time.perf_counter()``
seconds.  ``parent`` is the id of the span open on the same thread when
this one began, or the id passed as ``parent=`` where work crosses
threads (the feeder's ``input.source`` causes a worker's
``input.place``).  ``seq`` is what the spans of one unit of work share —
a step's ordinal in its ``DistributedTrainStep``, a batch's in its
``PrefetchIterator`` — and is inherited from the enclosing span when not
given.  A span that an exception ends is recorded with
``attrs["error"]``, the exception's type name.

On in every process, in memory only: the last :data:`CAPACITY` spans,
whatever happens, so that the record of a stall exists *after* the stall
(docs/metrics.md "Spans").  Nothing is written anywhere unless asked:
:func:`snapshot` hands out what the ring holds, and while a timeline is
running (``hvd.start_timeline`` / ``HOROVOD_TIMELINE``) every span also
opens and closes a timeline activity — lane = thread name, activity =
span name — so the Chrome trace gains the train step's host side
(docs/timeline.md).

One span nobody opens: ``host.gc``, a garbage collection that stopped
the interpreter for :data:`GC_FLOOR_S` or longer (``attrs``:
``generation``, ``collected``), from a ``gc.callbacks`` hook that is
installed while the recorder is enabled.  The hook times every
collection and changes nothing about when or how the collector runs;
shorter pauses (generation 0 sweeps run hundreds of times a second
while a step is traced) are not recorded, so they cannot flush the
ring.  It is in the ring only, never on a timeline: the collector runs
its callbacks wherever an allocation trips it, which may be inside the
timeline queue's own ``put`` with its lock held, so the hook takes no
lock and calls nothing that does.
"""

from __future__ import annotations

import collections
import gc
import itertools
import threading
import time
from typing import NamedTuple, Optional

from horovod_tpu.runtime import state as _rt_state

CAPACITY = 65536
#: the shortest collector pause recorded as a ``host.gc`` span, seconds
GC_FLOOR_S = 1e-3


class Span(NamedTuple):
    id: int
    parent: Optional[int]
    name: str
    start: float
    end: float
    thread: str
    seq: Optional[int]
    attrs: Optional[dict]


_ring: collections.deque = collections.deque(maxlen=CAPACITY)
_ids = itertools.count(1)
_local = threading.local()
_enabled = True
_now = time.perf_counter


def enable() -> None:
    global _enabled
    _enabled = True
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def disable() -> None:
    """Stop recording (spans still time themselves: their ``seconds``
    feed ``stall_samples`` and the registry's histograms) and take the
    collector's hook away."""
    global _enabled
    _enabled = False
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def snapshot(since: Optional[float] = None,
             until: Optional[float] = None) -> list:
    """The recorded spans that overlap ``[since, until]`` (``None`` =
    unbounded; ``perf_counter`` seconds), oldest start first."""
    spans = [Span._make(s) for s in list(_ring)
             if (since is None or s[4] >= since)
             and (until is None or s[3] <= until)]
    spans.sort(key=lambda s: s.start)
    return spans


def annotate(**attrs) -> None:
    """Add ``attrs`` to the innermost span open on this thread; nothing
    where none is open.  How code that runs *inside* a span it did not
    open says what it saw: a model traced under ``train_step.lower``
    names its shapes there."""
    stack = getattr(_local, "stack", None)
    if stack is not None and len(stack) > 1:
        stack[-1].attrs = {**(stack[-1].attrs or {}), **attrs}


class span:
    """Context manager timing one span; ``attrs`` may be set on the
    handle until it exits, ``id`` passed to another thread as its
    ``parent``."""

    __slots__ = ("name", "seq", "parent", "attrs", "id", "start", "end",
                 "_stack", "_lane")

    def __init__(self, name: str, seq: Optional[int] = None,
                 parent: Optional[int] = None,
                 attrs: Optional[dict] = None):
        self.name = name
        self.seq = seq
        self.parent = parent
        self.attrs = attrs
        self.id = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "span":
        if _enabled:
            try:
                stack = self._stack = _local.stack
            except AttributeError:
                # [thread name, open span, open span, ...]
                stack = self._stack = _local.stack = \
                    [threading.current_thread().name]
            if len(stack) > 1:
                outer = stack[-1]
                if self.parent is None:
                    self.parent = outer.id
                if self.seq is None:
                    self.seq = outer.seq
            self.id = next(_ids)
            stack.append(self)
            st = _rt_state._state
            if st is not None and st.timeline is not None:
                # a nested span takes a lane of its own: the timeline's
                # annotation bridge keeps one open activity a
                # (thread, lane) and would close the parent's instead
                depth = len(stack) - 2
                self._lane = (st.timeline, f"{stack[0]}/{depth}"
                              if depth else stack[0])
                st.timeline.start_activity(self._lane[1], self.name)
            else:
                self._lane = None
        self.start = _now()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.end = _now()
        if self.id is not None:
            stack = self._stack
            stack.pop()
            if exc_type is not None:
                self.attrs = dict(self.attrs or (),
                                  error=exc_type.__name__)
            _ring.append((self.id, self.parent, self.name, self.start,
                          self.end, stack[0], self.seq, self.attrs))
            if self._lane is not None:
                self._lane[0].end_activity(self._lane[1])
        return False


_gc_began = 0.0


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: the collection just ended becomes a
    ``host.gc`` span if it lasted :data:`GC_FLOOR_S`.  Runs wherever the
    collector was tripped, so it only reads the clock and appends to the
    ring (``deque.append`` is atomic and takes no lock)."""
    global _gc_began
    if phase == "start":
        _gc_began = _now()
        return
    end = _now()
    if end - _gc_began >= GC_FLOOR_S:
        _ring.append((next(_ids), None, "host.gc", _gc_began, end,
                      threading.current_thread().name, None,
                      {"generation": info["generation"],
                       "collected": info["collected"]}))


enable()
