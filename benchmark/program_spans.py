"""The program's own spans, as the per-layer readers take them.

``horovod_tpu.telemetry.spans`` is the program's flight recorder: an
always-on ring of finished host spans ``(id, parent, name, start, end,
thread, seq, attrs)`` on ``time.perf_counter`` — the clock of the
benchmark's own ``observe.Spans``, so the two compare unchanged.  The
readers take a snapshot of it after the run and keep what the window
holds: the window is the first ``next_batch``'s start to the last
``read_loss``'s end of ``observed.spans``, which is ``loop._window``'s
``opened`` and ``closed`` to within a few microseconds.

A program without the recorder (an older commit) gives no spans: every
function here then returns ``None`` or an empty list, and the reader's
metric is left out.
"""

from __future__ import annotations


def observed(obs):
    """``(opened, closed, spans)``: the measured window on the spans'
    clock and the recorder's spans up to its close, set-up's included,
    oldest first.  ``None`` before a window ran, where the program has no
    recorder, or where the recorder holds nothing."""
    try:
        from horovod_tpu.telemetry import spans
    except ImportError:
        return None
    first = obs.spans.records.get("next_batch")
    last = obs.spans.records.get("read_loss")
    if not first or not last:
        return None
    opened, closed = first[0][0], last[-1][0] + last[-1][1]
    recorded = spans.snapshot(until=closed)
    return (opened, closed, recorded) if recorded else None


def seconds_in_window(obs, name: str) -> list:
    """Seconds of each span of ``name`` that lies wholly inside the
    window."""
    got = observed(obs)
    if got is None:
        return []
    opened, closed, spans = got
    return [s.end - s.start for s in spans
            if s.name == name and s.start >= opened and s.end <= closed]


def busy_share(obs, name: str):
    """Per cent of the window's blocks covered by spans of ``name``
    (which one thread records one after another, so they do not
    overlap).  Over the blocks, not the window: in a traced run the
    window also holds the profiler's start and stop, between blocks,
    while the pipeline is parked."""
    got = observed(obs)
    if got is None:
        return None
    spans = [s for s in got[2] if s.name == name]
    begins, ends = obs.spans.records["next_batch"], \
        obs.spans.records["read_loss"]
    steps = len(begins) // len(ends)
    busy = total = 0.0
    for i, (read, seconds) in enumerate(ends):
        lo, hi = begins[i * steps][0], read + seconds
        total += hi - lo
        busy += sum(max(0.0, min(s.end, hi) - max(s.start, lo))
                    for s in spans)
    return 100.0 * busy / total


def setup_seconds(obs, name: str):
    """Seconds in spans of ``name`` between the measured step's first
    call and the window's opening.  That call is the latest
    ``train_step.call`` of ordinal 0 before the window: the parity
    check's steps are earlier instances with an ordinal 0 of their own.
    More than the first step's: warm-up compiled again."""
    got = observed(obs)
    if got is None:
        return None
    opened, _, spans = got
    firsts = [s.start for s in spans if s.name == "train_step.call"
              and s.seq == 0 and s.start < opened]
    if not firsts:
        return None
    return sum(s.end - s.start for s in spans if s.name == name
               and s.start >= firsts[-1] and s.end <= opened)
