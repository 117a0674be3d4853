"""Per-layer readers, one a file, found by the metric's name in
``BENCHMARK.json`` (which holds its unit, layer, source and the
end-to-end metric it should move).  A reader provides::

    applies(config, job) -> bool     # by property, never by cell name
    read(observed) -> float | None   # None: nothing to read, left out

``observed`` is ``benchmark.loop.Observed``.  Trace seconds are totals
over the traced steps; a reader divides by ``observed.traced_steps``.
"""

from __future__ import annotations

import statistics


def median_ms(seconds) -> float | None:
    return statistics.median(seconds) * 1e3 if seconds else None


def trace_ms_per_step(obs, seconds) -> float | None:
    """A total of the traced window, as milliseconds a step."""
    if not obs.trace or seconds is None:
        return None
    return seconds / obs.traced_steps * 1e3


def class_seconds(obs, kind: str) -> float | None:
    """Device seconds of the traced window in operations of one class of
    ``hlo.op_classes`` (``compute``, ``mosaic``)."""
    return obs.trace.get("class_s", {}).get(kind)
