"""What the benchmark observes for itself: compilations, device memory,
and host spans around its calls into each layer of the program."""

from __future__ import annotations

import contextlib
import time

import jax


class CompileCounter:
    """Counts backend compilations through ``jax.monitoring`` — the
    listener sees every XLA compile of the process, served from the
    persistent cache or not (copied from ``chip_smoke.py``)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.count += 1


def device_footprint_bytes() -> dict:
    """``{device id: bytes_in_use + bytes_reserved}``: live arrays plus
    the region the loaded executable keeps for its temporaries.
    ``peak_bytes_in_use`` alone counts only the former (PERF.md, PR 21).
    A backend that keeps no such statistics is an error here."""
    out = {}
    for d in jax.devices():
        stats = d.memory_stats()
        if not stats or "bytes_in_use" not in stats \
                or "bytes_reserved" not in stats:
            raise RuntimeError(
                f"device {d} reports no bytes_in_use / bytes_reserved: "
                f"{stats}")
        out[d.id] = stats["bytes_in_use"] + stats["bytes_reserved"]
    return out


def devices_holding(tree) -> set:
    """Ids of the devices that hold a shard of any leaf of ``tree``."""
    return {shard.device.id for leaf in jax.tree_util.tree_leaves(tree)
            for shard in leaf.addressable_shards}


class Spans:
    """Host-clock spans by name, kept in memory: ``(start, seconds)`` on
    ``time.perf_counter``.  The profiler's own host tracer is left off (at
    any level it logs the runtime's transfer threads, some 900,000 events
    a 38.5 MB batch, which starved the host-fed ResNet cell to a fifth of
    its rate — chip runs, PR 22); ``trace.host_events`` puts these spans
    on the trace's clock instead."""

    def __init__(self):
        self.records: dict = {}        # name -> [(start, seconds), ...]

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.records.setdefault(name, []).append(
                (t0, time.perf_counter() - t0))

    def seconds(self, name: str) -> list:
        return [d for _, d in self.records.get(name, [])]
