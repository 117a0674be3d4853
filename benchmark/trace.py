"""From a profiler trace to per-layer numbers.

The reducer works on a plain list of events ``(plane, line, name,
start_ns, duration_ns)`` so that a hand-written list with known answers
tests it (``fixtures/``); ``load_events`` makes that list from the
``.xplane.pb`` file ``jax.profiler`` writes, with JAX alone.

On a device plane the line ``XLA Ops`` holds one event an executed HLO
operation, named by the operation's whole instruction text (``%fusion.7
= bf16[...] fusion(...), kind=...``: the name is what stands before the
``=``), and ``XLA Modules`` one event a program run.  Asynchronous copies
have a line of their own, ``Async XLA Ops``, which is not read: their
``-start`` and ``-done`` are on ``XLA Ops`` too.  The benchmark's own spans (``next_batch``,
``dispatch``, ``read_loss``, and ``traced_window`` around the traced
steps) are events of the host plane on the same clock: ``host_events``
puts them there from the benchmark's own host-clock records.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "traced_window"
CLOCK_MARK = "benchmark_clock_mark"     # a jitted function of this name
HOST_SPANS = ("next_batch", "dispatch", "read_loss")


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_events(xplane_path: str) -> list:
    """Every event of the device planes' ``XLA Ops`` and ``XLA Modules``
    lines.  Of an operation's kilobyte of instruction text only its name
    is kept (a four-chip trace holds 190,000 of them)."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    return [(plane.name, line.name, op_name(e.name), e.start_ns,
             e.duration_ns)
            for plane in data.planes if DEVICE_PLANE.match(plane.name)
            for line in plane.lines if line.name in (OPS_LINE, MODULES_LINE)
            for e in line.events]


def host_events(device_events: list, spans: dict, window: tuple,
                launches: list) -> list:
    """The benchmark's spans as events on the trace's clock.

    ``spans`` maps a name to ``(start, seconds)`` records on the host's
    ``perf_counter`` and ``window`` is the traced block's ``(start, end)``
    on it.  ``launches`` ties the two clocks: each is the host's time just
    before one fenced run of the tiny ``CLOCK_MARK`` program while the
    profiler was on, and the trace holds that run as a program event.  A
    run starts on the device as soon as the host has launched it (and is
    reported back only milliseconds after it ends: chip runs, PR 22), so
    its start is tied to its launch: the quickest launch of the few places
    the host's spans late by at most that launch's latency, about a
    millisecond on this runtime — enough to say under which span a gap of
    milliseconds lies, not to time anything.  (The profiler's own host
    tracer, when it was still on, put the step's first program 0.1 ms
    *before* the host call that launches it began: its two clocks are no
    closer.)"""
    marks = sorted(start for _, line, name, start, _ in device_events
                   if line == MODULES_LINE and CLOCK_MARK in name)
    if not marks or len(marks) != len(launches):
        return []
    offset_ns = min(start - launched * 1e9
                    for launched, start in zip(launches, marks))
    events = [(HOST_PLANE, "benchmark", WINDOW_SPAN,
               window[0] * 1e9 + offset_ns, (window[1] - window[0]) * 1e9)]
    for name in HOST_SPANS:
        events.extend((HOST_PLANE, "benchmark", name,
                       begin * 1e9 + offset_ns, seconds * 1e9)
                      for begin, seconds in spans.get(name, ())
                      if window[0] <= begin < window[1])
    return events


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals) -> list:
    """Sorted, disjoint ``[start, end]`` covering the same points."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """The part of the disjoint sorted ``a`` that no interval of the
    disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cursor = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cursor:
                out.append([cursor, b[k][0]])
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < e:
            out.append([cursor, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _overlap(s, e, intervals) -> float:
    return sum(max(0.0, min(e, b) - max(s, a)) for a, b in intervals)


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def op_name(event_name: str) -> str:
    """``fusion.7`` from ``%fusion.7 = bf16[...] fusion(...), ...``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _self_times(ops: list) -> list:
    """``(name, start, end, self_ns, is_leaf)`` an event: an enclosing
    operation (a ``while``, a ``call``) is charged only the time its
    children leave — the interval stack of
    ``examples/profile_resnet.exclusive_op_times``."""
    out, stack = [], []
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(e, parent[2]) - s
            parent[4] = False
        out.append([name, s, e, e - s, True])
        stack.append(len(out) - 1)
    return out


def _collective_intervals(timed: list, classes: dict, done_of: dict) -> list:
    """An async collective lasts from its ``-start`` to the end of the
    ``-done`` that takes it (the n-th run of one with the n-th run of the
    other); a synchronous one lasts its own event."""
    intervals, open_starts = [], {}
    for name, s, e, _, _ in timed:
        kind = classes.get(name)
        if kind == "collective":
            intervals.append([s, e])
        elif kind == "collective-start":
            open_starts.setdefault(name, []).append(s)
        elif kind == "collective-done":
            pending = open_starts.get(done_of.get(name))
            intervals.append([pending.pop(0) if pending else s, e])
    return intervals


def _device(ops, modules, window, known: dict) -> dict:
    classes, done_of = known["classes"], known["done_of"]
    lo, hi = window
    # an operation that takes no time (a bitcast) starts with the next
    # one and would pass for its child
    timed = _self_times([(op_name(n), s, s + d) for n, s, d in ops
                         if d > 0 and s + d > lo and s < hi])
    busy = clip(union([t[1], t[2]] for t in timed), lo, hi)
    collective = clip(union(_collective_intervals(timed, classes, done_of)),
                      lo, hi)
    compute = clip(union(
        [t[1], t[2]] for t in timed
        if t[4] and not classes.get(t[0], "compute").startswith("collective")),
        lo, hi)
    class_ns, op_ns, unknown_ns = {}, {}, 0.0
    for name, _, _, self_ns, _ in timed:
        kind = classes.get(name)
        if kind is None:
            kind, unknown_ns = "compute", unknown_ns + self_ns
        kind = "collective" if kind.startswith("collective") else kind
        class_ns[kind] = class_ns.get(kind, 0.0) + self_ns
        op_ns[name] = op_ns.get(name, 0.0) + self_ns
    return {
        "busy": busy, "busy_ns": length(busy),
        "compute_ns": length(compute),
        "collective_ns": length(collective),
        "collective_exposed_ns": length(subtract(collective, compute)),
        "class_ns": class_ns, "op_ns": op_ns, "unknown_ns": unknown_ns,
        "modules": clip(union([s, s + d] for _, s, d in modules), lo, hi),
    }


def _attribute_gaps(gaps, in_program, host_spans) -> dict:
    """Idle nanoseconds by what the host was doing: the part of a gap
    inside a program run is the device's own (``in_program``); the rest
    goes to the benchmark's span that covers most of it, or to
    ``outside_spans``."""
    by = {}
    for s, e in gaps:
        inside = _overlap(s, e, in_program)
        if inside:
            by["in_program"] = by.get("in_program", 0.0) + inside
        rest = (e - s) - inside
        if rest <= 0:
            continue
        covers = {name: _overlap(s, e, spans)
                  for name, spans in host_spans.items()}
        best = max(covers, key=covers.get, default=None)
        label = best if best and covers[best] > 0 else "outside_spans"
        by[label] = by.get(label, 0.0) + rest
    return by


def reduce_trace(events: list, known: dict) -> dict:
    """The numbers the per-layer readers take.  Seconds are totals over
    the traced window; the readers divide by the steps traced.

    ``known`` is ``hlo.op_classes`` of the compiled step: ``classes``,
    ``done_of`` and, for the breakdown's names, ``labels``.  The *slowest* device is the one with most
    non-collective work: the others wait for it inside their
    collectives, so its exposed collective time is the exchange's own
    cost and its idle time is the host's doing."""
    ops, modules, host = {}, {}, {}
    window = None
    for plane, line, name, start, duration in events:
        if DEVICE_PLANE.match(plane):
            if line == OPS_LINE:
                ops.setdefault(plane, []).append((name, start, duration))
            elif line == MODULES_LINE:
                modules.setdefault(plane, []).append((name, start, duration))
        elif name == WINDOW_SPAN:
            window = (start, start + duration)
        elif name in HOST_SPANS:
            host.setdefault(name, []).append([start, start + duration])
    if not ops:
        return {}
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    per_device = {plane: _device(ops[plane], modules.get(plane, []), window,
                                 known) for plane in sorted(ops)}
    slowest = max(per_device, key=lambda p: per_device[p]["compute_ns"])
    dev = per_device[slowest]
    gaps = subtract([list(window)], dev["busy"])
    by_host = _attribute_gaps(gaps, dev["modules"],
                              {n: union(v) for n, v in host.items()})

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    # operations that do the same thing in every layer share a label:
    # added up, ten lines cover most of a step
    labels, by_label = known.get("labels", {}), {}
    for name, ns in dev["op_ns"].items():
        entry = by_label.setdefault(labels.get(name, name), [0.0, 0])
        entry[0] += ns
        entry[1] += 1
    device_ops = {f"{label} x{count}" if count > 1 else label: ns
                  for label, (ns, count) in by_label.items()}

    window_ns = window[1] - window[0]
    return {
        "devices": len(per_device), "slowest": slowest,
        "window_s": window_ns / 1e9,
        "busy_s": sum(d["busy_ns"] for d in per_device.values())
        / len(per_device) / 1e9,
        "idle_share": 1.0 - dev["busy_ns"] / window_ns,
        "class_s": {k: v / 1e9 for k, v in dev["class_ns"].items()},
        "collective_s": dev["collective_ns"] / 1e9,
        "collective_exposed_s": dev["collective_exposed_ns"] / 1e9,
        "unclassified_s": dev["unknown_ns"] / 1e9,
        "device_ops": top(device_ops),
        "idle_gaps": top(by_host),
    }
