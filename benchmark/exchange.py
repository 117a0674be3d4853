"""The exchange, read where the compiler may have fused it.

``hlo.collectives`` and ``hlo.op_classes`` know a collective as a line of
its own.  XLA:TPU's async collective fusion runs an all-reduce in steps
instead, each step a ``fusion`` whose computation holds a clone of the
all-reduce (all clones carry one ``channel_id``) beside a start or done
custom call, an optimizer update, a weight-gradient matmul, or nothing.
Read by line, such an exchange counts its bytes once a clone and its time
as compute.  Read here:

- a *channel* is one collective, whatever number of lines carry it: the
  ``channel_id`` inside fusions, the line itself outside them (the CPU
  backend gives every collective the same ``channel_id``);
- an operation *holds* a channel when it is the collective, its
  ``-start`` or ``-done``, or a fusion whose computation contains it;
- it holds it *alone* when nothing else in it does work: a fusion whose
  computation has only the collective, plumbing (``PLUMBING``) and the
  async machinery's custom calls.  Any other operation, a ``copy`` too,
  makes the fusion *shared*: its time is the other work's as well;
- a channel is *in flight*, in each run of the step's program, from the
  start of the first operation that holds it to the end of the last;
- the exchange is *exposed* while an operation that holds a channel
  alone runs and no other operation does.

The same reading gives the parent's 16 synchronous all-reduces their own
32.6 ms (chip runs, PR 27), so both sides of a comparison are read by
one rule.  Nothing here imports the program.
"""

from __future__ import annotations

import bisect
import functools
import os
import re

from benchmark import hlo, trace

PLUMBING = {"parameter", "tuple", "get-tuple-element", "constant",
            "bitcast", "custom-call"}

# "%name (params) -> type {" / "ENTRY %name (...) -> type {": a
# computation's header, at column 0
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_CHANNEL_RE = re.compile(r"\bchannel_id=(\d+)")


def _parsed(text: str) -> tuple:
    """``(ops, fused)``: every operation of the module as ``(computation,
    name, opcode, line, where its operands begin)``, and ``{fusion: the
    computation it calls}``."""
    ops, fused, computation = [], {}, None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION_RE.match(line)
            computation = m.group(1) if m else None
            continue
        m = hlo._OP_RE.match(line)
        if m is None:
            continue
        name, _, opcode = m.groups()
        ops.append((computation, name, opcode, line, m.end()))
        if opcode == "fusion":
            called = _CALLS_RE.search(line)
            if called:
                fused[name] = called.group(1)
    return ops, fused


def _kind(opcode: str):
    """``(collective kind, suffix)`` of an opcode, or ``(None, "")``."""
    for suffix in ("-start", "-done", ""):
        if opcode.endswith(suffix):
            base = opcode[:len(opcode) - len(suffix)]
            if base in hlo.COLLECTIVE_KINDS:
                return base, suffix
    return None, ""


def read_step(text: str) -> dict:
    """``channels``: ``{channel: {"bytes", "group_size", "fused"}}``, each
    collective once; ``holds``: ``{operation the trace can show: (channels
    it holds, alone)}``."""
    ops, fused = _parsed(text)
    inside = set(fused.values())
    payload = {c["name"]: c for c in hlo.collectives(text)}
    channels, in_computation, busy = {}, {}, set()
    holds, started = {}, {}
    for computation, name, opcode, line, operands in ops:
        kind, suffix = _kind(opcode)
        if computation in inside:
            if kind is None:
                if opcode not in PLUMBING:
                    busy.add(computation)
                continue
            channel = _CHANNEL_RE.search(line)
            key = f"channel {channel.group(1)}" if channel else name
            in_computation.setdefault(computation, set()).add(key)
        elif kind is None:
            continue
        elif suffix == "-done":
            operand = hlo._OPERAND_RE.search(line, operands)
            key = started.get(operand.group(1)) if operand else None
            if key is not None:
                holds[name] = ({key}, True)
            continue
        else:
            key = name
            holds[name] = ({key}, True)
            if suffix == "-start":
                started[name] = key
        if name in payload:
            channels.setdefault(key, {
                "bytes": payload[name]["bytes"],
                "group_size": payload[name]["group_size"],
                "fused": computation in inside})
    for name, computation in fused.items():
        if computation in in_computation:
            holds[name] = (in_computation[computation],
                           computation not in busy)
    return {"channels": channels, "holds": holds}


def exchange_bytes(text: str, group_size: int) -> int:
    """Payload bytes a step of the collectives that span ``group_size``
    devices, each channel once."""
    return sum(c["bytes"] for c in read_step(text)["channels"].values()
               if c["group_size"] == group_size)


def _step_runs(modules: list) -> list:
    """``[start, end]`` of every run of the program the device spent most
    of its time in: the train step."""
    total, runs = {}, {}
    for name, start, duration in modules:
        total[name] = total.get(name, 0) + duration
        runs.setdefault(name, []).append([start, start + duration])
    return sorted(runs[max(total, key=total.get)]) if total else []


def _device(ops: list, modules: list, holds: dict):
    runs = _step_runs(modules)
    if not runs:
        return None
    starts = [r[0] for r in runs]
    timed = trace._self_times(
        [(trace.op_name(name), s, s + d) for name, s, d in ops
         if d > 0 and s + d > runs[0][0] and s < runs[-1][1]])
    alone, other, flights = [], [], {}
    for name, s, e, _, leaf in timed:
        held = holds.get(name)
        if held is not None:
            run = bisect.bisect_right(starts, s) - 1
            for channel in held[0]:
                flight = flights.setdefault((run, channel), [s, e])
                flight[0], flight[1] = min(flight[0], s), max(flight[1], e)
        if leaf:
            (alone if held is not None and held[1] else other).append([s, e])
    alone, other = trace.union(alone), trace.union(other)
    return {
        "runs": len(runs),
        "other_ns": trace.length(other),
        "in_flight_ns": trace.length(trace.union(flights.values())),
        "exposed_ns": trace.length(trace.subtract(alone, other)),
    }


def reduce_events(events: list, holds: dict) -> dict:
    """``exchange_s`` and ``exchange_exposed_s`` a run of the step's
    program, on the device with most other work (the others wait for it
    inside their collectives: ``trace.reduce_trace``'s rule).  Empty
    where no device ran a program or no operation holds a channel."""
    ops, modules = {}, {}
    for plane, line, name, start, duration in events:
        if trace.DEVICE_PLANE.match(plane):
            into = ops if line == trace.OPS_LINE else \
                modules if line == trace.MODULES_LINE else None
            if into is not None:
                into.setdefault(plane, []).append((name, start, duration))
    per_device = [d for d in (
        _device(ops[plane], modules.get(plane, []), holds)
        for plane in sorted(ops)) if d is not None]
    if not per_device or not holds:
        return {}
    dev = max(per_device, key=lambda d: d["other_ns"])
    return {"runs": dev["runs"],
            "exchange_s": dev["in_flight_ns"] / dev["runs"] / 1e9,
            "exchange_exposed_s": dev["exposed_ns"] / dev["runs"] / 1e9}


@functools.lru_cache(maxsize=1)
def _reduced(xplane_path: str, hlo_text: str) -> dict:
    return reduce_events(trace.load_events(xplane_path),
                         read_step(hlo_text)["holds"])


def of_run(obs) -> dict:
    """``reduce_events`` of the traced block of this run: the readers of
    ``loop.Observed`` are not handed the trace's events, so they are read
    again from where ``loop`` had the profiler write them."""
    if not obs.trace or not obs.hlo_text:
        return {}
    from benchmark import loop

    try:
        path = trace.newest_xplane(os.path.join(loop.TRACE_ROOT,
                                                obs.cell.name))
    except FileNotFoundError:
        return {}
    return _reduced(path, obs.hlo_text)
