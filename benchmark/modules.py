"""Device time by the model's modules, read from the compiled step's
``op_name`` paths.

A flax module runs under its name, so an operation of the compiled step
carries the path of the module it was lowered from in its metadata
(``op_name="jit(step)/.../layer_3/moe/experts/..."``): the same
mechanism that yields ``fwd_wi/dot_general`` in the ledger's
``breakdown``.  Here the profiler's events are added up by the *kind* of
mixer — ``mamba``, ``moe``, ``attn`` — and below it by the named part
(``moe/experts``, ``mamba/ssd``), forward, recomputation and backward
alike.  Rules, each a choice:

- an operation belongs to the module of its *own* path.  A fusion's path
  is its root's: where XLA fuses across a module boundary (a mixer's
  last matmul with the residual add after it, the next layer's norm with
  the add before it) the whole fusion goes to the module its root came
  from, none of it to the other;
- a Mosaic call under an ``attn`` path is kernel ``gqa_flash``.  The
  experts' grouped matmuls are known by their instruction's name, which
  is the Pallas kernel's (``gmm``, ``tgmm``): XLA hands such a call the
  metadata of whatever it fused into its operands, so its path may be a
  neighbour's.  They are kernel ``grouped_matmul``, module ``moe``, part
  ``experts``.  Neither reads the other's time (``flash_ms`` reads every
  Mosaic call of a step as flash, and so applies only where flash is
  the one kernel);
- an enclosing operation (``while``, ``conditional``, ``call``) is
  charged only what its children leave (``trace._self_times``).

Seconds are totals over the traced block; a reader divides by the steps
traced.  Nothing here imports the program, and a step that holds none
of these names (any other configuration, a parent commit) reads as
nothing.
"""

from __future__ import annotations

import functools
import os
import re

from benchmark import hlo, trace

KINDS = ("mamba", "moe", "attn")
PARTS = {"moe": ("router", "dispatch", "experts", "shared", "combine"),
         "mamba": ("conv", "ssd", "gated_norm")}
GROUPED = re.compile(r"^t?gmm(\.\d+)?$")


def classify(name: str, opcode: str, line: str) -> tuple:
    """``(kind, part, kernel)`` of one operation; each may be None."""
    mosaic = opcode == "custom-call" and hlo.MOSAIC_TARGET in line
    if mosaic and GROUPED.match(name):
        return "moe", "experts", "grouped_matmul"
    source = hlo._SOURCE_RE.search(line)
    if source is None:
        return None, None, None
    steps = source.group(1).split("/")
    kind = next((s for s in steps if s in KINDS), None)
    if kind is None:
        return None, None, None
    part = next((s for s in steps if s in PARTS.get(kind, ())), None)
    return kind, part, "gqa_flash" if mosaic and kind == "attn" else None


def read_step(text: str) -> dict:
    """``{operation name: (kind, part, kernel)}`` for the operations of
    the compiled step that belong to a mixer."""
    out = {}
    for line in text.splitlines():
        m = hlo._OP_RE.match(line)
        if m is None:
            continue
        name, _, opcode = m.groups()
        found = classify(name, opcode, line)
        if found[0] is not None:
            out[name] = found
    return out


def reduce_events(events: list, known: dict) -> dict:
    """``{"module_s": {"moe": s, "moe/experts": s, ...}, "kernel_s":
    {"grouped_matmul": s, ...}}`` on the first device plane that ran
    operations; empty where none of them belongs to a mixer."""
    ops = {}
    for plane, line, name, start, duration in events:
        if trace.DEVICE_PLANE.match(plane) and line == trace.OPS_LINE \
                and duration > 0:
            ops.setdefault(plane, []).append(
                (trace.op_name(name), start, start + duration))
    if not ops or not known:
        return {}
    module_s, kernel_s = {}, {}
    for name, _, _, self_ns, _ in trace._self_times(ops[sorted(ops)[0]]):
        kind, part, kernel = known.get(name, (None, None, None))
        if kind is None:
            continue
        module_s[kind] = module_s.get(kind, 0.0) + self_ns / 1e9
        if part is not None:
            key = f"{kind}/{part}"
            module_s[key] = module_s.get(key, 0.0) + self_ns / 1e9
        if kernel is not None:
            kernel_s[kernel] = kernel_s.get(kernel, 0.0) + self_ns / 1e9
    if not module_s:
        return {}
    return {"module_s": module_s, "kernel_s": kernel_s}


@functools.lru_cache(maxsize=1)
def _reduced(xplane_path: str, hlo_text: str) -> dict:
    return reduce_events(trace.load_events(xplane_path),
                         read_step(hlo_text))


def of_run(obs) -> dict:
    """``reduce_events`` of the traced block of this run, the profile
    loaded as ``exchange.of_run`` loads it."""
    if not obs.trace or not obs.hlo_text:
        return {}
    from benchmark import loop

    try:
        path = trace.newest_xplane(os.path.join(loop.TRACE_ROOT,
                                                obs.cell.name))
    except FileNotFoundError:
        return {}
    return _reduced(path, obs.hlo_text)


def ms_per_step(obs, group: str, key: str):
    """Milliseconds a traced step under ``module_s`` / ``kernel_s``."""
    seconds = of_run(obs).get(group, {}).get(key)
    if seconds is None or not obs.traced_steps:
        return None
    return seconds / obs.traced_steps * 1e3
