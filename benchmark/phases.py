"""Device time by the phase of the compiled step, read from the scopes the
program lays (docs/metrics.md "Scopes inside the compiled step").

``DistributedTrainStep`` runs the phases of its step under
``jax.named_scope`` — ``loss_fn``, ``exchange`` (with the sharded
exchange's ``exchange/scatter`` and ``exchange/gather``), ``guard``,
``update`` — and the models lay ``head`` and ``loss`` beside flax's
module names.  An operation of the compiled step carries those names as
steps of its ``op_name`` path, so the table ``{operation name: (group,
part, kernel)}`` built here sorts the profiler's events by them through
``modules.reduce_events`` (``benchmark/modules.py`` as accepted, whose
rule for an enclosing operation — it keeps what its children leave —
holds here too).  The rules, each a choice:

- an operation belongs to the group of its *own* path.  A fusion's is
  its root's, which XLA hands the fusion: where it fuses across a
  boundary (the AdamW update into a weight gradient's matmul, on one
  chip) the whole fusion goes to its root's group, none to the other;
- **a fusion that carries no path of its own** is read through the
  computation it calls: it takes the path of that computation's root
  instruction; where the root has none (the tuple of a multi-output
  fusion, a slice or an async start XLA made) that of the instruction
  nearest the root that has one; where none has, it holds a collective
  or it does not: one that does is ``exchange_fused`` (XLA's
  reduce-scatter decomposer writes pad + all-reduce + dynamic-slice
  without metadata: the exchange's, on a program with scopes or
  without), one that does not is unnamed;
- groups by path step, in this precedence: the innermost of
  ``exchange`` (parts ``scatter``, ``gather``), ``update``, ``guard``;
  then ``head``; ``loss``; ``embed``; ``model`` — a flax module below
  the model's root (``layer_3/attn/...``, any module of the ResNet),
  with the mixer it belongs to as its part (``modules.KINDS`` and
  ``hc``, ``mlp``); ``loss_fn`` with none of these below it (the
  user's own loss arithmetic); else ``unnamed``: nothing is left of the
  path once JAX's own wrappers (``jit(..)``, ``pjit``, ``jvp(..)``,
  ``transpose(..)``, ``shard_map``, ``checkpoint``,
  ``rematted_computation``, ``custom_vjp*``, ``while`` / ``body`` /
  ``cond`` / ``branch_*``), the model's class name and the final
  primitive are taken away — or there is no path at all (XLA's own
  copies between memory spaces);
- the experts' grouped matmuls are known by their instruction's name
  (``gmm``, ``tgmm``: their path may be a neighbour's,
  ``modules.classify``) and are ``model`` / ``moe``; a Mosaic call named
  ``flash_fwd`` / ``flash_bwd`` is that kernel;
- collectives (``hlo.op_classes``' ``collective*``) are left to the
  ``exchange_*`` readers and are in no group.

Read on the device ``trace.reduce_trace`` took for the slowest.  Seconds
are totals over the traced block.  A step that holds none of the
program's scopes (a parent commit) reads ``None`` for the by-scope
groups and has all its time outside flax's modules in ``unnamed``.

**Whose names they are.**  JAX's compile-cache key leaves locations — and
so every scope — out, so a cache that another tree filled serves this
program an executable with that tree's names (a step with no Mosaic
body, the ResNet's, moves its key with nothing else).  The program says
what it laid (``step_scopes`` on its ``train_step.lower`` span): where
that and the executable's text disagree — scopes said and none held, or
held and none said — the names are not this program's, and every reader
here reads ``None`` (:func:`foreign_names`); one line on standard error
says so.  The program is read through its recorder alone
(``program_spans``).
"""

from __future__ import annotations

import functools
import os
import re
import sys

from benchmark import hlo, modules, trace
from benchmark import program_spans as ps

SCOPES = ("exchange", "update", "guard")        # the innermost wins
PARTS = {"exchange": ("scatter", "gather")}
NAMED = ("head", "loss", "embed")
MIXERS = modules.KINDS + ("hc", "mlp")
# the groups a step of a program without the scopes cannot hold
BY_SCOPE = SCOPES + ("head", "loss", "loss_fn")
FLASH = re.compile(r"^(flash_fwd|flash_bwd)(\.\d+)?$")

_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_WRAPPED_RE = re.compile(r"^(\w+)\((.*)\)$")
# name(inner): a transformation of the scope ``inner``, which it keeps
_TRANSFORMS = ("jvp", "transpose", "vmap", "pmap", "remat", "checkpoint",
               "custom_jvp", "custom_vjp")
_WRAPPERS = re.compile(
    r"^(pjit|shard_map|checkpoint|remat|rematted_computation|while|body|"
    r"cond|closed_call|core_call|custom_[jv][jv]p\w*|branch_\d+)$")


def steps(path: str) -> list:
    """The steps of an ``op_name`` path that a scope or a module laid:
    the final primitive and JAX's own wrappers taken away."""
    out = []
    for step in path.split("/")[:-1]:
        wrapped = _WRAPPED_RE.match(step)
        while wrapped and wrapped.group(1) in _TRANSFORMS:
            step = wrapped.group(2)
            wrapped = _WRAPPED_RE.match(step)
        # jit(f) names a function, not a scope
        if step and not wrapped and not _WRAPPERS.match(step):
            out.append(step)
    return out


def group_of(path) -> tuple:
    """``(group, part)`` of an ``op_name`` path, by the precedence of
    the module's docstring; ``part`` may be None."""
    found = steps(path) if path else []
    at = max((i for i, s in enumerate(found) if s in SCOPES), default=None)
    if at is not None:
        group = found[at]
        below = found[at + 1] if at + 1 < len(found) else None
        return group, below if below in PARTS.get(group, ()) else None
    for name in NAMED:
        if name in found:
            return name, None
    # what is left begins with the model's class name (and holds it
    # again where a rematerialised block runs under the backward's)
    rest = [s for s in found if s != "loss_fn"]
    below_root = [s for s in rest if s != rest[0]]
    if below_root:
        return "model", next((s for s in below_root if s in MIXERS), None)
    return ("loss_fn" if "loss_fn" in found else "unnamed"), None


def _computations(text: str) -> dict:
    """``{computation: (path read through it, holds a collective)}``:
    the root's path, else that of the instruction nearest the root."""
    out, name, path, holds = {}, None, None, False
    for line in text.splitlines():
        header = _COMPUTATION_RE.match(line)
        if header:
            name, path, holds = header.group(1), None, False
            continue
        op = hlo._OP_RE.match(line)
        if name is None or op is None:
            continue
        opcode = op.group(3)
        for suffix in ("-start", "-done"):
            if opcode.endswith(suffix):
                opcode = opcode[:-len(suffix)]
        holds = holds or opcode in hlo.COLLECTIVE_KINDS
        source = hlo._SOURCE_RE.search(line)
        if source:
            path = source.group(1)          # the last stands nearest
        out[name] = (path, holds)
    return out


@functools.lru_cache(maxsize=1)
def read_step(text: str) -> dict:
    """``{operation name: (group, part, kernel)}``, the table
    ``modules.reduce_events`` takes, for every operation of the compiled
    step that is not a collective."""
    classes = hlo.op_classes(text)["classes"]
    computations = _computations(text)
    known = {}
    for line in text.splitlines():
        op = hlo._OP_RE.match(line)
        if op is None:
            continue
        name, _, opcode = op.groups()
        if classes[name].startswith("collective"):
            continue
        mosaic = classes[name] == "mosaic"
        if mosaic and modules.GROUPED.match(name):
            known[name] = ("model", "moe", None)
            continue
        source = hlo._SOURCE_RE.search(line)
        path, holds = source.group(1) if source else None, False
        calls = _CALLS_RE.search(line)
        if path is None and opcode == "fusion" and calls:
            path, holds = computations.get(calls.group(1), (None, False))
        flash = FLASH.match(name) if mosaic else None
        known[name] = (("exchange_fused", None) if path is None and holds
                       else group_of(path)) \
            + (flash.group(1) if flash else None,)
    return known


@functools.lru_cache(maxsize=1)
def _reduced(xplane_path: str, plane, hlo_text: str) -> dict:
    events = [e for e in trace.load_events(xplane_path)
              if plane is None or e[0] == plane]
    return modules.reduce_events(events, read_step(hlo_text))


def lays_scopes(obs) -> bool:
    """Whether the compiled step holds any of the program's scopes."""
    return any(group in BY_SCOPE
               for group, _, _ in read_step(obs.hlo_text).values())


def said_scopes(obs):
    """The scopes the program says its measured step entered: the
    ``step_scopes`` attribute of the last ``train_step.lower`` span
    before the window opened (the measured step's; the parity check's
    steps lower earlier).  ``""`` from a program that says none (a
    parent commit); None where the recorder holds no such span."""
    got = ps.observed(obs)
    if got is None:
        return None
    opened, _, spans = got
    lowered = [s for s in spans
               if s.name == "train_step.lower" and s.end <= opened]
    if not lowered:
        return None
    return (lowered[-1].attrs or {}).get("step_scopes", "")


def foreign_names(obs) -> bool:
    """Whether the executable's names are another tree's: the program
    says it laid scopes and the compiled text holds none, or holds some
    and the program says none — an executable served from a compile
    cache that a tree with other names filled."""
    said = said_scopes(obs)
    return said is not None and bool(said) != lays_scopes(obs)


def key_moves_with_names(config) -> bool:
    """Whether a configuration's by-name readings are this tree's
    whatever filled the compile cache: its step holds a Mosaic body (it
    lists a kernel), and a body carries the source lines of the frames
    it was traced under, so JAX's cache key moves with the tree.  A
    step with none (the ResNet's) is served the executable of whichever
    tree compiled that program first — on the driver's machine the
    parent's, in every run of a PR that renames or adds a scope
    (``step_compile_s`` 1.46 | 1.44 s on both sides, ledger, PR 35) —
    and a metric that comes and goes with the cache's history is none
    for the ledger: the readers by name do not apply there until the
    harness compiles what it reads (PERF.md section 7 (13))."""
    return bool(config["kernels"])


def of_run(obs) -> dict:
    """``modules.reduce_events`` of this run's traced block under
    ``read_step``'s table, on the device ``reduce_trace`` read; empty
    for an untraced run, a lost profile, or names that are not this
    program's."""
    if not obs.trace or not obs.hlo_text or not obs.traced_steps:
        return {}
    if foreign_names(obs):
        _warn_once(obs.cell.name)
        return {}
    from benchmark import loop

    try:
        path = trace.newest_xplane(os.path.join(loop.TRACE_ROOT,
                                                obs.cell.name))
    except FileNotFoundError:
        return {}
    return _reduced(path, obs.trace.get("slowest"), obs.hlo_text)


@functools.lru_cache(maxsize=None)
def _warn_once(cell: str) -> None:
    print(f"benchmark: {cell}: the executable's names are not this "
          f"program's (a compile cache another tree filled served it): "
          f"the readers on benchmark/phases.py are left out; recompile "
          f"the step to read them", file=sys.stderr)


def ms_per_step(obs, key: str, group: str = "module_s"):
    """Milliseconds a traced step under ``key`` (``update``,
    ``exchange/scatter``, ...; with ``group="kernel_s"`` a kernel's).
    None for an untraced run — and, for a by-scope group, for a step
    that lays no scope; 0.0 where the step lays them and nothing stands
    under this one."""
    reduced = of_run(obs)
    by_scope = group == "module_s" and key.split("/")[0] in BY_SCOPE
    if not reduced or (by_scope and not lays_scopes(obs)):
        return None
    seconds = reduced.get(group, {}).get(key, 0.0 if by_scope else None)
    return None if seconds is None else seconds / obs.traced_steps * 1e3


def account(obs) -> dict:
    """``{line: ms a step}``: the whole of the traced block — every
    group (``model`` by mixer, ``exchange`` by part), the collectives'
    exposed time, idle, and ``unaccounted``, what the lines leave of the
    ``block`` (operations of the trace the compiled text does not hold;
    a collective's time under compute is in no line).  For PERF.md
    section 5; not a metric."""
    module_s = of_run(obs).get("module_s")
    if not module_s:
        return {}
    per_step = 1e3 / obs.traced_steps
    lines = {}
    for key, seconds in module_s.items():
        group, _, part = key.partition("/")
        if part:
            lines[part if group == "model" else key] = seconds * per_step
    for group in {k.partition("/")[0] for k in module_s}:
        parts = sum(s for k, s in module_s.items()
                    if k.startswith(group + "/"))
        lines[group] = (module_s[group] - parts) * per_step
    block = obs.trace["window_s"] * per_step
    lines["collective_exposed"] = \
        obs.trace["collective_exposed_s"] * per_step
    lines["idle"] = obs.trace["idle_share"] * block
    lines["unaccounted"] = block - sum(lines.values())
    lines["block"] = block
    return lines
