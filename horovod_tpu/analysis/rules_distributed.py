"""HVD001-HVD003: the SPMD/tracing correctness rules.

These three rules police the failure classes the paper's runtime
controller policed dynamically (SURVEY §"collective negotiation"): the
reference's rank-0 controller *detects* a rank-divergent collective at
runtime by matching per-rank submissions; an SPMD program has no
controller, so a divergent collective simply deadlocks the pod.  The
compile-time answer is lexical: a collective call must never be
guarded by rank-dependent control flow (HVD001).  HVD002/HVD003 guard
the two tracing-level costs with no runtime guard at all — host syncs
inside the jitted step (a dispatch stall the overlap probe measures but
cannot attribute) and unstable cache keys / tracer branching
(silent warm-start misses, recompiles).
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set

from horovod_tpu.analysis import astutil as A
from horovod_tpu.analysis.engine import Finding, Module, Project, Rule, \
    Severity

# The package's collective surface (ops/collectives.py + ops/eager.py
# public API) plus the jax.lax collective primitives they lower to.
COLLECTIVE_NAMES: Set[str] = {
    # ops/collectives.py
    "allreduce", "grouped_allreduce", "quantized_allreduce",
    "quantized_reducescatter", "grouped_reducescatter",
    "hierarchical_reducescatter", "hierarchical_allgather",
    "grouped_allgather", "sparse_allreduce", "allgather", "allgather_v",
    "broadcast", "reducescatter", "alltoall", "alltoall_v", "barrier",
    "bitwise_and", "bitwise_or",
    # functions.py frontends
    "broadcast_variables", "broadcast_optimizer_state", "allreduce_",
    # jax.lax primitives
    "psum", "pmean", "pmax", "pmin", "ppermute", "pshuffle",
    "all_gather", "all_to_all", "psum_scatter", "axis_index_groups",
}

# names whose *value* differs per rank: branching on them forks the SPMD
# program across the pod
_RANK_VALUE_NAMES = {"rank", "local_rank", "cross_rank", "node_rank",
                     "process_index", "axis_index", "local_rank_id"}
_RANK_BOOL_NAMES = {"is_root", "_is_root", "is_master", "is_chief",
                    "is_coordinator"}
# names that look rank-ish but are uniform across the world
_UNIFORM_NAMES = {"process_count", "size", "world_size", "num_ranks",
                  "local_size", "cross_size", "axis_size", "shard_count"}


def _is_rank_dependent(test: ast.AST) -> Optional[str]:
    """The offending name when ``test`` references a per-rank value."""
    for node in ast.walk(test):
        if isinstance(node, (ast.Name, ast.Attribute)):
            tail = A.name_tail(node)
            if tail is None or tail in _UNIFORM_NAMES:
                continue
            if tail in _RANK_VALUE_NAMES or tail in _RANK_BOOL_NAMES \
                    or tail.endswith("_rank"):
                return tail
    return None


def _is_collective_call(node: ast.Call) -> Optional[str]:
    tail = A.name_tail(node.func)
    if tail in COLLECTIVE_NAMES:
        return tail
    return None


def _contains_exit(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, (ast.Return, ast.Raise, ast.Break, ast.Continue)):
            return True
        if isinstance(n, ast.Call):
            d = A.dotted_name(n.func)
            if d in ("sys.exit", "os._exit", "exit"):
                return True
    return False


class CollectiveDivergenceRule(Rule):
    """HVD001: a collective call reachable under rank-dependent control
    flow.  Ranks that skip (or double) a collective desynchronize the
    pod's collective schedule — the remaining ranks block in the op
    forever.  The reference caught this at runtime via controller
    negotiation (its ``NegotiateResponse`` mismatch error); SPMD has no
    negotiation, so the guard must be lexical."""

    id = "HVD001"
    severity = Severity.P0
    name = "collective-divergence"
    rationale = ("collective under rank-dependent control flow → "
                 "a subset of ranks enters the op → pod deadlock")

    def check(self, module: Module, project: Project) -> Iterable[Finding]:
        if module.tree is None:
            return
        parents = A.ParentMap(module.tree)
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            coll = _is_collective_call(node)
            if coll is None:
                continue
            fn = parents.enclosing_function(node)
            # (a) the collective sits inside a rank-dependent branch
            guard = self._rank_guard(node, fn, parents)
            if guard is not None:
                yield self.finding(
                    module, node,
                    f"collective '{coll}' is guarded by "
                    f"rank-dependent control flow (branches on "
                    f"'{guard}') — ranks that skip it deadlock the "
                    f"rest of the pod in the collective")
                continue
            # (b) the collective follows a rank-dependent early exit
            # in the same function: `if rank() != 0: return` above a
            # broadcast means only rank 0 ever reaches the op
            if fn is None:
                continue
            for stmt in ast.walk(fn):
                if not isinstance(stmt, ast.If):
                    continue
                if stmt.end_lineno is None or \
                        stmt.end_lineno >= node.lineno:
                    continue
                if parents.enclosing_function(stmt) is not fn:
                    continue
                # the exit must be in the rank-guarded suite itself,
                # not in an else branch
                dep = _is_rank_dependent(stmt.test)
                if dep is not None and \
                        any(_contains_exit(s) for s in stmt.body):
                    yield self.finding(
                        module, node,
                        f"collective '{coll}' follows a "
                        f"rank-dependent early exit at line "
                        f"{stmt.lineno} (branches on '{dep}') — "
                        f"only a subset of ranks reaches the op")
                    break

    @staticmethod
    def _rank_guard(node: ast.AST, fn: Optional[ast.AST],
                    parents: A.ParentMap) -> Optional[str]:
        for anc in parents.ancestors(node):
            if anc is fn:
                return None
            test = None
            if isinstance(anc, (ast.If, ast.While, ast.IfExp)):
                test = anc.test
            elif isinstance(anc, ast.Assert):
                test = anc.test
            if test is None:
                continue
            dep = _is_rank_dependent(test)
            if dep is not None:
                return dep
        return None


# -- HVD002 -----------------------------------------------------------------

_JIT_WRAPPERS = {"jit", "pjit", "pmap", "shard_map", "smap",
                 "checkpoint", "remat"}
_SYNC_METHODS = {"item", "block_until_ready"}
_SYNC_CALLS = {"float", "int", "bool"}
_SYNC_DOTTED_TAILS = {"asarray", "array", "device_get"}
_SYNC_DOTTED_PREFIXES = ("np.", "numpy.", "jax.")


def jit_compiled_functions(tree: ast.AST) -> Dict[str, ast.FunctionDef]:
    """Functions that end up traced: decorated with a jit-family
    transform, or referenced by name inside a ``jax.jit(...)`` /
    ``shard_map(...)`` call chain."""
    defs: Dict[str, ast.FunctionDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            defs.setdefault(node.name, node)
    out: Dict[str, ast.FunctionDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                d = A.name_tail(dec)
                if d in _JIT_WRAPPERS:
                    out[node.name] = node
                elif isinstance(dec, ast.Call) and \
                        A.name_tail(dec.func) == "partial" and dec.args \
                        and A.name_tail(dec.args[0]) in _JIT_WRAPPERS:
                    out[node.name] = node
        if isinstance(node, ast.Call) and \
                A.name_tail(node.func) in _JIT_WRAPPERS:
            # jit(f) / jit(shard_map(f, ...)): any plain-name argument
            # that resolves to a local def is traced
            stack = list(node.args)
            while stack:
                a = stack.pop()
                if isinstance(a, ast.Name) and a.id in defs:
                    out[a.id] = defs[a.id]
                elif isinstance(a, ast.Call):
                    stack.extend(a.args)
    return out


def _static_argnames(fn: ast.FunctionDef) -> Set[str]:
    """Names listed in ``static_argnames=`` of a jit decorator — those
    parameters are Python values, free to branch on."""
    names: Set[str] = set()
    for dec in fn.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        for kw in dec.keywords:
            if kw.arg == "static_argnames":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and \
                            isinstance(n.value, str):
                        names.add(n.value)
    return names


class HostSyncInHotPathRule(Rule):
    """HVD002: ``float()``/``.item()``/``np.asarray``/
    ``block_until_ready`` on traced values inside jit/train-step
    bodies.  Each one forces a device→host transfer and a dispatch
    fence; inside the steady-state step it serializes the pipeline the
    async dispatch exists to keep full — a stall the overlap probe
    measures but cannot attribute to a line of code.  (At trace time it
    is outright hostile: it concretizes the tracer or fails.)"""

    id = "HVD002"
    severity = Severity.P1
    name = "host-sync-in-hot-path"
    rationale = ("host synchronization inside a jitted body → "
                 "dispatch stall / tracer concretization error")

    def check(self, module: Module, project: Project) -> Iterable[Finding]:
        if module.tree is None:
            return
        jitted = jit_compiled_functions(module.tree)
        seen: Set[int] = set()
        for fn in jitted.values():
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                hit = self._sync_kind(node)
                if hit is None:
                    continue
                seen.add(id(node))
                yield self.finding(
                    module, node,
                    f"host sync '{hit}' inside jit-compiled "
                    f"'{fn.name}' — forces a device fence in the hot "
                    f"path (or a tracer concretization error); move it "
                    f"outside the compiled region")

    @staticmethod
    def _sync_kind(node: ast.Call) -> Optional[str]:
        tail = A.name_tail(node.func)
        if isinstance(node.func, ast.Name) and tail in _SYNC_CALLS:
            # float(3.0) / float("inf") are static Python, not a sync
            if node.args and isinstance(node.args[0], ast.Constant):
                return None
            return f"{tail}()"
        if isinstance(node.func, ast.Attribute):
            if tail in _SYNC_METHODS:
                return f".{tail}()"
            dotted = A.dotted_name(node.func) or ""
            if tail in _SYNC_DOTTED_TAILS and \
                    dotted.startswith(_SYNC_DOTTED_PREFIXES):
                return dotted
        return None


# -- HVD003 -----------------------------------------------------------------

_UNSTABLE_BUILTINS = {"hash", "id"}
_KEYISH = ("key", "cache", "fingerprint", "digest")


class RetraceHazardRule(Rule):
    """HVD003: retrace / warm-start-miss hazards.

    (a) Python ``if``/``while`` on a *traced* parameter inside a jitted
    body — either a concretization error or, with weak types, a silent
    per-value retrace.  (b) process-unstable values (builtin ``hash``
    — salted per process — ``id``, and ``repr`` of arbitrary objects,
    which embeds ``0x...`` addresses) flowing into cache-key
    construction: a cache keyed so computes a different key every
    process start and every warm start silently misses, re-paying the
    40-50 s compile."""

    id = "HVD003"
    severity = Severity.P1
    name = "retrace-hazard"
    rationale = ("tracer branching / process-unstable cache-key input "
                 "→ recompiles and silent warm-start misses")

    def check(self, module: Module, project: Project) -> Iterable[Finding]:
        if module.tree is None:
            return
        yield from self._tracer_branches(module)
        yield from self._unstable_keys(module)

    def _tracer_branches(self, module: Module) -> Iterable[Finding]:
        jitted = jit_compiled_functions(module.tree)
        for fn in jitted.values():
            params = {a.arg for a in fn.args.args + fn.args.kwonlyargs
                      if a.arg not in ("self", "cls")}
            params -= _static_argnames(fn)
            for node in ast.walk(fn):
                if not isinstance(node, (ast.If, ast.While, ast.IfExp)):
                    continue
                name = self._traced_param_in_test(node.test, params)
                if name is None:
                    continue
                yield self.finding(
                    module, node,
                    f"Python branch on traced parameter '{name}' "
                    f"inside jit-compiled '{fn.name}' — concretization "
                    f"error or a silent retrace per value; use "
                    f"lax.cond/jnp.where or mark it static")

    @staticmethod
    def _traced_param_in_test(test: ast.AST,
                              params: Set[str]) -> Optional[str]:
        # `x is None` / `x is not None` / isinstance(x, ...) are static
        # trace-time dispatch on the *Python* value, not tracer branching
        if isinstance(test, ast.Compare) and \
                all(isinstance(op, (ast.Is, ast.IsNot))
                    for op in test.ops):
            return None
        if isinstance(test, ast.Call) and \
                A.name_tail(test.func) in ("isinstance", "len", "hasattr",
                                           "callable"):
            return None
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return RetraceHazardRule._traced_param_in_test(test.operand,
                                                           params)
        if isinstance(test, ast.BoolOp):
            for v in test.values:
                n = RetraceHazardRule._traced_param_in_test(v, params)
                if n is not None:
                    return n
            return None
        if isinstance(test, ast.Name):
            return test.id if test.id in params else None
        if isinstance(test, ast.Compare):
            for side in [test.left] + list(test.comparators):
                if isinstance(side, ast.Name) and side.id in params:
                    # comparisons against None are trace-static
                    others = [s for s in [test.left] + list(test.comparators)
                              if s is not side]
                    if any(isinstance(o, ast.Constant) and o.value is None
                           for o in others):
                        return None
                    return side.id
        return None

    def _unstable_keys(self, module: Module) -> Iterable[Finding]:
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.FunctionDef) or \
                    not any(k in node.name.lower() for k in _KEYISH):
                continue
            for call in ast.walk(node):
                if not isinstance(call, ast.Call):
                    continue
                tail = A.name_tail(call.func)
                if isinstance(call.func, ast.Name) and \
                        tail in _UNSTABLE_BUILTINS:
                    yield self.finding(
                        module, call,
                        f"'{tail}()' in cache-key path '{node.name}' — "
                        f"builtin {tail}() is not stable across "
                        f"processes (PYTHONHASHSEED / address reuse); "
                        f"the key changes every start and the warm "
                        f"start silently misses")
                for kw in call.keywords:
                    if kw.arg == "default" and \
                            A.name_tail(kw.value) == "repr":
                        yield self.finding(
                            module, call,
                            f"'default=repr' serializing the cache key "
                            f"in '{node.name}' — repr of arbitrary "
                            f"objects embeds '0x...' addresses, so the "
                            f"key differs every process and warm "
                            f"starts silently miss")


RULES: List[Rule] = [CollectiveDivergenceRule, HostSyncInHotPathRule,
                     RetraceHazardRule]
