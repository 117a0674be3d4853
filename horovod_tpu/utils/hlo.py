"""Compiled-HLO collective inspection.

The reference's gradient fusion is *runtime*-observable: the controller
merges pending tensors into one fused buffer per cycle
(``controller.cc:686 FuseResponses``, fusion-buffer threshold
``HOROVOD_FUSION_THRESHOLD``).  Here fusion happens at *compile* time —
autodiff inserts one psum per gradient leaf and XLA's all-reduce
combiner merges them into one grouped collective — so the observable
artifact is the optimized HLO module.  This module parses collectives
out of compiled HLO text so tests can guard the fusion invariant (a
regression that silently de-fuses into per-leaf collectives would pass
every numerics test and only show up as wire overhead on a real pod)
and so the scaling model can count bytes on the wire per step
(``docs/scaling.md``).

Usage::

    txt = step.compiled_text(params, opt_state, batch)
    ops = collective_ops(txt)
    [o for o in ops if o.kind == "all-reduce"]
"""

from __future__ import annotations

import dataclasses
import re
from typing import List, Optional, Tuple

# HLO primitive byte widths (token/opaque excluded — they never carry
# payload).
_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_KINDS = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
          "collective-permute", "collective-broadcast")

# one result tensor: dtype[dims]{layout} — layout block optional
_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\](?:\{[^}]*\})?")
# op-definition line: "%name = <result-type> <kind>[-start](operands...)".
# The result type may be a tuple wrapped in extra parens with trailing
# context scalars — newer XLA emits ``((f32[...], f32[...]), u32[])``
# and ``(f32[...], u32[])`` variants — so the kind match anchors on the
# closing bracket/brace of the type (``(?<=[\]})])``) and tolerates a
# missing separator space rather than requiring ``<type> <kind>``.
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)(?<=[\]})])\s*\b("
    + "|".join(_KINDS) + r")(-start)?\(")


@dataclasses.dataclass
class CollectiveOp:
    """One collective in an optimized HLO module."""

    kind: str                      # e.g. "all-reduce"
    shapes: List[Tuple[str, Tuple[int, ...]]]   # (dtype, dims) per operand
    bytes: int                     # payload bytes (sum over operands)
    replica_groups: Optional[str]  # raw attribute text, None if absent
    group_size: Optional[int]      # devices per group, None if unknown
    line: str                      # the full HLO line (diagnostics)
    asynchronous: bool = False     # issued as a -start/-done pair

    @property
    def dtypes(self) -> set:
        return {d for d, _ in self.shapes}


def _parse_shapes(result_type: str) -> List[Tuple[str, Tuple[int, ...]]]:
    shapes = []
    for dt, dims in _SHAPE_RE.findall(result_type):
        if dt not in _DTYPE_BYTES:
            continue                    # token/opaque/etc
        shape = tuple(int(d) for d in dims.split(",") if d) \
            if dims else ()
        shapes.append((dt, shape))
    return shapes


def _nbytes(shapes) -> int:
    total = 0
    for dt, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _replica_groups(line: str):
    """Return (raw_attr, group_size) from either the explicit
    ``{{0,1},{2,3}}`` form or the iota ``[2,4]<=[8]`` form."""
    m = re.search(r"replica_groups=(\{\{[^=]*?\}\}|\{\}|\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)",
                  line)
    if not m:
        return None, None
    raw = m.group(1)
    if raw.startswith("{{"):
        first = raw[2:].split("}", 1)[0]
        return raw, len([x for x in first.split(",") if x.strip() != ""])
    if raw == "{}":
        return raw, None
    dims = raw[1:].split("]", 1)[0]     # iota: [G,S]<=[N] — S per group
    parts = [int(x) for x in dims.split(",")]
    return raw, parts[-1]


_IOTA_GROUPS_RE = re.compile(
    r"^\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?$")


def _transposed_iota(dims: List[int], perm: List[int]) -> List[int]:
    """``transpose(iota(prod(dims)).reshape(dims), perm).flatten()``
    in pure stdlib — the device-id order of an iota replica-group
    attribute with a ``T(...)`` permutation."""
    strides = [1] * len(dims)
    for i in range(len(dims) - 2, -1, -1):
        strides[i] = strides[i + 1] * dims[i + 1]
    shape_t = [dims[p] for p in perm]
    n = 1
    for d in dims:
        n *= d
    out = []
    for flat in range(n):
        rem, idx_t = flat, []
        for d in reversed(shape_t):
            idx_t.append(rem % d)
            rem //= d
        idx_t.reverse()
        out.append(sum(idx_t[k] * strides[perm[k]]
                       for k in range(len(perm))))
    return out


def replica_group_members(raw: Optional[str]
                          ) -> Optional[List[List[int]]]:
    """Materialize a replica-groups attribute into explicit member
    lists — ``[[0,2],[1,3]]`` — from either the explicit
    ``{{0,2},{1,3}}`` form or the iota ``[G,S]<=[dims]`` /
    ``[G,S]<=[dims]T(perm)`` form.  Returns ``None`` for absent/empty
    attributes and spellings this parser cannot expand (the caller
    then falls back to size-only reasoning).  The iota ids are the
    row-major iota over ``dims``, transposed by ``perm`` and reshaped
    to ``[G,S]``; a 1-D ``dims`` with a 2-D ``perm`` (a spelling some
    dumps use) is read with the source shape implied by the transpose
    target."""
    if not raw or raw == "{}":
        return None
    if raw.startswith("{{"):
        inner = raw[2:-2]
        groups = []
        for grp in inner.split("},{"):
            members = [int(x) for x in grp.split(",") if x.strip()]
            if members:
                groups.append(members)
        return groups or None
    m = _IOTA_GROUPS_RE.match(raw.replace(" ", ""))
    if m is None:
        return None
    g, s = int(m.group(1)), int(m.group(2))
    dims = [int(x) for x in m.group(3).split(",")]
    n = g * s
    prod = 1
    for d in dims:
        prod *= d
    if prod != n or n == 0:
        return None
    if m.group(4) is None:
        order = list(range(n))
    else:
        perm = [int(x) for x in m.group(4).split(",")]
        if len(perm) != len(dims):
            # 1-D source with an N-D perm: the source shape is the one
            # whose transpose-by-perm is the [G,S] target
            target = [g, s]
            if len(perm) != 2 or sorted(perm) != [0, 1]:
                return None
            dims = [0, 0]
            for k, p in enumerate(perm):
                dims[p] = target[k]
        order = _transposed_iota(dims, perm)
    return [order[i * s:(i + 1) * s] for i in range(g)]


def replica_group_stride(raw: Optional[str]) -> Optional[int]:
    """Device-id step between consecutive members of the first replica
    group, or ``None`` when unknown (absent attribute, singleton
    groups, or non-uniform spacing).  On a row-major mesh this is the
    signature that separates topology levels of EQUAL extent: level ℓ's
    groups step by the product of the extents inside it (the intra-
    slice scope strides 1, the cross-slice scope strides ``n_ici``) —
    the quantity ``analysis/cost_model.collective_wire_by_level`` keys
    attribution on."""
    groups = replica_group_members(raw)
    if not groups or len(groups[0]) < 2:
        return None
    first = groups[0]
    stride = first[1] - first[0]
    if any(b - a != stride for a, b in zip(first, first[1:])):
        return None
    return stride


def collective_ops(hlo_text: str) -> List[CollectiveOp]:
    """All collective ops in an (optimized) HLO module dump.

    Async pairs (``all-reduce-start``/``-done``) count once, under the
    start op.  Shapes come from the op's *result* type — for
    ``all-reduce`` the result equals the reduced payload; for
    ``all-gather`` it is the gathered (output) size; for
    ``reduce-scatter`` the scattered (per-shard output) size.
    """
    ops = []
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if m is None:
            continue
        result_type, kind, is_async = m.group(1), m.group(2), m.group(3)
        shapes = _parse_shapes(result_type)
        # async start tuples carry trailing scalar context values on
        # TPU (the u32[] in `(f32[...], u32[])`); they are bookkeeping,
        # not payload — drop them BEFORE picking the output element,
        # otherwise the context scalar is mistaken for the output (and
        # every byte-based fusion guard sees a 4-byte collective)
        if is_async and len(shapes) >= 2:
            while len(shapes) > 1 and shapes[-1][1] == () and \
                    shapes[-1][0] in ("u32", "s32"):
                shapes = shapes[:-1]
        # async starts of gather/scatter/permute carry `(input, output)`
        # tuples; the payload is the output alone — summing the whole
        # tuple double-counts
        if is_async and kind in ("all-gather", "reduce-scatter",
                                 "collective-permute") \
                and len(shapes) >= 2:
            shapes = [shapes[1]]
        raw, gsize = _replica_groups(line)
        ops.append(CollectiveOp(kind=kind, shapes=shapes,
                                bytes=_nbytes(shapes),
                                replica_groups=raw, group_size=gsize,
                                line=line.strip(),
                                asynchronous=bool(is_async)))
    return ops


# "%name (params) -> type {" / "ENTRY %name (...) -> type {": a
# computation's header, at column 0
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_FUSION_CALLS_RE = re.compile(r"\sfusion\(.*\bcalls=%?([\w.\-]+)")
_CHANNEL_RE = re.compile(r"\bchannel_id=(\d+)")


def exchange_counts(hlo_text: str, group_size: int) -> dict:
    """How the collectives over ``group_size`` devices were compiled:
    ``{"ops", "async_ops", "bytes", "async_bytes"}``, each collective
    once.

    XLA:TPU's async collective fusion clones a collective into every
    step's computation, so a per-line count (:func:`collective_ops`)
    reads one exchange several times; the clones share their
    ``channel_id``, which is what crosses the wire once.  A collective
    is *asynchronous* when it is issued in steps: as a ``-start``/
    ``-done`` pair, or from computations that fusions call.  That says
    how it was issued, not that it is hidden: of such a chain the start,
    the done and some steps are fusions that hold nothing but the
    collective, and the step waits in those as it does in a synchronous
    one (5.9 of the 871M step's 16.6 exposed ms, PERF.md section 6,
    PR 27).  So ``async_bytes`` bounds what is overlapped from above;
    the trace says how much is (``benchmark/exchange.py``).  Outside
    fusions a line is a collective (the CPU backend gives every
    collective the same ``channel_id``)."""
    fused, found, computation = set(), [], None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION_RE.match(line)
            computation = m.group(1) if m else None
            continue
        if " fusion(" in line:
            m = _FUSION_CALLS_RE.search(line)
            if m:
                fused.add(m.group(1))
        if "replica_groups=" not in line:
            continue
        ops = collective_ops(line)
        if ops and ops[0].group_size == group_size:
            found.append((ops[0], computation))
    once: dict = {}             # key -> (bytes, asynchronous)
    for n, (op, where) in enumerate(found):
        channel = _CHANNEL_RE.search(op.line)
        in_fusion = where in fused
        key = channel.group(1) if in_fusion and channel else n
        once.setdefault(key, (op.bytes, in_fusion or op.asynchronous))
    return {
        "ops": len(once),
        "async_ops": sum(1 for _, a in once.values() if a),
        "bytes": sum(b for b, _ in once.values()),
        "async_bytes": sum(b for b, a in once.values() if a),
    }


# -- whole-module accounting (cost model substrate) -------------------------
#
# The collective parser above serves the fusion guards; the functions
# below extend the same text-level parse to the quantities the static
# cost model (analysis/cost_model.py, docs/perf_gate.md) needs from a
# lowered module without hardware: per-op FLOPs for the compute ceiling
# and buffer lifetimes for a memory high-water estimate.

# any op-definition line: "%name = <result-type> <opcode>(..."
_ANY_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.+?)(?<=[\]})])\s*\b([\w\-]+)\(")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")
# every %name token on a line (defs and uses alike)
_NAME_RE = re.compile(r"%[\w.\-]+")


def _prod(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def result_bytes(result_type: str) -> int:
    """Payload bytes of one result type string — tuple types sum their
    elements (the tuple-wrapped async-start variants parse like any
    other tuple; their u32[] context scalars are 4 bytes of noise in a
    *memory* estimate, unlike the wire accounting above where
    :func:`collective_ops` strips them)."""
    return _nbytes(_parse_shapes(result_type))


def _operand_shapes(line: str, opcode: str):
    """Typed operand shapes of an op line: the shapes inside the
    ``opcode(...)`` parens.  Dumps that elide operand types (bare
    ``dot(%a, %b)``) yield [] — FLOP counting then skips the op rather
    than guessing."""
    start = line.find(opcode + "(")
    if start < 0:
        return []
    seg, depth = [], 0
    for ch in line[start + len(opcode):]:
        if ch == "(":
            depth += 1
            if depth == 1:
                continue
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        seg.append(ch)
    shapes = []
    for dt, dims in _SHAPE_RE.findall("".join(seg)):
        if dt not in _DTYPE_BYTES:
            continue
        shapes.append((dt, tuple(int(d) for d in dims.split(",") if d)
                       if dims else ()))
    return shapes


def _dot_flops(line: str, result_dims, opcode: str) -> Optional[int]:
    """``2 · |result| · K`` for a dot: every output element costs one
    multiply-add per contracted element.  K comes from the lhs operand
    type + ``lhs_contracting_dims``; batch dims are already in the
    result product."""
    operands = _operand_shapes(line, opcode)
    m = _CONTRACT_RE.search(line)
    if not operands or m is None:
        return None
    lhs_dims = operands[0][1]
    contract = [int(x) for x in m.group(1).split(",") if x != ""]
    if any(c >= len(lhs_dims) for c in contract):
        return None
    k = _prod(lhs_dims[c] for c in contract)
    return 2 * _prod(result_dims) * k


def _conv_flops(line: str, result_dims, opcode: str) -> Optional[int]:
    """``2 · |result| · (kernel elements per output feature)`` for a
    convolution: each output element reduces over the kernel's spatial
    × input-feature window.  The kernel's output-feature dim (``o`` in
    ``dim_labels``' second segment) is excluded — it indexes outputs,
    it is not reduced over."""
    operands = _operand_shapes(line, opcode)
    m = _DIM_LABELS_RE.search(line)
    if len(operands) < 2 or m is None:
        return None
    kernel_dims = operands[1][1]
    kernel_labels = m.group(2)
    o_idx = kernel_labels.find("o")
    if o_idx < 0 or o_idx >= len(kernel_dims) or kernel_dims[o_idx] == 0:
        return None
    window = _prod(kernel_dims) // kernel_dims[o_idx]
    return 2 * _prod(result_dims) * window


def op_flops(hlo_text: str) -> List[Tuple[str, str, int]]:
    """``(op_name, opcode, flops)`` for every countable matmul-class op
    (``dot``, ``convolution``) in the module text.

    Fusion bodies are separate computations in the same dump, so a
    ``fusion(...)`` op's inner dots are counted exactly once — at their
    definition inside the fused computation — and the ``fusion`` line
    itself contributes nothing.  Elementwise/reduce ops are ignored:
    on the MXU the matmul class is the FLOP budget (everything else is
    the memory-bound remainder the roofline's HBM term covers)."""
    out: List[Tuple[str, str, int]] = []
    for line in hlo_text.splitlines():
        m = _ANY_OP_RE.match(line)
        if m is None:
            continue
        name, result_type, opcode = m.group(1), m.group(2), m.group(3)
        result_dims = [dims for dt, dims in _parse_shapes(result_type)]
        if not result_dims:
            continue
        flops = None
        if opcode == "dot":
            flops = _dot_flops(line, result_dims[0], opcode)
        elif opcode == "convolution":
            flops = _conv_flops(line, result_dims[0], opcode)
        if flops:
            out.append((name, opcode, flops))
    return out


def module_flops(hlo_text: str) -> int:
    """Total countable FLOPs of one module dump (see :func:`op_flops`)."""
    return sum(f for _, _, f in op_flops(hlo_text))


def entry_computation(hlo_text: str) -> str:
    """The ENTRY computation's lines (between ``ENTRY ... {`` and its
    matching brace), or the whole text when no ENTRY marker exists.
    Memory accounting scopes here: fusion-body instructions never
    materialize their own buffers, so counting them would double-book
    the fusion op's result."""
    lines = hlo_text.splitlines()
    start = next((i for i, ln in enumerate(lines)
                  if ln.lstrip().startswith("ENTRY ")), None)
    if start is None:
        return hlo_text
    depth, out = 0, []
    for ln in lines[start:]:
        depth += ln.count("{") - ln.count("}")
        out.append(ln)
        if depth <= 0 and out:
            break
    return "\n".join(out)


def buffer_liveness(hlo_text: str) -> List[Tuple[str, int, int, int]]:
    """``(name, bytes, def_index, last_use_index)`` per ENTRY-scope
    instruction, indices into the ENTRY line list.  A buffer is modeled
    live from its defining line through the last line that mentions it
    (a never-used def dies on its own line) — the classic linear-scan
    lifetime, ignoring aliasing/donation, so the estimate is an upper
    bound."""
    lines = entry_computation(hlo_text).splitlines()
    defs: List[Tuple[str, int, int]] = []        # (name, bytes, def idx)
    last_use: dict = {}
    for i, line in enumerate(lines):
        m = _ANY_OP_RE.match(line)
        if m is not None:
            defs.append((m.group(1), result_bytes(m.group(2)), i))
        for name in _NAME_RE.findall(line):
            last_use[name] = i
    return [(name, nbytes, d, max(last_use.get(name, d), d))
            for name, nbytes, d in defs]


# one `{out_index}: (param_number, {param_index}, kind)` entry of the
# module-header input_output_alias attribute
_ALIAS_ENTRY_RE = re.compile(
    r"\{\s*[\d\s,]*\}\s*:\s*\(\s*(\d+)\s*,\s*\{([\d\s,]*)\}")
_PARAM_NUM_RE = re.compile(r"\bparameter\((\d+)\)")


def donated_param_bytes(hlo_text: str) -> int:
    """Total bytes of donated ENTRY parameters — inputs the module
    header's ``input_output_alias`` maps onto outputs (``jit``
    ``donate_argnums``: the step's params/opt_state, and the batch
    under ``donate_batch``).  A donated input's buffer IS its output's
    buffer, so a liveness scan that allocates both double-counts
    exactly these bytes.  Nested alias indices (a donated tuple
    *element*) contribute the whole parameter — an over-subtraction in
    theory, but XLA flattens jit arguments to leaf parameters, so the
    index is ``{}`` in every dump this parser meets.  The attribute is
    captured to its balanced closing brace, so a dump that wraps the
    alias list across lines still counts every entry."""
    m = re.search(r"input_output_alias=\{", hlo_text)
    if m is None:
        return 0
    depth, j = 1, m.end()
    while j < len(hlo_text) and depth:
        c = hlo_text[j]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
        j += 1
    attr = hlo_text[m.end():j - 1]
    sizes = {}
    for line in entry_computation(hlo_text).splitlines():
        om = _ANY_OP_RE.match(line)
        if om is None or om.group(3) != "parameter":
            continue
        pm = _PARAM_NUM_RE.search(line)
        if pm is not None:
            sizes[int(pm.group(1))] = result_bytes(om.group(2))
    return sum(sizes.get(int(pnum), 0)
               for pnum, _pidx in _ALIAS_ENTRY_RE.findall(attr))


def memory_high_water(hlo_text: str) -> int:
    """Peak sum of simultaneously-live ENTRY buffers — the static
    per-device memory high-water estimate the cost model reports
    (docs/perf_gate.md lists the assumptions: no aliasing between
    distinct values, tuple results counted whole).  Donated inputs
    (``input_output_alias``) are accounted: the ROOT's result reuses
    their buffers, so its allocation is reduced by
    :func:`donated_param_bytes` — without this every donated train
    step double-counted params + opt_state at the update point."""
    live = buffer_liveness(hlo_text)
    if not live:
        return 0
    donated = donated_param_bytes(hlo_text)
    if donated:
        lines = entry_computation(hlo_text).splitlines()
        root = next((i for i, ln in enumerate(lines)
                     if ln.lstrip().startswith("ROOT ")), None)
        if root is not None:
            # credit the donation against the ROOT's own allocation,
            # exactly once — never per-buffer, which double-subtracts
            # when another def shares the ROOT line index
            rm = _ANY_OP_RE.match(lines[root])
            root_name = rm.group(1) if rm is not None else None
            credited, fixed = False, []
            for name, nbytes, d, last in live:
                if not credited and d == root and \
                        (root_name is None or name == root_name):
                    nbytes -= min(nbytes, donated)
                    credited = True
                fixed.append((name, nbytes, d, last))
            live = fixed
    n = max(last for _, _, _, last in live) + 1
    alloc = [0] * n
    free = [0] * n
    for _, nbytes, d, last in live:
        alloc[d] += nbytes
        free[last] += nbytes
    cur = peak = 0
    for i in range(n):
        cur += alloc[i]
        peak = max(peak, cur)
        cur -= free[i]
    return peak


def count_by_kind(ops: List[CollectiveOp]) -> dict:
    out: dict = {}
    for o in ops:
        out[o.kind] = out.get(o.kind, 0) + 1
    return out


def scopes_by_kind(ops: List[CollectiveOp]) -> dict:
    """kind → sorted tuple of distinct replica-group sizes — the
    *scope* structure of a module's collectives.  The hierarchical
    exchange's signature is ``{"reduce-scatter": (dcn, ici), ...}``:
    two distinct scopes, one per mesh level, where the flat exchange
    shows a single world-sized scope.  ``None`` group sizes (HLO's
    "all devices" spellings) are kept so a scopeless op can't hide."""
    out: dict = {}
    for o in ops:
        out.setdefault(o.kind, set()).add(o.group_size)
    return {k: tuple(sorted(v, key=lambda s: (s is None, s)))
            for k, v in out.items()}


# opcodes that count as "compute scheduled between start and done" for
# the serial-tail scan: matmul-class ops, fused elementwise bodies and
# loops all give the async collective something to hide under
_COMPUTE_OPS = ("dot", "convolution", "fusion", "while")


def serial_tail_collectives(hlo_text: str,
                            kinds=("reduce-scatter",
                                   "all-gather")) -> int:
    """1 if the module's FINAL async RS/AG pair is a *serial tail* —
    no compute op scheduled between its ``-start`` and ``-done`` — else
    0.  This is the exposure the tile-fused exchange exists to remove
    (HLO005, docs/fused_kernels.md): the last bucket's collective with
    nothing left to hide under.  Synchronous backends (no -start/-done
    pairs, e.g. this image's CPU XLA) return 0 — a sync schedule has no
    window to judge."""
    lines = entry_computation(hlo_text).splitlines()
    last = None
    for i, ln in enumerate(lines):
        m = _ANY_OP_RE.match(ln)
        if m is None:
            continue
        opcode = m.group(3)
        for k in kinds:
            if opcode == f"{k}-start":
                last = (i, k)
    if last is None:
        return 0
    i, kind = last
    done = None
    for j in range(i + 1, len(lines)):
        m = _ANY_OP_RE.match(lines[j])
        if m is not None and m.group(3) == f"{kind}-done":
            done = j
            break
    if done is None:
        return 0
    for ln in lines[i + 1:done]:
        m = _ANY_OP_RE.match(ln)
        if m is not None and m.group(3) in _COMPUTE_OPS:
            return 0
    return 1
