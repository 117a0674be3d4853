"""What the compiled step holds, read from its optimized-HLO text.

The collective arithmetic is copied from ``horovod_tpu/utils/hlo.py``
(sound; PR 21 used it on the chip) so that a later change to the program
cannot move the yardstick.  Added here: which operation is a Mosaic
kernel, which a collective, and which ``-done`` closes which ``-start``;
the trace reducer sorts device time by these.
"""

from __future__ import annotations

import re

MOSAIC_TARGET = "tpu_custom_call"

DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
COLLECTIVE_KINDS = ("all-reduce", "reduce-scatter", "all-gather",
                    "all-to-all", "collective-permute",
                    "collective-broadcast")

_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\](?:\{[^}]*\})?")
# "%name = <result type> <opcode>(": the result type may be a tuple and,
# on a TPU, carries layouts such as {0:T(8,128)(2,1)S(1)} — but nothing
# in it is a space, a lower-case word and an opening bracket, which is
# how the opcode starts
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s([a-z][\w\-]*)\(")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_SOURCE_RE = re.compile(r'op_name="([^"]*)"')


def _shapes(result_type: str) -> list:
    out = []
    for dtype, dims in _SHAPE_RE.findall(result_type):
        if dtype in DTYPE_BYTES:               # not token / opaque
            out.append((dtype, tuple(int(d) for d in dims.split(",") if d)))
    return out


def _nbytes(shapes) -> int:
    total = 0
    for dtype, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n * DTYPE_BYTES[dtype]
    return total


def _group_size(line: str):
    m = re.search(r"replica_groups=(\{\{[^=]*?\}\}|\[[\d,]+\]<=\[[\d,]+\])",
                  line)
    if not m:
        return None
    raw = m.group(1)
    if raw.startswith("{{"):
        first = raw[2:].split("}", 1)[0]
        return len([x for x in first.split(",") if x.strip()])
    return int(raw[1:].split("]", 1)[0].split(",")[-1])   # [G,S]<=[N]


def collectives(text: str) -> list:
    """One dict a collective: ``kind``, payload ``bytes``, ``group_size``.

    An async pair counts once, under its ``-start``.  Bytes come from the
    result type: the reduced payload of an all-reduce, the gathered
    output of an all-gather, the scattered shard of a reduce-scatter.
    An async start's type carries trailing ``u32[]`` context scalars (no
    payload) and, for gather / scatter / permute, an ``(input, output)``
    tuple of which the output alone is the payload."""
    out = []
    for line in text.splitlines():
        m = _OP_RE.match(line)
        if m is None:
            continue
        name, result_type, opcode = m.groups()
        is_async = opcode.endswith("-start")
        kind = opcode[:-len("-start")] if is_async else opcode
        if kind not in COLLECTIVE_KINDS:
            continue
        shapes = _shapes(result_type)
        if is_async:
            while len(shapes) > 1 and shapes[-1] in (("u32", ()),
                                                     ("s32", ())):
                shapes = shapes[:-1]
            if kind in ("all-gather", "reduce-scatter",
                        "collective-permute") and len(shapes) >= 2:
                shapes = [shapes[1]]
        out.append({"name": name, "kind": kind, "bytes": _nbytes(shapes),
                    "group_size": _group_size(line)})
    return out


def collective_bytes(text: str, group_size: int) -> int:
    """Payload bytes a step of the collectives that span ``group_size``
    devices."""
    return sum(c["bytes"] for c in collectives(text)
               if c["group_size"] == group_size)


def mosaic_lines(text: str) -> list:
    return [ln for ln in text.splitlines() if MOSAIC_TARGET in ln]


def op_classes(text: str) -> dict:
    """``{operation name: class}`` for every operation of the module.

    Classes: ``mosaic`` (a Pallas kernel), ``collective`` (and
    ``collective-start`` / ``collective-done``), ``compute`` (everything
    else: fusions, convolutions, copies).  No finer: XLA decides what
    shares a fusion — the optimizer's update rides in the weight
    gradient's matmul on one chip and not on four — so a split by
    hardware unit would move with the fusion, not with the work.
    ``done_of`` maps a ``-done`` to its ``-start``; ``labels`` gives an
    operation its opcode, whether it belongs to the backward pass, and
    the end of the JAX source path it was lowered from — the same label
    for the same operation of every layer, so that a breakdown can add
    them up."""
    classes, done_of, labels = {}, {}, {}
    for line in text.splitlines():
        m = _OP_RE.match(line)
        if m is None:
            continue
        name, _, opcode = m.groups()
        source = _SOURCE_RE.search(line)
        if source:
            path = source.group(1)
            labels[name] = (f"{opcode} {'bwd' if 'transpose(' in path else 'fwd'}"
                            f" {'/'.join(path.split('/')[-2:])}")
        else:
            labels[name] = opcode
        base = opcode
        for suffix in ("-start", "-done"):
            if opcode.endswith(suffix):
                base = opcode[:-len(suffix)]
        if base in COLLECTIVE_KINDS:
            classes[name] = "collective" + opcode[len(base):]
            if opcode.endswith("-done"):
                operand = _OPERAND_RE.search(line, m.end())
                if operand:
                    done_of[name] = operand.group(1)
        elif opcode == "custom-call" and MOSAIC_TARGET in line:
            classes[name] = "mosaic"
        else:
            classes[name] = "compute"
    return {"classes": classes, "done_of": done_of, "labels": labels}
