"""Device milliseconds a step in the Mamba-2 mixers outside their matmuls
and the scan's kernels: every operation whose own ``op_name`` path holds
the module name ``mamba`` and neither ``in_proj`` nor ``out_proj``, and
that is no Mosaic call named ``ssd_fwd`` / ``ssd_bwd`` — the causal
convolution with its SiLU, the ``D``-skip, the gate and the grouped
norm, the sums of the two ``dx``, the scan's layout prologue and its
triangle product, forward, recomputation and backward; XLA's fusions or
the program's kernels (``mamba_conv_*``, ``mamba_gated_norm_*``) alike,
so a program without those kernels reads here too.  By
``benchmark/modules.py``'s rules (a fusion's path is its root's; an
enclosing operation keeps what its children leave), with a table of its
own as ``mlp_ms`` has.  Part of ``ssm_ms``.  A step that holds no
``mamba`` module reads as nothing."""

import re

from benchmark import hlo, tables

PROJECTIONS = ("in_proj", "out_proj")
SCAN = re.compile(r"ssd_(fwd|bwd)")


def applies(config, job) -> bool:
    return "M" in config.get("layers", "")


def _pointwise(text: str) -> dict:
    """``{operation name: ("mamba", "pointwise", None)}``, the table
    ``modules.reduce_events`` takes."""
    known = {}
    for line in text.splitlines():
        op, source = hlo._OP_RE.match(line), hlo._SOURCE_RE.search(line)
        if not op or not source:
            continue
        steps = source.group(1).split("/")
        if "mamba" not in steps or any(p in steps for p in PROJECTIONS):
            continue
        if hlo.MOSAIC_TARGET in line and SCAN.search(op.group(1)):
            continue
        known[op.group(1)] = ("mamba", "pointwise", None)
    return known


def read(obs):
    seconds = tables.traced_seconds(obs, _pointwise(obs.hlo_text),
                                    "module_s", "mamba/pointwise")
    return None if seconds is None else seconds / obs.traced_steps * 1e3
