"""Share of its roofline the Mamba-2 scan's Mosaic kernels reach: the
least time the chip could take for a step's scan, forward and backward
once (``Built.kernel_cost["ssd"]``: ``granite_flops.ssd_step_cost``, from
shapes alone) against the chip's peaks, over the device time of the
Mosaic calls whose instruction is named for the kernels, ``ssd_fwd`` and
``ssd_bwd``.  Where a block is rematerialised the forward runs twice and
its second run is time with no work counted, as a flash forward's
recomputation is in ``gqa_flash_roofline``.  The einsum form of the scan
holds no such call and reads as nothing; so does a program whose step
has no scan."""

import re

from benchmark import flops, hlo, tables

SCAN = re.compile(r"ssd_(fwd|bwd)")


def applies(config, job) -> bool:
    return "ssd" in config["kernels"]


def _scan_calls(text: str) -> dict:
    """``{operation name: ("mamba", "ssd", "ssd")}``, the table
    ``modules.reduce_events`` takes, for the scan's Mosaic calls."""
    known = {}
    for line in hlo.mosaic_lines(text):
        op = hlo._OP_RE.match(line)
        if op and SCAN.search(op.group(1)):
            known[op.group(1)] = ("mamba", "ssd", "ssd")
    return known


def kernel_seconds(obs):
    """Seconds of the traced block in ``ssd_fwd`` / ``ssd_bwd`` calls."""
    return tables.traced_seconds(obs, _scan_calls(obs.hlo_text),
                                 "kernel_s", "ssd")


def read(obs):
    seconds = kernel_seconds(obs)
    cost = obs.built.kernel_cost.get("ssd")
    if not seconds or not obs.peaks or not cost:
        return None
    least, _bound = flops.roofline_s(cost["flops"], cost["bytes"], obs.peaks)
    return 100.0 * least * obs.traced_steps / seconds
