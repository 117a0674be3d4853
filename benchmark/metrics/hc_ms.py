"""Device milliseconds a step in the hyper-connections of a model whose
residual is several streams: every operation whose own ``op_name`` path
holds the scope ``hc`` — the norm over the streams, the coefficient
matmul, Sinkhorn's rounds, the read-out and the write-in — forward,
recomputation and backward, by ``benchmark/modules.py``'s rules (a
fusion's path is its root's; an enclosing operation keeps what its
children leave).  A step that holds no such scope reads as nothing."""

import os

from benchmark import hlo, modules, trace


def applies(config, job) -> bool:
    return config.get("hc_mult", 1) > 1


def _under_hc(text: str) -> dict:
    """``{operation name: ("hc", None, None)}``, the table
    ``modules.reduce_events`` takes, for the operations under ``hc``."""
    known = {}
    for line in text.splitlines():
        op, source = hlo._OP_RE.match(line), hlo._SOURCE_RE.search(line)
        if op and source and "hc" in source.group(1).split("/"):
            known[op.group(1)] = ("hc", None, None)
    return known


def read(obs):
    if not obs.trace or not obs.hlo_text or not obs.traced_steps:
        return None
    from benchmark import loop

    try:
        path = trace.newest_xplane(os.path.join(loop.TRACE_ROOT,
                                                obs.cell.name))
    except FileNotFoundError:
        return None
    seconds = modules.reduce_events(
        trace.load_events(path), _under_hc(obs.hlo_text)) \
        .get("module_s", {}).get("hc")
    return None if seconds is None else seconds / obs.traced_steps * 1e3
