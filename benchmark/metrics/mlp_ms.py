"""Device milliseconds a step in the dense gated MLP sublayers of a hybrid
model (pattern character ``D``): every operation whose own ``op_name``
path holds the module name ``mlp`` — the gate, up and down projections
and the SiLU gate between them — forward, recomputation and backward, by
``benchmark/modules.py``'s rules (a fusion's path is its root's; an
enclosing operation keeps what its children leave).  ``modules.KINDS``
has no ``mlp`` (the file is as accepted), so this reader brings its own
table, as ``hc_ms`` does.  A step that holds no such module reads as
nothing."""

from benchmark import hlo, tables


def applies(config, job) -> bool:
    return "D" in config.get("layers", "")


def _under_mlp(text: str) -> dict:
    """``{operation name: ("mlp", None, None)}``, the table
    ``modules.reduce_events`` takes, for the operations under ``mlp``."""
    known = {}
    for line in text.splitlines():
        op, source = hlo._OP_RE.match(line), hlo._SOURCE_RE.search(line)
        if op and source and "mlp" in source.group(1).split("/"):
            known[op.group(1)] = ("mlp", None, None)
    return known


def read(obs):
    seconds = tables.traced_seconds(obs, _under_mlp(obs.hlo_text),
                                    "module_s", "mlp")
    return None if seconds is None else seconds / obs.traced_steps * 1e3
