"""Device time of a traced run under a reader's own table of operations.

``benchmark/modules.py`` (as accepted) adds the profiler's events up by a
table ``{operation name: (kind, part, kernel)}`` and builds one such
table, by the mixers' module names.  A reader whose operations that
table does not know — another module name, a kernel known by its
instruction's name — builds its own and reads the run through here."""

from __future__ import annotations

import os

from benchmark import modules, trace


def traced_seconds(obs, known: dict, group: str, key: str):
    """Seconds of the traced block under ``group`` / ``key``
    (``module_s`` or ``kernel_s``) of ``modules.reduce_events`` with the
    table ``known``; None for an untraced run, a lost profile, or a step
    that holds none of the table's operations."""
    if not obs.trace or not obs.hlo_text or not obs.traced_steps:
        return None
    from benchmark import loop

    try:
        path = trace.newest_xplane(os.path.join(loop.TRACE_ROOT,
                                                obs.cell.name))
    except FileNotFoundError:
        return None
    return modules.reduce_events(trace.load_events(path), known) \
        .get(group, {}).get(key)
