"""Published peaks of a chip, keyed by the exact ``device_kind`` JAX
reports (``peaks.json``, each with its source).  A device that is not in
the table is an error, never a default."""

from __future__ import annotations

from benchmark.cells import HERE, load_json


def lookup(device_kind: str) -> dict:
    table = load_json(HERE, "peaks.json")
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmark/peaks.json (it has {sorted(table)}); add the "
            f"chip with the source of its figures")
    return table[device_kind]
