"""Cells, found by name: ``BENCHMARK.json`` → configuration, job, metrics.

A cell is one entry of ``workloads``.  Its ``config`` names
``configs/<config>.json`` (the sizes as run) and ``configs/<config>.py``
(the builder and the plain reference); its ``traffic`` names
``jobs/<traffic>.json`` (the training job).  Nothing here imports JAX.
"""

from __future__ import annotations

import dataclasses
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    job: dict             # jobs/<traffic>.json
    end_to_end: tuple     # the BENCHMARK.json entries this cell reports
    per_layer: tuple      # every entry: a reader's ``applies`` decides

    @property
    def rate_metric(self) -> str:
        return f"{self.config['unit']}_per_s_per_chip"


def rehearsal(d: dict) -> dict:
    """``d`` with its ``rehearsal`` group laid over it: the tiny sizes
    ``--rehearse`` debugs the harness with on the CPU."""
    out = {k: v for k, v in d.items() if k != "rehearsal"}
    out.update(d.get("rehearsal", {}))
    return out


def resolve(name: str, rehearse: bool = False) -> Cell:
    bench = load_benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(
            f"benchmark: no workload {name!r} in BENCHMARK.json; "
            f"there are {sorted(entries)}")
    w = entries[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = load_json(ROOT, files[w["config"]])
    job = load_json(HERE, "jobs", w["traffic"] + ".json")
    if job["chips"] != w["chips"]:
        raise SystemExit(
            f"benchmark: cell {name} asks for {w['chips']} chip(s), its "
            f"job {w['traffic']} for {job['chips']}")
    if rehearse:
        config, job = rehearsal(config), rehearsal(job)
    return Cell(
        name=name, chips=w["chips"], config=config, job=job,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if "workloads" not in m
                         or name in m["workloads"]),
        per_layer=tuple(bench["per_layer"]))
