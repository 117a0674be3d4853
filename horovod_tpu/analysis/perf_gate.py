"""Perf regression gate: diff bench artifacts against the checked-in
trajectory and fail on throughput/overlap/wire-byte regressions.

A trajectory is a list of bench artifacts, oldest first; a collapse of
one round's rate against its predecessors is the anomaly class that
should fail a merge, not decorate a log.  The gate runs two ways:

* **trajectory walk** (no candidate): every checked-in artifact is
  diffed against the best comparable value among its predecessors —
  the tier-1 self-check that the history itself is regression-free;
* **candidate diff** (``--candidate new.json``): a fresh
  ``bench.py --json-out`` artifact is diffed against the best
  comparable value anywhere in the trajectory.

"Comparable" is load-bearing: the transformer grew 183.8M→870.9M
params between r03 and r04, so tokens/sec across that boundary is not
a regression, it's a different model — throughput fields carry a
comparability key (``transformer_params_m`` etc.) and only matching
artifacts are diffed.  Schema-versioned artifacts
(``bench.py`` ``schema_version`` ≥ 1) additionally pin device/mesh
identity, and the gate REFUSES to diff mismatched identities with a
clear error instead of producing a nonsense verdict (or a KeyError).
Calibration provenance is identity too: artifacts stamped with
differing ``calibration_fingerprint`` (the run consumed a measured
hardware model via ``HOROVOD_CALIBRATION_PATH``; docs/calibration.md)
were priced against different machines and are likewise refused.

Rules (ids continue the HLO00x pack; docs/perf_gate.md):

=========  ==============================================================
PERF001    throughput field dropped more than the tolerance vs the best
           comparable trajectory value
PERF002    measured ``overlap_fraction`` dropped more than the overlap
           tolerance (absolute)
PERF003    per-level exchange wire bytes grew more than the wire
           tolerance at the same hierarchy (de-fusion/de-quantization
           shows up here before a pod does)
PERF004    candidate artifact reports a failed run (``rc``/``ok``)
PERF006    measured HBM high-water grew more than the memory tolerance
           at the same remat policy + plan (a remat or donation
           regression shows up here before an OOM does)
=========  ==============================================================

Tolerances come from ``HOROVOD_PERF_GATE_TOLERANCE`` (relative
throughput drop, default 0.10), ``HOROVOD_PERF_GATE_OVERLAP_TOLERANCE``
(absolute overlap drop, default 0.10),
``HOROVOD_PERF_GATE_WIRE_TOLERANCE`` (relative wire growth, default
0.10) and ``HOROVOD_PERF_GATE_MEMORY_TOLERANCE`` (relative HBM
high-water growth, default 0.10) — registered knobs
(docs/running.md).  Blessing an intentional
regression = updating the trajectory the gate reads
(docs/perf_gate.md walks the procedure).
"""

from __future__ import annotations

import argparse
import dataclasses
import glob as _glob
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from horovod_tpu.analysis import cost_model as CM
from horovod_tpu.analysis import engine

#: Highest bench-artifact schema this gate understands.
SCHEMA_VERSION = 1

#: v1 provenance fields bench.py stamps (artifact_metadata()).
_V1_REQUIRED = ("jax_version", "platform", "device_kind", "n_devices",
                "mesh_shape")
#: identity fields that must MATCH for two v1 artifacts to be diffable
_V1_IDENTITY = ("platform", "device_kind", "n_devices", "mesh_shape")

#: throughput fields and the comparability key guarding each — only
#: artifacts agreeing on the key's value are diffed (None key field on
#: both sides also matches).  ``plan`` guards every field: a dp=8 run
#: against a dp=4,fsdp=2 run measures two different exchange
#: schedules, not a regression (bench.py --plan; docs/parallelism.md).
#: ``reduction`` guards them the same way: a sum→adasum switch moves
#: the outer exchange level onto the pairwise full-block schedule —
#: a schedule change, never a throughput regression (bench.py
#: --reduction; docs/adasum.md); legacy artifacts without the field
#: keep gating via the None-matches-None rule
THROUGHPUT_FIELDS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("value", ("metric", "plan", "reduction")),
    # sp extent + sequence length guard the transformer diff: an
    # sp=2 seq-4096 long-context run against an sp=1 seq-512 one
    # measures a different attention schedule and a t²-different
    # FLOP mix, never a regression (bench.py --plan dp×sp)
    ("transformer_tokens_per_sec",
     ("transformer_params_m", "plan", "sp", "transformer_seq_len",
      "reduction")),
    # routing config guards the MoE diff: a capacity-factor or ep-extent
    # change is a schedule change (different dispatch geometry + drop
    # behavior), never a throughput regression
    ("moe_tokens_per_sec",
     ("moe_params_m", "plan", "moe_capacity_factor", "moe_ep",
      "reduction")),
    ("vit_img_sec_per_chip", ("vit_params_m", "plan", "reduction")),
    # model count + tenant-class mix guard the serving diff: a fleet
    # artifact (3 tenants behind weighted-fair scheduling) measures a
    # different arbitration/hot-swap schedule than a single-model one,
    # never a regression; legacy single-model artifacts carry neither
    # key and stay comparable with each other (None matches None)
    ("serve_throughput_rps",
     ("serve_offered_rps", "plan", "serve_models",
      "serve_tenant_mix")),
)

#: latency (lower-is-better) fields and their comparability keys —
#: PERF005 fails on *growth* beyond the throughput tolerance, so
#: ``bench.py --serve`` tail latency is gateable like throughput
LATENCY_FIELDS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("serve_p50_latency_s",
     ("serve_offered_rps", "plan", "serve_models",
      "serve_tenant_mix")),
    ("serve_p99_latency_s",
     ("serve_offered_rps", "plan", "serve_models",
      "serve_tenant_mix")),
)

#: memory (lower-is-better) fields and their comparability keys —
#: PERF006 fails on growth beyond the memory tolerance.  ``remat_policy``
#: guards the diff: a none-vs-full comparison measures two different
#: recompute trades, not a leak (bench.py --hbm-budget; docs/memory.md)
MEMORY_FIELDS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("hbm_high_water_bytes", ("remat_policy", "plan")),
)


class GateError(Exception):
    """Artifact unusable (unreadable, unknown schema, identity
    mismatch) — the gate refuses with this instead of guessing."""


@dataclasses.dataclass(frozen=True)
class GateFinding:
    rule: str
    message: str
    detail: str = ""

    def format(self) -> str:
        d = f" ({self.detail})" if self.detail else ""
        return f"{self.rule}: {self.message}{d}"

    def as_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class Tolerances:
    throughput: float = 0.10     # relative drop allowed
    overlap: float = 0.10        # absolute overlap_fraction drop
    wire: float = 0.10           # relative wire-byte growth allowed
    memory: float = 0.10         # relative HBM high-water growth allowed

    @staticmethod
    def from_env(throughput: Optional[float] = None,
                 overlap: Optional[float] = None,
                 wire: Optional[float] = None,
                 memory: Optional[float] = None) -> "Tolerances":
        def knob(name: str, override: Optional[float],
                 default: float) -> float:
            if override is not None:
                return float(override)
            raw = os.environ.get(name)
            if raw in (None, ""):
                return default
            try:
                return float(raw)
            except ValueError:
                raise GateError(f"{name} must be a float, got {raw!r}")

        return Tolerances(
            throughput=knob("HOROVOD_PERF_GATE_TOLERANCE",
                            throughput, 0.10),
            overlap=knob("HOROVOD_PERF_GATE_OVERLAP_TOLERANCE",
                         overlap, 0.10),
            wire=knob("HOROVOD_PERF_GATE_WIRE_TOLERANCE", wire, 0.10),
            memory=knob("HOROVOD_PERF_GATE_MEMORY_TOLERANCE",
                        memory, 0.10))


@dataclasses.dataclass(frozen=True)
class Artifact:
    """One normalized bench artifact: flattened fields + provenance."""

    name: str
    fields: Dict
    schema_version: int

    def get(self, key, default=None):
        return self.fields.get(key, default)


def load_artifact(path: str) -> Artifact:
    """Read + normalize one artifact file.

    Accepts the raw ``bench.py --json-out`` object, the driver wrapper
    (``{"parsed": {...}, "rc": ...}``) and the metric-less
    ``MULTICHIP_r0*`` health stubs.  Raises
    :class:`GateError` with a pointed message on anything unreadable or
    schema-invalid — never a KeyError."""
    try:
        with open(path) as f:
            data = json.load(f)
    except OSError as e:
        raise GateError(f"{path}: cannot read artifact: {e}")
    except json.JSONDecodeError as e:
        raise GateError(f"{path}: not valid JSON: {e}")
    if not isinstance(data, dict):
        raise GateError(f"{path}: artifact must be a JSON object, got "
                        f"{type(data).__name__}")
    if isinstance(data.get("parsed"), dict):
        data = dict(data, **data["parsed"])
    return _validate(os.path.basename(path), data)


def _validate(name: str, data: Dict) -> Artifact:
    version = data.get("schema_version", 0)
    if not isinstance(version, int) or version < 0:
        raise GateError(f"{name}: schema_version must be a non-negative "
                        f"int, got {version!r}")
    if version > SCHEMA_VERSION:
        raise GateError(
            f"{name}: schema_version {version} is newer than this "
            f"gate understands (≤ {SCHEMA_VERSION}) — upgrade "
            f"horovod_tpu before diffing this artifact")
    if version >= 1:
        missing = [k for k in _V1_REQUIRED if data.get(k) is None]
        if missing:
            raise GateError(
                f"{name}: schema_version {version} artifact is missing "
                f"required provenance field(s) {missing} — it was not "
                f"written by bench.py --json-out; refusing to diff it")
    return Artifact(name=name, fields=data, schema_version=version)


def _identity(art: Artifact) -> Optional[Tuple]:
    if art.schema_version < 1:
        return None
    return tuple(json.dumps(art.get(k), sort_keys=True)
                 for k in _V1_IDENTITY)


def check_comparable(baseline: Sequence[Artifact],
                     candidate: Artifact) -> None:
    """Refuse (GateError) when the candidate's device/mesh identity
    contradicts a schema-versioned baseline artifact.  Legacy (v0)
    artifacts carry no identity and are accepted — the checked-in
    trajectory predates the schema."""
    cand_id = _identity(candidate)
    if cand_id is None:
        return
    for base in baseline:
        base_id = _identity(base)
        if base_id is not None and base_id != cand_id:
            diffs = [f"{k}: {base.get(k)!r} vs {candidate.get(k)!r}"
                     for k in _V1_IDENTITY
                     if base.get(k) != candidate.get(k)]
            raise GateError(
                f"{candidate.name}: not comparable with "
                f"{base.name} — {'; '.join(diffs)}; a perf diff "
                f"across different hardware/mesh identities is "
                f"meaningless, refusing")
        # calibration provenance: two artifacts priced/pruned against
        # measured hardware models fitted on DIFFERENT hardware are not
        # a perf diff, they are a hardware change (docs/calibration.md)
        base_fp = base.get("calibration_fingerprint")
        cand_fp = candidate.get("calibration_fingerprint")
        if base_fp is not None and cand_fp is not None \
                and base_fp != cand_fp:
            raise GateError(
                f"{candidate.name}: not comparable with {base.name} — "
                f"calibration_fingerprint {base_fp!r} vs {cand_fp!r} "
                f"(calibrated on "
                f"{base.get('calibration_device_kind')!r} vs "
                f"{candidate.get('calibration_device_kind')!r}); a "
                f"perf diff across different measured hardware models "
                f"is meaningless — recalibrate on one machine "
                f"(bench --calibrate) or drop the stale artifact, "
                f"refusing")


def _keys_match(a: Artifact, b: Artifact, keys: Tuple[str, ...]) -> bool:
    for k in keys:
        va, vb = a.get(k), b.get(k)
        if isinstance(va, float) or isinstance(vb, float):
            if va is None or vb is None:
                if va is not vb:
                    return False
            elif abs(float(va) - float(vb)) > 1e-3 * max(
                    abs(float(va)), abs(float(vb)), 1e-12):
                return False
        elif va != vb:
            return False
    return True


def _numeric(v) -> Optional[float]:
    return float(v) if isinstance(v, (int, float)) \
        and not isinstance(v, bool) else None


def diff(baseline: Sequence[Artifact], candidate: Artifact,
         tol: Tolerances) -> List[GateFinding]:
    """All regressions of ``candidate`` vs the best comparable baseline
    values.  Pure function of its inputs — the gate's two-run
    determinism contract."""
    findings: List[GateFinding] = []

    # PERF004 — a failed run can't vouch for anything
    if candidate.get("rc", 0) not in (0, None) \
            or candidate.get("ok") is False:
        findings.append(GateFinding(
            "PERF004",
            f"{candidate.name}: artifact reports a failed run "
            f"(rc={candidate.get('rc')!r}, ok={candidate.get('ok')!r}) "
            f"— fix the run before gating on its numbers"))

    # PERF001 — throughput
    for field, keys in THROUGHPUT_FIELDS:
        cand_v = _numeric(candidate.get(field))
        if cand_v is None:
            continue
        best: Optional[Tuple[float, str]] = None
        for base in baseline:
            base_v = _numeric(base.get(field))
            if base_v is None or not _keys_match(base, candidate, keys):
                continue
            if best is None or base_v > best[0]:
                best = (base_v, base.name)
        if best is None:
            continue
        ref, ref_name = best
        if ref > 0 and cand_v < (1.0 - tol.throughput) * ref:
            drop = (ref - cand_v) / ref
            findings.append(GateFinding(
                "PERF001",
                f"{candidate.name}: {field} regressed "
                f"{drop * 100:.1f}% ({cand_v:g} vs {ref:g} in "
                f"{ref_name}; tolerance "
                f"{tol.throughput * 100:.0f}%)"))

    # PERF005 — latency (lower is better): growth beyond the
    # throughput tolerance vs the best (lowest) comparable baseline
    for field, keys in LATENCY_FIELDS:
        cand_v = _numeric(candidate.get(field))
        if cand_v is None:
            continue
        best = None
        for base in baseline:
            base_v = _numeric(base.get(field))
            if base_v is None or not _keys_match(base, candidate, keys):
                continue
            if best is None or base_v < best[0]:
                best = (base_v, base.name)
        if best is None:
            continue
        ref, ref_name = best
        if ref > 0 and cand_v > (1.0 + tol.throughput) * ref:
            growth = (cand_v - ref) / ref
            findings.append(GateFinding(
                "PERF005",
                f"{candidate.name}: {field} inflated "
                f"{growth * 100:.1f}% ({cand_v:g} vs {ref:g} in "
                f"{ref_name}; tolerance "
                f"{tol.throughput * 100:.0f}%) — tail latency "
                f"regressed under the same offered load"))

    # PERF006 — HBM high-water (lower is better): growth beyond the
    # memory tolerance vs the best (lowest) comparable baseline
    for field, keys in MEMORY_FIELDS:
        cand_v = _numeric(candidate.get(field))
        if cand_v is None:
            continue
        best = None
        for base in baseline:
            base_v = _numeric(base.get(field))
            if base_v is None or not _keys_match(base, candidate, keys):
                continue
            if best is None or base_v < best[0]:
                best = (base_v, base.name)
        if best is None:
            continue
        ref, ref_name = best
        if ref > 0 and cand_v > (1.0 + tol.memory) * ref:
            growth = (cand_v - ref) / ref
            findings.append(GateFinding(
                "PERF006",
                f"{candidate.name}: {field} grew "
                f"{growth * 100:.1f}% ({cand_v:g} vs {ref:g} in "
                f"{ref_name}; tolerance {tol.memory * 100:.0f}%) — "
                f"more HBM at the same remat policy and plan"))

    # PERF002 — measured overlap
    for key in sorted(candidate.fields):
        if not key.endswith("overlap_fraction") \
                or key.endswith("h2d_overlap_fraction"):
            continue
        cand_v = _numeric(candidate.get(key))
        if cand_v is None:
            continue
        refs = [(v, b.name) for b in baseline
                if (v := _numeric(b.get(key))) is not None]
        if not refs:
            continue
        ref, ref_name = max(refs)
        if ref - cand_v > tol.overlap:
            findings.append(GateFinding(
                "PERF002",
                f"{candidate.name}: {key} dropped {ref - cand_v:.2f} "
                f"({cand_v:.2f} vs {ref:.2f} in {ref_name}; tolerance "
                f"{tol.overlap:.2f} absolute) — the exchange lost its "
                f"compute overlap"))

    # PERF003 — wire bytes per level, comparable only at the same
    # hierarchy (two_level vs flat is a topology change, not a leak)
    for key in sorted(candidate.fields):
        if not (key.endswith("exchange_wire_bytes_ici")
                or key.endswith("exchange_wire_bytes_dcn")):
            continue
        cand_v = _numeric(candidate.get(key))
        if cand_v is None:
            continue
        prefix = key[: -len("exchange_wire_bytes_ici")] \
            if key.endswith("_ici") else \
            key[: -len("exchange_wire_bytes_dcn")]
        hier_key = f"{prefix}exchange_hierarchy"
        refs = [(v, b.name) for b in baseline
                if b.get(hier_key) == candidate.get(hier_key)
                and (v := _numeric(b.get(key))) is not None]
        if not refs:
            continue
        ref, ref_name = min(refs)
        if ref >= 0 and cand_v > (1.0 + tol.wire) * max(ref, 1.0):
            growth = (cand_v - ref) / max(ref, 1.0)
            findings.append(GateFinding(
                "PERF003",
                f"{candidate.name}: {key} grew {growth * 100:.1f}% "
                f"({cand_v:g} vs {ref:g} in {ref_name}; tolerance "
                f"{tol.wire * 100:.0f}%) — more bytes on the wire for "
                f"the same exchange"))
    return findings


@dataclasses.dataclass
class GateReport:
    findings: List[GateFinding]
    artifacts: List[str]
    candidate: Optional[str]
    predictions: List[Dict]      # cost-model context, informational

    @property
    def exit_code(self) -> int:
        return 1 if self.findings else 0

    def as_json(self) -> dict:
        return {"findings": [f.as_json() for f in self.findings],
                "artifacts": self.artifacts,
                "candidate": self.candidate,
                "predictions": self.predictions}


def _predictions(trajectory: Sequence[Artifact],
                 target: Artifact) -> List[Dict]:
    """Calibrated-roofline context for the report: predicted vs
    measured rate per family, calibrated on the trajectory *excluding*
    the target.  Informational — the gate's verdict comes from the
    direct diffs; this line is what tells a reader whether a failure
    is 'model drifted' or 'run collapsed'."""
    out: List[Dict] = []
    # the roofline is calibrated on TPU rounds; predicting a known
    # non-TPU artifact (CPU twin runs) with v5e constants is noise
    platform = target.get("platform")
    if platform is not None and platform != "tpu":
        return out
    # calibration artifact > preset knob > device_kind preset > v5e;
    # device_kind only steers the preset on real TPU artifacts — the
    # precedence chain of docs/calibration.md
    hw = CM.resolve_hardware_model(
        device_kind=target.get("device_kind")
        if platform == "tpu" else None)
    cal = CM.calibrate([t.fields for t in trajectory
                        if t.name != target.name], hw=hw)
    for w in CM.workloads_from_artifact(target.fields):
        pred = CM.predict_rate(cal, w)
        measured = _numeric(target.get(w.rate_field))
        if pred is None or measured is None:
            continue
        out.append({
            "family": w.family, "field": w.rate_field,
            "predicted": round(pred, 1), "measured": measured,
            "error": round(abs(pred - measured) / measured, 4)
            if measured else None})
    return out


def run_gate(trajectory_paths: Sequence[str],
             candidate_path: Optional[str] = None,
             tolerances: Optional[Tolerances] = None) -> GateReport:
    """Run the gate: candidate-vs-trajectory when ``candidate_path`` is
    given, else the trajectory self-walk (each artifact vs its
    predecessors).  Deterministic for fixed inputs + env."""
    tol = tolerances or Tolerances.from_env()
    trajectory = [load_artifact(p) for p in trajectory_paths]
    findings: List[GateFinding] = []
    if not trajectory:
        # nothing recorded yet, nothing to regress against; a candidate
        # is still loaded so an unreadable one fails here
        cand = load_artifact(candidate_path) if candidate_path else None
        return GateReport(findings=[], artifacts=[],
                          candidate=cand.name if cand else None,
                          predictions=[])
    if candidate_path is not None:
        candidate = load_artifact(candidate_path)
        check_comparable(trajectory, candidate)
        findings = diff(trajectory, candidate, tol)
        predictions = _predictions(trajectory, candidate)
        cand_name = candidate.name
    else:
        for i in range(1, len(trajectory)):
            check_comparable(trajectory[:i], trajectory[i])
            findings.extend(diff(trajectory[:i], trajectory[i], tol))
        # prediction context anchors on the newest artifact that
        # actually measures a workload (MULTICHIP stubs carry none)
        target = next((t for t in reversed(trajectory)
                       if CM.workloads_from_artifact(t.fields)),
                      trajectory[-1])
        predictions = _predictions(trajectory, target)
        cand_name = None
    return GateReport(findings=findings,
                      artifacts=[t.name for t in trajectory],
                      candidate=cand_name, predictions=predictions)


# -- CLI (python -m horovod_tpu.analysis perf-gate / hvdlint perf-gate) -----


def default_trajectory(root: Optional[str] = None) -> List[str]:
    """The trajectory at the repo root: any ``BENCH_r0*.json`` +
    ``MULTICHIP_r0*.json``, oldest→newest.  May be empty (no round
    recorded yet): the gate then has nothing to regress against and
    passes."""
    root = root or engine.find_repo_root(os.getcwd()) or os.getcwd()
    return (sorted(_glob.glob(os.path.join(root, "BENCH_r0*.json")))
            + sorted(_glob.glob(os.path.join(root,
                                             "MULTICHIP_r0*.json"))))


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m horovod_tpu.analysis perf-gate",
        description="perf regression gate: diff bench artifacts "
                    "against the checked-in trajectory "
                    "(docs/perf_gate.md)")
    p.add_argument("--trajectory", action="append", default=[],
                   metavar="PATH",
                   help="baseline artifact path or glob (repeatable; "
                        "default: <repo>/BENCH_r0*.json + "
                        "MULTICHIP_r0*.json)")
    p.add_argument("--candidate", default=None, metavar="PATH",
                   help="new bench --json-out artifact to gate; "
                        "without it the trajectory self-walk runs")
    p.add_argument("--tolerance", type=float, default=None,
                   help="relative throughput-drop tolerance (overrides "
                        "HOROVOD_PERF_GATE_TOLERANCE; default 0.10)")
    p.add_argument("--json", action="store_true", dest="json_out")
    args = p.parse_args(argv)

    try:
        paths: List[str] = []
        for pat in args.trajectory:
            hits = sorted(_glob.glob(pat))
            if not hits and os.path.exists(pat):
                hits = [pat]
            if not hits:
                raise GateError(f"--trajectory {pat}: no artifacts "
                                f"match")
            paths.extend(hits)
        if not paths:
            paths = default_trajectory()
        report = run_gate(paths, candidate_path=args.candidate,
                          tolerances=Tolerances.from_env(
                              throughput=args.tolerance))
    except GateError as e:
        print(f"perf-gate: {e}", file=sys.stderr)
        return 2

    if args.json_out:
        print(json.dumps(report.as_json(), indent=2))
    else:
        for f in report.findings:
            print(f.format())
        for pr in report.predictions:
            print(f"perf-gate: cost model [{pr['family']}] predicted "
                  f"{pr['predicted']:g} {pr['field']}, measured "
                  f"{pr['measured']:g} ({pr['error'] * 100:.1f}% off)")
        verdict = "FAIL" if report.findings else "ok"
        target = report.candidate or "trajectory self-walk"
        print(f"perf-gate: {target} vs {len(report.artifacts)} "
              f"artifact(s): {len(report.findings)} finding(s) — "
              f"{verdict}")
    return report.exit_code


if __name__ == "__main__":
    sys.exit(main())
