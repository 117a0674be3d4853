"""hvdlint: distributed-correctness static analysis for horovod_tpu.

The compile-time half of the repo's correctness tooling (docs/
analysis.md): an AST-based rule engine that finds the bug classes the
paper's runtime controller policed dynamically — rank-divergent
collectives (HVD001), host syncs in jitted bodies (HVD002), retrace/
warm-start-miss hazards (HVD003), unlocked cross-thread mutations and
lock-order inversions (HVD004), undeclared/undocumented env knobs
(HVD005), chaos-hook coverage rot (HVD006) — plus an offline HLO/
bench-artifact rule pack (:mod:`~horovod_tpu.analysis.hlo_lint`), the
static HLO cost model (:mod:`~horovod_tpu.analysis.cost_model`:
per-op FLOPs, per-level wire bytes, memory high-water, calibrated
roofline) and the perf regression gate
(:mod:`~horovod_tpu.analysis.perf_gate`, PERF001-PERF004).

The package self-run is a tier-1 test (``tests/test_analysis.py``),
and so are the perf gate's trajectory walk and the combined CI entry
point (``tests/test_perf_gate.py``)::

    python -m horovod_tpu.analysis horovod_tpu/
    python -m horovod_tpu.analysis --changed --json
    python -m horovod_tpu.analysis --artifact bench.json
    python -m horovod_tpu.analysis perf-gate --candidate new.json
    python -m horovod_tpu.analysis ci

The rule engine is AST-only and never imports the analyzed code, so a
module that cannot import (missing optional dep, syntax error) can
still be linted.
"""

from horovod_tpu.analysis.engine import (
    Finding,
    Report,
    Rule,
    Severity,
    default_rules,
    run_analysis,
    write_baseline,
)
from horovod_tpu.analysis.perf_gate import (
    GateError,
    GateFinding,
    Tolerances,
    run_gate,
)

__all__ = [
    "Finding",
    "GateError",
    "GateFinding",
    "Report",
    "Rule",
    "Severity",
    "Tolerances",
    "default_rules",
    "run_analysis",
    "run_gate",
    "write_baseline",
]
