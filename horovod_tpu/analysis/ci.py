"""``python -m horovod_tpu.analysis ci`` / ``hvdci`` — the one-shot CI
entry point.

Eleven gates, one invocation, one exit code (docs/perf_gate.md):

1. **hvdlint** over the pre-commit scope (``--changed``: staged +
   unstaged + untracked files under ``horovod_tpu/``; falls back to the
   full package scan outside a git checkout — an sdist CI job still
   gets linted, just wider);
2. the **HLO/artifact rule pack** over every ``BENCH_r0*.json`` /
   ``MULTICHIP_r0*.json`` at the repo root (there may be none);
3. the **perf gate** trajectory self-walk;
4. the **guard-chaos smoke** (``guard/smoke.py``): a seeded silent-
   corruption → detect → rollback → replay round trip, run twice and
   required bit-identical (docs/guardian.md);
5. the **serve-chaos smoke** (``serve/smoke.py``): the serving plane's
   enqueue → batch → kill-replica → requeue → drain loop, seeded, run
   twice and required bit-identical (docs/serving.md);
6. the **plan smoke** (``parallel/smoke.py``): a seeded dp×tp×pp
   virtual-device walk of the sharding-plan compiler — tensor shards,
   data-extent exchange and the interleaved-1F1B tick schedule, run
   twice and required bit-identical (docs/parallelism.md);
7. the **degrade smoke** (``elastic/smoke.py``): the plan-aware
   degradation loop — seeded kill → dp-shrink reshard → replay →
   promote at the next checkpoint boundary, bit-exact against a
   never-degraded run, run twice and required bit-identical
   (docs/elastic.md "Degraded mode");
8. the **memory smoke** (``memory/smoke.py``): the HBM-budgeted
   planner — unconstrained vs budgeted search must pick different
   feasible winners, an infeasible budget must raise naming the
   tightest axis, run twice and required bit-identical
   (docs/memory.md);
9. the **calibration smoke** (``analysis/calibration.py``): a seeded
   pure-sim calibrate → fit → ``HardwareModel.from_calibration`` →
   price round trip, run twice and required bit-identical, plus the
   artifact schema check over any checked-in ``CALIBRATION*.json``
   (docs/calibration.md);
10. the **adasum smoke** (``analysis/adasum_smoke.py``): seeded
    gradient-pair fixtures of the pairwise reduction operator
    (parallel/orthogonal/antiparallel/zero-norm) plus a two-slice
    convergence loop — adasum at 2× tracks the base-batch sum
    trajectory while plain sum at 2× degrades — run twice and
    required bit-identical (docs/adasum.md);
11. the **fleet smoke** (``serve/fleet_smoke.py``): the hvdfleet
    story — 3-model weighted-fair enqueue → live weight refresh
    mid-load (fingerprint-verified flip) → kill-replica →
    autoscale-up → drain, seeded, run twice and required
    bit-identical (docs/serving.md).

The whole run is a tier-1 test with the same <30 s budget as the
hvdlint self-run, so "CI passed" and "the analysis suite passed" are
the same fact.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Optional

from horovod_tpu.analysis import engine, hlo_lint, metrics_schema, perf_gate


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m horovod_tpu.analysis ci",
        description="run hvdlint (--changed scope), the artifact rule "
                    "pack and the perf gate in one invocation")
    p.add_argument("--full", action="store_true",
                   help="lint the whole package instead of the "
                        "--changed scope")
    p.add_argument("--root", default=None,
                   help="repo root (default: auto-detected)")
    p.add_argument("--json", action="store_true", dest="json_out")
    args = p.parse_args(argv)

    t0 = time.perf_counter()
    root = args.root or engine.find_repo_root(os.getcwd()) or os.getcwd()
    pkg = os.path.join(root, "horovod_tpu")

    # 1 — hvdlint
    scope = "full"
    paths: List[str] = [pkg]
    if not args.full:
        try:
            changed = [f for f in engine.changed_files(root)
                       if os.path.abspath(f).startswith(pkg + os.sep)]
            paths, scope = changed, "--changed"
        except Exception:          # noqa: BLE001 — not a git checkout
            pass
    baseline = os.path.join(root, "analysis_baseline.json")
    if paths:
        lint = engine.run_analysis(
            paths, root=root,
            baseline_path=baseline if os.path.exists(baseline) else None)
    else:
        lint = engine.Report(findings=[], suppressed=[], baselined=[])

    # 2 — artifact rule pack (HLO001-HLO004 over the checked-in runs)
    # plus the hvdtel metrics-snapshot schema check: any embedded
    # "metrics" block must parse against the telemetry contract
    # (analysis/metrics_schema.py; legacy artifacts without one pass)
    artifacts = perf_gate.default_trajectory(root)
    art_findings = []
    metrics_errors = []
    art_error = None
    for art in artifacts:
        try:
            art_findings.extend(hlo_lint.lint_artifact_path(art))
            with open(art) as f:
                blob = json.load(f)
            metrics_errors.extend(
                f"{os.path.basename(art)}: {e}"
                for e in metrics_schema.validate_artifact_metrics(blob))
        except (OSError, json.JSONDecodeError) as e:
            art_error = f"cannot read {art}: {e}"
            break

    # 3 — perf gate trajectory self-walk
    gate_error = None
    gate = None
    if artifacts and art_error is None:
        try:
            gate = perf_gate.run_gate(artifacts)
        except perf_gate.GateError as e:
            gate_error = str(e)

    # 4 — guard-chaos smoke: the integrity plane's detect→rollback→
    # replay loop, seeded and deterministic (sub-second, CPU-only)
    try:
        from horovod_tpu.guard.smoke import run_smoke

        guard_errors = run_smoke()
    except Exception as e:          # noqa: BLE001 — a crash IS a failure
        guard_errors = [f"guard-smoke crashed: {type(e).__name__}: {e}"]

    # 5 — serve-chaos smoke: the serving plane's crash→requeue→drain
    # loop, seeded and deterministic (sub-second, CPU-only)
    try:
        from horovod_tpu.serve.smoke import run_smoke as run_serve_smoke

        serve_errors = run_serve_smoke()
    except Exception as e:          # noqa: BLE001 — a crash IS a failure
        serve_errors = [f"serve-smoke crashed: {type(e).__name__}: {e}"]

    # 6 — plan smoke: the sharding-plan compiler's dp×tp×pp virtual-
    # device walk, seeded and deterministic (sub-second, CPU-only)
    try:
        from horovod_tpu.parallel.smoke import run_smoke as run_plan_smoke

        plan_errors = run_plan_smoke()
    except Exception as e:          # noqa: BLE001 — a crash IS a failure
        plan_errors = [f"plan-smoke crashed: {type(e).__name__}: {e}"]

    # 7 — degrade smoke: the plan-aware degradation loop's kill →
    # shrink → replay → promote round trip, seeded and deterministic
    try:
        from horovod_tpu.elastic.smoke import run_smoke as \
            run_degrade_smoke

        degrade_errors = run_degrade_smoke()
    except Exception as e:          # noqa: BLE001 — a crash IS a failure
        degrade_errors = [f"degrade-smoke crashed: "
                          f"{type(e).__name__}: {e}"]

    # 8 — memory smoke: the HBM-budgeted planner's free → budgeted →
    # infeasible walk, seeded and deterministic (sub-second, no JAX)
    try:
        from horovod_tpu.memory.smoke import run_smoke as \
            run_memory_smoke

        memory_errors = run_memory_smoke()
    except Exception as e:          # noqa: BLE001 — a crash IS a failure
        memory_errors = [f"memory-smoke crashed: "
                         f"{type(e).__name__}: {e}"]

    # 9 — calibration smoke: seeded sim calibrate→fit→price, run twice
    # bit-identical, + schema check over checked-in CALIBRATION*.json
    try:
        from horovod_tpu.analysis.calibration import run_smoke as \
            run_calibration_smoke

        calibration_errors = run_calibration_smoke(root)
    except Exception as e:          # noqa: BLE001 — a crash IS a failure
        calibration_errors = [f"calibration-smoke crashed: "
                              f"{type(e).__name__}: {e}"]

    # 10 — adasum smoke: seeded pair fixtures + the two-slice
    # convergence loop, run twice bit-identical (sub-second, stdlib)
    try:
        from horovod_tpu.analysis.adasum_smoke import run_smoke as \
            run_adasum_smoke

        adasum_errors = run_adasum_smoke(root)
    except Exception as e:          # noqa: BLE001 — a crash IS a failure
        adasum_errors = [f"adasum-smoke crashed: "
                         f"{type(e).__name__}: {e}"]

    # 11 — fleet smoke: the multi-tenant serving plane's weighted-fair
    # enqueue → refresh-mid-load → kill → scale-up → drain loop,
    # seeded and deterministic (sub-second, CPU-only)
    try:
        from horovod_tpu.serve.fleet_smoke import run_smoke as \
            run_fleet_smoke

        fleet_errors = run_fleet_smoke()
    except Exception as e:          # noqa: BLE001 — a crash IS a failure
        fleet_errors = [f"fleet-smoke crashed: "
                        f"{type(e).__name__}: {e}"]

    elapsed = time.perf_counter() - t0
    gate_findings = gate.findings if gate is not None else []
    rc = 2 if (art_error or gate_error) else (
        1 if (lint.findings or art_findings or gate_findings
              or metrics_errors or guard_errors or serve_errors
              or plan_errors or degrade_errors or memory_errors
              or calibration_errors or adasum_errors or fleet_errors)
        else 0)

    if args.json_out:
        print(json.dumps({
            "lint": dict(lint.as_json(), scope=scope),
            "artifact_findings": [f.as_json() for f in art_findings],
            "metrics_schema_errors": metrics_errors,
            "guard_smoke_errors": guard_errors,
            "serve_smoke_errors": serve_errors,
            "plan_smoke_errors": plan_errors,
            "degrade_smoke_errors": degrade_errors,
            "memory_smoke_errors": memory_errors,
            "calibration_smoke_errors": calibration_errors,
            "adasum_smoke_errors": adasum_errors,
            "fleet_smoke_errors": fleet_errors,
            "perf_gate": gate.as_json() if gate is not None else None,
            "errors": [e for e in (art_error, gate_error) if e],
            "elapsed_s": round(elapsed, 3),
            "exit_code": rc,
        }, indent=2))
        return rc

    for f in lint.findings:
        print(f.format())
    for f in art_findings:
        print(f.format())
    for e in metrics_errors:
        print(f"hvdci: metrics-schema: {e}")
    for e in guard_errors:
        print(f"hvdci: guard-smoke: {e}")
    for e in serve_errors:
        print(f"hvdci: serve-smoke: {e}")
    for e in plan_errors:
        print(f"hvdci: plan-smoke: {e}")
    for e in degrade_errors:
        print(f"hvdci: degrade-smoke: {e}")
    for e in memory_errors:
        print(f"hvdci: memory-smoke: {e}")
    for e in calibration_errors:
        print(f"hvdci: calibration-smoke: {e}")
    for e in adasum_errors:
        print(f"hvdci: adasum-smoke: {e}")
    for e in fleet_errors:
        print(f"hvdci: fleet-smoke: {e}")
    for f in gate_findings:
        print(f.format())
    for err in (art_error, gate_error):
        if err:
            print(f"hvdci: ERROR {err}", file=sys.stderr)
    print(f"hvdci: lint[{scope}] {len(lint.findings)} · "
          f"artifacts[{len(artifacts)}] "
          f"{len(art_findings) + len(metrics_errors)} · "
          f"perf-gate {len(gate_findings)} · "
          f"guard-smoke {len(guard_errors)} · "
          f"serve-smoke {len(serve_errors)} · "
          f"plan-smoke {len(plan_errors)} · "
          f"degrade-smoke {len(degrade_errors)} · "
          f"memory-smoke {len(memory_errors)} · "
          f"calibration-smoke {len(calibration_errors)} · "
          f"adasum-smoke {len(adasum_errors)} · "
          f"fleet-smoke {len(fleet_errors)} finding(s) "
          f"in {elapsed:.2f}s — {'FAIL' if rc else 'ok'}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
