"""Perf regression gate (analysis/perf_gate.py) + the hvdci entry:
the checked-in BENCH/MULTICHIP trajectory must pass, a fixture with a
synthetic >10% throughput drop must fail, both deterministically
across two runs, and schema/comparability violations refuse with a
clear error — never a KeyError."""

import copy
import json
import time
from pathlib import Path

import pytest

from horovod_tpu.analysis import perf_gate as PG
from horovod_tpu.analysis.__main__ import main as cli_main
from horovod_tpu.analysis.ci import main as ci_main

REPO = Path(__file__).resolve().parent.parent
# test input, not measurements: five rounds of `parsed` fields in the
# driver-wrapper layout (tests/fixtures/gate_trajectory/)
GATE_INPUT = Path(__file__).resolve().parent / "fixtures" / "gate_trajectory"


def trajectory_paths():
    paths = sorted(str(p) for p in GATE_INPUT.glob("gate_input_r0*.json")) \
        + PG.default_trajectory(str(REPO))
    assert len(paths) >= 10, paths
    return paths


def r05_copy(tmp_path, mutate=None, name="BENCH_candidate.json"):
    """A candidate artifact cloned from the newest fixture round,
    optionally mutated (the satellite's synthetic-regression recipe)."""
    with open(GATE_INPUT / "gate_input_r05.json") as f:
        data = json.load(f)
    if mutate is not None:
        mutate(data["parsed"])
    p = tmp_path / name
    p.write_text(json.dumps(data))
    return str(p)


class TestTrajectory:
    def test_checked_in_trajectory_passes(self):
        report = PG.run_gate(trajectory_paths())
        assert report.findings == [], \
            [f.format() for f in report.findings]

    def test_deterministic_across_two_runs(self):
        paths = trajectory_paths()
        a, b = PG.run_gate(paths), PG.run_gate(paths)
        assert [f.as_json() for f in a.findings] == \
            [f.as_json() for f in b.findings]
        assert a.predictions == b.predictions

    def test_walk_reports_cost_model_context(self):
        """The walk anchors its calibrated-prediction context on the
        newest artifact that measures a workload (the MULTICHIP stubs
        carry none)."""
        report = PG.run_gate(trajectory_paths())
        fams = {p["family"] for p in report.predictions}
        assert fams == {"resnet", "transformer"}
        assert all(p["error"] < 0.25 for p in report.predictions)

    def test_incomparable_transformer_rounds_not_diffed(self):
        """r03 (183.8M params) → r04 (870.9M) drops tokens/sec 58% —
        a model change, not a regression; the params comparability key
        keeps the walk green (this is what the trajectory pass already
        proves; here the key is pinned directly)."""
        a = PG._validate("r03", {"transformer_tokens_per_sec": 60224.4,
                                 "transformer_params_m": 183.8})
        b = PG._validate("r04", {"transformer_tokens_per_sec": 25281.7,
                                 "transformer_params_m": 870.9})
        assert PG.diff([a], b, PG.Tolerances()) == []


class TestSyntheticRegression:
    def test_15pct_throughput_drop_fails(self, tmp_path):
        def drop(parsed):
            parsed["transformer_tokens_per_sec"] = round(
                parsed["transformer_tokens_per_sec"] * 0.85, 1)

        cand = r05_copy(tmp_path, drop)
        report = PG.run_gate(trajectory_paths(), candidate_path=cand)
        rules = [f.rule for f in report.findings]
        assert rules == ["PERF001"], \
            [f.format() for f in report.findings]
        assert "transformer_tokens_per_sec" in \
            report.findings[0].message
        # deterministic: the acceptance criterion's two-run identity
        again = PG.run_gate(trajectory_paths(), candidate_path=cand)
        assert [f.as_json() for f in report.findings] == \
            [f.as_json() for f in again.findings]

    def test_unchanged_copy_passes(self, tmp_path):
        cand = r05_copy(tmp_path)
        report = PG.run_gate(trajectory_paths(), candidate_path=cand)
        assert report.findings == [], \
            [f.format() for f in report.findings]

    def test_drop_within_tolerance_passes(self, tmp_path):
        def drop(parsed):
            parsed["value"] = round(parsed["value"] * 0.95, 2)

        report = PG.run_gate(trajectory_paths(),
                             candidate_path=r05_copy(tmp_path, drop))
        assert report.findings == []

    def test_tolerance_knob_widens_the_gate(self, tmp_path,
                                            monkeypatch):
        def drop(parsed):
            parsed["value"] = round(parsed["value"] * 0.85, 2)

        cand = r05_copy(tmp_path, drop)
        assert PG.run_gate(trajectory_paths(),
                           candidate_path=cand).findings
        monkeypatch.setenv("HOROVOD_PERF_GATE_TOLERANCE", "0.25")
        assert PG.run_gate(trajectory_paths(),
                           candidate_path=cand).findings == []

    def test_bad_tolerance_knob_is_a_clear_error(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_PERF_GATE_TOLERANCE", "fast")
        with pytest.raises(PG.GateError, match="must be a float"):
            PG.Tolerances.from_env()

    def test_failed_run_candidate_flagged(self, tmp_path):
        p = tmp_path / "failed.json"
        p.write_text(json.dumps({"rc": 1, "ok": False, "tail": "boom"}))
        report = PG.run_gate(trajectory_paths(),
                             candidate_path=str(p))
        assert [f.rule for f in report.findings] == ["PERF004"]


class TestOverlapAndWire:
    BASE = {"exchange_hierarchy": "two_level",
            "overlap_fraction": 0.70,
            "exchange_wire_bytes_ici": 1_000_000,
            "exchange_wire_bytes_dcn": 50_000}

    def _art(self, name, **over):
        return PG._validate(name, dict(self.BASE, **over))

    def test_overlap_drop_fires_perf002(self):
        base = self._art("base")
        cand = self._art("cand", overlap_fraction=0.40)
        rules = [f.rule for f in PG.diff([base], cand,
                                         PG.Tolerances())]
        assert rules == ["PERF002"]
        # within the absolute tolerance: fine
        ok = self._art("ok", overlap_fraction=0.62)
        assert PG.diff([base], ok, PG.Tolerances()) == []

    def test_wire_growth_fires_perf003(self):
        base = self._art("base")
        cand = self._art("cand", exchange_wire_bytes_dcn=200_000)
        findings = PG.diff([base], cand, PG.Tolerances())
        assert [f.rule for f in findings] == ["PERF003"]
        assert "exchange_wire_bytes_dcn" in findings[0].message

    def test_wire_not_compared_across_hierarchies(self):
        """flat vs two_level is a topology change — more ICI bytes is
        expected, not a leak."""
        base = self._art("base")
        cand = self._art("cand", exchange_hierarchy="flat",
                         exchange_wire_bytes_ici=3_000_000,
                         exchange_wire_bytes_dcn=6_000_000)
        assert PG.diff([base], cand, PG.Tolerances()) == []

    def test_prefixed_fields_compare_per_model(self):
        base = PG._validate("base", {
            "resnet_exchange_hierarchy": "flat",
            "resnet_exchange_wire_bytes_ici": 100_000,
            "resnet_exchange_wire_bytes_dcn": 0})
        cand = PG._validate("cand", {
            "resnet_exchange_hierarchy": "flat",
            "resnet_exchange_wire_bytes_ici": 150_000,
            "resnet_exchange_wire_bytes_dcn": 0})
        findings = PG.diff([base], cand, PG.Tolerances())
        assert [f.rule for f in findings] == ["PERF003"]
        assert "resnet_" in findings[0].message


class TestPlanComparability:
    """ISSUE 13 satellite: the plan string guards every throughput/
    latency comparability key — a dp=8 number against a dp=4,fsdp=2
    number measures two exchange schedules, not a regression."""

    def _art(self, name, value, plan=None):
        parsed = {"metric": "resnet50_img_sec_per_chip", "value": value}
        if plan is not None:
            parsed["plan"] = plan
        return PG._validate(name, parsed)

    def test_plan_change_not_diffed(self):
        base = self._art("base", 3000.0, plan="dp=8")
        cand = self._art("cand", 1000.0, plan="dp=4,fsdp=2")
        assert PG.diff([base], cand, PG.Tolerances()) == []

    def test_same_plan_regression_fires(self):
        base = self._art("base", 3000.0, plan="dp=8")
        cand = self._art("cand", 1000.0, plan="dp=8")
        assert [f.rule for f in PG.diff([base], cand,
                                        PG.Tolerances())] == ["PERF001"]

    def test_planless_artifacts_still_gate(self):
        """Legacy artifacts carry no plan field; None matches None, so
        the trajectory keeps gating."""
        base = self._art("base", 3000.0)
        cand = self._art("cand", 1000.0)
        assert [f.rule for f in PG.diff([base], cand,
                                        PG.Tolerances())] == ["PERF001"]

    def test_plan_is_comparability_not_identity(self):
        """A plan change skips the diff silently — it is NOT a device-
        identity mismatch, which refuses with a GateError (the refusal
        stays reserved for category errors like v5e-vs-v4)."""
        meta = dict(TestSchema.META)
        base = PG._validate("base", dict(meta, value=3000.0,
                                         plan="dp=8"))
        cand = PG._validate("cand", dict(meta, value=10.0,
                                         plan="dp=4,fsdp=2"))
        PG.check_comparable([base], cand)      # no raise
        assert PG.diff([base], cand, PG.Tolerances()) == []
        # device identity still refuses, plan or no plan
        other = PG._validate("other", dict(meta, value=10.0,
                                           plan="dp=8",
                                           device_kind="TPU v4"))
        with pytest.raises(PG.GateError, match="not comparable"):
            PG.check_comparable([base], other)

    def test_serve_latency_fields_plan_guarded(self):
        base = PG._validate("base", {"serve_offered_rps": 100,
                                     "serve_p99_latency_s": 0.010,
                                     "plan": "dp=8"})
        cand = PG._validate("cand", {"serve_offered_rps": 100,
                                     "serve_p99_latency_s": 0.100,
                                     "plan": "dp=2,fsdp=4"})
        assert PG.diff([base], cand, PG.Tolerances()) == []


class TestReductionComparability:
    """ISSUE 19 satellite: the reduction operator is a comparability
    key on every throughput field — sum→adasum runs a different
    outer-level schedule (plus its dot/norm wire), so a rate shift
    across the switch is a schedule change, never PERF001; legacy
    artifacts without the field keep gating (None matches None)."""

    def _art(self, name, value, reduction=None):
        parsed = {"metric": "resnet50_img_sec_per_chip",
                  "value": value}
        if reduction is not None:
            parsed["reduction"] = reduction
        return PG._validate(name, parsed)

    def test_reduction_switch_not_diffed(self):
        base = self._art("base", 3000.0)
        base_r = self._art("base_r", 3000.0, reduction="sum")
        cand = self._art("cand", 1000.0, reduction="adasum")
        # operator switch: not diffed (sum-vs-adasum AND legacy
        # None-vs-adasum are both schedule changes)
        assert PG.diff([base_r], cand, PG.Tolerances()) == []
        assert PG.diff([base], cand, PG.Tolerances()) == []
        # same operator: the regression still fires
        cand_same = PG._validate("cand_same", dict(
            {"metric": "resnet50_img_sec_per_chip", "value": 1000.0},
            reduction="adasum"))
        assert [f.rule for f in PG.diff([cand], cand_same,
                                        PG.Tolerances())] == []
        slow = PG._validate("slow", dict(
            {"metric": "resnet50_img_sec_per_chip", "value": 500.0},
            reduction="adasum"))
        assert [f.rule for f in PG.diff([cand], slow,
                                        PG.Tolerances())] == ["PERF001"]

    def test_legacy_artifacts_still_gate(self):
        # legacy artifacts without the field: None matches None
        base = self._art("base", 3000.0)
        legacy = self._art("legacy", 1000.0)
        assert [f.rule for f in PG.diff([base], legacy,
                                        PG.Tolerances())] == ["PERF001"]


class TestServeFleetComparability:
    """ISSUE 20 satellite: serve_models + serve_tenant_mix are
    comparability keys on every serve field — a 3-tenant fleet run
    measures a different arbitration/hot-swap schedule than a
    single-model run, so rate/tail shifts across that switch are never
    PERF001/PERF005; legacy artifacts without the keys keep gating
    each other (None matches None)."""

    def _art(self, name, rps, p99, models=None, mix=None):
        parsed = {"metric": "serve", "serve_offered_rps": 400.0,
                  "serve_throughput_rps": rps,
                  "serve_p99_latency_s": p99}
        if models is not None:
            parsed["serve_models"] = models
            parsed["serve_tenant_mix"] = mix
        return PG._validate(name, parsed)

    def test_fleet_switch_not_diffed(self):
        base = self._art("base", 380.0, 0.012)
        fleet = self._art("fleet", 150.0, 0.05, models=3,
                          mix="batch:1|interactive:1|standard:1")
        # single-model (legacy, no keys) vs fleet: different experiment
        assert PG.diff([base], fleet, PG.Tolerances()) == []
        # a different tenant mix at the same model count: also guarded
        other_mix = self._art("other", 300.0, 0.02, models=3,
                              mix="interactive:3")
        assert PG.diff([fleet], other_mix, PG.Tolerances()) == []

    def test_same_fleet_shape_still_gates(self):
        fleet = self._art("fleet", 300.0, 0.02, models=3,
                          mix="batch:1|interactive:1|standard:1")
        slow = self._art("slow", 100.0, 0.09, models=3,
                         mix="batch:1|interactive:1|standard:1")
        rules = {f.rule for f in PG.diff([fleet], slow,
                                         PG.Tolerances())}
        assert rules == {"PERF001", "PERF005"}

    def test_legacy_serve_artifacts_still_gate(self):
        base = self._art("base", 380.0, 0.012)
        slow = self._art("slow", 150.0, 0.05)
        rules = {f.rule for f in PG.diff([base], slow,
                                         PG.Tolerances())}
        assert rules == {"PERF001", "PERF005"}


class TestMoeComparability:
    def test_moe_routing_config_guards_the_diff(self):
        """ISSUE 16 satellite: capacity_factor and the ep extent are
        comparability keys on the MoE throughput — a routing-config
        change is a schedule change (different dispatch geometry +
        drop behavior), never a regression."""
        def art(name, value, cf, ep):
            return PG._validate(name, {
                "moe_tokens_per_sec": value, "moe_params_m": 100.0,
                "moe_capacity_factor": cf, "moe_ep": ep})

        base = art("base", 30_000.0, 1.25, 1)
        # cf change: half the throughput, no finding
        assert PG.diff([base], art("cand", 15_000.0, 2.0, 1),
                       PG.Tolerances()) == []
        # ep change: no finding
        assert PG.diff([base], art("cand", 15_000.0, 1.25, 8),
                       PG.Tolerances()) == []
        # same routing config: the regression fires
        assert [f.rule for f in PG.diff(
            [base], art("cand", 15_000.0, 1.25, 1),
            PG.Tolerances())] == ["PERF001"]

    def test_moe_legacy_artifacts_still_gate(self):
        """BENCH_r0* rounds predate the routing keys; None matches
        None so the checked-in MoE trajectory keeps gating."""
        def art(name, value):
            return PG._validate(name, {"moe_tokens_per_sec": value,
                                       "moe_params_m": 100.0})

        assert [f.rule for f in PG.diff(
            [art("base", 30_000.0)], art("cand", 15_000.0),
            PG.Tolerances())] == ["PERF001"]


class TestSchema:
    META = {"schema_version": 1, "jax_version": "0.4.37",
            "jaxlib_version": "0.4.36", "platform": "tpu",
            "device_kind": "TPU v5 lite", "n_devices": 1,
            "mesh_shape": [1, 1]}

    def test_newer_schema_refused_with_clear_error(self, tmp_path):
        p = tmp_path / "future.json"
        p.write_text(json.dumps({"schema_version": 99, "value": 1.0}))
        with pytest.raises(PG.GateError, match="newer than this gate"):
            PG.load_artifact(str(p))

    def test_v1_missing_provenance_refused(self, tmp_path):
        p = tmp_path / "torn.json"
        p.write_text(json.dumps({"schema_version": 1, "value": 1.0}))
        with pytest.raises(PG.GateError, match="missing required"):
            PG.load_artifact(str(p))

    def test_identity_mismatch_refused_not_diffed(self):
        base = PG._validate("base", dict(self.META, value=3000.0))
        cand = PG._validate(
            "cand", dict(self.META, value=10.0,
                         device_kind="TPU v4", n_devices=8))
        with pytest.raises(PG.GateError, match="not comparable"):
            PG.check_comparable([base], cand)

    def test_matching_identity_diffs_normally(self):
        base = PG._validate("base", dict(
            self.META, metric="resnet50_img_sec_per_chip",
            value=3000.0))
        cand = PG._validate("cand", dict(
            self.META, metric="resnet50_img_sec_per_chip",
            value=2000.0))
        PG.check_comparable([base], cand)    # no raise
        assert [f.rule for f in PG.diff([base], cand,
                                        PG.Tolerances())] == ["PERF001"]

    def test_legacy_v0_carries_no_identity(self):
        legacy = PG._validate("old", {"value": 3000.0})
        v1 = PG._validate("new", dict(self.META, value=2900.0))
        PG.check_comparable([legacy], v1)    # no raise

    def test_garbage_artifact_is_a_clear_error(self, tmp_path):
        p = tmp_path / "garbage.json"
        p.write_text("not json {")
        with pytest.raises(PG.GateError, match="not valid JSON"):
            PG.load_artifact(str(p))
        p2 = tmp_path / "list.json"
        p2.write_text("[1, 2]")
        with pytest.raises(PG.GateError, match="JSON object"):
            PG.load_artifact(str(p2))

    def test_bench_metadata_satisfies_the_schema(self):
        """bench.py's artifact_metadata() output validates as a v1
        artifact — the producer and the gate agree on the contract."""
        import bench

        class FakeHvd:
            @staticmethod
            def size():
                return 1

        meta = bench.artifact_metadata(FakeHvd)
        assert meta["schema_version"] == PG.SCHEMA_VERSION
        art = PG._validate("fresh", dict(meta, value=1.0))
        assert art.schema_version == 1


class TestCli:
    def test_perf_gate_subcommand_trajectory(self, capsys):
        rc = cli_main(["perf-gate"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "trajectory self-walk" in out and "ok" in out

    def test_perf_gate_subcommand_candidate_json(self, tmp_path,
                                                 capsys):
        def drop(parsed):
            parsed["value"] = round(parsed["value"] * 0.80, 2)

        cand = r05_copy(tmp_path, drop)
        traj = ["--trajectory", str(GATE_INPUT / "gate_input_r0*.json")]
        rc = cli_main(["perf-gate", *traj, "--candidate", cand, "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert out["findings"][0]["rule"] == "PERF001"
        # --tolerance flag overrides the env default
        assert cli_main(["perf-gate", *traj, "--candidate", cand,
                         "--tolerance", "0.5"]) == 0

    def test_empty_trajectory_is_tolerated(self, tmp_path, capsys):
        """No round recorded yet (the root trajectory files are gone):
        nothing to regress against, so the walk and a candidate both
        pass — and an unreadable candidate still fails."""
        assert PG.default_trajectory(str(tmp_path)) == []
        assert PG.run_gate([]).exit_code == 0
        assert PG.run_gate([], candidate_path=r05_copy(tmp_path)) \
            .findings == []
        bad = tmp_path / "bad.json"
        bad.write_text("not json")
        with pytest.raises(PG.GateError):
            PG.run_gate([], candidate_path=str(bad))

    def test_perf_gate_bad_trajectory_is_usage_error(self, tmp_path,
                                                     capsys):
        rc = cli_main(["perf-gate", "--trajectory",
                       str(tmp_path / "nope_*.json")])
        assert rc == 2
        assert "no artifacts match" in capsys.readouterr().err

    def test_schema_refusal_exits_2(self, tmp_path, capsys):
        p = tmp_path / "future.json"
        p.write_text(json.dumps({"schema_version": 99}))
        rc = cli_main(["perf-gate", "--candidate", str(p)])
        assert rc == 2
        assert "newer than this gate" in capsys.readouterr().err


class TestCiEntry:
    def test_ci_self_run_green_and_in_budget(self, capsys):
        """The tier-1 gate: hvdlint --changed + the artifact pack +
        the perf-gate walk, one invocation, same <30 s budget as the
        hvdlint self-run."""
        t0 = time.perf_counter()
        rc = ci_main([])
        elapsed = time.perf_counter() - t0
        out = capsys.readouterr().out
        assert rc == 0, out
        assert elapsed < 30, f"ci run took {elapsed:.1f}s"
        assert "hvdci:" in out and "ok" in out

    def test_ci_subcommand_json(self, capsys):
        rc = cli_main(["ci", "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert out["exit_code"] == 0
        assert out["perf_gate"]["findings"] == []
        assert out["lint"]["findings"] == []

    def test_ci_full_scan(self, capsys):
        assert ci_main(["--full"]) == 0
        assert "lint[full]" in capsys.readouterr().out


class TestSpComparability:
    """ISSUE 17 satellite: the sp extent and the sequence length are
    comparability keys on the transformer throughput — an sp=2
    seq-4096 long-context run against an sp=1 seq-512 one measures a
    different attention schedule and a t²-different FLOP mix, never a
    regression."""

    @staticmethod
    def _art(name, value, sp=None, seq=None, plan=None):
        fields = {"transformer_tokens_per_sec": value,
                  "transformer_params_m": 10.0}
        if sp is not None:
            fields["sp"] = sp
        if seq is not None:
            fields["transformer_seq_len"] = seq
        if plan is not None:
            fields["plan"] = plan
        return PG._validate(name, fields)

    def test_sp_extent_change_not_diffed(self):
        base = self._art("base", 60_000.0, sp=1, seq=512)
        cand = self._art("cand", 20_000.0, sp=2, seq=512)
        assert PG.diff([base], cand, PG.Tolerances()) == []

    def test_seq_len_change_not_diffed(self):
        base = self._art("base", 60_000.0, sp=2, seq=512)
        cand = self._art("cand", 20_000.0, sp=2, seq=4096)
        assert PG.diff([base], cand, PG.Tolerances()) == []

    def test_same_sp_and_seq_regression_fires(self):
        base = self._art("base", 60_000.0, sp=2, seq=4096,
                         plan="dp=4,sp=2")
        cand = self._art("cand", 20_000.0, sp=2, seq=4096,
                         plan="dp=4,sp=2")
        assert [f.rule for f in PG.diff([base], cand,
                                        PG.Tolerances())] == ["PERF001"]

    def test_legacy_artifacts_without_sp_keys_still_gate(self):
        # BENCH_r0* rounds predate the keys; None matches None
        base = self._art("base", 60_000.0)
        cand = self._art("cand", 20_000.0)
        assert [f.rule for f in PG.diff([base], cand,
                                        PG.Tolerances())] == ["PERF001"]
