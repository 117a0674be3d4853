"""Measured hardware model (analysis/calibration.py + the cost-model
consumers): alpha-beta fit round-trips, the versioned artifact schema,
the ``calibration > preset > device_kind > v5e`` precedence chain, the
degenerate-tree pricing pins (a 2-level tree IS ``two_level``, a
1-level tree IS ``flat``), stride-aware wire attribution, and the
tightened held-out acceptance bars — calibrate on r01–r04, predict
r05 within 1.7% (resnet) / 0.21% (transformer)."""

import glob
import json
import warnings
from pathlib import Path

import pytest

from horovod_tpu.analysis import calibration as CAL
from horovod_tpu.analysis import cost_model as CM
from horovod_tpu.analysis import perf_gate as PG
from horovod_tpu.runtime import topology as T
from horovod_tpu.utils import hlo as H

REPO = Path(__file__).resolve().parent.parent


class TestAlphaBetaFit:
    def test_noiseless_round_trip(self):
        """A sweep generated from known (alpha, beta) truth recovers
        both constants exactly (closed-form least squares on an exact
        line) with ~zero residual."""
        alpha, beta = 25e-6, 40e9
        sizes = [2 ** p for p in range(16, 27, 2)]
        times = [alpha + n / beta for n in sizes]
        a, b, res = CAL.fit_alpha_beta(sizes, times)
        assert a == pytest.approx(alpha, rel=1e-9)
        assert b == pytest.approx(beta, rel=1e-9)
        assert res < 1e-12

    def test_fit_level_carries_metadata(self):
        sizes = [1e5, 1e6, 1e7]
        fit = CAL.fit_level("reduce_scatter", sizes,
                            [1e-5 + n / 1e10 for n in sizes])
        assert fit.collective == "reduce_scatter"
        assert fit.n_points == 3
        assert fit.predict_s(2e6) == pytest.approx(
            fit.alpha_s + 2e6 / fit.beta_bytes_per_s)

    def test_negative_latency_clamped_to_zero(self):
        """Noise can push the intercept below 0 — clamp, don't emit a
        negative latency."""
        sizes = [1e6, 2e6, 4e6]
        times = [n / 1e10 - 1e-6 for n in sizes]
        a, _, _ = CAL.fit_alpha_beta(sizes, times)
        assert a == 0.0

    def test_degenerate_sweeps_raise(self):
        with pytest.raises(ValueError, match=">= 2"):
            CAL.fit_alpha_beta([1e6], [1e-3])
        with pytest.raises(ValueError, match="distinct"):
            CAL.fit_alpha_beta([1e6, 1e6], [1e-3, 1e-3])
        # time DECREASING with bytes: no bandwidth to resolve
        with pytest.raises(ValueError, match="slope"):
            CAL.fit_alpha_beta([1e6, 2e6], [2e-3, 1e-3])


class TestSimulatedCalibration:
    def test_seeded_sim_is_bit_deterministic(self):
        a = CAL.simulated_calibration(seed=17)
        b = CAL.simulated_calibration(seed=17)
        assert json.dumps(a, sort_keys=True) == \
            json.dumps(b, sort_keys=True)
        assert a != CAL.simulated_calibration(seed=18)

    def test_sim_artifact_validates_and_fingerprints(self):
        art = CAL.simulated_calibration(seed=17)
        assert CAL.validate_calibration(art) == []
        assert art["calibration_fingerprint"] == \
            CM.calibration_fingerprint(art)
        assert art["source"] == "simulated"
        assert art["level_order"] == ["ici", "dcn"]

    def test_fit_recovers_the_simulated_truth(self):
        """HardwareModel.from_calibration on a sim artifact lands
        within 1% of the preset the sweep was simulated from — the
        round trip hvdci gate 9 pins."""
        hw = CM.HardwareModel.from_calibration(
            CAL.simulated_calibration(seed=17))
        assert hw.name == "calibrated:simulated:v5e"
        assert hw.ici_bytes_per_s == pytest.approx(
            CM.V5E.ici_bytes_per_s, rel=0.01)
        assert hw.dcn_bytes_per_s == pytest.approx(
            CM.V5E.dcn_bytes_per_s, rel=0.01)
        assert hw.peak_flops_per_s == CM.V5E.peak_flops_per_s

    def test_smoke_gate_passes(self):
        assert CAL.run_smoke() == []

    def test_save_load_round_trip(self, tmp_path):
        art = CAL.simulated_calibration(seed=17)
        p = tmp_path / "CALIBRATION.json"
        CAL.save_artifact(art, str(p))
        assert CAL.load_artifact(str(p)) == art


class TestArtifactSchema:
    def _art(self):
        return CAL.simulated_calibration(seed=17)

    def test_missing_field_flagged(self):
        art = self._art()
        del art["matmul_flops_per_s"]
        assert any("matmul_flops_per_s" in e
                   for e in CAL.validate_calibration(art))

    def test_wrong_kind_flagged(self):
        art = dict(self._art(), kind="something_else")
        assert CAL.validate_calibration(art)

    def test_newer_schema_version_refused(self):
        art = dict(self._art(), schema_version=99)
        assert any("newer" in e for e in CAL.validate_calibration(art))

    def test_level_order_mismatch_flagged(self):
        art = dict(self._art(), level_order=["ici", "pod"])
        assert any("level_order" in e
                   for e in CAL.validate_calibration(art))

    def test_non_positive_beta_flagged(self):
        art = json.loads(json.dumps(self._art()))
        art["levels"]["dcn"]["collectives"]["reduce_scatter"][
            "beta_bytes_per_s"] = 0.0
        assert any("beta" in e for e in CAL.validate_calibration(art))

    def test_tampered_fingerprint_flagged(self):
        art = dict(self._art(), n_devices=64)
        assert any("fingerprint" in e
                   for e in CAL.validate_calibration(art))

    def test_load_artifact_raises_on_invalid(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps({"kind": "horovod_calibration"}))
        with pytest.raises(ValueError, match="missing field"):
            CAL.load_artifact(str(p))


class TestPresetsAndPrecedence:
    def test_builtin_preset_vocabulary(self):
        assert set(CM.HW_PRESETS) == {"v5e", "v5p", "v4", "cpu-twin"}
        assert CM.HW_PRESETS["v5p"].peak_flops_per_s > \
            CM.HW_PRESETS["v4"].peak_flops_per_s > \
            CM.HW_PRESETS["v5e"].peak_flops_per_s

    def test_device_kind_mapping(self):
        assert CM.preset_for_device_kind("TPU v5 lite") is CM.V5E
        assert CM.preset_for_device_kind("TPU v5p") is CM.V5P
        assert CM.preset_for_device_kind("TPU v4") is CM.V4
        assert CM.preset_for_device_kind("cpu") is CM.CPU_TWIN

    def test_unknown_kind_warns_loudly(self):
        with pytest.warns(UserWarning, match="bench --calibrate"):
            assert CM.preset_for_device_kind("TPU v9 mega") is None
        # warn=False: silent None (the from_calibration capacity path)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert CM.preset_for_device_kind("TPU v9 mega",
                                             warn=False) is None

    def _sim_path(self, tmp_path):
        p = tmp_path / "CAL.json"
        CAL.save_artifact(CAL.simulated_calibration(seed=17), str(p))
        return str(p)

    def test_calibration_env_beats_preset_env(self, tmp_path,
                                              monkeypatch):
        monkeypatch.setenv("HOROVOD_CALIBRATION_PATH",
                           self._sim_path(tmp_path))
        monkeypatch.setenv("HOROVOD_HW_PRESET", "v4")
        hw = CM.resolve_hardware_model(device_kind="TPU v5p")
        assert hw.name.startswith("calibrated:")

    def test_preset_env_beats_device_kind(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_CALIBRATION_PATH", raising=False)
        monkeypatch.setenv("HOROVOD_HW_PRESET", "v4")
        assert CM.resolve_hardware_model(
            device_kind="TPU v5p") is CM.V4

    def test_device_kind_beats_default(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_CALIBRATION_PATH", raising=False)
        monkeypatch.delenv("HOROVOD_HW_PRESET", raising=False)
        assert CM.resolve_hardware_model(
            device_kind="TPU v5p") is CM.V5P
        assert CM.resolve_hardware_model() is CM.V5E

    def test_broken_calibration_path_raises_not_falls_back(
            self, tmp_path, monkeypatch):
        """Measured constants were promised — a silent fallback to
        builtin guesses would un-promise them."""
        p = tmp_path / "torn.json"
        p.write_text("{not json")
        monkeypatch.setenv("HOROVOD_CALIBRATION_PATH", str(p))
        with pytest.raises(ValueError, match="HOROVOD_CALIBRATION_PATH"):
            CM.resolve_hardware_model()

    def test_unknown_preset_name_raises(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_CALIBRATION_PATH", raising=False)
        monkeypatch.setenv("HOROVOD_HW_PRESET", "v99")
        with pytest.raises(ValueError, match="HOROVOD_HW_PRESET"):
            CM.resolve_hardware_model()


class TestHeldOutAcceptanceBars:
    def test_r05_prediction_within_tightened_bars(self):
        """The ISSUE-18 acceptance bar: the trajectory-calibrated
        model's held-out r05 prediction error stays at the measured
        1.7% (resnet) / 0.21% (transformer) level — tightened from
        the original 25% bar, so an efficiency-model regression of
        any size is visible."""
        paths = sorted(glob.glob(str(
            REPO / "tests/fixtures/gate_trajectory/gate_input_r0*.json")))
        assert len(paths) >= 5, "trajectory fixture missing"
        cal = CM.calibrate(paths[:4])
        with open(paths[4]) as f:
            r05 = json.load(f)["parsed"]
        bars = {"resnet": 0.017, "transformer": 0.0021}
        for w in CM.workloads_from_artifact(r05):
            measured = float(r05[w.rate_field])
            predicted = CM.predict_rate(cal, w)
            err = abs(predicted - measured) / measured
            assert err <= bars[w.family], (w.family, err)


class TestDegenerateTreePricing:
    B = 3.484e9

    def test_two_level_tree_equals_exchange_wire_bytes(self):
        """The degenerate-tree pin: a 2-level (ici, dcn) tree prices
        exactly what the historical two_level model prices."""
        legacy = CM.exchange_wire_bytes(self.B, n_dcn=2, n_ici=4,
                                        hierarchy="two_level",
                                        wire_bits_dcn=8)
        tree = CM.exchange_wire_by_level(
            self.B, (("ici", 4, None), ("dcn", 2, 8)))
        assert tree["ici"] == pytest.approx(legacy.ici)
        assert tree["dcn"] == pytest.approx(legacy.dcn)

    def test_one_level_tree_equals_flat(self):
        legacy = CM.exchange_wire_bytes(self.B, n_dcn=1, n_ici=8,
                                        hierarchy="flat")
        tree = CM.exchange_wire_by_level(self.B, (("ici", 8, None),))
        assert tree["ici"] == pytest.approx(legacy.ici)

    def test_three_level_tree_shrinks_outer_hops(self):
        """Each outer level moves only the block surviving the inner
        scatters: payload/∏inner, with its own ring factor and wire
        width."""
        levels = (("chip", 4, None), ("slice", 2, None), ("pod", 2, 8))
        wire = CM.exchange_wire_by_level(self.B, levels)
        assert wire["chip"] == pytest.approx(2 * (3 / 4) * self.B)
        assert wire["slice"] == pytest.approx(2 * (1 / 2) * self.B / 4)
        assert wire["pod"] == pytest.approx(
            2 * (1 / 2) * (self.B / 8) * (8 / 32))

    def test_plan_pricing_accepts_a_topology(self):
        """plan_exchange_wire_bytes(topology=) prices the data world
        over the tree and returns the per-level dict; a topology that
        does not factor the plan's data world is refused."""
        levels = (("chip", 2, None), ("slice", 2, None),
                  ("pod", 2, 8))
        out = CM.plan_exchange_wire_bytes("dp=8", self.B,
                                          topology=levels)
        assert set(out) == {"chip", "slice", "pod"}
        assert out == CM.exchange_wire_by_level(self.B, levels)
        with pytest.raises(ValueError, match="factor"):
            CM.plan_exchange_wire_bytes("dp=4", self.B,
                                        topology=levels)

    def test_exchange_time_composes_level_bandwidths(self):
        levels = (("ici", 4, None), ("dcn", 2, 8))
        wire = CM.exchange_wire_by_level(1e9, levels)
        bw = CM.level_bandwidths(levels)
        assert bw == {"ici": CM.V5E.ici_bytes_per_s,
                      "dcn": CM.V5E.dcn_bytes_per_s}
        t = CM.exchange_time_by_level(wire, bw)
        assert t == pytest.approx(wire["ici"] / bw["ici"]
                                  + wire["dcn"] / bw["dcn"])
        with pytest.raises(ValueError, match="no bandwidth"):
            CM.exchange_time_by_level(wire, {"ici": bw["ici"]})

    def test_calibrated_bandwidths_price_the_tree(self):
        art = CAL.simulated_calibration(seed=17)
        bw = CM.calibration_level_bandwidths(art)
        assert set(bw) == {"ici", "dcn"}
        assert bw["ici"] == pytest.approx(CM.V5E.ici_bytes_per_s,
                                          rel=0.01)


class TestStrideAwareAttribution:
    """The ISSUE-18 bugfix pin: on a mesh where two levels share an
    extent, attribution must consult the replica-group STRIDE — the
    size-only rule booked every n_dcn-sized group (including
    intra-slice ones) to the DCN hop."""

    def _op(self, groups):
        line = (f"  %rs = f32[13]{{0}} reduce-scatter(%x), "
                f"replica_groups={groups}, dimensions={{0}}, "
                f"to_apply=%add")
        [op] = H.collective_ops(line)
        return op

    def test_equal_extents_no_longer_alias(self):
        """2x2 mesh (n_ici == n_dcn == 2): the intra-slice scope
        ({{0,1},{2,3}}, stride 1) books ICI; the cross-slice scope
        ({{0,2},{1,3}}, stride 2) books DCN."""
        intra, cross = self._op("{{0,1},{2,3}}"), \
            self._op("{{0,2},{1,3}}")
        levels = CM.collective_wire_by_level([intra, cross],
                                             n_dcn=2, n_ici=2)
        assert levels["ici"] > 0.0 and levels["dcn"] > 0.0
        only_intra = CM.collective_wire_by_level([intra],
                                                 n_dcn=2, n_ici=2)
        assert only_intra["dcn"] == 0.0 and only_intra["ici"] > 0.0

    def test_three_level_tree_middle_hop(self):
        """On a 2x2x2 tree every level has extent 2 — only the stride
        separates them: stride 2 is the middle (slice) hop."""
        topo = (("chip", 2, None), ("slice", 2, None),
                ("pod", 2, None))
        mid = self._op("{{0,2},{1,3},{4,6},{5,7}}")
        levels = CM.collective_wire_by_level([mid], topology=topo)
        assert levels["slice"] > 0.0
        assert levels["chip"] == 0.0 and levels["pod"] == 0.0

    def test_unmatched_groups_ride_the_innermost_fabric(self):
        world = self._op("{{0,1,2,3,4,5,6,7}}")
        levels = CM.collective_wire_by_level([world],
                                             n_dcn=2, n_ici=2)
        assert levels["ici"] > 0.0 and levels["dcn"] == 0.0

    def test_stride_parser(self):
        assert H.replica_group_stride("{{0,2},{1,3}}") == 2
        assert H.replica_group_stride("{{0,1},{2,3}}") == 1
        assert H.replica_group_stride(None) is None
        assert H.replica_group_stride("{{0,1,3}}") is None


class TestTopologyResolution:
    def test_degenerate_modes(self):
        assert T.resolve_topology("auto", (2, 4)).mode == "two_level"
        assert T.resolve_topology("auto", (1, 8)).mode == "flat"
        assert T.resolve_topology("auto", (8,)).mode == "flat"
        assert T.resolve_topology("auto", (2, 2, 2)).mode == "tree"
        assert T.resolve_topology("flat", (2, 4)).mode == "flat"

    def test_tree_levels_are_innermost_first(self):
        topo = T.resolve_topology("tree", (2, 4, 8))
        assert topo.names == ("chip", "slice", "pod")
        assert [lv.extent for lv in topo.levels] == [8, 4, 2]
        assert topo.world == 64
        # 2-axis trees keep the historical (ici, dcn) names
        assert T.resolve_topology("tree", (2, 4)).names == \
            ("ici", "dcn")

    def test_wire_bits_ride_the_outermost_hop_only(self):
        topo = T.resolve_topology("tree", (2, 2, 2), wire_bits=8)
        assert [lv.wire_bits for lv in topo.levels] == [None, None, 8]
        flat = T.resolve_topology("flat", (2, 4), wire_bits=8)
        assert flat.levels[0].wire_bits == 8

    def test_level_codecs_override_by_name(self):
        codecs = T.parse_level_codecs("slice=int8,chip=fp32")
        topo = T.resolve_topology("tree", (2, 2, 2), wire_bits=8,
                                  level_codecs=codecs)
        assert [lv.wire_bits for lv in topo.levels] == [None, 8, 8]
        with pytest.raises(ValueError, match="unknown level"):
            T.resolve_topology("tree", (2, 2),
                               level_codecs={"pod": 8})

    def test_codec_grammar(self):
        assert T.parse_level_codecs(None) == {}
        assert T.parse_level_codecs("dcn=int8,ici=fp32") == \
            {"dcn": 8, "ici": None}
        assert T.parse_level_codecs("pod=fp8_e4m3") == {"pod": 8}
        with pytest.raises(ValueError, match="bad level codec"):
            T.parse_level_codecs("dcn=fp4")
        with pytest.raises(ValueError, match="duplicate"):
            T.parse_level_codecs("dcn=int8,dcn=fp32")

    def test_effective_drops_size_one_levels(self):
        topo = T.resolve_topology("tree", (2, 1, 4))
        assert topo.names == ("chip", "slice", "pod")
        assert topo.effective().names == ("chip", "pod")
        # a 1-device world stays representable
        assert T.resolve_topology("flat", (1,)).effective().world == 1

    def test_pricing_levels_feed_the_cost_model(self):
        topo = T.resolve_topology("tree", (2, 2, 2), wire_bits=8)
        wire = CM.exchange_wire_by_level(1e9, topo.pricing_levels())
        assert set(wire) == {"chip", "slice", "pod"}

    def test_resolve_hierarchy_legacy_contract(self):
        """The 2-axis resolver's answers are unchanged, and a >2-axis
        auto still answers flat (trees did not exist in its
        vocabulary)."""
        assert T.resolve_hierarchy("auto", (2, 4)) == "two_level"
        assert T.resolve_hierarchy("auto", (2, 2, 2)) == "flat"
        with pytest.raises(ValueError, match="2-axis"):
            T.resolve_hierarchy("two_level", (8,))


class TestPerfGateRefusal:
    META = {"schema_version": 1, "jax_version": "0.9.0",
            "jaxlib_version": "0.9.0", "platform": "cpu",
            "device_kind": "TPU v5 lite", "n_devices": 1,
            "mesh_shape": [1, 1]}

    def test_differing_fingerprints_refused(self):
        base = PG._validate("base", dict(
            self.META, value=3000.0,
            calibration_fingerprint="aaaa000011112222",
            calibration_device_kind="TPU v5 lite"))
        cand = PG._validate("cand", dict(
            self.META, value=2000.0,
            calibration_fingerprint="bbbb333344445555",
            calibration_device_kind="TPU v4"))
        with pytest.raises(PG.GateError,
                           match="measured hardware models"):
            PG.check_comparable([base], cand)

    def test_matching_fingerprints_diff_normally(self):
        art = dict(self.META, metric="resnet50_img_sec_per_chip",
                   calibration_fingerprint="aaaa000011112222")
        base = PG._validate("base", dict(art, value=3000.0))
        cand = PG._validate("cand", dict(art, value=2000.0))
        PG.check_comparable([base], cand)       # no raise
        assert [f.rule for f in PG.diff([base], cand,
                                        PG.Tolerances())] == ["PERF001"]

    def test_uncalibrated_runs_stay_comparable(self):
        """A legacy artifact with no fingerprint diffs against a
        calibrated one — only two CONFLICTING measured models
        refuse."""
        base = PG._validate("base", dict(self.META, value=3000.0))
        cand = PG._validate("cand", dict(
            self.META, value=2900.0,
            calibration_fingerprint="aaaa000011112222"))
        PG.check_comparable([base], cand)       # no raise
