"""Static HLO cost model (analysis/cost_model.py + the utils/hlo.py
parser extensions): FLOP-counting fixtures for dot/convolution/fusion,
buffer-lifetime memory accounting, per-level wire attribution, and the
calibrated-roofline acceptance bar — predicted step time within 25% of
measured on BENCH_r05 for both flagship models, held-out (calibrated
on r01–r04 only)."""

import glob
import json
from pathlib import Path

import pytest

from horovod_tpu.analysis import cost_model as CM
from horovod_tpu.utils import hlo as H

REPO = Path(__file__).resolve().parent.parent

DOT_LINE = ("  %dot.1 = f32[6,1024,32000]{2,1,0} "
            "dot(f32[6,1024,2048]{2,1,0} %x, f32[2048,32000]{1,0} %w), "
            "lhs_contracting_dims={2}, rhs_contracting_dims={0}")
CONV_LINE = ("  %conv = f32[128,112,112,64]{3,2,1,0} "
             "convolution(f32[128,224,224,3]{3,2,1,0} %x, "
             "f32[7,7,3,64]{3,2,1,0} %k), "
             "window={size=7x7 stride=2x2 pad=3_3x3_3}, "
             "dim_labels=b01f_01io->b01f")

FUSION_MODULE = """\
%fused_computation.1 (p0: f32[4,8], p1: f32[8,2]) -> f32[4,2] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %p1 = f32[8,2]{1,0} parameter(1)
  ROOT %d = f32[4,2]{1,0} dot(f32[4,8]{1,0} %p0, f32[8,2]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

ENTRY %main (a: f32[4,8], b: f32[8,2]) -> f32[4,2] {
  %a = f32[4,8]{1,0} parameter(0)
  %b = f32[8,2]{1,0} parameter(1)
  ROOT %fusion = f32[4,2]{1,0} fusion(f32[4,8]{1,0} %a, f32[8,2]{1,0} %b), kind=kOutput, calls=%fused_computation.1
}
"""


class TestFlopCounting:
    def test_dot_flops(self):
        """2 · |result| · K: batch dims ride the result product, K from
        lhs_contracting_dims against the lhs operand type."""
        [(name, kind, flops)] = H.op_flops(DOT_LINE)
        assert (name, kind) == ("%dot.1", "dot")
        assert flops == 2 * 6 * 1024 * 32000 * 2048
        assert H.module_flops(DOT_LINE) == flops

    def test_convolution_flops(self):
        """2 · |result| · kernel-window (spatial × input features; the
        o dim of dim_labels' kernel segment indexes outputs and is
        excluded)."""
        [(name, kind, flops)] = H.op_flops(CONV_LINE)
        assert (name, kind) == ("%conv", "convolution")
        assert flops == 2 * (128 * 112 * 112 * 64) * (7 * 7 * 3)

    def test_fusion_body_counted_once(self):
        """Fusion bodies are separate computations in the same dump:
        the inner dot counts at its definition, the fusion() op line
        itself contributes nothing — no double counting."""
        assert H.module_flops(FUSION_MODULE) == 2 * 4 * 2 * 8

    def test_untyped_operands_are_skipped_not_guessed(self):
        bare = ("  %d = f32[4,2]{1,0} dot(%a, %b), "
                "lhs_contracting_dims={1}, rhs_contracting_dims={0}")
        assert H.op_flops(bare) == []

    def test_elementwise_and_collectives_ignored(self):
        text = "\n".join([
            "  %add = f32[1024]{0} add(f32[1024]{0} %a, f32[1024]{0} %b)",
            "  %ar = f32[1024]{0} all-reduce(%g), "
            "replica_groups=[1,8]<=[8], to_apply=%sum",
        ])
        assert H.module_flops(text) == 0


class TestBufferAccounting:
    def test_result_bytes_tuple_and_async_variants(self):
        """Tuple results sum their elements — including the
        tuple-wrapped async-start variants PR 6 hardened the collective
        parser against; for *memory* accounting the u32[] context
        scalar is 4 real bytes, not payload noise."""
        assert H.result_bytes("f32[104]{0}") == 416
        assert H.result_bytes("(f32[104]{0}, f32[13]{0})") == 416 + 52
        assert H.result_bytes("((f32[104]{0}, f32[13]{0}), u32[])") \
            == 416 + 52 + 4
        # the WIRE parser still strips the context scalar (PR 6)
        line = ("  %rs = ((f32[104]{0}, f32[13]{0}), u32[]) "
                "reduce-scatter-start(%x), replica_groups=[1,4]<=[8], "
                "dimensions={0}, to_apply=%add")
        [op] = H.collective_ops(line)
        assert op.bytes == 52

    def test_memory_high_water_linear_scan(self):
        """a (128B) and b (64B) are live until the fusion line; the
        fusion result (32B) allocates on the same line — peak = all
        three."""
        assert H.memory_high_water(FUSION_MODULE) == 128 + 64 + 32

    def test_memory_high_water_frees_after_last_use(self):
        text = """\
ENTRY %main (p: f32[256]) -> f32[64] {
  %p = f32[256]{0} parameter(0)
  %t1 = f32[256]{0} negate(f32[256]{0} %p)
  %t2 = f32[64]{0} slice(f32[256]{0} %t1), slice={[0:64]}
  ROOT %out = f32[64]{0} negate(f32[64]{0} %t2)
}
"""
        # p dies at %t1 (line idx 2): peak is p+t1 = 2048 at that line,
        # then t1 (1024) + t2 (256) = 1280, then t2+out = 512
        assert H.memory_high_water(text) == 1024 + 1024

    def test_fusion_bodies_do_not_double_book(self):
        """ENTRY-scope only: the fused computation's internal buffers
        never materialize, so the estimate excludes them."""
        live_names = {n for n, _, _, _ in
                      H.buffer_liveness(FUSION_MODULE)}
        assert live_names == {"%a", "%b", "%fusion"}

    def test_no_entry_marker_falls_back_to_whole_text(self):
        text = "  %p = f32[256]{0} parameter(0)"
        assert H.memory_high_water(text) == 1024


# a donated train step's module shape, as jit emits it: the alias map
# rides the HloModule header line, ENTRY params are %Arg_N, and a
# fusion body contributes its own parameter(0/1) lines that the
# donation parser must NOT pick up (they'd shadow the ENTRY sizes)
DONATED_MODULE = """\
HloModule jit_step, is_scheduled=true, \
input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {}, may-alias) }

%fused_update (p0: f32[256], p1: f32[256]) -> f32[256] {
  %param_0.1 = f32[256]{0} parameter(0)
  %param_1.2 = f32[256]{0} parameter(1)
  ROOT %a = f32[256]{0} add(f32[256]{0} %param_0.1, f32[256]{0} %param_1.2)
}

ENTRY %main (Arg_0.1: f32[256], Arg_1.2: f32[256], Arg_2.3: f32[64]) -> (f32[256], f32[256]) {
  %Arg_0.1 = f32[256]{0} parameter(0)
  %Arg_1.2 = f32[256]{0} parameter(1)
  %Arg_2.3 = f32[64]{0} parameter(2)
  %upd = f32[256]{0} fusion(f32[256]{0} %Arg_0.1, f32[256]{0} %Arg_1.2), kind=kLoop, calls=%fused_update
  ROOT %out = (f32[256]{0}, f32[256]{0}) tuple(f32[256]{0} %upd, f32[256]{0} %Arg_1.2)
}
"""


class TestDonationAccounting:
    def test_donated_param_bytes_reads_the_alias_header(self):
        """Params 0 and 1 (1024 B each) are donated; param 2 is not."""
        assert H.donated_param_bytes(DONATED_MODULE) == 2048

    def test_donated_sizes_scope_to_entry_not_fusion_bodies(self):
        """A fusion body whose parameter(0) is a different size from
        ENTRY's must not shadow it: shrink the body params to f32[4]
        and the donated total must still be the ENTRY 2048."""
        text = DONATED_MODULE.replace(
            "%param_0.1 = f32[256]{0}", "%param_0.1 = f32[4]{0}").replace(
            "%param_1.2 = f32[256]{0}", "%param_1.2 = f32[4]{0}").replace(
            "(p0: f32[256], p1: f32[256])", "(p0: f32[4], p1: f32[4])")
        assert H.donated_param_bytes(text) == 2048

    def test_high_water_credits_donation_at_the_root(self):
        """Without the alias header the scan books params AND the ROOT
        result at the update point — donated steps double-count exactly
        params+opt_state.  With it, the ROOT alloc is reduced by the
        donated bytes (clamped at zero) and the peak drops.

        Plain: peak is the ROOT line — Arg_1 (1024) + upd (1024) +
        out (2048) = 4096.  Donated: the 2048 B out is fully credited
        (2048 donated), the peak moves to the fusion line — Arg_0 +
        Arg_1 + upd = 3072."""
        undonated = "\n".join(
            ln for ln in DONATED_MODULE.splitlines()
            if "input_output_alias" not in ln)
        assert H.memory_high_water(undonated) == 4096
        assert H.memory_high_water(DONATED_MODULE) == 3072

    def test_wrapped_alias_attribute_counts_every_entry(self):
        """A dump that wraps the alias list across lines (long module
        headers do) must still count every donated entry — the capture
        runs to the balanced closing brace, not end-of-line."""
        wrapped = DONATED_MODULE.replace(
            "may-alias), {1}:", "may-alias),\n  {1}:")
        assert H.donated_param_bytes(wrapped) == 2048
        assert H.memory_high_water(wrapped) == 3072

    def test_missing_alias_header_is_a_no_op(self):
        assert H.donated_param_bytes(FUSION_MODULE) == 0

    def test_buffer_liveness_is_untouched_by_donation(self):
        """Donation is a memory_high_water credit only — the liveness
        list (names, sizes, lifetimes) must be identical with and
        without the header, so every other consumer is unaffected."""
        undonated = "\n".join(
            ln for ln in DONATED_MODULE.splitlines()
            if "input_output_alias" not in ln)
        assert H.buffer_liveness(DONATED_MODULE) == \
            H.buffer_liveness(undonated)


class TestWireAttribution:
    RS_ICI = ("  %rs = f32[13]{0} reduce-scatter(%x), "
              "replica_groups=[2,4]<=[8], dimensions={0}, to_apply=%add")
    RS_DCN = ("  %rs2 = s8[13]{0} reduce-scatter(%y), "
              "replica_groups=[4,2]<=[8]T(1,0), dimensions={0}, "
              "to_apply=%add")

    def test_levels_split_by_group_size(self):
        ops = H.collective_ops(self.RS_ICI + "\n" + self.RS_DCN)
        levels = CM.collective_wire_by_level(ops, n_dcn=2, n_ici=4)
        # ici RS: group 4, result 52B -> (4-1)*52; dcn RS: group 2,
        # result 13B (s8) -> (2-1)*13
        assert levels["ici"] == pytest.approx(3 * 52)
        assert levels["dcn"] == pytest.approx(1 * 13)

    def test_single_slice_mesh_attributes_everything_to_ici(self):
        ops = H.collective_ops(self.RS_DCN)
        levels = CM.collective_wire_by_level(ops, n_dcn=1, n_ici=8)
        assert levels["dcn"] == 0.0
        assert levels["ici"] > 0.0

    def test_module_cost_composes(self):
        text = FUSION_MODULE + "\n" + self.RS_ICI
        cost = CM.module_cost(text, n_dcn=2, n_ici=4)
        assert cost.flops == 2 * 4 * 2 * 8
        assert cost.wire_bytes["ici"] == pytest.approx(3 * 52)
        assert cost.memory_high_water_bytes >= 128 + 64 + 32
        assert cost.predicted_step_time_s() > 0


class TestExchangeWireBytes:
    B = 3.484e9     # flagship gradient payload

    def test_flat_single_fabric_matches_ring_bound(self):
        wb = CM.exchange_wire_bytes(self.B, n_dcn=1, n_ici=64)
        assert wb.ici == pytest.approx(2 * 63 / 64 * self.B)
        assert wb.dcn == 0.0

    def test_two_level_int8_dcn_shrinks_the_cross_hop(self):
        """The satellite's correction: a 16×4 v5e-64 two-level int8
        exchange crosses DCN with B/n_ici at 1/4 width — 16× less than
        the flat fp32 model claimed."""
        flat = CM.exchange_wire_bytes(self.B, n_dcn=16, n_ici=4,
                                      hierarchy="flat")
        two = CM.exchange_wire_bytes(self.B, n_dcn=16, n_ici=4,
                                     hierarchy="two_level")
        assert two.ici == flat.ici          # intra phase identical
        assert two.dcn == pytest.approx(flat.dcn / 16)
        assert two.total < flat.total

    def test_degenerate_extents_cost_nothing(self):
        assert CM.exchange_wire_bytes(self.B, 1, 1).total == 0.0

    def test_bad_hierarchy_rejected(self):
        with pytest.raises(ValueError, match="hierarchy"):
            CM.exchange_wire_bytes(self.B, 2, 4, hierarchy="auto")


class TestCalibratedRoofline:
    def _trajectory(self):
        paths = sorted(glob.glob(str(
            REPO / "tests/fixtures/gate_trajectory/gate_input_r0*.json")))
        assert len(paths) >= 5, "trajectory fixture missing"
        return paths

    def test_rooflines_bind_on_the_right_ceiling(self):
        """ResNet-50 is HBM-bound on v5e (~4,100 img/s ceiling, the
        PERF_NOTES envelope), the 870.9M transformer compute-bound
        (~36,300 tok/s) — a FLOPs-only model would be 4x off for
        resnet."""
        r = CM.roofline_rate(CM.resnet_workload())
        assert 3800 < r < 4400
        t = CM.roofline_rate(CM.transformer_workload(params=870.9e6))
        assert 33000 < t < 40000

    def test_acceptance_predicts_bench_r05_within_25pct(self):
        """The ISSUE-7 acceptance bar, held-out: calibrate on r01–r04,
        predict r05's measured rate AND step time for both models
        within 25%."""
        paths = self._trajectory()
        cal = CM.calibrate(paths[:4])
        with open(paths[4]) as f:
            r05 = json.load(f)["parsed"]
        workloads = CM.workloads_from_artifact(r05)
        assert {w.family for w in workloads} == {"resnet",
                                                 "transformer"}
        for w in workloads:
            measured_rate = float(r05[w.rate_field])
            predicted_rate = CM.predict_rate(cal, w)
            assert predicted_rate is not None
            assert abs(predicted_rate - measured_rate) / measured_rate \
                < 0.25, (w.family, predicted_rate, measured_rate)
            measured_t = w.units_per_step / measured_rate
            predicted_t = CM.predict_step_time_s(cal, w)
            assert abs(predicted_t - measured_t) / measured_t < 0.25, \
                (w.family, predicted_t, measured_t)

    def test_calibration_is_deterministic(self):
        paths = self._trajectory()
        a, b = CM.calibrate(paths), CM.calibrate(paths)
        assert a.efficiency == b.efficiency
        assert a.samples == b.samples

    def test_latest_artifact_wins(self):
        arts = [{"metric": "resnet50_img_sec_per_chip", "value": 2000.0},
                {"metric": "resnet50_img_sec_per_chip", "value": 3000.0}]
        cal = CM.calibrate(arts)
        w = CM.resnet_workload()
        assert CM.predict_rate(cal, w) == pytest.approx(3000.0)
        assert len(cal.samples["resnet"]) == 2

    def test_unseen_family_predicts_none_never_guesses(self):
        cal = CM.calibrate([])
        assert CM.predict_rate(cal, CM.resnet_workload()) is None
        assert CM.predict_step_time_s(cal, CM.resnet_workload()) is None

    def test_multichip_stubs_contribute_nothing(self):
        paths = sorted(glob.glob(str(REPO / "MULTICHIP_r0*.json")))
        cal = CM.calibrate(paths)
        assert cal.efficiency == {}


class TestFusionPredictor:
    def test_ranks_fewer_flushes_above_per_tensor(self):
        predict = CM.make_fusion_predictor(
            payload_bytes=64 << 20, n_leaves=200, world=8)
        per_tensor = predict((0, 1.0))
        fused = predict((64 << 20, 5.0))
        assert fused > per_tensor

    def test_cycle_time_is_a_latency_penalty(self):
        predict = CM.make_fusion_predictor(
            payload_bytes=64 << 20, n_leaves=200, world=8)
        assert predict((64 << 20, 1.0)) > predict((64 << 20, 20.0))


class TestFusedExchangeCeiling:
    """Overlap-aware roofline for the tile-fused exchange (ISSUE 9):
    the model the autotuner prunes the fused_collectives axis with."""

    def test_unfused_exposes_full_wire(self):
        assert CM.fused_tail_exchange_s(0.010, 0.5,
                                                n_tiles=1) == 0.010

    def test_compute_bound_leaves_first_tile_exposed(self):
        # plenty of compute: only the first tile's share stays exposed
        got = CM.fused_tail_exchange_s(0.008, 1.0, n_tiles=4)
        assert abs(got - 0.002) < 1e-12

    def test_wire_bound_exposes_excess(self):
        # wire exceeds compute: excess + first-tile share exposed
        got = CM.fused_tail_exchange_s(0.010, 0.004, n_tiles=4)
        assert abs(got - (0.010 / 4 + 0.006)) < 1e-12

    def test_monotone_in_tiles(self):
        vals = [CM.fused_tail_exchange_s(0.01, 1.0, n_tiles=t)
                for t in (1, 2, 4, 8)]
        assert vals == sorted(vals, reverse=True)
        assert all(v >= 0 for v in vals)

    def test_zero_wire(self):
        assert CM.fused_tail_exchange_s(0.0, 1.0) == 0.0


class TestScoreExchangeSchedule:
    def test_none_without_exchange_knobs(self):
        assert CM.score_exchange_schedule(
            {"steps_per_call": 10}, 1e8) is None

    def test_fused_scores_at_least_unfused(self):
        on = CM.score_exchange_schedule(
            {"hierarchy": "flat", "fused_collectives": "on"},
            1e9, n_dcn=2, n_ici=4, compute_s=1.0)
        off = CM.score_exchange_schedule(
            {"hierarchy": "flat", "fused_collectives": "off"},
            1e9, n_dcn=2, n_ici=4, compute_s=1.0)
        assert on > off            # less exposed wire = higher score

    def test_two_level_beats_flat_on_factored_mesh(self):
        two = CM.score_exchange_schedule(
            {"hierarchy": "two_level", "fused_collectives": "off"},
            1e9, n_dcn=2, n_ici=4)
        flat = CM.score_exchange_schedule(
            {"hierarchy": "flat", "fused_collectives": "off"},
            1e9, n_dcn=2, n_ici=4)
        assert two > flat          # 1/n_ici int8 DCN hop wins

    def test_non_exchange_axis_scores_constant(self):
        a = CM.score_exchange_schedule(
            {"hierarchy": "flat", "fused_collectives": "off",
             "steps_per_call": 1}, 1e8, n_dcn=2, n_ici=4)
        b = CM.score_exchange_schedule(
            {"hierarchy": "flat", "fused_collectives": "off",
             "steps_per_call": 40}, 1e8, n_dcn=2, n_ici=4)
        assert a == b

    def test_wire_dtype_narrow_scores_at_least_fp32(self):
        """The codec-width axis ranks: fewer wire bits, less serial
        exchange time, higher score (int8 and fp8 tie — both 8-bit)."""
        def score(wd):
            return CM.score_exchange_schedule(
                {"hierarchy": "flat", "wire_dtype": wd}, 1e9,
                n_dcn=2, n_ici=4)

        assert score("int8") > score("fp32")
        assert score("fp8_e4m3") > score("fp32")
        assert score("int8") == score("fp8_e4m3")


class TestParsePlan:
    """The analysis-layer mirror of ``ShardingPlan.from_string``
    (ISSUE 13): a stdlib parser so the cost model prices plan strings
    without importing the jax-facing parallel package."""

    def test_full_extent_dict(self):
        ext = CM.parse_plan("dp=2,tp=4")
        assert ext["dp"] == 2 and ext["tp"] == 4
        # absent axes fill at 1, every grammar key present
        assert ext["pp"] == ext["fsdp"] == ext["ep"] == ext["sp"] \
            == ext["v"] == 1

    def test_dict_passthrough_and_unresolved_dp(self):
        assert CM.parse_plan({"dp": 4, "pp": 2})["pp"] == 2
        assert CM.parse_plan("dp=?,tp=8")["dp"] == 1   # prices as dp=1

    def test_errors(self):
        with pytest.raises(ValueError, match="bad plan term"):
            CM.parse_plan("dp:2")
        with pytest.raises(ValueError, match="bad plan term"):
            CM.parse_plan("zz=2")
        with pytest.raises(ValueError, match="duplicate plan axis"):
            CM.parse_plan("dp=2,dp=4")
        with pytest.raises(ValueError, match=">= 1"):
            CM.parse_plan("dp=0")

    def test_bubble_matches_pipeline_module(self):
        """One formula, two layers: the analysis mirror agrees with
        ``parallel/pipeline.bubble_fraction`` everywhere it's used."""
        from horovod_tpu.parallel import bubble_fraction

        for s, m, v in [(4, 8, 1), (4, 8, 2), (8, 16, 4), (2, 4, 1)]:
            assert CM.pipeline_bubble_fraction(s, m, v) == \
                pytest.approx(bubble_fraction(s, m, virtual_stages=v))


class TestPlanCost:
    """Plan-space pricing (ISSUE 13 tentpole): the cost model ranks
    parallelism plans so the autotuner prunes the plan axis, and the
    interleaved-1F1B acceptance pin reads off the bubble term."""

    def test_1f1b_beats_gpipe_in_cost_model(self):
        """Acceptance pin: same plan with v=2 virtual stages predicts
        strictly less step time than the v=1 (GPipe) schedule whenever
        compute dominates — the bubble shrinks (s-1)/(m+s-1) ->
        (s-1)/(v*m+s-1) and nothing else changes."""
        kw = dict(payload_bytes=1e9, n_dcn=2, n_ici=4, compute_s=1.0)
        assert CM.plan_cost_s("dp=2,pp=2,v=2", **kw) < \
            CM.plan_cost_s("dp=2,pp=2", **kw)
        one = CM.score_exchange_schedule(
            {"plan": "dp=2,pp=2"}, 1e9, n_dcn=2, n_ici=4, compute_s=1.0)
        two = CM.score_exchange_schedule(
            {"plan": "dp=2,pp=2,v=2"}, 1e9, n_dcn=2, n_ici=4,
            compute_s=1.0)
        assert two > one

    def test_model_axes_shrink_the_exchange(self):
        """tp shards the parameters, so each data replica exchanges
        1/tp of the payload — a dp=2,tp=4 plan prices below pure
        dp=8 on the same single-slice fabric (on the 2x4 fabric the
        two plans coincidentally tie: dp=8's two-level 1/n_ici DCN
        codec saves exactly what tp=4's payload shrink saves)."""
        kw = dict(payload_bytes=1e9, n_dcn=1, n_ici=8)
        assert CM.plan_cost_s("dp=2,tp=4", **kw) < \
            CM.plan_cost_s("dp=8", **kw)

    def test_plan_wire_bytes_follow_axis_order(self):
        """dp absorbs the DCN extent first (AXIS_ORDER DCN-outer):
        dp=2,fsdp=4 on a 2x4 fabric goes two-level with the 1/n_ici
        DCN hop; dp=8 on one slice (n_dcn=1) stays flat with zero
        DCN bytes."""
        two = CM.plan_exchange_wire_bytes("dp=2,fsdp=4", 1e9,
                                          n_dcn=2, n_ici=4)
        assert two.dcn > 0 and two.ici > 0
        flat = CM.plan_exchange_wire_bytes("dp=8", 1e9, n_dcn=1,
                                           n_ici=8)
        assert flat.dcn == 0

    def test_pp_only_plan_still_scores(self):
        """A pipeline-only plan has no gradient exchange to price but
        the bubble term still ranks it — score is not None."""
        s = CM.score_exchange_schedule({"plan": "pp=4"}, 1e9,
                                       compute_s=1.0)
        assert s is not None and s < 0


class TestMoePricing:
    """MoE expert-dispatch pricing (ISSUE 16): wire volume is
    schedule-invariant, only the exposure moves; the routing-axis
    scorer obeys the predict contract."""

    def test_capacity_mirrors_expert_module(self):
        # parallel/expert.py: capacity = max(1, ceil(cf * tokens / E))
        assert CM.moe_capacity(512, 8, 1.25) == 80
        assert CM.moe_capacity(13, 8, 1.25) == 3
        assert CM.moe_capacity(1, 64, 0.5) == 1      # floor at 1

    def test_wire_volume_schedule_invariant(self):
        """Fused ring and boundary-wide all_to_all move the same
        bytes: 2·(ep−1)·(E/ep)·C·d·elem — the gauge is honest for
        both schedules; ep=1 prices zero (local experts)."""
        w = CM.moe_dispatch_wire_bytes(512, 1024, 64, 8,
                                       capacity_factor=1.25)
        cap = CM.moe_capacity(512, 64, 1.25)
        assert w == 2.0 * 7 * (64 // 8) * cap * 1024 * 4.0
        assert CM.moe_dispatch_wire_bytes(512, 1024, 64, 1) == 0.0

    def test_fused_exposure_at_most_unfused(self):
        wire_s, compute_s = 1e-3, 2e-3
        fused = CM.moe_dispatch_exposed_s(wire_s, compute_s, ep=8,
                                          fused=True)
        unfused = CM.moe_dispatch_exposed_s(wire_s, compute_s, ep=8,
                                            fused=False)
        assert fused <= unfused
        assert unfused == wire_s
        # compute-rich: only the first tile's share stays exposed
        assert fused == pytest.approx(wire_s / 8)

    def test_score_none_without_routing_knob(self):
        """The predict contract: a point with no knob the model can
        price must score None (never narrow the grid)."""
        assert CM.score_moe_schedule(
            {"steps_per_call": 10}, tokens=512, d_model=1024,
            d_ff=4096, num_experts=8) is None

    def test_capacity_factor_axis_ranks(self):
        """Lower cf -> smaller capacity bucket -> less expert compute
        and wire -> higher (less negative) score."""
        def score(cf):
            return CM.score_moe_schedule(
                {"capacity_factor": cf}, tokens=512, d_model=1024,
                d_ff=4096, num_experts=8, ep=8)

        assert score(0.5) > score(1.25) > score(2.0)

    def test_cf_composes_with_tokens_per_expert(self):
        """When BOTH knobs land in one sample point the cf axis must
        still rank (capacity = ceil(cf·tpe)) — a flat cf scan would
        prune nothing."""
        def score(cf):
            return CM.score_moe_schedule(
                {"capacity_factor": cf, "tokens_per_expert": 64},
                tokens=512, d_model=1024, d_ff=4096, num_experts=8,
                ep=8)

        assert score(0.5) > score(1.0) > score(2.0)


class TestMoeMemoryPlane:
    """Expert-parameter and capacity-buffer components of
    plan_memory_bytes (ISSUE 16): ep shards the expert weights, the
    dispatch buckets are ep-invariant, and a multi-billion-parameter
    Switch twin certifies under a per-chip HBM budget."""

    def test_components_default_to_zero(self):
        mb = CM.plan_memory_bytes("dp=8", param_bytes=1e9,
                                  activation_bytes=1e8)
        assert mb.expert_params == 0.0 and mb.moe_buffers == 0.0

    def test_expert_params_shard_and_fold_into_grads_optimizer(self):
        dense = CM.plan_memory_bytes(
            "dp=2,ep=4", param_bytes=8e9, activation_bytes=1e8)
        moe = CM.plan_memory_bytes(
            "dp=2,ep=4", param_bytes=8e9, activation_bytes=1e8,
            expert_param_bytes=4e9, moe_capacity_buffer_bytes=5e7)
        # expert weights divide over the ep extent
        assert moe.expert_params == 4e9 / 4
        # their grads + optimizer slots ride the same components
        assert moe.grads == dense.grads + 1e9
        assert moe.optimizer == dense.optimizer + 2 * 1e9
        # the (E, C, d) buckets are per-device as-is
        assert moe.moe_buffers == 5e7
        assert moe.total > dense.total

    def test_switch_twin_certified_under_hbm_budget(self):
        """The tentpole's training claim, priced statically: a
        Switch-style twin with 8.6B expert + 1.6B dense params (bf16)
        trains under a 16 GB/chip budget on a dp=2,fsdp=2,ep=8,tp=2
        plan with the ZeRO exchange — and the certificate is the
        expert-aware components (the same budget refuses when ep
        cannot shard the experts)."""
        # 16 MoE layers x 64 experts x 2 matmuls x 4096 x 8192, bf16
        expert_bytes = 16 * 64 * 2 * 4096 * 8192 * 2.0   # ~137e9... scaled below
        expert_bytes = expert_bytes / 16                  # 8.6e9
        dense_bytes = 1.6e9 * 2.0
        cap = CM.moe_capacity(8192, 64, 1.25)
        buffers = 2 * 64 * cap * 4096 * 2.0
        kw = dict(param_bytes=dense_bytes, activation_bytes=4e9,
                  remat_policy="full", shard_optimizer_states=True,
                  expert_param_bytes=expert_bytes,
                  moe_capacity_buffer_bytes=buffers)
        mb = CM.plan_memory_bytes("dp=2,fsdp=2,ep=8,tp=2", **kw)
        budget = 16e9
        assert mb.expert_params > 0 and mb.moe_buffers > 0
        assert CM.plan_fits(mb, budget), mb
        # without the ep extent the expert shard alone blows the
        # budget: the certificate genuinely prices the expert plane
        flat = CM.plan_memory_bytes("dp=16,tp=2", **kw)
        assert not CM.plan_fits(flat, budget), flat
        # deterministic: the certificate is pure arithmetic
        assert CM.plan_memory_bytes(
            "dp=2,fsdp=2,ep=8,tp=2", **kw) == mb


class TestSpRingPricing:
    """Sequence-parallel pricing (ISSUE 17): the K/V ring wire gauge,
    the 1/sp attention compute split, the fused-vs-unfused exposure,
    and the 1/sp activation scaling the --sp-budget certification
    leans on."""

    def test_wire_volume_is_ring_exact(self):
        # 2 tensors (K and V) x (sp-1) hops x b·t_local·h·d fp32
        got = CM.sp_ring_wire_bytes(512, 8, 64, sp=4, batch=2)
        assert got == 2 * 3 * 2 * 512 * 8 * 64 * 4.0
        assert CM.sp_ring_wire_bytes(512, 8, 64, sp=1) == 0.0

    def test_wire_volume_is_schedule_invariant(self):
        # fusion changes the exposure, never the bytes — the same
        # gauge prices the fused and jnp rings
        assert CM.sp_ring_wire_bytes(128, 4, 32, sp=8) == \
            CM.sp_ring_wire_bytes(128, 4, 32, sp=8)

    def test_attention_compute_divides_by_sp(self):
        one = CM.sp_attention_compute_s(4096, 8, 64, sp=1)
        four = CM.sp_attention_compute_s(4096, 8, 64, sp=4)
        assert one == pytest.approx(4 * four)

    def test_causal_halves_the_flops(self):
        full = CM.sp_attention_compute_s(4096, 8, 64, sp=2)
        causal = CM.sp_attention_compute_s(4096, 8, 64, sp=2,
                                           causal=True)
        assert causal == pytest.approx(full / 2)

    def test_fused_exposure_at_most_unfused(self):
        wire, compute = 1e-3, 5e-3
        fused = CM.sp_ring_exposed_s(wire, compute, sp=4, fused=True)
        unfused = CM.sp_ring_exposed_s(wire, compute, sp=4, fused=False)
        assert unfused == pytest.approx(wire)
        assert 0.0 <= fused < unfused

    def test_score_prices_the_sp_ring(self):
        """An sp plan with attention pricing scores strictly below the
        same-wire dp plan (the ring costs something), and the fused
        point at least matches the unfused one."""
        kw = dict(payload_bytes=1e6, n_ici=8, compute_s=1e-3,
                  sp_attn_wire_s=2e-3, sp_attn_compute_s=8e-3)
        dp = CM.score_exchange_schedule({"plan": "dp=8"}, **kw)
        sp_off = CM.score_exchange_schedule(
            {"plan": "dp=4,sp=2", "fused_collectives": "off"}, **kw)
        sp_on = CM.score_exchange_schedule(
            {"plan": "dp=4,sp=2", "fused_collectives": "on"}, **kw)
        assert sp_off < dp
        assert sp_on >= sp_off

    def test_plan_memory_activations_divide_by_sp(self):
        m1 = CM.plan_memory_bytes("dp=2", param_bytes=1e6,
                                  activation_bytes=8e6)
        m2 = CM.plan_memory_bytes("dp=2,sp=2", param_bytes=1e6,
                                  activation_bytes=8e6)
        m4 = CM.plan_memory_bytes("dp=2,sp=4", param_bytes=1e6,
                                  activation_bytes=8e6)
        assert m2.activations == pytest.approx(m1.activations / 2)
        assert m4.activations == pytest.approx(m1.activations / 4)
        # sp replicates parameters — only activations shrink
        assert m2.params == m1.params
        assert m2.grads == m1.grads

    def test_sp_budget_separates_the_plans(self):
        """The --sp-budget shape: a budget between the two footprints
        admits the sp=2 plan and refuses sp=1."""
        kw = dict(param_bytes=1e6, activation_bytes=64e6)
        m1 = CM.plan_memory_bytes("dp=4", **kw)
        m2 = CM.plan_memory_bytes("dp=2,sp=2", **kw)
        budget = (m1.total + m2.total) / 2
        assert CM.plan_fits(m2, budget)
        assert not CM.plan_fits(m1, budget)
