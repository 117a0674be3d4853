"""bench.py measurement-layer unit tests.

Pins the trailing-window anomaly handling in ``median_rate`` (a final
timed iteration collapsing because deferred teardown work drained at
the final timed fence — ROADMAP S9): a
sole final-iteration collapse is drained and re-measured once; genuine
slowdowns and mid-run outliers are never rewritten.
"""

import time

import pytest

import bench


def make_step(durations):
    """step_fn whose i-th call sleeps durations[i] (0 when exhausted) —
    the timed wall-clock is fully scripted."""
    it = iter(durations)

    def step(state):
        time.sleep(next(it, 0.0))
        return (0.5,)

    return step


# long enough that a loaded host's 2-3 ms of sleep overshoot stays well
# inside median_rate's 20% outlier band (10 ms steps read 12 under xdist)
FAST, SLOW = 0.03, 0.36


def run(durations, iters=4):
    rate, _warmup_s, _state = bench.median_rate(
        make_step(durations), (0.5,),
        warmup_batches=1, iters=iters,
        batches_per_iter=1, units_per_batch=1.0,
        label="test")
    return rate


class TestTrailingCollapse:
    def test_sole_final_outlier_is_remeasured(self, capsys):
        # warmup + 3 fast iters + 1 collapsed final; the drain and the
        # re-measure both come back fast -> the collapse was teardown
        # cost, the final rate is substituted and no warning fires
        rate = run([0.0, FAST, FAST, FAST, SLOW, FAST, FAST])
        assert rate == pytest.approx(1.0 / FAST, rel=0.5)
        err = capsys.readouterr().err
        assert "substituting" in err
        assert "WARNING" not in err

    def test_reproduced_slow_final_is_kept(self, capsys):
        # the re-measure is just as slow -> a genuine trend, original
        # rate stays and the deviation warning still fires
        run([0.0, FAST, FAST, FAST, SLOW, SLOW, SLOW])
        err = capsys.readouterr().err
        assert "keeping the original" in err
        assert "WARNING" in err

    def test_mid_run_outlier_untouched(self, capsys):
        # an outlier that is NOT the final window gets no re-measure
        # (nothing to drain mid-run; it warns like before)
        run([0.0, FAST, SLOW, FAST, FAST])
        err = capsys.readouterr().err
        assert "re-measure" not in err
        assert "WARNING" in err

    def test_fast_final_outlier_untouched(self, capsys):
        # only LOW final outliers are teardown-shaped; an anomalously
        # fast final window is left alone
        run([0.0, SLOW, SLOW, SLOW, FAST])
        err = capsys.readouterr().err
        assert "re-measure" not in err

    def test_clean_run_is_untouched(self, capsys):
        rate = run([0.0, FAST, FAST, FAST, FAST])
        assert rate == pytest.approx(1.0 / FAST, rel=0.5)
        err = capsys.readouterr().err
        assert "re-measure" not in err and "WARNING" not in err

    def test_two_iter_runs_skip_the_heuristic(self, capsys):
        # <3 samples can't distinguish an outlier from a trend
        run([0.0, FAST, SLOW], iters=2)
        assert "re-measure" not in capsys.readouterr().err


class TestWarmupAndState:
    def test_warmup_time_and_final_state_returned(self):
        rate, warmup_s, state = bench.median_rate(
            make_step([SLOW, FAST, FAST]), (0.5,),
            warmup_batches=1, iters=2, batches_per_iter=1,
            units_per_batch=1.0, label="test")
        assert warmup_s >= SLOW          # warmup window was timed
        assert state == (0.5,)           # live post-loop state comes back

    def test_no_warmup_reports_zero(self):
        _rate, warmup_s, _state = bench.median_rate(
            make_step([FAST, FAST]), (0.5,),
            warmup_batches=0, iters=2, batches_per_iter=1,
            units_per_batch=1.0, label="test")
        assert warmup_s == 0.0

    def test_on_warmup_end_fires_between_warmup_and_timing(self):
        """The input-pipeline stall snapshot hook: exactly once, after
        the warmup fence, before the first timed step."""
        calls = []
        seen = []

        def step(state):
            seen.append(len(calls))
            return (0.5,)

        bench.median_rate(
            step, (0.5,), warmup_batches=2, iters=2,
            batches_per_iter=1, units_per_batch=1.0, label="test",
            on_warmup_end=lambda: calls.append(True))
        assert calls == [True]
        # 2 warmup calls saw no hook; both timed calls saw it fired
        assert seen == [0, 0, 1, 1]


class TestWarmstartFields:
    class FakeStep:
        def __init__(self, hit):
            self.compile_cache_hit = hit

    def test_cold_run(self):
        f = bench.warmstart_fields(self.FakeStep(False), 42.1, "resnet_")
        assert f == {"resnet_warmup_s": 42.1, "resnet_cache_hit": False,
                     "resnet_warmup_cached_s": None}

    def test_warm_run_reports_cached_warmup(self):
        f = bench.warmstart_fields(self.FakeStep(True), 3.2)
        assert f == {"warmup_s": 3.2, "cache_hit": True,
                     "warmup_cached_s": 3.2}


class TestJsonOut:
    def test_emit_writes_artifact(self, tmp_path, capsys):
        import json

        path = tmp_path / "BENCH.json"
        bench.emit({"metric": "x", "value": 1.5}, str(path))
        assert json.loads(path.read_text()) == {"metric": "x",
                                                "value": 1.5}
        # stdout contract unchanged: the JSON line still prints
        assert json.loads(capsys.readouterr().out.strip()) == \
            {"metric": "x", "value": 1.5}
        # no tmp droppings next to the artifact
        assert list(tmp_path.iterdir()) == [path]

    def test_emit_without_path_only_prints(self, capsys):
        bench.emit({"a": 1})
        assert "\"a\": 1" in capsys.readouterr().out


class TestPlanProbeFields:
    """``--plan`` BENCH fields (ISSUE 13): the resolved plan string and
    the pipeline schedule geometry the acceptance check reads —
    ``pipeline_bubble_1f1b < pipeline_bubble_gpipe``."""

    class FakeHvd:
        def __init__(self, n):
            self._n = n

        def size(self):
            return self._n

    @staticmethod
    def _args(plan):
        import types

        return types.SimpleNamespace(plan=plan)

    def test_no_plan_no_fields(self):
        assert bench.plan_probe_fields(self._args(None),
                                       self.FakeHvd(8)) == {}

    def test_non_pipeline_plan_emits_only_the_plan(self):
        f = bench.plan_probe_fields(self._args("tp=2"), self.FakeHvd(8))
        assert f == {"plan": "dp=4,tp=2"}   # dp resolved to 8/2

    def test_pipeline_plan_probe_geometry(self):
        f = bench.plan_probe_fields(self._args("pp=2,v=2"),
                                    self.FakeHvd(8))
        assert f["plan"] == "dp=4,pp=2,v=2"   # dp resolved to 8/2
        assert f["pipeline_stages"] == 2
        assert f["pipeline_virtual"] == 2
        assert f["pipeline_microbatches"] == 8
        # s=2, m=8: GPipe 9 ticks, 1F1B v=2 17 ticks over 2x the work
        assert f["pipeline_ticks_gpipe"] == 9
        assert f["pipeline_ticks_1f1b"] == 17
        # the acceptance inequality, straight off the artifact fields
        assert f["pipeline_bubble_1f1b"] < f["pipeline_bubble_gpipe"]

    def test_probe_depth_rounds_up_to_stage_multiple(self):
        f = bench.plan_probe_fields(self._args("dp=1,pp=3,fsdp=2"),
                                    self.FakeHvd(6))
        assert f["pipeline_microbatches"] % 3 == 0

    def test_plan_axis_values_enumerate_data_factorizations(self):
        assert bench._plan_axis_values(8) == \
            ["dp=8", "dp=4,fsdp=2", "dp=2,fsdp=4", "dp=1,fsdp=8"]
        assert bench._plan_axis_values(1) == ["dp=1"]


class TestMoeAutotune:
    """``--autotune --model moe`` (ISSUE 16): the routing axes
    (capacity_factor, tokens_per_expert) race through the coordinate
    descent with the cost-model predictor pruning, the twin probe is
    disabled inside the race, and HOROVOD_HBM_BUDGET_BYTES gates each
    candidate through the expert-aware plan_memory_bytes before it is
    allowed to measure."""

    class FakeHvd:
        def size(self):
            return 1

    @staticmethod
    def _args(tmp_path):
        import types

        return types.SimpleNamespace(
            model="moe", num_iters=5, num_batches_per_iter=5,
            num_warmup_batches=2, shard_optimizer_states=False,
            moe_experts=4, tf_seq_len=128, moe_d_model=32,
            moe_layers=2, moe_batch_size=4, plan=None,
            autotune_log=str(tmp_path / "tune.csv"))

    def _patch_run_moe(self, monkeypatch, seen):
        def fake_run_moe(a, hvd):
            assert a.moe_fused is None      # no twin probe in the race
            assert a.num_iters == 2         # short measurement windows
            seen.append((a.moe_capacity_factor, a.moe_batch_size,
                         a.steps_per_call))
            # reward high cf/tpe so any low-capacity winner below can
            # only come from the budget gate, not the measurement
            return {"moe_tokens_per_sec":
                    a.moe_capacity_factor * 1000.0
                    + a.moe_batch_size * 32.0 + a.steps_per_call}

        monkeypatch.setattr(bench, "run_moe", fake_run_moe)

    def test_routing_axes_race_and_log(self, tmp_path, monkeypatch):
        monkeypatch.delenv("HOROVOD_HBM_BUDGET_BYTES", raising=False)
        seen = []
        self._patch_run_moe(monkeypatch, seen)
        out = bench.run_autotune(self._args(tmp_path), self.FakeHvd())
        assert out["metric"] == "autotune_moe"
        assert out["unit"] == "tokens/sec/chip"
        best = out["best_point"]
        assert best["capacity_factor"] in [0.5, 1.0, 1.25, 1.5, 2.0]
        assert best["tokens_per_expert"] in [32, 64, 128]
        assert best["steps_per_call"] in [1, 5, 10, 20, 40]
        assert seen, "nothing raced"
        # tokens_per_expert reaches the measurement through the batch
        # size: tpe * E / seq with E=4, seq=128 -> tpe/32
        assert {b for _, b, _ in seen} <= {1, 2, 4}
        log = (tmp_path / "tune.csv").read_text().splitlines()
        assert len(log) >= 2                # header + samples

    def test_hbm_budget_gates_capacity(self, tmp_path, monkeypatch):
        """Budget chosen so the dispatch buffers of cap > 131 blow it:
        dense 4*(P+E) + activations = 19,005,440 fixed bytes, buffers
        2*E*cap*d*4 = 1024*cap.  The measured rate rewards HIGH
        capacity, so every point that raced being small-capacity is
        the feasibility gate at work."""
        monkeypatch.setenv("HOROVOD_HBM_BUDGET_BYTES", "19140000")
        seen = []
        self._patch_run_moe(monkeypatch, seen)
        out = bench.run_autotune(self._args(tmp_path), self.FakeHvd())
        assert seen, "nothing raced"
        for cf, batch, _spc in seen:
            tpe = batch * 32
            cap = -(-cf * tpe // 1)
            assert cap <= 131, (cf, tpe)
        best = out["best_point"]
        assert -(-best["capacity_factor"]
                 * best["tokens_per_expert"] // 1) <= 131


class TestAutotuneConsumesCalibration:
    """``--autotune`` prices its pruning predictors with the measured
    hardware model (ISSUE 18): a ``bench --calibrate`` artifact on
    ``HOROVOD_CALIBRATION_PATH`` replaces the builtin preset, the
    artifact name lands in the JSON output, and two runs over the same
    fitted model pick the same winner — calibrated pruning is
    deterministic, not a noise source."""

    def _run(self, tmp_path, monkeypatch):
        tmp_path.mkdir(parents=True, exist_ok=True)
        seen = []
        helper = TestMoeAutotune()
        helper._patch_run_moe(monkeypatch, seen)
        out = bench.run_autotune(TestMoeAutotune._args(tmp_path),
                                 TestMoeAutotune.FakeHvd())
        return out, seen

    def test_fitted_model_reaches_the_race_and_is_deterministic(
            self, tmp_path, monkeypatch):
        from horovod_tpu.analysis import calibration as CAL

        art = CAL.simulated_calibration(seed=17)
        path = tmp_path / "CALIBRATION.json"
        CAL.save_artifact(art, str(path))
        monkeypatch.setenv("HOROVOD_CALIBRATION_PATH", str(path))
        monkeypatch.delenv("HOROVOD_HW_PRESET", raising=False)
        monkeypatch.delenv("HOROVOD_HBM_BUDGET_BYTES", raising=False)

        first, seen_a = self._run(tmp_path / "a", monkeypatch)
        second, seen_b = self._run(tmp_path / "b", monkeypatch)
        # the calibrated constants — not a builtin preset — priced it
        assert first["hw_model"] == "calibrated:simulated:v5e"
        assert second["hw_model"] == first["hw_model"]
        # same fitted model, same walk, same winner
        assert seen_a == seen_b
        assert second["best_point"] == first["best_point"]

    def test_broken_calibration_path_refuses_to_race(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("HOROVOD_CALIBRATION_PATH",
                           str(tmp_path / "missing.json"))
        with pytest.raises(Exception, match="HOROVOD_CALIBRATION_PATH"):
            self._run(tmp_path, monkeypatch)


class TestSpRingBench:
    """``--plan`` dp×sp bench surface (ISSUE 17): the plan axis grows
    dp×sp factorizations only at long context, the ring twin probe
    emits the HLO007-judged structural fields plus the closed
    hvd_sp_* telemetry series, and the --sp-budget artifact certifies
    sp=2 under an HBM budget that refuses sp=1."""

    def test_plan_axis_values_gate_sp_on_seq_len(self):
        # short context: the dp×fsdp walk only
        assert all("sp=" not in p for p in bench._plan_axis_values(8))
        assert all("sp=" not in p
                   for p in bench._plan_axis_values(8, seq_len=512))
        # seq >= 4096: every dividing sp extent joins the race
        plans = bench._plan_axis_values(8, seq_len=4096)
        for want in ("dp=4,sp=2", "dp=2,sp=4", "dp=1,sp=8"):
            assert want in plans, plans
        # sp must divide both the world and the sequence
        assert all("sp=3" not in p
                   for p in bench._plan_axis_values(6, seq_len=4096))

    def test_sp_ring_twin_fields_and_lint(self):
        import types

        from horovod_tpu.analysis import hlo_lint
        from horovod_tpu.ops import pallas_kernels as PK

        fields = bench._sp_ring_twin(types.SimpleNamespace(), sp=2,
                                     heads=2, head_dim=8, seq_local=16)
        assert fields["sp_fused_collectives"] == "on"
        # the structural triple HLO007 judges — clean by construction
        assert fields["sp_serial_tail_permutes"] == 0
        assert fields["sp_attention_allgathers"] == 0
        assert fields["sp_collective_permutes"] >= 2
        # launch census comes straight from ring_step_schedule
        sched = PK.ring_step_schedule(2, causal=True,
                                      layout=fields["sp_layout"])
        assert fields["sp_ring_steps"] == sched["launches"]
        assert fields["sp_skipped_ring_steps"] == sched["skipped"]
        assert fields["sp_tail_s"] >= 0.0
        assert fields["sp_ring_wire_bytes"] > 0
        # the artifact the twin stamps passes the lint rule it feeds
        art = dict(fields, sp=2)
        assert [f.rule for f in hlo_lint.lint_artifact(art)
                if f.rule == "HLO007"] == []

    def test_sp_ring_twin_zigzag_layout_census(self, monkeypatch):
        import types

        monkeypatch.setenv("HOROVOD_SP_LAYOUT", "zigzag")
        fields = bench._sp_ring_twin(types.SimpleNamespace(), sp=2,
                                     heads=2, head_dim=8, seq_local=16)
        assert fields["sp_layout"] == "zigzag"
        # zigzag never fully masks a step: all sp² launches live
        assert fields["sp_ring_steps"] == 4
        assert fields["sp_skipped_ring_steps"] == 0

    @pytest.mark.slow
    def test_sp_budget_certifies_long_context(self):
        """The seq-4096 CPU-twin certification: both twins compile
        through the blocked kernels, plan_memory_bytes' 1/sp scaling
        lands within the 25% bar, and the midpoint budget admits
        dp=4,sp=2 while refusing dp=8."""
        import types

        import horovod_tpu as hvd

        hvd.init()
        try:
            out = bench.run_sp_budget(
                types.SimpleNamespace(tf_seq_len=4096), hvd)
        finally:
            hvd.shutdown()
        assert out["sp_budget_certified_plan"] == "dp=4,sp=2"
        assert out["sp_budget_refused_plan"] == "dp=8"
        assert out["sp_plan_memory_rel_err"] <= 0.25
        assert out["sp_hbm_high_water_bytes_sp2"] < \
            out["sp_hbm_high_water_bytes_sp1"]
