"""``mamba_pointwise_ms`` on a hand-made step and trace with known
answers: everything under a ``mamba`` module that is neither a
projection nor one of the scan's Mosaic calls — XLA's fusions (a program
without the mixer's kernels) and the kernels' calls alike, forward,
recomputation and backward."""

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import cells, modules, tables, trace  # noqa: E402
from benchmark.metrics import mamba_pointwise_ms, ssm_ms  # noqa: E402

STEP = """\
HloModule jit_step

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %m = f32[8] multiply(%p, %p), metadata={op_name="jit(step)/layer_1/mamba/ssd/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %fusion.1 = f32[8] fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(HybridLM)/layer_1/mamba/in_proj/dot_general"}
  %fusion.2 = f32[8] fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(HybridLM)/layer_1/mamba/conv/mul"}
  %mamba_conv_fwd.3 = f32[8] custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(HybridLM)/layer_3/mamba/conv/mamba_conv_fwd/pallas_call"}
  %fusion.4 = f32[8] fusion(%fusion.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(HybridLM)/layer_1/mamba/ssd/zgcrs,ls->zgcrl/dot_general"}
  %ssd_fwd.5 = f32[8] custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(HybridLM)/layer_1/mamba/ssd/ssd_fwd/pallas_call"}
  %ssd_bwd.6 = f32[8] custom-call(%ssd_fwd.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(HybridLM))/jvp(HybridLM)/checkpoint/layer_1/mamba/ssd/ssd_bwd/pallas_call"}
  %add_any.7 = f32[8] add(%ssd_bwd.6, %ssd_bwd.6), metadata={op_name="jit(step)/transpose(jvp(HybridLM))/jvp(HybridLM)/checkpoint/layer_1/mamba/ssd/add_any"}
  %mamba_gated_norm_bwd.8 = f32[8] custom-call(%add_any.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(HybridLM))/jvp(HybridLM)/checkpoint/layer_3/mamba/gated_norm/mamba_gated_norm_bwd/pallas_call"}
  %fusion.9 = f32[8] fusion(%mamba_gated_norm_bwd.8), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(HybridLM))/layer_1/mamba/out_proj/dot_general"}
  ROOT %custom-call.10 = f32[8] custom-call(%fusion.9), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/layer_8/attn/pallas_call"}
}
"""


def test_everything_under_mamba_but_projections_and_the_scan_s_calls(
        monkeypatch):
    known = mamba_pointwise_ms._pointwise(STEP)
    # (and ``m``, an instruction inside the fusions: no event bears it)
    assert set(known) - {"m"} == {"fusion.2", "mamba_conv_fwd.3", "fusion.4",
                          "add_any.7", "mamba_gated_norm_bwd.8"}
    assert set(known.values()) == {("mamba", "pointwise", None)}
    dev = "/device:TPU:0"

    def op(name, start, duration):
        return (dev, trace.OPS_LINE, f"%{name} = f32[8] x()", start, duration)
    events = [op("fusion.1", 0, 9000), op("fusion.2", 10000, 700),
              op("mamba_conv_fwd.3", 11000, 440), op("fusion.4", 12000, 30),
              op("ssd_fwd.5", 13000, 1500), op("ssd_bwd.6", 15000, 1600),
              op("add_any.7", 17000, 250),
              op("mamba_gated_norm_bwd.8", 18000, 840),
              op("fusion.9", 19000, 5000), op("custom-call.10", 25000, 500),
              (dev, trace.MODULES_LINE, "jit_step", 0, 26000)]
    pointwise_ns = 700 + 440 + 30 + 250 + 840
    reduced = modules.reduce_events(events, known)
    assert reduced["module_s"]["mamba/pointwise"] \
        == pytest.approx(pointwise_ns * 1e-9)

    class Observed:
        traced_steps = 10
        hlo_text = STEP

    monkeypatch.setattr(tables, "traced_seconds",
                        lambda obs, table, group, key:
                        modules.reduce_events(events, table)
                        .get(group, {}).get(key))
    assert mamba_pointwise_ms.read(Observed()) \
        == pytest.approx(pointwise_ns * 1e-9 / 10 * 1e3)
    # a part of ssm_ms: the accepted reader holds these and the
    # projections and the scan's calls besides
    whole = modules.reduce_events(events, modules.read_step(STEP))
    assert whole["module_s"]["mamba"] == pytest.approx(
        (pointwise_ns + 9000 + 5000 + 1500 + 1600) * 1e-9)
    monkeypatch.setattr(modules, "of_run", lambda obs: whole)
    assert ssm_ms.read(Observed()) > mamba_pointwise_ms.read(Observed())
    # a step with no Mamba layer reads as nothing, and does not raise
    monkeypatch.setattr(tables, "traced_seconds",
                        lambda obs, table, group, key: None)
    assert mamba_pointwise_ms.read(Observed()) is None
    assert mamba_pointwise_ms._pointwise(
        STEP.replace("/mamba/", "/mlp/")) == {}


@pytest.mark.parametrize("cell,applies", [
    ("nemotron3nano-s8192-b1", True), ("granite4hmicro-s8192-b1", True),
    ("xing4-s4096-b1", False), ("lm871m-s4096-b1", False),
    ("resnet50-b256", False)])
def test_it_applies_where_a_mamba_layer_runs(cell, applies):
    resolved = cells.resolve(cell)
    assert mamba_pointwise_ms.applies(resolved.config, resolved.job) is applies
    entry, = [m for m in resolved.per_layer
              if m["name"] == "mamba_pointwise_ms"]
    assert (cell in entry["workloads"]) is applies
    assert entry["moves"] == "tokens_per_s_per_chip"
