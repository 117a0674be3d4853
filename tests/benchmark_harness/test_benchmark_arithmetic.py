"""The benchmark's own arithmetic: FLOPs from shapes, the roofline, the
reading of compiled HLO, the rate of a window.  Counts only — nothing
here is a time."""

import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import flops, hlo  # noqa: E402

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_lm_flops_a_token_from_shapes():
    got = flops.transformer_lm_flops_per_token(16, 2048, 8192, 32000, 1024)
    # 6 x 870.8M matmul weights (the 870.9M parameters less the norm
    # scales) + 6 x 16 layers x 1024 x 2048 of causal attention
    assert got == 6 * (16 * (4 * 2048 ** 2 + 2 * 2048 * 8192)
                       + 32000 * 2048) + 6 * 16 * 1024 * 2048
    assert got == pytest.approx(5.43e9, rel=2e-3)
    both_ways = flops.transformer_lm_flops_per_token(
        16, 2048, 8192, 32000, 1024, causal=False)
    assert both_ways - got == 6 * 16 * 1024 * 2048


@pytest.mark.parametrize("blocks,window_s", [
    ((1.0, 1.0, 1.0, 1.0), 4.0),        # steady
    ((1.0, 1.0, 6.0, 1.0), 9.0),        # one block stalled for 5 s
])
def test_a_rate_is_the_units_of_whole_blocks_over_the_window(blocks,
                                                             window_s):
    """A stall costs the rate what it cost the window: a user pays wall
    time, not the median block's."""
    from benchmark import run

    cell = types.SimpleNamespace(job={"batch_per_chip": 256},
                                 rate_metric="images_per_s_per_chip")
    obs = types.SimpleNamespace(
        cell=cell, steps_per_block=10, block_seconds=blocks,
        window_s=window_s, setup={"setup_s": 30.0}, peaks=V5E,
        built=types.SimpleNamespace(units_per_sample=1, flops_per_unit=2e9))
    got = run.end_to_end_values(obs, {"footprint_bytes": {0: 9e9, 1: 8e9}})
    rate = len(blocks) * 10 * 256 / window_s
    assert got == {"images_per_s_per_chip": rate, "setup_s": 30.0,
                   "mfu": rate * 2e9 / 197e12, "hbm_gb_per_chip": 9.0}


def test_resnet50_forward_is_two_times_4_09e9():
    macs = flops.resnet_forward_macs([3, 4, 6, 3])
    assert macs == pytest.approx(4.09e9, rel=1e-3)
    assert flops.resnet_flops_per_image([3, 4, 6, 3]) == 6.0 * macs
    # the old accounting, 3 x 4.1e9, took a multiply-add for one
    # operation against a peak that counts two: half of this
    assert 3 * 4.1e9 == pytest.approx(
        flops.resnet_flops_per_image([3, 4, 6, 3]) / 2, rel=3e-3)


def test_resnet_macs_agree_with_the_model_s_own_convolutions():
    """The formula against the shapes the flax model really traces."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.resnet import ResNet

    stages, size, classes = [1, 2, 1], 64, 10
    model = ResNet(stage_sizes=stages, num_classes=classes, num_filters=16)
    x = jax.ShapeDtypeStruct((1, size, size, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros(x.shape), train=False))
    jaxpr = jax.make_jaxpr(
        lambda v, x: model.apply(v, x, train=False))(variables, x)
    macs = 0
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            kh, kw, cin, cout = eqn.invars[1].aval.shape
            _, oh, ow, _ = eqn.outvars[0].aval.shape
            macs += oh * ow * kh * kw * cin * cout
        elif eqn.primitive.name == "dot_general":
            macs += eqn.invars[1].aval.size
    assert macs == flops.resnet_forward_macs(stages, size, 16, classes)


def test_flash_cost_and_which_bound_binds():
    cost = flops.flash_step_cost(rows=96, seq=1024, head_dim=128, layers=16)
    assert cost["flops"] == 96 * 16 * 7 * 1024 * 1024 * 128
    assert cost["bytes"] == 96 * 16 * (12 * 1024 * 128 * 2 + 2 * 1024 * 4)
    least, bound = flops.roofline_s(cost["flops"], cost["bytes"], V5E)
    assert bound == "compute"
    assert least == pytest.approx(cost["flops"] / 197e12)
    assert flops.roofline_s(1e9, 1e9, V5E)[1] == "memory"


# optimized HLO as XLA:TPU prints it: layouts with tiles and memory
# spaces inside the result types, async pairs, a fusion around a
# convolution, a Mosaic call
TPU_HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation.5 (p0: bf16[6144,2048], p1: bf16[2048,8192]) -> bf16[6144,8192] {
  %p0 = bf16[6144,2048]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[2048,8192]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.9 = bf16[6144,8192]{1,0:T(8,128)(2,1)} convolution(%p0, %p1), dim_labels=bf_io->bf
}

%fused_computation.6 (p0: f32[2048]) -> f32[2048] {
  %p0.1 = f32[2048]{0:T(1024)S(1)} parameter(0)
  ROOT %multiply.3 = f32[2048]{0:T(1024)S(1)} multiply(%p0.1, %p0.1)
}

ENTRY %main.1 (a: bf16[6144,2048], b: bf16[2048,8192]) -> bf16[6144,8192] {
  %a = bf16[6144,2048]{1,0:T(8,128)(2,1)} parameter(0)
  %b = bf16[2048,8192]{1,0:T(8,128)(2,1)} parameter(1)
  %convolution_bitcast_fusion.4 = bf16[6144,8192]{1,0:T(8,128)(2,1)S(1)} fusion(%a, %b), kind=kOutput, calls=%fused_computation.5
  %multiply_fusion = f32[2048]{0:T(1024)S(1)} fusion(%c), kind=kLoop, calls=%fused_computation.6
  %custom-call.7 = (bf16[96,1024,128]{2,1,0:T(8,128)(2,1)}, f32[96,1024]{1,0:T(8,128)}) custom-call(bf16[96,1024,128]{2,1,0:T(8,128)(2,1)} %q), custom_call_target="tpu_custom_call"
  %custom-call.8 = bf16[4,4]{1,0} custom-call(%x), custom_call_target="ConcatBitcast"
  %all-reduce.113 = (bf16[2048,2048]{1,0:T(8,128)(2,1)}, bf16[2048,6144]{1,0:T(8,128)(2,1)S(1)}) all-reduce(%g1, %g2), channel_id=5, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add
  %all-reduce.2 = f32[8]{0} all-reduce(%s), channel_id=6, replica_groups={{0,1},{2,3}}, to_apply=%add
  %all-gather-start.1 = (f32[1024]{0}, f32[4096]{0}) all-gather-start(%shard), replica_groups=[1,4]<=[4], dimensions={0}
  %all-gather-done.3 = f32[4096]{0} all-gather-done(%all-gather-start.1)
  %all-reduce-start.4 = (f32[512]{0:T(512)}, u32[]) all-reduce-start(%t), replica_groups=[1,4]<=[4], to_apply=%add
  %all-reduce-done.4 = f32[512]{0:T(512)} all-reduce-done(%all-reduce-start.4)
  %copy-start.1 = (bf16[8,8]{1,0:T(8,128)(2,1)S(1)}, bf16[8,8]{1,0}, u32[]) copy-start(%y)
  ROOT %copy-done.1 = bf16[8,8]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.1)
}
"""


def test_collectives_of_a_tpu_module_are_counted_in_payload_bytes():
    found = {c["name"]: c for c in hlo.collectives(TPU_HLO)}
    assert set(found) == {"all-reduce.113", "all-reduce.2",
                          "all-gather-start.1", "all-reduce-start.4"}
    assert found["all-reduce.113"]["bytes"] == \
        2 * (2048 * 2048 + 2048 * 6144)
    assert found["all-reduce.113"]["group_size"] == 4
    assert found["all-reduce.2"]["group_size"] == 2
    assert found["all-gather-start.1"]["bytes"] == 4096 * 4  # the output
    assert found["all-reduce-start.4"]["bytes"] == 512 * 4   # no u32[]
    assert hlo.collective_bytes(TPU_HLO, 4) == \
        2 * (2048 * 2048 + 2048 * 6144) + 4096 * 4 + 512 * 4
    assert hlo.collective_bytes(TPU_HLO, 2) == 32


def test_operations_are_sorted_into_the_classes_the_reducer_sums():
    ops = hlo.op_classes(TPU_HLO)
    want = {
        "convolution_bitcast_fusion.4": "compute",
        "multiply_fusion": "compute",
        "custom-call.7": "mosaic", "custom-call.8": "compute",
        "all-reduce.113": "collective",
        "all-gather-start.1": "collective-start",
        "all-gather-done.3": "collective-done",
        "all-reduce-start.4": "collective-start",
        "all-reduce-done.4": "collective-done",
        "copy-start.1": "compute", "copy-done.1": "compute",
    }
    assert {k: ops["classes"][k] for k in want} == want
    assert ops["done_of"] == {"all-gather-done.3": "all-gather-start.1",
                              "all-reduce-done.4": "all-reduce-start.4"}
    assert len(hlo.mosaic_lines(TPU_HLO)) == 1
    assert "[96,1024,128]" in hlo.mosaic_lines(TPU_HLO)[0]
