"""``granite4hmicro``: the system against the configuration's plain
reference at the rehearsal's sizes in float32 — every kind of sublayer
alone and the whole pattern, all three muP scalars off 1, the tied head,
one B/C group whose 8 heads the scan's kernels take in blocks of 4, loss
and every gradient leaf; what the parity case refuses; the reference kept
apart from the program; the file against the published ``config.json``;
the parameter table; the new arithmetic; and the two new readers on a
hand-made trace."""

import ast
import functools
import importlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import cells, flops, granite_flops, modules, trace  # noqa: E402
from benchmark.configs import granite4hmicro  # noqa: E402
from benchmark.metrics import mlp_ms, ssd_roofline  # noqa: E402
from test_benchmark_xing4 import _readings  # noqa: E402 — what parity.check compares

PUBLISHED = cells.load_json(REPO, "benchmark/configs/granite4hmicro.json")
TINY = cells.rehearsal(PUBLISHED)
CELL = "granite4hmicro-s8192-b1"


@pytest.fixture
def head_blocks_of_four(monkeypatch):
    """The rehearsal's 8 heads fit one grid step; the timed shape's 64 do
    not.  Take them 4 at a step here, so that the blocked calls run."""
    from horovod_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "ssd_head_block",
                        lambda r, *shape: 4)


def _case(pattern: str, seq: int, seed: int = 0, moved: bool = True):
    import flax.linen as nn

    model = granite4hmicro._model(TINY, pattern)
    batch = granite4hmicro._sampler(TINY, seq, seed)(
        np.random.default_rng(seed), 2)
    init = granite4hmicro._init(model, seq)
    if moved:
        init = granite4hmicro.off_seed(init)
    return model, nn.meta.unbox(init(jax.random.PRNGKey(seed))), batch


@pytest.mark.parametrize("pattern,seq,moved", [
    ("MD", 128, True), ("*D", 128, True), ("MD*D", 256, True),
    ("MD*D", 256, False), ("MDMD*DMD", 128, True)])
def test_system_agrees_with_the_plain_reference(pattern, seq, moved,
                                                head_blocks_of_four):
    """Loss and every gradient leaf, the scan's and flash's kernels
    interpreted: each mixer with its MLP, the parity cut from the model's
    initial values and off them, and a longer run of layers."""
    model, plain, batch = _case(pattern, seq, moved=moved)
    step = jax.jit(jax.value_and_grad(granite4hmicro._loss_fn(model)))
    if "M" in pattern:      # the blocked kernels, not the einsum form
        calls = str(jax.make_jaxpr(step)(plain, batch))
        assert "ssd_fwd" in calls and "ssd_bwd" in calls
    loss, grads = step(plain, batch)
    want_loss, want = jax.jit(jax.value_and_grad(functools.partial(
        granite4hmicro.reference_loss, config=TINY, pattern=pattern)))(
            plain, batch)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert "head" not in grads["params"]
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree_util.tree_leaves(want)):
        norm = float(jnp.sqrt(jnp.sum(ref * ref)))
        assert norm > 0, jax.tree_util.keystr(path)
        assert float(jnp.sqrt(jnp.sum((got - ref) ** 2))) / norm < 1e-3, \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("wrong", [
    {"round_to": jnp.float8_e4m3fn}, {"logits_scaling": 64.0},
    {"residual_multiplier": 1.0}])
def test_the_parity_case_refuses_another_program(wrong):
    """In the program's place: the reference with fp8 matmul operands, a
    head divided by 64 where 8 is meant, branches not scaled — each
    misses one of the rehearsal's limits at least; the program itself
    keeps them all."""
    job = {"seq": 128, "batch_per_chip": 2}
    case = granite4hmicro.parity_case(TINY, job, 1, seed=5)
    pattern = TINY["parity"]["layers"]
    import flax.linen as nn

    params = nn.meta.unbox(case.init(jax.random.PRNGKey(5)))
    batch = case.sample(np.random.default_rng(5), 2)
    reference = functools.partial(granite4hmicro.reference_loss,
                                  config=TINY, pattern=pattern)
    limits = {k: TINY["parity"][k] for k in
              ("loss_rtol", "grad_norm_rtol", "grad_rel_l2")}
    off = _readings(functools.partial(reference, **wrong), reference,
                    params, batch)
    assert any(off[k] > limits[k] for k in limits), off
    sound = _readings(case.loss_fn, reference, params, batch)
    assert all(sound[k] <= limits[k] for k in limits), sound


def test_the_parity_case_starts_where_no_scale_or_bias_is_idle():
    """``off_seed`` moves the norm scales, the gated norm's, ``D`` and the
    convolution's bias off 1 and 0, from the key alone, and nothing
    else."""
    import flax.linen as nn

    model = granite4hmicro._model(TINY, "MD*D")
    init = granite4hmicro._init(model, 128)
    key = jax.random.PRNGKey(7)
    at, off = (nn.meta.unbox(f(key))["params"]
               for f in (init, granite4hmicro.off_seed(init)))
    again = nn.meta.unbox(granite4hmicro.off_seed(init)(key))["params"]
    moved = set()
    for (path, a), b, c in zip(jax.tree_util.tree_leaves_with_path(at),
                               jax.tree_util.tree_leaves(off),
                               jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(b, c)
        if not np.array_equal(a, b):
            moved.add(jax.tree_util.keystr(path).split("']['")[-1][:-2])
            assert 0.0 < float(jnp.max(jnp.abs(a - b))) < 0.6
    assert moved == {"scale", "norm_scale", "D", "conv_bias"}


def test_the_reference_imports_nothing_of_the_program():
    source = (REPO / "benchmark/configs/granite4hmicro.py").read_text()
    tree = ast.parse(source)
    references = {"mamba2_reference", "attention_reference",
                  "gated_mlp_reference", "reference_loss"}
    found = set()
    for node in tree.body:      # at module level: no import of it at all
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [a.name for a in node.names] + \
                [getattr(node, "module", "") or ""]
            assert not any(n.startswith("horovod_tpu") for n in names)
        if isinstance(node, ast.FunctionDef) and node.name in references:
            found.add(node.name)
            text = ast.unparse(node)
            for word in ("horovod_tpu", "pallas", "ssd_", "cumsum", "repeat",
                         "flash"):
                assert word not in text, (node.name, word)
    assert found == references
    # the recurrence one step after another, attention through the (T, T)
    # scores of a key/value head against its own query heads
    assert "jax.lax.scan(one, state, part)" in source
    assert "jnp.tril(jnp.ones((t, t), bool))" in source
    assert "embedding.T) / divisor" in source


def test_configuration_holds_every_published_key_but_the_reduced_ones():
    """Against the widths written here in any case, and against the
    catalog's row where the guides are installed."""
    row = PUBLISHED
    assert (row["hidden_size"], row["shared_intermediate_size"],
            row["intermediate_size"]) == (2048, 8192, 8192)
    assert (row["mamba_n_heads"], row["mamba_d_head"], row["mamba_n_groups"],
            row["mamba_d_state"], row["mamba_d_conv"],
            row["mamba_chunk_size"], row["mamba_conv_bias"],
            row["mamba_proj_bias"]) == (64, 64, 1, 128, 4, 256, True, False)
    assert (row["num_attention_heads"], row["num_key_value_heads"],
            row["attention_multiplier"], row["position_embedding_type"]) \
        == (32, 8, 1 / 64, "nope")
    assert (row["embedding_multiplier"], row["residual_multiplier"],
            row["logits_scaling"], row["tie_word_embeddings"]) \
        == (12, 0.22, 8, True)
    assert row["num_local_experts"] == 0 and row["rms_norm_eps"] == 1e-5
    assert row["num_hidden_layers"] == 40 and row["num_layers"] == 10
    types = row["layer_types"]
    assert len(types) == 40 and [i for i, kind in enumerate(types)
                                 if kind == "attention"] == [5, 15, 25, 35]
    # a character a sublayer, the first period of layer_types
    assert row["layers"] == "".join(
        ("*" if kind == "attention" else "M") + "D" for kind in types[:10])
    assert row["vocab_size"] * 8 == 100352 and row["vocab_size"] % 128 == 0
    assert sorted(row["reduced"]) == ["num_layers", "vocab_size"]
    assert row["kernels"] == ["gqa_flash", "ssd"]
    assert "first of four pipeline stages" in row["stands_for"]
    for key in ("initialisation", "mamba_precision", "positions", "head",
                "optimizer", "remat"):
        assert key in row["assumed"], key
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        return
    published = next(
        r for r in map(json.loads, catalog.read_text().splitlines())
        if r["source_url"] == row["source"])["config"]
    assert {k for k, v in published.items() if row.get(k) != v} \
        == {"vocab_size"}


def test_the_cut_s_parameters_are_the_issue_s_table():
    """772,160,448 parameters at the published widths: counted from the
    model's own shapes, nothing initialised."""
    import flax.linen as nn

    model = granite4hmicro._model(PUBLISHED, PUBLISHED["layers"])
    shapes = jax.eval_shape(granite4hmicro._init(model, 128),
                            jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(nn.meta.unbox(tree)))

    p = shapes["params"]
    assert "head" not in p and len(p) == 22
    mamba = 2048 * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096 + 4096 * 2048
    assert count(p["layer_0"]) == mamba + 2048 == 25_849_280
    assert count(p["layer_10"]) == 2048 * 3072 + 2048 * 2048 + 2048 \
        == 10_487_808
    assert count(p["layer_1"]) == 3 * 2048 * 8192 + 2048 == 50_333_696
    assert count(p["embed"]) + count(p["ln_f"]) == 12544 * 2048 + 2048
    assert count(p) == 9 * 25_849_280 + 10_487_808 + 10 * 50_333_696 \
        + 25_692_160 == 772_160_448


def test_flops_a_token_and_the_scan_s_cost_from_shapes():
    """40 TFLOP a step: 6 FLOPs a matmul weight a token, the causal
    scores, the scan's products; the scan's cost a function of the shapes
    alone."""
    seq = 8192
    per_token = granite_flops.granite_lm_flops_per_token(PUBLISHED, seq)
    mamba = 2048 * 8512 + 4096 * 2048
    attn = 2048 * 3072 + 2048 * 2048
    weights = 9 * mamba + attn + 10 * 3 * 2048 * 8192 + 2048 * 12544
    forward, backward = granite_flops.ssd_products(seq, 64, 64, 1, 128, 256)
    assert forward == 32 * (2 * 256 * 256 * 128
                            + 64 * (2 * 64 * 256 * 256 + 4 * 64 * 256 * 128))
    assert backward == 32 * (6 * 256 * 256 * 128 + 64 * (
        4 * 64 * 256 * 256 + 10 * 64 * 256 * 128))
    assert per_token == pytest.approx(
        6 * weights + 9 * (forward + backward) / seq + 6 * seq * 2048)
    assert 39e12 < per_token * seq < 41e12
    built = granite4hmicro.build(PUBLISHED, cells.resolve(CELL).job, 1, 0)
    assert built.flops_per_unit == per_token
    assert built.kernel_cost["gqa_flash"] == flops.flash_step_cost(
        32, seq, 64, 1)
    scan = built.kernel_cost["ssd"]
    assert scan == granite_flops.ssd_step_cost(1, seq, 64, 64, 1, 128, 256, 9)
    assert scan["flops"] == 9 * (forward + backward)
    # x 64 MiB, y and dy 128 MiB each, the starting states 64 MiB twice
    assert 9 * 0.55e9 < scan["bytes"] < 9 * 0.70e9
    # eight groups of eight heads: the same heads' products, C^T B and
    # its cotangents eight times
    eight = granite_flops.ssd_products(seq, 64, 64, 8, 128, 256)
    assert eight[0] - forward == 32 * 7 * 2 * 256 * 256 * 128


STEP = """\
HloModule jit_step

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %m = f32[8] multiply(%p, %p), metadata={op_name="jit(step)/layer_1/mlp/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %fusion.1 = f32[8] fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(HybridLM)/layer_1/mlp/gate/dot_general"}
  %fusion.2 = f32[8] fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(HybridLM))/jvp(HybridLM)/checkpoint/rematted_computation/layer_1/mlp/up/dot_general"}
  %fusion.3 = f32[8] fusion(%fusion.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(HybridLM))/layer_1/mlp/down/dot_general"}
  %jvp_ssd_fwd_.4 = f32[8] custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(HybridLM)/layer_0/mamba/ssd/ssd_fwd"}
  %jvp_ssd_fwd_.5 = f32[8] custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(HybridLM))/jvp(HybridLM)/checkpoint/rematted_computation/layer_0/mamba/ssd/ssd_fwd"}
  %transpose_jvp_ssd_bwd__.6 = f32[8] custom-call(%jvp_ssd_fwd_.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(HybridLM))/layer_0/mamba/ssd/ssd_bwd"}
  %fusion.7 = f32[8] fusion(%transpose_jvp_ssd_bwd__.6), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(HybridLM)/layer_0/mamba/ssd/mul"}
  %flash_fwd.8 = f32[8] custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/layer_10/attn/flash_fwd"}
  ROOT %fusion.9 = f32[8] fusion(%flash_fwd.8), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(HybridLM)/layer_0/mamba/in_proj/dot_general"}
}
"""


class _Built:
    kernel_cost = {"ssd": {"flops": 197e12 * 1e-6, "bytes": 1.0}}


class _Observed:
    """What the readers take of a run: a traced block of two steps."""

    def __init__(self, text, built=_Built):
        self.traced_steps = 2
        self.trace = {"busy_s": 1.0}
        self.hlo_text = text
        self.cell = cells.resolve(CELL)
        self.built = built
        self.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_new_readers_on_a_hand_made_trace(monkeypatch):
    """``mlp_ms``: every operation under a module named ``mlp``, forward,
    recomputed and backward.  ``ssd_roofline``: the Mosaic calls named
    ``ssd_fwd`` / ``ssd_bwd`` alone — not the fusion beside them under
    ``mamba/ssd``, not flash — against the cost the configuration
    built."""
    dev = "/device:TPU:0"

    def op(name, start, duration):
        return (dev, trace.OPS_LINE, f"%{name} = f32[8] x()", start, duration)
    events = [op("fusion.1", 0, 100), op("fusion.2", 100, 50),
              op("fusion.3", 200, 70), op("jvp_ssd_fwd_.4", 300, 400),
              op("jvp_ssd_fwd_.5", 800, 400),
              op("transpose_jvp_ssd_bwd__.6", 1300, 1200),
              op("fusion.7", 2600, 33), op("flash_fwd.8", 2700, 500),
              op("fusion.9", 3300, 999),
              (dev, trace.MODULES_LINE, "jit_step", 0, 4400)]
    assert set(mlp_ms._under_mlp(STEP)) == {"m", "fusion.1", "fusion.2",
                                            "fusion.3"}
    assert set(ssd_roofline._scan_calls(STEP)) == {
        "jvp_ssd_fwd_.4", "jvp_ssd_fwd_.5", "transpose_jvp_ssd_bwd__.6"}
    # the accepted readers see the same step as before: mlp is none of
    # their kinds, and the scan's calls stay under mamba/ssd
    mixers = modules.reduce_events(events, modules.read_step(STEP))
    assert mixers["module_s"]["mamba/ssd"] == pytest.approx(2033e-9)
    assert mixers["module_s"]["mamba"] == pytest.approx(3032e-9)
    assert mixers["kernel_s"] == {"gqa_flash": pytest.approx(500e-9)}
    monkeypatch.setattr(trace, "newest_xplane", lambda root: "profile")
    monkeypatch.setattr(trace, "load_events", lambda path: events)
    obs = _Observed(STEP)
    assert mlp_ms.read(obs) == pytest.approx(220e-9 / 2 * 1e3)
    assert ssd_roofline.kernel_seconds(obs) == pytest.approx(2000e-9)
    # a step's least time 1 us (compute binds), two steps traced in 2 us
    assert ssd_roofline.read(obs) == pytest.approx(100.0)
    # a step with no such module or call — the parent commit, another
    # model, the einsum form — reads as nothing, and so does an untraced
    # run or a configuration that built no such cost
    bare = STEP.replace("/mlp/", "/other/").replace("ssd_fwd", "scan_f") \
        .replace("ssd_bwd", "scan_b")
    assert mlp_ms.read(_Observed(bare)) is None
    assert ssd_roofline.read(_Observed(bare)) is None

    class NoCost:
        kernel_cost = {}
    assert ssd_roofline.read(_Observed(STEP, NoCost)) is None
    untraced = _Observed(STEP)
    untraced.trace = {}
    assert mlp_ms.read(untraced) is None
    assert ssd_roofline.read(untraced) is None


def test_the_new_readers_keep_to_their_cells():
    mine = cells.resolve(CELL)
    applying = {m["name"] for m in mine.per_layer
                if importlib.import_module(
                    f"benchmark.metrics.{m['name']}").applies(
                        mine.config, mine.job)}
    assert {"mlp_ms", "ssd_roofline", "ssm_ms", "ssd_ms", "attn_ms",
            "gqa_flash_ms", "gqa_flash_roofline", "input_wait_ms",
            "compute_ms", "device_idle_share"} <= applying
    assert not {"flash_ms", "flash_roofline", "moe_ms", "moe_experts_ms",
                "grouped_matmul_ms", "hc_ms", "exchange_ms",
                "collective_ms"} & applying
    assert mine.job == cells.resolve("nemotron3nano-s8192-b1").job
    applies = {name: (mlp_ms.applies(c.config, c.job),
                      ssd_roofline.applies(c.config, c.job))
               for name, c in ((n, cells.resolve(n)) for n in (
                   "xing4-s4096-b1", "nemotron3nano-s8192-b1",
                   "lm871m-s4096-b1", "resnet50-b256"))}
    assert applies == {"xing4-s4096-b1": (True, False),
                       "nemotron3nano-s8192-b1": (False, False),
                       "lm871m-s4096-b1": (False, False),
                       "resnet50-b256": (False, False)}
