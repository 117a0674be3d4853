"""``nemotron3nano``: the system against the configuration's plain
reference at a small size in float32 — each kind of layer alone and the
nine-layer period, loss and gradient; the share test that ties the cut
to the model; the reference kept apart from the program; the new
arithmetic, and the reader of device time by module on a hand-made
trace."""

import ast
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import cells, hlo, hybrid_flops, modules, trace  # noqa: E402
from benchmark.configs import nemotron3nano  # noqa: E402

PUBLISHED = cells.load_json(REPO, "benchmark/configs/nemotron3nano.json")
TINY = cells.rehearsal(PUBLISHED)


def _tiny(pattern: str) -> dict:
    return dict(TINY, layers=pattern, num_layers=len(pattern))


def _case(pattern: str, seq: int, seed: int = 0):
    config = _tiny(pattern)
    model = nemotron3nano._model(config, pattern)
    batch = nemotron3nano._sampler(config, seq, seed)(
        np.random.default_rng(seed), 2)
    variables = nemotron3nano._init(model, seq)(jax.random.PRNGKey(seed))
    return config, model, variables, batch


@pytest.mark.parametrize("pattern,seq", [
    ("M", 48), ("M", 37), ("E", 48), ("*", 48), ("EMEMEMEM*", 40)])
def test_system_agrees_with_the_plain_reference(pattern, seq):
    """Loss and gradient, each kind of layer alone (the Mamba layer also
    at a length that is no multiple of the chunk, 16) and the period."""
    import flax.linen as nn

    config, model, variables, batch = _case(pattern, seq)
    loss, grads = jax.jit(jax.value_and_grad(
        nemotron3nano._loss_fn(model)))(variables, batch)
    plain = nn.meta.unbox(variables)
    want_loss, want = jax.jit(jax.value_and_grad(functools.partial(
        nemotron3nano.reference_loss, config=config, pattern=pattern)))(
            plain, batch)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    got = jax.tree_util.tree_leaves(nn.meta.unbox(grads))
    want = jax.tree_util.tree_leaves(want)
    norm = np.sqrt(sum(float(jnp.sum(w * w)) for w in want))
    diff = np.sqrt(sum(float(jnp.sum((g - w) ** 2))
                       for g, w in zip(got, want)))
    assert norm > 0 and diff / norm < 1e-4


def test_the_shares_of_an_expert_layer_add_up_to_the_whole_layer():
    """16 experts in 4 shares of 4: the four ranks' partial results, the
    shared expert (which every rank computes alike) counted once, add up
    to the uncut reference's layer output — values and gradients.  The
    router's matrix is the exception that shows why a rank freezes it:
    a rank alone has one term in four of that gradient."""
    from horovod_tpu.models.hybrid import ExpertMixer

    config = _tiny("E")
    experts, d = config["router_experts"], config["hidden_size"]
    width = config["moe_intermediate_size"]
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    u = jax.random.normal(keys[0], (2, 24, d))
    whole = {
        "router": jax.random.normal(keys[1], (d, experts)),
        "bias": jnp.zeros((experts,)),
        "experts_up": jax.random.normal(keys[2], (experts, d, width)) * 0.2,
        "experts_down": jax.random.normal(keys[3], (experts, width, d)) * 0.2,
        "shared_up": {"kernel": jax.random.normal(
            keys[4], (d, config["moe_shared_expert_intermediate_size"])) * 0.2},
        "shared_down": {"kernel": jax.random.normal(
            keys[5], (config["moe_shared_expert_intermediate_size"], d)) * 0.2},
    }

    def uncut(u, p):        # the whole layer trains its router
        return nemotron3nano.experts_reference(
            p, u, dict(config, train_router=True), held=(0, experts))

    def shared_alone(u, p):
        return nemotron3nano.experts_reference(p, u, config, held=(0, 0))

    def shares(u, p):
        total = 0.0
        for lo in range(0, experts, 4):
            cfg = nemotron3nano._hybrid_config(
                dict(config, experts_held=[lo, lo + 4]), "E")
            mine = dict(p, experts_up=p["experts_up"][lo:lo + 4],
                        experts_down=p["experts_down"][lo:lo + 4])
            total = total + ExpertMixer(cfg).apply({"params": mine}, u)
        return total - 3 * shared_alone(u, p)

    np.testing.assert_allclose(jax.jit(shares)(u, whole), uncut(u, whole),
                               rtol=1e-4, atol=1e-4)
    cot = jax.random.normal(jax.random.PRNGKey(6), u.shape)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(shares(*a) * cot),
                           argnums=(0, 1)))(u, whole)
    want = jax.grad(lambda *a: jnp.sum(uncut(*a) * cot),
                    argnums=(0, 1))(u, whole)
    assert not np.any(got[1].pop("router"))
    assert np.any(want[1].pop("router"))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3)


def test_the_parity_batch_holds_only_ids_whose_choice_is_decided():
    """The comparison behind ``correct`` runs over tokens that the
    program and the reference route alike: every id of its batch has its
    sixth and seventh score further apart than the margin, undecided ids
    are replaced in inputs and labels alike, and a bfloat16 rounding of
    the router's input changes no choice in it."""
    import flax.linen as nn

    config = dict(_tiny("EM*"), parity=dict(TINY["parity"],
                                            choice_margin=0.02))
    job = {"seq": 24, "batch_per_chip": 2}
    case = nemotron3nano.parity_case(config, job, 1, seed=3)
    variables = case.init(jax.random.PRNGKey(3))
    decided = np.asarray(nemotron3nano.decided_ids(config, variables))
    assert 0 < decided.sum() < decided.size         # some ids are left out
    batch = case.sample(np.random.default_rng(0), 8)
    plain = nemotron3nano._sampler(config, 24, 3)(
        np.random.default_rng(0), 8)
    assert decided[batch["inputs"]].all() and decided[batch["labels"]].all()
    assert not decided[plain["inputs"]].all()
    kept = decided[plain["inputs"]]
    assert np.array_equal(batch["inputs"][kept], plain["inputs"][kept])
    assert np.array_equal(batch["inputs"][:, 1:], batch["labels"][:, :-1])

    p = nn.meta.unbox(variables)["params"]
    u = nemotron3nano._rms(p["embed"]["embedding"][batch["inputs"]],
                           p["layer_0"]["norm"]["scale"], config["norm_eps"])
    router = p["layer_0"]["moe"]["router"]

    def choice(u):
        return jnp.sort(jax.lax.top_k(jax.nn.sigmoid(u @ router),
                                      config["num_experts_per_tok"])[1], -1)
    assert np.array_equal(
        choice(u), choice(u.astype(jnp.bfloat16).astype(jnp.float32)))
    with pytest.raises(ValueError, match="one expert layer, and first"):
        nemotron3nano.parity_case(
            dict(config, parity=dict(config["parity"], layers="ME*")),
            job, 1, seed=3)


def test_a_rounded_reference_is_another_result():
    """The readings PERF.md gives for a lower precision come from the
    reference with its matmul operands rounded: they must move it."""
    config, _, variables, batch = _case("EM*", 32)
    import flax.linen as nn

    plain = nn.meta.unbox(variables)
    exact = nemotron3nano.reference_loss(plain, batch, config=config,
                                         pattern="EM*")
    rounded = nemotron3nano.reference_loss(
        plain, batch, config=config, pattern="EM*",
        round_to=jnp.float8_e4m3fn)
    assert np.isfinite(float(rounded)) and float(rounded) != float(exact)


def test_the_reference_imports_nothing_of_the_program():
    source = (REPO / "benchmark/configs/nemotron3nano.py").read_text()
    tree = ast.parse(source)
    references = {"mamba2_reference", "attention_reference",
                  "experts_reference", "reference_loss", "_rms", "_relu2",
                  "_matmul"}
    for node in tree.body:      # at module level: no import of it at all
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [a.name for a in node.names] + \
                [getattr(node, "module", "") or ""]
            assert not any(n.startswith("horovod_tpu") for n in names)
        if isinstance(node, ast.FunctionDef) and node.name in references:
            assert "horovod_tpu" not in ast.unparse(node)
            assert "ragged_dot" not in ast.unparse(node)
    assert "lax.scan(one" in source         # the recurrence, step by step


def test_configuration_holds_the_published_widths():
    row = PUBLISHED
    assert row["hidden_size"] == 2688 and row["norm_eps"] == 1e-5
    assert (row["mamba_num_heads"], row["mamba_head_dim"], row["n_groups"],
            row["ssm_state_size"], row["conv_kernel"],
            row["chunk_size"]) == (64, 64, 8, 128, 4, 128)
    assert (row["num_attention_heads"], row["num_key_value_heads"],
            row["head_dim"]) == (32, 2, 128)
    assert (row["moe_intermediate_size"],
            row["moe_shared_expert_intermediate_size"]) == (1856, 3712)
    assert (row["router_experts"], row["num_experts_per_tok"],
            row["routed_scaling_factor"]) == (128, 6, 2.5)
    assert row["num_hidden_layers"] == 52 and row["num_layers"] == 9
    assert row["layers"] == row["hybrid_override_pattern"][34:43]
    assert row["experts_held"] == [0, 8] and row["n_routed_experts"] == 8
    assert row["vocab_size"] * 8 == 131072
    assert "one of 16 chips that share each layer" in row["stands_for"]
    for key in ("scoring", "selection_bias", "positions"):
        assert key in row["assumed"]


def test_flops_a_token_from_shapes():
    """The issue's count: 318.6M matmul weights a token at the uniform
    expectation of 0.375 of an expert, and 6·T·4096 of causal
    attention."""
    config = PUBLISHED
    flops = hybrid_flops.hybrid_lm_flops_per_token(config, 8192)
    scan = 4 * 15 * 64 * 64 * 128
    scores = 6 * 8192 * 4096
    weights = (flops - scan - scores) / 6
    assert weights == pytest.approx(318.6e6, rel=1e-3)
    mamba = 2688 * (2 * 4096 + 2 * 8 * 128 + 64) + 4096 * 2688
    assert mamba == pytest.approx(38.74e6, rel=1e-3)
    cost = hybrid_flops.grouped_matmul_step_cost(3072, 2688, 1856, 8, 4)
    assert cost["flops"] == 4 * 6 * 2 * 3072 * 2688 * 1856
    assert cost["bytes"] == 4 * 6 * 2 * (3072 * (2688 + 1856)
                                         + 8 * 2688 * 1856)


# ---------------------------------------------------------------------------
# device time by module, on a hand-made trace
# ---------------------------------------------------------------------------

STEP = """\
HloModule jit_step

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %m = f32[8] multiply(%p, %p), metadata={op_name="jit(step)/layer_1/mamba/ssd/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %fusion.1 = f32[8] fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(HybridLM)/layer_1/mamba/ssd/mul"}
  %fusion.2 = f32[8] fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(HybridLM))/layer_1/mamba/out_proj/dot_general"}
  %fusion.3 = f32[8] fusion(%fusion.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/layer_0/moe/router/dot_general"}
  %fusion.5 = s32[9] fusion(%fusion.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/layer_0/moe/experts/cumsum"}
  %gmm.4 = f32[8] custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/integer_pow"}
  %conditional.1 = f32[8] conditional(%fusion.3), metadata={op_name="jit(step)/layer_0/moe/cond"}
  %custom-call.7 = f32[8] custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/layer_8/attn/pallas_call"}
  %fusion.8 = f32[8] fusion(%custom-call.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/layer_8/attn/proj/dot_general"}
  %custom-call.9 = f32[8] custom-call(%fusion.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/other/pallas_call"}
  ROOT %fusion.10 = f32[8] fusion(%custom-call.9), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/ln_f/mul"}
}
"""


def test_operations_are_sorted_by_the_module_in_their_path():
    known = modules.read_step(STEP)
    assert known["fusion.1"] == ("mamba", "ssd", None)
    assert known["fusion.2"] == ("mamba", None, None)
    assert known["fusion.3"] == ("moe", "router", None)
    # by the kernel's name: the path is a fused neighbour's
    assert known["gmm.4"] == ("moe", "experts", "grouped_matmul")
    assert modules.classify("tgmm", "custom-call", "tpu_custom_call")[2] \
        == "grouped_matmul"
    assert modules.classify("gmm.4", "fusion", "")[0] is None
    assert known["fusion.5"] == ("moe", "experts", None)
    assert known["custom-call.7"] == ("attn", None, "gqa_flash")
    assert known["fusion.8"] == ("attn", None, None)
    # a Mosaic call outside the mixers is no kernel of theirs; the head
    # no mixer
    assert "custom-call.9" not in known and "fusion.10" not in known


def test_device_time_is_added_up_by_module_and_by_kernel():
    dev = "/device:TPU:0"

    def op(name, start, duration):
        return (dev, trace.OPS_LINE, f"%{name} = f32[8] x()", start, duration)
    events = [
        op("fusion.1", 0, 100), op("fusion.2", 100, 50),
        op("fusion.3", 200, 10),
        # a conditional encloses its branch's operations
        op("conditional.1", 300, 400),
        op("fusion.5", 310, 5), op("gmm.4", 320, 200),
        op("custom-call.7", 800, 300), op("fusion.8", 1100, 40),
        op("custom-call.9", 1200, 70), op("fusion.10", 1300, 999),
        (dev, trace.MODULES_LINE, "jit_step", 0, 2300),
    ]
    out = modules.reduce_events(events, modules.read_step(STEP))
    ns = 1e-9
    assert out["module_s"]["mamba"] == pytest.approx(150 * ns)
    assert out["module_s"]["mamba/ssd"] == pytest.approx(100 * ns)
    # the conditional keeps what its children leave: 400 - 205
    assert out["module_s"]["moe"] == pytest.approx((10 + 195 + 205) * ns)
    assert out["module_s"]["moe/experts"] == pytest.approx(205 * ns)
    assert out["module_s"]["attn"] == pytest.approx(340 * ns)
    assert out["kernel_s"] == {
        "grouped_matmul": pytest.approx(200 * ns),
        "gqa_flash": pytest.approx(300 * ns)}
    # a step that holds none of the names reads as nothing
    assert modules.reduce_events(events, {}) == {}
    plain = hlo.op_classes(STEP)["classes"]
    # by class both are "mosaic": what flash_ms would have added up
    assert plain["custom-call.7"] == plain["gmm.4"] == "mosaic"


def test_flash_readers_keep_to_flash_and_the_new_ones_to_their_cell():
    """``flash_ms`` / ``flash_roofline`` read every Mosaic call as flash:
    they must not apply where a second Mosaic kernel runs; the hybrid's
    readers apply to the hybrid alone."""
    import importlib

    hybrid = cells.resolve("nemotron3nano-s8192-b1")
    decoder = cells.resolve("lm871m-s4096-b1")
    mine = ("ssm_ms", "moe_ms", "moe_experts_ms", "attn_ms", "gqa_flash_ms",
            "gqa_flash_roofline", "grouped_matmul_ms")
    for name in mine + ("flash_ms", "flash_roofline"):
        reader = importlib.import_module(f"benchmark.metrics.{name}")
        assert reader.applies(hybrid.config, hybrid.job) == (name in mine)
        assert reader.applies(decoder.config, decoder.job) == \
            (name not in mine)
