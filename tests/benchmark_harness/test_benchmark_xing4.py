"""``xing4``: the system against the configuration's plain reference at a
small size in float32 — each kind of sublayer alone inside its
hyper-connection and the whole rehearsal model, loss and gradient; the
share test that ties the cut to the model; the reference kept apart from
the program; the file against the published ``config.json``; the new
arithmetic; and the reader of the hyper-connections' device time on a
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

from benchmark import cells, flops, latent_flops, modules, trace  # noqa: E402
from benchmark.configs import xing4  # noqa: E402
from benchmark.metrics import hc_ms  # noqa: E402

PUBLISHED = cells.load_json(REPO, "benchmark/configs/xing4.json")
TINY = cells.rehearsal(PUBLISHED)
CELL = "xing4-s4096-b1"


def _case(pattern: str, seq: int, seed: int = 0):
    model = xing4._model(TINY, pattern)
    batch = xing4._sampler(TINY, seq, seed)(np.random.default_rng(seed), 2)
    variables = xing4._init(model, seq)(jax.random.PRNGKey(seed))
    return model, variables, batch


def _unlike_the_initial_values(variables, seed: int = 9):
    """At the initial values the streams are copies of one another and
    the mixing matrix has nothing to mix: move every hyper-connection
    away from them, so that the comparison sees its arithmetic."""
    import flax.linen as nn

    plain = nn.meta.unbox(variables)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    for name, layer in plain["params"].items():
        if not name.startswith("layer_"):
            continue
        hc = layer["hc"]
        hc["gates"] = jnp.asarray([0.6, -0.4, 0.8])
        hc["phi"] = 0.05 * jax.random.normal(next(keys), hc["phi"].shape)
        hc["b_pre"] = jax.random.normal(next(keys), hc["b_pre"].shape)
        hc["b_post"] = jax.random.normal(next(keys), hc["b_post"].shape)
        hc["b_res"] = hc["b_res"] + jax.random.normal(
            next(keys), hc["b_res"].shape)
    return plain


@pytest.mark.parametrize("pattern,seq,moved", [
    ("*", 48, True), ("D", 48, True), ("E", 48, True),
    ("*D*E", 40, False), ("*D*E", 40, True), ("E*D", 40, True)])
def test_system_agrees_with_the_plain_reference(pattern, seq, moved):
    """Loss and gradient: each kind of sublayer alone, the rehearsal's
    two layers at their initial values and away from them, and the
    parity cut's order."""
    import flax.linen as nn

    model, variables, batch = _case(pattern, seq)
    plain = _unlike_the_initial_values(variables) if moved \
        else nn.meta.unbox(variables)
    loss, grads = jax.jit(jax.value_and_grad(xing4._loss_fn(model)))(
        plain, batch)
    want_loss, want = jax.jit(jax.value_and_grad(functools.partial(
        xing4.reference_loss, config=TINY, pattern=pattern)))(plain, batch)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    got = jax.tree_util.tree_leaves(grads)
    want = jax.tree_util.tree_leaves(want)
    norm = np.sqrt(sum(float(jnp.sum(w * w)) for w in want))
    diff = np.sqrt(sum(float(jnp.sum((g - w) ** 2))
                       for g, w in zip(got, want)))
    assert norm > 0 and diff / norm < 1e-4


def test_the_shares_of_an_expert_layer_add_up_to_the_whole_layer():
    """16 experts in 4 shares of 4: the four ranks' partial results, the
    shared expert (which every rank computes alike) counted once, add up
    to the uncut reference's layer output — values and gradients, but
    for the router's matrix, of which a rank alone has one term in four
    (why a rank freezes it)."""
    from horovod_tpu.models.hybrid import ExpertMixer

    config = TINY
    experts, d = config["router_experts"], config["hidden_size"]
    width = config["moe_intermediate_size"]
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 12))
    u = jax.random.normal(next(keys), (2, 24, d))

    def matrix(*shape):
        return jax.random.normal(next(keys), shape) * 0.2

    whole = {
        "router": jax.random.normal(next(keys), (d, experts)),
        "bias": jnp.zeros((experts,)),
        "experts_gate": matrix(experts, d, width),
        "experts_up": matrix(experts, d, width),
        "experts_down": matrix(experts, width, d),
        "shared_gate": {"kernel": matrix(d, width)},
        "shared_up": {"kernel": matrix(d, width)},
        "shared_down": {"kernel": matrix(width, d)},
    }

    def uncut(u, p):        # the whole layer trains its router
        return xing4.experts_reference(
            p, u, dict(config, train_router=True), held=(0, experts))

    def shared_alone(u, p):
        return xing4.experts_reference(p, u, config, held=(0, 0))

    def shares(u, p):
        total = 0.0
        for lo in range(0, experts, 4):
            cfg = xing4._hybrid_config(
                dict(config, experts_held=[lo, lo + 4]), "E")
            mine = dict(p, **{k: p[k][lo:lo + 4] for k in (
                "experts_gate", "experts_up", "experts_down")})
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
    """The parity cut starts with the expert sublayer, whose router
    reads the hyper-connection's read-out of four copies of the
    embedding: a function of the id.  Every id of the batch has its
    fourth and fifth score further apart than the margin, and the
    program's own router picks, for every decided id, the experts the
    reference's scores pick."""
    import flax.linen as nn

    from horovod_tpu.models.hybrid import HybridLM

    config = dict(TINY, parity=dict(TINY["parity"], choice_margin=0.02))
    job = {"seq": 24, "batch_per_chip": 2}
    case = xing4.parity_case(config, job, 1, seed=3)
    variables = case.init(jax.random.PRNGKey(3))
    decided = np.asarray(xing4.decided_ids(config, variables))
    assert 0 < decided.sum() < decided.size         # some ids are left out
    batch = case.sample(np.random.default_rng(0), 8)
    plain = xing4._sampler(config, 24, 3)(np.random.default_rng(0), 8)
    assert decided[batch["inputs"]].all() and decided[batch["labels"]].all()
    assert not decided[plain["inputs"]].all()
    assert np.array_equal(batch["inputs"][:, 1:], batch["labels"][:, :-1])

    # the program's first sublayer, asked which experts it picks for
    # every id of the slice
    model = HybridLM(xing4._hybrid_config(config, "E"))
    ids = jnp.arange(config["vocab_size"])[None, :]
    first = {"params": {k: v for k, v in nn.meta.unbox(
        variables)["params"].items() if k in ("embed", "layer_0", "ln_f",
                                              "head")}}
    _, state = model.apply(first, ids, mutable=["intermediates"])
    p = first["params"]["layer_0"]
    emb = first["params"]["embed"]["embedding"]
    u = xing4._rms(jnp.tile(emb, (1, 4)), p["hc"]["norm_scale"], 1e-6)
    h_pre = jax.nn.sigmoid(p["hc"]["gates"][0] * (u @ p["hc"]["phi"][:, :4])
                           + p["hc"]["b_pre"])
    read = xing4._rms(h_pre.sum(-1, keepdims=True) * emb,
                      p["norm"]["scale"], 1e-6)
    lo, hi = config["experts_held"]
    _, chosen = jax.lax.top_k(jax.nn.sigmoid(read @ p["moe"]["router"]),
                              config["num_experts_per_tok"])
    held = np.asarray(jnp.sum((chosen[decided] >= lo)
                              & (chosen[decided] < hi)))
    undecided_too = np.asarray(jnp.sum((chosen >= lo) & (chosen < hi)))
    landed = int(np.sum(state["intermediates"]["layer_0"]["moe"][
        "held_load"][0]))
    assert held <= landed == undecided_too
    with pytest.raises(ValueError, match="one expert layer, and first"):
        xing4.parity_case(
            dict(config, parity=dict(config["parity"], layers="*DE")),
            job, 1, seed=3)


def test_a_lower_precision_in_the_reference_is_another_result():
    """The readings PERF.md gives for a lower precision come from the
    reference with its matmul operands, its router or its Sinkhorn
    rounded: each must move it."""
    _, variables, batch = _case("E*D", 32)
    plain = _unlike_the_initial_values(variables)
    loss = functools.partial(xing4.reference_loss, plain, batch,
                             config=TINY, pattern="E*D")
    exact = float(loss())
    for lower in ({"round_to": jnp.float8_e4m3fn},
                  {"router_dtype": jnp.bfloat16},
                  {"sinkhorn_dtype": jnp.bfloat16}):
        moved = float(loss(**lower))
        assert np.isfinite(moved) and moved != exact, lower


def _readings(system_loss, reference_loss, params, batch):
    """What ``parity.check`` compares: loss, gradient norm, direction."""
    loss, grads = jax.value_and_grad(system_loss)(params, batch)
    want_loss, want = jax.value_and_grad(reference_loss)(params, batch)
    got, want = (jax.tree_util.tree_leaves(t) for t in (grads, want))
    norm = np.sqrt(sum(float(jnp.sum(w * w)) for w in want))
    mine = np.sqrt(sum(float(jnp.sum(g * g)) for g in got))
    diff = np.sqrt(sum(float(jnp.sum((g - w) ** 2))
                       for g, w in zip(got, want)))
    return {"loss_rtol": abs(float(loss) - float(want_loss))
            / abs(float(want_loss)),
            "grad_norm_rtol": abs(mine - norm) / norm,
            "grad_rel_l2": diff / norm}


@pytest.mark.parametrize("wrong", ["transposed", "permuted"])
def test_the_parity_case_tells_a_wrong_mixing_matrix_apart(monkeypatch,
                                                           wrong):
    """From the model's own initial values the streams stay copies of one
    another and ``H_res`` acts through its row sums: its transpose, or
    its rows in another order, give the same loss and gradient.  The
    parity case starts off them (``off_seed``), and there either misses
    the rehearsal's limits."""
    import flax.linen as nn

    job = {"seq": 40, "batch_per_chip": 2}
    case = xing4.parity_case(TINY, job, 1, seed=4)
    batch = case.sample(np.random.default_rng(4), 2)
    reference = functools.partial(xing4.reference_loss, config=TINY,
                                  pattern=TINY["parity"]["layers"])
    plain = xing4.sinkhorn_reference

    def mixed_up(*args, **kwargs):
        m = plain(*args, **kwargs)
        return jnp.swapaxes(m, -1, -2) if wrong == "transposed" \
            else jnp.roll(m, 1, axis=-2)

    def system(params, batch):      # the patch holds while this is traced
        with monkeypatch.context() as patch:
            patch.setattr(xing4, "sinkhorn_reference", mixed_up)
            return reference(params, batch)

    limits = {k: TINY["parity"][k] for k in
              ("loss_rtol", "grad_norm_rtol", "grad_rel_l2")}
    model = xing4._model(TINY, TINY["parity"]["layers"])
    seeded = nn.meta.unbox(xing4._init(model, 40)(jax.random.PRNGKey(4)))
    at_seed = _readings(system, reference, seeded, batch)
    assert all(at_seed[k] <= limits[k] for k in limits), at_seed
    moved = nn.meta.unbox(case.init(jax.random.PRNGKey(4)))
    off = _readings(system, reference, moved, batch)
    assert any(off[k] > limits[k] for k in limits), off
    # and the program agrees with the reference from there
    sound = _readings(xing4._loss_fn(model), reference, moved, batch)
    assert all(sound[k] <= limits[k] for k in limits), sound


def test_the_reference_imports_nothing_of_the_program():
    source = (REPO / "benchmark/configs/xing4.py").read_text()
    tree = ast.parse(source)
    references = {"sinkhorn_reference", "hyper_connection_reference",
                  "yarn_inverse_frequencies", "_rotate",
                  "latent_attention_reference", "gated_mlp_reference",
                  "experts_reference", "reference_loss"}
    found = set()
    for node in tree.body:      # at module level: no import of it at all
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [a.name for a in node.names] + \
                [getattr(node, "module", "") or ""]
            assert not any(n.startswith("horovod_tpu") for n in names)
        if isinstance(node, ast.FunctionDef) and node.name in references:
            found.add(node.name)
            text = ast.unparse(node)
            for word in ("horovod_tpu", "ragged_dot", "pallas", "argsort",
                         "jax.checkpoint(functools"):
                assert word not in text, (node.name, word)
    assert found == references
    # Sinkhorn as rounds written out over the last two axes, attention
    # through the (T, T) scores
    assert "for _ in range(config['hc_sinkhorn_iters'])" in ast.unparse(tree)
    assert "jnp.tril(jnp.ones((t, t), bool))" in source


def test_configuration_holds_every_published_key_but_the_reduced_ones():
    """Against the catalog's row where the guides are installed, and
    against the widths written here in any case."""
    row = PUBLISHED
    assert (row["hidden_size"], row["num_attention_heads"],
            row["q_lora_rank"], row["kv_lora_rank"],
            row["qk_nope_head_dim"], row["qk_rope_head_dim"],
            row["v_head_dim"]) == (3584, 32, 768, 512, 128, 64, 128)
    assert (row["intermediate_size"], row["moe_intermediate_size"],
            row["router_experts"], row["num_experts_per_tok"],
            row["routed_scaling_factor"], row["n_shared_experts"]) \
        == (9216, 1024, 64, 4, 2, 1)
    assert (row["hc_mult"], row["hc_sinkhorn_iters"], row["hc_eps"],
            row["mhc_h_res_clamp_min"], row["mhc_h_res_clamp_max"]) \
        == (4, 20, 1e-6, -30, 30)
    assert row["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert row["num_hidden_layers"] == 40 and row["num_layers"] == 5
    assert row["first_k_dense_replace"] == 2
    assert row["layers"] == "*D" + "*E" * 4
    assert row["experts_held"] == [0, 8] and row["n_routed_experts"] == 8
    assert row["vocab_size"] * 8 == 131072
    assert row["num_nextn_predict_layers"] == 0
    assert sorted(row["reduced"]) == [
        "n_routed_experts", "num_layers", "num_nextn_predict_layers",
        "vocab_size"]
    assert "one of 8 chips that share each layer" in row["stands_for"]
    for key in ("hyper_connection_form", "hyper_connection_initial_values",
                "rotation_pair_layout", "yarn", "scoring", "selection_bias",
                "router_matrix", "learning_rate_warmup", "dense_layers"):
        assert key in row["assumed"], key
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        return
    published = next(
        r for r in map(json.loads, catalog.read_text().splitlines())
        if r["source_url"] == row["source"])["config"]
    differ = {k for k, v in published.items() if row.get(k) != v}
    assert differ == {"n_routed_experts", "vocab_size",
                      "num_nextn_predict_layers"}


def test_the_cut_s_parameters_are_the_issue_s_arithmetic():
    """759.5M parameters at the published widths: counted from the
    model's own shapes, nothing initialised."""
    model = xing4._model(PUBLISHED, PUBLISHED["layers"])
    shapes = jax.eval_shape(xing4._init(model, 128), jax.random.PRNGKey(0))
    import flax.linen as nn

    def count(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(nn.meta.unbox(tree)))

    p = shapes["params"]
    assert count(p["layer_0"]["attn"]) == pytest.approx(28.41e6, rel=1e-3)
    assert count(p["layer_0"]["hc"]) == 14336 * 24 + 14336 + 3 + 4 + 4 + 16
    assert count(p["layer_1"]["mlp"]) == 3 * 3584 * 9216
    assert count(p["layer_3"]["moe"]) == \
        (8 + 1) * 3 * 3584 * 1024 + 3584 * 64 + 64
    assert count(p) == pytest.approx(759.5e6, rel=1e-3)


def test_flops_a_token_and_the_two_width_flash_cost_from_shapes():
    """The issue's count: 2.85 GFLOP a token, 0.63 of it attention's
    products; seven causal products a block pair, four 192 wide and
    three 128 wide, 3.09 TFLOP a step — 15.7 ms at the chip's peak."""
    per_token = latent_flops.latent_lm_flops_per_token(PUBLISHED, 4096)
    scores = 5 * 3 * 4096 * 32 * (192 + 128)
    assert per_token == pytest.approx(2.851e9, rel=1e-3)
    assert scores == pytest.approx(0.629e9, rel=1e-3)
    weights = (per_token - scores) / 6
    attn, hc = 28_409_856, 4 * 3584 * 24
    expert = 3 * 3584 * 1024
    assert weights == 5 * attn + 10 * hc + 3 * 3584 * 9216 \
        + 4 * (3584 * 64 + expert + 0.5 * expert) + 3584 * 16384
    cost = latent_flops.latent_flash_step_cost(32, 4096, 192, 128, 5)
    assert cost["flops"] == 32 * 5 * 4096 ** 2 * (4 * 192 + 3 * 128)
    assert cost["flops"] == pytest.approx(3.09e12, rel=1e-3)
    assert cost["bytes"] == 32 * 5 * (6 * 4096 * 320 * 2 + 2 * 4096 * 4)
    assert cost["flops"] / 197e12 == pytest.approx(15.7e-3, rel=2e-3)
    # at equal widths it is the accepted one-width cost
    assert latent_flops.latent_flash_step_cost(16, 4096, 128, 128, 16) \
        == flops.flash_step_cost(16, 4096, 128, 16)
    gmm = latent_flops.swiglu_grouped_matmul_step_cost(2048, 3584, 1024, 8, 4)
    assert gmm["flops"] == 4 * 9 * 2 * 2048 * 3584 * 1024
    built = xing4.build(PUBLISHED, cells.resolve(CELL).job, 1, seed=0)
    assert built.kernel_cost["gqa_flash"] == cost
    assert built.kernel_cost["grouped_matmul"] == gmm
    assert built.flops_per_unit == per_token and built.kernel_operand is None


# ---------------------------------------------------------------------------
# device time under ``hc``, on a hand-made trace
# ---------------------------------------------------------------------------

STEP = """\
HloModule jit_step

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %m = f32[8] multiply(%p, %p), metadata={op_name="jit(step)/layer_0/hc/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %fusion.1 = f32[8] fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(HybridLM)/layer_0/hc/rsqrt"}
  %fusion.2 = f32[8] fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(HybridLM)/layer_0/attn/q_a/dot_general"}
  %custom-call.3 = f32[8] custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(HybridLM)/layer_0/attn/pallas_call"}
  %fusion.4 = f32[8] fusion(%custom-call.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(HybridLM))/layer_0/hc/concatenate"}
  %fusion.5 = f32[8] fusion(%fusion.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(HybridLM))/rematted_computation/layer_3/hc/div"}
  %fusion.6 = f32[8] fusion(%fusion.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/layer_3/moe/shared/dot_general"}
  ROOT %fusion.7 = f32[8] fusion(%fusion.6), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/hchc/ln_f/mul"}
}
"""


class _Observed:
    def __init__(self, text, traced_steps=2):
        self.hlo_text, self.traced_steps = text, traced_steps
        self.trace = {"busy_s": 1.0}
        self.cell = cells.resolve(CELL)


def test_device_time_under_hc_is_added_up_forward_recomputed_and_backward(
        monkeypatch):
    dev = "/device:TPU:0"

    def op(name, start, duration):
        return (dev, trace.OPS_LINE, f"%{name} = f32[8] x()", start, duration)
    events = [op("fusion.1", 0, 100), op("fusion.2", 100, 50),
              op("custom-call.3", 200, 300), op("fusion.4", 600, 70),
              op("fusion.5", 700, 30), op("fusion.6", 800, 40),
              op("fusion.7", 900, 999),
              (dev, trace.MODULES_LINE, "jit_step", 0, 1900)]
    known = hc_ms._under_hc(STEP)
    # (a fused computation's own operations never run under their name)
    assert set(known) == {"m", "fusion.1", "fusion.4", "fusion.5"}
    assert modules.reduce_events(events, known)["module_s"] \
        == {"hc": pytest.approx(200e-9)}
    # the accepted readers see the same step as before: hc is none of
    # their kinds
    mixers = modules.reduce_events(events, modules.read_step(STEP))
    assert mixers["module_s"]["attn"] == pytest.approx(350e-9)
    assert mixers["kernel_s"] == {"gqa_flash": pytest.approx(300e-9)}
    monkeypatch.setattr(trace, "newest_xplane", lambda root: "profile")
    monkeypatch.setattr(trace, "load_events", lambda path: events)
    assert hc_ms.read(_Observed(STEP)) == pytest.approx(200e-9 / 2 * 1e3)
    # a step with no such scope — a parent commit, any other model —
    # reads as nothing, and so does an untraced run
    assert hc_ms.read(_Observed(STEP.replace("/hc/", "/other/"))) is None
    untraced = _Observed(STEP)
    untraced.trace = {}
    assert hc_ms.read(untraced) is None


def test_the_new_reader_keeps_to_its_cell_and_the_kernel_readers_apply():
    mine = cells.resolve(CELL)
    applying = {m["name"] for m in mine.per_layer
                if importlib.import_module(
                    f"benchmark.metrics.{m['name']}").applies(
                        mine.config, mine.job)}
    assert {"hc_ms", "attn_ms", "moe_ms", "moe_experts_ms", "gqa_flash_ms",
            "gqa_flash_roofline", "grouped_matmul_ms", "input_wait_ms",
            "compute_ms", "device_idle_share"} <= applying
    assert not {"flash_ms", "flash_roofline", "ssm_ms", "ssd_ms",
                "exchange_ms", "collective_ms"} & applying
    for name in ("nemotron3nano-s8192-b1", "lm871m-s4096-b1",
                 "resnet50-b256", "lm871m-s1024-b6-zero4"):
        other = cells.resolve(name)
        assert not hc_ms.applies(other.config, other.job)
    zero = cells.resolve("lm871m-s1024-b6-zero4")
    assert zero.job["train_step"] == {"mode": "shard_map",
                                      "shard_optimizer_states": True}
    dp = cells.resolve("lm871m-s1024-b6-dp4")
    assert {k: v for k, v in zero.job.items()
            if k not in ("why", "train_step")} \
        == {k: v for k, v in dp.job.items() if k not in ("why", "train_step")}
