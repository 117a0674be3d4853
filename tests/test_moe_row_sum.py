"""The expert layer's rows-to-tokens sum (``ops/pallas_kernels.moe_row_sum``)
against the ``jax.numpy`` lines it stands in for — ``held_expert_ffn``'s
combine, ``zeros.at[token_of].add(w * rows)``, and the transpose of its
dispatch's gather — interpreted on the CPU in fp32: tokens on two and on
``top_k`` held experts, an empty expert, nothing landing, everything
landing, both MoE cells' width and ``top_k``; what stands past the last
group never reaching a token or a gradient; ``held_expert_ffn``'s
gradients with the kernel against autodiff of the fall-back; the
stable-sort invariant the kernel leans on; the rule that selects it, what
a traced model says of it, and the fall-back as the program it was."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu import telemetry
from horovod_tpu.models import HybridConfig, HybridLM, hybrid_lm_loss
from horovod_tpu.ops import pallas_kernels as pk
from horovod_tpu.parallel import expert
from horovod_tpu.parallel.expert import (
    held_assignments,
    held_expert_ffn,
    topk_routing,
)

f32 = jnp.float32

# name: (tokens, d, experts, top_k, held, scores added to the held experts,
#        held experts that take nothing)
CASES = {
    # a token lands on none, one or two of the held experts
    "two_held_of_top4": (256, 128, 16, 4, (4, 12), 0.0, ()),
    # every choice of every token is a held expert: the largest buffer
    "all_top_k_held": (256, 128, 8, 4, (0, 8), 0.0, ()),
    "everything_lands_top6": (128, 128, 8, 6, (0, 8), 0.0, ()),
    "an_empty_expert": (256, 128, 16, 4, (0, 8), 1.0, (2, 5)),
    "nothing_lands": (256, 128, 16, 2, (8, 16), -50.0, ()),
    "one_crowded_expert": (384, 256, 32, 4, (8, 16), 0.0, ()),
    # the two MoE cells' width, top_k and held share, the tokens cut
    "xing4": (256, 3584, 64, 4, (0, 8), 0.5, ()),
    "nemotron3nano": (256, 2688, 128, 6, (0, 8), 0.5, ()),
}


def routed(name, seed=0):
    tokens, d, experts, top_k, held, boost, empty = CASES[name]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    scores = jax.random.normal(keys[0], (tokens, experts))
    scores = scores.at[:, held[0]:held[1]].add(boost)
    if name == "one_crowded_expert":
        scores = scores.at[:, held[0] + 3].add(50.0)
    for g in empty:
        scores = scores.at[:, held[0] + g].add(-100.0)
    idx, w = topk_routing(scores, jnp.zeros(experts), top_k, 2.0)
    return tokens, d, top_k, held, idx, w, keys[1]


def scatter_lines(rows, token_of, w, real, tokens):
    """The fall-back's combine (``held_expert_ffn``), in the rows' type."""
    out = jnp.where(real[:, None], rows, 0) * w[:, None].astype(rows.dtype)
    return jnp.zeros((tokens, rows.shape[-1]), rows.dtype) \
        .at[token_of].add(out)


def buffer_of(name, rows_a_token, spoil=True):
    """A sorted buffer of ``tokens * rows_a_token`` rows for the case's
    routing, NaN past the last group, with the lines' operands and the
    kernel's."""
    tokens, d, top_k, held, idx, w, key = routed(name)
    order, sizes = held_assignments(idx, held)
    cap, landed = tokens * rows_a_token, int(jnp.sum(sizes))
    assert landed <= cap
    picked = order[:cap]
    token_of, real = picked // top_k, jnp.arange(cap) < landed
    rows = jax.random.normal(key, (cap, d))
    if spoil:
        rows = jnp.where(real[:, None], rows, jnp.nan)
    tile = pk.moe_row_sum_tile(tokens, d, top_k, held[1] - held[0], 4)
    starts, pos, wt = pk.moe_row_sum_plan(
        expert._flat_held(idx, held), sizes, w, tile)
    return dict(tokens=tokens, top_k=top_k, landed=landed, sizes=sizes,
                rows=rows, token_of=token_of, real=real, tile=tile,
                w=jnp.where(real, w.reshape(-1)[picked], 0.0),
                starts=starts, pos=pos, wt=wt)


@pytest.mark.parametrize("name,rows_a_token", [
    ("two_held_of_top4", 3), ("two_held_of_top4", 4), ("all_top_k_held", 4),
    ("everything_lands_top6", 6), ("an_empty_expert", 4),
    ("nothing_lands", 1), ("one_crowded_expert", 2), ("xing4", 2),
    ("nemotron3nano", 2)])
def test_the_sum_is_the_scatter_lines(name, rows_a_token):
    b = buffer_of(name, rows_a_token)
    if name in ("all_top_k_held", "everything_lands_top6"):
        assert b["landed"] == b["rows"].shape[0]    # no row is spare
    if name == "nothing_lands":
        assert b["landed"] == 0
    if name == "an_empty_expert":
        assert b["sizes"].tolist()[2] == b["sizes"].tolist()[5] == 0
    if name == "one_crowded_expert":    # a stretch as long as its tile
        assert int(b["sizes"][3]) == b["tokens"]
    got = pk.moe_row_sum(b["rows"], b["starts"], b["pos"], b["wt"],
                         top_k=b["top_k"], interpret=True)
    want = scatter_lines(b["rows"], b["token_of"], b["w"], b["real"],
                         b["tokens"])
    assert np.all(np.isfinite(got))     # nothing past the last group
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # at weight one (the dispatch's transpose): a token's rows added up
    ones = pk.moe_row_sum(b["rows"], b["starts"], b["pos"], b["pos"] >= 0,
                          top_k=b["top_k"], interpret=True)
    np.testing.assert_allclose(
        ones, scatter_lines(b["rows"], b["token_of"],
                            b["real"].astype(f32), b["real"], b["tokens"]),
        rtol=1e-5, atol=1e-5)


def test_in_the_rows_type_the_sum_is_added_in_fp32_and_rounded_once():
    """bf16 rows: the kernel is nearer the fp32 lines than the bf16 lines
    are (those round after every row they add)."""
    b = buffer_of("all_top_k_held", 4, spoil=False)
    rows = b["rows"].astype(jnp.bfloat16)
    truth = scatter_lines(rows.astype(f32), b["token_of"],
                          b["w"].astype(jnp.bfloat16).astype(f32),
                          b["real"], b["tokens"])
    got = pk.moe_row_sum(rows, b["starts"], b["pos"], b["wt"],
                         top_k=b["top_k"], interpret=True)
    lines = scatter_lines(rows, b["token_of"], b["w"], b["real"],
                          b["tokens"])
    assert got.dtype == jnp.bfloat16

    def off(v):
        return float(jnp.max(jnp.abs(v.astype(f32) - truth)))

    assert off(got) <= off(lines) and off(got) < 0.05


@pytest.mark.parametrize("name", ["two_held_of_top4", "all_top_k_held",
                                  "an_empty_expert", "nemotron3nano"])
def test_the_stable_sort_keeps_tokens_ascending_and_single_in_a_run(name):
    """What the kernel leans on: inside a held expert's run the rows
    ascend by token and no token repeats, so a tile's rows of a run are
    one stretch, which ``starts`` bounds and ``pos`` indexes."""
    tokens, d, top_k, held, idx, w, _ = routed(name)
    order, sizes = held_assignments(idx, held)
    token_of = np.asarray(order // top_k)
    ends = np.cumsum(np.asarray(sizes))
    tile = pk.moe_row_sum_tile(tokens, d, top_k, held[1] - held[0], 4)
    starts, pos, wt = pk.moe_row_sum_plan(
        expert._flat_held(idx, held), sizes, w, tile)
    starts = np.asarray(starts).reshape(tokens // tile + 1, -1)
    pos = np.asarray(pos)
    for g, (lo, hi) in enumerate(zip(ends - np.asarray(sizes), ends)):
        run = token_of[lo:hi]
        assert np.all(np.diff(run) > 0)     # ascending, none twice
        assert starts[0, g] == lo and starts[-1, g] == hi
        for i in range(tokens // tile):
            mine = token_of[starts[i, g]:starts[i + 1, g]]
            assert np.all(mine // tile == i)
            # a token's place in the stretch is the one ``pos`` says
            np.testing.assert_array_equal(
                pos[g, mine], np.arange(len(mine)))
        assert np.sum(pos[g] >= 0) == hi - lo
    # the weights by token and run: zero exactly where a token has no row
    assert np.array_equal(np.asarray(wt) > 0, pos >= 0)
    np.testing.assert_allclose(
        jnp.sum(wt), jnp.sum(jnp.where((idx >= held[0]) & (idx < held[1]),
                                       w, 0.0)), rtol=1e-5)


def _grouped(params, rows, group_sizes):
    up, down = params
    hidden = jnp.square(jax.nn.relu(lax.ragged_dot(rows, up, group_sizes)))
    return lax.ragged_dot(hidden, down, group_sizes)


def _ffn_operands(name, dtype, width=64):
    tokens, d, top_k, held, idx, w, key = routed(name)
    keys = jax.random.split(key, 4)
    groups = held[1] - held[0]
    x = jax.random.normal(keys[0], (tokens, d)).astype(dtype)
    params = ((jax.random.normal(keys[1], (groups, d, width))
               * d ** -0.5).astype(dtype),
              (jax.random.normal(keys[2], (groups, width, d))
               * width ** -0.5).astype(dtype))
    cot = jax.random.normal(keys[3], (tokens, d)).astype(dtype)
    return idx, held, x, w, params, cot


@pytest.mark.parametrize("name,dtype,tol", [
    ("two_held_of_top4", f32, 1e-4), ("all_top_k_held", f32, 1e-4),
    ("an_empty_expert", f32, 1e-4), ("nothing_lands", f32, 1e-4),
    ("everything_lands_top6", f32, 1e-4),
    # within bf16 rounding: the lines round after every row they add
    ("two_held_of_top4", jnp.bfloat16, 4e-2),
    ("all_top_k_held", jnp.bfloat16, 4e-2)])
def test_held_expert_ffn_with_the_kernel_is_autodiff_of_the_fall_back(
        name, dtype, tol):
    """Value and the gradients with respect to ``x``, ``weights`` and the
    expert parameters, through whichever buffer the count picks."""
    idx, held, x, w, params, cot = _ffn_operands(name, dtype)

    def scalar(interpret, x, w, params):
        y = held_expert_ffn(x, idx, w, held, _grouped, params,
                            interpret=interpret)
        return jnp.sum(y.astype(f32) * cot.astype(f32)), y

    run = [jax.jit(jax.value_and_grad(functools.partial(scalar, flag),
                                      argnums=(0, 1, 2), has_aux=True))
           for flag in (True, False)]
    got, want = (f(x, w, params) for f in run)
    for u, v in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert u.dtype == v.dtype and np.all(np.isfinite(u.astype(f32)))
        scale = max(1.0, float(jnp.max(jnp.abs(v.astype(f32)))))
        np.testing.assert_allclose(u.astype(f32), v.astype(f32),
                                   rtol=tol, atol=tol * scale)


def test_no_scatter_is_left_where_the_kernel_runs():
    """Neither side's backward puts one back: the jaxpr of the gradient
    holds the sum's calls and no scatter-add over the buffer's rows (the
    one scatter left hands ``dw`` back to (tokens, top_k): distinct
    indices, a scalar a row)."""
    idx, held, x, w, params, cot = _ffn_operands("two_held_of_top4", f32)

    def scalar(interpret, x, w, params):
        return jnp.sum(held_expert_ffn(x, idx, w, held, _grouped, params,
                                       interpret=interpret) * cot)

    def census(interpret):
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name in ("scatter-add", "scatter_add",
                                          "pallas_call"):
                    found.append((eqn.primitive.name,
                                  eqn.outvars[0].aval.shape))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(jax.grad(functools.partial(scalar, interpret),
                                     argnums=(0, 1, 2)))(x, w, params).jaxpr)
        return found

    tokens, d = x.shape
    with_kernel = census(True)
    assert [s for n, s in with_kernel if n != "pallas_call"
            and len(s) == 2 and s[-1] == d] == []
    # a buffer: the combine forward, and the dispatch's transpose
    assert sum(n == "pallas_call" for n, _ in with_kernel) >= 3 * 2
    wide = [s for n, s in census(False) if n != "pallas_call"
            and s == (tokens, d)]
    assert len(wide) >= 3 * 2       # the fall-back's, as they were


def test_rows_past_the_last_assignment_reach_nothing_through_the_kernel():
    """tests/test_hybrid.py's spoiling test where the kernel runs: NaN
    past the last group in the forward buffers and in the backward's
    reaches neither a token nor a gradient."""
    idx, held, x, w, params, _ = _ffn_operands("two_held_of_top4", f32)

    @jax.custom_vjp
    def spoil(rows, landed):
        return jnp.where((jnp.arange(rows.shape[0]) < landed)[:, None],
                         rows, jnp.nan)

    spoil.defvjp(lambda rows, landed: (spoil(rows, landed), landed),
                 lambda landed, g: (spoil(g, landed), None))

    def untouched(params, rows, sizes):
        return spoil(_grouped(params, spoil(rows, jnp.sum(sizes)), sizes),
                     jnp.sum(sizes))

    def system(grouped_fn, interpret, x, w, params):
        return jnp.sum(held_expert_ffn(x, idx, w, held, grouped_fn, params,
                                       interpret=interpret) ** 2)

    got = jax.jit(jax.value_and_grad(
        functools.partial(system, untouched, True),
        argnums=(0, 1, 2)))(x, w, params)
    want = jax.value_and_grad(functools.partial(system, _grouped, False),
                              argnums=(0, 1, 2))(x, w, params)
    for u, v in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(u))
        np.testing.assert_allclose(u, v, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tokens,d,top_k,groups,itemsize,tile", [
    (4096, 3584, 4, 8, 2, 256),         # xing4-s4096-b1
    (8192, 2688, 6, 8, 2, 256),         # nemotron3nano-s8192-b1
    (384, 256, 4, 8, 4, 128),           # 128 where 256 does not divide
    (4096, 3000, 4, 8, 2, None),        # d no whole lane tiles
    (96, 128, 4, 8, 2, None),           # no tile of tokens
    (4096, 4096, 8, 8, 4, 128),         # 256 tokens' rows do not fit VMEM
    (4096, 8192, 8, 8, 4, None),        # nor 128's
])
def test_the_rule_that_selects_the_kernel(tokens, d, top_k, groups,
                                          itemsize, tile):
    assert pk.moe_row_sum_tile(tokens, d, top_k, groups, itemsize) == tile
    dtype = {2: jnp.bfloat16, 4: f32}[itemsize]
    # the default backend here is the CPU: only interpreted
    assert not pk.moe_row_sum_runs_kernel(tokens, d, top_k, groups, dtype)
    assert pk.moe_row_sum_runs_kernel(
        tokens, d, top_k, groups, dtype, interpret=True) == bool(tile)
    if tile:        # what the call asks of VMEM, the chip has
        need = pk._row_sum_vmem_bytes(tile, d, top_k, groups, itemsize)
        assert need <= 64 << 20
        assert pk._row_sum_hold(tile, top_k, groups) % pk._ROW_SUM_TRIP == 0


def small(**kw):
    base = dict(vocab_size=64, pattern="E*E", d_model=128, dtype=f32,
                num_heads=4, num_kv_heads=2, head_dim=32, num_experts=16,
                experts_held=(4, 12), top_k=4, expert_width=32,
                shared_width=64)
    base.update(kw)
    return HybridConfig(**base)


@pytest.mark.parametrize("kw,layers", [
    (dict(flash_interpret=True), 2),            # both E layers
    (dict(), 0),                                # no Mosaic on this backend
    (dict(flash_interpret=True, d_model=96, head_dim=24), 0),   # no tile
])
def test_a_traced_model_says_how_many_layers_run_the_sum(kw, layers):
    model = HybridLM(small(**kw))
    tokens = jnp.zeros((1, 128), jnp.int32)
    was_on = telemetry.enabled()
    telemetry.enable()
    try:
        since = telemetry.spans._now()
        with telemetry.span("probe"):
            jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
        said = [s for s in telemetry.spans.snapshot(since=since)
                if s.name == "probe"][-1].attrs
        assert said["moe_row_sum_layers"] == layers
        assert telemetry.value("hvd_hybrid_moe_row_sum_layers") == layers
    finally:
        if not was_on:
            telemetry.disable()


def test_a_model_with_the_kernel_trains_as_the_one_without():
    """Loss and every gradient of a cut ``* E`` model, the sums by the
    kernel (interpreted) against the ``jax.numpy`` lines."""
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 65), 0, 64)
    batch = {"inputs": tokens[:, :-1], "labels": tokens[:, 1:]}
    variables = HybridLM(small(pattern="*E")).init(jax.random.PRNGKey(1),
                                                   batch["inputs"])
    got, want = (jax.jit(jax.value_and_grad(functools.partial(
        hybrid_lm_loss,
        HybridLM(small(pattern="*E", flash_interpret=flag)))))(
            variables, batch) for flag in (True, False))
    for u, v in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(u, v, rtol=2e-4, atol=2e-5)


# sha256 of the lowered (StableHLO) gradient of ``held_expert_ffn`` at
# ``two_held_of_top4`` on the CPU as the commit before the kernel lowered
# it: where the rule says no, the layer is the program it was
FALL_BACK = \
    "4c3c7e45193eaab854e3f824c326a8cd1a42c3e5b38626bcafec49e9d99841a5"


def test_where_the_rule_says_no_the_layer_is_the_program_it_was():
    idx, held, x, w, params, cot = _ffn_operands("two_held_of_top4", f32)

    def scalar(x, w, params):
        return jnp.sum(held_expert_ffn(x, idx, w, held, _grouped, params)
                       * cot)

    text = jax.jit(jax.grad(scalar, argnums=(0, 1, 2))).lower(
        x, w, params).as_text()
    assert "pallas_call" not in text and "moe_row_sum" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == FALL_BACK
