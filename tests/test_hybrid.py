"""The hybrid LM's own pieces: the chunked Mamba-2 scan against the
recurrence, grouped-query attention against repeated heads, dropless
top-k routing over held experts at any imbalance, the routing probe, and
what the model says of itself to telemetry.  (System against the plain
reference: tests/benchmark_harness/test_benchmark_hybrid.py.)"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu import telemetry
from horovod_tpu.models import (
    HybridConfig,
    HybridLM,
    TransformerConfig,
    TransformerLM,
    expert_load,
    hybrid_lm_loss,
)
from horovod_tpu.models.hybrid import ssd_chunked
from horovod_tpu.models.transformer import Attention
from horovod_tpu.parallel.expert import (
    held_assignments,
    held_expert_ffn,
    topk_routing,
)


def tiny(**kw):
    base = dict(vocab_size=64, pattern="EM*", d_model=32, dtype=jnp.float32,
                num_heads=4, num_kv_heads=2, head_dim=16, mamba_heads=4,
                mamba_head_dim=8, mamba_groups=2, ssm_state=16, chunk=8,
                num_experts=16, experts_held=(4, 8), top_k=3,
                expert_width=24, shared_width=48)
    base.update(kw)
    return HybridConfig(**base)


def recurrence(x, dt, a, b, c):
    """``h_t = exp(dt_t a) h_{t-1} + dt_t b_t (x) x_t``, ``y_t = c_t.h_t``,
    one step at a time."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    b, c = (jnp.repeat(v, h // g, axis=2) for v in (b, c))

    def one(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    _, y = lax.scan(one, jnp.zeros((bsz, h, p, n)),
                    tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


@pytest.mark.parametrize("seq", [32, 8, 40, 7, 1])
def test_chunked_scan_is_the_recurrence(seq):
    """At lengths that are and are not multiples of the chunk (8):
    values and gradients."""
    keys = jax.random.split(jax.random.PRNGKey(seq), 5)
    x = jax.random.normal(keys[0], (2, seq, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (2, seq, 4)))
    a = -jnp.exp(jax.random.normal(keys[2], (4,)))
    b = jax.random.normal(keys[3], (2, seq, 2, 16))
    c = jax.random.normal(keys[4], (2, seq, 2, 16))
    chunked = functools.partial(ssd_chunked, chunk=8)
    np.testing.assert_allclose(chunked(x, dt, a, b, c),
                               recurrence(x, dt, a, b, c),
                               rtol=2e-4, atol=2e-4)

    def scalar(f):
        return lambda *args: jnp.sum(jnp.sin(f(*args)))
    got = jax.grad(scalar(chunked), argnums=range(5))(x, dt, a, b, c)
    want = jax.grad(scalar(recurrence), argnums=range(5))(x, dt, a, b, c)
    for u, v in zip(got, want):
        np.testing.assert_allclose(u, v, rtol=2e-3, atol=2e-3)


def test_gqa_is_mha_over_repeated_key_value_heads():
    cfg = TransformerConfig(num_heads=4, num_kv_heads=2, head_width=16,
                            d_model=24, dtype=jnp.float32, rotary=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 24))
    pos = jnp.arange(12)
    gqa = Attention(cfg)
    variables = gqa.init(jax.random.PRNGKey(1), x, pos)
    kernel = jax.tree_util.tree_leaves(variables["params"]["qkv"])[0]
    assert kernel.shape == (24, (4 + 2 * 2) * 16)
    # the same weights as four key/value heads, each written out twice
    q, k, v = jnp.split(kernel, [64, 96], axis=-1)
    wide = jnp.concatenate(
        [q] + [jnp.repeat(m.reshape(24, 2, 16), 2, axis=1).reshape(24, 64)
               for m in (k, v)], axis=-1)
    mha = Attention(TransformerConfig(num_heads=4, head_width=16,
                                      d_model=24, dtype=jnp.float32,
                                      rotary=False))
    boxed = mha.init(jax.random.PRNGKey(1), x, pos)
    mha_vars = {"params": {
        "qkv": jax.tree_util.tree_map(lambda _: wide,
                                      boxed["params"]["qkv"]),
        "proj": variables["params"]["proj"]}}
    np.testing.assert_allclose(gqa.apply(variables, x, pos),
                               mha.apply(mha_vars, x, pos),
                               rtol=1e-5, atol=1e-5)


def test_the_new_attention_fields_leave_the_default_step_as_it_was():
    """Defaults, and the same head counts and width spelt out, lower to
    the same program text: the compile-cache key of a TransformerLM
    step does not move."""
    tokens = jnp.zeros((2, 16), jnp.int32)
    texts = []
    for extra in ({}, dict(num_kv_heads=4, head_width=8, rotary=True)):
        model = TransformerLM(TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=4, d_model=32, d_ff=64,
            dtype=jnp.float32, **extra))
        variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
        texts.append(jax.jit(model.apply).lower(variables, tokens).as_text())
    assert texts[0] == texts[1]
    assert TransformerConfig(num_heads=4, d_model=32).head_dim == 8


def test_topk_routing_scores_weights_and_bias():
    scores = jnp.array([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 3.0, 1.0]])
    idx, w = topk_routing(scores, jnp.zeros(4), 2, scale=2.5)
    assert idx.tolist() == [[0, 1], [2, 3]]
    s = jax.nn.sigmoid(scores)
    np.testing.assert_allclose(
        w[0], 2.5 * s[0, :2] / jnp.sum(s[0, :2]), rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 2.5, rtol=1e-6)
    # the bias moves the choice and not the weights
    idx, w = topk_routing(scores, jnp.array([0.0, 0.0, 0.0, 5.0]), 2, 1.0)
    assert sorted(idx[0].tolist()) == [0, 3]
    np.testing.assert_allclose(
        jnp.sort(w[0]), jnp.sort(s[0, jnp.array([0, 3])]
                                 / (s[0, 0] + s[0, 3])), rtol=1e-6)


def _grouped(params, rows, group_sizes):
    up, down = params
    hidden = jnp.square(jax.nn.relu(lax.ragged_dot(rows, up, group_sizes)))
    return lax.ragged_dot(hidden, down, group_sizes)


def _masked_loop(x, idx, w, held, params):
    up, down = params
    y = 0.0
    for e in range(*held):
        m = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        y = y + m[:, None] * (
            jnp.square(jax.nn.relu(x @ up[e - held[0]])) @ down[e - held[0]])
    return y


@pytest.mark.parametrize("crowd", [0.0, 4.0, 50.0])
def test_no_assignment_is_dropped_at_any_imbalance(crowd):
    """A router forced to send most tokens to two held experts (``crowd``
    is added to their scores): every assignment that lands is computed —
    the output is the masked loop's, through whichever buffer the count
    picks, the one that holds every assignment of the step among them."""
    tokens, d, experts, top_k, held = 96, 16, 16, 3, (4, 8)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(keys[0], (tokens, d))
    scores = jax.random.normal(keys[1], (tokens, experts))
    scores = scores.at[:, 4:6].add(crowd)
    params = (jax.random.normal(keys[2], (4, d, 24)) * 0.3,
              jax.random.normal(keys[3], (4, 24, d)) * 0.3)
    idx, w = topk_routing(scores, jnp.zeros(experts), top_k, 2.5)
    order, sizes = held_assignments(idx, held)
    landed = int(jnp.sum((idx >= 4) & (idx < 8)))
    assert int(jnp.sum(sizes)) == landed
    if crowd == 50.0:       # both crowded experts take every token
        assert sizes.tolist()[:2] == [tokens, tokens]
        assert landed > tokens * 2            # the largest buffer
    assert sorted(order.tolist()) == list(range(tokens * top_k))

    def system(x, w, params):
        return held_expert_ffn(x, idx, w, held, _grouped, params)

    def plain(x, w, params):
        return _masked_loop(x, idx, w, held, params)

    np.testing.assert_allclose(jax.jit(system)(x, w, params),
                               plain(x, w, params), rtol=1e-4, atol=1e-4)
    cot = jax.random.normal(jax.random.PRNGKey(9), (tokens, d))
    got = jax.jit(jax.grad(lambda *a: jnp.sum(system(*a) * cot),
                           argnums=(0, 1, 2)))(x, w, params)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * cot),
                    argnums=(0, 1, 2))(x, w, params)
    for u, v in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(u, v, rtol=1e-3, atol=1e-3)


def test_rows_past_the_last_assignment_are_never_read():
    """A grouped matmul need not touch the buffer's rows past the last
    group (the kernel on the chip leaves them as they were): whatever
    stands there, in the forward and in the backward pass, reaches
    neither a token nor a gradient."""
    tokens, d, top_k, held = 32, 8, 2, (0, 2)
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(keys[0], (tokens, d))
    idx, w = topk_routing(jax.random.normal(keys[1], (tokens, 8)),
                          jnp.zeros(8), top_k)
    params = (jax.random.normal(keys[2], (2, d, 12)) * 0.3,
              jax.random.normal(keys[2], (2, 12, d)) * 0.3)

    @jax.custom_vjp
    def spoil(rows, landed):
        return jnp.where((jnp.arange(rows.shape[0]) < landed)[:, None],
                         rows, jnp.nan)

    spoil.defvjp(lambda rows, landed: (spoil(rows, landed), landed),
                 lambda landed, g: (spoil(g, landed), None))

    def untouched(params, rows, sizes):
        return spoil(_grouped(params, spoil(rows, jnp.sum(sizes)), sizes),
                     jnp.sum(sizes))

    def system(grouped_fn, x, w, params):
        return jnp.sum(held_expert_ffn(x, idx, w, held, grouped_fn,
                                       params) ** 2)

    assert 0 < int(jnp.sum((idx >= 0) & (idx < 2))) < tokens * top_k
    got = jax.jit(jax.value_and_grad(functools.partial(system, untouched),
                                     argnums=(0, 1, 2)))(x, w, params)
    want = jax.value_and_grad(functools.partial(system, _grouped),
                              argnums=(0, 1, 2))(x, w, params)
    for u, v in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(u))
        np.testing.assert_allclose(u, v, rtol=1e-5, atol=1e-6)


def test_a_grouped_matmul_runs_once_forward_in_a_train_step():
    """Under the model's block rematerialisation too: an expert block is
    not rematerialised as a block, and the layer's own rematerialisation
    keeps the grouped matmuls' results — so the gradient of the loss
    holds, for each of the layer's buffers, two of them forward and four
    for the gradients, none a second time.  (What a step's time follows
    is the rows that land times the grouped matmuls that read them.)"""
    model = HybridLM(tiny(pattern="ME", remat_policy="full"))
    tokens = jnp.zeros((2, 16), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    batch = {"inputs": tokens, "labels": tokens}
    jaxpr = jax.make_jaxpr(jax.grad(
        functools.partial(hybrid_lm_loss, model)))(variables, batch)

    def count(jaxpr, name):
        return sum((eqn.primitive.name == name)
                   + sum(count(sub, name)
                         for sub in jax.core.jaxprs_in_params(eqn.params))
                   for eqn in jaxpr.eqns)

    buffers = 3         # one row a token, two, top_k = 3
    assert count(jaxpr.jaxpr, "ragged_dot_general") == buffers * 6


def test_config_refuses_what_it_cannot_run():
    with pytest.raises(ValueError, match="a layer is one of"):
        tiny(pattern="EMX")
    with pytest.raises(ValueError, match="no range of the 16 experts"):
        tiny(experts_held=(12, 20))


@pytest.mark.parametrize("train_router", [True, False])
@pytest.mark.parametrize("remat", [None, "full"])
def test_model_trains_a_step_and_the_bias_takes_no_gradient(remat,
                                                            train_router):
    model = HybridLM(tiny(pattern="EMEM*", remat_policy=remat,
                          train_router=train_router))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 21), 0, 64)
    variables = model.init(jax.random.PRNGKey(1), tokens[:, :-1])
    moe = variables["params"]["layer_0"]["moe"]
    assert moe["router"].shape == (32, 16)          # all 16 wide
    assert moe["experts_up"].shape == (4, 32, 24)   # the 4 held
    batch = {"inputs": tokens[:, :-1], "labels": tokens[:, 1:]}
    loss, grads = jax.jit(jax.value_and_grad(
        functools.partial(hybrid_lm_loss, model)))(variables, batch)
    assert float(loss) == pytest.approx(np.log(64), rel=0.2)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        assert np.all(np.isfinite(g)), name
        if "moe" in name and ("bias" in name or (
                "router" in name and not train_router)):
            assert not np.any(g)    # frozen
        elif "conv_bias" not in name:
            assert np.any(g), name


def kernel_shaped(**kw):
    """``E M *`` at a small width whose Mamba shapes ``ssd_scan`` has a
    kernel for: chunk 128, state 128, one group of 2 heads x 64."""
    return tiny(mamba_heads=2, mamba_head_dim=64, mamba_groups=1,
                ssm_state=128, chunk=128, flash_block=128, **kw)


def test_the_scan_s_kernels_give_the_einsum_path_s_loss_and_gradient(
        remat="full"):
    """The same parameters through the model with its Pallas kernels
    interpreted (the scan's forward and backward among them, inside a
    rematerialised block as the benchmark's cell runs them) and through
    the ``jax.numpy`` forms."""
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 257), 0, 64)
    batch = {"inputs": tokens[:, :-1], "labels": tokens[:, 1:]}
    einsum = HybridLM(kernel_shaped(remat_policy=remat))
    kernels = HybridLM(kernel_shaped(remat_policy=remat,
                                     attention_impl="flash",
                                     flash_interpret=True))
    variables = einsum.init(jax.random.PRNGKey(1), batch["inputs"])

    def step(model):
        return jax.jit(jax.value_and_grad(
            functools.partial(hybrid_lm_loss, model)))
    assert "pallas_call" not in str(jax.make_jaxpr(step(einsum))(
        variables, batch))
    scans = str(jax.make_jaxpr(step(kernels))(variables, batch))
    assert "ssd_fwd" in scans and "ssd_bwd" in scans
    want_loss, want = step(einsum)(variables, batch)
    got_loss, got = step(kernels)(variables, batch)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    for (path, u), v in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            u, v, rtol=2e-3, atol=2e-5 * float(jnp.max(jnp.abs(v))) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


def test_routing_probe_counts_what_lands_here():
    cfg = tiny(pattern="EME*")
    model = HybridLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0, 64)
    variables = model.init(jax.random.PRNGKey(1), tokens)
    out = jax.jit(functools.partial(expert_load, model))(variables, tokens)
    assert out["local_share"].shape == (2,)         # two expert layers
    assert np.all((out["local_share"] > 0) & (out["local_share"] < 1))
    assert np.all(out["max_over_mean"] >= 1.0)
    # a bias that sends every token to the four held experts first
    biased = jax.tree_util.tree_map(lambda a: a, variables)
    for layer in ("layer_0", "layer_2"):
        biased["params"][layer]["moe"]["bias"] = \
            jnp.zeros(16).at[4:8].set(10.0)
    out = jax.jit(functools.partial(expert_load, model))(biased, tokens)
    np.testing.assert_allclose(out["local_share"], 1.0)     # top-3 of 4
    # the step itself records nothing
    assert "intermediates" not in model.apply(
        variables, tokens, mutable=["batch_stats"])[1]


@pytest.mark.parametrize("cfg,seq,chunks,impl,calls", [
    (tiny, 16, 2, "einsum", 0),
    (functools.partial(kernel_shaped, pattern="M", flash_interpret=True),
     256, 2, "mosaic", 2),
    (functools.partial(kernel_shaped, pattern="M", flash_interpret=True,
                       remat_policy="full"), 128, 1, "mosaic", 3),
])
def test_a_traced_step_names_its_shapes_to_the_compile_span(
        hvd_runtime, cfg, seq, chunks, impl, calls):
    import optax

    hvd = hvd_runtime
    was_on = telemetry.enabled()
    telemetry.enable()
    model = HybridLM(cfg())
    tokens = np.zeros((8, seq + 1), np.int32)
    step = hvd.DistributedTrainStep(
        functools.partial(hybrid_lm_loss, model), optax.sgd(0.1))
    params, opt_state = step.init(
        model.init(jax.random.PRNGKey(0), tokens[:1, :-1]))
    since = telemetry.spans._now()
    batch = step.shard_batch({"inputs": tokens[:, :-1],
                              "labels": tokens[:, 1:]})
    jax.block_until_ready(step(params, opt_state, batch))
    compiles = [s for s in telemetry.spans.snapshot(since=since)
                if s.name == "train_step.compile"]
    assert compiles and \
        compiles[-1].attrs["hybrid_pattern"] == model.cfg.pattern
    rows = compiles[-1].attrs["tokens_per_step"]
    assert compiles[-1].attrs["experts_held"] == 4
    assert compiles[-1].attrs["assignments_per_step"] == rows * 3
    assert compiles[-1].attrs["expert_buffer_rows"] == rows * 3
    assert compiles[-1].attrs["ssd_chunks_per_sequence"] == chunks
    # which form of the scan the step holds, and its Mosaic calls a
    # layer: forward, backward, and the forward again where the block is
    # rematerialised
    assert compiles[-1].attrs["ssd_impl"] == impl
    assert compiles[-1].attrs["ssd_kernel_calls_per_layer"] == calls
    assert telemetry.value("hvd_hybrid_ssd_kernel_calls_per_layer") == calls
    for name in ("mosaic", "einsum"):
        assert telemetry.value("hvd_hybrid_ssd_impl", impl=name) \
            == (name == impl)
    assert telemetry.value("hvd_hybrid_experts_held") == 4
    assert telemetry.value("hvd_hybrid_assignments_per_step") == rows * 3
    assert telemetry.value("hvd_hybrid_expert_buffer_rows") == rows * 3
    if not was_on:
        telemetry.disable()


def test_annotate_reaches_the_innermost_open_span_only():
    telemetry.annotate(lost=1)          # none open: nothing happens
    with telemetry.span("outer") as outer:
        with telemetry.span("inner", attrs={"a": 1}) as inner:
            telemetry.annotate(b=2)
        telemetry.annotate(c=3)
    assert inner.attrs == {"a": 1, "b": 2}
    assert outer.attrs == {"c": 3}


def test_the_hybrid_example_trains():
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "examples" \
        / "hybrid_lm_example.py"
    spec = importlib.util.spec_from_file_location("hybrid_lm_example", path)
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    losses = example.main(["--steps", "8", "--seq-len", "32"])
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_grouped_matmul_kernel_is_ragged_dot():
    """The TPU path (megablox, interpreted here) against
    ``jax.lax.ragged_dot``: a group without rows, an ``n`` that is no
    multiple of the block, values and gradients; rows that fit no row
    block fall back."""
    from horovod_tpu.ops.pallas_kernels import grouped_matmul

    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    lhs = jax.random.normal(keys[0], (256, 384))
    rhs = jax.random.normal(keys[1], (3, 384, 464)) * 0.05
    sizes = jnp.array([100, 0, 156], jnp.int32)

    def kernel(lhs, rhs):
        return grouped_matmul(lhs, rhs, sizes, interpret=True)

    def plain(lhs, rhs):
        return lax.ragged_dot(lhs, rhs, sizes)

    np.testing.assert_allclose(kernel(lhs, rhs), plain(lhs, rhs),
                               rtol=1e-4, atol=1e-4)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) ** 2), argnums=(0, 1))(
        lhs, rhs)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), argnums=(0, 1))(
        lhs, rhs)
    for u, v in zip(got, want):
        np.testing.assert_allclose(u, v, rtol=1e-3, atol=1e-3)
    assert not np.any(got[1][1])            # the empty group's matrix
    odd = grouped_matmul(lhs[:100], rhs, jnp.array([50, 0, 50]),
                         interpret=True)    # 100 rows: no row block
    np.testing.assert_allclose(
        odd, lax.ragged_dot(lhs[:100], rhs, jnp.array([50, 0, 50])),
        rtol=1e-5, atol=1e-5)


def test_the_router_runs_in_float32_at_the_highest_precision():
    """In a bfloat16 model too: the one matmul whose result is a discrete
    choice.  (On the chip a bfloat16 router is not told apart by the
    parity check — a flipped choice moves the gradient's direction as far
    either way — so its precision is pinned here.)"""
    from horovod_tpu.models.hybrid import ExpertMixer

    cfg = tiny(dtype=jnp.bfloat16)
    u = jnp.zeros((1, 8, 32), jnp.bfloat16)
    mixer = ExpertMixer(cfg)
    variables = mixer.init(jax.random.PRNGKey(0), u)
    jaxpr = jax.make_jaxpr(mixer.apply)(variables, u)

    def dots(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    routers = [e for e in dots(jaxpr.jaxpr)
               if e.outvars[0].aval.shape[-1] == cfg.num_experts]
    assert len(routers) == 1
    (router,) = routers
    assert all(v.aval.dtype == jnp.float32 for v in router.invars)
    assert router.outvars[0].aval.dtype == jnp.float32
    assert router.params["precision"] == (lax.Precision.HIGHEST,) * 2
