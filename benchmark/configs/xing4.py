"""``xing4``: Xing4.0-29B-A4B as one rank of an ``ep=8`` deployment holds
five of its layers, and its plain reference.

The system under test is ``horovod_tpu.models.HybridLM`` with
``residual="hc"``: every sublayer — latent attention (a flash kernel with
192-wide q / k and 128-wide v), the dense SwiGLU MLP, the dropless top-4
expert layer that holds 8 of the 64 SwiGLU experts — sits inside a
hyper-connection over four residual streams.  The reference below is the
same mathematics written out in float32 ``jax.numpy``, reading the same
parameter tree and importing nothing of the program: the streams as a
``(batch, seq, 4, width)`` array and the mixing as einsums over them,
Sinkhorn as rounds over the last two axes, YaRN's frequencies and the
pairwise rotation from their formulas, dense masked softmax attention a
few heads at a time, the expert layer as a loop over the held experts
with a 0/1 mask — no sort, no buffer — and the loss over the vocabulary
slice.

Departures from the published model, each also an entry of the JSON:
the cut (5 of 40 layers, 8 of 64 experts held, an eighth of the
vocabulary, no multi-token-prediction module); the selection bias and
the router's matrix take no gradient and the learning rate warms up, as
in ``nemotron3nano`` and for its reasons; the hyper-connection's initial
values and the pair layout of the rotation are assumed.  What the absent
experts would add is left out here as in the program.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from benchmark import latent_flops
from benchmark.configs import Built, ParityCase, nemotron3nano

HEADS_AT_A_TIME = 4     # of the reference's (T, T) scores, so they fit

_rms = nemotron3nano._rms
_matmul = nemotron3nano._matmul
_sampler = nemotron3nano._sampler       # lm871m's Zipf(1.0) unigram
_init = nemotron3nano._init
_loss_fn = nemotron3nano._loss_fn


def _hybrid_config(config: dict, pattern: str):
    from horovod_tpu.models import HybridConfig

    return HybridConfig(
        vocab_size=config["vocab_size"], pattern=pattern,
        d_model=config["hidden_size"], norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]),
        residual="hc", hc_streams=config["hc_mult"],
        hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        hc_clamp=(config["mhc_h_res_clamp_min"],
                  config["mhc_h_res_clamp_max"]),
        attention_kind="latent",
        num_heads=config["num_attention_heads"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        rope_base=config["rope_theta"], rope_scaling=config["rope_scaling"],
        attention_impl=config["attention"],
        flash_block=config["flash_block"],
        flash_interpret=config["flash_interpret"],
        mlp_width=config["intermediate_size"],
        num_experts=config["router_experts"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["n_shared_experts"]
        * config["moe_intermediate_size"],
        routed_scale=config["routed_scaling_factor"],
        expert_act="swiglu", train_router=config["train_router"],
        remat_policy=config["remat_policy"])


def _model(config: dict, pattern: str):
    from horovod_tpu.models import HybridLM

    if config["experts_held"][1] - config["experts_held"][0] \
            != config["n_routed_experts"]:
        raise ValueError("experts_held / n_routed_experts disagree")
    return HybridLM(_hybrid_config(config, pattern))


def build(config: dict, job: dict, chips: int, seed: int) -> Built:
    seq, rows = job["seq"], job["batch_per_chip"]
    if len(config["layers"]) != 2 * config["num_layers"]:
        raise ValueError("layers holds two sublayers a layer of num_layers")
    model = _model(config, config["layers"])
    opt = config["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"xing4 trains with adamw, got {opt}")
    uniform_rows = rows * seq * config["num_experts_per_tok"] \
        * config["n_routed_experts"] / config["router_experts"]
    return Built(
        init=_init(model, seq), loss_fn=_loss_fn(model),
        # the rate climbs to its value over warmup_steps (the JSON's
        # assumed.learning_rate_warmup)
        optimizer=optax.adamw(optax.linear_schedule(
            0.0, opt["learning_rate"], opt["warmup_steps"])),
        sample=_sampler(config, seq, seed), units_per_sample=seq,
        flops_per_unit=latent_flops.latent_lm_flops_per_token(config, seq),
        # two Mosaic kernels a step, on operands of three widths: no one
        # operand is in every call
        kernel_operand=None,
        kernel_cost={
            "gqa_flash": latent_flops.latent_flash_step_cost(
                rows * config["num_attention_heads"], seq,
                config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
                config["v_head_dim"], config["layers"].count("*")),
            # at the uniform expectation: what lands in the timed steps
            # no reader can see yet (PERF.md section 7)
            "grouped_matmul": latent_flops.swiglu_grouped_matmul_step_cost(
                uniform_rows, config["hidden_size"],
                config["moe_intermediate_size"],
                config["n_routed_experts"], config["layers"].count("E"))})


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def sinkhorn_reference(logits, config: dict, dtype=jnp.float32):
    """``exp(logits)``, then ``hc_sinkhorn_iters`` rounds of: every row
    divided by its sum + ``hc_eps``, every column by its sum + ``hc_eps``
    (the matrices over the last two axes).  ``dtype`` only for the
    reading that says what a bfloat16 Sinkhorn would cost."""
    m = jnp.exp(logits.astype(dtype))
    for _ in range(config["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + config["hc_eps"])
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + config["hc_eps"])
    return m.astype(jnp.float32)


def hyper_connection_reference(lp, x, sublayer, config: dict, mm=jnp.matmul,
                               sinkhorn_dtype=jnp.float32):
    """``X' = H_res X + h_post^T F(h_pre X)`` over the streams ``x``:
    (batch, seq, n, width); ``sublayer`` is ``F`` with its pre-norm."""
    n = config["hc_mult"]
    bsz, t, _, width = x.shape
    u = _rms(x.reshape(bsz, t, n * width), lp["norm_scale"],
             config["rms_norm_eps"])
    a_pre, a_post, a_res = jnp.split(mm(u, lp["phi"]), [n, 2 * n], axis=-1)
    g_pre, g_post, g_res = lp["gates"]
    h_pre = jax.nn.sigmoid(g_pre * a_pre + lp["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(g_post * a_post + lp["b_post"])
    h_res = sinkhorn_reference(
        jnp.clip(g_res * a_res.reshape(bsz, t, n, n) + lp["b_res"],
                 config["mhc_h_res_clamp_min"],
                 config["mhc_h_res_clamp_max"]), config, sinkhorn_dtype)
    y = sublayer(jnp.einsum("btj,btjc->btc", h_pre, x))
    return jnp.einsum("btij,btjc->btic", h_res, x) \
        + h_post[..., None] * y[:, :, None, :]


def yarn_inverse_frequencies(config: dict):
    """The 32 rotation frequencies of the 64 rotary dimensions: pair
    ``i`` turns at ``theta^(-2i/64)``; slowed by ``factor`` from the
    pair that completes ``beta_slow`` turns in the original context on,
    untouched up to the pair that completes ``beta_fast``, a linear
    blend between."""
    y, dim = config["rope_scaling"], config["qk_rope_head_dim"]
    base = config["rope_theta"]

    def pair(turns):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(pair(y["beta_fast"])), 0)
    high = min(math.ceil(pair(y["beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        plain = base ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(plain * (1.0 - ramp) + plain / y["factor"] * ramp)
    return jnp.asarray(out, jnp.float32)


def _rotate(x, inv_freq):
    """Pairs (2i, 2i+1) of the last axis turned by position x frequency;
    ``x``: (batch, seq, ..., 2 x pairs)."""
    t = x.shape[1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (-1,))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                      even * jnp.sin(angle) + odd * jnp.cos(angle)],
                     axis=-1).reshape(x.shape)


def latent_attention_reference(lp, u, config: dict, mm=jnp.matmul):
    """``c_q = RMSNorm(u W_dq)``, ``q = c_q W_uq``; ``[c_kv | k_r] = u
    W_dkv``, ``[k_nope | v] = RMSNorm(c_kv) W_ukv``; the 64-wide parts
    rotated, ``k_r`` shared by the heads; causal softmax of ``q k^T s``
    with ``s = 192^-1/2 m^2``; ``W_o``.  Dense scores, a few heads at a
    time."""
    h = config["num_attention_heads"]
    nope, rope, dv = config["qk_nope_head_dim"], \
        config["qk_rope_head_dim"], config["v_head_dim"]
    eps, rank = config["rms_norm_eps"], config["kv_lora_rank"]
    y = config["rope_scaling"]
    bsz, t, _ = u.shape
    c_q = _rms(mm(u, lp["q_a"]["kernel"]), lp["q_a_norm"]["scale"], eps)
    q = mm(c_q, lp["q_b"]["kernel"]).reshape(bsz, t, h, nope + rope)
    down = mm(u, lp["kv_a"]["kernel"])
    c_kv = _rms(down[..., :rank], lp["kv_a_norm"]["scale"], eps)
    kv = mm(c_kv, lp["kv_b"]["kernel"]).reshape(bsz, t, h, nope + dv)
    inv_freq = yarn_inverse_frequencies(config)
    q = jnp.concatenate(
        [q[..., :nope], _rotate(q[..., nope:], inv_freq)], axis=-1)
    k_r = _rotate(down[..., rank:], inv_freq)                # (B, T, 64)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, :, None, :],
                                          (bsz, t, h, rope))], axis=-1)
    v = kv[..., nope:]
    m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
    scale = (nope + rope) ** -0.5 * m * m
    mask = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def some_heads(qkv):
        q, k, v = qkv                                   # (B, T, g, .)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    g = next(s for s in range(min(HEADS_AT_A_TIME, h), 0, -1) if h % s == 0)
    groups = jax.tree_util.tree_map(
        lambda a: jnp.moveaxis(
            a.reshape(bsz, t, h // g, g, a.shape[-1]), 2, 0), (q, k, v))
    o = jnp.moveaxis(jax.lax.map(some_heads, groups), 0, 2)
    return mm(o.reshape(bsz, t, h * dv), lp["o"]["kernel"])


def gated_mlp_reference(lp, u, config: dict, mm=jnp.matmul):
    """``W_d (silu(W_g u) * W_u u)``."""
    return mm(jax.nn.silu(mm(u, lp["gate"]["kernel"]))
              * mm(u, lp["up"]["kernel"]), lp["down"]["kernel"])


def experts_reference(lp, u, config: dict, mm=jnp.matmul, held=None,
                      shared: bool = True, router_dtype=jnp.float32):
    """``s = sigmoid(W_r u)``; the ``top_k`` largest of ``s + bias``;
    weights ``s_i / sum_chosen(s) * scale``; SwiGLU experts: a loop over
    the experts ``held`` (a range of ids whose parameters ``lp`` holds in
    order; the configuration's by default) with a 0/1 mask, plus the
    shared expert on every token.  ``router_dtype`` only for the reading
    that says what a bfloat16 router would cost."""
    lo, hi = config["experts_held"] if held is None else held
    router = lp["router"]
    if not config["train_router"]:      # a stated departure of the cut
        router = jax.lax.stop_gradient(router)
    scores = jnp.matmul(u.astype(router_dtype), router.astype(router_dtype))
    s = jax.nn.sigmoid(scores.astype(jnp.float32))
    _, chosen = jax.lax.top_k(s + lp["bias"], config["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) \
        * config["routed_scaling_factor"]

    def swiglu(gate, up, down):
        return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)

    y = swiglu(lp["shared_gate"]["kernel"], lp["shared_up"]["kernel"],
               lp["shared_down"]["kernel"]) if shared else jnp.zeros_like(u)
    for e in range(lo, hi):
        w = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        y = y + w[..., None] * swiglu(lp["experts_gate"][e - lo],
                                      lp["experts_up"][e - lo],
                                      lp["experts_down"][e - lo])
    return y


MIXERS = {"*": ("attn", latent_attention_reference),
          "D": ("mlp", gated_mlp_reference),
          "E": ("moe", experts_reference)}


def reference_loss(params, batch, *, config: dict, pattern: str,
                   round_to=None, router_dtype=jnp.float32,
                   sinkhorn_dtype=jnp.float32):
    """Next-token cross-entropy over the vocabulary slice in float32:
    the embedding repeated over the streams, one hyper-connection round
    ``mixer(RMSNorm(.))`` a character of ``pattern``, the streams summed,
    RMSNorm, untied head."""
    p = params["params"]
    mm = _matmul(round_to)
    eps, n = config["rms_norm_eps"], config["hc_mult"]
    x = p["embed"]["embedding"][batch["inputs"]]
    x = jnp.repeat(x[:, :, None, :], n, axis=2)
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(pattern):
            lp = p[f"layer_{i}"]
            name, mixer = MIXERS[kind]
            extra = {"router_dtype": router_dtype} if kind == "E" else {}

            def sublayer(v, lp=lp, name=name, mixer=mixer, extra=extra):
                return mixer(lp[name], _rms(v, lp["norm"]["scale"], eps),
                             config, mm, **extra)

            x = hyper_connection_reference(lp["hc"], x, sublayer, config,
                                           mm, sinkhorn_dtype)
        logits = mm(_rms(jnp.sum(x, axis=2), p["ln_f"]["scale"], eps),
                    p["head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def decided_ids(config: dict, variables):
    """For every id of the slice, whether the first sublayer's choice of
    experts is *decided*: its fourth and fifth ``score + bias`` lie
    further apart than ``parity["choice_margin"]``.  The parity cut's
    first sublayer is the expert layer and the streams enter it as
    copies of the embedding, so what its router reads — the
    hyper-connection's read-out, normed — is a function of the id, as in
    ``nemotron3nano.decided_ids``."""
    p = nn.meta.unbox(variables)["params"]
    lp, eps, n = p["layer_0"], config["rms_norm_eps"], config["hc_mult"]
    emb = p["embed"]["embedding"]                           # (V, C)
    with jax.default_matmul_precision("highest"):
        u = _rms(jnp.tile(emb, (1, n)), lp["hc"]["norm_scale"], eps)
        h_pre = jax.nn.sigmoid(
            lp["hc"]["gates"][0] * (u @ lp["hc"]["phi"][:, :n])
            + lp["hc"]["b_pre"])
        read = jnp.sum(h_pre, axis=-1, keepdims=True) * emb
        s = jax.nn.sigmoid(_rms(read, lp["norm"]["scale"], eps)
                           @ lp["moe"]["router"]) + lp["moe"]["bias"]
    top, _ = jax.lax.top_k(s, config["num_experts_per_tok"] + 1)
    return top[:, -2] - top[:, -1] > config["parity"]["choice_margin"]


def off_seed(init):
    """``init`` with every hyper-connection moved off its initial values:
    the gates at 1 and ``B_res`` drawn N(0, 1) (``phi`` is random as it
    is).  At the model's own values (gates 0.01, ``B_res = 3 I``) the
    coefficients hardly depend on the token and the streams stay copies
    of one another, so ``H_res`` acts through its row sums alone and a
    transposed or permuted mixing matrix reads the same; from here the
    streams differ from the second sublayer on and every entry of the
    three coefficients counts.  The first sublayer still reads copies of
    the embedding: :func:`decided_ids` holds."""
    def moved(key):
        variables = init(key)
        params = dict(variables["params"])
        for name in params:
            if name.startswith("layer_"):
                hc = dict(params[name]["hc"])
                hc["gates"] = jnp.ones_like(hc["gates"])
                hc["b_res"] = jax.random.normal(
                    jax.random.fold_in(key, 1 + int(name[len("layer_"):])),
                    hc["b_res"].shape, hc["b_res"].dtype)
                params[name] = {**params[name], "hc": hc}
        return {**variables, "params": params}

    return moved


def parity_case(config: dict, job: dict, chips: int, seed: int) -> ParityCase:
    """One sublayer of each kind, the expert layer first, each inside its
    hyper-connection, from initial values at which the streams differ
    (:func:`off_seed`); compared over tokens whose choice of experts is
    decided (:func:`decided_ids`), for ``nemotron3nano.parity_case``'s
    reason."""
    cut = config["parity"]
    seq, pattern = job["seq"], cut["layers"]
    if pattern[0] != "E" or "E" in pattern[1:]:
        raise ValueError(f"parity layers {pattern!r}: one expert layer, "
                         f"and first, where its choice is the id's")
    model = _model(config, pattern)
    init = off_seed(_init(model, seq))
    decided = jax.jit(lambda key: decided_ids(config, init(key)))(
        jax.random.PRNGKey(seed))

    def reference(params, batch):
        return reference_loss(nn.meta.unbox(params), batch, config=config,
                              pattern=pattern)

    return ParityCase(
        init=init, loss_fn=_loss_fn(model), reference_loss=reference,
        sample=nemotron3nano._on_decided_ids(
            _sampler(config, seq, seed), decided),
        rows_per_chip=job["batch_per_chip"],
        reference_chunk_rows=1,     # rows are independent
        loss_rtol=cut["loss_rtol"],
        grad_norm_rtol=cut["grad_norm_rtol"],
        grad_rel_l2=cut["grad_rel_l2"])
