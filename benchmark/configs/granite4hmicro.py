"""``granite4hmicro``: granite-4.0-h-micro as the first of four pipeline
stages holds one period of it, and its plain reference.

The system under test is ``horovod_tpu.models.HybridLM`` with the muP
scalars of the Granite family: nine Mamba-2 layers in ten with all 64
heads in one B/C group (the chunked scan as Mosaic kernels, a group's
heads in blocks), one grouped-query attention layer with 64-wide heads in
the flash kernels at softmax scale 1/64, a SwiGLU MLP after every mixer,
the embedding multiplied by 12, every branch by 0.22 before the add, and
the embedding read again as the head with the logits divided by 8.  The
reference below is the published mathematics written out in float32
``jax.numpy``, reading the same parameter tree and importing nothing of
the program: the Mamba-2 mixer as the *sequential* recurrence, one time
step after another (never the chunked algebra it is to check), attention
as a dense masked softmax over grouped heads (a key/value head against
its four query heads, never repeated), the MLP, the three scalars, the
tied head, the loss over the vocabulary slice.

Departures from the published modelling code, each also an entry of the
JSON: the cut — 10 of 40 layers, an eighth of the vocabulary; initial
values and the Mamba-2 precision as ``nemotron3nano`` (config.json has no
key for them); no positional term (``position_embedding_type`` "nope");
the reference's recurrence is rematerialised in segments and its scores
taken a key/value group at a time, so that its gradients fit one chip at
8,192 tokens — neither changes a value.

Data: tokens drawn independently from a Zipf unigram over the slice, as
``lm871m`` draws them.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from benchmark import flops, granite_flops
from benchmark.configs import Built, ParityCase, nemotron3nano

SEGMENT = 128           # steps of the recurrence rematerialised together

_rms = nemotron3nano._rms
_matmul = nemotron3nano._matmul
_sampler = nemotron3nano._sampler       # lm871m's Zipf(1.0) unigram
_init = nemotron3nano._init
_loss_fn = nemotron3nano._loss_fn


def _head_width(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def _hybrid_config(config: dict, pattern: str):
    from horovod_tpu.models import HybridConfig

    return HybridConfig(
        vocab_size=config["vocab_size"], pattern=pattern,
        d_model=config["hidden_size"], norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=_head_width(config),
        attention_scale=config["attention_multiplier"],
        attention_impl=config["attention"],
        flash_block=config["flash_block"],
        flash_interpret=config["flash_interpret"],
        mlp_width=config["shared_intermediate_size"],
        mamba_heads=config["mamba_n_heads"],
        mamba_head_dim=config["mamba_d_head"],
        mamba_groups=config["mamba_n_groups"],
        ssm_state=config["mamba_d_state"],
        conv_kernel=config["mamba_d_conv"],
        chunk=config["mamba_chunk_size"],
        time_step_min=config["time_step_min"],
        time_step_max=config["time_step_max"],
        time_step_floor=config["time_step_floor"],
        embedding_scale=config["embedding_multiplier"],
        residual_scale=config["residual_multiplier"],
        logits_divisor=config["logits_scaling"],
        tie_head=config["tie_word_embeddings"],
        remat_policy=config["remat_policy"],
        mlp_remat_policy=config["mlp_remat_policy"])


def _model(config: dict, pattern: str):
    from horovod_tpu.models import HybridLM

    if set(pattern[1::2]) != {"D"} or "D" in pattern[0::2]:
        raise ValueError(f"layers {pattern!r}: every layer is a mixer "
                         f"(M or *) and then the MLP (D)")
    return HybridLM(_hybrid_config(config, pattern))


def build(config: dict, job: dict, chips: int, seed: int) -> Built:
    seq, rows = job["seq"], job["batch_per_chip"]
    layers = config["layers"]
    if len(layers) != 2 * config["num_layers"]:
        raise ValueError("layers holds two sublayers a layer of num_layers")
    model = _model(config, layers)
    opt = config["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"granite4hmicro trains with adamw, got {opt}")
    rate = opt["learning_rate"]
    if opt["warmup_steps"]:
        rate = optax.linear_schedule(0.0, rate, opt["warmup_steps"])
    return Built(
        init=_init(model, seq), loss_fn=_loss_fn(model),
        optimizer=optax.adamw(rate),
        sample=_sampler(config, seq, seed), units_per_sample=seq,
        flops_per_unit=granite_flops.granite_lm_flops_per_token(config, seq),
        # two Mosaic kernels a step, on operands of their own shapes: no
        # one operand is in every call
        kernel_operand=None,
        kernel_cost={
            "gqa_flash": flops.flash_step_cost(
                rows * config["num_attention_heads"], seq,
                _head_width(config), layers.count("*")),
            "ssd": granite_flops.ssd_step_cost(
                rows, seq, config["mamba_n_heads"], config["mamba_d_head"],
                config["mamba_n_groups"], config["mamba_d_state"],
                config["mamba_chunk_size"], layers.count("M"))})


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def mamba2_reference(lp, u, config: dict, mm=jnp.matmul):
    """``[z | xBC | dt] = in_proj(u)``; ``xBC <- silu(conv1d_k(xBC) + b)``
    causal, depthwise; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t + D x_t`` with ``B``, ``C`` shared by every head of
    the group; ``RMSNorm(y * silu(z)) w`` over the inner width of a
    group; ``out_proj``.  The recurrence one time step after another."""
    h, p = config["mamba_n_heads"], config["mamba_d_head"]
    g, n, k = config["mamba_n_groups"], config["mamba_d_state"], \
        config["mamba_d_conv"]
    inner = h * p
    bsz, t, _ = u.shape
    z, xbc, dt = jnp.split(mm(u, lp["in_proj"]["kernel"]),
                           [inner, 2 * inner + 2 * g * n], axis=-1)
    # tap i of the causal convolution reads the input k-1-i steps back
    padded = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    xbc = jax.nn.silu(sum(padded[:, i:i + t] * lp["conv_kernel"][i]
                          for i in range(k)) + lp["conv_bias"])
    x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    x = x.reshape(bsz, t, g, h // g, p)
    b, c = b.reshape(bsz, t, g, n), c.reshape(bsz, t, g, n)
    dt = jax.nn.softplus(dt + lp["dt_bias"]).reshape(bsz, t, g, h // g)
    a = -jnp.exp(lp["A_log"]).reshape(g, h // g)

    def one(state, at_t):               # state: (B, G, R, P, N)
        x_t, b_t, c_t, dt_t = at_t
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] \
            * b_t[:, :, None, None, :]
        return state, jnp.sum(state * c_t[:, :, None, None, :], axis=-1)

    @jax.checkpoint
    def segment(state, part):
        return jax.lax.scan(one, state, part)

    seg = next(s for s in range(min(SEGMENT, t), 0, -1) if t % s == 0)
    steps = jax.tree_util.tree_map(
        lambda v: jnp.moveaxis(v, 1, 0).reshape(
            (t // seg, seg) + v.shape[:1] + v.shape[2:]), (x, b, c, dt))
    _, y = jax.lax.scan(
        segment, jnp.zeros((bsz, g, h // g, p, n), jnp.float32), steps)
    y = jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)  # (B,T,G,R,P)
    y = y + lp["D"].reshape(g, h // g)[..., None] * x
    y = y.reshape(bsz, t, g, inner // g) \
        * jax.nn.silu(z).reshape(bsz, t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + config["rms_norm_eps"])
    return mm(y.reshape(bsz, t, inner) * lp["norm_scale"],
              lp["out_proj"]["kernel"])


def attention_reference(lp, u, config: dict, mm=jnp.matmul):
    """``q = W_q u`` (32 heads), ``k, v = W_k u, W_v u`` (8 heads, each
    read by 4 query heads: query head i reads key/value head i // 4),
    causal ``softmax(q k^T s) v`` with ``s = attention_multiplier``,
    ``W_o``; no rotation, no bias.  Dense scores, one key/value head and
    its query heads at a time."""
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, scale = _head_width(config), config["attention_multiplier"]
    bsz, t, _ = u.shape
    q, k, v = jnp.split(mm(u, lp["qkv"]["kernel"]),
                        [h * d, (h + kv) * d], axis=-1)
    q = q.reshape(bsz, t, kv, h // kv, d)
    k, v = k.reshape(bsz, t, kv, d), v.reshape(bsz, t, kv, d)
    mask = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_group(qkv):
        q, k, v = qkv                   # (B, T, R, d), (B, T, d) twice
        s = jnp.einsum("bqrd,bkd->brqk", q, k) * scale
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("brqk,bkd->bqrd", w, v)

    o = jax.lax.map(one_group, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                                jnp.moveaxis(v, 2, 0)))
    return mm(jnp.moveaxis(o, 0, 2).reshape(bsz, t, h * d),
              lp["proj"]["kernel"])


def gated_mlp_reference(lp, u, config: dict, mm=jnp.matmul):
    """``W_d (silu(W_g u) * W_u u)``."""
    return mm(jax.nn.silu(mm(u, lp["gate"]["kernel"]))
              * mm(u, lp["up"]["kernel"]), lp["down"]["kernel"])


MIXERS = {"M": ("mamba", mamba2_reference),
          "*": ("attn", attention_reference),
          "D": ("mlp", gated_mlp_reference)}


def reference_loss(params, batch, *, config: dict, pattern: str,
                   round_to=None, residual_multiplier=None,
                   logits_scaling=None):
    """Next-token cross-entropy over the vocabulary slice in float32:
    ``h = embedding_multiplier E[tokens]``; a sublayer of ``pattern``
    ``h <- h + residual_multiplier mixer(RMSNorm(h))``; ``logits =
    RMSNorm(h) E^T / logits_scaling``.  ``round_to``, and the two scalars
    where given, only for the readings that say what the comparison
    refuses (PERF.md): matmul operands in a lower precision, a branch
    not scaled, a head scaled by another number."""
    p = params["params"]
    mm = _matmul(round_to)
    eps = config["rms_norm_eps"]
    branch = config["residual_multiplier"] if residual_multiplier is None \
        else residual_multiplier
    divisor = config["logits_scaling"] if logits_scaling is None \
        else logits_scaling
    embedding = p["embed"]["embedding"]
    x = config["embedding_multiplier"] * embedding[batch["inputs"]]
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(pattern):
            lp = p[f"layer_{i}"]
            name, mixer = MIXERS[kind]
            x = x + branch * mixer(
                lp[name], _rms(x, lp["norm"]["scale"], eps), config, mm)
        logits = mm(_rms(x, p["ln_f"]["scale"], eps), embedding.T) / divisor
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def off_seed(init):
    """``init`` with every leaf that starts at a value at which it is
    idle moved off it, from the same key: the norm scales (block norms,
    the final norm, the gated norm's: all 1) drawn N(1, 0.1), the
    convolution's bias (0) N(0, 0.1), ``D`` (1) N(1, 0.1).  At 1 a scale
    multiplies nothing and at 0 a bias adds nothing: a program that
    applied one in the wrong place — the gated norm's scale before the
    normalisation, the bias after the SiLU — would give the same loss
    and differ only in that leaf's own gradient (PERF.md, PR 32's lesson
    4: compare where every mechanism does something)."""
    def moved(key):
        variables = init(key)
        flat = jax.tree_util.tree_leaves_with_path(variables)
        out = []
        for n, (path, leaf) in enumerate(flat):
            name = jax.tree_util.keystr(path)
            if name.endswith(("['scale']", "['norm_scale']", "['D']",
                              "['conv_bias']")):
                leaf = leaf + 0.1 * jax.random.normal(
                    jax.random.fold_in(key, 1 + n), leaf.shape, leaf.dtype)
            out.append(leaf)
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(variables), out)

    return moved


def parity_case(config: dict, job: dict, chips: int, seed: int) -> ParityCase:
    """A Mamba layer and the attention layer, each with its MLP
    (``MD*D``), at the published widths and the timed shape, from initial
    values at which no scale or bias is idle (:func:`off_seed`)."""
    cut = config["parity"]
    seq, pattern = job["seq"], cut["layers"]
    model = _model(config, pattern)

    def reference(params, batch):
        return reference_loss(nn.meta.unbox(params), batch, config=config,
                              pattern=pattern)

    return ParityCase(
        init=off_seed(_init(model, seq)), loss_fn=_loss_fn(model),
        reference_loss=reference, sample=_sampler(config, seq, seed),
        rows_per_chip=job["batch_per_chip"],
        reference_chunk_rows=1,     # rows are independent
        loss_rtol=cut["loss_rtol"],
        grad_norm_rtol=cut["grad_norm_rtol"],
        grad_rel_l2=cut["grad_rel_l2"])
