"""``nemotron3nano``: NVIDIA-Nemotron-3-Nano-30B-A3B as one rank of an
``ep=16`` deployment holds a period of it, and its plain reference.

The system under test is ``horovod_tpu.models.HybridLM``: Mamba-2 layers
in the chunked (SSD) form, a dropless top-6 expert layer that holds 8 of
the 128 experts (a Pallas grouped matmul over sorted rows), grouped-query
flash attention.  The reference below is
the published mathematics written out in float32 ``jax.numpy``, reading
the same parameter tree and importing nothing of the program: the
Mamba-2 layer as the *sequential* recurrence (never the chunked algebra
it is to check), dense masked softmax attention over repeated key/value
heads, the expert layer as a loop over the held experts with a 0/1
mask — no sort, no buffer — and the loss over the vocabulary slice.

Departures from the published model, each also an entry of the JSON:
the selection bias is frozen at zero (its load-balancing update needs an
auxiliary output the train step does not have) and so is the router's
matrix (one rank has a sixteenth of that gradient's terms and no
exchange brings the rest: ``experts_reference``); attention has no
positional term (the Nemotron-H report uses none; ``rope_theta`` is
unused by the published modelling code); the cut — 9 of 52 layers, 8 of
128 experts held, an eighth of the vocabulary.  What the absent experts
would add is left out here as in the program.

Data: tokens drawn independently from a Zipf unigram over the slice, as
``lm871m`` draws them, so routing is uneven as real text makes it.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import flops, hybrid_flops
from benchmark.configs import Built, ParityCase, lm871m

# the reference recurrence is rematerialised in segments of this many
# steps, and its attention taken this many heads at a time, so that
# their gradients fit one chip at 8,192 tokens
SEGMENT = 128
HEADS_AT_A_TIME = 4


def _hybrid_config(config: dict, pattern: str):
    from horovod_tpu.models import HybridConfig

    return HybridConfig(
        vocab_size=config["vocab_size"], pattern=pattern,
        d_model=config["hidden_size"], norm_eps=config["norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        attention_impl=config["attention"],
        flash_block=config["flash_block"],
        flash_interpret=config["flash_interpret"],
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        mamba_groups=config["n_groups"],
        ssm_state=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk=config["chunk_size"],
        time_step_min=config["time_step_min"],
        time_step_max=config["time_step_max"],
        time_step_floor=config["time_step_floor"],
        num_experts=config["router_experts"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["moe_shared_expert_intermediate_size"],
        routed_scale=config["routed_scaling_factor"],
        train_router=config["train_router"],
        remat_policy=config["remat_policy"])


def _model(config: dict, pattern: str):
    from horovod_tpu.models import HybridLM

    if len(config["layers"]) != config["num_layers"] or \
            config["experts_held"][1] - config["experts_held"][0] \
            != config["n_routed_experts"]:
        raise ValueError("layers / num_layers or experts_held / "
                         "n_routed_experts disagree")
    return HybridLM(_hybrid_config(config, pattern))


def _loss_fn(model):
    from horovod_tpu.models import hybrid_lm_loss

    return functools.partial(hybrid_lm_loss, model)


def _init(model, seq: int):
    return lambda key: model.init(key, jnp.zeros((1, seq), jnp.int32))


# the Zipf(1.0) unigram of lm871m, over this configuration's vocab_size
_sampler = lm871m._sampler


def build(config: dict, job: dict, chips: int, seed: int) -> Built:
    seq, rows = job["seq"], job["batch_per_chip"]
    model = _model(config, config["layers"])
    opt = config["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"nemotron3nano trains with adamw, got {opt}")
    sample = _sampler(config, seq, seed)
    init = _init(model, seq)

    uniform_rows = rows * seq * config["num_experts_per_tok"] \
        * config["n_routed_experts"] / config["router_experts"]
    attn_layers = config["layers"].count("*")
    heads = rows * config["num_attention_heads"]
    return Built(
        init=init, loss_fn=_loss_fn(model),
        # the rate climbs to its value over warmup_steps, as every run of
        # such a model starts (the JSON's assumed.learning_rate_warmup)
        optimizer=optax.adamw(optax.linear_schedule(
            0.0, opt["learning_rate"], opt["warmup_steps"])),
        sample=sample, units_per_sample=seq,
        flops_per_unit=hybrid_flops.hybrid_lm_flops_per_token(config, seq),
        # two Mosaic kernels a step (flash, and the experts' grouped
        # matmul): no one operand is in every call, and on one chip the
        # operand is the whole operand
        kernel_operand=None,
        # the grouped matmuls' at the uniform expectation: what lands in
        # the timed steps no reader can see yet (PERF.md section 7)
        kernel_cost={
            "gqa_flash": flops.flash_step_cost(
                heads, seq, config["head_dim"], attn_layers),
            "grouped_matmul": hybrid_flops.grouped_matmul_step_cost(
                uniform_rows, config["hidden_size"],
                config["moe_intermediate_size"],
                config["n_routed_experts"], config["layers"].count("E"))})


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _rms(x, scale, epsilon):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + epsilon) * scale


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _matmul(round_to):
    """``a @ b`` in float32 — or, for the reading that says what a lower
    precision would cost (PERF.md), with both operands rounded to
    ``round_to`` first."""
    if round_to is None:
        return jnp.matmul

    def rounded(a, b):
        return jnp.matmul(a.astype(round_to).astype(jnp.float32),
                          b.astype(round_to).astype(jnp.float32))
    return rounded


def mamba2_reference(lp, u, config: dict, mm=jnp.matmul):
    """The Mamba-2 mixer as published, the recurrence one step at a time:
    ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t``,
    ``y_t = C_t . h_t + D x_t``."""
    h, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, n, k = config["n_groups"], config["ssm_state_size"], \
        config["conv_kernel"]
    inner = h * p
    bsz, t, _ = u.shape
    z, xbc, dt = jnp.split(mm(u, lp["in_proj"]["kernel"]),
                           [inner, 2 * inner + 2 * g * n], axis=-1)
    # causal depthwise convolution: tap i reads the input k-1-i steps back
    padded = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    xbc = jax.nn.silu(sum(padded[:, i:i + t] * lp["conv_kernel"][i]
                          for i in range(k)) + lp["conv_bias"])
    x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    x = x.reshape(bsz, t, h, p)
    # head i reads group i // (h // g)
    b = jnp.repeat(b.reshape(bsz, t, g, n), h // g, axis=2)
    c = jnp.repeat(c.reshape(bsz, t, g, n), h // g, axis=2)
    dt = jax.nn.softplus(dt + lp["dt_bias"])            # (B, T, H)
    a = -jnp.exp(lp["A_log"])

    def one(state, at_t):
        x_t, b_t, c_t, dt_t = at_t                      # (B, H, ...)
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    @jax.checkpoint
    def segment(state, part):
        return jax.lax.scan(one, state, part)

    seg = next(s for s in range(min(SEGMENT, t), 0, -1) if t % s == 0)
    steps = jax.tree_util.tree_map(
        lambda v: jnp.moveaxis(v, 1, 0).reshape(
            (t // seg, seg) + v.shape[:1] + v.shape[2:]), (x, b, c, dt))
    _, y = jax.lax.scan(segment, jnp.zeros((bsz, h, p, n), jnp.float32),
                        steps)
    y = jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)   # (B, T, H, P)
    y = (y + lp["D"][:, None] * x).reshape(bsz, t, inner)
    # gated RMSNorm in groups of inner / g
    y = (y * jax.nn.silu(z)).reshape(bsz, t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + config["norm_eps"])
    return mm(y.reshape(bsz, t, inner) * lp["norm_scale"],
              lp["out_proj"]["kernel"])


def attention_reference(lp, u, config: dict, mm=jnp.matmul):
    """Causal softmax attention, 32 query heads over 2 key/value heads
    (query head i reads key/value head i // 16), scale head_dim^-1/2, no
    positional term; dense scores, a few heads at a time."""
    h, kv, d = config["num_attention_heads"], \
        config["num_key_value_heads"], config["head_dim"]
    bsz, t, _ = u.shape
    q, k, v = jnp.split(mm(u, lp["qkv"]["kernel"]),
                        [h * d, (h + kv) * d], axis=-1)
    q = q.reshape(bsz, t, h, d)
    k = jnp.repeat(k.reshape(bsz, t, kv, d), h // kv, axis=2)
    v = jnp.repeat(v.reshape(bsz, t, kv, d), h // kv, axis=2)
    mask = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def some_heads(qkv):
        q, k, v = qkv                                   # (B, T, m, d)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    m = next(s for s in range(min(HEADS_AT_A_TIME, h), 0, -1) if h % s == 0)
    groups = jax.tree_util.tree_map(
        lambda a: jnp.moveaxis(a.reshape(bsz, t, h // m, m, d), 2, 0),
        (q, k, v))
    o = jnp.moveaxis(jax.lax.map(some_heads, groups), 0, 2)
    return mm(o.reshape(bsz, t, h * d), lp["proj"]["kernel"])


def experts_reference(lp, u, config: dict, mm=jnp.matmul,
                      held=None, shared: bool = True,
                      router_dtype=jnp.float32):
    """``s = sigmoid(W_r u)``; the ``top_k`` largest of ``s + bias``;
    weights ``s_i / sum_chosen(s) * scale``; expert
    ``W_down . relu(W_up u)^2``: a loop over the experts ``held`` (a
    range of ids whose parameters ``lp`` holds in order; the
    configuration's by default) with a 0/1 mask, plus the shared expert
    on every token.  ``router_dtype`` only for the reading that says
    what a bfloat16 router would cost."""
    lo, hi = config["experts_held"] if held is None else held
    router = lp["router"]
    if not config["train_router"]:
        # one rank's share of the experts gives a share of the router's
        # gradient, and nothing here brings the other ranks' terms: the
        # matrix takes none (a stated departure of the cut)
        router = jax.lax.stop_gradient(router)
    scores = jnp.matmul(u.astype(router_dtype), router.astype(router_dtype))
    s = jax.nn.sigmoid(scores.astype(jnp.float32))
    _, chosen = jax.lax.top_k(s + lp["bias"], config["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) \
        * config["routed_scaling_factor"]
    y = mm(_relu2(mm(u, lp["shared_up"]["kernel"])),
           lp["shared_down"]["kernel"]) if shared else jnp.zeros_like(u)
    for e in range(lo, hi):
        w = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        y = y + w[..., None] * mm(_relu2(mm(u, lp["experts_up"][e - lo])),
                                  lp["experts_down"][e - lo])
    return y


MIXERS = {"M": ("mamba", mamba2_reference),
          "E": ("moe", experts_reference),
          "*": ("attn", attention_reference)}


def reference_loss(params, batch, *, config: dict, pattern: str,
                   round_to=None, router_dtype=jnp.float32):
    """Next-token cross-entropy over the vocabulary slice in float32:
    embedding, one ``x + mixer(RMSNorm(x))`` a layer of ``pattern``,
    RMSNorm, untied head."""
    p = params["params"]
    mm = _matmul(round_to)
    eps = config["norm_eps"]
    x = p["embed"]["embedding"][batch["inputs"]]
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(pattern):
            lp = p[f"layer_{i}"]
            name, mixer = MIXERS[kind]
            extra = {"router_dtype": router_dtype} if kind == "E" else {}
            x = x + mixer(lp[name], _rms(x, lp["norm"]["scale"], eps),
                          config, mm, **extra)
        logits = mm(_rms(x, p["ln_f"]["scale"], eps), p["head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def decided_ids(config: dict, variables):
    """For every id of the slice, whether the first layer's choice of
    experts is *decided*: its sixth and seventh ``score + bias`` lie
    further apart than ``parity["choice_margin"]``.  The first layer of
    the parity cut is the expert layer, so its router reads the norm of
    the embedding and the choice is a function of the id; a bfloat16
    rounding of that input moves a score by about 1e-4 (PERF.md, PR 28),
    and an id inside the margin may pick another expert in the program
    than in the reference — in every token that holds it at once."""
    p = nn.meta.unbox(variables)["params"]
    lp = p["layer_0"]
    u = _rms(p["embed"]["embedding"], lp["norm"]["scale"], config["norm_eps"])
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(u @ lp["moe"]["router"]) + lp["moe"]["bias"]
    top, _ = jax.lax.top_k(s, config["num_experts_per_tok"] + 1)
    return top[:, -2] - top[:, -1] > config["parity"]["choice_margin"]


def _on_decided_ids(sample, decided):
    """``sample`` with every undecided id replaced by the next decided
    one (cyclically), in inputs and labels alike."""
    keep = np.flatnonzero(np.asarray(decided))
    nearest = keep[np.searchsorted(keep, np.arange(len(decided))) % len(keep)]

    def draw(rng, n: int) -> dict:
        return {k: nearest[v].astype(v.dtype)
                for k, v in sample(rng, n).items()}
    return draw


def parity_case(config: dict, job: dict, chips: int, seed: int) -> ParityCase:
    """One layer of each kind, the expert layer first.  The comparison
    is made over tokens whose choice of experts is decided
    (:func:`decided_ids`, at the parameters ``parity.check`` will seed):
    where the program and the reference route a token to different
    experts they compute different functions, and their distance says
    nothing of either's arithmetic."""
    cut = config["parity"]
    seq, pattern = job["seq"], cut["layers"]
    if pattern[0] != "E" or "E" in pattern[1:]:
        raise ValueError(f"parity layers {pattern!r}: one expert layer, "
                         f"and first, where its choice is the id's")
    model = _model(dict(config, layers=pattern, num_layers=len(pattern)),
                   pattern)
    init = _init(model, seq)
    decided = jax.jit(lambda key: decided_ids(config, init(key)))(
        jax.random.PRNGKey(seed))

    def reference(params, batch):
        return reference_loss(nn.meta.unbox(params), batch, config=config,
                              pattern=pattern)

    return ParityCase(
        init=init, loss_fn=_loss_fn(model), reference_loss=reference,
        sample=_on_decided_ids(_sampler(config, seq, seed), decided),
        rows_per_chip=job["batch_per_chip"],
        reference_chunk_rows=1,     # rows are independent
        loss_rtol=cut["loss_rtol"],
        grad_norm_rtol=cut["grad_norm_rtol"],
        grad_rel_l2=cut["grad_rel_l2"])
