"""``lm871m``: the repo's flagship decoder LM, and its plain reference.

The system under test is ``horovod_tpu.models.TransformerLM`` with flash
attention; the reference below is the same mathematics written out in
float32 ``jax.numpy`` — dense causal attention, no kernel, no mesh —
reading the same parameter tree.  Data: tokens drawn independently from
a Zipf unigram over the vocabulary (which ids are frequent is drawn from
the seed), so the loss can fall from ln(V) towards the unigram entropy.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import flops
from benchmark.configs import Built, ParityCase

ZIPF_EXPONENT = 1.0


def _model(config: dict, seq: int, num_layers: int):
    from horovod_tpu.models import TransformerConfig, TransformerLM

    if config["num_heads"] * config["head_dim"] != config["d_model"]:
        raise ValueError("num_heads x head_dim must be d_model")
    return TransformerLM(TransformerConfig(
        vocab_size=config["vocab_size"], num_layers=num_layers,
        num_heads=config["num_heads"], d_model=config["d_model"],
        d_ff=config["d_ff"], max_seq_len=seq,
        dtype=jnp.dtype(config["compute_dtype"]),
        attention_impl=config["attention"],
        flash_block=config["flash_block"],
        flash_interpret=config["flash_interpret"]))


def _loss_fn(model):
    def loss_fn(params, batch):
        logits = model.apply(params, batch["inputs"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), batch["labels"]).mean()
    return loss_fn


def _init(model, seq: int):
    return lambda key: model.init(key, jnp.zeros((1, seq), jnp.int32))


def _sampler(config: dict, seq: int, seed: int):
    vocab = config["vocab_size"]
    weights = 1.0 / np.arange(1, vocab + 1) ** ZIPF_EXPONENT
    cdf = np.cumsum(weights / weights.sum())
    ids = np.random.default_rng(seed).permutation(vocab).astype(np.int32)

    def sample(rng, n: int) -> dict:
        ranks = np.searchsorted(cdf, rng.random((n, seq + 1)))
        raw = ids[np.minimum(ranks, vocab - 1)]
        return {"inputs": raw[:, :-1], "labels": raw[:, 1:]}
    return sample


def build(config: dict, job: dict, chips: int, seed: int) -> Built:
    seq = job["seq"]
    model = _model(config, seq, config["num_layers"])
    opt = config["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"lm871m trains with adamw, got {opt}")
    rows = job["batch_per_chip"] * config["num_heads"]
    return Built(
        init=_init(model, seq), loss_fn=_loss_fn(model),
        optimizer=optax.adamw(opt["learning_rate"]),
        sample=_sampler(config, seq, seed),
        units_per_sample=seq,
        flops_per_unit=flops.transformer_lm_flops_per_token(
            config["num_layers"], config["d_model"], config["d_ff"],
            config["vocab_size"], seq),
        # flash takes q as (batch rows x heads, seq, head_dim): on a chip
        # it must be that chip's rows only
        kernel_operand=f"[{rows},{seq},{config['head_dim']}]",
        kernel_cost={"flash": flops.flash_step_cost(
            rows, seq, config["head_dim"], config["num_layers"])})


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _rms(x, scale, epsilon=1e-6):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + epsilon) * scale


def _rope(x, base=10_000.0):
    """Rotary positions on (batch, seq, heads, head_dim): pairs
    (2i, 2i+1) turn by position x base^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def reference_loss(params, batch, *, num_layers: int, num_heads: int):
    """Next-token cross-entropy of the decoder in float32, written out:
    embedding, ``num_layers`` x (RMSNorm, causal softmax attention with
    rotary positions, RMSNorm, GELU MLP), RMSNorm, tied head."""
    p = params["params"]
    emb = p["embed"]["embedding"]
    x = emb[batch["inputs"]]
    b, t, d = x.shape
    hd = d // num_heads
    mask = jnp.tril(jnp.ones((t, t), bool))
    with jax.default_matmul_precision("highest"):
        for i in range(num_layers):
            lp = p[f"layer_{i}"]
            h = _rms(x, lp["ln1"]["scale"])
            q, k, v = jnp.split(h @ lp["attn"]["qkv"]["kernel"], 3, axis=-1)
            q, k, v = (a.reshape(b, t, num_heads, hd) for a in (q, k, v))
            q, k = _rope(q), _rope(k)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
            w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, t, d)
            x = x + o @ lp["attn"]["proj"]["kernel"]
            h = _rms(x, lp["ln2"]["scale"])
            h = jax.nn.gelu(h @ lp["mlp"]["wi"]["kernel"], approximate=True)
            x = x + h @ lp["mlp"]["wo"]["kernel"]
        logits = _rms(x, p["ln_f"]["scale"]) @ emb.T
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def parity_case(config: dict, job: dict, chips: int, seed: int) -> ParityCase:
    cut = config["parity"]
    seq, layers = job["seq"], cut["num_layers"]
    model = _model(config, seq, layers)

    def reference(params, batch):
        return reference_loss(nn.meta.unbox(params), batch,
                              num_layers=layers,
                              num_heads=config["num_heads"])

    return ParityCase(
        init=_init(model, seq), loss_fn=_loss_fn(model),
        reference_loss=reference, sample=_sampler(config, seq, seed),
        rows_per_chip=job["batch_per_chip"],
        # rows are independent, and 24 rows of float32 dense attention
        # would not fit one chip: the reference takes a chip's rows at a
        # time and averages
        reference_chunk_rows=job["batch_per_chip"],
        loss_rtol=cut["loss_rtol"],
        grad_norm_rtol=cut["grad_norm_rtol"],
        grad_rel_l2=cut["grad_rel_l2"])
