"""Operations and bytes of the latent-attention / hyper-connection /
SwiGLU-expert decoder (``configs/xing4.json``), computed from shapes —
beside ``flops.py`` and ``hybrid_flops.py``, which stay as they were
accepted.  The same rules: a multiply-add counts two, backward costs
twice forward, recomputation counts nothing."""

from __future__ import annotations


def latent_lm_flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward, a token.

    Matmul weights, 6 FLOPs each a token: a latent-attention sublayer's
    five matrices (query down 768 and up to heads x 192, key/value down
    512 + 64 and up to heads x 256, output); a hyper-connection's one
    (4·3584) x 24 coefficient matrix; the dense SwiGLU MLP's three; an
    expert sublayer's router, its shared expert's three, and of the
    routed experts the *expectation under uniform routing* of what lands
    on the experts held here — ``top_k * held / router width`` (0.5) of
    one three-matrix expert a token; the untied head.  The embedding
    lookup is no matmul.  Causal attention a layer: QKᵀ over the 192-wide
    q / k and PV over the 128-wide v, 2·T·width each forward, x3 with
    backward, halved by the mask: ``3·T·heads·(192 + 128)``.  Left out:
    the stream mixing itself — the hyper-connections' norm over 4·3584,
    Sinkhorn's 20 rounds on a 4 x 4 matrix and the weighted sums over
    streams, ≈ 0.3 MFLOP a token and sublayer of elementwise work that
    HBM bounds, not the MXU — and the norms, rotations and gates."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    attn = d * config["q_lora_rank"] + config["q_lora_rank"] * heads * qk \
        + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"]) \
        + config["kv_lora_rank"] * heads * (config["qk_nope_head_dim"] + v) \
        + heads * v * d
    n = config["hc_mult"]
    hc = n * d * n * (n + 2)
    expert = 3 * d * config["moe_intermediate_size"]
    held_share = config["num_experts_per_tok"] \
        * config["n_routed_experts"] / config["router_experts"]
    moe = d * config["router_experts"] \
        + config["n_shared_experts"] * expert + held_share * expert
    layers = config["layers"]
    weights = layers.count("*") * attn + len(layers) * hc \
        + layers.count("D") * 3 * d * config["intermediate_size"] \
        + layers.count("E") * moe + d * config["vocab_size"]
    return 6.0 * weights + layers.count("*") * 3.0 * seq * heads * (qk + v)


def latent_flash_step_cost(rows: int, seq: int, qk_dim: int, v_dim: int,
                           layers: int, causal: bool = True,
                           itemsize: int = 2) -> dict:
    """What the flash forward and backward kernels of one train step
    need on one chip when q and k are ``qk_dim`` wide and v, the output
    and their gradients ``v_dim``: ``rows`` = batch rows x heads.

    Seven (T, T, width) products a block pair as ``flops.
    flash_step_cost`` counts them, 2·T²·width each and half under the
    causal mask: four over the q / k width (QKᵀ forward; the scores
    again, dQ and dK backward) and three over the v width (PV forward;
    dP and dV backward).  Bytes: six q/k-wide tensors (q, k forward; q,
    k, dq, dk backward), six v-wide (v, o forward; v, o, dO, dv
    backward) and the fp32 row statistics written and read.  A width is
    counted as it is, not as the lanes it is served in: 192 costs the
    MXU's contraction two passes, as 256 would."""
    pair = 2.0 * seq * seq * (0.5 if causal else 1.0)
    return {
        "flops": rows * layers * pair * (4 * qk_dim + 3 * v_dim),
        "bytes": rows * layers * (6 * seq * (qk_dim + v_dim) * itemsize
                                  + 2 * seq * 4),
    }


def swiglu_grouped_matmul_step_cost(rows: float, d: int, width: int,
                                    held: int, layers: int,
                                    itemsize: int = 2) -> dict:
    """What the grouped matmuls of one train step need on one chip when
    ``rows`` assignments land on the ``held`` SwiGLU experts of each of
    ``layers`` expert layers: ``[gate | up]`` as one (d x 2·width)
    product and ``down`` (width x d), each forward, input gradient and
    weight gradient — 3 x 3 x 2·rows·d·width FLOPs — moving the rows on
    both sides of each product and every held expert's matrices once a
    product."""
    return {
        "flops": layers * 3 * 3 * 2.0 * rows * d * width,
        "bytes": layers * 3 * itemsize * (
            rows * (d + 2 * width) + rows * (width + d)
            + held * 3 * d * width),
    }
