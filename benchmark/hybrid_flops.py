"""Operations and bytes of the hybrid (Mamba-2 / routed experts / GQA)
decoder, computed from shapes — beside ``flops.py``, which stays as it
was accepted.  The same rules: a multiply-add counts two, backward costs
twice forward, recomputation counts nothing."""

from __future__ import annotations


def hybrid_lm_flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward, a token, of ``configs/nemotron3nano.json``.

    Matmul weights, 6 FLOPs each a token: a Mamba-2 layer's in- and
    out-projection; an expert layer's router, its shared expert, and of
    the routed experts the *expectation under uniform routing* of what
    lands on the experts held here — ``top_k * held / router width``
    (0.375) of one expert a token; attention's fused qkv (query heads
    plus twice the key/value heads) and output projection; the untied
    head.  The embedding lookup is no matmul.  Causal attention as
    ``flops.transformer_lm_flops_per_token`` counts it, 6·T·(heads·width)
    a layer.  The state-space recurrence itself, as the recurrence needs
    it and not as the chunked form spends it: decay, rank-one update and
    read-out are 5 FLOPs a state element a token, x3 with backward.  The
    4-tap convolution, norms and gates are left out (under 0.1%)."""
    d = config["hidden_size"]
    heads, head_p = config["mamba_num_heads"], config["mamba_head_dim"]
    inner = heads * head_p
    bc = 2 * config["n_groups"] * config["ssm_state_size"]
    mamba = d * (2 * inner + bc + heads) + inner * d
    scan = 15.0 * heads * head_p * config["ssm_state_size"]
    expert = 2 * d * config["moe_intermediate_size"]
    held_share = config["num_experts_per_tok"] \
        * config["n_routed_experts"] / config["router_experts"]
    moe = d * config["router_experts"] \
        + 2 * d * config["moe_shared_expert_intermediate_size"] \
        + held_share * expert
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    attn = d * (q + 2 * kv) + q * d
    scores = 6.0 * seq * q
    layers = config["layers"]
    weights = layers.count("M") * mamba + layers.count("E") * moe \
        + layers.count("*") * attn + d * config["vocab_size"]
    return 6.0 * weights + layers.count("M") * scan \
        + layers.count("*") * scores


def grouped_matmul_step_cost(rows: float, d: int, width: int, held: int,
                         layers: int, itemsize: int = 2) -> dict:
    """What the grouped matmuls of one train step need on one chip when
    ``rows`` assignments land on the ``held`` experts of each of
    ``layers`` expert layers: six matmuls a layer (up and down forward,
    their two input gradients, their two weight gradients) of
    2·rows·d·width FLOPs, each moving the rows on both sides of it and
    every held expert's (d x width) matrix once."""
    return {
        "flops": layers * 6 * 2.0 * rows * d * width,
        "bytes": layers * 6 * itemsize * (rows * (d + width)
                                          + held * d * width),
    }
