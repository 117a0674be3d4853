"""Operations and bytes of the Granite-4.0-H decoder (Mamba-2 and
grouped-query attention mixers, a SwiGLU MLP after each, a tied head),
computed from shapes — beside ``flops.py`` and ``hybrid_flops.py``, which
stay as they were accepted.  The same rules: a multiply-add counts two,
backward costs twice forward, recomputation counts nothing."""

from __future__ import annotations


def ssd_products(seq: int, heads: int, head_p: int, groups: int, state: int,
                 chunk: int) -> tuple:
    """``(forward, backward)`` FLOPs of the chunked (SSD) scan's matrix
    products over one sequence of one layer, as the algorithm at chunk
    ``Q`` needs them whatever runs it (Mosaic kernels a whole group or a
    block of its heads a grid step, or ``jax.numpy`` einsums).

    A chunk, forward: ``C^T B`` once a group (2 Q² N); a head's
    intra-chunk product (2 P Q²), the carried state's contribution to
    its outputs and the state it closes the chunk with (2 P Q N each).
    Backward: ``C^T B`` again and the two products that carry its
    cotangent to ``B`` and ``C`` once a group (3 x 2 Q² N); a head's two
    (P, Q, Q) products (the cotangent of the masked matrix, and of ``x
    dt`` through it) and five (P, Q, N) ones (the closing state's
    cotangent to ``x dt`` and to ``B``, the carried state's contribution
    again, its cotangent to ``C``, and the cotangent handed to the chunk
    before).  The decays, masks and prefix sums are elementwise and left
    out, as ``flops.flash_step_cost`` leaves the softmax out."""
    q, n, p = chunk, state, head_p
    chunks = seq // chunk
    forward = chunks * (groups * 2.0 * q * q * n
                        + heads * (2.0 * p * q * q + 4.0 * p * q * n))
    backward = chunks * (groups * 6.0 * q * q * n
                         + heads * (4.0 * p * q * q + 10.0 * p * q * n))
    return forward, backward


def ssd_step_cost(rows: int, seq: int, heads: int, head_p: int, groups: int,
                  state: int, chunk: int, layers: int,
                  itemsize: int = 2) -> dict:
    """What the scan of one train step needs on one chip — forward and
    backward once, ``rows`` sequences, ``layers`` Mamba layers.  Bytes:
    forward reads ``x``, ``B``, ``C`` (``itemsize``), ``dt`` and the
    log-decay prefix in both its layouts (fp32) and writes ``y`` (fp32)
    and every chunk's starting state (fp32); backward reads all of those
    but ``y``, and ``dy`` (fp32), and writes a gradient for each operand.
    Nothing here depends on how many heads a kernel takes at a step."""
    forward, backward = ssd_products(seq, heads, head_p, groups, state, chunk)
    x = seq * heads * head_p
    bc = 2 * seq * groups * state
    small = 3 * seq * heads * 4                 # dt, the prefix twice
    starts = (seq // chunk) * heads * head_p * state * 4
    fwd_bytes = (x + bc) * itemsize + small + x * 4 + starts
    bwd_bytes = (x + bc) * itemsize + small + starts + x * 4 \
        + (x + bc) * itemsize + small
    return {"flops": rows * layers * (forward + backward),
            "bytes": rows * layers * (fwd_bytes + bwd_bytes)}


def granite_lm_flops_per_token(config: dict, seq: int) -> float:
    """Forward + backward, a token, of ``configs/granite4hmicro.json``.

    Matmul weights, 6 FLOPs each a token: a Mamba-2 sublayer's in- and
    out-projection; attention's fused qkv (query heads plus twice the
    key/value heads) and output projection; a SwiGLU MLP's three
    matrices; the tied head (the embedding *lookup* is no matmul, its
    second use as the head is).  Causal attention as
    ``flops.transformer_lm_flops_per_token`` counts it, 6·T·(heads·width)
    a layer.  The scan: the products of its chunked form at the published
    chunk (:func:`ssd_products`: 13.9M a token and layer at these shapes;
    the bare recurrence, as ``hybrid_flops`` counts the other hybrid's,
    would be 7.9M — 1.1% of the step either way).  The 4-tap convolution,
    norms, gates and the muP scalars are left out (under 0.1%)."""
    d = config["hidden_size"]
    heads, head_p = config["mamba_n_heads"], config["mamba_d_head"]
    groups, state = config["mamba_n_groups"], config["mamba_d_state"]
    inner = heads * head_p
    mamba = d * (2 * inner + 2 * groups * state + heads) + inner * d
    width = d // config["num_attention_heads"]
    q = config["num_attention_heads"] * width
    kv = config["num_key_value_heads"] * width
    attn = d * (q + 2 * kv) + q * d
    mlp = 3 * d * config["shared_intermediate_size"]
    layers = config["layers"]
    weights = layers.count("M") * mamba + layers.count("*") * attn \
        + layers.count("D") * mlp + d * config["vocab_size"]
    scan = sum(ssd_products(seq, heads, head_p, groups, state,
                            config["mamba_chunk_size"])) / seq
    return 6.0 * weights + layers.count("M") * scan \
        + layers.count("*") * 6.0 * seq * q
