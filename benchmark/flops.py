"""Operations and bytes, computed from shapes.

Model FLOPs are what the forward and backward passes *require* — a
multiply-add counts two, backward costs twice forward, recomputation
(remat, the flash backward's second look at the scores) counts
nothing — so that rate x FLOPs a unit / peak is the utilisation the
on-chip-measurement guide defines.  A kernel's own FLOPs and bytes are
what *its* algorithm needs for one call, for its roofline share.
"""

from __future__ import annotations


def transformer_lm_flops_per_token(num_layers: int, d_model: int, d_ff: int,
                                   vocab_size: int, seq: int,
                                   causal: bool = True) -> float:
    """Decoder LM, forward + backward, a token.

    Matmul weights a layer: qkv 3d², proj d², MLP 2·d·d_ff; the (tied)
    head V·d — the embedding *lookup* is no matmul.  6 FLOPs a weight a
    token (2 forward, 4 backward).  Attention a layer: QKᵀ and PV are
    2·T·d each forward, x3 with backward, halved by the causal mask:
    6·T·d (12·T·d bidirectional)."""
    weights = num_layers * (4 * d_model * d_model + 2 * d_model * d_ff) \
        + vocab_size * d_model
    attention = num_layers * seq * d_model * (6 if causal else 12)
    return 6.0 * weights + attention


def conv_macs(out_hw: int, kernel: int, cin: int, cout: int) -> int:
    return out_hw * out_hw * kernel * kernel * cin * cout


def resnet_forward_macs(stage_sizes, image_size: int = 224,
                        num_filters: int = 64,
                        num_classes: int = 1000) -> int:
    """Multiply-adds of one forward pass of a bottleneck ResNet v1.5
    (7x7/2 stem, 3x3/2 max-pool, stride in the 3x3, 1x1 projection on
    the first block of a stage), an image.  ResNet-50 at 224: 4.09e9."""
    hw = image_size // 2
    macs = conv_macs(hw, 7, 3, num_filters)
    hw //= 2
    cin = num_filters
    for i, blocks in enumerate(stage_sizes):
        f = num_filters * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out_hw = hw // stride
            macs += conv_macs(hw, 1, cin, f)
            macs += conv_macs(out_hw, 3, f, f)
            macs += conv_macs(out_hw, 1, f, 4 * f)
            if j == 0:
                macs += conv_macs(out_hw, 1, cin, 4 * f)
            cin, hw = 4 * f, out_hw
    return macs + cin * num_classes


def resnet_flops_per_image(stage_sizes, image_size: int = 224,
                           num_filters: int = 64,
                           num_classes: int = 1000) -> float:
    """Forward + backward: 3 x forward, 2 FLOPs a multiply-add."""
    return 6.0 * resnet_forward_macs(stage_sizes, image_size, num_filters,
                                     num_classes)


def flash_step_cost(rows: int, seq: int, head_dim: int, layers: int,
                    causal: bool = True, itemsize: int = 2) -> dict:
    """What the flash forward and backward kernels of one train step
    need on one chip: ``rows`` = batch rows x heads on the chip.

    A (T, T, d) matmul is 2·T²·d FLOPs, half under the causal mask.
    Forward needs two (QKᵀ, PV); backward five (the scores again, dP,
    dV, dQ, dK) — a backward that recomputes more than that is slower
    than its roofline, which is the point of the share.  Bytes: forward
    reads q, k, v and writes o and the fp32 row statistics; backward
    reads q, k, v, o, dO and the statistics and writes dq, dk, dv."""
    matmul = 2.0 * seq * seq * head_dim * (0.5 if causal else 1.0)
    tensor = seq * head_dim * itemsize
    stats = seq * 4
    return {
        "flops": rows * layers * 7 * matmul,
        "bytes": rows * layers * (12 * tensor + 2 * stats),
    }


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which bound binds."""
    compute = flops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
