"""The yardstick's arithmetic: operations per sample and the chip's peak.

Copied in spirit from ``torchmpi_tpu/utils/flops.py`` so that a later PR can
change the program and not the yardstick. Conventions: one multiply-add is
two operations; a training step is three forward passes' worth (backward is
one pass for input gradients and one for weight gradients); elementwise
work, normalisation, pooling and softmax are not counted; operations that
the algorithm does not need (masked-out attention products, recomputed
activations) are not counted either, so ``mfu`` cannot be raised by doing
more work.
"""

from __future__ import annotations

import math

# Per-chip dense bf16 peak in operations per second, keyed by the exact
# ``device_kind`` the chip reports to jax. Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16. A kind that is not here is
# an error, never a default.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def peak_flops(device_kind: str) -> float:
    if device_kind not in PEAK_BF16_FLOPS:
        raise ValueError(
            f"no peak for device_kind {device_kind!r} (known: "
            f"{sorted(PEAK_BF16_FLOPS)}); add it to benchmark/flops.py "
            "with its source"
        )
    return PEAK_BF16_FLOPS[device_kind]


def conv2d_flops(h, w, cin, cout, kh, kw, stride=1):
    """Operations of a SAME-padded convolution: (flops, h_out, w_out)."""
    ho, wo = math.ceil(h / stride), math.ceil(w / stride)
    return 2 * kh * kw * cin * cout * ho * wo, ho, wo


def resnet_forward_flops(image, stage_sizes, num_classes, num_filters=64):
    """Forward operations per image of a bottleneck ResNet v1.5 (stride on
    the 3x3): 7x7/2 stem, 3x3/2 max-pool, 1x1 -> 3x3 -> 1x1 (x4) blocks,
    a 1x1 projection wherever the shape changes, a dense head. ResNet-50 at
    224 px gives 8.2 GFLOP, the usual 4.1 GMAC."""
    total, (h, w) = 0, (image, image)
    f, h, w = conv2d_flops(h, w, 3, num_filters, 7, 7, 2)
    total += f
    h, w = math.ceil(h / 2), math.ceil(w / 2)
    cin = num_filters
    for i, count in enumerate(stage_sizes):
        feats = num_filters * 2**i
        cout = 4 * feats
        for j in range(count):
            stride = 2 if (i > 0 and j == 0) else 1
            f1, _, _ = conv2d_flops(h, w, cin, feats, 1, 1)
            f2, h2, w2 = conv2d_flops(h, w, feats, feats, 3, 3, stride)
            f3, _, _ = conv2d_flops(h2, w2, feats, cout, 1, 1)
            total += f1 + f2 + f3
            if cin != cout or stride != 1:
                fp, _, _ = conv2d_flops(h, w, cin, cout, 1, 1, stride)
                total += fp
            h, w, cin = h2, w2, cout
    return total + 2 * cin * num_classes


def causal_lm_forward_flops(seq, d_model, layers, heads, head_dim, vocab,
                            mlp_ratio=4):
    """Forward operations per sequence of a decoder-only transformer with
    an untied output head. Causal attention needs the products on and
    under the diagonal only, seq*(seq+1)/2 of the seq*seq, so the score
    and value products are counted at that share (the issue's "half")."""
    attn = heads * head_dim
    pairs = seq * (seq + 1) // 2
    per_layer = (
        2 * d_model * 3 * attn * seq        # q, k, v projections
        + 2 * pairs * attn                  # q . k
        + 2 * pairs * attn                  # weights . v
        + 2 * attn * d_model * seq          # output projection
        + 2 * 2 * d_model * mlp_ratio * d_model * seq  # MLP up and down
    )
    return layers * per_layer + 2 * d_model * vocab * seq


def train_flops(forward_flops):
    return 3 * forward_flops


def mfu_percent(samples_per_s_per_chip, flops_per_sample, device_kind):
    return 100.0 * samples_per_s_per_chip * flops_per_sample / peak_flops(
        device_kind
    )
