"""Analytic FLOP models for the benchmark workloads.

The reference grounds every reported number in an analytic model (its
collectives tester converts measured time to bus GB/s with an algorithm
bandwidth formula, ``test/collectives_all.lua:313-318``). This module does
the same for compute: walk the model architectures layer by layer, count
multiply-accumulate FLOPs, and convert a measured samples/sec into achieved
FLOP/s and model-FLOPs-utilization (MFU) against the chip's peak.

Conventions (stated so the numbers are auditable):
- 1 MAC = 2 FLOPs (multiply + add), the standard accounting.
- Training step = 3x forward FLOPs (backward ~= 2x forward: one pass for
  input grads, one for weight grads). Elementwise ops (relu, batchnorm,
  pooling, softmax) are ignored — they are <1% of conv/dense FLOPs and are
  VPU work, not MXU work, so including them would overstate MFU.
- Peaks are per-chip dense bf16 from Google's published specs. MFU is
  reported as ``None`` when the device kind is unknown (e.g. CPU) rather
  than guessed.
"""

from __future__ import annotations

import math
from typing import Optional


def conv2d_flops(h: int, w: int, cin: int, cout: int, kh: int, kw: int,
                 stride: int = 1) -> tuple[int, int, int]:
    """FLOPs of a SAME-padded conv; returns (flops, h_out, w_out)."""
    ho, wo = math.ceil(h / stride), math.ceil(w / stride)
    return 2 * kh * kw * cin * cout * ho * wo, ho, wo


def dense_flops(cin: int, cout: int) -> int:
    return 2 * cin * cout


def resnet_forward_flops(image: int = 224, stage_sizes=(3, 4, 6, 3),
                         bottleneck: bool = True, num_classes: int = 1000,
                         num_filters: int = 64) -> int:
    """Per-sample forward FLOPs of ``models.resnet.ResNet`` (NHWC input).

    Mirrors the module walk in ``models/resnet.py`` exactly: 7x7/2 stem,
    3x3/2 max-pool, then bottleneck (1x1 -> 3x3 -> 1x1, x4 expansion) or
    basic (3x3 -> 3x3) stages with stride-2 at each stage entry (v1.5:
    stride on the 3x3) and a 1x1 projection whenever shapes change.
    For 224px ResNet-50 this yields ~8.2 GFLOP forward (= the commonly
    cited ~4.1 GMACs at 2 FLOPs/MAC).
    """
    total, h, w = 0, image, image
    f, h, w = conv2d_flops(h, w, 3, num_filters, 7, 7, stride=2)
    total += f
    h, w = math.ceil(h / 2), math.ceil(w / 2)  # max_pool 3x3 s2 SAME
    cin = num_filters
    for i, count in enumerate(stage_sizes):
        feats = num_filters * 2 ** i
        cout = feats * 4 if bottleneck else feats
        for j in range(count):
            stride = 2 if (i > 0 and j == 0) else 1
            if bottleneck:
                f1, _, _ = conv2d_flops(h, w, cin, feats, 1, 1)
                f2, h2, w2 = conv2d_flops(h, w, feats, feats, 3, 3, stride)
                f3, _, _ = conv2d_flops(h2, w2, feats, cout, 1, 1)
                total += f1 + f2 + f3
            else:
                f2, h2, w2 = conv2d_flops(h, w, cin, feats, 3, 3, stride)
                f3, _, _ = conv2d_flops(h2, w2, feats, feats, 3, 3)
                total += f2 + f3
            if cin != cout or stride != 1:
                fp, _, _ = conv2d_flops(h, w, cin, cout, 1, 1, stride)
                total += fp
            h, w, cin = h2, w2, cout
    total += dense_flops(cin, num_classes)
    return total


def transformer_forward_flops(seq: int, d_model: int, num_layers: int,
                              num_heads: int, head_dim: int, vocab: int,
                              mlp_ratio: int = 4) -> int:
    """Per-SEQUENCE forward FLOPs of ``models.LongContextTransformer``.

    Counts the matmuls as executed: the attention kernels compute the full
    T x T score/value products and mask afterwards (streaming-softmax ring
    blocks do the same per block pair), so causal masking does NOT halve
    the counted FLOPs — masked MACs still run on the MXU. Embedding lookup
    (a gather) is free; the vocabulary head is not. Divide by ``seq`` for
    per-token FLOPs (the LM bench reports tokens/sec)."""
    attn_dim = num_heads * head_dim
    per_layer = (
        dense_flops(d_model, 3 * attn_dim) * seq          # qkv projection
        + 2 * seq * seq * attn_dim                        # q @ k^T
        + 2 * seq * seq * attn_dim                        # softmax @ v
        + dense_flops(attn_dim, d_model) * seq            # output proj
        + dense_flops(d_model, mlp_ratio * d_model) * seq # mlp up
        + dense_flops(mlp_ratio * d_model, d_model) * seq # mlp down
    )
    head = dense_flops(d_model, vocab) * seq
    return num_layers * per_layer + head


def train_flops(forward_flops: int) -> int:
    """Forward + backward (~2x forward) per-sample training FLOPs."""
    return 3 * forward_flops


# Per-chip dense bf16 peak FLOP/s, keyed by the exact ``device_kind`` a chip
# reported to jax (not a substring guess). Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16). A kind is added here when it
# has been read off such a chip.
_TPU_PEAK_BF16 = {
    "TPU v5 lite": 197e12,  # v5e
}


def device_peak_flops(device) -> Optional[float]:
    """Per-chip bf16 peak for a jax device. ``None`` only for the CPU
    (no MFU there); a TPU whose ``device_kind`` is not in the table is an
    error, not a default."""
    if device.platform == "cpu":
        return None
    if device.platform != "tpu" or device.device_kind not in _TPU_PEAK_BF16:
        raise ValueError(
            f"no peak-FLOP/s entry for platform {device.platform!r}, "
            f"device_kind {device.device_kind!r} (known: "
            f"{sorted(_TPU_PEAK_BF16)}); add it to utils/flops.py with "
            "its source"
        )
    return _TPU_PEAK_BF16[device.device_kind]


def mfu(samples_per_sec_per_chip: float, flops_per_sample: int,
        device) -> tuple[float, Optional[float]]:
    """(achieved FLOP/s per chip, fraction-of-peak or None)."""
    achieved = samples_per_sec_per_chip * flops_per_sample
    peak = device_peak_flops(device)
    return achieved, (achieved / peak if peak else None)
