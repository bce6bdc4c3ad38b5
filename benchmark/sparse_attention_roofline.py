"""What the attention over a selection NEEDS, as functions of the shapes:
the operations and the HBM bytes of one selected-attention layer forward
and backward, for the roofline share of its kernels
(``layer_metrics/attn_sparse_kernel_roofline.py``).

Counted is what the algorithm needs, not what a program runs: the products
over the SELECTED query-key pairs alone (``sum_i min(i + 1, top_k)``), two
forward (scores, values) and four backward (dP, dV, dQ, dK), nothing for a
recomputed forward or for the probabilities made again in backward; the
bytes of ``q``, ``k``, ``v`` and the output read or written once forward,
and of those, the output's gradient and the three gradients once backward.
The indexer's loss needs the probabilities summed over heads, which the
forward has: no further product. A multiply-add is two operations."""

from __future__ import annotations

from benchmark.decoder_flops import visible_pairs

# Google Cloud documentation, "TPU v5e": 819 GB/s of HBM a chip
PEAK_HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}


def peak_hbm_bytes_per_s(kind: str) -> float:
    if kind not in PEAK_HBM_BYTES_PER_S:
        raise KeyError(
            f"no HBM peak for device kind {kind!r} (known: "
            f"{sorted(PEAK_HBM_BYTES_PER_S)})")
    return PEAK_HBM_BYTES_PER_S[kind]


def layer_ops(seq, heads, head_dim, top_k) -> int:
    """Operations of one layer's attention over its selection, forward and
    backward, a sequence."""
    return 6 * 2 * visible_pairs(seq, top_k) * heads * head_dim


def layer_bytes(seq, heads, kv_heads, head_dim, itemsize=2) -> int:
    """HBM bytes of the same: q, k, v, out forward; q, k, v, out, d out in
    and dq, dk, dv out backward."""
    q, kv = seq * heads * head_dim, seq * kv_heads * head_dim
    forward = 2 * q + 2 * kv
    backward = 3 * q + 2 * kv + q + 2 * kv
    return itemsize * (forward + backward)


def least_seconds(cfg, peak_flops, peak_bytes_per_s):
    """(seconds, "ops" or "bytes"): the least time a step's selected
    layers' attention could take on the chip, and which peak bounds it."""
    layers, batch = cfg["num_hidden_layers"], cfg["per_chip_batch"]
    ops = layers * batch * layer_ops(
        cfg["sequence_length"], cfg["num_attention_heads"], cfg["head_dim"],
        cfg["sa_config"]["topk"])
    moved = layers * batch * layer_bytes(
        cfg["sequence_length"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"])
    by_ops, by_bytes = ops / peak_flops, moved / peak_bytes_per_s
    return max(by_ops, by_bytes), "ops" if by_ops >= by_bytes else "bytes"
