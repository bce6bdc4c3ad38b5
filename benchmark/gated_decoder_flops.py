"""Operations per sequence of a sparse decoder held by share: a count of
query and KV heads by layer with a gate on each head, full or
sliding-window attention by layer, leading layers with a dense
feed-forward of which some columns are held, then layers with a shared
expert whole and this chip's share of the routed experts.
``decoder_flops.py``'s conventions: a multiply-add is two operations; only
what the algorithm needs is counted, and only what is held here (the absent
heads, columns and experts cost this chip nothing); norms, rotary position,
softmax, sigmoids, sorting and gathering are not counted; a training step
is three forward passes' worth (``flops.train_flops``)."""

from __future__ import annotations

from benchmark.decoder_flops import visible_pairs


def gated_decoder_forward_flops(seq, d_model, heads, kv_heads, head_dim,
                                windows, dense_layers, dense_columns,
                                expert_width, shared_width, experts, top_k,
                                held, vocab) -> int:
    """Forward operations of one sequence. ``heads`` and ``windows`` have
    one entry a layer: the query heads held there, and None for full
    attention or the window's length; ``kv_heads`` are held in every
    layer. The first ``dense_layers`` layers multiply by ``dense_columns``
    columns of a gated feed-forward; each of the others by a router over
    all ``experts``, a shared expert of ``shared_width`` and, at the
    nominal share ``held / experts`` of a token's ``top_k`` routes, a
    routed expert of ``expert_width`` (what a batch really sends here is a
    counter's to say)."""
    def gated(width):  # gate, up, down
        return 3 * 2 * d_model * width

    total = 2 * seq * d_model * vocab
    for layer, (n, window) in enumerate(zip(heads, windows)):
        q, kv = n * head_dim, kv_heads * head_dim
        # q, k, v, a gate a query head, o; scores and values over the pairs
        total += 2 * seq * d_model * (q + 2 * kv + n) + 2 * seq * q * d_model
        total += 2 * 2 * visible_pairs(seq, window) * q
        if layer < dense_layers:
            total += seq * gated(dense_columns)
        else:
            total += seq * (2 * d_model * experts + gated(shared_width))
            total += seq * top_k * held * gated(expert_width) // experts
    return total
