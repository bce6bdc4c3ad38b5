"""Operations per sequence of a sparse decoder whose layers attend over
the whole causal prefix or within a sliding window, on one chip's share of
the experts. ``flops.py``'s conventions: a multiply-add is two operations;
only what the algorithm needs is counted (no masked-out score, nothing
recomputed); norms, rotary position, softmax, sorting and gathering are
not counted; a training step is three forward passes' worth
(``flops.train_flops``)."""

from __future__ import annotations


def visible_pairs(seq: int, window=None) -> int:
    """Query-key pairs a causal layer needs: key ``j`` for query ``i`` iff
    ``0 <= i - j`` (and ``< window``): exactly, not by blocks."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def moe_decoder_forward_flops(seq, d_model, heads, kv_heads, head_dim,
                              expert_width, experts, top_k, held, vocab,
                              windows) -> int:
    """Forward operations of one sequence. ``windows`` has one entry a
    layer: None for full attention, the window's length otherwise.
    ``held`` of the ``experts`` are here, and a token's ``top_k`` routes
    fall on them at the nominal share ``held / experts`` (what the routing
    of a given batch really sends here is a counter's to say, not this
    count's)."""
    q, kv = heads * head_dim, kv_heads * head_dim
    projections = 2 * seq * d_model * (q + 2 * kv) + 2 * seq * q * d_model
    router = 2 * seq * d_model * experts
    per_route = 3 * 2 * d_model * expert_width  # gate, up, down
    routed = seq * top_k * held * per_route // experts
    total = 0
    for window in windows:
        # scores and values: two products over every visible pair
        total += (projections + router + routed
                  + 2 * 2 * visible_pairs(seq, window) * q)
    return total + 2 * seq * d_model * vocab
