"""Operations per sequence of a sparse decoder whose layers attend over the
keys a learned indexer selects for each query, on one chip's share of the
experts. ``flops.py``'s conventions (``decoder_flops.py``'s): a
multiply-add is two operations; only what the algorithm needs is counted;
norms, rotary position, softmax, the k-th largest and gathering are not
counted; a training step is three forward passes' worth
(``flops.train_flops``).

What the algorithm needs here: attention's two products over the SELECTED
query-key pairs alone, ``sum_i min(i + 1, top_k)``, however many pairs the
program multiplies on its way; the indexer's scores, one product of ``hI``
heads of ``dI`` over EVERY causal pair, since a key cannot be passed over
before it is scored; the indexer's three projections."""

from __future__ import annotations

from benchmark.decoder_flops import visible_pairs


def selected_pairs(seq: int, top_k: int) -> int:
    """``sum_i min(i + 1, top_k)``: the pairs query ``i`` attends to when it
    selects ``top_k`` of its ``i + 1`` causal keys (the same count as a
    causal band of ``top_k``)."""
    return visible_pairs(seq, top_k)


def selected_decoder_forward_flops(seq, d_model, heads, kv_heads, head_dim,
                                   expert_width, experts, top_k, held, vocab,
                                   layers, index_heads, index_dim,
                                   index_top_k) -> int:
    """Forward operations of one sequence through ``layers`` selected
    layers and the head. ``held`` of the ``experts`` are here, and a
    token's ``top_k`` routes fall on them at the nominal share."""
    q, kv = heads * head_dim, kv_heads * head_dim
    projections = 2 * seq * d_model * (q + 2 * kv) + 2 * seq * q * d_model
    router = 2 * seq * d_model * experts
    routed = seq * top_k * held * (3 * 2 * d_model * expert_width) // experts
    attention = 2 * 2 * selected_pairs(seq, index_top_k) * q
    index_scores = 2 * index_heads * index_dim * visible_pairs(seq)
    index_projections = 2 * seq * d_model * (
        index_heads * index_dim + index_dim + index_heads)
    layer = (projections + router + routed + attention + index_scores
             + index_projections)
    return layers * layer + 2 * seq * d_model * vocab
