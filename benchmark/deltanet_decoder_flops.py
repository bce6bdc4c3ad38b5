"""Operations per sequence of a gated-delta decoder on one chip's share of
the experts: layers whose mixer is the gated delta rule behind a short
convolution, layers of softmax attention with an elementwise output gate,
and under each a router over all the experts, a shared expert whole and this
chip's share of the routed ones. ``decoder_flops.py``'s conventions: a
multiply-add is two operations; only what the algorithm needs is counted,
and only what is held here; norms, rotary position, softmax, sigmoids, SiLU,
sorting and gathering are not counted; a training step is three forward
passes' worth (``flops.train_flops``).

The rule is counted **by the recurrence's own operations**, not by any way
of computing it: a position and value head with a state of ``[dk, dv]``, the
decay ``S <- e^g S`` (a multiply an element), the read ``S^T k``, the update
``S + k u^T`` and the output ``S^T q`` (a multiply-add an element each):
``7 dk dv``. So the count does not move when the chunking changes or a
kernel is written, and the chunked form's triangular system and ``[chunk,
chunk]`` products, more operations for the same result, raise no ``mfu``."""

from __future__ import annotations

from benchmark.decoder_flops import visible_pairs


def delta_rule_forward_flops(seq, value_heads, dk, dv) -> int:
    """The recurrence over ``seq`` positions, a layer."""
    return seq * value_heads * 7 * dk * dv


def deltanet_decoder_forward_flops(
        seq, d_model, linear_layers, full_layers, key_heads, value_heads, dk,
        dv, taps, heads, kv_heads, head_dim, expert_width, shared_width,
        experts, top_k, held, vocab) -> int:
    """Forward operations of one sequence: ``linear_layers`` layers of the
    delta mixer (``key_heads`` of ``dk`` read by ``value_heads`` of ``dv``,
    ``taps`` taps over q, k and v), ``full_layers`` of attention over every
    causal pair (``heads`` query heads with a gate as wide as the head, to
    ``kv_heads`` KV heads of ``head_dim``); in each a router over all
    ``experts``, a shared expert of ``shared_width`` with one gate a token
    and, at the nominal share ``held / experts`` of a token's ``top_k``
    routes, a routed expert of ``expert_width`` (what a batch really sends
    here is a counter's to say); the head over ``vocab`` rows."""
    def gated(width):  # gate, up, down
        return 3 * 2 * d_model * width

    kw, vw = key_heads * dk, value_heads * dv
    linear = (
        2 * seq * d_model * (2 * kw + 2 * vw + 2 * value_heads)  # qkvz, ba
        + 2 * seq * vw * d_model                                 # out
        + 2 * seq * taps * (2 * kw + vw)
        + delta_rule_forward_flops(seq, value_heads, dk, dv))
    q, kv = heads * head_dim, kv_heads * head_dim
    full = (
        2 * seq * d_model * (2 * q + 2 * kv) + 2 * seq * q * d_model
        + 2 * 2 * visible_pairs(seq) * q)  # scores and values over the pairs
    sparse = (
        seq * (2 * d_model * experts + gated(shared_width) + 2 * d_model)
        + seq * top_k * held * gated(expert_width) // experts)
    return (linear_layers * linear + full_layers * full
            + (linear_layers + full_layers) * sparse
            + 2 * seq * d_model * vocab)
