"""Operations per sequence of a decoder whose mixer is, by layer, a gated
short convolution or softmax attention over every causal pair, with leading
dense feed-forwards and then this chip's share of routed experts under a
router over all of them, and a vocabulary head that is the embedding's
table. ``decoder_flops.py``'s conventions: a multiply-add is two operations;
only what the algorithm needs is counted, and only what is held here; norms,
rotary position, softmax, sigmoids, SiLU, sorting and gathering are not
counted; a training step is three forward passes' worth
(``flops.train_flops``).

The convolution mixer is two products and elementwise work between them:
``[B | C | x] = a W_in`` and ``y W_out`` are counted as products; the two
gates (a multiply an element each) and the ``taps`` taps (a multiply-add an
element each) as the elementwise operations they are, ``2 + 2 taps`` a
channel and position, whatever a program or a kernel does to make them. A
tied head is one product: the table is read, not multiplied, by the
lookup."""

from __future__ import annotations

from benchmark.decoder_flops import visible_pairs


def short_conv_forward_flops(seq, d_model, taps) -> int:
    """The mixer's middle over ``seq`` positions, a layer: the two gates
    and the taps."""
    return seq * d_model * (2 + 2 * taps)


def short_conv_bytes(seq, d_model, itemsize, recomputed) -> int:
    """HBM bytes the same operation needs a layer and sequence, forward and
    backward, whatever implements it: ``[B | C | x]`` read and ``y`` written
    forward (4 elements a channel and position); those three and ``dy`` read
    and their three gradients written backward (7); forward once more where
    the block is recomputed. The taps and their gradient are ``2 taps``
    rows: not counted."""
    return seq * d_model * itemsize * (4 + 7 + (4 if recomputed else 0))


def sconv_decoder_forward_flops(seq, d_model, kinds, taps, heads, kv_heads,
                                head_dim, dense_layers, dense_width,
                                expert_width, experts, top_k, held,
                                vocab) -> int:
    """Forward operations of one sequence. ``kinds`` has one entry a layer,
    ``"conv"`` or ``"full_attention"`` (``heads`` query heads to
    ``kv_heads`` KV heads of ``head_dim``). The first ``dense_layers``
    layers multiply by a gated feed-forward of ``dense_width``; each of the
    others by a router over all ``experts`` and, at the nominal share ``held
    / experts`` of a token's ``top_k`` routes, a routed expert of
    ``expert_width`` (what a batch really sends here is a counter's to
    say); the head over ``vocab`` rows."""
    def gated(width):  # gate, up, down
        return 3 * 2 * d_model * width

    q, kv = heads * head_dim, kv_heads * head_dim
    total = 2 * seq * d_model * vocab
    for layer, kind in enumerate(kinds):
        if kind == "conv":
            total += 2 * seq * d_model * 3 * d_model  # into [B | C | x]
            total += 2 * seq * d_model * d_model      # out
            total += short_conv_forward_flops(seq, d_model, taps)
        else:
            total += 2 * seq * d_model * (q + 2 * kv) + 2 * seq * q * d_model
            total += 2 * 2 * visible_pairs(seq) * q  # scores and values
        if layer < dense_layers:
            total += seq * gated(dense_width)
        else:
            total += seq * 2 * d_model * experts
            total += seq * top_k * held * gated(expert_width) // experts
    return total
