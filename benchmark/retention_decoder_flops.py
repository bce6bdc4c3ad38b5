"""Operations per sequence of a retentive decoder held by share: in every
layer power retention of degree 2 where attention stood, read by grouped
query heads, then a dense gated feed-forward of which some columns are
held. ``decoder_flops.py``'s conventions: a multiply-add is two operations;
only what the algorithm needs is counted, and only what is held here; norms,
rotary position, the gate's log-sigmoid and the division by the normaliser
are not counted; a training step is three forward passes' worth
(``flops.train_flops``).

Retention is counted **by the recurrence's own operations, with the
symmetric state**, not by any way of computing it: ``D = d (d + 1) / 2``
features of a head of ``d``, a state of ``[D, d + 1]`` a KV head (the
normaliser carried with the values). A position: the state's decay and
update ``S <- e^g S + phi(k) (x) [v | 1]``, a multiply and a multiply-add an
element (``3 D (d + 1)`` a KV head); the read-out ``phi(q)^T S``, a
multiply-add an element (``2 D (d + 1)`` a query head); ``phi`` itself, a
multiply a feature (``D`` a head, query and KV). So the count does not move
when the chunking changes or a kernel is written, and the chunked form's
masked ``[chunk, chunk]`` products, or the attention form's over every causal
pair (three times as many at 32,768 positions), raise no ``mfu``."""

from __future__ import annotations


def state_features(head_dim: int) -> int:
    return head_dim * (head_dim + 1) // 2


def retention_forward_flops(seq, heads, kv_heads, head_dim) -> int:
    """The recurrence over ``seq`` positions, a layer."""
    features = state_features(head_dim)
    state = features * (head_dim + 1)
    return seq * (3 * state * kv_heads + 2 * state * heads
                  + features * (heads + kv_heads))


def retention_decoder_forward_flops(seq, d_model, layers, heads, kv_heads,
                                    head_dim, mlp_columns, vocab) -> int:
    """Forward operations of one sequence: ``layers`` layers alike, each
    with ``heads`` query heads reading ``kv_heads`` KV heads of ``head_dim``,
    a gate a KV head, and ``mlp_columns`` columns of the gated feed-forward;
    the head over ``vocab`` rows."""
    q, kv = heads * head_dim, kv_heads * head_dim
    projections = (
        2 * seq * d_model * (q + 2 * kv + kv_heads)  # q k v, the gate
        + 2 * seq * q * d_model)                     # o
    feed_forward = 3 * 2 * seq * d_model * mlp_columns   # gate, up, down
    return layers * (
        projections + feed_forward
        + retention_forward_flops(seq, heads, kv_heads, head_dim)
    ) + 2 * seq * d_model * vocab
