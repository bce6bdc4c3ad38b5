"""Operations per sequence of a hybrid decoder held by share: in every layer
a state-space mixer beside grouped-head attention over the whole causal
prefix, then a dense gated feed-forward of which some columns are held.
``decoder_flops.py``'s conventions: a multiply-add is two operations; only
what the algorithm needs is counted, and only what is held here; norms,
rotary position, softmax, the gates' and the convolution's SiLU, softplus
and the scalar multipliers are not counted; a training step is three
forward passes' worth (``flops.train_flops``).

The scan is counted **by the recurrence's own operations**, not by any way
of computing it: a position and head, the state's update ``S <- decay S +
(delta x) (x) B`` (a multiply, and a multiply-add, an element of the ``[P,
N]`` state: 3 P N) and the read-out ``S C`` (a multiply-add an element: 2 P
N). So the count does not move when the chunking changes or a kernel is
written, and the chunked dual's masked ``[chunk, chunk]`` products, more
operations for the same result, raise no ``mfu``."""

from __future__ import annotations

from benchmark.decoder_flops import visible_pairs


def scan_forward_flops(seq, heads, head_dim, state) -> int:
    """The selective recurrence over ``seq`` positions: ``5 P N`` a position
    and head."""
    return 5 * seq * heads * head_dim * state


def hybrid_decoder_forward_flops(seq, d_model, layers, heads, kv_heads,
                                 head_dim, ssm_heads, ssm_head_dim,
                                 ssm_groups, ssm_state, conv_width,
                                 mlp_columns, vocab) -> int:
    """Forward operations of one sequence: ``layers`` layers alike, each
    with ``heads`` query and ``kv_heads`` KV heads of ``head_dim``, a mixer
    of ``ssm_heads`` heads of ``ssm_head_dim`` reading ``ssm_groups`` groups
    of ``B`` and ``C`` of ``ssm_state``, and ``mlp_columns`` columns of the
    gated feed-forward; the head over ``vocab`` rows."""
    q, kv = heads * head_dim, kv_heads * head_dim
    inner, bc = ssm_heads * ssm_head_dim, ssm_groups * ssm_state
    attention = (
        2 * seq * d_model * (q + 2 * kv) + 2 * seq * q * d_model  # q k v, o
        + 2 * 2 * visible_pairs(seq) * q)                   # scores, values
    mixer = (
        2 * seq * d_model * (2 * inner + 2 * bc + ssm_heads)  # z x B C dt
        + 2 * seq * inner * d_model                           # W_out
        + 2 * conv_width * seq * (inner + 2 * bc)             # a tap, 2
        + scan_forward_flops(seq, ssm_heads, ssm_head_dim, ssm_state))
    feed_forward = 3 * 2 * seq * d_model * mlp_columns      # gate, up, down
    return layers * (attention + mixer + feed_forward) \
        + 2 * seq * d_model * vocab
