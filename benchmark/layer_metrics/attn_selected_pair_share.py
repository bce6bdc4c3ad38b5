"""Attention, what the indexers selected (parallel/selected_attention.py
``note_selection`` over ``note_selected_layers``): the query-key pairs
selected in the last step whose loss the engine read
(``tm_attn_selected_pairs_per_step``, counted from the masks the step
made) as a share of the causal pairs of the selecting layers
(``tm_attn_causal_pairs_per_step``, static shapes). ``sum_i min(i + 1,
2048)`` over ``t (t + 1) / 2``: 23.44 % at 16,384 positions; any other
reading says the selection did not run as the model states it. None where
the program has no such gauge."""

from benchmark import scopes


def read(run):
    selected = scopes.counter("tm_attn_selected_pairs_per_step")
    causal = scopes.counter("tm_attn_causal_pairs_per_step")
    if selected is None or not causal:
        return None
    return 100.0 * selected / causal
