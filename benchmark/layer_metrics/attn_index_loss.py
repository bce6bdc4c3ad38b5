"""Attention, the indexers' own loss (parallel/selected_attention.py
``note_selection``): ``L_I``, the divergence of a layer's attention
probabilities from the softmax of its index scores over the selection, mean
over the selecting layers, in the last step whose loss the engine read
(gauge ``tm_attn_index_loss_last_step``). It falls as the indexer learns
which keys the attention weighs. None where the program has no such gauge."""

from benchmark import scopes


def read(run):
    return scopes.counter("tm_attn_index_loss_last_step")
