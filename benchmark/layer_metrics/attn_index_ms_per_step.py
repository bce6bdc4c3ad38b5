"""Attention, the indexer of the layers that select their keys
(models/decoder.py ``MoEDecoderBlock._indexer``, parallel/
selected_attention.py ``_index_scores``): the device time of the operations
under the ``tm.attn.index`` scope (the indexer's three projections, its
LayerNorm and rotation, and the float32 index scores of every causal pair),
forward, recomputation and backward (the scores are made again there), per
optimizer step of the steady trace. None where the program opens no such
scope."""

from benchmark import inner_scopes


def read(run):
    return inner_scopes.inner_ms_per_step(run, "tm.attn.index")
