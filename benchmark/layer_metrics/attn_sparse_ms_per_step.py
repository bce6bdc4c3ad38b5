"""Attention over the selection (parallel/selected_attention.py): the
device time of the operations under the ``tm.attn.sparse`` scope (rotary
position, the mask's tables, the attention kernels forward and backward,
the head-summed probabilities, the indexer's loss and its gradient), per
optimizer step of the steady trace. None where the program opens no such
scope."""

from benchmark import inner_scopes


def read(run):
    return inner_scopes.inner_ms_per_step(run, "tm.attn.sparse")
