"""Attention, the selection (parallel/selected_attention.py ``_select``,
``_cut_of_ties``, ``_chosen``): the device time of the operations under the
``tm.attn.select`` scope (each query's k-th largest index score, the tie
rule, the mask), forward and recomputation, and in backward the mask made
again from the saved thresholds, per optimizer step of the steady trace.
None where the program opens no such scope."""

from benchmark import inner_scopes


def read(run):
    return inner_scopes.inner_ms_per_step(run, "tm.attn.select")
