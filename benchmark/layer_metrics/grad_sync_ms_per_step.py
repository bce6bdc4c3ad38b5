"""Gradient sync (nn/: ``in_graph_synchronize_gradients*``): the device time
of the operations under ``tm.grad_sync`` and its phases (pack, reduce,
unpack), per optimizer step of the steady trace, mean over the chips. On
one chip the sync reduces over one rank and should cost nothing."""

from benchmark import scopes


def read(run):
    return scopes.scope_ms_per_step(run, "tm.grad_sync")
