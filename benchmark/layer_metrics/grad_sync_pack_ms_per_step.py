"""Gradient sync, packing (nn/ ``_sync_flat_group``): the device time under
``tm.grad_sync/pack`` and ``tm.grad_sync/unpack``, the copies into the
flat buffer and out of it, per optimizer step of the steady trace."""

from benchmark import scopes


def read(run):
    return scopes.scope_ms_per_step(
        run, "tm.grad_sync/pack", "tm.grad_sync/unpack")
