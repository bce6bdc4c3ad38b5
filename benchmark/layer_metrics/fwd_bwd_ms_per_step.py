"""Device step, forward and backward (engine/sgd.py ``_value_and_grad``):
the device time of the operations under the ``tm.fwd_bwd`` scope, per
optimizer step of the steady trace, mean over the chips."""

from benchmark import scopes


def read(run):
    return scopes.scope_ms_per_step(run, "tm.fwd_bwd")
