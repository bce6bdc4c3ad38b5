"""Device step, the optimizer (engine/sgd.py ``_apply_update``): the device
time of the operations under the ``tm.optimizer`` scope, per optimizer
step of the steady trace, mean over the chips."""

from benchmark import scopes


def read(run):
    return scopes.scope_ms_per_step(run, "tm.optimizer")
