"""Expert layer, its balance (parallel/ep.py ``note_expert_load``): the
program's gauge ``tm_moe_max_over_mean_load``, the tokens the most loaded
held expert received over the mean held expert's, of the layer where that
is worst, in the last step whose loss the engine read (the load rides the
engine's ``model_state``; the gauge is set at an epoch's end)."""

from benchmark import scopes


def read(run):
    return scopes.counter("tm_moe_max_over_mean_load")
