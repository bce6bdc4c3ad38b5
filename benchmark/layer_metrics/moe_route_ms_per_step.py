"""Expert layer, everything but the products (parallel/ep.py
``moe_local_experts``): the device time under ``tm.moe.route`` (top-k,
softmax, ordering the routes by expert, gathering their rows) and
``tm.moe.combine`` (the rows back in token order, summed over a token's
routes), forward, recomputation and backward, per optimizer step of the
steady trace."""

from benchmark import inner_scopes


def read(run):
    return inner_scopes.inner_ms_per_step(
        run, "tm.moe.route", "tm.moe.combine")
