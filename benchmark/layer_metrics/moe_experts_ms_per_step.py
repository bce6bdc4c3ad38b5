"""Expert layer, the grouped products (parallel/ep.py
``moe_local_experts``: gate, up and down of the held experts by
``lax.ragged_dot``, and their gradients): the device time under the
``tm.moe.experts`` scope, forward, recomputation and backward, per
optimizer step of the steady trace."""

from benchmark import inner_scopes


def read(run):
    return inner_scopes.inner_ms_per_step(run, "tm.moe.experts")
