"""Expert layer, the shared expert (models/decoder.py ``MoEDecoderBlock``
with ``shared_width``): the device time of the operations under the
``tm.moe.shared`` scope (the three dense products of the expert every token
takes, its activation and the sum into the residual stream), forward,
recomputation and backward, per optimizer step of the steady trace. It is
held whole on every chip that shares a layer, so on one chip of 32 it sees
32 times its share of the tokens against the routed experts. None where
the program has no such scope."""

from benchmark import inner_scopes


def read(run):
    return inner_scopes.inner_ms_per_step(run, "tm.moe.shared")
