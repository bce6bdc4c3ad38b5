"""Dense feed-forward, a leading layer's (models/decoder.py
``MoEDecoderBlock`` with ``dense_width``): the device time of the
operations under the ``tm.moe.dense`` scope (the three dense products over
the columns this chip holds, the activation and the sum into the residual
stream; the scope stands in the slot the experts have in the other layers,
hence its namespace), forward, recomputation and backward, per optimizer
step of the steady trace. None where the program has no such scope (a model
without a dense layer, or the parent of the PR that added it)."""

from benchmark import inner_scopes


def read(run):
    return inner_scopes.inner_ms_per_step(run, "tm.moe.dense")
