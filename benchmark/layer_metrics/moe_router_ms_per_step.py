"""Expert layer, the router's product (models/decoder.py
``MoEDecoderBlock``): the device time of the operations under the
``tm.moe.router`` scope (the float32 product with the router's matrix at
precision highest, read before attention or after the second norm; its
weight gradient in backward), forward, recomputation and backward, per
optimizer step of the steady trace. What the router's rule does with the
logits is ``moe_route_ms_per_step``'s. Own intervals by the innermost scope
of an ``op_name`` (``benchmark/model_scopes.py``); what XLA fuses into a
neighbour bears the neighbour's scope. None where the program has no such
scope."""

from benchmark import model_scopes


def read(run):
    return model_scopes.bucket_ms_per_step(run, "tm.moe.router")
