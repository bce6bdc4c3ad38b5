"""Dense feed-forward of a block (models/transformer.py
``RingAttentionBlock``, GPT-2's): the device time of the operations under
the ``tm.lm.mlp`` scope (the two products with their biases, the GELU and
the sum into the residual stream), forward, recomputation and backward, per
optimizer step of the steady trace. Own intervals by the innermost scope of
an ``op_name`` (``benchmark/model_scopes.py``); what XLA fuses into a
neighbour bears the neighbour's scope. The decoders' dense leading layer is
``mlp_dense_ms_per_step``'s. None where the program has no such scope."""

from benchmark import model_scopes


def read(run):
    return model_scopes.bucket_ms_per_step(run, "tm.lm.mlp")
