"""The gated short convolution's mixer (models/decoder.py ``MoEDecoderBlock``
with ``conv_taps``): the device time of the operations under the
``tm.lm.sconv_proj`` scope (the product into ``[B | C | x]``, the product
out and the sum into the residual stream), forward, recomputation and
backward, per optimizer step of the steady trace. Own intervals by the
innermost scope of an ``op_name`` (``benchmark/model_scopes.py``); what XLA
fuses into a neighbour bears the neighbour's scope. None where the program
has no such scope."""

from benchmark import model_scopes


def read(run):
    return model_scopes.bucket_ms_per_step(run, "tm.lm.sconv_proj")
