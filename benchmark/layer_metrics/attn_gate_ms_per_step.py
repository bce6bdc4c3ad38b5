"""Attention, the gate on each head (models/decoder.py ``MoEDecoderBlock``
with ``head_gate``): the device time of the operations under the
``tm.attn.gate`` scope (the gate's product with the normed input, one
column a held query head, its sigmoid, and the multiplication of each
head's attention output by its gate), forward, recomputation and backward,
per optimizer step of the steady trace. What XLA fuses into a neighbour
(the multiply into the output projection's operand, say) bears that
fusion's scope and is not counted here. None where the program has no such
scope (a model without the gate, or the parent of the PR that added it)."""

from benchmark import inner_scopes


def read(run):
    return inner_scopes.inner_ms_per_step(run, "tm.attn.gate")
