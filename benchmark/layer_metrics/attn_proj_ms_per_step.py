"""Attention, the projections (models/decoder.py ``MoEDecoderBlock``,
models/transformer.py ``RingAttentionBlock``): the device time of the
operations under the ``tm.attn.proj`` scope (the ``q``, ``k``, ``v`` and
``o`` products and the sum into the residual stream; GPT-2: the one ``qkv``
product, its split, and ``o`` with its bias; NOT the indexer's products,
which stay under ``tm.attn.index``, and NOT the reshapes between a product
and the attention, which stand under no scope: XLA merges one with the
attention's own reshape into a copy that bears both ``op_name``s, and it
stays the attention's), forward, recomputation and backward, per optimizer
step of the steady trace. Own intervals by the innermost scope of an
``op_name`` (``benchmark/model_scopes.py``); what XLA fuses into a
neighbour (a norm, the attention's layout change) bears the neighbour's
scope. None where the program has no such scope."""

from benchmark import model_scopes


def read(run):
    return model_scopes.bucket_ms_per_step(run, "tm.attn.proj")
