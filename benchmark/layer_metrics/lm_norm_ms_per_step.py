"""Norms (models/decoder.py ``MoEDecoderBlock``, models/transformer.py
``RingAttentionBlock``, and both models' last one): the device time of the
operations under the ``tm.lm.norm`` scope (a block's two RMSNorms or
LayerNorms, the model's last, the norm of each query and key head where a
model has them), forward, recomputation and backward, per optimizer step of
the steady trace. Own intervals by the innermost scope of an ``op_name``
(``benchmark/model_scopes.py``). A fusion bears its root's scope: a norm
that XLA fuses into the product that reads it counts there, and a product's
epilogue fused into the norm's reduction counts here. None where the program
has no such scope."""

from benchmark import model_scopes


def read(run):
    return model_scopes.bucket_ms_per_step(run, "tm.lm.norm")
