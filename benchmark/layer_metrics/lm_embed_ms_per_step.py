"""Embedding (models/decoder.py ``MoEDecoder.__call__``,
models/transformer.py ``LongContextTransformer.__call__``): the device time
of the operations under the ``tm.lm.embed`` scope (the token embedding's
gather; GPT-2: and the position embedding's and their sum; in backward the
scatter-add of the rows' gradients), per optimizer step of the steady trace.
Own intervals by the innermost scope of an ``op_name``
(``benchmark/model_scopes.py``); what XLA fuses into a neighbour bears the
neighbour's scope. None where the program has no such scope."""

from benchmark import model_scopes


def read(run):
    return model_scopes.bucket_ms_per_step(run, "tm.lm.embed")
