"""Vocabulary head (models/decoder.py ``MoEDecoder.__call__``,
models/transformer.py ``LongContextTransformer.__call__`` and
``lm_cross_entropy``): the device time of the operations under the
``tm.lm.head`` scope (the product with the vocabulary matrix, GPT-2's bias;
in backward its two products) and the ``tm.lm.loss`` scope (the float32
log-softmax over the logits, the pick of the targets, the mean), forward and
backward, per optimizer step of the steady trace. Own intervals by the
innermost scope of an ``op_name`` (``benchmark/model_scopes.py``): a fusion
bears its root's scope, so what XLA fuses across the boundary (the last
norm into the product, the head's weight gradient into its AdamW update
under ``tm.optimizer``) goes to one side whole. None where the program has
neither scope (the parent of the PR that added them)."""

from benchmark import model_scopes


def read(run):
    return model_scopes.bucket_ms_per_step(run, "tm.lm.head", "tm.lm.loss")
