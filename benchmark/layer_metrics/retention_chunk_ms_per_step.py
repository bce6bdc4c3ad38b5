"""Power retention (models/retentive.py ``RetentionDecoderBlock``,
parallel/retention.py ``power_retention``): the device time of the
operations under the ``tm.lm.ret_chunk`` scope (rotary position, the scale
and the masked ``[chunk, chunk]`` products inside a chunk: the squared
float32 scores under their decays against ``[v | 1]``), forward,
recomputation and backward, per optimizer step of the steady trace. Own
intervals by the innermost scope of an ``op_name``
(``benchmark/model_scopes.py``); what XLA fuses into a neighbour bears the
neighbour's scope. None where the program has no such scope."""

from benchmark import model_scopes


def read(run):
    return model_scopes.bucket_ms_per_step(run, "tm.lm.ret_chunk")
