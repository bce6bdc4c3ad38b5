"""The gated delta rule's mixer (models/deltanet.py ``GatedDeltaDecoderBlock``,
parallel/deltanet.py ``gated_delta_rule``): the device time of the
operations under the ``tm.lm.gdn_proj`` scope (the mixer's three dense
products: into [q | k | v | z], into [b | a] and out), forward,
recomputation and backward, per optimizer step of the steady trace. Own
intervals by the innermost scope of an ``op_name``
(``benchmark/model_scopes.py``); what XLA fuses into a neighbour bears the
neighbour's scope. None where the program has no such scope."""

from benchmark import model_scopes


def read(run):
    return model_scopes.bucket_ms_per_step(run, "tm.lm.gdn_proj")
