"""State-space mixer, the scan (models/hybrid.py ``HybridDecoderBlock``,
parallel/ssm.py ``ssd_chunked_scan``): the device time of the operations
under the ``tm.lm.ssm_scan`` scope (``delta``'s softplus, the decays, the
chunked dual's four products, the state carried between chunks, ``D x``),
forward, recomputation and backward, per optimizer step of the steady
trace. Own intervals by the innermost scope of an ``op_name``
(``benchmark/model_scopes.py``); what XLA fuses into a neighbour bears the
neighbour's scope. None where the program has no such scope."""

from benchmark import model_scopes


def read(run):
    return model_scopes.bucket_ms_per_step(run, "tm.lm.ssm_scan")
