"""Device step, what the model's scopes leave: of the device time under
``tm.fwd_bwd`` (with XLA's ``ragged-dot`` kernels) the share whose
``op_name`` bears no ``tm.lm.*`` / ``tm.attn.*`` / ``tm.moe.*`` name
(``benchmark/model_scopes.py``), in the steady trace. 100 % for a model that
opens no scope; more than a few percent means a part of the model has no
name. Logs forward and backward by inner scope and phase, and the five
longest operations under no inner scope with their ``op_name``s. None where
nothing ran under ``tm.fwd_bwd``."""

from benchmark import model_scopes


def read(run):
    return model_scopes.unnamed_share(run)
