"""Device step, what recomputation costs: the device time of the operations
of forward and backward whose ``op_name`` passes
``rematted_computation`` (jax's name for what a checkpointed block
computes again in backward), over every inner scope and what none names, per
optimizer step of the steady trace (``benchmark/model_scopes.py``). The name
is jax's, so a program whose model opens no scope reads too. XLA's
``ragged-dot`` kernels bear no ``op_name`` and are not counted: a recomputed
grouped product is missing here. A fusion of recomputed and other
operations goes to its root's side whole. 0 where nothing is recomputed;
None where nothing ran under ``tm.fwd_bwd``."""

from benchmark import model_scopes


def read(run):
    return model_scopes.phase_ms_per_step(run, "recompute")
