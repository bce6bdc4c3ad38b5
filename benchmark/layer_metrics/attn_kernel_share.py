"""Attention, whether the fused kernels engaged (parallel/
ring_attention.py ``blocked_self_attention``): of the attention calls of
the step most recently traced (gauge ``tm_attn_calls_per_step``, one a
layer), the share that took the fused kernels and not the loops of XLA
operations (gauge ``tm_attn_kernel_calls_per_step``). 100 % where every
layer's heads are a multiple of 128 wide and the step runs on a TPU; a
model with narrower heads reads 0 % and is thereby known to bypass the
kernels. None where the program has no such gauge or the step has no such
call."""

from benchmark import scopes


def read(run):
    calls = scopes.counter("tm_attn_calls_per_step")
    taken = scopes.counter("tm_attn_kernel_calls_per_step")
    if not calls or taken is None:
        return None
    return 100.0 * taken / calls
