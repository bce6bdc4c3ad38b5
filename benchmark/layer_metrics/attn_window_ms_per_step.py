"""Attention, the sliding-window layers (models/decoder.py
``MoEDecoderBlock``, parallel/ring_attention.py ``blocked_self_attention``
with a window): the device time of the operations under the
``tm.attn.window`` scope (rotary position, blocked scores and values
within the band; no projection), forward, recomputation and backward, per
optimizer step of the steady trace."""

from benchmark import inner_scopes


def read(run):
    return inner_scopes.inner_ms_per_step(run, "tm.attn.window")
