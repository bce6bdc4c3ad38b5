"""Attention, the layers that see the whole causal prefix
(models/decoder.py ``MoEDecoderBlock``, parallel/ring_attention.py
``blocked_self_attention`` with ``window=None``): the device time of the
operations under the ``tm.attn.full`` scope (blocked scores and values;
no projection), forward, recomputation and backward, per optimizer step of
the steady trace."""

from benchmark import inner_scopes


def read(run):
    return inner_scopes.inner_ms_per_step(run, "tm.attn.full")
