"""Ring attention: sequence/context parallelism over a mesh axis.

Long-context capability absent from the 2017 reference (SURVEY.md §5) but
first-class here: the sequence axis is sharded over devices, and attention
is computed by rotating key/value blocks around the ring with ``ppermute``
(one ICI hop per step) while queries stay resident — communication overlaps
the per-block attention compute, and no device ever materialises the full
sequence. Flash-style streaming softmax (running max + normalizer) keeps
the math exact.

Two backends behind one function: the pure-XLA path (``backend='xla'``,
works on the CPU test mesh and lowers ppermute to ICI collective-permute
on TPU) and the Pallas kernel with explicit double-buffered K/V RDMA and
the streaming-softmax merge in-kernel
(``backend='pallas'``/``'pallas_interpret'``, ``ops/ring_attention_kernel
.py``). Oversized working sets auto-chunk over batch/heads (each chunk
rides its own ring); a single (batch, head) cell beyond the kernel's
VMEM envelope raises — the kernel never gives way to the XLA path on
its own.

Derived from the ring-attention pattern in the public pallas guide and the
scaling-book recipe: shift-K/V ring + online softmax.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_attn(q, k, v, bias):
    """Scores and partial numerator/denominator for one (q-block, kv-block)
    pair with streaming-softmax bookkeeping. Score/accumulator math in
    float32 regardless of input dtype (flash-attention numerics)."""
    s = jnp.einsum(
        "...qhd,...khd->...hqk",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
    ) / math.sqrt(q.shape[-1])
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)  # [..., h, q]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)  # [..., h, q]
    o = jnp.einsum("...hqk,...khd->...qhd", p, v.astype(jnp.float32))
    return o, m, l


def ring_self_attention(
    q,
    k,
    v,
    axis: str = "sp",
    causal: bool = False,
    axis_size: Optional[int] = None,
    backend: str = "xla",
):
    """Exact self-attention over a sequence sharded along ``axis``.

    Args: q/k/v of shape ``[batch, seq_local, heads, head_dim]`` — the local
    sequence shard. Returns the attention output for the local queries,
    identical (up to float error) to full attention over the gathered
    sequence.

    ``backend``: ``'xla'`` (ppermute ring) or any
    combination of ``'pallas'`` with the suffix tokens ``_interpret``
    (interpret mode — CPU-mesh validation), ``_bidir`` (bidirectional
    forward: both ICI directions carry K/V chains, ~half the ring
    steps), and ``_full`` (RDMA backward kernel too — dK/dV accumulators
    ride the ring home with their blocks; default backward is the
    analytic XLA ring from the saved residuals). E.g.
    ``'pallas_interpret_bidir_full'``.

    Causal masking accounts for the global positions: the k/v block visiting
    at ring step s originated on rank ``(r - s) mod p``, so its global
    offset is known statically per step.
    """
    if backend != "xla":
        from ..ops.ring_attention_kernel import ring_attention

        tokens = set(backend.split("_"))
        if not backend.startswith("pallas") or not tokens <= {
            "pallas", "interpret", "full", "bidir"
        }:
            raise ValueError(f"unknown ring-attention backend {backend!r}")
        return ring_attention(
            q, k, v, axis, causal, axis_size,
            "interpret" in tokens,
            "full" in tokens,
            None,
            "bidir" in tokens,
        )
    p = axis_size or lax.axis_size(axis)
    b, n_local, h, d = q.shape
    r = lax.axis_index(axis)
    perm = [(i, (i + 1) % p) for i in range(p)]

    q_pos = r * n_local + jnp.arange(n_local)  # global query positions

    def step(s, carry):
        o, m, l, kv = carry
        kb, vb = kv
        src = (r - s) % p  # which rank's shard we hold this step
        k_pos = src * n_local + jnp.arange(n_local)
        bias = None
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]  # [q, k]
            bias = jnp.where(mask, 0.0, NEG_INF)[None, None, :, :]
        ob, mb, lb = _block_attn(q, kb, vb, bias)
        # streaming softmax merge
        m_new = jnp.maximum(m, mb)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(mb - m_new)
        l_new = l * alpha + lb * beta
        o_new = (
            o * alpha.transpose(0, 2, 1)[..., None]
            + ob * beta.transpose(0, 2, 1)[..., None]
        )
        # rotate k/v to the next rank (skip the final, unused rotation is
        # harmless and keeps the loop body uniform)
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        return o_new, m_new, l_new, (kb, vb)

    o0 = jnp.zeros((b, n_local, h, d), jnp.float32)  # f32 accumulator
    m0 = jnp.full((b, h, n_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, n_local), jnp.float32)
    o, m, l, _ = lax.fori_loop(0, p, step, (o0, m0, l0, (k, v)))
    l = jnp.maximum(l, 1e-30)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def full_self_attention(q, k, v, causal: bool = False):
    """Single-device reference attention (for parity tests)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        n = q.shape[1]
        mask = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)
