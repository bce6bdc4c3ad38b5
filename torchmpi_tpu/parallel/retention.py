"""Power retention (Buckman, Gelada, Zhang, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239), degree 2, for heads held by share:
a layer's sequence operation where softmax attention stood, linear in the
sequence, with a large state. XLA operations throughout; the gradients are
jax's own of these.

The attention form, a query head ``n`` reading KV head ``h``, ``g <= 0`` the
logarithm of a gate, one number a position and KV head: ``a_ij = exp(sum_{l
= j+1..i} g_l) (q_i . k_j / sqrt(d))^2`` for ``j <= i``, else 0; ``y_i =
sum_j a_ij v_j / (sum_j a_ij + eps)``. Every weight is positive (an even
power): no softmax and no running maximum.

The same as a recurrence. ``phi: R^d -> R^D``, ``D = d (d + 1) / 2``, is the
symmetric square, ``x_a x_b`` once for every pair ``a <= b``, times ``sqrt
2`` off the diagonal, so that ``phi(x) . phi(y) = (x . y)^2``. With ``q`` and
``k`` each times ``d^(-1/4)``: ``S_t = e^{g_t} S_{t-1} + phi(k_t) (x) [v_t |
1]`` from ``S_{-1} = 0``, a state of ``[D, d + 1]`` a KV head (8,256 x 129
at ``d`` 128), the normaliser carried with the values; ``[num | den] =
phi(q_t)^T S_t``; ``y_t = num / (den + eps)``.

The chunked form (``power_retention``), over chunks of ``chunk`` positions,
with ``cum_i`` the float32 cumulative sum of ``g`` from the chunk's first
position to ``i``, the skeleton of ``parallel/ssm.py``'s scan: inside a chunk
the masked ``[chunk, chunk]`` product of the attention form, ``exp(cum_i -
cum_j)`` times the squared float32 scores, against ``[v | 1]``; a chunk's own
state ``sum_j exp(cum_last - cum_j) phi(k_j) (x) [v_j | 1]``; between chunks
the carried state ``S <- exp(cum_last) S + `` the chunk's own, a ``lax.scan``
in float32; what the state carried into a chunk adds to a position,
``exp(cum_i) phi(q_i)^T S``; one division at the end. The decays are
differences of a cumulative sum **within a chunk only**. The products'
operands are cast to ``dtype``; the sums, the decays, the state and the
division are float32.

**No array of all the positions times ``D``.** The whole of it is ONE
``lax.scan`` over the chunks whose carry is the state: ``phi`` is made for
one chunk at a time inside the body, consumed by the body's products, and
the body is recomputed in backward (``jax.checkpoint``), so that no ``phi``
is kept from forward either: at 32,768 positions ``phi`` of the keys would
be 541 MB a head and of five query heads 2.7 GB, a layer. What a call keeps
for backward is its inputs and the state each chunk starts from. The
states stand values-major, ``[d + 1, features]``, so that the long axis is
the minor one. The program's ``phi`` (``symmetric_square``) has a few
features more than ``D`` (9,216 at ``d`` 128, ``features``): pairs inside a
diagonal block stand twice, which is what lets XLA make it from whole
blocks.

**Held by share.** The heads given are those this device holds, a KV head
with its whole group of query heads (``heads`` a multiple of ``kv_heads``,
query head ``n`` reading KV head ``n // (heads // kv_heads)``). Heads do not
interact.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import telemetry as _telemetry
from ..telemetry import names as _names


BLOCK = 16  # coordinates a block of the symmetric square (below): on the
#            chip a layer's retention ran 12 % faster with 16 than with 8,
#            which has 5.6 % fewer features (PERF.md section 6, PR 41)


def features(d: int, block: int = BLOCK) -> int:
    """The features the program's ``phi`` has: ``block^2`` for every pair of
    blocks ``I <= J`` of the ``d / block`` (9,216 at ``d`` 128: the ``D`` =
    8,256 pairs ``a <= b``, of which the 960 inside a diagonal block stand
    twice)."""
    n = d // block
    return n * (n + 1) // 2 * block * block


def note_retention_step(layers: int, sequences: int, kv_heads: int,
                        head_dim: int, chunks: int) -> None:
    """Set, from static shapes while a step is traced: the KV heads this
    rank holds summed over its layers, the chunks its retention runs over
    (layers x sequences x chunks a sequence) and the bytes of the float32
    states the recurrence carries (layers x sequences x KV heads held x
    ``features(head_dim) x (head_dim + 1)`` x 4)."""
    gauge = _telemetry.metrics.gauge
    gauge(_names.GAUGE_RETENTION_KV_HEADS_HELD,
          "KV heads of power retention this rank holds, summed over the "
          "layers of the step most recently traced").set(layers * kv_heads)
    gauge(_names.GAUGE_RETENTION_CHUNKS,
          "chunks the power retention of the step most recently traced "
          "runs over: layers x sequences x chunks a sequence").set(
              layers * sequences * chunks)
    gauge(_names.GAUGE_RETENTION_STATE_BYTES,
          "bytes of the float32 states the recurrences of the step most "
          "recently traced carry: layers x sequences x KV heads held x "
          "features x (head_dim + 1) x 4").set(
              layers * sequences * kv_heads * features(head_dim)
              * (head_dim + 1) * 4)


def symmetric_square(x, block: int = BLOCK):
    """``phi(x)`` over the last axis ``d``: ``[..., features(d, block)]`` with
    ``phi(x) . phi(y) = (x . y)^2``, by blocks of ``block`` coordinates: for
    every pair of blocks ``I <= J`` the whole outer product ``x_I (x) x_J``,
    times ``sqrt 2`` where ``I < J``. Inside a diagonal block a pair ``a !=
    b`` stands twice at weight 1 in place of once at ``sqrt 2``: the same
    inner product with ``(block - 1) / (d + 1)`` more features than ``D``,
    and nothing but broadcasts and products of whole blocks (the ``D``
    features alone, by rotations of the axis or a gather, cost XLA a
    thousand small operations a chunk, or a scatter in backward: measured,
    ``PERF.md`` section 6, PR 41). Float32 in, float32 out: the caller
    rounds."""
    d = x.shape[-1]
    n = d // block
    if d % block:
        raise ValueError(f"the symmetric square is written for a width that "
                         f"blocks of {block} divide (got {d})")
    blocks = x.reshape(x.shape[:-1] + (n, block))
    # block I beside each J >= I, the pairs in the order (0, 0..n-1), (1,
    # 1..n-1), ...: slices and broadcasts, whose transposes are sums
    first = jnp.concatenate(
        [jnp.broadcast_to(blocks[..., i:i + 1, :],
                          blocks.shape[:-2] + (n - i, block))
         for i in range(n)], axis=-2)
    second = jnp.concatenate([blocks[..., i:, :] for i in range(n)], axis=-2)
    weight = np.concatenate(
        [[1.0] + [math.sqrt(2.0)] * (n - i - 1) for i in range(n)]
    ).astype(np.float32)[:, None, None]
    both = weight * first[..., :, None] * second[..., None, :]
    return both.reshape(x.shape[:-1] + (features(d, block),))


def power_retention(q, k, v, log_g, chunk: int = 256, dtype=None,
                    eps: float = 1e-12):
    """``y`` ``[batch, t, heads, d]`` float32 of the recurrence above by its
    chunked form. ``q`` ``[batch, t, heads, d]``; ``k``, ``v`` ``[batch, t,
    kv_heads, d]``; ``log_g`` ``[batch, t, kv_heads]`` (``<= 0``). ``t`` need
    not be a multiple of ``chunk``: it is padded with positions of ``log_g =
    0`` and ``k = 0``, which neither decay nor feed the state. ``dtype``: the
    products' operands (default ``q``'s). ``eps`` stands beside the
    normaliser as a guard against ``0 / 0`` alone (a padded position sees
    nothing) and is far under any weight: position 0's normaliser is ONE
    term, ``a_00``, and an ``eps`` within a thousandth of it makes ``y_0 =
    v_0 a / (a + eps)`` and its gradient turn on the last digits of one
    score, where the mathematics (no ``eps``) has ``y_0 = v_0`` whatever
    the score (``PERF.md`` section 6, PR 41). Its square's inverse stays
    inside float32."""
    batch, t, heads, d = q.shape
    kv_heads = k.shape[2]
    if (heads % kv_heads or k.shape != v.shape
            or k.shape != (batch, t, kv_heads, d)
            or log_g.shape != k.shape[:3]):
        raise ValueError(
            f"{heads} heads must be a multiple of the {kv_heads} KV heads, k "
            f"and v alike and log_g a number a KV head (got q {q.shape}, k "
            f"{k.shape}, v {v.shape}, log_g {log_g.shape})")
    dtype = jnp.dtype(dtype or q.dtype)
    f32 = jnp.float32
    r = heads // kv_heads
    pad = -t % chunk
    if pad:
        q, k, v, log_g = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, log_g))
    chunks = (t + pad) // chunk

    def by_chunk(a, head_axes=1):
        """``[batch, t, <head axes>, ...]`` as ``[chunks, batch, <head
        axes>, chunk, ...]``: the loop runs over the leading axis, and
        positions and channels are the minor axes."""
        a = a.reshape((batch, chunks, chunk) + a.shape[2:])
        return jnp.moveaxis(a, (1, 2), (0, 2 + head_axes))

    with jax.named_scope(_names.SCOPE_RET_GATE):
        # [chunks, batch, kv, chunk]
        cum = jnp.cumsum(by_chunk(log_g.astype(f32)), axis=-1)
    scale = d ** -0.25
    # [chunks, batch, kv, (r,) chunk, d]
    qs = by_chunk(q.reshape(batch, t + pad, kv_heads, r, d), head_axes=2)
    ks, vs = by_chunk(k), by_chunk(v)
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))

    @jax.checkpoint
    def one_chunk(state, now):
        """A chunk's positions from the state it starts from, ``[batch, kv,
        d + 1, features]``, and the state it leaves. Under the caller's
        ``tm.lm.ret_state``; the gate's and the chunk's own parts open
        their scopes inside it (the readers go by the innermost name)."""
        q_, k_, v_, cum_ = now
        with jax.named_scope(_names.SCOPE_RET_GATE):
            decay = jnp.exp(jnp.where(
                seen, cum_[..., :, None] - cum_[..., None, :], -jnp.inf))
            to_end = jnp.exp(cum_[..., -1:] - cum_)  # a position's to the end
            whole = jnp.exp(cum_[..., -1])           # the chunk's, end to end
            since = jnp.exp(cum_)                    # since the chunk began
        q_, k_ = scale * q_.astype(f32), scale * k_.astype(f32)
        v1 = jnp.concatenate(                        # [v | 1]
            [v_.astype(f32), jnp.ones(v_.shape[:-1] + (1,), f32)], axis=-1)
        with jax.named_scope(_names.SCOPE_RET_CHUNK):
            # the chunk's own positions: the masked product
            scores = jnp.einsum(
                "zhrid,zhjd->zhrij", q_.astype(dtype), k_.astype(dtype),
                preferred_element_type=f32)
            weights = jnp.square(scores) * decay[:, :, None]
            total = jnp.einsum(
                "zhrij,zhjv->zhriv", weights.astype(dtype), v1.astype(dtype),
                preferred_element_type=f32)
        # what the state the chunk starts from adds: exp(cum_i) phi(q_i)^T S
        total = total + since[:, :, None, :, None] * jnp.einsum(
            "zhrif,zhvf->zhriv", symmetric_square(q_).astype(dtype),
            state.astype(dtype), preferred_element_type=f32)
        y = total[..., :d] / (total[..., d:] + eps)
        # what the chunk leaves: sum_j exp(cum_last - cum_j) [v_j | 1] (x)
        # phi(k_j) on the decayed state
        own = jnp.einsum(
            "zhjv,zhjf->zhvf", (v1 * to_end[..., None]).astype(dtype),
            symmetric_square(k_).astype(dtype), preferred_element_type=f32)
        return own + whole[..., None, None] * state, y

    # the scope holds the loop itself too: the states it keeps a chunk for
    # backward and reads back are the state's carriage
    with jax.named_scope(_names.SCOPE_RET_STATE):
        _, y = lax.scan(
            one_chunk,
            jnp.zeros((batch, kv_heads, d + 1, features(d)), f32),
            (qs, ks, vs, cum))
    # [chunks, batch, kv, r, chunk, d] -> [batch, t, heads, d]
    y = jnp.moveaxis(y, (0, 4), (1, 2)).reshape(batch, t + pad, heads, d)
    return y[:, :t]
