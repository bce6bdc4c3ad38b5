"""The gated delta rule (Yang, Kautz and Hatamizadeh, "Gated Delta Networks:
Improving Mamba2 with Delta Rule", arXiv:2412.06464) for heads held by
share: a layer's sequence operation where softmax attention stood, linear in
the sequence, whose state is **corrected** by each token and not only added
to. XLA operations throughout; the gradients are jax's own of these.

The recurrence, a value head ``n`` of ``dv`` reading key head ``n // (value
heads // key heads)`` of ``dk``, ``g_t <= 0`` the logarithm of a decay and
``beta_t`` in (0, 1) a writing strength, one number each a position and
value head, the state ``S`` ``[dk, dv]`` float32 from ``S_{-1} = 0``: ``S_t
= e^{g_t} S_{t-1}``; ``r_t = S_t^T k_t`` (what the state holds under this
key); ``u_t = beta_t (v_t - r_t)``; ``S_t <- S_t + k_t u_t^T``; ``o_t =
S_t^T q_t``. That is ``S <- a S (I - b k k^T) + b k v^T``.

The chunked form (``gated_delta_rule``), over chunks of ``chunk``
positions, with ``c_i`` the float32 cumulative sum of ``g`` from the
chunk's first position to ``i`` and ``G_ij = exp(c_i - c_j)`` for ``i >=
j``, the skeleton of ``parallel/ssm.py``'s and ``parallel/retention.py``'s
scans. A chunk's ``u`` solve a strictly lower-triangular system: ``A =
tril(diag(beta) (K K^T * G), -1)``, ``T = (I + A)^{-1}``, ``U = T
diag(beta) V``, ``W = T diag(beta) (K * exp(c))``, all made for every chunk
at once before the loop; then over the chunks in order, the carry ``S``
float32: ``V' = U - W S`` (the values the chunk really writes); ``O = (Q *
exp(c)) S + tril(Q K^T * G) V'``; ``S <- exp(c_last) S + (K * exp(c_last -
c))^T V'``. The decays are differences of a cumulative sum **within a chunk
only**. ``T``, the decays, the sums and ``S`` are float32; the operands of
the other products are cast to ``dtype``.

``T`` is made by XLA's triangular solve of ``I + A`` against the identity
(``_unit_lower_inverse``: on the chip it ran faster than the product ``(I -
A)(I + A^2)(I + A^4) ...`` that ``A^chunk = 0`` allows; ``PERF.md`` section
6, PR 45).

**No array of ``t x t`` and no state a position.** What a call keeps for
backward is its inputs, what is made a chunk at a time before the loop and
the state each chunk starts from (``[chunks, value heads, dk, dv]`` float32:
the loop's body is recomputed in backward, ``jax.checkpoint``).

**Held by share.** The heads given are those this device holds, a key head
with the value heads that read it (``value heads`` a multiple of ``key
heads``). Heads do not interact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular

from .. import telemetry as _telemetry
from ..telemetry import names as _names


def note_gdn_step(layers: int, sequences: int, chunks: int) -> None:
    """Set, from static shapes while a step is traced, the chunks the gated
    delta rule runs over: the ``layers`` that have it x sequences x chunks a
    sequence."""
    _telemetry.metrics.gauge(
        _names.GAUGE_GDN_CHUNKS,
        "chunks the gated delta rule of the step most recently traced "
        "runs over: its layers x sequences x chunks a sequence").set(
            layers * sequences * chunks)


def _unit_lower_inverse(a):
    """``(I + a)^{-1}`` for ``a`` ``[..., n, n]`` float32 strictly lower
    triangular, by XLA's triangular solve against the identity (its
    derivative is jax's own: ``dT = -T da T`` from ``T`` alone). On the chip
    at the cell's size a layer's rule ran forward in 14.9 ms and forward and
    backward in 40.1 so, against 21.3 and 49.5 with the inverse as the
    product ``(I - a)(I + a^2)(I + a^4) ...`` (``a^n = 0``: five squarings
    and five products of ``[64, 64]`` at precision highest) and 16.9 and
    45.5 with that product at the default precision; the results differ by
    the rounding of ``T`` to ``dtype`` alone (``PERF.md`` section 6, PR
    45)."""
    eye = jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=a.dtype), a.shape)
    return solve_triangular(eye + a, eye, lower=True, unit_diagonal=True)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64, dtype=None):
    """``o`` ``[batch, t, value_heads, dv]`` float32 of the recurrence above
    by its chunked form. ``q``, ``k`` ``[batch, t, key_heads, dk]`` (the
    caller's norms and the query's scale applied); ``v`` ``[batch, t,
    value_heads, dv]``; ``g`` (``<= 0``), ``beta`` ``[batch, t,
    value_heads]``. ``t`` need not be a multiple of ``chunk``: it is padded
    with positions of ``g = 0``, ``beta = 0`` and ``k = 0``, which neither
    decay the state nor write to it. ``dtype``: the products' operands
    (default ``q``'s)."""
    batch, t, key_heads, dk = q.shape
    value_heads, dv = v.shape[2:]
    if (value_heads % key_heads or k.shape != q.shape
            or v.shape[:2] != (batch, t)
            or g.shape != (batch, t, value_heads) or beta.shape != g.shape):
        raise ValueError(
            f"{value_heads} value heads must be a multiple of the "
            f"{key_heads} key heads, q and k alike and g and beta a number "
            f"a value head (got q {q.shape}, k {k.shape}, v {v.shape}, g "
            f"{g.shape}, beta {beta.shape})")
    dtype = jnp.dtype(dtype or q.dtype)
    f32 = jnp.float32
    r = value_heads // key_heads
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    chunks = (t + pad) // chunk

    def by_chunk(a, head_axes):
        """``[batch, t, <head axes>, ...]`` as ``[chunks, batch, <head
        axes>, chunk, ...]``: the loop runs over the leading axis, and
        positions and channels are the minor axes."""
        a = a.reshape((batch, chunks, chunk) + a.shape[2:])
        return jnp.moveaxis(a, (1, 2), (0, 2 + head_axes))

    def of_key_head(a):  # [batch, t, value heads, ...] by its key head
        return a.reshape((batch, t + pad, key_heads, r) + a.shape[3:])

    with jax.named_scope(_names.SCOPE_GDN_GATE):
        # [chunks, batch, key heads, r, chunk]
        cum = jnp.cumsum(by_chunk(of_key_head(g.astype(f32)), 2), axis=-1)
        gap = cum[..., :, None] - cum[..., None, :]
        seen = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(seen, gap, -jnp.inf))  # G, 1 on the diagonal
        since = jnp.exp(cum)[..., None]        # since the chunk began
        to_end = jnp.exp(cum[..., -1:] - cum)[..., None]  # a position's, on
        whole = jnp.exp(cum[..., -1])          # the chunk's, end to end
    with jax.named_scope(_names.SCOPE_GDN_CHUNK):
        qs, ks = by_chunk(q.astype(dtype), 1), by_chunk(k.astype(dtype), 1)
        vs = by_chunk(of_key_head(v.astype(f32)), 2)
        betas = by_chunk(of_key_head(beta.astype(f32)), 2)[..., None]
        kk = jnp.einsum("nbhid,nbhjd->nbhij", ks, ks,
                        preferred_element_type=f32)[:, :, :, None]
        qk = jnp.einsum("nbhid,nbhjd->nbhij", qs, ks,
                        preferred_element_type=f32)[:, :, :, None]
        inverse = _unit_lower_inverse(jnp.where(
            jnp.tril(seen, -1), betas * kk * decay, 0.0)).astype(dtype)
        # a key head's keys beside each value head that reads it
        k_r = ks.astype(f32)[:, :, :, None]
        u = jnp.einsum("nbhrij,nbhrjv->nbhriv", inverse,
                       (betas * vs).astype(dtype),
                       preferred_element_type=f32)
        w = jnp.einsum("nbhrij,nbhrjd->nbhrid", inverse,
                       (betas * since * k_r).astype(dtype),
                       preferred_element_type=f32).astype(dtype)
        q_since = (since * qs.astype(f32)[:, :, :, None]).astype(dtype)
        k_to_end = (to_end * k_r).astype(dtype)
        within = (qk * decay).astype(dtype)  # tril(Q K^T * G)

    @jax.checkpoint
    def one_chunk(state, now):
        """A chunk's outputs from the state it starts from, ``[batch, key
        heads, r, dk, dv]`` float32, and the state it leaves."""
        u_, w_, q_, k_, within_, whole_ = now
        held = state.astype(dtype)
        wrote = (u_ - jnp.einsum(
            "bhrid,bhrdv->bhriv", w_, held, preferred_element_type=f32)
        ).astype(dtype)
        out = jnp.einsum(
            "bhrid,bhrdv->bhriv", q_, held, preferred_element_type=f32
        ) + jnp.einsum(
            "bhrij,bhrjv->bhriv", within_, wrote, preferred_element_type=f32)
        state = whole_[..., None, None] * state + jnp.einsum(
            "bhrjd,bhrjv->bhrdv", k_, wrote, preferred_element_type=f32)
        return state, out

    # the scope holds the loop itself too: the states it keeps a chunk for
    # backward and reads back are the state's carriage
    with jax.named_scope(_names.SCOPE_GDN_STATE):
        _, out = lax.scan(
            one_chunk, jnp.zeros((batch, key_heads, r, dk, dv), f32),
            (u, w, q_since, k_to_end, within, whole))
    # [chunks, batch, key heads, r, chunk, dv] -> [batch, t, value heads, dv]
    out = jnp.moveaxis(out, (0, 4), (1, 2)).reshape(
        batch, t + pad, value_heads, dv)
    return out[:, :t]
