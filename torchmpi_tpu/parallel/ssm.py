"""A state-space mixer's sequence operations (Mamba-2, arXiv:2405.21060)
for heads held by share: the causal depthwise convolution over time, the
selective state-space recurrence computed as the state-space dual's chunked
scan, and the gated RMSNorm by groups of channels. XLA operations
throughout; the gradients are jax's own of these.

The recurrence, a head of ``P`` channels with a state ``[P, N]``, its
group's ``B_t`` and ``C_t`` ``[N]``, ``delta_t > 0`` and ``A < 0`` one
number a head: ``S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t`` from
``S_{-1} = 0``; ``y_t = S_t C_t + D x_t``.

The chunked dual (``ssd_chunked_scan``), over chunks of ``chunk`` positions,
with ``cum_i`` the float32 cumulative sum of ``delta A`` from the chunk's
first position to ``i``: inside a chunk ``y_i = sum_{j <= i} (C_i . B_j)
exp(cum_i - cum_j) delta_j x_j``, a masked ``[chunk, chunk]`` product a
group; the chunk's own state ``sum_j exp(cum_last - cum_j) delta_j x_j (x)
B_j``; between chunks the carried state ``S <- exp(cum_last) S + `` the
chunk's own, a loop over the chunks in float32; and what the state carried
into a chunk adds, ``exp(cum_i) (S C_i)``. The decays are differences of a
cumulative sum **within a chunk only**, so no difference of large numbers
is taken however long the sequence. The operands of the four products are
cast to ``dtype``; the sums, the decays and the carried state are float32.

**Held by share.** The heads given are the heads this device holds, with
the groups they belong to whole (``heads`` a multiple of ``groups``, head
``n`` reading group ``n // (heads // groups)``). Heads do not interact in
the convolution or the scan. The norm does mix a group's channels: where a
group's heads lie on several devices, ``gated_group_norm`` takes the
``axis_name`` over which the group's mean square is a ``psum``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry as _telemetry
from ..telemetry import names as _names


def note_ssm_step(heads: int, chunks: int) -> None:
    """Set, from static shapes while a step is traced, the mixer heads this
    rank holds summed over its layers and the chunks its scans run over
    (layers x sequences x chunks a sequence)."""
    _telemetry.metrics.gauge(
        _names.GAUGE_SSM_HEADS_HELD,
        "state-space mixer heads this rank holds, summed over the layers of "
        "the step most recently traced").set(heads)
    _telemetry.metrics.gauge(
        _names.GAUGE_SSM_CHUNKS,
        "chunks the chunked scans of the step most recently traced run "
        "over: layers x sequences x chunks a sequence").set(chunks)


def causal_conv1d(x, kernel, bias):
    """Causal depthwise convolution over time: ``x`` ``[b, t, c]``,
    ``kernel`` ``[k, c]``, ``bias`` ``[c]``; ``y_t = bias + sum_j kernel[j]
    x_{t - (k - 1) + j}`` with zeros before position 0, so ``kernel[k - 1]``
    meets the current position. Float32."""
    k, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    y = bias.astype(jnp.float32)
    for j in range(k):
        y = y + kernel[j].astype(jnp.float32) * padded[:, j:j + t]
    return y


def ssd_chunked_scan(x, dt, a, b, c, d, chunk: int = 128, dtype=None):
    """``y`` ``[batch, t, heads, P]`` float32 of the recurrence above by the
    chunked dual. ``x`` ``[batch, t, heads, P]``; ``dt`` ``[batch, t,
    heads]`` (``delta``, positive); ``a`` ``[heads]`` (negative); ``b``,
    ``c`` ``[batch, t, groups, N]``; ``d`` ``[heads]``. ``t`` need not be a
    multiple of ``chunk``: it is padded with positions of ``delta = 0``,
    which neither decay nor feed the state. ``dtype``: the products'
    operands (default ``x``'s)."""
    batch, t, heads, p = x.shape
    groups, n = b.shape[2:]
    if heads % groups or b.shape != c.shape or dt.shape != x.shape[:3]:
        raise ValueError(
            f"{heads} heads must be a multiple of the {groups} groups, b "
            f"and c alike and dt a number a head (got x {x.shape}, dt "
            f"{dt.shape}, b {b.shape}, c {c.shape})")
    dtype = jnp.dtype(dtype or x.dtype)
    f32 = jnp.float32
    r = heads // groups
    pad = -t % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c))
    chunks = (t + pad) // chunk
    # [batch, chunk, group, head of the group, position, ...]: positions and
    # channels are the minor axes of every array below
    xs = x.reshape(batch, chunks, chunk, groups, r, p).transpose(
        0, 1, 3, 4, 2, 5)
    dts = dt.astype(f32).reshape(batch, chunks, chunk, groups, r).transpose(
        0, 1, 3, 4, 2)
    bs, cs = (
        v.astype(dtype).reshape(batch, chunks, chunk, groups, n).transpose(
            0, 1, 3, 2, 4) for v in (b, c))
    cum = jnp.cumsum(
        dts * a.astype(f32).reshape(groups, r, 1), axis=-1)

    # inside a chunk: the masked product, i the query's position, j the key's
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        seen, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    scores = jnp.einsum(
        "zcgin,zcgjn->zcgij", cs, bs, preferred_element_type=f32)
    weights = scores[:, :, :, None] * decay * dts[..., None, :]
    y = jnp.einsum("zcgrij,zcgrjp->zcgrip", weights.astype(dtype),
                   xs.astype(dtype), preferred_element_type=f32)

    # a chunk's own state, as its last position holds it
    fed = xs.astype(f32) * (jnp.exp(cum[..., -1:] - cum) * dts)[..., None]
    own = jnp.einsum("zcgrjp,zcgjn->zcgrpn", fed.astype(dtype), bs,
                     preferred_element_type=f32)
    whole = jnp.exp(cum[..., -1])  # a chunk's decay from end to end

    def carry_on(state, chunk_):
        decay_, own_ = chunk_
        return decay_[..., None, None] * state + own_, state

    _, carried = lax.scan(
        carry_on, jnp.zeros_like(own[:, 0]),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(own, 1, 0)))
    carried = jnp.moveaxis(carried, 0, 1)  # the state a chunk starts from
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "zcgin,zcgrpn->zcgrip", cs, carried.astype(dtype),
        preferred_element_type=f32)
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(batch, t + pad, heads, p)[:, :t]
    return y + d.astype(f32)[:, None] * x[:, :t].astype(f32)


def gated_group_norm(y, z, scale, groups: int, eps: float,
                     axis_name: Optional[str] = None):
    """``y * silu(z)``, then RMSNorm over each of the ``groups`` equal runs
    of the channels (the last axis), times ``scale``: the gate before the
    norm. Float32. With ``axis_name``, each device of that axis holds a
    part of every group given, and the mean square is over the devices'
    channels together (a ``psum`` of the partial sums)."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = gated.reshape(gated.shape[:-1] + (groups, -1))
    total = jnp.sum(jnp.square(parts), axis=-1, keepdims=True)
    count = parts.shape[-1]
    if axis_name is not None:
        total = lax.psum(total, axis_name)
        count = lax.psum(count, axis_name)
    normed = parts * lax.rsqrt(total / count + eps)
    return normed.reshape(gated.shape) * scale.astype(jnp.float32)
