"""A state-space mixer's sequence operations (Mamba-2, arXiv:2405.21060)
for heads held by share: the causal depthwise convolution over time, the
selective state-space recurrence computed as the state-space dual's chunked
scan, and the gated RMSNorm by groups of channels; and the one sequence operation
of a mixer that is a convolution and nothing else (``gated_short_conv``: two
elementwise gates around three taps). XLA operations, and the
gradients jax's own of these, but for the convolution with its SiLU
(``causal_conv1d_silu``): one operation under a derivative rule of its own,
two Pallas kernels (``ops/conv_kernel.py``) where the program is lowered for
a TPU and the shapes are whole tiles of enough channels.

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

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry as _telemetry
from ..ops import conv_kernel as _conv_kernel
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


def _set_conv_gauges(elements: int, in_kernels: int) -> None:
    _telemetry.metrics.gauge(
        _names.GAUGE_CONV_ELEMENTS,
        "elements (layers x sequences x positions x channels) that go "
        "through a short causal convolution (causal_conv1d_silu, "
        "gated_short_conv) in the step most recently traced").set(elements)
    _telemetry.metrics.gauge(
        _names.GAUGE_CONV_KERNEL_ELEMENTS,
        "those of tm_conv_elements_per_step whose shapes take the fused "
        "kernels (whole tiles of positions, whole lanes), traced where "
        "jax's backend is a TPU").set(in_kernels)


def note_conv_step(layers: int, shape, dtype, taps: int) -> None:
    """Set, from static shapes while a step is traced, the elements that go
    through ``causal_conv1d_silu`` (``layers`` calls over ``shape`` ``[b, t,
    c]`` of ``dtype``) and those of them whose shapes take the kernels,
    traced where jax's backend is a TPU (as ``tm_attn_kernel_calls_per_step``:
    the platform of the lowering is not known yet)."""
    elements = layers * math.prod(shape)
    kernels = (_conv_kernel.takes(shape, dtype, taps)
               and jax.default_backend() == "tpu")
    _set_conv_gauges(elements, elements if kernels else 0)


def note_gated_conv_step(layers: int, shape) -> None:
    """The same two gauges for ``layers`` calls of ``gated_short_conv``
    whose convolution runs over ``shape`` ``[b, t, c]``: none of its
    elements takes a kernel, whatever the shape, since the operation has
    none (``ops/conv_kernel.py`` fuses the taps with a SiLU this mixer has
    not)."""
    _set_conv_gauges(layers * math.prod(shape), 0)


def gated_short_conv(bcx, taps):
    """The middle of a gated short-convolution mixer (the ``lfm2`` family's
    ``conv`` layers): ``C * conv(B * x)`` from ``bcx`` ``[b, t, 3 c]``, the
    three column blocks ``[B | C | x]`` of ONE product, and ``taps`` ``[k,
    c]``: two elementwise gates around a causal depthwise convolution of
    ``k`` taps (``causal_conv1d``: zeros before position 0, ``taps[k - 1]``
    meets the current position), no bias, no activation, no state but ``k -
    1`` positions. The gates' products and the taps' sum in float32, the
    result in ``bcx``'s dtype. XLA's expressions, differentiated by jax: on
    the chip that is the pad and the shifted float32 copies PR 46 measured
    under ``causal_conv1d_silu``; the operation's kernel is not written
    (``note_gated_conv_step`` says so to ``conv_kernel_share``)."""
    gate_in, gate_out, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    mixed = causal_conv1d(
        gate_in * x, taps, jnp.zeros(taps.shape[1:], jnp.float32))
    return (gate_out * mixed).astype(bcx.dtype)


def _conv_silu_plain(x, kernel, bias):
    return jax.nn.silu(causal_conv1d(x, kernel, bias))


@jax.custom_vjp
def _conv_silu(x, kernel, bias):
    return lax.platform_dependent(
        x, kernel, bias, tpu=_conv_kernel.forward, default=_conv_silu_plain)


def _conv_silu_fwd(x, kernel, bias):
    return _conv_silu(x, kernel, bias), (x, kernel, bias)


def _conv_silu_bwd(saved, dy):
    def kernels(x, kernel, bias, dy):
        dx, dtaps, dbias = _conv_kernel.backward(x, kernel, bias, dy)
        return dx, dtaps.astype(kernel.dtype), dbias.astype(bias.dtype)

    def plain(x, kernel, bias, dy):
        return jax.vjp(_conv_silu_plain, x, kernel, bias)[1](dy)

    return lax.platform_dependent(*saved, dy, tpu=kernels, default=plain)


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def causal_conv1d_silu(x, kernel, bias):
    """``jax.nn.silu(causal_conv1d(x, kernel, bias))``, float32, as one
    operation in one of two executions chosen by what the call can observe.

    Where ``x``'s positions are whole tiles and its channels whole lanes
    and 2,048 or more (``ops/conv_kernel.py`` ``takes``; the width is what
    was measured in a step on the chip): a derivative rule of its own that
    keeps ``x`` as it came, the taps and the bias, and nothing of ``[t, c]``
    in float32. Lowered for a TPU it is two kernels, forward and backward,
    each reading its operands from HBM once and writing its results once:
    the shifts are made in VMEM, the pre-activation again in backward, ``dx``
    rounded to ``x``'s dtype, the taps' and the bias's gradients summed in
    float32. Lowered for anything else the rule runs the expressions above
    and jax's derivative of them (``lax.platform_dependent``: the platform
    the program is lowered for, as ``blocked_self_attention``). Other
    shapes (an odd length, channels that fill no lane, ``falcon-h1-34b``'s
    1,024): the expressions, differentiated by jax."""
    if not _conv_kernel.takes(x.shape, x.dtype, kernel.shape[0]):
        return _conv_silu_plain(x, kernel, bias)
    return _conv_silu(x, kernel, bias)


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
