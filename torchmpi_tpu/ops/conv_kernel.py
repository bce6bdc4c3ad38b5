"""The short causal depthwise convolution over time fused with its SiLU
(Pallas TPU): one kernel forward, one backward, each reading its operands
from HBM once and writing its results once.

``y[t, c] = silu(bias[c] + sum_j taps[j, c] * x[t - (k - 1) + j, c])`` with
zeros before position 0, ``x`` ``[b, t, c]`` bfloat16 or float32, ``taps``
``[k, c]`` and ``bias`` ``[c]`` float32; the sum (in the order of ``j``) and
the SiLU in float32, ``y`` float32. XLA's lowering of the same expressions
pads a float32 copy of ``x`` and reads it at ``k`` row offsets of an
``(8, 128)``-tiled array: four shifted copies through HBM and, differentiated
by jax, as many again backward. Here a tile of positions and channels is
brought to VMEM once, the shift by 1 to ``k - 1`` rows is made in registers
(static slices of a few rows with the 8 rows before them), and the float32
copy, the padded array and the pre-activation never exist in HBM.

Forward, grid (batch, tiles of channels, tiles of positions), every step its
own: the ``k - 1`` rows before a tile come as a small block of their own of
the same array (the 8 or 16 rows that end where the tile starts).

Backward, from ``x``, ``taps``, ``bias`` and ``dy``: the pre-activation ``p``
again in registers, ``dp = dy * sigmoid(p) * (1 + p * (1 - sigmoid(p)))``,
``dx[t] = sum_j taps[j] * dp[t + (k - 1) - j]`` rounded to ``x``'s dtype,
``dtaps[j] = sum_t dp[t] * x[t - (k - 1) + j]`` and ``dbias = sum_t dp[t]``
in float32. ``dx`` reaches ``k - 1`` rows AHEAD, so the positions run from
the sequence's end to its start, inside a tile too, and the first rows of
``dp`` of what came after ride along (a VMEM scratch between tiles); the two
sums stand in output blocks that stay resident while batch and positions
run. Grid (tiles of channels, batch, tiles of positions, reversed).

No model knowledge: what calls this, and where the XLA expressions stand
instead, is ``parallel/ssm.py``'s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

POSITIONS = 1024  # a tile's rows ...
CHANNELS = 512    # ... and lanes, chosen on the chip (PERF.md, PR 46)
ROWS = 16         # rows the inner loop holds in registers at a time
HALO = 8          # float32 rows beside them that a shift may reach into
# the fewest channels the kernels are taken at. Measured on the chip in the
# STEP, not alone (PERF.md, PR 46): alone the kernels halve XLA's time at
# [16384, 1024] float32 too, and in ``falcon-h1-34b``'s step the operation
# fell from 4.2 to 2.5 ms, but the step rose by 9.6 ms: with the kernels in
# it XLA no longer brought the feed-forward's weights to VMEM ahead of their
# products. At 8,192 channels the step fell by 58 ms. Between the two nothing
# was measured
WIDE = 2048


def takes(shape, dtype, taps: int) -> bool:
    """Whether the kernels run ``x`` of ``shape`` ``[b, t, c]`` and
    ``dtype`` under ``taps`` taps: whole tiles of positions, whole lanes,
    ``WIDE`` channels or more."""
    return (shape[1] % POSITIONS == 0 and shape[2] % 128 == 0
            and shape[2] >= WIDE and 1 <= taps <= HALO + 1
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32))


def _lanes(channels: int) -> int:
    return next(n for n in range(CHANNELS, 0, -128) if channels % n == 0)


def _sublanes(dtype) -> int:
    """Rows of ``dtype``'s native tile: the least block of rows."""
    return HALO * 4 // jnp.dtype(dtype).itemsize


def _params(interpret, *semantics):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=48 * 2**20)}


def _shifted(before, x, k):
    """``[x[t - (k - 1) + j] for j in range(k)]`` for the positions ``t`` of
    ``x`` ``[n, c]`` float32, ``before`` the ``HALO`` rows that precede
    them."""
    held = jnp.concatenate([before, x], axis=0)
    n = x.shape[0]
    return [held[HALO - (k - 1 - j):HALO - (k - 1 - j) + n] for j in range(k)]


def _pre_activation(shifted, taps_ref, bias_ref):
    p = bias_ref[...]
    for j, x in enumerate(shifted):
        p = p + taps_ref[j:j + 1, :] * x
    return p


def _fwd_kernel(halo_ref, x_ref, taps_ref, bias_ref, y_ref, *, rows):
    k, f32 = taps_ref.shape[0], jnp.float32
    before = jnp.where(
        pl.program_id(2) > 0, halo_ref[...].astype(f32)[-HALO:], 0.0)

    def some_rows(r, before):
        at = pl.multiple_of(r * rows, rows)
        x = x_ref[pl.ds(at, rows), :].astype(f32)
        p = _pre_activation(_shifted(before, x, k), taps_ref, bias_ref)
        y_ref[pl.ds(at, rows), :] = p * jax.nn.sigmoid(p)
        return x[-HALO:]

    lax.fori_loop(0, x_ref.shape[0] // rows, some_rows, before)


def _blocks(x, taps, positions, lanes, index):
    """The grid's block specifications of the rows before a tile, the tile,
    the taps and the bias; ``index(*grid ids) -> (sequence, tile of
    positions, tile of channels)``."""
    least = _sublanes(x.dtype)

    def halo(*ids):
        b, i, c = index(*ids)
        return b, jnp.maximum(i * (positions // least) - 1, 0), c

    def channel(*ids):
        return 0, index(*ids)[2]

    tile = pl.BlockSpec((None, positions, lanes), index)
    return tile, [
        pl.BlockSpec((None, least, lanes), halo), tile,
        pl.BlockSpec((taps.shape[0], lanes), channel),
        pl.BlockSpec((1, lanes), channel)]


@functools.partial(
    jax.jit, static_argnames=("positions", "lanes", "rows", "interpret"))
def forward(x, taps, bias, positions: int = POSITIONS, lanes=None,
            rows: int = ROWS, interpret: bool = False):
    """``y`` ``[b, t, c]`` float32 of the module's docstring."""
    b, t, c = x.shape
    lanes = lanes or _lanes(c)
    tile, operands = _blocks(
        x, taps, positions, lanes, lambda b, c, i: (b, i, c))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, rows=rows),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        grid=(b, c // lanes, t // positions),
        in_specs=operands, out_specs=tile,
        name="tm_conv_silu_fwd",
        **_params(interpret, "parallel", "parallel", "parallel"),
    )(x, x, taps.astype(jnp.float32),
      bias.astype(jnp.float32).reshape(1, c))


def _by_eights(x):
    """``x`` ``[n, c]`` summed to ``[HALO, c]``: whole registers added, the
    8 sublanes left for the tile's end."""
    return sum(x[at:at + HALO] for at in range(0, x.shape[0], HALO))


def _bwd_kernel(halo_ref, x_ref, dy_ref, taps_ref, bias_ref,
                dx_ref, dtaps_ref, dbias_ref, after_ref, *, rows):
    k, f32 = taps_ref.shape[0], jnp.float32
    least = halo_ref.shape[0]
    i, last = pl.program_id(2), pl.num_programs(2) - 1  # i: from the END

    @pl.when((pl.program_id(1) == 0) & (i == 0))
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    @pl.when(i == 0)
    def _():  # nothing follows a sequence's last position
        after_ref[...] = jnp.zeros_like(after_ref)

    first = jnp.where(i < last, halo_ref[...].astype(f32)[-HALO:], 0.0)
    passes = x_ref.shape[0] // rows

    def some_rows(n, carried):
        after, sums = carried  # dp of the HALO rows after these; the sums
        r = passes - 1 - n
        at = pl.multiple_of(r * rows, rows)
        ahead = pl.multiple_of(jnp.maximum(at - least, 0), least)
        before = jnp.where(
            r > 0, x_ref[pl.ds(ahead, least), :].astype(f32)[-HALO:], first)
        shifted = _shifted(before, x_ref[pl.ds(at, rows), :].astype(f32), k)
        p = _pre_activation(shifted, taps_ref, bias_ref)
        s = jax.nn.sigmoid(p)
        dp = dy_ref[pl.ds(at, rows), :] * (s * (1.0 + p * (1.0 - s)))
        held = jnp.concatenate([dp, after], axis=0)
        dx = taps_ref[0:1, :] * held[k - 1:k - 1 + rows]
        for j in range(1, k):
            dx = dx + taps_ref[j:j + 1, :] * held[k - 1 - j:k - 1 - j + rows]
        dx_ref[pl.ds(at, rows), :] = dx.astype(dx_ref.dtype)
        return dp[:HALO], tuple(
            total + _by_eights(dp * x)
            for total, x in zip(sums, shifted)) + (
                sums[k] + _by_eights(dp),)

    nothing = jnp.zeros((HALO, x_ref.shape[1]), f32)
    after, sums = lax.fori_loop(
        0, passes, some_rows, (after_ref[...], (nothing,) * (k + 1)))
    after_ref[...] = after
    for j in range(k):
        dtaps_ref[j:j + 1, :] += jnp.sum(sums[j], axis=0, keepdims=True)
    dbias_ref[...] += jnp.sum(sums[k], axis=0, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("positions", "lanes", "rows", "interpret"))
def backward(x, taps, bias, dy, positions: int = POSITIONS, lanes=None,
             rows: int = ROWS, interpret: bool = False):
    """``(dx`` in ``x``'s dtype, ``dtaps`` ``[k, c]``, ``dbias`` ``[c]``
    float32``)`` of the module's docstring from ``dy`` ``[b, t, c]``
    float32."""
    b, t, c = x.shape
    lanes = lanes or _lanes(c)
    tiles = t // positions
    tile, operands = _blocks(
        x, taps, positions, lanes, lambda c, b, i: (b, tiles - 1 - i, c))
    halo, x_tile, of_taps, of_bias = operands
    dx, dtaps, dbias = pl.pallas_call(
        functools.partial(_bwd_kernel, rows=rows),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(taps.shape, jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)),
        grid=(c // lanes, b, tiles),
        in_specs=[halo, x_tile, tile, of_taps, of_bias],
        out_specs=(tile, of_taps, of_bias),
        scratch_shapes=[pltpu.VMEM((HALO, lanes), jnp.float32)],
        name="tm_conv_silu_bwd",
        **_params(interpret, "parallel", "arbitrary", "arbitrary"),
    )(x, x, dy.astype(jnp.float32), taps.astype(jnp.float32),
      bias.astype(jnp.float32).reshape(1, c))
    return dx, dtaps, dbias.reshape(c)
