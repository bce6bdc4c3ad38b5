"""Attention over the keys a learned indexer selects for each query.

``selected_self_attention`` is causal self-attention on one device in which
query ``i`` sees, of the keys ``j <= i``, only the ``min(i + 1, top_k)``
with the largest index score (ties: the lower ``j``). The score is the
indexer's, a small attention of its own read from the same hidden state::

    I[i, j] = (sum_n w[i, n] * relu(qI[i, n] . kI[j])) * dI^-1/2 * hI^-1/2

(``hI`` heads of ``dI``, one key head), made in float32 at precision
highest: the selection is a step function of ``I``, so ``I`` is the one
quantity here that may not be rounded. The indexer is trained beside the
model by a loss of its own, the divergence of the layer's attention
probabilities (summed over heads, as a constant) from the softmax of ``I``
over the selection::

    L_I = mean_i KL(p_i || softmax_{j in S_i} I[i, j])

Its gradient reaches the indexer's inputs alone, the output's gradient
reaches ``q``, ``k`` and ``v`` alone: the function carries both rules itself.
No ``t x t`` tensor a head is ever held; one sequence's float32 index scores
(``t x t``) do pass through memory, and are made again in backward.

Two executions of one mathematics, chosen as ``blocked_self_attention``
chooses (the platform the program is lowered for, and the heads' width):
on a TPU, Pallas kernels (the index scores, the k-th largest by bisection
over the scores' bit pattern, jax's splash attention under the selection's
mask, the head-summed probabilities, the indexer's gradient); elsewhere
blocks of queries against all the keys in XLA operations.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import telemetry as _telemetry
from ..telemetry import names as _names
from . import ring_attention as _ring

HIGHEST = lax.Precision.HIGHEST
NEG_INF = _ring.NEG_INF
_INT_MIN = np.int32(-2**31)
_INT_MAX = np.int32(2**31 - 1)


def _index_scale(heads: int, dim: int) -> float:
    return float(dim) ** -0.5 * float(heads) ** -0.5


def _chosen(scores, thr, cut, cols):
    """The selection's mask from its two saved numbers a row: key ``j`` is
    selected iff its score is over the row's threshold, or equal to it and
    ``j <= cut`` (the tie rule: the lower ``j``). ``scores`` are ``-inf``
    outside the causal prefix."""
    return (scores > thr) | ((scores == thr) & (cols <= cut))


def _cut_of_ties(scores, thr, want):
    """``(cut, selected)`` a row: the last key among those that tie at the
    threshold that is still selected, so that ``want`` keys are; the count
    selected. ``scores`` ``[rows, t]``, ``thr``, ``want`` ``[rows, 1]``."""
    over = jnp.sum(scores > thr, axis=-1, keepdims=True, dtype=jnp.int32)
    tie = scores == thr
    ties = jnp.sum(tie, axis=-1, keepdims=True, dtype=jnp.int32)
    room = want - over  # ties to take: at least one

    def counted():
        rank = jnp.cumsum(tie.astype(jnp.int32), axis=-1)
        return jnp.sum(rank <= room, axis=-1, keepdims=True,
                       dtype=jnp.int32) - 1

    # more ties at the threshold than there is room for: all but never
    cut = lax.cond(jnp.any(ties > room), counted,
                   lambda: jnp.full_like(room, scores.shape[-1]))
    return cut, over + jnp.minimum(ties, room)


# ---------------------------------------------------------------------------
# XLA operations: a block of queries at a time against all the keys
# ---------------------------------------------------------------------------


def _scores_of(iq, ik, iw):
    """Index scores of a block of queries against every key, float32 at
    precision highest: ``iq`` ``[bq, hI, dI]``, ``ik`` ``[t, dI]``, ``iw``
    ``[bq, hI]`` -> ``[bq, t]``."""
    s = jnp.einsum("qnd,kd->qnk", iq, ik, precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * iw[:, :, None], axis=1) \
        * _index_scale(iq.shape[1], iq.shape[2])


def _block(qb, k, v, iqb, ik, iwb, thr, cut, start, real):
    """One block of queries against all the keys, under the selection that
    ``thr`` and ``cut`` describe: (output ``[bq, hq, d]`` float32, the sum of
    the block's rows of ``L_I``). Differentiable as the layer is: the
    indexer's loss sees the attention's probabilities as a constant."""
    bq, hq, d = qb.shape
    t, hkv, _ = k.shape
    f32 = jnp.float32
    rows = start + jnp.arange(bq)[:, None]
    cols = jnp.arange(t)[None, :]
    scores = jnp.where(cols <= rows, _scores_of(iqb, ik, iwb), -jnp.inf)
    chosen = _chosen(lax.stop_gradient(scores), thr, cut, cols)
    s = jnp.einsum(
        "qhgd,khd->hgqk", qb.reshape(bq, hkv, hq // hkv, d).astype(f32),
        k.astype(f32)) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(chosen, s, NEG_INF), axis=-1)
    out = jnp.einsum("hgqk,khd->qhgd", p, v.astype(f32)).reshape(bq, hq, d)
    mean_p = lax.stop_gradient(jnp.mean(p, axis=(0, 1)))  # [bq, t]
    log_r = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    kl = jnp.where(chosen, jax.scipy.special.xlogy(mean_p, mean_p)
                   - mean_p * jnp.where(chosen, log_r, 0.0), 0.0)
    return out, jnp.sum(jnp.where(rows < real, kl, 0.0))


def _threshold_rows(scores, rows, top_k):
    """``(thr, cut, selected)`` of a block's rows from their causal scores
    (``-inf`` outside the prefix): the ``min(i + 1, top_k)``-th largest."""
    k = min(int(top_k), scores.shape[-1])
    largest = lax.top_k(scores, k)[0]
    want = jnp.minimum(rows + 1, k)
    thr = jnp.take_along_axis(largest, want - 1, axis=-1)
    cut, selected = _cut_of_ties(scores, thr, want)
    return thr, cut, selected


def _loops_forward(q, k, v, iq, ik, iw, top_k, block, real):
    t, hq, d = q.shape
    nb = t // block

    def one(i):
        start = i * block
        rows = start + jnp.arange(block)[:, None]
        take = partial(
            lax.dynamic_slice_in_dim, start_index=start, slice_size=block)
        with jax.named_scope(_names.SCOPE_ATTN_INDEX):
            scores = jnp.where(
                jnp.arange(t)[None, :] <= rows,
                _scores_of(take(iq), ik, take(iw)), -jnp.inf)
        with jax.named_scope(_names.SCOPE_ATTN_SELECT):
            thr, cut, selected = _threshold_rows(scores, rows, top_k)
        with jax.named_scope(_names.SCOPE_ATTN_SPARSE):
            out, loss = _block(
                take(q), k, v, take(iq), ik, take(iw), thr, cut, start,
                real)
        pairs = jnp.sum(jnp.where(rows < real, selected, 0))
        return out.astype(q.dtype), loss, thr, cut, pairs

    out, loss, thr, cut, pairs = lax.map(one, jnp.arange(nb))
    return (out.reshape(t, hq, d), jnp.sum(loss) / real,
            jnp.sum(pairs).astype(jnp.float32),
            (thr.reshape(t, 1), cut.reshape(t, 1)))


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _loops(q, k, v, iq, ik, iw, top_k, block, real):
    return _loops_forward(q, k, v, iq, ik, iw, top_k, block, real)[:3]


def _loops_fwd(q, k, v, iq, ik, iw, top_k, block, real):
    out, loss, pairs, (thr, cut) = _loops_forward(
        q, k, v, iq, ik, iw, top_k, block, real)
    return (out, loss, pairs), (q, k, v, iq, ik, iw, thr, cut)


def _loops_bwd(top_k, block, real, saved, cot):
    """Backward a block of queries at a time: the block is made again under
    the saved selection, and pulled back."""
    q, k, v, iq, ik, iw, thr, cut = saved
    dout, dloss, _ = cot
    t = q.shape[0]
    f32 = jnp.float32

    def one(carry, i):
        start = i * block
        take = partial(
            lax.dynamic_slice_in_dim, start_index=start, slice_size=block)
        _, pull = jax.vjp(
            lambda qb, k, v, iqb, ik, iwb: _block(
                qb, k, v, iqb, ik, iwb, take(thr), take(cut), start, real),
            take(q), k, v, take(iq), ik, take(iw))
        dqb, dkb, dvb, diqb, dikb, diwb = pull(
            (take(dout).astype(f32), dloss / real))
        dk, dv, dik = carry
        return ((dk + dkb.astype(f32), dv + dvb.astype(f32), dik + dikb),
                (dqb, diqb, diwb))

    zeros = (jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32),
             jnp.zeros(ik.shape, f32))
    with jax.named_scope(_names.SCOPE_ATTN_SPARSE):
        (dk, dv, dik), (dq, diq, diw) = lax.scan(
            one, zeros, jnp.arange(t // block))

    def flat(a, like):
        return a.reshape(like.shape).astype(like.dtype)

    return (flat(dq, q), dk.astype(k.dtype), dv.astype(v.dtype),
            flat(diq, iq), dik.astype(ik.dtype), flat(diw, iw))


_loops.defvjp(_loops_fwd, _loops_bwd)


# ---------------------------------------------------------------------------
# the same in kernels
# ---------------------------------------------------------------------------

SAVED = "tm_attn_selected"  # checkpoint_name of what a forward pass keeps
TILE = 512        # the kernels' tile of queries and of keys
SELECT_ROWS = 64  # rows of scores whose k-th largest one kernel step finds
PANEL = 4096      # queries whose scores, masks and probabilities are held
#                   at once: a panel of rows against the keys up to its end
# what a device trace calls the kernels of the attention over the selection
# (an event's name is the kernel's HLO instruction): jax's splash kernels,
# and the head-summed probabilities for the indexer's loss
SPARSE_KERNEL_EVENTS = ("splash_mqa_", "tm_attn_sparse_")


def _tile_of(t: int) -> int:
    return next(s for s in (TILE, 256, 128) if t % s == 0)


def _panel_of(t: int) -> int:
    return next((p for p in (PANEL, 2048, 1024) if t % p == 0), t)


def _last_tile(first, qi, bq, bk):
    """The last tile of keys that holds a key of the causal prefix of any
    query of tile ``qi`` of a panel whose first query is ``first``."""
    return (first + qi * bq + bq - 1) // bk


def _params(interpret, *semantics):
    from jax.experimental.pallas import tpu as pltpu

    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=96 * 2**20)}


def _nt(a, b, precision=None):
    """``a @ b.T`` with float32 accumulation."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           precision=precision,
                           preferred_element_type=jnp.float32)


def _index_scores_kernel(iq_ref, ik_ref, iw_ref, out_ref, *, first, scale):
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(0), pl.program_id(1)
    bq, bk = out_ref.shape
    inside = kj <= _last_tile(first, qi, bq, bk)

    @pl.when(inside)
    def _():
        ik, iw = ik_ref[...], iw_ref[...]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for n in range(iq_ref.shape[0]):
            s = _nt(iq_ref[n], ik, HIGHEST)
            acc = acc + jnp.maximum(s, 0.0) * iw[:, n:n + 1]
        rows = first + qi * bq + lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        cols = kj * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        out_ref[...] = jnp.where(cols <= rows, acc * scale, -jnp.inf)

    @pl.when(jnp.logical_not(inside))
    def _():
        out_ref[...] = jnp.full((bq, bk), -jnp.inf, jnp.float32)


def _index_scores(iq, ik, iw, first, interpret):
    """``I`` of a panel of queries ``[rows, keys]`` float32, ``-inf``
    outside the causal prefix. ``iq`` ``[hI, rows, dI]`` (head-major), ``ik``
    ``[keys, dI]``, ``iw`` ``[rows, hI]``; the panel's first query is
    ``first``, its last the last key."""
    from jax.experimental import pallas as pl

    heads, rows, dim = iq.shape
    keys = ik.shape[0]
    b = _tile_of(rows)
    return pl.pallas_call(
        partial(_index_scores_kernel, first=first,
                scale=_index_scale(heads, dim)),
        grid=(rows // b, keys // b),
        in_specs=[pl.BlockSpec((heads, b, dim), lambda qi, kj: (0, qi, 0)),
                  pl.BlockSpec((b, dim), lambda qi, kj: (
                      jnp.minimum(kj, _last_tile(first, qi, b, b)), 0)),
                  pl.BlockSpec((b, heads), lambda qi, kj: (qi, 0))],
        out_specs=pl.BlockSpec((b, b), lambda qi, kj: (qi, kj)),
        out_shape=jax.ShapeDtypeStruct((rows, keys), jnp.float32),
        name="tm_attn_index_scores",
        **_params(interpret, "parallel", "arbitrary"),
    )(iq, ik, iw)


def _ordered(x):
    """float32 -> int32 whose signed order is the floats' order."""
    bits = lax.bitcast_convert_type(x + 0.0, jnp.int32)  # -0.0 -> 0.0
    return jnp.where(bits < 0, bits ^ _INT_MAX, bits)


def _select_kernel(scores_ref, thr_ref, keys_ref, *, first, top_k, chunk):
    """The ``min(i + 1, top_k)``-th largest of each row, exactly: the
    largest bit pattern that at least that many of the row's scores reach,
    built from the top bit down, 32 passes of compare-and-count over the
    chunks of the row that hold a key of the causal prefix. The scores'
    ordered bit patterns are made once, into ``keys_ref``; a pass counts
    lane by lane and sums across the lanes once."""
    from jax.experimental import pallas as pl

    rows, _ = scores_ref.shape
    lanes = _ring.LANES
    row0 = first + pl.program_id(0) * rows
    want = jnp.minimum(
        row0 + 1 + lax.broadcasted_iota(jnp.int32, (rows, 1), 0), top_k)
    chunks = (row0 + rows - 1) // chunk + 1

    def fill(c, _):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        keys_ref[:, at] = _ordered(scores_ref[:, at])
        return 0

    lax.fori_loop(0, chunks, fill, 0)

    def count(bound):
        wide = jnp.broadcast_to(bound, (rows, lanes))

        def add(c, n):
            keys = keys_ref[:, pl.ds(pl.multiple_of(c * chunk, chunk), chunk)]
            for at in range(0, chunk, lanes):
                n = n + (keys[:, at:at + lanes] >= wide).astype(jnp.int32)
            return n

        return jnp.sum(
            lax.fori_loop(0, chunks, add,
                          jnp.zeros((rows, lanes), jnp.int32)),
            axis=1, keepdims=True)

    def bit(b, found):
        # ``found`` holds the pattern in unsigned order: the signed keys
        # are compared against it with the top bit flipped
        trial = found | lax.shift_left(jnp.int32(1), 31 - b)
        return jnp.where(count(trial ^ _INT_MIN) >= want, trial, found)

    key = lax.fori_loop(0, 32, bit, jnp.zeros((rows, 1), jnp.int32)) \
        ^ _INT_MIN
    thr_ref[...] = lax.bitcast_convert_type(
        jnp.where(key < 0, key ^ _INT_MAX, key), jnp.float32)


def _select(scores, first, top_k, interpret):
    """Each row's threshold ``[rows, 1]`` float32 (a panel's scores)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    panel, keys = scores.shape
    rows = min(SELECT_ROWS, panel)
    return pl.pallas_call(
        partial(_select_kernel, first=first, top_k=int(top_k),
                chunk=_tile_of(panel)),
        grid=(panel // rows,),
        in_specs=[pl.BlockSpec((rows, keys), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((rows, 1), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((panel, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, keys), jnp.int32)],
        name="tm_attn_select_kth",
        **_params(interpret, "parallel"),
    )(scores)


def _mean_probabilities_kernel(q_ref, k_ref, lse_ref, mask_ref, out_ref, *,
                               first, groups):
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(0), pl.program_id(1)
    bq, bk = out_ref.shape
    heads = q_ref.shape[0]
    inside = kj <= _last_tile(first, qi, bq, bk)

    @pl.when(inside)
    def _():
        lse = lse_ref[...]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(heads):
            s = _nt(q_ref[h], k_ref[h // groups])
            acc = acc + jnp.exp(s - lse[:, h:h + 1])
        out_ref[...] = jnp.where(mask_ref[...] != 0, acc * (1.0 / heads), 0.0)

    @pl.when(jnp.logical_not(inside))
    def _():
        out_ref[...] = jnp.zeros((bq, bk), jnp.float32)


def _mean_probabilities(q, k, lse, mask, first, interpret):
    """The attention's probabilities summed over the heads and divided by
    their number, a panel ``[rows, keys]`` float32, zero off the selection:
    ``q`` ``[hq, rows, d]`` (scaled), ``k`` ``[hkv, keys, d]``, ``lse``
    ``[rows, hq]``, ``mask`` ``[rows, keys]`` int8."""
    from jax.experimental import pallas as pl

    hq, rows, d = q.shape
    hkv, keys, _ = k.shape
    b = _tile_of(rows)
    return pl.pallas_call(
        partial(_mean_probabilities_kernel, first=first, groups=hq // hkv),
        grid=(rows // b, keys // b),
        in_specs=[pl.BlockSpec((hq, b, d), lambda qi, kj: (0, qi, 0)),
                  pl.BlockSpec((hkv, b, d), lambda qi, kj: (
                      0, jnp.minimum(kj, _last_tile(first, qi, b, b)), 0)),
                  pl.BlockSpec((b, hq), lambda qi, kj: (qi, 0)),
                  pl.BlockSpec((b, b), lambda qi, kj: (qi, kj))],
        out_specs=pl.BlockSpec((b, b), lambda qi, kj: (qi, kj)),
        out_shape=jax.ShapeDtypeStruct((rows, keys), jnp.float32),
        name="tm_attn_sparse_mean_probabilities",
        **_params(interpret, "parallel", "arbitrary"),
    )(q, k, lse, mask)


def _index_grad_queries_kernel(g_ref, iq_ref, ik_ref, iw_ref, diq_ref,
                               diw_ref, *, first, scale):
    """d ``iq``, d ``iw`` of a tile of queries, summed over the tiles of
    keys (the grid's inner axis): the tile's scores are made again, at the
    default precision (a gradient may be rounded, the selection not)."""
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(0), pl.program_id(1)
    bq, bk = g_ref.shape
    heads = iq_ref.shape[0]

    @pl.when(kj == 0)
    def _():
        diq_ref[...] = jnp.zeros_like(diq_ref)
        diw_ref[...] = jnp.zeros_like(diw_ref)

    @pl.when(kj <= _last_tile(first, qi, bq, bk))
    def _():
        g, ik, iw = g_ref[...] * scale, ik_ref[...], iw_ref[...]
        head = lax.broadcasted_iota(jnp.int32, (1, heads), 1)
        diw = jnp.zeros((bq, heads), jnp.float32)
        for n in range(heads):
            s = _nt(iq_ref[n], ik)
            diw = diw + jnp.where(head == n, jnp.sum(
                g * jnp.maximum(s, 0.0), axis=1, keepdims=True), 0.0)
            through = jnp.where(s > 0.0, g * iw[:, n:n + 1], 0.0)
            diq_ref[n] += jnp.dot(through, ik,
                                  preferred_element_type=jnp.float32)
        diw_ref[...] += diw


def _index_grad_keys_kernel(g_ref, iq_ref, ik_ref, iw_ref, dik_ref, *,
                            first, scale):
    """d ``ik`` of a tile of keys, summed over the tiles of queries (the
    grid's inner axis): the products contract the queries' axis."""
    from jax.experimental import pallas as pl

    kj, qi = pl.program_id(0), pl.program_id(1)
    bq, bk = g_ref.shape

    @pl.when(qi == 0)
    def _():
        dik_ref[...] = jnp.zeros_like(dik_ref)

    @pl.when(kj <= _last_tile(first, qi, bq, bk))
    def _():
        g, ik, iw = g_ref[...] * scale, ik_ref[...], iw_ref[...]
        acc = jnp.zeros(dik_ref.shape, jnp.float32)
        for n in range(iq_ref.shape[0]):
            iq = iq_ref[n]
            through = jnp.where(_nt(iq, ik) > 0.0, g * iw[:, n:n + 1], 0.0)
            acc = acc + lax.dot_general(
                through, iq, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dik_ref[...] += acc


def _index_grads(g, iq, ik, iw, first, interpret):
    """Pull ``g = dL/dI`` of a panel ``[rows, keys]`` (zero off the
    selection) back through the index scores: (d ``iq`` ``[hI, rows, dI]``,
    d ``ik`` ``[keys, dI]``, d ``iw`` ``[rows, hI]``)."""
    from jax.experimental import pallas as pl

    heads, rows, dim = iq.shape
    keys = ik.shape[0]
    b = _tile_of(rows)
    scale = _index_scale(heads, dim)
    key_tile = lambda qi, kj: jnp.minimum(  # noqa: E731
        kj, _last_tile(first, qi, b, b))
    # the first tile of the panel's queries that sees tile ``kj`` of keys
    query_tile = lambda kj, qi: jnp.maximum(qi, kj - first // b)  # noqa: E731
    diq, diw = pl.pallas_call(
        partial(_index_grad_queries_kernel, first=first, scale=scale),
        grid=(rows // b, keys // b),
        in_specs=[
            pl.BlockSpec((b, b), lambda qi, kj: (qi, key_tile(qi, kj))),
            pl.BlockSpec((heads, b, dim), lambda qi, kj: (0, qi, 0)),
            pl.BlockSpec((b, dim), lambda qi, kj: (key_tile(qi, kj), 0)),
            pl.BlockSpec((b, heads), lambda qi, kj: (qi, 0))],
        out_specs=[pl.BlockSpec((heads, b, dim), lambda qi, kj: (0, qi, 0)),
                   pl.BlockSpec((b, heads), lambda qi, kj: (qi, 0))],
        out_shape=[jax.ShapeDtypeStruct((heads, rows, dim), jnp.float32),
                   jax.ShapeDtypeStruct((rows, heads), jnp.float32)],
        name="tm_attn_index_grad_queries",
        **_params(interpret, "parallel", "arbitrary"),
    )(g, iq, ik, iw)
    dik = pl.pallas_call(
        partial(_index_grad_keys_kernel, first=first, scale=scale),
        grid=(keys // b, rows // b),
        in_specs=[
            pl.BlockSpec((b, b), lambda kj, qi: (query_tile(kj, qi), kj)),
            pl.BlockSpec((heads, b, dim), lambda kj, qi: (
                0, query_tile(kj, qi), 0)),
            pl.BlockSpec((b, dim), lambda kj, qi: (kj, 0)),
            pl.BlockSpec((b, heads), lambda kj, qi: (
                query_tile(kj, qi), 0))],
        out_specs=pl.BlockSpec((b, dim), lambda kj, qi: (kj, 0)),
        out_shape=jax.ShapeDtypeStruct((keys, dim), jnp.float32),
        name="tm_attn_index_grad_keys",
        **_params(interpret, "parallel", "arbitrary"),
    )(g, iq, ik, iw)
    return diq, dik, diw


def _splash(keys: int, interpret: bool):
    """What jax's splash-attention kernels are called with here: the key
    tile of ``ring_attention._fused_kernel`` and its one backward kernel,
    but 512 queries a tile: a mixed tile's mask reaches the kernel as int32,
    and 1,024 x 1,024 of them, double-buffered, pass the 16 MiB of VMEM a
    kernel may take."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel,
    )

    tile = _ring._fused_tile(keys)
    piece = 512 if tile % 512 == 0 else tile
    sizes = kernel.BlockSizes(
        block_q=piece, block_kv=tile, block_kv_compute=piece,
        block_q_dkv=piece, block_kv_dkv=tile, block_kv_dkv_compute=piece,
        use_fused_bwd_kernel=True)
    return kernel, sizes, dict(
        mask_value=kernel.DEFAULT_MASK_VALUE, is_mqa=True, block_sizes=sizes,
        residual_checkpoint_name=None, mask_function=None,
        attn_logits_soft_cap=None, interpret=interpret)


def _mask_tables(mask, groups: int, tile, backward: bool):
    """splash attention's tables for a mask known only when the step runs:
    one head's tables (which tiles are empty, whole or mixed, and the mixed
    tiles' masks) shared by the ``groups`` query heads of a KV head."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask_info as info,
    )

    tables, _ = info._process_dynamic_mask(
        mask[None], tile, is_dkv=backward)

    def every(a):
        return jnp.broadcast_to(a, (groups,) + a.shape[1:])

    return tables._replace(
        data_next=every(tables.data_next), mask_next=every(tables.mask_next),
        block_mask=every(tables.block_mask),
        partial_mask_blocks=tables.partial_mask_blocks.reshape(
            (-1,) + tables.partial_mask_blocks.shape[-2:]))


def _head_major(x):
    return jnp.moveaxis(x, 1, 0)


def _panels(t: int):
    """(first query, one past the last) of each panel: a panel's queries
    see no key past its own end."""
    size = _panel_of(t)
    return [(first, first + size) for first in range(0, t, size)]


def _selection_of(iq, ik, iw, first, top_k, interpret, saved=None):
    """A panel's (index scores ``[rows, keys]``, mask, thr, cut, selected a
    row); ``iq`` head-major. With ``saved`` (thr, cut), the mask is made
    from them again and nothing is selected anew."""
    rows, keys = iq.shape[1], ik.shape[0]
    with jax.named_scope(_names.SCOPE_ATTN_INDEX):
        scores = _index_scores(iq, ik, iw, first, interpret)
    with jax.named_scope(_names.SCOPE_ATTN_SELECT):
        selected = None
        if saved is None:
            thr = _select(scores, first, top_k, interpret)
            want = jnp.minimum(
                first + jnp.arange(rows)[:, None] + 1, int(top_k))
            cut, selected = _cut_of_ties(scores, thr, want)
        else:
            thr, cut = saved
        mask = _chosen(scores, thr, cut, jnp.arange(keys)[None, :])
    return scores, mask, thr, cut, selected


def _panel_probabilities(qh, kh, lse, mask, first, end, interpret):
    """``_mean_probabilities`` of the panel ``[first, end)``: ``qh`` ``[hkv,
    g, t, d]`` (scaled), ``kh`` ``[hkv, t, d]``, the panel's ``lse`` and
    mask."""
    hkv, g, _, d = qh.shape
    return _mean_probabilities(
        qh[:, :, first:end].reshape(hkv * g, end - first, d), kh[:, :end],
        lse, mask.astype(jnp.int8), first, interpret)


def _kernels_forward(q, k, v, iq, ik, iw, top_k, real, interpret):
    t, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qs = (q * (1.0 / math.sqrt(d))).astype(q.dtype)
    qh = _head_major(qs).reshape(hkv, g, t, d)
    kh, vh, iqh = _head_major(k), _head_major(v), _head_major(iq)
    outs, small, loss, pairs = [], [], 0.0, 0.0
    for first, end in _panels(t):
        scores, mask, thr, cut, selected = _selection_of(
            iqh[:, first:end], ik[:end], iw[first:end], first, top_k,
            interpret)
        with jax.named_scope(_names.SCOPE_ATTN_SPARSE):
            kernel, sizes, how = _splash(end, interpret)
            tables = _mask_tables(
                mask, g, (sizes.block_q, sizes.block_kv), backward=False)
            out, (lse,) = jax.vmap(
                lambda q, k, v: kernel._splash_attention_forward(
                    tables, q, k, v, None, None, save_residuals=True, **how)
            )(qh[:, :, first:end], kh[:, :end], vh[:, :end])
            lse = lse.reshape(hq, end - first).T
            mean_p = _panel_probabilities(
                qh, kh, lse, mask, first, end, interpret)
            log_z = jax.nn.logsumexp(
                jnp.where(mask, scores, -jnp.inf), axis=-1, keepdims=True)
            counts = first + jnp.arange(end - first)[:, None] < real
            kl = jnp.where(
                mask & counts, jax.scipy.special.xlogy(mean_p, mean_p)
                - mean_p * (jnp.where(mask, scores, 0.0) - log_z), 0.0)
            loss = loss + jnp.sum(kl)
            pairs = pairs + jnp.sum(jnp.where(counts, selected, 0))
        outs.append(out.reshape(hq, end - first, d))
        small.append((lse, thr, cut))
    out = jnp.moveaxis(jnp.concatenate(outs, axis=1), 0, 1)
    return (out, loss / real, jnp.asarray(pairs, jnp.float32),
            tuple(jnp.concatenate(a) for a in zip(*small)))


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _kernels(q, k, v, iq, ik, iw, top_k, real, interpret):
    return _kernels_forward(q, k, v, iq, ik, iw, top_k, real, interpret)[:3]


def _kernels_fwd(q, k, v, iq, ik, iw, top_k, real, interpret):
    from jax.ad_checkpoint import checkpoint_name

    out, loss, pairs, small = _kernels_forward(
        q, k, v, iq, ik, iw, top_k, real, interpret)
    # named, so that a caller that recomputes its layer in backward can keep
    # these four (a policy of ``save_only_these_names(SAVED)``) and has the
    # scores, the k-th largest and the forward kernel made once a step
    out, lse, thr, cut = (checkpoint_name(a, SAVED) for a in (out, *small))
    return (out, loss, pairs), (q, k, v, iq, ik, iw, out, lse, thr, cut)


def _kernels_bwd(top_k, real, interpret, saved, cot):
    q, k, v, iq, ik, iw, out, lse, thr, cut = saved
    dout, dloss, _ = cot
    t, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    f32 = jnp.float32
    qs = (q * (1.0 / math.sqrt(d))).astype(q.dtype)

    def heads(a):
        return _head_major(a).reshape(hkv, g, t, d)

    qh, oh, doh = heads(qs), heads(out), heads(dout.astype(out.dtype))
    kh, vh, iqh = _head_major(k), _head_major(v), _head_major(iq)
    lse_h = lse.T.reshape(hkv, g, t)
    dqs, diqs, diws = [], [], []
    dk, dv = jnp.zeros(kh.shape, f32), jnp.zeros(vh.shape, f32)
    dik = jnp.zeros(ik.shape, f32)

    def ahead(a, end, axis):
        """A panel's gradient of the keys up to ``end``, among all ``t``."""
        return jnp.pad(a.astype(f32), [(0, t - end if i == axis else 0)
                                       for i in range(a.ndim)])

    for first, end in _panels(t):
        rows = slice(first, end)
        scores, mask, *_ = _selection_of(
            iqh[:, rows], ik[:end], iw[rows], first, top_k, interpret,
            saved=(thr[rows], cut[rows]))
        with jax.named_scope(_names.SCOPE_ATTN_SPARSE):
            kernel, sizes, how = _splash(end, interpret)
            tables = _mask_tables(
                mask, g, (sizes.block_q_dkv, sizes.block_kv_dkv),
                backward=True)

            def pull(q, k, v, out, lse, do):
                res = (q, k, v, None, None, out, lse, None, tables)
                return kernel._splash_attention_bwd(
                    False, how["mask_value"], True, sizes, None, None, None,
                    interpret, res, do)[3:6]

            dq, dkp, dvp = jax.vmap(pull)(
                qh[:, :, rows], kh[:, :end], vh[:, :end], oh[:, :, rows],
                lse_h[:, :, rows], doh[:, :, rows])
            dqs.append(dq.reshape(hq, end - first, d))
            dk, dv = dk + ahead(dkp, end, 1), dv + ahead(dvp, end, 1)
            mean_p = _panel_probabilities(
                qh, kh, lse[rows], mask, first, end, interpret)
            counts = first + jnp.arange(end - first)[:, None] < real
            pulled = jnp.where(
                mask & counts,
                (jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
                 - mean_p) * (dloss / real), 0.0)
            diq, dikp, diw = _index_grads(
                pulled, iqh[:, rows], ik[:end], iw[rows], first, interpret)
            dik = dik + ahead(dikp, end, 0)
            diqs.append(diq)
            diws.append(diw)
    dq = jnp.moveaxis(jnp.concatenate(dqs, axis=1), 0, 1) \
        * (1.0 / math.sqrt(d))
    return (dq.astype(q.dtype), jnp.moveaxis(dk, 0, 1).astype(k.dtype),
            jnp.moveaxis(dv, 0, 1).astype(v.dtype),
            _head_major(jnp.concatenate(diqs, axis=1)).astype(iq.dtype),
            dik.astype(ik.dtype), jnp.concatenate(diws).astype(iw.dtype))


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


# ---------------------------------------------------------------------------
# what a step's selection measured
# ---------------------------------------------------------------------------


def _selection_gauges():
    m = _telemetry.metrics
    return (
        m.gauge(
            "tm_attn_causal_pairs_per_step",
            "query-key pairs j <= i of the selected-attention layers of "
            "the step most recently traced, on this rank (static shapes)"),
        m.gauge(
            "tm_attn_selected_pairs_per_step",
            "of those pairs, the ones the indexers selected in the last "
            "step read, summed over the layers"),
        m.gauge(
            "tm_attn_index_loss_last_step",
            "the indexers' loss L_I in the last step read, mean over the "
            "selected-attention layers"),
    )


def note_selected_layers(batch: int, t: int, layers: int) -> None:
    """Publish the causal pairs of a step's selected-attention layers from
    static shapes: a model calls this where its forward pass begins to be
    traced (as ``ep.note_expert_layers``)."""
    if layers:
        _selection_gauges()[0].set(
            int(layers) * int(batch) * int(t) * (int(t) + 1) // 2)


def note_selection(index_loss, pairs, selects) -> None:
    """Publish a step's measured selection (host arrays ``[layers]``;
    ``selects`` says which layers select)."""
    on = np.asarray(selects, bool)
    _, selected, loss = _selection_gauges()
    selected.set(float(np.asarray(pairs, np.float64)[on].sum()))
    loss.set(float(np.asarray(index_loss, np.float64)[on].mean()))


# ---------------------------------------------------------------------------
# the function
# ---------------------------------------------------------------------------


def _pad_rows(a, multiple: int):
    pad = -a.shape[0] % multiple
    return a if not pad else jnp.pad(
        a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))


def _one_sequence(run, multiple, args):
    """``run`` over one sequence whose arrays are padded to a multiple of
    ``multiple`` rows: keys past the end lie in every real query's future,
    the rows of the queries past the end are cut off again and count for
    nothing in the loss."""
    t = args[0].shape[0]
    out, loss, pairs = run(*(_pad_rows(a, multiple) for a in args), t)
    return out[:t], loss, pairs


def selected_self_attention(q, k, v, index_q, index_k, index_w, top_k: int,
                            block: int = 512):
    """Causal self-attention over the ``top_k`` keys an indexer selects for
    each query, with the indexer's own loss.

    ``q`` ``[batch, t, heads, head_dim]``; ``k``, ``v`` may have fewer heads
    (grouped, not repeated). ``index_q`` ``[batch, t, hI, dI]``, ``index_k``
    ``[batch, t, dI]``, ``index_w`` ``[batch, t, hI]``, float32: the
    indexer's queries, its one head of keys and its head weights. Query
    ``i`` attends to the ``min(i + 1, top_k)`` keys ``j <= i`` with the
    largest ``I[i, j]`` (the module's formula; ties to the lower ``j``);
    with ``top_k >= t`` that is ``blocked_self_attention`` over the causal
    prefix.

    Returns ``(out [batch, t, heads, head_dim], index_loss [], selected
    pairs [] float32)``: ``index_loss`` is the mean over the batch's
    queries of ``KL(p_i || softmax_{S_i} I_i)``, ``p_i`` the attention's
    probabilities summed over heads over their number, taken as a
    constant. Gradients: ``out``'s reaches ``q``, ``k``, ``v`` alone,
    ``index_loss``'s the three index arrays alone (a caller that wants the
    indexer detached from the model hands it a ``stop_gradient`` of its
    input).

    Lowered for a TPU with ``head_dim`` a multiple of 128: kernels (see the
    module); elsewhere blocks of ``block`` queries against all the keys in
    XLA operations. No flag: the call decides, as
    ``blocked_self_attention`` does."""
    if q.shape[2] % k.shape[2] or k.shape != v.shape:
        raise ValueError(
            f"query heads {q.shape[2]} must be a multiple of the KV heads "
            f"{k.shape[2]}, and k and v alike (got {k.shape}, {v.shape})")
    b, t = q.shape[:2]
    if (index_q.shape[:2] != (b, t) or index_k.shape != (
            b, t, index_q.shape[3]) or index_w.shape != index_q.shape[:3]):
        raise ValueError(
            "the indexer's arrays must be [b, t, hI, dI], [b, t, dI], "
            f"[b, t, hI]; got {index_q.shape}, {index_k.shape}, "
            f"{index_w.shape}")
    if int(top_k) < 1:
        raise ValueError(f"top_k must be positive, got {top_k}")
    f32 = jnp.float32
    args = (q, k, v, index_q.astype(f32), index_k.astype(f32),
            index_w.astype(f32))
    block = min(int(block), t)

    def loops(*args):
        return lax.map(lambda a: _one_sequence(
            lambda *p: _loops(*p[:-1], int(top_k), block, p[-1]),
            block, a), args)

    def kernels(*args):
        # a sequence at a time, unrolled: the tables of a sequence's mask
        # are the kernels' scalar arguments
        each = [_one_sequence(
            lambda *p: _kernels(*p[:-1], int(top_k), p[-1], False),
            _ring._fused_tile(t), [a[i] for a in args]) for i in range(b)]
        return tuple(jnp.stack(x) for x in zip(*each))

    fused = q.shape[-1] % _ring.LANES == 0
    _ring._note_attention_call(fused and jax.default_backend() == "tpu")
    if fused:
        out, loss, pairs = lax.platform_dependent(
            *args, tpu=kernels, default=loops)
    else:
        out, loss, pairs = loops(*args)
    return out, jnp.mean(loss), jnp.sum(pairs)
