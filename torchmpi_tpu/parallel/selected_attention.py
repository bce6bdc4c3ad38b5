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
do pass through memory, a panel of ``PANEL`` queries against the keys up to
the panel's end at a time, and the kernels' forward pass hands its panels to
backward as residuals: the scores are made once, and backward masks with
the bits forward selected from. They bear the name ``SAVED``
(``ring_attention.SAVED``: one name for what any attention call's forward
kernels keep for backward) with the small
residuals, for a caller that recomputes the layer: ``4 * t * (t + PANEL) / 2``
bytes a sequence (640 MiB at 16,384 positions, 2.25 GiB at 32,768) beside
the 130 MiB of the other five. One name, because scores made again from a
recomputed indexer are not forward's bits on the chip (XLA rounds the
recomputed block elsewhere: up to 0.038 apart, measured, PR 36), and
backward would then mask with another selection than forward attended.

Two executions of one mathematics, chosen as ``blocked_self_attention``
chooses (the platform the program is lowered for, and the heads' width):
on a TPU, Pallas kernels of this module (the index scores, the k-th largest
by bisection over the scores' bit pattern, attention over the selection
forward and backward, the indexer's loss from the head-summed
probabilities, the indexer's gradient); elsewhere blocks of queries against
all the keys in XLA operations. In the kernels the selection is never an
array: each takes a tile of index scores and its rows' two numbers
(threshold and tie cut) and makes the tile's mask in VMEM, once for all the
query heads that share the tile.
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
_TINY = float(np.finfo(np.float32).tiny)


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
    ties = jnp.sum(scores == thr, axis=-1, keepdims=True, dtype=jnp.int32)
    room = want - over  # ties to take: at least one

    def counted():
        rank = jnp.cumsum((scores == thr).astype(jnp.int32), axis=-1)
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

SAVED = _ring.SAVED  # checkpoint_name of what a forward pass keeps
TILE = 512        # the kernels' tile of queries and of keys
SELECT_ROWS = 64  # rows of scores whose k-th largest one kernel step finds
PANEL = 4096      # queries whose scores (and, in backward, the indexer's
#                   gradient of them) are held at once: a panel of rows
#                   against the keys up to its end
# what a device trace calls the kernels of the attention over the selection
# (an event's name is the kernel's HLO instruction): forward, the indexer's
# loss from the head-summed probabilities, backward
SPARSE_KERNEL_EVENTS = ("tm_attn_sparse_",)


def _tile_of(t: int) -> int:
    return next(s for s in (TILE, 256, 128) if t % s == 0)


def _wide_tile_of(t: int) -> int:
    """The forward attention kernel's tile of keys: two tiles of queries
    wide where a panel allows (a row's maximum crosses the lanes once a
    tile of keys, so a wider tile is cheaper a key: 34.3 ms a layer at 512,
    23.0 at 1,024, 23.5 at 2,048 on a v5e; PERF.md, PR 31)."""
    return 2 * TILE if t % (2 * TILE) == 0 else _tile_of(t)


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


def _chosen_tile(scores_ref, thr_ref, cut_ref, kj, at=0, width=None):
    """``_chosen`` on tile ``kj`` of keys (its ``width`` columns from ``at``
    on), from the tile's scores and its rows' two numbers, all in VMEM."""
    size = scores_ref.shape[1]
    scores = scores_ref[:, at:at + (width or size)]
    cols = kj * size + at + lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    return scores, _chosen(scores, thr_ref[...], cut_ref[...], cols)


def _attend_kernel(q_ref, k_ref, v_ref, scores_ref, thr_ref, cut_ref,
                   out_ref, lse_ref, m_ref, l_ref, acc_ref, *, first):
    """Causal attention of one KV head's query heads over the selection: a
    tile of queries against the tiles of keys up to its diagonal (the
    grid's inner axis), streaming softmax. The selection is made here, from
    the tile's index scores, once for the heads that share the tile. A
    tile of keys is several tiles of queries wide (a row's maximum crosses
    the lanes once a tile); on the diagonal only the pieces that hold a key
    of the tile's causal prefix are visited."""
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(1), pl.program_id(2)
    g, bq, _ = q_ref.shape
    bk = k_ref.shape[0]
    last_row = first + qi * bq + bq - 1
    last = last_row // bk

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def visit(at, width):
        _, chosen = _chosen_tile(scores_ref, thr_ref, cut_ref, kj, at, width)
        # a piece with no selected key yet leaves a row's maximum at
        # NEG_INF and its sums at what exp(0) gives; the first selected
        # key's alpha, exp(NEG_INF - s) = 0, wipes them
        off = jnp.where(chosen, 0.0, NEG_INF)
        k, v = k_ref[at:at + width], v_ref[at:at + width]

        def head(h, _):
            s = _nt(q_ref[h], k) + off
            m_prev = m_ref[h]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_next
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return 0

        lax.fori_loop(0, g, head, 0)

    @pl.when(kj < last)
    def _():
        visit(0, bk)

    for piece in range(bk // bq):  # the diagonal's tile, a piece at a time
        @pl.when((kj == last) & (kj * bk + piece * bq <= last_row))
        def _():
            visit(piece * bq, bq)

    @pl.when(kj == last)
    def _():
        lane = lax.broadcasted_iota(jnp.int32, lse_ref.shape, 1)
        lse = jnp.zeros(lse_ref.shape, jnp.float32)
        for h in range(g):
            out_ref[h] = (acc_ref[h] / l_ref[h]).astype(out_ref.dtype)
            lse = jnp.where(lane == h, m_ref[h] + jnp.log(l_ref[h]), lse)
        lse_ref[...] = lse


def _attend(q, k, v, scores, thr, cut, first, interpret):
    """Attention over the selection, a panel of queries: ``q`` ``[hkv, g,
    t, d]`` (scaled; the panel's rows are taken from ``first`` on), ``k``,
    ``v`` ``[hkv, t, d]`` (the keys up to the panel's end are read), the
    panel's ``scores`` ``[rows, keys]`` and ``thr``, ``cut`` ``[rows, 1]``
    -> (out ``[hkv, g, rows, d]``, the log-sum-exps ``[rows, hkv * g]``
    float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hkv, g, _, d = q.shape
    rows, keys = scores.shape
    bq, bk = _tile_of(rows), _wide_tile_of(rows)
    lanes = -(-g // _ring.LANES) * _ring.LANES
    key_tile = lambda qi, kj: jnp.minimum(  # noqa: E731
        kj, _last_tile(first, qi, bq, bk))
    of_rows = lambda h, qi, kj: (qi, 0)  # noqa: E731
    out, lse = pl.pallas_call(
        partial(_attend_kernel, first=first),
        grid=(hkv, rows // bq, keys // bk),
        in_specs=[
            pl.BlockSpec((None, g, bq, d),
                         lambda h, qi, kj: (h, 0, first // bq + qi, 0)),
            pl.BlockSpec((None, bk, d),
                         lambda h, qi, kj: (h, key_tile(qi, kj), 0)),
            pl.BlockSpec((None, bk, d),
                         lambda h, qi, kj: (h, key_tile(qi, kj), 0)),
            pl.BlockSpec((bq, bk), lambda h, qi, kj: (qi, key_tile(qi, kj))),
            pl.BlockSpec((bq, 1), of_rows), pl.BlockSpec((bq, 1), of_rows)],
        out_specs=[
            pl.BlockSpec((None, g, bq, d), lambda h, qi, kj: (h, 0, qi, 0)),
            pl.BlockSpec((None, bq, lanes), lambda h, qi, kj: (h, qi, 0))],
        out_shape=[jax.ShapeDtypeStruct((hkv, g, rows, d), q.dtype),
                   jax.ShapeDtypeStruct((hkv, rows, lanes), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g, bq, 1), jnp.float32),
                        pltpu.VMEM((g, bq, 1), jnp.float32),
                        pltpu.VMEM((g, bq, d), jnp.float32)],
        name="tm_attn_sparse_fwd",
        **_params(interpret, "parallel", "parallel", "arbitrary"),
    )(q, k, v, scores, thr, cut)
    return out, jnp.moveaxis(lse[:, :, :g], 0, 1).reshape(rows, hkv * g)


def _mean_probabilities_kernel(q_ref, k_ref, lse_ref, scores_ref, thr_ref,
                               cut_ref, loss_ref, log_z_ref, m_ref, l_ref,
                               a_ref, c_ref, *, first, groups, real):
    """A tile of queries' rows of ``L_I``, summed over the tiles of keys
    (the grid's inner axis): the attention's probabilities summed over the
    heads and divided by their number stay in VMEM; what leaves is a row's
    ``sum_S p log p - sum_S p I + log_z sum_S p`` and its ``log_z``, the
    log-sum-exp of its selected index scores."""
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(0), pl.program_id(1)
    heads, bq, _ = q_ref.shape
    bk = k_ref.shape[1]
    last = _last_tile(first, qi, bq, bk)

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        for ref in (l_ref, a_ref, c_ref):
            ref[...] = jnp.zeros(ref.shape, jnp.float32)

    @pl.when(kj <= last)
    def _():
        lse = lse_ref[...]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(heads):
            s = _nt(q_ref[h], k_ref[h // groups])
            acc = acc + jnp.exp(s - lse[:, h:h + 1])
        scores, chosen = _chosen_tile(scores_ref, thr_ref, cut_ref, kj)
        p = jnp.where(chosen, acc * (1.0 / heads), 0.0)
        held = jnp.where(chosen, scores, 0.0)
        # p log p - p I; where p is 0 the logarithm's floor keeps it 0
        a_ref[...] += jnp.sum(
            p * (jnp.log(jnp.maximum(p, _TINY)) - held), axis=1,
            keepdims=True)
        c_ref[...] += jnp.sum(p, axis=1, keepdims=True)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(
            jnp.where(chosen, scores, NEG_INF), axis=1, keepdims=True))
        l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_next) + jnp.sum(
            jnp.where(chosen, jnp.exp(held - m_next), 0.0), axis=1,
            keepdims=True)
        m_ref[...] = m_next

    @pl.when(kj == last)
    def _():
        log_z = m_ref[...] + jnp.log(l_ref[...])
        rows = first + qi * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        log_z_ref[...] = log_z
        loss_ref[...] = jnp.where(
            rows < real, a_ref[...] + c_ref[...] * log_z, 0.0)


def _mean_probabilities(q, k, lse, scores, thr, cut, first, real, interpret):
    """``L_I`` of a panel's rows, before the mean: (each row's term ``[rows,
    1]`` float32, zero past the ``real`` queries; each row's ``log_z``).
    ``q`` ``[hq, t, d]`` (scaled), ``k`` ``[hkv, t, d]``, the panel's ``lse``
    ``[rows, hq]``, ``scores`` ``[rows, keys]``, ``thr``, ``cut`` ``[rows,
    1]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hq, _, d = q.shape
    hkv = k.shape[0]
    rows, keys = scores.shape
    b = _tile_of(rows)
    key_tile = lambda qi, kj: jnp.minimum(  # noqa: E731
        kj, _last_tile(first, qi, b, b))
    of_rows = lambda qi, kj: (qi, 0)  # noqa: E731
    column = jax.ShapeDtypeStruct((rows, 1), jnp.float32)
    return pl.pallas_call(
        partial(_mean_probabilities_kernel, first=first, groups=hq // hkv,
                real=real),
        grid=(rows // b, keys // b),
        in_specs=[
            pl.BlockSpec((hq, b, d), lambda qi, kj: (0, first // b + qi, 0)),
            pl.BlockSpec((hkv, b, d),
                         lambda qi, kj: (0, key_tile(qi, kj), 0)),
            pl.BlockSpec((b, hq), of_rows),
            pl.BlockSpec((b, b), lambda qi, kj: (qi, key_tile(qi, kj))),
            pl.BlockSpec((b, 1), of_rows), pl.BlockSpec((b, 1), of_rows)],
        out_specs=[pl.BlockSpec((b, 1), of_rows),
                   pl.BlockSpec((b, 1), of_rows)],
        out_shape=[column, column],
        scratch_shapes=[pltpu.VMEM((b, 1), jnp.float32)] * 4,
        name="tm_attn_sparse_mean_probabilities",
        **_params(interpret, "parallel", "arbitrary"),
    )(q, k, lse, scores, thr, cut)


def _attend_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                       scores_ref, thr_ref, cut_ref, log_z_ref, dq_ref,
                       dk_ref, dv_ref, g_ref, dq_acc, sum_ref, *, first,
                       groups, real, scale):
    """Backward of ``_attend_kernel`` for every head at once, a tile of
    queries against the tiles of keys up to its diagonal: the tile's
    probabilities are made again keys-major (S and dP transposed, as jax's
    fused backward kernel makes them, so that dV and dK are plain products
    and only dQ wants a transpose), dQ gathers over the keys' tiles in
    VMEM, dK and dV leave as this tile of queries' part. The heads'
    probabilities are also summed: what leaves with them is ``dL_I / dI``
    of the tile before the loss's own factor, ``softmax_S(I) - p`` on the
    selection."""
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(0), pl.program_id(1)
    heads, bq, _ = q_ref.shape
    hkv, bk, _ = k_ref.shape
    last = _last_tile(first, qi, bq, bk)

    @pl.when(kj == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
    dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

    @pl.when(kj <= last)
    def _():
        scores, chosen = _chosen_tile(scores_ref, thr_ref, cut_ref, kj)
        off = jnp.where(chosen, 0.0, NEG_INF).T
        sum_ref[...] = jnp.zeros(sum_ref.shape, jnp.float32)
        for n in range(hkv):
            k, v = k_ref[n], v_ref[n]

            def head(j, _):
                h = n * groups + j
                q, do = q_ref[h], do_ref[h]
                p = jnp.exp(_nt(k, q) + off - lse_ref[h])
                ds = p * (_nt(v, do) - di_ref[h])
                sum_ref[...] += p
                dv_ref[n] += jnp.dot(
                    p.astype(do.dtype), do,
                    preferred_element_type=jnp.float32)
                dk_ref[n] += jnp.dot(
                    ds.astype(q.dtype), q,
                    preferred_element_type=jnp.float32)
                dq_acc[h] += jnp.dot(
                    ds.T.astype(k.dtype), k,
                    preferred_element_type=jnp.float32)
                return 0

            lax.fori_loop(0, groups, head, 0)
        rows = first + qi * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        g_ref[...] = jnp.where(
            chosen & (rows < real),
            jnp.exp(scores - log_z_ref[...])
            - sum_ref[...].T * (1.0 / heads), 0.0)

    @pl.when(kj == last)
    def _():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _attend_bwd(q, k, v, do, lse, di, scores, thr, cut, log_z, first, real,
                scale, interpret):
    """Pull ``do`` back through a panel's attention: ``q``, ``do`` ``[hq, t,
    d]``, ``k``, ``v`` ``[hkv, t, d]``, ``lse`` and ``di = sum(out * do)``
    ``[hq, 1, t]``; the panel's ``scores`` and ``thr``, ``cut``, ``log_z``
    ``[rows, 1]`` -> (dq ``[hq, rows, d]``, dk and dv ``[hkv, keys, d]``
    float32, and ``dL_I / dI`` ``[rows, keys]`` before the loss's own
    factor; its tiles past a tile of queries' diagonal are not written,
    and ``_index_grads`` does not read them)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hq, _, d = q.shape
    hkv = k.shape[0]
    rows, keys = scores.shape
    bq = bk = _tile_of(rows)
    key_tile = lambda qi, kj: jnp.minimum(  # noqa: E731
        kj, _last_tile(first, qi, bq, bk))
    of_rows = lambda qi, kj: (qi, 0)  # noqa: E731
    own_rows = lambda qi, kj: (0, first // bq + qi, 0)  # noqa: E731
    own_lanes = lambda qi, kj: (0, 0, first // bq + qi)  # noqa: E731
    own_keys = lambda qi, kj: (0, key_tile(qi, kj), 0)  # noqa: E731
    own_tile = lambda qi, kj: (qi, key_tile(qi, kj))  # noqa: E731
    parts = jax.ShapeDtypeStruct((rows // bq, hkv, keys, d), jnp.float32)
    dq, dk, dv, g = pl.pallas_call(
        partial(_attend_bwd_kernel, first=first, groups=hq // hkv,
                real=real, scale=scale),
        grid=(rows // bq, keys // bk),
        in_specs=[
            pl.BlockSpec((hq, bq, d), own_rows),
            pl.BlockSpec((hkv, bk, d), own_keys),
            pl.BlockSpec((hkv, bk, d), own_keys),
            pl.BlockSpec((hq, bq, d), own_rows),
            pl.BlockSpec((hq, 1, bq), own_lanes),
            pl.BlockSpec((hq, 1, bq), own_lanes),
            pl.BlockSpec((bq, bk), own_tile),
            pl.BlockSpec((bq, 1), of_rows), pl.BlockSpec((bq, 1), of_rows),
            pl.BlockSpec((bq, 1), of_rows)],
        out_specs=[
            pl.BlockSpec((hq, bq, d), lambda qi, kj: (0, qi, 0)),
            pl.BlockSpec((None, hkv, bk, d), lambda qi, kj: (qi, 0, kj, 0)),
            pl.BlockSpec((None, hkv, bk, d), lambda qi, kj: (qi, 0, kj, 0)),
            pl.BlockSpec((bq, bk), own_tile)],
        out_shape=[
            jax.ShapeDtypeStruct((hq, rows, d), q.dtype), parts, parts,
            jax.ShapeDtypeStruct((rows, keys), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hq, bq, d), jnp.float32),
                        pltpu.VMEM((bk, bq), jnp.float32)],
        name="tm_attn_sparse_bwd",
        **_params(interpret, "parallel", "arbitrary"),
    )(q, k, v, do, lse, di, scores, thr, cut, log_z)
    return dq, jnp.sum(dk, axis=0), jnp.sum(dv, axis=0), g


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


def _head_major(x):
    return jnp.moveaxis(x, 1, 0)


def _panels(t: int):
    """(first query, one past the last) of each panel: a panel's queries
    see no key past its own end."""
    size = _panel_of(t)
    return [(first, first + size) for first in range(0, t, size)]


def _panel_scores(iq, ik, iw, first, end, interpret):
    """A panel's index scores ``[rows, keys]``; ``iq`` head-major."""
    iq, ik, iw = iq[:, first:end], ik[:end], iw[first:end]
    with jax.named_scope(_names.SCOPE_ATTN_INDEX):
        return _index_scores(iq, ik, iw, first, interpret)


def _kernels_forward(q, k, v, iq, ik, iw, top_k, real, interpret):
    t, hq, d = q.shape
    hkv = k.shape[1]
    qs = (q * (1.0 / math.sqrt(d))).astype(q.dtype)
    qh, kh, vh, iqh = (_head_major(a) for a in (qs, k, v, iq))
    grouped = qh.reshape(hkv, hq // hkv, t, d)
    outs, small, panels, loss, pairs = [], [], [], 0.0, 0.0
    for first, end in _panels(t):
        rows = first + jnp.arange(end - first)[:, None]
        scores = _panel_scores(iqh, ik, iw, first, end, interpret)
        with jax.named_scope(_names.SCOPE_ATTN_SELECT):
            thr = _select(scores, first, top_k, interpret)
            cut, selected = _cut_of_ties(
                scores, thr, jnp.minimum(rows + 1, int(top_k)))
        with jax.named_scope(_names.SCOPE_ATTN_SPARSE):
            out, lse = _attend(
                grouped, kh, vh, scores, thr, cut, first, interpret)
            terms, log_z = _mean_probabilities(
                qh, kh, lse, scores, thr, cut, first, real, interpret)
            loss = loss + jnp.sum(terms)
            pairs = pairs + jnp.sum(jnp.where(rows < real, selected, 0))
        outs.append(out.reshape(hq, end - first, d))
        small.append((lse, thr, cut, log_z))
        panels.append(scores)
    out = jnp.moveaxis(jnp.concatenate(outs, axis=1), 0, 1)
    return (out, loss / real, jnp.asarray(pairs, jnp.float32),
            tuple(jnp.concatenate(a) for a in zip(*small)), tuple(panels))


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _kernels(q, k, v, iq, ik, iw, top_k, real, interpret):
    return _kernels_forward(q, k, v, iq, ik, iw, top_k, real, interpret)[:3]


def _kernels_fwd(q, k, v, iq, ik, iw, top_k, real, interpret):
    from jax.ad_checkpoint import checkpoint_name

    out, loss, pairs, small, panels = _kernels_forward(
        q, k, v, iq, ik, iw, top_k, real, interpret)
    # named, so that a caller that recomputes its layer in backward can keep
    # these (a policy of ``save_only_these_names(SAVED)``) and has the index
    # scores, the k-th largest and both forward kernels run once a step;
    # backward masks with the bits forward selected from
    out, *small = (checkpoint_name(a, SAVED) for a in (out, *small))
    panels = tuple(checkpoint_name(a, SAVED) for a in panels)
    return (out, loss, pairs), (q, k, v, iq, ik, iw, out, *small, panels)


def _kernels_bwd(top_k, real, interpret, saved, cot):
    q, k, v, iq, ik, iw, out, lse, thr, cut, log_z, panels = saved
    dout, dloss, _ = cot
    t, hq, d = q.shape
    f32 = jnp.float32
    scale = 1.0 / math.sqrt(d)
    dout = dout.astype(out.dtype)
    qh, kh, vh, iqh, doh = (_head_major(a) for a in (
        (q * scale).astype(q.dtype), k, v, iq, dout))

    with jax.named_scope(_names.SCOPE_ATTN_SPARSE):
        # [t, hq] -> [hq, 1, t]: a head's row along the lanes
        lse, di = (a.T[:, None, :] for a in (lse, jnp.sum(
            out.astype(f32) * dout.astype(f32), axis=-1)))
    dqs, diqs, diws = [], [], []
    dk, dv = jnp.zeros(kh.shape, f32), jnp.zeros(vh.shape, f32)
    dik = jnp.zeros(ik.shape, f32)

    def ahead(a, end, axis):
        """A panel's gradient of the keys up to ``end``, among all ``t``."""
        return jnp.pad(a, [(0, t - end if i == axis else 0)
                           for i in range(a.ndim)])

    for (first, end), scores in zip(_panels(t), panels):
        rows = slice(first, end)
        with jax.named_scope(_names.SCOPE_ATTN_SPARSE):
            dq, dkp, dvp, pulled = _attend_bwd(
                qh, kh, vh, doh, lse, di, scores, thr[rows],
                cut[rows], log_z[rows], first, real, scale, interpret)
            dqs.append(dq)
            dk, dv = dk + ahead(dkp, end, 1), dv + ahead(dvp, end, 1)
            diq, dikp, diw = _index_grads(
                pulled, iqh[:, rows], ik[:end], iw[rows], first, interpret)
            dik = dik + ahead(dikp, end, 0)
            diqs.append(diq)
            diws.append(diw)
    dq = jnp.moveaxis(jnp.concatenate(dqs, axis=1), 0, 1)
    of_loss = dloss / real  # the index gradients are linear in ``pulled``
    return (dq.astype(q.dtype), jnp.moveaxis(dk, 0, 1).astype(k.dtype),
            jnp.moveaxis(dv, 0, 1).astype(v.dtype),
            (_head_major(jnp.concatenate(diqs, axis=1)) * of_loss).astype(
                iq.dtype),
            (dik * of_loss).astype(ik.dtype),
            (jnp.concatenate(diws) * of_loss).astype(iw.dtype))


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
    ``blocked_self_attention`` does.

    What the kernels' forward pass keeps for backward bears the
    ``checkpoint_name`` ``SAVED``, for a caller's ``jax.checkpoint`` policy:
    the output, the log-sum-exps and each row's threshold, tie cut and
    ``log_z`` (130 MiB a sequence of 16,384 with 32 heads of 128), and the
    float32 index scores, a panel's ``[PANEL, keys up to its end]`` each
    (640 MiB at 16,384 positions, 2.25 GiB at 32,768). A caller that
    recomputes nothing holds them as any residual; one that does not name
    ``SAVED`` recomputes the whole layer."""
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
        # a sequence at a time, unrolled (under ``lax.map`` the interpreted
        # kernels gave wrong index gradients once)
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
