"""The token lookup of every language model here, with a derivative rule of
its own.

Forward is ``fnn.Embed``'s, bit for bit: the table cast to ``dtype``, its
rows taken. jax's transpose of that gather is a scatter-add of one row a
token into the ``[V, D]`` table, and on the chip XLA's takes 61 ms where
the table is ``f32[32640, 5120]`` (a step of ``falcon-h1-34b``; the traffic
needs 1.3), whatever the ids are and however few: its time follows the
table's rows and turns on their width (``takes_sorted_sum``; ``PERF.md``
section 6, PR 42). A step's ids repeat heavily (Zipf: a third of them
distinct, the most frequent a tenth of the step), so backward here sums the
gradient rows of equal ids BEFORE anything touches the table, and brings
each distinct id's sum to the table once (``sorted_embedding_grad``). No
scatter-add of rows.
"""

from __future__ import annotations

import functools

import flax.linen as fnn
import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry as _telemetry
from ..telemetry import names as _names

# the widest row of whole KiB of elements at which XLA's scatter-add is on
# its fast side (``takes_sorted_sum``)
SCATTER_ADD_WIDTHS_TO = 4096

# rows of one product: a block of ``BLOCK`` sorted rows holds at most
# ``BLOCK`` distinct ids, whatever they are, so its sums are one
# ``[BLOCK ranks, BLOCK rows]`` one-hot product with its rows
BLOCK = 256


def _by_id(g, ids):
    """The rows in their ids' order (one stable sort of ``T`` integers, one
    gather of the rows), so that equal ids lie together: ``(rows, sorted
    ids, whether a row is its id's first, rank)``, ``rank`` each row's
    id's place among the step's distinct ids."""
    order = jnp.argsort(ids, stable=True).astype(jnp.int32)
    sorted_ids = ids[order]
    first = jnp.pad(
        sorted_ids[1:] != sorted_ids[:-1], (1, 0), constant_values=True)
    return g[order], sorted_ids, first, jnp.cumsum(first, dtype=jnp.int32) - 1


def _sums_by_rank(rows, rank, block):
    """``[>= T, D]`` float32: row ``r`` the sum of the ``rows`` of rank
    ``r`` (zeros past the last rank). A block of ``block`` sorted rows holds
    ranks in ``[r0, r0 + block)``, so its sums by rank are one one-hot
    product on the MXU (the one-hot side is exact; the rows keep float32:
    precision highest, or bfloat16 rows as they are). A run of equal ids
    that crosses blocks shows in each of their windows: the blocks'
    ``[block, D]`` products are added in at ``r0`` one after another, each
    made where it is added (made for all blocks at once and kept, the
    products cross HBM once more: 0.3 to 1.4 ms at the decoders' shapes on
    the chip, ``PERF.md`` section 6, PR 42)."""
    T, D = rows.shape
    blocks = -(-T // block)
    pad = blocks * block - T
    # the padding's rows are zero and bear the last rank: they add nothing
    rows = jnp.pad(rows, ((0, pad), (0, 0))).reshape(blocks, block, D)
    rank = jnp.pad(rank, (0, pad), mode="edge").reshape(blocks, block)
    ranks = jnp.arange(block, dtype=jnp.int32)[:, None]
    precision = lax.Precision.HIGHEST if rows.dtype.itemsize > 2 else None

    def add_block(b, sums):
        r0 = rank[b, 0]
        one_hot = (rank[b][None, :] - r0 == ranks).astype(rows.dtype)
        at = (r0, 0)
        return lax.dynamic_update_slice(
            sums, lax.dynamic_slice(sums, at, (block, D)) + lax.dot(
                one_hot, rows[b], precision=precision,
                preferred_element_type=jnp.float32), at)

    return lax.fori_loop(
        0, blocks, add_block,
        jnp.zeros((blocks * block + block, D), jnp.float32))


def _to_table(sums, sorted_ids, first, rank, vocab):
    """``[vocab, D]``: every table row reads its rank's sum, if its id came
    at all. ``T`` integers are scattered to say where, not ``T`` rows; the
    table is one gather of ``vocab`` rows."""
    rank_of = jnp.full((vocab,), -1, jnp.int32).at[
        jnp.where(first, sorted_ids, vocab)
    ].set(rank, mode="drop", unique_indices=True)
    return jnp.where(
        (rank_of >= 0)[:, None], sums[jnp.maximum(rank_of, 0)], 0)


def sorted_embedding_grad(g, ids, vocab, block=BLOCK):
    """``jnp.zeros((vocab, D), float32).at[ids].add(g)`` with every sum in
    float32 and no scatter of rows: ``g`` is ``[T, D]``, ``ids`` ``[T]``
    int32 in ``[0, vocab)``. The rows sorted by id (``_by_id``), the runs
    of equal ids summed by blocks on the MXU (``_sums_by_rank``), each
    distinct id's sum brought to the table once (``_to_table``)."""
    rows, sorted_ids, first, rank = _by_id(g, ids)
    return _to_table(
        _sums_by_rank(rows, rank, block), sorted_ids, first, rank, vocab)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def embedding_lookup(table, tokens, dtype):
    """``table.astype(dtype)[tokens]``; backward is
    ``sorted_embedding_grad`` in the table's dtype."""
    return jnp.take(table.astype(dtype), tokens, axis=0)


def _lookup_fwd(table, tokens, dtype):
    # the table is a parameter, alive anyway: kept for its shape and dtype
    return embedding_lookup(table, tokens, dtype), (table, tokens)


def _lookup_bwd(dtype, kept, g):
    table, tokens = kept
    vocab = table.shape[0]
    # as ``jnp.take`` reads them: a negative id counts from the table's end
    ids = jnp.where(tokens < 0, tokens + vocab, tokens).reshape(-1)
    grad = sorted_embedding_grad(g.reshape(-1, g.shape[-1]), ids, vocab)
    return grad.astype(table.dtype), None


embedding_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def takes_sorted_sum(features: int, itemsize: int) -> bool:
    """Whether a lookup whose rows are gathered ``features`` wide at
    ``itemsize`` bytes an element sums its gradient by sorted ids, or keeps
    jax's transpose, XLA's scatter-add: by the static shapes alone.

    On the chip XLA's scatter-add walks the TABLE (its time follows
    ``vocab``, hardly the tokens: 9.1 ms for 8,192 rows, 9.8 for 16,384)
    at a cost a table row that turns on the row's width in steps nobody has
    explained: bfloat16 rows of 1,024, 2,048, 3,072 and 4,096 are its fast
    side (0.04 to 0.3 us a table row), 2,176 to 2,432 and 3,584 between,
    2,560 (in either dtype) and float32 rows of 5,120 its slow side (0.5
    and 2 us): ``PERF.md`` section 6, PR 42, the probe's second table. The
    sorted sum costs what its traffic and its loop cost at any width, so it
    is the rule, and jax's transpose is kept only where it was MEASURED the
    faster or level: bfloat16 rows of whole KiB of elements up to 4,096
    (level to 1.8 ms faster; ``laguna-s-2-1``, ``keye-vl-2-30b-a3b`` and
    GPT-2 among the benchmark's: in their steps the sorted sum read 0.8 to
    1.0 ms slower, but for GPT-2, 0.2 faster). A width it does not name
    loses at most that under the sorted sum, and is spared the slow side's
    6 to 50 ms."""
    return not (itemsize == 2 and features % 1024 == 0
                and features <= SCATTER_ADD_WIDTHS_TO)


class TokenEmbed(fnn.Embed):
    """``fnn.Embed`` (the same parameter ``embedding``, the same
    initializer, the same forward bits) whose gradient sums equal ids
    before it touches the table, where ``takes_sorted_sum`` says that this
    is the faster: the shapes alone decide, no flag and no argument."""

    def __call__(self, tokens):
        if not jnp.issubdtype(tokens.dtype, jnp.integer):
            raise ValueError("tokens must be integers")
        dtype = jnp.dtype(self.dtype or self.embedding.dtype)
        sorted_sum = takes_sorted_sum(self.features, dtype.itemsize)
        _telemetry.metrics.gauge(
            _names.GAUGE_EMBED_GRAD_SORTED_ROWS,
            "token rows of the step most recently traced whose embedding "
            "gradient is summed by sorted ids before it touches the table "
            "(0: the shapes keep jax's scatter-add)"
        ).set(tokens.size if sorted_sum else 0)
        if not sorted_sum:
            return super().__call__(tokens)
        return embedding_lookup(self.embedding, tokens, dtype)
