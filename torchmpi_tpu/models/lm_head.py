"""The vocabulary head of every language model here together with its loss,
a block of token rows at a time, with a derivative rule of its own.

``lm_cross_entropy(scale * (x @ W + b), targets)`` written plainly makes
the ``[rows, V]`` float32 logits as one array, and jax's transpose makes
their gradient as another: 1.99 GiB each in a step of ``falcon-h1-34b``,
crossing HBM five times, and inside the step XLA fused the weight gradient
into AdamW's update and the input gradient into the last norm's and ran
both at half the rate the same products have alone (``PERF.md`` section 6,
PR 43). The mean's cotangent is one number, so nothing backward needs is
unknown while forward runs: ``head_loss`` walks the rows by blocks and,
while a block's logits exist, makes the loss's share, ``d = softmax -
onehot`` and from it ``dx`` and ``dW`` (``db``). Its residuals are those
gradients; backward multiplies them by the cotangent. Three products a
step, as before; the logits and ``d`` live a block at a time.
"""

from __future__ import annotations

import functools

import flax.linen as fnn
import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry as _telemetry
from ..telemetry import names as _names

# the most rows of a block, and the most bytes its float32 logits take
# (``block_rows``)
BLOCK_ROWS = 8192
LOGITS_BLOCK_BYTES = 2**30


def block_rows(rows: int, vocab: int) -> int:
    """The rows of one block, from the static shapes alone: ``BLOCK_ROWS``,
    or the largest power of two under it whose ``[block, vocab]`` float32
    logits stay within ``LOGITS_BLOCK_BYTES``; all the rows where they fit
    one block (one visit, no loop).

    On the chip, the head alone (``scripts/lm_head_probe.py``; ``PERF.md``
    section 6, PR 43, the probe's table): blocks of 8,192 rows were the
    fastest or within 0.8 ms of it at every width and vocabulary of the
    benchmark's cells but GPT-2's (whose 8,192 rows would be one visit: the
    byte limit gives it 4,096, 1.9 ms slower and half the memory);
    smaller blocks read and write the ``[D, V]`` float32 weight gradient
    more often (2 x 668 MB a block in ``falcon-h1-34b``), one visit of
    16,384 rows was 1.5 to 2 ms SLOWER than two of 8,192 at the three
    narrow widths, and the byte limit keeps a vocabulary of 128 Ki ids at
    2,048 rows."""
    block = max(min(LOGITS_BLOCK_BYTES // (4 * vocab), BLOCK_ROWS), 8)
    block = 1 << (block.bit_length() - 1)
    return rows if rows <= block else block


def _logits(x, kernel, bias, scale):
    """``scale * (x @ W + b)`` in float32, as ``fnn.Dense(dtype=float32)``
    makes it: float32 operands at the default precision."""
    with jax.named_scope(_names.SCOPE_LM_HEAD):
        logits = lax.dot_general(
            x.astype(jnp.float32), kernel.astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        return logits if scale == 1.0 else scale * logits


def _row_losses(logits, targets):
    """(the rows' ``-log p`` of their targets, the rows' log-sum-exp): the
    float32 log-softmax as ``jax.nn.log_softmax`` spells it, but for the
    ``[block, V]`` array of ``log p``: a row's is ``logits - lse``, made
    where it is read, so that the logits are the one array of that shape
    a visit writes."""
    with jax.named_scope(_names.SCOPE_LM_LOSS):
        top = jnp.max(logits, axis=-1)
        lse = top + jnp.log(
            jnp.sum(jnp.exp(logits - top[:, None]), axis=-1))
        picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return lse - picked, lse


def _visit(x, kernel, bias, scale, targets, weight):
    """One block with its gradients: ``(sum of -log p, dx, dW, db)``;
    ``weight`` ``[block]`` is ``1 / rows`` of the whole walk for a row that
    counts and 0 for one of the padding."""
    logits = _logits(x, kernel, bias, scale)
    nll, lse = _row_losses(logits, targets)
    with jax.named_scope(_names.SCOPE_LM_LOSS):
        ids = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        d = (jnp.exp(logits - lse[:, None]) - (ids == targets[:, None])) * (
            scale * weight)[:, None]
        loss = jnp.sum(nll * weight)
    with jax.named_scope(_names.SCOPE_LM_HEAD):
        dx = lax.dot_general(
            d, kernel.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dw = lax.dot_general(
            x.astype(jnp.float32), d, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        db = None if bias is None else jnp.sum(d, axis=0)
    return loss, dx.astype(x.dtype), dw, db


def _walk(visit, start, x, targets, block):
    """``lax.scan`` of ``visit(carry, (x, targets, weight)) -> (carry, a
    block's rows or None)`` over the blocks of ``x`` ``[rows, D]`` and
    ``targets`` ``[rows]``: ``(carry, the rows' results)``. ``weight`` is a
    row's share of the mean: ``1 / rows``, and 0 for the rows that pad the
    last block. Where the rows fit one block, one call and no loop."""
    rows = x.shape[0]
    weight = jnp.full((rows,), 1.0 / rows, jnp.float32)
    if rows <= block:
        return visit(start, (x, targets, weight))
    blocks = -(-rows // block)
    pad = blocks * block - rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        targets, weight = jnp.pad(targets, (0, pad)), jnp.pad(weight, (0, pad))
    with jax.named_scope(_names.SCOPE_LM_HEAD):
        carry, out = lax.scan(visit, start, (
            x.reshape(blocks, block, -1), targets.reshape(blocks, block),
            weight.reshape(blocks, block)))
        return carry, out if out is None else out.reshape(
            blocks * block, -1)[:rows]


def _loss_alone(x, kernel, bias, scale, targets, block):
    def visit(loss, blk):
        x, targets, weight = blk
        nll, _ = _row_losses(_logits(x, kernel, bias, scale), targets)
        with jax.named_scope(_names.SCOPE_LM_LOSS):
            return loss + jnp.sum(nll * weight), None

    return _walk(visit, jnp.zeros((), jnp.float32), x, targets, block)[0]


def _loss_and_grads(x, kernel, bias, scale, targets, block):
    """``(loss, (dx, dW, db))``, the gradients of the loss itself (a
    cotangent of 1) in the dtypes of ``x``, ``kernel`` and ``bias``; ``dW``
    and ``db`` summed over the blocks in float32."""
    def visit(sums, blk):
        loss, dx, dw, db = _visit(blk[0], kernel, bias, scale, *blk[1:])
        with jax.named_scope(_names.SCOPE_LM_HEAD):
            return jax.tree_util.tree_map(jnp.add, sums, (loss, dw, db)), dx

    # ``bias`` may be None: an empty subtree, here and below
    dw, db = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), (kernel, bias))
    (loss, dw, db), dx = _walk(
        visit, (jnp.zeros((), jnp.float32), dw, db), x, targets, block)
    return loss, (dx, *jax.tree_util.tree_map(
        lambda g, a: g.astype(a.dtype), (dw, db), (kernel, bias)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 5))
def blocked_head_loss(x, kernel, bias, scale, targets, block):
    """Mean over the ``rows`` of ``-log softmax(scale * (x @ kernel +
    bias))[target]``: ``x`` ``[rows, D]``, ``kernel`` ``[D, V]``, ``bias``
    ``[V]`` or None, ``targets`` ``[rows]`` integers, ``scale`` a Python
    number; ``block`` rows at a time. Differentiated, forward makes the
    gradients with the loss and backward multiplies them by the cotangent;
    called plainly, the walk makes the loss alone."""
    return _loss_alone(x, kernel, bias, scale, targets, block)


def _head_bwd(scale, block, grads, g):
    with jax.named_scope(_names.SCOPE_LM_HEAD):
        return (*jax.tree_util.tree_map(
            lambda a: (g * a).astype(a.dtype), grads), None)


blocked_head_loss.defvjp(_loss_and_grads, _head_bwd)


def head_loss(x, kernel, bias, scale, targets):
    """``lm_cross_entropy(scale * (x @ kernel + bias), targets)`` for ``x``
    ``[..., D]`` and ``targets`` ``[...]``, through ``blocked_head_loss``
    with the block that ``block_rows`` reads off the shapes. The gradients
    come in the parameters' dtypes."""
    rows, vocab = targets.size, kernel.shape[1]
    block = block_rows(rows, vocab)
    _telemetry.metrics.gauge(
        _names.GAUGE_LM_HEAD_BLOCKED_ROWS,
        "token rows of the step most recently traced whose loss came from "
        "the vocabulary head's own rule, a block of rows at a time"
    ).set(rows)
    _telemetry.metrics.gauge(
        _names.GAUGE_LM_HEAD_BLOCKS,
        "blocks of rows the vocabulary head's rule walks in the step most "
        "recently traced (1: one visit, no loop)"
    ).set(-(-rows // block))
    return blocked_head_loss(
        x.reshape(rows, x.shape[-1]), kernel, bias, scale,
        targets.reshape(rows), block)


class VocabHead(fnn.Dense):
    """``fnn.Dense`` (the same parameters ``kernel`` and ``bias``, the same
    initializers) times ``scale``: the logits to whoever asks for logits;
    with ``targets``, the mean next-token loss through ``head_loss`` and no
    ``[rows, V]`` array. The one spelling of every model's head.

    With ``tied``, a ``[V, D]`` table (the model's token embedding), the
    head has no parameter of its own: its matrix is the table's transpose,
    so the table is ONE leaf that receives the head's blocked ``dW`` (made
    ``[D, V]``, transposed by jax's rule of the transpose) and the lookup's
    gradient (``models/embedding.py``) summed."""

    scale: float = 1.0

    @fnn.compact
    def __call__(self, x, targets=None, tied=None):
        if tied is None and targets is None:
            with jax.named_scope(_names.SCOPE_LM_HEAD):
                return self.scale * super().__call__(x)
        if tied is not None:
            if self.use_bias or tied.shape != (self.features, x.shape[-1]):
                raise ValueError(
                    f"a tied head has no bias and a table of [{self.features}"
                    f", {x.shape[-1]}]; got use_bias={self.use_bias} and "
                    f"{tied.shape}")
            with jax.named_scope(_names.SCOPE_LM_HEAD):
                kernel, bias = tied.T, None
        else:
            kernel = self.param(
                "kernel", self.kernel_init, (x.shape[-1], self.features),
                self.param_dtype)
            bias = self.param(
                "bias", self.bias_init, (self.features,), self.param_dtype
            ) if self.use_bias else None
        if targets is None:
            return _logits(
                x.reshape(-1, x.shape[-1]), kernel, bias, self.scale
            ).reshape(x.shape[:-1] + (self.features,))
        return head_loss(x, kernel, bias, self.scale, targets)
