"""Attention over the keys a learned indexer selects
(parallel/selected_attention.py), the operation in its loops of XLA
operations against plain arithmetic: the selection against ``argsort``, the
function against a dense masked softmax and against
``blocked_self_attention``. Tiny sizes that keep what matters: 4 query to 2
KV heads, 3 index heads, a selection far smaller than the sequence. (Its
kernels against these loops: ``tests/test_selected_attention_kernels.py``
and ``tests/test_selected_attention_panels.py``; the decoder that uses it:
``tests/test_selected_decoder.py``.)"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from selected_attention_cases import (
    brute_selection,
    dense,
    index_scores,
    inputs,
    weighed,
)
from torchmpi_tpu.parallel import (
    blocked_self_attention,
    selected_attention as sa,
    selected_self_attention,
)


# -- the selection ----------------------------------------------------------
@pytest.mark.parametrize("t,top_k,rows", [(40, 12, 16), (64, 64, 64),
                                          (50, 1, 50), (96, 200, 32)])
def test_the_threshold_selects_what_argsort_selects(t, top_k, rows):
    """Every query's selected set, from the two numbers a row the function
    keeps (threshold and tie cut), against brute force; ``i < top_k``
    selects all of the causal prefix."""
    _, _, _, iq, ik, iw = inputs(t, 1, t, 2, 1, 8, 3, 8)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)),
                       index_scores(iq[0], ik[0], iw[0]), -jnp.inf)
    got = []
    for start in range(0, t, rows):
        block = scores[start:start + rows]
        at = start + jnp.arange(len(block))[:, None]
        thr, cut, selected = sa._threshold_rows(block, at, top_k)
        chosen = sa._chosen(block, thr, cut, jnp.arange(t)[None, :])
        np.testing.assert_array_equal(
            selected[:, 0], np.minimum(np.asarray(at[:, 0]) + 1, top_k))
        got.append(chosen)
    want = brute_selection(scores, top_k)
    np.testing.assert_array_equal(np.concatenate(got), want)
    early = np.arange(t) < top_k
    np.testing.assert_array_equal(
        want[early], np.tril(np.ones((t, t), bool))[early])


def test_ties_go_to_the_lower_key():
    """Scores that tie at the threshold (a row of equal scores, exact zeros
    where every index head's ReLU is shut): the lower keys are taken."""
    t, top_k = 24, 5
    scores = np.zeros((t, t), np.float32)
    scores[:, ::3] = 1.0          # every third key ties above the rest
    scores[7] = 0.0               # a row that ties throughout
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    at = jnp.arange(t)[:, None]
    thr, cut, selected = sa._threshold_rows(scores, at, top_k)
    chosen = np.asarray(sa._chosen(scores, thr, cut, jnp.arange(t)[None]))
    np.testing.assert_array_equal(chosen, brute_selection(scores, top_k))
    np.testing.assert_array_equal(np.nonzero(chosen[7])[0], np.arange(5))
    np.testing.assert_array_equal(selected[:, 0], np.minimum(at[:, 0] + 1, 5))


# -- the function -----------------------------------------------------------
@pytest.mark.parametrize("b,t,top_k,block", [(2, 40, 12, 16), (1, 33, 7, 8)])
def test_selected_attention_matches_a_dense_masked_softmax(
        b, t, top_k, block):
    """Output, ``L_I``, the pairs counted and all six gradients, with
    grouped heads and a selection that bites (``top_k < t``) and a ``t``
    that is no multiple of the block. float32 on both sides; the blocks
    sum in another order: 1e-5 of the largest value."""
    args = inputs(0, b, t, 4, 2, 16, 3, 8)
    fn = lambda *a: selected_self_attention(  # noqa: E731
        *a, top_k=top_k, block=block)
    out, loss, pairs = fn(*args)
    want_out, want_loss, chosen = dense(*args, top_k)
    np.testing.assert_allclose(out, want_out, atol=1e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    assert float(pairs) == float(chosen.sum()) == b * sum(
        min(i + 1, top_k) for i in range(t))
    got = jax.grad(weighed(fn), argnums=range(6))(*args)
    want = jax.grad(weighed(lambda *a: dense(*a, top_k)),
                    argnums=range(6))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            g, w, atol=1e-5 * float(jnp.max(jnp.abs(w))))


def test_selecting_every_key_is_blocked_attention():
    """``top_k >= t``: the output and ``dq``, ``dk``, ``dv`` are
    ``blocked_self_attention``'s over the causal prefix."""
    args = inputs(1, 2, 48, 4, 2, 16, 3, 8)
    through = lambda f: jax.value_and_grad(  # noqa: E731
        lambda q, k, v: jnp.sum(f(q, k, v) * jnp.sin(
            jnp.arange(args[0].size).reshape(args[0].shape))),
        argnums=(0, 1, 2))(*args[:3])
    for top_k in (48, 2048):
        got = through(lambda q, k, v: selected_self_attention(
            q, k, v, *args[3:], top_k=top_k, block=16)[0])
        want = through(lambda q, k, v: blocked_self_attention(
            q, k, v, block=16))
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for g, w in zip(got[1], want[1]):
            np.testing.assert_allclose(g, w, atol=2e-5)


def test_the_two_gradient_walls_are_exact():
    """The output's gradient is exactly zero on the indexer's three arrays,
    ``L_I``'s exactly zero on ``q``, ``k`` and ``v``."""
    args = inputs(2, 1, 40, 4, 2, 16, 3, 8)
    fn = partial(selected_self_attention, top_k=9, block=16)
    of_out = jax.grad(lambda *a: jnp.sum(fn(*a)[0] ** 2),
                      argnums=range(6))(*args)
    of_loss = jax.grad(lambda *a: fn(*a)[1], argnums=range(6))(*args)
    for g in of_out[3:] + of_loss[:3]:
        assert not np.any(np.asarray(g))
    for g in of_out[:3] + of_loss[3:]:
        assert np.any(np.asarray(g))


def test_selected_attention_rejects_bad_shapes():
    q, k, v, iq, ik, iw = inputs(0, 1, 16, 4, 2, 8, 3, 8)
    with pytest.raises(ValueError, match="multiple of the KV heads"):
        selected_self_attention(q[:, :, :3], k, v, iq, ik, iw, top_k=4)
    with pytest.raises(ValueError, match="indexer"):
        selected_self_attention(q, k, v, iq, ik[:, :8], iw, top_k=4)
    with pytest.raises(ValueError, match="top_k"):
        selected_self_attention(q, k, v, iq, ik, iw, top_k=0)
