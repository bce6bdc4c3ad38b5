"""Selected attention's hand-written kernels, the TPU's execution
interpreted on the CPU, against the loops of
``tests/test_selected_attention.py``: the bisection for the k-th largest
score, and outputs, ``L_I``, pairs and all six gradients on sequences of up
to two panels of queries. (Three panels take the interpreter minutes:
``tests/test_selected_attention_panels.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from selected_attention_cases import (
    index_scores,
    inputs,
    interpreted,
    kernels_are_the_loops,
    weighed,
)
from torchmpi_tpu.parallel import (
    blocked_self_attention,
    selected_attention as sa,
    selected_self_attention,
)


@pytest.mark.parametrize("t,top_k,period", [(256, 40, 0), (384, 500, 0),
                                            (384, 90, 6)])
def test_the_bisection_kernel_finds_the_kth_largest_exactly(
        t, top_k, period):
    _, _, _, iq, ik, iw = inputs(3, 1, t, 2, 1, 8, 3, 64)
    if period:  # tied scores, and rows of exact zeros
        ik, iw = ik[:, jnp.arange(t) % period], iw.at[:, ::5].set(0.0)
    scores = sa._index_scores(
        jnp.moveaxis(iq[0], 1, 0), ik[0], iw[0], 0, True)
    want_scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)),
                            index_scores(iq[0], ik[0], iw[0]), -jnp.inf)
    np.testing.assert_allclose(scores, want_scores, rtol=1e-5, atol=1e-6)
    thr = sa._select(scores, 0, top_k, True)
    k = min(top_k, t)
    want = jnp.take_along_axis(
        jax.lax.top_k(scores, k)[0],
        jnp.minimum(jnp.arange(t), k - 1)[:, None], axis=-1)
    np.testing.assert_array_equal(thr, want)


def test_the_kernels_are_the_loops_mathematics():
    """The TPU's execution, interpreted on the CPU, against the loops: two
    panels of queries, a padded tail, ``top_k`` under and over a panel."""
    args = inputs(3, 1, 1100, 2, 1, 128, 2, 64)
    for top_k in (70,):
        kernels = lambda *a: sa._one_sequence(  # noqa: E731
            lambda *p: sa._kernels(*p[:-1], top_k, p[-1], True), 1024,
            [x[0] for x in a])
        loops = lambda *a: selected_self_attention(  # noqa: E731
            *a, top_k=top_k, block=512)
        got, want = kernels(*args), loops(*args)
        np.testing.assert_allclose(got[0], want[0][0], atol=2e-5)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-5)
        assert float(got[2]) == float(want[2])
        g_got = jax.grad(weighed(kernels), argnums=range(6))(*args)
        g_want = jax.grad(weighed(loops), argnums=range(6))(*args)
        for g, w in zip(g_got, g_want):
            np.testing.assert_allclose(
                g, w, atol=2e-5 * float(jnp.max(jnp.abs(w))))


@pytest.mark.parametrize("t,hq,hkv,top_k,period", [
    (512, 1, 1, 70, 0),       # one tile; one KV head, a group of one
    (300, 4, 4, 40, 0),       # shorter than a tile; 4 KV heads, groups of 1
    (1100, 8, 1, 70, 0),      # a padded tail; a group of 8; top_k < a tile
    (1100, 8, 1, 700, 7),     # ties at the threshold across a tile's edge
], ids=["one_tile", "short", "padded_group8", "ties_tile"])
def test_the_kernels_are_the_loops_on_what_a_kernel_can_get_wrong(
        t, hq, hkv, top_k, period):
    kernels_are_the_loops(t, hq, hkv, top_k, period)


@pytest.mark.parametrize("t,top_k", [(700, 700), (1100, 5000)])
def test_the_kernels_selecting_every_key_are_blocked_attention(t, top_k):
    """``top_k >= t`` through the kernels: the output and ``dq``, ``dk``,
    ``dv`` are ``blocked_self_attention``'s over the causal prefix."""
    args = inputs(5, 1, t, 4, 2, 128, 2, 64)
    weight = jnp.sin(jnp.arange(args[0].size).reshape(args[0].shape))
    through = lambda f: jax.value_and_grad(  # noqa: E731
        lambda q, k, v: jnp.sum(f(q, k, v) * weight),
        argnums=(0, 1, 2))(*args[:3])
    got = through(lambda q, k, v: interpreted(
        top_k, q, k, v, *args[3:])[0][None])
    want = through(lambda q, k, v: blocked_self_attention(
        q, k, v, block=256))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, atol=2e-5)
