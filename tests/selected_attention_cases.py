"""What the files of selected attention's tests share, collected nowhere by
itself: seeded inputs, the operation written plainly (the index scores, the
selection by ``argsort``, a dense masked softmax a head), the TPU's execution
interpreted on the CPU, and the comparison of the kernels with the loops,
which ``tests/test_selected_attention_kernels.py`` runs within a panel of
queries and ``tests/test_selected_attention_panels.py`` over three.
``tests/conftest.py`` registers this module for assertion rewriting."""

import re
import sys
from collections import Counter
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from torchmpi_tpu.parallel import selected_attention as sa
from torchmpi_tpu.parallel import selected_self_attention

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def inputs(seed, b, t, hq, hkv, d, hi, di):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = jax.random.normal
    return (n(ks[0], (b, t, hq, d)), n(ks[1], (b, t, hkv, d)),
            n(ks[2], (b, t, hkv, d)), n(ks[3], (b, t, hi, di)),
            n(ks[4], (b, t, di)), n(ks[5], (b, t, hi)))


def index_scores(iq, ik, iw):
    s = jnp.einsum("qnd,kd->qnk", iq, ik, precision="highest")
    return jnp.sum(jax.nn.relu(s) * iw[:, :, None], axis=1) \
        * iq.shape[-1] ** -0.5 * iq.shape[1] ** -0.5


def brute_selection(scores, top_k):
    """``[t, t]`` bool by ``argsort``: the ``min(i + 1, top_k)`` largest of
    each row's causal scores, ties to the lower ``j`` (a stable sort of the
    negated scores; a key's rank is its place in that order)."""
    t = scores.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))
    order = jnp.argsort(
        -jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf),
        axis=-1, stable=True)
    return np.asarray(causal & (jnp.argsort(order, axis=-1) < top_k)) \
        if not isinstance(scores, jax.core.Tracer) \
        else causal & (jnp.argsort(order, axis=-1) < top_k)


def dense(q, k, v, iq, ik, iw, top_k):
    """(out, L_I, the selection) by a ``t x t`` masked softmax a head."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    outs, loss, chosen = [], 0.0, []
    for n in range(b):
        scores = index_scores(iq[n], ik[n], iw[n])
        seen = jnp.asarray(brute_selection(scores, top_k))
        s = jnp.einsum("qhgd,khd->hgqk", q[n].reshape(t, hkv, hq // hkv, d),
                       k[n]) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hgqk,khd->qhgd", p, v[n]).reshape(t, hq, d))
        target = jax.lax.stop_gradient(p.mean((0, 1)))
        log_r = jax.nn.log_softmax(jnp.where(seen, scores, -jnp.inf), -1)
        loss = loss + jnp.sum(jnp.where(
            seen, jax.scipy.special.xlogy(target, target)
            - target * jnp.where(seen, log_r, 0.0), 0.0)) / t
        chosen.append(seen)
    return jnp.stack(outs), loss / b, jnp.stack(chosen)


def weighed(fn):
    """A scalar of both outputs, so that every gradient path is used."""
    def total(*args):
        out, loss = fn(*args)[:2]
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(
            out.shape))) + 3.0 * loss
    return total


def interpreted(top_k, *args):
    """The TPU's execution of one sequence, interpreted on the CPU, padded
    as ``selected_self_attention`` pads it."""
    t = args[0].shape[1]
    return sa._one_sequence(
        lambda *p: sa._kernels(*p[:-1], top_k, p[-1], True),
        sa._ring._fused_tile(t), [x[0] for x in args])


def tied(args, period):
    """The same inputs with index keys that repeat with ``period`` and a
    few queries whose every score is exactly zero: a row's scores take
    ``period`` values, so its threshold is tied many times over, on both
    sides of every tile edge."""
    q, k, v, iq, ik, iw = args
    t = ik.shape[1]
    ik = ik[:, jnp.arange(t) % period]
    iw = iw.at[:, ::5].set(0.0)
    return q, k, v, iq, ik, iw


def kernels_are_the_loops(t, hq, hkv, top_k, period):
    """Outputs, ``L_I``, the pairs counted and all six gradients of the
    hand-written kernels against the loops. A key wrongly in or out of one
    row's selection moves that row's output by 1 / top_k of a value, far
    over the tolerance: with tied scores this holds the tie rule as the
    kernels evaluate it (``_chosen`` on a tile in VMEM) to the loops'."""
    args = inputs(t, 1, t, hq, hkv, 128, 2, 64)
    if period:
        args = tied(args, period)
        thr, cut, _ = sa._threshold_rows(
            jnp.where(jnp.tril(jnp.ones((t, t), bool)), index_scores(
                args[3][0], args[4][0], args[5][0]), -jnp.inf),
            jnp.arange(t)[:, None], top_k)
        assert int(jnp.sum(cut < t)) > t // 4  # rows whose ties are cut
    kernels = partial(interpreted, top_k)
    loops = lambda *a: selected_self_attention(  # noqa: E731
        *a, top_k=top_k, block=512)
    got, want = kernels(*args), loops(*args)
    np.testing.assert_allclose(got[0], want[0][0], atol=2e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-5)
    assert float(got[2]) == float(want[2]) == sum(
        min(i + 1, top_k) for i in range(t))
    g_got = jax.grad(weighed(kernels), argnums=range(6))(*args)
    g_want = jax.grad(weighed(loops), argnums=range(6))(*args)
    for g, w in zip(g_got, g_want):
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.max(jnp.abs(w))))


def kernel_calls(lowered_text):
    """How often each of the module's kernels is called in a program's
    text as lowered for a TPU (a ``tpu_custom_call`` bears its name)."""
    return Counter(re.findall(r'kernel_name = "(tm_attn_\w+)"', lowered_text))


def lowered_for_tpu(fn, *args):
    """``fn``'s text as jax hands it to the TPU's compiler: the lowering
    takes the kernels though this process's backend is the CPU."""
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def benchmark_file(*parts):
    from benchmark import configs

    return configs.load_module(ROOT.joinpath("benchmark", *parts))


def index_kernel_name():
    """The name the benchmark's ``attn_index_kernel_ms_per_step`` looks
    for in a device trace (the reader holds it itself)."""
    return benchmark_file(
        "layer_metrics", "attn_index_kernel_ms_per_step.py").KERNEL
