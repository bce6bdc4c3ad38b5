"""Attention over the keys a learned indexer selects
(parallel/selected_attention.py), and the decoder that uses it
(models/decoder.py with ``selected_layout``), against plain arithmetic:
the selection against ``argsort``, the function against a dense masked
softmax and against ``blocked_self_attention``, its two executions against
each other (the kernels interpreted on the CPU), and the whole model
against the benchmark's plain float32 reference of the configuration that
runs it (``benchmark/reference/keye-vl-2-30b-a3b.py``, loaded by path, which
imports nothing of the program): the two loss terms and every gradient
leaf. Tiny sizes that keep what matters: 4 query to 2 KV heads, 3 index
heads, a selection far smaller than the sequence."""

import importlib.util
import re
import sys
from collections import Counter
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import torchmpi_tpu as mpi
from torchmpi_tpu import telemetry
from torchmpi_tpu.engine import AllReduceSGDEngine
from torchmpi_tpu.models import (
    MoEDecoder,
    init_lm_params,
    init_moe_state,
    make_moe_lm_loss_fn,
)
from torchmpi_tpu.parallel import (
    blocked_self_attention,
    selected_attention as sa,
    selected_self_attention,
)
from torchmpi_tpu.telemetry import names

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
CONFIG = "keye-vl-2-30b-a3b"


@pytest.fixture(scope="module")
def plain():
    path = ROOT / "benchmark" / "reference" / f"{CONFIG}.py"
    spec = importlib.util.spec_from_file_location("plain_keye", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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


@pytest.mark.parametrize("t,hq,hkv,top_k,period", [
    (512, 1, 1, 70, 0),       # one tile; one KV head, a group of one
    (300, 4, 4, 40, 0),       # shorter than a tile; 4 KV heads, groups of 1
    (1100, 8, 1, 70, 0),      # a padded tail; a group of 8; top_k < a tile
    (2500, 32, 4, 1500, 0),   # 3 panels; 4 groups of 8; top_k over a panel
    (1100, 8, 1, 700, 7),     # ties at the threshold across a tile's edge
    (2500, 2, 2, 600, 5),     # ... and across a panel's
], ids=["one_tile", "short", "padded_group8", "panels_4x8", "ties_tile",
        "ties_panels"])
def test_the_kernels_are_the_loops_on_what_a_kernel_can_get_wrong(
        t, hq, hkv, top_k, period):
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


def test_selected_attention_rejects_bad_shapes():
    q, k, v, iq, ik, iw = inputs(0, 1, 16, 4, 2, 8, 3, 8)
    with pytest.raises(ValueError, match="multiple of the KV heads"):
        selected_self_attention(q[:, :, :3], k, v, iq, ik, iw, top_k=4)
    with pytest.raises(ValueError, match="indexer"):
        selected_self_attention(q, k, v, iq, ik[:, :8], iw, top_k=4)
    with pytest.raises(ValueError, match="top_k"):
        selected_self_attention(q, k, v, iq, ik, iw, top_k=0)


# -- the decoder against the plain reference -------------------------------
SEQ = 40


def tiny_cfg(**over):
    """The published keys at test sizes, as the reference reads them."""
    cfg = {
        "hidden_size": 32, "head_dim": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "moe_intermediate_size": 16,
        "num_experts_per_tok": 3, "num_hidden_layers": 2,
        "rms_norm_eps": 1e-6, "rope_theta": 10000000, "vocab_size": 61,
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 3,
                      "indexer_num_kv_heads": 1, "topk": 9},
        "model": {"router_outputs": 8, "experts_held": [0, 1, 5]},
        "optimizer": {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9,
                      "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01},
    }
    cfg.update(over)
    return cfg


def tiny_model(cfg, dtype=jnp.float32, remat=True):
    sa_cfg = cfg["sa_config"]
    return MoEDecoder(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        expert_width=cfg["moe_intermediate_size"],
        num_experts=cfg["model"]["router_outputs"],
        top_k=cfg["num_experts_per_tok"],
        held=tuple(cfg["model"]["experts_held"]), window_layout=(0,),
        rope_layout=(1,), rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"], attn_block=16,
        activation=jax.nn.silu, router_after_norm=True, qk_norm=True,
        selected_layout=(1,), index_top_k=sa_cfg["topk"],
        index_heads=sa_cfg["indexer_num_heads"],
        index_dim=sa_cfg["indexer_head_dim"], remat=remat, dtype=dtype)


def seeded_params(model, seq, seed=0, std=0.3):
    shapes = jax.eval_shape(lambda: init_lm_params(model, seq))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def leaf(path, shape, key):
        last = str(getattr(path[-1], "key", ""))
        if last == "scale":  # norms near 1, not at it: their gradients show
            return 1.0 + 0.1 * jax.random.normal(key, shape.shape)
        return std * jax.random.normal(key, shape.shape, jnp.float32)

    return treedef.unflatten(
        [leaf(p, s, k) for (p, s), k in zip(leaves, keys)])


def tokens(n, seq, vocab, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, vocab, size=(n, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


def indexer_leaf(path) -> bool:
    return any(str(getattr(k, "key", "")).startswith("index_") for k in path)


def test_decoder_loss_terms_and_every_gradient_match_the_reference(plain):
    """``L_lm``, each layer's ``L_I`` and every gradient leaf, selected
    layers at ``top_k`` 9 of up to 40 keys, grouped heads, 3 of 8 experts
    held. float32 on both sides at precision highest; the program sums
    attention a block of queries at a time and the experts' rows in
    another order: 2e-6 on a loss near log(61), 2e-4 of a leaf's largest
    value on gradients that are sums over 120 tokens."""
    cfg = tiny_cfg()
    model = tiny_model(cfg)
    params = seeded_params(model, SEQ)
    x, y = tokens(3, SEQ, cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        (loss, state), grads = jax.jit(jax.value_and_grad(
            make_moe_lm_loss_fn(model), has_aux=True))(
                params, init_moe_state(model),
                (jnp.asarray(x), jnp.asarray(y)))
        row = jax.jit(jax.value_and_grad(
            lambda p, xi, yi: (lambda lm, index: (lm + index.sum(),
                                                  (lm, index)))(
                *plain.loss_terms(p, xi, yi, cfg, "float32")),
            has_aux=True))
        got = [row(params, jnp.asarray(x[i]), jnp.asarray(y[i]))
               for i in range(len(x))]
    terms, rows = [g[0][1] for g in got], [g[1] for g in got]
    lm = sum(t[0] for t in terms) / len(x)
    index = sum(t[1] for t in terms) / len(x)
    np.testing.assert_allclose(state["attn_index_loss"], index, rtol=1e-5)
    np.testing.assert_allclose(loss, lm + index.sum(), rtol=2e-6)
    assert float(index.min()) > 1e-3  # the term is there
    want = jax.tree_util.tree_map(lambda *g: sum(g) / len(x), *rows)
    gaps = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))),
        grads, want)
    worst = max(jax.tree_util.tree_leaves(gaps))
    assert worst < 2e-4, gaps
    np.testing.assert_array_equal(
        state["attn_selected_pairs"],
        len(x) * sum(min(i + 1, 9) for i in range(SEQ)))


def test_each_loss_term_reaches_its_own_parameters_alone(plain):
    """The two walls through the whole model: ``grad L_lm`` is exactly zero
    on every indexer leaf, ``grad sum L_I`` exactly zero on every other."""
    cfg = tiny_cfg()
    model = tiny_model(cfg, remat=False)
    params = seeded_params(model, SEQ)
    x, y = tokens(2, SEQ, cfg["vocab_size"])
    batch = (jnp.asarray(x), jnp.asarray(y))
    loss_fn = make_moe_lm_loss_fn(model)
    state = init_moe_state(model)

    def index_term(p):
        return jnp.sum(loss_fn(p, state, batch)[1]["attn_index_loss"])

    of_index = jax.jit(jax.grad(index_term))(params)
    of_lm = jax.jit(jax.grad(
        lambda p: loss_fn(p, state, batch)[0] - index_term(p)))(params)
    for tree, wall in ((of_lm, True), (of_index, False)):
        for path, g in jax.tree_util.tree_flatten_with_path(tree)[0]:
            if indexer_leaf(path) == wall:
                assert not np.any(np.asarray(g)), path
            else:
                assert np.any(np.asarray(g)), path
    # the reference's walls stand in the same places
    lm_only = jax.jit(jax.grad(lambda p: plain.loss_terms(
        p, batch[0][0], batch[1][0], cfg, "float32")[0]))(params)
    for path, g in jax.tree_util.tree_flatten_with_path(lm_only)[0]:
        assert bool(np.any(np.asarray(g))) != indexer_leaf(path), path


def test_one_block_builds_both_decoder_configurations():
    """The fields' defaults are the other configuration's layer: its
    parameter names are as before, and the new layer adds its own."""
    kinds = lambda model: set(jax.eval_shape(  # noqa: E731
        lambda: init_lm_params(model, 16))["MoEDecoderBlock_0"])
    older = kinds(MoEDecoder(vocab_size=61, num_layers=1, d_model=32,
                             head_dim=8, expert_width=16))
    assert older == {"router", "norm_attn", "q", "k", "v", "o", "norm_moe",
                     "experts_gate", "experts_up", "experts_down"}
    assert kinds(tiny_model(tiny_cfg())) == older | {
        "q_norm", "k_norm", "index_q", "index_k", "index_k_norm", "index_w"}
    assert set(init_moe_state(tiny_model(tiny_cfg()))) == {
        "moe_load", "moe_rows", "attn_index_loss", "attn_selected_pairs"}
    assert set(init_moe_state(MoEDecoder())) == {"moe_load", "moe_rows"}


def test_the_decoders_recomputation_changes_no_number():
    cfg = tiny_cfg()
    x, y = tokens(2, SEQ, cfg["vocab_size"])
    batch = (jnp.asarray(x), jnp.asarray(y))
    got = []
    for remat in (False, True):
        model = tiny_model(cfg, remat=remat)
        params = seeded_params(model, SEQ)
        (loss, _), grads = jax.jit(jax.value_and_grad(
            make_moe_lm_loss_fn(model), has_aux=True))(
                params, init_moe_state(model), batch)
        got.append((loss, grads))
    np.testing.assert_allclose(got[0][0], got[1][0], rtol=1e-6)
    for a, b in zip(*(jax.tree_util.tree_leaves(g) for _, g in got)):
        np.testing.assert_allclose(a, b, atol=1e-6)


# -- what a recomputing caller keeps ----------------------------------------
_names_kept = jax.checkpoint_policies.save_only_these_names
KEPT = {
    "no_recomputation": jax.checkpoint_policies.everything_saveable,
    "saved": _names_kept(sa.SAVED),
}
REMAT_T, REMAT_TOP_K = 2500, 600  # 3 panels; ties across their edges


def test_remat_keeps_the_selection_and_changes_no_number():
    """A caller that recomputes the layer in backward and keeps nothing
    but ``SAVED`` (the panels of index scores among it: backward then
    masks with the very bits forward selected from), against one that
    keeps everything: the value and every gradient are EQUAL, not close,
    on the interpreted kernels. The inputs' scores tie across tile and
    panel edges."""
    args = tied(inputs(11, 1, REMAT_T, 2, 1, 128, 2, 64), 5)
    assert len(sa._panels(3072)) == 3
    (got, grads), (want, want_grads) = (
        jax.jit(jax.value_and_grad(jax.checkpoint(
            weighed(partial(interpreted, REMAT_TOP_K)),
            policy=KEPT[kept]), argnums=range(6)))(*args)
        for kept in ("saved", "no_recomputation"))
    np.testing.assert_array_equal(got, want)
    for g, w in zip(grads, want_grads):
        assert np.any(np.asarray(w))
        np.testing.assert_array_equal(g, w)


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


@pytest.mark.parametrize("kept", sorted(KEPT))
def test_the_index_scores_are_made_once(kept):
    """A selecting layer's forward and backward, lowered for a TPU:
    backward holds no ``tm_attn_index_scores`` call beyond the forward's
    (one a panel), whether the caller recomputes the layer and keeps
    ``SAVED`` or recomputes nothing. Every other kernel runs once too."""
    t, panels = 3072, 3
    shapes = [(1, t, 2, 128), (1, t, 1, 128), (1, t, 1, 128), (1, t, 2, 64),
              (1, t, 64), (1, t, 2)]
    layer = jax.checkpoint(
        weighed(partial(selected_self_attention, top_k=70)),
        policy=KEPT[kept])
    calls = kernel_calls(lowered_for_tpu(
        jax.grad(layer, argnums=range(6)),
        *[jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]))
    # the reader's name is the lowered program's, or this finds none
    assert calls.pop(index_kernel_name()) == panels
    assert calls == dict.fromkeys((
        "tm_attn_select_kth", "tm_attn_sparse_fwd",
        "tm_attn_sparse_mean_probabilities", "tm_attn_sparse_bwd",
        "tm_attn_index_grad_queries", "tm_attn_index_grad_keys"), panels)


def test_the_decoders_policy_keeps_the_index_scores():
    """The decoder's own recomputation (``remat=True``) names ``SAVED``:
    its step lowered for a TPU makes each selecting layer's index scores once
    a panel, forward and backward together."""
    t, panels, layers = 3072, 3, 2
    cfg = tiny_cfg(head_dim=128, num_attention_heads=2,
                   num_key_value_heads=1)
    model = tiny_model(cfg)
    params = jax.eval_shape(lambda: init_lm_params(model, t))
    ids = jax.ShapeDtypeStruct((1, t), jnp.int32)
    calls = kernel_calls(lowered_for_tpu(
        jax.grad(lambda p, x, y: make_moe_lm_loss_fn(model)(
            p, init_moe_state(model), (x, y))[0]), params, ids, ids))
    assert set(calls.values()) == {layers * panels}
    assert len(calls) == 7 and index_kernel_name() in calls


def test_three_engine_steps_match_the_reference_and_set_the_gauges(plain):
    """``engine.train`` for three steps against the reference's ``follow``
    on the same batches, and what the selection measured as gauges where
    the epoch's loss was read."""
    cfg = tiny_cfg()
    model = tiny_model(cfg)
    params = seeded_params(model, SEQ)
    opt = cfg["optimizer"]
    batches = [tokens(2, SEQ, cfg["vocab_size"], seed=s) for s in range(3)]
    mpi.start(devices=jax.devices()[:1])
    engine = AllReduceSGDEngine(
        make_moe_lm_loss_fn(model), params,
        optimizer=optax.adamw(
            opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
            eps=opt["eps"], weight_decay=opt["weight_decay"]),
        model_state=init_moe_state(model))
    losses = []
    engine.hooks = {"on_update": lambda s: losses.append(float(s["loss"]))}
    with jax.default_matmul_precision("highest"):
        engine.train(lambda: iter(batches), max_epochs=1)
        want = plain.follow(cfg, params, batches)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    gauges = telemetry.metrics.snapshot()
    value = lambda k: gauges[k]["series"][""]  # noqa: E731
    pairs = 2 * 2 * sum(min(i + 1, 9) for i in range(SEQ))
    assert value("tm_attn_selected_pairs_per_step") == pairs
    assert value("tm_attn_causal_pairs_per_step") == 2 * 2 * SEQ * (
        SEQ + 1) // 2
    assert value("tm_attn_index_loss_last_step") == pytest.approx(
        float(np.mean(engine.model_state["attn_index_loss"])))
    assert value("tm_attn_calls_per_step") == 2


def test_the_three_scopes_are_in_the_lowered_step():
    cfg = tiny_cfg()
    model = tiny_model(cfg)
    params = seeded_params(model, SEQ)
    x, y = tokens(2, SEQ, cfg["vocab_size"])
    text = jax.jit(jax.grad(lambda p: make_moe_lm_loss_fn(model)(
        p, init_moe_state(model), (jnp.asarray(x), jnp.asarray(y)))[0])
    ).lower(params).as_text(debug_info=True)
    for scope in (names.SCOPE_ATTN_INDEX, names.SCOPE_ATTN_SELECT,
                  names.SCOPE_ATTN_SPARSE):
        assert scope in names.MODEL_SCOPE_NAMES and scope + "/" in text
    assert names.SCOPE_ATTN_FULL + "/" not in text
