"""The sparse decoder (models/decoder.py), its blocked attention
(parallel/ring_attention.py) and its dropless expert layer (parallel/ep.py)
against plain arithmetic: a dense masked softmax, a per-token loop, and the
benchmark's plain float32 reference of the configuration that runs them
(``benchmark/reference/smallthinker-21b-a3b.py``, loaded by path, which
imports nothing of the program). Tiny sizes that keep what matters: the
pattern [0, 1, 1, 1], a window shorter than the sequence, 4 query to 2 KV
heads, 8 experts at 3 a token."""

import importlib.util
import math
import re
import sys
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
from torchmpi_tpu.parallel import blocked_self_attention, moe_local_experts
from torchmpi_tpu.telemetry import names

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
CONFIG = "smallthinker-21b-a3b"
CELL = CONFIG + ".stream.x1"


def _load(path):
    spec = importlib.util.spec_from_file_location(
        "plain_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def plain():
    """The benchmark's plain reference of the configuration, by path."""
    return _load(ROOT / "benchmark" / "reference" / f"{CONFIG}.py")


def tiny_cfg(**over):
    """The published keys at test sizes, as the reference reads them."""
    cfg = {
        "hidden_size": 32, "head_dim": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "moe_ffn_hidden_size": 16,
        "moe_num_active_primary_experts": 3, "num_hidden_layers": 4,
        "rms_norm_eps": 1e-6, "rope_theta": 1500000, "vocab_size": 61,
        "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
        "sliding_window_size": 12,
        "model": {"router_outputs": 8, "experts_held": [0, 1, 2, 3, 4, 5, 6,
                                                        7]},
        "optimizer": {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9,
                      "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01},
    }
    cfg.update(over)
    return cfg


def tiny_model(cfg, dtype=jnp.float32, remat=True, block=8):
    return MoEDecoder(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        expert_width=cfg["moe_ffn_hidden_size"],
        num_experts=cfg["model"]["router_outputs"],
        top_k=cfg["moe_num_active_primary_experts"],
        held=tuple(cfg["model"]["experts_held"]),
        window=cfg["sliding_window_size"],
        window_layout=tuple(cfg["sliding_window_layout"]),
        rope_layout=tuple(cfg["rope_layout"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        attn_block=block, remat=remat, dtype=dtype,
    )


def seeded_params(model, seq, seed=0, std=0.3):
    """Seeded normal weights large enough that routing and attention are
    far from uniform; norm scales 1."""
    shapes = jax.eval_shape(lambda: init_lm_params(model, seq))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten([
        jnp.ones(s.shape, jnp.float32)
        if str(getattr(p[-1], "key", "")) == "scale"
        else std * jax.random.normal(k, s.shape, jnp.float32)
        for (p, s), k in zip(leaves, keys)])


def tokens(n, seq, vocab, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, vocab, size=(n, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


# -- blocked attention against a dense masked softmax ----------------------
def dense_attention(q, k, v, window):
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("t,window,block,heads,kv_heads", [
    (64, None, 16, 4, 4),   # the causal band
    (64, 24, 16, 4, 4),     # the window band, across block edges
    (64, 16, 16, 4, 2),     # grouped heads, window = block
    (64, None, 16, 6, 2),   # grouped heads, three to a KV head
    (50, 24, 16, 4, 2),     # a sequence that is no multiple of the block
    (37, 5, 8, 4, 1),       # all query heads on one KV head, odd length
    (64, 100, 16, 4, 2),    # a window longer than the sequence
    (20, 7, 1024, 4, 2),    # one block longer than the sequence
    (64, 1, 16, 4, 2),      # a window of the token itself
])
def test_blocked_attention_matches_dense_masked_softmax(
        t, window, block, heads, kv_heads):
    ks = jax.random.split(jax.random.PRNGKey(t + block), 4)
    q = jax.random.normal(ks[0], (2, t, heads, 8))
    k = jax.random.normal(ks[1], (2, t, kv_heads, 8))
    v = jax.random.normal(ks[2], (2, t, kv_heads, 8))
    w = jax.random.normal(ks[3], (2, t, heads, 8))

    def through(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2))

    got, got_g = jax.jit(through(
        lambda q, k, v: blocked_self_attention(q, k, v, window, block))
    )(q, k, v)
    want, want_g = through(
        lambda q, k, v: dense_attention(q, k, v, window))(q, k, v)
    # float32 throughout; the blocks only change the order of the sums
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_blocked_attention_skips_blocks_outside_the_band():
    """Skipped, not masked: the loops' bounds leave them out, so the
    compiled program does less work for a window than for the full band."""
    q = jax.ShapeDtypeStruct((1, 256, 4, 8), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 256, 2, 8), jnp.float32)

    def visits(window):
        # the key blocks each query block's loop visits
        from torchmpi_tpu.parallel.ring_attention import _first_block
        return sum(i + 1 - int(_first_block(i, 32, window))
                   for i in range(256 // 32))

    assert visits(None) == 36 and visits(64) == 8 + 7 + 6
    assert visits(1) == 8 and visits(33) == 8 + 7
    out = jax.eval_shape(
        lambda q, k, v: blocked_self_attention(q, k, v, 64, 32), q, k, k)
    assert out.shape == q.shape


def test_blocked_attention_rejects_bad_shapes():
    q = jnp.zeros((1, 8, 3, 4))
    with pytest.raises(ValueError, match="multiple of the KV heads"):
        blocked_self_attention(q, q[:, :, :2], q[:, :, :2])
    with pytest.raises(ValueError, match="window must be positive"):
        blocked_self_attention(q, q, q, window=0)


# -- the dropless expert layer against a per-token loop --------------------
T, D, F, E, K = 48, 16, 12, 8, 3
# sizes at which a share under half has a compact tier of rows (it takes
# more routes than one tile of 512): 2 of 16 experts held, 1,536 routes,
# a tier of 512
BIG_T, BIG_E = 512, 16


def expert_inputs(skew=True, t=T, e=E):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (t, D))
    logits = jax.random.normal(ks[1], (t, e))
    if skew:  # expert 2 is on every token's list, expert 5 on none
        logits = logits.at[:, 2].add(6.0).at[:, 5].add(-60.0)
    w = [0.3 * jax.random.normal(k, s) for k, s in zip(
        ks[2:], [(e, D, F), (e, D, F), (e, F, D)])]
    return x, logits, w


def token_loop(x, logits, w, held):
    x, lg = np.asarray(x, np.float64), np.asarray(logits, np.float64)
    wg, wu, wd = (np.asarray(a, np.float64) for a in w)
    y, load = np.zeros(x.shape), np.zeros(len(held))
    for t in range(len(x)):
        top = np.argsort(-lg[t], kind="stable")[:K]
        gate = np.exp(lg[t][top] - lg[t][top].max())
        gate /= gate.sum()
        for e, g in zip(top, gate):
            if e in held:
                h = np.maximum(x[t] @ wg[e], 0) * (x[t] @ wu[e])
                y[t] += g * (h @ wd[e])
                load[held.index(e)] += 1
    return y, load


def held_layer(x, logits, w, held, rows=False, activation=jax.nn.relu):
    """``(y, load)`` of the layer over the experts ``held`` of ``w``'s;
    with ``rows`` also the rows its grouped products ran over."""
    sel = jnp.asarray(held)
    out = moe_local_experts(
        x, logits, K, w[0][sel], w[1][sel], w[2][sel], held,
        activation=activation)
    return out if rows else out[:2]


@pytest.mark.parametrize("held", [
    list(range(8)), [0, 1], [2, 3, 4, 5], [5], [6, 2], [7]])
def test_expert_layer_matches_a_token_loop_under_skewed_routing(held):
    x, logits, w = expert_inputs()
    y, load = jax.jit(lambda *a: held_layer(*a, held))(x, logits, w)
    want, want_load = token_loop(x, logits, w, held)
    assert np.all(np.isfinite(y))
    np.testing.assert_allclose(y, want, atol=1e-5)
    # nothing dropped: every route to a held expert was counted and
    # computed, the overloaded expert's 48 rows among them
    np.testing.assert_array_equal(load, want_load)
    if 2 in held:
        assert load[held.index(2)] == T
    if 5 in held:
        assert load[held.index(5)] == 0


@pytest.mark.parametrize("held,more,tier", [
    ([5, 7], 0, "compact"),    # a tenth of the tier's rows in use
    ([2, 5], 0, "compact"),    # every token on expert 2: the tier full
    ([2, 5], 1, "all rows"),   # ... and one route more than it holds
    ([2, 7], 0, "all rows"),   # the overloaded expert and another's
    ([2, 5], 300, "all rows"),
])
def test_nothing_is_dropped_on_either_side_of_the_compact_tier(
        held, more, tier):
    """2 of 16 experts held: a tier of 512 rows for 1,536 routes. A step
    takes it while its held routes fit, ``sum(sizes) <= 512``, and the
    execution over all rows from the first route past it; either way every
    route to a held expert is computed, as the token loop computes it."""
    from torchmpi_tpu.parallel import ep

    x, logits, w = expert_inputs(t=BIG_T, e=BIG_E)
    # ``more`` tokens put expert 5, on no token's list, first on theirs
    logits = logits.at[:more, 5].set(60.0)
    tier_rows = ep.compact_rows(BIG_T * K, len(held), BIG_E)
    assert tier_rows == 512
    y, load, rows = jax.jit(
        lambda *a: held_layer(*a, held, rows=True))(x, logits, w)
    want, want_load = token_loop(x, logits, w, held)
    np.testing.assert_array_equal(load, want_load)
    np.testing.assert_allclose(y, want, atol=1e-5)
    if held == [2, 5]:
        assert load.sum() == tier_rows + more
    assert rows == {"compact": tier_rows, "all rows": BIG_T * K}[tier]
    assert (load.sum() <= tier_rows) == (tier == "compact")


def sorted_routes(logits, held):
    """``(weight, slot, order)`` as the layer makes them: each token's
    softmax over its top ``K``, each route's place among the ``held`` (or
    their count), the routes' stable order by that."""
    top, chosen = jax.lax.top_k(logits, K)
    slot_of = np.full((logits.shape[1],), len(held), np.int32)
    slot_of[held] = np.arange(len(held))
    slot = jnp.asarray(slot_of)[chosen].reshape(-1)
    return (jax.nn.softmax(top, axis=-1), slot,
            jnp.argsort(slot, stable=True).astype(jnp.int32))


def both_executions(x, logits, w, held, through):
    """The layer's ``(y, load)`` by its compact execution and by the one
    over all rows: ``through`` "direct" calls the two, "cond" the layer
    itself, which takes the compact one here, and the layer with no
    compact tier to take."""
    from torchmpi_tpu.parallel import ep

    if through == "cond":
        got = held_layer(x, logits, w, held)
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(ep, "compact_rows", lambda routes, *_: routes)
            return got, held_layer(x, logits, w, held)
    weight, slot, order = sorted_routes(logits, held)
    mine = [a[jnp.asarray(held)] for a in w]
    want, sizes = ep._all_rows(
        jax.nn.relu, x, weight, slot, order, *mine)
    got = ep._first_rows(
        ep.compact_rows(slot.size, len(held), logits.shape[1]), jax.nn.relu,
        x, weight, order, sizes, *mine)
    load = sizes.astype(jnp.float32)
    return (got, load), (want, load)


@pytest.mark.parametrize("through", ["direct", "cond"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_compact_execution_equals_the_one_over_all_rows(dtype, through):
    """Value, load and all five gradients, 2 of 16 held under the skewed
    routing (expert 2's 512 rows fill the tier to its last row). The same
    products on the same rows: only the order of the float32 sum over a
    token's routes differs, so float32 agrees to rounding and bfloat16 to
    a rounding of the result."""
    x, logits, w = expert_inputs(t=BIG_T, e=BIG_E)
    x = x.astype(dtype)
    mix = jax.random.normal(jax.random.PRNGKey(7), x.shape)

    def run(which):
        def loss(x, lg, wg, wu, wd):
            y, load = both_executions(
                x, lg, [wg, wu, wd], [2, 5], through)[which]
            return jnp.sum(y.astype(jnp.float32) * mix), (y, load)

        return jax.jit(jax.value_and_grad(
            jax.checkpoint(loss), argnums=(0, 1, 2, 3, 4), has_aux=True))(
                x, logits, *w)

    ((_, (y, load)), grads), ((_, (want, want_load)), want_g) = run(0), run(1)
    np.testing.assert_array_equal(load, want_load)
    assert load.sum() == 512
    tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -8
    close = lambda a, b: np.testing.assert_allclose(  # noqa: E731
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        atol=tol * float(jnp.max(jnp.abs(b.astype(jnp.float32)))))
    close(y, want)
    for a, b in zip(grads, want_g):
        assert a.dtype == b.dtype and np.all(np.isfinite(a))
        close(a, b)


@pytest.mark.parametrize("rows,t,k", [
    (64, 40, 3), (512, 100, 6), (16, 300, 1), (8, 3, 6)])
def test_rows_are_summed_into_their_tokens_as_np_add_at_sums_them(
        rows, t, k):
    """The compact execution's combine (and its gather's transpose): a sum
    by token made of a sort, gathers and shifted adds, against
    ``np.add.at``, with tokens of no row, of one and of up to ``k``."""
    from torchmpi_tpu.parallel import ep

    rng = np.random.default_rng(rows)
    token = rng.permutation(np.repeat(np.arange(t), k))[:rows]
    values = rng.normal(size=(rows, 5)).astype(np.float32)
    want = np.zeros((t, 5), np.float32)
    np.add.at(want, token, values)
    got = jax.jit(ep._sum_by_token, static_argnums=(2, 3))(
        jnp.asarray(values), jnp.asarray(token, jnp.int32), t, k)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert 1 <= np.bincount(token, minlength=t).max() <= k


def equations_in(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.tree_util.tree_leaves(
                list(eqn.params.values()),
                is_leaf=lambda p: hasattr(p, "eqns") or hasattr(p, "jaxpr")):
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield from equations_in(inner)


def arrays_in(jaxpr):
    return [v.aval for eqn in equations_in(jaxpr) for v in eqn.outvars]


def conds_in(jaxpr):
    return [e for e in equations_in(jaxpr) if e.primitive.name == "cond"]


def test_compact_execution_holds_no_array_of_all_the_routes_rows():
    """The shape audit. Differentiated, the compact execution holds no
    array of ``R x d`` or ``R x f`` elements, and the layer's ``cond``s,
    forward and backward, hand out nothing with ``R`` rows (the derivative
    of a ``lax.cond`` as jax makes it hands every branch's residuals out of
    every branch: the layer's own rule is there so that it does not)."""
    from torchmpi_tpu.parallel import ep

    x, logits, w = expert_inputs(t=BIG_T, e=BIG_E)
    routes = BIG_T * K

    def compact(x, lg, wg, wu, wd):
        weight, slot, order = sorted_routes(lg, [2, 9])
        return jnp.sum(ep._first_rows(
            512, jax.nn.relu, x, weight, order, ep._group_sizes(slot, 2),
            wg[:2], wu[:2], wd[:2]))

    def layer(x, lg, wg, wu, wd):
        return jnp.sum(held_layer(x, lg, [wg, wu, wd], [2, 9])[0])

    compact, layer = (
        jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2, 3, 4)))(
            x, logits, *w).jaxpr for f in (compact, layer))
    sizes = {a.size for a in arrays_in(compact)}
    assert 512 * D in sizes and 512 * F in sizes  # the tier's own arrays
    assert max(sizes) < routes * min(D, F), sorted(sizes)[-3:]
    # ... while the layer as a whole does hold them, in its other branch
    assert routes * D in {a.size for a in arrays_in(layer)}
    conds = conds_in(layer)
    assert len(conds) == 2  # forward, and the rule's own in backward
    for eqn in conds:
        shapes = [v.aval.shape for v in eqn.outvars]
        assert shapes and all(s[:1] != (routes,) for s in shapes), shapes
    assert [v.aval.shape for v in conds[0].outvars] == [(BIG_T, D)]


def parents_layer(x, router_logits, top_k, w_gate, w_up, w_down, held,
                  activation=jax.nn.relu):
    """``moe_local_experts`` as it stood before the layer had a compact
    tier (commit 705a2ff), its checks left out: the text a layer with half
    its experts or more held must still lower to."""
    from torchmpi_tpu.parallel.ep import _permute_rows

    lax, T, d = jax.lax, *x.shape
    E = router_logits.shape[-1]
    k, n = int(top_k), len(held)
    R = T * k
    top, chosen = lax.top_k(router_logits.astype(jnp.float32), k)
    weight = jax.nn.softmax(top, axis=-1)
    slot_of = np.full((E,), n, np.int32)
    slot_of[list(held)] = np.arange(n, dtype=np.int32)
    slot = jnp.asarray(slot_of)[chosen].reshape(R)
    order = jnp.argsort(slot, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    sizes = jnp.sum(
        slot[:, None] == jnp.arange(n, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)
    held_row = _permute_rows((slot < n).reshape(R, 1), order, inverse)
    gate_row = _permute_rows(weight.reshape(R, 1), order, inverse)
    rows = _permute_rows(jnp.repeat(x, k, axis=0), order, inverse)
    own = lambda a: jnp.where(held_row, a, 0)  # noqa: E731
    dt = x.dtype
    rows = own(rows)
    hidden = own(
        activation(lax.ragged_dot(rows, w_gate.astype(dt), sizes))
        * lax.ragged_dot(rows, w_up.astype(dt), sizes))
    hidden = own(hidden * gate_row.astype(dt))
    rows = own(lax.ragged_dot(hidden, w_down.astype(dt), sizes))
    routes = _permute_rows(rows, inverse, order).reshape(T, k, d)
    y = jnp.sum(routes, axis=1, dtype=jnp.float32).astype(dt)
    return y, sizes.astype(jnp.float32)


@pytest.mark.parametrize("held,t,e", [
    (list(range(8)), T, E),            # a device that holds them all
    ([2, 3, 4, 5], T, E),              # ... or half
    (list(range(8)), BIG_T, BIG_E),    # half of 16, at the larger size
    ([0, 1], T, E),                    # a quarter, of too few routes
])
def test_a_layer_with_no_compact_tier_lowers_to_the_parents_text(
        held, t, e):
    x, logits, w = expert_inputs(t=t, e=e)
    x = x.astype(jnp.bfloat16)
    sel = jnp.asarray(held)

    def text(layer):
        def loss(x, lg, *w):
            y, load = layer(x, lg, K, *[a[sel] for a in w], held)[:2]
            return jnp.sum(y.astype(jnp.float32) ** 2), load

        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)).lower(
                x, logits, *w).as_text()

    assert text(moe_local_experts) == text(parents_layer)


def test_expert_layer_gradients_are_finite_and_match_a_dense_mixture():
    x, logits, w = expert_inputs()

    def dense_mixture(x, logits, w):
        top, chosen = jax.lax.top_k(logits, K)
        gate = jax.nn.softmax(top, axis=-1)
        y = 0.0
        for e in range(E):
            g = jnp.sum(jnp.where(chosen == e, gate, 0.0), axis=-1)
            y = y + g[:, None] * (
                (jax.nn.relu(x @ w[0][e]) * (x @ w[1][e])) @ w[2][e])
        return y

    def loss(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) ** 2), argnums=(0, 1, 2))

    got = jax.jit(loss(
        lambda x, lg, w: held_layer(x, lg, w, list(range(E)))[0]))(
            x, logits, w)
    want = loss(dense_mixture)(x, logits, w)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_expert_layer_rejects_bad_arguments():
    x, logits, w = expert_inputs()
    with pytest.raises(ValueError, match="held must be distinct"):
        moe_local_experts(x, logits, K, w[0][:2], w[1][:2], w[2][:2], [1, 1])
    with pytest.raises(ValueError, match="held must be distinct"):
        moe_local_experts(x, logits, K, w[0][:1], w[1][:1], w[2][:1], [8])
    with pytest.raises(ValueError, match="expected"):
        moe_local_experts(x, logits, K, w[0][:3], w[1][:2], w[2][:2], [0, 1])
    with pytest.raises(ValueError, match="router_logits"):
        moe_local_experts(x, logits[:, :2], K, *[a[:2] for a in w], [0, 1])


@pytest.mark.parametrize("config,activation", [
    (CONFIG, jax.nn.relu),              # ReGLU experts
    ("keye-vl-2-30b-a3b", jax.nn.silu),  # SwiGLU experts
], ids=["smallthinker-21b-a3b", "keye-vl-2-30b-a3b"])
@pytest.mark.parametrize("shares", [
    [[e] for e in range(8)],          # 8 shares of 1 expert
    [[0, 1, 2, 3], [4, 5, 6, 7]],     # 2 shares of 4
    [[6, 1], [0, 7, 3], [2], [5, 4]],  # uneven shares, out of order
])
def test_shares_add_up_to_the_uncut_reference_layer(
        shares, config, activation):
    """The share test: what every share computes of the expert layer,
    added, is what the configuration's plain reference gives for the layer
    with all the experts (nothing is computed by every share alike here:
    neither model has a shared expert). Each reference is handed the
    router's logits as its layer would read them (before attention, or
    after the second norm): the layer under test begins at the logits."""
    x, logits, w = expert_inputs(skew=False)
    cfg = tiny_cfg()
    cfg["model"]["experts_held"] = list(range(E))
    whole = _load(ROOT / "benchmark" / "reference" / f"{config}.py").experts(
        x, logits, {"experts_gate": w[0], "experts_up": w[1],
                    "experts_down": w[2]},
        {**cfg, "moe_num_active_primary_experts": K,
         "num_experts_per_tok": K}, "float32")
    parts, loads = zip(*(held_layer(x, logits, w, held,
                                    activation=activation)
                         for held in shares))
    np.testing.assert_allclose(sum(parts), whole, atol=1e-5)
    assert sum(float(l.sum()) for l in loads) == T * K  # every route, once


# -- the decoder against the plain reference -------------------------------
SEQ = 40


def plain_loss_and_grads(plain, cfg, params, x, y):
    row = jax.jit(jax.value_and_grad(
        lambda p, xi, yi: plain.loss_fn(p, xi, yi, cfg, "float32")))
    rows = [row(params, jnp.asarray(x[i]), jnp.asarray(y[i]))
            for i in range(len(x))]
    loss = sum(r[0] for r in rows) / len(rows)
    grads = jax.tree_util.tree_map(
        lambda *g: sum(g) / len(rows), *[r[1] for r in rows])
    return loss, grads


@pytest.mark.parametrize("held", [list(range(8)), [0, 1], [3, 6, 7]],
                         ids=["all8", "held2", "held3"])
def test_decoder_loss_and_gradients_match_the_plain_reference(plain, held):
    cfg = tiny_cfg()
    cfg["model"]["experts_held"] = held
    model = tiny_model(cfg)
    params = seeded_params(model, SEQ)
    x, y = tokens(3, SEQ, cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        (loss, state), grads = jax.jit(jax.value_and_grad(
            make_moe_lm_loss_fn(model), has_aux=True))(
                params, init_moe_state(model),
                (jnp.asarray(x), jnp.asarray(y)))
        want, want_g = plain_loss_and_grads(plain, cfg, params, x, y)
    # float32 on both sides; the program sums attention in blocks and the
    # experts' rows in another order: a few units in the last place of a
    # loss near log(61), more on gradients that are sums over 120 tokens
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))),
        grads, want_g)))
    assert worst < 2e-4, worst
    assert state["moe_load"].shape == (4, len(held))
    # a layer's load is its routes that fell on held experts
    assert float(state["moe_load"].sum()) <= 4 * 3 * SEQ * 3
    if len(held) == 8:
        np.testing.assert_array_equal(
            state["moe_load"].sum(axis=1), 3 * SEQ * 3)


def test_decoder_in_bfloat16_stays_near_the_reference(plain):
    """bfloat16 products over float32 parameters, the router in float32:
    the loss within 2 % and the gradient within 10 % of its norm. Rounding
    to 8 bits of mantissa moves each product by up to 0.4 %, and a route
    that flips on a near tie moves more; a wrong mask, a missing rotary
    or a dropped route moves the loss by tens of percent (the float32 test
    above is the tight one)."""
    cfg = tiny_cfg()
    cfg["model"]["experts_held"] = [0, 1, 2, 3]
    model16 = tiny_model(cfg, dtype=jnp.bfloat16)
    params = seeded_params(model16, SEQ, std=0.1)
    x, y = tokens(3, SEQ, cfg["vocab_size"])
    (loss, _), grads = jax.jit(jax.value_and_grad(
        make_moe_lm_loss_fn(model16), has_aux=True))(
            params, init_moe_state(model16), (jnp.asarray(x), jnp.asarray(y)))
    with jax.default_matmul_precision("highest"):
        want, want_g = plain_loss_and_grads(plain, cfg, params, x, y)
    np.testing.assert_allclose(loss, want, rtol=0.02)
    norm = lambda t: math.sqrt(sum(  # noqa: E731
        float(jnp.sum(jnp.square(a))) for a in jax.tree_util.tree_leaves(t)))
    diff = jax.tree_util.tree_map(jnp.subtract, grads, want_g)
    assert norm(diff) < 0.1 * norm(want_g)


def test_remat_and_block_size_change_no_number():
    cfg = tiny_cfg()
    x, y = tokens(2, SEQ, cfg["vocab_size"])
    batch = (jnp.asarray(x), jnp.asarray(y))
    losses = []
    for remat, block in [(False, 8), (True, 8), (True, 16), (True, 1024)]:
        model = tiny_model(cfg, remat=remat, block=block)
        params = seeded_params(model, SEQ)
        losses.append(float(make_moe_lm_loss_fn(model)(
            params, init_moe_state(model), batch)[0]))
    np.testing.assert_allclose(losses, losses[0], rtol=1e-6)


# -- through the engine -----------------------------------------------------
@pytest.mark.parametrize("devices", [1, 2])
def test_three_engine_steps_match_the_reference(plain, devices):
    """``engine.train`` for three steps on 1 and on 2 CPU devices (two
    sequences a device), against the reference's ``follow`` on the same
    batches: each step's loss, and the norm of the parameters' change."""
    cfg = tiny_cfg()
    cfg["model"]["experts_held"] = [0, 1]
    model = tiny_model(cfg)
    params = seeded_params(model, SEQ)
    opt = cfg["optimizer"]
    n = 2 * devices
    batches = [tokens(n, SEQ, cfg["vocab_size"], seed=s) for s in range(3)]
    mpi.start(devices=jax.devices()[:devices])
    telemetry.metrics.gauge("tm_moe_max_over_mean_load").set(0.0)
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
        want = plain.follow(cfg, params, batches, groups=devices)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    change = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm((a - b).ravel())),
        engine.params, params)
    np.testing.assert_allclose(
        jax.tree_util.tree_leaves(change),
        jax.tree_util.tree_leaves(want["update_norms"]), rtol=1e-3)
    # the step's measured routing rode the model state, the mean over the
    # devices, and became gauges where the epoch's loss was read
    load = np.asarray(engine.model_state["moe_load"])
    assert load.shape == (4, 2) and 0 < load.sum() <= 4 * 2 * SEQ * 3
    gauges = telemetry.metrics.snapshot()
    value = lambda k: gauges[k]["series"][""]  # noqa: E731
    assert value("tm_moe_held_routes_last_step") == pytest.approx(load.sum())
    assert value("tm_moe_max_over_mean_load") == pytest.approx(
        np.max(load.max(axis=1) / load.mean(axis=1)))
    assert value("tm_moe_routes_per_step") == 2 * SEQ * 3 * 4
    assert value("tm_moe_grouped_rows_per_step") == 2 * SEQ * 3 * 4
    assert value("tm_moe_experts_held") == 2


@pytest.mark.parametrize("crowded", [False, True],
                         ids=["compact", "past_the_tier"])
def test_gauges_say_which_rows_each_layers_products_ran_over(
        monkeypatch, crowded):
    """2 of 8 experts held and 768 routes a layer: a compact tier of 512
    rows. The tier each layer took rides the model state beside the load,
    and becomes gauges where the epoch's loss is read. ``crowded``: a tier
    of 8 rows, which no layer's held routes fit, so every layer takes the
    execution over all its routes."""
    from torchmpi_tpu.parallel import ep

    seq, routes = 128, 2 * 128 * 3
    assert ep.compact_rows(routes, 2, 8) == 512
    if crowded:
        monkeypatch.setattr(ep, "compact_rows", lambda *_: 8)
    cfg = tiny_cfg()
    cfg["model"]["experts_held"] = [0, 1]
    model = tiny_model(cfg)
    mpi.start(devices=jax.devices()[:1])
    engine = AllReduceSGDEngine(
        make_moe_lm_loss_fn(model), seeded_params(model, seq),
        optimizer=optax.sgd(0.01), model_state=init_moe_state(model))
    gauges = lambda: {  # noqa: E731
        k: v["series"].get("") for k, v in
        telemetry.metrics.snapshot().items() if k.startswith("tm_moe_")}
    telemetry.metrics.gauge("tm_moe_compact_layers_last_step").set(-1.0)
    batch = tokens(2, seq, cfg["vocab_size"])
    engine.step(batch)
    # traced, never read: the worst case, every route
    assert gauges()["tm_moe_grouped_rows_per_step"] == 4 * routes
    assert gauges()["tm_moe_compact_layers_last_step"] == -1.0
    engine.train(lambda: iter([batch]), max_epochs=1)
    load = np.asarray(engine.model_state["moe_load"])
    rows = np.asarray(engine.model_state["moe_rows"])
    fits = load.sum(axis=1) <= (8 if crowded else 512)
    assert fits.tolist() == [not crowded] * 4, load.sum(axis=1)
    np.testing.assert_array_equal(rows, np.where(fits, 512, routes))
    assert gauges()["tm_moe_grouped_rows_per_step"] == rows.sum()
    assert gauges()["tm_moe_compact_layers_last_step"] == fits.sum()
    assert gauges()["tm_moe_routes_per_step"] == 4 * routes
    assert gauges()["tm_moe_held_routes_last_step"] == load.sum()


@pytest.mark.parametrize("selecting", [False, True],
                         ids=["full-and-window", "selected"])
def test_model_scopes_nest_under_fwd_bwd_in_the_lowered_step(selecting):
    """The scopes each decoder configuration opens: full and window
    attention, or the indexer, the selection and the attention over it;
    the expert layer's three in both."""
    assert names.MODEL_SCOPE_NAMES == (
        "tm.attn.full", "tm.attn.window", "tm.moe.route", "tm.moe.experts",
        "tm.moe.combine", "tm.attn.index", "tm.attn.select",
        "tm.attn.sparse", "tm.attn.gate", "tm.moe.shared", "tm.moe.dense",
        # every language model's parts (tests/test_model_scopes.py)
        "tm.lm.embed", "tm.lm.norm", "tm.attn.proj", "tm.lm.mlp",
        "tm.moe.router", "tm.lm.head", "tm.lm.loss",
        # the state-space mixer's (tests/test_hybrid_decoder.py)
        "tm.lm.ssm_proj", "tm.lm.ssm_conv", "tm.lm.ssm_scan",
        "tm.lm.ssm_gate",
        # power retention's (tests/test_retention_decoder.py)
        "tm.lm.ret_gate", "tm.lm.ret_chunk", "tm.lm.ret_state",
        # the gated delta rule's (tests/test_deltanet_decoder.py)
        "tm.lm.gdn_proj", "tm.lm.gdn_conv", "tm.lm.gdn_gate",
        "tm.lm.gdn_chunk", "tm.lm.gdn_state",
        # the gated short convolution's (tests/test_sconv_decoder.py)
        "tm.lm.sconv_proj", "tm.lm.sconv")
    cfg = tiny_cfg()
    model = tiny_model(cfg)
    experts = {names.SCOPE_MOE_ROUTE, names.SCOPE_MOE_EXPERTS,
               names.SCOPE_MOE_COMBINE}
    opened = experts | {names.SCOPE_ATTN_FULL, names.SCOPE_ATTN_WINDOW}
    if selecting:
        model = model.clone(
            window_layout=(0,), rope_layout=(1,), selected_layout=(1,),
            index_top_k=9, index_heads=3, index_dim=8)
        opened = experts | {names.SCOPE_ATTN_INDEX, names.SCOPE_ATTN_SELECT,
                            names.SCOPE_ATTN_SPARSE}
    mpi.start(devices=jax.devices()[:1])
    engine = AllReduceSGDEngine(
        make_moe_lm_loss_fn(model), seeded_params(model, SEQ),
        optimizer=optax.sgd(0.1), model_state=init_moe_state(model))
    x, y = tokens(2, SEQ, cfg["vocab_size"])
    text = engine._step_fn.lower(
        engine.params, engine.opt_state, engine.model_state,
        engine._prepare_batch((x, y))).as_text(debug_info=True)
    from benchmark import inner_scopes, scopes

    op_names = set(re.findall(r'"(jit\(tm_train_step\)[^"]*)"', text))
    seen = {}
    for op in op_names:
        inner = inner_scopes.inner_scope_of(op)
        if inner in names.ATTN_MOE_SCOPE_NAMES:
            # the first tm. component is the engine's: fwd_bwd stays whole
            assert scopes.scope_of(op) == "tm.fwd_bwd", op
            seen.setdefault(inner, set()).add("transpose(" in op)
    if selecting and "tm.attn.select" not in seen:
        # what the function opens inside its own derivative rule lies in a
        # nested location of the lowered text, a fragment of its own
        assert '"tm.attn.select/' in text
        seen["tm.attn.select"] = {False}
    assert set(seen) == opened, seen
    # forward and backward alike, seen through jax's wrappers (backward
    # selects nothing: it makes the mask again from the saved thresholds,
    # inside the attention's own scope)
    assert all(kinds == {False, True} for scope, kinds in seen.items()
               if scope != "tm.attn.select"), seen


def test_observe_state_is_called_only_at_an_epochs_loss_read():
    calls = []

    def loss(params, state, batch):
        return jnp.sum(params["w"] * batch[0]), {"n": state["n"] + 1.0}

    loss.observe_state = lambda state: calls.append(float(state["n"]))
    mpi.start(devices=jax.devices()[:1])
    engine = AllReduceSGDEngine(
        loss, {"w": jnp.ones((2,))}, model_state={"n": jnp.zeros(())})
    batch = (np.ones((2, 2), np.float32), np.zeros((2,), np.float32))
    engine.train(lambda: iter([batch] * 3), max_epochs=2)
    assert calls == [3.0, 6.0]


@pytest.mark.parametrize("t,e,held,boost,rows", [
    (T, E, [0, 2, 5, 6, 7], [], T * K),          # one tier
    (BIG_T, BIG_E, [0, 2, 5], [], 1024),         # the compact tier, half idle
    (BIG_T, BIG_E, [0, 2, 7], [0], BIG_T * K),   # some routes past it
])
def test_rows_of_no_group_may_hold_anything(monkeypatch, t, e, held, boost,
                                            rows):
    """On the chip a grouped product leaves the rows that belong to no
    group as it found them, in its result and in the gradient it hands
    back (NaN, in the first run of this layer there). Stand-in: a
    ``ragged_dot`` that poisons exactly those rows, both ways. The layer's
    result and every gradient must come out as with the clean one, in the
    execution over all rows (alone, and as the branch a crowded step
    takes) and in the compact one, whose tier is never full."""
    from torchmpi_tpu.parallel import ep

    real = jax.lax.ragged_dot

    def poison(a, sizes):
        behind = jnp.arange(a.shape[0])[:, None] >= jnp.sum(sizes)
        return jnp.where(behind, jnp.nan, a)

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        return poison(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(saved, g):
        lhs, rhs, sizes = saved
        _, pull = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = pull(g)
        return poison(d_lhs, sizes), d_rhs, None

    poisoned.defvjp(fwd, bwd)
    x, logits, w = expert_inputs(t=t, e=e)
    logits = logits.at[:, boost].add(6.0)  # a second expert on every list

    def run():
        return jax.value_and_grad(
            lambda x, lg, w: (lambda y, load, rows: (jnp.sum(y ** 2), rows))(
                *held_layer(x, lg, w, held, rows=True)),
            argnums=(0, 1, 2), has_aux=True)(x, logits, w)

    want = run()
    assert want[0][1] == rows
    monkeypatch.setattr(
        ep.lax, "ragged_dot",
        lambda lhs, rhs, sizes, **kw: poisoned(lhs, rhs, sizes))
    got = run()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
