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


def expert_inputs(skew=True):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (T, D))
    logits = jax.random.normal(ks[1], (T, E))
    if skew:  # expert 2 is on every token's list, expert 5 on none
        logits = logits.at[:, 2].add(6.0).at[:, 5].add(-60.0)
    w = [0.3 * jax.random.normal(k, s) for k, s in zip(
        ks[2:], [(E, D, F), (E, D, F), (E, F, D)])]
    return x, logits, w


def token_loop(x, logits, w, held):
    x, lg = np.asarray(x, np.float64), np.asarray(logits, np.float64)
    wg, wu, wd = (np.asarray(a, np.float64) for a in w)
    y, load = np.zeros((T, D)), np.zeros(len(held))
    for t in range(T):
        top = np.argsort(-lg[t], kind="stable")[:K]
        gate = np.exp(lg[t][top] - lg[t][top].max())
        gate /= gate.sum()
        for e, g in zip(top, gate):
            if e in held:
                h = np.maximum(x[t] @ wg[e], 0) * (x[t] @ wu[e])
                y[t] += g * (h @ wd[e])
                load[held.index(e)] += 1
    return y, load


def held_layer(x, logits, w, held):
    sel = jnp.asarray(held)
    return moe_local_experts(
        x, logits, K, w[0][sel], w[1][sel], w[2][sel], held)


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


@pytest.mark.parametrize("shares", [
    [[e] for e in range(8)],          # 8 shares of 1 expert
    [[0, 1, 2, 3], [4, 5, 6, 7]],     # 2 shares of 4
    [[6, 1], [0, 7, 3], [2], [5, 4]],  # uneven shares, out of order
])
def test_shares_add_up_to_the_uncut_reference_layer(plain, shares):
    """The share test: what every share computes of the expert layer,
    added, is what the plain reference gives for the layer with all the
    experts (nothing is computed by every share alike here: there is no
    shared expert)."""
    x, logits, w = expert_inputs(skew=False)
    cfg = tiny_cfg()
    cfg["model"]["experts_held"] = list(range(E))
    whole = plain.experts(
        x, logits, {"experts_gate": w[0], "experts_up": w[1],
                    "experts_down": w[2]},
        {**cfg, "moe_num_active_primary_experts": K}, "float32")
    parts, loads = zip(*(held_layer(x, logits, w, held) for held in shares))
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


def test_model_scopes_nest_under_fwd_bwd_in_the_lowered_step():
    assert names.MODEL_SCOPE_NAMES == (
        "tm.attn.full", "tm.attn.window", "tm.moe.route", "tm.moe.experts",
        "tm.moe.combine")
    cfg = tiny_cfg()
    model = tiny_model(cfg)
    mpi.start(devices=jax.devices()[:1])
    engine = AllReduceSGDEngine(
        make_moe_lm_loss_fn(model), seeded_params(model, SEQ),
        optimizer=optax.sgd(0.1), model_state=init_moe_state(model))
    x, y = tokens(2, SEQ, cfg["vocab_size"])
    text = engine._step_fn.lower(
        engine.params, engine.opt_state, engine.model_state,
        engine._prepare_batch((x, y))).as_text(debug_info=True)
    from benchmark import inner_scopes, scopes

    op_names = set(re.findall(r'"(jit\(tm_step\)[^"]*)"', text))
    seen = {}
    for op in op_names:
        inner = inner_scopes.inner_scope_of(op)
        if inner is not None:
            # the first tm. component is the engine's: fwd_bwd stays whole
            assert scopes.scope_of(op) == "tm.fwd_bwd", op
            seen.setdefault(inner, set()).add("transpose(" in op)
    assert set(seen) == set(names.MODEL_SCOPE_NAMES), seen
    # forward and backward alike, seen through jax's wrappers
    assert all(kinds == {False, True} for kinds in seen.values()), seen


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


def test_rows_of_no_group_may_hold_anything(monkeypatch):
    """On the chip a grouped product leaves the rows that belong to no
    group as it found them, in its result and in the gradient it hands
    back (NaN, in the first run of this layer there). Stand-in: a
    ``ragged_dot`` that poisons exactly those rows, both ways. The layer's
    result and every gradient must come out as with the clean one."""
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
    x, logits, w = expert_inputs()
    held = [0, 2, 5]

    def run():
        return jax.value_and_grad(
            lambda x, lg, w: jnp.sum(held_layer(x, lg, w, held)[0] ** 2),
            argnums=(0, 1, 2))(x, logits, w)

    want = run()
    monkeypatch.setattr(
        ep.lax, "ragged_dot",
        lambda lhs, rhs, sizes, **kw: poisoned(lhs, rhs, sizes))
    got = run()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
