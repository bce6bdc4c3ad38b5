"""The retentive decoder (models/retentive.py: power retention of degree 2
where attention stood, in every block) and its sequence operation
(parallel/retention.py) against plain arithmetic: the recurrence itself, a
loop over positions with the symmetric state, and the benchmark's plain
float32 reference of the configuration that runs them
(``benchmark/reference/brumby-14b.py``, loaded by path, which imports
nothing of the program and computes retention in its attention form, every
causal pair). Tiny sizes that keep what matters: 5 query heads to a KV
head of 32 (two blocks of the symmetric square), a sequence that is no
multiple of the chunk, gates that forget over a few positions to a few
dozen."""

import importlib.util
import json
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
    RetentionDecoder,
    RetentionDecoderBlock,
    init_lm_params,
    make_lm_loss_fn,
)
from torchmpi_tpu.parallel import power_retention, symmetric_square
from torchmpi_tpu.telemetry import names

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
CONFIG = "brumby-14b"
SEQ, CHUNK = 37, 8  # four chunks and five positions of a fifth
RET = ("tm.lm.ret_gate", "tm.lm.ret_chunk", "tm.lm.ret_state")


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


def tiny_cfg(shares=1):
    """The published keys at test sizes, as the reference reads them: the
    whole layer (8 KV heads with 5 query heads each, 32 columns), or what
    one of ``shares`` = 8 chips holds of it."""
    return {
        "hidden_size": 32, "head_dim": 32, "num_hidden_layers": 2,
        "num_attention_heads": 40 // shares,
        "num_key_value_heads": 8 // shares,
        "rms_norm_eps": 1e-6, "rope_theta": 1e6, "vocab_size": 61,
        "model": {"dense_columns_held": 32 // shares, "retention_eps": 1e-12,
                  "retention_chunk": CHUNK},
        "optimizer": {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9,
                      "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01},
    }


def sizes_of(cfg, **over):
    return {**dict(
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_width=cfg["model"]["dense_columns_held"],
        chunk=cfg["model"]["retention_chunk"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], eps=cfg["model"]["retention_eps"]),
        **over}


def tiny_model(cfg, **over):
    return RetentionDecoder(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], **sizes_of(cfg, remat=True, **over))


def seeded(shapes, seed=0, std=0.3):
    """Seeded normal weights large enough that the scores, the gates and
    the feed-forward are far from flat; the gates' biases so that a state
    lasts a few positions to a few dozen; the norms' scales off 1."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def leaf(path, s, k):
        name = str(getattr(path[-1], "key", ""))
        if name == "scale":
            return 1.0 + std * jax.random.normal(k, s.shape, jnp.float32)
        if name == "bias":
            return jax.random.uniform(k, s.shape, minval=0.5, maxval=4.0)
        return std * jax.random.normal(k, s.shape, jnp.float32)

    return treedef.unflatten(
        [leaf(p, s, k) for (p, s), k in zip(leaves, keys)])


def tokens(n, seq, vocab, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, vocab, size=(n, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


# -- parallel/retention.py against the recurrence and the attention form -------
def by_pairs(x):
    """The symmetric square written out: ``x_a x_b`` for every ``a <= b``,
    times ``sqrt 2`` off the diagonal."""
    a, b = np.triu_indices(x.shape[-1])
    return x[..., a] * x[..., b] * np.where(a == b, 1.0, math.sqrt(2.0))


def recurrence(q, k, v, log_g, eps=1e-12):
    """One sequence, a step a position: ``S_t = e^{g_t} S_{t-1} + phi(k_t)
    (x) [v_t | 1]``, ``[num | den] = phi(q_t)^T S_t``. q: [t, kv, group, d];
    k, v: [t, kv, d]; log_g: [t, kv]."""
    kv, d = k.shape[1:]
    scale = d ** -0.25

    def step(state, now):
        q_t, k_t, v_t, g_t = now
        fed = jnp.concatenate([v_t, jnp.ones((kv, 1))], axis=-1)
        state = jnp.exp(g_t)[:, None, None] * state \
            + by_pairs(scale * k_t)[:, :, None] * fed[:, None, :]
        out = jnp.einsum("hgf,hfv->hgv", by_pairs(scale * q_t), state)
        return state, out[..., :d] / (out[..., d:] + eps)

    _, y = jax.lax.scan(
        step, jnp.zeros((kv, d * (d + 1) // 2, d + 1)), (q, k, v, log_g))
    return y


def retention_inputs(t, kv=2, group=5, d=32, batch=2):
    k = jax.random.split(jax.random.PRNGKey(t), 4)
    return (jax.random.normal(k[0], (batch, t, kv * group, d)),
            jax.random.normal(k[1], (batch, t, kv, d)),
            jax.random.normal(k[2], (batch, t, kv, d)),
            2.0 + jax.random.normal(k[3], (batch, t, kv)))   # the gate, raw


def test_the_symmetric_square_squares_the_dot_product():
    from torchmpi_tpu.parallel.retention import BLOCK, features

    x, y = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 128))
    phi = symmetric_square(x)
    # 36 pairs of blocks of 16, 256 features each: the 8,256 pairs a <= b,
    # of which the 8 x 120 inside a diagonal block stand twice
    assert (BLOCK, features(128)) == (16, 9216) and phi.shape == (3, 9216)
    assert features(128) - 8256 == 8 * 120
    np.testing.assert_allclose(
        jnp.sum(phi * symmetric_square(y), -1), jnp.sum(x * y, -1) ** 2,
        rtol=1e-5)
    # one block is the symmetric square written out, feature by feature
    np.testing.assert_allclose(
        np.sort(symmetric_square(x[:, :8], block=1), -1),
        np.sort(by_pairs(np.asarray(x[:, :8])), -1), rtol=1e-6)
    # the same features whatever the block, up to the pairs that stand
    # twice: the squared norm is |x|^4 for each
    for block in (1, 4, 8, 128):
        np.testing.assert_allclose(
            jnp.sum(symmetric_square(x, block) ** 2, -1),
            jnp.sum(x * x, -1) ** 2, rtol=1e-5)
    with pytest.raises(ValueError, match="blocks of 16 divide"):
        symmetric_square(x[:, :24])


@pytest.mark.parametrize("t,chunk", [(37, 8), (8, 8), (5, 8)],
                         ids=["no-multiple", "one-chunk", "part-of-a-chunk"])
def test_the_chunked_form_is_the_recurrence_and_the_attention_form(
        plain, t, chunk):
    """Values and the gradients of ``q``, ``k``, ``v`` and the gate: the
    chunked form against the recurrence, a step a position, AND against the
    reference's attention form over every causal pair; 5 query heads read
    each of 2 KV heads."""
    args = retention_inputs(t)
    kv, d = args[1].shape[2:]

    def chunked(q, k, v, raw):
        return power_retention(
            q, k, v, jax.nn.log_sigmoid(raw), chunk=chunk)

    def by_sequence(one):
        def run(q, k, v, raw):
            return jax.vmap(lambda q, k, v, raw: one(
                q.reshape(t, kv, -1, d), k, v, jax.nn.log_sigmoid(raw)
            ).reshape(q.shape))(q, k, v, raw)
        return run

    both = lambda fn: jax.jit(lambda *a: (fn(*a), jax.grad(  # noqa: E731
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3))(*a)))
    with jax.default_matmul_precision("highest"):
        got, grads = both(chunked)(*args)
        wants = [both(by_sequence(lambda *a: plain.retention(
            *a, 1e-12, "float32")))(*args)]
    # the recurrence in float64: through ``phi`` a small ``(q . k)^2`` is a
    # sum of 36 large terms that cancel, which float32 does not hold
    with jax.enable_x64(True):
        wants.append(both(by_sequence(recurrence))(
            *(np.asarray(a, np.float64) for a in args)))
    assert got.shape == args[0].shape and got.dtype == jnp.float32
    for want, want_grads in wants:
        assert np.max(np.abs(want)) > 1.0
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        for g, w in zip(grads, want_grads):
            top = float(np.max(np.abs(w)))
            assert top > 1e-2
            # (the first positions' normalisers have few terms: where all
            # are small the gradient is large and turns on the scores' last
            # digits; position 0's one term, with an eps near it, most)
            np.testing.assert_allclose(g, w, atol=5e-5 * max(1.0, top))


def test_the_products_take_the_stated_dtype_and_shapes_are_checked():
    """bfloat16 operands, float32 sums: near the float32 result, not equal
    to it, and float32 out."""
    q, k, v, raw = retention_inputs(40)
    run = lambda dtype: jax.jit(lambda: power_retention(  # noqa: E731
        q, k, v, jax.nn.log_sigmoid(raw), chunk=8, dtype=dtype))()
    exact, rounded = run(jnp.float32), run(jnp.bfloat16)
    assert rounded.dtype == jnp.float32
    gap = float(jnp.max(jnp.abs(exact - rounded)) / jnp.max(jnp.abs(exact)))
    assert 1e-4 < gap < 5e-2, gap
    with pytest.raises(ValueError, match="multiple of the 3 KV heads"):
        power_retention(q, k[:, :, :1].repeat(3, 2), v[:, :, :1].repeat(3, 2),
                        raw[:, :, :1].repeat(3, 2))


def test_no_array_holds_the_symmetric_square_of_every_position():
    """Forward and backward of the operation, lowered: ``phi`` (last axis
    ``D``) is made a chunk at a time, so the largest array with that axis is
    the chunks' states, not ``t x D``; and no ``t x t`` array."""
    from torchmpi_tpu.parallel.retention import features as features_of

    t, chunk, d = 64, 16, 32
    features = features_of(d)   # three pairs of two blocks: 768
    q, k, v, raw = retention_inputs(t, kv=1, batch=1)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(power_retention(
        *a[:3], jax.nn.log_sigmoid(a[3]), chunk=chunk)),
        argnums=(0, 1, 2, 3))).lower(q, k, v, raw).as_text()
    shapes = [tuple(int(n) for n in dims.split("x"))
              for dims in re.findall(r"tensor<((?:\d+x)+)f32>", text)
              for dims in [dims.rstrip("x")]]
    wide = [s for s in shapes if s[-1] == features]
    # a chunk's keys [16, 768], its five query heads' [5, 16, 768], the four
    # chunks' states [4, 33, 768]: all under 5 x 64 x 768
    assert wide and max(math.prod(s) for s in wide) == 4 * (d + 1) * features
    assert max(math.prod(s) for s in wide) < t * features * 5
    assert not [s for s in wide if t in s]
    assert not [s for s in shapes if s[-2:] == (t, t)]


# -- the block against the reference's layer -----------------------------------
def whole_layer(cfg, seed=3):
    """(the uncut layer's block, its seeded parameters, an input)."""
    block = RetentionDecoderBlock(**sizes_of(cfg))
    x = jax.random.normal(jax.random.PRNGKey(7), (1, SEQ, cfg["hidden_size"]))
    shapes = jax.eval_shape(
        lambda: block.init(jax.random.PRNGKey(0), x))["params"]
    return block, seeded(shapes, seed=seed), x


def plain_layer(plain, cfg, p, h):
    """(the reference's layer of ``h``, (its retention's and, of ``h``
    itself, its feed-forward's part))."""
    @jax.jit
    def run(p, h):
        u = plain.rms_norm(h, p["norm_ret"]["scale"], cfg["rms_norm_eps"])
        return plain.layer(h, p, cfg, "float32"), (
            plain.retention_part(u, p, cfg, "float32"),
            plain.feed_forward_part(h, p, cfg, "float32"))

    return run(p, h)


def test_the_block_is_the_references_layer(plain):
    cfg = tiny_cfg()
    block, p, x = whole_layer(cfg)
    with jax.default_matmul_precision("highest"):
        want, parts = plain_layer(plain, cfg, p, x[0])
        got = jax.jit(block.apply)({"params": p}, x)[0]
    # each part is there: retention and the feed-forward
    assert all(float(jnp.max(jnp.abs(part))) > 0.02 for part in parts)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=2e-5)


# -- the shares add up ---------------------------------------------------------
def share_of(p, cfg, s, shares=8):
    """What chip ``s`` of 8 holds of the layer's parameters: KV head ``s``
    with its 5 query heads and its gate (columns of q, k, v and the gate,
    the gate's bias, rows of o), 4 of the 32 columns of the feed-forward;
    the norms, the heads' too, whole."""
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    dim = cfg["head_dim"]
    q = np.arange(s * group * dim, (s + 1) * group * dim)
    kv = np.arange(s * dim, (s + 1) * dim)
    n = p["mlp_up"]["kernel"].shape[1] // shares
    mine = slice(s * n, (s + 1) * n)
    return {**p,
            "q": {"kernel": p["q"]["kernel"][:, q]},
            "k": {"kernel": p["k"]["kernel"][:, kv]},
            "v": {"kernel": p["v"]["kernel"][:, kv]},
            "gate": {"kernel": p["gate"]["kernel"][:, s:s + 1],
                     "bias": p["gate"]["bias"][s:s + 1]},
            "o": {"kernel": p["o"]["kernel"][q]},
            "mlp_gate": {"kernel": p["mlp_gate"]["kernel"][:, mine]},
            "mlp_up": {"kernel": p["mlp_up"]["kernel"][:, mine]},
            "mlp_down": {"kernel": p["mlp_down"]["kernel"][mine]}}


def silenced(p, name):
    """``p`` with the matrix ``name`` zero: that sublayer adds nothing to
    the residual stream."""
    return {**p, name: {"kernel": jnp.zeros_like(p[name]["kernel"])}}


def test_the_eight_shares_add_up_to_the_uncut_layer(plain):
    """The deployment in small: 8 chips share a layer of 8 KV heads with 5
    query heads each and 32 columns. Chip ``s`` holds KV head ``s``, its
    query heads, its gate and 4 columns; each KV head's retention is
    counted once. The summed ``o`` and feed-forward partials are the uncut
    reference's layer."""
    cfg, held = tiny_cfg(), tiny_cfg(shares=8)
    _, p, x = whole_layer(cfg)
    alone = jax.jit(RetentionDecoderBlock(**sizes_of(held)).apply)
    with jax.default_matmul_precision("highest"):
        want, (want_ret, _) = plain_layer(plain, cfg, p, x[0])
        ret = sum(
            alone({"params": silenced(share_of(p, cfg, s), "mlp_down")}, x)
            - x for s in range(8))
        np.testing.assert_allclose(ret[0], want_ret, atol=3e-5, rtol=2e-5)
        # a share alone is not the layer's
        one = alone({"params": silenced(share_of(p, cfg, 0), "mlp_down")}, x)
        assert float(jnp.max(jnp.abs((one - x)[0] - want_ret))) > 1e-2
        # the feed-forward's columns read the summed x' = x + retention
        mixed = x + ret
        out = mixed + sum(
            alone({"params": silenced(share_of(p, cfg, s), "o")}, mixed)
            - mixed for s in range(8))
    np.testing.assert_allclose(out[0], want, atol=4e-5, rtol=2e-5)


# -- the decoder against the plain reference ---------------------------------
def plain_loss_and_grads(plain, cfg, params, x, y):
    row = jax.jit(jax.value_and_grad(
        lambda p, xi, yi: plain.loss_fn(p, xi, yi, cfg, "float32")))
    rows = [row(params, jnp.asarray(x[i]), jnp.asarray(y[i]))
            for i in range(len(x))]
    loss = sum(r[0] for r in rows) / len(rows)
    grads = jax.tree_util.tree_map(
        lambda *g: sum(g) / len(rows), *[r[1] for r in rows])
    return loss, grads


@pytest.mark.parametrize("shares", [1, 8], ids=["whole", "a-share"])
def test_decoder_loss_and_gradients_match_the_plain_reference(plain, shares):
    cfg = tiny_cfg(shares)
    model = tiny_model(cfg)
    params = seeded(jax.eval_shape(lambda: init_lm_params(model, SEQ)))
    assert {"q", "k", "v", "o", "q_norm", "k_norm", "gate", "mlp_gate",
            "mlp_up", "mlp_down", "norm_ret", "norm_mlp"} == set(
                params["RetentionDecoderBlock_0"])
    assert set(params["RetentionDecoderBlock_0"]["gate"]) == {
        "kernel", "bias"}  # the layer's one bias
    x, y = tokens(2, SEQ, cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(make_lm_loss_fn(model)))(
            params, (x, y))
        want_loss, want = plain_loss_and_grads(plain, cfg, params, x, y)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(want)
    for (path, w), g in zip(flat, jax.tree_util.tree_leaves(grads)):
        assert float(jnp.max(jnp.abs(w))) > 1e-6, path  # every leaf learns
        # (the normaliser's small values at a sequence's first positions
        # carry float32's rounding into the fourth digit of q's and k's)
        np.testing.assert_allclose(
            g, w, atol=3e-4 * max(1.0, float(jnp.max(jnp.abs(w)))),
            err_msg=str(path))


def test_the_default_gates_remember_dozens_to_thousands_of_positions():
    cfg = tiny_cfg()
    p = jax.jit(lambda: init_lm_params(tiny_model(cfg), SEQ))()[
        "RetentionDecoderBlock_1"]
    kept = 1 / (1 + np.exp(-np.asarray(p["gate"]["bias"], np.float64)))
    assert np.all((kept > 1 - 1 / 63.9) & (kept < 1 - 1 / 8193))
    assert float(jnp.std(p["gate"]["kernel"])) < 0.02 / math.sqrt(32) * 1.5
    np.testing.assert_array_equal(p["q_norm"]["scale"], 1.0)
    assert p["q_norm"]["scale"].shape == (cfg["head_dim"],)


def test_two_engine_steps_match_the_reference_and_set_the_gauges(plain):
    """``engine.train`` for two AdamW steps against the reference's
    ``follow`` on the same batches: each step's loss, the first moment's
    and the parameters' change leaf by leaf; and what of its layers the
    device holds, as the gauges say it. No model state: ``loss_fn(params,
    batch)``, as GPT-2's."""
    cfg = tiny_cfg(shares=8)
    model = tiny_model(cfg)
    params = seeded(jax.eval_shape(lambda: init_lm_params(model, SEQ)))
    opt = cfg["optimizer"]
    batches = [tokens(2, SEQ, cfg["vocab_size"], seed=s) for s in range(2)]
    mpi.start(devices=jax.devices()[:1])
    engine = AllReduceSGDEngine(
        make_lm_loss_fn(model), params,
        optimizer=optax.adamw(
            opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
            eps=opt["eps"], weight_decay=opt["weight_decay"]))
    losses = []
    engine.hooks = {"on_update": lambda s: losses.append(float(s["loss"]))}
    with jax.default_matmul_precision("highest"):
        engine.train(lambda: iter(batches), max_epochs=1)
        want = plain.follow(cfg, params, batches, moment_after=2)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    norm = lambda a: float(jnp.linalg.norm(a.ravel()))  # noqa: E731
    np.testing.assert_allclose(
        jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda a, b: norm(a - b), engine.params, params)),
        jax.tree_util.tree_leaves(want["update_norms"]), rtol=1e-3)
    np.testing.assert_allclose(
        jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            norm, engine.opt_state[0].mu)),
        jax.tree_util.tree_leaves(want["moment_norms"]), rtol=1e-3)
    gauges = telemetry.metrics.snapshot()
    value = lambda k: gauges[k]["series"][""]  # noqa: E731
    assert value(names.GAUGE_RETENTION_KV_HEADS_HELD) == 2 * 1  # layers x held
    # layers x sequences x chunks a sequence: 37 positions are 5 chunks of 8
    assert value(names.GAUGE_RETENTION_CHUNKS) == 2 * 2 * 5
    # layers x sequences x KV heads x features x (d + 1) x 4: heads of 32
    # are two blocks, three pairs of them, 768 features
    assert value(names.GAUGE_RETENTION_STATE_BYTES) == (
        2 * 2 * 1 * 768 * 33 * 4)


def test_the_retentions_scopes_nest_under_fwd_bwd_in_the_lowered_step():
    """The three ``tm.lm.ret_*`` scopes, q, k, v and o under
    ``tm.attn.proj``, the feed-forward under ``tm.lm.mlp``: each reaches
    forward, the recomputed block and backward, seen by the benchmark's
    reader as a bucket of its own; no attention's and no mixer's scope."""
    from benchmark import model_scopes, scopes

    cfg = tiny_cfg(shares=8)
    model = tiny_model(cfg)
    mpi.start(devices=jax.devices()[:1])
    engine = AllReduceSGDEngine(
        make_lm_loss_fn(model),
        seeded(jax.eval_shape(lambda: init_lm_params(model, SEQ))),
        optimizer=optax.sgd(0.1))
    x, y = tokens(2, SEQ, cfg["vocab_size"])
    # the COMPILED step's op_names: an operation inside the scan's body
    # bears its whole path there (the lowered text names it from the body's
    # own function on)
    text = engine._step_fn.lower(
        engine.params, engine.opt_state, engine.model_state,
        engine._prepare_batch((x, y))).compile().as_text()
    seen = {}
    for op in set(re.findall(r'op_name="(jit\(tm_train_step\)[^"]*)"', text)):
        bucket = model_scopes.bucket_of(op)
        if bucket not in (None, model_scopes.UNNAMED):
            assert scopes.scope_of(op) == "tm.fwd_bwd", op
            seen.setdefault(bucket, set()).add(model_scopes.phase_of(op))
            # no scope lies inside another, but the chunk's and the gate's
            # inside the state's, which holds the scan over the chunks
            inner = model_scopes.BUCKET.findall(op.split(
                "rematted_computation")[-1].split("transpose(")[-1])
            assert len(inner) <= 1 or (
                inner[0] == "tm.lm.ret_state" and len(inner) == 2
                and inner[1] in RET[:2]), op
    assert names.RETENTION_SCOPE_NAMES == RET
    assert set(seen) == set(RET) | {
        "tm.lm.embed", "tm.lm.norm", "tm.attn.proj", "tm.lm.mlp",
        "tm.lm.head", "tm.lm.loss"}, seen
    for scope in RET + ("tm.lm.mlp", "tm.attn.proj"):
        assert seen[scope] == set(model_scopes.PHASES), (scope, seen[scope])


@pytest.mark.parametrize("phase,wrap", [
    ("forward", "jvp(RetentionDecoder)/RetentionDecoderBlock_2/"),
    ("recompute", "transpose(jvp(RetentionDecoder))/tm.fwd_bwd/jvp("
     "RetentionDecoder)/checkpoint/rematted_computation/"
     "RetentionDecoderBlock_2/"),
    ("backward", "transpose(jvp(RetentionDecoder))/RetentionDecoderBlock_2/"),
])
def test_an_operation_of_the_state_has_a_bucket_of_its_own(phase, wrap):
    from benchmark import model_scopes

    op = ("jit(tm_train_step)/shard_map/tm.fwd_bwd/" + wrap
          + "tm.lm.ret_state/while/body/closed_call/checkpoint/")
    assert model_scopes.bucket_of(op + "dot_general") == "tm.lm.ret_state"
    assert model_scopes.phase_of(op + "dot_general") == phase
    # the chunk's own products, opened inside the state's loop: the
    # innermost name takes them
    assert model_scopes.bucket_of(
        op + "tm.lm.ret_chunk/dot_general") == "tm.lm.ret_chunk"
    # the loop's own plumbing is the state's
    assert model_scopes.bucket_of(
        op[:op.index("closed_call")] + "dynamic_update_slice"
    ) == "tm.lm.ret_state"


# -- the benchmark's configuration ---------------------------------------------
def test_the_file_keeps_every_catalog_number():
    """Every number of the catalog's entry under its own key, but those
    that are cut, which ``reduced`` and ``published`` name: counts of
    layers, rows and heads, never a width."""
    cfg = json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    catalog = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
    }
    for key, value in catalog.items():
        assert cfg[key] == value and key not in cfg["reduced"], key
    cut = {"num_hidden_layers": (4, 40), "vocab_size": (18992, 151936),
           "num_attention_heads": (5, 40), "num_key_value_heads": (1, 8)}
    assert cfg["reduced"] == list(cut)
    for key, (here, published) in cut.items():
        assert cfg[key] == here and cfg["published"][key] == published, key
    assert set(cfg) - set(catalog) - set(cut) == {
        "name", "source", "published", "deployment", "model",
        "sequence_length", "compute_dtype", "param_dtype", "optimizer",
        "init", "per_chip_batch", "remat", "reduced", "assumed",
        "rehearsal", "limits", "limits_from"}
    # what is held of each width is the share's, an eighth, and stands
    # under ``model``; the chunk is the program's
    assert cfg["model"] == {
        "dense_columns_held": 17408 // 8, "kv_head_held": 0,
        "retention_chunk": 256, "retention_degree": 2,
        "retention_eps": 1e-12}
    assert cfg["vocab_size"] * 8 == 151936
    assert cfg["sequence_length"] == cfg["max_position_embeddings"]
    assert {"layer", "degree", "gate", "gate_bias", "scale", "normaliser",
            "heads", "chunk"} <= set(cfg["assumed"])
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/"
        "config.json")
    # the catalog row itself, where the guides are installed
    rows = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if rows.is_file():
        row = next(r for r in map(json.loads, rows.read_text().splitlines())
                   if r["name"] == "Brumby-14B-Base")
        assert row["source_url"] == cfg["source"]
        for key, value in row["config"].items():
            assert cfg[key] == (cut[key][0] if key in cut else value), key
            assert key not in cut or cfg["published"][key] == value
    tiny = cfg["rehearsal"]
    assert tiny["sequence_length"] % tiny["model"]["retention_chunk"]
    assert tiny["num_attention_heads"] == 5 * tiny["num_key_value_heads"]
    # the worst leaf is not judged (the file's rehearsal.why says why)
    assert "moment_norm_gap" not in tiny["limits"]["stream"]


def test_flops_of_the_configuration_are_the_issues_arithmetic():
    from benchmark import configs, retention_decoder_flops as flops

    count = lambda **over: (  # noqa: E731
        flops.retention_decoder_forward_flops(**{**dict(
            seq=32768, d_model=5120, layers=4, heads=5, kv_heads=1,
            head_dim=128, mlp_columns=2176, vocab=18992), **over}))
    forward = count()
    assert 19.0e12 < forward < 19.05e12          # ISSUE 41: 19.0 T forward
    cfg = configs.load(CONFIG)
    built = configs.build(CONFIG, cfg)
    seq = cfg["sequence_length"]
    assert built.flops_per_sample == 3 * count(seq=seq)
    t, d = 32768, 5120
    # each part by itself: the head 34 %, the feed-forward 46 %, ...
    assert forward - count(vocab=0) == 2 * t * d * 18992
    assert 0.33 < (forward - count(vocab=0)) / forward < 0.34
    assert forward - count(mlp_columns=0) == 4 * 6 * t * d * 2176
    assert 0.455 < (forward - count(mlp_columns=0)) / forward < 0.465
    # retention by the recurrence, with the symmetric state: 13.9 MFLOP a
    # token and layer, 9.6 % of the whole
    assert flops.state_features(128) == 8256
    ret = flops.retention_forward_flops(t, 5, 1, 128)
    assert ret == t * (3 * 8256 * 129 + 5 * 2 * 8256 * 129 + 6 * 8256)
    assert 13.8e6 < ret / t < 14.0e6
    assert 0.095 < 4 * ret / forward < 0.097
    # a KV head more with its 5 query heads and its gate
    assert count(heads=10, kv_heads=2) - forward == 4 * (
        2 * t * d * (640 + 128 + 128 + 1) + 2 * t * 640 * d + ret)
    # the chunk is no argument: the count cannot move with the chunking
    assert "chunk" not in (
        flops.retention_decoder_forward_flops.__code__.co_varnames
        + flops.retention_forward_flops.__code__.co_varnames)
