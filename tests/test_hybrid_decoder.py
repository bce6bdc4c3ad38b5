"""The hybrid decoder (models/hybrid.py: a state-space mixer beside
attention in every block, the Falcon-H1 family's multipliers) and the
mixer's sequence operations (parallel/ssm.py) against plain arithmetic: a
loop, and the benchmark's plain float32 reference of the configuration that
runs them (``benchmark/reference/falcon-h1-34b.py``, loaded by path, which
imports nothing of the program and computes the scan as the recurrence
itself). Tiny sizes that keep what matters: 8 mixer heads in 2 groups, 8
query to 4 KV heads, a sequence that is no multiple of the chunk, every
multiplier off 1."""

import importlib.util
import json
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
    HybridDecoder,
    HybridDecoderBlock,
    Multipliers,
    init_lm_params,
    make_lm_loss_fn,
)
from torchmpi_tpu.parallel import (
    causal_conv1d,
    gated_group_norm,
    ssd_chunked_scan,
)
from torchmpi_tpu.telemetry import names

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
CONFIG = "falcon-h1-34b"
SEQ, CHUNK = 37, 8  # four chunks and five positions of a fifth


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
    whole layer (8 query to 4 KV heads, 8 mixer heads in 2 groups, 32
    columns), or what one of ``shares`` = 8 chips holds of it."""
    return {
        "hidden_size": 32, "head_dim": 8, "num_hidden_layers": 2,
        "num_attention_heads": 8 if shares == 1 else 2,
        "num_key_value_heads": 4 if shares == 1 else 1,
        "mamba_n_heads": 8 // shares, "mamba_n_groups": 2 if shares == 1
        else 1, "mamba_d_head": 8, "mamba_d_state": 6, "mamba_d_conv": 4,
        "mamba_chunk_size": CHUNK, "rms_norm_eps": 1e-5, "rope_theta": 1e11,
        "vocab_size": 61,
        "embedding_multiplier": 5.656854249492381,
        "lm_head_multiplier": 0.6, "key_multiplier": 0.7,
        "attention_in_multiplier": 0.9, "attention_out_multiplier": 0.4,
        "ssm_in_multiplier": 0.25, "ssm_out_multiplier": 0.3,
        "ssm_multipliers": [0.35, 0.25, 0.18, 0.5, 0.36],
        "mlp_multipliers": [0.18, 0.4],
        "model": {"dense_columns_held": 32 // shares},
        "optimizer": {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9,
                      "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01},
    }


def multipliers_of(cfg):
    return Multipliers(
        embedding=cfg["embedding_multiplier"],
        lm_head=cfg["lm_head_multiplier"], key=cfg["key_multiplier"],
        attention_in=cfg["attention_in_multiplier"],
        attention_out=cfg["attention_out_multiplier"],
        ssm_in=cfg["ssm_in_multiplier"], ssm_out=cfg["ssm_out_multiplier"],
        ssm=tuple(cfg["ssm_multipliers"]), mlp=tuple(cfg["mlp_multipliers"]))


def sizes_of(cfg, **over):
    return {**dict(
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_groups=cfg["mamba_n_groups"], ssm_state=cfg["mamba_d_state"],
        mlp_width=cfg["model"]["dense_columns_held"],
        multipliers=multipliers_of(cfg), conv_width=cfg["mamba_d_conv"],
        chunk=cfg["mamba_chunk_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], attn_block=8), **over}


def tiny_model(cfg, **over):
    return HybridDecoder(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], **sizes_of(cfg, remat=True, **over))


def seeded(shapes, seed=0, std=0.3):
    """Seeded normal weights large enough that attention, the gates and
    the decays are far from uniform; ``A_log`` and ``dt_bias`` so that a
    state lasts a few positions to a few dozen."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def leaf(path, s, k):
        name = str(getattr(path[-1], "key", ""))
        if name == "scale":
            return jnp.ones(s.shape, jnp.float32)
        if name == "A_log":
            return jnp.log(jax.random.uniform(k, s.shape, minval=1., maxval=8.))
        if name == "dt_bias":
            return jax.random.uniform(k, s.shape, minval=-3.0, maxval=0.0)
        if name in ("D", "ssm_norm"):
            return 1.0 + std * jax.random.normal(k, s.shape, jnp.float32)
        return std * jax.random.normal(k, s.shape, jnp.float32)

    return treedef.unflatten(
        [leaf(p, s, k) for (p, s), k in zip(leaves, keys)])


def tokens(n, seq, vocab, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, vocab, size=(n, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


# -- parallel/ssm.py against loops --------------------------------------------
def scan_inputs(t, heads=4, groups=2, p=8, n=6, batch=2):
    k = jax.random.split(jax.random.PRNGKey(t), 6)
    return (jax.random.normal(k[0], (batch, t, heads, p)),
            jax.random.normal(k[1], (batch, t, heads)),      # dt, raw
            jax.random.normal(k[2], (heads,)),               # A_log
            jax.random.normal(k[3], (batch, t, groups, n)),
            jax.random.normal(k[4], (batch, t, groups, n)),
            jax.random.normal(k[5], (heads,)))


@pytest.mark.parametrize("t,chunk", [(37, 8), (8, 8), (5, 8)],
                         ids=["no-multiple", "one-chunk", "part-of-a-chunk"])
def test_the_chunked_scan_is_the_recurrence(plain, t, chunk):
    """Values and the gradients of ``x``, ``dt``, ``A_log``, ``B``, ``C``
    and ``D``: the chunked dual against the reference's ``lax.scan`` over
    time."""
    args = scan_inputs(t)

    def chunked(x, dt, a_log, b, c, d):
        return ssd_chunked_scan(
            x, jax.nn.softplus(dt), -jnp.exp(a_log), b, c, d, chunk=chunk)

    def recurrence(x, dt, a_log, b, c, d):
        return jax.vmap(lambda x, dt, b, c: plain.selective_scan(
            x, jax.nn.softplus(dt), -jnp.exp(a_log), b, c, d, "float32"),
        )(x, dt, b, c)

    both = lambda fn: jax.jit(lambda *a: (fn(*a), jax.grad(  # noqa: E731
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=tuple(range(6)))(*a)))
    with jax.default_matmul_precision("highest"):
        (want, want_grads), (got, grads) = (
            both(recurrence)(*args), both(chunked)(*args))
    assert got.shape == args[0].shape and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(want))) > 1.0
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    for g, w in zip(grads, want_grads):
        assert float(jnp.max(jnp.abs(w))) > 1e-2
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=1e-4)


def test_the_scans_products_take_the_stated_dtype():
    """bfloat16 operands, float32 sums: near the float32 result, not equal
    to it, and float32 out."""
    x, dt, a_log, b, c, d = scan_inputs(40)
    run = lambda dtype: jax.jit(lambda: ssd_chunked_scan(  # noqa: E731
        x, jax.nn.softplus(dt), -jnp.exp(a_log), b, c, d, chunk=8,
        dtype=dtype))()
    exact, rounded = run(jnp.float32), run(jnp.bfloat16)
    assert rounded.dtype == jnp.float32
    gap = float(jnp.max(jnp.abs(exact - rounded)) / jnp.max(jnp.abs(exact)))
    assert 1e-4 < gap < 3e-2, gap
    with pytest.raises(ValueError, match="multiple of the 3 groups"):
        ssd_chunked_scan(x, dt, a_log, b[:, :, :1].repeat(3, 2),
                         c[:, :, :1].repeat(3, 2), d)


def test_the_convolution_against_a_loop():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 11, 5)).astype(np.float32)
    kernel = rng.normal(size=(4, 5)).astype(np.float32)
    bias = rng.normal(size=(5,)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(11):
        for ch in range(5):
            want[:, t, ch] = bias[ch] + sum(
                kernel[j, ch] * x[:, t - 3 + j, ch]
                for j in range(4) if t - 3 + j >= 0)
    np.testing.assert_allclose(
        causal_conv1d(x, kernel, bias), want, atol=1e-6)
    # causal: a later position moves no earlier one
    moved = x.copy()
    moved[:, 7:] += 1.0
    np.testing.assert_array_equal(
        np.asarray(causal_conv1d(moved, kernel, bias))[:, :7],
        np.asarray(causal_conv1d(x, kernel, bias))[:, :7])


def test_the_gated_norm_is_by_groups_and_its_psum_joins_the_parts():
    rng = np.random.default_rng(1)
    y, z = rng.normal(size=(2, 3, 7, 16)).astype(np.float32)
    scale = rng.normal(size=(16,)).astype(np.float32)
    gated = y * z / (1 + np.exp(-z))
    parts = gated.reshape(3, 7, 2, 8)
    want = (parts / np.sqrt((parts ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 7, 16) * scale
    np.testing.assert_allclose(
        gated_group_norm(y, z, scale, 2, 1e-5), want, atol=1e-5)
    # each of four devices holds a quarter of each group's channels
    cut = lambda a: jnp.asarray(a).reshape(  # noqa: E731
        a.shape[:-1] + (2, 4, 2)).swapaxes(-2, -3).reshape(
            a.shape[:-1] + (4, 4))
    held = jax.vmap(
        lambda y, z, s: gated_group_norm(y, z, s, 2, 1e-5, axis_name="tp"),
        in_axes=-2, out_axes=-2, axis_name="tp")(cut(y), cut(z), cut(scale))
    np.testing.assert_allclose(held, cut(want), atol=1e-5)


# -- the block against the reference's layer -----------------------------------
def whole_layer(cfg, seed=3):
    """(the uncut layer's block, its seeded parameters, an input)."""
    block = HybridDecoderBlock(**sizes_of(cfg))
    x = jax.random.normal(jax.random.PRNGKey(7), (1, SEQ, cfg["hidden_size"]))
    shapes = jax.eval_shape(
        lambda: block.init(jax.random.PRNGKey(0), x))["params"]
    return block, seeded(shapes, seed=seed), x


def plain_layer(plain, cfg, p, h):
    """(the reference's layer of ``h``, (its mixer's, its attention's and,
    of ``h`` itself, its feed-forward's part))."""
    @jax.jit
    def run(p, h):
        a = plain.rms_norm(h, p["norm_mix"]["scale"], cfg["rms_norm_eps"])
        return plain.layer(h, p, cfg, "float32"), (
            plain.mixer_part(a, p, cfg, "float32"),
            plain.attention_part(a, p, cfg, "float32"),
            plain.feed_forward_part(h, p, cfg, "float32"))

    return run(p, h)


def test_the_block_is_the_references_layer(plain):
    cfg = tiny_cfg()
    block, p, x = whole_layer(cfg)
    with jax.default_matmul_precision("highest"):
        want, parts = plain_layer(plain, cfg, p, x[0])
        got = jax.jit(block.apply)({"params": p}, x)[0]
    # each part is there: the mixer, attention and the feed-forward
    assert all(float(jnp.max(jnp.abs(part))) > 0.02 for part in parts)
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- the shares add up ---------------------------------------------------------
def mixer_share(p, cfg, head):
    """What the chip that holds mixer head ``head`` holds of the mixer's
    parameters: the head's columns of z, x and dt, its group's B and C
    whole, the same channels of the convolution, the head's weights of the
    norm and rows of ``W_out``."""
    heads, groups = cfg["mamba_n_heads"], cfg["mamba_n_groups"]
    dim, state = cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner, bc = heads * dim, groups * state
    group = head // (heads // groups)
    mine = np.arange(head * dim, (head + 1) * dim)
    b = 2 * inner + np.arange(group * state, (group + 1) * state)
    in_columns = np.concatenate(
        [mine, inner + mine, b, bc + b, [2 * inner + 2 * bc + head]])
    conv = np.concatenate([mine, inner + np.arange(
        group * state, (group + 1) * state), inner + bc + np.arange(
            group * state, (group + 1) * state)])
    return {
        "ssm_in": {"kernel": p["ssm_in"]["kernel"][:, in_columns]},
        "conv_kernel": p["conv_kernel"][:, conv],
        "conv_bias": p["conv_bias"][conv],
        "A_log": p["A_log"][head:head + 1], "D": p["D"][head:head + 1],
        "dt_bias": p["dt_bias"][head:head + 1],
        "ssm_norm": p["ssm_norm"][mine],
        "ssm_out": {"kernel": p["ssm_out"]["kernel"][mine]},
    }


def attention_share(p, cfg, kv_head):
    """One KV head with its group of query heads: columns of q, k and v,
    rows of o."""
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    dim = cfg["head_dim"]
    q = np.arange(kv_head * group * dim, (kv_head + 1) * group * dim)
    kv = np.arange(kv_head * dim, (kv_head + 1) * dim)
    return {"q": {"kernel": p["q"]["kernel"][:, q]},
            "k": {"kernel": p["k"]["kernel"][:, kv]},
            "v": {"kernel": p["v"]["kernel"][:, kv]},
            "o": {"kernel": p["o"]["kernel"][q]}}


def column_share(p, share, shares):
    n = p["mlp_up"]["kernel"].shape[1] // shares
    mine = slice(share * n, (share + 1) * n)
    return {"mlp_gate": {"kernel": p["mlp_gate"]["kernel"][:, mine]},
            "mlp_up": {"kernel": p["mlp_up"]["kernel"][:, mine]},
            "mlp_down": {"kernel": p["mlp_down"]["kernel"][mine]}}


def silenced(p, *names_):
    """``p`` with the matrices ``names_`` zero: those sublayers add nothing
    to the residual stream."""
    return {**p, **{n: {"kernel": jnp.zeros_like(p[n]["kernel"])}
                    for n in names_}}


def test_the_eight_shares_add_up_to_the_uncut_layer(plain):
    """The deployment in small: 8 chips share a layer of 8 mixer heads in 2
    groups, 4 KV heads with 2 query heads each and 32 columns. Chip ``s``
    holds mixer head ``s`` with its group's B and C whole, KV head ``s //
    2`` (two chips hold the same one and divide the sequences: its
    attention is counted once) and 4 columns. The mixer's norm is over a
    group's 4 heads, on 4 chips: its mean square is a ``psum`` among them
    (a ``vmap`` with an axis name stands for the four devices). The summed
    ``mix``, ``att`` and feed-forward partials are the uncut reference's
    layer."""
    cfg, held = tiny_cfg(), tiny_cfg(shares=8)
    _, p, x = whole_layer(cfg)
    share = HybridDecoderBlock(**sizes_of(held))
    alone = jax.jit(share.apply)
    joined = jax.jit(jax.vmap(
        lambda q, x: share.clone(axis_name="group").apply({"params": q}, x),
        in_axes=(0, None), axis_name="group"))
    parts = lambda s: {  # noqa: E731
        **p, **mixer_share(p, cfg, s), **attention_share(p, cfg, s // 2),
        **column_share(p, s, 8)}
    with jax.default_matmul_precision("highest"):
        want, (want_mix, want_att, _) = plain_layer(plain, cfg, p, x[0])

        # the mixers, four chips a group, the group's psum among them
        mix = 0.0
        for group in range(2):
            stacked = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves),
                *[silenced(parts(4 * group + i), "o", "mlp_down")
                  for i in range(4)])
            mix = mix + jnp.sum(joined(stacked, x) - x, axis=0)[0]
        np.testing.assert_allclose(mix, want_mix, atol=2e-5)
        # without the psum each chip norms by its own head: not the layer's
        apart = sum(
            alone({"params": silenced(parts(s), "o", "mlp_down")}, x) - x
            for s in range(8))[0]
        assert float(jnp.max(jnp.abs(apart - want_mix))) > 1e-2

        # attention: each KV head once (chips 0, 2, 4 and 6 carry it here)
        att = sum(
            alone({"params": silenced(parts(s), "ssm_out", "mlp_down")}, x)
            - x for s in range(0, 8, 2))[0]
        np.testing.assert_allclose(att, want_att, atol=2e-5)

        # the feed-forward's columns read the summed h' = h + mix + att
        mixed = x + mix + att
        out = mixed + sum(
            alone({"params": silenced(parts(s), "o", "ssm_out")}, mixed)
            - mixed for s in range(8))
    np.testing.assert_allclose(out[0], want, atol=3e-5)


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
    assert {"ssm_in", "ssm_out", "conv_kernel", "conv_bias", "A_log", "D",
            "dt_bias", "ssm_norm", "q", "k", "v", "o", "mlp_gate", "mlp_up",
            "mlp_down", "norm_mix", "norm_mlp"} == set(
                params["HybridDecoderBlock_0"])
    x, y = tokens(2, SEQ, cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(make_lm_loss_fn(model)))(
            params, (x, y))
        want_loss, want = plain_loss_and_grads(plain, cfg, params, x, y)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(want)
    for (path, w), g in zip(flat, jax.tree_util.tree_leaves(grads)):
        assert float(jnp.max(jnp.abs(w))) > 1e-6, path  # every leaf learns
        np.testing.assert_allclose(
            g, w, atol=2e-5 * max(1.0, float(jnp.max(jnp.abs(w)))),
            err_msg=str(path))


def test_the_default_initialisation_is_mamba_2s():
    cfg = tiny_cfg()
    p = jax.jit(lambda: init_lm_params(tiny_model(cfg), SEQ))()[
        "HybridDecoderBlock_1"]
    assert np.all((np.exp(p["A_log"]) >= 1) & (np.exp(p["A_log"]) <= 16))
    delta = np.log1p(np.exp(np.asarray(p["dt_bias"], np.float64)))
    assert np.all((delta > 0.99e-3) & (delta < 1.01e-1))
    np.testing.assert_array_equal(p["D"], 1.0)
    np.testing.assert_array_equal(p["ssm_norm"], 1.0)
    assert float(jnp.max(jnp.abs(p["conv_kernel"]))) <= 0.5
    assert float(jnp.max(jnp.abs(p["conv_bias"]))) <= 0.5


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
    assert value(names.GAUGE_SSM_HEADS_HELD) == 2 * 1  # layers x heads held
    # layers x sequences x chunks a sequence: 37 positions are 5 chunks of 8
    assert value(names.GAUGE_SSM_CHUNKS) == 2 * 2 * 5
    assert value("tm_attn_calls_per_step") == 2


def test_the_mixers_scopes_nest_under_fwd_bwd_in_the_lowered_step():
    """The four ``tm.lm.ssm_*`` scopes, the feed-forward under ``tm.lm.mlp``
    and attention under the decoders' names: each reaches forward, the
    recomputed block and backward, seen by the benchmark's reader as a
    bucket of its own."""
    from benchmark import model_scopes, scopes

    cfg = tiny_cfg(shares=8)
    model = tiny_model(cfg)
    mpi.start(devices=jax.devices()[:1])
    engine = AllReduceSGDEngine(
        make_lm_loss_fn(model),
        seeded(jax.eval_shape(lambda: init_lm_params(model, SEQ))),
        optimizer=optax.sgd(0.1))
    x, y = tokens(2, SEQ, cfg["vocab_size"])
    text = engine._step_fn.lower(
        engine.params, engine.opt_state, engine.model_state,
        engine._prepare_batch((x, y))).as_text(debug_info=True)
    seen = {}
    for op in set(re.findall(r'"(jit\(tm_train_step\)[^"]*)"', text)):
        bucket = model_scopes.bucket_of(op)
        if bucket not in (None, model_scopes.UNNAMED):
            assert scopes.scope_of(op) == "tm.fwd_bwd", op
            seen.setdefault(bucket, set()).add(model_scopes.phase_of(op))
    mixer = set(names.SSM_SCOPE_NAMES)
    assert mixer == {"tm.lm.ssm_proj", "tm.lm.ssm_conv", "tm.lm.ssm_scan",
                     "tm.lm.ssm_gate"}
    assert set(seen) == mixer | {
        "tm.lm.embed", "tm.lm.norm", "tm.attn.proj", "tm.attn.full",
        "tm.lm.mlp", "tm.lm.head", "tm.lm.loss"}, seen
    for scope in mixer | {"tm.lm.mlp", "tm.attn.proj", "tm.attn.full"}:
        assert seen[scope] == set(model_scopes.PHASES), (scope, seen[scope])


@pytest.mark.parametrize("phase,wrap", [
    ("forward", "jvp(HybridDecoder)/HybridDecoderBlock_2/"),
    ("recompute", "transpose(jvp(HybridDecoder))/tm.fwd_bwd/jvp("
     "HybridDecoder)/checkpoint/rematted_computation/HybridDecoderBlock_2/"),
    ("backward", "transpose(jvp(HybridDecoder))/HybridDecoderBlock_2/"),
])
def test_an_operation_of_the_scan_has_a_bucket_of_its_own(phase, wrap):
    from benchmark import model_scopes

    op = ("jit(tm_train_step)/shard_map/tm.fwd_bwd/" + wrap
          + "tm.lm.ssm_scan/while/body/dot_general")
    assert model_scopes.bucket_of(op) == "tm.lm.ssm_scan"
    assert model_scopes.phase_of(op) == phase


# -- the benchmark's configuration ---------------------------------------------
def test_the_file_keeps_every_published_width_and_multiplier():
    """Every number of the catalog's entry under its own key, but those
    that are cut, which ``reduced`` and ``published`` name: counts of
    layers, rows, heads and groups, never a width or a multiplier."""
    cfg = json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    catalog = {
        "attention_bias": False, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 21504, "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
        "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
        "mamba_norm_before_gate": False, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_use_mlp": True,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "model_type": "falcon_h1", "num_logits_to_keep": 1,
        "projectors_bias": False, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 100000000000,
        "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
        "tie_word_embeddings": False,
    }
    for key, value in catalog.items():
        assert cfg[key] == value and key not in cfg["reduced"], key
    cut = {"num_hidden_layers": (4, 72), "vocab_size": (32640, 261120),
           "num_attention_heads": (5, 20), "num_key_value_heads": (1, 4),
           "mamba_n_heads": (4, 32), "mamba_n_groups": (1, 2)}
    assert cfg["reduced"] == list(cut)
    for key, (here, published) in cut.items():
        assert cfg[key] == here and cfg["published"][key] == published, key
    assert set(cfg) - set(catalog) - set(cut) == {
        "name", "source", "published", "deployment", "model",
        "sequence_length", "compute_dtype", "param_dtype", "optimizer",
        "init", "per_chip_batch", "remat", "reduced", "departures",
        "assumed", "rehearsal", "limits", "limits_from"}
    # what is held of each width is the share's, an eighth (the KV heads a
    # quarter), and stands under ``model``
    assert cfg["model"] == {
        "dense_columns_held": 21504 // 8, "ssm_heads_held": [0, 1, 2, 3],
        "ssm_group_held": 0, "kv_head_held": 0, "attention_block": 1024}
    assert cfg["vocab_size"] * 8 == 261120
    assert cfg["mamba_n_heads"] * cfg["mamba_d_head"] * 8 == 4096
    assert "group_norm" in cfg["departures"]
    assert {"multipliers", "mixer", "weights", "mixer_values"} <= set(
        cfg["assumed"])
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/"
        "config.json")
    tiny = cfg["rehearsal"]
    assert tiny["sequence_length"] % tiny["mamba_chunk_size"]  # a part chunk
    assert tiny["mamba_n_heads"] == len(tiny["model"]["ssm_heads_held"])


def test_flops_of_the_configuration_are_the_issues_arithmetic():
    from benchmark import configs, hybrid_decoder_flops

    count = lambda **over: (  # noqa: E731
        hybrid_decoder_flops.hybrid_decoder_forward_flops(**{**dict(
            seq=16384, d_model=5120, layers=4, heads=5, kv_heads=1,
            head_dim=128, ssm_heads=4, ssm_head_dim=128, ssm_groups=1,
            ssm_state=256, conv_width=4, mlp_columns=2688, vocab=32640),
            **over}))
    forward = count()
    assert 14.70e12 < forward < 14.73e12        # ISSUE 39: 14.7 T forward
    cfg = configs.load(CONFIG)
    built = configs.build(CONFIG, cfg)
    seq = cfg["sequence_length"]
    assert built.flops_per_sample == 3 * count(seq=seq)
    t, d = 16384, 5120
    # each part by itself: the head 37 %, the feed-forward 37 %, ...
    assert forward - count(vocab=0) == 2 * t * d * 32640
    assert 0.37 < (forward - count(vocab=0)) / forward < 0.375
    assert forward - count(mlp_columns=0) == 4 * 6 * t * d * 2688
    # the scan by the recurrence: 5 P N a position and head, 0.3 %
    scan = hybrid_decoder_flops.scan_forward_flops(t, 4, 128, 256)
    assert scan == 5 * 128 * 256 * 4 * t
    assert 0.0025 < 4 * scan / forward < 0.0035
    # a mixer head more: its columns of z, x and dt, its rows of W_out,
    # its channels of the convolution and its scan
    assert count(ssm_heads=5) - forward == 4 * (
        2 * t * d * (2 * 128 + 1) + 2 * t * 128 * d + 8 * t * 128
        + 5 * 128 * 256 * t)
    # the chunk is no argument: the count cannot move with the chunking
    assert "chunk" not in (
        hybrid_decoder_flops.hybrid_decoder_forward_flops.__code__
        .co_varnames)
