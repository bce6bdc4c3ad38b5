"""The gated-delta decoder (models/deltanet.py: three Gated DeltaNet layers
to one of gated softmax attention, an expert layer with a gated shared
expert under each) and its sequence operation (parallel/deltanet.py) against
plain arithmetic: the recurrence itself, a step a position, and the
benchmark's plain float32 reference of the configuration that runs them
(``benchmark/reference/qwen3-next-80b-a3b.py``, loaded by path, which
imports nothing of the program and computes the rule as the recurrence).
Tiny sizes that keep what matters: two value heads to a key head, a head
wider than what is rotated, a sequence that is no multiple of the chunk,
decays that forget over a few positions to a few dozen, 8 experts at 3 a
token."""

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
    GatedDeltaDecoder,
    GatedDeltaDecoderBlock,
    init_lm_params,
    init_moe_state,
    make_moe_lm_loss_fn,
)
from torchmpi_tpu.parallel import gated_delta_rule
from torchmpi_tpu.telemetry import names

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
CONFIG = "qwen3-next-80b-a3b"
SEQ, CHUNK = 37, 8  # four chunks and five positions of a fifth
GDN = ("tm.lm.gdn_proj", "tm.lm.gdn_conv", "tm.lm.gdn_gate",
       "tm.lm.gdn_chunk", "tm.lm.gdn_state")


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


def tiny_cfg(held=range(8)):
    """The published keys at test sizes, as the reference reads them: the
    router's 8 experts, of which ``held`` are here."""
    return {
        "hidden_size": 32, "head_dim": 16, "num_hidden_layers": 4,
        "full_attention_interval": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 12,
        "linear_conv_kernel_dim": 4, "moe_intermediate_size": 16,
        "shared_expert_intermediate_size": 24, "num_experts_per_tok": 3,
        "rms_norm_eps": 1e-6, "rope_theta": 1e7, "vocab_size": 61,
        "model": {"router_outputs": 8, "experts_held": list(held),
                  "gdn_chunk": CHUNK},
        # a rate at which AdamW's first step, which moves every element by
        # the rate whatever its gradient, turns no token's choice of experts
        "optimizer": {"name": "adamw", "learning_rate": 1e-5, "b1": 0.9,
                      "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01},
    }


def sizes_of(cfg, **over):
    return {**dict(
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        key_heads=cfg["linear_num_key_heads"],
        value_heads=cfg["linear_num_value_heads"],
        key_dim=cfg["linear_key_head_dim"],
        value_dim=cfg["linear_value_head_dim"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["shared_expert_intermediate_size"],
        num_experts=cfg["model"]["router_outputs"],
        top_k=cfg["num_experts_per_tok"],
        held=tuple(cfg["model"]["experts_held"]),
        conv_width=cfg["linear_conv_kernel_dim"],
        chunk=cfg["model"]["gdn_chunk"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], attn_block=8), **over}


def tiny_model(cfg, **over):
    return GatedDeltaDecoder(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        full_interval=cfg["full_attention_interval"],
        **sizes_of(cfg, remat=True, **over))


def seeded(shapes, seed=0, std=0.3):
    """Seeded normal weights large enough that the scores, the gates, the
    router and the experts are far from flat; the decays so that a state
    lasts a few positions to a few dozen; the norms' scales off their
    start."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def leaf(path, s, k):
        name = str(getattr(path[-1], "key", ""))
        if name == "out_norm":
            return 1.0 + std * jax.random.normal(k, s.shape, jnp.float32)
        if name == "A_log":
            return jnp.log(
                jax.random.uniform(k, s.shape, minval=1.0, maxval=4.0))
        if name == "dt_bias":
            return jax.random.uniform(k, s.shape, minval=-4.0, maxval=-1.0)
        return std * jax.random.normal(k, s.shape, jnp.float32)

    return treedef.unflatten(
        [leaf(p, s, k) for (p, s), k in zip(leaves, keys)])


def tokens(n, seq, vocab, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, vocab, size=(n, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


# -- parallel/deltanet.py against the recurrence ------------------------------
def recurrence(q, k, v, g, beta):
    """A step a position: ``S <- e^g S``; ``u = beta (v - S^T k)``; ``S <- S
    + k u^T``; ``o = S^T q``. q, k: [b, t, key heads, dk]; v: [b, t, value
    heads, dv]; g, beta: [b, t, value heads]."""
    r = v.shape[2] // q.shape[2]
    q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)

    def step(state, now):
        q_t, k_t, v_t, g_t, beta_t = now
        state = jnp.exp(g_t)[..., None, None] * state
        wrote = beta_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * wrote[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, o = jax.lax.scan(
        step,
        jnp.zeros(v.shape[:1] + v.shape[2:3] + q.shape[3:] + v.shape[3:]),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def rule_inputs(t, key_heads=2, per_key=2, dk=16, dv=12, batch=2):
    ks = jax.random.split(jax.random.PRNGKey(t), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    heads = key_heads * per_key
    return (unit(jax.random.normal(ks[0], (batch, t, key_heads, dk)))
            / math.sqrt(dk),
            unit(jax.random.normal(ks[1], (batch, t, key_heads, dk))),
            jax.random.normal(ks[2], (batch, t, heads, dv)),
            # a position forgets a four-hundredth to all but a third
            -jnp.exp(jax.random.uniform(
                ks[3], (batch, t, heads), minval=-6.0, maxval=0.1)),
            jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (batch, t, heads))))


@pytest.mark.parametrize("per_key", [1, 2], ids=["a-key-a-value", "shared"])
@pytest.mark.parametrize("t,chunk", [(37, 8), (64, 16), (5, 8), (40, 64)],
                         ids=["ragged", "whole", "short", "one-chunk"])
def test_the_chunked_rule_is_the_recurrence(t, chunk, per_key):
    """Forward and every input's gradient, float32, at lengths that are and
    are not a multiple of the chunk, at chunks the product form of the
    triangular inverse reaches in 2, 3 and 5 squarings, with a key head read
    by one value head and by two."""
    args = rule_inputs(t, per_key=per_key)
    probe = lambda fn: (lambda *a: jnp.sum(jnp.sin(3.0 * fn(*a))))  # noqa
    chunked = lambda *a: gated_delta_rule(*a, chunk=chunk)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got, want = jax.jit(chunked)(*args), jax.jit(recurrence)(*args)
        grads = jax.jit(jax.grad(probe(chunked), argnums=range(5)))(*args)
        wants = jax.jit(jax.grad(probe(recurrence), argnums=range(5)))(*args)
    assert got.shape == args[2].shape and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-6)
    for g, w in zip(grads, wants):
        np.testing.assert_allclose(
            g, w, atol=5e-6 * max(1.0, float(jnp.max(jnp.abs(w)))))


def test_the_state_is_corrected_and_not_only_added_to():
    """A key written twice at full strength holds its SECOND value alone
    (the delta rule: what the state held under the key is taken out), and
    with beta = 0 nothing is written at all."""
    k = jnp.zeros((1, 3, 1, 4)).at[:, :, :, 0].set(1.0)
    v = jnp.ones((1, 3, 1, 2)) * jnp.asarray(
        [1.0, 5.0, 9.0]).reshape(1, 3, 1, 1)
    none = jnp.zeros((1, 3, 1))
    out = gated_delta_rule(k, k, v, none, none + 1.0, chunk=2)
    np.testing.assert_allclose(out[0, :, 0, 0], [1.0, 5.0, 9.0], atol=1e-6)
    assert float(jnp.max(jnp.abs(
        gated_delta_rule(k, k, v, none, none, chunk=2)))) == 0.0


def test_the_products_take_the_stated_dtype_and_shapes_are_checked():
    args = rule_inputs(24)
    half = jax.jit(lambda *a: gated_delta_rule(
        *a, chunk=8, dtype=jnp.bfloat16))(*args)
    full = jax.jit(lambda *a: gated_delta_rule(*a, chunk=8))(*args)
    assert half.dtype == jnp.float32
    gap = float(jnp.max(jnp.abs(half - full)))
    assert 1e-5 < gap < 0.05 * float(jnp.max(jnp.abs(full)))
    q, k, v, g, beta = args
    with pytest.raises(ValueError, match="multiple"):
        three = lambda a: jnp.repeat(a[:, :, :1], 3, axis=2)  # noqa: E731
        gated_delta_rule(three(q), three(k), v, g, beta)
    with pytest.raises(ValueError, match="a number a value head"):
        gated_delta_rule(q, k, v, g[..., :2], beta)


def test_no_array_holds_the_sequence_squared_or_a_state_a_position():
    """The lowered forward and backward of the operation at 2,048 positions
    hold no ``t x t`` array and no ``[t, heads, dk, dv]`` state: the widest
    with ``dk x dv`` for its last axes is the chunks' states."""
    t, chunk, heads, dk, dv = 2048, 64, 4, 16, 24
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (1, t, 2, dk), (1, t, 2, dk), (1, t, heads, dv), (1, t, heads),
        (1, t, heads))]
    text = jax.jit(jax.grad(lambda *a: jnp.sum(gated_delta_rule(
        *a, chunk=chunk, dtype=jnp.bfloat16)), argnums=range(5))).lower(
            *shapes).compile().as_text()
    dims = {tuple(int(d) for d in dims.split(","))
            for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text)}
    assert not [s for s in dims if s.count(t) > 1]
    states = [s for s in dims if s[-2:] == (dk, dv)]
    assert states and max(math.prod(s) for s in states) == (
        t // chunk * heads * dk * dv)
    assert not [s for s in dims if math.prod(s) >= t * heads * dk * dv]


# -- the block against the reference's layer ----------------------------------
def whole_layer(cfg, linear, seed=3):
    """(the block of one kind, its seeded parameters, an input)."""
    block = GatedDeltaDecoderBlock(linear=linear, **sizes_of(cfg))
    x = jax.random.normal(jax.random.PRNGKey(7), (1, SEQ, cfg["hidden_size"]))
    shapes = jax.eval_shape(
        lambda: block.init(jax.random.PRNGKey(0), x))["params"]
    return block, seeded(shapes, seed=seed), x


def plain_layer(plain, cfg, linear, p, h):
    """(the reference's layer of ``h``, (its mixer's and, of ``h`` itself,
    its expert layer's part))."""
    @jax.jit
    def run(p, h):
        u = plain.zrms(h, p["norm_mix"]["scale"], cfg["rms_norm_eps"])
        mixer = plain.linear_part if linear else plain.full_part
        return plain.layer(h, p, cfg, linear, "float32"), (
            mixer(u, p, cfg, "float32"), plain.moe_part(h, p, cfg, "float32"))

    return run(p, h)


@pytest.mark.parametrize("linear", [True, False], ids=["linear", "full"])
def test_the_block_is_the_references_layer(plain, linear):
    cfg = tiny_cfg()
    block, p, x = whole_layer(cfg, linear)
    assert ("in_qkvz" in p) == linear and ("q_norm" in p) != linear
    with jax.default_matmul_precision("highest"):
        want, parts = plain_layer(plain, cfg, linear, p, x[0])
        got, (load, rows) = jax.jit(block.apply)({"params": p}, x)
    # each part is there: the mixer and the expert layer
    assert all(float(jnp.max(jnp.abs(part))) > 0.02 for part in parts)
    np.testing.assert_allclose(got[0], want, atol=3e-5, rtol=2e-5)
    assert float(jnp.sum(load)) == SEQ * 3 and float(rows) == SEQ * 3


# -- the shares add up --------------------------------------------------------
def share_of(p, held):
    """What a chip that holds the experts ``held`` has of the layer's
    parameters: those experts' matrices; the mixer, the router, the shared
    expert and the norms whole."""
    held = np.asarray(held)
    return {**p, **{name: p[name][held] for name in (
        "experts_gate", "experts_up", "experts_down")}}


def silenced(p, *names_):
    """``p`` with the matrices ``names_`` zero: those sublayers add nothing
    to the residual stream."""
    return {**p, **{n: {"kernel": jnp.zeros_like(p[n]["kernel"])}
                    for n in names_}}


@pytest.mark.parametrize("linear", [True, False], ids=["linear", "full"])
def test_the_four_shares_add_up_to_the_uncut_layer(plain, linear):
    """The deployment in small: 4 chips share a layer's 8 experts, 2 each,
    every chip with the mixer, the router and the shared expert whole. The
    mixer and the shared expert counted once, the four chips' routed
    partials summed: the uncut reference's layer."""
    cfg = tiny_cfg()
    block, p, x = whole_layer(cfg, linear)
    mixer_out = "out" if linear else "o"
    with jax.default_matmul_precision("highest"):
        want, (want_mix, want_moe) = plain_layer(plain, cfg, linear, p, x[0])
        # the mixer alone: no expert, shared or routed, adds anything
        mixed, _ = jax.jit(block.apply)({"params": silenced(
            {**p, "experts_down": jnp.zeros_like(p["experts_down"])},
            "shared_down")}, x)
        np.testing.assert_allclose(
            (mixed - x)[0], want_mix, atol=3e-5, rtol=2e-5)
        routed, loads = 0.0, []
        for s in range(4):
            held = (2 * s, 2 * s + 1)
            alone = jax.jit(GatedDeltaDecoderBlock(linear=linear, **sizes_of(
                tiny_cfg(held))).apply)
            out, (load, _) = alone({"params": silenced(
                share_of(p, held), mixer_out, "shared_down")}, mixed)
            routed = routed + (out - mixed)
            loads.append(load)
        # every route lands on exactly one chip
        assert float(sum(jnp.sum(a) for a in loads)) == SEQ * 3
        # the shared expert once, with the mixer's and the routed parts
        once, _ = jax.jit(block.apply)({"params": silenced(
            {**p, "experts_down": jnp.zeros_like(p["experts_down"])},
            mixer_out)}, mixed)
        # a share alone is not the expert layer's
        assert float(jnp.max(jnp.abs(
            (out - mixed) + (once - mixed) - want_moe))) > 1e-2
    np.testing.assert_allclose(
        (once + routed)[0], want, atol=4e-5, rtol=2e-5)


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


@pytest.mark.parametrize("held", [range(8), (2, 3)], ids=["whole", "a-share"])
def test_decoder_loss_and_gradients_match_the_plain_reference(plain, held):
    cfg = tiny_cfg(held)
    model = tiny_model(cfg)
    params = seeded(jax.eval_shape(lambda: init_lm_params(model, SEQ)))
    sparse = {"router", "shared_gate", "shared_up", "shared_down",
              "shared_expert_gate", "experts_gate", "experts_up",
              "experts_down", "norm_mix", "norm_moe"}
    assert sparse | {"in_qkvz", "in_ba", "conv_kernel", "A_log", "dt_bias",
                     "out_norm", "out"} == set(
                         params["GatedDeltaDecoderBlock_2"])
    assert sparse | {"q", "k", "v", "o", "q_norm", "k_norm"} == set(
        params["GatedDeltaDecoderBlock_3"])
    x, y = tokens(2, SEQ, cfg["vocab_size"])
    loss_fn = make_moe_lm_loss_fn(model)
    with jax.default_matmul_precision("highest"):
        (loss, measured), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True))(params, init_moe_state(model), (x, y))
        want_loss, want = plain_loss_and_grads(plain, cfg, params, x, y)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    assert measured["moe_load"].shape == (4, len(held))
    flat, _ = jax.tree_util.tree_flatten_with_path(want)
    for (path, w), g in zip(flat, jax.tree_util.tree_leaves(grads)):
        assert float(jnp.max(jnp.abs(w))) > 1e-6, path  # every leaf learns
        np.testing.assert_allclose(
            g, w, atol=1e-4 * max(1.0, float(jnp.max(jnp.abs(w)))),
            err_msg=str(path))


def test_the_default_decays_last_one_to_a_thousand_positions():
    cfg = tiny_cfg()
    p = jax.jit(lambda: init_lm_params(tiny_model(cfg), SEQ))()
    block = p["GatedDeltaDecoderBlock_1"]
    rate = np.exp(np.asarray(block["A_log"], np.float64)) * np.log1p(
        np.exp(np.asarray(block["dt_bias"], np.float64)))  # -g at a = 0
    assert np.all((rate > 1e-3 * 0.99) & (rate < 1.6 * 1.01))
    assert float(
        jnp.std(block["in_ba"]["kernel"])) < 0.02 / math.sqrt(32) * 1.5
    assert abs(float(jnp.max(jnp.abs(block["conv_kernel"])))) <= 0.5
    np.testing.assert_array_equal(block["out_norm"], 1.0)
    for norm in (block["norm_mix"], block["norm_moe"], p["norm"],
                 p["GatedDeltaDecoderBlock_3"]["q_norm"]):
        np.testing.assert_array_equal(norm["scale"], 0.0)
    assert p["GatedDeltaDecoderBlock_3"]["k_norm"]["scale"].shape == (
        cfg["head_dim"],)


def test_a_zero_centred_norms_weight_decays_to_scale_one():
    """Under weight decay alone (a gradient of zero) every zero-centred
    norm's parameter goes to 0, its scale ``1 + w`` to 1; the delta mixer's
    output norm, whose parameter IS the scale, goes to 0 as any weight."""
    cfg = tiny_cfg()
    params = seeded(jax.eval_shape(
        lambda: init_lm_params(tiny_model(cfg), SEQ)))
    opt = optax.adamw(0.1, weight_decay=1.0)
    state = opt.init(params)
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    before = params
    for _ in range(3):
        updates, state = opt.update(zero, state, params)
        params = optax.apply_updates(params, updates)
    w = lambda p: p["GatedDeltaDecoderBlock_0"]["norm_mix"]["scale"]  # noqa
    assert float(jnp.max(jnp.abs(w(before)))) > 0.3
    np.testing.assert_allclose(w(params), 0.9 ** 3 * w(before), rtol=1e-5)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, cfg["hidden_size"]))
    from torchmpi_tpu.models.deltanet import ZeroCentredRMSNorm

    at = lambda scale: ZeroCentredRMSNorm().apply(  # noqa: E731
        {"params": {"scale": scale}}, x)
    np.testing.assert_allclose(
        at(0.0 * w(before)), x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                                          + 1e-6), rtol=1e-5)
    np.testing.assert_allclose(at(w(before)), at(0.0 * w(before))
                               * (1.0 + w(before)), rtol=1e-6)
    out = lambda p: p["GatedDeltaDecoderBlock_0"]["out_norm"]  # noqa: E731
    np.testing.assert_allclose(out(params), 0.9 ** 3 * out(before), rtol=1e-5)


def test_two_engine_steps_match_the_reference_and_set_the_gauges(plain):
    """``engine.train`` for two AdamW steps against the reference's
    ``follow`` on the same batches: each step's loss, the first moment's
    and the parameters' change leaf by leaf; what the layers measured of
    their routing rides the model state to ``observe_state``; and what the
    rule runs over, as the gauges say it."""
    cfg = tiny_cfg((2, 3))
    model = tiny_model(cfg)
    params = seeded(jax.eval_shape(lambda: init_lm_params(model, SEQ)))
    opt = cfg["optimizer"]
    batches = [tokens(2, SEQ, cfg["vocab_size"], seed=s) for s in range(2)]
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
    # 3 linear layers of the 4 x sequences x chunks a sequence: 37 positions
    # are 5 chunks of 8
    assert value(names.GAUGE_GDN_CHUNKS) == 3 * 2 * 5
    assert value("tm_moe_routes_per_step") == 2 * SEQ * 3 * 4
    assert value("tm_moe_experts_held") == 2
    held_routes = value("tm_moe_held_routes_last_step")
    assert 0 < held_routes < 2 * SEQ * 3 * 4
    assert value("tm_attn_calls_per_step") == 1  # the one full layer


def test_the_scopes_nest_under_fwd_bwd_in_the_lowered_step():
    """The five ``tm.lm.gdn_*`` scopes, the full layer under attention's
    names, the expert layer under ``tm.moe.*``: each reaches forward, the
    recomputed block and backward, seen by the benchmark's reader as a
    bucket of its own."""
    from benchmark import model_scopes, scopes

    cfg = tiny_cfg((2, 3))
    model = tiny_model(cfg)
    mpi.start(devices=jax.devices()[:1])
    engine = AllReduceSGDEngine(
        make_moe_lm_loss_fn(model),
        seeded(jax.eval_shape(lambda: init_lm_params(model, SEQ))),
        optimizer=optax.sgd(0.1), model_state=init_moe_state(model))
    x, y = tokens(2, SEQ, cfg["vocab_size"])
    # the COMPILED step's op_names: an operation inside the scan's body
    # bears its whole path there
    text = engine._step_fn.lower(
        engine.params, engine.opt_state, engine.model_state,
        engine._prepare_batch((x, y))).compile().as_text()
    seen = {}
    for op in set(re.findall(r'op_name="(jit\(tm_train_step\)[^"]*)"', text)):
        bucket = model_scopes.bucket_of(op)
        if bucket not in (None, model_scopes.UNNAMED):
            assert scopes.scope_of(op) == "tm.fwd_bwd", op
            seen.setdefault(bucket, set()).add(model_scopes.phase_of(op))
            # no scope of the mixer lies inside another
            inner = [n for n in model_scopes.BUCKET.findall(op.split(
                "rematted_computation")[-1].split("transpose(")[-1])
                if n in GDN]
            assert len(inner) <= 1, op
    assert names.GDN_SCOPE_NAMES == GDN
    assert set(GDN) < set(names.MODEL_SCOPE_NAMES)
    assert set(seen) >= set(GDN) | {
        "tm.lm.embed", "tm.lm.norm", "tm.attn.proj", "tm.attn.full",
        "tm.attn.gate", "tm.moe.router", "tm.moe.shared", "tm.moe.route",
        "tm.moe.experts", "tm.moe.combine", "tm.lm.head", "tm.lm.loss"}, seen
    assert not [s for s in seen if s.startswith(("tm.lm.ssm", "tm.lm.ret"))]
    for scope in GDN + ("tm.attn.gate", "tm.moe.shared"):
        assert seen[scope] == set(model_scopes.PHASES), (scope, seen[scope])
    # the full layer's ``q``, ``k``, ``v`` and the residual after ``o`` bear
    # ``models.lm``'s names, and this device reports no memory: every kind is
    # kept, and the recomputed block makes none of the scope's products again
    assert seen["tm.attn.proj"] == {"forward", "backward"}


@pytest.mark.parametrize("phase,wrap", [
    ("forward", "jvp(GatedDeltaDecoder)/GatedDeltaDecoderBlock_2/"),
    ("recompute", "transpose(jvp(GatedDeltaDecoder))/tm.fwd_bwd/jvp("
     "GatedDeltaDecoder)/checkpoint/rematted_computation/"
     "GatedDeltaDecoderBlock_2/"),
    ("backward",
     "transpose(jvp(GatedDeltaDecoder))/GatedDeltaDecoderBlock_2/"),
])
def test_an_operation_of_the_loop_has_a_bucket_of_its_own(phase, wrap):
    from benchmark import model_scopes

    op = ("jit(tm_train_step)/shard_map/tm.fwd_bwd/" + wrap
          + "tm.lm.gdn_state/while/body/closed_call/checkpoint/dot_general")
    assert model_scopes.bucket_of(op) == "tm.lm.gdn_state"
    assert model_scopes.phase_of(op) == phase
    assert model_scopes.bucket_of(
        op.replace("gdn_state/while/body/closed_call/checkpoint", "gdn_chunk")
    ) == "tm.lm.gdn_chunk"


# -- the benchmark's configuration --------------------------------------------
def test_the_file_keeps_every_catalog_number():
    """Every number of the catalog's entry under its own key at its
    published value, but those that are cut, which ``reduced`` and
    ``published`` name: counts of layers, experts and rows, never a
    width."""
    cfg = json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    catalog = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
    }
    for key, value in catalog.items():
        assert cfg[key] == value and key not in cfg["reduced"], key
    cut = {"num_hidden_layers": (4, 48), "num_experts": (16, 512),
           "vocab_size": (18992, 151936)}
    assert sorted(cut) == sorted(cfg["reduced"])
    for key, (here, published) in cut.items():
        assert cfg[key] == here and cfg["published"][key] == published
    assert cfg["vocab_size"] * 8 == 151936
    assert cfg["model"] == {**cfg["model"], "router_outputs": 512,
                            "experts_held": list(range(16)), "gdn_chunk": 64}
    assert cfg["sequence_length"] == 16384 and cfg["per_chip_batch"] == 1
    assert "32 chips" in cfg["deployment"] and "16 a chip" in cfg["deployment"]
    assert {"multi_token_prediction", "auxiliary_loss"} == set(
        cfg["departures"])
    assert {"norms", "output_gate", "rotary", "linear_mixer",
            "shared_expert", "router", "weights"} <= set(cfg["assumed"])
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    tiny = cfg["rehearsal"]
    # a whole period, 2 value heads to a key head, a head wider than what is
    # rotated, 2 of 8 experts at 3 a token, 2.5 chunks a sequence
    assert tiny["linear_num_value_heads"] == 2 * tiny["linear_num_key_heads"]
    assert tiny["head_dim"] * cfg["partial_rotary_factor"] == 4
    assert tiny["model"]["experts_held"] == [0, 1]
    assert tiny["model"]["router_outputs"] == 8
    assert tiny["sequence_length"] == 2.5 * tiny["model"]["gdn_chunk"]


def test_flops_of_the_configuration_are_the_issues_arithmetic():
    from benchmark import configs, deltanet_decoder_flops as count

    cfg = configs.load(CONFIG)
    forward = lambda **over: (  # noqa: E731
        count.deltanet_decoder_forward_flops(**{**dict(
            seq=16384, d_model=2048, linear_layers=3, full_layers=1,
            key_heads=16, value_heads=32, dk=128, dv=128, taps=4, heads=16,
            kv_heads=2, head_dim=256, expert_width=512, shared_width=512,
            experts=512, top_k=10, held=16, vocab=18992), **over}))
    whole = forward()
    t = 16384
    assert 520.5e6 < whole / t < 522e6           # ISSUE 45: 521 MFLOP a token
    assert configs.build(CONFIG, cfg).flops_per_sample == 3 * whole
    assert 25.5e12 < 3 * whole < 25.7e12         # 25.6 TFLOP a step
    # each part by itself
    linear = (whole - forward(linear_layers=2)) / t
    full = (whole - forward(full_layers=0)) / t
    assert 81e6 < linear < 82e6 and 199e6 < full < 200e6  # with the experts'
    assert whole - forward(vocab=0) == 2 * t * 2048 * 18992
    assert whole - forward(held=0) == 4 * (t * 10 * 16 * 6 * 2048 * 512 // 512)
    assert whole - forward(shared_width=0) == 4 * t * 6 * 2048 * 512
    # the rule by the recurrence's operations, whatever the chunk
    assert count.delta_rule_forward_flops(t, 32, 128, 128) == (
        t * 32 * 7 * 128 * 128)
    assert forward(value_heads=33) - whole == 3 * (
        2 * t * 2048 * (2 * 128 + 2) + 2 * t * 128 * 2048 + 2 * t * 4 * 128
        + t * 7 * 128 * 128)


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_the_held_experts_stand_under_the_routers_as_seeded(seed):
    """The cell holds experts 0 to 15 under routers whose columns are as the
    seed made them (ISSUE 45; no column is moved to choose the group held):
    a seed is one state however often it is made, seeds past 32 signed bits
    too, another seed another, and a router's columns are one population
    (std 0.02, held or not)."""
    from benchmark import configs

    cfg = configs.load(CONFIG, rehearse=True)
    built = configs.build(CONFIG, cfg)
    params, again, other = (
        jax.device_get(built.make_state(s)[0]) for s in (seed, seed, seed + 1))
    same = lambda a, b: jax.tree_util.tree_all(  # noqa: E731
        jax.tree_util.tree_map(
            lambda x, y: bool(np.array_equal(x, y)), a, b))
    assert same(params, again) and not same(params, other)
    held = len(cfg["model"]["experts_held"])
    for i in range(cfg["num_hidden_layers"]):
        router = params[f"GatedDeltaDecoderBlock_{i}"]["router"]["kernel"]
        assert router.shape == (
            cfg["hidden_size"], cfg["model"]["router_outputs"])
        for part in (router[:, :held], router[:, held:]):
            assert 0.015 < float(np.std(part)) < 0.025
