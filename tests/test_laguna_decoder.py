"""The decoder held by share (models/decoder.py with heads by layer, a
gate on each head, YaRN over a part of the head, a sigmoid router, a shared
expert, a leading dense layer) against plain arithmetic: numbers worked by
hand, and the benchmark's plain float32 reference of the configuration
that runs it (``benchmark/reference/laguna-s-2-1.py``, loaded by path,
which imports nothing of the program). Tiny sizes that keep what matters:
layer kinds full, sliding, sliding, sliding, full with 2 and 3 query heads
to a KV head, a window shorter than the sequence, half a head rotated, 8
experts at 3 a token, layer 0 dense."""

import hashlib
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
    MoEDecoderBlock,
    Rotary,
    init_lm_params,
    init_moe_state,
    make_moe_lm_loss_fn,
)
from torchmpi_tpu.models.decoder import rotary_part, yarn_inv_freq
from torchmpi_tpu.parallel import (
    moe_local_experts,
    sigmoid_route_weights,
    softmax_route_weights,
)
from torchmpi_tpu.telemetry import names

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
CONFIG = "laguna-s-2-1"
SEQ = 40
KINDS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = Rotary(500000.0, 64, 128.0, 8192, 32.0, 1.0, 1.4852030263919618)


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


def tiny_cfg(kv_heads=1, held=(0, 1), columns=24):
    """The published keys at test sizes, as the reference reads them:
    ``kv_heads`` KV heads each with 2 (full) or 3 (sliding) query heads."""
    return {
        "hidden_size": 32, "head_dim": 8, "num_key_value_heads": kv_heads,
        "num_attention_heads_per_layer": [
            kv_heads * (3 if k == "sliding_attention" else 2) for k in KINDS],
        "layer_types": KINDS, "mlp_layer_types": ["dense"] + ["sparse"] * 4,
        "num_hidden_layers": 5, "sliding_window": 12, "rms_norm_eps": 1e-6,
        "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
        "num_experts_per_tok": 3, "moe_routed_scaling_factor": 2.5,
        "vocab_size": 61,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 16, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000,
                "partial_rotary_factor": 1}},
        "model": {"router_outputs": 8, "experts_held": list(held),
                  "dense_columns_held": columns},
        "optimizer": {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9,
                      "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01},
    }


def rope_of(cfg):
    full = cfg["rope_parameters"]["full_attention"]
    return Rotary(
        float(full["rope_theta"]),
        int(cfg["head_dim"] * full["partial_rotary_factor"]),
        float(full["factor"]), full["original_max_position_embeddings"],
        float(full["beta_fast"]), float(full["beta_slow"]),
        full["attention_factor"])


def tiny_model(cfg, dtype=jnp.float32, remat=True):
    return MoEDecoder(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=tuple(cfg["num_attention_heads_per_layer"]),
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        expert_width=cfg["moe_intermediate_size"],
        num_experts=cfg["model"]["router_outputs"],
        top_k=cfg["num_experts_per_tok"],
        held=tuple(cfg["model"]["experts_held"]),
        window=cfg["sliding_window"], window_layout=(0, 1, 1, 1),
        rope_layout=(1,), rope_theta=1e4, rope_full=rope_of(cfg),
        attn_block=8, activation=jax.nn.silu, router_after_norm=True,
        head_gate=True,
        route_weights=sigmoid_route_weights(cfg["moe_routed_scaling_factor"]),
        shared_width=cfg["shared_expert_intermediate_size"],
        dense_layers=1, dense_width=cfg["model"]["dense_columns_held"],
        remat=remat, dtype=dtype)


def seeded(shapes, seed=0, std=0.3):
    """Seeded normal weights large enough that routing, the gates and
    attention are far from uniform; norm scales 1."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten([
        jnp.ones(s.shape, jnp.float32)
        if str(getattr(p[-1], "key", "")) == "scale"
        else std * jax.random.normal(k, s.shape, jnp.float32)
        for (p, s), k in zip(leaves, keys)])


def seeded_params(model, seq=SEQ):
    return seeded(jax.eval_shape(lambda: init_lm_params(model, seq)))


def tokens(n, seq, vocab, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, vocab, size=(n, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


# -- numbers worked by hand -------------------------------------------------
def test_yarn_frequencies_are_the_numbers_worked_by_hand(plain):
    """D = 64, theta 5e5, factor 128 from 8,192 positions, 32 and 1 turns:
    ln 5e5 = 13.122363; low = floor(64 ln(8192 / (32 x 2 pi)) / (2 ln 5e5))
    = floor(64 x 3.70729 / 26.24473) = floor(9.04) = 9; high = ceil(64
    ln(8192 / (2 pi)) / 26.24473) = ceil(64 x 7.17304 / 26.24473) =
    ceil(17.49) = 18. So index 9 and under keep f_i, 18 and over take f_i /
    128, and index 12 is a third of the way: f_12 (1/3 / 128 + 2/3)."""
    inv = yarn_inv_freq(PUBLISHED)
    assert inv.shape == (32,) and inv.dtype == np.float32
    f = lambda i: math.exp(-2 * i / 64 * 13.122363)  # noqa: E731
    np.testing.assert_allclose(inv[0], 1.0)
    np.testing.assert_allclose(inv[9], 0.024955, rtol=1e-4)   # f_9
    np.testing.assert_allclose(inv[9], f(9), rtol=1e-5)
    np.testing.assert_allclose(
        inv[12], f(12) * (1 / 3 / 128 + 2 / 3), rtol=1e-5)
    np.testing.assert_allclose(inv[17], f(17) * (8 / 9 / 128 + 1 / 9),
                               rtol=1e-5)
    np.testing.assert_allclose(inv[18], 4.8654e-6, rtol=1e-4)  # f_18 / 128
    np.testing.assert_allclose(inv[31], f(31) / 128, rtol=1e-5)
    # the plain reference works them out by itself
    width, want = plain.frequencies({
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "partial_rotary_factor": 0.5}, 128)
    assert width == 64
    np.testing.assert_array_equal(inv, want)


def test_a_part_of_the_head_is_rotated_and_the_rest_passes():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 3, 16))
    rope = Rotary(500000.0, 8, 128.0, 16, 32.0, 1.0, 1.5)
    y = rotary_part(x, rope)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
    # position 0 turns nothing: cos 1, sin 0, times the attention factor
    np.testing.assert_allclose(y[:, 0, :, :8], 1.5 * x[:, 0, :, :8],
                               rtol=1e-6)
    # a rotation of pairs (i, i + 4) scaled by 1.5: their norms say so
    pair = lambda a, i: np.hypot(a[..., i], a[..., i + 4])  # noqa: E731
    for i in range(4):
        np.testing.assert_allclose(pair(y, i), 1.5 * pair(x, i), rtol=1e-5)
    assert not np.allclose(y[:, 5, :, :8], 1.5 * x[:, 5, :, :8])


@pytest.mark.parametrize("scale", [1.0, 2.5])
def test_the_sigmoid_rules_weights_sum_to_the_scale(scale):
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    weight, chosen = sigmoid_route_weights(scale)(logits, 5)
    np.testing.assert_allclose(weight.sum(axis=-1), scale, rtol=1e-6)
    # the sigmoid is monotone: the experts of the 5 largest logits, each
    # weighted by its own score over the chosen ones' sum
    np.testing.assert_array_equal(chosen, jax.lax.top_k(logits, 5)[1])
    score = jax.nn.sigmoid(jnp.take_along_axis(logits, chosen, axis=-1))
    np.testing.assert_allclose(
        weight, scale * score / score.sum(axis=-1, keepdims=True), rtol=1e-6)
    # the default rule: the softmax over the chosen logits
    weight, chosen = softmax_route_weights(logits, 5)
    np.testing.assert_allclose(
        weight, jax.nn.softmax(jax.lax.top_k(logits, 5)[0], axis=-1))


def test_the_rule_is_all_that_differs_in_the_expert_layer():
    """The layer under the sigmoid rule against a per-expert loop with the
    same weights; and handed the default rule by name it lowers to the
    text it lowers to with no rule given."""
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (48, 16))
    logits = 2.0 * jax.random.normal(ks[1], (48, 8))
    w = [0.3 * jax.random.normal(k, s) for k, s in zip(
        ks[2:], [(8, 16, 12), (8, 16, 12), (8, 12, 16)])]
    rule = sigmoid_route_weights(2.5)
    y, load, _ = moe_local_experts(
        x, logits, 3, *[a[:4] for a in w], [0, 1, 2, 3],
        activation=jax.nn.silu, route_weights=rule)
    weight, chosen = rule(logits, 3)
    want = 0.0
    for e in range(4):
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        want = want + w_e[:, None] * (
            (jax.nn.silu(x @ w[0][e]) * (x @ w[1][e])) @ w[2][e])
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert float(load.sum()) == float(jnp.sum(chosen < 4))

    def text(**rule):
        return jax.jit(lambda x, lg, *w: moe_local_experts(
            x, lg, 3, *w, [0, 1, 2, 3], **rule)[0]).lower(
                x, logits, *[a[:4] for a in w]).as_text()

    assert text() == text(route_weights=softmax_route_weights)
    assert text() != text(route_weights=rule)


# The decoder cells' whole steps (loss, gradients, AdamW) at
# their rehearsals' sizes, as lowered for the CPU: the characters and the
# first 16 of the text's sha256, as the parent of the PR that added the
# route rule, the heads by layer, the gate, the shared expert and the
# dense layer lowered them (PERF.md section 6, PR 31 and PR 32). A PR that
# means to change those steps changes these; one that does not, must not.
# PR 38 meant one change and made it: ``blocked_self_attention``'s loops are
# traced once a shape (``jax.jit(..., inline=True)``), so the lowering
# writes ONE function for the loops' bodies that the layers share where it
# wrote one a layer: the same operations in fewer characters (697,739 ->
# 658,943 and 934,255 -> 877,075; 56 -> 54 and 70 -> 67 functions), and
# ``keye-vl-2-30b-a3b``, whose layers all select, as it was. Lowered for the
# TPU at the cells' own sizes, where the loops are dropped, all three are
# the parent's text letter for letter:
# each ``test_chip_<config>.py``'s ``PIN`` holds one.
# PR 42 meant one change too: the token lookup has a derivative rule of its
# own (``models/embedding.py``), so each step's backward ends in a sort, a
# loop of one-hot products and a gather where jax's scatter-add of the
# embedding's rows stood (658,943 9b0c6898b47d4450, 1,205,758
# 55eca190dcff1715 and 877,075 9b3ffce1528a44a1 before). At the rehearsals'
# width of 64 all three take it; at the cells' own widths two of them keep
# jax's transpose (``embedding.takes_sorted_sum``;
# ``test_chip_<config>.py`` pins those).
# PR 43 meant one change as well: the head and its loss are one function
# with a derivative rule of its own (``models/lm_head.py``) that makes the
# three gradients in forward, while the logits exist; at the rehearsals'
# sizes the rows fit one block, so it is one visit and no loop (674,337
# e723f486eb70e742, 1,221,274 beddecbeff4a157f and 892,530 dd63b85e94a9ce73
# before).
LOWERED = {
    # the loops name nothing a recomputation could keep, so the selecting
    # layers' policy (PR 36) moved none of the three; since PR 47 the blocks'
    # dense products' results bear names (``models.lm.product``) and a CPU,
    # which reports no memory, keeps every kind: backward reads them and
    # makes none again (673,550 f07f688062ecf681, 1,220,489
    # 930b6c2ebd7b1451 and 891,753 f0bf817fddd1c07f before)
    "smallthinker-21b-a3b": (672017, "fc032d96c25e70db"),
    "keye-vl-2-30b-a3b": (1217911, "94fd16c6e58aa759"),
    "laguna-s-2-1": (888808, "2fea3a172a2cfcfc"),
}


@pytest.mark.parametrize("config", sorted(LOWERED))
def test_the_decoders_steps_lower_to_the_text_they_had(config):
    from benchmark import configs

    cfg = configs.load(config, rehearse=True)
    built = configs.build(config, cfg)

    def step(params, opt_state, state, batch):
        (loss, state), grads = jax.value_and_grad(
            built.loss_fn, has_aux=True)(params, state, batch)
        updates, opt_state = built.optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, state, loss

    params, state = jax.eval_shape(built.state_at, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct(
        (cfg["per_chip_batch"], cfg["sequence_length"]), jnp.int32)
    text = jax.jit(step).lower(
        params, jax.eval_shape(built.optimizer.init, params), state,
        (ids, ids)).as_text()
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()[:16]) == (
        LOWERED[config])


# -- the shares add up --------------------------------------------------------
def block_of(cfg, kind, heads, kv_heads, held=(), dense=None):
    sliding = kind == "sliding_attention"
    return MoEDecoderBlock(
        num_heads=heads, num_kv_heads=kv_heads, head_dim=cfg["head_dim"],
        expert_width=cfg["moe_intermediate_size"],
        num_experts=cfg["model"]["router_outputs"],
        top_k=cfg["num_experts_per_tok"], held=tuple(held),
        window=cfg["sliding_window"] if sliding else None,
        rope_theta=1e4, rope=None if sliding else rope_of(cfg),
        attn_block=8, activation=jax.nn.silu, router_after_norm=True,
        head_gate=True,
        route_weights=sigmoid_route_weights(cfg["moe_routed_scaling_factor"]),
        shared_width=cfg["shared_expert_intermediate_size"],
        dense_width=dense)


def whole_layer(cfg, kind, sparse=True):
    """(the uncut layer's block, its seeded parameters, an input)."""
    heads = cfg["num_attention_heads_per_layer"][KINDS.index(kind)]
    block = block_of(
        cfg, kind, heads, cfg["num_key_value_heads"],
        held=range(8), dense=None if sparse else 24)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, SEQ, 32))
    shapes = jax.eval_shape(
        lambda: block.init(jax.random.PRNGKey(0), x))["params"]
    return block, seeded(shapes, seed=3), x


def columns(kernel, share, shares):
    """The columns of ``kernel`` that share ``share`` of ``shares`` holds:
    one run of them (a KV head's query heads lie together)."""
    n = kernel.shape[-1] // shares
    return kernel[..., share * n:(share + 1) * n]


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_the_head_shares_add_up_to_the_uncut_attention_sublayer(plain, kind):
    """2 KV heads with their groups of 2 (full) or 3 (sliding) query
    heads, one KV head a share: each share's block, its feed-forward
    silenced, adds its heads' part to the residual stream; the two parts
    added are what the plain reference's attention sublayer gives for the
    layer with all the heads."""
    cfg = tiny_cfg(kv_heads=2, held=range(8))
    block, p, x = whole_layer(cfg, kind)
    heads = block.num_heads
    with jax.default_matmul_precision("highest"):
        want = plain.attention_part(x[0], p, cfg, kind, "float32")
        total = 0.0
        for s in range(2):
            part = {
                **p,
                **{n: {"kernel": columns(p[n]["kernel"], s, 2)}
                   for n in ("q", "k", "v", "head_gate")},
                # the heads' rows of the output projection
                "o": {"kernel": columns(p["o"]["kernel"].T, s, 2).T},
                # no feed-forward: what is left is h + this share's heads
                "shared_down": {"kernel": jnp.zeros_like(
                    p["shared_down"]["kernel"])},
                "experts_down": jnp.zeros_like(p["experts_down"]),
            }
            share = block_of(cfg, kind, heads // 2, 1, held=range(8))
            total = total + share.apply({"params": part}, x)[0][0] - x[0]
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(total, want, atol=2e-5)


@pytest.mark.parametrize("shares", [
    [[0, 1], [2, 3], [4, 5], [6, 7]],     # 4 shares of 2 experts
    [[6, 1], [0, 7, 3], [2], [5, 4]],     # uneven shares, out of order
])
def test_the_expert_shares_add_up_to_the_uncut_expert_sublayer(
        plain, shares):
    """Given the whole ``h'`` (the attention silenced, so that the layer's
    input is it): each share's routed part, added, and the shared expert
    counted once (share 0 carries it), are what the plain reference gives
    for the sublayer with all 8 experts."""
    cfg = tiny_cfg(kv_heads=1, held=range(8))
    block, p, x = whole_layer(cfg, "sliding_attention")
    p = {**p, "o": {"kernel": jnp.zeros_like(p["o"]["kernel"])}}
    with jax.default_matmul_precision("highest"):
        want = plain.feed_forward_part(x[0], p, cfg, True, "float32")
        total, routes = 0.0, 0.0
        for s, held in enumerate(shares):
            sel = jnp.asarray(held)
            part = {**p, **{n: p[n][sel] for n in (
                "experts_gate", "experts_up", "experts_down")}}
            if s:  # every chip computes it alike: counted once
                part["shared_down"] = {"kernel": jnp.zeros_like(
                    p["shared_down"]["kernel"])}
            share = block_of(cfg, "sliding_attention", 3, 1, held=held)
            out, (load, *_) = share.apply({"params": part}, x)
            total, routes = total + out[0] - x[0], routes + float(load.sum())
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert routes == SEQ * 3  # every route, once


def test_the_column_shares_add_up_to_the_uncut_dense_feed_forward(plain):
    cfg = tiny_cfg(kv_heads=1)
    block, p, x = whole_layer(cfg, "full_attention", sparse=False)
    p = {**p, "o": {"kernel": jnp.zeros_like(p["o"]["kernel"])}}
    with jax.default_matmul_precision("highest"):
        want = plain.feed_forward_part(x[0], p, cfg, False, "float32")
        total = 0.0
        for s in range(2):
            part = {
                **p,
                "mlp_gate": {"kernel": columns(p["mlp_gate"]["kernel"], s, 2)},
                "mlp_up": {"kernel": columns(p["mlp_up"]["kernel"], s, 2)},
                "mlp_down": {"kernel": columns(
                    p["mlp_down"]["kernel"].T, s, 2).T},
            }
            share = block_of(cfg, "full_attention", 2, 1, dense=12)
            total = total + share.apply({"params": part}, x)[0][0] - x[0]
    np.testing.assert_allclose(total, want, atol=2e-5)


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


@pytest.mark.parametrize("held", [[0, 1], [3, 6, 7], list(range(8))],
                         ids=["held2", "held3", "all8"])
def test_decoder_loss_and_gradients_match_the_plain_reference(plain, held):
    cfg = tiny_cfg(held=held)
    model = tiny_model(cfg)
    params = seeded_params(model)
    assert {"head_gate", "mlp_gate", "mlp_up", "mlp_down"} <= set(
        params["MoEDecoderBlock_0"]) and "router" not in params[
            "MoEDecoderBlock_0"]
    assert {"head_gate", "router", "shared_gate", "shared_down",
            "experts_up"} <= set(params["MoEDecoderBlock_4"])
    assert params["MoEDecoderBlock_0"]["q"]["kernel"].shape == (32, 16)
    assert params["MoEDecoderBlock_1"]["q"]["kernel"].shape == (32, 24)
    assert params["MoEDecoderBlock_1"]["k"]["kernel"].shape == (32, 8)
    x, y = tokens(3, SEQ, cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        (loss, state), grads = jax.jit(jax.value_and_grad(
            make_moe_lm_loss_fn(model), has_aux=True))(
                params, init_moe_state(model),
                (jnp.asarray(x), jnp.asarray(y)))
        want, want_g = plain_loss_and_grads(plain, cfg, params, x, y)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))),
        grads, want_g)))
    assert worst < 2e-4, worst
    # the dense layer routes nothing: four layers' loads
    assert state["moe_load"].shape == (4, len(held))
    assert state["moe_rows"].shape == (4,)
    if len(held) == 8:
        np.testing.assert_array_equal(
            state["moe_load"].sum(axis=1), 3 * SEQ * 3)


def test_two_engine_steps_match_the_reference_and_set_the_gauges(plain):
    """``engine.train`` for two AdamW steps against the reference's
    ``follow`` on the same batches: each step's loss, the first moment's
    and the parameters' change leaf by leaf; and what of its layers the
    device holds, as the gauges say it."""
    cfg = tiny_cfg()
    model = tiny_model(cfg)
    params = seeded_params(model)
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
    assert value(names.GAUGE_ATTN_HEADS_HELD) == 2 + 3 + 3 + 3 + 2
    # the expert layers' gauges count the four layers that have experts
    assert value("tm_moe_routes_per_step") == 2 * SEQ * 3 * 4
    assert np.asarray(engine.model_state["moe_load"]).shape == (4, 2)


def test_the_new_scopes_nest_under_fwd_bwd_in_the_lowered_step():
    from benchmark import inner_scopes, scopes

    cfg = tiny_cfg()
    model = tiny_model(cfg)
    mpi.start(devices=jax.devices()[:1])
    engine = AllReduceSGDEngine(
        make_moe_lm_loss_fn(model), seeded_params(model),
        optimizer=optax.sgd(0.1), model_state=init_moe_state(model))
    x, y = tokens(2, SEQ, cfg["vocab_size"])
    text = engine._step_fn.lower(
        engine.params, engine.opt_state, engine.model_state,
        engine._prepare_batch((x, y))).as_text(debug_info=True)
    ops = set(re.findall(r'"(jit\(tm_train_step\)[^"]*)"', text))
    seen = {}
    for op in ops:
        for scope in ("tm.attn.gate", "tm.moe.shared", "tm.moe.dense"):
            if scope in op:
                assert scopes.scope_of(op) == "tm.fwd_bwd", op
                seen.setdefault(scope, set()).add("transpose(" in op)
    # forward and backward alike, seen through jax's wrappers
    assert seen == {s: {False, True} for s in (
        "tm.attn.gate", "tm.moe.shared", "tm.moe.dense")}, seen
    inner = {inner_scopes.inner_scope_of(op) for op in ops}
    assert {"tm.attn.gate", "tm.moe.shared", "tm.moe.dense", "tm.attn.full",
            "tm.attn.window", "tm.moe.route"} <= inner
    # the dense layer opens no expert scope, the others no dense one
    assert not [op for op in ops if "MoEDecoderBlock_0/tm.moe" in op
                and "tm.moe.dense" not in op]
    assert not [op for op in ops if "tm.moe.dense" in op
                and "MoEDecoderBlock_0" not in op]


def test_a_model_needs_a_layer_with_experts():
    cfg = tiny_cfg()
    model = tiny_model(cfg).clone(dense_layers=5)
    with pytest.raises(ValueError, match="dense_layers"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
