"""The decoder that selects its keys (models/decoder.py with
``selected_layout``) against the benchmark's plain float32 reference of the
configuration that runs it (``benchmark/reference/keye-vl-2-30b-a3b.py``,
loaded by path, which imports nothing of the program): the two loss terms
and every gradient leaf, its recomputation, three engine steps and its
scopes. Tiny sizes that keep what matters: 4 query to 2 KV heads, 3 index
heads, a selection far smaller than the sequence. (The operation itself:
``tests/test_selected_attention.py``.)"""

import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import torchmpi_tpu as mpi
from selected_attention_cases import (
    ROOT,
    index_kernel_name,
    kernel_calls,
    lowered_for_tpu,
)
from torchmpi_tpu import telemetry
from torchmpi_tpu.engine import AllReduceSGDEngine
from torchmpi_tpu.models import (
    MoEDecoder,
    init_lm_params,
    init_moe_state,
    make_moe_lm_loss_fn,
)
from torchmpi_tpu.telemetry import names

CONFIG = "keye-vl-2-30b-a3b"


@pytest.fixture(scope="module")
def plain():
    path = ROOT / "benchmark" / "reference" / f"{CONFIG}.py"
    spec = importlib.util.spec_from_file_location("plain_keye", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
