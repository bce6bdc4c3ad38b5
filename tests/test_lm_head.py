"""The vocabulary head's own derivative rule (``models/lm_head.py``): the
loss and its gradients a block of rows at a time, against ``jax.grad`` of
``lm_cross_entropy(scale * (x @ W + b), targets)`` in float32."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from torchmpi_tpu import telemetry
from torchmpi_tpu.models import (
    HybridDecoder,
    LongContextTransformer,
    MoEDecoder,
    Multipliers,
    RetentionDecoder,
    init_lm_params,
    init_moe_state,
    lm_head,
    make_lm_loss_fn,
    make_moe_lm_loss_fn,
)
from torchmpi_tpu.models.lm_head import (
    VocabHead,
    block_rows,
    blocked_head_loss,
    head_loss,
)
from torchmpi_tpu.models.lm import lm_cross_entropy
from torchmpi_tpu.telemetry import names

V, D = 97, 24  # the rehearsals' vocabulary


def problem(rows, bias, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((rows, D)), dtype)
    kernel = jnp.asarray(rng.standard_normal((D, V)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal(V), jnp.float32) if bias else None
    targets = jnp.asarray(rng.integers(0, V, rows), jnp.int32)
    return x, kernel, b, targets


def plain(x, kernel, bias, scale, targets):
    """What the models spelled before the rule: ``fnn.Dense`` in float32,
    the scale, ``lm_cross_entropy``."""
    logits = x.astype(jnp.float32) @ kernel
    if bias is not None:
        logits = logits + bias
    return lm_cross_entropy(scale * logits, targets)


def close(got, want, what, tol=2e-6):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    np.testing.assert_allclose(
        got, want, rtol=0, atol=tol * max(float(np.abs(want).max()), 1e-30),
        err_msg=what)


@pytest.mark.parametrize("bias,scale,dtype", [
    (False, 1.0, jnp.float32), (True, 3.0, jnp.float32),
    (False, 0.0078125, jnp.bfloat16), (True, 1.0, jnp.bfloat16)],
    ids=["no_bias-1-f32", "bias-3-f32", "no_bias-128th-bf16", "bias-1-bf16"])
@pytest.mark.parametrize("rows,block", [
    (96, 32),    # whole blocks
    (100, 32),   # the last block is part padding
    (33, 32),    # one row past a block
    (40, 64),    # the rows fit one block: one visit, no loop
    (64, 64),
    (7, 1)])     # a row a block
def test_the_loss_and_its_gradients_are_jax_grads(
        rows, block, bias, scale, dtype):
    """Loss, ``dx``, ``dW``, ``db`` with a cotangent of 2.5 (the rule's
    backward is the multiply), and the loss without a derivative."""
    x, kernel, b, targets = problem(rows, bias, dtype)
    wrt = (0, 1, 2) if bias else (0, 1)
    want_loss, want = jax.value_and_grad(
        lambda *a: 2.5 * plain(*a, scale, targets), wrt)(x, kernel, b)
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda *a: 2.5 * blocked_head_loss(*a, scale, targets, block),
        wrt))(x, kernel, b)
    close(got_loss, want_loss, "loss")
    alone = jax.jit(
        lambda *a: blocked_head_loss(*a, scale, targets, block))(x, kernel, b)
    close(2.5 * alone, want_loss, "the loss without a derivative")
    for name, g, w, like in zip(("dx", "dW", "db"), got, want, (x, kernel, b)):
        assert (g.shape, g.dtype) == (like.shape, like.dtype), name
        # a bfloat16 ``dx`` is one rounding of the float32 sums
        close(g, w, name, 8e-3 if g.dtype == jnp.bfloat16 else 2e-6)
        assert float(jnp.abs(g.astype(jnp.float32)).max()) > 0


def test_the_walk_holds_no_array_of_all_rows_by_the_vocabulary():
    """384 rows by blocks of 64: the traced loss and its gradients hold a
    block's ``[64, V]`` and nothing of ``[384, V]``; three products and one
    loop, whose body is traced once."""
    x, kernel, _, targets = problem(384, False, jnp.float32)
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda x, k: blocked_head_loss(x, k, None, 1.0, targets, 64),
        (0, 1)))(x, kernel))
    assert f"384,{V}]" not in text and f"64,{V}]" in text
    assert text.count("dot_general") == 3
    assert text.count("scan[") == 1
    whole = str(jax.make_jaxpr(jax.value_and_grad(
        lambda x, k: plain(x, k, None, 1.0, targets), (0, 1)))(x, kernel))
    assert f"384,{V}]" in whole


def test_the_rule_inside_shard_map_with_the_engines_check_vma_off():
    """As the engine's replicated step runs it: every device its own rows,
    the weight gradient summed over the axis."""
    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ("dp",))
    n = len(devices)
    x, kernel, b, targets = problem(n * 40, True, jnp.float32)

    def local(x, kernel, b, targets):
        loss, grads = jax.value_and_grad(
            lambda *a: blocked_head_loss(*a, 0.5, targets, 16),
            (0, 1, 2))(x, kernel, b)
        return (jax.lax.pmean(loss, "dp"), grads[0],
                jax.lax.pmean(grads[1], "dp"), jax.lax.pmean(grads[2], "dp"))

    got = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("dp"), P(), P(), P("dp")),
        out_specs=(P(), P("dp"), P(), P()), check_vma=False))(
            x, kernel, b, targets)
    want_loss, want = jax.value_and_grad(
        lambda *a: plain(*a, 0.5, targets), (0, 1, 2))(x, kernel, b)
    close(got[0], want_loss, "loss")
    close(got[1] / n, want[0], "dx")  # a shard's mean is over its own rows
    close(got[2], want[1], "dW")
    close(got[3], want[2], "db")


# -- the block, from the shapes ----------------------------------------------
@pytest.mark.parametrize("rows,vocab,block", [
    (16384, 32640, 8192),   # falcon-h1-34b: 1,020 MiB of logits a block
    (32768, 18992, 8192),   # brumby-14b
    (16384, 18992, 8192),   # smallthinker-21b-a3b, keye-vl-2-30b-a3b
    (16384, 12544, 8192),   # laguna-s-2-1: two visits, though one would fit
    (8192, 50257, 4096),    # gpt2-medium, either cell: the byte limit
    (64, 97, 64),           # a rehearsal: one visit
    (8192, 32640, 8192), (8193, 32640, 8192), (16384, 131072, 2048),
    (10, 10**9, 8)])
def test_the_block_is_read_off_the_shapes(rows, vocab, block):
    assert block_rows(rows, vocab) == block
    # a power of two within both limits, but never under 8 rows
    assert block == rows or block & (block - 1) == 0
    assert block in (rows, 8, lm_head.BLOCK_ROWS) or (
        4 * block * vocab <= lm_head.LOGITS_BLOCK_BYTES < 8 * block * vocab)
    assert block <= max(lm_head.BLOCK_ROWS, 8)


def test_the_gauges_read_the_rows_and_the_blocks(monkeypatch):
    rows_gauge = telemetry.metrics.gauge(names.GAUGE_LM_HEAD_BLOCKED_ROWS, "")
    blocks_gauge = telemetry.metrics.gauge(names.GAUGE_LM_HEAD_BLOCKS, "")
    x, kernel, b, targets = problem(2 * 52, True, jnp.float32)
    x, targets = x.reshape(2, 52, D), targets.reshape(2, 52)
    for limit, blocks in ((lm_head.LOGITS_BLOCK_BYTES, 1),
                          (4 * V * 32, 4)):
        monkeypatch.setattr(lm_head, "LOGITS_BLOCK_BYTES", limit)
        rows_gauge.set(-1)
        blocks_gauge.set(-1)
        # a function of its own a round: nothing traced is found again
        loss = jax.jit(lambda *a: head_loss(*a[:3], 2.0, a[3]))(
            x, kernel, b, targets)
        close(loss, plain(x, kernel, b, 2.0, targets), "loss")
        assert (rows_gauge.value(), blocks_gauge.value()) == (104, blocks)
    snapshot = telemetry.metrics.snapshot()
    assert snapshot[names.GAUGE_LM_HEAD_BLOCKED_ROWS]["series"][""] == 104


def test_the_module_has_denses_parameters_and_logits():
    """``VocabHead`` is ``fnn.Dense`` to whoever asks for logits: the same
    parameter tree from the same key, the same logits times the scale."""
    import flax.linen as fnn

    x = problem(12, True, jnp.float32)[0].reshape(2, 6, D)
    key = jax.random.PRNGKey(5)
    for use_bias in (True, False):
        head = VocabHead(V, use_bias=use_bias, dtype=jnp.float32, scale=0.5)
        dense = fnn.Dense(V, use_bias=use_bias, dtype=jnp.float32)
        params, want = head.init(key, x), dense.init(key, x)
        assert jax.tree_util.tree_structure(params) == (
            jax.tree_util.tree_structure(want))
        for a, b in zip(*map(jax.tree_util.tree_leaves, (params, want))):
            assert a.dtype == b.dtype and np.array_equal(a, b)
        np.testing.assert_array_equal(
            head.apply(params, x), 0.5 * dense.apply(want, x))
        # initialised through the loss, the tree is the same
        targets = jnp.zeros((2, 6), jnp.int32)
        for a, b in zip(*map(jax.tree_util.tree_leaves, (
                head.init(key, x, targets), want))):
            assert a.dtype == b.dtype and np.array_equal(a, b)


# -- the four models' losses ---------------------------------------------------
def _models():
    small = dict(vocab_size=V, num_layers=2, d_model=32)
    return {
        "gpt2": LongContextTransformer(
            num_heads=2, head_dim=16, max_len=64, **small),
        "moe": MoEDecoder(
            num_heads=2, num_kv_heads=1, head_dim=16, expert_width=32,
            num_experts=4, top_k=2, held=(0, 1, 2, 3), **small),
        "hybrid": HybridDecoder(
            num_heads=2, num_kv_heads=1, head_dim=16, ssm_heads=2,
            ssm_head_dim=16, ssm_state=16, mlp_width=64, chunk=8,
            multipliers=Multipliers(lm_head=0.25), **small),
        "retention": RetentionDecoder(
            num_heads=2, num_kv_heads=1, head_dim=16, mlp_width=64,
            chunk=8, **small),
    }


@pytest.mark.parametrize("name,blocks", [
    ("gpt2", "one_visit"), ("gpt2", "four_blocks"), ("moe", "four_blocks"),
    ("hybrid", "four_blocks"), ("retention", "four_blocks")])
def test_a_models_loss_is_what_it_was_through_lm_cross_entropy(
        name, blocks, monkeypatch):
    """``make_*_loss_fn`` (the head's rule) against ``lm_cross_entropy`` of
    the same model's logits, on one parameter tree: the loss and every
    leaf's gradient to the order of the sums, the tree's paths, shapes and
    dtypes the same, the gauge the step's rows."""
    if blocks == "four_blocks":
        monkeypatch.setattr(lm_head, "LOGITS_BLOCK_BYTES", 4 * V * 16)
    model = _models()[name]
    seq = 32
    params = init_lm_params(model, seq, seed=2)
    rng = np.random.default_rng(4)
    tokens, targets = (
        jnp.asarray(rng.integers(0, V, (2, seq)), jnp.int32) for _ in "ab")
    if name == "moe":
        fn = make_moe_lm_loss_fn(model)
        state = init_moe_state(model)
        new = lambda p: fn(p, state, (tokens, targets))[0]  # noqa: E731
        old = lambda p: lm_cross_entropy(  # noqa: E731
            model.apply({"params": p}, tokens)[0], targets)
    else:
        fn = make_lm_loss_fn(model)
        new = lambda p: fn(p, (tokens, targets))  # noqa: E731
        old = lambda p: lm_cross_entropy(  # noqa: E731
            model.apply({"params": p}, tokens), targets)
    gauge = telemetry.metrics.gauge(names.GAUGE_LM_HEAD_BLOCKED_ROWS, "")
    gauge.set(-1)
    loss, grads = jax.jit(jax.value_and_grad(new))(params)
    assert gauge.value() == 2 * seq
    assert telemetry.metrics.gauge(names.GAUGE_LM_HEAD_BLOCKS, "").value() == (
        4 if blocks == "four_blocks" else 1)
    want_loss, want = jax.jit(jax.value_and_grad(old))(params)
    close(loss, want_loss, "loss")
    if blocks == "four_blocks":  # an evaluation: no derivative asked
        close(jax.jit(new)(params), want_loss, "the loss alone")
    flat, flat_want, flat_params = (
        dict(jax.tree_util.tree_flatten_with_path(t)[0])
        for t in (grads, want, params))
    assert flat.keys() == flat_want.keys() == flat_params.keys()
    for path, leaf in flat.items():
        assert (leaf.shape, leaf.dtype) == (
            flat_params[path].shape, flat_params[path].dtype), path
        close(leaf, flat_want[path], str(path), 2e-5)
    head = grads["Dense_0" if name == "gpt2" else "head"]
    assert set(head) == ({"kernel", "bias"} if name == "gpt2" else {"kernel"})
    assert float(jnp.abs(head["kernel"]).max()) > 0
    # the tree that ``model.init`` makes through the loss is the same tree
    through_loss = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, targets))["params"]
    assert jax.tree_util.tree_map(
        lambda a: (a.shape, a.dtype), through_loss) == jax.tree_util.tree_map(
            lambda a: (a.shape, a.dtype), params)


def test_the_loss_without_a_derivative_is_one_product():
    """An evaluation loss (``engine.test``) is the walk that makes the loss
    alone: one product in the traced function, a block's logits."""
    x, kernel, _, targets = problem(64, False, jnp.float32)
    text = str(jax.make_jaxpr(lambda x, k: blocked_head_loss(
        x, k, None, 1.0, targets, 16))(x, kernel))
    assert text.count("dot_general") == 1 and text.count("scan[") == 1
    assert f"64,{V}]" not in text and f"16,{V}]" in text


# -- the benchmark's reader of the gauge --------------------------------------
def _reader():
    from benchmark import configs

    return configs.load_module(
        configs.HERE.parent / "layer_metrics" / "lm_head_blocked_share.py")


@pytest.mark.parametrize("config,chips", [
    ("gpt2-medium", 1), ("gpt2-medium", 4), ("smallthinker-21b-a3b", 1),
    ("keye-vl-2-30b-a3b", 1), ("laguna-s-2-1", 1), ("falcon-h1-34b", 1),
    ("brumby-14b", 1), ("qwen3-next-80b-a3b", 1)])
def test_the_share_is_the_gauge_over_a_chips_tokens(config, chips):
    """``lm_head_blocked_share`` at each language-model cell's own sizes
    (a chip of four traces its own share of the batch: the same rows a
    chip), and the entry the benchmark lists it under, beside the layer's
    other metric."""
    from benchmark import configs

    cfg = configs.load(config)
    spec = json.loads((configs.HERE.parents[1] / "BENCHMARK.json").read_text())
    entry = next(m for m in spec["per_layer"]
                 if m["name"] == "lm_head_blocked_share")
    assert entry == {
        "name": "lm_head_blocked_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "vocabulary head",
        "moves": "samples_per_s_per_chip", "workloads": entry["workloads"]}
    assert entry["workloads"] == next(
        m for m in spec["per_layer"] if m["name"] == "lm_head_ms_per_step"
    )["workloads"]
    assert f"{config}.stream.x{chips}" in entry["workloads"]
    # exactly the cells of the language models, in the benchmark's order
    assert entry["workloads"] == [
        w["name"] for w in spec["workloads"]
        if w["config"] != "resnet50-224"]
    rows = cfg["per_chip_batch"] * cfg["sequence_length"]
    telemetry.metrics.gauge(names.GAUGE_LM_HEAD_BLOCKED_ROWS, "").set(rows)
    assert _reader().read({"cfg": cfg}) == 100.0


def test_a_program_without_the_gauge_reads_none(monkeypatch):
    """The parent of PR 43, or a model with no vocabulary head: the line
    leaves the metric out."""
    from benchmark import configs

    real = telemetry.metrics.snapshot
    monkeypatch.setattr(telemetry.metrics, "snapshot", lambda *a, **kw: {
        k: v for k, v in real(*a, **kw).items()
        if k != names.GAUGE_LM_HEAD_BLOCKED_ROWS})
    for config in ("gpt2-medium", "falcon-h1-34b", "resnet50-224"):
        assert _reader().read({"cfg": configs.load(config)}) is None
    monkeypatch.undo()
    telemetry.metrics.gauge(names.GAUGE_LM_HEAD_BLOCKED_ROWS, "").set(8)
    assert _reader().read({"cfg": {}}) is None  # no tokens to divide by
    assert _reader().read(
        {"cfg": {"per_chip_batch": 2, "sequence_length": 8}}) == 50.0
