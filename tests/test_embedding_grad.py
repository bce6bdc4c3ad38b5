"""The token lookup's own derivative rule (``models/embedding.py``): the
gradient rows of equal ids are summed in float32 before anything touches
the table. Against ``zeros.at[ids].add(g)`` worked in float64."""

import hashlib

import flax.linen as fnn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchmpi_tpu import telemetry
from torchmpi_tpu.models import LongContextTransformer, embedding
from torchmpi_tpu.models.embedding import (
    BLOCK,
    TokenEmbed,
    embedding_lookup,
    sorted_embedding_grad,
)
from torchmpi_tpu.models.lm import init_lm_params, make_lm_loss_fn
from torchmpi_tpu.telemetry import names

V = 97  # the rehearsals' vocabulary


def zipf(rng, shape, vocab):
    """The cells' generator: id 0 is the most frequent token."""
    cdf = np.cumsum(1.0 / np.arange(1, vocab + 1))
    return np.searchsorted(
        cdf / cdf[-1], rng.random(shape), side="right"
    ).clip(max=vocab - 1).astype(np.int32)


def _ids(case, rng):
    """name -> (ids, vocab)."""
    return {
        # the rehearsals' steps: T no multiple of the block
        "rehearsal_2x52": lambda: (zipf(rng, (2, 52), V), V),
        "rehearsal_2x56": lambda: (zipf(rng, (2, 56), V), V),
        "rehearsal_2x32": lambda: (zipf(rng, (2, 32), V), V),
        # id 0 comes about 400 times of 2,048: a run longer than a block,
        # and the runs behind it cross the blocks' edges where they fall
        "zipf_long_runs": lambda: (zipf(rng, (2048,), V), V),
        "zipf_ragged": lambda: (zipf(rng, (3, 333), 700), 700),
        "all_equal": lambda: (np.full((2 * BLOCK + 88,), 5, np.int32), V),
        "all_distinct": lambda: (
            rng.permutation(700)[:600].astype(np.int32), 700),
        "first_and_last_id": lambda: (
            np.where(rng.random(600) < 0.5, 0, V - 1).astype(np.int32), V),
        "one_row": lambda: (np.array([V - 1], np.int32), V),
        # as ``jnp.take`` reads them: -1 is the last row, and both spellings
        # of a row sum into it
        "negative_ids": lambda: (
            rng.integers(-V, V, size=(2, 150)).astype(np.int32), V),
    }[case]()


CASES = ["rehearsal_2x52", "rehearsal_2x56", "rehearsal_2x32",
         "zipf_long_runs", "zipf_ragged", "all_equal", "all_distinct",
         "first_and_last_id", "one_row", "negative_ids"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_the_lookups_gradient_is_the_sum_by_id_in_float32(case, dtype):
    """``jax.grad`` through the lookup, under ``jax.jit``: each table row
    the sum of its tokens' cotangent rows. The cotangent is made in the
    gather's dtype, the sum in float32: never further from the sum in
    float64 than a float32 scatter-add is, and for bfloat16 rows exact to
    float32's rounding where jax's transpose adds in bfloat16."""
    rng = np.random.default_rng(CASES.index(case))
    ids, vocab = _ids(case, rng)
    D = 24
    table = jnp.asarray(rng.standard_normal((vocab, D)), jnp.float32)
    weight = jnp.asarray(rng.standard_normal(ids.shape + (D,)), dtype)

    def loss(table, take):
        return jnp.sum((take(table) * weight).astype(jnp.float32))

    tokens = jnp.asarray(ids)
    grad = jax.jit(jax.grad(lambda t: loss(
        t, lambda t: embedding_lookup(t, tokens, dtype))))(table)
    assert grad.shape == table.shape and grad.dtype == table.dtype
    ids = np.where(ids < 0, ids + vocab, ids)
    exact = np.zeros((vocab, D))
    np.add.at(exact, ids.reshape(-1),
              np.asarray(weight.astype(jnp.float32), np.float64).reshape(
                  -1, D))
    longest = np.bincount(ids.reshape(-1)).max()
    # float32's rounding of a sum of ``longest`` terms of size about 1
    bound = 4 * np.finfo(np.float32).eps * longest * np.abs(
        np.asarray(weight, np.float32)).max()
    assert np.abs(np.asarray(grad, np.float64) - exact).max() <= bound
    # ids that did not come have a gradient of exactly zero
    absent = np.setdiff1d(np.arange(vocab), ids)
    assert not np.asarray(grad)[absent].any()


@pytest.mark.parametrize("block", [8, 32, BLOCK, 2 * BLOCK])
def test_the_sum_is_the_same_at_every_block(block):
    """Runs that cross many edges (blocks of 8), and one block that holds
    the whole step."""
    rng = np.random.default_rng(block)
    ids = zipf(rng, (500,), V)
    g = jnp.asarray(rng.standard_normal((500, 16)), jnp.float32)
    exact = np.zeros((V, 16))
    np.add.at(exact, ids, np.asarray(g, np.float64))
    got = jax.jit(lambda g, i: sorted_embedding_grad(g, i, V, block))(
        g, jnp.asarray(ids))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got, np.float64), exact,
                               rtol=0, atol=2e-5)


@pytest.mark.parametrize("dtype", [None, jnp.float32, jnp.bfloat16],
                         ids=["none", "float32", "bfloat16"])
def test_the_forward_is_flaxs_embed_bit_for_bit(dtype):
    """The same parameter under the same name from the same key, and the
    same rows out of it, through the lookup's own rule (rows of 64 take
    it)."""
    assert embedding.takes_sorted_sum(64, 2)
    tokens = jnp.asarray(zipf(np.random.default_rng(0), (2, 52), V))
    ours = TokenEmbed(V, 64, dtype=dtype, name="embed")
    flaxs = fnn.Embed(V, 64, dtype=dtype, name="embed")
    key = jax.random.PRNGKey(4)
    params = ours.init(key, tokens)
    theirs = flaxs.init(key, tokens)
    assert jax.tree_util.tree_structure(params) == (
        jax.tree_util.tree_structure(theirs))
    np.testing.assert_array_equal(
        params["params"]["embedding"], theirs["params"]["embedding"])
    out, ref = (jax.jit(m.apply)(params, tokens) for m in (ours, flaxs))
    assert out.dtype == ref.dtype and out.shape == (2, 52, 64)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(ref, np.float32))
    with pytest.raises(ValueError, match="integers"):
        ours.apply(params, tokens.astype(jnp.float32))


@pytest.mark.parametrize("sorted_sum", [True, False],
                         ids=["sorted", "scatter_add"])
def test_the_gauge_reads_the_rows_whose_gradient_is_sorted(
        sorted_sum, monkeypatch):
    """Each answer of the rule, forced: the gauge reads the step's rows or
    0, and the traced backward holds the sort or jax's scatter-add."""
    monkeypatch.setattr(
        embedding, "takes_sorted_sum", lambda *_: sorted_sum)
    gauge = telemetry.metrics.gauge(names.GAUGE_EMBED_GRAD_SORTED_ROWS, "")
    tokens = jnp.zeros((2, 52), jnp.int32)
    module = TokenEmbed(V, 64)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), tokens)
    gauge.set(-1)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: jnp.sum(module.apply(p, tokens))))(
            jax.tree_util.tree_map(jnp.zeros_like, params)))
    assert gauge.value() == (104 if sorted_sum else 0)
    snapshot = telemetry.metrics.snapshot()
    assert snapshot[names.GAUGE_EMBED_GRAD_SORTED_ROWS]["series"][""] == (
        gauge.value())
    assert ("sort[" in text) == sorted_sum
    assert ("scatter-add[" in text) == (not sorted_sum)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "recomputed"])
def test_a_models_gradients_through_the_lookup(remat, monkeypatch):
    """``jax.grad`` of a whole model's loss, through a ``recomputed`` block
    stack too: every leaf's gradient with the sorted sum forced is what
    the same model has with jax's transpose of the gather forced (the
    rule's two answers), the token table's to float32's rounding of
    another order of sums."""
    model = LongContextTransformer(
        vocab_size=V, num_layers=2, num_heads=2, head_dim=16, d_model=32,
        max_len=64, remat=remat)
    params = init_lm_params(model, 56, seed=1)
    rng = np.random.default_rng(3)
    batch = tuple(jnp.asarray(zipf(rng, (2, 56), V)) for _ in range(2))
    loss_fn = make_lm_loss_fn(model)

    def grads():
        return jax.jit(jax.value_and_grad(loss_fn))(params, batch)

    gauge = telemetry.metrics.gauge(names.GAUGE_EMBED_GRAD_SORTED_ROWS, "")
    monkeypatch.setattr(embedding, "takes_sorted_sum", lambda *_: True)
    loss, new = grads()
    assert gauge.value() == 112  # the tokens' rows, not the positions'
    monkeypatch.setattr(embedding, "takes_sorted_sum", lambda *_: False)
    ref_loss, ref = grads()
    assert gauge.value() == 0
    assert float(loss) == float(ref_loss)  # the forward's bits
    flat, flat_ref = (dict(jax.tree_util.tree_flatten_with_path(t)[0])
                      for t in (new, ref))
    assert flat.keys() == flat_ref.keys()
    for path, leaf in flat.items():
        np.testing.assert_allclose(
            leaf, flat_ref[path], rtol=0, atol=1e-6 * float(
                jnp.abs(flat_ref[path]).max()), err_msg=str(path))
    assert float(jnp.abs(new["Embed_0"]["embedding"]).max()) > 0


# Each language model's parameter tree at its rehearsal's sizes, as the
# parent of PR 42 (the lookup still ``fnn.Embed``) made it: the number of
# leaves, the first 16 of the sha256 of its lines ``path shape dtype``, and
# the embeddings' lines. No benchmark file and no checkpoint notices that
# the lookup is another class.
TREES = {
    "gpt2-medium": (30, "571965c87e5c4cc8", [
        "['Embed_0']['embedding'] (97, 64) float32",
        "['Embed_1']['embedding'] (32, 64) float32"]),
    "smallthinker-21b-a3b": (43, "4ec10429c4555d23", [
        "['embed']['embedding'] (97, 64) float32"]),
    "keye-vl-2-30b-a3b": (71, "f3a6fc72495d2548", [
        "['embed']['embedding'] (97, 64) float32"]),
    "laguna-s-2-1": (69, "e928e0d9bbeae982", [
        "['embed']['embedding'] (97, 64) float32"]),
    "falcon-h1-34b": (71, "79145d2188759e4e", [
        "['embed']['embedding'] (97, 64) float32"]),
    "brumby-14b": (55, "0456dbafa38e5338", [
        "['embed']['embedding'] (97, 64) float32"]),
}


@pytest.mark.parametrize("config", sorted(TREES))
def test_the_models_parameter_trees_are_the_parents(config):
    from benchmark import configs, weights  # conftest.py: the root is on the path

    built = configs.build(config, configs.load(config, rehearse=True))
    params, _ = jax.eval_shape(built.state_at, weights.seed_key(3))
    lines = [
        f"{jax.tree_util.keystr(path)} {tuple(leaf.shape)} {leaf.dtype}"
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]]
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]
    assert (len(lines), digest, [
        line for line in lines if "mbed" in line]) == TREES[config]


# what each language-model cell's lookup takes, by the width and dtype of
# the rows it gathers (the probe on the chip, PERF.md section 6, PR 42)
SORTED = {"gpt2-medium": False, "smallthinker-21b-a3b": True,
          "keye-vl-2-30b-a3b": False, "laguna-s-2-1": False,
          "falcon-h1-34b": True, "brumby-14b": True,
          "qwen3-next-80b-a3b": False}  # keye's width: bfloat16 rows of 2,048


@pytest.mark.parametrize("features,itemsize,sorted_sum", [
    (5120, 4, True), (2560, 2, True), (2560, 4, True), (2432, 2, True),
    (3584, 2, True), (5120, 2, True), (8192, 2, True), (64, 2, True),
    (1024, 4, True),                 # measured faster as it is; no cell's
    (1024, 2, False), (2048, 2, False), (3072, 2, False), (4096, 2, False)])
def test_the_rule_keeps_the_scatter_add_at_its_measured_fast_widths(
        features, itemsize, sorted_sum):
    assert embedding.takes_sorted_sum(features, itemsize) is sorted_sum


# -- the benchmark's reader of the gauge --------------------------------------
def _reader():
    from benchmark import configs

    return configs.load_module(
        configs.HERE.parent / "layer_metrics" / "embed_grad_sorted_share.py")


@pytest.mark.parametrize("config,chips", [
    ("gpt2-medium", 1), ("gpt2-medium", 4), ("smallthinker-21b-a3b", 1),
    ("keye-vl-2-30b-a3b", 1), ("laguna-s-2-1", 1), ("falcon-h1-34b", 1),
    ("brumby-14b", 1), ("qwen3-next-80b-a3b", 1)])
def test_the_share_is_the_gauge_over_a_chips_tokens(config, chips):
    """``embed_grad_sorted_share`` at each language-model cell's own sizes:
    the gauge as it reads after the step was traced (what the rule answers
    for the cell's rows; a chip of four traces its own share of the batch:
    the same rows a chip) over ``per_chip_batch x sequence_length``, and
    the entry the benchmark lists it under."""
    import json

    from benchmark import configs

    cfg = configs.load(config)
    spec = json.loads((configs.HERE.parents[1] / "BENCHMARK.json").read_text())
    entry = next(m for m in spec["per_layer"]
                 if m["name"] == "embed_grad_sorted_share")
    assert entry == {
        "name": "embed_grad_sorted_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "embedding",
        "moves": "samples_per_s_per_chip", "workloads": entry["workloads"]}
    assert f"{config}.stream.x{chips}" in entry["workloads"]
    # exactly the cells of the language models, in the benchmark's order
    assert entry["workloads"] == [
        w["name"] for w in spec["workloads"]
        if w["config"] != "resnet50-224"]
    rows = cfg["per_chip_batch"] * cfg["sequence_length"]
    width = cfg.get("hidden_size", cfg["model"].get("n_embd"))
    # falcon and brumby gather float32 and cast after; the others gather
    # in the compute dtype
    itemsize = 4 if config in ("falcon-h1-34b", "brumby-14b") else (
        jnp.dtype(cfg["compute_dtype"]).itemsize)
    taken = embedding.takes_sorted_sum(width, itemsize)
    assert taken is SORTED[config]
    gauge = telemetry.metrics.gauge(names.GAUGE_EMBED_GRAD_SORTED_ROWS, "")
    gauge.set(rows if taken else 0)
    assert _reader().read({"cfg": cfg}) == (100.0 if taken else 0.0)


def test_a_program_without_the_gauge_reads_none(monkeypatch):
    """The parent of PR 42, or a model with no token lookup: the line
    leaves the metric out."""
    from benchmark import configs

    real = telemetry.metrics.snapshot
    monkeypatch.setattr(telemetry.metrics, "snapshot", lambda *a, **kw: {
        k: v for k, v in real(*a, **kw).items()
        if k != names.GAUGE_EMBED_GRAD_SORTED_ROWS})
    for config in ("gpt2-medium", "falcon-h1-34b", "resnet50-224"):
        assert _reader().read({"cfg": configs.load(config)}) is None
    monkeypatch.undo()
    telemetry.metrics.gauge(names.GAUGE_EMBED_GRAD_SORTED_ROWS, "").set(8)
    assert _reader().read({"cfg": {}}) is None  # no tokens to divide by
    assert _reader().read(
        {"cfg": {"per_chip_batch": 2, "sequence_length": 8}}) == 50.0
