"""What a recomputed block keeps of its dense products (``models/lm.py``):
the rule as a pure function of bytes, what it is handed when a model is
traced, and that keeping changes no gradient: for a small model of each
model file whose products bear names (the benchmark's configurations at
their rehearsals' sizes), the gradients with every kind kept equal those
with none kept and those with ``remat=False`` to the dtype's rounding, on
the CPU."""

import sys
from pathlib import Path

import flax.linen as fnn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchmpi_tpu import telemetry
from torchmpi_tpu.models import lm
from torchmpi_tpu.telemetry import names

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

GIB = 2**30
V5E = int(15.75 * GIB)


# -- the rule, a pure function ---------------------------------------------
def gpt2_kinds(batch):
    """GPT-2 medium's three kinds at 1,024 positions, by their shapes: 24
    layers of ``[batch, 1024, 3072 | 1024 | 4096]`` bfloat16, each product
    over 1,024 terms."""
    rows = 24 * batch * 1024
    return {kind: (2 * rows * columns, 2 * rows * columns * 1024)
            for kind, columns in ((lm.QKV, 3072), (lm.RESIDUAL, 1024),
                                  (lm.MLP_GATE, 4096))}


KINDS = {"wide": (4 * GIB, 4 * GIB * 512), "deep": (GIB, GIB * 4096),
         "small": (GIB // 64, GIB // 64 * 1024)}


def test_a_device_that_reports_no_limit_keeps_every_kind():
    assert lm.kinds_kept(None, 10**12, 10**12, KINDS) == (
        "deep", "small", "wide")
    assert lm.kinds_kept(None, 0, 0, {}) == ()


def test_kinds_enter_in_the_order_of_operations_a_byte():
    """... the most first, whatever their size, equal ones by name; and
    the taking stops at the first that does not fit: a later, smaller kind
    is not taken in its place."""
    at = lambda room: lm.kinds_kept(  # noqa: E731
        int((room + 8 * GIB) / lm.FILL), GIB, 4 * GIB, KINDS)
    assert at(0.5 * GIB) == ()  # ``small`` would fit: it is not its turn
    assert at(1.0 * GIB + 1) == ("deep",)
    assert at(1.1 * GIB) == ("deep", "small")
    assert at(5.1 * GIB) == ("deep", "small", "wide")
    same = gpt2_kinds(8)
    assert lm.kinds_kept(None, 0, 0, same) == tuple(sorted(same))


@pytest.mark.parametrize("parameters,beside", [(0, 0), (GIB, 2 * GIB),
                                                (2 * GIB, 5 * GIB)])
def test_more_memory_never_keeps_less(parameters, beside):
    kept = [lm.kinds_kept(limit, parameters, beside, KINDS)
            for limit in range(0, 24 * GIB, GIB // 4)]
    assert all(a == b[:len(a)] for a, b in zip(kept, kept[1:]))
    assert kept[0] == () and kept[-1] == ("deep", "small", "wide")
    # ... nor do more parameters or more beside them ever keep more
    for more in (GIB, 3 * GIB):
        tighter = lm.kinds_kept(16 * GIB, parameters + more, beside, KINDS)
        assert len(tighter) <= len(
            lm.kinds_kept(16 * GIB, parameters, beside, KINDS))


def test_gpt2_at_batch_16_keeps_less_than_at_batch_8():
    """A user's GPT-2 medium at batch 16 (all three kinds would be 6 GiB
    there; the estimate's parts here are the probe's, ``python3
    scripts/recompute_probe.py gpt2-medium --batch 16``) still fits a
    v5e: the rule keeps what the 14.0 GiB leave room for, one kind."""
    parameters = int(1.514 * GIB)
    eight = lm.kinds_kept(V5E, parameters, int(2.033 * GIB), gpt2_kinds(8))
    sixteen = lm.kinds_kept(
        V5E, parameters, int(3.263 * GIB), gpt2_kinds(16))
    assert eight == (lm.MLP_GATE, lm.QKV, lm.RESIDUAL)
    # 11.44 GiB compiled with that one kind kept, the estimate 12.32
    assert sixteen == (lm.MLP_GATE,)
    kept = sum(gpt2_kinds(16)[k][0] for k in sixteen)
    assert 4 * parameters + 3.263 * GIB + kept <= 14.0 * GIB


# -- what the rule is handed, and what keeping changes ----------------------
# by model file, a configuration of the benchmark that is built on it, at
# its rehearsal's sizes: (the configuration, the kinds its blocks name)
MODELS = {
    "transformer": ("gpt2-medium", {lm.QKV, lm.RESIDUAL, lm.MLP_GATE}),
    "retentive": ("brumby-14b", {lm.MLP_GATE, lm.MLP_UP}),
    "decoder": ("smallthinker-21b-a3b", {lm.ROUTER, lm.QKV, lm.RESIDUAL}),
    "decoder-selecting": ("keye-vl-2-30b-a3b", {
        lm.ROUTER, lm.QKV, lm.RESIDUAL, lm.INDEX}),
    "decoder-by-share": ("laguna-s-2-1", {
        lm.ROUTER, lm.QKV, lm.RESIDUAL, lm.HEAD_GATE, lm.MLP_GATE,
        lm.MLP_UP}),
    "deltanet": ("qwen3-next-80b-a3b", {
        lm.MIXER_IN, lm.MIXER_GATES, lm.QKV, lm.RESIDUAL, lm.ROUTER,
        lm.MLP_GATE, lm.MLP_UP}),
}
CASES = [(family, "float32") for family in sorted(MODELS)] + [
    ("transformer", "bfloat16"), ("retentive", "bfloat16")]


def built_at(family, dtype="float32", remat=True):
    """(the configuration's loss as ``loss(params)``, its seeded parameters)
    at the rehearsal's sizes."""
    from benchmark import configs

    config, _ = MODELS[family]
    cfg = configs.load(config, rehearse=True)
    cfg.update(compute_dtype=dtype, remat=remat)
    built = configs.build(config, cfg)
    params, state = built.state_at(jax.random.PRNGKey(3))
    batch = tuple(jnp.asarray(a) for a in built.make_data(
        7, cfg["per_chip_batch"]))
    if state is None:
        return lambda p: built.loss_fn(p, batch), params
    return lambda p: built.loss_fn(p, state, batch)[0], params


def gauge(name):
    return telemetry.metrics.snapshot()[name]["series"][""]


@pytest.mark.parametrize("family,dtype", CASES)
def test_keeping_the_products_changes_no_gradient(family, dtype, monkeypatch):
    named = MODELS[family][1]
    asked = {}
    rule = lm.kinds_kept

    def recorded(limit, parameters, beside, kinds):
        asked.update(limit=limit, parameters=parameters, kinds=dict(kinds))
        return rule(limit, parameters, beside, kinds)

    monkeypatch.setattr(lm, "kinds_kept", recorded)
    loss, params = built_at(family, dtype)
    every = jax.jit(jax.value_and_grad(loss))(params)
    # a CPU reports no memory: every kind the file names is kept, and the
    # parameters counted are the tree the model was applied to
    assert asked["limit"] is None and set(asked["kinds"]) == named
    assert asked["parameters"] == sum(
        a.size * 4 for a in jax.tree_util.tree_leaves(params))
    total = sum(b for b, _ in asked["kinds"].values())
    assert gauge(names.GAUGE_RECOMPUTE_NAMED_BYTES) == total > 0
    assert gauge(names.GAUGE_RECOMPUTE_KEPT_BYTES) == total
    # a device with no room at all keeps none
    monkeypatch.setattr(lm, "device_bytes", lambda: 1)
    none = jax.jit(jax.value_and_grad(built_at(family, dtype)[0]))(params)
    assert gauge(names.GAUGE_RECOMPUTE_NAMED_BYTES) == total
    assert gauge(names.GAUGE_RECOMPUTE_KEPT_BYTES) == 0
    plain = jax.jit(jax.value_and_grad(
        built_at(family, dtype, remat=False)[0]))(params)
    # a block made again is not bit for bit the block (XLA fuses it
    # differently): to the dtype's rounding, each leaf by its largest entry
    tol = 2e-5 if dtype == "float32" else 0.05
    for other in (none, plain):
        np.testing.assert_allclose(every[0], other[0], rtol=tol)
        for a, b in zip(jax.tree_util.tree_leaves(every[1]),
                        jax.tree_util.tree_leaves(other[1])):
            scale = max(float(jnp.abs(b).max()), 1e-6)
            np.testing.assert_allclose(a / scale, b / scale, atol=tol)


@pytest.mark.parametrize("family", sorted(MODELS))
def test_the_policy_keeps_the_kinds_it_is_handed_and_no_other(family):
    """The residuals of the recomputed blocks' derivative, by their bytes:
    with a kind kept they grow by that kind's bytes over the layers as
    ``product`` counted them, whole lanes and all (less a bias that is then
    no longer read to make the product again, and the lanes' padding, which
    the shapes here do not show)."""
    named = MODELS[family][1]
    loss, params = built_at(family)

    def held(keep):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(lm, "kinds_kept", lambda *_: keep)
            patch.setattr(lm, "LANES", 1)
            back = jax.eval_shape(lambda p: jax.vjp(loss, p)[1], params)
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(back))

    asked = {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lm, "kinds_kept",
                      lambda *a: asked.update(kinds=a[3]) or ())
        patch.setattr(lm, "LANES", 1)
        jax.eval_shape(loss, params)
    assert set(asked["kinds"]) == named
    nothing = held(())
    for kind in sorted(named):
        counted = asked["kinds"][kind][0]
        # the indexer's queries are read rotated, and backward has no use
        # for them as the product made them: the count errs above
        least = 0.4 if kind == lm.INDEX else 0.9
        assert least * counted <= held((kind,)) - nothing <= counted, kind
    counted = sum(b for b, _ in asked["kinds"].values())
    assert 0.85 * counted <= held(tuple(named)) - nothing <= counted


def test_a_file_that_names_no_product_keeps_none(monkeypatch):
    """``models/hybrid.py`` asks the rule as the others do and names
    nothing yet: both gauges read 0, and ``recompute_kept_share`` 0."""
    from benchmark import configs

    asked = {}
    monkeypatch.setattr(
        lm, "kinds_kept", lambda *a: asked.update(kinds=a[3]) or ())
    cfg = configs.load("falcon-h1-34b", rehearse=True)
    built = configs.build("falcon-h1-34b", cfg)
    params, _ = built.state_at(jax.random.PRNGKey(3))
    batch = tuple(jnp.asarray(a) for a in built.make_data(7, 1))
    jax.eval_shape(lambda p: built.loss_fn(p, batch), params)
    assert asked["kinds"] == {}
    assert gauge(names.GAUGE_RECOMPUTE_NAMED_BYTES) == 0
    assert gauge(names.GAUGE_RECOMPUTE_KEPT_BYTES) == 0
    assert reader().read({}) == 0.0


def reader():
    from benchmark import configs

    return configs.load_module(
        ROOT / "benchmark" / "layer_metrics" / "recompute_kept_share.py")


def test_the_benchmarks_reader_gives_the_kept_share(monkeypatch):
    """``benchmark/layer_metrics/recompute_kept_share.py``: the second
    gauge over the first in per cent, and None where the program has
    neither (the parent of the PR that brought them)."""
    from benchmark import scopes

    recompute_kept_share = reader()
    values = {}
    monkeypatch.setattr(scopes, "counter", values.get)
    assert recompute_kept_share.read({}) is None
    values.update({names.GAUGE_RECOMPUTE_NAMED_BYTES: 400,
                   names.GAUGE_RECOMPUTE_KEPT_BYTES: 100})
    assert recompute_kept_share.read({}) == 25.0
    values[names.GAUGE_RECOMPUTE_KEPT_BYTES] = 400
    assert recompute_kept_share.read({}) == 100.0


def test_a_model_being_initialized_asks_nothing(monkeypatch):
    monkeypatch.setattr(
        lm, "kinds_kept", lambda *_: pytest.fail("asked while initializing"))
    from torchmpi_tpu.models import LongContextTransformer

    model = LongContextTransformer(
        vocab_size=97, num_layers=2, num_heads=2, head_dim=16, d_model=32,
        max_len=16, remat=True)
    assert model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))


def test_the_forward_results_count_what_is_stored_not_what_fuses():
    """A block's stored bytes are what its products read and write: an
    elementwise chain between two products counts once, at the product it
    feeds."""
    class Two(fnn.Module):
        @fnn.compact
        def __call__(self, x):
            h = fnn.Dense(64, use_bias=False)(x)
            h = jnp.tanh(h * 2.0 + 1.0) * jax.nn.silu(h)
            return fnn.Dense(32, use_bias=False)(h)

    x = jax.ShapeDtypeStruct((8, 32), jnp.float32)
    block = Two()
    params = jax.eval_shape(block.init, jax.random.PRNGKey(0), x)["params"]
    stored, noted = lm._forward_results(block, params, x)
    # the first product's result, the chain's end, the second's result
    assert stored == 4 * (8 * 64 + 8 * 64 + 8 * 32) and noted == []
