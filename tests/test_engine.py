"""Engine tests: distributed-vs-sequential loss parity.

Mirrors the reference's e2e strategy: ``mnist_sequential.lua`` is the
baseline, distributed runs must match its loss (mnist_allreduce.lua:87-113).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import torchmpi_tpu as mpi
from torchmpi_tpu import nn as mpinn
from torchmpi_tpu.engine import AllReduceSGDEngine
from torchmpi_tpu.models import (
    LogisticRegression,
    MLP6,
    accuracy,
    init_params,
    make_loss_fn,
)
from torchmpi_tpu.utils import DistributedIterator, synthetic_mnist


@pytest.fixture(autouse=True)
def _start():
    mpi.start()
    yield


def _sequential_baseline(model, params, xtr, ytr, batch, epochs, lr, seed):
    """Single-process SGD over the SAME per-rank batch partitioning: with
    averaged gradients the distributed run must follow the identical
    trajectory (the mnist_sequential.lua comparison)."""
    loss_fn = make_loss_fn(model)
    opt = optax.sgd(lr)
    opt_state = opt.init(params)
    it = DistributedIterator(
        xtr, ytr, batch, num_ranks=mpi.size(), seed=seed, prefetch=1
    )

    @jax.jit
    def step(params, opt_state, x, y):
        # x: [p, B, ...] -> flatten to the full global batch
        x = x.reshape((-1,) + x.shape[2:])
        y = y.reshape((-1,))
        loss, grads = jax.value_and_grad(loss_fn)(params, (x, y))
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(epochs):
        for x, y in it:
            params, opt_state, loss = step(
                params, opt_state, np.asarray(x), np.asarray(y)
            )
        losses.append(float(loss))
    return params, losses


@pytest.mark.slow
def test_engine_matches_sequential():
    """Distributed AllReduceSGD must track the sequential baseline loss
    step-for-step (averaged grads over rank-shards == full-batch grad)."""
    p = mpi.size()
    (xtr, ytr), _ = synthetic_mnist(num_train=1024, num_test=1)
    model = LogisticRegression()
    params = init_params(model, (1, 28, 28))
    batch, epochs, lr, seed = 16 * p, 2, 0.2, 7

    _, seq_losses = _sequential_baseline(
        model, params, xtr, ytr, batch, epochs, lr, seed
    )

    engine = AllReduceSGDEngine(
        make_loss_fn(model),
        params,
        optimizer=optax.sgd(lr),
        average_gradients=True,
    )
    it = DistributedIterator(
        xtr, ytr, batch, p, seed=seed, sharding=engine.batch_sharding
    )
    state = engine.train(lambda: iter(it), max_epochs=epochs)
    # per-rank mean-loss average == global batch loss for equal shards
    # accumulation order differs per mesh size: generous-but-tight bound
    np.testing.assert_allclose(state["losses"], seq_losses, rtol=2e-3)


def test_engine_replica_consistency():
    p = mpi.size()
    (xtr, ytr), _ = synthetic_mnist(num_train=512, num_test=1)
    model = LogisticRegression()
    params = init_params(model, (1, 28, 28))
    engine = AllReduceSGDEngine(make_loss_fn(model), params)
    it = DistributedIterator(
        xtr, ytr, 8 * p, p, sharding=engine.batch_sharding
    )
    engine.train(lambda: iter(it), max_epochs=1)
    final = jax.device_get(engine.params)
    stacked = jax.tree_util.tree_map(
        lambda w: jnp.broadcast_to(jnp.asarray(w), (p,) + np.asarray(w).shape),
        final,
    )
    mpinn.check_with_allreduce(stacked)  # 1e-7 invariant


def test_engine_hooks_fire_in_order():
    p = mpi.size()
    (xtr, ytr), _ = synthetic_mnist(num_train=256, num_test=1)
    model = LogisticRegression()
    params = init_params(model, (1, 28, 28))
    calls = []
    hooks = {
        name: (lambda n: lambda s: calls.append(n))(name)
        for name in (
            "on_start",
            "on_start_epoch",
            "on_sample",
            "on_forward",
            "on_backward",
            "on_update",
            "on_end_epoch",
            "on_end",
        )
    }
    engine = AllReduceSGDEngine(make_loss_fn(model), params, hooks=hooks)
    it = DistributedIterator(xtr, ytr, 8 * p, p, sharding=engine.batch_sharding)
    engine.train(lambda: iter(it), max_epochs=1)
    assert calls[0] == "on_start" and calls[-1] == "on_end"
    assert calls.count("on_end_epoch") == 1
    assert calls.count("on_sample") == len(it)
    i = calls.index("on_sample")
    assert calls[i : i + 4] == ["on_sample", "on_forward", "on_backward", "on_update"]


def test_engine_does_not_donate_caller_params():
    """The jitted step donates its inputs; the engine must own copies so
    the caller's params (which device_put may alias on matching shardings)
    survive training — and can seed a second engine."""
    p = mpi.size()
    model = LogisticRegression()
    params = init_params(model, (1, 28, 28))
    x = np.zeros((p, 2, 28, 28), np.float32)
    y = np.zeros((p, 2), np.int32)
    for _ in range(2):  # second engine reuses the same caller-owned params
        engine = AllReduceSGDEngine(make_loss_fn(model), params)
        engine.train(lambda: iter([(x, y)]), max_epochs=1)
    # caller's tree still readable
    for leaf in jax.tree_util.tree_leaves(params):
        np.asarray(leaf)


def test_engine_train_resident_matches_train():
    """Device-resident epoch scan must follow the same trajectory as the
    per-step train() loop on the same unshuffled data partitioning."""
    p = mpi.size()
    (xtr, ytr), _ = synthetic_mnist(num_train=256, num_test=1)
    model = LogisticRegression()
    params = init_params(model, (1, 28, 28))
    epochs, lr, per_rank = 2, 0.2, 8

    eng_a = AllReduceSGDEngine(
        make_loss_fn(model), params, optimizer=optax.sgd(lr)
    )
    it = DistributedIterator(
        xtr, ytr, per_rank * p, p, shuffle=False,
        sharding=eng_a.batch_sharding,
    )
    st_a = eng_a.train(lambda: iter(it), max_epochs=epochs)

    eng_b = AllReduceSGDEngine(
        make_loss_fn(model), params, optimizer=optax.sgd(lr)
    )
    st_b = eng_b.train_resident(
        xtr, ytr, per_rank, max_epochs=epochs, shuffle=False
    )
    # train() records the per-epoch FINAL loss; train_resident records both
    assert st_b["samples"] == st_a["samples"]
    np.testing.assert_allclose(st_b["loss"], st_a["losses"][-1], rtol=1e-4)
    a = jax.tree_util.tree_leaves(jax.device_get(eng_a.params))
    b = jax.tree_util.tree_leaves(jax.device_get(eng_b.params))
    for la, lb in zip(a, b):
        np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-6)


def test_engine_train_resident_shuffles_and_converges():
    p = mpi.size()
    (xtr, ytr), (xte, yte) = synthetic_mnist(num_train=1024, num_test=256)
    model = LogisticRegression()
    params = init_params(model, (1, 28, 28))
    engine = AllReduceSGDEngine(
        make_loss_fn(model), params, optimizer=optax.sgd(0.2)
    )
    state = engine.train_resident(xtr, ytr, 8, max_epochs=3, seed=5)
    assert state["losses"][-1] < state["losses"][0]
    assert len(state["epoch_times"]) == 3
    acc = engine.evaluate(
        lambda prm, x: model.apply({"params": prm}, x), xte, yte, accuracy
    )
    assert acc > 0.5


def test_engine_public_step():
    """engine.step(batch) is the public per-step API (no private reach-in)."""
    p = mpi.size()
    model = LogisticRegression()
    params = init_params(model, (1, 28, 28))
    engine = AllReduceSGDEngine(make_loss_fn(model), params)
    engine.broadcast_parameters_now()
    x = np.random.RandomState(0).randn(p, 4, 28, 28).astype(np.float32)
    y = np.zeros((p, 4), np.int32)
    l1 = float(engine.step((x, y)))
    l2 = float(engine.step((x, y)))
    assert l2 < l1  # same batch twice: loss must drop


@pytest.mark.slow
def test_engine_fsdp_matches_replicated():
    """ZeRO-3 mode: sharded params/opt-state must follow the replicated
    trajectory exactly (same global-batch means), with leaves actually
    sharded over the mesh."""
    p = mpi.size()
    (xtr, ytr), _ = synthetic_mnist(num_train=256, num_test=1)
    model = MLP6(features=8 * p)  # divisible dims so fsdp shards engage
    params = init_params(model, (1, 28, 28))
    epochs, lr, per_rank = 2, 0.1, 8

    states = {}
    engines = {}
    for sharding in ("replicated", "fsdp"):
        eng = AllReduceSGDEngine(
            make_loss_fn(model),
            params,
            optimizer=optax.sgd(lr),
            param_sharding=sharding,
        )
        states[sharding] = eng.train_resident(
            xtr, ytr, per_rank, max_epochs=epochs, shuffle=False
        )
        engines[sharding] = eng
    np.testing.assert_allclose(
        states["fsdp"]["losses"], states["replicated"]["losses"], rtol=1e-4
    )
    a = jax.tree_util.tree_leaves(jax.device_get(engines["replicated"].params))
    b = jax.tree_util.tree_leaves(jax.device_get(engines["fsdp"].params))
    for la, lb in zip(a, b):
        np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-6)
    # at least one parameter leaf is genuinely sharded (not replicated)
    sharded = [
        leaf
        for leaf in jax.tree_util.tree_leaves(engines["fsdp"].params)
        if any(s is not None for s in leaf.sharding.spec)
    ]
    assert sharded, "no fsdp leaf ended up sharded"
    one = sharded[0]
    assert (
        one.addressable_shards[0].data.shape != one.shape or p == 1
    ), "fsdp shard holds the full leaf"


@pytest.mark.slow
def test_engine_zero1_matches_replicated():
    """ZeRO-1: sharded optimizer state, replicated params — must follow
    the replicated trajectory exactly, with opt-state leaves actually
    sharded and params actually replicated after stepping."""
    p = mpi.size()
    (xtr, ytr), _ = synthetic_mnist(num_train=256, num_test=1)
    model = MLP6(features=8 * p)
    params = init_params(model, (1, 28, 28))

    states, engines = {}, {}
    for sharding in ("replicated", "zero1"):
        eng = AllReduceSGDEngine(
            make_loss_fn(model),
            params,
            optimizer=optax.adam(1e-2),  # adam: REAL optimizer moments
            param_sharding=sharding,
        )
        states[sharding] = eng.train_resident(
            xtr, ytr, 8, max_epochs=2, shuffle=False
        )
        engines[sharding] = eng
    np.testing.assert_allclose(
        states["zero1"]["losses"], states["replicated"]["losses"], rtol=1e-4
    )
    for la, lb in zip(
        jax.tree_util.tree_leaves(jax.device_get(engines["replicated"].params)),
        jax.tree_util.tree_leaves(jax.device_get(engines["zero1"].params)),
    ):
        np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-6)
    # params stay replicated...
    for leaf in jax.tree_util.tree_leaves(engines["zero1"].params):
        assert all(s is None for s in leaf.sharding.spec), leaf.sharding
    # ...while at least one optimizer moment is genuinely sharded
    sharded = [
        leaf
        for leaf in jax.tree_util.tree_leaves(engines["zero1"].opt_state)
        if hasattr(leaf, "sharding")
        and any(s is not None for s in leaf.sharding.spec)
    ]
    assert sharded, "no zero1 opt-state leaf ended up sharded"
    one = sharded[0]
    assert (
        one.addressable_shards[0].data.shape != one.shape or p == 1
    ), "zero1 shard holds the full leaf"


@pytest.mark.parametrize("sharding", ["replicated", "fsdp"])
def test_engine_accum_steps_matches_unaccumulated(sharding):
    """accum_steps=k must follow the k=1 trajectory exactly: equal
    microbatches make the accumulated mean gradient identical to the
    full-batch mean gradient (capability extension; no reference analog)."""
    p = mpi.size()
    (xtr, ytr), _ = synthetic_mnist(num_train=256, num_test=1)
    model = MLP6(features=8 * p)
    params = init_params(model, (1, 28, 28))

    losses = {}
    final = {}
    for k in (1, 4):
        eng = AllReduceSGDEngine(
            make_loss_fn(model),
            params,
            optimizer=optax.sgd(0.1),
            param_sharding=sharding,
            accum_steps=k,
        )
        st = eng.train_resident(xtr, ytr, 8, max_epochs=2, shuffle=False)
        losses[k] = st["losses"]
        final[k] = jax.tree_util.tree_leaves(jax.device_get(eng.params))
    np.testing.assert_allclose(losses[4], losses[1], rtol=1e-4)
    for la, lb in zip(final[1], final[4]):
        np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-6)


@pytest.mark.slow
def test_engine_accum_steps_validation():
    (xtr, ytr), _ = synthetic_mnist(num_train=64, num_test=1)
    model = MLP6()
    params = init_params(model, (1, 28, 28))
    with pytest.raises(ValueError, match="accum_steps"):
        AllReduceSGDEngine(make_loss_fn(model), params, accum_steps=0)
    eng = AllReduceSGDEngine(
        make_loss_fn(model), params, optimizer=optax.sgd(0.1), accum_steps=3
    )
    with pytest.raises(ValueError, match="not divisible"):
        # per-rank batch 8 not divisible by accum_steps 3
        eng.train_resident(xtr, ytr, 8, max_epochs=1)


def test_engine_fsdp_step_and_eval():
    p = mpi.size()
    (xtr, ytr), (xte, yte) = synthetic_mnist(num_train=512, num_test=128)
    model = LogisticRegression()
    params = init_params(model, (1, 28, 28))
    engine = AllReduceSGDEngine(
        make_loss_fn(model),
        params,
        optimizer=optax.sgd(0.2),
        param_sharding="fsdp",
    )
    x = np.random.RandomState(0).randn(p * 4, 28, 28).astype(np.float32)
    y = np.zeros((p * 4,), np.int32)
    l1 = float(engine.step((x, y)))
    l2 = float(engine.step((x, y)))
    assert l2 < l1
    st = engine.train_resident(xtr, ytr, 8, max_epochs=4, seed=1)
    assert st["losses"][-1] < st["losses"][0]
    acc = engine.evaluate(
        lambda prm, xx: model.apply({"params": prm}, xx), xte, yte, accuracy
    )
    assert acc > 0.6  # short run after 2 junk warm-up steps


@pytest.mark.slow
def test_engine_fsdp_checkpoint_roundtrip(tmp_path):
    """Save/restore must preserve the fsdp SHARDED placement (densifying
    to replicated would silently drop ZeRO-3) and resume identically."""
    from torchmpi_tpu.utils import checkpoint

    p = mpi.size()
    (xtr, ytr), _ = synthetic_mnist(num_train=256, num_test=1)
    model = MLP6(features=8 * p)
    params = init_params(model, (1, 28, 28))
    eng = AllReduceSGDEngine(
        make_loss_fn(model), params, optimizer=optax.sgd(0.1),
        param_sharding="fsdp",
    )
    eng.train_resident(xtr, ytr, 8, max_epochs=1, shuffle=False)
    checkpoint.save_engine(tmp_path / "ck", eng, step=1)

    eng2 = AllReduceSGDEngine(
        make_loss_fn(model), params, optimizer=optax.sgd(0.1),
        param_sharding="fsdp",
    )
    meta = checkpoint.restore_engine(tmp_path / "ck", eng2)
    assert meta["step"] == 1
    # placement preserved: some leaf still sharded after restore
    sharded = [
        leaf for leaf in jax.tree_util.tree_leaves(eng2.params)
        if any(s is not None for s in leaf.sharding.spec)
    ]
    assert sharded, "restore densified the fsdp sharding"
    # continued training follows the original trajectory
    st_a = eng.train_resident(xtr, ytr, 8, max_epochs=1, shuffle=False, seed=9)
    st_b = eng2.train_resident(xtr, ytr, 8, max_epochs=1, shuffle=False, seed=9)
    np.testing.assert_allclose(st_b["losses"], st_a["losses"], rtol=1e-5)


def test_engine_fsdp_rejects_summed_gradients():
    model = LogisticRegression()
    params = init_params(model, (1, 28, 28))
    with pytest.raises(ValueError, match="fsdp"):
        AllReduceSGDEngine(
            make_loss_fn(model), params, average_gradients=False,
            param_sharding="fsdp",
        )


def test_iterator_partitioning():
    """makeiterator.lua:31 semantics: global batch split evenly per rank,
    each rank sampling its own dataset shard."""
    p = mpi.size()
    x = np.arange(160, dtype=np.float32)[:, None]
    y = np.arange(160, dtype=np.int32)
    it = DistributedIterator(x, y, batch_size=2 * p, num_ranks=p, shuffle=False)
    xb, yb = next(iter(it))
    assert xb.shape == (p, 2, 1)
    shard = 160 // p
    for r in range(p):
        assert set(np.asarray(yb)[r]) <= set(range(r * shard, (r + 1) * shard))


def test_engine_accepts_flat_batches():
    """Flat [p*B, ...] batches (documented contract) including the ambiguous
    B=1 case where x.shape[0] == p must not be misread as rank-stacked."""
    p = mpi.size()
    model = LogisticRegression()
    params = init_params(model, (1, 28, 28))
    engine = AllReduceSGDEngine(make_loss_fn(model), params)
    x = np.random.RandomState(0).randn(p, 28, 28).astype(np.float32)  # B=1
    y = np.zeros((p,), np.int32)
    state = engine.train(lambda: iter([(x, y)]), max_epochs=1)
    assert len(state["losses"]) == 1


def test_engine_empty_iterator_raises():
    model = LogisticRegression()
    params = init_params(model, (1, 28, 28))
    engine = AllReduceSGDEngine(make_loss_fn(model), params)
    with pytest.raises(RuntimeError, match="no batches"):
        engine.train(lambda: iter([]), max_epochs=1)


def test_iterator_early_break_no_thread_leak():
    import threading

    p = mpi.size()
    x = np.zeros((128, 4), np.float32)
    y = np.zeros((128,), np.int32)
    before = threading.active_count()
    for _ in range(5):
        it = DistributedIterator(x, y, p, p, prefetch=1)
        next(iter(it))  # break after one batch
    import time

    time.sleep(0.5)
    assert threading.active_count() <= before + 1


def test_iterator_batch_divisibility():
    with pytest.raises(ValueError):
        DistributedIterator(
            np.zeros((64, 2)), np.zeros(64), batch_size=9, num_ranks=8
        )


def test_fn_key_pins_referents_no_id_reuse():
    """The eval-fn cache key must never alias across GC: _fn_key pins every
    captured object (_IdRef holds a strong ref), so a dead model's id can
    never be recycled into a stale jitted-executable hit."""
    import gc
    import weakref

    from torchmpi_tpu.engine.sgd import _fn_key

    class M:
        pass

    def make(m):
        return lambda x: (m, x)

    a, b = M(), M()
    ka, kb = _fn_key(make(a)), _fn_key(make(b))
    assert ka != kb  # same code object, different captures
    assert ka == _fn_key(make(a))  # re-created lambda over same model hits
    wr = weakref.ref(a)
    del a
    gc.collect()
    # the key holds the referent alive: its id cannot be reused while the
    # cache entry exists, so no fresh object can ever compare equal to ka
    assert wr() is not None
    assert _fn_key(make(M())) != ka


def test_engine_evaluate_keys_on_captured_values():
    """Two metric lambdas created on the SAME source line over different
    captured values must dispatch to different executables (the id()-reuse
    hazard class: a stale hit would return the first lambda's result)."""
    (xtr, ytr), (xte, yte) = synthetic_mnist(num_train=64, num_test=64)
    model = LogisticRegression()
    params = init_params(model, (1, 28, 28))
    engine = AllReduceSGDEngine(
        make_loss_fn(model), params, optimizer=optax.sgd(0.1)
    )
    engine.broadcast_parameters_now()

    def metric_for(shift):
        return lambda logits, y: accuracy(logits, y) + shift

    apply_fn = lambda prm, x: model.apply({"params": prm}, x)  # noqa: E731
    v0 = engine.evaluate(apply_fn, xte, yte, metric_for(0.0))
    v1 = engine.evaluate(apply_fn, xte, yte, metric_for(10.0))
    assert abs((v1 - v0) - 10.0) < 1e-5


def test_engine_evaluate_observes_single_element_mutation():
    """A ONE-element in-place write to a cached eval array must be seen
    (restaged), not served stale — the round-3 strided fingerprint could
    miss sub-stride writes; the full-buffer checksum cannot."""
    (xtr, ytr), (xte, yte) = synthetic_mnist(num_train=64, num_test=64)
    model = LogisticRegression()
    params = init_params(model, (1, 28, 28))
    engine = AllReduceSGDEngine(
        make_loss_fn(model), params, optimizer=optax.sgd(0.1)
    )
    engine.broadcast_parameters_now()

    apply_fn = lambda prm, x: model.apply({"params": prm}, x)  # noqa: E731
    mean_logit = lambda logits, y: jnp.mean(logits)  # noqa: E731
    v0 = engine.evaluate(apply_fn, xte, yte, mean_logit)
    assert engine.evaluate(apply_fn, xte, yte, mean_logit) == v0  # cached
    xte[3, 7, 7] += 1000.0  # single element: sub-stride for any sampling
    v1 = engine.evaluate(apply_fn, xte, yte, mean_logit)
    assert v1 != v0, "mutated eval array served from stale cache"

    # explicit invalidation drops the staged slot outright
    engine.invalidate_eval_cache(xte, yte)
    assert (id(xte), id(yte)) not in engine._eval_data
    assert engine.evaluate(apply_fn, xte, yte, mean_logit) == v1
    # x-only form drops every slot staged for that array
    engine.invalidate_eval_cache(xte)
    assert all(k[0] != id(xte) for k in engine._eval_data)
    assert engine.evaluate(apply_fn, xte, yte, mean_logit) == v1
    engine.invalidate_eval_cache()
    assert not engine._eval_data
