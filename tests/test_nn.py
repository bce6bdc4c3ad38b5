"""NN-layer synchronization tests (reference ``torchmpi/nn.lua`` semantics +
``test/blockSequential.lua`` partition checks)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchmpi_tpu as mpi
from torchmpi_tpu import constants, nn as mpinn
from torchmpi_tpu.nn import GradientBuckets


@pytest.fixture(autouse=True)
def _start():
    mpi.start()
    yield


def _stacked_tree(p, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "dense1": {
            "kernel": jnp.asarray(rng.randn(p, 20, 30).astype(np.float32)),
            "bias": jnp.asarray(rng.randn(p, 30).astype(np.float32)),
        },
        "dense2": {"kernel": jnp.asarray(rng.randn(p, 30, 7).astype(np.float32))},
    }


@pytest.mark.parametrize("fused", [True, False])
def test_synchronize_parameters_broadcast(fused):
    p = mpi.size()
    tree = _stacked_tree(p)
    out = mpinn.synchronize_parameters(tree, fused=fused)
    for leaf, src in zip(
        jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(tree)
    ):
        expect = np.broadcast_to(np.asarray(src)[0:1], src.shape)
        np.testing.assert_allclose(np.asarray(leaf), expect, rtol=1e-6)


def test_synchronize_parameters_allreduce_mean():
    p = mpi.size()
    tree = _stacked_tree(p)
    out = mpinn.synchronize_parameters(tree, with_allreduce=True)
    for leaf, src in zip(
        jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(tree)
    ):
        mean = np.asarray(src).mean(axis=0, keepdims=True)
        np.testing.assert_allclose(
            np.asarray(leaf), np.broadcast_to(mean, src.shape), rtol=1e-5
        )


@pytest.mark.parametrize("fused", [True, False])
def test_synchronize_gradients_sum(fused):
    """Reference semantics: SUM, not mean (nn.lua:49-56)."""
    p = mpi.size()
    tree = _stacked_tree(p, seed=1)
    out = mpinn.synchronize_gradients(tree, fused=fused)
    for leaf, src in zip(
        jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(tree)
    ):
        total = np.asarray(src).sum(axis=0, keepdims=True)
        np.testing.assert_allclose(
            np.asarray(leaf), np.broadcast_to(total, src.shape), rtol=1e-5
        )


def test_gradient_buckets_partition():
    """Equal-parameter-count partitioning (BlockSequential.lua:29-89) in
    reverse leaf order, every leaf in exactly one bucket."""
    p = mpi.size()
    tree = _stacked_tree(p)
    buckets = GradientBuckets(tree, 2)
    assert buckets.num_buckets == 2
    all_leaves = sorted(i for b in buckets.buckets for i in b)
    assert all_leaves == list(range(3))
    # reverse order: bucket 0 holds the LAST leaves
    assert max(buckets.buckets[0]) > min(buckets.buckets[-1])


def test_gradient_buckets_async_roundtrip():
    p = mpi.size()
    tree = _stacked_tree(p, seed=2)
    buckets = GradientBuckets(tree, 2)
    handles = buckets.allreduce_async(tree)
    assert len(handles) == 2
    out = buckets.wait_and_unflatten(tree, handles)
    for leaf, src in zip(
        jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(tree)
    ):
        total = np.asarray(src).sum(axis=0, keepdims=True)
        np.testing.assert_allclose(
            np.asarray(leaf), np.broadcast_to(total, src.shape), rtol=1e-5
        )


def test_bucket_count_clamped():
    p = mpi.size()
    tree = _stacked_tree(p)
    assert GradientBuckets(tree, 100).num_buckets <= 3
    assert GradientBuckets(tree, 1).num_buckets == 1


@pytest.mark.parametrize("average", [False, True], ids=["sum", "mean"])
@pytest.mark.parametrize("route", ["per_leaf", "bf16", "int8"])
def test_in_graph_sync_equals_psum_of_the_concatenated_tree(route, average):
    """The leaves reduced where they lie give, bit for bit and in each
    leaf's own dtype, what one psum of the flat buffer gave: a mixed
    tree, the integers beyond float32's 2**24. A compressed wire takes
    the float32 leaves alone, within its tolerance, and leaves the
    others as exact."""
    from jax.sharding import PartitionSpec as P

    p = mpi.size()
    mesh = mpi.current_communicator().flat_mesh("mpi")
    rng = np.random.RandomState(7)
    big = 2**24 + 1
    tree = {
        "w": jnp.asarray(rng.randn(p * 2, 17).astype(np.float32)),
        "h": jnp.asarray(rng.randn(p * 3), jnp.bfloat16),
        "b": jnp.asarray(rng.randn(p * 5).astype(np.float32)),
        "n": jnp.asarray(big + rng.randint(0, 9, size=(p * 2, 2)), jnp.int32),
    }
    wire = None if route == "per_leaf" else route
    if wire:
        constants.set("wire_quant_min_elements", 1)

    def lies(t):
        return mpinn.in_graph_synchronize_gradients(
            t, "mpi", average=average, wire_dtype=wire)

    def flat(t):
        leaves, treedef = jax.tree_util.tree_flatten(t)
        for dtype in {leaf.dtype for leaf in leaves}:
            idxs = [i for i, leaf in enumerate(leaves) if leaf.dtype == dtype]
            buf = jax.lax.psum(
                jnp.concatenate([leaves[i].reshape(-1) for i in idxs]), "mpi")
            if average:
                buf = (buf / p).astype(dtype)
            cuts = np.cumsum([leaves[i].size for i in idxs])[:-1]
            for i, part in zip(idxs, jnp.split(buf, cuts)):
                leaves[i] = part.reshape(leaves[i].shape)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    run = lambda f: jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("mpi"), out_specs=P("mpi"),
        check_vma=False))(tree)
    got, want = run(lies), run(flat)
    for k in tree:
        assert got[k].dtype == tree[k].dtype, k
        g, w = (np.asarray(a[k], np.float64) for a in (got, want))
        if wire and tree[k].dtype == jnp.float32:
            assert not np.array_equal(g, w), k  # the wire did engage
            # the ring's own bound at 8 ranks (tests/test_wire_formats.py)
            np.testing.assert_allclose(g, w, atol=2e-2 * np.abs(w).max())
        else:
            np.testing.assert_array_equal(g, w)
    if not average:  # the integers summed exactly
        total = np.asarray(tree["n"]).reshape(p, 2, 2).sum(axis=0)
        np.testing.assert_array_equal(np.asarray(got["n"])[:2], total)


def test_fused_sync_preserves_integer_leaves():
    """Fused sync must not round-trip int leaves through float32 (values
    above 2^24 would corrupt)."""
    p = mpi.size()
    big = 2**24 + 1
    tree = {
        "w": jnp.ones((p, 3), jnp.float32),
        "count": jnp.full((p, 2), big, jnp.int32),
    }
    out = mpinn.synchronize_parameters(tree)
    assert out["count"].dtype == jnp.int32
    assert int(np.asarray(out["count"])[0, 0]) == big
    out2 = mpinn.synchronize_gradients({"n": jnp.full((p, 1), big, jnp.int64)})
    assert int(np.asarray(out2["n"])[p - 1, 0]) == big * p


def test_check_with_allreduce_consistent():
    p = mpi.size()
    rng = np.random.RandomState(4)
    local = rng.randn(50).astype(np.float32)
    tree = {"w": jnp.asarray(np.tile(local[None], (p, 1)))}
    mpinn.check_with_allreduce(tree)  # must not raise


def test_check_with_allreduce_detects_desync():
    p = mpi.size()
    if p == 1:
        pytest.skip("desync is undefined with a single replica")
    rng = np.random.RandomState(5)
    vals = rng.randn(p, 50).astype(np.float32)  # every replica different
    with pytest.raises(AssertionError, match="desync"):
        mpinn.check_with_allreduce({"w": jnp.asarray(vals)})


# ---------------------------------------------------------------------------
# overlap scheduler + error-feedback compression
# ---------------------------------------------------------------------------


def test_sync_scheduled_bitwise_none_vs_reverse():
    """The flush scheduler moves time, not bits: 'none' and 'reverse'
    run the identical per-bucket collectives on identical payloads, so
    the synced trees are BITWISE equal at f32 wire."""
    p = mpi.size()
    comm = mpi.current_communicator()
    tree = _stacked_tree(p, seed=7)
    buckets = GradientBuckets(tree, 2)
    out_none = buckets.sync_scheduled(
        tree, comm=comm, wire_dtype="full", schedule="none"
    )
    out_rev = buckets.sync_scheduled(
        tree, comm=comm, wire_dtype="full", schedule="reverse"
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(out_none),
        jax.tree_util.tree_leaves(out_rev),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and both carry the plain allreduce-sum semantics
    for leaf, src in zip(
        jax.tree_util.tree_leaves(out_rev), jax.tree_util.tree_leaves(tree)
    ):
        total = np.asarray(src).sum(axis=0, keepdims=True)
        np.testing.assert_allclose(
            np.asarray(leaf), np.broadcast_to(total, src.shape), rtol=1e-5
        )


def test_sync_scheduled_rejects_unknown_schedule():
    p = mpi.size()
    tree = _stacked_tree(p)
    buckets = GradientBuckets(tree, 2)
    with pytest.raises(ValueError, match="overlap_schedule"):
        buckets.sync_scheduled(tree, schedule="forward")


def _ef_problem(p, n=1024, block=128):
    """Quadratic model engineered so plain int8 starves: each scale
    block holds ONE dominant component (sets the quantization scale)
    and small ones that round to zero on the wire without error
    feedback."""
    target = np.full(n, 0.01, np.float32)
    target[::block] = 100.0
    return jnp.asarray(np.tile(target[None], (p, 1)))


def _ef_train(wire, error_feedback, steps=30, lr=0.1):

    p = mpi.size()
    comm = mpi.current_communicator()
    constants.set("wire_dtype", wire)
    constants.set("wire_quant_min_elements", 256)
    constants.set("wire_error_feedback", error_feedback)
    # the compressed wire lives in the ring backends; the small-op cutoff
    # would silently re-route this payload to the (full-precision) fused
    # XLA path and no quantization would ever happen
    constants.set("small_allreduce_size_cpu", 0)
    target = _ef_problem(p)
    w = jnp.zeros_like(target)
    buckets = GradientBuckets({"w": w}, 1)
    for _ in range(steps):
        grads = {"w": w - target}
        synced = buckets.sync_scheduled(
            grads, comm=comm, backend="ring", average=True
        )
        w = w - lr * synced["w"]
    return np.asarray(w[0]), np.asarray(target[0])


def test_error_feedback_convergence_twin():
    """int8+EF must track the f32 trajectory where plain int8 drifts:
    the residual accumulator eventually ships the small components the
    per-block scale rounds to zero (1-bit SGD / EQuARX lineage)."""
    w_f32, target = _ef_train("full", False)
    w_plain, _ = _ef_train("int8", False)
    w_ef, _ = _ef_train("int8", True)

    small = np.ones_like(target, bool)
    small[::128] = False  # drop the scale-setting dominant components

    # f32 oracle converges geometrically on every component
    assert np.max(np.abs(w_f32 - target)[small]) < 1e-3
    # plain int8 starves the small components: quantized to zero every
    # step, they never move off the origin
    drift_plain = np.max(np.abs(w_plain - w_f32)[small])
    assert drift_plain > 5e-3
    # error feedback ships them once the residual crosses the scale
    drift_ef = np.max(np.abs(w_ef - w_f32)[small])
    assert drift_ef < 0.5 * drift_plain
    # the dominant components quantize exactly (they ARE the scale), so
    # every wire format agrees there
    big = ~small
    assert np.max(np.abs(w_plain - w_f32)[big]) < 1e-2
    assert np.max(np.abs(w_ef - w_f32)[big]) < 1e-2


def test_error_feedback_residual_lifecycle():
    """EF stores one on-device residual per bucket only while the wire
    engages; the f32 wire path never allocates residual state."""

    p = mpi.size()
    comm = mpi.current_communicator()
    constants.set("wire_quant_min_elements", 256)
    constants.set("wire_error_feedback", True)
    target = _ef_problem(p)
    buckets = GradientBuckets({"w": target}, 1)

    constants.set("wire_dtype", "full")
    buckets.sync_scheduled({"w": target}, comm=comm)
    assert not buckets._residuals, "f32 wire must not allocate residuals"

    constants.set("wire_dtype", "int8")
    buckets.sync_scheduled({"w": target}, comm=comm)
    assert len(buckets._residuals) == 1
    res = np.asarray(list(buckets._residuals.values())[0])
    assert np.any(res != 0.0), "quantizing 0.01s must leave a residual"
