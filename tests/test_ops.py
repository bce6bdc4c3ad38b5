"""Pallas kernel tests: interpret mode on the CPU mesh. The same kernels go
through the Mosaic compiler and real ICI traffic in ``chip_smoke.py``'s
kernel phase, against the XLA path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

INTERPRET = True

from torchmpi_tpu.ops.reduce_kernel import accumulate, scale_accumulate
from torchmpi_tpu.ops.ring_kernels import available, ring_allreduce_pallas


# Device-count sweep for the interpret-mode kernel tests: p=2 (minimum
# ring) and p=3 (odd/ragged schedules) stay in the fast bucket; the wider
# p=4/8 sweeps are `slow` so `-m "not slow"` iterates quickly
# (the reference's quick-vs-full test tiers, scripts/test_cpu.sh).
P_SWEEP = [2, 3,
           pytest.param(4, marks=pytest.mark.slow),
           pytest.param(8, marks=pytest.mark.slow)]


def test_accumulate_matches_add():
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(317, 53).astype(np.float32))  # ragged shape
    b = jnp.asarray(rng.randn(317, 53).astype(np.float32))
    out = accumulate(a, b, interpret=INTERPRET)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(a) + np.asarray(b), rtol=1e-6
    )


def test_scale_accumulate():
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.randn(1000).astype(np.float32))
    b = jnp.asarray(rng.randn(1000).astype(np.float32))
    out = scale_accumulate(a, b, -0.25, interpret=INTERPRET)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(a) - 0.25 * np.asarray(b), rtol=1e-5
    )


def test_accumulate_large_multiblock():
    n = 3 * 1024 * 128 + 17  # multiple grid blocks + ragged tail
    a = jnp.ones((n,), jnp.float32)
    b = jnp.full((n,), 2.0, jnp.float32)
    out = accumulate(a, b, interpret=INTERPRET)
    np.testing.assert_array_equal(np.asarray(out), 3.0)


@pytest.mark.parametrize("p", P_SWEEP)
@pytest.mark.parametrize("n", [1024, 1000, 8 * 128 * 8 + 3])
def test_pallas_ring_allreduce_interpret(p, n):
    """The RDMA ring allreduce (interpret mode) must equal the sum across
    devices, including non-divisible and sublane-padded sizes."""
    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
    rng = np.random.RandomState(p * 1000 + n)
    x = rng.randn(p, n).astype(np.float32)
    f = jax.jit(
        jax.shard_map(
            lambda b: ring_allreduce_pallas(
                b, "mpi", axis_size=p, interpret=INTERPRET
            ),
            mesh=mesh,
            in_specs=P("mpi"),
            out_specs=P("mpi"),
            check_vma=False,
        )
    )
    out = np.asarray(f(x))
    expect = x.sum(axis=0)
    np.testing.assert_allclose(out, np.tile(expect, (p, 1)), rtol=2e-5, atol=1e-5)


def test_pallas_ring_multidim_and_dtype():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    p = 4
    mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
    rng = np.random.RandomState(9)
    x = rng.randn(p, 6, 50).astype(np.float32)
    f = jax.jit(
        jax.shard_map(
            lambda b: ring_allreduce_pallas(b, "mpi", axis_size=p, interpret=INTERPRET),
            mesh=mesh,
            in_specs=P("mpi"),
            out_specs=P("mpi"),
            check_vma=False,
        )
    )
    out = np.asarray(f(x))
    np.testing.assert_allclose(
        out, np.tile(x.sum(axis=0)[None], (p, 1, 1)), rtol=2e-5
    )


def test_pallas_singleton_axis_passthrough():
    mesh = Mesh(np.array(jax.devices()[:1]), ("mpi",))
    x = jnp.ones((1, 16))
    out = jax.jit(
        jax.shard_map(
            lambda b: ring_allreduce_pallas(b, "mpi", axis_size=1, interpret=INTERPRET),
            mesh=mesh,
            in_specs=P("mpi"),
            out_specs=P("mpi"),
            check_vma=False,
        )
    )(x)
    np.testing.assert_array_equal(np.asarray(out), 1.0)


def test_available_gating():
    # on the CPU test mesh the hardware pallas path must report unavailable
    assert available() is False


def test_pallas_ring_2d_mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    """MESH-coordinate addressing: the ring over one axis of a 2-D mesh must
    stay within its row (a LOGICAL flat id would cross rows)."""
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("x", "mpi"))
    x = np.random.RandomState(1).randn(2, 4, 500).astype(np.float32)
    f = jax.jit(
        jax.shard_map(
            lambda b: ring_allreduce_pallas(b, "mpi", axis_size=4, interpret=INTERPRET),
            mesh=mesh,
            in_specs=P("x", "mpi"),
            out_specs=P("x", "mpi"),
            check_vma=False,
        )
    )
    out = np.asarray(f(x))
    np.testing.assert_allclose(
        out, np.broadcast_to(x.sum(axis=1, keepdims=True), x.shape),
        rtol=2e-5, atol=1e-5,
    )


@pytest.mark.slow
def test_pallas_ring_vmem_segmentation():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    """Buffers beyond the VMEM budget split into sequential ring segments."""
    from torchmpi_tpu.ops import ring_kernels as rk

    old = rk._VMEM_BUDGET_BYTES
    rk._VMEM_BUDGET_BYTES = 64 * 1024  # force several segments
    try:
        p = 4
        mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
        n = 3 * 4 * 8 * 128 + 100  # > one tiny-budget segment
        x = np.random.RandomState(2).randn(p, n).astype(np.float32)
        f = jax.jit(
            jax.shard_map(
                lambda b: ring_allreduce_pallas(b, "mpi", axis_size=p, interpret=INTERPRET),
                mesh=mesh,
                in_specs=P("mpi"),
                out_specs=P("mpi"),
                check_vma=False,
            )
        )
        out = np.asarray(f(x))
        np.testing.assert_allclose(
            out, np.tile(x.sum(axis=0), (p, 1)), rtol=2e-5, atol=1e-5
        )
    finally:
        rk._VMEM_BUDGET_BYTES = old


@pytest.mark.parametrize(
    "dtype", [jnp.int32, jnp.bfloat16, jnp.int8, jnp.float16, jnp.int16]
)
def test_pallas_ring_dtype_preserving(dtype):
    """Round-1 regression: the kernel cast everything through f32, silently
    corrupting int32 sums >= 2^24. Every supported dtype must round-trip
    exactly (ints) or to dtype precision (floats)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    p = 4
    mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
    if jnp.dtype(dtype).kind in "iu":
        # values whose sum is NOT representable in f24 mantissa steps
        base = 1 << 24 if jnp.dtype(dtype).itemsize >= 4 else 13
        x = np.arange(p * 300, dtype=np.int64).reshape(p, 300) % 97 + base
        x = x.astype(dtype)
        expect = x.astype(np.int64).sum(axis=0).astype(dtype)
    else:
        x = np.random.RandomState(5).randn(p, 300).astype(dtype)
        expect = x.sum(axis=0).astype(dtype)
    f = jax.jit(
        jax.shard_map(
            lambda b: ring_allreduce_pallas(b, "mpi", axis_size=p, interpret=INTERPRET),
            mesh=mesh,
            in_specs=P("mpi"),
            out_specs=P("mpi"),
            check_vma=False,
        )
    )
    out = np.asarray(f(jnp.asarray(x)))
    assert out.dtype == np.asarray(expect).dtype
    if jnp.dtype(dtype).kind in "iu":
        np.testing.assert_array_equal(out, np.tile(expect, (p, 1)))
    else:
        np.testing.assert_allclose(
            out.astype(np.float32),
            np.tile(expect.astype(np.float32), (p, 1)),
            rtol=3e-2 if dtype in (jnp.bfloat16, jnp.float16) else 2e-5,
        )


@pytest.mark.parametrize("p", P_SWEEP)
@pytest.mark.parametrize("root", [0, 1])
@pytest.mark.parametrize("k", [None, 4])
def test_pallas_ring_broadcast_interpret(p, root, k):
    """Pipelined RDMA broadcast: every device receives the root's block."""
    from torchmpi_tpu.ops.ring_kernels import ring_broadcast_pallas

    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    root = root % p
    mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
    rng = np.random.RandomState(p * 7 + root)
    x = rng.randn(p, 1500).astype(np.float32)
    f = jax.jit(
        jax.shard_map(
            lambda b: ring_broadcast_pallas(
                b, root, "mpi", axis_size=p, num_chunks=k, interpret=INTERPRET
            ),
            mesh=mesh,
            in_specs=P("mpi"),
            out_specs=P("mpi"),
            check_vma=False,
        )
    )
    out = np.asarray(f(x))
    np.testing.assert_array_equal(out, np.tile(x[root], (p, 1)))


@pytest.mark.parametrize("p", P_SWEEP)
def test_pallas_reduce_scatter_interpret(p):
    """psum_scatter semantics: device r gets the sum of every device's
    segment r."""
    from torchmpi_tpu.ops.ring_kernels import ring_reduce_scatter_pallas

    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
    rng = np.random.RandomState(p)
    seg = 40
    # global input: [p, p*seg]; device r's block is row r
    x = rng.randn(p, p * seg).astype(np.float32)
    f = jax.jit(
        jax.shard_map(
            lambda b: ring_reduce_scatter_pallas(
                b.reshape(p * seg), "mpi", axis_size=p, interpret=INTERPRET
            ),
            mesh=mesh,
            in_specs=P("mpi"),
            out_specs=P("mpi"),
            check_vma=False,
        )
    )
    out = np.asarray(f(x)).reshape(p, seg)
    summed = x.sum(axis=0).reshape(p, seg)  # segment r = summed[r]
    np.testing.assert_allclose(out, summed, rtol=2e-5, atol=1e-5)
    # parity with lax.psum_scatter
    ps = jax.jit(
        jax.shard_map(
            lambda b: jax.lax.psum_scatter(
                b.reshape(p * seg), "mpi", scatter_dimension=0, tiled=True
            ),
            mesh=mesh,
            in_specs=P("mpi"),
            out_specs=P("mpi"),
            check_vma=False,
        )
    )
    np.testing.assert_allclose(
        out, np.asarray(ps(x)).reshape(p, seg), rtol=2e-5, atol=1e-5
    )


def test_pallas_reduce_scatter_rejects_indivisible():
    from torchmpi_tpu.ops.ring_kernels import ring_reduce_scatter_pallas

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    p = 4
    mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(
            jax.shard_map(
                lambda b: ring_reduce_scatter_pallas(
                    b.reshape(-1), "mpi", axis_size=p, interpret=INTERPRET
                ),
                mesh=mesh,
                in_specs=P("mpi"),
                out_specs=P("mpi"),
                check_vma=False,
            )
        )(np.zeros((p, 7), np.float32))


@pytest.mark.slow
def test_pallas_broadcast_vmem_segmentation_and_bitcast():
    """Broadcasts beyond the VMEM budget run as sequential segments; non-
    native dtypes ride losslessly as a byte view (here: int64)."""
    from torchmpi_tpu.ops import ring_kernels as rk

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    p = 4
    old = rk._VMEM_BUDGET_BYTES
    rk._VMEM_BUDGET_BYTES = 64 * 1024
    try:
        mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
        n = 3 * 8 * 128 * 8 + 11  # several tiny-budget segments
        # uint32 is not kernel-native: rides as a lossless byte view
        x = (
            np.random.RandomState(4)
            .randint(0, 1 << 31, (p, n))
            .astype(np.uint32)
        )
        x[:, 0] = 0xDEADBEEF  # not representable in f32
        f = jax.jit(
            jax.shard_map(
                lambda b: rk.ring_broadcast_pallas(
                    b, 2, "mpi", axis_size=p, interpret=INTERPRET
                ),
                mesh=mesh,
                in_specs=P("mpi"),
                out_specs=P("mpi"),
                check_vma=False,
            )
        )
        out = np.asarray(f(x))
        np.testing.assert_array_equal(out, np.tile(x[2], (p, 1)))
    finally:
        rk._VMEM_BUDGET_BYTES = old


@pytest.mark.slow
def test_pallas_reduce_scatter_vmem_segmentation():
    from torchmpi_tpu.ops import ring_kernels as rk

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    p = 4
    old = rk._VMEM_BUDGET_BYTES
    rk._VMEM_BUDGET_BYTES = 64 * 1024
    try:
        mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
        seg = 8 * 128 * 6  # rows beyond the tiny budget
        x = np.random.RandomState(6).randn(p, p * seg).astype(np.float32)
        f = jax.jit(
            jax.shard_map(
                lambda b: rk.ring_reduce_scatter_pallas(
                    b.reshape(-1), "mpi", axis_size=p, interpret=INTERPRET
                ),
                mesh=mesh,
                in_specs=P("mpi"),
                out_specs=P("mpi"),
                check_vma=False,
            )
        )
        out = np.asarray(f(x)).reshape(p, seg)
        np.testing.assert_allclose(
            out, x.sum(axis=0).reshape(p, seg), rtol=2e-5, atol=1e-5
        )
    finally:
        rk._VMEM_BUDGET_BYTES = old


def test_pallas_broadcast_bool_rides_as_uint8():
    from torchmpi_tpu.ops import ring_kernels as rk

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    p = 4
    mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
    x = np.random.RandomState(8).rand(p, 600) > 0.5
    f = jax.jit(
        jax.shard_map(
            lambda b: rk.ring_broadcast_pallas(
                b, 1, "mpi", axis_size=p, interpret=INTERPRET
            ),
            mesh=mesh,
            in_specs=P("mpi"),
            out_specs=P("mpi"),
            check_vma=False,
        )
    )
    out = np.asarray(f(x))
    assert out.dtype == np.bool_
    np.testing.assert_array_equal(out, np.tile(x[1], (p, 1)))


@pytest.mark.parametrize("p", P_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
def test_pallas_allgather_interpret(p, dtype):
    """Pallas ring allgather: every device gets [p, ...] stacked in rank
    order, bit-exact (float blocks ride as byte views: -0.0 preserved)."""
    from torchmpi_tpu.ops.ring_kernels import ring_allgather_pallas

    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
    rng = np.random.RandomState(p)
    x = rng.randn(p, 7, 33).astype(np.float32)
    if jnp.dtype(dtype).kind in "iu":
        x = (x * 100).astype(dtype)
    else:
        x = x.astype(dtype)
        x[:, 0, 0] = -0.0  # bit-exactness probe
    f = jax.jit(
        jax.shard_map(
            lambda b: ring_allgather_pallas(
                b[0], "mpi", axis_size=p, interpret=INTERPRET
            )[None],
            mesh=mesh,
            in_specs=P("mpi"),
            out_specs=P("mpi"),
            check_vma=False,
        )
    )
    out = np.asarray(f(jnp.asarray(x)))  # [p, p, 7, 33]
    assert out.dtype == x.dtype
    # BYTE comparison for every float dtype: -0.0 must survive (bf16's
    # numpy kind is 'V', so check float-ness via jnp.issubdtype)
    as_bytes = jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
    for r in range(p):
        np.testing.assert_array_equal(
            out[r].view(np.uint8) if as_bytes else out[r],
            x.view(np.uint8) if as_bytes else x,
        )


def test_eager_pallas_allgather_dispatch():
    """backend='pallas' allgather concats along the last dim in rank order
    through the eager contract (forced interpret)."""
    import torchmpi_tpu as mpi
    from torchmpi_tpu.collectives import eager
    from torchmpi_tpu.ops import ring_kernels as rk

    mpi.start()
    rk._FORCE_INTERPRET = INTERPRET
    try:
        p = mpi.size()
        comm = mpi.current_communicator()
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(p, 40).astype(np.float32))
        out = np.asarray(eager.run("allgather", x, comm, backend="pallas"))
        expect = np.asarray(x).reshape(-1)
        for r in range(p):
            np.testing.assert_array_equal(out[r], expect)
    finally:
        rk._FORCE_INTERPRET = False
        mpi.stop()


def test_eager_pallas_reducescatter_dispatch():
    """backend='pallas' reducescatter scatters the summed last dim in rank
    order through the eager contract (forced interpret)."""
    import torchmpi_tpu as mpi
    from torchmpi_tpu.collectives import eager
    from torchmpi_tpu.ops import ring_kernels as rk

    mpi.start()
    rk._FORCE_INTERPRET = INTERPRET
    try:
        p = mpi.size()
        comm = mpi.current_communicator()
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(p, 4 * p).astype(np.float32))
        out = np.asarray(eager.run("reducescatter", x, comm, backend="pallas"))
        assert out.shape == (p, 4)
        total = np.asarray(x).sum(axis=0)
        for r in range(p):
            np.testing.assert_allclose(
                out[r], total[4 * r : 4 * (r + 1)], rtol=1e-5, atol=1e-6
            )
    finally:
        rk._FORCE_INTERPRET = False
        mpi.stop()


def test_pallas_reduction_rejects_lossy_dtype():
    from torchmpi_tpu.ops import ring_kernels as rk

    with pytest.raises(ValueError, match="not supported"):
        rk._carrier_dtype(jnp.uint32)


def test_eager_pallas_backend_dispatch():
    """backend='pallas' flows through the eager dispatch to the RDMA kernel
    (forced interpret so it runs on the CPU mesh)."""
    import torchmpi_tpu as mpi
    from torchmpi_tpu.ops import ring_kernels as rk

    mpi.start()
    rk._FORCE_INTERPRET = INTERPRET
    try:
        mpi.constants.set("small_allreduce_size_cpu", 1)  # stay on pallas
        p = mpi.size()
        x = jnp.tile(jnp.arange(p, dtype=jnp.float32)[:, None], (1, 700))
        from torchmpi_tpu.collectives import eager

        out = np.asarray(eager.run("allreduce", x, mpi.current_communicator(),
                                   backend="pallas"))
        np.testing.assert_array_equal(out, p * (p - 1) / 2)
    finally:
        rk._FORCE_INTERPRET = False
        mpi.stop()


def test_eager_pallas_broadcast_dispatch():
    """backend='pallas' broadcast takes the RDMA pipelined kernel above the
    tree cutoff (forced interpret)."""
    import torchmpi_tpu as mpi
    from torchmpi_tpu.ops import ring_kernels as rk

    mpi.start()
    rk._FORCE_INTERPRET = INTERPRET
    try:
        mpi.constants.set("small_broadcast_size_cpu", 1)
        mpi.constants.set("broadcast_size_tree_based_cpu", 64)  # pipeline
        p = mpi.size()
        comm = mpi.current_communicator()
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(p, 3000).astype(np.float32))
        from torchmpi_tpu.collectives import eager

        out = np.asarray(
            eager.run("broadcast", x, comm, backend="pallas", root=1 % p)
        )
        np.testing.assert_array_equal(
            out, np.tile(np.asarray(x)[1 % p], (p, 1))
        )
    finally:
        rk._FORCE_INTERPRET = False
        mpi.stop()


def test_eager_pallas_dtype_fallback():
    """Unsupported dtypes through backend='pallas' silently fall back to the
    ppermute ring and stay exact (the round-1 int corruption regression)."""
    import torchmpi_tpu as mpi
    from torchmpi_tpu.collectives import eager
    from torchmpi_tpu.ops import ring_kernels as rk

    mpi.start()
    rk._FORCE_INTERPRET = INTERPRET
    try:
        mpi.constants.set("small_allreduce_size_cpu", 1)
        mpi.constants.set("use_hierarchical_collectives", False)
        p = mpi.size()
        comm = mpi.current_communicator()
        # int32 IS supported natively now: values >= 2^24 stay exact
        big = 1 << 24
        x = jnp.full((p, 700), big, jnp.int32)
        out = np.asarray(eager.run("allreduce", x, comm, backend="pallas"))
        np.testing.assert_array_equal(out, np.int64(big) * p)
        # uint32 is NOT in the native set and has no lossless carrier ->
        # must have routed through the ppermute ring, still exact
        assert not rk.supports_dtype(jnp.uint32)
        xu = jnp.full((p, 700), 3, jnp.uint32)
        outu = np.asarray(eager.run("allreduce", xu, comm, backend="pallas"))
        np.testing.assert_array_equal(outu, 3 * p)
        keys = [
            k for k in comm._collective_resources
            if k[0] == "allreduce" and k[1] == "ring"
        ]
        assert keys, "uint32 did not fall back to the ppermute ring"
    finally:
        rk._FORCE_INTERPRET = False
        mpi.stop()


@pytest.mark.parametrize("p", P_SWEEP)
@pytest.mark.parametrize("root", [0, 1])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
def test_pallas_ring_reduce_interpret(p, root, dtype):
    """Pallas ring reduce: root receives the sum (RS + root-directed chunk
    gather), every other device returns its input unchanged."""
    from torchmpi_tpu.ops.ring_kernels import ring_reduce_pallas

    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    root = root % p
    mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
    rng = np.random.RandomState(p * 13 + root)
    if jnp.dtype(dtype).kind in "iu":
        x = rng.randint(-1000, 1000, (p, 300)).astype(dtype)
        expect_root = x.sum(axis=0).astype(dtype)
    else:
        x = rng.randn(p, 300).astype(dtype)
        expect_root = x.sum(axis=0).astype(dtype)
    f = jax.jit(
        jax.shard_map(
            lambda b: ring_reduce_pallas(
                b, root, "mpi", axis_size=p, interpret=INTERPRET
            ),
            mesh=mesh,
            in_specs=P("mpi"),
            out_specs=P("mpi"),
            check_vma=False,
        )
    )
    out = np.asarray(f(jnp.asarray(x)))
    assert out.dtype == x.dtype
    expect = np.asarray(x).copy()
    expect[root] = np.asarray(expect_root)
    if jnp.dtype(dtype).kind in "iu":
        np.testing.assert_array_equal(out, expect)
    else:
        np.testing.assert_allclose(
            out.astype(np.float32),
            expect.astype(np.float32),
            rtol=3e-2 if dtype in (jnp.bfloat16, jnp.float16) else 2e-5,
        )


@pytest.mark.slow
def test_pallas_ring_step_counts():
    """The dedicated allgather schedule is (p-1) steps — NOT the 2(p-1) of
    the round-2 zero-padded allreduce reuse; allreduce/reduce stay 2(p-1)
    and reduce-scatter (p-1). Counts are recorded at trace time from the
    static schedule."""
    from torchmpi_tpu.ops import ring_kernels as rk

    p = 8
    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
    x = np.random.RandomState(0).randn(p, 256).astype(np.float32)

    def run(fn):
        rk._LAST_STEP_COUNTS.clear()
        jax.jit(
            jax.shard_map(
                fn, mesh=mesh, in_specs=P("mpi"), out_specs=P("mpi"),
                check_vma=False,
            )
        )(x)

    run(lambda b: rk.ring_allgather_pallas(
        b[0], "mpi", axis_size=p, interpret=INTERPRET)[None])
    assert rk._LAST_STEP_COUNTS["allgather"] == p - 1

    run(lambda b: rk.ring_allreduce_pallas(
        b, "mpi", axis_size=p, interpret=INTERPRET))
    assert rk._LAST_STEP_COUNTS["allreduce"] == 2 * (p - 1)

    run(lambda b: rk.ring_reduce_pallas(
        b, 0, "mpi", axis_size=p, interpret=INTERPRET))
    assert rk._LAST_STEP_COUNTS["reduce"] == 2 * (p - 1)

    run(lambda b: rk.ring_reduce_scatter_pallas(
        b.reshape(-1), "mpi", axis_size=p, interpret=INTERPRET))
    assert rk._LAST_STEP_COUNTS["reduce_scatter"] == p - 1


def test_eager_pallas_reduce_dispatch():
    """backend='pallas' reduce flows through the eager dispatch to the RDMA
    reduce kernel (no ppermute fallback), forced interpret."""
    import torchmpi_tpu as mpi
    from torchmpi_tpu.collectives import eager
    from torchmpi_tpu.ops import ring_kernels as rk

    mpi.start()
    rk._FORCE_INTERPRET = INTERPRET
    try:
        p = mpi.size()
        comm = mpi.current_communicator()
        rng = np.random.RandomState(11)
        x = jnp.asarray(rng.randn(p, 500).astype(np.float32))
        root = 1 % p
        out = np.asarray(eager.run("reduce", x, comm, backend="pallas", root=root))
        expect = np.asarray(x).copy()
        expect[root] = np.asarray(x).sum(axis=0)
        np.testing.assert_allclose(out, expect, rtol=2e-5, atol=1e-5)
        keys = [
            k for k in comm._collective_resources
            if k[0] == "reduce" and k[1] == "pallas"
        ]
        assert keys, "reduce did not dispatch to the pallas backend"
    finally:
        rk._FORCE_INTERPRET = False
        mpi.stop()


@pytest.mark.parametrize("p", P_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_pallas_bidir_allreduce_interpret(p, dtype):
    """Bidirectional ring allreduce: two half-buffers reduced in opposite
    directions simultaneously — numerically identical to the flat sum."""
    from torchmpi_tpu.ops.ring_kernels import ring_allreduce_bidir_pallas

    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    mesh = Mesh(np.array(jax.devices()[:p]), ("mpi",))
    rng = np.random.RandomState(p * 5)
    if jnp.dtype(dtype).kind in "iu":
        x = rng.randint(-999, 999, (p, 513)).astype(dtype)  # odd: uneven halves
    else:
        x = rng.randn(p, 513).astype(dtype)
    expect = x.sum(axis=0).astype(dtype)
    f = jax.jit(
        jax.shard_map(
            lambda b: ring_allreduce_bidir_pallas(
                b, "mpi", axis_size=p, interpret=INTERPRET
            ),
            mesh=mesh,
            in_specs=P("mpi"),
            out_specs=P("mpi"),
            check_vma=False,
        )
    )
    out = np.asarray(f(jnp.asarray(x)))
    assert out.dtype == x.dtype
    if jnp.dtype(dtype).kind in "iu":
        np.testing.assert_array_equal(out, np.tile(expect, (p, 1)))
    else:
        # atol: the leftward ring accumulates in mirrored order, so
        # near-zero sums round differently than numpy's (catastrophic
        # cancellation, not a kernel defect; all rows agree exactly)
        np.testing.assert_allclose(
            out, np.tile(expect, (p, 1)), rtol=2e-5, atol=1e-5
        )


def test_eager_pallas_bidir_dispatch():
    """ring_implementation='pallas_bidir' routes eager allreduce through
    the bidirectional kernel (cache-keyed: toggling the constant swaps
    executables)."""
    import torchmpi_tpu as mpi
    from torchmpi_tpu.collectives import eager
    from torchmpi_tpu.ops import ring_kernels as rk

    mpi.start()
    rk._FORCE_INTERPRET = INTERPRET
    try:
        mpi.constants.set("small_allreduce_size_cpu", 1)
        mpi.constants.set("use_hierarchical_collectives", False)
        mpi.constants.set("ring_implementation", "pallas_bidir")
        p = mpi.size()
        comm = mpi.current_communicator()
        x = jnp.tile(jnp.arange(p, dtype=jnp.float32)[:, None], (1, 700))
        rk._LAST_STEP_COUNTS.clear()
        out = np.asarray(eager.run("allreduce", x, comm, backend="pallas"))
        np.testing.assert_array_equal(out, p * (p - 1) / 2)
        if p >= 3:
            assert "allreduce_bidir" in rk._LAST_STEP_COUNTS
        elif p == 2:
            # two devices share one link: the kernel intentionally
            # delegates to the unidirectional schedule
            assert "allreduce" in rk._LAST_STEP_COUNTS
        keys = [
            k for k in comm._collective_resources
            if k[0] == "allreduce" and k[1] == "pallas" and "bidir" in k[3]
        ]
        assert keys, "bidir variant not in the executable cache key"
    finally:
        rk._FORCE_INTERPRET = False
        mpi.stop()


# ---------------------------------------------------------------------------
# ring attention kernel
# ---------------------------------------------------------------------------


def _ra_mesh(p):
    return Mesh(np.array(jax.devices()[:p]), ("sp",))


@pytest.mark.parametrize("p", P_SWEEP)
@pytest.mark.parametrize("causal", [False, True])
def test_pallas_ring_attention_interpret(p, causal):
    """The RDMA ring-attention kernel (interpret mode) == full attention
    over the gathered sequence, causal and not, p = 2..8."""
    from torchmpi_tpu.ops import ring_attention_pallas
    from torchmpi_tpu.parallel.ring_attention import full_self_attention

    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    rng = np.random.RandomState(100 * p + causal)
    b, t, h, d = 2, 8 * p, 2, 16
    q = rng.randn(b, t, h, d).astype(np.float32)
    k = rng.randn(b, t, h, d).astype(np.float32)
    v = rng.randn(b, t, h, d).astype(np.float32)
    f = jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention_pallas(
                q, k, v, "sp", causal=causal, axis_size=p, interpret=INTERPRET
            ),
            mesh=_ra_mesh(p),
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
            check_vma=False,
        )
    )
    out = np.asarray(f(q, k, v))
    expect = np.asarray(full_self_attention(q, k, v, causal=causal))
    np.testing.assert_allclose(out, expect, atol=2e-5)


def test_pallas_ring_attention_bf16():
    from torchmpi_tpu.ops import ring_attention_pallas
    from torchmpi_tpu.parallel.ring_attention import full_self_attention

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    rng = np.random.RandomState(7)
    b, t, h, d = 1, 32, 2, 8
    mk = lambda: jnp.asarray(rng.randn(b, t, h, d), jnp.bfloat16)  # noqa: E731
    q, k, v = mk(), mk(), mk()
    f = jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention_pallas(
                q, k, v, "sp", causal=True, axis_size=4, interpret=INTERPRET
            ),
            mesh=_ra_mesh(4),
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
            check_vma=False,
        )
    )
    out = f(q, k, v)
    assert out.dtype == jnp.bfloat16
    expect = full_self_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect), atol=0.05
    )


def test_pallas_ring_attention_grad_matches_xla():
    """backend='pallas_interpret' must train: its custom VJP (XLA-ring
    backward) produces the same loss AND gradients as the pure XLA ring."""
    from torchmpi_tpu.parallel.ring_attention import ring_self_attention

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    p = 4
    rng = np.random.RandomState(11)
    b, t, h, d = 1, 8 * p, 2, 8
    q = rng.randn(b, t, h, d).astype(np.float32)
    k = rng.randn(b, t, h, d).astype(np.float32)
    v = rng.randn(b, t, h, d).astype(np.float32)

    def make(backend):
        def loss(q, k, v):
            o = ring_self_attention(
                q, k, v, "sp", causal=True, backend=backend
            )
            return jax.lax.pmean(jnp.mean(o**2), "sp")

        return jax.jit(
            jax.shard_map(
                jax.value_and_grad(loss, argnums=(0, 1, 2)),
                mesh=_ra_mesh(p),
                in_specs=(P(None, "sp"),) * 3,
                out_specs=(P(), (P(None, "sp"),) * 3),
                check_vma=False,
            )
        )

    l0, g0 = make("xla")(q, k, v)
    l1, g1 = make("pallas_interpret")(q, k, v)
    np.testing.assert_allclose(float(l1), float(l0), atol=1e-6)
    for a, b_ in zip(g0, g1):
        np.testing.assert_allclose(
            np.asarray(b_), np.asarray(a), atol=2e-5
        )


@pytest.mark.slow
def test_pallas_ring_attention_vmem_envelope():
    """Working sets beyond the VMEM budget AUTO-CHUNK over batch/heads
    (each chunk rides its own ring); only a single oversized (batch,
    head) cell is rejected loudly."""
    from torchmpi_tpu.ops import ring_attention_pallas
    from torchmpi_tpu.ops.ring_attention_kernel import (
        ring_attention_vmem_bytes,
    )

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    big = (8, 2048, 8, 64)  # over budget in aggregate, cells fit
    assert ring_attention_vmem_bytes(big, jnp.bfloat16) > 10 * 1024 * 1024
    q = jnp.zeros(big, jnp.bfloat16)

    def shaped(q):
        return jax.eval_shape(
            lambda q: jax.shard_map(
                lambda q: ring_attention_pallas(
                    q, q, q, "sp", axis_size=2, interpret=INTERPRET
                ),
                mesh=_ra_mesh(2),
                in_specs=P(None, "sp"),
                out_specs=P(None, "sp"),
                check_vma=False,
            )(q),
            q,
        )

    assert shaped(q).shape == big  # chunked, not rejected
    huge_cell = jnp.zeros((1, 65536, 1, 256), jnp.bfloat16)
    with pytest.raises(ValueError, match="VMEM envelope"):
        shaped(huge_cell)


@pytest.mark.parametrize("p", [2, 3])
@pytest.mark.parametrize("causal", [False, True])
def test_pallas_ring_attention_chunked_matches_unchunked(p, causal):
    """A tiny forced budget splits the call into per-(batch, head) ring
    trips; outputs and grads must match the unchunked kernel exactly."""
    from functools import partial

    from jax.sharding import Mesh

    from torchmpi_tpu.ops import ring_attention_kernel as rak

    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    b, n, h, d = 2, 4 * p, 4, 8
    rs = np.random.RandomState(11 + p)
    q = rs.randn(b, n, h, d).astype(np.float32)
    k = rs.randn(b, n, h, d).astype(np.float32)
    v = rs.randn(b, n, h, d).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:p]), ("sp",))
    # budgets that admit two (batch, head) cells of the eight per call
    two_cells = (1, n // p, 2, d)

    def fwd(budget):
        f = lambda q, k, v: rak.ring_attention_pallas(  # noqa: E731
            q, k, v, axis="sp", causal=causal, interpret=True,
            vmem_budget_bytes=budget,
        )
        return jax.jit(partial(
            jax.shard_map, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False,
        )(f))(q, k, v)

    np.testing.assert_allclose(
        np.asarray(fwd(rak.ring_attention_vmem_bytes(two_cells, q.dtype))),
        np.asarray(fwd(None)),
        rtol=1e-5, atol=1e-5,
    )

    def fwd_bidir(budget):
        f = lambda q, k, v: rak.ring_attention_bidir_pallas(  # noqa: E731
            q, k, v, axis="sp", causal=causal, interpret=True,
            vmem_budget_bytes=budget,
        )
        return jax.jit(partial(
            jax.shard_map, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False,
        )(f))(q, k, v)

    np.testing.assert_allclose(
        np.asarray(fwd_bidir(
            rak.ring_attention_bidir_vmem_bytes(two_cells, q.dtype)
        )),
        np.asarray(fwd(None)),
        rtol=1e-5, atol=1e-5,
    )

    def grads(budget):
        def loss(q, k, v):
            out = rak.ring_attention(
                q, k, v, "sp", causal, None, True, True,
                vmem_budget_bytes=budget,
            )
            return (out * out).sum()

        return jax.jit(jax.grad(partial(
            jax.shard_map, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(), check_vma=False,
        )(lambda q, k, v: jax.lax.psum(loss(q, k, v), "sp")),
            argnums=(0, 1, 2)))(q, k, v)

    bwd_budget = rak.ring_attention_bwd_vmem_bytes(two_cells, q.dtype)
    for a, g in zip(grads(None), grads(bwd_budget)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(a), rtol=1e-4, atol=1e-5
        )


@pytest.mark.slow
def test_long_context_transformer_pallas_backend():
    """The model's sp_backend switch routes attention through the kernel:
    forward logits match the XLA-ring backend."""
    from torchmpi_tpu.models import LongContextTransformer

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    p = 4
    rng = np.random.RandomState(5)
    tokens = rng.randint(0, 64, (2, 8 * p)).astype(np.int32)

    def run(backend):
        lm = LongContextTransformer(
            vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
            d_model=32, max_len=64, sp_axis="sp", sp_backend=backend,
        )

        def fwd(tok):
            params = lm.init(jax.random.PRNGKey(0), tok)["params"]
            return lm.apply({"params": params}, tok)

        return np.asarray(
            jax.jit(
                jax.shard_map(
                    fwd,
                    mesh=_ra_mesh(p),
                    in_specs=P(None, "sp"),
                    out_specs=P(None, "sp"),
                    check_vma=False,
                )
            )(tokens)
        )

    np.testing.assert_allclose(
        run("pallas_interpret"), run("xla"), atol=2e-4
    )


def test_pallas_ring_attention_grad_singleton_axis():
    """backend='pallas' on a size-1 sp axis: the custom VJP's p==1 branch
    (single score matrix for out + lse, local full-attention backward)
    must match plain autodiff of full attention."""
    from torchmpi_tpu.ops import ring_attention
    from torchmpi_tpu.parallel.ring_attention import full_self_attention

    rng = np.random.RandomState(13)
    b, t, h, d = 2, 16, 2, 8
    q = rng.randn(b, t, h, d).astype(np.float32)
    k = rng.randn(b, t, h, d).astype(np.float32)
    v = rng.randn(b, t, h, d).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))

    def loss(fn):
        return lambda q, k, v: jnp.mean(fn(q, k, v) ** 2)

    ring_fn = lambda q, k, v: ring_attention(  # noqa: E731
        q, k, v, "sp", True, 1, INTERPRET
    )
    l1, g1 = jax.jit(
        jax.shard_map(
            jax.value_and_grad(loss(ring_fn), argnums=(0, 1, 2)),
            mesh=mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=(P(), (P(None, "sp"),) * 3),
            check_vma=False,
        )
    )(q, k, v)
    full_fn = lambda q, k, v: full_self_attention(  # noqa: E731
        q, k, v, causal=True
    )
    l0, g0 = jax.value_and_grad(loss(full_fn), argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    )
    np.testing.assert_allclose(float(l1), float(l0), atol=1e-6)
    for a, b_ in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a), atol=2e-5)


@pytest.mark.parametrize("p", P_SWEEP)
@pytest.mark.parametrize("causal", [False, True])
def test_pallas_ring_attention_bwd_kernel_matches_xla(p, causal):
    """The RDMA backward kernel ('pallas_*_full' backends): dq/dk/dv match
    the analytic XLA ppermute backward bit-for-purpose — the dK/dV
    accumulators ride the ring home with their blocks (the fused-transport
    symmetry of collectives_cuda.cpp:202-388)."""
    from functools import partial

    from jax.sharding import Mesh, PartitionSpec as P

    from torchmpi_tpu.parallel.ring_attention import ring_self_attention

    b, n, h, d = 2, 4 * p, 2, 8
    rs = np.random.RandomState(7 + p)
    q = rs.randn(b, n, h, d).astype(np.float32)
    k = rs.randn(b, n, h, d).astype(np.float32)
    v = rs.randn(b, n, h, d).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:p]), ("sp",))

    def grads(backend):
        def loss(q, k, v):
            out = ring_self_attention(
                q, k, v, axis="sp", causal=causal, backend=backend
            )
            return (out * out).sum()

        f = jax.jit(jax.grad(
            partial(
                jax.shard_map, mesh=mesh,
                in_specs=(P(None, "sp"),) * 3, out_specs=P(),
                check_vma=False,
            )(lambda q, k, v: jax.lax.psum(loss(q, k, v), "sp")),
            argnums=(0, 1, 2),
        ))
        return f(q, k, v)

    ref = grads("xla")
    got = grads("pallas_interpret_full")
    for r, g, name in zip(ref, got, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch (p={p}, causal={causal})",
        )


def test_pallas_ring_attention_bwd_vmem_envelope():
    """The backward's bigger working set (4 extra f32 ring slots) is
    gated: an oversized shard raises with the fallback suggestion."""
    from torchmpi_tpu.ops.ring_attention_kernel import (
        _VMEM_BUDGET_BYTES,
        ring_attention_bwd_vmem_bytes,
    )

    small = ring_attention_bwd_vmem_bytes((1, 128, 2, 64), jnp.float32)
    assert small < _VMEM_BUDGET_BYTES
    big = ring_attention_bwd_vmem_bytes((8, 4096, 16, 128), jnp.float32)
    assert big > _VMEM_BUDGET_BYTES
    # the backward set strictly dominates the forward's (it carries the
    # f32 dK/dV slots on top of the K/V ring)
    from torchmpi_tpu.ops.ring_attention_kernel import ring_attention_vmem_bytes

    assert ring_attention_bwd_vmem_bytes(
        (2, 256, 4, 64), jnp.bfloat16
    ) > ring_attention_vmem_bytes((2, 256, 4, 64), jnp.bfloat16)


@pytest.mark.parametrize("p", P_SWEEP)
@pytest.mark.parametrize("causal", [False, True])
def test_pallas_ring_attention_bidir_interpret(p, causal):
    """Bidirectional forward ('pallas_*_bidir'): two K/V chains in
    opposite ICI directions cover sources {my, my±1, my±2, ...} in
    ceil((p-1)/2)+1 steps; the order-independent streaming-softmax merge
    makes the result exactly the unidirectional ring's."""
    from functools import partial

    from jax.sharding import Mesh

    from torchmpi_tpu.parallel.ring_attention import ring_self_attention

    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    b, n, h, d = 2, 4 * p, 2, 8
    rs = np.random.RandomState(29 + p)
    q = rs.randn(b, n, h, d).astype(np.float32)
    k = rs.randn(b, n, h, d).astype(np.float32)
    v = rs.randn(b, n, h, d).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:p]), ("sp",))

    def run(backend):
        f = lambda q, k, v: ring_self_attention(  # noqa: E731
            q, k, v, axis="sp", causal=causal, backend=backend
        )
        return jax.jit(partial(
            jax.shard_map, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False,
        )(f))(q, k, v)

    np.testing.assert_allclose(
        np.asarray(run("pallas_interpret_bidir")),
        np.asarray(run("xla")),
        rtol=2e-4, atol=2e-4,
    )


@pytest.mark.parametrize("p", [3, 4])
@pytest.mark.parametrize("backend", [
    "pallas_interpret_bidir", "pallas_interpret_bidir_full",
])
def test_pallas_ring_attention_bidir_grads(backend, p):
    """Gradients through the bidir forward: the saved (o, lse) residuals
    feed either the analytic XLA backward or the RDMA backward kernel —
    both must match the all-XLA reference. p=3 has equal chains
    (nR == nL == 1); p=4 exercises the asymmetric case (the L chain one
    distance short, its early-stop at t > nL)."""
    from functools import partial

    from jax.sharding import Mesh

    from torchmpi_tpu.parallel.ring_attention import ring_self_attention
    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    b, n, h, d = 2, 4 * p, 2, 8
    rs = np.random.RandomState(5)
    q = rs.randn(b, n, h, d).astype(np.float32)
    k = rs.randn(b, n, h, d).astype(np.float32)
    v = rs.randn(b, n, h, d).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:p]), ("sp",))

    def grads(bk):
        def loss(q, k, v):
            out = ring_self_attention(
                q, k, v, axis="sp", causal=True, backend=bk
            )
            return (out * out).sum()

        return jax.jit(jax.grad(partial(
            jax.shard_map, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(), check_vma=False,
        )(lambda q, k, v: jax.lax.psum(loss(q, k, v), "sp")),
            argnums=(0, 1, 2)))(q, k, v)

    for a, g in zip(grads("xla"), grads(backend)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(a), rtol=2e-4, atol=2e-4
        )
