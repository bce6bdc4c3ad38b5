"""Block-quantized wire format (PR 2): correctness across backends and
dtypes, routing/engagement rules, tracing byte accounting, autotune
persistence, selector dump, and the satellite regressions that ride
along (PS transport poison ordering + shared pool, bidirectional causal
ring-attention skip, bench stdout hygiene).

Error metric: quantization error is bounded RELATIVE TO THE PAYLOAD
SCALE, so assertions normalize by ``max|ref|`` — per-element relative
error is unbounded near sign cancellations of the sum and would test
the data, not the wire format.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import torchmpi_tpu as mpi
from torchmpi_tpu import constants
from torchmpi_tpu.collectives import primitives as prim

INTERPRET = True

P_SWEEP = [2, 3,
           pytest.param(4, marks=pytest.mark.slow),
           pytest.param(8, marks=pytest.mark.slow)]


def _mesh(p):
    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    return Mesh(np.array(jax.devices()[:p]), ("mpi",))


def _norm_err(out, ref):
    return np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-12)


def _engage_all():
    """Drop the min-elements cutoff so small test payloads engage."""
    constants.set("wire_quant_min_elements", 1)


# ---------------------------------------------------------------------------
# quantization helpers
# ---------------------------------------------------------------------------


def test_quantize_roundtrip_bounds():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1000).astype(np.float32))
    q, scale, n = prim.quantize_blocks(x, 128)
    assert q.dtype == jnp.int8 and n == 1000
    back = np.asarray(prim.dequantize_blocks(q, scale, n))
    # one quantization event: error <= scale/2 per block
    per_block_bound = np.asarray(scale).repeat(128)[:n] / 2 + 1e-7
    assert (np.abs(back - np.asarray(x)) <= per_block_bound).all()


def test_quantize_constant_blocks_exact():
    x = jnp.full((512,), 3.25, jnp.float32)
    q, scale, n = prim.quantize_blocks(x, 128)
    back = np.asarray(prim.dequantize_blocks(q, scale, n))
    np.testing.assert_allclose(back, 3.25, rtol=1e-6)


def test_quantize_zero_blocks_exact():
    q, scale, n = prim.quantize_blocks(jnp.zeros(256, jnp.float32), 128)
    assert np.asarray(prim.dequantize_blocks(q, scale, n)).max() == 0.0


def test_wire_encoded_bytes_model():
    # 2^18 f32 elements: int8 = payload + 1/128 scales -> ~3.88x
    n = 1 << 18
    full = prim.wire_encoded_bytes(n, 4, "full", 128)
    int8 = prim.wire_encoded_bytes(n, 4, "int8", 128)
    bf16 = prim.wire_encoded_bytes(n, 4, "bf16", 128)
    assert full == 4 * n and bf16 == 2 * n
    assert full / int8 >= 3.0


# ---------------------------------------------------------------------------
# ppermute ring (the CPU/interpret mirror of the pallas kernels)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", P_SWEEP)
@pytest.mark.parametrize("wire", ["int8", "bf16"])
@pytest.mark.parametrize("n", [1024, 999])  # odd size: pad/unpad path
def test_ppermute_wire_allreduce(p, wire, n):
    mesh = _mesh(p)
    _engage_all()
    rng = np.random.RandomState(p * 7 + n)
    x = rng.randn(p, n).astype(np.float32)
    f = jax.jit(jax.shard_map(
        lambda b: prim.ring_allreduce(b, "mpi", axis_size=p, wire_dtype=wire),
        mesh=mesh, in_specs=P("mpi"), out_specs=P("mpi"), check_vma=False,
    ))
    out = np.asarray(f(jnp.asarray(x)))
    tol = 1e-2 if p <= 4 else 2e-2  # error accumulates over p-1 requants
    assert _norm_err(out, x.sum(0)) <= tol


@pytest.mark.parametrize("wire", ["int8", "bf16"])
def test_ppermute_wire_reduce_scatter(wire):
    p = 4
    mesh = _mesh(p)
    _engage_all()
    rng = np.random.RandomState(3)
    d = p * 96
    x = rng.randn(p, d).astype(np.float32)
    f = jax.jit(jax.shard_map(
        lambda b: prim.ring_reduce_scatter(
            b, "mpi", dim=-1, axis_size=p, wire_dtype=wire
        ),
        mesh=mesh, in_specs=P("mpi"), out_specs=P("mpi"), check_vma=False,
    ))
    out = np.asarray(f(jnp.asarray(x)))  # [p, d/p]: rank r = slice r of sum
    ref = x.sum(0).reshape(p, d // p)
    assert _norm_err(out, ref) <= 1e-2


def test_wire_int_dtype_passes_through_exact():
    """Integer payloads bypass compression entirely — bit-exact sums."""
    p = 4
    mesh = _mesh(p)
    _engage_all()
    x = (np.arange(p * 1024, dtype=np.int32).reshape(p, 1024) * 7919) % (
        1 << 20
    )
    f = jax.jit(jax.shard_map(
        lambda b: prim.ring_allreduce(
            b, "mpi", axis_size=p, wire_dtype="int8"
        ),
        mesh=mesh, in_specs=P("mpi"), out_specs=P("mpi"), check_vma=False,
    ))
    out = np.asarray(f(jnp.asarray(x)))  # rank-stacked: every row = sum
    np.testing.assert_array_equal(out, np.broadcast_to(x.sum(0), out.shape))


def test_wire_below_cutoff_is_exact():
    """Below wire_quant_min_elements the encoding must not engage: f32
    results equal the uncompressed ring bit-for-bit."""
    p = 2
    mesh = _mesh(p)
    constants.set("wire_quant_min_elements", 1 << 20)
    rng = np.random.RandomState(11)
    x = rng.randn(p, 256).astype(np.float32)

    def run(wire):
        f = jax.jit(jax.shard_map(
            lambda b: prim.ring_allreduce(
                b, "mpi", axis_size=p, wire_dtype=wire
            ),
            mesh=mesh, in_specs=P("mpi"), out_specs=P("mpi"),
            check_vma=False,
        ))
        return np.asarray(f(jnp.asarray(x)))

    np.testing.assert_array_equal(run("int8"), run(None))


# ---------------------------------------------------------------------------
# pallas quantized kernels (interpret mode; compiled in chip_smoke.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", P_SWEEP)
@pytest.mark.parametrize("wire", ["int8", "bf16"])
@pytest.mark.parametrize("n", [4096, 5000])  # tile-even and ragged
def test_pallas_quant_allreduce_interpret(p, wire, n):
    from torchmpi_tpu.ops.ring_kernels import ring_allreduce_pallas

    mesh = _mesh(p)
    _engage_all()
    rng = np.random.RandomState(p * 13 + n)
    x = rng.randn(p, n).astype(np.float32)
    f = jax.jit(jax.shard_map(
        lambda b: ring_allreduce_pallas(
            b, "mpi", axis_size=p, interpret=INTERPRET, wire_dtype=wire
        ),
        mesh=mesh, in_specs=P("mpi"), out_specs=P("mpi"), check_vma=False,
    ))
    out = np.asarray(f(jnp.asarray(x)))
    tol = 1e-2 if p <= 4 else 2e-2
    assert _norm_err(out, x.sum(0)) <= tol


@pytest.mark.parametrize("wire", ["int8", "bf16"])
def test_pallas_quant_reduce_scatter_interpret(wire):
    from torchmpi_tpu.ops.ring_kernels import ring_reduce_scatter_pallas

    p = 4
    mesh = _mesh(p)
    _engage_all()
    rng = np.random.RandomState(5)
    seg = 600  # ragged: not a multiple of 128 lanes
    x = rng.randn(p, p * seg).astype(np.float32)
    f = jax.jit(jax.shard_map(
        lambda b: ring_reduce_scatter_pallas(
            b[0].reshape(p, seg), "mpi", axis_size=p,
            interpret=INTERPRET, wire_dtype=wire,
        )[None],
        mesh=mesh, in_specs=P("mpi"), out_specs=P("mpi"), check_vma=False,
    ))
    out = np.asarray(f(jnp.asarray(x.reshape(p, 1, p * seg))))
    ref = x.reshape(p, p, seg).sum(0)
    assert _norm_err(out.reshape(p, seg), ref) <= 1e-2


def test_pallas_quant_matches_ppermute_semantics():
    """Both backends implement the same algorithm (per-128-block scales,
    f32 accumulate): when their chunk geometry coincides (per-rank chunk
    = exactly one pallas 128x128 tile group) the results must agree to
    the fp-rounding level, not just the quantization level."""
    from torchmpi_tpu.ops.ring_kernels import ring_allreduce_pallas

    p = 4
    mesh = _mesh(p)
    _engage_all()
    rng = np.random.RandomState(17)
    n = p * 128 * 128  # per-rank chunk == one [128, 128] pallas tile
    x = rng.randn(p, n).astype(np.float32)

    def run(fn):
        f = jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=P("mpi"), out_specs=P("mpi"),
            check_vma=False,
        ))
        return np.asarray(f(jnp.asarray(x)))

    a = run(lambda b: ring_allreduce_pallas(
        b, "mpi", axis_size=p, interpret=INTERPRET, wire_dtype="int8"))
    b = run(lambda b: prim.ring_allreduce(
        b, "mpi", axis_size=p, wire_dtype="int8"))
    assert _norm_err(a, b) <= 1e-6


# ---------------------------------------------------------------------------
# eager routing + tracing counters
# ---------------------------------------------------------------------------


def test_eager_wire_dtype_end_to_end_and_tracing():
    """The acceptance path: eager int8 allreduce above the cutoff on the
    ring backend — result within the normalized error bound, tracing
    reports >= 3x on-wire byte reduction."""
    from torchmpi_tpu.utils.tracing import wire_stats

    mpi.start()
    try:
        p = mpi.size()
        n = 1 << 17  # above the default 2^16 cutoff
        rng = np.random.RandomState(23)
        x = rng.randn(p, n).astype(np.float32)
        ref = np.asarray(mpi.ring.allreduce_tensor(jnp.asarray(x)))
        wire_stats.reset()
        out = np.asarray(
            mpi.ring.allreduce_tensor(jnp.asarray(x), wire_dtype="int8")
        )
        assert _norm_err(out, ref) <= (1e-2 if p <= 4 else 2e-2)
        snap = wire_stats.snapshot()
        assert snap["calls"] == 1
        assert snap["compression_ratio"] >= 3.0
        assert any(k.startswith("allreduce:int8") for k in snap["by_format"])
    finally:
        mpi.stop()


def test_eager_wire_dtype_cache_key_distinct():
    """Toggling wire_dtype must compile distinct executables (the wire
    format participates in the cache key)."""
    mpi.start()
    try:
        comm = mpi.current_communicator()
        p = comm.size
        n = 1 << 17
        x = jnp.ones((p, n), jnp.float32)
        mpi.ring.allreduce_tensor(x)
        mpi.ring.allreduce_tensor(x, wire_dtype="int8")
        mpi.ring.allreduce_tensor(x, wire_dtype="bf16")
        cache = comm._collective_resources

        def wire_tags(obj, out):
            if isinstance(obj, tuple):
                if obj and obj[0] in ("full", "int8", "bf16"):
                    out.add(obj[0])
                for part in obj:
                    wire_tags(part, out)

        wire_keys = set()
        for k in cache:
            if isinstance(k, tuple) and k and k[0] in (
                "allreduce", "hier_allreduce"
            ):
                wire_tags(k, wire_keys)
        assert {"full", "int8", "bf16"} <= wire_keys
    finally:
        mpi.stop()


def test_resolve_wire_dtype_rules():
    from torchmpi_tpu.collectives.eager import resolve_wire_dtype

    cutoff = constants.get("wire_quant_min_elements")
    assert resolve_wire_dtype("allreduce", cutoff, jnp.float32, "int8") == "int8"
    assert resolve_wire_dtype("allreduce", cutoff - 1, jnp.float32, "int8") == "full"
    assert resolve_wire_dtype("allreduce", cutoff, jnp.int32, "int8") == "full"
    assert resolve_wire_dtype("broadcast", cutoff, jnp.float32, "int8") == "full"
    assert resolve_wire_dtype("allreduce", cutoff, jnp.float32, None) == "full"
    constants.set("wire_dtype", "bf16")
    assert resolve_wire_dtype("allreduce", cutoff, jnp.float32, None) == "bf16"
    with pytest.raises(Exception):
        resolve_wire_dtype("allreduce", cutoff, jnp.float32, "fp4")


def test_selector_dump_lists_wire_formats():
    from torchmpi_tpu.collectives.selector import (
        selector,
        wire_format_availability,
    )

    avail = wire_format_availability()
    assert avail["full"] and avail["int8"] and avail["bf16"]
    dump = mpi.collective_availability()
    assert "Wire formats" in dump and "int8" in dump and "bf16" in dump
    # per-collective routing lines reflect the constants default
    assert "wire.allreduce: -> full" in dump
    constants.set("wire_dtype", "int8")
    assert selector.select_wire("allreduce") == "int8"
    assert selector.select_wire("broadcast") == "full"  # not a wire op
    assert "wire.allreduce: -> int8" in mpi.collective_availability()


# ---------------------------------------------------------------------------
# nn / engine surface
# ---------------------------------------------------------------------------


def test_synchronize_gradients_wire_dtype():
    """wire_dtype threads through the eager nn sync (engaging only when
    the selector routes a ring backend) and through GradientBuckets with
    a pinned ring backend (where it MUST engage — asserted via the
    tracing counters, not just the value bound)."""
    from torchmpi_tpu.nn import GradientBuckets
    from torchmpi_tpu.utils.tracing import wire_stats

    mpi.start()
    try:
        _engage_all()
        p = mpi.size()
        rng = np.random.RandomState(31)
        grads = {
            "w": jnp.asarray(rng.randn(p, 300, 7).astype(np.float32)),
            "steps": jnp.ones((p, 4), jnp.int32),  # int leaf: exact
        }
        ref = mpi.nn.synchronize_gradients(grads)
        out = mpi.nn.synchronize_gradients(grads, wire_dtype="int8")
        assert _norm_err(np.asarray(out["w"]), np.asarray(ref["w"])) <= 1e-2
        np.testing.assert_array_equal(
            np.asarray(out["steps"]), np.asarray(ref["steps"])
        )
        # bucketed async with the ring backend pinned: engagement is
        # observable in the wire counters. Drop the small-message reroute
        # too — op_route would otherwise bounce this test-sized payload
        # to the fused XLA path before the wire decision.
        constants.set("small_allreduce_size_cpu", 1)
        template = {k: v[0] for k, v in grads.items()}
        buckets = GradientBuckets(template, 2)
        wire_stats.reset()
        handles = buckets.allreduce_async(
            grads, backend="ring", wire_dtype="int8"
        )
        synced = buckets.wait_and_unflatten(grads, handles)
        snap = wire_stats.snapshot()
        assert any(k.startswith("allreduce:int8") for k in snap["by_format"])
        assert _norm_err(
            np.asarray(synced["w"]), np.asarray(ref["w"])
        ) <= 2e-2
    finally:
        mpi.stop()


def test_engine_wire_dtype_trains():
    """An engine configured with wire_dtype='int8' must still train (loss
    decreases) — the compressed gradient sync is a drop-in."""
    import optax

    mpi.start()
    try:
        _engage_all()
        from torchmpi_tpu.engine import AllReduceSGDEngine

        rng = np.random.RandomState(5)
        w_true = rng.randn(32).astype(np.float32)
        xs = rng.randn(256, 32).astype(np.float32)
        ys = (xs @ w_true).astype(np.float32)

        def loss_fn(params, batch):
            x, y = batch
            pred = x @ params["w"]
            return jnp.mean((pred - y) ** 2)

        engine = AllReduceSGDEngine(
            loss_fn,
            {"w": jnp.zeros(32, jnp.float32)},
            optimizer=optax.sgd(0.1),
            wire_dtype="int8",
        )
        first = last = None
        for i in range(0, 256, 64):
            batch = (jnp.asarray(xs[i:i + 64]), jnp.asarray(ys[i:i + 64]))
            last = float(engine.step(batch))
            if first is None:
                first = last
        assert last < first
    finally:
        mpi.stop()


def test_engine_wire_dtype_validation():
    import optax

    mpi.start()
    try:
        from torchmpi_tpu.engine import AllReduceSGDEngine

        loss = lambda p, b: jnp.sum(p["w"] ** 2)  # noqa: E731
        with pytest.raises(ValueError):
            AllReduceSGDEngine(
                loss, {"w": jnp.zeros(4)}, optimizer=optax.sgd(0.1),
                wire_dtype="fp4",
            )
        with pytest.raises(ValueError):
            AllReduceSGDEngine(
                loss, {"w": jnp.zeros((8, 8))}, optimizer=optax.sgd(0.1),
                wire_dtype="int8", param_sharding="fsdp",
            )
    finally:
        mpi.stop()


def test_tree_hierarchical_allreduce_honors_wire():
    """A non-cartesian (ragged/tree) communicator must not silently drop
    the wire format (review finding): every binomial exchange hop ships
    the encoding, and results stay within the quantization bound."""
    from torchmpi_tpu.collectives.eager import run_tree_hierarchical_allreduce

    mpi.start()
    try:
        if mpi.size() < 4:
            pytest.skip("needs >= 4 ranks for ragged groups")
        constants.set("use_cartesian_communicator", False)
        mpi.push_communicator(
            lambda r: "a" if r < 3 else "b", name="ragged-wire"
        )
        comm = mpi.current_communicator()
        assert not comm.cartesian
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(comm.size, 4096).astype(np.float32))
        ref = np.asarray(x).sum(0)
        out = np.asarray(
            run_tree_hierarchical_allreduce(x, comm, wire="int8")
        )
        err = _norm_err(out, np.broadcast_to(ref, out.shape))
        assert 0 < err <= 1e-2  # engaged (not bit-exact) AND bounded
    finally:
        mpi.stop()


# ---------------------------------------------------------------------------
# autotune persistence
# ---------------------------------------------------------------------------


def test_tune_wire_dtype_measures_all_formats(tmp_path, monkeypatch):
    monkeypatch.setenv(
        "TORCHMPI_TPU_TUNING_CACHE", str(tmp_path / "autotune.json")
    )
    mpi.start()
    try:
        from torchmpi_tpu.utils import autotune

        winner, results = autotune.tune_wire_dtype(
            nelem=1 << 16, warmup=0, timed=1, apply=True
        )
        assert winner in ("full", "bf16", "int8")
        assert [w for w, _ in results] == ["full", "bf16", "int8"]
        assert constants.get("wire_dtype") == winner
    finally:
        mpi.stop()


def test_wire_dtype_persists_and_start_reapplies(tmp_path, monkeypatch):
    """The persisted wire_dtype decision per (platform, world size) must
    survive a stop/start cycle: start() re-applies it."""
    monkeypatch.setenv(
        "TORCHMPI_TPU_TUNING_CACHE", str(tmp_path / "autotune.json")
    )
    mpi.start()
    try:
        from torchmpi_tpu.utils import autotune

        constants.set("wire_dtype", "int8")
        path = autotune.save_tuning()
        assert path.exists()
        entry = autotune.load_tuning(apply=False)
        assert entry["wire_dtype"] == "int8"
    finally:
        mpi.stop()
    constants.set("wire_dtype", "full")
    mpi.start()  # load_tuned_constants=True re-applies the cache entry
    try:
        assert constants.get("wire_dtype") == "int8"
    finally:
        mpi.stop()


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------


def test_transport_failed_single_update_replay_gets_error():
    """ADVICE r5: a replayed FAILED single-UPDATE seq must be re-answered
    with ERROR from the poison record — never a false ACK from the
    (later-advanced) _applied high-water mark."""
    import socket
    import threading

    from torchmpi_tpu.parameterserver import transport as T

    applies = []

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            def run():
                if float(np.asarray(msg.payload)[0]) < 0:
                    msg.error = "negative payloads explode"
                else:
                    applies.append(rank)
                msg.done.set()

            threading.Thread(target=run, daemon=True).start()

    lst = T._Listener(lambda i: FakeInst())
    try:
        s = socket.create_connection(("localhost", lst.port), timeout=10)
        s.settimeout(10)
        bad = (-np.ones(4, np.float32))
        good = np.ones(4, np.float32)
        # seq 5 fails; seq 6 succeeds and advances the high-water mark
        T._send_frame(
            s, T._KIND_UPDATE, inst=1, rank=0, client=0, seq=5, rule="add",
            dtype=bad.dtype.str, payload=bad.tobytes(),
        )
        assert T._recv_frame(s)[0] == T._KIND_ERROR
        T._send_frame(
            s, T._KIND_UPDATE, inst=1, rank=0, client=0, seq=6, rule="add",
            dtype=good.dtype.str, payload=good.tobytes(),
        )
        assert T._recv_frame(s)[0] == T._KIND_ACK
        # replay of the failed seq 5 (reconnect after a lost ERROR):
        # must be ERROR again (answered from the poison record), and must
        # not re-run the apply
        n_applies = len(applies)
        T._send_frame(
            s, T._KIND_UPDATE, inst=1, rank=0, client=0, seq=5, rule="add",
            dtype=bad.dtype.str, payload=bad.tobytes(),
        )
        frame = T._recv_frame(s)
        assert frame[0] == T._KIND_ERROR
        assert "explode" in frame[6]  # the recorded failure, verbatim
        assert len(applies) == n_applies
        s.close()
    finally:
        lst.close()


def test_transport_shared_pool_across_connections():
    """The apply/reply pool is listener-wide: reconnect churn must not
    grow a per-connection pool population."""
    import socket

    from torchmpi_tpu.parameterserver import transport as T

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            msg.done.set()

    lst = T._Listener(lambda i: FakeInst())
    try:
        assert hasattr(lst, "_pool")
        payload = np.ones(2, np.float32)
        for seq in range(1, 6):  # 5 sequential connections (churn)
            s = socket.create_connection(("localhost", lst.port), timeout=10)
            s.settimeout(10)
            T._send_frame(
                s, T._KIND_UPDATE, inst=1, rank=0, client=0, seq=seq,
                rule="add", dtype=payload.dtype.str,
                payload=payload.tobytes(),
            )
            assert T._recv_frame(s)[0] == T._KIND_ACK
            s.close()
        # the shared pool's thread count stays bounded by its max_workers
        assert len(lst._pool._threads) <= lst._pool._max_workers
    finally:
        lst.close()


@pytest.mark.parametrize("p", [3, 4])
def test_bidir_ring_attention_causal_skip_exact(p):
    """The causal L-chain skip must not change results: bidir == uni ==
    full attention on the gathered sequence."""
    from torchmpi_tpu.ops.ring_attention_kernel import (
        _full_attention_with_lse,
        ring_attention_bidir_pallas,
        ring_attention_pallas,
    )

    if len(jax.devices()) < p:
        pytest.skip(f"needs {p} devices")
    mesh = Mesh(np.array(jax.devices()[:p]), ("sp",))
    rng = np.random.RandomState(41 + p)
    b, n, h, d = 1, 16, 2, 8
    q = rng.randn(p, b, n, h, d).astype(np.float32)
    k = rng.randn(p, b, n, h, d).astype(np.float32)
    v = rng.randn(p, b, n, h, d).astype(np.float32)

    def run(fn):
        f = jax.jit(jax.shard_map(
            lambda qq, kk, vv: fn(
                qq[0], kk[0], vv[0], "sp", causal=True, axis_size=p,
                interpret=INTERPRET,
            )[None],
            mesh=mesh, in_specs=(P("sp"), P("sp"), P("sp")),
            out_specs=P("sp"), check_vma=False,
        ))
        return np.asarray(f(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))

    out_bidir = run(ring_attention_bidir_pallas)
    out_uni = run(ring_attention_pallas)
    np.testing.assert_allclose(out_bidir, out_uni, atol=2e-5, rtol=2e-5)
    # and against the gathered-sequence reference
    qg = np.concatenate([q[i] for i in range(p)], axis=1)
    kg = np.concatenate([k[i] for i in range(p)], axis=1)
    vg = np.concatenate([v[i] for i in range(p)], axis=1)
    ref, _ = _full_attention_with_lse(
        jnp.asarray(qg), jnp.asarray(kg), jnp.asarray(vg), True
    )
    ref = np.asarray(ref).reshape(p, b, n, h, d)
    np.testing.assert_allclose(out_bidir, ref, atol=2e-4, rtol=2e-4)
