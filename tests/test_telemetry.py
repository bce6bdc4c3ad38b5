"""Unified telemetry subsystem: metrics registry, spans/trace export, and
the instrumented hot paths (collectives, engine, PS transport, autotuner).

Acceptance contract (ISSUE 3):
- the exported trace validates as Chrome ``trace_event`` JSON
  (``json.load`` + required ``ph``/``ts``/``name`` keys per event);
- a metrics snapshot taken after an eager allreduce + one engine step +
  one PS update contains nonzero collective, engine, and transport series;
- the disabled path adds no measurable per-call allocation (span object
  reuse).
"""

import json
import threading
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchmpi_tpu as mpi
from torchmpi_tpu import telemetry


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Each test starts from empty series and leaves telemetry disabled
    (so unrelated test files never pay the enabled hot paths)."""
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram_with_labels():
    m = telemetry.metrics
    c = m.counter("tm_t_requests_total", "test counter")
    c.inc(op="a")
    c.inc(2, op="a")
    c.inc(op="b")
    assert c.value(op="a") == 3 and c.value(op="b") == 1
    assert c.total() == 4

    g = m.gauge("tm_t_depth")
    g.set(7, queue="x")
    assert g.value(queue="x") == 7
    g.set(9, queue="x")
    assert g.value(queue="x") == 9

    h = m.histogram("tm_t_latency_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v, kind="u")
    assert h.count(kind="u") == 4
    snap = m.snapshot()["tm_t_latency_seconds"]["series"]["kind=u"]
    assert snap["count"] == 4
    assert snap["buckets"]["0.01"] == 1 and snap["buckets"]["+Inf"] == 1
    assert abs(snap["sum"] - 5.555) < 1e-9

    # same name with a different type must fail loudly
    with pytest.raises(TypeError):
        m.gauge("tm_t_requests_total")


def test_registry_prometheus_text_format():
    m = telemetry.metrics
    m.counter("tm_t_prom_total", "things").inc(3, op="x")
    m.histogram("tm_t_prom_seconds", buckets=(0.1, 1.0)).observe(0.5)
    text = telemetry.prometheus_text()
    assert "# TYPE tm_t_prom_total counter" in text
    assert 'tm_t_prom_total{op="x"} 3' in text
    assert "# TYPE tm_t_prom_seconds histogram" in text
    assert 'tm_t_prom_seconds_bucket{le="1.0"} 1' in text
    assert 'tm_t_prom_seconds_bucket{le="+Inf"} 1' in text
    assert "tm_t_prom_seconds_count 1" in text


def test_snapshot_carries_wire_stats_collector():
    from torchmpi_tpu.utils.tracing import wire_stats

    wire_stats.reset()
    wire_stats.record("allreduce", "int8", 1000, 300)
    try:
        ws = telemetry.snapshot()["metrics"]["wire_stats"]
        assert ws["calls"] == 1 and ws["wire_bytes"] == 300
        assert ws["compression_ratio"] == pytest.approx(1000 / 300)
    finally:
        wire_stats.reset()


def test_reset_clears_series_but_keeps_metric_objects():
    c = telemetry.metrics.counter("tm_t_reset_total")
    c.inc(5)
    telemetry.reset()
    assert c.value() == 0
    c.inc()  # the object instrumented modules hold stays usable
    assert c.value() == 1


# ---------------------------------------------------------------------------
# spans + trace export
# ---------------------------------------------------------------------------


def test_trace_export_is_chrome_trace_json(tmp_path):
    telemetry.enable()
    with telemetry.span("unit.work", op="allreduce", nelem=64):
        pass
    with telemetry.span("unit.other"):
        pass
    paths = telemetry.dump(tmp_path / "snap.json")
    # snapshot half
    snap = json.load(open(paths[0]))
    assert snap["enabled"] is True and snap["spans"]["recorded"] == 2
    # trace half: the acceptance validation — every event has ph/ts/name,
    # complete events also carry a duration
    trace = json.load(open(paths[1]))
    events = trace["traceEvents"]
    assert len(events) >= 3  # metadata + the two spans
    for ev in events:
        assert "ph" in ev and "ts" in ev and "name" in ev
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"unit.work", "unit.other"}
    for e in xs:
        assert e["dur"] >= 0 and "pid" in e and "tid" in e
    attrs = next(e for e in xs if e["name"] == "unit.work")["args"]
    assert attrs == {"op": "allreduce", "nelem": 64,
                     "span_id": attrs["span_id"]}
    # spans are on the wall clock: the clock of a device trace's origin
    assert abs(xs[0]["ts"] * 1e-6 - snap["time"]) < 60


def test_span_ring_buffer_is_bounded():
    rec = telemetry.spans
    telemetry.enable()
    for i in range(rec.capacity + 10):
        rec.record(f"s{i}", 0.0, 1.0, None)
    assert len(rec) == rec.capacity
    assert rec.total_recorded == rec.capacity + 10


def test_disabled_span_is_reused_and_allocation_free():
    """Tier-1 guard for the disabled hot path: span() hands back ONE
    shared no-op object (no per-call span allocation), and a loop of
    disabled spans retains no memory."""
    telemetry.disable()
    assert telemetry.span("a") is telemetry.span("b")
    tracemalloc.start()
    try:
        with telemetry.span("warmup"):
            pass
        base = tracemalloc.get_traced_memory()[0]
        for _ in range(2000):
            with telemetry.span("noop"):
                pass
        grown = tracemalloc.get_traced_memory()[0] - base
    finally:
        tracemalloc.stop()
    assert grown < 512, f"disabled span path retained {grown} bytes"


# ---------------------------------------------------------------------------
# end-to-end acceptance: collective + engine + transport series
# ---------------------------------------------------------------------------


def test_end_to_end_nonzero_series_and_valid_trace(tmp_path):
    import optax

    from torchmpi_tpu.engine import AllReduceSGDEngine
    from torchmpi_tpu.parameterserver import ParameterServer, free_all
    from torchmpi_tpu.parameterserver import transport as pst

    telemetry.enable()
    mpi.start()
    try:
        p = mpi.size()

        # 1. eager allreduce (above the wire cutoff, ring backend)
        x = jnp.ones((p, 1 << 17), jnp.float32)
        mpi.ring.allreduce_tensor(x)
        mpi.ring.allreduce_tensor(x)  # second call = executable cache hit

        # 2. one engine epoch through train(): a telemetry-enabled engine
        # runs the same program and never blocks per step, so the rate
        # gauges are set where it waits anyway, at the epoch's end
        rng = np.random.RandomState(0)
        w = rng.randn(8).astype(np.float32)

        def loss_fn(params, batch):
            xb, yb = batch
            return jnp.mean((xb @ params - yb) ** 2)

        engine = AllReduceSGDEngine(
            loss_fn, jnp.zeros(8), optimizer=optax.sgd(0.1),
            flops_per_sample=2 * 8,
        )
        xb = rng.randn(2 * p, 8).astype(np.float32)
        engine.train(
            lambda: iter([(jnp.asarray(xb), jnp.asarray(xb @ w))]),
            max_epochs=1,
        )

        # 3. one PS update over the REAL socket transport (loopback)
        ps = ParameterServer(np.zeros(64, np.float32))
        tr = pst.ensure_transport()
        inst = ps._inst
        s, e = inst.ranges[0]
        tr.update(
            0, inst.id, 0, 0, "add", np.ones(e - s, np.float32),
            fp=inst.fingerprint,
        )
        np.testing.assert_array_equal(
            np.asarray(ps.receive().wait()).reshape(-1)[s:e], 1.0
        )

        m = telemetry.snapshot()["metrics"]
        # collective series
        calls = m["tm_collective_calls_total"]["series"]
        assert calls.get("backend=ring,op=allreduce,wire=full", 0) >= 2
        assert m["tm_collective_cache_hits_total"]["series"].get(
            "backend=ring,op=allreduce", 0
        ) >= 1
        assert m["tm_collective_compiles_total"]["series"].get(
            "backend=ring,op=allreduce", 0
        ) >= 1
        assert sum(
            s["count"]
            for s in m["tm_collective_dispatch_seconds"]["series"].values()
        ) >= 2
        # engine series
        assert sum(m["tm_engine_steps_total"]["series"].values()) >= 1
        assert m["tm_engine_epoch_seconds"]["series"][""]["count"] == 1
        assert "tm_engine_grad_norm" not in m  # needed a second program
        assert m["tm_engine_examples_per_sec"]["series"][""] > 0
        assert m["tm_engine_tflops_per_chip"]["series"][""] > 0
        # transport series
        assert m["tm_ps_requests_total"]["series"].get("kind=update", 0) >= 1
        lat = m["tm_ps_rpc_latency_seconds"]["series"]["kind=update"]
        assert lat["count"] >= 1 and lat["sum"] > 0
        listener = m["ps_listener"]
        assert listener["alive"] is True
        assert listener["queue_depth"] is not None

        # the trace written from this run validates per the acceptance
        paths = telemetry.dump(tmp_path / "e2e.json")
        events = json.load(open(paths[1]))["traceEvents"]
        names = {e["name"] for e in events}
        assert "collective.allreduce" in names and "engine.dispatch" in names
        for ev in events:
            assert "ph" in ev and "ts" in ev and "name" in ev
        # prometheus rendering of the same registry stays well-formed
        text = telemetry.prometheus_text()
        assert "tm_collective_calls_total{" in text
        assert "tm_ps_rpc_latency_seconds_bucket{" in text
    finally:
        pst.shutdown_transport()
        free_all()
        mpi.stop()


# ---------------------------------------------------------------------------
# satellite: hierarchical compositions feed the wire counters
# ---------------------------------------------------------------------------


def test_hierarchical_allreduce_records_wire_bytes():
    """Direct run_hierarchical_allreduce calls (and run()-routed ones)
    must feed wire_stats so compression_ratio() stays honest — the old
    accounting only saw flat-ring dispatches."""
    from torchmpi_tpu.collectives.eager import run_hierarchical_allreduce
    from torchmpi_tpu.utils.tracing import wire_stats

    mpi.start()
    if mpi.size() < 4:
        pytest.skip("needs >= 4 ranks for a 2-level topology")
    mpi.push_communicator(lambda r: str(r % 2), name="tele-h")
    comm = mpi.current_communicator()
    assert comm.cartesian
    x = jnp.asarray(
        np.random.RandomState(0).randn(comm.size, 1 << 14).astype(np.float32)
    )
    wire_stats.reset()
    run_hierarchical_allreduce(x, comm, impl="ring", wire="int8")
    snap = wire_stats.snapshot()
    assert snap["calls"] == 1
    assert any(k.startswith("allreduce:int8") for k in snap["by_format"])
    assert snap["compression_ratio"] > 3.0

    # the staged (host-hop) variant records too
    wire_stats.reset()
    run_hierarchical_allreduce(
        x, comm, impl="staged", staged_intra="ring", wire="int8"
    )
    assert wire_stats.snapshot()["calls"] == 1
    wire_stats.reset()


def test_tree_hierarchical_allreduce_records_wire_bytes():
    from torchmpi_tpu import constants
    from torchmpi_tpu.collectives.eager import run_tree_hierarchical_allreduce
    from torchmpi_tpu.utils.tracing import wire_stats

    constants.set("use_cartesian_communicator", False)
    mpi.start()
    if mpi.size() < 4:
        pytest.skip("needs >= 4 ranks for ragged groups")
    mpi.push_communicator(
        lambda r: "a" if r == 0 else "b", name="tele-tree"
    )
    comm = mpi.current_communicator()
    assert not comm.cartesian
    x = jnp.ones((comm.size, 4096), jnp.float32)
    wire_stats.reset()
    run_tree_hierarchical_allreduce(x, comm, wire="int8")
    snap = wire_stats.snapshot()
    assert snap["calls"] == 1
    assert any(k.startswith("allreduce:int8") for k in snap["by_format"])
    wire_stats.reset()


def test_routed_hierarchical_dispatch_records_once():
    """An eager call that run() routes to the hierarchical composition
    must count exactly ONE wire dispatch (no double accounting between
    run() and the composition it delegates to)."""
    from torchmpi_tpu import constants
    from torchmpi_tpu.utils.tracing import wire_stats

    mpi.start()
    if mpi.size() < 4:
        pytest.skip("needs >= 4 ranks for a 2-level topology")
    mpi.push_communicator(lambda r: str(r % 2), name="tele-route")
    constants.set("small_allreduce_size_cpu", 1)
    x = jnp.ones((mpi.size(), 2048), jnp.float32)
    wire_stats.reset()
    mpi.ring.allreduce_tensor(x)
    snap = wire_stats.snapshot()
    assert snap["calls"] == 1
    wire_stats.reset()


# ---------------------------------------------------------------------------
# satellite: WireByteCounters thread safety + snapshot/reset round-trip
# ---------------------------------------------------------------------------


def test_wire_counters_concurrent_records():
    from torchmpi_tpu.utils.tracing import WireByteCounters

    wc = WireByteCounters()
    n_threads, per_thread = 8, 500

    def pound(i):
        fmt = "int8" if i % 2 else "bf16"
        for _ in range(per_thread):
            wc.record("allreduce", fmt, 100, 30)

    threads = [
        threading.Thread(target=pound, args=(i,)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per_thread
    assert wc.calls == total
    assert wc.logical_bytes == 100 * total
    assert wc.wire_bytes == 30 * total
    half = total // 2
    assert wc.by_format[("allreduce", "int8")] == [half, 100 * half, 30 * half]
    assert wc.by_format[("allreduce", "bf16")] == [half, 100 * half, 30 * half]
    assert wc.compression_ratio() == pytest.approx(100 / 30)


def test_wire_counters_snapshot_reset_roundtrip():
    from torchmpi_tpu.utils.tracing import WireByteCounters

    wc = WireByteCounters()
    wc.record("allreduce", "int8", 1024, 300)
    wc.record("reducescatter", "bf16", 512, 256)
    snap = wc.snapshot()
    assert snap["calls"] == 2
    assert snap["logical_bytes"] == 1536 and snap["wire_bytes"] == 556
    assert snap["by_format"]["allreduce:int8"] == (1, 1024, 300)
    assert snap["by_format"]["reducescatter:bf16"] == (1, 512, 256)
    assert snap["compression_ratio"] == pytest.approx(1536 / 556)
    wc.reset()
    empty = wc.snapshot()
    assert empty["calls"] == 0 and empty["by_format"] == {}
    assert empty["compression_ratio"] == 1.0 and wc.compression_ratio() == 1.0
    # counters keep working after reset
    wc.record("allreduce", "full", 64, 64)
    assert wc.snapshot()["calls"] == 1


# ---------------------------------------------------------------------------
# satellite: ProfilerWindow bounds + engine close-on-exit
# ---------------------------------------------------------------------------


def test_profiler_window_validates_bounds(tmp_path):
    from torchmpi_tpu.utils.tracing import ProfilerWindow

    with pytest.raises(ValueError, match="begin < end"):
        ProfilerWindow(str(tmp_path), begin=5, end=5)
    with pytest.raises(ValueError, match="begin < end"):
        ProfilerWindow(str(tmp_path), begin=8, end=3)
    with pytest.raises(ValueError, match="begin < end"):
        ProfilerWindow(str(tmp_path), begin=-1, end=3)


def test_profiler_window_closes_short_loop(tmp_path):
    """A loop ending before the window's end must not leak an active
    trace: close() stops it."""
    from torchmpi_tpu.utils.tracing import ProfilerWindow

    win = ProfilerWindow(str(tmp_path / "t"), begin=0, end=100)
    win.step(0)  # starts
    win.close()  # loop "ended" at step 1
    assert not win.active
    # a fresh trace can start — nothing was leaked
    jax.profiler.start_trace(str(tmp_path / "t2"))
    jax.profiler.stop_trace()


def test_engine_closes_profiler_window_on_exception(tmp_path):
    import optax

    from torchmpi_tpu.engine import AllReduceSGDEngine

    mpi.start()
    p = mpi.size()

    def loss_fn(params, batch):
        xb, yb = batch
        return jnp.mean((xb @ params - yb) ** 2)

    engine = AllReduceSGDEngine(
        loss_fn, jnp.zeros(4), optimizer=optax.sgd(0.1),
        profile_dir=str(tmp_path / "prof"), profile_window=(0, 100),
    )
    xb = np.ones((p, 4), np.float32)
    yb = np.ones((p,), np.float32)

    def bad_iter():
        yield jnp.asarray(xb), jnp.asarray(yb)
        raise RuntimeError("iterator died mid-epoch")

    with pytest.raises(RuntimeError, match="iterator died"):
        engine.train(lambda: bad_iter(), max_epochs=1)
    # the window was closed on the exception path: a fresh profiler
    # trace must start cleanly (an active leaked trace would raise)
    jax.profiler.start_trace(str(tmp_path / "after"))
    jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# autotuner decision audit log
# ---------------------------------------------------------------------------


def test_autotune_decisions_land_in_audit_log():
    from torchmpi_tpu.utils import autotune

    mpi.start()
    comm = mpi.current_communicator()
    winner, _ = autotune.tune_ring_implementation(comm, nelem=256)
    entries = [
        e for e in telemetry.audit_log()
        if e["event"] == "autotune" and e["knob"] == "ring_implementation"
    ]
    assert entries, "tuner decision missing from the audit log"
    assert entries[-1]["chosen"] == winner
    assert entries[-1]["applied"] is True
    # the audit journal rides in every snapshot
    snap = telemetry.snapshot()
    assert any(
        a.get("knob") == "ring_implementation" for a in snap["audit"]
    )
