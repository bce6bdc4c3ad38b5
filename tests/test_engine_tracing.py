"""The engine traces itself (PR 24): host spans on the wall clock in the
always-on ring, named scopes inside the jitted step, counters at the same
boundaries, and telemetry that changes neither the compiled program nor
where the host waits."""

import json
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import torchmpi_tpu as mpi
from torchmpi_tpu import constants, telemetry
from torchmpi_tpu.data import InputPipeline
from torchmpi_tpu.engine import AllReduceSGDEngine
from torchmpi_tpu.telemetry import names
from torchmpi_tpu.telemetry import analyze

ring = telemetry.spans


@pytest.fixture(autouse=True)
def _start():
    was_on = telemetry.enabled()
    mpi.start()
    ring.reset()
    yield
    (telemetry.enable if was_on else telemetry.disable)()


def _loss(params, batch):
    x, y = batch
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return jnp.mean((h @ params["w2"] - y) ** 2)


def _params():
    rng = np.random.RandomState(0)
    return {
        "w1": jnp.asarray(rng.randn(8, 16), jnp.float32),
        "b1": jnp.zeros(16, jnp.float32),
        "w2": jnp.asarray(rng.randn(16), jnp.float32),
    }


TREE_SIZE = 8 * 16 + 16 + 16


def _data(steps, per_rank=2):
    p = mpi.size()
    rng = np.random.RandomState(1)
    x = rng.randn(steps * per_rank * p, 8).astype(np.float32)
    return x, x.sum(axis=1).astype(np.float32)


def _engine(layout="sync", **kw):
    """An engine with the gradient sync or parameter layout ``layout``."""
    if layout in ("int8", "bf16"):
        # a cutoff low enough for the wire to engage
        constants.set("wire_quant_min_elements", 1)
        kw.update(wire_dtype=layout)
    elif layout in ("fsdp", "zero1"):
        kw.update(param_sharding=layout)
    return AllReduceSGDEngine(
        _loss, _params(), optimizer=optax.adam(1e-2), **kw)


def _step_text(engine, per_rank=2):
    p = mpi.size()
    batch = (jnp.zeros((per_rank * p, 8)), jnp.zeros((per_rank * p,)))
    return engine._step_fn.lower(
        engine.params, engine.opt_state, engine.model_state, batch
    ).as_text(debug_info=True)


WIRE_SCOPES = ("tm.grad_sync/pack", "tm.grad_sync/reduce",
               "tm.grad_sync/unpack")
SYNC_SCOPES = {
    "sync": ("tm.grad_sync/reduce", "tm.grad_sync/unpack"),
    "int8": WIRE_SCOPES,
    "bf16": WIRE_SCOPES,
    "fsdp": (),
    "zero1": (),
}


def _sync_ops(text):
    """The ``op_name`` of every operation of the lowered step under
    ``tm.grad_sync``, once for each use: ``tm.grad_sync/reduce/psum``."""
    scoped = dict(re.findall(
        r'^(#loc\d+) = loc\("(%s/[^"]*)"' % names.SCOPE_GRAD_SYNC, text,
        re.M))
    return [scoped[ref] for line in text.splitlines()
            if "stablehlo.return" not in line  # a reducer's own
            for ref in re.findall(r"loc\((#loc\d+)\)", line)
            if ref in scoped]


# -- (a) the scope names reach the lowered step -------------------------
@pytest.mark.parametrize("layout", sorted(SYNC_SCOPES))
def test_scope_names_in_lowered_step(layout):
    text = _step_text(_engine(layout))
    assert "jit(tm_train_step)" in text
    expect = (names.SCOPE_FWD_BWD, names.SCOPE_OPTIMIZER) + SYNC_SCOPES[layout]
    if layout not in ("fsdp", "zero1"):
        expect += (names.SCOPE_LOSS_SYNC,)
    for scope in expect:
        assert scope + "/" in text, (layout, scope)
    if layout in ("fsdp", "zero1"):
        # no sync call of the engine's own: GSPMD inserts the collectives
        assert names.SCOPE_GRAD_SYNC not in text


@pytest.mark.parametrize("layout", sorted(SYNC_SCOPES))
def test_one_gradient_sync_and_no_bucket_level_in_its_scopes(layout):
    """The sync's phases lie directly under ``tm.grad_sync``, whatever
    the layout or the wire: what reads a device trace by these names
    finds no ``b<k>`` level between them."""
    text = _step_text(_engine(layout))
    assert not re.search(r"%s/b\d+/" % names.SCOPE_GRAD_SYNC, text)


@pytest.mark.parametrize("gone,value", [("mode", "async"), ("num_buckets", 2)])
def test_engine_takes_no_mode_and_no_bucket_count(gone, value):
    with pytest.raises(TypeError, match=gone):
        _engine(**{gone: value})


@pytest.mark.parametrize("layout", ["sync", "fsdp"])
def test_scope_names_in_resident_epoch_program(layout):
    engine = _engine(layout)
    x, y = _data(3)
    xd, yd = engine.stage_dataset(x, y)
    fn = engine._build_epoch_fn(3, 2, True)
    text = fn.lower(
        engine.params, engine.opt_state, engine.model_state, xd, yd,
        jax.random.PRNGKey(0),
    ).as_text(debug_info=True)
    assert "jit(tm_epoch)" in text
    for scope in (names.SCOPE_RESIDENT_GATHER, names.SCOPE_FWD_BWD,
                  names.SCOPE_OPTIMIZER):
        assert scope + "/" in text, scope
    assert (names.SCOPE_GRAD_SYNC in text) == (layout == "sync")


def test_state_sync_scope_with_model_state():
    def loss(params, state, batch):
        return _loss(params, batch), {"n": state["n"] + 1.0}

    engine = AllReduceSGDEngine(
        loss, _params(), model_state={"n": jnp.zeros(())})
    assert names.SCOPE_STATE_SYNC + "/" in _step_text(engine)


def test_names_are_pinned():
    """The benchmark's readers and PERF.md find spans and scopes by these
    strings: a change here is a change to a yardstick."""
    assert names.SPAN_NAMES == (
        "engine.init", "engine.broadcast", "engine.stage_dataset",
        "engine.program_build", "engine.input_wait", "engine.dispatch",
        "engine.hooks", "engine.epoch_end", "engine.epoch",
        "engine.epoch.dispatch", "engine.epoch.wait", "engine.checkpoint",
        "engine.resize", "input.epoch_start", "input.assemble",
        "input.stage", "input.ring_wait", "profiler.window",
    )
    assert names.SCOPE_NAMES == (
        "tm.fwd_bwd", "tm.state_sync", "tm.grad_sync", "tm.grad_sync/pack",
        "tm.grad_sync/reduce", "tm.grad_sync/unpack", "tm.optimizer",
        "tm.loss_sync", "tm.resident_gather",
    )


# -- (b) telemetry changes neither the program nor where the host waits --
@pytest.mark.parametrize("layout", ["sync", "int8", "fsdp"])
def test_telemetry_leaves_the_lowered_step_unchanged(layout):
    texts = []
    for switch in (telemetry.disable, telemetry.enable):
        switch()  # one call site for both: the text holds source lines
        texts.append(_step_text(_engine(layout)))
    assert texts[0] == texts[1]


@pytest.mark.parametrize("on", [False, True], ids=["off", "on"])
def test_train_never_blocks_inside_an_epoch(on, monkeypatch):
    (telemetry.enable if on else telemetry.disable)()
    engine = _engine(flops_per_sample=1000)
    x, y = _data(3)
    pipe = InputPipeline((x, y), batch_size=2 * mpi.size(),
                         num_ranks=mpi.size(),
                         sharding=engine.batch_sharding)
    events = []
    real = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda tree: (events.append("block"), real(tree))[1])
    engine.hooks = {
        "on_sample": lambda s: events.append("step"),
        "on_end_epoch": lambda s: events.append("end"),
    }
    state = engine.train(pipe, max_epochs=2)
    assert state["t"] == 6
    # the broadcast before the first epoch and the final wait may block;
    # between an epoch's first step and its end nothing does
    first = events.index("step")
    last = len(events) - 1 - events[::-1].index("end")
    assert "block" not in events[first:last], events
    series = telemetry.snapshot()["metrics"]
    if on:
        assert series["tm_engine_examples_per_sec"]["series"][""] > 0
        assert series["tm_engine_tflops_per_chip"]["series"][""] > 0
        assert sum(series["tm_engine_steps_total"]["series"].values()) >= 6
    for gone in ("tm_engine_step_seconds", "tm_engine_grad_norm"):
        assert gone not in series


def test_step_returns_an_unblocked_loss_and_sets_no_rate(monkeypatch):
    telemetry.enable()
    telemetry.metrics.reset()
    engine = _engine(flops_per_sample=1000)
    blocked = []
    monkeypatch.setattr(jax, "block_until_ready", blocked.append)
    x, y = _data(1)
    loss = engine.step((jnp.asarray(x), jnp.asarray(y)))
    assert not blocked and np.isfinite(float(loss))
    series = telemetry.snapshot()["metrics"]
    assert sum(series["tm_engine_steps_total"]["series"].values()) == 1
    assert series["tm_engine_examples_per_sec"]["series"] == {}
    (rec,) = [r for r in ring.records() if r.name == names.ENGINE_DISPATCH]
    assert rec.step == (0, 0) and engine.steps_run == 1


# -- (c) the ring after a run -------------------------------------------
def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


def _check_clock_and_order(records, t0, t1):
    for r in records:
        assert t0 <= r.start_ns <= r.end_ns <= t1 + 1_000_000, r
    ends = [r.end_ns for r in records if r.tid == records[-1].tid]
    assert ends == sorted(ends)


def test_train_spans():
    engine = _engine()
    x, y = _data(3)
    pipe = InputPipeline((x, y), batch_size=2 * mpi.size(),
                         num_ranks=mpi.size(),
                         sharding=engine.batch_sharding)
    engine.hooks = {"on_update": lambda s: None}
    ring.reset()
    t0 = time.time_ns()
    engine.train(pipe, max_epochs=2)
    t1 = time.time_ns()
    records = ring.records()
    got = _by_name(records)
    waits, steps = got[names.ENGINE_INPUT_WAIT], got[names.ENGINE_DISPATCH]
    assert [r.step for r in waits] == [
        (0, 0), (0, 1), (0, 2), (0, 3), (1, 3), (1, 4), (1, 5), (1, 6)]
    assert [r.step for r in steps] == [
        (0, 0), (0, 1), (0, 2), (1, 3), (1, 4), (1, 5)]
    assert [r.step for r in got[names.ENGINE_EPOCH_END]] == [(0, 3), (1, 6)]
    assert len(got[names.ENGINE_HOOKS]) == 6
    assert len(got[names.ENGINE_BROADCAST]) == 1
    for r in waits + steps + got[names.ENGINE_EPOCH_END]:
        assert r.parent is None
    # the pipeline's consumer-side spans hang under the wait they ran in
    wait_ids = {r.id: r for r in waits}
    starts = got[names.INPUT_EPOCH_START]
    assert len(starts) == 2 and all(r.parent in wait_ids for r in starts)
    for r in got[names.INPUT_RING_WAIT] + got[names.INPUT_STAGE]:
        parent = next(x for x in records if x.id == r.parent)
        assert parent.name in (names.ENGINE_INPUT_WAIT,
                               names.INPUT_EPOCH_START)
        assert r.step == parent.step
    # the step's program was built inside the first dispatch, at step 0
    builds = [r for r in got[names.ENGINE_PROGRAM_BUILD]
              if r.attrs["program"] == "jit(tm_train_step)"]
    assert [(r.parent, r.step) for r in builds] == [(steps[0].id, (0, 0))]
    _check_clock_and_order(records, t0, t1)


def test_train_resident_spans():
    engine = _engine()
    x, y = _data(3)
    ring.reset()
    t0 = time.time_ns()
    state = engine.train_resident(x, y, 2, max_epochs=2)
    t1 = time.time_ns()
    records = ring.records()
    got = _by_name(records)
    assert len(got[names.ENGINE_STAGE_DATASET]) == 1
    epochs = got[names.ENGINE_EPOCH]
    assert [r.step for r in epochs] == [(0, 0), (1, 3)]
    for name in (names.ENGINE_EPOCH_DISPATCH, names.ENGINE_EPOCH_WAIT):
        assert [r.parent for r in got[name]] == [r.id for r in epochs]
        assert [r.step for r in got[name]] == [r.step for r in epochs]
    assert [r.step for r in got[names.ENGINE_EPOCH_END]] == [(0, 0), (1, 3)]
    assert state["epoch_times"] == pytest.approx(
        [r.dur_ns * 1e-9 for r in epochs])
    assert (engine.epochs_run, engine.steps_run) == (2, 6)
    _check_clock_and_order(records, t0, t1)


@pytest.mark.parametrize("workers", [1, 3])
def test_input_pipeline_spans_across_producer_threads(workers):
    x, y = _data(4)
    pipe = InputPipeline((x, y), batch_size=2 * mpi.size(),
                         num_ranks=mpi.size(), workers=workers)
    ring.reset()
    t0 = time.time_ns()
    for _ in range(2):
        assert len(list(pipe())) == 4
    t1 = time.time_ns()
    records = ring.records()
    got = _by_name(records)
    starts = got[names.INPUT_EPOCH_START]
    assert [r.attrs["epoch"] for r in starts] == [0, 1]
    assemblies = got[names.INPUT_ASSEMBLE]
    assert sorted(r.step for r in assemblies) == [
        (e, b) for e in range(2) for b in range(4)]
    me = threading.get_ident() & 0xFFFFFFFF
    for r in assemblies:  # on a producer thread, caused by its epoch's start
        assert r.tid != me and r.parent == starts[r.step[0]].id
    assert len({r.tid for r in assemblies}) <= 2 * workers
    for name in (names.INPUT_RING_WAIT, names.INPUT_STAGE):
        assert len(got[name]) == 8 and all(r.tid == me for r in got[name])
        # the double buffer: two batches fetched before the first is out
        assert sum(r.parent == starts[0].id for r in got[name]) == 2
    _check_clock_and_order(records, t0, t1)


# -- (d) a recompilation is seen from inside ----------------------------
def test_new_shape_in_mid_run_is_one_program_build():
    engine = _engine()
    p = mpi.size()
    x, y = _data(4)

    def batches():
        for i in range(3):
            n = 2 * p if i < 2 else p  # the third batch has a new shape
            yield jnp.asarray(x[:n]), jnp.asarray(y[:n])

    met = telemetry.metrics
    engine.train(batches, max_epochs=1)
    built = met.counter("tm_engine_programs_built_total").total()
    seconds = met.counter("tm_engine_program_build_seconds_total").total()
    steps = [r for r in ring.records()
             if r.name == names.ENGINE_PROGRAM_BUILD
             and r.attrs["program"] == "jit(tm_train_step)"]
    assert [r.step for r in steps] == [(0, 0), (0, 2)]
    assert steps[1].attrs["seconds"] > 0
    assert steps[1].attrs["event"].endswith("backend_compile_duration")
    ring.reset()
    engine.train(batches, max_epochs=1)  # both shapes are built: none now
    assert not [r for r in ring.records()
                if r.name == names.ENGINE_PROGRAM_BUILD
                and r.attrs["program"] == "jit(tm_train_step)"]
    assert built >= 2 and seconds > 0
    assert met.gauge("tm_engine_init_seconds").value() > 0


# -- (e) what the in-graph sync reduces ---------------------------------
@pytest.mark.parametrize("layout,calls", [
    ("sync", 3), ("int8", 1), ("bf16", 1)])
def test_sync_counters_equal_the_tree(layout, calls):
    """One collective per leaf where the leaves are reduced as they lie,
    one per flat buffer where a wire format packs them; the same bytes
    either way."""
    telemetry.metrics.reset()
    _step_text(_engine(layout))
    met = telemetry.metrics
    assert met.gauge("tm_engine_sync_bytes_per_step").value() == 4 * TREE_SIZE
    assert met.gauge("tm_engine_sync_calls_per_step").value() == calls


# -- (f) a flat buffer only where a wire format needs one ---------------
def test_full_wire_step_holds_no_flat_buffer():
    text = _step_text(_engine())
    ops = _sync_ops(text)
    # jax binds one psum for each leaf of a list: XLA's combiner groups them
    assert sum(op.endswith("/psum") for op in ops) == 3
    assert not [op for op in ops if "concatenate" in op]
    assert f"/{names.SCOPE_PACK}/" not in text


@pytest.mark.parametrize("wire", ["int8", "bf16"])
def test_compressed_wire_step_still_packs(wire):
    ops = _sync_ops(_step_text(_engine(wire)))
    assert "tm.grad_sync/pack/concatenate" in ops
    assert not [op for op in ops if op.endswith("/psum")]


@pytest.mark.parametrize("layout", ["sync", "int8"])
def test_fusion_buffer_bytes_does_not_reach_the_lowered_step(layout):
    texts = []
    for size in (0, 4 << 20):
        constants.set("fusion_buffer_bytes", size)
        texts.append(_step_text(_engine(layout)))
    assert texts[0] == texts[1]


# -- one clock -----------------------------------------------------------
def test_a_span_starts_on_the_wall_clock():
    before = time.time_ns()
    with ring.span("t.outer", {"k": 1}) as outer:
        ring.set_step(4, 2)
        with ring.span("t.inner"):
            time.sleep(0.002)
        after_inner = time.time_ns()
    inner, rec = ring.records()[-2:]
    assert before <= rec.start_ns <= before + 1_000_000
    assert rec.name == "t.outer" and rec.id == outer.id
    assert inner.parent == outer.id and inner.step == (4, 2)
    assert inner.dur_ns >= 2_000_000 and inner.end_ns <= after_inner
    assert outer.seconds == pytest.approx(rec.dur_ns * 1e-9)
    # a finished span handed in takes the thread's open span and step
    with ring.span("t.host") as host:
        ring.record("t.done", time.time_ns(), 5)
    assert ring.records()[-2].parent == host.id
    assert ring.records()[-2].step == (4, 2)
    # a reset forgets the thread's step with the records
    ring.reset()
    with ring.span("t.after"):
        pass
    (after,) = ring.records()
    assert after.step is None and after.parent is None


def test_export_and_analyzer_use_the_wall_clock(tmp_path):
    with ring.span("t.export"):
        pass
    now_us = time.time_ns() / 1e3
    ev = telemetry.trace_events()[-1]
    assert ev["name"] == "t.export" and abs(ev["ts"] - now_us) < 1e6
    assert ev["args"]["span_id"] == ring.records()[-1].id
    snap = telemetry.snapshot()
    assert snap["spans"]["clock"] == "time_ns"
    # no perf-counter offset for such a snapshot, whatever its clock sync
    snap["clock_sync"] = {"wall_time": 50.0, "perf_counter": 20.0}
    assert analyze._wall_offset_us({"snapshot": snap}) == 0.0
    del snap["spans"]["clock"]  # a snapshot from before: the old offset
    assert analyze._wall_offset_us({"snapshot": snap}) == 30.0e6
    out = telemetry.export_trace(tmp_path / "t.trace.json")
    assert json.loads(out.read_text())["spanClock"] == "time_ns"


def test_profiler_window_records_its_origin(tmp_path):
    from torchmpi_tpu.utils.tracing import ProfilerWindow

    win = ProfilerWindow(str(tmp_path / "trace"), 1, 2)
    win.step(0)
    assert not win.active
    before = time.time_ns()
    win.step(1)
    assert win.active and before <= win.origin_ns <= time.time_ns()
    origin = win.origin_ns
    jnp.ones(4).block_until_ready()
    win.step(2)
    win.close()  # a second close is nothing
    assert not win.active
    (rec,) = [r for r in ring.records() if r.name == names.PROFILER_WINDOW]
    assert rec.start_ns == origin == rec.attrs["origin_ns"]
    assert rec.attrs["log_dir"] == str(tmp_path / "trace") and rec.dur_ns > 0
    assert list((tmp_path / "trace").rglob("*.xplane.pb"))


def test_checkpoint_span_on_the_training_thread(tmp_path):
    engine = _engine()
    engine.checkpoint_every(2, tmp_path / "ck")
    x, y = _data(1)
    batch = (jnp.asarray(x), jnp.asarray(y))
    for _ in range(2):
        engine.step(batch)
    engine.flush_checkpoint()
    (rec,) = [r for r in ring.records() if r.name == names.ENGINE_CHECKPOINT]
    assert rec.attrs == {"ckpt_step": 2} and rec.step == (0, 1)
