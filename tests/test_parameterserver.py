"""Parameter-server tests.

Mirrors ``test/parameterserver.lua``: init defaults, multi-dim tensors,
zero/copy/add rules in loops with the documented handle/barrier reasoning
(lua:23-183), plus the Update schedules and the mixed PS x DP composition
(``test/hierarchical_communicators.lua`` + ``update.lua:82-113``).
"""

import jax.numpy as jnp
import numpy as np
import pytest

import torchmpi_tpu as mpi
from torchmpi_tpu.parameterserver import (
    DownpourUpdate,
    EASGDUpdate,
    ParameterServer,
    PSGroup,
    shard_range,
    synchronize_gradients_with_parameterserver,
)


@pytest.fixture(autouse=True)
def _start():
    mpi.start()
    yield
    from torchmpi_tpu.parameterserver import free_all

    free_all()


def test_shard_range_uniform():
    """getRange parity (parameterserver.cpp:282-294): full coverage, no
    overlap, remainder spread over the first shards."""
    for n, p in [(100, 8), (7, 8), (8, 8), (1000, 7), (3, 2)]:
        ranges = [shard_range(n, p, r) for r in range(p)]
        assert ranges[0][0] == 0 and ranges[-1][1] == n
        for (a, b), (c, d) in zip(ranges, ranges[1:]):
            assert b == c
        sizes = [e - s for s, e in ranges]
        assert max(sizes) - min(sizes) <= 1


def test_init_from_value_and_receive():
    v = np.arange(100, dtype=np.float32).reshape(10, 10)
    ps = ParameterServer(v)
    out = ps.receive().wait()
    np.testing.assert_array_equal(out, v)
    ps.free()


def test_rule_zero_copy_add_loop():
    """The lua test's 100-iteration rule loop (parameterserver.lua:88-150):
    zero -> add from every rank -> value == sum of contributions."""
    p = mpi.size()
    n = 67  # not divisible by 8: exercises ragged shards
    ps = ParameterServer(np.zeros(n, np.float32))
    for it in range(20):
        ps.send(np.zeros(n, np.float32), rule="zero").wait()
        hs = [
            ps.send(np.full(n, float(r + 1), np.float32), rule="add", client=r)
            for r in range(p)
        ]
        for h in hs:
            h.wait()
        out = ps.receive().wait()
        np.testing.assert_array_equal(out, p * (p + 1) / 2)
    ps.free()


def test_rule_copy_last_writer_wins():
    ps = ParameterServer(np.zeros(10, np.float32))
    ps.send(np.full(10, 3.0), rule="copy").wait()
    np.testing.assert_array_equal(ps.receive().wait(), 3.0)
    ps.free()


def test_scaled_send():
    """Downpour's localUpdate -lr scaling via the scale argument."""
    ps = ParameterServer(np.zeros(10, np.float32))
    ps.send(np.ones(10), rule="add", scale=-0.5).wait()
    np.testing.assert_allclose(ps.receive().wait(), -0.5)
    ps.free()


def test_multidim_tensors():
    v = np.random.RandomState(0).randn(4, 5, 6).astype(np.float32)
    ps = ParameterServer(v)
    ps.send(np.ones_like(v), rule="add").wait()
    np.testing.assert_allclose(ps.receive().wait(), v + 1, rtol=1e-6)
    ps.free()


def test_unknown_rule_rejected():
    ps = ParameterServer(np.zeros(4, np.float32))
    with pytest.raises(KeyError):
        ps.send(np.ones(4), rule="multiply")
    ps.free()


def test_send_after_free_rejected():
    ps = ParameterServer(np.zeros(4, np.float32))
    ps.free()
    with pytest.raises(RuntimeError):
        ps.send(np.ones(4))


def test_wrong_size_rejected():
    ps = ParameterServer(np.zeros(4, np.float32))
    with pytest.raises(ValueError):
        ps.send(np.ones(5))
    ps.free()


def test_async_handles_overlap():
    """Sends are async (thread-pool futures); handles complete with the
    server-applied guarantee (the Ssend happens-before)."""
    p = mpi.size()
    ps = ParameterServer(np.zeros(1 << 14, np.float32))
    hs = [ps.send(np.ones(1 << 14), rule="add", client=r) for r in range(p)]
    assert all(isinstance(h, mpi.SyncHandle) for h in hs)
    for h in hs:
        h.wait()
    np.testing.assert_array_equal(ps.receive().wait(), p)
    ps.free()


# ---------------------------------------------------------------------------
# PSGroup + DSGD
# ---------------------------------------------------------------------------


def _stacked(p, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "a": jnp.asarray(rng.randn(p, 11).astype(np.float32)),
        "b": jnp.asarray(rng.randn(p, 3, 4).astype(np.float32)),
    }


def test_psgroup_roundtrip():
    p = mpi.size()
    tree = _stacked(p)
    grp = PSGroup(tree)
    center = grp.receive_full()
    # initialised from rank 0's replica
    np.testing.assert_allclose(center["a"], np.asarray(tree["a"])[0], rtol=1e-6)
    grp.free()


def test_dsgd_equals_allreduce():
    """DSGD through the PS must equal an averaged allreduce."""
    p = mpi.size()
    tree = _stacked(p, seed=3)
    synced, grp = synchronize_gradients_with_parameterserver(tree)
    for name in ("a", "b"):
        expect = np.asarray(tree[name]).mean(axis=0)
        got = np.asarray(synced[name])
        for r in range(p):
            np.testing.assert_allclose(got[r], expect, rtol=1e-5)
    # group reuse across steps (cache.parameterServers analog)
    synced2, grp2 = synchronize_gradients_with_parameterserver(tree, grp)
    assert grp2 is grp
    grp.free()


# ---------------------------------------------------------------------------
# Update schedules
# ---------------------------------------------------------------------------


def test_downpour_schedule():
    """Downpour semantics: center accumulates scaled gradient sums; replicas
    adopt the center at integration steps."""
    p = mpi.size()
    params = {"w": jnp.zeros((p, 8), jnp.float32)}
    lr = 0.1
    upd = DownpourUpdate(
        local_update=lambda t: -lr * t,
        send_frequency=1,
        update_frequency=2,
        init_delay=1,
        prefetch=0,
    )
    ones = {"w": jnp.ones((p, 8), jnp.float32)}
    # steps 0..5 with constant gradient 1
    for step in range(6):
        params = upd.update(step, params, ones)
    # gradient units accumulate every step from step 0 (like the reference's
    # tensorReferences); sends at steps 2,3,4,5 deliver 3+1+1+1 = 6 units,
    # each unit adding sum_r(-lr * 1) = -p*lr to the center
    center = upd.ps.receive_full()["w"]
    units = 6
    np.testing.assert_allclose(center, -lr * p * units, rtol=1e-5)
    # integration happened at step 3 and 5 (init_delay + k*update_frequency)
    assert np.allclose(np.asarray(params["w"]), np.asarray(params["w"])[0])
    upd.free()


def test_easgd_moves_toward_center():
    p = mpi.size()
    rng = np.random.RandomState(1)
    w0 = rng.randn(p, 6).astype(np.float32)
    params = {"w": jnp.asarray(w0)}
    upd = EASGDUpdate(beta=0.9, update_frequency=1, init_delay=0, prefetch=0)
    zeros = {"w": jnp.zeros((p, 6), jnp.float32)}
    params1 = upd.update(0, params, zeros)  # shard at step 0
    params2 = upd.update(1, params1, zeros)  # first integration
    alpha = 0.9 / p
    center0 = w0[0]  # init from rank 0
    expect = w0 + alpha * (center0[None] - w0)
    np.testing.assert_allclose(np.asarray(params2["w"]), expect, rtol=1e-5)
    # the elastic differences -alpha*(center - x_old) were sent with 'add'
    # in the same tick ("we send immediately after integrating"): the center
    # moves toward the replicas
    for h in upd.handles_send:
        h.wait()
    center = upd.ps.receive_full()["w"]
    np.testing.assert_allclose(
        center, center0 - alpha * (center0[None] - w0).sum(axis=0), rtol=1e-4
    )
    upd.free()


def test_prefetch_distance_schedule():
    """prefetch > 0: the first integration precedes the first prefetch
    (update.lua counter arithmetic); integrate falls back to a synchronous
    fetch instead of crashing."""
    p = mpi.size()
    upd = DownpourUpdate(
        local_update=lambda t: t,
        send_frequency=1,
        update_frequency=5,
        prefetch=2,
        init_delay=0,
    )
    params = {"w": jnp.zeros((p, 4), jnp.float32)}
    ones = {"w": jnp.ones((p, 4), jnp.float32)}
    for step in range(16):
        params = upd.update(step, params, ones)
    upd.free()


def test_free_with_pending_send_never_hangs():
    ps = ParameterServer(np.zeros(8, np.float32))
    h = ps.send(np.ones(8), rule="add")
    ps.free()
    h.wait()  # must complete (applied or failed), never hang


def test_update_prefetch_validation():
    with pytest.raises(ValueError):
        DownpourUpdate(update_frequency=5, prefetch=9)


def test_mixed_ps_dataparallel():
    """PS over sharding comm x DP groups: only DP roots integrate, then the
    integrated params broadcast within each DP group
    (update.lua:82-113, mnist_parameterserver_easgd_dataparallel.lua)."""
    p = mpi.size()
    # DP groups of 2: ranks {0,1},{2,3},{4,5},{6,7}; roots 0,2,4,6
    dp_level = mpi.push_communicator(lambda r: str(r // 2), name="dp")
    mpi.set_communicator(0)
    params = {"w": jnp.zeros((p, 4), jnp.float32)}
    upd = DownpourUpdate(
        local_update=lambda t: t,
        send_frequency=1,
        update_frequency=1,
        init_delay=0,
        prefetch=0,
        sharding_level=0,
        dataparallel_level=dp_level,
    )
    ones = {"w": jnp.ones((p, 4), jnp.float32)}
    params = upd.update(0, params, ones)  # shard (center = 0)
    params = upd.update(1, params, ones)  # fetch+integrate, then send
    w = np.asarray(params["w"])
    # all replicas within each dp group identical (root integrated the
    # center fetched at integration time = 0, then broadcast to its group)
    for g in range(p // 2):
        np.testing.assert_array_equal(w[2 * g], w[2 * g + 1])
    np.testing.assert_array_equal(w, 0)
    # the same-tick send lands after integration: accumulated 2 gradient
    # units x p ranks x 1.0 now sit on the center
    center = upd.ps.receive_full()["w"]
    np.testing.assert_allclose(center, 2.0 * p, rtol=1e-5)
    upd.free()


def test_group_broadcast_eager_op():
    from torchmpi_tpu.collectives.eager import run_group_broadcast

    p = mpi.size()
    mpi.push_communicator(lambda r: str(r // 4), name="halves")
    comm = mpi.current_communicator()
    x = jnp.arange(p, dtype=jnp.float32)[:, None] * jnp.ones((1, 5))
    out = np.asarray(run_group_broadcast(x, comm, root=0))
    # group {0..3} root 0, group {4..7} root 4
    np.testing.assert_array_equal(out[:4], 0)
    np.testing.assert_array_equal(out[4:], 4)


def test_stop_frees_parameter_servers():
    ps = ParameterServer(np.zeros(4, np.float32))
    mpi.stop()
    # global server thread stopped; instance freed via shutdown
    from torchmpi_tpu.parameterserver.server import _server

    assert _server._thread is None or not _server._thread.is_alive()


def test_transport_barrier_generation_counting():
    """A fast peer's NEXT barrier frame (same tag) arriving before this
    process finishes the current wait must be banked for the next wait,
    not discarded (round-2 advisor finding)."""
    from torchmpi_tpu.parameterserver.transport import _Listener

    lst = _Listener(lambda i: None)
    try:
        lst.barrier_arrived("t", 1)
        lst.barrier_arrived("t", 1)  # early arrival of the NEXT generation
        assert lst.barrier_wait("t", {1}, timeout=1.0)
        assert lst.barrier_wait("t", {1}, timeout=1.0)  # banked generation
        assert not lst.barrier_wait("t", {1}, timeout=0.05)  # drained
    finally:
        lst.close()


def test_transport_retry_waits_for_inflight_apply():
    """A reconnect retry racing the still-in-flight FIRST apply of the same
    (inst, rank, client, seq) must WAIT for it and ack its outcome — not
    re-post the update (double-applying a non-idempotent 'add'; round-2
    advisor medium finding)."""
    import socket
    import threading
    import time

    from torchmpi_tpu.parameterserver import transport as T

    applies = []

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            def run():
                time.sleep(0.4)  # slow apply: the retry lands mid-flight
                applies.append(float(np.asarray(msg.payload).sum()))
                msg.done.set()

            threading.Thread(target=run, daemon=True).start()

    inst = FakeInst()
    lst = T._Listener(lambda i: inst)
    try:
        payload = np.ones(4, np.float32)
        s1 = socket.create_connection(("localhost", lst.port), timeout=10)
        s2 = socket.create_connection(("localhost", lst.port), timeout=10)
        for s in (s1, s2):
            s.settimeout(10)
        kw = dict(
            inst=1, rank=0, client=0, seq=7, rule="add",
            dtype=payload.dtype.str, payload=payload.tobytes(),
        )
        T._send_frame(s1, T._KIND_UPDATE, **kw)
        time.sleep(0.1)  # first apply is now in flight
        T._send_frame(s2, T._KIND_UPDATE, **kw)  # the racing retry
        k1 = T._recv_frame(s1)[0]
        k2 = T._recv_frame(s2)[0]
        assert k1 == T._KIND_ACK and k2 == T._KIND_ACK
        assert applies == [4.0], applies  # applied exactly ONCE
        s1.close()
        s2.close()
    finally:
        lst.close()


def test_transport_multi_rank_update_frame():
    """A _KIND_UPDATE_MULTI frame applies every (rank, slice) it carries
    and is acked/deduped as a unit (one round trip per peer instead of
    one per shard rank)."""
    import socket
    import threading

    from torchmpi_tpu.parameterserver import transport as T

    applied = {}

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            applied.setdefault(rank, []).append(
                np.asarray(msg.payload).copy()
            )
            msg.done.set()

    lst = T._Listener(lambda i: FakeInst())
    try:
        s = socket.create_connection(("localhost", lst.port), timeout=10)
        s.settimeout(10)
        a = np.arange(4, dtype=np.float32)
        b = np.arange(6, dtype=np.float32) + 100
        payload = (
            T._MULTI_COUNT.pack(2)
            + T._MULTI_ITEM.pack(0, a.nbytes)
            + T._MULTI_ITEM.pack(3, b.nbytes)
            + a.tobytes()
            + b.tobytes()
        )
        kw = dict(
            inst=1, rank=T._MULTI_RANK, client=2, seq=9, rule="add",
            dtype=a.dtype.str, payload=payload,
        )
        T._send_frame(s, T._KIND_UPDATE_MULTI, **kw)
        assert T._recv_frame(s)[0] == T._KIND_ACK
        np.testing.assert_array_equal(applied[0][0], a)
        np.testing.assert_array_equal(applied[3][0], b)
        # retry of the same frame (post-ACK): deduped, applied exactly once
        T._send_frame(s, T._KIND_UPDATE_MULTI, **kw)
        assert T._recv_frame(s)[0] == T._KIND_ACK
        assert len(applied[0]) == 1 and len(applied[3]) == 1
        s.close()
    finally:
        lst.close()


def test_transport_poisoned_multi_frame_not_reapplied():
    """A partially-failed multi frame must answer its reconnect retry from
    the poison record — never re-apply the items that succeeded."""
    import socket
    import threading
    import time

    from torchmpi_tpu.parameterserver import transport as T

    applies = []

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            def run():
                if rank == 3:
                    msg.error = "shard 3 exploded"
                else:
                    applies.append(rank)
                msg.done.set()

            threading.Thread(target=run, daemon=True).start()

    lst = T._Listener(lambda i: FakeInst())
    try:
        s = socket.create_connection(("localhost", lst.port), timeout=10)
        s.settimeout(10)
        a = np.ones(4, np.float32)
        payload = (
            T._MULTI_COUNT.pack(2)
            + T._MULTI_ITEM.pack(0, a.nbytes)
            + T._MULTI_ITEM.pack(3, a.nbytes)
            + a.tobytes() * 2
        )
        kw = dict(
            inst=1, rank=T._MULTI_RANK, client=0, seq=4, rule="add",
            dtype=a.dtype.str, payload=payload,
        )
        T._send_frame(s, T._KIND_UPDATE_MULTI, **kw)
        k, *_, rrule, _, _ = T._recv_frame(s)
        assert k == T._KIND_ERROR and "exploded" in rrule
        assert applies == [0]  # rank 0 applied once, rank 3 failed
        # the reconnect retry (same seq): answered from the poison record,
        # rank 0 NOT re-applied
        s2 = socket.create_connection(("localhost", lst.port), timeout=10)
        s2.settimeout(10)
        T._send_frame(s2, T._KIND_UPDATE_MULTI, **kw)
        k2, *_, rrule2, _, _ = T._recv_frame(s2)
        assert k2 == T._KIND_ERROR and "exploded" in rrule2
        time.sleep(0.1)
        assert applies == [0], applies
        s.close()
        s2.close()
    finally:
        lst.close()


def test_transport_pipelined_demux_correlation():
    """Concurrent TRIGGERs through ONE pipelined channel must each get
    their own rank's shard back — the FIFO demux correlates replies to
    requests without request ids because the listener answers a
    connection's frames in order."""
    import threading

    from concurrent.futures import Future

    from torchmpi_tpu.parameterserver import transport as T

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            if msg.kind == "trigger":
                msg.reply.set_result(np.full(4, float(rank), np.float32))
            else:
                msg.done.set()

    lst = T._Listener(lambda i: FakeInst())
    ch = T._PeerChannel({0: ("localhost", lst.port)}, 0)
    try:
        results = {}
        errors = []

        def one(rank):
            try:
                results[rank] = ch.request(T._KIND_TRIGGER, 1, rank, 0)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=one, args=(r,)) for r in range(16)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not errors, errors
        for r in range(16):
            np.testing.assert_array_equal(
                results[r], np.full(4, float(r), np.float32)
            )
    finally:
        ch.close()
        lst.close()


def test_transport_channel_replay_applies_exactly_once():
    """Killing the connection mid-pipeline must not lose or double-apply
    updates: the channel replays un-answered frames in order and the
    listener's seq dedup absorbs replays of already-applied ones."""
    import threading
    import time

    from torchmpi_tpu.parameterserver import transport as T

    applies = []

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            def run():
                time.sleep(0.05)  # slow enough to keep a pipeline in flight
                applies.append(float(np.asarray(msg.payload).sum()))
                msg.done.set()

            threading.Thread(target=run, daemon=True).start()

    lst = T._Listener(lambda i: FakeInst())
    ch = T._PeerChannel({0: ("localhost", lst.port)}, 0)
    try:
        errors = []

        def one(i):
            try:
                ch.request(
                    T._KIND_UPDATE, 1, 0, i, rule="add",
                    payload_arr=np.full(2, float(i), np.float32),
                )
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=one, args=(i,)) for i in range(12)
        ]
        for t in threads:
            t.start()
        time.sleep(0.12)  # several applies done, several still in flight
        ch._kick()  # sever the connection mid-pipeline
        for t in threads:
            t.join(60)
        assert not errors, errors
        # every update applied EXACTLY once (replays of applied seqs are
        # deduped; un-applied ones are replayed in order)
        assert sorted(applies) == [2.0 * i for i in range(12)], sorted(applies)
    finally:
        ch.close()
        lst.close()


def test_transport_watchdog_measures_silence_not_queueing():
    """With a watchdog configured, a deep pipeline of slow-but-live
    applies must NOT trip it: replies keep landing, so the connection is
    live even though late waiters queue for longer than one window.
    (The watchdog bounds connection silence, not queue position.)"""
    import threading
    import time

    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            def run():
                time.sleep(0.3)  # live but slower than pipeline depth/wd
                msg.done.set()

            threading.Thread(target=run, daemon=True).start()

    prev = constants.get("deadlock_timeout_seconds")
    constants.set("deadlock_timeout_seconds", 2)
    lst = T._Listener(lambda i: FakeInst())
    ch = T._PeerChannel({0: ("localhost", lst.port)}, 0)
    try:
        errors = []

        def one(i):
            try:
                ch.request(
                    T._KIND_UPDATE, 1, 0, i, rule="add",
                    payload_arr=np.ones(2, np.float32),
                )
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        # 12 x 0.3s sequential applies = ~3.6s total queue, watchdog 2s:
        # every reply gap is ~0.3s so the connection is never silent for
        # a full window and nothing may fail
        threads = [
            threading.Thread(target=one, args=(i,)) for i in range(12)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors, errors
    finally:
        constants.set("deadlock_timeout_seconds", prev)
        ch.close()
        lst.close()


def test_transport_slow_shard_does_not_block_other_shard():
    """Server-side concurrency: one artificially slow shard apply must not
    head-of-line-block another shard's traffic on the SAME connection —
    replies are correlated by the echoed frame seq and applies run on a
    worker pool, the per-instance independence of the reference's Iprobe
    dispatch (parameterserver.cpp:404-541)."""
    import threading
    import time

    from torchmpi_tpu.parameterserver import transport as T

    order = []

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            def run():
                if rank == 0:
                    time.sleep(1.0)  # the slow shard
                order.append(rank)
                msg.done.set()

            threading.Thread(target=run, daemon=True).start()

    lst = T._Listener(lambda i: FakeInst())
    ch = T._PeerChannel({0: ("localhost", lst.port)}, 0)
    try:
        done = {}

        def one(rank):
            ch.request(
                T._KIND_UPDATE, 1, rank, 7, rule="add",
                payload_arr=np.ones(2, np.float32),
            )
            done[rank] = time.monotonic()

        t0 = time.monotonic()
        slow = threading.Thread(target=one, args=(0,))
        slow.start()
        time.sleep(0.05)  # the slow frame is on the wire first
        fast = threading.Thread(target=one, args=(1,))
        fast.start()
        fast.join(30)
        assert 1 in done, "fast shard never acked"
        fast_latency = done[1] - t0
        assert fast_latency < 0.8, (
            f"fast shard waited {fast_latency:.2f}s behind the slow one"
        )
        slow.join(30)
        assert 0 in done, "slow shard never acked"
        assert order == [1, 0], order  # fast applied (and acked) first
    finally:
        ch.close()
        lst.close()


def test_transport_trigger_overtakes_slow_update_on_other_rank():
    """A TRIGGER for one rank is answered while another rank's update is
    still applying on the same connection (out-of-order replies)."""
    import threading
    import time

    from concurrent.futures import Future

    from torchmpi_tpu.parameterserver import transport as T

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            def run():
                if msg.kind == "trigger":
                    msg.reply.set_result(np.full(3, 9.0, np.float32))
                    return
                time.sleep(1.0)
                msg.done.set()

            threading.Thread(target=run, daemon=True).start()

    lst = T._Listener(lambda i: FakeInst())
    ch = T._PeerChannel({0: ("localhost", lst.port)}, 0)
    try:
        t0 = time.monotonic()
        upd = threading.Thread(
            target=ch.request,
            args=(T._KIND_UPDATE, 1, 0, 7),
            kwargs=dict(rule="add", payload_arr=np.ones(2, np.float32)),
        )
        upd.start()
        time.sleep(0.05)
        shard = ch.request(T._KIND_TRIGGER, 1, 1, 7)
        assert time.monotonic() - t0 < 0.8, "trigger blocked behind update"
        np.testing.assert_array_equal(shard, np.full(3, 9.0, np.float32))
        upd.join(30)
    finally:
        ch.close()
        lst.close()


def test_transport_barrier_replay_does_not_double_count():
    """A channel-level replay of a BARRIER frame (same seq — the ACK was
    lost, the frame was resent) must not bank a second arrival
    generation: the surplus would let a LATER barrier with the same tag
    pass before that origin actually arrives."""
    import socket

    from torchmpi_tpu.parameterserver import transport as T

    lst = T._Listener(lambda i: None)
    try:
        s = socket.create_connection(("localhost", lst.port), timeout=10)
        s.settimeout(10)
        kw = dict(client=3, seq=5, rule="tag-a")
        T._send_frame(s, T._KIND_BARRIER, **kw)
        assert T._recv_frame(s)[0] == T._KIND_ACK
        T._send_frame(s, T._KIND_BARRIER, **kw)  # replay, same seq
        assert T._recv_frame(s)[0] == T._KIND_ACK
        # exactly ONE generation banked: the first wait passes instantly,
        # the second (same tag, same origin) must time out
        assert lst.barrier_wait("tag-a", {3}, timeout=5)
        assert not lst.barrier_wait("tag-a", {3}, timeout=0.3)
        # a FRESH barrier frame (new seq) banks a new generation
        T._send_frame(s, T._KIND_BARRIER, client=3, seq=6, rule="tag-a")
        assert T._recv_frame(s)[0] == T._KIND_ACK
        assert lst.barrier_wait("tag-a", {3}, timeout=5)
        s.close()
    finally:
        lst.close()


def test_transport_gather_replay_deduped_and_generations_banked():
    """GATHER frames: replay dedup (same seq re-delivered once) plus the
    generation banking — two distinct sends queue two payloads, consumed
    one per wait, in order."""
    import socket

    from torchmpi_tpu.parameterserver import transport as T

    lst = T._Listener(lambda i: None)
    try:
        s = socket.create_connection(("localhost", lst.port), timeout=10)
        s.settimeout(10)
        T._send_frame(s, T._KIND_GATHER, client=1, seq=2, rule="g",
                      payload=b"first")
        assert T._recv_frame(s)[0] == T._KIND_ACK
        T._send_frame(s, T._KIND_GATHER, client=1, seq=2, rule="g",
                      payload=b"first")  # replay
        assert T._recv_frame(s)[0] == T._KIND_ACK
        T._send_frame(s, T._KIND_GATHER, client=1, seq=3, rule="g",
                      payload=b"second")
        assert T._recv_frame(s)[0] == T._KIND_ACK
        got = lst.gather_wait("g", {1}, timeout=5)
        assert got == {1: b"first"}, got
        got = lst.gather_wait("g", {1}, timeout=5)
        assert got == {1: b"second"}, got
        assert lst.gather_wait("g", {1}, timeout=0.3) is None
        s.close()
    finally:
        lst.close()


# ---------------------------------------------------------------------------
# PS wire formats, chunk pipeline, delta fetches, prefetch (PR 5)
# ---------------------------------------------------------------------------


def _register_instance(n, dtype=np.float32):
    from torchmpi_tpu.parameterserver.server import _server

    return _server.register(np.zeros(n, dtype), 1), _server


def test_ps_wire_codec_roundtrip_bounds():
    """int8/bf16 PS codec: error bounded by the encoding's step size,
    exact for constant blocks (one shared scale represents them all)."""
    from torchmpi_tpu.parameterserver import wire as W

    rng = np.random.RandomState(0)
    x = rng.randn(70001).astype(np.float32)
    y = W.roundtrip(x, W.WIRE_FULL, 128)
    np.testing.assert_array_equal(y, x)
    y = W.roundtrip(x, W.WIRE_BF16, 128)
    assert float(np.abs(y - x).max() / np.abs(x).max()) < 8e-3
    y = W.roundtrip(x, W.WIRE_INT8, 128)
    assert float(np.abs(y - x).max() / np.abs(x).max()) < 2e-2
    const = np.full(1000, 3.25, np.float32)
    np.testing.assert_array_equal(W.roundtrip(const, W.WIRE_INT8, 128), const)


def test_ps_wire_chunk_container_accounting():
    """plan_chunks covers every element exactly once (block-aligned for
    int8) and container_nbytes matches the bytes encode actually emits."""
    from torchmpi_tpu.parameterserver import wire as W

    rng = np.random.RandomState(1)
    for n in (1, 127, 128, 5000, 70001):
        x = rng.randn(n).astype(np.float32)
        for code in (W.WIRE_FULL, W.WIRE_BF16, W.WIRE_INT8):
            chunks = W.plan_chunks(n, code, 128, 1 << 14)
            assert chunks[0][0] == 0
            assert sum(c for _, c in chunks) == n
            for (o1, c1), (o2, _) in zip(chunks, chunks[1:]):
                assert o1 + c1 == o2
            parts, total, nch = W.encode_frame_payload(x, code, 128, 1 << 14)
            assert nch == len(chunks)
            got = sum(len(memoryview(p).cast("B")) for p in parts)
            assert got == total
            assert (total, nch) == W.container_nbytes(n, code, 128, 1 << 14)
            dec = W.decode_parts(parts, code)
            assert dec.shape == (n,)


@pytest.mark.parametrize("wire_name", ["full", "bf16", "int8"])
@pytest.mark.parametrize("chunk_bytes", [0, 1 << 14])
def test_transport_wire_matrix_roundtrip(wire_name, chunk_bytes):
    """UPDATE + TRIGGER through the real listener/channel/mailbox/apply
    path for every (wire encoding x chunking) combination: decoded values
    within the encoding's bound, exact for full."""
    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T, wire as W

    inst, _server = _register_instance(70001)
    lst = T._Listener(lambda i: inst if i == inst.id else None)
    ch = T._PeerChannel({0: ("localhost", lst.port)}, 0)
    try:
        constants.set("parameterserver_wire_dtype", wire_name)
        constants.set("ps_chunk_bytes", chunk_bytes)
        x = np.random.RandomState(2).randn(70001).astype(np.float32)
        ch.request(T._KIND_UPDATE, inst.id, 0, 0, rule="copy", payload_arr=x)
        out = ch.request(
            T._KIND_TRIGGER, inst.id, 0, 0, wire=W.wire_code(wire_name)
        )
        err = float(np.abs(out - x).max() / np.abs(x).max())
        tol = {"full": 0.0, "bf16": 8e-3, "int8": 2e-2}[wire_name]
        assert err <= tol, (wire_name, chunk_bytes, err)
    finally:
        ch.close()
        lst.close()
        _server.unregister(inst)


def test_transport_wire_matrix_concurrent_clients():
    """Two pipelined channels adding int8-quantized updates concurrently:
    the f32 master shard accumulates every (dequantized) contribution —
    sums land within the summed quantization error, nothing is lost."""
    import threading

    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T

    inst, _server = _register_instance(4096)
    lst = T._Listener(lambda i: inst if i == inst.id else None)
    chans = [T._PeerChannel({0: ("localhost", lst.port)}, 0) for _ in range(2)]
    try:
        constants.set("parameterserver_wire_dtype", "int8")
        constants.set("ps_chunk_bytes", 1 << 12)
        rng = np.random.RandomState(3)
        payloads = [rng.randn(4096).astype(np.float32) for _ in range(8)]
        errs = []

        def client(ci):
            try:
                for k in range(ci, len(payloads), 2):
                    chans[ci].request(
                        T._KIND_UPDATE, inst.id, 0, ci, rule="add",
                        payload_arr=payloads[k],
                    )
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=client, args=(ci,)) for ci in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not errs, errs
        expect = np.sum(payloads, axis=0)
        got = inst.read_shard(0)
        # per-payload int8 step ~ amax/127; 8 payloads' errors add
        tol = sum(np.abs(p).max() / 127 for p in payloads)
        assert float(np.abs(got - expect).max()) <= tol
    finally:
        for ch in chans:
            ch.close()
        lst.close()
        _server.unregister(inst)


def test_transport_multi_frame_quantized_roundtrip():
    """UPDATE_MULTI with int8 wire: every item decodes on its own
    quantization grid and applies to its rank."""
    import socket

    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T, wire as W

    applied = {}

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            applied[rank] = np.asarray(msg.payload).copy()
            msg.done.set()

    lst = T._Listener(lambda i: FakeInst())
    try:
        constants.set("parameterserver_wire_dtype", "int8")
        a = np.random.RandomState(4).randn(300).astype(np.float32)
        b = 100 + np.random.RandomState(5).randn(500).astype(np.float32)
        blobs = []
        for arr in (a, b):
            parts, _, _ = W.encode_frame_payload(arr, W.WIRE_INT8, 128, 0)
            blobs.append(b"".join(bytes(p) for p in parts))
        payload = (
            T._MULTI_COUNT.pack(2)
            + T._MULTI_ITEM.pack(0, len(blobs[0]))
            + T._MULTI_ITEM.pack(3, len(blobs[1]))
            + blobs[0]
            + blobs[1]
        )
        s = socket.create_connection(("localhost", lst.port), timeout=10)
        s.settimeout(10)
        T._send_frame(
            s, T._KIND_UPDATE_MULTI, inst=1, rank=T._MULTI_RANK, client=0,
            seq=1, rule="copy", dtype="<f4", payload=payload,
            wire=W.WIRE_INT8,
        )
        assert T._recv_frame(s)[0] == T._KIND_ACK
        # item grids are independent: the b item's +100 offset must not
        # inflate the a item's quantization step
        assert float(np.abs(applied[0] - a).max()) <= np.abs(a).max() / 100
        assert float(np.abs(applied[3] - b).max()) <= np.abs(b).max() / 100
        s.close()
    finally:
        lst.close()


class _CuttingProxy:
    """Loopback proxy that severs its FIRST connection after forwarding
    ``cut_after`` bytes upstream (mid-chunk-stream fault injection);
    later connections pass everything through."""

    def __init__(self, target_port: int, cut_after: int):
        import socket
        import threading

        self._socket = socket
        self.target_port = target_port
        self.cut_after = cut_after
        self.conn_count = 0
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(8)
        self.port = self._srv.getsockname()[1]
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        import threading

        while True:
            try:
                c, _ = self._srv.accept()
            except OSError:
                return
            self.conn_count += 1
            limit = self.cut_after if self.conn_count == 1 else None
            u = self._socket.create_connection(
                ("127.0.0.1", self.target_port)
            )
            threading.Thread(
                target=self._pump, args=(c, u, limit), daemon=True
            ).start()
            threading.Thread(
                target=self._pump, args=(u, c, None), daemon=True
            ).start()

    def _pump(self, src, dst, limit):
        sent = 0
        try:
            while True:
                data = src.recv(16384)
                if not data:
                    break
                if limit is not None and sent + len(data) >= limit:
                    dst.sendall(data[: max(0, limit - sent)])
                    break  # sever mid-frame
                dst.sendall(data)
                sent += len(data)
        except OSError:
            pass
        finally:
            for s in (src, dst):
                try:
                    s.close()
                except OSError:
                    pass

    def close(self):
        try:
            self._srv.close()
        except OSError:
            pass


def test_transport_reconnect_mid_chunk_applies_exactly_once():
    """Severing the connection midway through a chunked quantized UPDATE
    stream must apply the update EXACTLY once: the torn frame applies
    nothing (chunks decode into a staging buffer, the apply is atomic on
    full receipt), the channel replay re-sends the retained frame, and
    the non-idempotent 'add' lands a single time."""
    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T

    inst, _server = _register_instance(1 << 16)
    lst = T._Listener(lambda i: inst if i == inst.id else None)
    # int8-encoded payload is ~67KB on the wire: cut mid-chunk-stream
    proxy = _CuttingProxy(lst.port, cut_after=30_000)
    ch = T._PeerChannel({0: ("127.0.0.1", proxy.port)}, 0)
    try:
        constants.set("parameterserver_wire_dtype", "int8")
        constants.set("ps_chunk_bytes", 1 << 14)
        x = np.random.RandomState(6).randn(1 << 16).astype(np.float32)
        ch.request(T._KIND_UPDATE, inst.id, 0, 0, rule="add", payload_arr=x)
        assert proxy.conn_count >= 2, "the cut never forced a reconnect"
        got = inst.read_shard(0)
        # applied exactly once: |got - x| within ONE quantization pass
        # (a double apply would be ~|x| off)
        assert float(np.abs(got - x).max()) <= np.abs(x).max() / 100
    finally:
        ch.close()
        proxy.close()
        lst.close()
        _server.unregister(inst)


def test_transport_delta_encoding_protocol():
    """Delta fetch protocol through a real Transport against its own
    listener: full -> same -> delta, with the delta chain tracking the
    server state far tighter than a full int8 refetch."""
    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T
    from torchmpi_tpu.parameterserver.server import _server

    constants.set("parameterserver_delta_encoding", True)
    constants.set("parameterserver_wire_dtype", "int8")
    inst = _server.register(np.zeros(5000, np.float32), 1)
    t = T.Transport(_server.get_instance)
    try:
        x = np.random.RandomState(7).randn(5000).astype(np.float32)
        t.update(0, inst.id, 0, 0, "copy", x, fp=inst.fingerprint)
        a1 = t.trigger(0, inst.id, 0, 0, fp=inst.fingerprint)  # full
        a2 = t.trigger(0, inst.id, 0, 0, fp=inst.fingerprint)  # same
        np.testing.assert_array_equal(a1, a2)
        t.update(
            0, inst.id, 0, 0, "add",
            np.full(5000, 0.01, np.float32), fp=inst.fingerprint,
        )
        a3 = t.trigger(0, inst.id, 0, 0, fp=inst.fingerprint)  # delta
        server_state = inst.read_shard(0)
        delta_err = float(np.abs(a3 - server_state).max())
        full_refetch_step = float(np.abs(server_state).max()) / 127 / 2
        assert delta_err < full_refetch_step / 5, (
            delta_err, full_refetch_step
        )
    finally:
        t.close()
        _server.unregister(inst)


def test_transport_delta_per_client_version_vectors():
    """Each client keys its own snapshot: client B's first fetch is full
    even after client A has a delta chain going, and an update between
    A's fetches yields A a delta while B still 'same's its own state."""
    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T
    from torchmpi_tpu.parameterserver.server import _server

    constants.set("parameterserver_delta_encoding", True)
    inst = _server.register(np.zeros(100, np.float32), 1)
    t = T.Transport(_server.get_instance)
    try:
        t.update(0, inst.id, 0, 0, "copy",
                 np.ones(100, np.float32), fp=inst.fingerprint)
        a = t.trigger(0, inst.id, 0, 0, fp=inst.fingerprint)  # A: full
        b = t.trigger(0, inst.id, 0, 1, fp=inst.fingerprint)  # B: full
        np.testing.assert_array_equal(a, b)
        b2 = t.trigger(0, inst.id, 0, 1, fp=inst.fingerprint)  # B: same
        np.testing.assert_array_equal(b2, b)
        t.update(0, inst.id, 0, 0, "add",
                 np.ones(100, np.float32), fp=inst.fingerprint)
        a2 = t.trigger(0, inst.id, 0, 0, fp=inst.fingerprint)  # A: delta
        np.testing.assert_allclose(a2, 2.0, rtol=1e-6)
    finally:
        t.close()
        _server.unregister(inst)


def test_prefetch_double_buffer_semantics():
    """prefetch() keeps at most `depth` fetches in flight; receive()
    consumes them oldest-first, so data races ahead of consumption by at
    most the double-buffer depth."""
    import time

    ps = ParameterServer(np.zeros(64, np.float32))
    ps.send(np.full(64, 1.0, np.float32), rule="copy").wait()
    ps.prefetch()
    ps.prefetch()
    ps.prefetch()  # depth 2: must not issue a third
    time.sleep(0.2)  # prefetched fetches complete with the OLD value
    ps.send(np.full(64, 2.0, np.float32), rule="copy").wait()
    assert float(ps.receive().wait()[0]) == 1.0
    assert float(ps.receive().wait()[0]) == 1.0
    assert float(ps.receive().wait()[0]) == 2.0  # queue drained: fresh
    ps.free()


def test_prefetch_coherence_never_observes_torn_apply():
    """A prefetched read must never see a torn apply: 'copy' updates of
    uniform values race prefetch+receive loops, and every SHARD slice of
    every fetch is uniform (cross-shard skew is the async-PS staleness
    contract; intra-shard tearing would be a coherence bug)."""
    import threading

    ps = ParameterServer(np.full(999, 1.0, np.float32))
    inst = ps._inst
    stop = threading.Event()
    errs = []

    def writer():
        v = 1.0
        try:
            while not stop.is_set():
                v = 3.0 - v  # alternate 1.0 <-> 2.0
                ps.send(np.full(999, v, np.float32), rule="copy").wait()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        for _ in range(30):
            ps.prefetch()
            out = np.asarray(ps.receive().wait())
            for s, e in inst.ranges:
                shard = out[s:e]
                assert shard.min() == shard.max(), (
                    "torn apply visible inside one shard"
                )
                assert shard[0] in (1.0, 2.0)
    finally:
        stop.set()
        t.join(30)
    assert not errs, errs
    ps.free()


def test_shard_range_rotation_properties():
    """Rotated shard ranges keep full coverage, zero overlap and the
    +/-1 size balance for every rotation."""
    for n, p in [(100, 8), (7, 8), (1000, 7), (3, 2), (67, 8)]:
        for rot in range(p):
            ranges = [shard_range(n, p, r, rot) for r in range(p)]
            assert ranges[0][0] == 0 and ranges[-1][1] == n
            for (a, b), (c, d) in zip(ranges, ranges[1:]):
                assert b == c
            sizes = [e - s for s, e in ranges]
            assert max(sizes) - min(sizes) <= 1
            assert sum(sizes) == n


def test_shard_rotation_balances_mixed_dtype_instances():
    """A group of mixed-dtype instances (the byte-aware satellite): the
    per-instance remainder rotation spreads extra ELEMENTS — and thus
    extra BYTES, 8 per f64 element vs 4 per f32 — round-robin across
    server ranks instead of piling them all on rank 0."""
    from torchmpi_tpu.parameterserver.server import _server

    p = 8
    n = 67  # 67 % 8 = 3 extra elements per instance
    insts = []
    for k in range(8):
        dt = np.float64 if k % 2 else np.float32
        insts.append(_server.register(np.zeros(n, dt), p))
    try:
        loads = np.zeros(p)
        base_loads = np.zeros(p)
        for inst in insts:
            item = inst.dtype.itemsize
            for r, (s, e) in enumerate(inst.ranges):
                loads[r] += (e - s) * item
            # counterfactual: every instance placing extras on low ranks
            for r in range(p):
                s, e = shard_range(n, p, r, 0)
                base_loads[r] += (e - s) * item
        # rotation: imbalance bounded by ~one max-itemsize element
        assert loads.max() - loads.min() <= 2 * 8
        # the unrotated layout concentrates every instance's extras
        assert base_loads.max() - base_loads.min() >= 8 * 4
    finally:
        for inst in insts:
            _server.unregister(inst)


def test_downpour_eager_prefetch_in_flight():
    """ps_prefetch: after an integration with prefetch distance 0 the
    NEXT fetch is already in flight (issued eagerly, consumed by the
    next integration); disabling the knob restores strict
    fetch-at-integration scheduling."""
    from torchmpi_tpu import constants

    p = mpi.size()
    ones = {"w": jnp.ones((p, 8), jnp.float32)}

    def run_steps(upd, n):
        params = {"w": jnp.zeros((p, 8), jnp.float32)}
        for step in range(n):
            params = upd.update(step, params, ones)
        return params

    upd = DownpourUpdate(
        local_update=lambda t: t, send_frequency=1, update_frequency=2,
        init_delay=1, prefetch=0,
    )
    run_steps(upd, 4)  # first integration at step 3
    assert upd.handles_prefetch, "eager prefetch not issued"
    params = run_steps(upd, 6)  # runs through the next integration
    assert np.all(np.isfinite(np.asarray(params["w"])))
    upd.free()

    constants.set("ps_prefetch", False)
    upd2 = DownpourUpdate(
        local_update=lambda t: t, send_frequency=1, update_frequency=2,
        init_delay=1, prefetch=0,
    )
    run_steps(upd2, 4)
    assert not upd2.handles_prefetch, "knob off must not prefetch eagerly"
    upd2.free()


def test_downpour_quantized_wire_converges_like_full():
    """Quantized-vs-fp32 equivalence on a quadratic downpour problem:
    int8 PS wire reaches the same optimum within quantization tolerance
    (the fast-tier stand-in for the MNIST example check)."""
    from torchmpi_tpu import constants

    p = mpi.size()
    rng = np.random.RandomState(11)
    target = rng.randn(32).astype(np.float32)
    lr = 0.2

    def run(wire_name):
        constants.set("parameterserver_wire_dtype", wire_name)
        params = {"w": jnp.zeros((p, 32), jnp.float32)}
        upd = DownpourUpdate(
            local_update=lambda t: (-lr / p) * t,
            send_frequency=1, update_frequency=2, init_delay=0, prefetch=0,
        )
        for step in range(40):
            w = np.asarray(params["w"])
            grads = {"w": jnp.asarray(w - target[None, :])}
            params = upd.update(step, params, grads)
            w2 = np.asarray(params["w"])
            params = {
                "w": jnp.asarray(w2 - lr * (w2 - target[None, :]))
            }
        out = np.asarray(params["w"])[0]
        upd.free()
        return out

    w_full = run("full")
    w_int8 = run("int8")
    err_full = float(np.abs(w_full - target).max())
    err_int8 = float(np.abs(w_int8 - target).max())
    # both converge; int8 lands within quantization distance of full
    assert err_full < 0.05
    assert err_int8 < err_full + 0.05


def test_tune_ps_chunk_bytes_measures_and_persists(tmp_path, monkeypatch):
    """tune_ps_chunk_bytes measures the real loopback round trip per
    candidate, applies the winner, and persists it with the other tuned
    knobs so start() re-applies it."""
    monkeypatch.setenv(
        "TORCHMPI_TPU_TUNING_CACHE", str(tmp_path / "autotune.json")
    )
    from torchmpi_tpu import constants
    from torchmpi_tpu.utils import autotune

    best, results = autotune.tune_ps_chunk_bytes(
        nelem=1 << 14, candidates=(0, 1 << 12), warmup=0, timed=1,
        apply=True,
    )
    assert [c for c, _ in results] == [0, 1 << 12]
    assert best in (0, 1 << 12)
    assert constants.get("ps_chunk_bytes") == best
    path = autotune.save_tuning()
    assert path.exists()
    import json

    entry = next(iter(json.loads(path.read_text()).values()))
    assert entry["ps_chunk_bytes"] == best


def test_transport_reconnect_replay_with_telemetry_enabled():
    """Regression: the reconnect/replay path reads the telemetry handle
    tuple (grown by the chunk/delta series) — with telemetry ON a broken
    connection must still replay cleanly instead of dying on the metric
    lookup."""
    import time

    from torchmpi_tpu import telemetry
    from torchmpi_tpu.parameterserver import transport as T

    applies = []

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            def run():
                time.sleep(0.05)
                applies.append(rank)
                msg.done.set()

            import threading

            threading.Thread(target=run, daemon=True).start()

    telemetry.enable()
    lst = T._Listener(lambda i: FakeInst())
    ch = T._PeerChannel({0: ("localhost", lst.port)}, 0)
    try:
        import threading

        threads = [
            threading.Thread(
                target=ch.request,
                args=(T._KIND_UPDATE, 1, i, 0),
                kwargs=dict(
                    rule="add", payload_arr=np.ones(2, np.float32)
                ),
            )
            for i in range(6)
        ]
        for t in threads:
            t.start()
        # wait until frames are actually in flight before severing (a
        # fixed sleep races thread startup under full-suite load)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with ch.lock:
                if len(ch.pending) >= 3:
                    break
            time.sleep(0.005)
        ch._kick()  # sever mid-pipeline with telemetry enabled
        for t in threads:
            t.join(30)
            assert not t.is_alive(), "request hung after telemetry replay"
        assert sorted(applies) == list(range(6))
        snap = telemetry.snapshot()["metrics"]
        assert snap.get("tm_ps_reconnects_total", {}).get("series")
    finally:
        telemetry.disable()
        ch.close()
        lst.close()


def test_transport_delta_snapshots_keyed_by_origin_process():
    """Two ORIGIN processes sharing a client id (both default client=0)
    must not overwrite each other's server-side reconstruction snapshot:
    frames carrying different origins key separate delta chains."""
    import socket

    from torchmpi_tpu.parameterserver import transport as T

    inst, _server = _register_instance(64)
    lst = T._Listener(lambda i: inst if i == inst.id else None)
    try:
        socks = []
        versions = {}
        for origin in (0, 1):
            s = socket.create_connection(("localhost", lst.port), timeout=10)
            s.settimeout(10)
            socks.append(s)
            T._send_frame(
                s, T._KIND_TRIGGER, inst=inst.id, rank=0, client=0,
                seq=1, rule=f"delta:-1:{origin}",
            )
            k, *_, rrule, _, _ = T._recv_frame(s)
            assert k == T._KIND_SHARD and rrule.startswith("full:")
            versions[origin] = int(rrule.split(":")[1])
        # origin 1's full fetch must NOT have clobbered origin 0's
        # snapshot: origin 0's next fetch at its version still 'same's
        T._send_frame(
            socks[0], T._KIND_TRIGGER, inst=inst.id, rank=0, client=0,
            seq=2, rule=f"delta:{versions[0]}:0",
        )
        k, *_, rrule, _, _ = T._recv_frame(socks[0])
        assert k == T._KIND_SHARD and rrule.startswith("same:"), rrule
        for s in socks:
            s.close()
    finally:
        lst.close()
        _server.unregister(inst)


# ---------------------------------------------------------------------------
# PS fabric: event-multiplexed listener, admission control, replication
# ---------------------------------------------------------------------------


def test_listener_multiplexed_dribble_frame():
    """A client dribbling a frame byte-by-byte must not stall anyone
    else: the event loop's per-connection state machine parks the
    partial frame while OTHER clients' RPCs complete on the same single
    loop thread (the head-of-line property thread-per-connection had
    per thread, now with O(1) threads)."""
    import socket
    import threading
    import time

    from torchmpi_tpu.parameterserver import transport as T

    applied = []

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            applied.append(msg.client)
            msg.done.set()

    lst = T._Listener(lambda i: FakeInst())
    try:
        payload = np.ones(8, np.float32)
        dribble = T._frame_bytes(
            T._KIND_UPDATE, inst=1, rank=0, client=77, seq=1, rule="add",
            dtype=payload.dtype.str, payload=payload.tobytes(),
        )
        slow = socket.create_connection(("localhost", lst.port), timeout=10)
        slow.settimeout(10)
        fast = socket.create_connection(("localhost", lst.port), timeout=10)
        fast.settimeout(10)
        fast_done = []

        def dribbler():
            for i in range(len(dribble)):
                slow.sendall(dribble[i:i + 1])
                time.sleep(0.002)

        t = threading.Thread(target=dribbler, daemon=True)
        t.start()
        # while the dribble is in progress, the fast client completes
        # many full round trips through the SAME loop thread
        for seq in range(1, 11):
            T._send_frame(
                fast, T._KIND_UPDATE, inst=1, rank=0, client=5, seq=seq,
                rule="add", dtype=payload.dtype.str,
                payload=payload.tobytes(),
            )
            assert T._recv_frame(fast)[0] == T._KIND_ACK
            fast_done.append(time.monotonic())
        assert t.is_alive(), "fast client should finish before the dribble"
        t.join(30)
        assert T._recv_frame(slow)[0] == T._KIND_ACK
        assert applied.count(5) == 10 and applied.count(77) == 1
        slow.close()
        fast.close()
    finally:
        lst.close()


def test_listener_client_dies_mid_chunk_event_loop():
    """A client that dies mid-chunk-container must not apply anything
    (the frame never completed), must be reaped (connection gauge back
    down), and must not disturb a concurrent healthy client."""
    import socket
    import time

    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T, wire as W

    applied = []

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            applied.append(np.asarray(msg.payload).sum())
            msg.done.set()

    lst = T._Listener(lambda i: FakeInst())
    try:
        n = 1 << 16
        block = constants.get("wire_quant_block_size")
        chunk_bytes = 4096
        total, nchunks = W.container_nbytes(n, W.WIRE_INT8, block,
                                            chunk_bytes)
        assert nchunks > 1
        header, rule_b, dtype_b = T._frame_header(
            T._KIND_UPDATE, 1, 0, 0, 3, 0, W.WIRE_INT8, nchunks,
            "add", "<f4", total,
        )
        chunks = list(W.iter_encoded_chunks(
            np.ones(n, np.float32), W.WIRE_INT8, block, chunk_bytes
        ))
        first = b"".join(bytes(memoryview(b).cast("B")) for b in chunks[0])
        dying = socket.create_connection(("localhost", lst.port), timeout=10)
        dying.sendall(header + rule_b + dtype_b + first)  # 1 of N chunks
        time.sleep(0.2)
        dying.close()  # mid-container EOF
        # healthy client unaffected; the torn frame never applied
        s = socket.create_connection(("localhost", lst.port), timeout=10)
        s.settimeout(10)
        payload = np.full(4, 2.0, np.float32)
        T._send_frame(
            s, T._KIND_UPDATE, inst=1, rank=0, client=9, seq=1, rule="add",
            dtype=payload.dtype.str, payload=payload.tobytes(),
        )
        assert T._recv_frame(s)[0] == T._KIND_ACK
        assert applied == [8.0], applied
        s.close()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            stats = {}
            q = getattr(lst._pool, "_work_queue", None)
            if lst._loop.connection_count() == 0:
                break
            time.sleep(0.05)
        assert lst._loop.connection_count() == 0
        assert lst._disconnects >= 2 and lst._accepts >= 2
    finally:
        lst.close()


def test_busy_backpressure_roundtrip():
    """With a tiny admission budget and a slow apply, concurrent updates
    get BUSY/retry-after replies; the _PeerChannel retries them with
    backoff TRANSPARENTLY and every update applies exactly once."""
    import threading
    import time

    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T

    applies = []

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            def run():
                time.sleep(0.05)
                applies.append(float(np.asarray(msg.payload).sum()))
                msg.done.set()

            threading.Thread(target=run, daemon=True).start()

    prev = constants.get("ps_pending_frame_budget")
    constants.set("ps_pending_frame_budget", 1)
    lst = T._Listener(lambda i: FakeInst())
    ch = T._PeerChannel({0: ("localhost", lst.port)}, 0)
    try:
        errors = []

        def one(i):
            try:
                ch.request(
                    T._KIND_UPDATE, 1, 0, i, rule="add",
                    payload_arr=np.full(2, float(i), np.float32),
                )
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=one, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors, errors
        assert sorted(applies) == [2.0 * i for i in range(8)], sorted(applies)
        assert lst._busy_rejects > 0  # backpressure actually engaged
    finally:
        ch.close()
        lst.close()
        constants.set("ps_pending_frame_budget", prev)


def test_listener_serves_a_fleet_exactly_once_on_bounded_threads():
    """64 concurrent downpour-shaped clients (4 ``add`` updates, 1 fetch,
    a socket each) against ONE listener and the real mailbox/apply path:
    no client error, every shard element equals the acked updates (a lost
    update a deficit, a double apply an excess), and the server's
    ``tm-ps`` threads do not grow with the clients."""
    import socket
    import threading

    from torchmpi_tpu.parameterserver import transport as T

    inst, _server = _register_instance(256)
    lst = T._Listener(lambda i: inst if i == inst.id else None)
    one = np.ones(256, np.float32)
    clients, rounds = 64, 3
    acked, errors = [], []
    connected = threading.Barrier(clients)

    def client(cid):
        try:
            s = socket.create_connection(("localhost", lst.port), timeout=60)
            s.settimeout(60)
            connected.wait(60)  # every connection open at once
            seq = 0
            for _ in range(rounds):
                for kind in (T._KIND_UPDATE,) * 4 + (T._KIND_TRIGGER,):
                    seq += 1
                    frame = dict(inst=inst.id, rank=0, client=cid, seq=seq)
                    if kind == T._KIND_UPDATE:
                        frame.update(rule="add", dtype=one.dtype.str,
                                     payload=one.tobytes())
                    T._send_frame(s, kind, **frame)
                    reply = T._recv_frame(s)
                    if kind == T._KIND_UPDATE:
                        assert reply[0] == T._KIND_ACK, reply[:7]
                        acked.append(cid)
                    else:
                        got = np.frombuffer(reply[8], np.float32)
                        assert reply[0] == T._KIND_SHARD
                        assert got.min() == got.max()  # no torn fetch
            s.close()
        except Exception as e:  # noqa: BLE001 - the audit reports it
            errors.append((cid, repr(e)))

    try:
        threads = [threading.Thread(target=client, args=(c + 1,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        server_threads = [t.name for t in threading.enumerate()
                          if t.name.startswith("tm-ps")]
        assert not errors, errors[:3]
        assert len(acked) == clients * rounds * 4
        shard = inst.read_shard(0)
        assert shard.min() == shard.max() == len(acked)
        assert len(server_threads) <= 14, server_threads
    finally:
        lst.close()
        _server.unregister(inst)


def test_busy_order_fence_on_connection():
    """Once an UPDATE is BUSY-rejected, later pipelined UPDATEs on the
    same connection are rejected too (even with budget available) until
    the first rejected seq retries — so retried updates can never apply
    out of their assignment order."""
    import socket
    import threading
    import time

    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T

    release = threading.Event()
    applied = []

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            def run():
                release.wait(30)
                applied.append(float(np.asarray(msg.payload).sum()))
                msg.done.set()

            threading.Thread(target=run, daemon=True).start()

    prev = constants.get("ps_pending_frame_budget")
    constants.set("ps_pending_frame_budget", 1)
    lst = T._Listener(lambda i: FakeInst())
    try:
        s = socket.create_connection(("localhost", lst.port), timeout=10)
        s.settimeout(10)
        p = np.ones(1, np.float32)
        kw = dict(inst=1, rank=0, client=0, rule="add",
                  dtype=p.dtype.str, payload=p.tobytes())
        T._send_frame(s, T._KIND_UPDATE, seq=1, **kw)  # admitted (budget 1)
        time.sleep(0.1)
        T._send_frame(s, T._KIND_UPDATE, seq=2, **kw)  # over budget: BUSY
        assert T._recv_frame(s)[0] == T._KIND_BUSY
        release.set()  # seq 1 applies; budget frees
        assert T._recv_frame(s)[0] == T._KIND_ACK  # seq 1's ack
        time.sleep(0.3)
        # seq 3 arrives with budget available — but the order fence is
        # armed at seq 2: it must be BUSY'd, not admitted ahead of seq 2
        T._send_frame(s, T._KIND_UPDATE, seq=3, **kw)
        assert T._recv_frame(s)[0] == T._KIND_BUSY
        # the retry of seq 2 clears the fence and applies...
        T._send_frame(s, T._KIND_UPDATE, seq=2, **kw)
        assert T._recv_frame(s)[0] == T._KIND_ACK
        # ...and seq 3's retry is then admitted normally
        T._send_frame(s, T._KIND_UPDATE, seq=3, **kw)
        assert T._recv_frame(s)[0] == T._KIND_ACK
        assert len(applied) == 3
        s.close()
    finally:
        lst.close()
        constants.set("ps_pending_frame_budget", prev)


def test_ps_listen_backlog_knob(monkeypatch):
    """ps_listen_backlog reaches the listener's listen(2) call."""
    import socket

    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T

    seen = []
    real_listen = socket.socket.listen

    def spy(self, backlog):
        seen.append(backlog)
        return real_listen(self, backlog)

    monkeypatch.setattr(socket.socket, "listen", spy)
    prev = constants.get("ps_listen_backlog")
    constants.set("ps_listen_backlog", 131)
    try:
        lst = T._Listener(lambda i: None)
        lst.close()
    finally:
        constants.set("ps_listen_backlog", prev)
    assert 131 in seen


def test_connection_lifecycle_stats_and_telemetry():
    """The ps_listener collector reports connection lifecycle counts and
    the admitted-frame backlog; with telemetry on, the labelled
    gauge/counters and the server-side queue/apply histograms record."""
    import socket

    from torchmpi_tpu import telemetry
    from torchmpi_tpu.parameterserver import transport as T

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            msg.done.set()

    telemetry.reset()
    telemetry.enable()
    try:
        T._SRV_MET = None  # re-resolve handles against the fresh registry
        lst = T._Listener(lambda i: FakeInst())
        try:
            p = np.ones(2, np.float32)
            socks = []
            for cid in (1, 2):
                s = socket.create_connection(
                    ("localhost", lst.port), timeout=10
                )
                s.settimeout(10)
                socks.append(s)
                T._send_frame(
                    s, T._KIND_UPDATE, inst=1, rank=0, client=cid, seq=1,
                    rule="add", dtype=p.dtype.str, payload=p.tobytes(),
                )
                assert T._recv_frame(s)[0] == T._KIND_ACK
            from torchmpi_tpu.telemetry import metrics as reg

            snap = reg.snapshot()
            stats = snap["ps_listener"]
            assert stats["accepted"] >= 2
            assert stats["connections"] >= 2
            assert stats["pending_frames"] == 0  # all replied
            label = f"listener={lst.port}"
            assert snap["tm_ps_accepts_total"]["series"][label] >= 2
            assert snap["tm_ps_connections_open"]["series"][label] >= 2
            qh = snap["tm_ps_server_queue_seconds"]["series"]["kind=update"]
            ah = snap["tm_ps_server_apply_seconds"]["series"]["kind=update"]
            assert qh["count"] >= 2 and ah["count"] >= 2
            for s in socks:
                s.close()
            import time as _time

            deadline = _time.monotonic() + 5
            while _time.monotonic() < deadline:
                if reg.snapshot()["ps_listener"]["disconnected"] >= 2:
                    break
                _time.sleep(0.05)
            assert reg.snapshot()["ps_listener"]["disconnected"] >= 2
        finally:
            lst.close()
    finally:
        telemetry.disable()
        telemetry.reset()
        T._SRV_MET = None


def test_instance_replica_chain_layout():
    """Replica chains derive deterministically from (owners, knob):
    head = owner, successors = next distinct procs in ring order;
    replicas allocate real storage; the fingerprint pins the layout."""
    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver.server import _Instance
    from torchmpi_tpu.parameterserver.transport import instance_fingerprint

    prev = constants.get("ps_replication")
    constants.set("ps_replication", 2)
    try:
        full = np.arange(8, dtype=np.float32)
        a = _Instance(7, full, 2, owners=[0, 1], my_proc=0)
        b = _Instance(7, full, 2, owners=[0, 1], my_proc=1)
        assert a.chains == [[0, 1], [1, 0]] and b.chains == a.chains
        # head stores its own shard AND its replica shard
        assert a.has_storage(0) and a.has_storage(1)
        assert b.has_storage(0) and b.has_storage(1)
        assert a.is_local(0) and not a.is_local(1)
        # chain successor: head forwards to the replica; replica is tail
        assert a.next_in_chain(0) == 1 and a.next_in_chain(1) is None
        assert b.next_in_chain(1) == 0 and b.next_in_chain(0) is None
        # replicated layout fingerprints differently from unreplicated
        assert a.fingerprint == b.fingerprint
        assert a.fingerprint != instance_fingerprint(
            full.shape, full.dtype, 2, [0, 1], a.shard_rotation, 1
        )
    finally:
        constants.set("ps_replication", prev)


def _chain_listener(inst_map, forward=None):
    from torchmpi_tpu.parameterserver import transport as T

    return T._Listener(lambda i: inst_map.get(i))


def test_replica_chain_failover_exactly_once():
    """THE failover acceptance test: a 2-process replica chain
    [head, replica] with chained forwarding; the head is killed
    MID-STREAM; the client fails over to the replica, re-issuing
    unacknowledged updates with their origin seqs — and the surviving
    replica's state matches the expected apply sequence exactly (no
    lost updates, no double-applies), because forwarded frames carried
    the same (client, oseq) dedup identity the re-issues use."""
    import threading
    import time

    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T
    from torchmpi_tpu.parameterserver.server import (
        _Instance, _Message, _ReplicaPump,
    )

    prev = constants.get("ps_replication")
    constants.set("ps_replication", 2)
    try:
        full = np.zeros(4, np.float32)  # 2 ranks x 2-element shards
        # "process 1" (the replica): a real _Instance + its own listener
        inst_b = _Instance(3, full, 2, owners=[0, 1], my_proc=1)
        lst_b = _chain_listener({3: inst_b})
        # "process 0" (the head): real _Instance + listener + a pump
        # forwarding rank-0 applies to the replica over a real channel
        inst_a = _Instance(3, full, 2, owners=[0, 1], my_proc=0)
        lst_a = _chain_listener({3: inst_a})
        pool = T._PeerPool({1: ("127.0.0.1", lst_b.port)})

        def forward(succ, r, msg):
            pool.request(
                succ, T._KIND_UPDATE, 3, r, msg.client,
                rule=msg.rule, payload_arr=np.asarray(msg.payload),
                oseq=msg.oseq,
            )

        inst_a.attach_replication(forward)
        assert inst_a._pump is not None
        # drive both instances' mailboxes like the global server thread
        stop = threading.Event()

        def serve():
            while not stop.is_set():
                worked = inst_a.serve_once() | inst_b.serve_once()
                if not worked:
                    time.sleep(0.0005)

        server_thread = threading.Thread(target=serve, daemon=True)
        server_thread.start()

        # the client: sends updates to the HEAD, with origin seqs — the
        # replicated-update path Transport.update takes
        ch_a = T._PeerChannel({0: ("127.0.0.1", lst_a.port)}, 0)
        ch_b = T._PeerChannel({1: ("127.0.0.1", lst_b.port)}, 1)
        acked = []
        unacked = []
        killed = threading.Event()

        def client():
            for oseq in range(1, 25):
                payload = np.full(2, float(oseq), np.float32)
                try:
                    ch_a.request(
                        T._KIND_UPDATE, 3, 0, 0, rule="add",
                        payload_arr=payload, oseq=oseq,
                    )
                    acked.append(oseq)
                except Exception:  # noqa: BLE001 - head died mid-stream
                    unacked.append(oseq)
                if oseq == 10:
                    killed.set()  # signal the main thread to kill the head
                    time.sleep(0.3)

        ct = threading.Thread(target=client, daemon=True)
        ct.start()
        assert killed.wait(30)
        lst_a.close()  # kill the head server mid-stream
        ct.join(60)
        assert unacked, "the kill must have interrupted some updates"
        # failover: re-issue every unacknowledged update to the replica
        # with the SAME origin seq (what Transport.update does when the
        # chain head raises ConnectionError)
        for oseq in unacked:
            payload = np.full(2, float(oseq), np.float32)
            ch_b.request(
                T._KIND_UPDATE, 3, 0, 0, rule="add",
                payload_arr=payload, oseq=oseq,
            )
        # ... and a duplicate re-issue of an ACKED update (an ack whose
        # delivery raced the kill): the replica's high-water dedups it
        if acked:
            dup = acked[-1]
            ch_b.request(
                T._KIND_UPDATE, 3, 0, 0, rule="add",
                payload_arr=np.full(2, float(dup), np.float32), oseq=dup,
            )
        # the surviving replica's state == every update applied exactly
        # once: sum over oseq 1..24 of full(oseq)
        time.sleep(0.2)
        expected = float(sum(range(1, 25)))
        shard = inst_b.read_shard(0)
        np.testing.assert_allclose(shard, np.full(2, expected))
        # fetch failover: the replica serves the FETCH the head no
        # longer can (Transport.trigger walks the same chain)
        got = ch_b.request(T._KIND_TRIGGER, 3, 0, 0)
        np.testing.assert_allclose(got, np.full(2, expected))
        stop.set()
        server_thread.join(10)
        ch_a.close()
        ch_b.close()
        pool.close()
        lst_b.close()
    finally:
        constants.set("ps_replication", prev)


def test_transport_chain_routing_marks_dead_and_fails_over():
    """Transport.update/trigger with a chain: a dead head is marked and
    skipped; the update lands on the replica with its origin seq."""
    from torchmpi_tpu.parameterserver import transport as T

    applied = []

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            if msg.kind == "trigger":
                msg.reply.set_result(np.full(2, 9.0, np.float32))
            else:
                applied.append((msg.oseq, float(np.asarray(msg.payload)[0])))
                msg.done.set()

    lst = T._Listener(lambda i: FakeInst())
    try:
        tr = T.Transport.__new__(T.Transport)
        tr.process_index = 9
        tr.pool = T._PeerPool({
            0: ("127.0.0.1", 1),  # dead head: nothing listens on port 1
            1: ("127.0.0.1", lst.port),
        })
        tr._dead_procs = {}
        tr._dead_expired = set()
        tr._oseq = {}
        from torchmpi_tpu.analysis import lockmon

        tr._dead_lock = lockmon.make_lock("test.dead")
        tr._oseq_lock = lockmon.make_lock("test.oseq")
        # read-path routing state (see Transport.__init__)
        tr._acked = {}
        tr._read_rr = {}
        tr._read_lock = lockmon.make_lock("test.read")
        tr._shm_readers = {}
        tr._shm_failed = set()
        tr._read_versions = {}
        tr.update(
            0, 5, 0, 0, "add", np.full(2, 3.0, np.float32), chain=[0, 1]
        )
        assert 0 in tr._dead_procs
        assert applied == [(1, 3.0)]  # oseq assigned, replica applied
        # subsequent traffic skips the dead head immediately
        out = tr.trigger(0, 5, 0, 0, chain=[0, 1])
        np.testing.assert_allclose(out, np.full(2, 9.0, np.float32))
        # the dead-mark is NOT permanent: within the retry window the
        # head is skipped, but once ps_dead_peer_retry_s elapses the
        # chain walk re-probes it (bounding the split-brain window a
        # transient stall can open)
        from torchmpi_tpu import constants

        assert tr._alive_chain([0, 1]) == [1]
        tr._dead_procs[0] -= 3600.0  # age the mark past any window
        assert tr._alive_chain([0, 1]) == [0, 1]
        prev = constants.get("ps_dead_peer_retry_s")
        constants.set("ps_dead_peer_retry_s", 0.0)  # 0 = permanent
        try:
            assert tr._alive_chain([0, 1]) == [1]
        finally:
            constants.set("ps_dead_peer_retry_s", prev)
        tr.pool.close()
    finally:
        lst.close()


def test_malformed_delta_trigger_releases_admission_slot():
    """A TRIGGER with a garbage delta rule is answered with ERROR and
    releases its admission slot — it must not leak budget (enough leaks
    would wedge the listener into BUSYing everything) or kill the
    connection."""
    from torchmpi_tpu.parameterserver import transport as T

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            msg.reply.set_result(np.full(2, 7.0, np.float32))

    lst = T._Listener(lambda i: FakeInst())
    ch = T._PeerChannel({0: ("localhost", lst.port)}, 0)
    try:
        with pytest.raises(RuntimeError, match="bad delta trigger rule"):
            ch.request(T._KIND_TRIGGER, 1, 0, 0, rule="delta:x")
        assert lst._pending_frames == 0  # slot released, not leaked
        # same connection still serves: a healthy trigger roundtrips
        out = ch.request(T._KIND_TRIGGER, 1, 0, 0)
        np.testing.assert_allclose(
            np.frombuffer(out, np.float32) if isinstance(out, bytes)
            else out,
            np.full(2, 7.0, np.float32),
        )
    finally:
        ch.close()
        lst.close()


# ---------------------------------------------------------------------------
# PS read path: replica-aware routing, read-your-writes sessions, shm lane
# ---------------------------------------------------------------------------


def _bare_read_transport(addresses):
    """A Transport wired straight at in-test listeners (the client half
    only — no listener of its own), with the read-path routing state
    Transport.__init__ would have built."""
    from torchmpi_tpu.analysis import lockmon
    from torchmpi_tpu.parameterserver import transport as T

    tr = T.Transport.__new__(T.Transport)
    tr.process_index = 99
    tr.pool = T._PeerPool(dict(addresses))
    tr._dead_procs = {}
    tr._dead_expired = set()
    tr._dead_lock = lockmon.make_lock("test.dead")
    tr._oseq = {}
    tr._oseq_lock = lockmon.make_lock("test.oseq")
    tr._delta_cache = {}
    tr._delta_locks = {}
    tr._delta_guard = lockmon.make_lock("test.delta")
    tr._acked = {}
    tr._read_rr = {}
    tr._read_lock = lockmon.make_lock("test.read")
    tr._shm_readers = {}
    tr._shm_failed = set()
    tr._read_versions = {}
    return tr


class _ChainPair:
    """A live 2-process replica chain for read-path tests: two real
    _Instances (owners=[0, 1], chains [[0, 1], [1, 0]]), each behind its
    own listener, a pause-able serve thread driving both mailboxes, and
    per-member TRIGGER counters (a stale refusal is answered BEFORE the
    mailbox post, so the counters measure fetches actually SERVED)."""

    def __init__(self, inst_id=21, with_pump=True, n=4):
        import threading

        from torchmpi_tpu.parameterserver import transport as T
        from torchmpi_tpu.parameterserver.server import _Instance

        full = np.zeros(n, np.float32)
        self.inst_a = _Instance(inst_id, full, 2, owners=[0, 1], my_proc=0)
        self.inst_b = _Instance(inst_id, full, 2, owners=[0, 1], my_proc=1)
        self.lst_a = T._Listener(lambda i: self.inst_a)
        self.lst_b = T._Listener(lambda i: self.inst_b)
        self.served = {0: 0, 1: 0}
        for pidx, inst in ((0, self.inst_a), (1, self.inst_b)):
            self._count_triggers(pidx, inst)
        self._fwd_pool = None
        if with_pump:
            # chain-forward rank-0 applies head -> replica, preserving
            # the original (client, oseq) dedup identity — the replica's
            # per-client applied high-water is what the RYW floor checks
            self._fwd_pool = T._PeerPool({1: ("127.0.0.1", self.lst_b.port)})

            def forward(succ, r, msg):
                self._fwd_pool.request(
                    succ, T._KIND_UPDATE, inst_id, r, msg.client,
                    rule=msg.rule, payload_arr=np.asarray(msg.payload),
                    oseq=msg.oseq,
                )

            self.inst_a.attach_replication(forward)
        self.paused = threading.Event()
        self._stop = threading.Event()

        def serve():
            import time as _t

            while not self._stop.is_set():
                if self.paused.is_set():
                    _t.sleep(0.0005)
                    continue
                if not (self.inst_a.serve_once() | self.inst_b.serve_once()):
                    _t.sleep(0.0005)

        self._thread = threading.Thread(target=serve, daemon=True)
        self._thread.start()

    def _count_triggers(self, pidx, inst):
        orig = inst.post

        def post(rank, msg):
            if msg.kind == "trigger":
                self.served[pidx] += 1
            return orig(rank, msg)

        inst.post = post

    def transport(self):
        return _bare_read_transport({
            0: ("127.0.0.1", self.lst_a.port),
            1: ("127.0.0.1", self.lst_b.port),
        })

    def close(self):
        self._stop.set()
        self._thread.join(10)
        if self._fwd_pool is not None:
            self._fwd_pool.close()
        self.lst_a.close()
        self.lst_b.close()


def test_read_policy_replica_spreads_and_survives_replica_death():
    """ps_read_policy=replica rotates fetches of ONE shard across both
    chain members; killing the replica mid-stream falls back to the
    owner with zero torn reads — every fetch returns the exact
    all-updates-applied value (the chain forward acks only after the
    replica applied, so a replica-served read is never mid-update)."""
    from torchmpi_tpu import constants

    constants.set("ps_replication", 2)
    constants.set("ps_read_policy", "replica")
    pair = _ChainPair(inst_id=21, with_pump=True)
    tr = pair.transport()
    try:
        for _ in range(5):
            tr.update(0, 21, 0, 0, "add", np.full(2, 1.0, np.float32),
                      chain=[0, 1])
        for _ in range(8):
            out = tr.trigger(0, 21, 0, 0, chain=[0, 1])
            np.testing.assert_allclose(out, np.full(2, 5.0, np.float32))
        # round-robin rotation: both members actually served fetches
        assert pair.served[0] > 0 and pair.served[1] > 0
        # replica death mid-stream: the walk marks it dead and the
        # owner serves every remaining fetch, still torn-free
        pair.lst_b.close()
        for _ in range(6):
            out = tr.trigger(0, 21, 0, 0, chain=[0, 1])
            assert out.min() == out.max() == 5.0  # zero torn reads
        assert 1 in tr._dead_procs
    finally:
        tr.pool.close()
        pair.close()


def test_read_your_writes_redirects_lagged_replica():
    """RYW with a deliberately LAGGED replica (no chain pump, so its
    applied high-water never advances): under ps_read_staleness=0 every
    replica-routed fetch is refused with stale:<hw> BEFORE reaching the
    replica's mailbox and redirected to the owner — the client always
    observes its own acked writes. Widening ps_read_staleness past the
    write count lets the lagged replica serve its old view again (the
    staleness bound is the knob, not a hardcoded freshness rule)."""
    from torchmpi_tpu import constants

    constants.set("ps_replication", 2)
    constants.set("ps_read_policy", "replica")
    constants.set("ps_read_staleness", 0)
    pair = _ChainPair(inst_id=22, with_pump=False)
    tr = pair.transport()
    try:
        for _ in range(3):
            # no chain: the write lands on the owner only (the replica
            # stays at 0.0 with applied high-water 0 — maximal lag)
            tr.update(0, 22, 0, 0, "add", np.full(2, 1.0, np.float32))
            tr._record_acked(22, 0, 0, tr.next_oseq(22, 0, 0))
        assert tr._session_floor(22, 0, 0) == 3
        for _ in range(6):
            out = tr.trigger(0, 22, 0, 0, chain=[0, 1])
            np.testing.assert_allclose(out, np.full(2, 3.0, np.float32))
        # the stale refusals never reached the replica's server loop
        assert pair.served[1] == 0
        assert pair.served[0] == 6
        # staleness allowance >= lag: the replica may serve its old view
        constants.set("ps_read_staleness", 10)
        assert tr._session_floor(22, 0, 0) == 0
        seen = set()
        for _ in range(4):
            seen.add(float(tr.trigger(0, 22, 0, 0, chain=[0, 1])[0]))
        assert pair.served[1] > 0  # lagged replica allowed to serve...
        assert 0.0 in seen  # ...and its stale view was observed
    finally:
        tr.pool.close()
        pair.close()


def test_read_your_writes_holds_across_busy_retry_window():
    """RYW survives BUSY/retry: with the serve thread paused and a tiny
    admission budget, concurrent fetches pile up, some are BUSYed and
    retried — and after serving resumes, EVERY fetch still returns the
    client's own acked writes (the session floor rides the retried
    frame unchanged)."""
    import threading

    from torchmpi_tpu import constants

    constants.set("ps_replication", 2)
    constants.set("ps_read_policy", "replica")
    pair = _ChainPair(inst_id=23, with_pump=True)
    tr = pair.transport()
    try:
        for _ in range(4):
            tr.update(0, 23, 0, 0, "add", np.full(2, 1.0, np.float32),
                      chain=[0, 1])
        constants.set("ps_pending_frame_budget", 2)
        pair.paused.set()  # frames pile up: nothing drains admission
        results, errs = [], []

        def fetch():
            try:
                results.append(tr.trigger(0, 23, 0, 0, chain=[0, 1]))
            except Exception as e:  # noqa: BLE001 - fail the test below
                errs.append(e)

        threads = [threading.Thread(target=fetch) for _ in range(6)]
        for t in threads:
            t.start()
        import time as _t

        _t.sleep(0.3)  # let the pile-up trip the admission budget
        pair.paused.clear()
        for t in threads:
            t.join(30)
        assert not errs, errs
        assert len(results) == 6
        for out in results:
            np.testing.assert_allclose(out, np.full(2, 4.0, np.float32))
        assert (pair.lst_a._busy_rejects + pair.lst_b._busy_rejects) > 0
    finally:
        tr.pool.close()
        pair.close()


def test_shm_seqlock_torn_read_retries_then_recovers():
    """The seqlock contract, forced deterministically: an odd version
    counter (a write frozen mid-flight) makes the reader spin its
    budget and return None with .retries advanced — never a torn
    payload; restoring a complete publish makes the same reader
    succeed at the new value."""
    import os

    from torchmpi_tpu.parameterserver import shmlane

    port = 40000 + os.getpid() % 20000
    pub = shmlane.ShmPublisher(port, 5)
    reader = None
    try:
        pub.publish(0, np.full(4, 2.0, np.float32), version=1)
        reader = shmlane.ShmReader(shmlane.segment_name(port, 5, 0))
        arr, version = reader.read()
        np.testing.assert_allclose(arr, np.full(4, 2.0, np.float32))
        assert version == 1
        # freeze the segment mid-write: pack an ODD counter in place
        seg = pub._segs[0]
        shmlane._HDR.pack_into(
            seg.buf, 0, shmlane._MAGIC, 3, 1, 16, b"<f4\x00\x00\x00\x00\x00"
        )
        before = reader.retries
        assert reader.read() is None  # spun out, no torn payload
        assert reader.retries > before
        pub.publish(0, np.full(4, 9.0, np.float32), version=2)
        arr, version = reader.read()
        np.testing.assert_allclose(arr, np.full(4, 9.0, np.float32))
        assert version == 2
    finally:
        if reader is not None:
            reader.close()
        pub.close()


def test_shm_seqlock_uniform_under_concurrent_writer():
    """Torn-read audit under a live concurrent writer: every publish is
    a uniform array, so ANY non-uniform read is a torn read. The reader
    hammers the segment while the writer republishes; every successful
    read must be uniform and version-consistent."""
    import os
    import threading

    from torchmpi_tpu.parameterserver import shmlane

    port = 40000 + (os.getpid() + 7) % 20000
    pub = shmlane.ShmPublisher(port, 6)
    pub.publish(0, np.full(1024, 0.0, np.float32), version=1)
    reader = shmlane.ShmReader(shmlane.segment_name(port, 6, 0))
    stop = threading.Event()

    def writer():
        v = 1
        while not stop.is_set():
            v += 1
            pub.publish(0, np.full(1024, float(v), np.float32), version=v)

    wt = threading.Thread(target=writer, daemon=True)
    wt.start()
    torn = 0
    reads = 0
    try:
        for _ in range(3000):
            res = reader.read()
            if res is None:
                continue  # spin budget exhausted: honest miss, not torn
            arr, version = res
            reads += 1
            if arr.min() != arr.max():
                torn += 1
        assert torn == 0
        assert reads > 0
    finally:
        stop.set()
        wt.join(10)
        reader.close()
        pub.close()


def test_shm_lane_serves_local_fetches_without_sockets():
    """ps_shm_lane end-to-end: the owner publishes on attach and after
    every applied update (BEFORE acking); a same-host client's trigger
    is served from the segment — zero TRIGGER frames reach the server
    loop — and observes its own acked write immediately (RYW by
    publish-before-ack)."""
    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import shmlane
    from torchmpi_tpu.parameterserver import transport as T
    from torchmpi_tpu.parameterserver.server import _Instance

    constants.set("ps_shm_lane", True)
    full = np.arange(4, dtype=np.float32)
    inst = _Instance(31, full, 2, owners=[0, 0], my_proc=0)
    lst = T._Listener(lambda i: inst)
    inst.attach_shm(shmlane.ShmPublisher(lst.port, 31))
    served = {"triggers": 0}
    orig_post = inst.post

    def post(rank, msg):
        if msg.kind == "trigger":
            served["triggers"] += 1
        return orig_post(rank, msg)

    inst.post = post
    import threading
    import time as _t

    stop = threading.Event()

    def serve():
        while not stop.is_set():
            if not inst.serve_once():
                _t.sleep(0.0005)

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    tr = _bare_read_transport({0: ("127.0.0.1", lst.port)})
    try:
        out = tr.trigger(0, 31, 0, 0)
        np.testing.assert_allclose(out, full[:2])
        out = tr.trigger(0, 31, 1, 0)
        np.testing.assert_allclose(out, full[2:])
        assert served["triggers"] == 0  # zero socket fetches
        # write -> republish-before-ack -> the NEXT shm read sees it
        tr.update(0, 31, 0, 0, "add", np.full(2, 10.0, np.float32))
        out = tr.trigger(0, 31, 0, 0)
        np.testing.assert_allclose(out, full[:2] + 10.0)
        assert served["triggers"] == 0
        # the lane recorded the shard version it observed (feeds the
        # serving tier's version vector)
        assert tr._read_versions[(31, 0, 0)] >= 1
    finally:
        stop.set()
        thread.join(10)
        tr.pool.close()
        inst.detach_shm()
        lst.close()


def test_route_read_rotation_prefer_and_adaptive_pressure():
    """route_read under each policy: owner pins the head; replica
    round-robins the live chain (so a fan-out's consecutive routes land
    on distinct endpoints); prefer pins the walk's first candidate to
    the member the caller already grouped by; adaptive spreads ONLY
    while the owner shows backpressure."""
    import time as _t

    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T

    tr = _bare_read_transport({})
    try:
        chain = [0, 1, 2]
        assert tr.route_read(0, 1, 0, chain, policy="owner") == 0
        assert tr.route_read(0, 1, 0, None, policy="replica") == 0
        got = [tr.route_read(0, 1, 0, chain, policy="replica")
               for _ in range(6)]
        assert got == [0, 1, 2, 0, 1, 2]
        # prefer pins the first candidate without advancing the cursor
        cands = tr._read_candidates(0, 1, 0, chain, "replica", prefer=2)
        assert cands == [2, 0, 1]
        # adaptive: calm owner -> owner-first (no spread) ...
        assert [tr.route_read(0, 1, 1, chain, policy="adaptive")
                for _ in range(3)] == [0, 0, 0]
        # ... BUSY backpressure within the last second -> spread
        ch = T._PeerChannel({0: ("127.0.0.1", 1)}, 0)
        tr.pool._channels[0] = ch
        ch.last_busy = _t.monotonic()
        assert tr._owner_pressured(0)
        got = [tr.route_read(0, 1, 1, chain, policy="adaptive")
               for _ in range(3)]
        assert sorted(set(got)) != [0]  # rotation engaged
        # dead-marked owner pressures too
        ch.last_busy = 0.0
        tr._mark_dead(0)
        assert tr._owner_pressured(0)
        # global knob drives the default
        constants.set("ps_read_policy", "replica")
        first = tr.route_read(0, 2, 0, chain)
        second = tr.route_read(0, 2, 0, chain)
        assert first != second
    finally:
        tr.pool.close()


def test_chain_forward_frames_bypass_admission():
    """A ``fwd:``-tagged UPDATE (a replica pump relaying an update the
    chain head already admitted) is NEVER BUSYed — re-admitting at each
    hop would invert priority, stalling the single in-order pump behind
    the client traffic it carries — while an untagged client update
    against the same zero budget is rejected."""
    from torchmpi_tpu import constants
    from torchmpi_tpu.parameterserver import transport as T

    applied = []

    class FakeInst:
        fingerprint = 0

        def post(self, rank, msg):
            applied.append((msg.rule, msg.oseq))
            msg.done.set()

    lst = T._Listener(lambda i: FakeInst())
    ch = T._PeerChannel({0: ("127.0.0.1", lst.port)}, 0)
    try:
        # saturate admission: budget 1 with the one slot pre-occupied
        constants.set("ps_pending_frame_budget", 1)
        with lst._pending_lock:
            lst._pending_frames += 1
        payload = np.full(2, 1.0, np.float32)
        ch.request(
            T._KIND_UPDATE, 1, 0, 0, rule="fwd:add",
            payload_arr=payload, oseq=7,
        )
        # forwarded frame sailed through the full budget, and the fwd:
        # tag was stripped before the apply saw the rule
        assert applied == [("add", 7)]
        assert lst._busy_rejects == 0
        # the SAME state rejects an untagged client update (probed via
        # the pure decision — the live channel would BUSY-retry forever
        # against a permanently saturated budget)
        admit, _ = T.admission_decision(
            lst._pending_frames, 1, None, 2, True
        )
        assert not admit
        with lst._pending_lock:
            lst._pending_frames -= 1
    finally:
        ch.close()
        lst.close()
