"""What the per-layer metrics read of the program's own tracing: its host
spans and counters, and the named scopes of its jitted step in a device
trace. The readers are ``layer_metrics/<name>.py``; the arithmetic they
share is here, on top of ``benchmark/xplane.py``.

**Spans.** The program records its loop's spans always, into a bounded
ring (``torchmpi_tpu.telemetry.spans``, process-global, still there after
the engine is released): ``engine.input_wait``, ``engine.dispatch``,
``input.epoch_start`` and the rest, each with its start on
``time.time_ns()``. The harness sets a trace's origin on the same clock, so
a span's place in a trace is ``start_ns - origin_ns``. A reader takes the
spans that overlap the trace's window: the first device operation's start
to the last one's end.

**Scopes.** The step's operations carry ``jax.named_scope`` names
(``tm.fwd_bwd``, ``tm.grad_sync/pack`` ...) in their ``op_name``. The
profiler writes it as the ``tf_op`` stat of each operation's *event
metadata* in the ``.xplane.pb``. ``jax.profiler.ProfileData`` (jax 0.9.0)
shows an event's own stats only, so the metadata is read from the file
itself with the few lines of protobuf wire format below (no import beyond
the standard library), and joined to ``ProfileData``'s events by the
event's name, which is the metadata's. A fusion belongs to the scope its
own ``op_name`` gives.

**A program without them.** A program that records no such span, counter
or scope (the parent of the PR that added these) makes every function here
return None, and the reader leaves its metric out.
"""

from __future__ import annotations

import functools
import statistics
from collections import defaultdict

from benchmark import xplane

SCOPE_PREFIX = "tm."
GRAD_SYNC = "tm.grad_sync"
PHASES = ("pack", "reduce", "unpack")


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


# -- the program's ring and registry ------------------------------------
def program_spans():
    """The program's buffered spans, oldest first, or None where the
    program keeps none that can be laid over a trace."""
    try:
        from torchmpi_tpu.telemetry import spans as ring
    except ImportError:
        return None
    records = getattr(ring, "records", None)
    return None if records is None else records()


def counter(name: str):
    """The unlabelled value of a gauge or counter in the program's
    registry, or None where the program has none of that name."""
    try:
        from torchmpi_tpu.telemetry import metrics
    except ImportError:
        return None
    series = metrics.snapshot().get(name, {}).get("series", {})
    return series.get("")


def named(records, name: str) -> list:
    return [r for r in records or () if r.name == name]


def on_trace_clock(records, origin_ns: int) -> list:
    """[(name, start, end)] in seconds from the trace's origin, by start."""
    return sorted(
        ((r.name, (r.start_ns - origin_ns) * 1e-9,
          (r.start_ns + r.dur_ns - origin_ns) * 1e-9) for r in records),
        key=lambda s: s[1])


def overlapping(records, origin_ns: int, window) -> list:
    """The records that overlap ``window`` (seconds of the trace)."""
    lo, hi = (origin_ns + int(t * 1e9) for t in window)
    return [r for r in records
            if r.start_ns < hi and r.start_ns + r.dur_ns > lo]


def self_time(records, origin_ns: int) -> list:
    """[(name, start, end)] on the trace's clock in which each span is cut
    down to the time none of its children covers, so that an interval of
    the host's time bears the name of the innermost span open in it."""
    children = defaultdict(list)
    for r in records:
        if r.parent is not None:
            children[r.parent].append(r)
    out = []
    for r in records:
        own = [(r.start_ns, r.start_ns + r.dur_ns)]
        inner = xplane.union(
            (c.start_ns, c.start_ns + c.dur_ns) for c in children[r.id])
        for s, e in xplane.subtract(own, inner):
            out.append((r.name, (s - origin_ns) * 1e-9,
                        (e - origin_ns) * 1e-9))
    return sorted(out, key=lambda s: s[1])


# -- protobuf wire format: the event metadata of an .xplane.pb ----------
def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")
        yield key >> 3, value


def _map_entries(plane, field: int):
    """(key, value message) of a ``map<int64, Message>`` field."""
    for number, entry in _fields(plane):
        if number == field:
            parts = dict(_fields(entry))
            yield parts.get(1, 0), parts.get(2, b"")


def op_names(path) -> dict:
    """{device plane name: {event name: op_name}} from the ``tf_op`` stat
    of each event's metadata. ``XSpace.planes = 1``; ``XPlane``: ``name =
    2``, ``event_metadata = 4``, ``stat_metadata = 5``; ``XEventMetadata``:
    ``name = 2``, ``stats = 5``; ``XStat``: ``metadata_id = 1``,
    ``str_value = 5``, ``ref_value = 7`` (a stat metadata's name);
    ``XStatMetadata``: ``name = 2``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name = next((bytes(v).decode() for n, v in _fields(plane) if n == 2),
                    "")
        if not name.startswith(xplane.DEVICE_PREFIX):
            continue
        stat_names = {
            key: bytes(dict(_fields(meta)).get(2, b"")).decode()
            for key, meta in _map_entries(plane, 5)
        }
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
        names = {}
        for _, meta in _map_entries(plane, 4):
            event_name, op = "", None
            for n, v in _fields(meta):
                if n == 2:
                    event_name = bytes(v).decode()
                elif n == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op:
                        op = (bytes(stat[5]).decode() if 5 in stat
                              else stat_names.get(stat.get(7), ""))
            if op:
                names[event_name] = op
        out[name] = names
    return out


def scope_of(op_name: str):
    """``tm.fwd_bwd``, ``tm.grad_sync/pack`` ... of an ``op_name`` such as
    ``jit(tm_step)/shard_map/tm.grad_sync/b3/pack/concatenate``: the first
    ``tm.`` component, and under ``tm.grad_sync`` the phase below it (a
    bucket's ``b<n>`` between them is passed over). None outside any."""
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        if part.startswith(SCOPE_PREFIX):
            if part == GRAD_SYNC:
                phase = next((p for p in parts[i + 1:i + 3] if p in PHASES),
                             None)
                return f"{part}/{phase}" if phase else part
            return part
    return None


# -- a trace by scope ---------------------------------------------------
def own_intervals(events):
    """[(name, [(start, end)])]: for each event of one chip (sorted by
    start, then longest first) the time it covers itself and no event
    nested in it does. A container (a ``while``) keeps the gaps between
    its operations, which ``xplane.device_summary`` counts as busy; a
    collective keeps its whole interval (an asynchronous one may span
    compute that it does not hold)."""
    out, stack = [], []  # a stack entry: [name, end, cursor, pieces]

    def close(name, end, cursor, pieces):
        if cursor < end:
            pieces.append((cursor, end))
        out.append((name, pieces))

    for name, s, e in events:
        if xplane.is_collective(name):
            out.append((name, [(s, e)]))
            continue
        while stack and stack[-1][1] <= s:
            close(*stack.pop())
        if stack:
            top = stack[-1]
            if s > top[2]:
                top[3].append((top[2], s))
            top[2] = max(top[2], e)
        stack.append([name, e, s, []])
    while stack:
        close(*stack.pop())
    return out


@functools.lru_cache(maxsize=4)
def by_scope(path: str):
    """One trace (the directory ``start_trace`` wrote, or the ``.pb``
    itself), per chip and averaged: seconds under each scope (the
    union of its operations' intervals), under none, the busy union, the
    steps, and the first chip's busy intervals and window. None where the
    trace holds no device operation; ``scope_s`` is empty where none lies
    under a ``tm.`` scope (a program without scopes)."""
    file = path if str(path).endswith(".pb") else xplane.find(path)
    trace = xplane.load(file)
    if not trace.ops:
        return None
    names = op_names(file)
    seconds, unscoped_ops, busy = defaultdict(float), defaultdict(float), 0.0
    n = len(trace.ops)
    for plane, events in trace.ops.items():
        table = names.get(plane, {})
        spans = defaultdict(list)
        for name, pieces in own_intervals(events):
            scope = scope_of(table.get(name, ""))
            spans[scope] += pieces
            if scope is None:
                unscoped_ops[name, table.get(name, "")] += (
                    xplane.length(pieces) / n)
        for scope, intervals in spans.items():
            seconds[scope] += xplane.length(xplane.union(intervals)) / n
        busy += xplane.length(xplane.union((s, e) for _, s, e in events)) / n
    unscoped = seconds.pop(None, 0.0)
    first = sorted(trace.ops)[0]
    summary = xplane.device_summary(trace.ops[first])
    return {
        "devices": n,
        "steps": xplane.step_count(trace.modules.get(first, [])),
        "scope_s": dict(seconds),
        "unscoped_s": unscoped,
        "unscoped_ops": sorted(
            unscoped_ops.items(), key=lambda kv: -kv[1])[:5],
        "busy_s": busy,
        "busy": summary["busy"],
        "window": summary["window"],
    }


# -- what the readers ask -----------------------------------------------
def traced_spans(run, which: str, name: str):
    """The program's spans called ``name`` that overlap the window of the
    run's trace ``which`` (``steady`` or ``boundary``), with the trace's
    origin and window; None where the program keeps no ring."""
    records = program_spans()
    if not records:
        return None
    path, origin = run["phase"]["traces"][which]
    trace = by_scope(str(path))
    if trace is not None:
        window = trace["window"]
    else:
        # a trace with no device in it (a rehearsal on the CPU): from the
        # origin to the newest span's end, so that the reader still runs
        window = (0.0, max(r.start_ns + r.dur_ns - origin
                           for r in records) * 1e-9)
    return overlapping(named(records, name), origin, window), origin, window


def median_ms(run, which: str, name: str):
    found = traced_spans(run, which, name)
    if not found or not found[0]:
        return None
    return 1e3 * statistics.median(r.dur_ns * 1e-9 for r in found[0])


def scope_ms_per_step(run, *scopes: str):
    """Milliseconds a step of the steady trace spends under the scopes
    whose names start with any of ``scopes``; 0 where the step has other
    scopes and none of these."""
    path, _ = run["phase"]["traces"]["steady"]
    scoped = by_scope(str(path))
    if scoped is None or not scoped["scope_s"]:
        return None
    steps = run["phase"].get("traced_steps") or scoped["steps"]
    if not steps:
        return None
    total = sum(t for k, t in scoped["scope_s"].items()
                if any(k == s or k.startswith(s + "/") for s in scopes))
    return 1e3 * total / steps
