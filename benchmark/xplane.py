"""From the profiler's ``.xplane.pb`` to the numbers the per-layer metrics
report. Read with nothing but jax (``jax.profiler.ProfileData``).

A TPU trace has one plane per chip, ``/device:TPU:<n>``, whose line
``XLA Ops`` holds every operation the core ran (nested where an operation
such as a ``while`` holds others) and whose line ``XLA Modules`` holds one
event per executed program. An event's time is counted from the trace's
origin (``ProfileOptions.start_timestamp_ns``) on the wall clock, the clock
the harness stamps its own ``bench.*`` host spans with; the profiler's host
tracer is off, so the spans are handed in, not read from the trace. All
times below are seconds from the origin.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "allreduce", "psum",
)


@dataclasses.dataclass
class Trace:
    ops: dict       # device plane name -> [(name, start, end)], by start
    modules: dict   # device plane name -> [(name, start, end)]


def find(trace_dir) -> str:
    """The one ``.xplane.pb`` under a directory ``start_trace`` wrote."""
    found = sorted(glob.glob(
        os.path.join(str(trace_dir), "plugins", "profile", "*",
                     "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops, modules = {}, {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                events = sorted(
                    ((e.name, e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9)
                     for e in line.events),
                    key=lambda e: (e[1], -e[2]))
                (ops if line.name == OPS_LINE else modules)[
                    plane.name] = events
    return Trace({k: v for k, v in ops.items() if v}, modules)


def on_trace_clock(spans, origin_ns: int):
    """Wall-clock spans [(name, start, end)] in seconds from the origin."""
    origin = origin_ns * 1e-9
    return sorted(((n, s - origin, e - origin) for n, s, e in spans),
                  key=lambda s: s[1])


# -- interval arithmetic ------------------------------------------------
def union(intervals):
    """Merged, sorted [(start, end)] of any (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(merged_a, merged_b):
    """The part of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in merged_a:
        cur = s
        while j < len(merged_b) and merged_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < e:
            bs, be = merged_b[k]
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged):
    """Idle intervals between the first start and the last end."""
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] > a[1]]


def overlap(a, b) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


# -- per device ---------------------------------------------------------
def is_collective(name: str) -> bool:
    low = name.lower()
    return any(c in low for c in COLLECTIVES)


def leaves(events):
    """The operations themselves, not their containers (``events`` sorted
    by start, then longest first): the events that hold no other event,
    and every collective (an asynchronous one may span compute, which it
    does not hold)."""
    out, stack = [ev for ev in events if is_collective(ev[0])], []
    for ev in events:
        if is_collective(ev[0]):
            continue
        while stack and stack[-1][0][2] <= ev[1]:
            top, has_child = stack.pop()
            if not has_child:
                out.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([ev, False])
    for top, has_child in stack:
        if not has_child:
            out.append(top)
    return out


def device_summary(events) -> dict:
    """Busy time, window, and exposed collective time of one chip."""
    merged = union((s, e) for _, s, e in events)
    ops = leaves(events)
    coll = union((s, e) for n, s, e in ops if is_collective(n))
    compute = union((s, e) for n, s, e in ops if not is_collective(n))
    return {
        "busy": merged,
        "busy_s": length(merged),
        "window": (merged[0][0], merged[-1][1]),
        "window_s": merged[-1][1] - merged[0][0],
        "collective_s": length(coll),
        "exposed_collective_s": length(subtract(coll, compute)),
    }


def op_times(events) -> dict:
    """Seconds by operation name, containers left out."""
    out = defaultdict(float)
    for name, s, e in leaves(events):
        out[name] += e - s
    return dict(out)


def step_count(modules) -> int:
    """Executions of the program that took most of the time: the step."""
    total, count = defaultdict(float), defaultdict(int)
    for name, s, e in modules:
        total[name] += e - s
        count[name] += 1
    if not total:
        return 0
    return count[max(total, key=total.get)]


# -- host against device ------------------------------------------------
def attribute(idle, spans, longest: int = 5):
    """Give each idle interval of a chip to the host span that covers most
    of it (``none`` where no span does). Returns (seconds by span name,
    the longest gaps as [(span name, seconds)])."""
    by_name, each = defaultdict(float), []
    for gap in idle:
        best, cover = "none", 0.0
        for name, s, e in spans:
            if s >= gap[1]:
                break
            c = overlap(gap, (s, e))
            if c > cover:
                best, cover = name, c
        by_name[best] += gap[1] - gap[0]
        each.append((best, gap[1] - gap[0]))
    each.sort(key=lambda g: -g[1])
    return dict(by_name), each[:longest]


def boundary_idle(busy, spans, prefix="bench.epoch_boundary"):
    """For each epoch boundary the host recorded (adjacent spans of that
    prefix are one boundary), the chip's idle time in the gaps that the
    boundary touches, each gap counted whole."""
    bounds = union((s, e) for n, s, e in spans if n.startswith(prefix))
    if not bounds:
        return []
    idle = gaps(busy)
    first, last = busy[0][0], busy[-1][1]
    out = []
    for b in bounds:
        if b[1] <= first or b[0] >= last:
            continue  # before the first or after the last step: no boundary
        out.append(sum(g[1] - g[0] for g in idle if overlap(g, b) > 0))
    return out


def reduce(path, spans=(), origin_ns: int = 0) -> dict:
    """Everything the per-layer metrics read of one trace, averaged over
    its chips where a share is asked for. ``spans`` are the harness's host
    spans by the wall clock, ``origin_ns`` the trace's origin on it."""
    trace = load(path)
    if not trace.ops:
        return {"devices": 0}
    spans = on_trace_clock(spans, origin_ns)
    per = {name: device_summary(ev) for name, ev in trace.ops.items()}
    n = len(per)
    first = sorted(per)[0]
    window = per[first]["window"]
    if spans and not (spans[0][1] - 5.0 <= window[0]
                      and window[1] <= spans[-1][2] + 5.0):
        raise ValueError(
            f"the device's window {window} does not lie among the host's "
            f"spans {spans[0][1]}..{spans[-1][2]}: the clocks disagree")
    times = defaultdict(float)
    for ev in trace.ops.values():
        for name, t in op_times(ev).items():
            times[name] += t / n
    by_span, longest = attribute(gaps(per[first]["busy"]), spans)
    return {
        "devices": n,
        "busy_s": sum(d["busy_s"] for d in per.values()) / n,
        "window_s": sum(d["window_s"] for d in per.values()) / n,
        "collective_s": sum(d["collective_s"] for d in per.values()) / n,
        "exposed_collective_s": sum(
            d["exposed_collective_s"] for d in per.values()) / n,
        "steps": step_count(trace.modules.get(first, [])),
        "op_times": dict(times),
        "idle_by_span": by_span,
        "longest_gaps": longest,
        "boundary_idle_s": boundary_idle(per[first]["busy"], spans),
    }


def breakdown(reduced: dict) -> dict:
    """The ledger's two lists, at most ten entries each."""
    ops = sorted(reduced["op_times"].items(), key=lambda kv: -kv[1])[:10]
    idle = [
        [f"all:{k}", v] for k, v in sorted(
            reduced["idle_by_span"].items(), key=lambda kv: -kv[1])[:5]
    ] + [[f"longest:{k}", v] for k, v in reduced["longest_gaps"][:5]]
    return {
        "device_ops": [[k[:64], v] for k, v in ops],
        "idle_gaps": idle[:10],
    }
