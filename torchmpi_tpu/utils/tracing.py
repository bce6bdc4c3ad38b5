"""Tracing / profiling / logging utilities.

Reference analogs (SURVEY.md §5):

- nvprof window between fixed steps (``sgdengine.lua:38-63``, ``wrap.sh``
  NVPROF=1) → :class:`ProfilerWindow` around ``jax.profiler`` traces (the
  engine wires this via ``profile_dir``/``profile_window``).
- ``VLOG_1/VLOG_2`` compile-time debug macros with thread ids
  (``resources.h:43-53``) → :func:`vlog` gated by the
  ``TORCHMPI_TPU_DEBUG`` env var (0/1/2).
- per-rank log redirection ``LOG_TO_FILE=1`` → ``/tmp/mpi_<rank>``
  (``wrap.sh:70-77``) → :func:`redirect_logs_per_process`.
- ``torch.Timer`` benchmark timing (``tester.lua``) → :class:`Timer`.
- logical-vs-on-wire byte accounting for the compressed wire formats
  (``wire_dtype``) → :data:`wire_stats` (no reference analog: the 2017
  reference shipped full-precision bytes only).
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from ..analysis import lockmon as _lockmon
from pathlib import Path
from typing import Dict, Optional, Tuple

_DEBUG_LEVEL = int(os.environ.get("TORCHMPI_TPU_DEBUG", "0") or 0)


def debug_level() -> int:
    return _DEBUG_LEVEL


def set_debug_level(level: int) -> None:
    global _DEBUG_LEVEL
    _DEBUG_LEVEL = int(level)


def vlog(level: int, msg: str) -> None:
    """VLOG-style leveled debug logging with thread id (resources.h:43-53)."""
    if _DEBUG_LEVEL >= level:
        tid = threading.get_ident() & 0xFFFF
        print(f"[tm:{level}][t{tid:04x}] {msg}", file=sys.stderr, flush=True)


class Timer:
    """torch.Timer-alike: lap timing for benchmark loops."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self._t0 = time.perf_counter()

    def time(self) -> float:
        return time.perf_counter() - self._t0


class ProfilerWindow:
    """Open a jax.profiler trace for steps [begin, end) — the engine's
    nvprof-window analog, usable standalone:

        win = ProfilerWindow('/tmp/trace', 3, 8)
        try:
            for step in ...:
                win.step(step)   # starts/stops the trace at the boundaries
        finally:
            win.close()          # loops shorter than the window, and
                                 # exception exits, must still stop it

    The trace is of the device alone: the profiler's host and Python
    tracers are off (on a TPU host the host tracer writes a million
    events per copied batch and slows the step it traces). Host-side
    work is in the program's own spans instead (``telemetry/spans.py``),
    and the two join by one subtraction: the trace's origin is set on
    ``time.time_ns()``, the spans' clock, and recorded when the window
    closes as a ``profiler.window`` span whose ``origin_ns`` attribute is
    that origin. An event at ``t`` ns of the trace happened at
    ``origin_ns + t`` on the wall clock; a span of
    ``telemetry.export_trace``'s file with ``ts`` microseconds lies at
    ``ts - origin_ns / 1e3`` microseconds of the trace.
    """

    def __init__(self, log_dir: str, begin: int = 3, end: int = 8):
        begin, end = int(begin), int(end)
        if begin < 0 or end <= begin:
            # a [begin, end) window with end <= begin would start a trace
            # it stops one step late (or never, if the loop ends first)
            raise ValueError(
                f"profiler window must satisfy 0 <= begin < end, got "
                f"[{begin}, {end})"
            )
        self.log_dir = log_dir
        self.begin = begin
        self.end = end
        self.origin_ns: Optional[int] = None
        self._t0 = 0

    @property
    def active(self) -> bool:
        """Whether a trace is currently open (callers that can name the
        in-flight arrays should block on them before the stopping
        ``step``/``close`` so async dispatch tails land in the trace)."""
        return self.origin_ns is not None

    def step(self, step: int) -> None:
        if step == self.begin and not self.active:
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            options.start_timestamp_ns = origin = time.time_ns()
            jax.profiler.start_trace(
                self.log_dir, profiler_options=options
            )
            self.origin_ns, self._t0 = origin, time.perf_counter_ns()
        elif step >= self.end:
            self.close()

    def close(self) -> None:
        if not self.active:
            return
        import jax

        from .. import telemetry

        origin, self.origin_ns = self.origin_ns, None
        try:
            jax.profiler.stop_trace()
        finally:
            telemetry.spans.record(
                telemetry.names.PROFILER_WINDOW, origin,
                time.perf_counter_ns() - self._t0,
                {"origin_ns": origin, "log_dir": str(self.log_dir)},
            )


def redirect_logs_per_process(directory: str = "/tmp", prefix: str = "tm_") -> Path:
    """Redirect this process's stdout/stderr to ``<dir>/<prefix><rank>``
    (wrap.sh LOG_TO_FILE analog). Returns the log path."""
    import jax

    rank = jax.process_index()
    path = Path(directory) / f"{prefix}{rank}"
    f = open(path, "a", buffering=1)
    os.dup2(f.fileno(), sys.stdout.fileno())
    os.dup2(f.fileno(), sys.stderr.fileno())
    return path


@contextlib.contextmanager
def annotate(name: str):
    """Named trace annotation (shows up in the profiler timeline)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


class WireByteCounters:
    """Logical-vs-on-wire byte accounting for the bandwidth-path
    collectives: every eager dispatch through a ring backend records its
    per-rank payload bytes (``logical``) and the bytes its wire encoding
    actually puts on each hop (``wire`` — int8 values padded to whole
    blocks plus one f32 scale per block; bf16 = half; full = identity).
    ``compression_ratio()`` is the observable the wire-format autotuner
    and the acceptance tests read. Thread-safe; counts accumulate until
    :meth:`reset`.

    Accounting model, not a packet capture: bytes are computed from the
    static encoding at dispatch time (compiled executables are cached, so
    in-graph instrumentation would count once per compile, not per call).
    """

    def __init__(self):
        self._lock = _lockmon.make_lock(
            "tracing.py:WireByteCounters._lock"
        )
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.calls = 0
            self.logical_bytes = 0
            self.wire_bytes = 0
            # (op, wire_format) -> [calls, logical, wire]
            self.by_format: Dict[Tuple[str, str], list] = {}

    def record(self, op: str, wire_format: str, logical: int,
               wire: int) -> None:
        with self._lock:
            self.calls += 1
            self.logical_bytes += int(logical)
            self.wire_bytes += int(wire)
            ent = self.by_format.setdefault((op, wire_format), [0, 0, 0])
            ent[0] += 1
            ent[1] += int(logical)
            ent[2] += int(wire)

    def compression_ratio(self) -> float:
        """logical/wire over everything recorded (1.0 when nothing is)."""
        with self._lock:
            if not self.wire_bytes:
                return 1.0
            return self.logical_bytes / self.wire_bytes

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "calls": self.calls,
                "logical_bytes": self.logical_bytes,
                "wire_bytes": self.wire_bytes,
                "compression_ratio": (
                    self.logical_bytes / self.wire_bytes
                    if self.wire_bytes
                    else 1.0
                ),
                "by_format": {
                    f"{op}:{fmt}": tuple(v)
                    for (op, fmt), v in self.by_format.items()
                },
            }


#: process-global wire-format byte counters (see :class:`WireByteCounters`)
wire_stats = WireByteCounters()
