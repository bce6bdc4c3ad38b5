"""ctypes bridge to the native runtime (csrc/tpumpi.cpp).

Loads ``libtpumpi.so`` (rebuilt through the bundled, incremental Makefile
on every load, so the binary always matches ``tpumpi.cpp``) and exposes
the C API. Everything degrades
gracefully: ``available()`` is False when no compiler/library is present and
callers fall back to the pure-Python implementations — the analog of the
reference's optional NCCL/Gloo feature detection (``lib/CMakeLists.txt``).

The constants table is mirrored into C++ through a listener (the C getters
are then the native code's source of truth, like the reference's C
getter/setter pairs).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from ..analysis import lockmon as _lockmon
from pathlib import Path
from typing import Optional

import numpy as np

_CSRC = Path(__file__).resolve().parent.parent / "csrc"
_SO = _CSRC / "libtpumpi.so"

_lib: Optional[ctypes.CDLL] = None
_load_lock = _lockmon.make_lock("native.py:_load_lock")
_load_attempted = False


def _build() -> bool:
    try:
        subprocess.run(
            ["make", "-s"],
            cwd=_CSRC,
            check=True,
            capture_output=True,
            timeout=120,
        )
        return _SO.exists()
    except Exception:
        return False


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.tpumpi_set_constant.argtypes = [c.c_char_p, c.c_int64]
    lib.tpumpi_set_constant.restype = c.c_int
    lib.tpumpi_get_constant.argtypes = [c.c_char_p, c.c_int64]
    lib.tpumpi_get_constant.restype = c.c_int64
    lib.tpumpi_freeze_constants.restype = None
    lib.tpumpi_constants_frozen.restype = c.c_int
    lib.tpumpi_reset_constants.restype = None

    lib.tpumpi_pool_create.argtypes = [c.c_int64]
    lib.tpumpi_pool_create.restype = c.c_int64
    lib.tpumpi_pool_destroy.argtypes = [c.c_int64]
    lib.tpumpi_pool_enqueue_signal.argtypes = [c.c_int64, c.c_int64]
    lib.tpumpi_pool_enqueue_signal.restype = c.c_int

    lib.tpumpi_spmc_create.argtypes = [c.c_int64, c.c_int64]
    lib.tpumpi_spmc_create.restype = c.c_int64
    lib.tpumpi_spmc_enqueue_signal.argtypes = [c.c_int64, c.c_int64]
    lib.tpumpi_spmc_enqueue_signal.restype = c.c_int
    lib.tpumpi_spmc_destroy.argtypes = [c.c_int64]

    lib.tpumpi_handle_create.restype = c.c_int64
    lib.tpumpi_handle_complete.argtypes = [c.c_int64, c.c_int64]
    lib.tpumpi_handle_wait.argtypes = [c.c_int64]
    lib.tpumpi_handle_wait.restype = c.c_int64
    lib.tpumpi_handles_outstanding.restype = c.c_int64

    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    lib.tpumpi_ring_plan.argtypes = [c.c_int64, c.c_int64, i64p, i64p]
    lib.tpumpi_ring_plan.restype = c.c_int64

    u8p = c.POINTER(c.c_uint8)
    lib.tpumpi_ps_create.argtypes = [i64p, c.c_int64, c.c_int, u8p]
    lib.tpumpi_ps_create.restype = c.c_int64
    lib.tpumpi_ps_apply.argtypes = [c.c_int64, c.c_int64, c.c_int64, u8p, c.c_int64]
    lib.tpumpi_ps_apply.restype = c.c_int
    lib.tpumpi_ps_read.argtypes = [c.c_int64, c.c_int64, u8p, c.c_int64]
    lib.tpumpi_ps_read.restype = c.c_int
    lib.tpumpi_ps_free.argtypes = [c.c_int64]
    lib.tpumpi_ps_count.restype = c.c_int64

    lib.tpumpi_barrier_create.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.tpumpi_barrier_create.restype = c.c_int64
    lib.tpumpi_barrier_wait.argtypes = [c.c_int64]
    lib.tpumpi_barrier_wait.restype = c.c_int
    lib.tpumpi_barrier_destroy.argtypes = [c.c_int64]

    lib.tpumpi_version.restype = c.c_char_p


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    with _load_lock:
        if _lib is not None or _load_attempted:
            return _lib
        _load_attempted = True
        # make every time (a no-op when up to date): a binary left from
        # an older tpumpi.cpp is never what executes
        if not _build():
            return None
        try:
            lib = ctypes.CDLL(str(_SO))
            _declare(lib)
            _lib = lib
            _mirror_constants(lib)
        except (OSError, AttributeError):
            # AttributeError: a stale .so missing a newly-added symbol —
            # degrade to the pure-Python fallbacks rather than raising
            # from available().
            _lib = None
            return None
        return _lib


def available() -> bool:
    return get_lib() is not None


def _mirror_constants(lib: ctypes.CDLL) -> None:
    from .. import constants

    def listener(name: str, value) -> None:
        if isinstance(value, bool):
            value = int(value)
        if isinstance(value, int):
            rc = lib.tpumpi_set_constant(name.encode(), value)
            if rc != 0:
                # The native table refused (frozen there but not here):
                # surface the divergence instead of silently disagreeing.
                raise RuntimeError(
                    f"native constants table rejected {name!r} "
                    "(frozen out-of-band?)"
                )

    constants.register_listener(listener)
    constants.register_freeze_listener(
        lambda: lib.tpumpi_freeze_constants()
    )
    if constants.constants_frozen():
        lib.tpumpi_freeze_constants()


# ---------------------------------------------------------------------------
# typed wrappers
# ---------------------------------------------------------------------------


def wait_request(request_id: int) -> int:
    """Wait a native handle (SyncHandle.native_id backend)."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native runtime not available")
    return int(lib.tpumpi_handle_wait(request_id))


def ring_plan(rank: int, size: int):
    """(send, recv) chunk-index schedules (values in [0, size)) for the
    2(p-1) ring steps (the memoized plan of resources.cpp:582-672). Buffers
    with k*size chunks run the same schedule per group of ``size`` chunks."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native runtime not available")
    steps = 2 * (size - 1)
    send = np.zeros(steps, np.int64)
    recv = np.zeros(steps, np.int64)
    n = lib.tpumpi_ring_plan(rank, size, send, recv)
    if n < 0:
        raise ValueError(f"invalid plan request ({rank=}, {size=})")
    return send, recv


class NativeShardStore:
    """C++-side PS shard storage: rules applied outside the GIL (the hybrid
    split of the reference — protocol in the scripting layer, byte-crunching
    in C++)."""

    RULES = {"zero": 0, "copy": 1, "add": 2}

    def __init__(self, shard_sizes, dtype, initial_flat: np.ndarray):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native runtime not available")
        self._lib = lib
        self.dtype = np.dtype(dtype)
        code = {np.dtype(np.float32): 0, np.dtype(np.float64): 1}.get(self.dtype)
        if code is None:
            raise TypeError(f"native PS supports f32/f64, got {self.dtype}")
        sizes = np.asarray(shard_sizes, np.int64)
        flat = np.ascontiguousarray(initial_flat, self.dtype)
        self.shard_sizes = [int(s) for s in sizes]
        self._id = lib.tpumpi_ps_create(
            sizes,
            len(sizes),
            code,
            flat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
        if self._id < 0:
            raise RuntimeError("native PS creation failed")
        self._freed = False

    def apply(self, shard_idx: int, rule: str, incoming: np.ndarray) -> None:
        if self._freed:
            raise RuntimeError("native shard store freed")
        buf = np.ascontiguousarray(incoming, self.dtype)
        rc = self._lib.tpumpi_ps_apply(
            self._id,
            shard_idx,
            self.RULES[rule],
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            buf.size,
        )
        if rc != 0:
            raise RuntimeError(f"native ps_apply failed rc={rc}")

    def read(self, shard_idx: int) -> np.ndarray:
        if self._freed:
            raise RuntimeError("native shard store freed")
        out = np.empty(self.shard_sizes[shard_idx], self.dtype)
        rc = self._lib.tpumpi_ps_read(
            self._id,
            shard_idx,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            out.size,
        )
        if rc != 0:
            raise RuntimeError(f"native ps_read failed rc={rc}")
        return out

    def free(self) -> None:
        if not self._freed:
            self._lib.tpumpi_ps_free(self._id)
            self._freed = True


class NativeBarrier:
    """POSIX named-semaphore intra-host barrier (lib/barrier.cpp analog)."""

    def __init__(self, name: str, size: int, owner: bool = True):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native runtime not available")
        self._lib = lib
        # owner=True unlinks stale semaphores from crashed prior runs;
        # joiner processes pass owner=False and start after the owner.
        self._id = lib.tpumpi_barrier_create(name.encode(), size, int(owner))
        if self._id < 0:
            raise RuntimeError("barrier creation failed")

    def wait(self) -> None:
        rc = self._lib.tpumpi_barrier_wait(self._id)
        if rc != 0:
            raise RuntimeError("barrier wait failed")

    def destroy(self) -> None:
        self._lib.tpumpi_barrier_destroy(self._id)
