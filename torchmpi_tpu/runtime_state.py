"""Global runtime state: the started flag and the communicator stack.

Analog of the process-global state in ``lib/torch_mpi.cpp:38-51`` (the
``mainThreadCommunicators`` vector and current cursor) plus the start/stop
lifecycle (``torch_mpi.cpp:233-306``).
"""

from __future__ import annotations

import os
import threading
from typing import Callable, List, Optional, Sequence, Union

import jax

from . import constants
from .analysis import lockmon as _lockmon
from .runtime import pools
from .runtime.communicator import (
    Communicator,
    CommunicatorStack,
    KeySpec,
    split_by_keys,
)
from .runtime.handles import sync_all

_lock = _lockmon.make_lock("runtime_state.py:_lock")
_stack: Optional[CommunicatorStack] = None
_started = False


class NotStartedError(RuntimeError):
    pass


def _apply_env_constants() -> None:
    """Apply ``launch --set-constant`` knob overrides (the
    TORCHMPI_TPU_CONSTANTS env var: ``name=value;name=value``). Values
    are coerced to the knob's current type (bool accepts
    1/0/true/false); unknown names or uncoercible values fail loudly —
    a typo'd fabric knob must never launch a silently-misconfigured
    world."""
    spec = os.environ.get("TORCHMPI_TPU_CONSTANTS", "")
    if not spec:
        return
    snap = constants.snapshot()
    for item in spec.split(";"):
        if not item.strip():
            continue
        name, _, raw = item.partition("=")
        name = name.strip()
        if name not in snap:
            raise KeyError(
                f"TORCHMPI_TPU_CONSTANTS names unknown knob {name!r} "
                "(see constants.snapshot() for valid knobs)"
            )
        current, raw = snap[name], raw.strip()
        if isinstance(current, bool):
            low = raw.lower()
            if low in ("1", "true", "yes", "on"):
                value: object = True
            elif low in ("0", "false", "no", "off"):
                value = False
            else:
                raise ValueError(
                    f"TORCHMPI_TPU_CONSTANTS: bool knob {name!r} got "
                    f"{raw!r} (expected 1/0/true/false/yes/no/on/off)"
                )
        elif isinstance(current, int):
            value = int(raw)
        elif isinstance(current, float):
            value = float(raw)
        else:
            value = raw
        constants.set(name, value)


def start(
    with_tpu: Optional[bool] = None,
    with_ici_groups: bool = True,
    custom_communicator_init: Optional[Callable[[], None]] = None,
    with_cartesian_communicator: Optional[bool] = None,
    collective_communicator: Optional[tuple] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    load_tuned_constants: bool = True,
    precompile_collectives: Optional[Sequence] = None,
    **constant_overrides,
) -> None:
    """Initialise the runtime (``MPI.start``, ``torchmpi/init.lua:31-100``).

    - ``with_tpu`` — use accelerator devices (reference ``withCuda``); default
      auto-detect. ``False`` forces CPU devices.
    - ``with_ici_groups`` — build per-host/ICI-domain communicators and set a
      two-level collective span, the analog of ``initPerNodeCommunicators``'s
      "<hostname> cuda p2p group(...)" key + span (``init.lua:417-461``) with
      the cudaIPC p2p-access probe replaced by process/slice locality.
    - ``custom_communicator_init`` — callback run right after start, in which
      user code may :func:`push_communicator` (``init.lua:84-91``).
    - ``with_cartesian_communicator`` — cartesian vs tree mode, set *before*
      building communicators (``init.lua:61-65``).
    - ``collective_communicator`` — explicit ``(begin, end)`` span.
    - ``devices`` — explicit device list (tests build synthetic topologies).
    - ``coordinator_address``/``num_processes``/``process_id`` — multi-
      controller JAX: forwarded to ``jax.distributed.initialize`` (the
      ``MPI_Init`` analog for multi-host TPU pods; on Cloud TPU the
      arguments are auto-detected and may be omitted by passing
      ``coordinator_address=""``). Single-controller runs skip this.
    - ``precompile_collectives`` — declared collective specs (see
      ``collectives.eager.precompile``) compiled AND pinned in the
      executable cache before ``start()`` returns, so step 1 of training
      never pays a collective compile (the AOT warm-up of the latency
      path). Runs AFTER the tuned constants load, against the
      communicator the collectives will actually use.
    - ``**constant_overrides`` — any :mod:`~torchmpi_tpu.constants` knob
      by name (``start(wire_dtype="int8", fusion_buffer_bytes=0)``):
      applied via ``constants.set`` before the runtime bootstraps, and
      RE-applied after the persisted autotuner results load, so an
      explicit override always beats a tuned value. Unknown names raise
      ``KeyError`` before any state changes. Overrides outlive a failed
      or stopped runtime (they are ordinary constants mutations).
    """
    global _stack, _started
    for _name in constant_overrides:
        if _name not in constants.snapshot():
            raise KeyError(
                f"start() got unknown constants override {_name!r} "
                f"(see constants.snapshot() for valid knobs)"
            )
    with _lock:
        if _started:
            raise RuntimeError("torchmpi_tpu.start() called twice")
    # launcher-provided knob overrides (`launch --set-constant NAME=VALUE`)
    # apply first; explicit start(**overrides) beat them
    _apply_env_constants()
    for _name, _value in constant_overrides.items():
        constants.set(_name, _value)
    if with_tpu is False:
        # before the first backend touch (devices/distributed init below):
        # a CPU-only process must not claim the chip, which belongs to one
        # process at a time
        jax.config.update("jax_platforms", "cpu")
    if coordinator_address is None and "TORCHMPI_TPU_COORDINATOR" in os.environ:
        # launcher-provided topology (``python -m torchmpi_tpu.launch``):
        # an unmodified single-process script becomes rank i of N, the
        # way MPI_Init reads its world from mpirun's environment
        coordinator_address = os.environ["TORCHMPI_TPU_COORDINATOR"]
        try:
            if num_processes is None:
                num_processes = int(os.environ["TORCHMPI_TPU_NUM_PROCESSES"])
            if process_id is None:
                process_id = int(os.environ["TORCHMPI_TPU_PROCESS_ID"])
        except KeyError as e:
            raise ValueError(
                "TORCHMPI_TPU_COORDINATOR is set but its companion "
                f"variable {e.args[0]} is missing — export all three "
                "(the launcher sets them together) or pass "
                "coordinator_address/num_processes/process_id explicitly"
            ) from None
    if coordinator_address is None and (
        num_processes is not None or process_id is not None
    ):
        raise ValueError(
            "num_processes/process_id require coordinator_address (pass "
            "coordinator_address='' for Cloud TPU auto-detection)"
        )
    if coordinator_address is not None:
        if not jax.distributed.is_initialized():
            kw = {}
            if coordinator_address:
                kw["coordinator_address"] = coordinator_address
            if num_processes is not None:
                kw["num_processes"] = num_processes
            if process_id is not None:
                kw["process_id"] = process_id
            jax.distributed.initialize(**kw)
    prev_cartesian = constants.get("use_cartesian_communicator")
    with _lock:
        if _started:  # re-check: distributed init released the lock
            raise RuntimeError("torchmpi_tpu.start() called twice")
        if devices is None:
            if with_tpu is None:
                devices = jax.devices()
            elif with_tpu:
                devices = jax.devices()
                if devices[0].platform == "cpu":
                    raise RuntimeError(
                        "with_tpu=True but no accelerator devices present"
                    )
            else:
                devices = jax.devices("cpu")
        # set AFTER every earlier failure point so a failed start() never
        # leaks the cartesian mode into a corrected retry; must still be
        # set before the Communicator is constructed (init.lua:61-65)
        if with_cartesian_communicator is not None:
            constants.set(
                "use_cartesian_communicator", bool(with_cartesian_communicator)
            )
        root = Communicator(list(devices), name="global")
        _stack = CommunicatorStack(root)
        _started = True

    try:
        # clock-sync record: one (wall, perf_counter, monotonic) triple
        # captured at start() — the per-rank offset handshake the offline
        # cross-rank analyzer (telemetry/analyze.py) aligns dumps with
        from . import telemetry

        import socket as _socket

        telemetry.record_clock_sync(
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            rank=int(os.environ.get("TORCHMPI_TPU_PROCESS_ID", -1))
            if "TORCHMPI_TPU_PROCESS_ID" in os.environ
            else jax.process_index(),
            host=_socket.gethostname(),
        )
        if constants.get("watchdog_timeout_seconds") > 0:
            from .telemetry.watchdog import start_watchdog

            start_watchdog(
                float(constants.get("watchdog_timeout_seconds")),
                interval=float(constants.get("watchdog_interval_seconds")),
            )

        if jax.process_count() > 1:
            # Bootstrap the cross-process PS transport HERE, where every
            # process participates (its address exchange is job-global);
            # parameter servers on sub-communicators then only barrier
            # among their own owner processes.
            from .parameterserver.transport import ensure_transport

            ensure_transport()

        if custom_communicator_init is not None:
            custom_communicator_init()

        if with_ici_groups:
            _init_per_node_communicators()

        if collective_communicator is not None:
            _stack.set_span(*collective_communicator)

        if load_tuned_constants and not constants.constants_frozen():
            # apply persisted autotuner results for this (platform, world
            # size) — the measured routing constants survive restarts
            # (c_api.h:93-95's autotuner, made durable)
            try:
                from .utils.autotune import load_tuning

                load_tuning(comm=_stack.current, apply=True)
            except Exception:
                pass  # cache is best-effort; defaults are always safe
            # measured cost-model calibration (schedule.calibrate(),
            # fed by the live telemetry plane) re-applies like the
            # tuned constants: persisted medians beat the analytic
            # plan_cost_* defaults for plans that were actually timed
            try:
                from .schedule import load_calibration

                load_calibration()
            except Exception:
                pass  # calibration is best-effort, like the tuning cache
            # launcher + explicit user overrides beat persisted tuned
            # values (explicit last: it wins over the launcher's too)
            _apply_env_constants()
            for _name, _value in constant_overrides.items():
                constants.set(_name, _value)

        if precompile_collectives:
            # AFTER tuning load: the warmed executables must be the ones
            # the tuned routing constants will select at step time
            from .collectives.eager import precompile as _precompile

            _precompile(precompile_collectives, comm=_stack.current)
    except BaseException:
        # Roll back so a corrected retry of start() works instead of
        # hitting 'called twice' on a half-initialized runtime — including
        # the cartesian constant set earlier in this call.
        with _lock:
            _stack = None
            _started = False
            if not constants.constants_frozen():
                try:
                    constants.set("use_cartesian_communicator", prev_cartesian)
                except Exception:
                    pass
        raise


def _init_per_node_communicators() -> None:
    """Push a per-host (ICI-domain) communicator level and set the 2-level
    collective span — ``initPerNodeCommunicators`` (``init.lua:417-461``)."""
    root = _stack.at(0)
    if root.num_nodes() <= 1:
        return  # single host: the global comm is already one ICI domain
    keys = [f"host{d.process_index} ici group" for d in root.devices]
    level = _stack.push(
        split_by_keys(root, keys, name="per-node ici groups")
    )
    # span (level-1, level): hierarchical collectives compose the per-node
    # intra groups with the cross-node inter comm (init.lua:445-446).
    _stack.set_span(max(0, level - 1), level)


def stop() -> None:
    """Teardown (``torchmpi_stop``, ``torch_mpi.cpp:282-306``): drain async
    work, stop parameter servers, free cached resources."""
    global _stack, _started
    if not _started:
        return
    sync_all()
    from .parameterserver import free_all as _ps_free_all

    _ps_free_all()
    # free cached compiled executables on every stack level (the
    # freeDescriptors sweep of torch_mpi.cpp:282-306 / cache.lua:19-61)
    from .collectives.eager import free_collective_resources

    if _stack is not None:
        for level in range(len(_stack.names())):
            try:
                free_collective_resources(_stack.at(level))
            except Exception:
                pass
    pools.shutdown_all()
    # stop the start()-scoped watchdog (all in-flight work drained above);
    # an env-armed one (launch --watchdog-timeout) is process-lived and
    # survives stop/start cycles
    from .telemetry.watchdog import stop_watchdog

    stop_watchdog(only_source="constants")
    with _lock:
        _stack = None
        _started = False


def started() -> bool:
    return _started


def _require_stack() -> CommunicatorStack:
    if _stack is None:
        raise NotStartedError("call torchmpi_tpu.start() first")
    return _stack


def stack() -> CommunicatorStack:
    return _require_stack()


def current_communicator() -> Communicator:
    return _require_stack().current


def rank() -> int:
    """Rank of this process's first device in the current communicator.

    Ranks are *devices* (reference rank = one MPI process driving one GPU; the
    TPU analog is one mesh position per chip). In single-controller mode one
    process owns every rank, so ``rank()`` is 0 and per-rank data is expressed
    as rank-stacked arrays rather than Python-level offsets; under
    multi-controller JAX each process gets the global index of its first local
    device, so ``rank() < size()`` and reference-style
    ``offset = rank() * per_rank`` sharding work per process. See
    ``local_ranks()`` for all ranks owned by this process.
    """
    comm = current_communicator()
    pid = jax.process_index()
    for i, d in enumerate(comm.devices):
        if d.process_index == pid:
            return i
    return 0


def local_ranks() -> List[int]:
    """All ranks (device indices) of the current communicator owned by this
    process."""
    comm = current_communicator()
    pid = jax.process_index()
    return [i for i, d in enumerate(comm.devices) if d.process_index == pid]


def size() -> int:
    """Number of ranks (devices) in the current communicator."""
    return current_communicator().size


def num_processes() -> int:
    return jax.process_count()


def push_communicator(keys: KeySpec, name: Optional[str] = None) -> int:
    """Split the *current* communicator by keys and push the result
    (``torchmpi_push_communicator`` splits the current level's comm,
    ``torch_mpi.cpp:75-79,251-255``), so keys are parent-local and nested
    splits refine the existing topology. Returns the new level."""
    st = _require_stack()
    comm = split_by_keys(st.current, keys, name=name)
    return st.push(comm)


def set_communicator(level: int) -> None:
    _require_stack().set_current(level)


def set_collective_span(begin: int, end: int) -> None:
    _require_stack().set_span(begin, end)


def communicator_names() -> List[str]:
    return _require_stack().names()


def describe() -> str:
    """Multi-line topology dump of the whole communicator stack — the
    analog of the reference's startup topology print
    (``torch_mpi.cpp:105-127``, ``init.lua:456-459``). Marks the current
    level and the hierarchical collective span."""
    st = _require_stack()
    begin, end = st.span
    lines = [
        f"communicator stack (depth={st.depth}, current level={end}, "
        f"span=[{begin}, {end}])"
    ]
    for level in range(st.depth):
        marker = "*" if level == end else " "
        desc = st.at(level).describe().replace("\n", "\n      ")
        lines.append(f" {marker}[{level}] {desc}")
    return "\n".join(lines)


def num_nodes_in_communicator(level: Optional[int] = None) -> int:
    st = _require_stack()
    comm = st.current if level is None else st.at(level)
    return comm.num_nodes()


def _reset_for_tests() -> None:
    global _stack, _started
    try:
        stop()
    except Exception:
        pass
    _stack = None
    _started = False
