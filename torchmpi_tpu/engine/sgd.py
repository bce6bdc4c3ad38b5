"""AllReduceSGD training engine.

Analog of ``torchmpi/engine/sgdengine.lua`` (``tnt.AllReduceSGDEngine``):
a hook-driven training loop that owns the data-parallel synchronization.

Reference behaviors preserved, re-designed for XLA:

- one-shot parameter broadcast before training (``sgdengine.lua:140-144``)
  → ``in_graph_synchronize_parameters`` on step 0, or eager broadcast.
- gradient sum-allreduce every step, the reference's sync mode
  (``sgdengine.lua:126-131``) and its async mode's per-layer overlapped
  allreduce (``sgdengine.lua:91-124``) alike → ONE jitted train step over
  the communicator's mesh with the in-graph psum of the gradient leaves;
  grouping the all-reduces and placing them against backward is XLA's.
- hooks: ``on_start, on_start_epoch, on_sample, on_forward, on_backward,
  on_update, on_end_epoch, on_end`` (the torchnet hook names,
  ``sgdengine.lua:82-135``), each receiving the mutable ``state`` dict.
- profiler window between steps 3 and 8 (``sgdengine.lua:38-63``'s
  nvprof window) → ``jax.profiler`` trace when ``profile_dir`` is set.
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
import zlib
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import nn as mpinn, telemetry as _telemetry
from ..runtime.communicator import Communicator
from ..telemetry import flightrecorder as _flight
from ..telemetry import tracecontext as _tracecontext

_AXIS = "mpi"
_NO_BATCH = object()  # next()'s default: the epoch's iterator is exhausted

_ring = _telemetry.spans  # the process-global span recorder
_names = _telemetry.names

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class _EngineMetrics:
    """The engine's handles in the process-wide registry."""

    def __init__(self):
        m = _telemetry.metrics
        self.steps = m.counter(
            "tm_engine_steps_total", "optimizer steps taken")
        self.epoch_seconds = m.histogram(
            "tm_engine_epoch_seconds",
            "wall time per epoch, to the point where the engine waits for "
            "the chip (train: the epoch's loss read; train_resident: the "
            "epoch program's end)",
        )
        self.examples_per_sec = m.gauge(
            "tm_engine_examples_per_sec",
            "training throughput over the last epoch, its measured input "
            "wait taken out",
        )
        self.mfu = m.gauge(
            "tm_engine_mfu",
            "model-FLOPs utilization vs the chip's bf16 peak over the last "
            "epoch less its measured input wait: what the loop would reach "
            "with input free (engines constructed with flops_per_sample "
            "only)",
        )
        self.tflops = m.gauge(
            "tm_engine_tflops_per_chip",
            "achieved TFLOP/s per chip (flops_per_sample engines)",
        )
        self.mfu_incl_input = m.gauge(
            "tm_engine_mfu_incl_input",
            "MFU over the whole epoch INCLUDING measured input-stall "
            "time — diverges from tm_engine_mfu exactly when the run "
            "is input-bound (streamed-iterator engines only)",
        )
        self.input_stall = m.counter(
            "tm_engine_input_stall_seconds",
            "seconds the training loop spent waiting on the input "
            "iterator (excluded from tm_engine_mfu's window; joins "
            "tm_input_consumer_stall_seconds)",
        )
        self.programs_built = m.counter(
            "tm_engine_programs_built_total",
            "programs jax compiled or loaded from its cache while an "
            "engine existed (one engine.program_build span each)",
        )
        self.program_build_seconds = m.counter(
            "tm_engine_program_build_seconds_total",
            "seconds jax spent compiling or loading those programs",
        )
        self.init_seconds = m.gauge(
            "tm_engine_init_seconds",
            "host seconds the newest engine's construction took (the "
            "copies of the caller's parameters and optimizer state)",
        )


_ENG_MET: Optional[_EngineMetrics] = None


def _engine_metrics() -> _EngineMetrics:
    global _ENG_MET
    if _ENG_MET is None:
        _ENG_MET = _EngineMetrics()
    return _ENG_MET


# The newest engine alive: a program jax builds while it exists is recorded
# as an ``engine.program_build`` span with that engine's step count, so a
# recompilation at step N is seen from inside.
_newest_engine: Callable[[], Optional["AllReduceSGDEngine"]] = lambda: None
_build_listener_lock = threading.Lock()
_build_listener_on = False
_cache_hit = threading.local()


def _on_jax_duration(event: str, seconds: float, **kw) -> None:
    if event == _CACHE_RETRIEVAL:
        # fires inside the backend-compile event of the same program
        _cache_hit.seconds = seconds
        return
    if event != _BACKEND_COMPILE:
        return
    hit = getattr(_cache_hit, "seconds", None)
    _cache_hit.seconds = None
    engine = _newest_engine()
    if engine is None:
        return
    dur = int(seconds * 1e9)
    _ring.record(
        _names.ENGINE_PROGRAM_BUILD, time.time_ns() - dur, dur,
        {"event": event, "seconds": seconds, "from_cache": hit is not None,
         "program": kw.get("fun_name")},
        step=(engine.epochs_run, engine.steps_run),
    )
    met = _engine_metrics()
    met.programs_built.inc()
    met.program_build_seconds.inc(seconds)


def _listen_for_builds(engine: "AllReduceSGDEngine") -> None:
    """Point the process's one jax.monitoring listener at ``engine``."""
    global _newest_engine, _build_listener_on
    with _build_listener_lock:
        _newest_engine = weakref.ref(engine)
        if not _build_listener_on:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            _build_listener_on = True


class _IdRef:
    """Identity key that pins its referent. Hashing/equality are by object
    identity, and the strong reference guarantees the identity stays valid:
    a raw ``id()`` key can collide when the original object is GC'd and a
    new one reuses its address (silently serving a stale jitted executable
    for a *different* model); holding the object makes that impossible —
    the id cannot be recycled while the cache entry (and thus this ref)
    is alive, and ``is`` comparison is exact either way."""

    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        # id-based regardless of the referent's own __hash__, matching the
        # identity equality (and defined even for unhashable referents).
        return object.__hash__(self.obj)

    def __eq__(self, other):
        return isinstance(other, _IdRef) and self.obj is other.obj


def _fn_key(fn) -> Any:
    """Stable cache key for a callable: code object + identities of captured
    closure values. A lambda re-created each call inside a loop shares its
    code object, so keying on the function object itself would miss (and
    recompile) every time; two lambdas from the same source line that close
    over different models still get distinct keys via the cell contents
    (``_IdRef`` pins them, so the keys can never alias across GC)."""
    code = getattr(fn, "__code__", None)
    if code is None:
        return _IdRef(fn)
    cells = getattr(fn, "__closure__", None) or ()
    # __self__ distinguishes bound methods of different instances (their
    # __code__/__closure__ proxy to the one shared class function);
    # __defaults__ distinguishes def f(x, m=model_a) from m=model_b.
    self_obj = getattr(fn, "__self__", None)
    return (
        code,
        _IdRef(self_obj) if self_obj is not None else None,
        tuple(_IdRef(d) for d in (getattr(fn, "__defaults__", None) or ())),
        tuple(_IdRef(c.cell_contents) for c in cells),
    )


def _array_fingerprint(a) -> tuple:
    """Exact content fingerprint (shape, dtype, full-buffer CRC32) used to
    detect in-place mutation of cached eval arrays. Round 3 sampled a
    stride across the buffer, which admitted silent staleness for
    sub-stride writes; a full checksum observes EVERY mutation. crc32
    streams at ~GB/s over the buffer protocol (no copy for contiguous
    arrays) and ``evaluate`` runs once per epoch, so exactness costs
    milliseconds per GB — not a restage, not a recompile."""
    arr = np.asarray(a)
    if arr.size == 0:
        return (arr.shape, arr.dtype.str, 0)
    if not arr.flags.c_contiguous:
        arr = np.ascontiguousarray(arr)
    return (
        arr.shape,
        arr.dtype.str,
        zlib.crc32(memoryview(arr).cast("B")),
    )


class AllReduceSGDEngine:
    """Data-parallel SGD engine over a communicator.

    Parameters
    ----------
    loss_fn : ``loss_fn(params, batch) -> scalar`` per-rank loss.
    params : initial parameter pytree (un-stacked; will be replicated).
    optimizer : an optax GradientTransformation (default: plain SGD).
    comm : communicator (default: current).
    average_gradients : divide the summed gradients by world size. The
        reference sums only (division left to the caller, nn.lua:40);
        True by default here because optax learning rates assume means.
    """

    def __init__(
        self,
        loss_fn: Callable,
        params,
        optimizer: Optional[optax.GradientTransformation] = None,
        comm: Optional[Communicator] = None,
        average_gradients: bool = True,
        broadcast_parameters: bool = True,
        profile_dir: Optional[str] = None,
        profile_window: tuple = (3, 8),
        hooks: Optional[Dict[str, Callable]] = None,
        batch_format: str = "auto",
        model_state=None,
        param_sharding: str = "replicated",
        accum_steps: int = 1,
        remat: bool = False,
        wire_dtype: Optional[str] = None,
        flops_per_sample: Optional[int] = None,
    ):
        """``model_state``: optional mutable-collection pytree (e.g. flax
        ``batch_stats``). When given, ``loss_fn`` must have the signature
        ``loss_fn(params, state, batch) -> (loss, new_state)``; the state is
        pmean-synchronized across ranks every step (cross-replica batch-norm
        statistics).

        ``param_sharding``: 'replicated' (the reference's model — every
        rank holds full params, gradients allreduced), 'fsdp' (ZeRO-3
        style: params/optimizer state SHARDED over the data axis, one
        logical copy; XLA/GSPMD inserts the gather/reduce-scatter
        collectives), or 'zero1' (ZeRO-1: ONLY the optimizer state is
        sharded — the memory win of sharded moments without per-layer
        parameter gathers; the update math runs sharded and the applied
        updates are gathered once per step). fsdp/zero1 require
        average_gradients=True (the loss is a global-batch mean, so
        gradients are means by construction); both are capability
        extensions — the reference has no sharded-optimizer mode.

        ``accum_steps``: gradient accumulation — each step's batch is cut
        into this many microbatches processed sequentially (a scan, so
        only ONE microbatch's activations are live at a time) and the
        averaged gradient drives a single optimizer update. Trades step
        latency for activation memory: the effective batch stays the
        caller's batch. Per-rank batch sizes must be divisible by it.
        Stateless models follow the k=1 trajectory exactly; mutable state
        (batch-norm statistics) gets k microbatch-sized updates per step,
        standard accumulation semantics. Capability extension (the
        reference predates accumulation).

        ``remat``: wrap the loss in ``jax.checkpoint`` — backward
        recomputes the forward instead of keeping its activations live
        (HBM traded for one extra forward). Composes with ``accum_steps``
        (remat within each microbatch) and with models' own per-layer
        remat; gradients are bit-identical by construction.

        ``wire_dtype``: on-wire encoding for the gradient allreduce
        ('full' | 'bf16' | 'int8'; None = the autotuned constants
        default). A compressed encoding sends the float32 gradients
        through the compressed-wire ring as one flat buffer
        (block-quantized send, f32 accumulate). Replicated
        param_sharding only: fsdp/zero1 leave the collectives to GSPMD,
        which has no wire-format hook.

        ``flops_per_sample``: analytic per-sample training FLOPs (see
        ``utils/flops.py``). Only consulted when telemetry is enabled:
        each epoch's throughput is converted to achieved TFLOP/s and MFU
        gauges where the engine already waits for the chip (the epoch's
        loss read in ``train``, the epoch program's end in
        ``train_resident``). Telemetry changes neither the compiled step
        nor where the host waits: the engine's own spans
        (``telemetry/spans.py``) are recorded whatever the switch says."""
        if comm is None:
            from .. import runtime_state

            comm = runtime_state.current_communicator()
        # the engine's own count of what it has run, over its lifetime:
        # the (epoch, step) its spans carry, and the ordinal of per-step
        # trace-context roots (every SPMD rank advances it identically,
        # so step N is ONE trace fleet-wide)
        self.epochs_run = 0
        self.steps_run = 0
        if batch_format not in ("auto", "flat", "stacked"):
            raise ValueError(
                f"batch_format must be auto/flat/stacked, got {batch_format!r}"
            )
        if param_sharding not in ("replicated", "fsdp", "zero1"):
            raise ValueError(
                "param_sharding must be replicated/fsdp/zero1, got "
                f"{param_sharding!r}"
            )
        if param_sharding in ("fsdp", "zero1") and not average_gradients:
            raise ValueError(
                f"param_sharding={param_sharding!r} requires "
                "average_gradients=True (the global-batch loss already "
                "yields mean gradients)"
            )
        if not isinstance(accum_steps, int) or accum_steps < 1:
            raise ValueError(
                f"accum_steps must be a positive int, got {accum_steps!r}"
            )
        if wire_dtype not in (None, "full", "bf16", "int8"):
            raise ValueError(
                "wire_dtype must be None/'full'/'bf16'/'int8', got "
                f"{wire_dtype!r}"
            )
        if wire_dtype is None:
            # the docstring contract: None = the (autotuned) constants
            # default. Resolved HERE, once — the step function is
            # compiled against this decision. fsdp/zero1 have no
            # wire-format hook (GSPMD collectives), so the constants
            # default only binds on the replicated path.
            from .. import constants

            wire_dtype = (
                constants.get("wire_dtype")
                if param_sharding == "replicated"
                else "full"
            )
        if wire_dtype in ("bf16", "int8") and param_sharding != "replicated":
            raise ValueError(
                f"wire_dtype={wire_dtype!r} requires "
                "param_sharding='replicated' (fsdp/zero1 collectives are "
                "inserted by GSPMD, which has no wire-format hook)"
            )
        self.wire_dtype = wire_dtype
        self.flops_per_sample = flops_per_sample
        self.accum_steps = accum_steps
        self.param_sharding = param_sharding
        self.batch_format = batch_format
        self.comm = comm
        self.loss_fn = jax.checkpoint(loss_fn) if remat else loss_fn
        self.remat = remat
        self.optimizer = optimizer or optax.sgd(0.2)
        self.average_gradients = average_gradients
        self.broadcast_parameters = broadcast_parameters
        self.profile_dir = profile_dir
        self.profile_window = profile_window
        self.hooks = hooks or {}
        _listen_for_builds(self)
        with _ring.span(_names.ENGINE_INIT, step=(0, 0)) as init:
            self._place_state(params, model_state)
        _engine_metrics().init_seconds.set(init.seconds)

    def _place_state(self, params, model_state) -> None:
        """Construction's work on the device: the mesh, the engine's own
        copies of the caller's parameters, model state and optimizer
        state, and the (lazily compiled) step."""
        comm, broadcast_parameters = self.comm, self.broadcast_parameters
        self.mesh = comm.flat_mesh(_AXIS)
        self.batch_sharding = NamedSharding(self.mesh, P(_AXIS))
        self.replicated = NamedSharding(self.mesh, P())

        def _sharded_leaf(a) -> NamedSharding:
            # shard along the first axis divisible by the world size
            # (falls back to replication for small/odd leaves)
            p = self.comm.size
            for i, dim in enumerate(np.shape(a)):
                if dim >= p and dim % p == 0:
                    return NamedSharding(
                        self.mesh, P(*([None] * i), _AXIS)
                    )
            return self.replicated

        def _leaf_sharding(a, shard: bool) -> NamedSharding:
            return _sharded_leaf(a) if shard else self.replicated

        # Which trees are sharded: fsdp shards params + optimizer state
        # (ZeRO-3); zero1 shards ONLY the optimizer state (ZeRO-1 — the
        # memory win of sharded moments without per-layer param gathers).
        shard_params = self.param_sharding == "fsdp"
        shard_opt = self.param_sharding in ("fsdp", "zero1")

        # Place initial params/opt state. Copy defensively: device_put may
        # alias the caller's buffers when the sharding already matches
        # (single device), and the jitted step DONATES its inputs —
        # without the copy, the caller's params would be deleted by the
        # first step.
        def _own(tree, shard: bool):
            return jax.tree_util.tree_map(
                lambda a: jax.device_put(
                    jnp.array(a, copy=True), _leaf_sharding(a, shard)
                ),
                tree,
            )

        if (
            self.param_sharding in ("fsdp", "zero1")
            and broadcast_parameters
            and jax.process_count() > 1
        ):
            # the one-shot replica equalization happens BEFORE sharding in
            # fsdp mode: each process's shards are filled from its host
            # copy, so differing per-process inits must be reconciled here
            # (afterwards there is exactly one logical copy)
            from jax.experimental import multihost_utils

            params = multihost_utils.broadcast_one_to_all(params)
            if model_state is not None:
                model_state = multihost_utils.broadcast_one_to_all(model_state)

        self.params = _own(params, shard_params)
        self.model_state = (
            _own(model_state, shard_params)
            if model_state is not None
            else None
        )
        self.opt_state = _own(self.optimizer.init(params), shard_opt)
        # Pin output shardings for the GSPMD step: without the constraint,
        # propagation from the sharded optimizer math could migrate the
        # (zero1) replicated params to a sharded layout after one step.
        # Read them off the just-placed trees so placement and constraint
        # can never diverge.
        def _shardings_of(tree):
            return jax.tree_util.tree_map(lambda a: a.sharding, tree)

        self._out_shardings = (
            _shardings_of(self.params),
            _shardings_of(self.opt_state),
            (
                _shardings_of(self.model_state)
                if self.model_state is not None
                else None
            ),
            self.replicated,
        )
        self._step_fn = self._build_step()
        self._bcast_fn = self._build_broadcast()
        self._epoch_fns: Dict[tuple, Callable] = {}
        self._eval_fns: Dict[Any, Callable] = {}
        self._eval_data: Dict[tuple, tuple] = {}
        self._aot_steps: Dict[tuple, Any] = {}  # precompile() executables
        # checkpoint_every(): the async rollback-artifact hook
        self._ckpt_every = 0
        self._ckpt_path = None
        self._ckpt_counter = 0
        self._ckpt_thread: Optional[threading.Thread] = None
        self._ckpt_warned = False

    # ------------------------------------------------------------------
    def _accum_value_and_grad(self, params, model_state, batch, split_fn):
        """Microbatched value_and_grad: ``split_fn`` cuts each batch leaf
        into ``accum_steps`` equal microbatches (leading axis k), a scan
        accumulates gradients/loss (one microbatch's activations live at a
        time — the memory point of accumulation), and the mean is returned.
        Equal microbatch sizes make mean-of-means == full-batch mean, so
        for stateless models accum_steps=k follows the k=1 trajectory
        exactly (tested). Models with mutable state (e.g. batch-norm
        statistics) apply k sequential microbatch-sized state updates per
        step instead of one full-batch update — standard accumulation
        semantics, NOT bit-identical to k=1 for the state."""
        k = self.accum_steps
        loss_fn = self.loss_fn
        has_state = model_state is not None
        micro = jax.tree_util.tree_map(split_fn, batch)

        def body(carry, mb):
            gsum, state = carry
            if has_state:
                (loss, state), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, state, mb
                )
            else:
                loss, g = jax.value_and_grad(loss_fn)(params, mb)
            gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
            # loss rides the scan OUTPUT (stacked [k]), not the carry: a
            # carry accumulator would need the loss dtype up front
            return (gsum, state), loss

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (gsum, new_state), losses = jax.lax.scan(
            body, (zeros, model_state), micro
        )
        grads = jax.tree_util.tree_map(lambda g: g / k, gsum)
        return jnp.mean(losses), new_state, grads

    def _value_and_grad(self, params, model_state, batch, split):
        """(loss, new model state, gradients) of one step's batch, under
        the ``tm.fwd_bwd`` scope; ``split`` cuts a batch leaf into
        ``accum_steps`` microbatches."""
        loss_fn = self.loss_fn
        with jax.named_scope(_names.SCOPE_FWD_BWD):
            if self.accum_steps > 1:
                return self._accum_value_and_grad(
                    params, model_state, batch, split
                )
            if model_state is not None:
                (loss, new_state), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, model_state, batch)
                return loss, new_state, grads
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            return loss, model_state, grads

    def _apply_update(self, params, opt_state, grads):
        with jax.named_scope(_names.SCOPE_OPTIMIZER):
            updates, opt_state = self.optimizer.update(
                grads, opt_state, params
            )
            return optax.apply_updates(params, updates), opt_state

    def _step_core(self, params, opt_state, model_state, batch):
        """Per-rank step body (inside shard_map): grad, sync, update. The
        named scopes are metadata on the operations (the schedule is
        XLA's as before): a device trace puts each operation down to one
        of them by its ``op_name``."""
        k = self.accum_steps

        def split(a):
            if a.shape[0] % k:
                raise ValueError(
                    f"per-rank batch {a.shape[0]} not divisible by "
                    f"accum_steps={k}"
                )
            return a.reshape((k, a.shape[0] // k) + a.shape[1:])

        loss, new_state, grads = self._value_and_grad(
            params, model_state, batch, split
        )
        if model_state is not None:
            with jax.named_scope(_names.SCOPE_STATE_SYNC):
                new_state = jax.tree_util.tree_map(
                    lambda s: jax.lax.pmean(s, _AXIS), new_state
                )
        # opens tm.grad_sync and its phases itself. At full wire the
        # compiled step holds no flat buffer (fusion_buffer_bytes governs
        # the eager FusionBuffer only): on the chip the packing cost twice
        # the all-reduce it fed (PERF.md)
        grads = mpinn.in_graph_synchronize_gradients(
            grads, _AXIS, average=self.average_gradients,
            wire_dtype=self.wire_dtype,
        )
        params, opt_state = self._apply_update(params, opt_state, grads)
        with jax.named_scope(_names.SCOPE_LOSS_SYNC):
            loss = jax.lax.pmean(loss, _AXIS)
        return params, opt_state, new_state, loss

    def _fsdp_step_core(self, params, opt_state, model_state, batch):
        """GSPMD step: ONE logical computation over the global batch; the
        sharded params/opt-state make XLA insert the all-gathers before
        use and reduce-scatter the gradients — ZeRO-3 for free from the
        sharding annotations. There is no sync call to scope: a
        collective GSPMD inserts carries the scope of the operation it
        was inserted for, so this step shows ``tm.fwd_bwd`` and
        ``tm.optimizer`` alone."""
        k, p = self.accum_steps, self.comm.size

        def split(a):
            n = a.shape[0]
            if n % (p * k):
                raise ValueError(
                    f"global batch {n} not divisible by world size x "
                    f"accum_steps = {p}x{k}"
                )
            # rank-major [p, k, b, ...]: each microbatch takes b rows
            # from EVERY rank's contiguous shard, so the batch axis
            # stays evenly sharded through the scan
            b = n // (p * k)
            a = a.reshape((p, k, b) + a.shape[1:])
            a = jnp.moveaxis(a, 1, 0)  # [k, p, b, ...]
            return a.reshape((k, p * b) + a.shape[3:])

        loss, new_state, grads = self._value_and_grad(
            params, model_state, batch, split
        )
        params, opt_state = self._apply_update(params, opt_state, grads)
        return params, opt_state, new_state, loss

    def _build_step(self):
        # The step's program is named ``tm_train_step`` (the resident
        # epoch's ``tm_epoch``): a stable name in a device trace's module
        # line. It is also part of the persistent compilation cache's key,
        # which scope names are not (jax strips metadata from the key): a
        # change to the scopes alone must change this name too, or a warm
        # cache hands back the old executable with the old scopes (the
        # name was ``tm_step`` until the language models named their parts).
        if self.param_sharding in ("fsdp", "zero1"):
            def tm_train_step(params, opt_state, model_state, batch):
                return self._fsdp_step_core(
                    params, opt_state, model_state, batch)

            return jax.jit(
                tm_train_step,
                donate_argnums=(0, 1, 2),
                out_shardings=self._out_shardings,
            )
        shmapped = jax.shard_map(
            self._step_core,
            mesh=self.mesh,
            in_specs=(P(), P(), P(), P(_AXIS)),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )

        def tm_train_step(params, opt_state, model_state, batch):
            return shmapped(params, opt_state, model_state, batch)

        return jax.jit(tm_train_step, donate_argnums=(0, 1, 2))

    def _build_broadcast(self):
        if self.param_sharding in ("fsdp", "zero1"):
            # one logical (sharded or replicated-under-GSPMD) copy:
            # nothing to equalize at step time (multi-process init
            # divergence was reconciled host-side in __init__)
            return lambda p: p
        bcast = jax.shard_map(
            lambda p: mpinn.in_graph_synchronize_parameters(p, _AXIS, 0),
            mesh=self.mesh,
            in_specs=P(),
            out_specs=P(),
            check_vma=False,
        )
        return jax.jit(bcast)

    # ------------------------------------------------------------------
    # telemetry plumbing. Nothing here waits for the chip or changes the
    # compiled step: per-step records are stamped at dispatch, and the
    # gauges that describe a rate are set once an epoch, where the engine
    # waits for the chip anyway.
    # ------------------------------------------------------------------
    def _dispatch(self, batch):
        """``_call_step`` under its span (always) and, with telemetry on,
        under the step's trace-context root, with the labelled step count
        and the flight recorder's ``engine.step`` event: all stamped at
        dispatch, none waits for the step."""
        _ring.set_step(self.epochs_run, self.steps_run)
        telemetry_on = _telemetry.enabled()
        # each step is one causal trace root: the ids are derived from
        # the step ordinal, so every SPMD rank running the same program
        # lands on the SAME trace id for the same step and the analyzer
        # can group cross-rank work per step
        root = (
            _tracecontext.use(
                _tracecontext.new_trace("engine.step", self.steps_run + 1))
            if telemetry_on else contextlib.nullcontext()
        )
        with root:
            t0 = time.time()
            with _ring.span(_names.ENGINE_DISPATCH):
                out = self._call_step(batch)
            if telemetry_on:
                _engine_metrics().steps.inc(sharding=self.param_sharding)
                if _flight.enabled():
                    # step events join the comm's flight stream (wall-clock
                    # stamps): per-seq issue-time spread across ranks is
                    # the analyzer's engine-level straggler signal
                    examples = jax.tree_util.tree_leaves(batch)[0].shape[0]
                    _flight.recorder.record_complete(
                        _flight.comm_key(self.comm), "engine.step",
                        t0, time.time(),
                        payload=f"examples={examples},steps=1",
                    )
        self.steps_run += 1
        return out

    def _record_epoch(self, examples: int, seconds: float,
                      input_stall_s: float = 0.0) -> None:
        """Telemetry at an epoch's end, after the engine waited for the
        chip. ``seconds`` is the epoch's wall time and ``input_stall_s``
        the part of it the training thread waited on its iterator.
        Throughput and MFU come from the rest, what the loop would reach
        with input free, and ``tm_engine_mfu_incl_input`` from the whole,
        so the gap IS the input-bound verdict. (In a loop that never
        blocks the device may work through a wait, so the first is an
        upper bound; the second is what the user got.)"""
        met = _engine_metrics()
        stall = min(max(float(input_stall_s), 0.0), seconds)
        dt = max(seconds - stall, 1e-12)
        met.epoch_seconds.observe(seconds)
        rate = examples / dt
        met.examples_per_sec.set(rate)
        if stall > 0:
            met.input_stall.inc(stall)
        if self.flops_per_sample:
            from ..utils.flops import mfu

            achieved, frac = mfu(
                rate / self.comm.size, self.flops_per_sample,
                self.comm._devices[0],
            )
            met.tflops.set(achieved / 1e12)
            if frac is not None:
                met.mfu.set(frac)
                met.mfu_incl_input.set(frac * dt / (dt + stall))

    # ------------------------------------------------------------------
    # AOT warm-up (the latency path): declare the collectives and compile
    # the step executable BEFORE training so step 1 pays dispatch only.
    # ------------------------------------------------------------------
    def collective_specs(self):
        """Declared eager-collective specs derived from the params
        template — the EXACT executables the eager gradient-sync paths
        for this model would compile: one ``{"layout": per-leaf
        widths}`` dict per dtype group (the coalesced plan
        ``nn.synchronize_gradients`` flushes through ``run_fused`` — a
        ``(p, total)`` spec would warm a cache key nothing ever
        dispatches). Feed to ``collectives.precompile`` (or
        ``start(precompile_collectives=...)``) so the eager latency path
        never compiles at step time. Empty for fsdp/zero1 (GSPMD owns
        those collectives)."""
        if self.param_sharding != "replicated":
            return []
        wire = self.wire_dtype if self.wire_dtype != "full" else None
        # per dtype group, per-leaf widths in tree order — the fused
        # group synchronize_gradients submits leaf-by-leaf
        by_dtype: Dict = {}
        for leaf in jax.tree_util.tree_leaves(self.params):
            by_dtype.setdefault(jnp.result_type(leaf), []).append(
                int(np.prod(np.shape(leaf)))
            )
        return [
            {
                "op": "allreduce",
                "layout": tuple(widths),
                "dtype": dt,
                "wire_dtype": wire,
            }
            for dt, widths in by_dtype.items()
        ]

    def _aot_key(self, batch) -> tuple:
        return tuple(
            (tuple(a.shape), str(jnp.result_type(a)))
            for a in jax.tree_util.tree_leaves(batch)
        )

    def precompile(self, batch) -> None:
        """AOT-compile the jitted training step for ``batch``'s shape (and
        warm + pin the eager collective cache from
        :meth:`collective_specs`), so the first real step compiles
        nothing. ``batch`` may be a concrete sample batch or a pytree of
        ``jax.ShapeDtypeStruct``-shaped arrays; only shapes/dtypes are
        read. The compiled executable is used automatically by
        :meth:`step`/:meth:`train` for matching batch shapes."""
        from ..collectives.eager import precompile as _eager_precompile

        specs = self.collective_specs()
        if specs:
            _eager_precompile(specs, comm=self.comm)

        def aval_of(a):
            try:
                return jax.ShapeDtypeStruct(
                    a.shape, jnp.result_type(a), sharding=a.sharding
                )
            except (AttributeError, TypeError):
                return jax.ShapeDtypeStruct(np.shape(a), jnp.result_type(a))

        batch = self._prepare_batch(
            jax.tree_util.tree_map(
                lambda a: jnp.zeros(np.shape(a), jnp.result_type(a)), batch
            )
        )
        tree_avals = jax.tree_util.tree_map
        args = (
            tree_avals(aval_of, self.params),
            tree_avals(aval_of, self.opt_state),
            (
                tree_avals(aval_of, self.model_state)
                if self.model_state is not None
                else None
            ),
            tree_avals(aval_of, batch),
        )
        self._aot_steps[self._aot_key(batch)] = (
            self._step_fn.lower(*args).compile()
        )

    def _call_step(self, batch):
        """Dispatch one step through the AOT executable when one matches,
        else the lazily-compiling jit (identical semantics, including
        donation)."""
        args = (self.params, self.opt_state, self.model_state, batch)
        if self._aot_steps:
            fn = self._aot_steps.get(self._aot_key(batch))
            if fn is not None:
                try:
                    return fn(*args)
                except (TypeError, ValueError):
                    # aval/sharding drift (e.g. params replaced
                    # wholesale) is rejected at DISPATCH time, before
                    # donation consumes anything: drop the stale
                    # executable, fall back to jit. Runtime failures
                    # (XlaRuntimeError, OOM) propagate — retrying after
                    # donation would run on deleted buffers and mask the
                    # real error.
                    self._aot_steps.pop(self._aot_key(batch), None)
        return self._step_fn(*args)

    # ------------------------------------------------------------------
    # public step API (drivers/benches must not reach into privates)
    # ------------------------------------------------------------------
    def step(self, batch):
        """Run one jitted training step on ``batch`` and return the loss.

        ``batch`` may be flat ``[p*B, ...]`` or rank-stacked ``[p, B, ...]``
        (see ``batch_format``). Updates ``self.params/opt_state/model_state``
        in place. The returned loss is a device scalar, not blocked on,
        with telemetry on or off: a single step has no point at which it
        waits for the chip, so it sets no rate gauge (``train`` does, at
        each epoch's end).
        """
        batch = self._prepare_batch(batch)
        self.params, self.opt_state, self.model_state, loss = (
            self._dispatch(batch)
        )
        self._maybe_checkpoint()
        return loss

    # ------------------------------------------------------------------
    # checkpoint_every: the async rollback-artifact hook
    # ------------------------------------------------------------------
    def checkpoint_every(self, steps: int, path,
                         start_step: int = 0) -> None:
        """Arm periodic async checkpointing: every ``steps`` calls to
        :meth:`step`, the engine saves a portable sharded checkpoint
        (:func:`~..utils.checkpoint.save_engine_sharded`: atomic
        ``CURRENT`` pointer, any-world restore) to ``path`` on a
        background thread and registers it as the newest rollback
        artifact (:mod:`~..supervise.checkpoints`) — the artifact the
        supervisor's rollback rung and a ``--max-restarts`` relaunch
        restore from. One save in flight at a time: a boundary reached
        while the previous save is still writing is skipped, not
        queued (the registry is a recency floor, not a history).
        ``steps=0`` disarms. A resumed run passes ``start_step`` (the
        restored checkpoint's step) so the saved step numbers continue
        the training trajectory instead of restarting at 0."""
        if int(steps) < 0:
            raise ValueError(
                f"checkpoint_every expects steps >= 0, got {steps}"
            )
        self._ckpt_every = int(steps)
        self._ckpt_path = path
        self._ckpt_counter = int(start_step)

    def _maybe_checkpoint(self) -> None:
        if not self._ckpt_every:
            return
        self._ckpt_counter += 1
        if self._ckpt_counter % self._ckpt_every:
            return
        t = self._ckpt_thread
        if t is not None and t.is_alive():
            return  # previous save still in flight
        step = self._ckpt_counter
        # materialize the state to HOST numpy on the step thread: jax
        # arrays are immutable but not undeletable — the next step()'s
        # donation consumes the old buffers, so a writer thread holding
        # device refs races an "Array has been deleted" error. The
        # device->host copy is the synchronous part (the span: what a
        # save costs the training thread); the file I/O (the slow part)
        # stays on the background thread.
        with _ring.span(_names.ENGINE_CHECKPOINT, {"ckpt_step": step}):
            state = {"params": self.params, "opt_state": self.opt_state}
            if self.model_state is not None:
                state["model_state"] = self.model_state
            state = jax.tree_util.tree_map(
                lambda a: np.asarray(jax.device_get(a)), state
            )
            self._ckpt_thread = threading.Thread(
                target=self._save_checkpoint, args=(step, state),
                name="tm-engine-ckpt", daemon=True,
            )
            self._ckpt_thread.start()

    def _save_checkpoint(self, step: int, state) -> None:
        import sys

        from ..utils import checkpoint as _ckpt

        try:
            _ckpt.save_engine_sharded(
                self._ckpt_path, self, step=step, state=state
            )
        except Exception as e:  # noqa: BLE001 - a failed async save must
            # never take the training loop down, but a save that ALWAYS
            # fails means no rollback artifact ever exists — say so once
            if not self._ckpt_warned:
                self._ckpt_warned = True
                print(
                    f"[engine] checkpoint_every save to "
                    f"{self._ckpt_path} failed: {e!r} (further "
                    "failures suppressed)",
                    file=sys.stderr,
                )

    def flush_checkpoint(self, timeout: float = 60.0) -> None:
        """Join any in-flight async save (call before a deliberate exit
        so the newest artifact is published)."""
        t = self._ckpt_thread
        if t is not None:
            t.join(timeout=timeout)

    def broadcast_parameters_now(self):
        """One-shot replica equalization (sgdengine.lua:140-144), blocking."""
        with _ring.span(_names.ENGINE_BROADCAST):
            self.params = jax.block_until_ready(self._bcast_fn(self.params))

    # ------------------------------------------------------------------
    # live world resize: redistribute fsdp/zero1 shards in place
    # ------------------------------------------------------------------
    def _leaf_shard_axis(self, leaf) -> Optional[int]:
        """The mesh-sharded axis of a live leaf (None = replicated)."""
        spec = getattr(getattr(leaf, "sharding", None), "spec", None)
        if not spec:
            return None
        for i, s in enumerate(spec):
            if s == _AXIS:
                return i
        return None

    def _resize_leaf(self, leaf, shard_tree: bool, new_comm, new_mesh,
                     stats: Dict[str, Any]):
        """Move one leaf onto the resized mesh through the reshard
        planner. Same-axis shard moves run the minimal chunked transfer
        schedule (owner-stable bytes never copied twice, scratch bounded
        by ``reshard_chunk_bytes``); axis changes and replicated targets
        assemble the full leaf (a replicated target *is* the full leaf on
        every rank)."""
        from .. import constants as _c
        from ..reshard import Layout, Redistributor

        p_new = new_comm.size
        shape = tuple(np.shape(leaf))
        dt = np.dtype(leaf.dtype)
        replicated_new = NamedSharding(new_mesh, P())

        def _new_leaf_sharding() -> NamedSharding:
            if not shard_tree:
                return replicated_new
            for i, dim in enumerate(shape):
                if dim >= p_new and dim % p_new == 0:
                    return NamedSharding(new_mesh, P(*([None] * i), _AXIS))
            return replicated_new

        dst_sharding = _new_leaf_sharding()
        src_ax = self._leaf_shard_axis(leaf)
        dst_ax = None
        for i, s in enumerate(dst_sharding.spec):
            if s == _AXIS:
                dst_ax = i
        largest = max(
            (int(np.prod(np.asarray(s.data).shape)) * dt.itemsize
             for s in leaf.addressable_shards),
            default=0,
        )
        stats["largest_shard_bytes"] = max(
            stats["largest_shard_bytes"], largest
        )

        if src_ax is None and dst_ax is None:
            # replicated -> replicated: same bytes, new mesh
            return jax.device_put(np.asarray(jax.device_get(leaf)),
                                  dst_sharding)
        if src_ax is not None and dst_ax is not None and src_ax != dst_ax:
            # axis migration (the divisible axis moved under the new
            # world): no contiguous flat mapping exists — assemble once
            stats["axis_fallbacks"] += 1
            return jax.device_put(np.asarray(jax.device_get(leaf)),
                                  dst_sharding)

        ax = src_ax if src_ax is not None else dst_ax
        n = int(np.prod(shape, dtype=np.int64))
        p_old = self.comm.size
        src_layout = Layout(p_old, "sharded" if src_ax is not None
                            else "replicated")
        # a replicated destination only needs ONE host assembly (jax
        # replicates it across the mesh at device_put): a Layout(p_new,
        # 'replicated') target would transfer the full leaf to p_new
        # buffers of which only outs[0] is read — p_new x the memory and
        # copy work on exactly the bounded-memory path
        dst_layout = (Layout(p_new) if dst_ax is not None else Layout(1))
        # moveaxis space: rank blocks along `ax` become contiguous flat
        # intervals, and divisibility (the engine's sharding rule) makes
        # the element-space Layout boundaries land exactly on row edges
        moved_shape = (shape[ax],) + tuple(
            d for i, d in enumerate(shape) if i != ax
        )
        if src_ax is None:
            full = np.moveaxis(np.asarray(jax.device_get(leaf)), ax, 0)
            flat_src = full.reshape(-1)

            def read(rank, off, view):
                # replicated source transfers carry GLOBAL offsets
                view[:] = flat_src[off:off + view.shape[0]]
        else:
            blocks: Dict[int, np.ndarray] = {}
            bs = shape[ax] // p_old
            for s in leaf.addressable_shards:
                r = (s.index[ax].start or 0) // bs
                blocks[r] = np.moveaxis(
                    np.asarray(s.data), ax, 0
                ).reshape(-1)

            def read(rank, off, view):
                view[:] = blocks[rank][off:off + view.shape[0]]

        rd = Redistributor(n, dt, src_layout, dst_layout)
        outs = {
            r: np.empty(max(0, e - s), dt)
            for r, (s, e) in enumerate(dst_layout.intervals(n))
        }

        def write(rank, off, values):
            outs[rank][off:off + values.shape[0]] = values

        rd.run(read, write)
        stats["peak_scratch_bytes"] = max(
            stats["peak_scratch_bytes"], rd.peak_scratch_bytes
        )
        stats["wire_elements"] += sum(
            t.n for t in rd.transfers if t.src != t.dst
        )
        stats["plans"].append(rd.plan.plan_id)

        if dst_ax is None:
            full = outs[0].reshape(moved_shape)
            return jax.device_put(np.moveaxis(full, 0, ax), dst_sharding)
        nbs = shape[ax] // p_new
        host_blocks = {}
        for r, buf in outs.items():
            blk = buf.reshape((nbs,) + moved_shape[1:])
            host_blocks[r] = np.ascontiguousarray(np.moveaxis(blk, 0, ax))

        def cb(index):
            return host_blocks[(index[ax].start or 0) // nbs]

        return jax.make_array_from_callback(shape, dst_sharding, cb)

    def resize(self, devices) -> Dict[str, Any]:
        """Resize the engine's world IN PLACE: redistribute the sharded
        param/optimizer state onto ``devices`` (grow or shrink) and
        rebuild the compiled step — training continues on the next
        ``step()`` call with no checkpoint restore.

        Every sharded leaf is moved through the reshard planner's minimal
        transfer schedule (owner-stable elements never copied through the
        scratch, chunked to ``reshard_chunk_bytes``) and lands bitwise
        equal to a fresh ``len(devices)``-way scatter of the gathered
        state. The ``resize_epoch`` constant is bumped (advancing
        ``constants.generation()``) so every generation-stamped cache —
        dispatch memos, plan cache, compiled reshard schedules —
        invalidates coherently; the engine's own epoch/eval/AOT caches
        are dropped here.

        Returns a stats dict: ``epoch``, ``old_world``, ``new_world``,
        ``peak_scratch_bytes`` (the asserted < 2x largest-shard memory
        bound), ``largest_shard_bytes``, ``wire_elements``,
        ``axis_fallbacks``, ``seconds``, ``plans``.
        """
        from .. import constants as _constants
        from ..runtime.communicator import Communicator

        devices = list(devices)
        if not devices:
            raise ValueError("resize() needs at least one device")
        old_world = self.comm.size
        new_comm = Communicator(
            devices, name=f"{getattr(self.comm, 'name', 'resized')}"
        )
        new_mesh = new_comm.flat_mesh(_AXIS)
        epoch = int(_constants.get("resize_epoch")) + 1
        t0 = time.perf_counter()
        entry = None
        if _flight.enabled():
            # the resize-epoch flight entry: comm "resize", seq = epoch.
            # Every rank records the identical (op, payload) stream, so a
            # rank that never entered the barrier is visible to the
            # analyzer as a missing seq (telemetry/analyze.py `resize`)
            entry = _flight.recorder.record(
                "resize", "resize.enter",
                payload=f"{old_world}->{new_comm.size}",
                backend="engine", routing=self.param_sharding, seq=epoch,
            )
        stats: Dict[str, Any] = {
            "epoch": epoch,
            "old_world": old_world,
            "new_world": new_comm.size,
            "peak_scratch_bytes": 0,
            "largest_shard_bytes": 0,
            "wire_elements": 0,
            "axis_fallbacks": 0,
            "plans": [],
        }
        shard_params = self.param_sharding == "fsdp"
        shard_opt = self.param_sharding in ("fsdp", "zero1")

        def _move(tree, shard: bool):
            return jax.tree_util.tree_map(
                lambda a: self._resize_leaf(
                    a, shard, new_comm, new_mesh, stats
                ),
                tree,
            )

        jax.block_until_ready(
            (self.params, self.opt_state, self.model_state)
        )
        new_params = _move(self.params, shard_params)
        new_opt = _move(self.opt_state, shard_opt)
        new_model_state = (
            _move(self.model_state, shard_params)
            if self.model_state is not None
            else None
        )
        # commit: swap world-derived state wholesale and rebuild the
        # compiled surface — nothing below this line can fail cheaply,
        # so the redistribution above ran to completion first
        self.comm = new_comm
        self.mesh = new_mesh
        self.batch_sharding = NamedSharding(new_mesh, P(_AXIS))
        self.replicated = NamedSharding(new_mesh, P())
        self.params, self.opt_state = new_params, new_opt
        self.model_state = new_model_state

        def _shardings_of(tree):
            return jax.tree_util.tree_map(lambda a: a.sharding, tree)

        self._out_shardings = (
            _shardings_of(self.params),
            _shardings_of(self.opt_state),
            (
                _shardings_of(self.model_state)
                if self.model_state is not None
                else None
            ),
            self.replicated,
        )
        self._step_fn = self._build_step()
        self._bcast_fn = self._build_broadcast()
        # world-size-keyed caches die with the old world (TPL007's whole
        # point): compiled epoch fns bake nb/p, AOT steps bake shardings
        self._epoch_fns.clear()
        self._eval_fns.clear()
        self._eval_data.clear()
        self._aot_steps.clear()
        try:
            # one knob write = one generation() bump: every cache that
            # embeds generation() (dispatch memos, plan cache, compiled
            # reshard schedules) invalidates with this single mutation
            _constants.set("resize_epoch", epoch)
        except _constants.FrozenConstantsError:
            pass  # frozen table: caches key on the new comm identity
        stats["seconds"] = time.perf_counter() - t0
        if entry is not None:
            _flight.FlightRecorder.complete(entry)
            wall_t1 = time.time()  # record_complete takes wall stamps
            # seq MUST be the epoch: an auto-drawn seq would fabricate a
            # phantom resize epoch in analyze_resizes and collide with
            # the next real epoch's enter entry
            _flight.recorder.record_complete(
                "resize", "resize.commit", wall_t1 - stats["seconds"],
                wall_t1, payload=f"{old_world}->{new_comm.size}",
                backend="engine", routing=self.param_sharding, seq=epoch,
            )
        dur = int(stats["seconds"] * 1e9)
        _ring.record(
            _names.ENGINE_RESIZE, time.time_ns() - dur, dur,
            {"old": old_world, "new": new_comm.size, "resize_epoch": epoch},
            step=(self.epochs_run, self.steps_run),
        )
        return stats

    # ------------------------------------------------------------------
    # device-resident epoch training: the whole dataset is staged into HBM
    # once and batches are gathered on-device inside a lax.scan, so a full
    # epoch is ONE dispatch — no per-step host->device transfer at all.
    # This is the TPU-idiomatic analog of the reference's prefetching
    # iterator (sgdengine.lua:118-124): instead of hiding the host copy,
    # eliminate it.
    # ------------------------------------------------------------------
    def stage_dataset(self, x, y, dtype=None):
        """Stage a dataset on device, batch-sharded over the communicator.

        Rank r owns the contiguous shard ``[r*ns, (r+1)*ns)`` (the
        DistributedIterator partitioning). Returns device arrays trimmed to
        a multiple of world size. ``dtype`` optionally narrows the image
        dtype (e.g. bfloat16) to halve HBM footprint and staging time.
        """
        p = self.comm.size
        n = (len(x) // p) * p
        # Cast host-side and device_put straight to the batch sharding: one
        # narrow transfer per shard, never a full-width staging copy on the
        # default device.
        xh = np.asarray(x[:n])
        if dtype is not None:
            xh = xh.astype(dtype)
        xd = jax.device_put(xh, self.batch_sharding)
        yd = jax.device_put(np.asarray(y[:n]), self.batch_sharding)
        return xd, yd

    def _build_epoch_fn(self, num_batches: int, per_rank: int, shuffle: bool):
        key = (num_batches, per_rank, shuffle)
        fn = self._epoch_fns.get(key)
        if fn is not None:
            return fn
        B, nb = per_rank, num_batches

        if self.param_sharding in ("fsdp", "zero1"):
            p = self.comm.size

            def fsdp_epoch(params, opt_state, model_state, xs, ys, rngkey):
                # identical data partitioning to the replicated path: rank
                # r draws from its contiguous shard [r*ns, (r+1)*ns) with
                # its own fold_in(key, r) permutation, so both modes walk
                # the exact same batch sequence (trajectory parity). The
                # gather is expressed SHARD-LOCALLY — a vmapped per-row
                # take whose leading axis aligns with the P(_AXIS) sharding
                # — so GSPMD keeps batch assembly on-device per shard (a
                # flat global take with data-dependent indices would force
                # a dataset-sized collective per step).
                ns = xs.shape[0] // p
                xs_r = xs.reshape((p, ns) + xs.shape[1:])
                ys_r = ys.reshape((p, ns) + ys.shape[1:])
                if shuffle:
                    perms = jax.vmap(
                        lambda r: jax.random.permutation(
                            jax.random.fold_in(rngkey, r), ns
                        )
                    )(jnp.arange(p))
                else:
                    perms = jnp.tile(jnp.arange(ns)[None], (p, 1))

                take_rows = jax.vmap(
                    lambda row, ii: jnp.take(row, ii, axis=0)
                )

                def body(carry, i):
                    params, opt_state, model_state = carry
                    with jax.named_scope(_names.SCOPE_RESIDENT_GATHER):
                        idx = jax.lax.dynamic_slice_in_dim(
                            perms, i * B, B, axis=1
                        )  # [p, B] per-rank LOCAL indices
                        xb = take_rows(xs_r, idx)
                        yb = take_rows(ys_r, idx)
                        batch = (
                            xb.reshape((p * B,) + xs.shape[1:]),
                            yb.reshape((p * B,) + ys.shape[1:]),
                        )
                    params, opt_state, model_state, loss = (
                        self._fsdp_step_core(
                            params, opt_state, model_state, batch
                        )
                    )
                    return (params, opt_state, model_state), loss

                (params, opt_state, model_state), losses = jax.lax.scan(
                    body, (params, opt_state, model_state), jnp.arange(nb)
                )
                return params, opt_state, model_state, losses

            def tm_epoch(params, opt_state, model_state, xs, ys, rngkey):
                return fsdp_epoch(
                    params, opt_state, model_state, xs, ys, rngkey)

            fn = jax.jit(
                tm_epoch,
                donate_argnums=(0, 1, 2),
                out_shardings=self._out_shardings,
            )
            self._epoch_fns[key] = fn
            return fn

        def epoch(params, opt_state, model_state, xs, ys, rngkey):
            # xs/ys: per-rank shard [ns, ...], ns >= nb*B.
            ns = xs.shape[0]
            if shuffle:
                r = jax.lax.axis_index(_AXIS)
                perm = jax.random.permutation(
                    jax.random.fold_in(rngkey, r), ns
                )
            else:
                perm = jnp.arange(ns)

            def body(carry, i):
                params, opt_state, model_state = carry
                with jax.named_scope(_names.SCOPE_RESIDENT_GATHER):
                    idx = jax.lax.dynamic_slice_in_dim(perm, i * B, B)
                    batch = (
                        jnp.take(xs, idx, axis=0), jnp.take(ys, idx, axis=0)
                    )
                params, opt_state, model_state, loss = self._step_core(
                    params, opt_state, model_state, batch
                )
                return (params, opt_state, model_state), loss

            (params, opt_state, model_state), losses = jax.lax.scan(
                body, (params, opt_state, model_state), jnp.arange(nb)
            )
            return params, opt_state, model_state, losses

        shmapped = jax.shard_map(
            epoch,
            mesh=self.mesh,
            in_specs=(P(), P(), P(), P(_AXIS), P(_AXIS), P()),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )

        def tm_epoch(params, opt_state, model_state, xs, ys, rngkey):
            return shmapped(params, opt_state, model_state, xs, ys, rngkey)

        fn = jax.jit(tm_epoch, donate_argnums=(0, 1, 2))
        self._epoch_fns[key] = fn
        return fn

    def train_resident(
        self,
        x,
        y,
        per_rank_batch: int,
        max_epochs: int = 5,
        shuffle: bool = True,
        seed: int = 0,
        image_dtype=None,
        epoch_callback: Optional[Callable[[int, float, float], None]] = None,
    ) -> Dict[str, Any]:
        """Device-resident training: stage ``(x, y)`` once, run
        ``max_epochs`` scan-compiled epochs. Returns a state dict like
        :meth:`train` plus per-epoch wall times in ``epoch_times``.

        Epoch-level hooks (``on_start``, ``on_start_epoch``,
        ``on_end_epoch``, ``on_end``) fire as in :meth:`train`; per-step
        hooks (``on_sample``/``on_forward``/``on_backward``/``on_update``)
        cannot — steps live inside a compiled ``lax.scan``.
        """
        p = self.comm.size
        _ring.set_step(self.epochs_run, self.steps_run)
        with _ring.span(_names.ENGINE_STAGE_DATASET):
            xd, yd = self.stage_dataset(x, y, dtype=image_dtype)
            jax.block_until_ready((xd, yd))
        ns = xd.shape[0] // p
        nb = ns // per_rank_batch
        if nb == 0:
            raise ValueError(
                f"dataset shard of {ns} samples < per-rank batch "
                f"{per_rank_batch}"
            )
        fn = self._build_epoch_fn(nb, per_rank_batch, shuffle)
        if self.broadcast_parameters:
            self.broadcast_parameters_now()

        state: Dict[str, Any] = {
            "engine": self,
            "epoch": 0,
            "t": 0,
            "training": True,
            "loss": None,
            "losses": [],
            "epoch_times": [],
            "samples": 0,
            "time": 0.0,
        }
        self._hook("on_start", state)
        t_start = time.perf_counter()
        for epoch in range(max_epochs):
            state["epoch"] = epoch
            _ring.set_step(self.epochs_run, self.steps_run)
            self._hook("on_start_epoch", state)
            with _ring.span(_names.ENGINE_EPOCH, {"steps": nb}) as whole:
                with _ring.span(_names.ENGINE_EPOCH_DISPATCH):
                    self.params, self.opt_state, self.model_state, losses = (
                        fn(
                            self.params,
                            self.opt_state,
                            self.model_state,
                            xd,
                            yd,
                            jax.random.fold_in(
                                jax.random.PRNGKey(seed), epoch),
                        )
                    )
                with _ring.span(_names.ENGINE_EPOCH_WAIT):
                    jax.block_until_ready(self.params)
            state["epoch_times"].append(whole.seconds)
            state["t"] += nb
            state["samples"] += nb * per_rank_batch * p
            if _telemetry.enabled():
                examples = nb * per_rank_batch * p
                self._record_epoch(examples, whole.seconds)
                # a resident epoch is one dispatch: its steps are counted
                # and its flight event stamped here, at the epoch's end
                _engine_metrics().steps.inc(
                    nb, sharding=self.param_sharding)
                if _flight.enabled():
                    wall_t1 = time.time()
                    _flight.recorder.record_complete(
                        _flight.comm_key(self.comm), "engine.epoch",
                        wall_t1 - whole.seconds, wall_t1,
                        payload=f"examples={examples},steps={nb}",
                    )
            with _ring.span(_names.ENGINE_EPOCH_END):
                losses_h = np.asarray(jax.device_get(losses))
                self._observe_state()
                state["loss"] = float(losses_h[-1])
                state["losses"].append(float(losses_h.mean()))
                if epoch_callback is not None:
                    epoch_callback(
                        epoch, state["losses"][-1], state["epoch_times"][-1]
                    )
                self._hook("on_end_epoch", state)
            self.epochs_run += 1
            self.steps_run += nb
        state["time"] = time.perf_counter() - t_start
        state["training"] = False
        self._hook("on_end", state)
        return state

    # ------------------------------------------------------------------
    def _observe_state(self) -> None:
        """Hand the model state to ``loss_fn.observe_state``, where a loss
        function has one (``models.make_moe_lm_loss_fn``: what the step
        measured of its routing becomes gauges). Called only where an
        epoch's loss has just been read: the step that made the state has
        ended, so the read waits for nothing."""
        observe = getattr(self.loss_fn, "observe_state", None)
        if observe is not None and self.model_state is not None:
            observe(jax.device_get(self.model_state))

    def _hook(self, name: str, state: Dict[str, Any]) -> None:
        fn = self.hooks.get(name)
        if fn is not None:
            fn(state)

    def train(
        self,
        iterator_fn: Callable[[], Any],
        max_epochs: int = 5,
    ) -> Dict[str, Any]:
        """Run the training loop.

        ``iterator_fn()`` is called per epoch and must yield ``(x, y)``
        device batches with leading axis ``p * per_rank`` (or rank-stacked
        ``[p, B, ...]`` — auto-flattened), matching the engine's mesh.
        """
        state: Dict[str, Any] = {
            "engine": self,
            "epoch": 0,
            "t": 0,
            "training": True,
            "loss": None,
            "losses": [],
            "samples": 0,
            "time": 0.0,
            "input_stall": 0.0,
        }
        _ring.set_step(self.epochs_run, self.steps_run)
        self._hook("on_start", state)

        if self.broadcast_parameters:
            # One-shot replica equalization (sgdengine.lua:140-144). Block
            # before the first step: the step's (slow) first compile would
            # otherwise run while the broadcast rendezvous is in flight,
            # which can starve a participant past the XLA CPU backend's 40s
            # hard timeout on low-core hosts (the reference likewise
            # device-syncs around the one-shot broadcast).
            self.broadcast_parameters_now()

        # nvprof-window analog, managed by ProfilerWindow so the trace is
        # ALWAYS stopped — including loops that end before the window does
        # and exception exits (the old inline flag leaked an active trace
        # on both). Bounds are validated by the window's constructor.
        from ..utils.tracing import ProfilerWindow

        win = (
            ProfilerWindow(self.profile_dir, *self.profile_window)
            if self.profile_dir
            else None
        )
        telemetry_on = _telemetry.enabled()
        per_step_hooks = any(
            h in self.hooks for h in ("on_forward", "on_backward", "on_update")
        )
        t_start = time.perf_counter()
        try:
            for epoch in range(max_epochs):
                state["epoch"] = epoch
                loss = None
                t_epoch = time.perf_counter()
                stall0, samples0 = state["input_stall"], state["samples"]
                _ring.set_step(self.epochs_run, self.steps_run)
                self._hook("on_start_epoch", state)
                # explicit next() so the wait on the iterator is MEASURED:
                # a streaming pipeline that can't keep up shows here as
                # input stall, not as silently-slower steps (the MFU fix)
                batch_iter = iter(iterator_fn())
                while True:
                    _ring.set_step(self.epochs_run, self.steps_run)
                    with _ring.span(_names.ENGINE_INPUT_WAIT) as wait:
                        batch = next(batch_iter, _NO_BATCH)
                    if batch is _NO_BATCH:
                        break
                    state["input_stall"] += wait.seconds
                    batch = self._prepare_batch(batch)
                    state["sample"] = batch
                    self._hook("on_sample", state)

                    if win is not None:
                        if win.active and state["t"] >= win.end:
                            # flush async dispatch before the window's
                            # stopping step so the traced tail is complete
                            # (params chain through every prior step)
                            jax.block_until_ready(self.params)
                        win.step(state["t"])

                    self.params, self.opt_state, self.model_state, loss = (
                        self._dispatch(batch)
                    )
                    state["loss"] = loss
                    if per_step_hooks:
                        # where a script reads its loss: the one place a
                        # step's host time can wait for the chip
                        with _ring.span(_names.ENGINE_HOOKS):
                            self._hook("on_forward", state)
                            self._hook("on_backward", state)
                            self._hook("on_update", state)
                    state["t"] += 1
                    state["samples"] += jax.tree_util.tree_leaves(batch)[0].shape[0]
                if loss is None:
                    raise RuntimeError(
                        f"iterator_fn() yielded no batches in epoch {epoch}; it "
                        "must return a fresh iterator each call (pass a factory, "
                        "e.g. lambda: iter(make_iterator()))"
                    )
                with _ring.span(_names.ENGINE_EPOCH_END):
                    state["losses"].append(float(jax.device_get(loss)))
                    self._observe_state()
                    if telemetry_on:
                        # the loss read above waited for the epoch's last
                        # step: the one place this loop may set a rate
                        self._record_epoch(
                            state["samples"] - samples0,
                            time.perf_counter() - t_epoch,
                            input_stall_s=state["input_stall"] - stall0,
                        )
                    self._hook("on_end_epoch", state)
                self.epochs_run += 1
        finally:
            if win is not None:
                if win.active:
                    try:  # same flush for loops ending inside the window
                        jax.block_until_ready(self.params)
                    except Exception:  # noqa: BLE001 - close regardless
                        pass
                win.close()
        jax.block_until_ready(self.params)
        state["time"] = time.perf_counter() - t_start
        state["training"] = False
        self._hook("on_end", state)
        return state

    def _prepare_batch(self, batch):
        """Accept [p, B, ...] rank-stacked or [p*B, ...] flat batches.

        In 'auto' mode a batch is treated as rank-stacked when *every* leaf
        has ndim >= 2 and leading axis == comm.size. That heuristic is
        ambiguous for flat batches of exactly p samples whose every leaf is
        >= 2-D (e.g. one-hot labels [p, C]); pass ``batch_format='flat'`` or
        ``'stacked'`` to the engine to make the contract explicit."""
        p = self.comm.size
        leaves = jax.tree_util.tree_leaves(batch)
        if self.batch_format == "auto":
            stacked = all(a.ndim >= 2 and a.shape[0] == p for a in leaves)
        else:
            stacked = self.batch_format == "stacked"
        if stacked:
            batch = jax.tree_util.tree_map(
                lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]),
                batch,
            )
        return jax.tree_util.tree_map(
            lambda a: a
            if getattr(a, "sharding", None) == self.batch_sharding
            else jax.device_put(a, self.batch_sharding),
            batch,
        )

    def invalidate_eval_cache(self, x=None, y=None) -> None:
        """Drop staged eval data — every slot (no arguments), every slot
        staged for array ``x`` (``y`` omitted), or exactly the ``(x, y)``
        slot. Mutations of cached host arrays are already observed
        automatically (``_array_fingerprint`` checksums the full buffer on
        every ``evaluate`` call — including after an invalidation, since
        the fingerprint is also what a restaged slot is stored under);
        this exists for callers who replace datasets wholesale and want
        the staged HBM back before the next ``evaluate``."""
        if x is None:
            self._eval_data.clear()
        elif y is None:
            for key in [k for k in self._eval_data if k[0] == id(x)]:
                del self._eval_data[key]
        else:
            self._eval_data.pop((id(x), id(y)), None)

    def evaluate(self, apply_fn: Callable, x, y, metric: Callable) -> float:
        """Device-resident evaluation of ``metric(apply_fn(...), y)``.

        ``apply_fn(params, x)`` normally; when the engine holds mutable
        ``model_state`` (e.g. batch_stats), ``apply_fn(params, state, x)``.
        Runs jitted on the engine's mesh with the eval batch sharded over
        ranks — parameters never leave the device (the round-1 version
        host-fetched, which is the wrong shape for ResNet-scale eval).
        ``metric`` must be a mean-style global reduction expressed in jnp
        ops (GSPMD computes the exact global value over the sharded batch).
        The tail ``len(x) % world_size`` samples are dropped to keep the
        batch evenly sharded.
        """
        p = self.comm.size
        n = (len(x) // p) * p
        # Stage-once cache: per-epoch evaluation on the same arrays must not
        # re-copy them from the host every call. Multi-slot (train/test sets
        # alternate) and fingerprinted with a FULL-buffer checksum: any
        # in-place mutation of a cached array — however small — restages
        # instead of returning stale results. ``invalidate_eval_cache``
        # force-drops slots without waiting for the checksum to notice.
        dkey = (id(x), id(y))
        fp = (_array_fingerprint(x), _array_fingerprint(y))
        cached = self._eval_data.get(dkey)
        if cached is not None and cached[0] == fp:
            xd, yd = cached[1], cached[2]
            # recency refresh: FIFO eviction would drop the entry a loop
            # alternating over >4 datasets is about to reuse
            self._eval_data[dkey] = self._eval_data.pop(dkey)
        else:
            xd = jax.device_put(np.asarray(x[:n]), self.batch_sharding)
            yd = jax.device_put(np.asarray(y[:n]), self.batch_sharding)
            if len(self._eval_data) >= 4:  # bound staged HBM
                self._eval_data.pop(next(iter(self._eval_data)))
            # keep x/y refs so the ids stay unique while cached
            self._eval_data[dkey] = (fp, xd, yd, x, y)
        has_state = self.model_state is not None
        key = (_fn_key(apply_fn), _fn_key(metric), has_state)
        fn = self._eval_fns.get(key)
        if fn is not None:
            self._eval_fns[key] = self._eval_fns.pop(key)  # LRU refresh
        else:
            if has_state:
                fn = jax.jit(
                    lambda params, state, x, y: metric(
                        apply_fn(params, state, x), y
                    )
                )
            else:
                fn = jax.jit(lambda params, x, y: metric(apply_fn(params, x), y))
            if len(self._eval_fns) >= 8:  # bound executables + _IdRef pins
                self._eval_fns.pop(next(iter(self._eval_fns)))
            self._eval_fns[key] = fn
        if has_state:
            return float(fn(self.params, self.model_state, xd, yd))
        return float(fn(self.params, xd, yd))
