"""Structured spans: a bounded ring buffer of wall-clock events with Chrome
``trace_event`` export (loadable in Perfetto / chrome://tracing).

A span is one timed region of host-side work — an eager collective
dispatch, the engine's wait for a batch, a PS RPC. Recording is designed
for the hot path: three clock reads, one tuple append into a
``deque(maxlen)`` under a lock, no I/O until :meth:`SpanRecorder.export`.

**One clock.** A span's start is ``time.time_ns()``: the clock the flight
recorder stamps with (``time.time()``) and the clock a device trace's
origin is set on (``ProfileOptions.start_timestamp_ns``, see
``utils.tracing.ProfilerWindow``). A span therefore joins a device trace
by subtraction: ``start_ns - origin_ns`` is its time in the trace. Nothing
here opens an annotation of jax's profiler: that needs the profiler's
host tracer, which on a TPU host writes a million events per copied batch
and slows the step it traces. A span's duration comes from the monotonic
clock, so a stepped wall clock cannot make it negative.

Each record carries its name, start, duration, thread, attributes, an id,
the id of the span that caused it (the span open on the same thread when
it started, or one given explicitly across threads) and the ``(epoch,
step)`` its thread was working on (:meth:`SpanRecorder.set_step`).

The names the engine, the input pipeline and the jitted step use are the
constants below: the benchmark's per-layer metrics find spans and device
operations by them, and ``tests/test_engine_tracing.py`` pins them.
"""

from __future__ import annotations

import itertools
import os
import threading
from ..analysis import lockmon as _lockmon
import time
from collections import deque
from typing import NamedTuple, Optional, Tuple

# -- host spans: engine/sgd.py ---------------------------------------------
ENGINE_INIT = "engine.init"
ENGINE_BROADCAST = "engine.broadcast"
ENGINE_STAGE_DATASET = "engine.stage_dataset"
ENGINE_PROGRAM_BUILD = "engine.program_build"
ENGINE_INPUT_WAIT = "engine.input_wait"
ENGINE_DISPATCH = "engine.dispatch"
ENGINE_HOOKS = "engine.hooks"
ENGINE_EPOCH_END = "engine.epoch_end"
ENGINE_EPOCH = "engine.epoch"
ENGINE_EPOCH_DISPATCH = "engine.epoch.dispatch"
ENGINE_EPOCH_WAIT = "engine.epoch.wait"
ENGINE_CHECKPOINT = "engine.checkpoint"
ENGINE_RESIZE = "engine.resize"
# -- host spans: data/__init__.py (InputPipeline) --------------------------
INPUT_EPOCH_START = "input.epoch_start"
INPUT_ASSEMBLE = "input.assemble"
INPUT_STAGE = "input.stage"
INPUT_RING_WAIT = "input.ring_wait"
# -- host span: utils/tracing.py (ProfilerWindow) --------------------------
PROFILER_WINDOW = "profiler.window"

SPAN_NAMES = (
    ENGINE_INIT, ENGINE_BROADCAST, ENGINE_STAGE_DATASET,
    ENGINE_PROGRAM_BUILD, ENGINE_INPUT_WAIT, ENGINE_DISPATCH, ENGINE_HOOKS,
    ENGINE_EPOCH_END, ENGINE_EPOCH, ENGINE_EPOCH_DISPATCH, ENGINE_EPOCH_WAIT,
    ENGINE_CHECKPOINT, ENGINE_RESIZE, INPUT_EPOCH_START, INPUT_ASSEMBLE,
    INPUT_STAGE, INPUT_RING_WAIT, PROFILER_WINDOW,
)

# -- jax.named_scope names inside the jitted step (metadata only) ----------
SCOPE_FWD_BWD = "tm.fwd_bwd"
SCOPE_STATE_SYNC = "tm.state_sync"
SCOPE_GRAD_SYNC = "tm.grad_sync"
# nested under SCOPE_GRAD_SYNC; a bucketed sync nests ``b<index>`` below
SCOPE_PACK, SCOPE_REDUCE, SCOPE_UNPACK = "pack", "reduce", "unpack"
SCOPE_OPTIMIZER = "tm.optimizer"
SCOPE_LOSS_SYNC = "tm.loss_sync"
SCOPE_RESIDENT_GATHER = "tm.resident_gather"

SCOPE_NAMES = (
    SCOPE_FWD_BWD, SCOPE_STATE_SYNC, SCOPE_GRAD_SYNC,
    f"{SCOPE_GRAD_SYNC}/{SCOPE_PACK}", f"{SCOPE_GRAD_SYNC}/{SCOPE_REDUCE}",
    f"{SCOPE_GRAD_SYNC}/{SCOPE_UNPACK}", SCOPE_OPTIMIZER, SCOPE_LOSS_SYNC,
    SCOPE_RESIDENT_GATHER,
)

# -- named scopes a model opens inside its forward pass, so nested under
# SCOPE_FWD_BWD (backward's operations carry them inside jax's
# ``transpose(jvp(...))`` wrappers): models/decoder.py, parallel/ep.py
SCOPE_ATTN_FULL = "tm.attn.full"      # rotary or none, blocked attention
SCOPE_ATTN_WINDOW = "tm.attn.window"  # the same within a sliding window
SCOPE_MOE_ROUTE = "tm.moe.route"      # top-k, softmax, ordering, dispatch
SCOPE_MOE_EXPERTS = "tm.moe.experts"  # the grouped products
SCOPE_MOE_COMBINE = "tm.moe.combine"  # weighted rows back to their tokens
# parallel/selected_attention.py: attention over the keys an indexer selects
SCOPE_ATTN_INDEX = "tm.attn.index"    # the indexer: projections, norm,
#                                       rotary, the index scores
SCOPE_ATTN_SELECT = "tm.attn.select"  # the k-th largest a query, the mask
SCOPE_ATTN_SPARSE = "tm.attn.sparse"  # attention over the selection, and
#                                       the indexer's loss and its gradient

# models/decoder.py: what a layer held by share may add to the two above
SCOPE_ATTN_GATE = "tm.attn.gate"      # the gate on each head: its product,
#                                       the sigmoid and the multiply
SCOPE_MOE_SHARED = "tm.moe.shared"    # the shared expert's three products
SCOPE_MOE_DENSE = "tm.moe.dense"      # a leading layer's dense feed-forward,
#                                       in the slot the experts have elsewhere

# models/decoder.py, models/transformer.py: the parts of a language model's
# step that are no attention and no expert layer. None encloses or lies
# inside a scope above. What XLA fuses across a boundary bears its root's
# scope, so a fusion goes to one side whole. One of them lies inside
# another: the head's rule (models/lm_head.py) walks the rows in a loop
# under ``tm.lm.head`` and opens ``tm.lm.loss`` inside the loop's body, and
# a reader takes the innermost (the last) name.
SCOPE_LM_EMBED = "tm.lm.embed"        # the embedding's gather (GPT-2: and
#                                       the positions'); backward: the rows
#                                       sorted by id, summed by blocks, one
#                                       gather into the table
#                                       (models/embedding.py)
SCOPE_LM_NORM = "tm.lm.norm"          # a block's norms, the model's last,
#                                       the query and key heads' norms
SCOPE_ATTN_PROJ = "tm.attn.proj"      # the q, k, v and o products; not the
#                                       indexer's, not the reshapes beside
#                                       the attention (under no scope)
SCOPE_LM_MLP = "tm.lm.mlp"            # GPT-2's feed-forward: two products
#                                       and the GELU
SCOPE_MOE_ROUTER = "tm.moe.router"    # the router's product, precision
#                                       highest
SCOPE_LM_HEAD = "tm.lm.head"          # the product with the vocabulary
#                                       matrix and, in the same visit of a
#                                       block of rows, the two gradient
#                                       products (forward's phase);
#                                       backward: the multiply by the
#                                       cotangent
SCOPE_LM_LOSS = "tm.lm.loss"          # the float32 log-softmax, the pick,
#                                       the mean, ``softmax - onehot``

# models/hybrid.py: a state-space mixer beside the attention of a block
# (parallel/ssm.py). Named ``tm.lm.*`` so that the benchmark's reader, which
# knows ``tm.(lm|attn|moe).*``, gives each a bucket of its own.
SCOPE_SSM_PROJ = "tm.lm.ssm_proj"     # the mixer's in and out products and
#                                       the multipliers on their parts
SCOPE_SSM_CONV = "tm.lm.ssm_conv"     # the causal depthwise convolution, its
#                                       bias and the SiLU
SCOPE_SSM_SCAN = "tm.lm.ssm_scan"     # delta, the decays, the chunked dual,
#                                       the carried state, D x
SCOPE_SSM_GATE = "tm.lm.ssm_gate"     # the gate and the norm by groups

# models/retentive.py: power retention where attention stood
# (parallel/retention.py). q, k, v and o stand under SCOPE_ATTN_PROJ.
SCOPE_RET_GATE = "tm.lm.ret_gate"     # the gate's projection, log-sigmoid,
#                                       the cumulative sums and the decays
SCOPE_RET_CHUNK = "tm.lm.ret_chunk"   # rotary position and the scale, the
#                                       masked products inside a chunk
SCOPE_RET_STATE = "tm.lm.ret_state"   # the symmetric square, a chunk's own
#                                       state, the carry, the state's read,
#                                       the division. It holds the scan
#                                       over the chunks, so the two above
#                                       are opened INSIDE it in the loop's
#                                       body: the one nesting among these
#                                       names (the readers go by the
#                                       innermost, the last, name)

# models/deltanet.py: a Gated DeltaNet mixer in the layers that have no
# softmax attention (parallel/deltanet.py). The softmax layers of that model
# stand under ``tm.attn.*`` as any decoder's, the expert layer under
# ``tm.moe.*``.
SCOPE_GDN_PROJ = "tm.lm.gdn_proj"     # the mixer's three products: into
#                                       [q | k | v | z], into [b | a], out
SCOPE_GDN_CONV = "tm.lm.gdn_conv"     # the causal depthwise taps over
#                                       [q | k | v] and the SiLU
SCOPE_GDN_GATE = "tm.lm.gdn_gate"     # the L2 norms of q and k, beta, g,
#                                       the cumulative sums and the decays,
#                                       the output norm and silu(z)
SCOPE_GDN_CHUNK = "tm.lm.gdn_chunk"   # what is made a chunk at a time
#                                       before the loop: K K^T, the
#                                       triangular system's inverse, the
#                                       corrected values and keys, Q K^T
SCOPE_GDN_STATE = "tm.lm.gdn_state"   # the loop over the chunks: the values
#                                       a chunk really writes, its output,
#                                       the carried state

# models/decoder.py: a gated short convolution where attention stands in
# the other layers (parallel/ssm.py ``gated_short_conv``; the ``lfm2``
# family's ``conv`` layers). Such a layer opens no ``tm.attn.*`` scope.
SCOPE_SCONV_PROJ = "tm.lm.sconv_proj"  # the mixer's two products: into
#                                        [B | C | x] and out, with the sum
#                                        into the residual stream
SCOPE_SCONV = "tm.lm.sconv"            # the two elementwise gates and the
#                                        taps between them

# The scopes by group, so that a reader or a test names the group it means
# and a model that brings names of its own appends a group and moves no
# other's place. ``MODEL_SCOPE_NAMES`` is the groups in the order they came.
ATTN_MOE_SCOPE_NAMES = (    # attention's and the expert layer's: what the
    #                         benchmark's older per-layer metrics read
    SCOPE_ATTN_FULL, SCOPE_ATTN_WINDOW, SCOPE_MOE_ROUTE, SCOPE_MOE_EXPERTS,
    SCOPE_MOE_COMBINE, SCOPE_ATTN_INDEX, SCOPE_ATTN_SELECT,
    SCOPE_ATTN_SPARSE, SCOPE_ATTN_GATE, SCOPE_MOE_SHARED, SCOPE_MOE_DENSE,
)
LM_SCOPE_NAMES = (          # the rest of a language model's step
    SCOPE_LM_EMBED, SCOPE_LM_NORM, SCOPE_ATTN_PROJ, SCOPE_LM_MLP,
    SCOPE_MOE_ROUTER, SCOPE_LM_HEAD, SCOPE_LM_LOSS,
)
SSM_SCOPE_NAMES = (         # the state-space mixer's
    SCOPE_SSM_PROJ, SCOPE_SSM_CONV, SCOPE_SSM_SCAN, SCOPE_SSM_GATE,
)
RETENTION_SCOPE_NAMES = (   # power retention's
    SCOPE_RET_GATE, SCOPE_RET_CHUNK, SCOPE_RET_STATE,
)
GDN_SCOPE_NAMES = (         # the gated delta rule's
    SCOPE_GDN_PROJ, SCOPE_GDN_CONV, SCOPE_GDN_GATE, SCOPE_GDN_CHUNK,
    SCOPE_GDN_STATE,
)
SCONV_SCOPE_NAMES = (       # the gated short convolution's
    SCOPE_SCONV_PROJ, SCOPE_SCONV,
)
MODEL_SCOPE_NAMES = (
    ATTN_MOE_SCOPE_NAMES + LM_SCOPE_NAMES + SSM_SCOPE_NAMES
    + RETENTION_SCOPE_NAMES + GDN_SCOPE_NAMES + SCONV_SCOPE_NAMES
)

# -- the gauge models/decoder.py sets from static shapes while its step is
# traced (as ``ep.note_expert_layers`` does): the query heads this device
# holds. The benchmark's ``attn_heads_held_share`` reads it against the
# heads of the same layers whole, which the configuration's file gives.
GAUGE_ATTN_HEADS_HELD = "tm_attn_query_heads_held_per_step"
# -- the gauges parallel/ssm.py ``note_ssm_step`` sets the same way for
# models/hybrid.py: the mixer heads this device holds, summed over the
# layers, and the chunks its scans run over (layers x sequences x chunks)
GAUGE_SSM_HEADS_HELD = "tm_ssm_heads_held_per_step"
GAUGE_SSM_CHUNKS = "tm_ssm_chunks_per_step"
# -- the gauges parallel/retention.py ``note_retention_step`` sets the same
# way for models/retentive.py: the KV heads of power retention this device
# holds, summed over the layers; the chunks (layers x sequences x chunks);
# the bytes of the float32 states its recurrences carry (layers x sequences
# x KV heads held x D x (head_dim + 1) x 4)
GAUGE_RETENTION_KV_HEADS_HELD = "tm_retention_kv_heads_held_per_step"
GAUGE_RETENTION_CHUNKS = "tm_retention_chunks_per_step"
GAUGE_RETENTION_STATE_BYTES = "tm_retention_state_bytes_per_step"
# -- the gauge parallel/deltanet.py ``note_gdn_step`` sets the same way for
# models/deltanet.py: the chunks the gated delta rule runs over (the layers
# that have it x sequences x chunks). The benchmark's ``gdn_chunks_per_step``
# reads it
GAUGE_GDN_CHUNKS = "tm_gdn_chunks_per_step"
# -- the gauge models/embedding.py ``TokenEmbed`` sets the same way: the
# token rows of the step most recently traced whose embedding gradient is
# summed by sorted ids before it touches the table (0 where the shapes keep
# jax's scatter-add). The benchmark's ``embed_grad_sorted_share`` reads it
# against the step's tokens
GAUGE_EMBED_GRAD_SORTED_ROWS = "tm_embed_grad_sorted_rows_per_step"
# -- the gauges models/lm_head.py ``head_loss`` sets the same way: the token
# rows of the step most recently traced whose loss came from the vocabulary
# head's own rule (the product, the log-softmax and the three gradients a
# block of rows at a time), and the blocks it walks (1: one visit, no loop).
# The benchmark's ``lm_head_blocked_share`` reads the first against the
# step's tokens
GAUGE_LM_HEAD_BLOCKED_ROWS = "tm_lm_head_blocked_rows_per_step"
GAUGE_LM_HEAD_BLOCKS = "tm_lm_head_blocks_per_step"
# -- the gauges parallel/ssm.py ``note_conv_step`` sets the same way for
# models/hybrid.py and models/deltanet.py: the elements (layers x sequences
# x positions x channels) that go through ``causal_conv1d_silu``, and those
# of them whose shapes take the fused kernels of ``ops/conv_kernel.py`` on a
# TPU. The benchmark's ``conv_kernel_share`` reads the second against the
# first. ``note_gated_conv_step`` sets them for models/decoder.py's gated
# short convolution, whose elements take no kernel (the second reads 0)
GAUGE_CONV_ELEMENTS = "tm_conv_elements_per_step"
GAUGE_CONV_KERNEL_ELEMENTS = "tm_conv_kernel_elements_per_step"
# -- the gauges models/lm.py ``products_kept`` sets the same way for every
# model whose blocks are recomputed: the bytes the dense products' named
# results (``lm.product``) would hold over the layers of the step most
# recently traced, and the bytes of the kinds the rule kept for backward to
# read. The benchmark's ``recompute_kept_share`` reads the second against
# the first
GAUGE_RECOMPUTE_NAMED_BYTES = "tm_recompute_named_bytes_per_step"
GAUGE_RECOMPUTE_KEPT_BYTES = "tm_recompute_kept_bytes_per_step"
# -- the gauges models/decoder.py ``make_moe_lm_loss_fn``'s
# ``observe_state`` sets where the engine reads an epoch's loss, for a
# router that chooses by its scores plus a bias (``ep.
# biased_sigmoid_route_weights``): the largest magnitude among the biases of
# every expert layer as the last step read left them, and the routes of
# that step whose expert the bare scores would not have chosen, summed over
# the layers. The benchmark's ``moe_bias_max_abs`` and
# ``moe_biased_route_share`` read them
GAUGE_MOE_BIAS_MAX_ABS = "tm_moe_bias_max_abs"
GAUGE_MOE_BIASED_ROUTES = "tm_moe_biased_routes_last_step"

# -- what a device trace calls the attention kernels (an event's name is
# the kernel's HLO instruction): jax's splash attention in
# parallel/ring_attention.py, one KV head with its group of query heads,
# ``%splash_mqa_fwd_residuals.3 = ...`` (forward, with the log-sum-exp
# saved or not) or ``%splash_mqa_dkv_no_residuals.7 = ...`` (backward); and
# the repo's own forward and backward kernels of the attention over a
# selection in parallel/selected_attention.py. Read with ``str.startswith``
ATTN_KERNEL_EVENT = ("splash_mqa_", "tm_attn_sparse_fwd", "tm_attn_sparse_bwd")


class SpanRecord(NamedTuple):
    name: str
    start_ns: int            # time.time_ns() at the span's start
    dur_ns: int              # monotonic
    tid: int
    attrs: Optional[dict]
    id: int
    parent: Optional[int]    # id of the span that caused this one
    step: Optional[Tuple[int, int]]  # (epoch, step) the thread worked on

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


class SpanRecorder:
    """Bounded ring buffer of completed spans (oldest evicted first)."""

    def __init__(self, capacity: int = 4096):
        self._lock = _lockmon.make_lock("spans.py:SpanRecorder._lock")
        self._buf: deque = deque(maxlen=int(capacity))
        self._ids = itertools.count(1)
        # per thread: the ids of the spans open on it, and its (epoch, step)
        self._ctx = threading.local()
        self.total_recorded = 0
        # spans evicted by ring wrap-around: > 0 means the exported trace
        # is TRUNCATED (detectable instead of silent — snapshot()["spans"]
        # ["dropped"] and the trace's "spanDropped" field both carry it)
        self.dropped = 0

    @property
    def capacity(self) -> int:
        return self._buf.maxlen or 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    # -- the calling thread's context -----------------------------------
    def set_step(self, epoch: int, step: int) -> None:
        """Name the ``(epoch, step)`` this thread works on from here: every
        span it opens until the next call carries it."""
        self._ctx.step = (epoch, step)

    def _open(self) -> list:
        try:
            return self._ctx.open
        except AttributeError:
            self._ctx.open = []
            return self._ctx.open

    def span(self, name: str, attrs: Optional[dict] = None,
             parent: Optional[int] = None,
             step: Optional[Tuple[int, int]] = None) -> "Span":
        """A context manager that records one span, whatever
        ``telemetry.enabled()`` says. ``parent`` and ``step`` default to
        the span still open on the calling thread, and the thread's step,
        when this one closes."""
        return Span(self, name, attrs, parent, step)

    def record(self, name: str, start_ns: int, dur_ns: int,
               attrs: Optional[dict] = None, parent: Optional[int] = None,
               step: Optional[Tuple[int, int]] = None,
               span_id: Optional[int] = None) -> int:
        """Append a finished span; returns its id. ``parent`` and ``step``
        default to the calling thread's open span and step."""
        if span_id is None:
            span_id = next(self._ids)
        if parent is None:
            stack = self._open()
            parent = stack[-1] if stack else None
        if step is None:
            step = getattr(self._ctx, "step", None)
        rec = SpanRecord(
            name, int(start_ns), max(0, int(dur_ns)),
            threading.get_ident() & 0xFFFFFFFF, attrs, span_id, parent, step,
        )
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(rec)
            self.total_recorded += 1
        return span_id

    def records(self) -> list:
        """The buffered spans, oldest first."""
        with self._lock:
            return list(self._buf)

    def reset(self) -> None:
        """Forget the recorded spans, and every thread's step (a span open
        across the call keeps its own record, and loses its place as a
        parent)."""
        with self._lock:
            self._buf.clear()
            self.total_recorded = 0
            self.dropped = 0
            self._ctx = threading.local()

    def trace_events(self) -> list:
        """Chrome ``trace_event`` list: one complete ('X') event per span
        (``ph``/``ts``/``dur``/``name``/``pid``/``tid`` + ``args``), plus a
        process-name metadata event so Perfetto labels the track. ``ts`` is
        microseconds of the wall clock: less a device trace's origin it is
        the span's place in that trace."""
        pid = os.getpid()
        events = [
            {
                "ph": "M",
                "ts": 0,
                "name": "process_name",
                "pid": pid,
                "tid": 0,
                "args": {"name": f"torchmpi_tpu pid {pid}"},
            }
        ]
        for r in self.records():
            args = {k: _jsonable(v) for k, v in (r.attrs or {}).items()}
            args["span_id"] = r.id
            if r.parent is not None:
                args["parent"] = r.parent
            if r.step is not None:
                args["epoch"], args["step"] = r.step
            events.append({
                "ph": "X",
                "name": r.name,
                "cat": "torchmpi_tpu",
                "ts": r.start_ns / 1e3,
                "dur": r.dur_ns / 1e3,
                "pid": pid,
                "tid": r.tid,
                "args": args,
            })
        return events

    def export(self, path) -> None:
        """Write ``{"traceEvents": [...]}`` JSON — the object form of the
        Chrome trace format, loadable in Perfetto / chrome://tracing."""
        import json

        with open(path, "w") as f:
            json.dump(
                {"traceEvents": self.trace_events(),
                 "displayTimeUnit": "ms",
                 # extra top-level keys are legal in the Chrome trace
                 # object form; > 0 flags a truncated (ring-wrapped) trace
                 "spanDropped": self.dropped,
                 "spanClock": "time_ns"},
                f,
            )


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


class Span:
    """Context manager timing one region into ``recorder``. After exit,
    ``seconds`` holds its duration and ``id`` its record's id."""

    __slots__ = ("_recorder", "name", "attrs", "parent", "step", "id",
                 "seconds", "_start_ns", "_t0")

    def __init__(self, recorder: SpanRecorder, name: str,
                 attrs: Optional[dict] = None, parent: Optional[int] = None,
                 step: Optional[Tuple[int, int]] = None):
        self._recorder = recorder
        self.name = name
        self.attrs = attrs
        self.parent = parent
        self.step = step
        self.seconds = 0.0

    def __enter__(self):
        rec = self._recorder
        self.id = next(rec._ids)
        rec._open().append(self.id)
        self._start_ns = time.time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter_ns() - self._t0
        stack = self._recorder._open()
        if stack:  # empty only after a reset() inside this span
            stack.pop()
        self.seconds = dur * 1e-9
        self._recorder.record(
            self.name, self._start_ns, dur, self.attrs, self.parent,
            self.step, self.id,
        )
        return False


class _NoopSpan:
    """Shared do-nothing span: the disabled path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NOOP_SPAN = _NoopSpan()
