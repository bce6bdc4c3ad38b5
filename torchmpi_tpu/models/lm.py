"""What every language model here calls and no one of them owns: the
recomputation of a block (``recomputed``) and what it keeps of the block's
dense products (``product`` names a result, ``products_kept`` asks the one
rule, ``kinds_kept``, which kinds the step has room for: by the device's
memory, the parameters' bytes and what the blocks' traced forward passes
store, no field of a configuration), the rotation of a whole head
(``rotary``), the sparse feed-forward half of a block
(``sparse_feed_forward``), the seeded decay of a gated recurrence
(``a_log_init``, ``dt_bias_init``) and taps of a short convolution
(``taps_init``), the engine's loss function of a model
that keeps no state (``make_lm_loss_fn``), the parameters of one (``init_lm_params``),
and the loss written plainly (``lm_cross_entropy``: no program path calls
it since the head makes its loss itself, ``lm_head.VocabHead``; it is the
reference ``tests/test_lm_head.py`` and ``scripts/lm_head_probe.py`` hold
the head to). The models' files import from here and not from each
other."""

from __future__ import annotations

import math
from typing import Callable, Optional, Sequence

import flax.linen as fnn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .. import telemetry as _telemetry
from ..parallel.ep import moe_local_experts, softmax_route_weights
from ..parallel.ring_attention import SAVED as _ATTN_SAVED
from ..telemetry import names as _names
from .lm_head import block_rows


def lm_cross_entropy(logits, targets):
    """Mean next-token cross-entropy over every position, the log-softmax
    in float32: the one loss of every language model here."""
    with jax.named_scope(_names.SCOPE_LM_LOSS):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(picked)


# -- what a recomputed block keeps of its dense products --------------------
# The ``checkpoint_name``s a block's dense products' results bear (``product``
# gives them), one name a kind and not a name a layer; inert without a policy,
# as ``ring_attention.SAVED`` is. ``recomputed(block_cls, keep=...)`` keeps
# the kinds it is handed; ``products_kept`` says which the step has room for.
QKV = "tm_kept_qkv"            # the query, key and value products, as one
#                                fused product made them or three
RESIDUAL = "tm_kept_residual"  # the stream after the mixer's output product
MLP_GATE = "tm_kept_mlp_gate"  # a feed-forward's first product before its
#                                activation (GPT-2's one; a gated one's gate)
MLP_UP = "tm_kept_mlp_up"      # a gated feed-forward's product beside it
MIXER_IN = "tm_kept_mixer_in"  # a recurrent mixer's input product, all its
#                                columns as one result
MIXER_GATES = "tm_kept_mixer_gates"  # ... and its per-head gates' product
INDEX = "tm_kept_index"        # a selecting layer's indexer's projections
ROUTER = "tm_kept_router"      # a router's float32 logits
HEAD_GATE = "tm_kept_head_gate"  # the columns of a gate on each head
PRODUCT_KINDS = (QKV, RESIDUAL, MLP_GATE, MLP_UP, MIXER_IN, MIXER_GATES,
                 INDEX, ROUTER, HEAD_GATE)
LANES = 128  # an array's last axis is stored in whole lanes

# what of the device's memory a step may be reckoned to fill: the chip's
# 15.75 GiB hold a step of 14.0 GiB with no rematerialization of the
# compiler's own (it began between 14.54 and 14.75: PERF.md section 6, PR 47)
FILL = 14.0 / 15.75

_noted: Optional[list] = None  # while ``_forward_results`` traces a block


def product(y, kind: str, depth: int, passes: int = 1):
    """``y``, the result of a dense product that summed over ``depth`` terms
    an element (in ``passes`` of the multiplier: 6 for float32 operands at
    precision highest), under the ``checkpoint_name`` ``kind`` (one of
    ``PRODUCT_KINDS``): what a recomputed block's policy may keep so that
    backward reads it and does not make the product again. Name a result
    where it is stored flat, ``[..., columns]`` before any reshape to heads:
    the compiler pads an axis of heads to its tiles (ten times the shape's
    bytes in ``brumby-14b``: PERF.md section 6, PR 47), and the bytes
    counted here are the shape's with the last axis in whole lanes."""
    assert kind in PRODUCT_KINDS, kind
    if _noted is not None:
        columns = -(-y.shape[-1] // LANES) * LANES
        _noted.append((kind, y.size // y.shape[-1] * columns
                       * y.dtype.itemsize, 2 * y.size * depth * passes))
    return checkpoint_name(y, kind)


def device_bytes() -> Optional[int]:
    """The memory of the device a step's arrays lie on
    (``memory_stats()["bytes_limit"]`` of the first local device); None
    where the devices report none (a CPU). The one reader of it: a test
    that lowers for a described chip from a CPU process sets it here."""
    stats = jax.local_devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def kinds_kept(limit: Optional[int], parameters: int, beside: int,
               kinds: dict) -> tuple:
    """Which kinds of named results a step keeps: THE rule, a pure function
    of bytes. ``limit``: the device's memory, None where it reports none
    (every kind is kept). ``parameters``: the bytes of the parameter tree
    (the parameters, their gradient and two moments of the optimizer are
    four times that; AdamW is the upper bound). ``beside``: the bytes the
    step is reckoned to hold beside those and the kept results. ``kinds``:
    ``{name: (bytes over the layers, operations over the layers)}``. Kinds
    are taken in the order of what a byte saves (operations a byte, the
    most first; equal ones by name), and the taking stops at the first that
    would carry the step past ``FILL`` of ``limit``: more memory never
    keeps less."""
    order = sorted(kinds, key=lambda k: (-kinds[k][1] / kinds[k][0], k))
    if limit is None:
        return tuple(order)
    room = FILL * limit - 4 * parameters - beside
    kept = []
    for kind in order:
        room -= kinds[kind][0]
        if room < 0:
            break
        kept.append(kind)
    return tuple(kept)


def _nbytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


# primitives whose result XLA makes inside the operation that reads it (or
# that move no bytes): a chain of them between two products is one fused
# loop, and only what a product, a loop, a kernel or a sort reads or writes
# is an array in memory. Anything not listed is taken to store its result.
_FUSED = frozenset("""
abs add and broadcast_in_dim ceil clamp convert_element_type copy cos div eq
erf exp exp2 expand_dims expm1 floor ge gt integer_pow iota is_finite le log
log1p logistic lt max min mul name ne neg not or pow reduce_and reduce_max
reduce_min reduce_or reduce_precision reduce_sum rem reshape round rsqrt
select_n sign sin slice split sqrt square squeeze stop_gradient sub tanh xor
""".split())


def _fuses(eqn) -> bool:
    """Whether ``eqn`` is elementwise all through: one of ``_FUSED``, or a
    call (``jax.nn.silu``, ``jnp.where``) whose every equation is."""
    if eqn.primitive.name in _FUSED:
        return True
    inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    return (eqn.primitive.name in ("jit", "custom_jvp_call")
            and inner is not None
            and all(_fuses(e) for e in getattr(inner, "jaxpr", inner).eqns))


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _forward_results(block: fnn.Module, params, stream):
    """(the bytes ``block``'s forward pass on ``stream`` stores, by the
    equations of its jaxpr: every array a product, a loop, a kernel or
    another operation that is no part of an elementwise chain reads or
    writes, each once, the parameters and the stream apart; what its
    ``product``s noted): one abstract trace, no name read."""
    global _noted
    before, _noted = _noted, []
    try:
        jaxpr = jax.make_jaxpr(
            lambda p, x: block.apply({"params": p}, x))(
                _shapes(params), stream).jaxpr
        noted = _noted
    finally:
        _noted = before
    stored = {}
    for eqn in jaxpr.eqns:
        if not _fuses(eqn):
            for v in (*eqn.invars, *eqn.outvars):
                if hasattr(v, "count") and v not in jaxpr.invars:
                    stored[v] = v.aval.size * v.aval.dtype.itemsize
    return sum(stored.values()), noted


def _held_for_backward(block: fnn.Module, params, stream) -> int:
    """The bytes ``block``, recomputed with no product kept, holds from its
    forward pass to its backward pass beside its parameters: its input and
    what its attention's kernels hand on (``ring_attention.SAVED``), by the
    residuals of its ``jax.vjp`` traced abstractly."""
    held = jax.eval_shape(
        lambda p, x: jax.vjp(jax.checkpoint(
            lambda p, x: block.apply({"params": p}, x),
            policy=jax.checkpoint_policies.save_only_these_names(
                _ATTN_SAVED)), p, x)[1], _shapes(params), stream)
    return max(0, _nbytes(held) - _nbytes(params))


def step_bytes_beside(held: int, stored: int, logits: int) -> int:
    """What a step of recomputed blocks is reckoned to hold beside its
    parameters' four copies and the kept products: what every layer holds
    for its backward pass (``held``, summed over the layers); while one
    block's backward runs, what that block's forward pass stores, made
    again, with half as much again for the gradients that are live beside
    it (``stored``: the widest block's); and one block of the vocabulary
    head's float32 logits (``logits``). The half is calibrated against the
    compiled steps of the benchmark's configurations
    (``scripts/recompute_probe.py``; PERF.md section 6, PR 47: they need
    0.6 to 1.4 of ``stored`` there, ``laguna-s-2-1`` the most): the
    estimate lies over the compiler's count in every one."""
    return held + stored + stored // 2 + logits


def products_kept(model: fnn.Module, block_cls, layers: Sequence[dict],
                  stream, vocab: int) -> tuple:
    """The kinds of ``product`` results ``model``'s layers keep when they
    are ``recomputed``: the layers are ``block_cls(**layer)`` for each of
    ``layers`` (the fields ``model`` is about to call them with, ``name``
    among them), on the ``stream`` (a ``ShapeDtypeStruct``), with a head of
    ``vocab`` ids after them. ``kinds_kept`` of the device's memory, the
    bytes of the parameter tree ``model`` was applied to, and each distinct
    block's forward pass and derivative traced once abstractly. Sets the
    two gauges ``tm_recompute_named_bytes_per_step`` and
    ``tm_recompute_kept_bytes_per_step``. Call it before anything that
    counts calls while the step is traced (``note_attention_step``)."""
    if model.is_initializing():
        return ()  # no parameters yet, and nothing differentiates this pass
    params = model.variables["params"]
    seen, kinds, held, widest = {}, {}, 0, 0
    for layer in layers:
        block = block_cls(**layer, parent=None)  # unbound: traced apart
        key = block.clone(name=None)
        if key not in seen:
            mine = params[block.name]
            seen[key] = (*_forward_results(block, mine, stream),
                         _held_for_backward(block, mine, stream))
        stored, noted, holds = seen[key]
        held, widest = held + holds, max(widest, stored)
        for kind, nbytes, ops in noted:
            had = kinds.get(kind, (0, 0))
            kinds[kind] = (had[0] + nbytes, had[1] + ops)
    rows = stream.size // stream.shape[-1]
    kept = kinds_kept(
        device_bytes(), _nbytes(params), step_bytes_beside(
            held, widest, 4 * vocab * block_rows(rows, vocab)), kinds)
    _telemetry.metrics.gauge(
        _names.GAUGE_RECOMPUTE_NAMED_BYTES,
        "bytes the dense products' named results would hold over the layers "
        "of the step most recently traced").set(
            sum(b for b, _ in kinds.values()))
    _telemetry.metrics.gauge(
        _names.GAUGE_RECOMPUTE_KEPT_BYTES,
        "those of tm_recompute_named_bytes_per_step whose kinds the rule "
        "kept: backward reads them, the rest is made again").set(
            sum(kinds[k][0] for k in kept))
    return kept


def recomputed(block_cls, keep: Sequence[str] = ()):
    """``block_cls`` recomputed in backward, but for what its attention
    call's forward kernels hand to their backward kernels: the arrays that
    bear the ``checkpoint_name`` ``ring_attention.SAVED``
    (``blocked_self_attention``'s output and log-sum-exp where it takes the
    fused kernels; a selecting layer's output, log-sum-exps, thresholds and
    panels of index scores) are kept, so an attention kernel runs forward
    once a step and not again with the block; and but for the dense
    products' results of the kinds in ``keep`` (``products_kept``: as many
    as the step has room for), which backward reads where it would make the
    product again. The rest of the block is made again. Where the call
    takes the loops nothing bears the attention's name. The one spelling of
    every model's ``remat``."""
    return fnn.remat(
        block_cls,
        policy=jax.checkpoint_policies.save_only_these_names(
            _ATTN_SAVED, *keep))


def make_lm_loss_fn(model: fnn.Module):
    """Next-token loss for the engine: ``loss_fn(params, batch)`` with
    ``batch = (tokens_in, tokens_target)``, both ``[B, T]`` int32. Mean
    cross-entropy over every position (the engine's batch contract matches
    ``models.mnist.make_loss_fn`` so LMs drive the same train loops the
    classifiers do). ``model(tokens, targets)`` is that loss."""

    def loss_fn(params, batch):
        tokens, targets = batch
        # the model's head makes the loss itself (``lm_head.VocabHead``): no
        # logits between the two
        return model.apply({"params": params}, tokens, targets)

    return loss_fn


def init_lm_params(model: fnn.Module, seq_len: int, seed: int = 0):
    rng = jax.random.PRNGKey(seed)
    variables = model.init(rng, jnp.zeros((1, seq_len), jnp.int32))
    return variables["params"]


def rotary(x, theta: float):
    """Rotary position over the whole head of ``x`` ``[b, t, h, d]``, its
    halves rotated against each other, positions ``0 .. t - 1``; float32
    inside, ``x``'s dtype out."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def taps_init(key, shape, dtype=jnp.float32):
    """Uniform within ``1 / sqrt(taps)``, a depthwise kernel's fan-in."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def a_log_init(key, shape, dtype=jnp.float32):
    """``A`` uniform in [1, 16] (Mamba-2's published initialisation; the
    gated delta rule's gate copies its parametrisation, ``g = -A softplus(a
    + dt_bias)``)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=jnp.float32):
    """``softplus(dt_bias)`` log-uniform in [1e-3, 1e-1], through the
    inverse of the softplus."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def sparse_feed_forward(block: fnn.Module, x, h, *, expert_width: int,
                        num_experts: int, top_k: int, held: Sequence[int],
                        activation: Callable, dtype, logits=None,
                        route_weights: Callable = softmax_route_weights,
                        shared_width: Optional[int] = None,
                        shared_sigmoid: bool = False, route_bias=None):
    """The sparse feed-forward half of a block, called inside ``block``'s
    compact ``__call__`` (the parameters made here are ``block``'s own, under
    the names every sparse decoder's tree has): ``x + shared(h) + sum_{e
    chosen, e held} w_e expert_e(h)`` from the stream ``x`` ``[b, t, d]``
    and its normed copy ``h``, each expert a gated feed-forward ``(act(h
    W_gate) * (h W_up)) W_down``, with what each held expert received and
    the rows the grouped products ran over (``ep.moe_local_experts``).

    ``logits``: the router's ``[b, t, num_experts]`` float32 where the
    caller read it elsewhere (before attention); None: the router reads
    ``h`` here, its product float32 at precision highest. ``shared_width``:
    a shared expert of that many columns beside the routed ones, which every
    token takes at weight 1 or, with ``shared_sigmoid``, at ``sigmoid(h .
    w_s)``, one number a token (``shared_expert_gate``). ``route_bias``:
    ``[num_experts]`` float32, the buffer of a router that chooses by its
    scores plus a bias; ``route_weights`` is then called with it and gives
    the rule (``ep.biased_sigmoid_route_weights`` with its numbers bound),
    and what that rule measured comes back after the rows."""
    b, t, d = x.shape
    if route_bias is not None:
        route_weights = route_weights(route_bias)
    dense = lambda n, name: fnn.Dense(  # noqa: E731
        n, use_bias=False, dtype=dtype, name=name)
    if logits is None:
        with jax.named_scope(_names.SCOPE_MOE_ROUTER):
            logits = product(fnn.Dense(
                num_experts, use_bias=False, dtype=jnp.float32,
                precision=lax.Precision.HIGHEST, name="router"
            )(h.astype(jnp.float32)), ROUTER, d, passes=6)
    # ``h`` is cast where it is read, twice, as ``MoEDecoderBlock`` did
    # before this was a function: its lowered steps are pinned by hash
    if shared_width is not None:
        with jax.named_scope(_names.SCOPE_MOE_SHARED):
            m = h.astype(dtype)
            shared = dense(d, "shared_down")(
                activation(product(
                    dense(shared_width, "shared_gate")(m), MLP_GATE, d))
                * product(dense(shared_width, "shared_up")(m), MLP_UP, d))
            if shared_sigmoid:
                shared = (shared * jax.nn.sigmoid(
                    dense(1, "shared_expert_gate")(m).astype(jnp.float32))
                ).astype(shared.dtype)
            x = x + shared
    init = fnn.initializers.lecun_normal(in_axis=-2, out_axis=-1)
    n, f = len(held), expert_width
    y, *measured = moe_local_experts(
        h.astype(dtype).reshape(b * t, d),
        logits.reshape(b * t, num_experts), top_k,
        block.param("experts_gate", init, (n, d, f), jnp.float32),
        block.param("experts_up", init, (n, d, f), jnp.float32),
        block.param("experts_down", init, (n, f, d), jnp.float32),
        tuple(held), activation=activation, route_weights=route_weights)
    return (x + y.reshape(b, t, d), *measured)
