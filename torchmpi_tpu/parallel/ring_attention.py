"""Ring attention: sequence/context parallelism over a mesh axis.

Long-context capability absent from the 2017 reference (SURVEY.md §5) but
first-class here: the sequence axis is sharded over devices, and attention
is computed by rotating key/value blocks around the ring with ``ppermute``
(one ICI hop per step) while queries stay resident — communication overlaps
the per-block attention compute, and no device ever materialises the full
sequence. Flash-style streaming softmax (running max + normalizer) keeps
the math exact.

Two backends behind one function: the pure-XLA path (``backend='xla'``,
works on the CPU test mesh and lowers ppermute to ICI collective-permute
on TPU) and the Pallas kernel with explicit double-buffered K/V RDMA and
the streaming-softmax merge in-kernel
(``backend='pallas'``/``'pallas_interpret'``, ``ops/ring_attention_kernel
.py``). Oversized working sets auto-chunk over batch/heads (each chunk
rides its own ring); a single (batch, head) cell beyond the kernel's
VMEM envelope raises — the kernel never gives way to the XLA path on
its own.

On ONE device ``blocked_self_attention`` is the same streaming softmax over
blocks of one sequence (causal, optionally within a window, grouped KV
heads), in two executions it chooses between itself: on a TPU, with heads
of 64 or of a multiple of 128 (``_kernels_take``), jax's fused
splash-attention kernels, in which a tile's scores stay in VMEM; elsewhere
loops of XLA operations.

Derived from the ring-attention pattern in the public pallas guide and the
scaling-book recipe: shift-K/V ring + online softmax.
"""

from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry as _telemetry

NEG_INF = -1e30


def _block_attn(q, k, v, bias):
    """Scores and partial numerator/denominator for one (q-block, kv-block)
    pair with streaming-softmax bookkeeping. Score/accumulator math in
    float32 regardless of input dtype (flash-attention numerics)."""
    s = jnp.einsum(
        "...qhd,...khd->...hqk",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
    ) / math.sqrt(q.shape[-1])
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)  # [..., h, q]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)  # [..., h, q]
    o = jnp.einsum("...hqk,...khd->...qhd", p, v.astype(jnp.float32))
    return o, m, l


def _merge(acc, blk):
    """Streaming-softmax merge of two partial results ``(o, m, l)``: the
    numerators ``[b, q, h, d]`` and denominators ``[b, h, q]`` brought to
    the larger of the two running maxima."""
    (o, m, l), (ob, mb, lb) = acc, blk
    m_new = jnp.maximum(m, mb)
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(mb - m_new)
    o_new = (
        o * alpha.transpose(0, 2, 1)[..., None]
        + ob * beta.transpose(0, 2, 1)[..., None]
    )
    return o_new, m_new, l * alpha + lb * beta


def ring_self_attention(
    q,
    k,
    v,
    axis: str = "sp",
    causal: bool = False,
    axis_size: Optional[int] = None,
    backend: str = "xla",
):
    """Exact self-attention over a sequence sharded along ``axis``.

    Args: q/k/v of shape ``[batch, seq_local, heads, head_dim]`` — the local
    sequence shard. Returns the attention output for the local queries,
    identical (up to float error) to full attention over the gathered
    sequence.

    ``backend``: ``'xla'`` (ppermute ring) or any
    combination of ``'pallas'`` with the suffix tokens ``_interpret``
    (interpret mode — CPU-mesh validation), ``_bidir`` (bidirectional
    forward: both ICI directions carry K/V chains, ~half the ring
    steps), and ``_full`` (RDMA backward kernel too — dK/dV accumulators
    ride the ring home with their blocks; default backward is the
    analytic XLA ring from the saved residuals). E.g.
    ``'pallas_interpret_bidir_full'``.

    Causal masking accounts for the global positions: the k/v block visiting
    at ring step s originated on rank ``(r - s) mod p``, so its global
    offset is known statically per step.
    """
    if backend != "xla":
        from ..ops.ring_attention_kernel import ring_attention

        tokens = set(backend.split("_"))
        if not backend.startswith("pallas") or not tokens <= {
            "pallas", "interpret", "full", "bidir"
        }:
            raise ValueError(f"unknown ring-attention backend {backend!r}")
        return ring_attention(
            q, k, v, axis, causal, axis_size,
            "interpret" in tokens,
            "full" in tokens,
            None,
            "bidir" in tokens,
        )
    p = axis_size or lax.axis_size(axis)
    b, n_local, h, d = q.shape
    r = lax.axis_index(axis)
    perm = [(i, (i + 1) % p) for i in range(p)]

    q_pos = r * n_local + jnp.arange(n_local)  # global query positions

    def step(s, carry):
        o, m, l, kv = carry
        kb, vb = kv
        src = (r - s) % p  # which rank's shard we hold this step
        k_pos = src * n_local + jnp.arange(n_local)
        bias = None
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]  # [q, k]
            bias = jnp.where(mask, 0.0, NEG_INF)[None, None, :, :]
        o_new, m_new, l_new = _merge(
            (o, m, l), _block_attn(q, kb, vb, bias))
        # rotate k/v to the next rank (skip the final, unused rotation is
        # harmless and keeps the loop body uniform)
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        return o_new, m_new, l_new, (kb, vb)

    o0 = jnp.zeros((b, n_local, h, d), jnp.float32)  # f32 accumulator
    m0 = jnp.full((b, h, n_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, n_local), jnp.float32)
    o, m, l, _ = lax.fori_loop(0, p, step, (o0, m0, l0, (k, v)))
    l = jnp.maximum(l, 1e-30)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def full_self_attention(q, k, v, causal: bool = False):
    """Single-device reference attention (for parity tests)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        n = q.shape[1]
        mask = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


# ---------------------------------------------------------------------------
# blocked attention on one device: causal band, grouped KV heads
# ---------------------------------------------------------------------------


def _first_block(i, block: int, window: Optional[int]):
    """The first block of keys that holds a key visible to any query of
    query block ``i`` (the last is ``i`` itself): blocks wholly behind the
    window are not visited."""
    if window is None:
        return 0
    return jnp.maximum(0, (i * block - (window - 1)) // block)


def _band_bias(i, j, block: int, window: Optional[int], groups: int):
    """The additive mask ``[groups * block, block]`` of query block ``i``
    against key block ``j`` (the query rows repeat once for each head of a
    KV group, head-major): key ``b`` is visible to query ``a`` iff ``0 <=
    a - b`` and, with a window, ``a - b < window``."""
    a = i * block + jnp.arange(block)[:, None]
    b = j * block + jnp.arange(block)[None, :]
    seen = b <= a
    if window is not None:
        seen = seen & (a - b < window)
    return jnp.tile(jnp.where(seen, 0.0, NEG_INF), (groups, 1))


def _fold(x, groups):
    """``[b, t, hkv * g, d] -> [b, g * t, hkv, d]``: the ``g`` query heads
    that share a KV head become further query rows of that head, so that a
    block routine written for equal head counts serves grouped heads and K
    and V are never repeated."""
    b, t, h, d = x.shape
    x = x.reshape(b, t, h // groups, groups, d)
    return x.transpose(0, 3, 1, 2, 4).reshape(b, groups * t, h // groups, d)


def _unfold(x, groups):
    b, gt, hkv, d = x.shape
    x = x.reshape(b, groups, gt // groups, hkv, d)
    return x.transpose(0, 2, 3, 1, 4).reshape(b, gt // groups, hkv * groups, d)


def _rows(x, i, block: int):
    """Block ``i`` of ``x``'s sequence axis (axis 1)."""
    return lax.dynamic_slice_in_dim(x, i * block, block, axis=1)


# Traced once a shape and laid into the caller's program as it is (``inline``:
# no call is left there), as ``_blocked_backward`` and ``_either`` are: a
# model of 24 unrolled blocks would else trace the loops 24 times forward and
# again wherever a derivative rule transposes the branch they stand in, 13 s
# of every start; with ``_either`` alone decorated still 6.6 s, 14 % of a
# warm start, so all three stay (PERF.md, PR 38).
@partial(jax.jit, static_argnums=(3, 4), inline=True)
def _blocked_forward(q, k, v, window, block):
    """(output ``[b, t, hq, d]`` in ``q``'s dtype; the log-sum-exp of each
    query's scores, ``[blocks, b, hkv, g * block]``). ``t`` is a multiple
    of ``block`` here. Loops, not unrolled pairs: one pair's scores live
    at a time, and the program stays small."""
    b, t, hq, d = q.shape
    g = hq // k.shape[2]

    def one(i):
        qf = _fold(_rows(q, i, block), g)

        def visit(j, acc):
            blk = _block_attn(qf, _rows(k, j, block), _rows(v, j, block),
                              _band_bias(i, j, block, window, g))
            return _merge(acc, blk)

        hkv = k.shape[2]
        acc = (jnp.zeros((b, g * block, hkv, d), jnp.float32),
               jnp.full((b, hkv, g * block), NEG_INF, jnp.float32),
               jnp.zeros((b, hkv, g * block), jnp.float32))
        o, m, l = lax.fori_loop(_first_block(i, block, window), i + 1,
                                visit, acc)
        out = _unfold(o / l.transpose(0, 2, 1)[..., None], g)
        return out.astype(q.dtype), m + jnp.log(l)

    outs, lses = lax.map(one, jnp.arange(t // block))
    return jnp.moveaxis(outs, 0, 1).reshape(b, t, hq, d), lses


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _blocked(q, k, v, window, block):
    return _blocked_forward(q, k, v, window, block)[0]


def _blocked_fwd(q, k, v, window, block):
    out, lses = _blocked_forward(q, k, v, window, block)
    return out, (q, k, v, out, lses)


def _blocked_bwd(window, block, saved, dout):
    return _blocked_backward(*saved, dout, window, block)


@partial(jax.jit, static_argnums=(6, 7), inline=True)
def _blocked_backward(q, k, v, out, lses, dout, window, block):
    """Backward from the saved output and log-sum-exps: each block pair's
    probabilities are made again from its scores, so nothing of size
    ``t x t`` is ever kept."""
    f32 = jnp.float32
    g = q.shape[2] // k.shape[2]
    scale = 1.0 / math.sqrt(q.shape[-1])

    def per_query_block(i, grads):
        qf = _fold(_rows(q, i, block), g).astype(f32)
        do = _fold(_rows(dout, i, block), g).astype(f32)
        delta = jnp.sum(
            do * _fold(_rows(out, i, block), g).astype(f32), axis=-1
        ).transpose(0, 2, 1)  # [b, h, q]
        lse = lses[i]

        def visit(j, carry):
            dqf, dk, dv = carry
            kb = _rows(k, j, block).astype(f32)
            vb = _rows(v, j, block).astype(f32)
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb) * scale
            s = s + _band_bias(i, j, block, window, g)
            p = jnp.exp(s - lse[..., None])
            dp = jnp.einsum("bqhd,bkhd->bhqk", do, vb)
            ds = p * (dp - delta[..., None]) * scale
            dqf = dqf + jnp.einsum("bhqk,bkhd->bqhd", ds, kb)
            at = j * block
            dk = lax.dynamic_update_slice_in_dim(
                dk, _rows(dk, j, block)
                + jnp.einsum("bhqk,bqhd->bkhd", ds, qf), at, axis=1)
            dv = lax.dynamic_update_slice_in_dim(
                dv, _rows(dv, j, block)
                + jnp.einsum("bhqk,bqhd->bkhd", p, do), at, axis=1)
            return dqf, dk, dv

        dq, dk, dv = grads
        dqf, dk, dv = lax.fori_loop(
            _first_block(i, block, window), i + 1, visit,
            (jnp.zeros_like(qf), dk, dv))
        dq = lax.dynamic_update_slice_in_dim(
            dq, _unfold(dqf, g).astype(dq.dtype), i * block, axis=1)
        return dq, dk, dv

    dq, dk, dv = lax.fori_loop(
        0, q.shape[1] // block, per_query_block,
        (jnp.zeros_like(q), jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32)))
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_blocked.defvjp(_blocked_fwd, _blocked_bwd)


# ---------------------------------------------------------------------------
# the same attention in fused kernels: a tile's scores never leave VMEM
# ---------------------------------------------------------------------------

LANES = 128  # the TPU's vector lanes: what the kernels' tiles must be
#              multiples of, and their heads but for half of it
SAVED = "tm_attn_saved"  # checkpoint_name of what an attention call's
#                          forward kernels hand to its backward kernels: the
#                          one name a recomputed block keeps (it is
#                          ``selected_attention``'s too; the policy is
#                          ``models.lm.recomputed``)


def _kernels_take(head_dim: int) -> bool:
    """Whether the fused kernels take heads of this width: the multiples
    of the lanes, and half the lanes (GPT-2's 64, which the kernels run as
    it comes: PERF.md, PR 38). No other width was measured on a chip, and
    those keep the loops. The one rule of the call and of its gauge."""
    return head_dim % LANES == 0 or head_dim == LANES // 2


def _fused_tile(t: int) -> int:
    """The kernels' tile of queries and of keys for a sequence of ``t``:
    1,024 (on a v5e the tile that ran fastest forward and backward at 8,192
    positions: PERF.md, PR 27; 2,048 queries do not fit VMEM), and for a
    shorter sequence the multiple of the lanes that holds it (GPT-2's
    1,024 positions are one tile, the whole square under its mask: its
    step ran 1.6 % faster so than with four of 512, of which three are
    visited: PERF.md, PR 38)."""
    return min(1024, -(-t // LANES) * LANES)


@lru_cache(maxsize=32)
def _fused_kernel(t: int, groups: int, window: Optional[int],
                  interpret: bool):
    """jax's fused attention kernels (``pallas.ops.tpu.splash_attention``)
    over the causal band of a ``t x t`` square (``t`` a multiple of its
    tile) for ``[b, hkv, groups, t, d]`` queries and ``[b, hkv, t, d]`` keys
    and values, one KV head at a time with its ``groups`` query heads: a
    forward kernel that saves the log-sum-exp and ONE backward kernel that
    makes the probabilities again and gives dQ, dK and dV (a second kernel
    for dQ alone would make them twice; it measured slower). Built once a
    shape.
    What forward hands to backward beside ``q``, ``k`` and ``v``, the output
    and the log-sum-exp, bears the ``checkpoint_name`` ``SAVED``: inert
    without a policy, and under ``save_only_these_names(SAVED)`` a caller
    that recomputes its block in backward keeps the two and does not run
    the forward kernel a second time. Tiles wholly outside the band do no
    work, tiles wholly inside it run without a mask, and the mask of the
    others is computed in the kernel from the positions. A tile of keys is
    worked through in pieces of 512 where it divides so."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel,
        splash_attention_mask as masks,
    )

    if window is None or window >= t:
        band = masks.CausalMask((t, t))
    else:
        band = masks.LocalMask((t, t), window_size=(window - 1, 0), offset=0)
    tile = _fused_tile(t)
    piece = 512 if tile % 512 == 0 else tile
    with jax.ensure_compile_time_eval():  # the tile tables: constants
        one_kv_head = kernel.make_splash_mqa_single_device(
            masks.MultiHeadMask([band] * groups),
            block_sizes=kernel.BlockSizes(
                block_q=tile, block_kv=tile, block_kv_compute=piece,
                block_q_dkv=tile, block_kv_dkv=tile,
                block_kv_dkv_compute=piece, use_fused_bwd_kernel=True),
            residual_checkpoint_name=SAVED, interpret=interpret)
    return _held_by_forward(jax.vmap(jax.vmap(one_kv_head)))


def _held_by_forward(attend):
    """``attend(q, k, v)`` with everything its derivative rule hands to
    backward made where its output is made: the kernels write each row's
    log-sum-exp a lane wide (128 float32 a row, twice the bytes of a
    bfloat16 output of heads of 128) and their rule keeps one column of it,
    a slice that XLA, left alone, makes where backward first reads it, so
    that the wide array lives from forward to backward (240 MiB in place
    of 81 over ``falcon-h1-34b``'s four layers, enough there for XLA to fit
    the step by making the head's product twice: PERF.md, PR 40). A barrier
    over the output and the rule's residuals together, in forward, has the
    slice made before the output is read. Backward is the rule's own."""
    @jax.custom_vjp
    def held(q, k, v):
        return attend(q, k, v)

    def forward(q, k, v):
        return lax.optimization_barrier(jax.vjp(attend, q, k, v))

    held.defvjp(forward, lambda pullback, dout: pullback(dout))
    return held


def _pad_sequence(q, k, v, multiple: int):
    """``q``, ``k``, ``v`` with their sequence axis padded to a multiple of
    ``multiple``: keys past the end lie in every real query's future, and
    the caller cuts the rows of the queries past the end off again."""
    pad = -q.shape[1] % multiple
    if not pad:
        return q, k, v
    return tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                 for a in (q, k, v))


def _fused(q, k, v, window: Optional[int], interpret: bool = False):
    """``blocked_self_attention`` in the fused kernels; ``head_dim`` is one
    ``_kernels_take`` here, and the kernels run it as it comes (a head of
    64 fills half the lanes of its tiles; padded with zeros to 128 it ran
    slower: PERF.md, PR 38). The kernels apply no scale: ``q`` is
    multiplied by ``1 / sqrt(head_dim)`` in its own dtype on the way in
    (one more rounding of a bfloat16 ``q``, where the loops scale the
    float32 scores; exact where the width is a power of four, as 64 is).
    The products take ``q``, ``k``, ``v`` as they come
    (bfloat16 into the MXU), the running maximum, normaliser and
    accumulator are float32. ``interpret``: on the CPU, for the tests."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    q, k, v = _pad_sequence(q, k, v, _fused_tile(t))
    padded = q.shape[1]
    q = (q * (1.0 / math.sqrt(d))).astype(q.dtype)
    # [b, t, hkv * g, d] -> [b, hkv, g, t, d]: a KV head's query heads share
    # its K and V, which are not repeated
    q = q.reshape(b, padded, hkv, g, d).transpose(0, 2, 3, 1, 4)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    out = _fused_kernel(padded, g, window, interpret)(q, k, v)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, padded, hq, d)[:, :t]


def _loops(q, k, v, window: Optional[int], block: int):
    """``blocked_self_attention`` as XLA operations: ``_blocked``'s loops."""
    t = q.shape[1]
    block = min(int(block), t)
    q, k, v = _pad_sequence(q, k, v, block)
    return _blocked(q, k, v, window, block)[:, :t]


def _attention_gauges():
    m = _telemetry.metrics
    return (
        m.gauge(
            "tm_attn_calls_per_step",
            "blocked_self_attention calls in the step most recently traced "
            "(one a layer; since the model's last note_attention_step)"),
        m.gauge(
            "tm_attn_kernel_calls_per_step",
            "those of tm_attn_calls_per_step that take the fused kernels: "
            "head_dim a width the kernels take (_kernels_take), traced "
            "where jax's backend is a TPU"),
    )


def note_attention_step() -> None:
    """Start the count of a step's attention calls: a model calls this
    where its forward pass begins to be traced, so that the two gauges
    describe the step most recently traced (as ``ep.note_expert_layers``'
    do)."""
    for gauge in _attention_gauges():
        gauge.set(0)


def _note_attention_call(fused: bool) -> None:
    calls, taken = _attention_gauges()
    calls.set((calls.value() or 0) + 1)
    taken.set((taken.value() or 0) + int(fused))


def blocked_self_attention(q, k, v, window: Optional[int] = None,
                           block: int = 1024):
    """Exact causal self-attention on one device that never holds a
    ``t x t`` tensor: blocked streaming-softmax attention, in one of two
    executions chosen by what the call can observe.

    ``q`` is ``[batch, t, heads, head_dim]``; ``k`` and ``v`` may have fewer
    heads (grouped KV heads: query head ``n`` reads KV head ``n // (heads //
    kv_heads)``), and are not repeated. Key ``j`` is visible to query ``i``
    iff ``0 <= i - j`` and, with ``window``, ``i - j < window``. Blocks
    wholly outside that band are skipped, not masked; ``t`` need not be a
    multiple of a block (it is padded to one).

    Where the step is lowered for a TPU and ``head_dim`` is a width the
    kernels take (``_kernels_take``): fused kernels
    (``_fused``), forward and backward, in which a tile's scores,
    probabilities and their gradients live in VMEM and never reach HBM; the
    tiles are chosen from ``t`` (``_fused_tile``). Everywhere else (the
    CPU, other widths): XLA operations (``_loops``), blocks of
    ``block`` queries against blocks of keys through ``_block_attn`` and
    the streaming-softmax merge of the ring, each block pair's float32
    scores passing through memory. Both keep the log-sum-exp and make each
    pair's probabilities again in backward. The kernels' output and
    log-sum-exp bear the ``checkpoint_name`` ``SAVED``: a caller that
    recomputes its block under ``jax.checkpoint`` with a policy of
    ``save_only_these_names(SAVED)`` keeps them (2 B x ``head_dim`` + 4 B a
    query and head) and runs the forward kernel once a step; the loops name
    nothing, and without a policy the name does nothing. The platform is
    the one the program is lowered for (``lax.platform_dependent``), not
    the process's default; both executions are traced wherever the heads
    allow the kernels, and the kernels' results declare no varying mesh
    axes, so inside a ``shard_map`` this wants ``check_vma=False`` (the
    engine's)."""
    if q.shape[2] % k.shape[2] or k.shape != v.shape:
        raise ValueError(
            f"query heads {q.shape[2]} must be a multiple of the KV heads "
            f"{k.shape[2]}, and k and v alike (got {k.shape}, {v.shape})")
    if window is not None and window < 1:
        raise ValueError(f"window must be positive, got {window}")
    # the gauge is set while the call is traced, when the platform of the
    # lowering is not known yet: it goes by the process's backend
    _note_attention_call(
        _kernels_take(q.shape[-1]) and jax.default_backend() == "tpu")
    return _either(q, k, v, window=window, block=int(block))


@partial(jax.jit, static_argnames=("window", "block"), inline=True)
def _either(q, k, v, window, block):
    """The two executions under the choice between them; see
    ``_blocked_forward`` for the decorator."""
    loops = partial(_loops, window=window, block=block)
    if not _kernels_take(q.shape[-1]):
        return loops(q, k, v)
    return lax.platform_dependent(
        q, k, v, tpu=partial(_fused, window=window), default=loops)
