"""Pallas ring-attention kernel: double-buffered K/V RDMA ring with the
streaming-softmax merge fused in-kernel.

The sequence-parallel capability extension (SURVEY.md §5: "ICI ring =
natural fit for ring attention") taken down to the transport the custom
ring collectives already use: queries stay resident in VMEM, K/V blocks
rotate around the mesh axis via inter-chip RDMA
(``pltpu.make_async_remote_copy``) into double-buffered VMEM slots, and
each ring step's block attention + flash-style online-softmax merge
(running max ``m``, normalizer ``l``, f32 accumulator ``o``) executes
while the next block is in flight — the same communication/compute
overlap the XLA ``ppermute`` path (``parallel/ring_attention.py``) asks
the compiler for, made explicit.

Transport discipline mirrors ``ring_kernels._ring_phases_kernel`` (the
reference's receive-centric ring, ``lib/detail/collectives_cuda.cpp:
202-388``): a neighbor barrier before the first push, per-step
``copy.wait()`` (send landed + symmetric incoming block arrived), and a
capacity semaphore closing the fast-sender/slow-consumer race — slot
``s%2`` is re-written by the LEFT neighbor at step s+1, so the consumer
signals left after its step-s compute and a sender waits for that signal
before pushing (signals stop two steps early so every semaphore ends the
kernel drained).

Numerics are the flash-attention contract: scores and accumulators in
float32 regardless of input dtype; outputs cast back. Causal masking
uses the static ring schedule — the block visiting at step s originated
on rank ``(r - s) mod p``, so global key positions are known in-kernel.

Differentiation: ``pallas_call`` has no autodiff, so the public
:func:`ring_attention` wraps the kernel in a ``jax.custom_vjp``. The
kernel saves the flash residuals — the output and the global
log-sum-exp — and the backward is the ANALYTIC flash-attention gradient
over a second K/V ring (``_ring_attention_bwd_xla``, ppermute
transport): ``P = exp(S - lse)``, ``dS = P (dP - rowsum(dO∘O))``, with
dK/dV accumulators riding the ring home. No forward recompute on the
gradient path — training with the pallas backend costs one kernel
forward plus one analytic backward, the same step economics as the XLA
ring's autodiff.

Tests validate correctness in TPU interpret mode on the virtual CPU mesh
(p = 2..8, causal x dtypes, vs gathered-sequence full attention);
``chip_smoke.py`` compiles forward (uni, bidir) and backward through
Mosaic on real chips against the XLA ring. A (batch, head) cell holds its
whole [n_local, n_local] score tile in VMEM, which bounds the local
sequence (about 2048 forward, 1024 backward at head_dim <= 128) — past
that the wrappers raise instead of giving way to the XLA ring.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ring_kernels import _LANES, neighbor_barrier

NEG_INF = -1e30

# Scoped-VMEM limit handed to Mosaic for every kernel here (a v5e core has
# 128 MiB; the compiler's default scope is 16 MiB), and the share of it
# the ``*_vmem_bytes`` working-set estimates may fill before a call is
# chunked over batch/heads — the rest is the compiler's own temporaries.
_VMEM_LIMIT_BYTES = 100 * 1024 * 1024
_VMEM_BUDGET_BYTES = 88 * 1024 * 1024


def _lane_pad(d: int) -> int:
    """Head dim as the kernels hold it: whole 128-lane tiles. A remote
    copy of a [.., n, d] slot with d < 128 is rejected by Mosaic ("slice
    shape must be aligned to tiling"), so the wrappers zero-pad q/k/v
    (scores and outputs are unchanged) and slice the result."""
    return -(-d // _LANES) * _LANES


def _column_bytes(b: int, h: int, n: int) -> int:
    """One [bh, n, 1] f32 column (m, l, lse, D): its single lane pads to
    a full 128-lane tile in VMEM."""
    return b * h * n * _LANES * 4


def _score_bytes(n: int) -> int:
    """Per-cell [n, n] f32 intermediates live at once (scores, exp and
    the masked/scaled copy the compiler keeps)."""
    return 3 * n * n * 4


def _flash_merge_cells(
    bh, n, my, src, causal, scale, q_ref, kbuf, vbuf, slot,
    oacc, macc, lacc,
):
    """Merge the K/V block in ``(kbuf, vbuf)[slot]`` (originating on rank
    ``src``) into the running flash accumulators, one 2D MXU step per
    (b, h) cell. Shared by the uni- and bidirectional forward kernels —
    the merge is order-independent, which is what makes the bidir
    schedule valid."""

    def cell(i, _):
        qi = q_ref[i].astype(jnp.float32)  # [n, d]
        ki = kbuf[slot, i].astype(jnp.float32)
        vi = vbuf[slot, i].astype(jnp.float32)
        sij = (
            lax.dot_general(
                qi, ki, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [n(q), n(k)]
        if causal:
            qpos = lax.broadcasted_iota(jnp.int32, (n, n), 0) + my * n
            kpos = lax.broadcasted_iota(jnp.int32, (n, n), 1) + src * n
            sij = jnp.where(qpos >= kpos, sij, NEG_INF)
        mb = jnp.max(sij, axis=1, keepdims=True)  # [n, 1]
        pexp = jnp.exp(sij - mb)
        lb = jnp.sum(pexp, axis=1, keepdims=True)  # [n, 1]
        ob = lax.dot_general(
            pexp, vi, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [n, d]
        m_old = macc[i]  # [n, 1]
        m_new = jnp.maximum(m_old, mb)
        alpha = jnp.exp(m_old - m_new)
        beta = jnp.exp(mb - m_new)
        lacc[i] = lacc[i] * alpha + lb * beta
        oacc[i] = oacc[i] * alpha + ob * beta
        macc[i] = m_new
        return 0

    lax.fori_loop(0, bh, cell, 0)


def _ring_attn_kernel(
    p: int,
    axis: str,
    causal: bool,
    scale: float,
    n: int,
    my_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    kbuf,
    vbuf,
    oacc,
    macc,
    lacc,
    send_k,
    recv_k,
    send_v,
    recv_v,
    cap_sem,
):
    """One device's program. ``q/k/v/o_ref``: [bh, n, d] VMEM (batch*heads
    flattened to the leading dim; every cell's math is 2D for the MXU).
    ``lse_ref``: [bh, n, 1] f32 log-sum-exp of the global scores — the
    residual the analytic backward needs. ``kbuf/vbuf``: [2, bh, n, d]
    double-buffered ring slots. ``oacc``: [bh, n, d] f32; ``macc/lacc``:
    [bh, n, 1] f32 (2D per cell)."""
    my = my_ref[0]
    right = lax.rem(my + 1, p)
    left = lax.rem(my + p - 1, p)
    bh = q_ref.shape[0]

    oacc[:] = jnp.zeros_like(oacc)
    macc[:] = jnp.full_like(macc, NEG_INF)
    lacc[:] = jnp.zeros_like(lacc)
    kbuf[0] = k_ref[:]
    vbuf[0] = v_ref[:]

    neighbor_barrier(axis, left, right)

    def block_merge(s: int, slot: int):
        """Attention of resident q against the slot's K/V block, merged
        into the running (o, m, l) — one 2D flash step per (b, h) cell."""
        src = lax.rem(my - s + p, p)  # rank whose shard this block is
        _flash_merge_cells(
            bh, n, my, src, causal, scale, q_ref, kbuf, vbuf, slot,
            oacc, macc, lacc,
        )

    for s in range(p):
        slot = s % 2
        nslot = 1 - slot
        copies = ()
        if s < p - 1:
            # the RIGHT neighbor computes on its slot ``nslot`` at step
            # s-1; wait for its consumed-signal before overwriting
            if s >= 1:
                pltpu.semaphore_wait(cap_sem.at[nslot], 1)
            copies = tuple(
                pltpu.make_async_remote_copy(
                    src_ref=buf.at[slot],
                    dst_ref=buf.at[nslot],
                    send_sem=ssem.at[slot],
                    recv_sem=rsem.at[slot],
                    device_id={axis: right},
                    device_id_type=pltpu.DeviceIdType.MESH,
                )
                for buf, ssem, rsem in (
                    (kbuf, send_k, recv_k),
                    (vbuf, send_v, recv_v),
                )
            )
            for c in copies:
                c.start()
        block_merge(s, slot)  # compute overlaps the in-flight DMA
        for c in copies:
            c.wait()  # our send landed + next block fully arrived
        if s < p - 2:
            # tell LEFT our slot is consumed (left overwrites it at its
            # step s+1). Strictly after the wait above: the outgoing DMA
            # reads this slot until the send completes, so an earlier
            # signal would let left clobber bytes still in flight. No
            # signal for the last two steps so cap_sem ends drained.
            pltpu.semaphore_signal(
                cap_sem.at[slot],
                inc=1,
                device_id={axis: left},
                device_id_type=pltpu.DeviceIdType.MESH,
            )

    def finalize(i, _):
        li = jnp.maximum(lacc[i], 1e-30)
        o_ref[i] = (oacc[i] / li).astype(o_ref.dtype)
        lse_ref[i] = macc[i] + jnp.log(li)
        return 0

    lax.fori_loop(0, bh, finalize, 0)


def _sequence_after(x, dep):
    """Give ``x`` a data dependency on ``dep`` so XLA cannot overlap two
    ring kernels that share a ``collective_id`` (and thus barrier/DMA
    semaphore state) — chunked sub-calls must run strictly one after
    another."""
    return lax.optimization_barrier((x, dep))[0]


def _chunk_plan(b, h, fits) -> Optional[tuple]:
    """(b_chunk, h_chunk) making ``fits(b_chunk, h_chunk)`` true, halving
    heads first (keeps batches coherent), or None when even a single
    (batch, head) cell is too large."""
    hh = h
    while hh > 1 and not fits(b, hh):
        hh = (hh + 1) // 2
    bb = b
    while bb > 1 and not fits(bb, hh):
        bb = (bb + 1) // 2
    return (bb, hh) if fits(bb, hh) else None


def _run_chunked(b, h, fits, sub, concat_axes, cell_bytes, budget, what):
    """Shared dispatch for VMEM auto-chunking (forward AND backward use
    it — the plan heuristic, sequencing scheme, and error text must never
    diverge between them). ``sub(bi, bb, hi, hh, prev)`` runs one chunk
    (applying its own slicing and the ``prev`` sequencing dependency) and
    returns a tuple of outputs; chunks are concatenated along
    ``concat_axes`` over heads, then axis 0 over batches."""
    plan = _chunk_plan(b, h, fits)
    if plan is None:
        raise ValueError(
            f"one {what} (batch, head) cell of {cell_bytes} B exceeds "
            f"the VMEM envelope {budget} B; shard the sequence further "
            "or use the XLA ppermute backend"
        )
    bb, hh = plan
    prev = None
    out_rows: Optional[list] = None
    for bi in range(0, b, bb):
        row: Optional[list] = None
        for hi in range(0, h, hh):
            outs = sub(bi, bb, hi, hh, prev)
            prev = outs[0]
            if row is None:
                row = [[] for _ in outs]
            for acc, t in zip(row, outs):
                acc.append(t)
        merged = [
            jnp.concatenate(acc, axis=ax)
            for acc, ax in zip(row, concat_axes)
        ]
        if out_rows is None:
            out_rows = [[] for _ in merged]
        for acc, t in zip(out_rows, merged):
            acc.append(t)
    return tuple(jnp.concatenate(acc, axis=0) for acc in out_rows)


def _to_cells(t, dp: int):
    """[b, n, h, d] -> [b*h, n, dp] (head dim zero-padded to lanes)."""
    b, n, h, d = t.shape
    cells = t.transpose(0, 2, 1, 3).reshape(b * h, n, d)
    if dp != d:
        cells = jnp.pad(cells, ((0, 0), (0, 0), (0, dp - d)))
    return cells


def _from_cells(cells, b: int, h: int, d: int):
    """Inverse of :func:`_to_cells`: drop the lane padding."""
    n = cells.shape[1]
    return cells[..., :d].reshape(b, h, n, d).transpose(0, 2, 1, 3)


def _make_fwd(kernel_fn, vmem_bytes_fn, scratch_fn, collective_id, what):
    """Build a forward-ring entry point: ONE wrapper body (p == 1
    degenerate, batch/head auto-chunking, cell layout, pallas_call
    scaffolding) shared by the uni- and bidirectional kernels, so the
    chunk-plan/sequencing discipline can never diverge between them.
    ``scratch_fn(bh, n, d, k_dtype, v_dtype)`` returns the kernel's
    scratch list."""

    def fwd(
        q,
        k,
        v,
        axis: str = "sp",
        causal: bool = False,
        axis_size: Optional[int] = None,
        interpret: bool = False,
        return_lse: bool = False,
        vmem_budget_bytes: Optional[int] = None,
    ):
        p = axis_size or lax.axis_size(axis)
        b, n, h, d = q.shape
        if p == 1:
            if return_lse:
                # one score matrix serves both the output and the residual
                return _full_attention_with_lse(q, k, v, causal)
            from ..parallel.ring_attention import full_self_attention

            return full_self_attention(q, k, v, causal=causal)
        budget = vmem_budget_bytes or _VMEM_BUDGET_BYTES
        if vmem_bytes_fn(q.shape, q.dtype) > budget:
            def sub(bi, bb, hi, hh, prev):
                qs = q[bi:bi + bb, :, hi:hi + hh]
                if prev is not None:
                    qs = _sequence_after(qs, prev)
                return fwd(
                    qs,
                    k[bi:bi + bb, :, hi:hi + hh],
                    v[bi:bi + bb, :, hi:hi + hh],
                    axis=axis, causal=causal, axis_size=axis_size,
                    interpret=interpret, return_lse=True,
                    vmem_budget_bytes=budget,
                )

            out, lse = _run_chunked(
                b, h,
                lambda bb, hh: vmem_bytes_fn(
                    (bb, n, hh, d), q.dtype
                ) <= budget,
                sub, (2, 1),
                vmem_bytes_fn((1, n, 1, d), q.dtype), budget, what,
            )
            return (out, lse) if return_lse else out
        bh = b * h
        dp = _lane_pad(d)
        # [b, n, h, d] -> [bh, n, dp]: per-cell 2D math on the MXU
        to_cells = functools.partial(_to_cells, dp=dp)
        scale = 1.0 / math.sqrt(d)
        my = lax.axis_index(axis).astype(jnp.int32).reshape(1)
        kernel = functools.partial(
            kernel_fn, p, axis, causal, scale, n
        )
        out, lse = pl.pallas_call(
            kernel,
            out_shape=(
                jax.ShapeDtypeStruct((bh, n, dp), q.dtype),
                jax.ShapeDtypeStruct((bh, n, 1), jnp.float32),
            ),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ),
            scratch_shapes=scratch_fn(bh, n, dp, k.dtype, v.dtype),
            compiler_params=pltpu.CompilerParams(
                collective_id=collective_id,
                vmem_limit_bytes=_VMEM_LIMIT_BYTES,
            ),
            interpret=pltpu.InterpretParams() if interpret else False,
        )(my, to_cells(q), to_cells(k), to_cells(v))
        out = _from_cells(out, b, h, d)
        if return_lse:
            return out, lse.reshape(b, h, n)
        return out

    return fwd


def _uni_scratch(bh, n, d, k_dtype, v_dtype):
    return [
        pltpu.VMEM((2, bh, n, d), k_dtype),
        pltpu.VMEM((2, bh, n, d), v_dtype),
        pltpu.VMEM((bh, n, d), jnp.float32),
        pltpu.VMEM((bh, n, 1), jnp.float32),
        pltpu.VMEM((bh, n, 1), jnp.float32),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.REGULAR((2,)),
    ]


def ring_attention_vmem_bytes(local_shape, dtype) -> int:
    """Kernel working-set estimate for the given local q shape, as VMEM
    holds it: q/k/v/o plus the 2x2 double-buffered slots in ``dtype``, the
    f32 accumulator, the m/l/lse columns and one cell's score tiles."""
    b, n, h, d = local_shape
    cells = b * h * n * _lane_pad(d)
    itemsize = jnp.dtype(dtype).itemsize
    return (
        cells * (8 * itemsize + 4)
        + 3 * _column_bytes(b, h, n)
        + _score_bytes(n)
    )


ring_attention_pallas = _make_fwd(
    _ring_attn_kernel, ring_attention_vmem_bytes, _uni_scratch, 11,
    "ring-attention",
)
ring_attention_pallas.__doc__ = """Forward ring attention via the RDMA
kernel. Call inside ``shard_map``; q/k/v are the local shards
``[b, n_local, h, d]``. Not differentiable — training uses
:func:`ring_attention` (custom VJP). ``return_lse=True`` additionally
returns the global log-sum-exp ``[b, h, n_local]`` f32 (the backward's
residual).

A working set over the VMEM envelope is AUTO-CHUNKED over batch and
heads (attention is independent across both): each chunk runs its own
full K/V ring, so total wire traffic is unchanged — every head's K/V
still crosses each link exactly once per step — while per-call VMEM
fits. Only a single (batch, head) cell too large for the envelope
raises; sequence length then needs more sp shards or the XLA backend."""


def _l_hop_needed(s, p: int, nL: int):
    """Whether the bidirectional kernel's L-chain hop carrying UNWRAPPED
    source index ``s`` (= sender rank + step; >= p once the block crossed
    rank 0) does any work under causal masking.

    Under causal, a block from source rank ``src`` is merged only by
    receivers that see it as a PAST rank — on the L chain (blocks moving
    toward lower ranks) that happens only after the block wraps past
    rank 0. Pre-wrap hops are pure transport toward the wrap point. So
    the hop matters iff the block already wrapped (``s >= p``) or still
    can within the chain's ``nL`` distances (``s < nL``); otherwise the
    block is strictly-future for every receiver it can reach, all its
    merges are beta=0, and the send is wire spent on provably-zero
    contributions (ADVICE r5 ``ops/ring_attention_kernel.py:520``).

    Pairing invariant (what keeps the semaphores drained): sender rank
    ``r+1`` and receiver ``r`` evaluate the SAME unwrapped index for one
    hop — send gate ``_l_hop_needed((r+1) + t)`` vs recv gate
    ``_l_hop_needed(r + 1 + t)`` — and the capacity signal at ``(r, t)``
    matches the upstream's wait before its ``t+1`` send (both index
    ``r + t + 2``). ``tests/test_fusion.py`` checks the pairing
    exhaustively over p/t/rank."""
    return (s >= p) | (s < nL)


def _ring_attn_bidir_kernel(
    p: int,
    axis: str,
    causal: bool,
    scale: float,
    n: int,
    my_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    kbufR,
    vbufR,
    kbufL,
    vbufL,
    oacc,
    macc,
    lacc,
    sendR_k,
    recvR_k,
    sendR_v,
    recvR_v,
    sendL_k,
    recvL_k,
    sendL_v,
    recvL_v,
    capR,
    capL,
):
    """Bidirectional forward: TWO independent K/V chains rotate in
    opposite ICI directions (the torus has a link each way), so the ring
    finishes in ceil((p-1)/2) + 1 steps instead of p — total wire bytes
    unchanged, wall-clock halved when both link directions run at full
    rate (the same trade as ``ring_allreduce_bidir_pallas``). The
    streaming-softmax merge is order-independent, so visiting sources as
    {my, my±1, my±2, ...} instead of {my, my-1, my-2, ...} is exact.

    Per loop step t (t also = block distance): the R chain's slot holds
    the block from rank (my - t), the L chain's from (my + t). The R
    chain delivers distances 1..ceil((p-1)/2); the L chain distances
    1..floor((p-1)/2) — at t = 0 both slots hold the LOCAL block and it
    is merged exactly once. Each chain runs the unidirectional kernel's
    transport discipline (prefetch-send, per-step wait, capacity
    semaphores toward its upstream neighbor) with its own buffers and
    semaphores."""
    my = my_ref[0]
    right = lax.rem(my + 1, p)
    left = lax.rem(my + p - 1, p)
    bh = q_ref.shape[0]

    oacc[:] = jnp.zeros_like(oacc)
    macc[:] = jnp.full_like(macc, NEG_INF)
    lacc[:] = jnp.zeros_like(lacc)
    kbufR[0] = k_ref[:]
    vbufR[0] = v_ref[:]
    kbufL[0] = k_ref[:]
    vbufL[0] = v_ref[:]

    neighbor_barrier(axis, left, right)

    # distances delivered per chain; nR >= nL, nR + nL = p - 1
    nR = (p - 1 + 1) // 2
    nL = (p - 1) // 2

    def l_needed(s):
        return _l_hop_needed(s, p, nL)

    chains = (
        # (buffers, sems, cap, dst neighbor, cap-signal target,
        #  #distances, is_l_chain)
        ((kbufR, vbufR), (sendR_k, recvR_k, sendR_v, recvR_v), capR,
         right, left, nR, False),
        ((kbufL, vbufL), (sendL_k, recvL_k, sendL_v, recvL_v), capL,
         left, right, nL, True),
    )

    for t in range(nR + 1):
        slot = t % 2
        nslot = 1 - slot
        all_copies = []
        for (bufs, sems, cap, dst, cap_to, ndist, is_l) in chains:
            if t < ndist:  # this chain still has a farther block to push
                # causal L-chain hops that can never contribute are
                # skipped (transport and, below, the merge)
                gated = causal and is_l
                # gates agree pairwise across neighbors: my send at t is
                # my-1's recv at t (both l_needed(my + t) from the
                # sender's frame); my cap signal at t enables my+1's
                # send at t+1 (both l_needed(my + t + 2))
                p_out = l_needed(my + t) if gated else None
                if t >= 1:
                    if p_out is None:
                        pltpu.semaphore_wait(cap.at[nslot], 1)
                    else:
                        @pl.when(p_out)
                        def _():
                            pltpu.semaphore_wait(cap.at[nslot], 1)
                sk, rk, sv, rv = sems
                copies = tuple(
                    pltpu.make_async_remote_copy(
                        src_ref=buf.at[slot],
                        dst_ref=buf.at[nslot],
                        send_sem=ssem.at[slot],
                        recv_sem=rsem.at[slot],
                        device_id={axis: dst},
                        device_id_type=pltpu.DeviceIdType.MESH,
                    )
                    for buf, ssem, rsem in (
                        (bufs[0], sk, rk),
                        (bufs[1], sv, rv),
                    )
                )
                if p_out is None:
                    for c in copies:
                        c.start()
                else:
                    @pl.when(p_out)
                    def _():
                        for c in copies:
                            c.start()
                all_copies.append((copies, gated, cap, cap_to, ndist))
        # merge this step's visiting block(s); t = 0 merges the local
        # block exactly once (both chains hold it)
        if t == 0:
            _flash_merge_cells(
                bh, n, my, my, causal, scale, q_ref, kbufR, vbufR, 0,
                oacc, macc, lacc,
            )
        else:
            # the R chain reaches every loop step (nR >= nL); the L
            # chain stops one distance short when p is even
            _flash_merge_cells(
                bh, n, my, lax.rem(my - t + p, p), causal, scale,
                q_ref, kbufR, vbufR, slot, oacc, macc, lacc,
            )
            if t <= nL:
                if causal:
                    # The L chain's block at step t originated on rank
                    # my + t. Without wraparound (my + t < p) that rank
                    # is strictly FUTURE, so every (q, k) pair is masked
                    # and the merge is a numerical no-op (its beta
                    # underflows to exactly 0) — skip the matmuls. Only
                    # wrapped sources (my + t - p < my: past blocks)
                    # contribute.
                    @pl.when(my + t >= p)
                    def _():
                        _flash_merge_cells(
                            bh, n, my, lax.rem(my + t, p), causal, scale,
                            q_ref, kbufL, vbufL, slot, oacc, macc, lacc,
                        )
                else:
                    _flash_merge_cells(
                        bh, n, my, lax.rem(my + t, p), causal, scale,
                        q_ref, kbufL, vbufL, slot, oacc, macc, lacc,
                    )
        for copies, gated, cap, cap_to, ndist in all_copies:
            if not gated:
                for c in copies:
                    c.wait()
            else:
                # decoupled waits (the causal-gated L chain): my own send
                # completed iff I sent (l_needed(my + t)); the incoming
                # block from my+1 landed iff IT sent, which from my frame
                # is l_needed(my + t + 1). The copy descriptor's recv
                # semaphore is the SPMD-symmetric one the incoming copy
                # signals, so wait_recv on it observes the inbound DMA.
                @pl.when(l_needed(my + t))
                def _():
                    for c in copies:
                        c.wait_send()

                @pl.when(l_needed(my + t + 1))
                def _():
                    for c in copies:
                        c.wait_recv()
            # slot consumed + our outgoing read landed: upstream may
            # overwrite it at its next send. Its sends stop at t = ndist-1,
            # so signals stop one step earlier (semaphores end drained).
            if t < ndist - 1:
                if not gated:
                    pltpu.semaphore_signal(
                        cap.at[slot],
                        inc=1,
                        device_id={axis: cap_to},
                        device_id_type=pltpu.DeviceIdType.MESH,
                    )
                else:
                    # pairs with my+1's cap wait before its t+1 send,
                    # which carries source my + t + 2 — same gate
                    @pl.when(l_needed(my + t + 2))
                    def _():
                        pltpu.semaphore_signal(
                            cap.at[slot],
                            inc=1,
                            device_id={axis: cap_to},
                            device_id_type=pltpu.DeviceIdType.MESH,
                        )

    def finalize(i, _):
        li = jnp.maximum(lacc[i], 1e-30)
        o_ref[i] = (oacc[i] / li).astype(o_ref.dtype)
        lse_ref[i] = macc[i] + jnp.log(li)
        return 0

    lax.fori_loop(0, bh, finalize, 0)


def ring_attention_bidir_vmem_bytes(local_shape, dtype) -> int:
    """Bidir working set: the unidirectional envelope plus the second
    chain's 2x2 K/V slots."""
    b, n, h, d = local_shape
    cells = b * h * n * _lane_pad(d)
    itemsize = jnp.dtype(dtype).itemsize
    return (
        cells * (12 * itemsize + 4)
        + 3 * _column_bytes(b, h, n)
        + _score_bytes(n)
    )


def _bidir_scratch(bh, n, d, k_dtype, v_dtype):
    return [
        pltpu.VMEM((2, bh, n, d), k_dtype),
        pltpu.VMEM((2, bh, n, d), v_dtype),
        pltpu.VMEM((2, bh, n, d), k_dtype),
        pltpu.VMEM((2, bh, n, d), v_dtype),
        pltpu.VMEM((bh, n, d), jnp.float32),
        pltpu.VMEM((bh, n, 1), jnp.float32),
        pltpu.VMEM((bh, n, 1), jnp.float32),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.REGULAR((2,)),
        pltpu.SemaphoreType.REGULAR((2,)),
    ]


ring_attention_bidir_pallas = _make_fwd(
    _ring_attn_bidir_kernel, ring_attention_bidir_vmem_bytes,
    _bidir_scratch, 13, "bidirectional ring-attention",
)
ring_attention_bidir_pallas.__doc__ = """Forward ring attention with BOTH
ICI directions carrying K/V chains (~half the steps of
:func:`ring_attention_pallas`). Same call contract, residuals, and
batch/head auto-chunking.

Causal caveat: under ``causal=True`` the L chain mostly carries blocks
from strictly-future ranks (source ``my + t`` with no wraparound), whose
scores are fully masked. The kernel SKIPS both the merge compute for
those blocks AND their K/V sends: an L-chain hop runs only when its
block already wrapped past rank 0 or still can within the chain
(:func:`_l_hop_needed`), with send / recv / capacity-semaphore gates
matched pairwise across neighbors so the transport discipline stays
deadlock-free. Wire bytes saved, not just FLOPs (ADVICE r5). Even so,
causal workloads get less
than the full ~2x: the R chain carries ``ceil((p-1)/2)`` useful blocks
regardless — measure (``utils.autotune``) rather than assume."""


def _full_attention_with_lse(q, k, v, causal):
    """Single-shard attention returning ``(out, lse[b, h, n])`` from ONE
    score matrix — the p == 1 degenerate of the kernel + its residual."""
    n = q.shape[1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
    ) / math.sqrt(q.shape[-1])
    if causal:
        mask = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    w = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype), lse


def _ring_attention_bwd_xla(q, k, v, o, lse, do, axis, causal, p):
    """Analytic flash-attention backward over a second K/V ring (XLA
    ppermute transport). The forward's residuals make recomputing the
    forward unnecessary: per visiting block, the true probabilities are
    ``P = exp(S - lse)`` and ``dS = P * (dP - D)`` with
    ``D = rowsum(dO * O)``; dK/dV accumulators ride the ring WITH their
    blocks and are home after the p-th rotation. All accumulation in f32.
    """
    b, n, h, d = q.shape
    r = lax.axis_index(axis)
    perm = [(i, (i + 1) % p) for i in range(p)]
    scale = 1.0 / math.sqrt(d)

    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    # D_i = sum_d dO * O  -> [b, h, n]
    D = jnp.einsum("bqhd,bqhd->bhq", dof, o.astype(jnp.float32))
    q_pos = r * n + jnp.arange(n)

    def step(s, carry):
        dq, kb, vb, dkb, dvb = carry
        src = (r - s) % p
        k_pos = src * n + jnp.arange(n)
        sij = jnp.einsum("bqhd,bkhd->bhqk", qf, kb) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            sij = jnp.where(mask[None, None], sij, NEG_INF)
        pij = jnp.exp(sij - lse[..., None])  # true softmax probs
        dvb = dvb + jnp.einsum("bhqk,bqhd->bkhd", pij, dof)
        dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vb)
        ds = pij * (dp - D[..., None])
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds, kb) * scale
        dkb = dkb + jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * scale
        rot = lambda t: lax.ppermute(t, axis, perm)  # noqa: E731
        return dq, rot(kb), rot(vb), rot(dkb), rot(dvb)

    zeros = jnp.zeros((b, n, h, d), jnp.float32)
    dq, _, _, dk, dv = lax.fori_loop(
        0,
        p,
        step,
        (zeros, k.astype(jnp.float32), v.astype(jnp.float32), zeros, zeros),
    )
    # p rotations = identity: dk/dv finished the loop back on the rank
    # that owns their block
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _ring_attn_bwd_kernel(
    p: int,
    axis: str,
    causal: bool,
    scale: float,
    n: int,
    my_ref,
    q_ref,
    o_ref,
    do_ref,
    lse_ref,
    k_ref,
    v_ref,
    dq_ref,
    dk_ref,
    dv_ref,
    kbuf,
    vbuf,
    dkbuf,
    dvbuf,
    dqacc,
    dacc,
    send_k,
    recv_k,
    send_v,
    recv_v,
    send_dk,
    recv_dk,
    send_dv,
    recv_dv,
    cap_sem,
):
    """Backward ring program: the K/V blocks make a SECOND trip around the
    ring, this time carrying their dK/dV accumulators with them (the
    fused-transport philosophy of ``collectives_cuda.cpp:202-388``): each
    rank computes the analytic flash gradients against the visiting block
    from the saved (o, lse) residuals — no forward recompute — adds its
    contribution to the riding accumulators, THEN forwards the 4-tensor
    payload. p sends total, so the last hop is the homecoming: every
    block's finished dK/dV lands back on its owner.

    Transport discipline differs from the forward in one way: the forward
    pushes its (immutable) block while computing on it; here the payload
    is MUTATED by the compute, so the send follows the compute and the
    overlap is between this step's compute and the NEXT block's in-flight
    arrival. Capacity semaphores close the same fast-sender race: a send
    into the right neighbor's slot waits for that slot's consumed-signal.
    """
    my = my_ref[0]
    right = lax.rem(my + 1, p)
    left = lax.rem(my + p - 1, p)
    bh = q_ref.shape[0]

    kbuf[0] = k_ref[:]
    vbuf[0] = v_ref[:]
    dkbuf[0] = jnp.zeros_like(dkbuf[0])
    dvbuf[0] = jnp.zeros_like(dvbuf[0])
    dqacc[:] = jnp.zeros_like(dqacc)

    def dinit(i, _):
        # D = rowsum(dO ∘ O): the softmax-jacobian correction, f32
        dacc[i] = jnp.sum(
            do_ref[i].astype(jnp.float32) * o_ref[i].astype(jnp.float32),
            axis=1,
            keepdims=True,
        )
        return 0

    lax.fori_loop(0, bh, dinit, 0)

    neighbor_barrier(axis, left, right)

    def block_grad(s: int, slot: int):
        """Analytic flash gradients of the visiting block, accumulated
        into dqacc (stays) and dkbuf/dvbuf[slot] (rides onward)."""
        src = lax.rem(my - s + p, p)

        def cell(i, _):
            qi = q_ref[i].astype(jnp.float32)  # [n, d]
            doi = do_ref[i].astype(jnp.float32)
            ki = kbuf[slot, i].astype(jnp.float32)
            vi = vbuf[slot, i].astype(jnp.float32)
            sij = (
                lax.dot_general(
                    qi, ki, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [n(q), n(k)]
            if causal:
                qpos = lax.broadcasted_iota(jnp.int32, (n, n), 0) + my * n
                kpos = lax.broadcasted_iota(jnp.int32, (n, n), 1) + src * n
                sij = jnp.where(qpos >= kpos, sij, NEG_INF)
            pij = jnp.exp(sij - lse_ref[i])  # true probs ([n,1] lse bcasts)
            dvbuf[slot, i] += lax.dot_general(
                pij, doi, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [n(k), d]
            dp = lax.dot_general(
                doi, vi, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [n(q), n(k)]
            ds = pij * (dp - dacc[i])
            dqacc[i] += (
                lax.dot_general(
                    ds, ki, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )
            dkbuf[slot, i] += (
                lax.dot_general(
                    ds, qi, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )
            return 0

        lax.fori_loop(0, bh, cell, 0)

    for s in range(p):
        slot = s % 2
        nslot = 1 - slot
        block_grad(s, slot)
        # forward the mutated payload; the right neighbor's slot must be
        # consumed (its step s-1 compute done AND its own send of that
        # slot landed — it signals after its c.wait())
        if s >= 1:
            pltpu.semaphore_wait(cap_sem.at[nslot], 1)
        copies = tuple(
            pltpu.make_async_remote_copy(
                src_ref=buf.at[slot],
                dst_ref=buf.at[nslot],
                send_sem=ssem.at[slot],
                recv_sem=rsem.at[slot],
                device_id={axis: right},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            for buf, ssem, rsem in (
                (kbuf, send_k, recv_k),
                (vbuf, send_v, recv_v),
                (dkbuf, send_dk, recv_dk),
                (dvbuf, send_dv, recv_dv),
            )
        )
        for c in copies:
            c.start()
        for c in copies:
            c.wait()  # our payload landed + next block fully arrived
        if s < p - 1:
            # my slot is consumed and my outgoing read of it is complete:
            # left may overwrite it at its step s+1. No signal after the
            # last step so every semaphore ends the kernel drained.
            pltpu.semaphore_signal(
                cap_sem.at[slot],
                inc=1,
                device_id={axis: left},
                device_id_type=pltpu.DeviceIdType.MESH,
            )

    home = p % 2  # p sends: each block's accumulators are back home

    def fin(i, _):
        dq_ref[i] = dqacc[i].astype(dq_ref.dtype)
        dk_ref[i] = dkbuf[home, i].astype(dk_ref.dtype)
        dv_ref[i] = dvbuf[home, i].astype(dv_ref.dtype)
        return 0

    lax.fori_loop(0, bh, fin, 0)


def ring_attention_bwd_vmem_bytes(local_shape, dtype) -> int:
    """Backward working-set estimate: q/o/do/k/v inputs + dq/dk/dv outputs
    + 2x2 K/V slots in ``dtype``, 2x2 dK/dV slots + dq accumulator in f32,
    plus the [.., n, 1] lse/D columns."""
    b, n, h, d = local_shape
    cells = b * h * n * _lane_pad(d)
    itemsize = jnp.dtype(dtype).itemsize
    return (
        cells * (12 * itemsize + 20)
        + 2 * _column_bytes(b, h, n)
        + 2 * _score_bytes(n)
    )


def ring_attention_bwd_pallas(
    q, k, v, o, lse, do,
    axis: str = "sp",
    causal: bool = False,
    axis_size: Optional[int] = None,
    interpret: bool = False,
    vmem_budget_bytes: Optional[int] = None,
):
    """Analytic flash-attention backward on the RDMA ring (the transport
    symmetry the XLA-ppermute backward leaves on the table). ``lse`` is
    the forward's ``[b, h, n]`` residual. Returns (dq, dk, dv).
    Auto-chunks over batch/heads like the forward (each chunk rides its
    own ring; wire bytes unchanged)."""
    p = axis_size or lax.axis_size(axis)
    b, n, h, d = q.shape
    assert p > 1, "p == 1 has no ring; callers differentiate locally"
    budget = vmem_budget_bytes or _VMEM_BUDGET_BYTES
    if ring_attention_bwd_vmem_bytes(q.shape, q.dtype) > budget:
        def sub(bi, bb, hi, hh, prev):
            qs = q[bi:bi + bb, :, hi:hi + hh]
            if prev is not None:
                qs = _sequence_after(qs, prev)
            return ring_attention_bwd_pallas(
                qs,
                k[bi:bi + bb, :, hi:hi + hh],
                v[bi:bi + bb, :, hi:hi + hh],
                o[bi:bi + bb, :, hi:hi + hh],
                lse[bi:bi + bb, hi:hi + hh],
                do[bi:bi + bb, :, hi:hi + hh],
                axis=axis, causal=causal, axis_size=axis_size,
                interpret=interpret, vmem_budget_bytes=budget,
            )

        return _run_chunked(
            b, h,
            lambda bb, hh: ring_attention_bwd_vmem_bytes(
                (bb, n, hh, d), q.dtype
            ) <= budget,
            sub, (2, 2, 2),
            ring_attention_bwd_vmem_bytes((1, n, 1, d), q.dtype), budget,
            "ring-attention backward",
        )
    bh = b * h
    dp = _lane_pad(d)
    to_cells = functools.partial(_to_cells, dp=dp)
    scale = 1.0 / math.sqrt(d)
    my = lax.axis_index(axis).astype(jnp.int32).reshape(1)
    kernel = functools.partial(
        _ring_attn_bwd_kernel, p, axis, causal, scale, n
    )
    dq, dk, dv = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((bh, n, dp), q.dtype),
            jax.ShapeDtypeStruct((bh, n, dp), k.dtype),
            jax.ShapeDtypeStruct((bh, n, dp), v.dtype),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, bh, n, dp), k.dtype),
            pltpu.VMEM((2, bh, n, dp), v.dtype),
            pltpu.VMEM((2, bh, n, dp), jnp.float32),
            pltpu.VMEM((2, bh, n, dp), jnp.float32),
            pltpu.VMEM((bh, n, dp), jnp.float32),
            pltpu.VMEM((bh, n, 1), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=12, vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(
        my, to_cells(q), to_cells(o), to_cells(do),
        lse.reshape(bh, n, 1), to_cells(k), to_cells(v),
    )
    back = functools.partial(_from_cells, b=b, h=h, d=d)
    return back(dq), back(dk), back(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def ring_attention(
    q, k, v, axis, causal=False, axis_size=None, interpret=False,
    bwd_kernel=False, vmem_budget_bytes=None, fwd_bidir=False,
):
    """Differentiable ring attention: RDMA-kernel forward (uni- or, with
    ``fwd_bidir=True``, bidirectional — both ICI directions carry K/V
    chains, ~half the ring steps), with the backward either the analytic
    XLA ppermute ring (default) or the RDMA backward kernel
    (``bwd_kernel=True``). Either way the saved (o, lse) residuals mean
    no forward recompute on the gradient path. ``vmem_budget_bytes``
    overrides the auto-chunking envelope for BOTH directions (None =
    module default)."""
    fwd = ring_attention_bidir_pallas if fwd_bidir else ring_attention_pallas
    return fwd(
        q, k, v, axis=axis, causal=causal, axis_size=axis_size,
        interpret=interpret, vmem_budget_bytes=vmem_budget_bytes,
    )


def _ra_fwd(q, k, v, axis, causal, axis_size, interpret, bwd_kernel,
            vmem_budget_bytes, fwd_bidir):
    fwd = ring_attention_bidir_pallas if fwd_bidir else ring_attention_pallas
    out, lse = fwd(
        q, k, v, axis=axis, causal=causal, axis_size=axis_size,
        interpret=interpret, return_lse=True,
        vmem_budget_bytes=vmem_budget_bytes,
    )
    return out, (q, k, v, out, lse)


def _ra_bwd(axis, causal, axis_size, interpret, bwd_kernel,
            vmem_budget_bytes, fwd_bidir, res, g):
    q, k, v, o, lse = res
    p = axis_size or lax.axis_size(axis)
    if p == 1:
        # no ring to walk: differentiate the local full attention
        from ..parallel.ring_attention import full_self_attention

        _, vjp = jax.vjp(
            lambda q, k, v: full_self_attention(q, k, v, causal=causal),
            q, k, v,
        )
        return vjp(g)
    if bwd_kernel:
        return ring_attention_bwd_pallas(
            q, k, v, o, lse, g, axis=axis, causal=causal,
            axis_size=axis_size, interpret=interpret,
            vmem_budget_bytes=vmem_budget_bytes,
        )
    return _ring_attention_bwd_xla(q, k, v, o, lse, g, axis, causal, p)


ring_attention.defvjp(_ra_fwd, _ra_bwd)
