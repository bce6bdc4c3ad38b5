"""The fused-kernel execution of ``blocked_self_attention``
(parallel/ring_attention.py ``_fused``: jax's splash-attention kernels
behind the repo's layout) in interpret mode on the CPU, against the loops
it stands in for (``_loops``) and against a plain ``t x t`` masked softmax
in float32: outputs and the gradients of ``q``, ``k``, ``v``. Then the
choice between the two, the gauges that say which was taken, and the two
readers the benchmark gained. What a lowering for a TPU takes is in
``test_decoder_chip_compile.py`` (the one file that loads the TPU's
compiler)."""

import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchmpi_tpu import telemetry
from torchmpi_tpu.models import MoEDecoder
from torchmpi_tpu.parallel import blocked_self_attention, full_self_attention
from torchmpi_tpu.parallel.ring_attention import (
    LANES,
    _fused,
    _fused_tile,
    _kernels_take,
    _loops,
    note_attention_step,
)
from torchmpi_tpu.telemetry import names

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
HEAD = LANES  # the head of the decoders' cells
NARROW = LANES // 2  # GPT-2's, the narrowest head the kernels take


def dense_attention(q, k, v, window):
    """Every query against every key, masked: float32, no blocks."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def gauges():
    snap = telemetry.metrics.snapshot()
    return tuple(snap[k]["series"][""] for k in (
        "tm_attn_calls_per_step", "tm_attn_kernel_calls_per_step"))


# the tile is 1,024 from 1,024 positions on; a band's edge is worth a case
# wherever it meets a tile's edge
@pytest.mark.parametrize("t,window,heads,kv_heads,dtype,head", [
    (2048, None, 2, 2, jnp.float32, HEAD),   # no window, equal heads, 2 tiles
    (2048, None, 7, 1, jnp.bfloat16, HEAD),  # seven heads to a KV head
    (2048, 100, 7, 1, jnp.float32, HEAD),    # a window smaller than a tile
    (3072, 2100, 1, 1, jnp.float32, HEAD),   # a window of several tiles
    (1300, 2000, 7, 1, jnp.float32, HEAD),   # window >= t; padded to 2 tiles
    (2100, 1200, 2, 2, jnp.bfloat16, HEAD),  # padded, the band across tiles
    (300, None, 2, 1, jnp.float32, HEAD),    # shorter than a tile: one of 384
    (2048, 1024, 2, 1, jnp.float32, HEAD),   # the window a tile exactly
    (2048, 1025, 2, 1, jnp.float32, HEAD),   # ... and one key more
    (2048, 1, 2, 1, jnp.float32, HEAD),      # a window of the token itself
    # heads of 64 (GPT-2's: one KV head a query head), as they come: one
    # tile, a sequence padded to one tile, one padded to two
    (256, None, 3, 3, jnp.float32, NARROW),
    (256, None, 3, 3, jnp.bfloat16, NARROW),
    (700, None, 3, 3, jnp.float32, NARROW),
    (700, None, 3, 3, jnp.bfloat16, NARROW),
    (1100, None, 3, 3, jnp.bfloat16, NARROW),  # two tiles, the last padded
])
def test_fused_attention_matches_the_loops_and_a_dense_masked_softmax(
        t, window, heads, kv_heads, dtype, head):
    ks = jax.random.split(jax.random.PRNGKey(t + heads), 4)
    q = jax.random.normal(ks[0], (1, t, heads, head), dtype)
    k = jax.random.normal(ks[1], (1, t, kv_heads, head), dtype)
    v = jax.random.normal(ks[2], (1, t, kv_heads, head), dtype)
    w = jax.random.normal(ks[3], (1, t, heads, head), jnp.float32)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731

    def through(fn):
        def weighed(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.jit(jax.value_and_grad(
            weighed, argnums=(0, 1, 2), has_aux=True))

    (_, got), got_g = through(
        lambda q, k, v: _fused(q, k, v, window, interpret=True))(q, k, v)
    (_, loops), loops_g = through(
        lambda q, k, v: _loops(q, k, v, window, 512))(q, k, v)
    (_, want), want_g = through(
        lambda q, k, v: dense_attention(q, k, v, window))(
            *(a.astype(jnp.float32) for a in (q, k, v)))
    assert got.shape == q.shape and got.dtype == q.dtype
    if dtype == jnp.float32:
        # the tiles only change the order of the sums: a pair left out of
        # the band, or let into it, would move a row by 1 / its keys
        tol = {"rtol": 2e-5, "atol": 2e-5}
        grad_tol = 1e-4
    else:
        # bfloat16 results, and bfloat16 operands of the kernels' products
        # (the scaled q, dS, dO) where the loops' are float32
        tol = {"rtol": 2e-2, "atol": 2e-2}
        grad_tol = 3e-2
    np.testing.assert_allclose(f32(got), f32(want), **tol)
    np.testing.assert_allclose(f32(got), f32(loops), **tol)
    for mine, theirs, exact in zip(got_g, loops_g, want_g):
        assert mine.shape == exact.shape and mine.dtype == dtype
        # (a window of one key leaves q and k no gradient at all)
        scale = max(np.max(np.abs(f32(exact))), 0.1)
        assert np.max(np.abs(f32(mine) - f32(exact))) <= grad_tol * scale
        assert np.max(np.abs(f32(mine) - f32(theirs))) <= grad_tol * scale
    if window is None and heads == kv_heads:
        # the library's own t x t reference, which GPT-2's block called
        (_, full), full_g = through(
            lambda q, k, v: full_self_attention(q, k, v, causal=True))(
                *(a.astype(jnp.float32) for a in (q, k, v)))
        np.testing.assert_allclose(f32(full), f32(want), rtol=1e-5, atol=1e-5)
        for mine, exact in zip(got_g, full_g):
            scale = np.max(np.abs(f32(exact)))
            assert np.max(np.abs(f32(mine) - f32(exact))) <= grad_tol * scale


def test_the_tile_follows_the_sequence():
    assert [_fused_tile(t) for t in (1, 128, 129, 300, 1024, 1025, 8192)] == [
        128, 128, 256, 384, 1024, 1024, 1024]
    # every decoder cell's length, and every other from 2,048 on: PR 27's
    assert {_fused_tile(t) for t in (
        2048, 2049, 4096, 8192, 16384, 16385, 10**6)} == {1024}


def test_the_kernels_take_heads_of_64_and_of_the_lanes_multiples():
    assert [d for d in range(1, 513) if _kernels_take(d)] == [
        64, 128, 256, 384, 512]


@pytest.mark.parametrize("head_dim", [32, NARROW, HEAD])
def test_the_cpu_and_narrow_heads_take_the_loops_and_the_gauges_say_so(
        head_dim):
    """On a CPU lowering no kernel is left in the program, whatever the
    heads; with heads the kernels take (64, the lanes' multiples) they are
    offered (the traced program holds both executions) and the lowering
    drops them."""
    q = jax.ShapeDtypeStruct((1, 256, 4, head_dim), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 256, 2, head_dim), jnp.float32)
    fn = lambda q, k, v: blocked_self_attention(q, k, v, 64, 64)  # noqa: E731
    note_attention_step()
    assert gauges() == (0, 0)
    traced = str(jax.make_jaxpr(fn)(q, k, k))
    assert ("pallas_call" in traced) == (head_dim in (NARROW, HEAD))
    assert gauges() == (1, 0)
    lowered = jax.jit(fn).lower(q, k, k).as_text()
    assert "tpu_custom_call" not in lowered and "while" in lowered
    # and the result is the loops' either way
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 4, head_dim))
    np.testing.assert_allclose(
        jax.jit(fn)(x, x[:, :, :2], x[:, :, :2]),
        _loops(x, x[:, :, :2], x[:, :, :2], 64, 64), rtol=1e-6)


def test_a_models_forward_pass_counts_its_attention_calls():
    model = MoEDecoder(
        vocab_size=61, num_layers=5, d_model=32, num_heads=4, num_kv_heads=2,
        head_dim=8, expert_width=16, num_experts=4, top_k=2,
        held=(0, 1, 2, 3), window=12, attn_block=8, remat=True)
    toks = jnp.zeros((2, 24), jnp.int32)
    telemetry.metrics.gauge("tm_attn_calls_per_step").set(99)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), toks)
    assert gauges() == (5, 0)
    jax.eval_shape(
        jax.grad(lambda p: jnp.sum(model.apply(p, toks)[0])), params)
    assert gauges() == (5, 0)  # a layer's call, not each of its traces


def test_gpt2s_forward_pass_counts_its_attention_calls():
    """``gpt2-medium`` at its real size, traced and not run: one call a
    block, none of them the kernels' on the CPU (24 of 24 on a TPU)."""
    from benchmark import configs

    cfg = configs.load("gpt2-medium")
    built = configs.build("gpt2-medium", cfg)
    tokens = jax.ShapeDtypeStruct(
        (cfg["per_chip_batch"], cfg["sequence_length"]), jnp.int32)
    params, _ = jax.eval_shape(built.state_at, jax.random.PRNGKey(0))
    telemetry.metrics.gauge("tm_attn_calls_per_step").set(99)
    jax.eval_shape(built.loss_fn, params, (tokens, tokens))
    assert gauges() == (cfg["model"]["n_layer"], 0) == (24, 0)
    jax.eval_shape(jax.grad(built.loss_fn), params, (tokens, tokens))
    assert gauges() == (24, 0)  # a block's call, not each of its traces


def test_the_kernels_event_names_are_the_kernels_own():
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel,
    )

    own = {kernel.get_kernel_name(True, saved, False, phase)
           for phase, saved in (("fwd", True), ("fwd", False),
                                ("dq", False), ("dkv", False))}
    assert all(n.startswith(names.ATTN_KERNEL_EVENT) for n in own)
    assert not "ragged-dot-none".startswith(names.ATTN_KERNEL_EVENT)


# -- the benchmark's two readers -------------------------------------------
def reader(name):
    from benchmark import configs

    return configs.load_module(
        ROOT / "benchmark" / "layer_metrics" / f"{name}.py").read


@pytest.mark.parametrize("op_times,want", [
    # names as the chip's trace has them (PR 27), and bare
    ({"%splash_mqa_fwd_residuals.15 = (f32[2,4,512,128]{3,2,1,0:T(8,128": 0.32,
      "%splash_mqa_dkv_no_residuals.7 = (f32[2,4,512,128]{3,2,1,0:T(8,1": 0.40,
      "splash_mqa_fwd_no_residuals": 0.08,
      "%fusion.68 = (f32[2560,18992]{0,1:T(8,128)}, f32[2560,18992]{0,1": 1.0,
      "%ragged-dot-none.2 = bf16[8,768,2560]{2,1,0:T(8,128)(2,1)}": 0.5}, 50.0),
    ({"%fusion.68 = (f32[2560,18992]{0,1:T(8,128)}": 1.0,
      "%ragged-dot-none.2 = bf16[8,768,2560]": 0.5}, None),  # the parent
    ({}, None),
])
def test_attn_kernel_ms_reads_the_kernels_events_by_name(op_times, want):
    run = {"steady": {"op_times": op_times, "steps": 16, "devices": 1},
           "phase": {"traced_steps": 16}}
    got = reader("attn_kernel_ms_per_step")(run)
    assert got == (None if want is None else pytest.approx(want))


def test_attn_kernel_share_reads_the_two_gauges():
    read = reader("attn_kernel_share")
    for calls, taken, want in ((4, 4, 100.0), (4, 0, 0.0), (0, 0, None)):
        telemetry.metrics.gauge("tm_attn_calls_per_step").set(calls)
        telemetry.metrics.gauge("tm_attn_kernel_calls_per_step").set(taken)
        assert read({}) == want
