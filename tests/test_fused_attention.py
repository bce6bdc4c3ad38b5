"""The fused-kernel execution of ``blocked_self_attention``
(parallel/ring_attention.py ``_fused``: jax's splash-attention kernels
behind the repo's layout) in interpret mode on the CPU, against the loops
it stands in for (``_loops``) and against a plain ``t x t`` masked softmax
in float32: outputs and the gradients of ``q``, ``k``, ``v``. Then the
choice between the two, the gauges that say which was taken, and the two
readers the benchmark gained. What a lowering for a TPU takes is in
``test_decoder_cells.py`` and the ``test_chip_<config>.py`` files (the
files that load the TPU's compiler)."""

import math
import re
import sys
from pathlib import Path
from typing import Optional

import flax.linen as fnn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchmpi_tpu import telemetry
from torchmpi_tpu.models import MoEDecoder
from torchmpi_tpu.models.lm import recomputed
from torchmpi_tpu.parallel import blocked_self_attention, full_self_attention
from torchmpi_tpu.parallel.ring_attention import (
    LANES,
    SAVED,
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


# -- what a recomputed block keeps of its attention ---------------------------
class Attention(fnn.Module):
    """An attention call as a block of its own, for ``recomputed``."""

    window: Optional[int] = None
    interpret: Optional[bool] = None  # None: the call's own choice

    @fnn.compact
    def __call__(self, q, k, v):
        if self.interpret is None:
            return blocked_self_attention(q, k, v, self.window, 64)
        return _fused(q, k, v, self.window, interpret=self.interpret)


def loss_and_grads(block_cls, w, **fields):
    """value_and_grad of a weighed sum of the block's output, by q, k, v."""
    def weighed(q, k, v):
        out = block_cls(**fields).apply({}, q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w)
    return jax.value_and_grad(weighed, argnums=(0, 1, 2))


def forward_kernels(jaxpr) -> int:
    return len(re.findall(r"name=splash_mqa_fwd_residuals\b", str(jaxpr)))


@pytest.mark.parametrize("window", [None, 300], ids=["full", "window"])
@pytest.mark.parametrize("head", [NARROW, HEAD])
def test_a_recomputed_block_keeps_the_kernels_output_and_log_sum_exp(
        window, head):
    """Under ``recomputed`` (``jax.checkpoint`` with the models' policy) the
    forward kernel's two results are kept: backward holds no second forward
    kernel, where a plain ``jax.checkpoint`` holds one; and what was kept is
    what would have been made again, so the gradients are those of no
    policy and of no checkpoint, bit for bit."""
    t, heads, kv_heads = 1200, 4, 2  # two tiles, the last padded
    ks = jax.random.split(jax.random.PRNGKey(head), 4)
    q = jax.random.normal(ks[0], (1, t, heads, head), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, t, kv_heads, head), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, t, kv_heads, head), jnp.bfloat16)
    w = jax.random.normal(ks[3], q.shape, jnp.float32)
    fields = {"window": window, "interpret": True}
    kept = loss_and_grads(recomputed(Attention), w, **fields)
    again = loss_and_grads(fnn.remat(Attention), w, **fields)
    plain = loss_and_grads(Attention, w, **fields)
    # forward's kernel, then in backward: none, the recomputed one, none
    assert [forward_kernels(jax.make_jaxpr(f)(q, k, v))
            for f in (kept, again, plain)] == [1, 2, 1]
    (loss, grads), *others = (jax.jit(f)(q, k, v)
                              for f in (kept, again, plain))
    assert np.isfinite(float(loss)) and all(
        np.any(np.asarray(g, np.float32)) for g in grads)
    for other_loss, other in others:
        assert float(other_loss) == float(loss)
        for mine, theirs in zip(grads, other):
            assert mine.dtype == theirs.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(mine, np.float32), np.asarray(theirs, np.float32))


def test_a_head_the_kernels_do_not_take_names_nothing_to_keep():
    """Heads of 96 take the loops on every platform: nothing bears
    ``SAVED``, and the block traces to the same program under the models'
    policy and under none."""
    assert not _kernels_take(96)
    q = jax.ShapeDtypeStruct((1, 256, 4, 96), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 256, 2, 96), jnp.float32)
    w = jnp.ones(q.shape, jnp.float32)
    texts = [re.sub(r"policy=[^\n]*", "policy=", str(jax.make_jaxpr(
        loss_and_grads(cls, w, window=100))(q, k, k)))
        for cls in (recomputed(Attention), fnn.remat(Attention))]
    assert texts[0] == texts[1]
    assert "remat2" in texts[0] and SAVED not in texts[0]
    assert "pallas_call" not in texts[0]
    # ... where a head the kernels take has them offered, named
    wide = jax.ShapeDtypeStruct((1, 256, 2, HEAD), jnp.float32)
    assert SAVED in str(jax.make_jaxpr(loss_and_grads(
        recomputed(Attention), jnp.ones(wide.shape), window=100))(
            wide, wide, wide))


@pytest.mark.parametrize("config", [
    "gpt2-medium", "smallthinker-21b-a3b", "laguna-s-2-1", "falcon-h1-34b"])
def test_the_models_recomputation_is_inert_where_the_loops_run(
        config, monkeypatch):
    """The three model families at their rehearsal sizes on the CPU (heads
    of 16 and 32: the loops, nothing of the attention's named), on a device
    with no room for a product's result (``models.lm``'s rule keeps none:
    what it keeps where there is room is ``tests/test_lm_recompute.py``'s):
    through ``recomputed`` the
    loss and every leaf of the gradient are, bit for bit, those of a
    recomputation with no policy (``fnn.remat`` alone, every model's
    spelling before), and those of ``remat=False`` as closely as any
    recomputation's are (XLA rounds a block it makes again at other places
    than the one it ran forward: bits differ there with no policy too)."""
    from benchmark import configs
    from torchmpi_tpu.models import decoder, hybrid, lm, transformer

    monkeypatch.setattr(lm, "device_bytes", lambda: 1)
    cfg = configs.load(config, rehearse=True)
    assert cfg["remat"] is True
    ids = jax.random.randint(
        jax.random.PRNGKey(3),
        (cfg["per_chip_batch"], cfg["sequence_length"]), 0, 97)

    def loss_and_gradient(remat):
        built = configs.build(config, {**cfg, "remat": remat})
        params, state = built.state_at(jax.random.PRNGKey(0))
        if state is None:
            return jax.jit(jax.value_and_grad(built.loss_fn))(
                params, (ids, ids))
        (loss, _), grads = jax.jit(jax.value_and_grad(
            built.loss_fn, has_aux=True))(params, state, (ids, ids))
        return loss, grads

    loss, grads = loss_and_gradient(True)
    want_loss, want = loss_and_gradient(False)
    for module in (transformer, decoder, hybrid):
        monkeypatch.setattr(
            module, "recomputed", lambda block_cls, keep=(): fnn.remat(
                block_cls))
    plain_loss, plain = loss_and_gradient(True)
    assert float(loss) == float(plain_loss) and np.isfinite(float(loss))
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    assert treedef == jax.tree_util.tree_structure(plain)
    assert treedef == jax.tree_util.tree_structure(want) and len(leaves) > 10
    for mine, theirs, unrecomputed in zip(
            leaves, jax.tree_util.tree_leaves(plain),
            jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
        scale = max(float(jnp.max(jnp.abs(unrecomputed))), 1e-3)
        # bfloat16 products: at most 1.8 % of a leaf's largest read here
        assert float(jnp.max(jnp.abs(mine - unrecomputed))) <= 5e-2 * scale
    assert abs(float(loss) - float(want_loss)) <= 1e-4 * abs(float(want_loss))


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
