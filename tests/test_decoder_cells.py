"""What the decoder configurations' cells share and no one of them owns: the
reader of their inner scopes, the count of the pairs a window lets a query
see, and the lowering's choice of the attention kernels by the width of the
heads. A configuration's own cases are in ``tests/test_cell_<config>.py``
(its cell rehearsed) and ``tests/test_chip_<config>.py`` (its step for the
described chip); what those have in common is ``tests/decoder_cases.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_cases import compile_uncached, one_chip  # noqa: F401 - fixture


def test_inner_scope_reader_sees_through_wrappers():
    from benchmark import inner_scopes

    pre = "jit(tm_step)/shard_map/tm.fwd_bwd/"
    for op, want in [
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_0/tm.attn.full/while/body/"
         "dot_general", "tm.attn.full"),
        (pre + "transpose(jvp(MoEDecoder))/tm.fwd_bwd/jvp(MoEDecoder)/"
         "checkpoint/rematted_computation/MoEDecoderBlock_1/tm.attn.window/"
         "while/body/exp", "tm.attn.window"),
        (pre + "transpose(jvp(MoEDecoder/MoEDecoderBlock_2/tm.moe.experts))"
         "/ragged_dot", "tm.moe.experts"),
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_3/tm.moe.route/sort",
         "tm.moe.route"),
        (pre + "jvp(MoEDecoder)/tm.moe.combine/reduce_sum",
         "tm.moe.combine"),
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_0/tm.attn.index/index_q/"
         "dot_general", "tm.attn.index"),
        (pre + "jvp(MoEDecoder)/checkpoint/MoEDecoderBlock_2/cond/"
         "branch_0_fun/tm.attn.select/tm_attn_select_kth/pallas_call",
         "tm.attn.select"),
        (pre + "transpose(jvp(MoEDecoder))/MoEDecoderBlock_1/cond/"
         "branch_0_fun/tm.attn.sparse/vmap(splash_mqa_dkv_no_residuals)/"
         "pallas_call", "tm.attn.sparse"),
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_1/tm.attn.gate/head_gate/"
         "dot_general", "tm.attn.gate"),
        (pre + "transpose(jvp(MoEDecoder))/MoEDecoderBlock_2/tm.moe.shared/"
         "shared_up/dot_general", "tm.moe.shared"),
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_0/tm.moe.dense/mlp_up/"
         "dot_general", "tm.moe.dense"),
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_3/q/dot_general", None),
        ("jit(tm_step)/shard_map/tm.optimizer/mul", None), ("", None),
    ]:
        assert inner_scopes.inner_scope_of(op) == want, op


@pytest.mark.parametrize("seq,window", [
    (16, None), (16, 5), (16, 16), (16, 40), (9, 1), (64, 24)])
def test_visible_pairs_counts_the_band_exactly(seq, window):
    from benchmark import decoder_flops

    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    assert decoder_flops.visible_pairs(seq, window) == int(seen.sum())


@pytest.mark.parametrize("head_dim,fused", [(128, True), (256, True),
                                            (64, True), (32, False)])
def test_a_tpu_lowering_takes_the_kernels_where_the_heads_allow(
        one_chip, head_dim, fused):
    """The choice is the lowering's: from this CPU process, a program
    lowered for the described chip holds the kernels, forward and
    backward, for heads of 64 or of a multiple of 128, and the loops
    otherwise."""
    from torchmpi_tpu.parallel import blocked_self_attention

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(blocked_self_attention(
            *a, window=300, block=256).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    q = jax.ShapeDtypeStruct((1, 1000, 4, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 1000, 2, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    text = compile_uncached(jax.jit(grads).lower(q, k, k)).as_text()
    assert (text.count("tpu_custom_call") == 2) == fused  # forward, backward
    assert ("while(" in text) != fused
