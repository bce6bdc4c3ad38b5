"""``falcon-h1-34b.stream.x1``'s training step at its real size for the
described chip: the cases every decoder configuration's step has
(``decoder_cases.py``), run here for this one on one lowering and one
compilation, then what only a hybrid step can hold or leave out."""

import math
import re

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    benchmark_spec,
    cell_of,
    compiled,
    kernel_phases,
    lowered,
    one_chip,
    per_layer_of,
    row_scatters,
    test_the_cells_step_fits_the_chip,
    test_the_cells_step_lowers_for_the_chip_to_the_text_it_had,
    test_the_configuration_is_a_cell_of_the_benchmark,
    whole_logits,
)

CONFIG = "falcon-h1-34b"
# as PR 40 lowered it: a recomputed block keeps the fused kernels' output and
# log-sum-exp and holds no second forward kernel (1,113,784 12847685d7cdf152
# before; the text is longer because the backward kernel's tile tables,
# constants, now stand in forward's barrier too); since PR 42 its backward
# ends in a sort, a loop of one-hot products and a gather where jax's
# scatter-add of the embedding's rows stood (``models/embedding.py``;
# 2,436,298 a49bae8c575c46cb before); since PR 43 the head and its loss are
# one function with a derivative rule of its own (``models/lm_head.py``), a
# loop over blocks of 8,192 rows where the float32 logits of every row stood
# (2,452,301 16a4c55366e3d917 before). PR 46 left it letter for letter: the
# mixer's convolution calls ``causal_conv1d_silu``, whose kernels are not
# taken at 1,024 channels (with them the text was 2,410,797 f7462252725b96f7
# and the step 9.6 ms longer on the chip)
PIN = (2454333, "8028a6527d2dc8db")
OWN = ["ssm_conv_ms_per_step", "ssm_gate_ms_per_step", "ssm_heads_held_share",
       "ssm_proj_ms_per_step", "ssm_scan_ms_per_step"]
PARAMETERS = (572_935_216,) * 2  # 4 layers of 59.68 M + 334.2 M of vocabulary
# 12 B a parameter of state (6.40 GiB) and 5.23 GiB of temporaries measured
# here, 11.64 GiB, at 1 x 16,384 (not the fallback of 8,192), 3 GiB under
# the 15.0 GiB ISSUE 39 set. Before the head had a derivative rule of its
# own (``models/lm_head.py``, PR 43) the float32 logits of 16,384 x 32,640
# and their gradient were 1.99 GiB an array and the step held 14.75 GiB,
# under 80 MiB from the size at which XLA fitted it by making the head's
# product twice (36 ms a step on the chip: PERF.md, PR 40). A block's logits
# are 1.0 GiB now and the step stands 3 GiB from there; the limit is what
# was measured and a margin, so that an array of that size coming back shows
# here
FITS_IN = 12.1 * 2**30
# every layer's attention takes the fused kernels with 5 query heads to the
# one KV head: one forward kernel a layer, its two results kept for backward
KERNELS = {"splash_mqa_fwd_residuals": 4, "splash_mqa_dkv_no_residuals": 4}
ATTENTION_KERNELS = set(KERNELS)
HOLDS = ()
HOLDS_NO = (r"\.remat[.\d]* = ",)


def test_the_hybrid_cells_step_holds_its_scan_in_chunks(compiled):
    """The scan holds no array of all the positions squared (its masked
    products are ``[128, 128]`` a chunk) and no state a position, and the
    carried state is one loop over the 128 chunks, forward and backward;
    the embedding's gradient is no scatter of rows into the table
    (``row_scatters``), and no array holds the logits of all 16,384 rows
    (``whole_logits``)."""
    cfg, text = compiled.cfg, compiled.text
    assert not row_scatters(text, cfg)
    assert not whole_logits(text, cfg)
    layers, seq = cfg["num_hidden_layers"], cfg["sequence_length"]
    assert (seq, cfg["mamba_chunk_size"]) == (16384, 128)
    # the state is [heads, P, N] = [4, 128, 256] a CHUNK (128 of them),
    # never a position: no array holds 16,384 states
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text)}
    states = [s for s in shapes if s[-2:] == (128, 256) and len(s) >= 3]
    assert states and max(math.prod(s) for s in states) == 128 * 4 * 128 * 256
    # forward, the recomputed forward and backward carry the state a layer
    assert text.count(" while(") >= 3 * layers


def test_the_hybrid_cells_convolution_stays_xlas(compiled):
    """At 1,024 channels the mixer's convolution is not wide enough for the
    kernels of ``ops/conv_kernel.py`` (``takes``: measured in this cell's
    step on the chip, PR 46), so the step calls none of them and is the
    parent's; ``conv_kernel_share`` is listed for the cell all the same and
    reads 0 there, as ``attn_kernel_share`` does where heads are narrow."""
    from torchmpi_tpu.ops import conv_kernel

    cfg = compiled.cfg
    channels = (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
                + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"])
    assert channels == 1024 < conv_kernel.WIDE
    assert not conv_kernel.takes(
        (cfg["per_chip_batch"], cfg["sequence_length"], channels), "float32",
        cfg["mamba_d_conv"])
    assert not kernel_phases(compiled.text, "tm_conv_silu")


def test_the_hybrid_cell_reads_the_feed_forward_and_full_attention():
    """GPT-2's feed-forward scope and the decoders' full attention and
    kernels, and nothing of an expert layer."""
    fourth = per_layer_of(benchmark_spec(), cell_of(CONFIG))
    assert {"mlp_ms_per_step", "attn_full_ms_per_step", "attn_kernel_share",
            "attn_kernel_ms_per_step", "fwd_bwd_unnamed_share",
            "conv_kernel_share"} <= fourth
    assert not [m for m in fourth if m.startswith("moe_")]
    assert not fourth & {"attn_window_ms_per_step", "mlp_dense_ms_per_step"}
