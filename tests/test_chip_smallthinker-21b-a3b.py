"""``smallthinker-21b-a3b.stream.x1``'s training step at its real size for
the described chip: the cases every decoder configuration's step has
(``decoder_cases.py``), run here for this one on one lowering and one
compilation, then what only a routed step can hold or leave out."""

import math
import re

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    compiled,
    lowered,
    one_chip,
    test_the_cells_step_fits_the_chip,
    test_the_cells_step_keeps_the_products_the_rule_counted,
    test_the_cells_step_lowers_for_the_chip_to_the_text_it_had,
    test_the_configuration_is_a_cell_of_the_benchmark,
    two_tiers,
)

CONFIG = "smallthinker-21b-a3b"
# as PR 40 lowered it: a recomputed block keeps the fused kernels' output and
# log-sum-exp and holds no second forward kernel (955,645 c6e7fca3cf766c29
# before; the text is longer because the backward kernel's tile tables,
# constants, now stand in forward's barrier too); since PR 42 its backward
# ends in a sort, a loop of one-hot products and a gather where jax's
# scatter-add of the embedding's rows stood (``models/embedding.py``;
# 1,482,928 2e54c323e433ec0e before); since PR 43 the head and its loss are
# one function with a derivative rule of its own (``models/lm_head.py``), a
# loop over blocks of 8,192 rows where the float32 logits of every row stood
# (1,498,767 6eca475fac54c18e before)
# since PR 47 a block's backward reads the router's logits, ``q``, ``k``,
# ``v`` and the residual after ``o``, kept by ``models.lm``'s rule, and makes
# none of them again (1,501,317 3f936926817e2f53 before)
PIN = (1499696, "81fc109608a4f86c")
OWN = ["attn_full_ms_per_step", "attn_kernel_ms_per_step",
       "attn_kernel_share", "attn_window_ms_per_step", "moe_compact_share",
       "moe_experts_ms_per_step", "moe_grouped_rows_per_step",
       "moe_held_route_share", "moe_max_over_mean_load",
       "moe_route_ms_per_step",
       # the three decoders' since PR 37; its list begins with the first
       "moe_router_ms_per_step"]
PARAMETERS = (370e6, 371e6)  # 4 layers of 68.3 M + 97.2 M of vocabulary
# parameters and AdamW's moments are 12 B each; the temporaries (the routed
# rows; attention's kernels keep their scores in VMEM; their largest own
# array is dQ's 8 parts, 1.75 GiB) measured 3.69 GiB here, 7.84 GiB in all
# (2.98 and 7.12 before the four layers' attention outputs, 112 MiB each,
# and log-sum-exps were kept): well inside the chip's 15.75 GiB
# ... and 8.28 GiB since the blocks keep the router's logits, ``q``, ``k``,
# ``v`` and the residual after ``o`` (PR 47: 0.906 GiB counted, the
# temporaries 3.475 -> 4.140 GiB)
FITS_IN = 9 * 2**30
# the temporaries of the step with no product kept
# (``scripts/recompute_probe.py smallthinker-21b-a3b --keep none --compile``)
NOTHING_KEPT = 3_730_833_920
PRODUCTS = (64, 84)  # 5 a layer fewer: the router, ``q``, ``k``, ``v``, ``o``
# a lowering for the TPU takes the fused attention kernels, though this
# process's backend is the CPU: in each of the 4 layers one forward and one
# backward, under the name the benchmark's reader looks for
KERNELS = {"splash_mqa_fwd_residuals": 4, "splash_mqa_dkv_no_residuals": 4}
ATTENTION_KERNELS = set(KERNELS)
# the grouped products are XLA's kernel, not a product an expert
HOLDS = ("ragged-dot",)
# no block pair's scores are left to cross HBM: not the loops' [batch, KV
# heads, group x block, block]
HOLDS_NO = (r"f32\[2,4,7168,1024\]",)


def test_the_cells_step_holds_no_dispatch_tensor(compiled):
    cfg, text = compiled.cfg, compiled.text
    batch, seq = cfg["per_chip_batch"], cfg["sequence_length"]
    # ... nor any array of four or more axes whose last two are both half a
    # tile (512) or more
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text)}
    assert not [s for s in shapes if len(s) >= 4 and min(s[-2:]) >= 512]
    # no [tokens, experts, capacity] tensor, and no t x t scores: every
    # array's element count stays under the float32 logits', but for the
    # attention backward's dQ, which the one kernel hands back as a part
    # from each tile of keys (8 of 1,024: 1.5 times the logits) for XLA
    # to sum
    tokens_a_step, experts = batch * seq, cfg["model"]["router_outputs"]
    dq_parts = (seq // 1024) * tokens_a_step * (
        cfg["num_attention_heads"] * cfg["head_dim"])
    largest = max(
        {math.prod(int(d) for d in dims.split(","))
         for dims in re.findall(r"(?:f32|bf16|s32|pred)\[([\d,]+)\]", text)}
        - {dq_parts})
    assert largest <= tokens_a_step * cfg["vocab_size"], largest
    # ep.moe_dispatch_combine's default capacity at these sizes
    capacity = 2 * -(-cfg["moe_num_active_primary_experts"] * tokens_a_step
                     // experts)
    assert largest < tokens_a_step * experts * capacity / 10
    # the expert layers' two tiers: a conditional a layer forward and one
    # in backward (the recomputed forward's is dead: the layer's own
    # derivative rule saves its inputs alone), and in each the compact
    # branch holds no array of all the routes' rows, [98304, 2560] or
    # [98304, 768], while the worst case's branch does
    routes = tokens_a_step * cfg["moe_num_active_primary_experts"]
    assert two_tiers(text, routes, (
        cfg["hidden_size"], cfg["moe_ffn_hidden_size"])) == (
            2 * cfg["num_hidden_layers"])
