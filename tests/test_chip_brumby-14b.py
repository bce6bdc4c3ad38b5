"""``brumby-14b.stream.x1``'s training step at its real size for the
described chip: the cases every decoder configuration's step has
(``decoder_cases.py``), run here for this one on one lowering and one
compilation, then what only a retentive step can hold or leave out."""

import math
import re

from decoder_cases import (  # noqa: F401 - collected here, for CONFIG
    benchmark_spec,
    cell_of,
    compiled,
    lowered,
    one_chip,
    per_layer_of,
    row_scatters,
    test_the_cells_step_fits_the_chip,
    test_the_cells_step_keeps_the_products_the_rule_counted,
    test_the_cells_step_lowers_for_the_chip_to_the_text_it_had,
    test_the_configuration_is_a_cell_of_the_benchmark,
    whole_logits,
)

CONFIG = "brumby-14b"
# as PR 41 brought it (with the normaliser's ``eps`` at 1e-12; 878,312
# fe718a62df96b44e at 1e-6); since PR 42 its backward ends in a sort, a loop
# of one-hot products and a gather where jax's scatter-add of the
# embedding's rows stood (``models/embedding.py``; 878,324 ff7090aaee6aae19
# before); since PR 43 the head and its loss are one function with a
# derivative rule of its own (``models/lm_head.py``), a loop over blocks of
# 8,192 rows where the float32 logits of every row stood (894,277
# 8eae3c7ec1685bf8 before); since PR 47 a block's backward reads the
# feed-forward's gate and up products' results, kept by ``models.lm``'s
# rule, and makes neither again (896,533 0fa665a951ba9d9a before)
PIN = (895853, "8b35cad78cee94c3")
OWN = ["retention_chunk_ms_per_step", "retention_gate_ms_per_step",
       "retention_heads_held_share", "retention_peak_share",
       "retention_state_ms_per_step"]
# 4 layers of 41.30 M + 194.5 M of vocabulary (ISSUE 41: 359.7 M)
PARAMETERS = (4 * 41_303_297 + 2 * 18992 * 5120 + 5120,) * 2
# 12 B a parameter of state (4.02 GiB) and 5.95 GiB of temporaries measured
# here, 9.97 GiB (12.50 while the float32 logits of 32,768 x 18,992 and
# their gradient were arrays, 2.32 GiB each: the head walks them by blocks
# of 8,192 rows since PR 43), at 1 x 32,768 (not the fallback of 16,384)
# under the 15.0 GiB ISSUE 41 set; the limit is what was measured and a
# margin; 11.04 GiB since the blocks keep the feed-forward's gate and up
# products' results (PR 47, on purpose: 2 x 136 MiB a layer, 1.06 GiB over
# 4, the temporaries 5.954 -> 7.016 GiB)
FITS_IN = 11.5 * 2**30
# the temporaries of the step with no product kept (5.954 GiB;
# ``scripts/recompute_probe.py brumby-14b --keep none --compile``)
NOTHING_KEPT = 6_392_994_304
PRODUCTS = (180, 188)  # 2 a layer fewer: the gate and the up
KERNELS = {}  # no attention kernel at all: no ``tpu_custom_call``
ATTENTION_KERNELS = set()
HOLDS = ()
# not an instruction of XLA's own rematerialization
HOLDS_NO = (r"\.remat", "tpu_custom_call")


def test_the_retentive_cells_step_holds_its_state_by_chunks(compiled):
    """No array of all the positions times the features of the symmetric
    square (9,216 in the program, 8,256 in the mathematics, 16,384 of the
    whole outer product), kept or transient, forward or backward: the
    widest with that axis is one chunk's five query heads, then the chunks'
    states; one loop over the chunks a layer, forward, recomputed and
    backward; the embedding's gradient is no scatter of rows into the table
    (``row_scatters``), and no array holds the logits of all 32,768 rows
    (``whole_logits``)."""
    from torchmpi_tpu.parallel.retention import features

    cfg, text = compiled.cfg, compiled.text
    assert not row_scatters(text, cfg)
    assert not whole_logits(text, cfg)
    layers, seq = cfg["num_hidden_layers"], cfg["sequence_length"]
    chunk, heads = cfg["model"]["retention_chunk"], cfg["num_attention_heads"]
    assert (seq, chunk, heads, features(128)) == (32768, 256, 5, 9216)
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text)}
    wide = [s for s in shapes if features(128) in s]
    assert wide and not [s for s in wide if seq in s]
    # a chunk's five query heads, or the chunks' states: 608 MB a layer,
    # while that layer's backward runs
    assert max(math.prod(s) for s in wide) == max(
        heads * chunk, seq // chunk * 129) * features(128)
    assert (seq // chunk, 1, 1, 129, features(128)) in shapes
    # nor the mathematics' 8,256 or the whole outer product's 16,384
    assert not [s for s in shapes if seq in s and (
        128 * 129 // 2 in s or 128 * 128 in s)]
    # ... one over the blocks of the embedding's sorted gradient rows and
    # one over the head's blocks of rows
    assert text.count(" while(") == 3 * layers + 2


def test_the_retentive_cells_step_makes_its_gate_and_up_products_once(
        compiled):
    """One product where there were two: of the ``[32768, 2176]`` results
    a layer's products make (the compiled step's ``convolution``s), the
    gate's and the up's forward and the gradient of their product's input
    in backward, 3 a layer; made again with the block they were 5. Both
    kinds the file names are kept: all it has."""
    layers = compiled.cfg["num_hidden_layers"]
    assert compiled.rule["kept"] == ("tm_kept_mlp_gate", "tm_kept_mlp_up")
    assert len(re.findall(
        r"= bf16\[32768,2176\]\S* convolution\(", compiled.text)) == 3 * layers


def test_the_retentive_cell_reads_what_the_hybrid_one_reads_but_attention():
    """... and the mixer's: it has neither; what it reads beyond is its
    own."""
    spec = benchmark_spec()
    fourth = per_layer_of(spec, cell_of("falcon-h1-34b"))
    fifth = per_layer_of(spec, cell_of(CONFIG))
    # ``conv_kernel_share`` is the mixer's too: its convolution's kernels
    assert {m for m in fourth - fifth if not m.startswith("ssm_")} == {
        "attn_full_ms_per_step", "attn_kernel_share",
        "attn_kernel_ms_per_step", "conv_kernel_share"}
    assert fifth - fourth == {
        m["name"] for m in spec["per_layer"]
        if m["workloads"] == [cell_of(CONFIG)]}
    assert {m["layer"] for m in spec["per_layer"]
            if m["name"].startswith("retention_")} == {"retention"}
