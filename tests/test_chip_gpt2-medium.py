"""``gpt2-medium``'s training step at its real size (both of its cells run
it) for the described chip, by ``decoder_cases.py``'s one lowering and one
compilation a file. It is no decoder configuration of that module's cases:
it has a cell of each traffic, no pin, and a ``[1024, 1024]`` array is one
of its weights."""

import re

from decoder_cases import (  # noqa: F401 - fixtures, for CONFIG
    compiled,
    kernel_calls,
    lowered,
    one_chip,
    test_the_cells_step_keeps_the_products_the_rule_counted,
    whole_logits,
)

CONFIG = "gpt2-medium"
# the temporaries of the step with no product kept (2.641 GiB: PR 43's
# program; ``scripts/recompute_probe.py gpt2-medium --keep none --compile``)
NOTHING_KEPT = 2_835_630_592
PRODUCTS = (291, 363)  # 3 a layer fewer: ``qkv``, ``o``, the hidden


def test_gpt2s_step_holds_its_attention_in_the_kernels(compiled):
    """Each of the 24 blocks' attention is the fused kernels, one forward
    and one backward (the block's recomputation keeps the forward kernel's
    output and log-sum-exp, ``ring_attention.SAVED``, and does not run it
    again), with heads of 64 and one tile of 1,024; no ``[b, h, t, t]``
    array of any type is left; the step's temporaries are the 2.64 GiB of
    the step that kept the kernels' 0.39 GiB alone and the 3.0 GiB of the
    three kinds of products' results the rule keeps here, all it names; nor
    is an ``[8, 1024, 50257]`` array of logits left (``whole_logits``)."""
    from torchmpi_tpu.telemetry import names

    cfg, text = compiled.cfg, compiled.text
    assert 406e6 < compiled.parameters < 407e6
    memory = compiled.step.memory_analysis()
    assert memory.argument_size_in_bytes > 12 * compiled.parameters
    # 3.359 GiB measured here (3,606,996,992 B) WITH the 24 layers' kept
    # outputs and log-sum-exps, 24 x (16 + 0.5 MiB) = 0.39 GiB: the bound
    # is the parent's step, which kept nothing and ran the forward kernel
    # twice, 3.392 GiB (3,641,704,448 B: PERF.md, PR 40). The peak stands
    # in backward, where a block's recomputed activations are live: the
    # kept arrays are live there in either program (made again or kept),
    # and keeping them spares the second kernel's own temporaries
    # ... and 2.641 GiB (2,835,630,592 B) since the head's own rule
    # (PR 43): the float32 logits were 1.53 GiB an array, a block of 4,096
    # rows is 0.77
    # ... and 5.649 GiB (6,065,081,344 B) since the blocks keep their
    # products' results (PR 47, on purpose: ``qkv`` 48 MiB, the residual
    # after ``o`` 16 and the feed-forward's hidden 64 a layer, 3.0 GiB over
    # 24, the bytes counted to the MiB; 10.19 GiB with the arguments): the
    # bound is what was measured and a margin
    assert memory.temp_size_in_bytes <= 6_100_000_000, memory
    assert compiled.rule["kept"] == (
        "tm_kept_mlp_gate", "tm_kept_qkv", "tm_kept_residual")
    assert not whole_logits(text, cfg)
    kernels = kernel_calls(text)
    layers = cfg["model"]["n_layer"]
    # one forward kernel a layer: what it hands to backward is kept
    assert kernels == {"splash_mqa_fwd_residuals": layers,
                       "splash_mqa_dkv_no_residuals": layers}, kernels
    assert all(k.startswith(names.ATTN_KERNEL_EVENT) for k in kernels)
    batch, seq = cfg["per_chip_batch"], cfg["sequence_length"]
    heads = cfg["model"]["n_head"]
    assert (batch, heads, seq) == (8, 16, 1024)
    for scores in (f"[{batch},{heads},{seq},{seq}]", f"[{heads},{seq},{seq}]",
                   f"[{batch * heads},{seq},{seq}]"):
        assert scores not in text  # no [b, h, t, t] array, of any type
    # ... nor any array of four or more axes whose last two are both 512
    # or more: a tile's scores stay in VMEM
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"\w+\[([\d,]+)\]", text)}
    assert not [s for s in shapes if len(s) >= 4 and min(s[-2:]) >= 512]


def test_gpt2s_step_makes_each_kept_product_once(compiled):
    """One product where there were two: by the result's shape, the
    products of the compiled step (its ``convolution``s). ``qkv`` forward
    alone (it was made again with the block: 2 a layer); the feed-forward's
    hidden forward and, in backward, its gradient (3 with the block's); of
    the ``[8, 1024, 1024]`` results the ``o`` product's second goes (6 a
    layer: 5): 72 products fewer, 363 -> 291."""
    layers = compiled.cfg["model"]["n_layer"]
    made = lambda shape: len(re.findall(  # noqa: E731
        r"= bf16\[8,1024,%d\]\S* convolution\(" % shape, compiled.text))
    assert (made(3072), made(4096), made(1024)) == (
        layers, 2 * layers, 5 * layers)
