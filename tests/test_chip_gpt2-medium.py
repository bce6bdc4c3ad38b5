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
    whole_logits,
)

CONFIG = "gpt2-medium"


def test_gpt2s_step_holds_its_attention_in_the_kernels(compiled):
    """Each of the 24 blocks' attention is the fused kernels, one forward
    and one backward (the block's recomputation keeps the forward kernel's
    output and log-sum-exp, ``ring_attention.SAVED``, and does not run it
    again), with heads of 64 and one tile of 1,024; no ``[b, h, t, t]``
    array of any type is left, and the step's temporaries, the kept
    0.39 GiB among them, are no larger than when nothing was kept; nor is
    an ``[8, 1024, 50257]`` array of logits left (``whole_logits``)."""
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
    assert memory.temp_size_in_bytes <= 3_641_704_448, memory
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
