"""The benchmark configuration's whole training step, compiled at its real
size for a v5e that is described and not attached (the TPU's compiler is
installed here): what the chip's compiler would refuse, it refuses here.
One file, and the topology is described inside a fixture, so that only the
worker that runs this file loads the TPU's library."""

import hashlib
import json
import math
import re
import sys
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import optax
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
CONFIG = "smallthinker-21b-a3b"
GPT2 = "gpt2-medium"
KEYE = "keye-vl-2-30b-a3b"
LAGUNA = "laguna-s-2-1"
FALCON = "falcon-h1-34b"
BRUMBY = "brumby-14b"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps it from describing
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def lowered_step(config, one_chip):
    """(cfg, the parameters' shapes, the cell's training step lowered for
    the described chip)."""
    from benchmark import configs

    cfg = configs.load(config)
    built = configs.build(config, cfg)
    batch, seq = cfg["per_chip_batch"], cfg["sequence_length"]

    def step(params, opt_state, state, tokens):
        if state is None:  # a model that keeps no state: loss_fn(params, batch)
            loss, grads = jax.value_and_grad(built.loss_fn)(params, tokens)
        else:
            (loss, state), grads = jax.value_and_grad(
                built.loss_fn, has_aux=True)(params, state, tokens)
        updates, opt_state = built.optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, state, loss

    params, state = jax.eval_shape(built.state_at, jax.random.PRNGKey(0))
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    return cfg, params, jax.jit(step, donate_argnums=(0, 1, 2)).lower(
        place(params), place(jax.eval_shape(built.optimizer.init, params)),
        place(state), (tokens, tokens))


def compiled_step(config, one_chip):
    """(cfg, the parameters' shapes, that step compiled)."""
    cfg, params, lowered = lowered_step(config, one_chip)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return cfg, params, lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def whole_logits(text, cfg):
    """The arrays of a compiled step's text, of any type, with a row for
    every token of the step and a column for every id: the ``[rows, V]``
    (or ``[batch, t, V]``) logits and their gradient, 1.99 GiB each in
    float32 in a step of ``falcon-h1-34b``. The head's own derivative rule
    (``models/lm_head.py``) leaves none: a block's ``[8192, V]`` at most."""
    batch, seq = cfg["per_chip_batch"], cfg["sequence_length"]
    vocab = cfg.get("vocab_size", cfg["model"].get("vocab_size"))
    return sorted(set(re.findall(
        r"\w+\[(?:%d,%d|%d),%d\]" % (batch, seq, batch * seq, vocab), text)))


def test_gpt2s_step_holds_its_attention_in_the_kernels(one_chip):
    """``gpt2-medium``'s step at its real size (both of its cells run it):
    each of the 24 blocks' attention is the fused kernels, one forward and
    one backward (the block's recomputation keeps the forward kernel's
    output and log-sum-exp, ``ring_attention.SAVED``, and does not run it
    again), with heads of 64 and one tile of 1,024; no ``[b, h, t, t]``
    array of any type is left, and the step's temporaries, the kept
    0.39 GiB among them, are no larger than when nothing was kept; nor is
    an ``[8, 1024, 50257]`` array of logits left (``whole_logits``)."""
    from torchmpi_tpu.telemetry import names

    cfg, params, compiled = compiled_step(GPT2, one_chip)
    count = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert 406e6 < count < 407e6
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 12 * count
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
    text = compiled.as_text()
    assert not whole_logits(text, cfg)
    kernels = Counter(
        re.findall(r"%([A-Za-z_]+?)[.\d]* = [^\n]*tpu_custom_call", text))
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


def test_the_selecting_cells_step_fits_the_chip_in_its_kernels(one_chip):
    """``keye-vl-2-30b-a3b.stream.x1``'s step at the published widths: it
    fits, every piece of the selected attention is a kernel of the repo's
    own (none of jax's splash kernels is left), the index scores, the
    selection and both forward kernels run once a step (the layer's
    recomputation keeps what they made, the sixteen panels of float32
    scores among it), the selection is nowhere an array (the kernels make
    it in VMEM from a tile of scores), and no ``t x t`` array is held: a
    panel of 4,096 queries at a time."""
    from torchmpi_tpu.parallel import selected_attention as sa
    from torchmpi_tpu.telemetry import names

    cfg, params, compiled = compiled_step(KEYE, one_chip)
    count = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert 465e6 < count < 466e6  # 4 layers of 96.9 M + 77.8 M of vocabulary
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # 12 B a parameter of state (5.20 GiB) and 7.13 GiB of temporaries
    # measured here, 12.33 GiB: 3.4 GiB inside the chip's 15.75. Of the
    # temporaries 2.12 GiB are the four layers' kept panels of index
    # scores (5.01 GiB without them, with a second run of their kernel)
    assert memory.argument_size_in_bytes > 12 * count
    assert held < 12.6 * 2**30, memory
    text = compiled.as_text()
    kernels = Counter(
        re.findall(r"%([A-Za-z_]+?)[.\d]* = [^\n]*tpu_custom_call", text))
    layers, seq = cfg["num_hidden_layers"], cfg["sequence_length"]
    panel = sa._panel_of(seq)
    panels = seq // panel
    assert panels == 4
    once = layers * panels
    assert kernels == {
        "tm_attn_index_scores": once,
        "tm_attn_select_kth": once,
        "tm_attn_sparse_fwd": once,
        "tm_attn_sparse_mean_probabilities": once,
        "tm_attn_sparse_bwd": once,
        "tm_attn_index_grad_queries": once,
        "tm_attn_index_grad_keys": once}, kernels
    assert all(k.startswith(sa.SPARSE_KERNEL_EVENTS) == (
        "index" not in k and "select" not in k) for k in kernels)
    # what the benchmark's attn_kernel_ms_per_step reads here: the forward
    # and the backward attention kernel, no other
    assert {k for k in kernels if k.startswith(names.ATTN_KERNEL_EVENT)} == {
        "tm_attn_sparse_fwd", "tm_attn_sparse_bwd"}
    # the selection is no array: no int8 mask of a panel anywhere, and no
    # int32 table or boolean mask of a panel's [4096, keys] is an
    # instruction's result outside a fusion (inside one, a comparison of
    # the scores is counted where it is made); float32 panels are left:
    # the scores, and in backward the indexer's gradient of them
    assert not re.findall(r"(?:s8|u8)\[%d,\d+\]" % panel, text)
    whole = re.compile(r"= \(?(s32|pred|f32)\[%d,\d{4,}\]" % panel)
    held = Counter(m.group(1) for m in map(whole.search, outside_fusions(
        text)) if m)
    assert set(held) == {"f32"}, held
    assert "ragged-dot" in text
    assert f"[{seq},{seq}]" not in text  # no t x t array, of any type


def test_the_shared_cells_step_fits_the_chip_with_its_heads_by_layer(
        one_chip):
    """``laguna-s-2-1.stream.x1``'s step at the published widths: it fits
    (1 x 16,384, not the fallback of 8,192), every layer's attention takes
    the fused kernels with 6 or 9 query heads to the one KV head and a
    window of 512 under tiles of 1,024, and the four expert layers have
    their two tiers with no array of all 163,840 routes' rows in a compact
    branch."""
    from torchmpi_tpu.parallel import ep
    from torchmpi_tpu.telemetry import names

    cfg, params, compiled = compiled_step(LAGUNA, one_chip)
    count = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert 468.8e6 < count < 469.0e6  # 19.7 + 3 x 93.6 + 91.3 + 77.1 M
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # 12 B a parameter of state and 5.04 GiB of temporaries measured here
    # (10.29 GiB; 10.16 before the five layers' attention outputs and
    # log-sum-exps were kept): inside the chip's 15.75 GiB
    assert memory.argument_size_in_bytes > 12 * count
    assert held < 11 * 2**30, memory
    text = compiled.as_text()
    kernels = Counter(
        re.findall(r"%([A-Za-z_]+?)[.\d]* = [^\n]*tpu_custom_call", text))
    layers = cfg["num_hidden_layers"]
    # one forward and one backward a layer: the block's recomputation
    # keeps the forward kernel's output and log-sum-exp (``SAVED``) and
    # does not run it a second time
    assert kernels == {"splash_mqa_fwd_residuals": layers,
                       "splash_mqa_dkv_no_residuals": layers}, kernels
    assert all(k.startswith(names.ATTN_KERNEL_EVENT) for k in kernels)
    seq = cfg["sequence_length"]
    assert f"[{seq},{seq}]" not in text  # no t x t array, of any type
    routes = seq * cfg["num_experts_per_tok"]
    assert ep.compact_rows(routes, 8, 256) == 10240
    wide = re.compile(r"\[%d,(?:%d|%d)\]" % (
        routes, cfg["hidden_size"], cfg["moe_intermediate_size"]))
    branches = conditional_branches(text)
    assert len(branches) == 2 * (layers - len(cfg["mlp_only_layers"]))
    for pair in branches:
        assert sorted(bool(wide.search(body)) for body in pair) == [
            False, True]


def row_scatters(text, cfg):
    """The ``scatter`` instructions of a compiled step's text whose operand
    is the ``f32[V, D]`` embedding table: jax's transpose of the token
    gather (64 ms a step of ``falcon-h1-34b`` on the chip, 42 of
    ``brumby-14b``, at these tables' width: PERF.md, PR 42).
    The lookup's own derivative rule (``models/embedding.py``) leaves none:
    what it scatters is ``V`` integers."""
    table = f"f32[{cfg['vocab_size']},{cfg['hidden_size']}]"
    return [line for line in text.splitlines()
            if re.search(r" scatter\(", line) and table in line]


def test_the_hybrid_cells_step_fits_the_chip_with_its_scan_in_chunks(
        one_chip):
    """``falcon-h1-34b.stream.x1``'s step at the published widths: it fits
    at 1 x 16,384 (not the fallback of 8,192), 3 GiB under the 15.0 GiB
    ISSUE 39 set; every layer's attention takes the fused kernels with 5
    query heads to the one KV head, the scan holds no array of all the
    positions squared (its masked products are ``[128, 128]`` a chunk) and
    no state a position, and the carried state is one loop over the 128 chunks,
    forward and backward; the embedding's gradient is no scatter of rows
    into the table (``row_scatters``), and no array holds the logits of
    all 16,384 rows (``whole_logits``)."""
    from torchmpi_tpu.telemetry import names

    cfg, params, compiled = compiled_step(FALCON, one_chip)
    count = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert count == 572_935_216  # 4 layers of 59.68 M + 334.2 M of vocabulary
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # 12 B a parameter of state (6.40 GiB) and 5.23 GiB of temporaries
    # measured here, 11.64 GiB. Before the head had a derivative rule of
    # its own (``models/lm_head.py``, PR 43) the float32 logits of 16,384
    # x 32,640 and their gradient were 1.99 GiB an array and the step held
    # 14.75 GiB, under 80 MiB from the size at which XLA fitted it by
    # making the head's product twice (36 ms a step on the chip: PERF.md,
    # PR 40). A block's logits are 1.0 GiB now and the step stands 3 GiB
    # from there; the limit is what was measured and a margin, so that an
    # array of that size coming back shows here
    assert memory.argument_size_in_bytes > 12 * count
    assert held < 12.1 * 2**30, memory
    text = compiled.as_text()
    assert not re.findall(r"\.remat[.\d]* = ", text)
    assert not row_scatters(text, cfg)
    assert not whole_logits(text, cfg)
    kernels = Counter(
        re.findall(r"%([A-Za-z_]+?)[.\d]* = [^\n]*tpu_custom_call", text))
    layers, seq = cfg["num_hidden_layers"], cfg["sequence_length"]
    # one forward kernel a layer: its two results are kept for backward
    assert kernels == {"splash_mqa_fwd_residuals": layers,
                       "splash_mqa_dkv_no_residuals": layers}, kernels
    assert all(k.startswith(names.ATTN_KERNEL_EVENT) for k in kernels)
    assert (seq, cfg["mamba_chunk_size"]) == (16384, 128)
    assert f"[{seq},{seq}]" not in text  # no t x t array, of any type
    # the state is [heads, P, N] = [4, 128, 256] a CHUNK (128 of them),
    # never a position: no array holds 16,384 states
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text)}
    states = [s for s in shapes if s[-2:] == (128, 256) and len(s) >= 3]
    assert states and max(math.prod(s) for s in states) == 128 * 4 * 128 * 256
    # forward, the recomputed forward and backward carry the state a layer
    assert text.count(" while(") >= 3 * layers


def test_the_retentive_cells_step_fits_the_chip_with_its_state_by_chunks(
        one_chip):
    """``brumby-14b.stream.x1``'s step at the published widths: it fits at
    1 x 32,768 (not the fallback of 16,384) under the 15.0 GiB ISSUE 41
    set, without an instruction of XLA's own rematerialization; no array of
    all the positions squared (the masked products are ``[chunk, chunk]``)
    and none of all the positions times the features of the symmetric
    square (9,216 in the program, 8,256 in the mathematics, 16,384 of the
    whole outer product), kept or transient, forward or backward: the
    widest with that axis is one chunk's five query heads, then the chunks'
    states; no attention kernel at all; one loop over the chunks a layer,
    forward, recomputed and backward; the embedding's gradient is no
    scatter of rows into the table (``row_scatters``), and no array holds
    the logits of all 32,768 rows (``whole_logits``)."""
    from torchmpi_tpu.parallel.retention import features

    cfg, params, compiled = compiled_step(BRUMBY, one_chip)
    count = sum(a.size for a in jax.tree_util.tree_leaves(params))
    # 4 layers of 41.30 M + 194.5 M of vocabulary (ISSUE 41: 359.7 M)
    assert count == 4 * 41_303_297 + 2 * 18992 * 5120 + 5120
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # 12 B a parameter of state (4.02 GiB) and 5.95 GiB of temporaries
    # measured here, 9.97 GiB (12.50 while the float32 logits of 32,768 x
    # 18,992 and their gradient were arrays, 2.32 GiB each: the head walks
    # them by blocks of 8,192 rows since PR 43); the limit is what was
    # measured and a margin
    assert memory.argument_size_in_bytes > 12 * count
    assert held < 10.5 * 2**30, memory
    text = compiled.as_text()
    assert ".remat" not in text
    assert not row_scatters(text, cfg)
    assert not whole_logits(text, cfg)
    assert "tpu_custom_call" not in text
    layers, seq = cfg["num_hidden_layers"], cfg["sequence_length"]
    chunk, heads = cfg["model"]["retention_chunk"], cfg["num_attention_heads"]
    assert (seq, chunk, heads, features(128)) == (32768, 256, 5, 9216)
    assert f"[{seq},{seq}]" not in text  # no t x t array, of any type
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


def test_the_cells_step_fits_the_chip_and_holds_no_dispatch_tensor(one_chip):
    from benchmark import configs
    from torchmpi_tpu.telemetry import names

    cfg = configs.load(CONFIG)
    built = configs.build(CONFIG, cfg)
    batch, seq = cfg["per_chip_batch"], cfg["sequence_length"]

    def step(params, opt_state, state, tokens):
        (loss, state), grads = jax.value_and_grad(
            built.loss_fn, has_aux=True)(params, state, tokens)
        updates, opt_state = built.optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, state, loss

    params, state = jax.eval_shape(built.state_at, jax.random.PRNGKey(0))
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            place(params), place(jax.eval_shape(built.optimizer.init, params)),
            place(state), (tokens, tokens)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    count = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert 370e6 < count < 371e6  # 4 layers of 68.3 M + 97.2 M of vocabulary
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # parameters and AdamW's moments are 12 B each; the temporaries (the
    # float32 logits, the routed rows; attention's kernels keep their
    # scores in VMEM; their largest own array is dQ's 8 parts, 1.75 GiB)
    # measured 3.69 GiB here, 7.84 GiB in all (2.98 and 7.12 before the
    # four layers' attention outputs, 112 MiB each, and log-sum-exps were
    # kept): well inside the chip's 15.75 GiB
    assert memory.argument_size_in_bytes > 12 * count
    assert held < 9 * 2**30, memory
    text = compiled.as_text()
    # the grouped products are XLA's kernel, not a product an expert
    assert "ragged-dot" in text
    # a lowering for the TPU takes the fused attention kernels, though this
    # process's backend is the CPU: in each of the 4 layers one forward
    # and one backward (the block's recomputation keeps what the forward
    # kernel made, ``ring_attention.SAVED``, and does not run it again),
    # under the name the benchmark's reader looks for
    kernels = re.findall(r"%(\w+?)[.\d]* = [^\n]*tpu_custom_call", text)
    assert Counter(re.sub(r"[.\d]+$", "", k) for k in kernels) == {
        "splash_mqa_fwd_residuals": cfg["num_hidden_layers"],
        "splash_mqa_dkv_no_residuals": cfg["num_hidden_layers"]}, kernels
    assert all(k.startswith(names.ATTN_KERNEL_EVENT) for k in kernels)
    # ... and no block pair's scores are left to cross HBM: neither the
    # loops' [batch, KV heads, group x block, block] nor any array of four
    # or more axes whose last two are both half a tile (512) or more
    assert "f32[2,4,7168,1024]" not in text
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
    wide = re.compile(r"\[%d,(?:%d|%d)\]" % (
        routes, cfg["hidden_size"], cfg["moe_ffn_hidden_size"]))
    branches = conditional_branches(text)
    assert len(branches) == 2 * cfg["num_hidden_layers"]
    for pair in branches:
        assert sorted(bool(wide.search(body)) for body in pair) == [
            False, True]


def outside_fusions(text):
    """The instructions of a compiled module's text that are no part of a
    fused computation: each one's result is an array in memory."""
    inside = False
    for line in text.splitlines():
        if re.match(r"%?fused_computation[\w.\-]* \(.*\{$", line):
            inside = True
        elif line.rstrip() == "}":
            inside = False
        elif not inside:
            yield line


def conditional_branches(text):
    """For each ``conditional`` of a compiled module's text, the text of
    each of its branches with every computation the branch calls."""
    bodies, name = {}, None
    for line in text.splitlines():
        header = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if header:
            name = header.group(1)
            bodies[name] = []
        elif line.rstrip() == "}":
            name = None
        elif name:
            bodies[name].append(line)

    def reached(name, seen):
        if name in bodies and name not in seen:
            seen.add(name)
            for line in bodies[name]:
                for called in re.findall(
                        r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                        line):
                    reached(called, seen)
        return seen

    found = []
    for lines in bodies.values():
        for line in lines:
            named = re.search(
                r" conditional\(.*branch_computations=\{([^}]*)\}", line)
            if named:
                found.append(["\n".join(
                    "\n".join(bodies[c]) for c in reached(
                        b.strip().lstrip("%"), set()))
                    for b in named.group(1).split(",")])
    return found


# The five decoder cells' whole steps at their real sizes as LOWERED for the
# described chip (not compiled): characters and the first 16 of the sha256
# of the text without the kernels' serialized bodies (they carry the
# checkout's path). ``keye-vl-2-30b-a3b`` as the parent of PR 38 lowered
# it, and as PR 40 left it: its layers all select, their kernels kept their
# results under the policy already, and the one helper that now spells
# every model's recomputation (``models.transformer.recomputed``) gives it
# the text it had. The other three as PR 40 lowered them: a recomputed
# block keeps the fused kernels' output and log-sum-exp and holds no
# second forward kernel (955,645 c6e7fca3cf766c29, 1,492,140
# fc51f3fc3cfee276 and 1,113,784 12847685d7cdf152 before; the text is
# longer because the backward kernel's tile tables, constants, now stand
# in forward's barrier too). The retentive one as PR 41 brought it (with
# the normaliser's ``eps`` at 1e-12; 878,312 fe718a62df96b44e at 1e-6).
# PR 42 meant to change three and did: the token lookup has a derivative
# rule of its own (``models/embedding.py``), so ``smallthinker-21b-a3b``,
# ``falcon-h1-34b`` and ``brumby-14b`` end their backward in a sort, a loop
# of one-hot products and a gather where jax's scatter-add of the
# embedding's rows stood (1,482,928 2e54c323e433ec0e, 2,436,298
# a49bae8c575c46cb and 878,324 ff7090aaee6aae19 before); the rule
# (``embedding.takes_sorted_sum``) keeps jax's transpose at the widths of
# ``keye-vl-2-30b-a3b`` and ``laguna-s-2-1``, whose steps are the parent's
# text letter for letter. PR 43 meant to change all five and did: the head
# and its loss are one function with a derivative rule of its own
# (``models/lm_head.py``), a loop over blocks of 8,192 rows that makes the
# three gradients while a block's logits exist, where the float32 logits
# of every row and jax's transpose of them stood (1,498,767
# 6eca475fac54c18e, 1,248,452 47bf5842f8ac3442, 2,943,293 162f73ace4dfee71,
# 2,452,301 16a4c55366e3d917 and 894,277 8eae3c7ec1685bf8 before). A PR
# that means to change those steps changes these; one that does not, must
# not.
TPU_LOWERED = {
    CONFIG: (1501317, "3f936926817e2f53"),
    KEYE: (1250741, "fe66531536376d56"),
    LAGUNA: (2945541, "df2d658f547a19f3"),
    FALCON: (2454333, "8028a6527d2dc8db"),
    BRUMBY: (896533, "0fa665a951ba9d9a"),
}


@pytest.mark.parametrize("config", sorted(TPU_LOWERED))
def test_the_decoder_cells_lower_for_the_chip_to_the_text_they_had(
        one_chip, config):
    text = lowered_step(config, one_chip)[2].as_text()
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
    text = re.sub(r"backend_config = \{[^\n]*", "", text)
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()[:16]) == (
        TPU_LOWERED[config])


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
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(grads).lower(q, k, k).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert (text.count("tpu_custom_call") == 2) == fused  # forward, backward
    assert ("while(" in text) != fused


@pytest.mark.parametrize("config,own", [
    (CONFIG, [
        "attn_full_ms_per_step", "attn_kernel_ms_per_step",
        "attn_kernel_share", "attn_window_ms_per_step",
        "moe_compact_share", "moe_experts_ms_per_step",
        "moe_grouped_rows_per_step",
        "moe_held_route_share", "moe_max_over_mean_load",
        "moe_route_ms_per_step",
        # the three decoders' since PR 37; its list begins with the first
        "moe_router_ms_per_step"]),
    (KEYE, [
        "attn_index_kernel_ms_per_step", "attn_index_loss",
        "attn_index_ms_per_step", "attn_select_ms_per_step",
        "attn_selected_pair_share", "attn_sparse_kernel_roofline",
        "attn_sparse_ms_per_step"]),
    (LAGUNA, [
        "attn_gate_ms_per_step", "attn_heads_held_share",
        "mlp_dense_ms_per_step", "moe_shared_ms_per_step"]),
    (FALCON, [
        "ssm_conv_ms_per_step", "ssm_gate_ms_per_step",
        "ssm_heads_held_share", "ssm_proj_ms_per_step",
        "ssm_scan_ms_per_step"]),
    (BRUMBY, [
        "retention_chunk_ms_per_step", "retention_gate_ms_per_step",
        "retention_heads_held_share", "retention_peak_share",
        "retention_state_ms_per_step"]),
], ids=[CONFIG, KEYE, LAGUNA, FALCON, BRUMBY])
def test_the_configuration_is_a_cell_of_the_benchmark(config, own):
    """The configuration's one cell, and the per-layer metrics that came
    with it: those whose list of cells begins with it."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(c for c in spec["workloads"] if c["config"] == config)
    assert cell == {**cell, "name": config + ".stream.x1",
                    "traffic": "stream", "chips": 1}
    assert len(cell["why"]) <= 200
    new = [m for m in spec["per_layer"] if m["workloads"][0] == cell["name"]]
    assert sorted(m["name"] for m in new) == own
    for m in new:
        assert (ROOT / "benchmark" / "layer_metrics"
                / f"{m['name']}.py").is_file()
    # the two decoders share the expert layer's and the kernels' metrics,
    # not the scopes of each other's attention
    shared = {m["name"] for m in spec["per_layer"]
              if {CONFIG + ".stream.x1", KEYE + ".stream.x1"} <= set(
                  m["workloads"])}
    assert {"moe_route_ms_per_step", "moe_compact_share",
            "attn_kernel_share", "attn_kernel_ms_per_step"} <= shared
    assert not shared & {"attn_full_ms_per_step", "attn_window_ms_per_step",
                         "attn_sparse_ms_per_step"}
    # the third decoder reads what the first reads (full and window
    # attention, the expert layer, the kernels), and nothing of the second
    # alone; but not ``moe_compact_share``, whose reader divides by every
    # layer where this configuration's layer 0 has no experts
    third = {m["name"] for m in spec["per_layer"]
             if LAGUNA + ".stream.x1" in m["workloads"]}
    assert {m["name"] for m in spec["per_layer"]
            if CONFIG + ".stream.x1" in m["workloads"]} - third == {
                "moe_compact_share"}
    assert not third & {"attn_sparse_ms_per_step", "attn_index_ms_per_step",
                        "attn_select_ms_per_step"}
    # the hybrid one reads GPT-2's feed-forward scope and the decoders'
    # full attention and kernels, and nothing of an expert layer
    fourth = {m["name"] for m in spec["per_layer"]
              if FALCON + ".stream.x1" in m["workloads"]}
    assert {"mlp_ms_per_step", "attn_full_ms_per_step", "attn_kernel_share",
            "attn_kernel_ms_per_step", "fwd_bwd_unnamed_share"} <= fourth
    assert not [m for m in fourth if m.startswith("moe_")]
    assert not fourth & {"attn_window_ms_per_step", "mlp_dense_ms_per_step"}
    # the retentive one reads what the hybrid one reads but attention's
    # and the mixer's: it has neither
    fifth = {m["name"] for m in spec["per_layer"]
             if BRUMBY + ".stream.x1" in m["workloads"]}
    assert {m for m in fourth - fifth if not m.startswith("ssm_")} == {
        "attn_full_ms_per_step", "attn_kernel_share",
        "attn_kernel_ms_per_step"}
    assert fifth - fourth == {
        m["name"] for m in spec["per_layer"]
        if m["workloads"] == [BRUMBY + ".stream.x1"]}
    assert {m["layer"] for m in spec["per_layer"]
            if m["name"].startswith("retention_")} == {"retention"}
