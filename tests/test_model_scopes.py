"""Forward and backward name their parts: the scopes a language model opens
for its embedding, norms, projections, feed-forward, router, head and loss
(``telemetry.names``, ``models/decoder.py``, ``models/transformer.py``) and
the benchmark's reader that divides ``tm.fwd_bwd`` among every inner scope
(``benchmark/model_scopes.py``). The scopes are metadata of the jitted step:
they reach every phase of it, move no operation of an older scope, and
change no byte of the compiled program."""

import contextlib
import functools
import re
import sys
from pathlib import Path
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import torchmpi_tpu as mpi
from torchmpi_tpu.engine import AllReduceSGDEngine
from torchmpi_tpu.models import (
    GatedDeltaDecoder,
    HybridDecoder,
    LongContextTransformer,
    MoEDecoder,
    RetentionDecoder,
    Rotary,
    init_lm_params,
    init_moe_state,
    make_lm_loss_fn,
    make_moe_lm_loss_fn,
)
from torchmpi_tpu.parallel import (
    biased_sigmoid_route_weights,
    sigmoid_route_weights,
)
from torchmpi_tpu.telemetry import names

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

SEQ, VOCAB = 24, 61
OLD = names.ATTN_MOE_SCOPE_NAMES      # what the benchmark's metrics read
SSM = names.SSM_SCOPE_NAMES           # the state-space mixer's (PR 39)
RET = names.RETENTION_SCOPE_NAMES     # power retention's (PR 41)
GDN = names.GDN_SCOPE_NAMES           # the gated delta rule's (PR 45)
SCONV = names.SCONV_SCOPE_NAMES       # the gated short convolution's (PR 48)
NEW = names.LM_SCOPE_NAMES + SSM + RET + GDN + SCONV  # this file's
EVERY_LM = {"tm.lm.embed", "tm.lm.norm", "tm.attn.proj", "tm.lm.head",
            "tm.lm.loss"}
# the scopes opened inside a block are recomputed with it; the embedding,
# the last norm's model-level call, the head and the loss are not
IN_BLOCKS = {"tm.lm.norm", "tm.attn.proj", "tm.lm.mlp", "tm.moe.router",
             *SSM, *RET, *GDN, *SCONV}


# the scopes of a family that hold nothing but products whose results bear a
# name (``models.lm.product``): here, where the device reports no memory and
# the rule keeps every kind, a block's recomputation makes none of them again
NOT_AGAIN = {family: {"tm.attn.proj", "tm.moe.router"} for family in (
    "smallthinker", "selected", "laguna", "gated_delta")}
NOT_AGAIN["short_conv"] = {
    "tm.attn.proj", "tm.moe.router", "tm.lm.sconv_proj"}


def _decoder(**over):
    kw = dict(
        vocab_size=VOCAB, num_layers=4, d_model=32, num_heads=4,
        num_kv_heads=2, head_dim=8, expert_width=16, num_experts=8, top_k=3,
        held=tuple(range(8)), window=12, window_layout=(0, 1, 1, 1),
        rope_layout=(0, 1, 1, 1), attn_block=8, remat=True)
    kw.update(over)
    return MoEDecoder(**kw)


class Family(NamedTuple):
    build: Callable  # () -> the model at this file's sizes
    own: set         # the new scopes it opens beside ``EVERY_LM``


ROUTED = {"tm.moe.router"}
FAMILIES = {
    # GPT-2's: LayerNorm, one qkv product, T x T attention, a GELU
    # feed-forward, each block recomputed
    "gpt2": Family(lambda: LongContextTransformer(
        vocab_size=VOCAB, num_layers=2, num_heads=2, head_dim=8, d_model=16,
        max_len=32, remat=True), {"tm.lm.mlp"}),
    # smallthinker-21b-a3b's: the router read before attention
    "smallthinker": Family(_decoder, ROUTED),
    # laguna-s-2-1's: heads by layer, a gate a head, the router after the
    # second norm, a shared expert, a dense leading layer
    "laguna": Family(lambda: _decoder(
        num_layers=5, num_heads=(2, 3, 3, 3), num_kv_heads=1,
        rope_layout=(1,), rope_theta=1e4,
        rope_full=Rotary(5e5, 4, 128.0, 8192, 32.0, 1.0, 1.4852),
        activation=jax.nn.silu, router_after_norm=True, head_gate=True,
        route_weights=sigmoid_route_weights(2.5), shared_width=16,
        dense_layers=1, dense_width=24), ROUTED),
    # falcon-h1-34b's: a state-space mixer beside attention in every block,
    # a gated feed-forward under GPT-2's scope, no router
    "hybrid": Family(lambda: HybridDecoder(
        vocab_size=VOCAB, num_layers=2, d_model=32, num_heads=2,
        num_kv_heads=1, head_dim=8, ssm_heads=2, ssm_head_dim=8,
        ssm_groups=1, ssm_state=6, mlp_width=24, chunk=8, attn_block=8,
        remat=True), {"tm.lm.mlp", *SSM}),
    # brumby-14b's: power retention where attention stood in every block (no
    # attention's scope at all), a norm on each query and key head, a gated
    # feed-forward under GPT-2's scope, no router
    "retentive": Family(lambda: RetentionDecoder(
        vocab_size=VOCAB, num_layers=2, d_model=32, num_heads=5,
        num_kv_heads=1, head_dim=32, mlp_width=24, chunk=8, remat=True),
        {"tm.lm.mlp", *RET}),
    # qwen3-next-80b-a3b's: three layers of the gated delta rule to one of
    # gated softmax attention, the router after the second norm, a gated
    # shared expert
    "gated_delta": Family(lambda: GatedDeltaDecoder(
        vocab_size=VOCAB, num_layers=4, d_model=32, num_heads=4,
        num_kv_heads=2, head_dim=16, rotary_dim=4, key_heads=2,
        value_heads=4, key_dim=8, value_dim=8, expert_width=16,
        shared_width=16, num_experts=8, top_k=3, held=tuple(range(8)),
        chunk=8, attn_block=8, remat=True), ROUTED | set(GDN)),
    # lfm2-8b-a1b's: a gated short convolution where attention stands in
    # four layers of five, a dense leading layer, a router that chooses by
    # its scores plus a bias, the head the embedding's table
    "short_conv": Family(lambda: _decoder(
        num_layers=5, window_layout=(0,), rope_layout=(1,), rope_theta=1e6,
        activation=jax.nn.silu, router_after_norm=True, qk_norm=True,
        route_weights=biased_sigmoid_route_weights, expert_bias=True,
        dense_layers=1, dense_width=24, conv_layout=(1, 0, 1, 1, 1),
        tied_head=True), ROUTED | set(SCONV)),
    # keye-vl-2-30b-a3b's: every layer selects, with a norm on each query
    # and key head; the indexer's projections stay under tm.attn.index
    "selected": Family(lambda: _decoder(
        window_layout=(0,), rope_layout=(1,), selected_layout=(1,),
        index_top_k=9, index_heads=3, index_dim=8, router_after_norm=True,
        qk_norm=True), ROUTED),
}


@pytest.fixture(autouse=True)
def _one_device():
    mpi.start(devices=jax.devices()[:1])


def _engine(family):
    model = FAMILIES[family].build()
    params = init_lm_params(model, SEQ)
    if isinstance(model, (MoEDecoder, GatedDeltaDecoder)):
        return AllReduceSGDEngine(
            make_moe_lm_loss_fn(model), params, optimizer=optax.sgd(0.1),
            model_state=init_moe_state(model))
    return AllReduceSGDEngine(
        make_lm_loss_fn(model), params, optimizer=optax.sgd(0.1))


def _lowered(engine):
    toks = np.random.default_rng(0).integers(
        0, VOCAB, size=(2, SEQ + 1), dtype=np.int32)
    return engine._step_fn.lower(
        engine.params, engine.opt_state, engine.model_state,
        engine._prepare_batch((toks[:, :-1], toks[:, 1:])))


def _op_names(family):
    lowered = _lowered(_engine(family))
    names_ = set(re.findall(
        r'"(jit\(tm_train_step\)[^"]*)"', lowered.as_text(debug_info=True)))
    if family in ("retentive", "gated_delta"):
        # an operation inside a scan's body is named from the body's own
        # function on in the lowered text; the compiled step has its path
        names_ |= set(re.findall(
            r'op_name="(jit\(tm_train_step\)[^"]*)"',
            lowered.compile().as_text()))
    return names_


# ``_op_names(family)`` of the step as the model traces it, no name dropped:
# made once a family for the tests of this file that read it
as_traced = functools.lru_cache(maxsize=None)(_op_names)


@contextlib.contextmanager
def _without(monkeypatch, dropped):
    """``jax.named_scope`` opens nothing for the names in ``dropped`` (every
    name where it is None): the step as a model without them traces it."""
    real = jax.named_scope
    with monkeypatch.context() as patch:
        patch.setattr(jax, "named_scope", lambda name: (
            contextlib.nullcontext() if dropped is None or name in dropped
            else real(name)))
        yield


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_new_scopes_reach_every_phase_under_fwd_bwd(family):
    from benchmark import model_scopes, scopes

    assert NEW == ("tm.lm.embed", "tm.lm.norm", "tm.attn.proj", "tm.lm.mlp",
                   "tm.moe.router", "tm.lm.head", "tm.lm.loss",
                   "tm.lm.ssm_proj", "tm.lm.ssm_conv", "tm.lm.ssm_scan",
                   "tm.lm.ssm_gate", "tm.lm.ret_gate", "tm.lm.ret_chunk",
                   "tm.lm.ret_state", "tm.lm.gdn_proj", "tm.lm.gdn_conv",
                   "tm.lm.gdn_gate", "tm.lm.gdn_chunk", "tm.lm.gdn_state",
                   "tm.lm.sconv_proj", "tm.lm.sconv")
    seen = {}
    for op in as_traced(family):
        bucket = model_scopes.bucket_of(op)
        if bucket in NEW:
            assert scopes.scope_of(op) == "tm.fwd_bwd", op
            seen.setdefault(bucket, set()).add(model_scopes.phase_of(op))
    assert set(seen) == EVERY_LM | FAMILIES[family].own, seen
    assert {s for s in seen if s in IN_BLOCKS and "recompute" not in seen[s]
            } == NOT_AGAIN.get(family, set())
    for scope, phases in seen.items():
        want = {"forward", "backward"}
        if scope in IN_BLOCKS and scope not in NOT_AGAIN.get(family, ()):
            want.add("recompute")
        if scope == "tm.lm.loss":
            # the head's own rule (models/lm_head.py) makes the gradients
            # while a block's logits exist: the loss and the head's three
            # products are forward's; backward is the head's multiply by
            # the cotangent
            want = {"forward"}
        assert phases == want, (scope, phases)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_older_inner_scopes_read_what_they_read(family, monkeypatch):
    """No new scope encloses or lies inside an older one: every operation
    ``inner_scopes.inner_scope_of`` gives to an older scope has the
    ``op_name`` and the scope it has in a step traced without the new
    scopes, and no other operation joins them."""
    from benchmark import inner_scopes

    def older(ops):
        pairs = {(op, inner_scopes.inner_scope_of(op)) for op in ops}
        return {(op, scope) for op, scope in pairs if scope in OLD}

    with _without(monkeypatch, NEW):
        before = older(_op_names(family))
    after = older(as_traced(family))
    # the retentive family opens none of the older scopes: no attention
    assert after == before and bool(before) == (family != "retentive")
    if family == "gpt2":  # its attention bears the decoders' name now
        assert {scope for _, scope in after} == {"tm.attn.full"}
    if family == "selected":
        assert any(op.endswith("_indexer/index_q/dot_general")
                   and scope == "tm.attn.index" for op, scope in after)
        assert not [op for op, _ in after if "tm.attn.proj" in op]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_scopes_leave_the_compiled_step_unchanged(family, monkeypatch):
    """The compiled step with every ``metadata={...}`` stripped is the
    same bytes with the scopes and with ``jax.named_scope`` opening nothing
    while the step is traced."""
    def strip(text):
        return re.sub(r",? ?metadata=\{[^}]*\}", "", text)

    texts = []
    for dropped in ((), None):  # nothing dropped, then every name
        with _without(monkeypatch, dropped):
            # one call site for both: the text holds source lines
            texts.append(_lowered(_engine(family)).compile().as_text())
    with_scopes, without = texts
    assert "tm.lm.norm" in with_scopes and "tm." not in without
    assert "tm." not in strip(with_scopes)  # all of it was metadata
    assert strip(with_scopes) == strip(without)


def test_bucket_and_phase_of_hand_written_op_names():
    """The reader's two rules on the ``op_name``s of ``inner_scopes.py``'s
    docstring and its self-test, and on the new scopes'."""
    from benchmark import model_scopes

    pre = "jit(tm_train_step)/shard_map/tm.fwd_bwd/"
    back = pre + "transpose(jvp(MoEDecoder))/tm.fwd_bwd/jvp(MoEDecoder)/"
    for op, bucket, phase in [
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_0/tm.attn.full/while/body/"
         "dot_general", "tm.attn.full", "forward"),
        (back + "checkpoint/rematted_computation/MoEDecoderBlock_1/"
         "tm.attn.window/while/body/dot_general", "tm.attn.window",
         "recompute"),
        (back + "checkpoint/MoEDecoderBlock_2/tm.moe.experts/ragged_dot",
         "tm.moe.experts", "backward"),
        (pre + "transpose(jvp(MoEDecoder/MoEDecoderBlock_3/tm.moe.route))"
         "/gather", "tm.moe.route", "backward"),
        (back + "checkpoint/rematted_computation/MoEDecoderBlock_1/"
         "tm.lm.norm/norm_attn/mul", "tm.lm.norm", "recompute"),
        (pre + "transpose(jvp(tm.lm.loss))/jit(log_softmax)/sub",
         "tm.lm.loss", "backward"),
        (pre + "jvp(MoEDecoder)/tm.lm.head/head/dot_general", "tm.lm.head",
         "forward"),
        # the program's older name, and a scope nested in another: the
        # innermost name takes the operation
        ("jit(tm_step)/shard_map/tm.fwd_bwd/jvp(M)/tm.attn.sparse/"
         "tm.attn.select/pallas_call", "tm.attn.select", "forward"),
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_3/add", "unnamed",
         "forward"),
        # two operations XLA merged into one copy: the attention's reshape
        # and the model's beside it, which stands under no scope so that
        # the copy stays the attention's
        (back + "checkpoint/MoEDecoderBlock_1/tm.attn.window/cond/"
         "branch_0_fun/reshape;" + back + "checkpoint/MoEDecoderBlock_1/"
         "reshape", "tm.attn.window", "backward"),
        ("ragged-dot-none:", "tm.moe.experts", "forward"),
        ("jit(tm_train_step)/shard_map/tm.optimizer/mul", None, None),
        ("jit(tm_train_step)/shard_map/tm.grad_sync/reduce/psum", None,
         None),
        ("", None, None),
    ]:
        assert model_scopes.bucket_of(op) == bucket, op
        if bucket is not None:
            assert model_scopes.phase_of(op) == phase, op


@pytest.mark.parametrize("case", ["hand_written", "recorded"])
def test_the_readers_self_test(case):
    from benchmark import model_scopes_selftest

    getattr(model_scopes_selftest, f"test_{case}")()
