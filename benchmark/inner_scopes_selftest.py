"""Self-test of the reader of the model's inner scopes.

    python3 benchmark/inner_scopes_selftest.py

First ``inner_scope_of`` on operation names written out by hand, then the
whole reduction on the trace recorded on the chip that lies beside this
file (``testdata/decoder.xplane.pb``, recorded by
``testdata/record_decoder.py``: two steps of the engine over a small
sparse decoder whose blocks are recomputed in backward), whose shape is
known from how it was made.
"""

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from benchmark import inner_scopes, scopes  # noqa: E402

SCOPES = {"tm.attn.full", "tm.attn.window", "tm.moe.route",
          "tm.moe.experts", "tm.moe.combine"}


def test_inner_scope_of():
    pre = "jit(tm_step)/shard_map/tm.fwd_bwd/"
    back = pre + "transpose(jvp(MoEDecoder))/tm.fwd_bwd/jvp(MoEDecoder)/"
    for op, want in [
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_0/tm.attn.full/while/body/"
         "...qhd,...khd->...hqk/dot_general", "tm.attn.full"),
        (back + "checkpoint/rematted_computation/MoEDecoderBlock_1/"
         "tm.attn.window/while/body/exp", "tm.attn.window"),
        (back + "checkpoint/MoEDecoderBlock_2/tm.moe.experts/ragged_dot",
         "tm.moe.experts"),
        # a wrapper that holds a whole path in its brackets
        (pre + "transpose(jvp(MoEDecoder/MoEDecoderBlock_3/tm.moe.route))"
         "/gather", "tm.moe.route"),
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_3/tm.moe.combine/"
         "reduce_sum", "tm.moe.combine"),
        ("ragged-dot-none:", "tm.moe.experts"),
        ("ragged-dot-metadata:", "tm.moe.experts"),
        (pre + "jvp(MoEDecoder)/MoEDecoderBlock_3/q/dot_general", None),
        ("jit(tm_step)/shard_map/tm.optimizer/mul", None),
        ("jit(tm_step)/shard_map/tm.grad_sync/reduce/psum", None),
        ("", None),
    ]:
        got = inner_scopes.inner_scope_of(op)
        assert got == want, (op, got)
        if want is not None and op.startswith("jit"):
            # the engine's reading stays whole
            assert scopes.scope_of(op) == "tm.fwd_bwd", op


def test_recorded():
    path = HERE / "testdata" / "decoder.xplane.pb"
    expect = json.loads((HERE / "testdata" / "decoder.expect.json")
                        .read_text())
    inner = inner_scopes.by_inner_scope(str(path))
    outer = scopes.by_scope(str(path))
    assert inner["steps"] == outer["steps"] == expect["steps"], inner
    assert set(inner["scope_s"]) == SCOPES, inner["scope_s"]
    brief = {"inner": inner, "fwd_bwd": outer["scope_s"]["tm.fwd_bwd"]}
    # every inner scope has time forward AND behind jax's transpose(jvp())
    # wrappers: backward is seen through them, and is the larger part
    for scope in SCOPES:
        total, back = inner["scope_s"][scope], inner["backward_s"][scope]
        assert 0 < back < total, (scope, brief)
    # backward alone holds the recomputation too: over half of each
    # attention scope (one forward against a recomputation and a backward)
    for scope in ("tm.attn.full", "tm.attn.window"):
        assert inner["backward_s"][scope] > 0.5 * inner["scope_s"][scope], (
            scope, brief)
    # the inner scopes are parts of tm.fwd_bwd, which holds the
    # projections, the head and the norms besides
    parts = sum(inner["scope_s"].values())
    assert parts < outer["scope_s"]["tm.fwd_bwd"], brief
    assert parts > 0.3 * outer["scope_s"]["tm.fwd_bwd"], brief
    # the full layer visits 10 block pairs of 16 (4 x 5 / 2), the window
    # layer 7 (window = block: the diagonal and the block before it) and
    # rotates its queries and keys besides: between half and twice
    ratio = inner["scope_s"]["tm.attn.window"] / inner["scope_s"][
        "tm.attn.full"]
    assert 0.5 < ratio < 2.0, (ratio, brief)
    # XLA's grouped-product kernel bears no op_name: read as the expert
    # layer's here, under no scope by scopes.by_scope
    assert any("ragged-dot" in event
               for (event, _), _ in outer["unscoped_ops"]), outer


if __name__ == "__main__":
    for test in (test_inner_scope_of, test_recorded):
        test()
        print(f"ok {test.__name__}")
