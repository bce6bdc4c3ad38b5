"""Self-test of the reader that divides forward and backward among the
model's inner scopes.

    python3 benchmark/model_scopes_selftest.py

First ``model_scopes.divide`` on one chip's events written out by hand,
where the buckets must add up to the whole; then the whole reduction on the
trace recorded on the chip that lies beside this file
(``testdata/model_scopes.xplane.pb``, recorded by
``testdata/record_model_scopes.py``: two steps of the engine over a small
sparse decoder whose blocks are recomputed in backward), whose sums are
written down below.
"""

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from benchmark import (  # noqa: E402
    inner_scopes,
    model_scopes,
    scopes,
    xplane,
)

US = 1e-6
# The recorded trace, microseconds over its two steps by bucket: forward,
# recompute, backward (my chip run, PR 37, TPU v5 lite, jax 0.9.0).
RECORDED = {
    "tm.attn.full": [289, 310, 622], "tm.attn.proj": [147, 148, 270],
    "tm.attn.window": [231, 270, 614], "tm.lm.embed": [47, 0, 134],
    # XLA fused the head's forward product into the log-softmax's first
    # reduction, whose scope the fusion bears: it went to the loss whole
    "tm.lm.head": [0, 0, 257], "tm.lm.loss": [238, 0, 12],
    "tm.lm.norm": [49, 74, 180], "tm.moe.combine": [703, 0, 133],
    "tm.moe.experts": [596, 0, 98], "tm.moe.route": [474, 115, 1395],
    "tm.moe.router": [17, 17, 102], "unnamed": [20, 8, 63],
}
RECORDED_WHOLE_US = 7630  # tm.fwd_bwd's 7,110 and the ragged-dot kernels


def test_hand_written():
    pre = "jit(tm_train_step)/shard_map/tm.fwd_bwd/"
    back = pre + "transpose(jvp(MoEDecoder))/tm.fwd_bwd/jvp(MoEDecoder)/"
    table = {
        # a container: it keeps the gaps between the operations nested in it
        "%while.1": pre + "jvp(MoEDecoder)/MoEDecoderBlock_0/tm.attn.full/"
                    "while",
        "%fusion.1": pre + "jvp(MoEDecoder)/MoEDecoderBlock_0/tm.attn.full/"
                     "while/body/dot_general",
        "%fusion.2": pre + "jvp(MoEDecoder)/MoEDecoderBlock_0/tm.attn.full/"
                     "while/body/exp",
        "%fusion.3": back + "checkpoint/rematted_computation/"
                     "MoEDecoderBlock_0/tm.lm.norm/norm_attn/mul",
        "%fusion.4": pre + "transpose(jvp(tm.lm.loss))/jit(log_softmax)/sub",
        # under two inner names: the innermost takes it
        "%fusion.5": pre + "jvp(MoEDecoder)/MoEDecoderBlock_0/tm.attn.sparse/"
                     "tm.attn.select/pallas_call",
        # XLA's grouped product: no op_name but its own
        "%ragged-dot.1": "ragged-dot-none:",
        # outside tm.fwd_bwd, and under no scope at all
        "%fusion.6": "jit(tm_train_step)/shard_map/tm.optimizer/mul",
        "%copy.1": "",
        # forward and backward, under no inner scope
        "%fusion.8": pre + "transpose(jvp(MoEDecoder))/MoEDecoderBlock_0/"
                     "add_any",
    }
    events = [(name, s * US, e * US) for name, s, e in [
        ("%while.1", 0, 100), ("%fusion.1", 10, 30), ("%fusion.2", 40, 60),
        ("%fusion.3", 100, 130), ("%fusion.4", 130, 150),
        ("%fusion.5", 150, 170), ("%ragged-dot.1", 170, 200),
        ("%fusion.6", 200, 210), ("%copy.1", 210, 215),
        ("%fusion.8", 215, 240),
    ]]
    buckets, grouped, unnamed = model_scopes.divide(events, table)
    got = {bucket: tuple(round(phases[p] / US) for p in model_scopes.PHASES)
           for bucket, phases in buckets.items()}
    assert got == {
        "tm.attn.full": (100, 0, 0),   # 40 nested + the container's 60
        "tm.lm.norm": (0, 30, 0),
        "tm.lm.loss": (0, 0, 20),
        "tm.attn.select": (20, 0, 0),
        "tm.moe.experts": (30, 0, 0),  # the rule leaves ragged-dot forward
        "unnamed": (0, 0, 25),
    }, got
    assert round(grouped / US) == 30
    assert {k: round(v / US) for k, v in unnamed.items()} == {
        ("%fusion.8", table["%fusion.8"]): 25}
    # the buckets add up to the whole: everything whose first tm. component
    # is tm.fwd_bwd, as scopes.by_scope reads it, and the grouped products
    fwd_bwd = [piece for name, pieces in scopes.own_intervals(events)
               if scopes.scope_of(table[name]) == "tm.fwd_bwd"
               for piece in pieces]
    whole = sum(sum(phases.values()) for phases in buckets.values())
    assert abs(whole - xplane.length(xplane.union(fwd_bwd)) - grouped) < US
    assert round(whole / US) == 225


def test_recorded():
    path = HERE / "testdata" / "model_scopes.xplane.pb"
    expect = json.loads(
        (HERE / "testdata" / "model_scopes.expect.json").read_text())
    found = model_scopes.by_bucket(str(path))  # asserts parts == whole
    outer = scopes.by_scope(str(path))
    assert found["steps"] == outer["steps"] == expect["steps"], found
    got = {bucket: [round(phases[p] / US) for p in model_scopes.PHASES]
           for bucket, phases in found["bucket_s"].items()}
    assert got == RECORDED, got
    assert round(found["whole_s"] / US) == RECORDED_WHOLE_US
    # the whole is tm.fwd_bwd and XLA's grouped products, which bear no
    # op_name and which scopes.by_scope counts under no scope
    grouped = found["whole_s"] - outer["scope_s"]["tm.fwd_bwd"]
    assert 0 < grouped < found["bucket_s"]["tm.moe.experts"]["forward"]
    assert any("ragged-dot" in event
               for (event, _), _ in outer["unscoped_ops"]), outer
    # every part of the model has a name: what is left is residual adds
    # and what XLA fuses across a boundary
    unnamed = sum(found["bucket_s"]["unnamed"].values())
    assert unnamed < 0.1 * found["whole_s"], found
    # each block is computed again in backward: the scopes opened inside a
    # block have all three phases; the embedding, the head and the loss
    # stand outside the blocks and are never recomputed
    for bucket, (forward, recompute, backward) in got.items():
        if bucket in ("tm.lm.norm", "tm.attn.proj", "tm.moe.router",
                      "tm.attn.full", "tm.attn.window", "tm.moe.route"):
            assert forward and recompute and backward, bucket
        if bucket in ("tm.lm.embed", "tm.lm.head", "tm.lm.loss"):
            assert backward and not recompute, bucket
    # the older reader sums unions scope by scope: on one chip, where no
    # two operations overlap, the two readers agree on every scope both read
    older = inner_scopes.by_inner_scope(str(path))["scope_s"]
    for scope, seconds in older.items():
        assert abs(seconds - sum(found["bucket_s"][scope].values())) < US, (
            scope)


if __name__ == "__main__":
    for test in (test_hand_written, test_recorded):
        test()
        print(f"ok {test.__name__}")
