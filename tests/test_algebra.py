"""Composition algebra: derivation parity, enumerator bounds, lowerings.

The algebra's contract has four legs, each tested here:

1. **gen_tree parity** — the hand-written tree generator was DELETED
   and re-derived as an algebra term; the derived plan must carry
   byte-identical steps and the identical ``plan_id`` on every
   (topology x op x wire x backend x payload) cell, so every persisted
   calibration table, plan override, and flight-recorder correlation
   keyed on a tree plan survives the refactor unchanged.
2. **Bounded enumeration** — :func:`synthesize` derives at most
   :data:`MAX_SYNTH_CANDIDATES` plans per request, deterministically,
   with O(log world) step entries: generation is O(candidates), never
   O(world size).
3. **Bitwise equivalence** — every synthesized family's lowering
   reproduces the flat ring reference bitwise per wire format on an
   exact payload (disjoint per-rank block support, values in {0, +-1}:
   single contributor per position, amax in {0, 1} per quantize
   segment — exact under any reduction association or hop
   segmentation).
4. **Integration** — the knob gates candidate enumeration, synthesized
   ring-phase plans earn pipeline twins (the ``_pipeline_eligible``
   fix), selection telemetry ticks, ``--explain`` renders derivations,
   overrides accept synthesized generators, and ``SimFleet._plan``
   re-races on a knob flip and prefers a synthesized plan at fleet
   scale.
"""

import math
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchmpi_tpu as mpi
from torchmpi_tpu import constants, telemetry
from torchmpi_tpu.collectives import eager
from torchmpi_tpu.schedule import (
    MAX_SYNTH_CANDIDATES,
    SYNTH_GENERATORS,
    Topology,
    candidate_plans,
    compiler as sched,
    explain,
    is_synthesized,
    payload_bucket,
)
from torchmpi_tpu.schedule import algebra
from torchmpi_tpu.schedule.generators import wire_bytes
from torchmpi_tpu.schedule.ir import Plan, Step
from torchmpi_tpu.schedule.topology import LINK_DCN, LINK_ICI, LINK_LOCAL


# ---------------------------------------------------------------------------
# 1. gen_tree parity: the deleted generator, kept verbatim as the golden
#    reference the derived plans are compared against
# ---------------------------------------------------------------------------


def _golden_gen_tree(op: str, nelem: int, itemsize: int, topo: Topology,
                     backend: str, wire: str) -> Plan:
    """The hand-written ``gen_tree`` exactly as deleted from
    ``generators.py`` — the parity oracle."""
    nbytes = nelem * itemsize
    enc = wire_bytes(nelem, itemsize, wire)
    if op == "allreduce":
        intra_depth = max(0, math.ceil(math.log2(max(1, topo.intra_size()))))
        inter_depth = max(0, math.ceil(math.log2(max(1, topo.num_groups))))
        steps: List[Step] = []
        for depth, level, note in (
            (intra_depth, LINK_ICI, "binomial intra reduce"),
            (inter_depth, LINK_DCN, "binomial roots reduce"),
        ):
            if not depth:
                continue
            if wire != "full":
                steps.append(Step("quantize", LINK_LOCAL, nbytes, depth,
                                  note))
            steps.append(Step("send", level, enc, depth, note))
            steps.append(Step("recv", level, enc, depth, note))
            if wire != "full":
                steps.append(Step("dequantize", LINK_LOCAL, nbytes, depth,
                                  note))
            steps.append(Step("local_reduce", LINK_LOCAL, nbytes, depth,
                              note))
        steps.append(Step("send", LINK_DCN, nbytes, 1,
                          "one-hop gather broadcast of the total"))
    else:  # broadcast
        fan_depth = max(1, math.ceil(math.log2(max(1, topo.num_groups))))
        steps = [
            Step("send", LINK_DCN, nbytes, fan_depth,
                 "binomial fan-out root -> group roots"),
            Step("send", LINK_ICI, nbytes, 1,
                 "group-root gather within every island"),
        ]
    return Plan(
        op=op, generator="tree", backend=backend, wire=wire, impl=backend,
        topology_fp=topo.fingerprint(), steps=tuple(steps),
    )


_PARITY_TOPOS = (
    Topology(platform="tpu", group_sizes=(1, 3, 4), nodes=1),
    Topology(platform="tpu", group_sizes=(2, 6), nodes=2),
    Topology(platform="tpu", group_sizes=(8,) * 4, cartesian=True,
             nodes=4),
    Topology(platform="cpu", group_sizes=(8,), nodes=1),
    Topology(platform="tpu", group_sizes=(4, 4), cartesian=True, nodes=2,
             staged_inter=True),
)


@pytest.mark.parametrize("wire", ["full", "bf16", "int8"])
@pytest.mark.parametrize("op", ["allreduce", "broadcast"])
def test_derive_tree_matches_deleted_gen_tree(op, wire):
    """The algebra term compiles to the SAME steps and the SAME plan_id
    the deleted hand-written generator produced — calibration tables
    and overrides keyed on tree plans stay valid."""
    for topo in _PARITY_TOPOS:
        for backend in ("ring", "pallas"):
            for nelem in (1 << 10, 1 << 16, 1 << 20):
                golden = _golden_gen_tree(op, nelem, 4, topo, backend,
                                          wire)
                derived = algebra.derive_tree(op, nelem, 4, topo, backend,
                                              wire)
                assert derived.steps == golden.steps, (op, wire, backend)
                assert derived.meta == golden.meta
                assert derived.plan_id == golden.plan_id, (
                    op, wire, backend, topo.fingerprint())


def test_tree_candidates_still_derived():
    """candidate_plans still offers the tree family (now algebra-built)
    on ragged topologies, with the golden identity."""
    topo = Topology(platform="tpu", group_sizes=(1, 3, 4), nodes=1)
    constants.set("use_hierarchical_collectives", True)
    cands = candidate_plans("allreduce", 1 << 20, 4, topo, "ring",
                            wire="int8")
    tree = [c for c in cands if c.plan.generator == "tree"
            and c.plan.pipeline == 1]
    assert tree and tree[0].feasible
    golden = _golden_gen_tree("allreduce", 1 << 20, 4, topo, "ring",
                              "int8")
    assert tree[0].plan.plan_id == golden.plan_id


# ---------------------------------------------------------------------------
# 2. bounded, deterministic enumeration
# ---------------------------------------------------------------------------


def _fleet_topo(world: int, g: int = 8) -> Topology:
    sizes = tuple([g] * (world // g))
    return Topology(platform="cpu", group_sizes=sizes, cartesian=True,
                    nodes=len(sizes), name="sim")


def test_enumerator_bounded_and_deterministic():
    """Candidate count is capped and world-size independent; the step
    lists stay O(log world); replaying the derivation is identical."""
    per_world = {}
    for world in (256, 4096):
        topo = _fleet_topo(world)
        plans = algebra.synthesize("allreduce", 1 << 20, 4, topo, "ring",
                                   "int8")
        assert 0 < len(plans) <= MAX_SYNTH_CANDIDATES
        for p in plans:
            assert is_synthesized(p.generator)
            assert p.generator in SYNTH_GENERATORS
            assert len(p.steps) <= 16 * world.bit_length(), p.plan_id
            assert algebra.term_of(p), "synthesized plan lost its term"
        again = algebra.synthesize("allreduce", 1 << 20, 4, topo, "ring",
                                   "int8")
        assert [p.plan_id for p in plans] == [p.plan_id for p in again]
        per_world[world] = sorted(p.generator for p in plans)
    # the derived FAMILY set is a property of the topology shape, not
    # its size: O(candidates) generation
    assert per_world[256] == per_world[4096]


def test_enumerator_admission():
    """halve needs a power-of-two axis; torus/stripe need a cartesian
    two-level topology; unknown ops derive nothing."""
    non_pow2 = Topology(platform="cpu", group_sizes=(6,), nodes=1)
    assert algebra.synthesize("allreduce", 1 << 10, 4, non_pow2, "ring",
                              "full") == []
    assert algebra.derive_synth("halve~synth", "allreduce", 1 << 10, 4,
                                non_pow2, "ring", "full") is None
    flat8 = Topology(platform="cpu", group_sizes=(8,), nodes=1)
    gens = [p.generator for p in algebra.synthesize(
        "allreduce", 1 << 10, 4, flat8, "ring", "full")]
    assert gens == ["halve~synth"]
    assert algebra.derive_synth("torus~synth", "allreduce", 1 << 10, 4,
                                flat8, "ring", "full") is None
    # ragged two-level with a power-of-two TOTAL: halve is structurally
    # derivable (synthesize admits it), but the policy gate in
    # candidate_plans rejects it under hierarchical routing — the
    # reduction order there delegates to the tree composition
    ragged = Topology(platform="tpu", group_sizes=(1, 3, 4), nodes=1)
    assert [p.generator for p in algebra.synthesize(
        "allreduce", 1 << 10, 4, ragged, "ring", "full"
    )] == ["halve~synth"]
    constants.set("use_plan_synthesis", True)
    constants.set("use_hierarchical_collectives", True)
    cands = candidate_plans("allreduce", 1 << 20, 4, ragged, "ring",
                            wire="int8", route_small=False)
    halve = [c for c in cands if c.plan.generator == "halve~synth"]
    assert halve and not any(c.feasible for c in halve)
    assert algebra.synthesize("broadcast", 1 << 10, 4, flat8, "ring",
                              "full") == []


def test_candidates_gated_by_knob():
    """use_plan_synthesis is the opt-in: off -> no synthesized
    candidates in the race; on -> they are enumerated, priced, and
    feasible on a custom-backend large-payload request."""
    topo = _fleet_topo(256)
    off = candidate_plans("allreduce", 1 << 20, 4, topo, "ring",
                          wire="int8", route_small=False)
    assert not any(is_synthesized(c.plan.generator) for c in off)
    constants.set("use_plan_synthesis", True)
    on = candidate_plans("allreduce", 1 << 20, 4, topo, "ring",
                         wire="int8", route_small=False)
    synth = [c for c in on if is_synthesized(c.plan.generator)]
    assert synth
    assert all(c.feasible and c.cost_us is not None for c in synth)
    # xla backend: enumerated but rejected (the latency path keeps its
    # fused primitive), so --explain can show the reason
    xla = candidate_plans("allreduce", 1 << 20, 4, topo, "xla",
                          wire="full", route_small=False)
    xla_synth = [c for c in xla if is_synthesized(c.plan.generator)]
    assert xla_synth and not any(c.feasible for c in xla_synth)


def test_live_mesh_prices_synth_within_the_models_band(_started):
    """On the 8-rank mesh the race prices the algebra's candidates beside
    the legacy families, and the best of them is selected or within the
    cost model's own error band (1.25x) of the best legacy plan; the
    strict win is a matter of scale (``sim.bench.check_synth_pricing``)."""
    constants.set("use_plan_synthesis", True)
    topo = Topology.from_communicator(mpi.current_communicator())
    priced = [c for c in candidate_plans(
        "allreduce", 1 << 20, 4, topo, "ring", wire="int8",
        route_small=True) if c.feasible and c.cost_us is not None]
    synth = [c.cost_us for c in priced if is_synthesized(c.plan.generator)]
    legacy = [c.cost_us for c in priced
              if not is_synthesized(c.plan.generator)]
    assert synth and legacy
    assert min(synth) <= 1.25 * min(legacy)


def test_synth_ring_phases_earn_pipeline_twins():
    """The ``_pipeline_eligible`` fix: synthesized plans whose phases
    are rings (stripe, torus) spawn depth twins like the legacy ring
    families; recursive halving (log-round exchange, no ring phase)
    must NOT."""
    constants.set("use_plan_synthesis", True)
    topo = Topology(platform="tpu", group_sizes=(8,) * 4, cartesian=True,
                    nodes=4)
    cands = candidate_plans("allreduce", 1 << 20, 4, topo, "ring",
                            wire="int8", route_small=False)
    depths = {}
    for c in cands:
        if is_synthesized(c.plan.generator) and c.feasible:
            depths.setdefault(c.plan.generator, set()).add(
                c.plan.pipeline)
    assert any(d > 1 for d in depths.get("stripe~synth", set()))
    assert any(d > 1 for d in depths.get("torus~synth", set()))
    assert depths.get("halve~synth", set()) == {1}


# ---------------------------------------------------------------------------
# 3. bitwise equivalence: synthesized lowerings vs the flat reference
# ---------------------------------------------------------------------------


@pytest.fixture
def _started():
    mpi.start()
    yield


def _exact_payload(p: int, n: int, blk: int = 256) -> jnp.ndarray:
    """Disjoint block-aligned support: rank r is nonzero only on blocks
    with ``block_idx % p == r``, values +-1 constant per block — every
    position has a single contributor (any reduction association is
    exact) and every quantize segment sees amax in {0, 1} (the int8 /
    bf16 encode round-trips are exact under any hop segmentation)."""
    idx = np.arange(n)
    signs = np.where((idx // blk) % 2 == 0, 1.0, -1.0)
    rows = np.stack([
        np.where((idx // blk) % p == r, signs, 0.0) for r in range(p)
    ]).astype(np.float32)
    return jnp.asarray(rows)


@pytest.mark.parametrize("wire", ["full", "bf16", "int8"])
@pytest.mark.parametrize(
    "family", ["halve~synth", "stripe~synth", "torus~synth"]
)
def test_synth_bitwise_vs_flat(family, wire, _started):
    """Every synthesized family, pinned through the compiler, matches
    the flat ring reference BITWISE per wire format — and both equal
    the exact sum."""
    p = mpi.size()
    if p < 4:
        pytest.skip("needs >= 4 ranks")
    constants.set("use_plan_synthesis", True)
    constants.set("wire_quant_min_elements", 1)
    if family == "halve~synth":
        comm = mpi.current_communicator()
    else:
        mpi.push_communicator(lambda r: str(r % 2), name="alg-2l")
        comm = mpi.current_communicator()
        assert comm.cartesian
    n = 1 << 12
    x = _exact_payload(p, n)
    ep_synth = sched.compile_collective(
        "allreduce", (p, n), jnp.float32, comm, backend="ring",
        generator=family, wire_override=wire,
    )
    assert ep_synth.plan.generator == family
    assert "~synth" in ep_synth.plan.plan_id
    ep_flat = sched.compile_collective(
        "allreduce", (p, n), jnp.float32, comm, backend="ring",
        generator="flat", impl="ring", wire_override=wire,
    )
    out_synth = np.asarray(jax.block_until_ready(ep_synth.execute(x)))
    out_flat = np.asarray(jax.block_until_ready(ep_flat.execute(x)))
    expected = np.tile(np.asarray(x).sum(axis=0), (p, 1))
    assert np.array_equal(out_synth, out_flat), (family, wire)
    assert np.array_equal(out_synth, expected), (family, wire)


def test_synth_fused_flush_bitwise(_started):
    """The fusion leg: a persisted override naming a synthesized
    generator steers the FUSED flush's plan, and the flushed results
    stay bitwise identical to the flat-plan flush."""
    p = mpi.size()
    comm = mpi.current_communicator()
    constants.set("use_plan_synthesis", True)
    constants.set("wire_quant_min_elements", 1)
    constants.set("wire_dtype", "int8")
    constants.set("small_allreduce_size_cpu", 1)
    from torchmpi_tpu.collectives import get_fusion_buffer

    n = 1 << 10
    xs = [_exact_payload(p, n, blk=64) for _ in range(3)]

    def flush_all():
        fb = get_fusion_buffer(comm)
        hs = [fb.submit("allreduce", x) for x in xs]
        fb.flush_all(reason="test")
        return [np.asarray(h.wait()) for h in hs]

    base = flush_all()
    topo = Topology.from_communicator(comm)
    # the fused flat buffer is 3n elements; override its bucket
    bucket = payload_bucket(3 * n * 4)
    key = sched.override_key("allreduce", topo.fingerprint(), bucket,
                             "int8")
    sched.set_plan_override(key, "halve~synth")
    try:
        eager.free_collective_resources(comm)
        pinned = flush_all()
    finally:
        sched.clear_plan_overrides()
    for a, b in zip(base, pinned):
        assert np.array_equal(a, b)


def test_pinned_synth_on_infeasible_topology_raises(_started):
    """A pinned synthesized generator the topology cannot express is a
    loud argument error, not a silent fallback."""
    p = mpi.size()
    comm = mpi.current_communicator()  # flat: no torus axes
    with pytest.raises(eager.CollectiveArgumentError):
        sched.compile_collective(
            "allreduce", (p, 1 << 10), jnp.float32, comm,
            backend="ring", generator="torus~synth",
        )


# ---------------------------------------------------------------------------
# 4. integration: telemetry, explain, overrides, sim pricing
# ---------------------------------------------------------------------------


def test_synth_selection_counters():
    """tm_plan_synth_candidates_total ticks per feasible synthesized
    candidate priced; tm_plan_synth_selected_total ticks when one wins
    — at fleet scale the halving plan does."""
    telemetry.enable()
    try:
        constants.set("use_plan_synthesis", True)
        topo = _fleet_topo(1024)
        plan, _ = sched.select_plan(
            "allreduce", 1 << 20, 4, topo, "ring", "int8",
            route_small=False,
        )
        assert is_synthesized(plan.generator)
        mets = telemetry.snapshot()["metrics"]
        cand = mets.get("tm_plan_synth_candidates_total", {}).get(
            "series", {})
        sel = mets.get("tm_plan_synth_selected_total", {}).get(
            "series", {})
        assert sum(cand.values()) >= 1
        assert sum(sel.values()) >= 1
        assert any("halve" in k for k in cand)
    finally:
        telemetry.disable()


def test_explain_derivation_panel_and_families():
    """--explain renders the algebra derivation for synthesized
    candidates; --families filters the rendering, never the decision."""
    constants.set("use_plan_synthesis", True)
    topo = _fleet_topo(128)
    kw = dict(op="allreduce", nbytes=64 << 20, topo=topo, wire="int8",
              backend="ring", route_small=False)
    full = explain(families="all", **kw)
    assert "derivations (composition algebra -> plan IR):" in full
    assert "~synth" in full
    synth_only = explain(families="synth", **kw)
    assert "candidates (synth families):" in synth_only
    assert "derivations (composition algebra -> plan IR):" in synth_only
    legacy_only = explain(families="legacy", **kw)
    assert "derivations (composition algebra -> plan IR):" \
        not in legacy_only
    # the decision is identical under every filter (the CHOSEN line
    # always renders, even when its family is filtered out)
    chosen = [ln for ln in full.splitlines() if "CHOSEN" in ln][0]
    for text in (synth_only, legacy_only):
        assert [ln for ln in text.splitlines()
                if "CHOSEN" in ln][0] == chosen


def test_override_accepts_synth_generator():
    """tune_plan's persistence surface accepts synthesized generator
    names, and select_plan honors the override."""
    with pytest.raises(ValueError):
        sched.set_plan_override("k", "nonsense~synth")
    constants.set("use_plan_synthesis", True)
    topo = Topology(platform="cpu", group_sizes=(8,), nodes=1)
    nelem = 1 << 20
    key = sched.override_key("allreduce", topo.fingerprint(),
                             payload_bucket(nelem * 4), "int8")
    sched.set_plan_override(key, "halve~synth")
    try:
        plan, _ = sched.select_plan(
            "allreduce", nelem, 4, topo, "ring", "int8",
            route_small=False,
        )
        assert plan.generator == "halve~synth"
        applied = sched.apply_plan_overrides({key: "halve~synth"})
        assert applied == {key: "halve~synth"}
    finally:
        sched.clear_plan_overrides()


def test_simfleet_plan_prefers_synth():
    """SimFleet's pricing path re-races on the knob flip (the memo key
    embeds constants.generation()) and a synthesized plan is strictly
    cheaper at 1k ranks."""
    from torchmpi_tpu.sim.fleet import SimFleet

    fleet = SimFleet(1024, seed=17, group_size=8, steps=2,
                     state_elems=1 << 12)
    id_off, cost_off = fleet._plan(1024)
    assert "~synth" not in id_off
    constants.set("use_plan_synthesis", True)
    id_on, cost_on = fleet._plan(1024)
    assert "~synth" in id_on
    assert cost_on < cost_off
