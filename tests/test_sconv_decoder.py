"""What LFM2-8B-A1B brought (``lfm2-8b-a1b``): the gated short convolution
where attention stands in four layers of five (parallel/ssm.py
``gated_short_conv``, models/decoder.py), the router that chooses by its
scores plus a bias and weighs by the bare scores (parallel/ep.py
``biased_sigmoid_route_weights``), the bias that a step's counts move and
the next step's forward pass reads (``make_moe_lm_loss_fn``), and the head
that is the embedding's table (models/lm_head.py), against plain arithmetic:
loops over positions, and the benchmark's plain float32 reference of the
configuration (``benchmark/reference/lfm2-8b-a1b.py``, loaded by path, which
imports nothing of the program). At the configuration's ``rehearsal`` sizes:
five layers (conv + dense; attention, conv, conv, conv, each with experts),
4 query heads of 16 to 2 KV heads, 8 experts at 3 a token."""

import functools
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import torchmpi_tpu as mpi
from torchmpi_tpu import telemetry
from torchmpi_tpu.engine import AllReduceSGDEngine
from torchmpi_tpu.models import (
    MoEDecoder,
    MoEDecoderBlock,
    init_moe_state,
    make_moe_lm_loss_fn,
)
from torchmpi_tpu.parallel import (
    biased_sigmoid_route_weights,
    gated_short_conv,
    sigmoid_route_weights,
)
from torchmpi_tpu.telemetry import names

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
CONFIG = "lfm2-8b-a1b"
SCONV = ("tm.lm.sconv_proj", "tm.lm.sconv")


@pytest.fixture(scope="module")
def plain():
    """The benchmark's plain reference of the configuration, by path."""
    path = ROOT / "benchmark" / "reference" / f"{CONFIG}.py"
    spec = importlib.util.spec_from_file_location("plain_lfm2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_cfg(held=(0, 1), **over):
    """The configuration at its rehearsal's sizes, in float32 (so that the
    program and the reference choose the same experts but for exact ties),
    holding the experts ``held`` of the router's 8."""
    from benchmark import configs

    cfg = configs.load(CONFIG, rehearse=True)
    cfg = {**cfg, "compute_dtype": "float32", "num_experts": len(held), **over}
    cfg["model"] = {**cfg["model"], "experts_held": list(held)}
    return cfg


def rule_of(cfg):
    return functools.partial(
        biased_sigmoid_route_weights,
        scale=float(cfg["routed_scaling_factor"]),
        eps=cfg["model"]["route_epsilon"])


def block_of(cfg, kind, dense=False, **over):
    """One layer of the configuration's model, by itself."""
    heads = cfg["num_attention_heads"]
    return MoEDecoderBlock(**{**dict(
        num_heads=heads, num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // heads,
        expert_width=cfg["moe_intermediate_size"],
        num_experts=cfg["model"]["router_outputs"],
        top_k=cfg["num_experts_per_tok"],
        held=tuple(cfg["model"]["experts_held"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["norm_eps"],
        attn_block=16, activation=jax.nn.silu, router_after_norm=True,
        qk_norm=True, route_weights=rule_of(cfg), expert_bias=True,
        dense_width=cfg["intermediate_size"] if dense else None,
        conv_taps=cfg["conv_L_cache"] if kind == "conv" else None), **over})


def seeded(shapes, seed=0, std=0.3):
    """Seeded normal weights large enough that the gates, the scores, the
    router and the experts are far from flat, the norms' scales off 1."""
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten([
        std * jax.random.normal(k, s.shape, jnp.float32)
        for s, k in zip(leaves, keys)])


def tokens(n, seq, vocab, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, vocab, size=(n, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


def built_model(cfg, **over):
    """(the configuration's ``Built``, the model inside its loss function)
    at ``cfg``'s sizes; ``over``: fields of the model replaced."""
    from benchmark import configs

    built = configs.build(CONFIG, cfg)
    model = next(c.cell_contents for c in built.loss_fn.__closure__
                 if isinstance(c.cell_contents, MoEDecoder))
    return built, model.clone(**over) if over else model


# -- (f) parallel/ssm.py against a loop over positions ------------------------
def looped(bcx, taps):
    """``C_t * sum_j taps[j] (B x)_{t - (k - 1) + j}``, a position at a
    time, in float64."""
    bcx, taps = np.asarray(bcx, np.float64), np.asarray(taps, np.float64)
    gate_in, gate_out, x = np.split(bcx, 3, axis=-1)
    u, k = gate_in * x, len(taps)
    y = np.zeros_like(u)
    for t in range(u.shape[1]):
        for j in range(k):
            if t - (k - 1) + j >= 0:
                y[:, t] += taps[j] * u[:, t - (k - 1) + j]
    return gate_out * y


@pytest.mark.parametrize("t,c,k", [(37, 20, 3), (5, 128, 3), (1, 8, 3),
                                   (40, 24, 4)])
def test_gated_short_conv_is_the_loop_over_positions(t, c, k):
    """... at lengths that are no whole tile of anything (37, 5, 1), with
    its gradients: the two gates, the taps, no activation, zeros before
    position 0, the last tap on the current position."""
    keys = jax.random.split(jax.random.PRNGKey(t), 3)
    bcx = jax.random.normal(keys[0], (2, t, 3 * c), jnp.float32)
    taps = jax.random.normal(keys[1], (k, c), jnp.float32)
    np.testing.assert_allclose(
        gated_short_conv(bcx, taps), looped(bcx, taps), atol=2e-5)
    # the result comes in the operand's dtype, the sums in float32
    low = gated_short_conv(bcx.astype(jnp.bfloat16), taps)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        low.astype(jnp.float32), looped(bcx.astype(jnp.bfloat16), taps),
        atol=0.05, rtol=0.02)
    # jax's derivative against differences of the loop along a direction
    dy = jax.random.normal(keys[2], (2, t, c), jnp.float32)
    g_bcx, g_taps = jax.grad(
        lambda b, w: jnp.sum(gated_short_conv(b, w) * dy), (0, 1))(bcx, taps)
    eps, rng = 1e-4, np.random.default_rng(0)
    d_bcx, d_taps = rng.normal(size=bcx.shape), rng.normal(size=taps.shape)
    b64, w64 = np.asarray(bcx, np.float64), np.asarray(taps, np.float64)
    dy64 = np.asarray(dy, np.float64)
    slope = (np.sum(looped(b64 + eps * d_bcx, w64 + eps * d_taps) * dy64)
             - np.sum(looped(b64 - eps * d_bcx, w64 - eps * d_taps) * dy64)
             ) / (2 * eps)
    np.testing.assert_allclose(
        np.sum(g_bcx * d_bcx) + np.sum(g_taps * d_taps), slope, rtol=2e-4)


def test_a_position_reads_itself_and_the_two_before():
    bcx = jnp.ones((1, 6, 3))
    taps = jnp.asarray([[100.0], [10.0], [1.0]])
    np.testing.assert_array_equal(
        gated_short_conv(bcx, taps)[0, :, 0], [1, 11, 111, 111, 111, 111])
    # nothing of a later position reaches an earlier one
    later = bcx.at[0, 4:].set(7.0)
    np.testing.assert_array_equal(
        gated_short_conv(later, taps)[0, :4], gated_short_conv(bcx, taps)[0, :4])


# -- (c) the rule that chooses by one number and weighs by another ------------
def test_a_route_the_bias_chose_carries_its_bare_scores_weight():
    logits = jnp.asarray([[2.0, 1.5, 1.0, 0.5, 0.0, -0.5, -1.0, -3.0],
                          [0.0, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7]])
    s = np.asarray(jax.nn.sigmoid(logits), np.float64)
    # expert 7 is the first token's last by its score, and is chosen by
    # its bias; the second token's choice stands
    bias = jnp.zeros((8,)).at[7].set(0.8)
    weight, chosen, (counts, turned) = biased_sigmoid_route_weights(
        bias, eps=1e-6)(logits, 3)
    assert sorted(np.asarray(chosen[0])) == [0, 1, 7]
    assert sorted(np.asarray(chosen[1])) == [5, 6, 7]
    for row in range(2):
        ids = np.asarray(chosen[row])
        np.testing.assert_allclose(
            weight[row], s[row, ids] / (s[row, ids].sum() + 1e-6), rtol=1e-6)
    # the turned route weighs what its BARE score says: little
    at = list(np.asarray(chosen[0])).index(7)
    assert float(weight[0, at]) < 0.05
    assert float(turned) == 1.0
    np.testing.assert_array_equal(counts, [1, 1, 0, 0, 0, 1, 1, 2])
    # no bias: the rule that chooses and weighs by the same scores, but for
    # the epsilon beside the sum
    weight0, chosen0, (_, turned0) = biased_sigmoid_route_weights(
        jnp.zeros((8,)), scale=2.5, eps=0.0)(logits, 3)
    same, chosen1 = sigmoid_route_weights(2.5)(logits, 3)
    np.testing.assert_array_equal(chosen0, chosen1)
    np.testing.assert_allclose(weight0, same, rtol=1e-6)
    assert float(turned0) == 0.0
    # no gradient reaches the bias, and the scores' reaches the logits
    g_bias, g_logits = jax.grad(lambda b, r: jnp.sum(
        biased_sigmoid_route_weights(b)(r, 3)[0] ** 2), (0, 1))(bias, logits)
    assert not np.any(np.asarray(g_bias)) and np.any(np.asarray(g_logits))


# -- each block against the reference's layer ---------------------------------
def whole_layer(cfg, kind, dense, seed=3):
    block = block_of(cfg, kind, dense)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 37, cfg["hidden_size"]))
    shapes = jax.eval_shape(
        lambda: block.init(jax.random.PRNGKey(0), x))["params"]
    return block, seeded(shapes, seed=seed), x


def plain_layer(plain, cfg, kind, sparse, p, h, bias):
    """(the reference's layer of ``h``, its mixer's part, its counts)."""
    @jax.jit
    def run(p, h, bias):
        a = plain.rms_norm(h, p["norm_attn"]["scale"], cfg["norm_eps"])
        mixer = plain.conv_part if kind == "conv" else plain.attention_part
        out, measured = plain.layer(h, p, bias, cfg, kind, sparse, "float32")
        return out, mixer(a, p, cfg, "float32"), measured

    return run(p, h, bias)


@pytest.mark.parametrize("kind,dense", [
    ("conv", True), ("conv", False), ("full_attention", False)])
def test_the_block_is_the_references_layer(plain, kind, dense):
    cfg = tiny_cfg(held=range(8))
    block, p, x = whole_layer(cfg, kind, dense)
    assert ("in_proj" in p) == (kind == "conv") != ("q_norm" in p)
    assert ("router" in p) != dense and ("mlp_gate" in p) == dense
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(5), (8,))
    with jax.default_matmul_precision("highest"):
        want, mixed, measured = plain_layer(
            plain, cfg, kind, not dense, p, x[0], bias)
        got, (load, rows, _, _, counts, turned) = jax.jit(block.apply)(
            {"params": p}, x, bias)
    assert float(jnp.max(jnp.abs(mixed))) > 0.02
    np.testing.assert_allclose(got[0], want, atol=5e-5, rtol=2e-5)
    if dense:
        assert not np.any(np.asarray(counts)) and float(turned) == 0.0
    else:
        np.testing.assert_array_equal(counts, measured[0])
        assert float(turned) == float(measured[1]) > 0
        assert float(jnp.sum(load)) == float(jnp.sum(counts)) == 37 * 3


# -- (b) the shares add up ----------------------------------------------------
@pytest.mark.parametrize("kind", ["conv", "full_attention"])
def test_the_four_shares_add_up_to_the_uncut_layer(plain, kind):
    """The deployment in small: 4 chips share a layer's 8 experts, 2 each,
    every chip with the mixer, the router and its biases whole. The mixer
    counted once, the four chips' routed partials summed: the uncut
    reference's layer."""
    cfg = tiny_cfg(held=range(8))
    block, p, x = whole_layer(cfg, kind, dense=False)
    mixer_out = "out_proj" if kind == "conv" else "o"
    silent = lambda q, *ns: {**q, **{  # noqa: E731
        n: {"kernel": jnp.zeros_like(q[n]["kernel"])} for n in ns}}
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(5), (8,))
    with jax.default_matmul_precision("highest"):
        want, want_mix, _ = plain_layer(
            plain, cfg, kind, True, p, x[0], bias)
        # the mixer alone: no expert adds anything
        mixed, _ = jax.jit(block.apply)({"params": {
            **p, "experts_down": jnp.zeros_like(p["experts_down"])}}, x, bias)
        np.testing.assert_allclose(
            (mixed - x)[0], want_mix, atol=5e-5, rtol=2e-5)
        routed, loads, every = 0.0, [], []
        for s in range(4):
            held = np.asarray((2 * s, 2 * s + 1))
            share = {**p, **{n: p[n][held] for n in (
                "experts_gate", "experts_up", "experts_down")}}
            out, measured = jax.jit(block_of(
                tiny_cfg(held), kind).apply)(
                    {"params": silent(share, mixer_out)}, mixed, bias)
            routed = routed + (out - mixed)
            loads.append(measured[0])
            every.append(measured[4])
            # a share alone is not the expert layer's
            assert float(jnp.max(jnp.abs(
                out[0] - want))) > 1e-2
        # every route lands on exactly one chip, and every chip counts the
        # choices of ALL the experts alike
        assert float(sum(jnp.sum(a) for a in loads)) == 37 * 3
        for counts in every[1:]:
            np.testing.assert_array_equal(counts, every[0])
        np.testing.assert_array_equal(
            jnp.concatenate(loads), every[0])
    np.testing.assert_allclose((mixed + routed)[0], want, atol=5e-5,
                               rtol=2e-5)


# -- (a) the decoder against the plain reference ------------------------------
def test_three_engine_steps_match_the_reference_and_move_the_bias(plain):
    """``engine.train`` for three AdamW steps on seeded weights against the
    reference's ``follow`` on the same batches: the first step's loss and
    gradients leaf by leaf, each step's loss, the parameters' change, and
    the bias after the three steps, element by element; what the layers
    measured rides the model state to ``observe_state``."""
    cfg = tiny_cfg()
    built, model = built_model(cfg)
    seq, vocab = cfg["sequence_length"], cfg["vocab_size"]
    params, state = built.make_state(5)
    assert "head" not in params and len(state["moe_bias"]) == 4
    assert {"in_proj", "conv_kernel", "out_proj", "mlp_gate", "mlp_up",
            "mlp_down", "norm_attn", "norm_moe"} == set(
                params["MoEDecoderBlock_0"])
    assert {"q", "k", "v", "o", "q_norm", "k_norm", "router", "experts_gate",
            "experts_up", "experts_down", "norm_attn", "norm_moe"} == set(
                params["MoEDecoderBlock_1"])
    batches = [tokens(2, seq, vocab, seed=s) for s in range(3)]
    with jax.default_matmul_precision("highest"):
        (loss, measured), grads = jax.jit(jax.value_and_grad(
            built.loss_fn, has_aux=True))(params, state, batches[0])
        rows = [jax.jit(jax.value_and_grad(
            lambda p, x, y: plain.loss_fn(
                p, state["moe_bias"], x, y, cfg, "float32"), has_aux=True))(
                    params, jnp.asarray(x), jnp.asarray(y))
                for x, y in zip(*batches[0])]
    want_loss = sum(float(r[0][0]) for r in rows) / 2
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    want = jax.tree_util.tree_map(
        lambda *g: sum(g) / 2, *[r[1] for r in rows])
    flat, _ = jax.tree_util.tree_flatten_with_path(want)
    for (path, w), g in zip(flat, jax.tree_util.tree_leaves(grads)):
        assert float(jnp.max(jnp.abs(w))) > 1e-9, path  # every leaf learns
        np.testing.assert_allclose(
            g, w, atol=2e-4 * float(jnp.max(jnp.abs(w))), err_msg=str(path))
    np.testing.assert_array_equal(
        sum(r[0][1][0] for r in rows)[:, :2], measured["moe_load"])

    mpi.start(devices=jax.devices()[:1])
    engine = AllReduceSGDEngine(
        built.loss_fn, params, optimizer=built.optimizer, model_state=state)
    losses = []
    engine.hooks = {"on_update": lambda s: losses.append(float(s["loss"]))}
    with jax.default_matmul_precision("highest"):
        engine.train(lambda: iter(batches), max_epochs=1)
        ref = plain.follow(cfg, params, batches, moment_after=1)
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-5)
    norm = lambda a: float(jnp.linalg.norm(a.ravel()))  # noqa: E731
    np.testing.assert_allclose(
        jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda a, b: norm(a - b), engine.params, params)),
        jax.tree_util.tree_leaves(ref["update_norms"]), rtol=2e-3)
    got = jax.device_get(engine.model_state)
    bias = np.stack(got["moe_bias"])
    np.testing.assert_array_equal(bias, np.stack(ref["model_state"]["moe_bias"]))
    # three moves of one rate each, up or down (or none on the mean itself)
    steps = np.round(bias / 1e-3)
    np.testing.assert_allclose(bias, 1e-3 * steps, atol=1e-9)
    assert set(np.abs(steps).ravel()) <= {0, 1, 2, 3} and np.any(steps == 3)
    np.testing.assert_array_equal(
        got["moe_load"], ref["model_state"]["moe_load"])
    np.testing.assert_array_equal(
        got["moe_biased_routes"], ref["model_state"]["moe_biased_routes"])
    np.testing.assert_array_equal(
        got["moe_rows"], ref["model_state"]["moe_rows"])
    # leaf for leaf what the harness compares
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(
        ref["stat_norms"])
    gauges = telemetry.metrics.snapshot()
    value = lambda k: gauges[k]["series"][""]  # noqa: E731
    assert value(names.GAUGE_MOE_BIAS_MAX_ABS) == pytest.approx(3e-3)
    assert value(names.GAUGE_MOE_BIASED_ROUTES) == float(
        np.sum(got["moe_biased_routes"]))
    assert value("tm_moe_routes_per_step") == 2 * seq * 3 * 4
    # four layers of five convolve, none of their elements in a kernel
    assert value(names.GAUGE_CONV_ELEMENTS) == 4 * 2 * seq * 64
    assert value(names.GAUGE_CONV_KERNEL_ELEMENTS) == 0
    assert value("tm_attn_calls_per_step") == 1


def test_the_bias_turns_routes_and_the_forward_pass_reads_it(plain):
    """A bias far from 0 changes which experts the step's tokens take, the
    loss with them, and nothing else reads it: the program's loss under it
    is the reference's under it."""
    cfg = tiny_cfg()
    built, model = built_model(cfg)
    params, state = built.make_state(6)
    x, y = tokens(2, cfg["sequence_length"], cfg["vocab_size"], seed=1)
    far = [0.3 * jax.random.normal(jax.random.PRNGKey(i), (8,))
           for i in range(4)]
    with jax.default_matmul_precision("highest"):
        at = lambda bias: jax.jit(built.loss_fn)(  # noqa: E731
            params, {**state, "moe_bias": bias}, (x, y))
        loss0, seen0 = at(state["moe_bias"])
        loss, seen = at(far)
        want = sum(float(plain.loss_fn(
            params, far, jnp.asarray(a), jnp.asarray(b), cfg, "float32")[0])
            for a, b in zip(x, y)) / 2
    assert float(loss) != float(loss0)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert not np.any(np.asarray(seen0["moe_biased_routes"]))
    assert np.all(np.asarray(seen["moe_biased_routes"]) > 0)
    # the new bias is the old one moved by one rate, whatever it was
    moved = np.stack(seen["moe_bias"]) - np.stack(far)
    assert set(np.round(moved / 1e-3).ravel()) <= {-1.0, 0.0, 1.0}


# -- (d) two devices end a step with the same bias ----------------------------
def test_two_devices_end_a_step_with_the_reference_bias_of_both_batches(plain):
    """Each of two devices brings its own sequences to the same experts:
    with the model's ``axis_name`` the counts of ALL the experts are summed
    over the devices before the sign, so both end the step with the same
    bias, the reference's on the two batches together; without it each
    would follow its own counts."""
    cfg = tiny_cfg()
    built, model = built_model(cfg, axis_name="dp")
    _, alone = built_model(cfg)
    params, state = built.make_state(7)
    x, y = tokens(4, cfg["sequence_length"], cfg["vocab_size"], seed=2)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))

    def new_bias(model):
        def per_device(params, state, x, y):
            _, new = make_moe_lm_loss_fn(model)(params, state, (x, y))
            return jnp.stack(new["moe_bias"])[None]

        return jax.jit(jax.shard_map(
            per_device, mesh=mesh, in_specs=(P(), P(), P("dp"), P("dp")),
            out_specs=P("dp"), check_vma=False))(params, state, x, y)

    with jax.default_matmul_precision("highest"):
        together, apart = new_bias(model), new_bias(alone)
        ref = plain.follow(cfg, params, [(x, y)], groups=2)
    want = np.stack(ref["model_state"]["moe_bias"])
    np.testing.assert_array_equal(together[0], together[1])
    np.testing.assert_array_equal(together[0], want)
    assert np.any(np.asarray(apart[0]) != np.asarray(apart[1]))
    # ... and what a chip's state shows of its own routing is the chips'
    # mean, as the engine's ``pmean`` leaves it
    assert ref["model_state"]["moe_load"].shape == (4, 2)


# -- (e) the tied leaf -------------------------------------------------------
def test_the_tied_tables_gradient_is_the_heads_and_the_lookups_summed():
    """The tied model's one table receives what an untied twin's head and
    lookup receive apart (the twin's head the table's transpose): the
    head's blocked ``dW`` ``[D, V]`` and the lookup's ``[V, D]`` land in one
    leaf; and the loss and every other gradient are the twin's."""
    cfg = tiny_cfg()
    built, tied = built_model(cfg)
    twin = tied.clone(tied_head=False)
    params, state = built.make_state(8)
    x, y = tokens(2, cfg["sequence_length"], cfg["vocab_size"], seed=3)
    table = params["embed"]["embedding"]
    apart = {**params, "head": {"kernel": table.T}}
    with jax.default_matmul_precision("highest"):
        (loss, _), got = jax.jit(jax.value_and_grad(
            make_moe_lm_loss_fn(tied), has_aux=True))(params, state, (x, y))
        (loss2, _), want = jax.jit(jax.value_and_grad(
            make_moe_lm_loss_fn(twin), has_aux=True))(apart, state, (x, y))
    np.testing.assert_allclose(loss, loss2, rtol=1e-6)
    head, lookup = want["head"]["kernel"].T, want["embed"]["embedding"]
    assert float(jnp.max(jnp.abs(head))) > 1e-6 < float(
        jnp.max(jnp.abs(lookup)))
    np.testing.assert_allclose(
        got["embed"]["embedding"], head + lookup,
        atol=1e-6 * float(jnp.max(jnp.abs(head + lookup))))
    for name in ("MoEDecoderBlock_0", "MoEDecoderBlock_1", "norm"):
        for a, b in zip(jax.tree_util.tree_leaves(got[name]),
                        jax.tree_util.tree_leaves(want[name])):
            np.testing.assert_allclose(
                a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))))
    # the logits to whoever asks for logits: the table's transpose
    logits, _ = tied.apply({"params": params}, x)
    logits2, _ = twin.apply({"params": apart}, x)
    assert logits.shape == (2, cfg["sequence_length"], cfg["vocab_size"])
    np.testing.assert_allclose(logits, logits2, rtol=1e-5, atol=1e-5)


# -- the scopes ----------------------------------------------------------------
def test_the_scopes_nest_under_fwd_bwd_in_the_lowered_step():
    """A convolution layer's operations stand under ``tm.lm.sconv_proj`` and
    ``tm.lm.sconv``, forward, recomputed and backward, neither inside the
    other nor inside attention's, and open no attention scope."""
    import re

    from benchmark import model_scopes, scopes

    assert names.SCONV_SCOPE_NAMES == SCONV
    cfg = tiny_cfg()
    built, _ = built_model(cfg)
    params, state = built.make_state(9)
    mpi.start(devices=jax.devices()[:1])
    engine = AllReduceSGDEngine(
        built.loss_fn, params, optimizer=built.optimizer, model_state=state)
    batch = tokens(2, cfg["sequence_length"], cfg["vocab_size"])
    text = engine._step_fn.lower(
        engine.params, engine.opt_state, engine.model_state,
        engine._prepare_batch(batch)).as_text(debug_info=True)
    seen = {}
    for op in set(re.findall(r'"(jit\(tm_train_step\)[^"]*)"', text)):
        bucket = model_scopes.bucket_of(op)
        if bucket not in (None, model_scopes.UNNAMED):
            assert scopes.scope_of(op) == "tm.fwd_bwd", op
            seen.setdefault(bucket, set()).add(model_scopes.phase_of(op))
            inner = model_scopes.BUCKET.findall(op.split(
                "rematted_computation")[-1].split("transpose(")[-1])
            assert len(inner) <= 1 or inner[-2:] == [
                "tm.lm.head", "tm.lm.loss"], op
    assert set(seen) == {
        *SCONV, "tm.lm.embed", "tm.lm.norm", "tm.attn.proj", "tm.attn.full",
        "tm.moe.dense", "tm.moe.router", "tm.moe.route", "tm.moe.experts",
        "tm.moe.combine", "tm.lm.head", "tm.lm.loss"}, seen
    assert seen["tm.lm.sconv"] == {"forward", "recompute", "backward"}


# -- the configuration's file -------------------------------------------------
def test_the_file_keeps_every_catalog_number():
    """Every number of the catalog's entry under its own key at its
    published value, but those that are cut, which ``reduced`` and
    ``published`` name: counts of layers, experts and rows, never a
    width."""
    cfg = json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    types = ["conv", "conv", "full_attention"] + 4 * [
        "conv", "conv", "conv", "full_attention"] + [
        "conv", "conv", "full_attention", "conv", "conv"]
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True,
    }
    for key, value in catalog.items():
        assert cfg[key] == value and key not in cfg["reduced"], key
    cut = {"num_hidden_layers": (5, 24), "num_dense_layers": (1, 2),
           "layer_types": (types[1:6], types), "num_experts": (8, 32),
           "vocab_size": (16384, 65536)}
    assert sorted(cut) == sorted(cfg["reduced"]) and len(types) == 24
    for key, (here, published) in cut.items():
        assert cfg[key] == here and cfg["published"][key] == published
    assert cfg["layer_types"] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert cfg["model"] == {**cfg["model"], "router_outputs": 32,
                            "experts_held": list(range(8)),
                            "bias_update_rate": 0.001,
                            "route_epsilon": 1e-06}
    assert cfg["sequence_length"] == 8192 and cfg["per_chip_batch"] == 2
    assert "4 chips" in cfg["deployment"] and "8 a chip" in cfg["deployment"]
    assert {"auxiliary_loss", "bias_in_state", "reference"} == set(
        cfg["departures"])
    assert {"expert_bias", "tied_head", "hidden_act", "rotary", "weights",
            "data", "learning_rate", "conv_mixer", "router"} <= set(
                cfg["assumed"])
    assert "2412.19437" in cfg["assumed"]["expert_bias"]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    tiny = cfg["rehearsal"]
    assert tiny["model"]["experts_held"] == [0, 1]
    assert tiny["model"]["router_outputs"] == 8


def test_flops_of_the_configuration_are_the_issues_arithmetic():
    from benchmark import configs, sconv_decoder_flops as count

    cfg = configs.load(CONFIG)
    kinds = ["conv", "full_attention", "conv", "conv", "conv"]
    forward = lambda **over: count.sconv_decoder_forward_flops(  # noqa: E731
        **{**dict(seq=8192, d_model=2048, kinds=kinds, taps=3, heads=32,
                  kv_heads=8, head_dim=64, dense_layers=1, dense_width=7168,
                  expert_width=1792, experts=32, top_k=4, held=8,
                  vocab=16384), **over})
    whole, t = forward(), 8192
    # ISSUE 48: 1.30 GFLOP a token trained, 21 TFLOP a step of 2 sequences
    assert 1.295e9 < 3 * whole / t < 1.305e9
    assert configs.build(CONFIG, cfg).flops_per_sample == 3 * whole
    assert 21.0e12 < 2 * 3 * whole < 21.5e12
    # by part: the conv mixers 31 %, the experts 20, the dense layer 20,
    # the head 16, attention 13
    conv = 4 * (2 * t * 2048 * 4 * 2048 + t * 2048 * 8)
    attention = (2 * t * 2048 * (2048 + 2 * 512) + 2 * t * 2048 * 2048
                 + 4 * (t * (t + 1) // 2) * 2048)
    dense = t * 6 * 2048 * 7168
    experts = 4 * (t * 2 * 2048 * 32 + t * 4 * 8 * 6 * 2048 * 1792 // 32)
    head = 2 * t * 2048 * 16384
    assert conv + attention + dense + experts + head == whole
    for part, low, high in [(conv, 0.305, 0.315), (experts, 0.20, 0.21),
                            (dense, 0.20, 0.21), (head, 0.15, 0.16),
                            (attention, 0.12, 0.13)]:
        assert low < part / whole < high, (part / whole, low)
    assert whole - forward(vocab=0) == head
    assert whole - forward(held=0) == experts - 4 * t * 2 * 2048 * 32
    # without the leading layer: its dense feed-forward and one mixer less
    assert forward(kinds=kinds[1:], dense_layers=0) == (
        whole - dense - conv // 4)
    # the operation's own work and traffic, whatever implements it
    assert count.short_conv_forward_flops(t, 2048, 3) == t * 2048 * 8
    assert count.short_conv_bytes(t, 2048, 2, True) == t * 2048 * 2 * 15
    assert count.short_conv_bytes(t, 2048, 2, False) == t * 2048 * 2 * 11


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_the_seeded_state_is_one_state_a_seed(seed):
    """A seed is one state however often it is made, seeds past 32 signed
    bits too, another seed another; a router's columns are one population
    (turned by whole groups, none rescaled), the taps lie within 1 /
    sqrt(3), the bias starts from 0."""
    from benchmark import configs

    cfg = configs.load(CONFIG, rehearse=True)
    built = configs.build(CONFIG, cfg)
    (params, state), (again, _), (other, _) = (
        jax.device_get(built.make_state(s)) for s in (seed, seed, seed + 1))
    same = lambda a, b: jax.tree_util.tree_all(  # noqa: E731
        jax.tree_util.tree_map(
            lambda x, y: bool(np.array_equal(x, y)), a, b))
    assert same(params, again) and not same(params, other)
    assert not any(np.any(b) for b in state["moe_bias"])
    for i, kind in enumerate(cfg["layer_types"]):
        block = params[f"MoEDecoderBlock_{i}"]
        if kind == "conv":
            assert float(np.max(np.abs(block["conv_kernel"]))) <= 3 ** -0.5
            assert 0.004 < float(np.std(block["out_proj"]["kernel"])) < 0.008
        if i >= cfg["num_dense_layers"]:
            router = block["router"]["kernel"]
            assert router.shape == (cfg["hidden_size"], 8)
            for part in (router[:, :2], router[:, 2:]):
                assert 0.015 < float(np.std(part)) < 0.025


@pytest.mark.parametrize("seed", [3, 77, 2**31 + 5])
def test_the_held_experts_are_the_group_at_the_mean_expected_load(seed):
    """Each router's seeded columns are turned by whole groups so that the
    experts held here are the group whose expected load, while the biases
    are 0, is nearest the mean share; the expectation worked again here in
    plain numpy (each id through the leading dense feed-forward by itself,
    an expert expected an id's tokens by its logit's distance from the
    middle between the id's third and fourth), and the turn undone and
    found again."""
    from benchmark import configs

    cfg = configs.load(CONFIG, rehearse=True)
    mod = configs.load_module(ROOT / "benchmark" / "configs" / f"{CONFIG}.py")
    params = jax.device_get(mod.build(cfg).make_state(seed)[0])
    n, seq = 2, cfg["sequence_length"]
    zipf = 1.0 / np.arange(1, 98)
    count = 2 * seq * zipf / zipf.sum()
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    h = params["embed"]["embedding"].astype(np.float64)
    for i in range(5):
        block = params[f"MoEDecoderBlock_{i}"]
        x = h / np.sqrt((h * h).mean(-1, keepdims=True) + 1e-5) * block[
            "norm_moe"]["scale"]
        if i == 0:
            h = h + (silu(x @ block["mlp_gate"]["kernel"])
                     * (x @ block["mlp_up"]["kernel"])
                     ) @ block["mlp_down"]["kernel"]
            continue
        logits = x @ block["router"]["kernel"]
        edge = np.sort(logits, axis=-1)[:, -4:-2].mean(-1, keepdims=True)
        load = (count[:, None] * 1.0 / (1.0 + np.exp(
            -(logits - edge) / mod.SOFT))).sum(0).reshape(-1, n).sum(-1)
        off = np.abs(load - load.mean())
        assert off[0] == off.min(), (i, off)
    # a router turned further by one group is turned back, nothing else moves
    again = mod.held_at_mean_load(cfg, params)
    moved = {**params, "MoEDecoderBlock_2": {
        **params["MoEDecoderBlock_2"], "router": {"kernel": np.roll(
            params["MoEDecoderBlock_2"]["router"]["kernel"], n, axis=1)}}}
    back = mod.held_at_mean_load(cfg, moved)
    for tree in (again, back):
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: bool(np.array_equal(a, b)), params, tree))
