"""The short causal convolution with its SiLU as one operation
(``parallel.ssm.causal_conv1d_silu``): the two kernels of
``ops/conv_kernel.py`` interpreted on the CPU against
``silu(causal_conv1d(...))`` and jax's derivative of it, the rule that
chooses between them, the gauges and the benchmark's reader of them."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchmpi_tpu import telemetry
from torchmpi_tpu.ops import conv_kernel
from torchmpi_tpu.parallel import ssm
from torchmpi_tpu.telemetry import names

TILE, LANES = 64, 128  # the tests' tile: the module's own is the chip's


def plain(x, taps, bias):
    return jax.nn.silu(ssm.causal_conv1d(x, taps, bias))


def problem(t, c, taps, dtype, biased, seed=0, batch=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    bound = taps ** -0.5
    bias = jax.random.uniform(ks[2], (c,), jnp.float32, -bound, bound)
    return (jax.random.normal(ks[0], (batch, t, c)).astype(dtype),
            jax.random.uniform(ks[1], (taps, c), jnp.float32, -bound, bound),
            bias if biased else jnp.zeros_like(bias),
            jax.random.normal(ks[3], (batch, t, c)))


def interpreted(x, taps, bias, dy):
    tile = {"positions": TILE, "lanes": LANES, "interpret": True}
    return (conv_kernel.forward(x, taps, bias, **tile),
            *conv_kernel.backward(x, taps, bias, dy, **tile))


@pytest.mark.parametrize("taps", [4, 2])
@pytest.mark.parametrize("tiles", [1, 3])
@pytest.mark.parametrize("biased", [False, True])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_kernels_are_the_expressions_and_their_derivative(
        dtype, biased, tiles, taps):
    """``y``, ``dx``, ``dtaps`` and ``dbias`` over one tile of positions
    and over three (the rows before a tile and, backward, the rows after it
    cross a tile's edge), two tiles of channels, two sequences: float32 to
    1e-6, ``dx`` of a bfloat16 input to one rounding."""
    x, kernel, bias, dy = problem(tiles * TILE, 2 * LANES, taps, dtype, biased)
    want, pull = jax.vjp(plain, x, kernel, bias)
    want_dx, want_dtaps, want_dbias = pull(dy)
    y, dx, dtaps, dbias = interpreted(x, kernel, bias, dy)
    assert (y.dtype, dx.dtype, dtaps.dtype, dbias.dtype) == (
        jnp.float32, dtype, jnp.float32, jnp.float32)
    np.testing.assert_allclose(y, want, atol=1e-6, rtol=1e-6)
    # a sum over 2 x t positions of terms of either sign
    np.testing.assert_allclose(dtaps, want_dtaps, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(dbias, want_dbias, atol=2e-5, rtol=1e-5)
    if dtype == jnp.float32:
        np.testing.assert_allclose(dx, want_dx, atol=1e-6, rtol=1e-6)
    else:
        got, want_dx = (np.asarray(v, np.float32) for v in (dx, want_dx))
        # one rounding: the two float32 sums may fall either side of a
        # tie, a unit in the last of bfloat16's 8 bits apart
        assert np.all(np.abs(got - want_dx)
                      <= 2.0 ** -7 * np.abs(want_dx) + 1e-6)
        assert np.mean(got != want_dx) < 0.01


def test_a_later_position_changes_nothing_before_it():
    """Causal across tiles: an input changed from position ``s`` on leaves
    ``y[:s]`` bit for bit, ``s`` just behind a tile's edge and in a tile's
    middle; and ``y[s]`` does change."""
    x, kernel, bias, _ = problem(3 * TILE, LANES, 4, jnp.float32, True)
    run = lambda x: conv_kernel.forward(  # noqa: E731
        x, kernel, bias, positions=TILE, lanes=LANES, interpret=True)
    y = np.asarray(run(x))
    for s in (TILE, TILE + 1, 2 * TILE - 1, 100):
        moved = np.asarray(run(x.at[:, s:].add(1.0)))
        np.testing.assert_array_equal(moved[:, :s], y[:, :s])
        assert np.all(moved[:, s] != y[:, s])


def test_the_first_positions_see_zeros_before_them():
    """Position 0 of every sequence reads the last tap alone, whatever the
    tile before it in memory holds (the second sequence's rows before its
    tile 0 are the first sequence's last)."""
    x, kernel, bias, _ = problem(2 * TILE, LANES, 4, jnp.bfloat16, True)
    y = conv_kernel.forward(
        x, kernel, bias, positions=TILE, lanes=LANES, interpret=True)
    np.testing.assert_allclose(
        y[:, 0], jax.nn.silu(bias + kernel[3] * x[:, 0].astype(jnp.float32)),
        atol=1e-6)


@pytest.mark.parametrize("shape,dtype,taps,taken", [
    ((1, 16384, 8192), jnp.bfloat16, 4, True),   # qwen3-next-80b-a3b's
    ((1, 16384, 1024), jnp.float32, 4, False),   # falcon-h1-34b's: narrow
    ((1, 16384, 2048), jnp.float32, 4, True),
    ((2, 40, 128), jnp.float32, 4, False),       # the rehearsals'
    ((1, 16383, 8192), jnp.float32, 4, False),   # an odd length
    ((1, 16384, 8200), jnp.float32, 4, False),   # channels that fill no lane
    ((1, 16384, 8192), jnp.float16, 4, False),
    ((1, 16384, 8192), jnp.float32, 10, False),  # more taps than rows held
])
def test_the_kernels_take_whole_tiles_of_wide_inputs(
        shape, dtype, taps, taken):
    assert conv_kernel.takes(shape, dtype, taps) is taken


def test_shapes_the_kernels_do_not_take_are_the_expressions(monkeypatch):
    """... with jax's own derivative: no rule, no kernel, the traced
    program the one ``silu(causal_conv1d(...))`` traces to."""
    x, kernel, bias, dy = problem(40, LANES, 4, jnp.float32, True)
    monkeypatch.setattr(ssm, "_conv_silu", None)  # would raise if called
    assert str(jax.make_jaxpr(ssm.causal_conv1d_silu)(x, kernel, bias)) == (
        str(jax.make_jaxpr(plain)(x, kernel, bias)))
    got = jax.vjp(ssm.causal_conv1d_silu, x, kernel, bias)[1](dy)
    for a, b in zip(got, jax.vjp(plain, x, kernel, bias)[1](dy)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_rule_keeps_the_input_as_it_came(dtype):
    """Where the shapes take the kernels the operation is one
    ``custom_vjp`` whose residuals are ``x`` in its own dtype, the taps and
    the bias; lowered for the CPU both ways are the expressions, and the
    gradients jax's own of them; lowered for a TPU they are the two kernels
    and no padded array."""
    x, kernel, bias, dy = problem(
        conv_kernel.POSITIONS, conv_kernel.WIDE, 4, dtype, True, batch=1)
    assert conv_kernel.takes(x.shape, x.dtype, 4)
    y, pull = jax.vjp(ssm.causal_conv1d_silu, x, kernel, bias)
    kept = {(v.shape, v.dtype.name) for v in jax.tree.leaves(pull)
            if hasattr(v, "shape")}
    assert kept == {(x.shape, jnp.dtype(dtype).name),
                    (kernel.shape, "float32"), (bias.shape, "float32")}
    want, want_pull = jax.vjp(plain, x, kernel, bias)
    np.testing.assert_array_equal(y, want)
    for a, b in zip(pull(dy), want_pull(dy)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    both = lambda x, k, b, dy: jax.vjp(  # noqa: E731
        ssm.causal_conv1d_silu, x, k, b)[1](dy)
    text = jax.jit(both).trace(x, kernel, bias, dy).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "tm_conv_silu_fwd"') == 0  # y not asked
    assert text.count('kernel_name = "tm_conv_silu_bwd"') == 1
    assert f"{conv_kernel.POSITIONS + 3}x" not in text
    text = jax.jit(ssm.causal_conv1d_silu).trace(x, kernel, bias).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "tm_conv_silu_fwd"') == 1
    assert f"{conv_kernel.POSITIONS + 3}x" not in text


def test_a_recomputed_call_runs_the_forward_kernel_again_not_the_rule():
    """Under ``jax.checkpoint``, with something behind the operation that
    needs its result in backward (the delta mixer's L2 norms), the forward
    kernel runs twice and the backward kernel once: what the mixer's own
    checkpoint costs. The rule itself needs no result made again."""
    x, kernel, bias, _ = problem(
        conv_kernel.POSITIONS, conv_kernel.WIDE, 4, jnp.bfloat16, False,
        batch=1)
    loss = lambda x, k: jnp.sum(jax.checkpoint(  # noqa: E731
        lambda x, k: jnp.square(ssm.causal_conv1d_silu(x, k, bias)))(x, k))
    text = jax.jit(jax.value_and_grad(loss, (0, 1))).trace(x, kernel).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "tm_conv_silu_fwd"') == 2
    assert text.count('kernel_name = "tm_conv_silu_bwd"') == 1


# -- the gauges and the benchmark's reader of them ----------------------------
def gauges():
    return tuple(telemetry.metrics.gauge(name, "").value() for name in (
        names.GAUGE_CONV_ELEMENTS, names.GAUGE_CONV_KERNEL_ELEMENTS))


def test_the_gauges_count_elements_and_those_the_kernels_take(monkeypatch):
    """From static shapes: layers x sequences x positions x channels; the
    second only where the shapes take the kernels AND jax's backend is a
    TPU (on this CPU nothing runs a kernel)."""
    ssm.note_conv_step(3, (1, 16384, 8192), jnp.bfloat16, 4)
    assert gauges() == (3 * 16384 * 8192, 0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ssm.note_conv_step(3, (1, 16384, 8192), jnp.bfloat16, 4)
    assert gauges() == (3 * 16384 * 8192, 3 * 16384 * 8192)
    ssm.note_conv_step(4, (2, 40, 128), jnp.float32, 4)
    assert gauges() == (4 * 2 * 40 * 128, 0)


def _reader():
    from benchmark import configs

    return configs.load_module(
        configs.HERE.parent / "layer_metrics" / "conv_kernel_share.py")


def test_the_share_is_the_second_gauge_over_the_first(monkeypatch):
    """``conv_kernel_share``, the entry the benchmark lists it under (the
    three cells whose models run a short convolution, beside each one's own
    ``*_conv_ms_per_step``; ``lfm2-8b-a1b``'s gated one has no kernel and
    reads 0 whatever its shape): 100 where the shapes take the kernels on a
    TPU, 0 where they do not (``falcon-h1-34b``'s 1,024 channels), and None
    where the program has no such gauge (the parent of PR 46, a model
    without the convolution)."""
    from benchmark import configs

    spec = json.loads((configs.HERE.parents[1] / "BENCHMARK.json").read_text())
    entry = next(
        m for m in spec["per_layer"] if m["name"] == "conv_kernel_share")
    assert entry == {
        "name": "conv_kernel_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "gated delta rule",
        "moves": "samples_per_s_per_chip",
        "workloads": ["qwen3-next-80b-a3b.stream.x1",
                      "falcon-h1-34b.stream.x1", "lfm2-8b-a1b.stream.x1"]}
    by_name = {m["name"]: m["workloads"] for m in spec["per_layer"]}
    assert entry["workloads"] == (
        by_name["gdn_conv_ms_per_step"] + by_name["ssm_conv_ms_per_step"]
        + by_name["short_conv_ms_per_step"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ssm.note_conv_step(3, (1, 16384, 8192), jnp.bfloat16, 4)
    assert _reader().read({}) == 100.0
    ssm.note_conv_step(4, (1, 16384, 1024), jnp.float32, 4)
    assert _reader().read({}) == 0.0  # falcon-h1-34b's: too narrow
    monkeypatch.undo()
    ssm.note_conv_step(3, (1, 16384, 8192), jnp.bfloat16, 4)
    assert _reader().read({}) == 0.0  # no TPU
    real = telemetry.metrics.snapshot
    monkeypatch.setattr(telemetry.metrics, "snapshot", lambda *a, **kw: {
        k: v for k, v in real(*a, **kw).items()
        if k != names.GAUGE_CONV_KERNEL_ELEMENTS})
    assert _reader().read({}) is None
    monkeypatch.undo()
    ssm.note_gated_conv_step(4, (2, 8192, 2048))
    assert _reader().read({}) == 0.0  # the gated one: no kernel
    telemetry.metrics.gauge(names.GAUGE_CONV_ELEMENTS, "").set(0)
    assert _reader().read({}) is None  # no call of the operation
