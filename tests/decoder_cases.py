"""The cases every decoder configuration of the benchmark has, written once
and collected nowhere by themselves: a configuration's own two test files
import them by name, so each case runs there, for that file's configuration,
under that file's node id (what ``--dist loadfile`` groups by). A new
configuration adds its two files and edits no file that is there. The way
``benchmark/tests/test_correct_<config>.py`` borrows ``test_correct.py`` is
the model. This module names no configuration.

``tests/test_cell_<config>.py``, the cell's whole run at the rehearsal's
sizes, imports the five cases of the first part and says

- ``CONFIG``: the configuration's name,
- ``MORE``: the metrics its traced rehearsal reports beyond ``REHEARSED``,
- ``ABSENT``: names, or prefixes of names, of which it reports none.

``tests/test_chip_<config>.py``, the cell's training step at its real size
for a v5e that is described and not attached (the TPU's compiler is
installed here: what the chip's compiler would refuse, it refuses here),
imports the fixtures ``one_chip``, ``lowered`` and ``compiled`` (the step is
lowered once a file and compiled once) and the three cases of the second
part, and says

- ``CONFIG``,
- ``PIN``: characters and the first 16 of the sha256 of the lowered step,
- ``OWN``: the per-layer metrics that came with its cell, sorted,
- ``PARAMETERS``: the least and the most parameters the step may have,
- ``FITS_IN``: bytes the step's arguments and temporaries stay under,
- ``KERNELS``: how often the compiled step calls each kernel,
- ``ATTENTION_KERNELS``: those of them ``attn_kernel_ms_per_step`` reads,
- ``HOLDS`` and ``HOLDS_NO``: patterns the compiled text must and must not
  match.

A file whose model's products bear names (``models.lm.product``) also imports
``test_the_cells_step_keeps_the_products_the_rule_counted`` and says
``NOTHING_KEPT``: the temporaries' bytes of the same step compiled with no
product kept (``python3 scripts/recompute_probe.py <config> --keep none
--compile``), and ``PRODUCTS``: the products (``convolution``s) of the
compiled step and of that one. The step is lowered with the described
chip's memory in the place of this process's (``models.lm.device_bytes``,
the one function that reads it), so the program compiled here is the chip's.

What a configuration alone has (its operation count, its published widths,
the shapes only its step can hold) are cases of its own in those files, which
may use everything here. ``tests/conftest.py`` registers this module for
assertion rewriting."""

import hashlib
import json
import re
import sys
from collections import Counter
from functools import lru_cache
from pathlib import Path
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

REHEARSED = {"engine_dispatch_ms"}  # what every cell's traced rehearsal reports


# -- the cell at its rehearsal's sizes --------------------------------------
def cell_of(config):
    return config + ".stream.x1"


def rehearsed_run(capsys, config, *args):
    """``benchmark/run.py --rehearse`` on the configuration's cell: (exit
    code, the JSON line it ends with, all it printed)."""
    from benchmark import run as bench

    rc = bench.main(["--workload", cell_of(config), *args, "--rehearse"])
    out = capsys.readouterr().out
    return rc, json.loads(out.strip().splitlines()[-1]), out


@lru_cache(maxsize=None)
def shown():
    """``benchmark/tests/test_correct.py``'s demonstrations, by path."""
    from benchmark import configs

    return configs.load_module(ROOT / "benchmark" / "tests" / "test_correct.py")


def test_zipf_token_ids_are_seeded_and_skewed(request):
    from benchmark import configs

    config = request.module.CONFIG
    cfg = configs.load(config, rehearse=True)
    built = configs.build(config, cfg)
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    x, y = built.make_data(big, 64)
    x2, _ = built.make_data(big, 64)
    np.testing.assert_array_equal(x, x2)
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    assert x.dtype == np.int32 and x.min() >= 0 and x.max() < 97
    counts = np.bincount(x.ravel(), minlength=97)
    # p(id) is 1 / (id + 1) over H_97 = 5.15: id 0 near a fifth
    assert 0.15 < counts[0] / x.size < 0.24
    assert counts[0] > counts[1] > counts[3] > counts[9] > counts[40]


def test_the_cells_rehearsal_is_correct(request, capsys):
    """The cell's whole run at the rehearsal's sizes, as
    ``benchmark/tests`` drives the other cells."""
    rc, line, out = rehearsed_run(
        capsys, request.module.CONFIG, "--seed", str(2**31 + 7),
        "--seconds", "1", "--trace", "0")
    assert rc == 0 and line["correct"] is True, out
    assert line["metrics"] == {} and line["attempted"] >= 32


def test_the_cells_traced_rehearsal_reports_the_routing_counters(
        request, capsys):
    """... and what its own layers measured where the engine's step is
    traced: ``MORE`` (a routed one's expert rows and load, a selecting
    one's selection, one held by share its share of the heads), and
    nothing of ``ABSENT``, the layers it has not (the scope metrics need a
    TPU's trace)."""
    rc, line, out = rehearsed_run(
        capsys, request.module.CONFIG, "--seed", "11", "--trace", "1")
    assert rc == 0 and line["correct"] is True, out
    assert REHEARSED | request.module.MORE <= set(line["rehearsed"])
    assert not [m for m in line["rehearsed"]
                if m.startswith(tuple(request.module.ABSENT))]


def test_a_step_that_changes_nothing_is_not_correct_in_the_cell(
        request, capsys, monkeypatch):
    shown().test_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch, cell_of(request.module.CONFIG))


def test_the_fp8_control_is_not_correct_in_the_cell(request):
    shown().test_fp8_control_is_not_correct(cell_of(request.module.CONFIG))


# -- the cell's step at its real size, for the described chip ---------------
@pytest.fixture(scope="module")
def one_chip():
    """The topology is described inside a fixture, so that only a worker
    that runs such a file loads the TPU's library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps it from describing
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


V5E_BYTES = 16_909_336_064  # a v5e's memory as its runtime reports it
#                            (``bytes_limit``: my chip run, PR 47)


def lowered_step(config, one_chip):
    """(cfg, the parameters' shapes, the cell's training step lowered for
    the described chip, what ``models.lm.kinds_kept`` was asked and said
    while it was traced: ``limit``, ``parameters``, ``beside``, ``kinds``,
    ``kept``). The rule reads the described chip's memory, not this
    process's, which has none."""
    from benchmark import configs
    from torchmpi_tpu.models import lm

    rule, kinds_kept = {}, lm.kinds_kept

    def recorded(limit, parameters, beside, kinds):
        kept = kinds_kept(limit, parameters, beside, kinds)
        rule.update(limit=limit, parameters=parameters, beside=beside,
                    kinds=kinds, kept=kept)
        return kept

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
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lm, "device_bytes", lambda: V5E_BYTES)
        patch.setattr(lm, "kinds_kept", recorded)
        step = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            place(params),
            place(jax.eval_shape(built.optimizer.init, params)),
            place(state), (tokens, tokens))
    return cfg, params, step, rule


def compile_uncached(lowered):
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


@pytest.fixture(scope="module")
def lowered(request, one_chip):
    """``lowered_step`` of the file's ``CONFIG``, made once for the pin and
    for the compilation."""
    return lowered_step(request.module.CONFIG, one_chip)


class Compiled(NamedTuple):
    cfg: dict
    parameters: int  # how many the step trains
    step: Any        # the compiled step
    text: str        # ... and its text
    rule: dict       # what the rule of ``models.lm`` counted and kept


@pytest.fixture(scope="module")
def compiled(lowered):
    """The file's step compiled, once for the cases that read it."""
    cfg, params, step, rule = lowered
    step = compile_uncached(step)
    return Compiled(
        cfg, sum(a.size for a in jax.tree_util.tree_leaves(params)), step,
        step.as_text(), rule)


def kernel_calls(text):
    """How often a compiled step's text calls each kernel."""
    return Counter(
        re.findall(r"%([A-Za-z_]+?)[.\d]* = [^\n]*tpu_custom_call", text))


def kernel_phases(text, kernel):
    """By (innermost scope, phase), as the benchmark's reader of a device
    trace files an event under ``tm.fwd_bwd`` (``benchmark/model_scopes.py``),
    how often a compiled step's text calls the kernels whose name starts
    with ``kernel``: a kernel whose call bore no ``op_name`` would read
    under no scope."""
    from benchmark import model_scopes

    return Counter(
        ((model_scopes.BUCKET.findall(op) or [None])[-1],
         model_scopes.phase_of(op))
        for op in re.findall(
            r'%%%s[\w.]* = [^\n]*tpu_custom_call[^\n]*op_name="([^"]*)"'
            % kernel, text))


def whole_logits(text, cfg):
    """The arrays of a compiled step's text, of any type, with a row for
    every token of the step and a column for every id: the ``[rows, V]``
    (or ``[batch, t, V]``) logits and their gradient, 1.99 GiB each in
    float32 in a step of 16,384 rows and 32,640 ids. The head's own
    derivative rule (``models/lm_head.py``) leaves none: a block's
    ``[8192, V]`` at most."""
    batch, seq = cfg["per_chip_batch"], cfg["sequence_length"]
    vocab = cfg.get("vocab_size", cfg["model"].get("vocab_size"))
    return sorted(set(re.findall(
        r"\w+\[(?:%d,%d|%d),%d\]" % (batch, seq, batch * seq, vocab), text)))


def row_scatters(text, cfg):
    """The ``scatter`` instructions of a compiled step's text whose operand
    is the ``f32[V, D]`` embedding table: jax's transpose of the token
    gather (64 and 42 ms a step on the chip at the two widest tables:
    PERF.md, PR 42). The lookup's own derivative rule
    (``models/embedding.py``) leaves none: what it scatters is ``V``
    integers."""
    table = f"f32[{cfg['vocab_size']},{cfg['hidden_size']}]"
    return [line for line in text.splitlines()
            if re.search(r" scatter\(", line) and table in line]


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


def two_tiers(text, routes, widths):
    """The expert layers' two tiers in a compiled step's text: of each
    conditional's two branches, the compact one holds no array of all the
    ``routes``' rows at one of ``widths`` and the worst case's does. Gives
    the number of conditionals."""
    wide = re.compile(r"\[%d,(?:%s)\]" % (routes, "|".join(map(str, widths))))
    branches = conditional_branches(text)
    for pair in branches:
        assert sorted(bool(wide.search(body)) for body in pair) == [
            False, True]
    return len(branches)


def test_the_cells_step_lowers_for_the_chip_to_the_text_it_had(
        request, lowered):
    """The cell's whole step at its real size as LOWERED for the described
    chip (not compiled) is ``PIN``: characters and the first 16 of the
    sha256 of the text without the kernels' serialized bodies (they carry
    the checkout's path). A PR that means to change that step changes its
    pin; one that does not, must not."""
    text = lowered[2].as_text()
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
    text = re.sub(r"backend_config = \{[^\n]*", "", text)
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()[:16]) == (
        request.module.PIN)


GIB = 2**30
STEP_FITS_IN = 14.0 * GIB  # the compiler rematerialized by itself from
#                            14.54 GiB on (``tests/test_chip_qwen3-next-
#                            80b-a3b.py``): what every step stays under


def test_the_cells_step_keeps_the_products_the_rule_counted(
        request, compiled):
    """What ``models.lm``'s rule kept of the recomputed blocks' dense
    products is in the compiled step at the bytes it counted: the
    temporaries rise over ``NOTHING_KEPT`` (the same step compiled with no
    product kept) by no more than the kept kinds' bytes and a tenth; the
    rule's estimate of the step with nothing kept is no smaller than the
    compiler's count of that step; and arguments and temporaries together
    stay at or under 14.0 GiB with no rematerialization of the compiler's
    own. And one product where there were two: the step's products
    (``PRODUCTS``: its ``convolution``s, and those of the step with nothing
    kept) are fewer by what backward no longer makes again, and as many
    where the rule kept nothing."""
    rule, memory = compiled.rule, compiled.step.memory_analysis()
    made, made_again = request.module.PRODUCTS
    assert compiled.text.count(" convolution(") == made, rule["kept"]
    assert (made < made_again) if rule["kept"] else (made == made_again)
    assert rule["limit"] == V5E_BYTES and rule["kinds"]
    counted = sum(rule["kinds"][k][0] for k in rule["kept"])
    nothing = request.module.NOTHING_KEPT
    assert memory.temp_size_in_bytes - nothing <= 1.1 * counted, (
        memory, counted)
    assert 4 * rule["parameters"] + rule["beside"] >= (
        memory.argument_size_in_bytes + nothing), rule
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            <= STEP_FITS_IN), memory
    assert ".remat" not in compiled.text


def per_layer_of(spec, cell):
    """The names of the per-layer metrics ``BENCHMARK.json`` reads in a
    cell."""
    return {m["name"] for m in spec["per_layer"] if cell in m["workloads"]}


def benchmark_spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_configuration_is_a_cell_of_the_benchmark(request):
    """The configuration's one cell, and the per-layer metrics that came
    with it: those whose list of cells begins with it."""
    config = request.module.CONFIG
    spec = benchmark_spec()
    cell = next(c for c in spec["workloads"] if c["config"] == config)
    assert cell == {**cell, "name": cell_of(config), "traffic": "stream",
                    "chips": 1}
    assert len(cell["why"]) <= 200
    new = [m for m in spec["per_layer"] if m["workloads"][0] == cell["name"]]
    assert sorted(m["name"] for m in new) == request.module.OWN
    for m in new:
        assert (ROOT / "benchmark" / "layer_metrics"
                / f"{m['name']}.py").is_file()


def test_the_cells_step_fits_the_chip(request, compiled):
    """The step at the published widths fits: parameters and AdamW's
    moments are 12 B a parameter among its arguments, and arguments and
    temporaries together stay under ``FITS_IN``, inside the chip's
    15.75 GiB. Its kernels are ``KERNELS``, each as often and no other (a
    recomputed block keeps what its forward kernel made,
    ``ring_attention.SAVED``, and does not run it again), and those the
    benchmark's ``attn_kernel_ms_per_step`` reads among them are
    ``ATTENTION_KERNELS``. No array of any type has the sequence squared
    for its last two axes, and the text matches ``HOLDS`` and none of
    ``HOLDS_NO``."""
    from torchmpi_tpu.telemetry import names

    expects = request.module
    least, most = expects.PARAMETERS
    assert least <= compiled.parameters <= most
    memory = compiled.step.memory_analysis()
    assert memory.argument_size_in_bytes > 12 * compiled.parameters
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < expects.FITS_IN), memory
    kernels = kernel_calls(compiled.text)
    assert kernels == expects.KERNELS, kernels
    assert {k for k in kernels if k.startswith(names.ATTN_KERNEL_EVENT)} == (
        expects.ATTENTION_KERNELS)
    seq = compiled.cfg["sequence_length"]
    assert f"[{seq},{seq}]" not in compiled.text  # no t x t array
    for pattern in expects.HOLDS:
        assert re.search(pattern, compiled.text), pattern
    for pattern in expects.HOLDS_NO:
        assert not re.search(pattern, compiled.text), pattern
