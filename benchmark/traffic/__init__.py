"""The one generator of every traffic mix.

A mix is a data file, ``traffic/<name>.json``. Its ``mode`` names the way
of driving the engine, which is ``traffic/<mode>.py``, found by that name
(``stream``: ``engine.train`` fed by ``data.InputPipeline``; ``resident``:
``engine.train_resident``); its ``engine`` holds keyword arguments for
``AllReduceSGDEngine`` (a parallel layout, a checkpoint interval), laid
over the configuration's own ``engine`` group; the rest are the mode's
parameters. A later PR adds a mix by adding a data file, and a new way of
driving the engine by adding ``traffic/<mode>.py`` beside it.

Each mode is one object that holds the engine and drives it through the
same entry in every phase: the followed first steps (what ``correct``
compares), the warm-up that sizes the window, the timed window, and the
traced run. So what is checked is what is timed. A window hands back what
it measured under ``end_to_end``, by name: ``end_to_end/<metric>.py`` reads
it from there.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str, rehearse: bool = False) -> dict:
    mix = json.loads((HERE / f"{name}.json").read_text())
    if rehearse:
        mix.update(mix.get("rehearsal", {}))
    return mix


def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree
    )


class Spans:
    """Host spans by the wall clock, ``time.time_ns()``: the clock the
    profiler stamps its events with, so a span can be laid over a trace
    (``xplane.reduce``) without the profiler's host tracer, which on this
    runtime writes a million events for every batch copied to the chip and
    slows the copy it traces. Spans open and close on the training thread,
    one after another, never nested."""

    def __init__(self):
        self.done = []  # (name, start, end), seconds of the wall clock
        self._open = {}

    def open(self, name: str) -> None:
        self._open[name] = time.time_ns()

    def close(self, name: str):
        t0 = self._open.pop(name, None)
        if t0 is None:
            return None
        t1 = time.time_ns()
        self.done.append((name, t0 * 1e-9, t1 * 1e-9))
        return (t1 - t0) * 1e-9

    def is_open(self, name: str) -> bool:
        return name in self._open

    def close_all(self) -> None:
        for name in list(self._open):
            self.close(name)


class VirtualSource:
    """A long index range over a physical array of a few thousand samples:
    ``InputPipeline`` takes any object with ``__len__`` and ``gather``. The
    host work per batch (a fancy-index gather, then the pipeline's
    transform) is that of a corpus of the virtual length; host memory and
    set-up are those of the physical one."""

    def __init__(self, x, y, length: int):
        self.x, self.y, self.length = x, y, int(length)

    def __len__(self) -> int:
        return self.length

    def gather(self, idx):
        idx = np.asarray(idx) % len(self.x)
        return (np.ascontiguousarray(self.x[idx]),
                np.ascontiguousarray(self.y[idx]))


class Mode:
    """What every mode shares: the engine, its state at the seed, and the
    norms that ``correct`` compares. ``traffic/<mode>.py`` exports its
    subclass under the name ``Mode``."""

    def __init__(self, mix, cfg, built, chips, seed, ledger):
        from torchmpi_tpu.engine import AllReduceSGDEngine

        self.mix, self.cfg, self.built = mix, cfg, built
        self.chips, self.seed, self.ledger = chips, int(seed), ledger
        self.seed31 = self.seed % (2**31 - 64)
        self.per_chip = cfg["per_chip_batch"]
        self.batch = self.per_chip * chips
        self.spans = Spans()
        self.followed = {}
        self.first_step_s = None
        params, model_state = built.make_state(self.seed)
        self.engine = AllReduceSGDEngine(
            built.loss_fn, params, optimizer=built.optimizer,
            model_state=model_state, hooks=self.hooks(),
            **{**cfg.get("engine", {}), **mix.get("engine", {})},
        )
        del params, model_state
        self._norms = jax.jit(leaf_norms)
        # the seeded parameters are made again inside this program, leaf by
        # leaf, so that no second copy of them ever lies on the device
        self._change = jax.jit(lambda now, key, which: leaf_norms(
            jax.tree_util.tree_map(
                jnp.subtract, now, built.state_at(key)[which])),
            static_argnums=2)

    def hooks(self) -> dict:
        raise NotImplementedError

    def moment_norms(self):
        return jax.device_get(
            self._norms(self.built.first_moment(self.engine.opt_state)))

    def update_norms(self):
        """The norm of each parameter's change since the seed."""
        from benchmark import weights

        return jax.device_get(self._change(
            self.engine.params, weights.seed_key(self.seed), 0))

    def stat_norms(self):
        """The norm of the change of each running statistic the model
        keeps (batch norm's averages) since the seed; None without any."""
        from benchmark import weights

        if self.engine.model_state is None:
            return None
        return jax.device_get(self._change(
            self.engine.model_state, weights.seed_key(self.seed), 1))

    def release(self) -> None:
        """Free the program's state on the device (the reference runs
        after this, in the memory it leaves)."""
        eng = self.engine
        eng.params = eng.opt_state = eng.model_state = None
        self.engine = None


def start_trace(path: Path) -> int:
    """Open a profiler trace of the device alone and return its origin:
    every event's time in the trace is counted from it, in nanoseconds of
    the wall clock."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    options.start_timestamp_ns = origin = time.time_ns()
    jax.profiler.start_trace(str(path), profiler_options=options)
    return origin


def make(name: str, cfg, built, chips, seed, ledger,
         rehearse: bool = False) -> Mode:
    from benchmark import configs

    mix = load(name, rehearse)
    code = HERE / f"{mix['mode']}.py"
    if not code.is_file():
        raise ValueError(
            f"traffic {name!r}: no mode {mix['mode']!r} (no {code.name} "
            f"under {HERE.name}/)")
    return configs.load_module(code).Mode(
        mix, cfg, built, chips, seed, ledger)
