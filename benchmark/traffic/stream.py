"""``stream``: ``engine.train(iterator_fn, max_epochs=...)`` fed by
``data.InputPipeline``. The timed window is one epoch, so it holds no
epoch boundary."""

import statistics
import time
from pathlib import Path

import jax
import numpy as np

from benchmark import traffic
from benchmark.traffic import VirtualSource, start_trace


class Mode(traffic.Mode):
    def __init__(self, mix, cfg, built, chips, seed, ledger):
        super().__init__(mix, cfg, built, chips, seed, ledger)
        x, y = built.make_data(self.seed, mix["physical_samples"])
        self.x, self.y = x, y
        self.phase = None
        self.step = 0
        self.reads = []      # (step, blocked time, loss)
        self.dispatch = []   # seconds from on_sample to on_update
        self.kept = []       # delivered batches of the followed steps
        self.trace_dir = None
        self.tracing = False
        self.origins = {}    # trace name -> its origin, wall-clock ns
        self.stretch = None  # wall-clock seconds of the traced steps
        self._t_sample = 0.0

    # -- feed -----------------------------------------------------------
    def pipe(self, steps: int, salt: int):
        from torchmpi_tpu.data import InputPipeline

        dtype = self.built.input_dtype
        return InputPipeline(
            VirtualSource(self.x, self.y, steps * self.batch),
            batch_size=self.batch, num_ranks=self.chips,
            sharding=self.engine.batch_sharding, seed=self.seed31 + salt,
            transform=(
                None if dtype is None
                else (lambda xb, yb: (xb.astype(dtype), yb))
            ),
        )

    def feed(self, pipe):
        """``iterator_fn`` for ``engine.train``: the pipeline's epoch,
        with the wait for each batch and the epoch's boundary as spans."""
        spans = self.spans

        def epoch():
            it = iter(pipe())
            while True:
                # an epoch's first batch is waited for inside its boundary
                if not spans.is_open("bench.epoch_boundary.first_batch"):
                    spans.open("bench.input_wait")
                try:
                    batch = next(it)
                except StopIteration:
                    spans.close("bench.input_wait")
                    spans.open("bench.epoch_boundary.loss_read")
                    return
                spans.close("bench.input_wait")
                spans.close("bench.epoch_boundary.first_batch")
                if self.phase == "follow":
                    self.kept.append(batch)
                yield batch

        return epoch

    # -- hooks ----------------------------------------------------------
    def hooks(self) -> dict:
        return {
            "on_sample": self.on_sample,
            "on_update": self.on_update,
            "on_end_epoch": self.on_end_epoch,
        }

    def on_sample(self, state):
        mix = self.mix
        if self.phase == "trace_steady":
            if self.step == mix["traced_from_step"]:
                self.origins["steady"] = start_trace(self.trace_dir)
                self.tracing = True
                self.stretch = [time.time(), None]
            elif self.step == mix["traced_to_step"] and self.tracing:
                self.stretch[1] = time.time()
                jax.block_until_ready(self.engine.params)
                jax.profiler.stop_trace()
                self.tracing = False
        self._t_sample = time.perf_counter()
        self.spans.open("bench.dispatch")

    def on_update(self, state):
        self.spans.close("bench.dispatch")
        now = time.perf_counter()
        self.dispatch.append(now - self._t_sample)
        self.step += 1
        every = 1 if self.phase in ("follow", "warm") \
            else self.mix["loss_read_every"]
        if self.step % every == 0:
            loss = float(state["loss"])
            self.reads.append((self.step, time.perf_counter(), loss))
            if self.phase == "follow" and self.step == 1:
                self.first_step_s = time.perf_counter() - self._t_sample
                self.followed["moment_norms"] = self.moment_norms()

    def on_end_epoch(self, state):
        self.spans.close("bench.epoch_boundary.loss_read")
        self.spans.open("bench.epoch_boundary.first_batch")

    def run(self, phase, steps, salt, epochs=1):
        self.phase, self.step = phase, 0
        self.reads, self.dispatch = [], []
        state = self.engine.train(
            self.feed(self.pipe(steps, salt)), max_epochs=epochs)
        self.spans.close_all()
        return state

    # -- phases ---------------------------------------------------------
    def first_steps(self):
        """The followed steps, through the window's own call and feed."""
        n = self.mix["followed_steps"]
        self.run("follow", n, salt=1)
        self.followed["losses"] = [r[2] for r in self.reads]
        self.followed["update_norms"] = self.update_norms()
        self.followed["stat_norms"] = self.stat_norms()
        self.followed["batches"] = [
            tuple(np.asarray(a).reshape((-1,) + a.shape[2:]) for a in b)
            for b in self.kept
        ]
        self.moment_after = 1
        self.kept = []
        self.loss_at_seed = self.followed["losses"][0]

    def warm_up(self):
        self.run("warm", self.mix["warmup_steps"], salt=2)
        times = [r[1] for r in self.reads]
        deltas = [b - a for a, b in zip(times[1:], times[2:])]
        self.step_s = statistics.median(deltas)

    def window(self, seconds: float) -> dict:
        every = self.mix["loss_read_every"]
        steps = max(2 * every, int(seconds / self.step_s))
        before = self.ledger.programs
        state = self.run("window", steps, salt=3)
        reads = self.reads
        rate = state["samples"] / state["time"] / self.chips
        chunk = [
            (s1 - s0) * self.batch / (t1 - t0) / self.chips
            for (s0, t0, _), (s1, t1, _) in zip(reads, reads[1:])
        ]
        return {
            "steps": state["t"],
            "samples": state["samples"],
            "time": state["time"],
            "input_stall": state["input_stall"],
            "end_to_end": {"samples_per_s_per_chip": rate},
            "rate_median": statistics.median(chunk),
            "losses": [r[2] for r in reads] + [state["losses"][-1]],
            "dispatch": list(self.dispatch),
            "programs_in_window": self.ledger.programs - before,
        }

    def traced(self, trace_dir: Path) -> dict:
        """Two traces: a stretch of steps in the middle of an epoch, and a
        few short epochs for their boundaries."""
        mix = self.mix
        self.spans.done = []
        self.trace_dir = trace_dir / "steady"
        before = self.ledger.programs
        try:
            state = self.run(
                "trace_steady", mix["traced_epoch_steps"], salt=4)
        finally:
            if self.tracing:
                jax.profiler.stop_trace()
                self.tracing = False
        t0, t1 = self.stretch
        waited = sum(
            max(0.0, min(e, t1) - max(s, t0))
            for name, s, e in self.spans.done if name == "bench.input_wait")
        steady = {
            "steps": state["t"], "samples": state["samples"],
            # the traced stretch alone: the phase's own time also holds the
            # profiler's start and stop, seconds each
            "input_wait_share": 100.0 * waited / (t1 - t0),
            "dispatch": list(self.dispatch),
            "losses": [r[2] for r in self.reads] + [state["losses"][-1]],
        }
        self.origins["boundary"] = start_trace(trace_dir / "boundary")
        try:
            bstate = self.run(
                "trace_boundary", mix["boundary_epoch_steps"], salt=5,
                epochs=mix["boundary_epochs"])
        finally:
            jax.profiler.stop_trace()
        steady["steps"] += bstate["t"]
        steady["losses"] += bstate["losses"]
        steady["programs_in_window"] = self.ledger.programs - before
        steady["traces"] = {
            k: (trace_dir / k, self.origins[k]) for k in self.origins}
        steady["spans"] = list(self.spans.done)
        return steady

    def reference_losses(self, losses):
        return list(losses)
