"""``resident``: ``engine.train_resident``, the dataset staged in HBM and
one ``lax.scan`` per epoch. The timed window is whole epochs, every one
counted."""

import statistics
from pathlib import Path

import jax
import numpy as np

from benchmark import traffic
from benchmark.traffic import start_trace


class Mode(traffic.Mode):
    def __init__(self, mix, cfg, built, chips, seed, ledger):
        super().__init__(mix, cfg, built, chips, seed, ledger)
        x, y = built.make_data(self.seed, mix["dataset_samples"])
        if built.input_dtype is not None:
            # cast once here, as train_resident's image_dtype would at
            # every call's staging
            x = x.astype(built.input_dtype)
        self.x, self.y = x, y
        self.steps_per_epoch = len(x) // chips // self.per_chip

    def hooks(self) -> dict:
        return {
            "on_start_epoch": self.on_start_epoch,
            "on_end_epoch": self.on_end_epoch,
        }

    def on_start_epoch(self, state):
        self.spans.close("bench.epoch_boundary")
        self.spans.open("bench.epoch")

    def on_end_epoch(self, state):
        self.spans.close("bench.epoch")
        self.spans.open("bench.epoch_boundary")

    def run(self, epochs: int):
        state = self.engine.train_resident(
            self.x, self.y, self.per_chip, max_epochs=epochs,
            shuffle=self.mix["shuffle"], seed=self.seed31,
        )
        self.spans.close_all()
        return state

    def first_steps(self):
        """The first epoch: the unit that this entry dispatches. Its steps
        cannot be told apart from outside, so the reference follows all of
        them and the buffers are compared at the epoch's end."""
        if self.mix["shuffle"]:
            raise ValueError("the followed epoch needs a known row order")
        state = self.run(1)
        self.first_step_s = state["epoch_times"][0]
        self.followed["losses"] = [state["losses"][0], state["loss"]]
        self.followed["moment_norms"] = self.moment_norms()
        self.followed["update_norms"] = self.update_norms()
        self.followed["stat_norms"] = self.stat_norms()
        p, b = self.chips, self.per_chip
        shard = len(self.x) // p
        rows = [
            np.concatenate([
                np.arange(r * shard + i * b, r * shard + (i + 1) * b)
                for r in range(p)
            ])
            for i in range(self.steps_per_epoch)
        ]
        self.followed["batches"] = [(self.x[i], self.y[i]) for i in rows]
        self.moment_after = self.steps_per_epoch
        # the epoch's mean: what this entry shows of where the loss began
        self.loss_at_seed = state["losses"][0]

    def warm_up(self):
        state = self.run(self.mix["warmup_epochs"])
        self.epoch_s = statistics.median(state["epoch_times"])

    def window(self, seconds: float) -> dict:
        epochs = max(2, int(seconds / self.epoch_s))
        before = self.ledger.programs
        state = self.run(epochs)
        per_epoch = self.steps_per_epoch * self.batch
        rate = state["samples"] / state["time"] / self.chips
        times = state["epoch_times"]
        return {
            "steps": state["t"],
            "samples": state["samples"],
            "time": state["time"],
            "end_to_end": {"samples_per_s_per_chip": rate},
            "rate_median": statistics.median(
                per_epoch / t / self.chips for t in times),
            "losses": list(state["losses"]),
            "programs_in_window": self.ledger.programs - before,
            # for the log: where a window's slow epochs lie
            "slowest": sorted(
                (round(t / self.epoch_s, 3), i) for i, t in enumerate(times)
            )[-3:],
        }

    def traced(self, trace_dir: Path) -> dict:
        self.spans.done = []
        before = self.ledger.programs
        origin = start_trace(trace_dir / "steady")
        try:
            state = self.run(self.mix["traced_epochs"])
        finally:
            jax.profiler.stop_trace()
        return {
            "steps": state["t"], "traced_steps": state["t"],
            "samples": state["samples"],
            "time": state["time"], "losses": list(state["losses"]),
            "programs_in_window": self.ledger.programs - before,
            # one trace serves both: its epochs are its boundaries
            "traces": {"steady": (trace_dir / "steady", origin),
                       "boundary": (trace_dir / "steady", origin)},
            "spans": list(self.spans.done),
        }

    def reference_losses(self, losses):
        return [float(np.mean(losses)), losses[-1]]
