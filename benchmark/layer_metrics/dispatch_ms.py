"""Engine, host side (engine/sgd.py ``train``): the host's time from the
``on_sample`` hook to the ``on_update`` hook, median over the traced
phases' steps. An entry that fires no per-step hooks has nothing to read."""

import statistics


def read(run):
    samples = run["phase"].get("dispatch")
    if not samples:
        return None
    return 1e3 * statistics.median(samples)
