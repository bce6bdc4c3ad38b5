"""Engine and input at an epoch's end (engine/sgd.py ``train`` /
``train_resident``, InputPipeline's restart): the chip's idle time in the
gaps that each boundary of the traced short epochs touches, median over
the boundaries."""

import statistics


def read(run):
    trace = run["boundary"]
    if not trace or not trace.get("boundary_idle_s"):
        return None
    return 1e3 * statistics.median(trace["boundary_idle_s"])
