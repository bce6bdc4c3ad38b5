"""The engine's step on the device (XLA's convolutions, matmuls and
attention): the union of the intervals in which an operation ran, over the
optimizer steps in the steady trace, mean over the chips."""


def read(run):
    trace = run["steady"]
    if not trace or not trace.get("devices"):
        return None
    steps = run["phase"].get("traced_steps") or trace["steps"]
    if not steps:
        return None
    return 1e3 * trace["busy_s"] / steps
