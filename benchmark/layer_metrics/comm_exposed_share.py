"""In-graph gradient sync (nn/: psum): the time in collective operations
during which no other operation ran on that chip, as a share of the steady
trace's window, mean over the chips. Zero on one chip, which runs none."""


def read(run):
    trace = run["steady"]
    if not trace or not trace.get("devices"):
        return None
    return 100.0 * trace["exposed_collective_s"] / trace["window_s"]
