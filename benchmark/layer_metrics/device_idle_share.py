"""Device: one minus the busy union over the steady trace's window (first
operation's start to last operation's end), mean over the chips."""


def read(run):
    trace = run["steady"]
    if not trace or not trace.get("devices"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
