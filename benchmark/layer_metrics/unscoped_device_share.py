"""Device: the busy time of the steady trace under no ``tm.`` scope, as a
share of the busy union, mean over the chips. What the scopes do not
explain: more than a few percent means a scope is missing. Logs the
milliseconds a step spends under each scope."""

import json

from benchmark import scopes


def read(run):
    path, _ = run["phase"]["traces"]["steady"]
    scoped = scopes.by_scope(str(path))
    if scoped is None or not scoped["scope_s"]:
        return None
    steps = run["phase"].get("traced_steps") or scoped["steps"] or 1
    scopes.log("device ms per step by scope: " + json.dumps({
        k: round(1e3 * t / steps, 3) for k, t in sorted(
            {**scoped["scope_s"], "none": scoped["unscoped_s"],
             "busy": scoped["busy_s"]}.items())}))
    scopes.log("seconds under no scope, the first five operations "
               "[event, its op_name, seconds]: " + json.dumps(
                   [[k[:80], op, t] for (k, op), t in scoped["unscoped_ops"]]))
    return 100.0 * scoped["unscoped_s"] / scoped["busy_s"]
