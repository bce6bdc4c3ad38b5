"""Device against host: the first chip's idle gaps in the steady trace,
each given (``xplane.attribute``) to the program's span the host was in
for most of it, a span counting only where none of its children is open;
the share of the idle time that some span explains. Logs the idle seconds
by span name."""

import json

from benchmark import scopes, xplane


def read(run):
    records = scopes.program_spans()
    if not records:
        return None
    path, origin = run["phase"]["traces"]["steady"]
    trace = scopes.by_scope(str(path))
    if trace is None:
        return None
    window, busy = trace["window"], trace["busy"]
    idle = xplane.gaps(busy)
    total = xplane.length(idle)
    if not total:
        return None
    by_name, _ = xplane.attribute(
        idle, scopes.self_time(
            scopes.overlapping(records, origin, window), origin))
    scopes.log("idle seconds of the steady trace by the program's span: "
               + json.dumps(dict(sorted(
                   by_name.items(), key=lambda kv: -kv[1]))))
    return 100.0 * (1.0 - by_name.get("none", 0.0) / total)
