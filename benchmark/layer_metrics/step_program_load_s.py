"""Start-up (jax's compile or cache load, seen from engine/sgd.py): the
seconds of every ``engine.program_build`` span, one per program jax
compiled or loaded from its cache, from the engine's construction to the
end of its first dispatch (stream: the first step's; resident: the first
epoch program's). Logs how many there were, how many came from the cache,
the part inside ``engine.init``, and the longest."""

import json

from benchmark import scopes


def read(run):
    records = scopes.program_spans()
    init = scopes.named(records, "engine.init")
    if not init:
        return None
    start = init[-1].start_ns
    first = min(
        (r.start_ns + r.dur_ns for r in records if r.start_ns >= start
         and r.name in ("engine.dispatch", "engine.epoch.dispatch")),
        default=None)
    if first is None:
        return None
    builds = [
        r for r in scopes.named(records, "engine.program_build")
        if start <= r.start_ns and r.start_ns + r.dur_ns <= first]
    if not builds:
        return 0.0
    longest = max(builds, key=lambda r: r.attrs["seconds"])
    scopes.log("programs built before the first step's end: " + json.dumps({
        "programs": len(builds),
        "from_cache": sum(bool(r.attrs["from_cache"]) for r in builds),
        "inside_engine_init_s": sum(
            r.attrs["seconds"] for r in builds if r.parent == init[-1].id),
        "longest": [longest.attrs["program"], longest.attrs["seconds"],
                    "cache" if longest.attrs["from_cache"] else "compiled"],
    }))
    return sum(r.attrs["seconds"] for r in builds)
