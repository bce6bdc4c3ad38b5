"""Input, seen from the engine (engine/sgd.py ``train``): the share of the
steady trace's window that lies inside ``engine.input_wait`` spans, the
engine's own around ``next()`` on its iterator. The inside twin of
``input_wait_share``."""

from benchmark import scopes


def read(run):
    found = scopes.traced_spans(run, "steady", "engine.input_wait")
    if not found or not found[0]:
        return None
    spans, origin, (lo, hi) = found
    waited = sum(
        max(0.0, min(e, hi) - max(s, lo))
        for _, s, e in scopes.on_trace_clock(spans, origin))
    return 100.0 * waited / (hi - lo)
