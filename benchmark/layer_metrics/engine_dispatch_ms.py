"""Engine, host side (engine/sgd.py ``_dispatch``): the ``engine.dispatch``
span around the step's call alone, median over the steady trace's window.
The inside twin of ``dispatch_ms``, which also holds the hooks."""

from benchmark import scopes


def read(run):
    return scopes.median_ms(run, "steady", "engine.dispatch")
