"""Start-up (engine/sgd.py ``__init__``): the host's time in the newest
``engine.init`` span, the engine's construction: its own copies of the
caller's parameters, model state and optimizer state."""

from benchmark import scopes


def read(run):
    found = scopes.named(scopes.program_spans(), "engine.init")
    return found[-1].dur_ns * 1e-9 if found else None
