"""Input at an epoch's start (data/__init__.py ``InputPipeline._run_epoch``):
the ``input.epoch_start`` span, from the epoch's call to its first batch
handed out (new producer threads, the empty ring, the double buffer);
median over the epochs that start inside the boundary trace's window."""

from benchmark import scopes


def read(run):
    return scopes.median_ms(run, "boundary", "input.epoch_start")
