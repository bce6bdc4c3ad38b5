"""Engine at an epoch's end (engine/sgd.py ``train``: the loss read and
``on_end_epoch``; ``train_resident``: the losses' ``device_get`` and the
callbacks): the ``engine.epoch_end`` span, median over the boundary
trace's window."""

from benchmark import scopes


def read(run):
    return scopes.median_ms(run, "boundary", "engine.epoch_end")
