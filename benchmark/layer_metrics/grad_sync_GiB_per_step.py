"""Gradient sync, its size (nn/ ``_note_sync``): the program's gauge
``tm_engine_sync_bytes_per_step``, the bytes each rank hands to the
in-graph sync per step, worked out from static shapes when the step is
traced."""

from benchmark import scopes


def read(run):
    value = scopes.counter("tm_engine_sync_bytes_per_step")
    return None if value is None else value / 2**30
