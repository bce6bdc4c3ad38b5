"""Training throughput per chip: all the samples of the timed window over
all its time, by the engine's own counters (``state["samples"]``,
``state["time"]``, which ends in ``block_until_ready(params)``), over the
chips. The mode hands it back under ``end_to_end``."""


def read(run):
    return run["phase"].get("end_to_end", {}).get("samples_per_s_per_chip")
