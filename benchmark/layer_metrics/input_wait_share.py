"""Input (data/InputPipeline and the copy to the device it starts): the
share of the traced stretch of steps that ``engine.train`` spent inside
``next()`` on its iterator, by the benchmark's span around that call (the
quantity the engine sums into ``state["input_stall"]``; the engine's sum
over the phase would also hold the profiler's start and stop). An entry
that takes no iterator has nothing to read."""


def read(run):
    return run["phase"].get("input_wait_share")
