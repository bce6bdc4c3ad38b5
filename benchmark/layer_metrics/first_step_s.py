"""Start-up (runtime_state.start, utils/compile_cache.py): the host's clock
around the first dispatch of the engine's entry, blocked: trace, compile or
cache load, and one execution (stream: one step; resident: one epoch)."""


def read(run):
    return run["first_step_s"]
