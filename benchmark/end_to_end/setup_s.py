"""Process start to the timed window: imports, seeded data and weights,
compile or cache load, the followed first steps, warm-up."""


def read(run):
    return run["setup_s"]
