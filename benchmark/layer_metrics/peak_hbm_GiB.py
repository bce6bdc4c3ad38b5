"""Device: ``memory_stats()["peak_bytes_in_use"]`` on the fullest chip, read
after the traced phases and before the reference runs."""


def read(run):
    peak = run["memory_peak_bytes"]
    return None if peak is None else peak / 2**30
