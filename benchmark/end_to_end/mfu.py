"""The share of the chip's bfloat16 peak that the operations forward and
backward need reach: the window's rate times the benchmark's own count of
operations per sample (``flops.py``; recomputed and masked-out operations
are not counted) over the peak of this exact ``device_kind``."""


def read(run):
    rate = run["phase"].get("end_to_end", {}).get("samples_per_s_per_chip")
    if rate is None or run["peak_flops"] is None:
        return None
    return 100.0 * rate * run["flops_per_sample"] / run["peak_flops"]
