"""Seconds a job swap takes, from the parent's stamps on the miner's
lines: last completed round of job N to first completed round of job
N+1 (the fetch, the new template and, where the target is a static
argument and the tip changed, the compile).  ``stat``: mean, median or
max over the swaps that ended inside the window."""

import statistics

from harness import minerlog


def read(observed: dict, spec: dict):
    w0, w1 = observed["window"]
    swaps = minerlog.swaps(observed["jobs"], w0, w1)
    if not swaps:
        return None
    stat = spec.get("stat", "mean")
    if stat == "median":
        return statistics.median(swaps)
    if stat == "max":
        return max(swaps)
    return sum(swaps) / len(swaps)
