"""One statistic of the program's own span ``spec["span"]``: the host
events of that name (the program writes each ``telemetry`` span into the
profiler's trace, on the device events' clock) that lie wholly inside
the traced window.  ``stat``: mean, median, max, sum or count, times
``scale`` (a trace counts nanoseconds: 1e-9 for s, 1e-6 for ms).  A
program that opens no such span gives nothing to read."""

import statistics

from harness import xplane

STATS = {"mean": statistics.fmean, "median": statistics.median,
         "max": max, "sum": sum, "count": len}


def read(observed: dict, spec: dict):
    records = observed["records"]
    win = xplane.window_of(records)
    if win is None:
        return None
    lo, hi = win
    durations = [r["dur_ns"] for r in records
                 if r["name"] == spec["span"]
                 and not xplane.DEVICE_PLANE.match(r["plane"])
                 and r["start_ns"] >= lo
                 and r["start_ns"] + r["dur_ns"] <= hi]
    if not durations:
        return None
    value = STATS[spec["stat"]](durations)
    return value if spec["stat"] == "count" else value * spec["scale"]
