"""Share of the chip's published memory bandwidth that a jitted program
reached on the bytes its algorithm has to move: ``100 x
observed["values"][key]`` (bytes, counted by ``harness/indexwork.py``
from the blocks' shapes) over the program's device seconds in the trace
times ``peaks(device_kind)["hbm_bytes_per_s"]``.  A share over 105 % is
the run's failure: the bytes are counted too high or the time leaves out
part of the work.  A driver that observed no such value, or a trace
without the program, gives nothing to read."""

from harness import manifest, xplane
from harness.manifest import BenchError


def read(observed: dict, spec: dict):
    value = observed.get("values", {}).get(spec["key"])
    kind = observed.get("device_kind")
    if value is None or kind is None:
        return None
    got = xplane.program_seconds(observed["records"], spec["program"])
    if not got["events"] or got["seconds"] <= 0 or value <= 0:
        return None
    peak = manifest.peaks(kind)["hbm_bytes_per_s"]
    share = 100.0 * value / (got["seconds"] * peak)
    if share > 105.0:
        raise BenchError(
            f"{spec['key']}: {value:.0f} bytes in {got['seconds']:.6f} "
            f"device seconds of {spec['program']!r} is {share:.1f}% of "
            f"{peak:.3g} B/s: over 105%, the count or the time is wrong")
    return share
