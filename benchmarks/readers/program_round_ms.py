"""Device milliseconds one round of the named jitted program takes: the
device time of its events in the trace (averaged over the devices that
ran it side by side) over their number."""

from harness import xplane


def read(observed: dict, spec: dict):
    got = xplane.program_seconds(observed["records"], spec["program"])
    if not got["events"]:
        return None
    return got["seconds"] / got["devices"] / got["events"] * 1e3
