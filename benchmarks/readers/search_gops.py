"""Achieved 32-bit integer operations per second of the search program
while it runs: the benchmark's own operation count for the nonces of
the traced rounds (``harness/opcount.py``) over the program's device
time, all devices together.  An achieved rate; no peak is divided by."""

from harness import opcount, xplane


def read(observed: dict, spec: dict):
    got = xplane.program_seconds(observed["records"], spec["program"])
    if not got["events"] or got["seconds"] <= 0:
        return None
    nonces = got["events"] * observed["round_nonces"]
    wall = got["seconds"] / got["devices"]
    return opcount.search_ops(nonces) / wall / 1e9
