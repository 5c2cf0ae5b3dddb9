"""A value the driver itself observed (``observed["values"][key]``: an
event of the set-up, a difference of two scrapes, a count of pushes),
times ``scale``.  With ``program`` it is set against the device seconds
of that jitted program in the trace: ``"per": "device_second"`` gives
the value a device second (a rate of work), ``"per": "value"`` the
device seconds a unit of the value (the program's time a block).  A
driver that observed no such value, or a trace without the program,
gives nothing to read."""

from harness import xplane


def read(observed: dict, spec: dict):
    value = observed.get("values", {}).get(spec["key"])
    if value is None:
        return None
    if "program" in spec:
        got = xplane.program_seconds(observed["records"], spec["program"])
        if not got["events"] or got["seconds"] <= 0 or value <= 0:
            return None
        value = value / got["seconds"] if spec["per"] == "device_second" \
            else got["seconds"] / value
    return value * spec.get("scale", 1.0)
