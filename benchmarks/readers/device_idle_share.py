"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy being the union of the device's operation
intervals, averaged over the devices used."""


def read(observed: dict, spec: dict):
    trace = observed["trace"]
    if trace["window_s"] <= 0 or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
