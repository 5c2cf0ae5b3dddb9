"""Share of the traced window in which no device ran anything while the
host was inside one of the program's spans ``spec["spans"]``: the idle
gaps of the window (where no device of the cell ran an operation, as
``xplane.reduce`` finds them) cut against the host events of those
names, over the window, in percent.  With ``"complement": true`` the
idle seconds that none of the names covers.  The shares are parts of
``device_idle_share``: they can add up to it on one chip and stay under
it on four, where it averages each chip's own idle time.  A program that
opens none of the spans gives nothing to read."""

from harness import xplane


def _overlap(intervals: list, cover: list) -> float:
    """Nanoseconds of the sorted, disjoint ``intervals`` that the sorted,
    disjoint ``cover`` covers (one sweep over both)."""
    total, j = 0.0, 0
    for a, b in intervals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            total += min(b, cover[k][1]) - max(a, cover[k][0])
            k += 1
    return total


def read(observed: dict, spec: dict):
    records = observed["records"]
    win = xplane.window_of(records)
    if win is None:
        return None
    lo, hi = win
    names = set(spec["spans"])
    spans = xplane.merge([
        [r["start_ns"], r["start_ns"] + r["dur_ns"]] for r in records
        if r["name"] in names and not xplane.DEVICE_PLANE.match(r["plane"])])
    ops = [r for dev in xplane.device_ops(records).values() for r in dev]
    if not spans or not ops or hi <= lo:
        return None
    busy = xplane.merge([[r["start_ns"], r["start_ns"] + r["dur_ns"]]
                         for r in ops])
    gaps, cursor = [], lo
    for a, b in busy + [[hi, hi]]:
        if min(a, hi) > cursor:
            gaps.append([cursor, min(a, hi)])
        cursor = max(cursor, b)
    idle = sum(b - a for a, b in gaps)
    covered = _overlap(gaps, spans)
    value = idle - covered if spec.get("complement") else covered
    return 100.0 * value / (hi - lo)
