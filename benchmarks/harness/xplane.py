"""From a profiler trace (``.xplane.pb``) to busy and idle seconds,
per-program device time and the breakdown.

``extract`` flattens ``jax.profiler.ProfileData`` into plain records
{plane, line, name, start_ns, dur_ns}; everything else works on those,
so the tests feed it records made by hand.  Times in a trace count
nanoseconds from the start of the trace.

What is read (TPU planes as the profiler of jax 0.9 writes them):

* a device is a plane named ``/device:TPU:<n>``;
* an operation that ran on it is an event of that plane's ``XLA Ops``
  line; a whole jitted program is an event of its ``XLA Modules`` line,
  named after the jitted function;
* the traced window is the host span ``perfbench.window`` that the
  child opens after ``start_trace`` and closes before ``stop_trace``.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "perfbench.window"


def find_trace(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return found[-1] if found else None


def extract(path: str) -> list:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def summary(records: list, top: int = 6) -> list:
    """One text line per (plane, line): events, seconds, commonest names
    — for looking at a trace by hand."""
    groups: dict = {}
    for r in records:
        g = groups.setdefault((r["plane"], r["line"]),
                              {"n": 0, "s": 0.0, "names": {}})
        g["n"] += 1
        g["s"] += r["dur_ns"] / 1e9
        key = r["name"][:60]
        g["names"][key] = g["names"].get(key, 0.0) + r["dur_ns"] / 1e9
    out = []
    for (plane, line), g in sorted(groups.items()):
        names = sorted(g["names"].items(), key=lambda kv: -kv[1])[:top]
        out.append(f"{plane} | {line}: {g['n']} events, {g['s']:.4f}s; "
                   + ", ".join(f"{n}={s:.4f}s" for n, s in names))
    return out


def merge(intervals: list) -> list:
    """Sorted, non-overlapping [start, end] from any intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def window_of(records: list):
    """(start_ns, end_ns) of the traced window: the child's host span,
    else the extent of all events."""
    for r in records:
        if r["name"] == WINDOW_SPAN:
            return r["start_ns"], r["start_ns"] + r["dur_ns"]
    if not records:
        return None
    return (min(r["start_ns"] for r in records),
            max(r["start_ns"] + r["dur_ns"] for r in records))


def device_ops(records: list) -> dict:
    """{device index: [record...]} of operations that ran on a device:
    the ``XLA Ops`` line where the plane has one, else every line of
    the plane but whole-program and step lines."""
    by_dev: dict = {}
    for r in records:
        m = DEVICE_PLANE.match(r["plane"])
        if m:
            by_dev.setdefault(int(m.group(1)), []).append(r)
    out = {}
    for dev, recs in by_dev.items():
        ops = [r for r in recs if r["line"] == OPS_LINE]
        if not ops:
            ops = [r for r in recs
                   if r["line"] not in (MODULES_LINE, "Steps")]
        out[dev] = ops
    return out


def reduce(records: list, phases=None, started_unix=None) -> dict:
    """busy_s and window_s (busy averaged over the devices seen), the
    idle gaps of the busiest device's complement, and the top device
    operations.  ``phases``: [(unix0, unix1, name)] from the parent's
    clock, placed on the trace's by ``started_unix`` (the unix time at
    which the window span opened); a gap is named by the phase its
    middle falls in."""
    win = window_of(records)
    if win is None:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                "device_ops": [], "idle_gaps": [], "per_device_busy_s": {}}
    lo, hi = win
    per_dev, op_seconds, all_busy = {}, {}, []
    for dev, ops in device_ops(records).items():
        merged = _clip(merge([[r["start_ns"], r["start_ns"] + r["dur_ns"]]
                              for r in ops]), lo, hi)
        per_dev[dev] = sum(b - a for a, b in merged) / 1e9
        all_busy.append(merged)
        for r in ops:
            a, b = max(r["start_ns"], lo), min(r["start_ns"] + r["dur_ns"],
                                               hi)
            if b > a:
                name = r["name"].split(" = ")[0][:80]   # not the HLO text
                op_seconds[name] = op_seconds.get(name, 0.0) \
                    + (b - a) / 1e9
    n = len(per_dev)
    busy = sum(per_dev.values()) / n if n else 0.0
    # gaps: where NO device ran anything
    any_busy = merge([iv for merged in all_busy for iv in merged])
    gaps, cursor = [], lo
    for a, b in any_busy + [[hi, hi]]:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)

    def phase_at(ns: float) -> str:
        if not phases or started_unix is None:
            return "unnamed"
        unix = started_unix + (ns - lo) / 1e9
        for p0, p1, name in phases:
            if p0 <= unix < p1:
                return name
        return "other"

    by_name: dict = {}
    for a, b in gaps:
        name = phase_at((a + b) / 2)
        by_name[name] = by_name.get(name, 0.0) + (b - a) / 1e9
    top_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy, "window_s": (hi - lo) / 1e9, "devices": n,
            "per_device_busy_s": per_dev,
            "device_ops": [[k, v / max(n, 1)] for k, v in top_ops],
            "idle_gaps": [[k, v] for k, v in top_gaps],
            "longest_gap_s": max((b - a for a, b in gaps), default=0.0)
            / 1e9}


def program_seconds(records: list, pattern: str) -> dict:
    """Device time of one jitted program: the events of the ``XLA
    Modules`` lines whose name holds ``pattern`` and that lie wholly
    inside the window.  {"events" (on the device that ran most),
    "seconds" (summed over devices; per device the events' union, so a
    program is counted once however the line nests), "devices",
    "ended" (events that ended inside the window, begun there or not,
    on the device that ran most: the rounds whose answers the host got
    in the window)}."""
    none = {"events": 0, "seconds": 0.0, "devices": 0, "ended": 0}
    win = window_of(records)
    if win is None:
        return none
    lo, hi = win
    per_dev: dict = {}
    for r in records:
        m = DEVICE_PLANE.match(r["plane"])
        if m and r["line"] == MODULES_LINE and pattern in r["name"]:
            per_dev.setdefault(int(m.group(1)), []).append(
                (r["start_ns"], r["start_ns"] + r["dur_ns"]))
    if not per_dev:
        return none
    inside = {dev: [(a, b) for a, b in evs if a >= lo and b <= hi]
              for dev, evs in per_dev.items()}
    seconds = sum(b - a for evs in inside.values()
                  for a, b in merge([[a, b] for a, b in evs])) / 1e9
    return {"events": max(len(evs) for evs in inside.values()),
            "seconds": seconds, "devices": len(per_dev),
            "ended": max(sum(1 for _a, b in evs if lo < b <= hi)
                         for evs in per_dev.values())}
