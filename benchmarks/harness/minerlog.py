"""The miner CLI's lines, parsed, and the jobs and rounds they add up to.

The lines are ``mine/miner.py``'s own (``run``, ``progress``): one per
job fetched, one per completed round, one when 2^32 nonces are spent
(``template expired``) or a nonce is found.  The parent stamps each with
its clock as it arrives; the child runs unbuffered.  A ``memory`` event
has ``peak`` (digits or ``null``) or, where the launcher could not read
the device, ``reason``; the other of the two is None.
"""

from __future__ import annotations

import json
import re

_PATTERNS = [
    ("start", re.compile(r"^upow_tpu miner: backend=(?P<backend>\S+) "
                         r"shard=(?P<shard>\S+) nonces=\[(?P<lo>\d+), "
                         r"(?P<hi>\d+)\)")),
    ("device", re.compile(r"^device: platform=(?P<platform>\S+) "
                          r"kind=(?P<device_kind>.*) count=(?P<count>\d+) "
                          r"compile_cache=(?P<cache>.*)$")),
    ("job", re.compile(r"^difficulty: (?P<difficulty>[\d.]+)  block: "
                       r"(?P<block>\d+)  confirming (?P<txs>\d+) "
                       r"transactions")),
    ("round", re.compile(r"^(?P<mhs>[\d.]+) MH/s \((?P<tried>\d+) "
                         r"hashes\)$")),
    ("expired", re.compile(r"^template expired after (?P<tried>\d+) "
                           r"hashes; refreshing")),
    ("found", re.compile(r"^found nonce (?P<nonce>\d+) at (?P<mhs>[\d.]+) "
                         r"MH/s \((?P<tried>\d+) hashes in "
                         r"(?P<seconds>[\d.]+)s, first dispatch "
                         r"(?P<first>[\d.]+)s\)")),
    ("mined", re.compile(r"^BLOCK MINED")),
    ("memory", re.compile(r"^memory: (?:peak_bytes=(?P<peak>\d+|null)"
                          r"|unreadable \((?P<reason>.*)\))")),
    ("trace", re.compile(r"^trace: (?P<what>started|stopped) "
                         r"unix=(?P<unix>[\d.]+)")),
    ("error", re.compile(r"^(node unreachable|push_block failed|no mining "
                         r"progress|Traceback|upow_tpu miner: )")),
]
#: what starts a line of the launcher's own threads (``launch/
#: miner_child.py``): written whole, but it can land between a line of
#: the miner's thread and that line's end
_LAUNCHER_LINE = re.compile(r"(?:trace|memory): ")
_INT = ("lo", "hi", "count", "block", "txs", "tried", "nonce")
_FLOAT = ("mhs", "seconds", "first", "unix", "difficulty")


def parse_line(text: str):
    """{"kind": ..., fields} for a line this benchmark reads, else None."""
    if text.startswith("mesh: "):
        try:
            return {"kind": "mesh", "mesh": json.loads(text[6:])}
        except ValueError:
            return None
    for kind, pattern in _PATTERNS:
        m = pattern.match(text)
        if m:
            rec = {"kind": kind}
            for key, value in m.groupdict().items():
                if key in _INT:
                    value = int(value)
                elif key in _FLOAT:
                    value = float(value)
                rec[key] = value
            return rec
    return None


def parse(lines: list) -> list:
    """[(unix seconds, text)] -> [{"t", "kind", ...}], unread lines dropped."""
    out = []
    for t, text in lines:
        cut = _LAUNCHER_LINE.search(text, 1)
        for part in ([text] if cut is None else
                     [text[:cut.start()], text[cut.start():]]):
            rec = parse_line(part)
            if rec is not None:
                rec["t"] = t
                out.append(rec)
    return out


def jobs(events: list) -> list:
    """One record per job the miner fetched: when it started, each round
    as (arrival time, nonces of that round), and how it ended."""
    out, job = [], None
    for ev in events:
        if ev["kind"] == "job":
            job = {"start_t": ev["t"], "difficulty": ev["difficulty"],
                   "block": ev["block"], "rounds": [], "tried": 0,
                   "end": None, "end_t": None}
            out.append(job)
        elif job is None:
            continue
        elif ev["kind"] == "round":
            job["rounds"].append((ev["t"], ev["tried"] - job["tried"]))
            job["tried"] = ev["tried"]
        elif ev["kind"] == "expired":
            job.update(end="expired", end_t=ev["t"], reported=ev["tried"])
        elif ev["kind"] == "found":
            # the round that held the hit prints no progress line
            job["rounds"].append((ev["t"], ev["tried"] - job["tried"]))
            job.update(end="found", end_t=ev["t"], reported=ev["tried"],
                       tried=ev["tried"], nonce=ev["nonce"],
                       first_dispatch_s=ev["first"])
    return out


def nonces_between(job_list: list, t0: float, t1: float) -> int:
    """Nonces of the rounds that completed in (t0, t1]."""
    return sum(n for job in job_list for t, n in job["rounds"]
               if t0 < t <= t1)


def swaps(job_list: list, t0: float, t1: float) -> list:
    """Seconds from the last completed round of job N to the first
    completed round of job N+1, for swaps that end inside (t0, t1]: the
    fetch, the new template and, where the target is a static argument
    and changed, its compile."""
    out = []
    for prev, nxt in zip(job_list, job_list[1:]):
        if prev["rounds"] and nxt["rounds"]:
            end = nxt["rounds"][0][0]
            if t0 < end <= t1:
                out.append(end - prev["rounds"][-1][0])
    return out


def sweep_seconds(job: dict):
    """First to last completed round of one job (None under 2 rounds)."""
    if len(job["rounds"]) < 2:
        return None
    return job["rounds"][-1][0] - job["rounds"][0][0]
