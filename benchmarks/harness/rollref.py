"""The plain reference for the header's timestamp: the node's rule, and
what counts as hashing a header twice.  Nothing of the program.

A block is valid with any timestamp later than the previous block's and
no later than the node's clock (upstream manager.py ``check_block_is_valid``:
``timestamp younger than previous block`` / ``timestamp in the future``).
Inside one tip every second of that window is a header of its own: 2^32
fresh candidates.  A miner that builds two jobs with the same previous
hash, merkle root, address, difficulty and timestamp over the same nonce
range hashes the same candidates twice.
"""

from __future__ import annotations

from . import powref

#: what makes two jobs the same work
IDENTITY = ("previous_hash", "merkle_root", "address", "difficulty",
            "timestamp", "range")


def valid(prev_ts: int, ts: int, now: int) -> bool:
    """The node's rule for a block's timestamp at its clock ``now``."""
    return prev_ts < ts <= now


def newest_fresh(prev_ts: int, now: int, swept):
    """The newest second the rule allows that is not in ``swept``; None
    where every one is, or the window is empty."""
    fresh = [s for s in range(prev_ts + 1, now + 1) if s not in swept]
    return max(fresh) if fresh else None


def repeats(jobs: list) -> list:
    """Indices of the jobs whose IDENTITY equals an earlier job's."""
    seen, out = set(), []
    for i, job in enumerate(jobs):
        key = tuple(job[k] if k != "range" else tuple(job[k])
                    for k in IDENTITY)
        if key in seen:
            out.append(i)
        seen.add(key)
    return out


def pushed_timestamp(content_hex: str) -> int:
    """The timestamp a pushed block's header carries."""
    return powref.parse_header(content_hex)["timestamp"]
