"""The plain reference for a miner whose node goes away and comes back:
what the node did, from its own log, against what the miner's lines say
it did.  Nothing of the program.

The log is the stub's (``drivers/mine_outage.py`` ``RestartingStub``),
in order, on the clock that stamps the miner's lines:

    {"kind": "state", "what": "down"|"syncing"|"stall"|"up", "t"}
        the node from ``t`` on: socket closed; answers the syncing
        envelope; accepts and holds; answers
    {"kind": "request", "what": "get_mining_info"|"push_block", "t",
     "answered_t", "answer": "ok"|"refused"|"syncing", "held",
     "block", "difficulty", "content"}
        one request that reached the node: when it came, when and what
        it was answered (``answered_t`` None: never), whether it was
        held first; for a template served (``ok``) the block number and
        difficulty a job built from it carries; for a push its bytes

A connection refused while the node is ``down`` reaches nobody and
leaves no entry: the ``state`` entries stand for those.
"""

from __future__ import annotations

from . import powref

#: seconds by which a line of the miner may trail what it reports (the
#: try's own milliseconds, the pipe, the parent's reader thread), and a
#: job's first line the choice of its template
LINE_SLACK_S = 0.25

_ERRORS = {"node unreachable": "get_mining_info",
           "push_block failed": "push_block"}


def served(log: list) -> list:
    """The templates the node served: its ``ok`` answers to
    ``get_mining_info``."""
    return [e for e in log if e["kind"] == "request" and e["answer"] == "ok"
            and e["what"] == "get_mining_info"]


def states(log: list, what: str) -> list:
    """[(from, to)] the node spent in state ``what`` (``to`` None: to the
    log's end)."""
    marks = [e for e in log if e["kind"] == "state"]
    return [(e["t"], nxt["t"] if nxt else None)
            for e, nxt in zip(marks, marks[1:] + [None])
            if e["what"] == what]


def returns(log: list) -> list:
    """The moments the node began to answer again."""
    marks = [e for e in log if e["kind"] == "state"]
    return [e["t"] for prev, e in zip(marks, marks[1:])
            if e["what"] == "up" and prev["what"] != "up"]


def templates_never_served_or_past_ttl(log: list, jobs: list,
                                       ttl: float) -> list:
    """[(job index, why)] of the jobs (``start_t``, ``block``,
    ``difficulty``) that no template served before they began accounts
    for, or whose youngest such template was older than ``ttl`` when
    they began.  Two answers of one block number and difficulty are one
    template to a job, so the youngest is the one that can clear it."""
    out, answers = [], served(log)
    for i, job in enumerate(jobs):
        ages = [job["start_t"] - e["answered_t"] for e in answers
                if e["answered_t"] <= job["start_t"]
                and (e["block"], e["difficulty"])
                == (job["block"], job["difficulty"])]
        if not ages:
            out.append((i, "never served"))
        elif min(ages) > ttl + LINE_SLACK_S:
            out.append((i, f"{min(ages):.2f}s old, ttl {ttl:g}s"))
    return out


def found_blocks_not_delivered(log: list, found: list) -> list:
    """Those of ``found`` (``t``, ``nonce``, ``timestamp``: a 'found
    nonce' line and its job's header) for which the node holds no
    answered push whose header carries that nonce and timestamp."""
    delivered = set()
    for e in log:
        if e["kind"] == "request" and e["what"] == "push_block" \
                and e["answered_t"] is not None:
            try:
                head = powref.parse_header(e["content"])
            except ValueError:
                continue
            delivered.add((head["nonce"], head["timestamp"]))
    return [f for f in found
            if (f["nonce"], f["timestamp"]) not in delivered]


def first_fresh_job_after_return_s(log: list, job_starts: list,
                                   until: float) -> list:
    """For each return of the node, the seconds from it to the first job
    that began after a template served after it; where no such job
    began, the seconds to ``until`` (what was seen of the miner ends
    there), which the true number is no less than."""
    out, answers = [], served(log)
    for r in returns(log):
        fresh = min((e["answered_t"] for e in answers
                     if e["answered_t"] >= r), default=None)
        began = None if fresh is None else min(
            (t for t in job_starts if t >= fresh), default=None)
        out.append((until if began is None else began) - r)
    return out


def errors_outside_the_schedule(log: list, errors: list) -> list:
    """Those of ``errors`` (``t``, ``text``: a line of the miner that says
    a request failed) that nothing the node did explains.  'node
    unreachable' and 'push_block failed' are explained by the node being
    ``down`` when the line came, or by one request of that kind, each
    good for one line, that was answered with the syncing envelope or
    held, at most ``LINE_SLACK_S`` before the line.  Any other error
    line is explained by nothing."""
    down = states(log, "down")
    spare = [e for e in log if e["kind"] == "request"
             and (e["answer"] == "syncing" or e["held"])]
    out = []
    for err in errors:
        what = next((w for text, w in _ERRORS.items()
                     if err["text"].startswith(text)), None)
        t = err["t"]
        if what is not None and any(
                a <= t and (b is None or t <= b + LINE_SLACK_S)
                for a, b in down):
            continue
        cause = next((e for e in spare if e["what"] == what
                      and e["t"] <= t and (e["answered_t"] is None or
                                           e["answered_t"]
                                           >= t - LINE_SLACK_S)), None)
        if cause is None:
            out.append(err)
        else:
            spare.remove(cause)
    return out
