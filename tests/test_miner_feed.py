"""The miner's loop against a node that goes away (ISSUE 40): a local
HTTP node that refuses, answers the syncing envelope, or holds a request;
``miner.run`` on the jnp engine, jobs of four 4,096-nonce rounds.  The
chip mines on the template in hand under fresh seconds, a template past
``--ttl`` is not mined, and a found block is pushed until the node gives
a verdict."""

import json
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from upow_tpu import telemetry
from upow_tpu.mine import miner
from upow_tpu.telemetry import scope

HEADER = re.compile(r"header: timestamp=(\d+) behind=(-?\d+) window=(-?\d+) "
                    r"repeat=([01]) held=([01]) age=(\d+\.\d)")
SHARD = (0, 1 << 18)      # 2^14 nonces a job: four rounds of 4,096
WAIT_S = 60               # no wait of a test is longer


class Node:
    """``get_mining_info`` and ``push_block`` in the node's envelopes.
    ``mode``: ok | refuse (the socket is closed) | envelope (the syncing
    answer) | hold (accepted, answered when ``release`` is set) | 503."""

    def __init__(self, difficulty=9.0):
        self.difficulty = difficulty
        self.mode = "ok"
        self.release = threading.Event()
        self.fetches, self.pushes = [], []   # (mode, unix) / bodies
        self.push_replies = []               # scripted, else {"ok": True}
        self.tip = 0xFEED
        self._port, self._server = 0, None
        self.up()

    @property
    def url(self):
        return f"http://127.0.0.1:{self._port}/"

    def info(self):
        return {"difficulty": self.difficulty,
                "last_block": {"hash": "%064x" % self.tip, "id": 41,
                               "timestamp": int(time.time()) - 600},
                "pending_transactions_hashes": ["%064x" % 5, "%064x" % 6]}

    def up(self):
        node = self

        class Handler(BaseHTTPRequestHandler):
            def _send(self, obj, status=200):
                data = json.dumps(obj).encode()
                self.send_response(status)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _away(self):
                mode = node.mode
                if mode == "hold":
                    node.release.wait(WAIT_S)
                elif mode == "envelope":
                    self._send({"ok": False, "error": "node is syncing"})
                elif mode == "503":
                    self._send({"ok": False, "error": "busy"}, 503)
                return mode in ("envelope", "503")

            def do_GET(self):
                node.fetches.append((node.mode, time.time()))
                if not self._away():
                    self._send({"ok": True, "result": node.info()})

            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                node.pushes.append(json.loads(self.rfile.read(n)))
                if self._away():
                    return
                reply = node.push_replies.pop(0) if node.push_replies \
                    else {"ok": True}
                if reply.get("ok"):
                    node.tip += 1
                self._send(reply)

            def log_message(self, *_a):
                pass

        self._server = ThreadingHTTPServer(("127.0.0.1", self._port),
                                           Handler)
        self._port = self._server.server_address[1]
        threading.Thread(target=self._server.serve_forever, args=(0.01,),
                         daemon=True).start()
        self.mode = "ok"

    def down(self):
        self.mode = "refuse"
        self._server.shutdown()
        self._server.server_close()

    def away(self, mode):
        self.release.clear()
        self.down() if mode == "refuse" else setattr(self, "mode", mode)

    def back(self):
        if self.mode == "refuse":
            self.up()
        self.mode = "ok"
        self.release.set()

    def close(self):
        self.release.set()
        if self.mode != "refuse":
            self.down()


@pytest.fixture
def node():
    n = Node()
    yield n
    n.close()


@pytest.fixture
def address():
    from upow_tpu.core import curve, point_to_string

    return point_to_string(curve.keygen(rng=40)[1])


class Done(BaseException):
    """Ends ``miner.run`` from inside its loop."""


def run_miner(monkeypatch, capsys, node, address, before_job, ttl=90.0,
              once=False):
    """``miner.run`` on a thread until ``before_job(k, jobs)`` (called in
    the loop before the k-th job's search; ``jobs`` = the header lines'
    fields so far) returns true.  Returns those fields, one tuple a job:
    (timestamp, behind, window, repeat, held, age); what ``run``
    returned; and what the miner wrote to stderr."""
    jobs, real_mine, ended, errs = [], miner.mine, [], []

    def mine(job, backend, **kw):
        out, err = capsys.readouterr()
        errs.append(err)
        jobs.extend(tuple(float(g) if "." in g else int(g)
                          for g in m.groups())
                    for m in map(HEADER.fullmatch, out.splitlines()) if m)
        if before_job(len(jobs) - 1, jobs):
            raise Done
        return real_mine(job, backend, **kw)

    def target():
        try:
            ended.append(miner.run(address, node.url, "jnp", 4096, ttl,
                                   shard=SHARD, once=once))
        except Done:
            ended.append("done")

    monkeypatch.setattr(miner, "mine", mine)
    monkeypatch.setattr(miner, "_start_hang_watchdog", lambda *a, **k: None)
    thread = threading.Thread(target=target, daemon=True)
    thread.start()
    thread.join(WAIT_S)
    assert not thread.is_alive(), (jobs, node.fetches[-3:])
    return jobs, ended[0], "".join(errs) + capsys.readouterr().err


def _counters(*names):
    have = telemetry.counters()
    return [have.get(n, 0) for n in names]


# ---- a node that is away: the chip mines on ----

@pytest.mark.parametrize("how", ["refuse", "envelope", "hold"])
def test_a_node_that_is_away_gives_held_jobs_on_fresh_seconds(
        monkeypatch, capsys, node, address, how):
    """The node answers the first fetch and is then away for six jobs:
    each of them is built from the template in hand under a second not
    swept before; once it is back a fresh template is taken."""
    def before_job(k, jobs):
        if k == 0:
            node.away(how)
        if k == 6:
            node.back()
        return k > 6 and jobs[-1][4] == 0     # a fresh one after the return

    names = ("mine.jobs", "mine.jobs_held", "mine.fetch_errors",
             "mine.templates_late") + miner.ROLL_COUNTERS \
        + miner.SEAM_COUNTERS
    before = _counters(*names)
    t0 = time.monotonic()
    jobs, _end, err = run_miner(monkeypatch, capsys, node, address, before_job)
    grew = dict(zip(names, (a - b for a, b in
                            zip(_counters(*names), before))))
    held = [j for j in jobs if j[4]]
    assert [j[4] for j in jobs[:7]] == [0] + [1] * 6
    # a held job is never one issued ahead: its seam found no template
    assert grew[miner.DRAINED] >= len(held) and grew[miner.DROPPED] == 0
    assert grew[miner.OVERLAPPED] + grew[miner.DRAINED] \
        == grew["mine.jobs"] - 1
    # fresh seconds: no header twice, none repeated, all inside the rule
    assert len({j[0] for j in jobs}) == len(jobs)
    assert all(j[3] == 0 and 0 <= j[1] < j[2] for j in jobs)
    # the loop never stood still for the node: seven jobs and their
    # swaps took less than one try of a held request would
    assert time.monotonic() - t0 < 20
    assert grew["mine.jobs_held"] == len(held) >= 6
    # one of the three a job, held or not
    assert grew["mine.jobs"] - 1 == len(jobs) - 1 == \
        sum(grew[n] for n in miner.ROLL_COUNTERS) - 1
    if how == "hold":
        # the held request's answer came after its job had begun: late,
        # and taken at the next job's end all the same
        assert grew["mine.templates_late"] >= 1
        assert grew["mine.fetch_errors"] == 0
    else:
        assert grew["mine.fetch_errors"] >= 1
        assert "node unreachable: " in err and "; retrying" in err
    # ages run from the fetch that brought the template
    ages = [j[5] for j in jobs[:7]]
    assert ages == sorted(ages)
    job = next(t for t in reversed(telemetry.traces()["recent"])
               if t["name"] == "mine.job" and t["fields"].get("held") == 1)
    built = next(c for c in job["spans"] if c["name"] == "mine.build_job")
    assert built["fields"]["held"] == 1
    assert built["fields"]["template_age_s"] == \
        job["fields"]["template_age_s"] > 0
    assert telemetry.stats()["mine.take_template"]["count"] >= len(jobs)


# ---- the seam and the feed (ISSUE 44) ----

def test_the_asks_lead_covers_a_fetch_longer_than_the_rounds_in_flight(
        monkeypatch, capsys):
    """Rounds of 50 ms, a node that takes 120 ms to answer: asked for
    only when two rounds are left (the rule before the seam) the
    template would come 20 ms after the job's end, and the job after it
    would be a held one.  The lead holds the fetch's seconds beside the
    rounds in flight, so it is there while the last round runs: the
    next job goes behind it, and none is held."""
    import miner_seams

    miner_seams.FakeDevice(monkeypatch, round_s=0.05)

    def serve(k):
        time.sleep(0.12)
        return miner_seams.info()

    names = ("mine.jobs", "mine.jobs_held") + miner.SEAM_COUNTERS
    before = miner_seams.counters(*names)
    out, _ = miner_seams.run_jobs(monkeypatch, capsys, 4, serve=serve,
                                  rounds=8, feed=miner.TemplateFeed)
    grew = miner_seams.grew(before)
    lines = [m.groups() for m in map(HEADER.fullmatch, out) if m]
    assert len(lines) == grew["mine.jobs"] == 4
    assert [held for *_rest, held, _age in lines] == ["0"] * 4
    assert grew["mine.jobs_held"] == 0 and grew[miner.DROPPED] == 0
    # a loaded host may lose one seam to its scheduler, not two
    assert grew[miner.OVERLAPPED] >= 2
    assert grew[miner.OVERLAPPED] + grew[miner.DRAINED] == 3


@pytest.mark.parametrize("depth", [2, 4])
def test_the_ask_leads_by_the_depth_in_force(monkeypatch, capsys, depth):
    """Sixteen rounds of 30 ms a job, a node that answers at once: the
    seam begins at the job's last issue, ``depth`` rounds before its end,
    and the ask comes a round before that (the look comes once a round),
    so every seam finds its template at the first look.  A slow seam's
    build may make it one round earlier, never later."""
    import miner_seams

    device = miner_seams.FakeDevice(monkeypatch, round_s=0.03, depth=depth)
    left = []

    class Feed(miner_seams.InlineFeed):
        def ask(self):
            if self._asked_for <= self.jobs:
                answered = sum(what == "wait" for what, *_ in device.log)
                left.append(16 - answered % 16)
            super().ask()

    names = ("mine.jobs", "mine.jobs_held") + miner.SEAM_COUNTERS
    before = miner_seams.counters(*names)
    miner_seams.run_jobs(monkeypatch, capsys, 3, rounds=16, feed=Feed)
    assert len(left) == 3 and set(left) <= {depth + 1, depth + 2}, left
    assert miner_seams.grew(before) == {
        "mine.jobs": 3, "mine.jobs_held": 0, miner.OVERLAPPED: 2,
        miner.DRAINED: 0, miner.DROPPED: 0}
    assert device.most_in_flight() == depth
    # the next job's first round goes out behind the answer that made
    # room for it: the first after the job in hand's last issue
    for number in (0, 1):
        last_issue = device.log.index(
            ("issue", number, miner_seams.RANGE - miner_seams.RANGE // 16))
        assert device.log[last_issue + 1][0] == "wait"
        assert device.log[last_issue + 2] == ("issue", number + 1, 0)


def test_a_template_that_comes_after_the_seams_last_look_makes_no_held_job(
        monkeypatch, capsys):
    """The node answers only once the last round of the job in hand is
    being waited for: every look of the seam finds the fetch still out,
    nothing is issued ahead, and the job's end takes the template as it
    always has: a drained seam, and no held job where the loop before
    the seam gave none."""
    import miner_seams

    asked, arrived = threading.Event(), threading.Event()
    device = miner_seams.FakeDevice(monkeypatch)

    class Feed(miner.TemplateFeed):
        def _arrived(self, got, for_job):
            super()._arrived(got, for_job)
            arrived.set()

    def serve(k):
        if k:
            assert asked.wait(WAIT_S)
            asked.clear()
        return miner_seams.info()

    last = miner_seams.RANGE - miner_seams.RANGE // 4

    def on_wait(number, start):
        if start == last:
            arrived.clear()
            asked.set()
            assert arrived.wait(WAIT_S)

    device.on_wait = on_wait
    names = ("mine.jobs", "mine.jobs_held") + miner.SEAM_COUNTERS
    before = miner_seams.counters(*names)
    out, _ = miner_seams.run_jobs(monkeypatch, capsys, 4, serve=serve,
                                  feed=Feed)
    grew = miner_seams.grew(before)
    assert grew == {"mine.jobs": 4, "mine.jobs_held": 0, miner.OVERLAPPED: 0,
                    miner.DRAINED: 3, miner.DROPPED: 0}
    lines = [m.groups() for m in map(HEADER.fullmatch, out) if m]
    assert [held for *_rest, held, _age in lines] == ["0"] * 4
    # no round of a job before the last answer of the one before it
    order = [n for _w, n, _s in device.log]
    assert order == sorted(order)


def test_one_fetch_a_job_across_overlapped_seams(monkeypatch, capsys, node,
                                                 address):
    """Jobs issued ahead ask the node once each, as drained ones do: the
    rounds the job in hand still reads after the seam took its template
    ask for nothing.  Rounds of 20 ms, so that the node's answer, a
    few ms on a loaded host, is there before a job of four ends: a late
    one would make a held job, as it always has."""
    import miner_seams

    miner_seams.FakeDevice(monkeypatch, round_s=0.02)
    names = ("mine.jobs",) + miner.SEAM_COUNTERS
    before = miner_seams.counters(*names)
    jobs, _end, _err = run_miner(monkeypatch, capsys, node, address,
                                 lambda k, jobs: k == 8)
    grew = miner_seams.grew(before)
    assert grew["mine.jobs"] == len(jobs) == 9
    assert all(j[4] == 0 for j in jobs)
    assert grew[miner.OVERLAPPED] + grew[miner.DRAINED] == 8
    # the ninth job's template, and at most the tenth's on its way
    assert len(jobs) <= len(node.fetches) <= len(jobs) + 1


def test_a_template_past_its_ttl_is_not_mined(monkeypatch, capsys, node,
                                              address):
    """--ttl 1: the node is away for 2.5 s from the first answer on.  The
    template is held for a second and then left; the loop waits (span
    mine.take_template) until the node is back."""
    begun, state = [], {}

    def before_job(k, jobs):
        begun.append(time.monotonic())
        if k == 0:
            node.away("envelope")
            state["away"] = time.monotonic()
            threading.Timer(2.5, node.back).start()
        return node.mode == "ok" and k > 0

    jobs, _end, err = run_miner(monkeypatch, capsys, node, address, before_job,
                           ttl=1.0)
    held = [j for j in jobs if j[4]]
    assert held and all(j[5] < 1.3 for j in held)     # a loaded host's slack
    # nothing began between the template's last second and the return
    assert not [t for t in begun[:-1] if t - state["away"] > 1.5]
    assert begun[-1] - state["away"] >= 2.4 and jobs[-1][4] == 0
    assert telemetry.stats()["mine.take_template"]["max_s"] >= 1.0


def test_a_late_answer_never_replaces_a_newer_one():
    feed = miner.TemplateFeed("http://x/")
    newer = miner.Template({"n": 2}, fetched=10.0, took=0.1)
    older = miner.Template({"n": 1}, fetched=5.0, took=6.0)
    before = _counters("mine.templates_late")[0]
    with feed._cond:
        feed._arrived(newer, for_job=1)
        feed._arrived(older, for_job=1)
    assert feed.take(None, 90.0, lambda: None) is newer
    assert feed.took == 0.1
    assert _counters("mine.templates_late")[0] == before + 1
    # one fetched before a push is dropped with it
    feed.forget()
    with feed._cond:
        feed._arrived(miner.Template({"n": 3}, fetched=11.0, took=0.1), 2)
        assert feed._newest is None and feed._wanted


def test_a_feed_that_dies_takes_the_loop_with_it(monkeypatch):
    def fetch(_node):
        raise ZeroDivisionError("a bug in the feed")

    monkeypatch.setattr(miner, "fetch_mining_info", fetch)
    feed = miner.TemplateFeed("http://x/")
    feed.start()
    with pytest.raises(ZeroDivisionError):
        feed.take(None, 90.0, lambda: None)


def test_the_loops_end_stops_the_feed(monkeypatch, capsys, node, address):
    threads = set(threading.enumerate())

    def before_job(k, jobs):
        if k == 0:
            node.away("envelope")       # the feed is kept trying
        return k == 2

    run_miner(monkeypatch, capsys, node, address, before_job)
    feeds = [t for t in set(threading.enumerate()) - threads
             if t.name == "miner-feed"]
    for thread in feeds:
        thread.join(WAIT_S)
    assert not [t for t in feeds if t.is_alive()]
    tries = len(node.fetches)
    time.sleep(2.5 * miner.FETCH_RETRY_S)
    assert len(node.fetches) == tries


# ---- a found block: pushed until the node gives a verdict ----

def test_a_block_found_while_the_node_is_away_arrives_byte_for_byte(
        monkeypatch, capsys, address):
    """Difficulty 1.0: a hit in the first round.  The node answers the
    push 503 twice, is then down for a try, then back: the same bytes
    every time, one verdict, nothing mined meanwhile."""
    node = Node(difficulty=1.0)
    monkeypatch.setattr(miner, "PUSH_RETRY_S", 0.05)
    real_push, tries = miner.push_block, []

    def push(*args):
        tries.append(args[1])
        if len(tries) == 1:
            node.away("503")
        elif len(tries) == 3:
            node.away("refuse")
        elif len(tries) == 4:
            node.back()
        return real_push(*args)

    monkeypatch.setattr(miner, "push_block", push)
    names = ("mine.push_errors", "mine.push_retries", "mine.jobs")
    before = _counters(*names)
    try:
        jobs, _end, err = run_miner(monkeypatch, capsys, node, address,
                               lambda k, jobs: k == 1)
    finally:
        node.close()
    assert len(tries) == 4 and len(set(tries)) == 1
    assert [p["block_content"] for p in node.pushes] == [tries[0]] * 3
    assert node.pushes[-1]["block_no"] == 42
    assert [a - b for a, b in zip(_counters(*names), before)] == [3, 3, 2]
    found = next(t for t in reversed(telemetry.traces()["recent"])
                 if t["fields"].get("end") == "found")
    pushed = next(c for c in found["spans"] if c["name"] == "mine.push")
    assert pushed["fields"] == {"ok": True, "attempts": 4}
    # the next job is on the tip the block made, from a fetch after it
    assert [j[4] for j in jobs] == [0, 0]
    assert err.count("push_block failed: ") == 3


def test_a_verdict_of_not_ok_is_not_retried(monkeypatch, capsys, address):
    node = Node(difficulty=1.0)
    node.push_replies.append({"ok": False, "error": "stale tip"})
    before = _counters("mine.push_errors", "mine.push_retries")
    try:
        jobs, _end, err = run_miner(monkeypatch, capsys, node, address,
                               lambda k, jobs: k == 1)
    finally:
        node.close()
    assert len(node.pushes) == 1
    assert _counters("mine.push_errors", "mine.push_retries") == before
    assert [j[4] for j in jobs] == [0, 0]       # and a fresh fetch after it


def test_a_push_is_given_up_when_its_template_passes_the_ttl(monkeypatch):
    monkeypatch.setattr(miner, "PUSH_RETRY_S", 0.01)
    monkeypatch.setattr(miner, "push_block", lambda *a: (_ for _ in ()).throw(
        OSError("connection refused")))
    template = miner.Template({}, time.monotonic() - 0.9, 0.0)
    reply, tries = miner.push_until_verdict(
        "http://x/", "00", [], 1, template, 1.0, lambda: None)
    assert reply == {"ok": False} and 2 <= tries <= 15


# ---- --once, and the counters ----

def test_once_fetches_in_the_loop_and_starts_no_feed(monkeypatch, capsys,
                                                     node, address):
    node.difficulty = 1.0
    threads = set(threading.enumerate())
    monkeypatch.setattr(miner.TemplateFeed, "start", lambda self: 1 / 0)
    jobs, rc, _err = run_miner(monkeypatch, capsys, node, address,
                               lambda k, jobs: False, once=True)
    assert rc == 0 and len(node.fetches) == 1 and len(node.pushes) == 1
    assert [j[3:] for j in jobs] == [(0, 0, 0.0)]
    assert not [t for t in set(threading.enumerate()) - threads
                if t.name == "miner-feed"]
    job = telemetry.traces()["recent"][-1]
    assert [c["name"] for c in job["spans"]][:2] == ["mine.fetch",
                                                     "mine.build_job"]
    assert job["spans"][0]["fields"] == {"ok": True}


def test_the_feeds_counters_are_exported_at_zero_from_the_first_scrape(
        monkeypatch):
    def fetch(_node):
        raise KeyboardInterrupt     # before any job is built

    monkeypatch.setattr(miner, "fetch_mining_info", fetch)
    with scope.activate(scope.TelemetryScope("feed")):
        assert not set(miner.FEED_COUNTERS) & set(telemetry.counters())
        with pytest.raises(KeyboardInterrupt):
            miner.run("addr", "http://x/", "python", 64, 1.0, once=True)
        have = telemetry.counters()
        assert [have[name] for name in miner.FEED_COUNTERS] == [0, 0, 0]
