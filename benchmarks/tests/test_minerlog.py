"""The miner-stdout parser on lines captured from chip runs (PR 22's
format, and PR 24's first run on the chip)."""

from harness import minerlog

CAPTURED = """\
upow_tpu miner: backend=pallas shard=0/2 nonces=[0, 2147483648) node=http://127.0.0.1:44317/
device: platform=tpu kind=TPU v5 lite count=1 compile_cache=/root/.cache/chiprun/jax
difficulty: 6.0  block: 2  confirming 64 transactions
3.96 MH/s (16777216 hashes)
found nonce 25127620 at 7.96 MH/s (33554432 hashes in 4.22s, first dispatch 4.20s)
{'ok': True}
BLOCK MINED

difficulty: 11.0  block: 3  confirming 64 transactions
7.40 MH/s (16777216 hashes)
14.72 MH/s (33554432 hashes)
22.01 MH/s (50331648 hashes)
template expired after 50331648 hashes; refreshing
difficulty: 11.0  block: 3  confirming 64 transactions
1900.12 MH/s (16777216 hashes)
mesh: {"devices": ["tpu:0", "tpu:1"], "batch_per_device": 8388608, "dispatches": 3, "last_round_shards": [[0, 8388608], [8388608, 16777216]]}
memory: peak_bytes=881664
trace: started unix=1790518093.250000
some line nobody reads
"""


def _events(step=0.5):
    lines = [(100.0 + i * step, text)
             for i, text in enumerate(CAPTURED.splitlines())]
    return minerlog.parse(lines)


def test_every_kind_of_line_is_read():
    kinds = [e["kind"] for e in _events()]
    assert kinds == ["start", "device", "job", "round", "found", "mined",
                     "job", "round", "round", "round", "expired", "job",
                     "round", "mesh", "memory", "trace"]


def test_device_line_keeps_its_kind_and_the_device_kind():
    dev = minerlog.parse_line("device: platform=tpu kind=TPU v5 lite "
                              "count=4 compile_cache=/x/y")
    assert dev == {"kind": "device", "platform": "tpu",
                   "device_kind": "TPU v5 lite", "count": 4, "cache": "/x/y"}


def test_start_line_gives_the_nonce_range():
    start = _events()[0]
    assert (start["backend"], start["lo"], start["hi"]) == \
        ("pallas", 0, 1 << 31)


def test_jobs_rounds_and_ends():
    jobs = minerlog.jobs(_events())
    assert [j["end"] for j in jobs] == ["found", "expired", None]
    assert jobs[0]["nonce"] == 25127620 and jobs[0]["tried"] == 33554432
    # the round that held the hit is counted although it prints no line
    assert [n for _t, n in jobs[0]["rounds"]] == [1 << 24, 1 << 24]
    assert jobs[1]["reported"] == jobs[1]["tried"] == 3 << 24
    assert jobs[1]["difficulty"] == 11.0 and jobs[1]["block"] == 3


def test_nonces_between_counts_rounds_by_arrival():
    jobs = minerlog.jobs(_events(step=1.0))
    # lines arrive at 100, 101, ...: job 1's rounds at 109, 110, 111
    assert minerlog.nonces_between(jobs, 108.5, 110.5) == 2 << 24
    assert minerlog.nonces_between(jobs, 0, 1e9) == 6 << 24
    assert minerlog.nonces_between(jobs, 111.0, 112.0) == 0


def test_swaps_last_round_to_first_round():
    jobs = minerlog.jobs(_events(step=1.0))
    # job 0's last round is the 'found' line at 104; job 1's first 109;
    # job 1's last 111; job 2's first 114
    assert minerlog.swaps(jobs, 0, 1e9) == [5.0, 3.0]
    assert minerlog.swaps(jobs, 110, 1e9) == [3.0]
    assert minerlog.sweep_seconds(jobs[1]) == 2.0
    assert minerlog.sweep_seconds(jobs[2]) is None


def test_a_tracer_line_written_into_a_miners_line_loses_neither():
    lines = [(1.0, "difficulty: 11.0  block: 3  confirming 64 transactions"),
             (2.0, "1890.12 MH/s (16777216 hashes)trace: started "
                   "unix=1790000000.250000"),
             (2.0, ""),
             (3.0, "1891.00 MH/s (33554432 hashes)")]
    events = minerlog.parse(lines)
    assert [e["kind"] for e in events] == ["job", "round", "trace", "round"]
    assert events[2]["unix"] == 1790000000.25
    assert minerlog.jobs(events)[0]["rounds"] == [(2.0, 16777216),
                                                  (3.0, 16777216)]


def test_a_memory_line_written_into_a_miners_line_loses_neither():
    lines = [(1.0, "difficulty: 11.0  block: 3  confirming 64 transactions"),
             (2.0, "1890.12 MH/s (16777216 hashes)memory: peak_bytes=1254912"),
             (2.0, ""),
             (3.0, "memory: unreadable (RuntimeError: no client (gone))"),
             (4.0, "memory: peak_bytes=null")]
    events = minerlog.parse(lines)
    assert [e["kind"] for e in events] == ["job", "round", "memory",
                                           "memory", "memory"]
    assert [(e["peak"], e["reason"]) for e in events[2:]] == [
        ("1254912", None), (None, "RuntimeError: no client (gone)"),
        ("null", None)]
    assert minerlog.jobs(events)[0]["rounds"] == [(2.0, 16777216)]
