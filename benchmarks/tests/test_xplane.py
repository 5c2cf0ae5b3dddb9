"""The trace reducer on a trace made by hand: known busy and idle
seconds, per-program time and gap names."""

import pytest

from harness import xplane

S = 1e9   # a trace counts nanoseconds


def rec(plane, line, name, start_s, dur_s):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_s * S, "dur_ns": dur_s * S}


def synthetic():
    """A 10 s window on two devices.  Device 0: a program of 2 s at 1 s
    (two operations of 1 s, back to back) and one of 2 s at 6 s; device
    1 the same but the second program is 1 s.  Host noise around."""
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    out = [rec("/host:CPU", "python3", "perfbench.window", 1.0, 10.0),
           rec("/host:CPU", "python3", "PjitFunction(search)", 0.5, 12.0)]
    for dev, second in ((d0, 2.0), (d1, 1.0)):
        out += [
            rec(dev, "XLA Modules", "jit__pow_search_x(123)", 2.0, 2.0),
            rec(dev, "XLA Ops", "%fusion.1 = u32[8] fusion(...)", 2.0, 1.0),
            rec(dev, "XLA Ops", "%custom-call.2 = s32[] custom-call(..)",
                3.0, 1.0),
            rec(dev, "XLA Modules", "jit__pow_search_x(123)", 7.0, second),
            rec(dev, "XLA Ops", "%fusion.1 = u32[8] fusion(...)", 7.0,
                second),
            rec(dev, "XLA Modules", "jit_other(9)", 0.2, 0.3),  # outside
            rec(dev, "XLA Ops", "%copy.3 = copy(...)", 0.2, 0.3),
            rec(dev, "Steps", "0", 2.0, 6.0),   # not an operation
        ]
    return out


def test_busy_idle_and_window():
    got = xplane.reduce(synthetic())
    assert got["window_s"] == pytest.approx(10.0)
    assert got["devices"] == 2
    assert got["per_device_busy_s"] == {0: pytest.approx(4.0),
                                       1: pytest.approx(3.0)}
    assert got["busy_s"] == pytest.approx(3.5)
    # no device ran anything in [1,2), [4,7), [9,11): 1 + 3 + 2 seconds
    assert sum(s for _n, s in got["idle_gaps"]) == pytest.approx(6.0)
    assert got["longest_gap_s"] == pytest.approx(3.0)


def test_top_operations_are_named_short_and_averaged_over_devices():
    ops = dict(xplane.reduce(synthetic())["device_ops"])
    assert ops["%fusion.1"] == pytest.approx((1 + 2 + 1 + 1) / 2)
    assert ops["%custom-call.2"] == pytest.approx(1.0)
    assert "%copy.3" not in ops            # ran before the window opened


def test_gaps_are_named_by_the_parents_phase():
    # the window span opened at unix 1000.0; trace second 1.0 = unix 1000
    phases = [(1000.0, 1001.5, "job_swap"), (1001.5, 1008.5, "sweep")]
    got = xplane.reduce(synthetic(), phases=phases, started_unix=1000.0)
    gaps = dict(got["idle_gaps"])
    # [1,2) -> unix 1000.5 job_swap; [4,7) -> 1004.5 sweep; [9,11) -> other
    assert gaps == {"job_swap": pytest.approx(1.0),
                    "sweep": pytest.approx(3.0),
                    "other": pytest.approx(2.0)}


def test_program_seconds_counts_modules_inside_the_window():
    got = xplane.program_seconds(synthetic(), "pow_search")
    assert got == {"events": 2, "seconds": pytest.approx(4.0 + 3.0),
                   "devices": 2, "ended": 2}
    assert xplane.program_seconds(synthetic(), "absent")["events"] == 0


def test_a_program_cut_by_the_windows_edge_is_not_timed():
    """An event that began before the window opened counts as a round
    that ended in it, and is left out of the time and its divisor."""
    recs = synthetic() + [rec("/device:TPU:0", "XLA Modules",
                              "jit__pow_search_x(123)", 0.5, 1.0),
                          rec("/device:TPU:0", "XLA Modules",
                              "jit__pow_search_x(123)", 10.5, 1.0)]
    got = xplane.program_seconds(recs, "pow_search")
    assert (got["events"], got["ended"]) == (2, 3)
    assert got["seconds"] == pytest.approx(4.0 + 3.0)


def test_overlapping_operations_are_counted_once():
    recs = [rec("/host:CPU", "python3", "perfbench.window", 0.0, 4.0),
            rec("/device:TPU:0", "XLA Ops", "%a", 1.0, 2.0),
            rec("/device:TPU:0", "XLA Ops", "%b", 1.5, 1.0),
            rec("/device:TPU:0", "XLA Ops", "%c", 3.5, 2.0)]  # clipped
    got = xplane.reduce(recs)
    assert got["busy_s"] == pytest.approx(2.5)
    assert xplane.merge([[3, 4], [1, 2], [1.5, 3.2]]) == [[1, 4]]


def test_a_trace_with_no_device_plane_reads_nothing():
    recs = [rec("/host:CPU", "python3", "perfbench.window", 0.0, 4.0)]
    got = xplane.reduce(recs)
    assert (got["devices"], got["busy_s"]) == (0, 0.0)
    assert xplane.reduce([])["window_s"] == 0.0
