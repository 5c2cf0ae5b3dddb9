"""The readers of the program's own spans, on records made by hand: a
20 s window on one device with two idle gaps, the miner's spans on one
host line and the device owner's on another."""

import json
import os

import pytest

from harness import manifest, xplane

S = 1e9   # a trace counts nanoseconds
HOST, DEV = "/host:CPU", "/device:TPU:0"
NEW = ["first_issue_s.mine", "job_fetch_ms.mine", "round_issue_ms.mine",
       "round_wait_ms.mine", "idle_first_issue_share.mine",
       "idle_fetch_share.mine", "idle_unattributed_share.mine"]


def rec(plane, line, name, start_s, dur_s):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_s * S, "dur_ns": dur_s * S}


def synthetic():
    """Window [10, 30).  The device runs [10, 12), [15, 20) and
    [20.5, 30): idle [12, 15) and [20, 20.5), 3.5 s of 20.  The miner is
    in ``mine.fetch`` over [12, 12.5) and in ``mine.first_issue`` over
    [12.5, 14.5); nothing names [14.5, 15) and [20, 20.5)."""
    out = [rec(HOST, "python3", "perfbench.window", 10.0, 20.0),
           rec(DEV, "XLA Ops", "%search.1", 10.0, 2.0),
           rec(DEV, "XLA Ops", "%search.1", 15.0, 5.0),
           rec(DEV, "XLA Ops", "%search.1", 20.5, 9.5),
           rec(DEV, "XLA Modules", "jit__pow_search_x(1)", 10.0, 2.0),
           rec(HOST, "python3", "mine.job", 12.0, 17.0),
           rec(HOST, "python3", "mine.fetch", 12.0, 0.5),
           rec(HOST, "python3", "mine.first_issue", 12.5, 2.0),
           # one that began before the window opened, one that outlives it
           rec(HOST, "python3", "mine.round.wait", 9.5, 1.0),
           rec(HOST, "python3", "mine.round.wait", 29.5, 1.0),
           # a device line of the same name is no host span
           rec(DEV, "XLA Ops", "mine.round.wait", 16.0, 3.0)]
    for start, dur in ((15.0, 0.002), (16.0, 0.004), (17.0, 0.009),
                       (18.0, 0.005)):
        out.append(rec(HOST, "python3", "mine.round.wait", start, dur))
        out.append(rec(HOST, "python3", "mine.round.issue", start + 0.5,
                       dur / 10))
        out.append(rec(HOST, "drainer", "runtime.call", start, dur / 2))
    return out


def read(reader: str, records: list, **spec):
    module = manifest.load_module("readers", reader)
    return module.read({"records": records}, spec)


@pytest.mark.parametrize("stat,want", [
    ("mean", 5.0), ("median", 4.5), ("max", 9.0), ("sum", 20.0),
    ("count", 4)])
def test_span_stat_over_the_events_wholly_inside_the_window(stat, want):
    got = read("span_stat", synthetic(), span="mine.round.wait", stat=stat,
               scale=1e-6)
    assert got == pytest.approx(want)


def test_span_stat_scales_to_seconds():
    got = read("span_stat", synthetic(), span="mine.first_issue",
               stat="max", scale=1e-9)
    assert got == pytest.approx(2.0)


def test_span_stat_leaves_out_events_across_the_windows_edge():
    only_edges = [r for r in synthetic()
                  if r["name"] != "mine.round.wait" or r["dur_ns"] == S]
    assert read("span_stat", only_edges, span="mine.round.wait",
                stat="count", scale=1.0) is None


def test_no_such_span_reads_nothing():
    assert read("span_stat", synthetic(), span="mine.push", stat="mean",
                scale=1e-6) is None
    assert read("idle_by_span", synthetic(), spans=["mine.push"]) is None
    assert read("span_stat", [], span="mine.fetch", stat="mean",
                scale=1e-6) is None
    assert read("idle_by_span", [], spans=["mine.fetch"]) is None


def test_idle_by_span_needs_a_device():
    hosts = [r for r in synthetic() if r["plane"] == HOST]
    assert read("idle_by_span", hosts, spans=["mine.fetch"]) is None


def test_idle_gaps_are_cut_against_the_named_spans():
    records = synthetic()
    whole = 100.0 * (1.0 - xplane.reduce(records)["busy_s"]
                     / xplane.reduce(records)["window_s"])
    assert whole == pytest.approx(100.0 * 3.5 / 20.0)
    first = read("idle_by_span", records, spans=["mine.first_issue"])
    fetch = read("idle_by_span", records,
                 spans=["mine.fetch", "mine.build_job", "mine.prepare"])
    rest = read("idle_by_span", records, complement=True,
                spans=["mine.fetch", "mine.first_issue", "mine.round.wait",
                       "runtime.call"])
    assert first == pytest.approx(100.0 * 2.0 / 20.0)
    assert fetch == pytest.approx(100.0 * 0.5 / 20.0)
    assert rest == pytest.approx(100.0 * 1.0 / 20.0)
    assert first + fetch + rest == pytest.approx(whole)
    # a span that covers a gap twice counts it once
    both = read("idle_by_span", records,
                spans=["mine.job", "mine.first_issue"])
    assert both == pytest.approx(100.0 * 3.5 / 20.0)


def test_on_several_chips_the_parts_stay_under_the_averaged_share():
    records = synthetic() + [
        rec("/device:TPU:1", "XLA Ops", "%search.1", 10.0, 3.0),
        rec("/device:TPU:1", "XLA Ops", "%search.1", 16.0, 14.0)]
    trace = xplane.reduce(records)
    whole = 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    parts = (read("idle_by_span", records, spans=["mine.first_issue"])
             + read("idle_by_span", records, spans=["mine.fetch"])
             + read("idle_by_span", records, complement=True,
                    spans=["mine.fetch", "mine.first_issue"]))
    # no chip ran anything in [13, 15); chip 0 alone idles in [20, 20.5)
    assert parts == pytest.approx(100.0 * 2.0 / 20.0)
    assert parts <= whole


def test_the_seven_new_metrics_load_and_read_through_the_manifest():
    mf = manifest.load_manifest()
    for cell in ("mine-sweep-1chip", "mine-sweep-4chip"):
        found = {entry["name"]: (entry, spec) for entry, spec
                 in manifest.layer_metrics_for(mf, cell)}
        assert set(NEW) <= set(found)
        for name in NEW:
            entry, spec = found[name]
            assert entry["source"] == spec["source"] == "program_span"
            assert entry["moves"] == spec["moves"] == "search_mhs"
            assert entry["unit"] == spec["unit"]
            assert entry["layer"] == spec["layer"]
            reader = manifest.load_module("readers", spec["reader"])
            value = reader.read({"records": synthetic()}, spec)
            assert isinstance(value, float), name
            # the parent's program opens no span: nothing to read, no raise
            bare = [r for r in synthetic()
                    if not r["name"].startswith(("mine.", "runtime."))]
            assert reader.read({"records": bare}, spec) is None


def test_the_new_metrics_values_on_the_hand_made_trace():
    mf = manifest.load_manifest()
    specs = {entry["name"]: spec for entry, spec
             in manifest.layer_metrics_for(mf, "mine-sweep-1chip")}

    def value(name):
        reader = manifest.load_module("readers", specs[name]["reader"])
        return reader.read({"records": synthetic()}, specs[name])

    assert value("first_issue_s.mine") == pytest.approx(2.0)
    assert value("job_fetch_ms.mine") == pytest.approx(500.0)
    assert value("round_wait_ms.mine") == pytest.approx(4.5)
    assert value("round_issue_ms.mine") == pytest.approx(0.45)
    assert value("idle_first_issue_share.mine") == pytest.approx(10.0)
    assert value("idle_fetch_share.mine") == pytest.approx(2.5)
    assert value("idle_unattributed_share.mine") == pytest.approx(5.0)
    ten = specs["idle_unattributed_share.mine"]["spans"]
    assert len(ten) == 10 and "mine.job" not in ten


def test_the_spec_files_are_data_with_the_keys_the_others_have():
    for name in NEW:
        path = os.path.join(manifest.BENCH, "layer_metrics", name + ".json")
        with open(path) as f:
            spec = json.load(f)
        assert {"layer", "modules", "unit", "source", "moves", "reader",
                "what"} <= set(spec)
