"""The manifest loader: a cell's configuration, traffic and per-layer
metric files are found by the names in BENCHMARK.json, and what is not
there is refused."""

import json
import os
import re

import pytest

from harness import manifest
from harness.manifest import BenchError

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def mf():
    return manifest.load_manifest()


def test_every_cell_finds_its_files(mf):
    for cell in mf["workloads"]:
        config = manifest.load_config(mf, cell)
        assert str(cell["chips"]) in config["children"], cell["name"]
        traffic = manifest.load_traffic(cell["traffic"])
        driver = manifest.load_module("drivers", traffic["driver"])
        assert callable(driver.run)
        layer = manifest.layer_metrics_for(mf, cell["name"])
        assert layer, f"{cell['name']} reports no per-layer metric"
        for entry, spec in layer:
            reader = manifest.load_module("readers", spec["reader"])
            assert callable(reader.read)
            # the data file and the manifest say the same thing
            assert spec["layer"] == entry["layer"]
            assert spec["unit"] == entry["unit"]
            assert spec["moves"] == entry["moves"]
            assert spec["source"] == entry["source"]
        names = [m["name"] for m in manifest.end_to_end_for(mf,
                                                            cell["name"])]
        assert "setup_s" in names and len(names) >= 2


def test_traffic_include_starts_from_the_named_mix(tmp_path):
    os.makedirs(tmp_path / "traffic")
    (tmp_path / "traffic" / "a.json").write_text(
        json.dumps({"driver": "d", "x": 1, "y": 2}))
    (tmp_path / "traffic" / "b.json").write_text(
        json.dumps({"include": "a", "y": 3}))
    (tmp_path / "traffic" / "c.json").write_text(
        json.dumps({"include": "c"}))
    assert manifest.load_traffic("b", str(tmp_path)) == \
        {"driver": "d", "x": 1, "y": 3}
    with pytest.raises(BenchError):
        manifest.load_traffic("c", str(tmp_path))
    with pytest.raises(BenchError):
        manifest.load_traffic("absent", str(tmp_path))


def test_only_the_pods_traced_runs_hold_a_shorter_window(mf):
    """``traced_window_s`` reaches the pod's cell through its ``include``
    and stays under the run's length; the one-chip mix has none."""
    pod = manifest.load_traffic("mine-sweep-pod")
    assert 0 < pod["traced_window_s"] < mf["run_seconds"]
    assert pod["why_traced_window"] and pod["driver"] == "mine_sweep"
    assert "traced_window_s" not in manifest.load_traffic("mine-sweep")
    by_cell = {w["name"]: manifest.load_traffic(w["traffic"])
               for w in mf["workloads"]}
    assert [n for n, t in by_cell.items() if "traced_window_s" in t] == \
        ["mine-sweep-4chip"]


def test_unknown_names_are_refused(mf):
    with pytest.raises(BenchError, match="no workload"):
        manifest.find_cell(mf, "no-such-cell")
    with pytest.raises(BenchError, match="no driver"):
        manifest.load_module("drivers", "no_such_driver")
    with pytest.raises(BenchError):
        manifest.load_module("readers", "../run")
    with pytest.raises(BenchError, match="not in"):
        manifest.peaks("TPU v9 imaginary")
    assert manifest.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_a_metric_is_reported_where_it_says(mf):
    e2e = {"name": "x", "workloads": ["a"]}
    fake = {"end_to_end": [e2e, {"name": "setup_s"}], "per_layer": []}
    assert manifest.reports(e2e, "a", fake)
    assert not manifest.reports(e2e, "b", fake)
    assert manifest.reports({"name": "setup_s"}, "b", fake)
    follows = {"name": "y", "moves": "x"}      # no workloads key
    assert manifest.reports(follows, "a", fake)
    assert not manifest.reports(follows, "b", fake)
    with pytest.raises(BenchError):
        manifest.reports({"name": "z", "moves": "absent"}, "a", fake)


def test_manifest_keeps_to_the_contract(mf):
    assert set(mf) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= mf["run_seconds"] <= 51
    names = ([c["name"] for c in mf["configs"]]
             + [w["name"] for w in mf["workloads"]]
             + [m["name"] for m in mf["end_to_end"] + mf["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in mf["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in mf["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(mf["workloads"]) // 2)
    e2e = {m["name"]: m for m in mf["end_to_end"]}
    assert "setup_s" in e2e and all(
        0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    cells = {w["name"] for w in mf["workloads"]}
    for m in mf["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert "peak" not in m["name"] and "roofline" not in m["name"]
    for c in mf["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in mf["paths"]))
        held = json.load(open(os.path.join(manifest.ROOT, c["file"])))
        assert all(k in held for k in c["reduced"]), c["reduced"]
    for text in ([w["why"] for w in mf["workloads"]]
                 + [c["why"] for c in mf["configs"]]
                 + [c["source"] for c in mf["configs"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text, text
