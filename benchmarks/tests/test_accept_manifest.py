"""The validator's entries of BENCHMARK.json: every file they name is
there, the data files say what the entries say, and the cell reports the
two end-to-end metrics this PR brought."""

import json
import os

from harness import manifest

CELL = "accept-2mb-cold"


def test_the_cell_finds_every_file_it_names():
    mf = manifest.load_manifest()
    cell = manifest.find_cell(mf, CELL)
    assert cell["chips"] == 1
    config = manifest.load_config(mf, cell)
    entry = next(c for c in mf["configs"] if c["name"] == cell["config"])
    assert os.path.isfile(os.path.join(manifest.ROOT, entry["file"]))
    assert config["source"] == entry["source"]
    assert set(entry["reduced"]) == set(config["reduced"]) and \
        all(k in config for k in entry["reduced"])
    assert os.path.isfile(os.path.join(manifest.ROOT, config["reference"]))
    for which in ("children", "rehearse_children"):
        assert "node_config" in config[which]["1"]
    assert config["children"]["1"]["node_config"]["device"] == {
        "device": "tpu", "verify_microbatch": 0, "txid_backend": "host"}
    traffic = manifest.load_traffic(cell["traffic"])
    driver = manifest.load_module("drivers", traffic["driver"])
    assert callable(driver.run) and set(driver.CONTROLS) >= {"host_verify"}
    assert traffic["fan_out"] * traffic["per_output"] == 8160 >= \
        traffic["min_block_txs"] >= 8000
    assert traffic["rehearse"]["fan_out"] * \
        traffic["rehearse"]["per_output"] == 32
    for name in ("launch/node_child.py", "launch/node_faults.py",
                 "harness/blockfixture.py", "harness/chainref.py"):
        assert os.path.isfile(os.path.join(manifest.BENCH, name)), name


def test_the_cells_metrics_and_their_data_files_agree():
    mf = manifest.load_manifest()
    e2e = {m["name"]: m for m in manifest.end_to_end_for(mf, CELL)}
    assert set(e2e) == {"accept_tx_per_s", "accept_s_p50", "setup_s"}
    for name in ("accept_tx_per_s", "accept_s_p50"):
        assert e2e[name]["workloads"] == [CELL]
        assert e2e[name]["source"] == "host_clock"
        assert 0.01 <= e2e[name]["bound"] <= 0.25
    assert e2e["accept_tx_per_s"]["better"] == "higher"
    assert e2e["accept_s_p50"]["better"] == "lower"
    layer = manifest.layer_metrics_for(mf, CELL)
    assert len(layer) >= 10
    for entry, spec in layer:
        assert entry["name"].endswith(".accept")
        assert entry["workloads"] == [CELL]
        assert entry["moves"] in e2e
        for key in ("layer", "unit", "moves", "source"):
            assert spec[key] == entry[key], (entry["name"], key)
        reader = manifest.load_module("readers", spec["reader"])
        assert callable(reader.read)
        assert "roofline" not in entry["name"] and "peak" not in \
            entry["name"]
    # the miner's cells report none of them, and the new cell none of
    # the miner's
    for cell in mf["workloads"]:
        if cell["name"] == CELL:
            continue
        names = {m["name"] for m in manifest.end_to_end_for(
            mf, cell["name"])}
        assert names == {"search_mhs", "setup_s"}
        assert not [e["name"] for e, _s in manifest.layer_metrics_for(
            mf, cell["name"]) if e["name"].endswith(".accept")]
    assert not [e["name"] for e, _s in layer
                if e["name"].endswith(".mine")]


def test_the_manifest_is_one_json_object_of_the_contracts_size():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    assert len(text.encode()) <= 64 * 1024
    mf = json.loads(text)
    four = sum(1 for w in mf["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(mf["workloads"]) // 2)
    assert len(mf["per_layer"]) <= 128 and len(mf["workloads"]) <= 24
