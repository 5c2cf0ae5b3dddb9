"""The whole of a ``utxo-at-scale`` run, driven past the look for a chip:
``fake_index_node.py`` says it is on a TPU and has a resident index, and
the driver's fill, its ending of its own and guarantee (5) do the rest.
Sound, the run is correct; on a program without the delta index it ends
before a block is built; each control, and the index broken underneath,
comes out not correct by the check meant for it."""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

import run as bench_run
from harness import manifest

FAKE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "fake_index_node.py")
TINY = {"fan_out": 4, "per_output": 4, "min_block_txs": 16,
        "valid_blocks": 7, "forge_within": 3, "start_timeout_s": 30,
        "first_dispatch_timeout_s": 30, "push_timeout_s": 30,
        "utxo_fill": 3000, "fill_addresses": 500}


def drive(fault="-", control=None, seed=11, seconds="3"):
    argv = [sys.executable, FAKE, "--port", "{port}", "--db", "{db}",
            "--fault", fault if fault != "-" else "{fault}",
            "--sig-backend", "{sig_backend}", "--config", "{name}.json"]
    args = ["--workload", "utxo-at-scale", "--seed", str(seed),
            "--seconds", seconds, "--trace", "0"]
    if control:
        args += ["--control", control]
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(args, faults={"child_argv": argv,
                                          "traffic": dict(TINY)})
    return rc, out.getvalue().strip().splitlines()


def _failed(lines):
    return [ln.split()[1].rstrip(":") for ln in lines
            if ln.startswith("[check] ") and "FAILED" in ln]


def test_a_sound_run_is_correct_and_holds_the_table_whole():
    rc, lines = drive()
    assert rc == 0, lines[-5:]
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0, \
        _failed(lines)
    assert result["attempted"] == 9          # 7 valid and 2 forged
    assert set(result["metrics"]) == {"accept_tx_per_s", "accept_s_p50",
                                      "setup_s"}
    by_name = {c["name"]: c for c in result["checks"]}
    live = 16 + 11 - 1      # lanes, eleven coinbases, the fan-out spent one
    assert by_name["durable_table_rows"]["value"] == 3000 + live
    assert by_name["durable_table_digest"]["ok"]
    assert by_name["index_apply_rows_in_window"]["value"] == 7 * 33
    assert by_name["index_probe_outpoints_in_window"]["value"] >= 7 * 16
    assert by_name["index_entries_after_last_push"]["value"] == 3000 + live
    assert by_name["durable_height"]["value"] == 11
    fill = [ln for ln in lines if ln.startswith("[fill] ")]
    assert len(fill) == 2 and "3000 filler rows" in fill[0]
    assert any(ln.startswith("[setup] index built over unspent_outputs")
               and "capacity 4096" in ln for ln in lines)
    assert any(ln.startswith("[window] the height-10 push") for ln in lines)
    assert any("the program has the delta index" in ln for ln in lines)


def test_a_program_without_the_delta_index_ends_before_any_block():
    rc, lines = drive(fault="no_delta_index")
    assert rc == 1
    assert lines[-1].startswith("FAILED: needs_the_delta_index")
    assert not any(ln.startswith(("[fixture]", "[fill]", "[push"))
                   for ln in lines)


@pytest.mark.parametrize("control,fault,check", [
    ("sql_scan", "-", "index_probe_outpoints_in_window"),
    ("stale_index", "-", "index_apply_rows_in_window"),
    ("host_verify", "-", "p256_lanes_real_in_window"),
    (None, "consulted", "index_shadow_consults_in_window"),
    (None, "host_fell", "resilience_device_fallback"),
])
def test_each_control_comes_out_not_correct(control, fault, check):
    rc, lines = drive(control=control, fault=fault)
    assert rc == 0, lines[-5:]
    assert json.loads(lines[-1])["correct"] is False
    assert check in _failed(lines), _failed(lines)


def test_the_driver_names_its_controls_and_the_launcher_has_the_fault():
    driver = manifest.load_module("drivers", "utxo_accept")
    assert set(driver.CONTROLS) == {
        "host_verify", "unverified", "forged_unmarked", "sql_scan",
        "stale_index"}
    assert os.path.isfile(driver.INDEX_LAUNCHER)
    with open(driver.INDEX_LAUNCHER) as f:
        assert 'FAULTS["stale_index"]' in f.read()


def test_the_cell_finds_every_file_it_names():
    mf = manifest.load_manifest()
    cell = manifest.find_cell(mf, "utxo-at-scale")
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    config = manifest.load_config(mf, cell)
    entry = next(c for c in mf["configs"] if c["name"] == cell["config"])
    assert config["source"] == entry["source"] and \
        len(entry["source"]) <= 200
    assert set(entry["reduced"]) == set(config["reduced"])
    assert config["children"]["1"]["node_config"]["device"] == {
        "device": "tpu", "utxo_index": True, "verify_microbatch": 0,
        "txid_backend": "host"}
    assert "utxo_index" not in config["base_child"]["node_config"]["device"]
    traffic = manifest.load_traffic(cell["traffic"])
    assert traffic["driver"] == "utxo_accept"
    assert traffic["utxo_fill"] == config["utxo_set"] == 4_000_000
    assert traffic["fan_out"] * traffic["per_output"] == 8160
    assert traffic["valid_blocks"] == 15 and traffic["traced_window_s"] == 6
    e2e = {m["name"] for m in manifest.end_to_end_for(mf, cell["name"])}
    assert e2e == {"accept_tx_per_s", "accept_s_p50", "setup_s"}
    layer = manifest.layer_metrics_for(mf, cell["name"])
    assert len(layer) == 15 + 9
    for m, spec in layer:
        for key in ("layer", "unit", "moves", "source"):
            assert spec[key] == m[key], (m["name"], key)
        assert callable(manifest.load_module("readers",
                                             spec["reader"]).read)
    scan = next(m for m in mf["per_layer"]
                if m["name"] == "spend_scan_ms.accept")
    assert scan["workloads"] == ["accept-2mb-cold", "utxo-at-scale"]
