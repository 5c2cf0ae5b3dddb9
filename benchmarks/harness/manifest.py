"""Finding a cell's files by the names in BENCHMARK.json.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file is the ``file`` of its ``configs`` entry;
the traffic mix is ``benchmarks/traffic/<traffic>.json`` and names its
driver, ``benchmarks/drivers/<driver>.py``; a per-layer metric is
``benchmarks/layer_metrics/<name>.json`` and names its reader,
``benchmarks/readers/<reader>.py``.  Nothing here knows a cell, a
configuration or a metric by name: a new one is new files plus entries
appended to BENCHMARK.json.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class BenchError(Exception):
    """The run cannot be made or has failed; no result line is printed."""


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise BenchError(f"cannot read {os.path.relpath(path, ROOT)}: {e}")
    except ValueError as e:
        raise BenchError(f"{os.path.relpath(path, ROOT)} is not JSON: {e}")


def load_manifest() -> dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"BENCHMARK.json has no workload {name!r}; it has "
                     f"{[c['name'] for c in manifest['workloads']]}")


def load_config(manifest: dict, cell: dict) -> dict:
    for entry in manifest["configs"]:
        if entry["name"] == cell["config"]:
            return _read_json(os.path.join(ROOT, entry["file"]))
    raise BenchError(f"cell {cell['name']!r} names configuration "
                     f"{cell['config']!r}, which BENCHMARK.json lacks")


def load_traffic(name: str, bench_dir: str = BENCH, _seen=()) -> dict:
    """``traffic/<name>.json``; an ``include`` key names another mix
    whose parameters this one starts from (its own keys win)."""
    if name in _seen:
        raise BenchError(f"traffic mixes include each other: {_seen}")
    own = _read_json(os.path.join(bench_dir, "traffic", name + ".json"))
    base = own.pop("include", None)
    if base is None:
        return own
    merged = load_traffic(base, bench_dir, _seen + (name,))
    merged.update(own)
    return merged


def reports(metric: dict, cell_name: str, manifest: dict) -> bool:
    """Does ``cell_name`` report this metric entry?  With a ``workloads``
    key: where it is listed.  Without: an end-to-end metric everywhere,
    a per-layer metric wherever the metric it moves is reported."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    for e2e in manifest["end_to_end"]:
        if e2e["name"] == moves:
            return reports(e2e, cell_name, manifest)
    raise BenchError(f"{metric['name']} moves {moves!r}, which is no "
                     "end-to-end metric")


def end_to_end_for(manifest: dict, cell_name: str) -> list:
    return [m for m in manifest["end_to_end"]
            if reports(m, cell_name, manifest)]


def layer_metrics_for(manifest: dict, cell_name: str,
                      bench_dir: str = BENCH) -> list:
    """[(BENCHMARK.json entry, layer_metrics/<name>.json)] of this cell."""
    out = []
    for m in manifest["per_layer"]:
        if reports(m, cell_name, manifest):
            spec = _read_json(os.path.join(
                bench_dir, "layer_metrics", m["name"] + ".json"))
            out.append((m, spec))
    return out


def load_module(kind: str, name: str, bench_dir: str = BENCH):
    """``benchmarks/<kind>/<name>.py`` as a module (kind: drivers,
    readers).  The name comes from a data file; a missing one is an
    error that names both."""
    if not name.replace("_", "").isalnum():
        raise BenchError(f"bad {kind} name {name!r}")
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind[:-1]} {name!r} ({kind}/{name}.py)")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def peaks(device_kind: str, bench_dir: str = BENCH) -> dict:
    """Published peaks of one chip, by ``device_kind``.  A kind that is
    not in the table is an error, never a default."""
    table = _read_json(os.path.join(bench_dir, "harness", "peaks.json"))
    if device_kind not in table["chips"]:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"harness/peaks.json ({sorted(table['chips'])})")
    return table["chips"][device_kind]
