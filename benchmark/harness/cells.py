"""Finding a cell's files by the names in BENCHMARK.json.

A cell is ``<config>.<traffic>``. Its configuration is
``benchmark/configs/<config>.json``, its traffic
``benchmark/traffic/<traffic>.json``; the traffic file names its kind
(``benchmark/traffic_kinds/<kind>.py``) and the configuration its runner
(``benchmark/runners/<runner>.py``). A per-layer metric is
``benchmark/layer_metrics/<name>.py``. Adding any of them is adding a
file and an entry; nothing here knows a name.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark_json() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def peaks_for(device_kind: str) -> Dict[str, Any]:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json: "
            "add it with its source, there is no default")
    return table[device_kind]


def load_cell(name: str) -> Dict[str, Any]:
    """The cell's entry with its configuration and traffic read in."""
    bench = benchmark_json()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       f"{sorted(cells)}")
    cell = dict(cells[name])
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    cell["config_data"] = load_json(os.path.join(ROOT, cfg_entry["file"]))
    cell["traffic_data"] = load_json(os.path.join(
        BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    cell["end_to_end"] = [
        m for m in bench["end_to_end"]
        if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [
        m for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m
            else m["moves"] in e2e_names)]
    return cell


def runner_module(cell):
    return importlib.import_module(
        "benchmark.runners." + cell["config_data"]["runner"])


def kind_module(cell):
    return importlib.import_module(
        "benchmark.traffic_kinds." + cell["traffic_data"]["kind"])


def layer_metric_readers(cell) -> List[Any]:
    """One module per per-layer metric of this cell, loaded by path (a
    metric's name may hold dots)."""
    out = []
    for m in cell["per_layer"]:
        path = os.path.join(BENCH_DIR, "layer_metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmark_layer_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for key in ("name", "unit", "layer", "moves", "source"):
            if getattr(mod, key.upper()) != m[key]:
                raise ValueError(
                    f"{path}: {key.upper()}={getattr(mod, key.upper())!r} "
                    f"but BENCHMARK.json says {m[key]!r}")
        out.append(mod)
    return out
