"""Find a cell's files by the names in BENCHMARK.json.

A cell is an entry of ``workloads``. Its configuration is the file the
``configs`` entry names; its traffic is ``perfbench/traffic/<traffic>.json``;
numbers that belong to the one cell (a served cell's rate, found by its own
sweep) are in ``perfbench/cells/<cell>.json`` and override the traffic
file's, key by key. Code that belongs to one traffic kind, one arrival
schedule or one per-layer metric is a file too, found by the name the data
gives: ``kinds/<kind>.py``, ``arrivals/<name>.py``, ``metrics/<stem>.py``. A
later PR adds a cell by adding an entry and files: nothing here names a
cell, a configuration, a traffic mix, a kind, a schedule or a metric.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merged(out[key], value)
        else:
            out[key] = value
    return out


class Cell:
    def __init__(self, name: str, bench: dict | None = None):
        bench = bench or benchmark()
        entry = next((w for w in bench["workloads"] if w["name"] == name), None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        cfg = next(c for c in bench["configs"] if c["name"] == self.config_name)
        self.config = _load(os.path.join(ROOT, cfg["file"]))
        traffic = _load(os.path.join(BENCH, "traffic", self.traffic_name + ".json"))
        own = os.path.join(BENCH, "cells", name + ".json")
        if os.path.exists(own):
            traffic = merged(traffic, _load(own))
        self.traffic = traffic

        def reported(metric: dict) -> bool:
            return name in metric.get("workloads", [name])

        self.end_to_end = [m for m in bench["end_to_end"] if reported(m)]
        self.per_layer = [m for m in bench["per_layer"] if reported(m)]


def stem(name: str) -> str:
    """``step_ms.flood`` -> ``step_ms``: a metric's name is the quantity,
    then, after a dot, the cells it is reported in. One quantity has one
    reader and one arithmetic; the suffix keeps apart cells whose end-to-end
    metrics, or whose bounds, differ."""
    return name.split(".", 1)[0]


def by_file(folder: str, name: str):
    """The module ``perfbench/<folder>/<name>.py``, loaded from its file."""
    path = os.path.join(BENCH, folder, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"perfbench/{folder}/{name}.py is not there: {name!r} is named by the "
            "benchmark's data and has to come with its file"
        )
    spec = importlib.util.spec_from_file_location(f"perfbench_{folder}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str):
    """The reader of one per-layer metric: ``perfbench/metrics/<stem>.py``,
    a module with ``read(ctx)`` that returns the number, or None where it
    finds nothing to read."""
    return by_file("metrics", stem(name)).read


def kind(name: str):
    """A traffic kind, the traffic file's ``kind``: ``perfbench/kinds/<kind>.py``,
    a module with ``run(cell, opt)`` that returns the result line. It states
    what it runs (``RUNS``) and refuses a configuration that states otherwise."""
    return by_file("kinds", name)


def arrivals(name: str):
    """An arrival schedule, a served traffic file's ``arrivals``:
    ``perfbench/arrivals/<name>.py`` with ``offsets_ns(n_txs, rate_tps, seed,
    params)``, the due time of each tx in ns after the schedule's start,
    never falling."""
    return by_file("arrivals", name).offsets_ns
