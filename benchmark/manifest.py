"""Reads ``BENCHMARK.json`` and finds, by name, the files a cell is made
of: its configuration, its traffic mix, its traffic kind, the statistics
of its end-to-end metrics, and the per-layer metrics with their readers.
Refuses what it cannot find.

A later PR adds a cell by adding files under this directory and one entry
to ``BENCHMARK.json``; nothing here lists the cells, mixes or metrics.
"""

from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _need(ok: bool, what: str) -> None:
    if not ok:
        raise ManifestError(what)


def _json(path: str) -> dict:
    _need(os.path.isfile(path), f"no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def _module(package: str, noun: str, name: str, needs):
    _need(bool(NAME.match(name)) and "." not in name,
          f"{noun} {name!r} is not a name")
    path = os.path.join(HERE, package, name + ".py")
    _need(os.path.isfile(path),
          f"unknown {noun} {name!r}: no benchmark/{package}/{name}.py")
    mod = importlib.import_module(f"{package}.{name}")
    for attr in needs:
        _need(hasattr(mod, attr), f"{package}/{name}.py defines no {attr}")
    return mod


def kind(name: str):
    return _module("kinds", "kind", name,
                   ("make_pool", "units", "Client", "decode", "expected"))


def reader(name: str):
    return _module("readers", "reader", name, ("read",))


def graph(name: str):
    return _module("graphs", "graph", name, ("build", "SCHEMA"))


def statistic(name: str):
    """How an end-to-end metric is taken from a window's records."""
    return _module("endtoend", "statistic", name, ("value",))


def metric_spec(name: str) -> dict:
    """A per-layer metric's reader and its parameters: the file named
    like the metric, or like the metric without its last ``.part``
    (``wave_rows.single`` reads ``metrics/wave_rows.json``), so that one
    quantity split over cells with different end-to-end metrics has one
    file.  Layer, unit, source and ``moves`` are ``BENCHMARK.json``'s."""
    for stem in (name, name.rpartition(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".json")
        if stem and os.path.isfile(path):
            return _json(path)
    raise ManifestError(f"metric {name}: no file benchmark/metrics/{name}.json")


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, doc: dict, entry: dict):
        self.name = entry["name"]
        self.chips = int(entry["chips"])
        conf = next(
            (c for c in doc["configs"] if c["name"] == entry["config"]), None
        )
        _need(conf is not None,
              f"cell {self.name}: no configuration {entry['config']!r}")
        self.config_name = conf["name"]
        self.config = _json(os.path.join(ROOT, conf["file"]))
        self.traffic_name = entry["traffic"]
        self.traffic = _json(
            os.path.join(HERE, "traffic", entry["traffic"] + ".json")
        )
        self.kind = kind(self.traffic["kind"])
        self.graph = graph(self.config["graph"]["kind"])
        rows = self.config.get("batch_rows")
        _need(rows is None or self.traffic.get("rows", rows) == rows,
              f"cell {self.name}: the mix sends {self.traffic.get('rows')} "
              f"rows a batch, the configuration states {rows}")
        self.end_to_end = [
            m for m in doc["end_to_end"]
            if self.name in m.get("workloads", [self.name])
        ]
        self.per_layer = []
        reports = {m["name"] for m in self.end_to_end}
        for m in doc["per_layer"]:
            # listed cells only; unlisted: every cell that reports what
            # the metric moves
            if self.name not in m.get("workloads", [self.name]) or (
                    m["moves"] not in reports):
                continue
            spec = metric_spec(m["name"])
            self.per_layer.append((m, spec, reader(spec["reader"])))


def load(path: str | None = None) -> dict:
    doc = _json(path or os.path.join(ROOT, "BENCHMARK.json"))
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"):
        _need(key in doc, f"BENCHMARK.json has no {key}")
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e.get("name", "") for e in doc[section]]
        for n in names:
            _need(bool(NAME.match(n)), f"{section}: {n!r} is not a name")
        _need(len(set(names)) == len(names),
              f"{section}: a name appears twice")
    for m in doc["end_to_end"] + doc["per_layer"]:
        _need(bool(UNIT.match(m.get("unit", ""))),
              f"metric {m['name']}: {m.get('unit')!r} is not a unit")
        _need(m.get("better") in ("lower", "higher"),
              f"metric {m['name']}: better is lower or higher")
        _need(m.get("source") in SOURCES,
              f"metric {m['name']}: unknown source {m.get('source')!r}")
    moved = {m["name"] for m in doc["end_to_end"]}
    for m in doc["per_layer"]:
        _need(m.get("moves") in moved,
              f"metric {m['name']} moves {m.get('moves')!r}, which is no "
              "end-to-end metric")
    return doc


def cell(doc: dict, name: str) -> Cell:
    entry = next((w for w in doc["workloads"] if w["name"] == name), None)
    _need(entry is not None, f"no cell {name!r} in BENCHMARK.json")
    return Cell(doc, entry)
