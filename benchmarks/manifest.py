"""``BENCHMARK.json`` and the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name in
``BENCHMARK.json``: ``configs[].file``,
``benchmarks/traffic/<traffic>.json`` and
``benchmarks/layer_metrics/<metric>.json``, all relative to the
directory that holds ``BENCHMARK.json`` (the checkout's root; the tests
point ``--root`` at a temporary one). A later PR adds a cell, a mix, a
configuration or a per-layer metric with new files and one entry here —
no file that is there is edited.
"""

from __future__ import annotations

import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
END_TO_END_SOURCES = ("host_clock", "device_trace")
#: how a declarative metric's ``value.kind`` maps to the contract's
#: ``source`` names
KIND_SOURCE = {"span": "program_span", "counter": "program_counter",
               "compile": "program_counter", "trace": "device_trace",
               "run_log": "host_clock", "client": "host_clock"}
REDUCTIONS = ("p50", "sum", "count", "per", "share_of",
              "inverse_share_of", "roofline")


class ManifestError(ValueError):
    pass


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str) -> None:
        self.root = os.path.abspath(root)
        self.doc = _load(os.path.join(self.root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}

    def path(self, rel: str) -> str:
        return os.path.join(self.root, rel)

    def cell(self, name: str) -> dict:
        try:
            return self.cells[name]
        except KeyError:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json (known: "
                f"{', '.join(sorted(self.cells))})") from None

    def config(self, cell: dict) -> dict:
        return _load(self.path(self.configs[cell["config"]]["file"]))

    def traffic(self, cell: dict) -> dict:
        return _load(self.path(os.path.join(
            "benchmarks", "traffic", cell["traffic"] + ".json")))

    def layer_metric(self, name: str) -> dict:
        return _load(self.path(os.path.join(
            "benchmarks", "layer_metrics", name + ".json")))

    def metrics_of(self, cell_name: str, group: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports:
        those without a ``workloads`` key and those that list it."""
        return [m for m in self.doc[group]
                if "workloads" not in m or cell_name in m["workloads"]]

    def validate(self) -> None:
        """The rules of the benchmark's contract that can be checked
        without a run; raises ManifestError naming the first breach."""
        doc = self.doc

        def need(cond, msg):
            if not cond:
                raise ManifestError(msg)

        need(set(doc) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"},
             f"BENCHMARK.json keys: {sorted(doc)}")
        need(isinstance(doc["run_seconds"], int)
             and 1 <= doc["run_seconds"] <= 51, "run_seconds out of range")
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [e["name"] for e in doc[group]]
            need(len(names) == len(set(names)), f"duplicate name in {group}")
            for n in names:
                need(NAME_RE.match(n), f"bad name {n!r} in {group}")
        need(1 <= len(doc["workloads"]) <= 24, "1 to 24 workloads")
        four = [w for w in doc["workloads"] if w["chips"] == 4]
        need(len(four) <= max(1, len(doc["workloads"]) // 2),
             "more than half of the cells ask for 4 chips")
        pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
        need(len(pairs) == len(set(pairs)), "a config/traffic pair twice")
        used = {w["config"] for w in doc["workloads"]}
        for c in doc["configs"]:
            need(c["name"] in used, f"config {c['name']} used by no cell")
            need(os.path.exists(self.path(c["file"])),
                 f"config file {c['file']} missing")
            need(any(c["file"].startswith(p + "/") for p in doc["paths"]),
                 f"config file {c['file']} outside paths")
        for w in doc["workloads"]:
            need(w["config"] in self.configs, f"{w['name']}: no such config")
            need(w["chips"] in (1, 4), f"{w['name']}: chips")
            need(NAME_RE.match(w["traffic"]), f"{w['name']}: traffic name")
            need(1 <= len(w["why"]) <= 200 and "\n" not in w["why"],
                 f"{w['name']}: why")
            mix = self.traffic(w)
            need(mix["chips"] == w["chips"],
                 f"{w['name']}: the mix file says {mix['chips']} chip(s)")
        need("setup_s" in self.end_to_end, "no setup_s")
        for m in doc["end_to_end"] + doc["per_layer"]:
            need(UNIT_RE.match(m["unit"]), f"{m['name']}: unit")
            need(m["better"] in ("lower", "higher"), f"{m['name']}: better")
            need(m["source"] in SOURCES, f"{m['name']}: source")
            for c in m.get("workloads", []):
                need(c in self.cells, f"{m['name']}: no cell {c}")
        for m in doc["end_to_end"]:
            need(m["source"] in END_TO_END_SOURCES, f"{m['name']}: source")
            need(0 < m["bound"] <= 0.25, f"{m['name']}: bound")
        for m in doc["per_layer"]:
            need(m["moves"] in self.end_to_end,
                 f"{m['name']}: moves {m['moves']!r} is no end-to-end "
                 "metric")
            moved = self.end_to_end[m["moves"]]
            for c in m.get("workloads", list(self.cells)):
                need("workloads" not in moved or c in moved["workloads"],
                     f"{m['name']}: cell {c} does not report {m['moves']}")
            decl = self.layer_metric(m["name"])
            for key in ("name", "unit", "better", "layer", "moves"):
                need(decl[key] == m[key],
                     f"{m['name']}: {key} differs between BENCHMARK.json "
                     "and its layer_metrics file")
            need(KIND_SOURCE[decl["value"]["kind"]] == m["source"],
                 f"{m['name']}: source does not match its value kind")
            need(decl["reduce"] in REDUCTIONS, f"{m['name']}: reduce")
        for w in doc["workloads"]:
            e2e = [m["name"] for m in self.metrics_of(w["name"],
                                                      "end_to_end")]
            need("setup_s" in e2e and len(e2e) >= 2,
                 f"{w['name']}: needs setup_s and one more metric")
            need(self.metrics_of(w["name"], "per_layer"),
                 f"{w['name']}: no per-layer metric")
