"""BENCHMARK.json and the files it names: loading, resolving and checking.

A cell's name resolves to files by name alone:
``workloads[i].config``  -> ``<paths[0]>/configs/<config>.json`` (as ``configs[].file`` says),
``workloads[i].traffic`` -> ``<paths[0]>/traffic/<traffic>.json``,
every metric             -> ``<paths[0]>/metrics/<metric>.json`` (``setup_s`` has none:
the command takes it itself), whose ``reader`` is ``<file>.py:<function>``.
A later PR adds files and entries; nothing here lists them.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Callable, Dict, List, Optional

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.doc = _load(os.path.join(self.root, "BENCHMARK.json"))
        self.home = os.path.join(self.root, self.doc["paths"][0])
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}

    # -- resolving -----------------------------------------------------------
    def cell(self, name: str) -> Dict:
        if name not in self.cells:
            raise ManifestError(f"no workload named {name!r} in BENCHMARK.json")
        return self.cells[name]

    def config_file(self, config: str) -> str:
        return os.path.join(self.root, self.configs[config]["file"])

    def traffic_file(self, traffic: str) -> str:
        return os.path.join(self.home, "traffic", f"{traffic}.json")

    def metric_file(self, metric: str) -> str:
        return os.path.join(self.home, "metrics", f"{metric}.json")

    def load_config(self, config: str) -> Dict:
        return _load(self.config_file(config))

    def load_traffic(self, traffic: str) -> Dict:
        return _load(self.traffic_file(traffic))

    def metrics_of(self, cell: str, kind: str) -> List[Dict]:
        """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
        table = self.end_to_end if kind == "end_to_end" else self.per_layer
        return [m for m in table.values()
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str) -> Optional[Callable]:
        """The metric's reader function, found through its own file."""
        if metric == "setup_s":
            return None
        spec = _load(self.metric_file(metric))
        file, func = spec["reader"].split(":")
        path = os.path.join(self.root, file)
        mod_spec = importlib.util.spec_from_file_location(
            "benchmark_reader_" + re.sub(r"\W", "_", file), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        fn = getattr(mod, func)
        return lambda run: fn(run, spec)

    # -- checking ------------------------------------------------------------
    def check(self) -> List[str]:
        """Every fault found; an empty list means the manifest is sound."""
        errs: List[str] = []
        doc = self.doc

        def name_ok(n, what):
            if not isinstance(n, str) or not NAME_RE.match(n):
                errs.append(f"{what}: bad name {n!r}")

        for c in doc["configs"]:
            name_ok(c["name"], "config")
            if not os.path.isfile(os.path.join(self.root, c["file"])):
                errs.append(f"config {c['name']}: no file {c['file']}")
        used = set()
        for w in doc["workloads"]:
            name_ok(w["name"], "workload")
            name_ok(w["traffic"], "traffic")
            used.add(w["config"])
            if w["config"] not in self.configs:
                errs.append(f"workload {w['name']}: unknown config")
            if w["chips"] not in (1, 4):
                errs.append(f"workload {w['name']}: chips must be 1 or 4")
            if not 1 <= len(w["why"]) <= 200:
                errs.append(f"workload {w['name']}: why must be 1..200 chars")
            tf = self.traffic_file(w["traffic"])
            if not os.path.isfile(tf):
                errs.append(f"workload {w['name']}: no traffic file {tf}")
            elif not os.path.isfile(os.path.join(
                    self.home, "drivers", f"{_load(tf).get('driver')}.py")):
                errs.append(f"traffic {w['traffic']}: no such driver")
        for c in self.configs:
            if c not in used:
                errs.append(f"config {c}: used by no cell")
        if "setup_s" not in self.end_to_end:
            errs.append("end_to_end lacks setup_s")
        seen = set()
        for kind in ("end_to_end", "per_layer"):
            for m in doc[kind]:
                name_ok(m["name"], kind)
                if m["name"] in seen:
                    errs.append(f"metric {m['name']}: named twice")
                seen.add(m["name"])
                if not UNIT_RE.match(m["unit"]):
                    errs.append(f"metric {m['name']}: bad unit {m['unit']!r}")
                if m["better"] not in ("lower", "higher"):
                    errs.append(f"metric {m['name']}: bad better")
                if m["source"] not in SOURCES:
                    errs.append(f"metric {m['name']}: bad source")
                for w in m.get("workloads", []):
                    if w not in self.cells:
                        errs.append(f"metric {m['name']}: unknown cell {w}")
                if m["name"] != "setup_s":
                    mf = self.metric_file(m["name"])
                    if not os.path.isfile(mf):
                        errs.append(f"metric {m['name']}: no file {mf}")
                        continue
                    spec = _load(mf)
                    for key in ("unit", "better", "source"):
                        if spec.get(key) != m[key]:
                            errs.append(f"metric {m['name']}: {key} differs "
                                        "between BENCHMARK.json and its file")
                    file = spec["reader"].split(":")[0]
                    if not os.path.isfile(os.path.join(self.root, file)):
                        errs.append(f"metric {m['name']}: no reader {file}")
        for m in doc["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                errs.append(f"end_to_end {m['name']}: source must be the "
                            "benchmark's own clock or trace")
            if not 0 < m["bound"] <= 0.1:
                errs.append(f"end_to_end {m['name']}: bound out of range")
        for m in doc["per_layer"]:
            moved = self.end_to_end.get(m["moves"])
            if moved is None:
                errs.append(f"per_layer {m['name']}: moves unknown metric")
                continue
            for cell in m.get("workloads", list(self.cells)):
                if "workloads" in moved and cell not in moved["workloads"]:
                    errs.append(f"per_layer {m['name']}: cell {cell} does "
                                f"not report {m['moves']}")
        for cell in self.cells:
            if len(self.metrics_of(cell, "end_to_end")) < 2:
                errs.append(f"cell {cell}: needs setup_s and one more")
            if not self.metrics_of(cell, "per_layer"):
                errs.append(f"cell {cell}: reports no per-layer metric")
        four = sum(1 for w in doc["workloads"] if w["chips"] == 4)
        if four > max(1, len(doc["workloads"]) // 4):
            errs.append("too many four-chip cells")
        return errs
