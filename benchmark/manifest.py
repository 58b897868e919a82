"""BENCHMARK.json and the data files it names, loaded and cross-checked.

A cell names a configuration and a traffic mix; the mix's file names the
runner; every per-layer metric has a reader file of its own. All are
found by name, so a later PR adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file
    traffic_name: str
    traffic: dict         # the traffic mix file
    end_to_end: dict      # metric name -> unit, as declared for this cell
    per_layer: dict       # metric name -> unit
    readers: dict         # per-layer metric name -> its reader file

    def declared(self, trace: bool) -> dict:
        return self.per_layer if trace else self.end_to_end


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))

    def _in_cell(self, metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def config_file(self, name: str) -> str:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return os.path.join(self.root, c["file"])
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    @staticmethod
    def traffic_file(name: str) -> str:
        return os.path.join(HERE, "traffic", name + ".json")

    @staticmethod
    def reader_file(metric: str) -> str:
        return os.path.join(HERE, "layer_metrics", metric + ".json")

    def cell(self, name: str) -> Cell:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                break
        else:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json; it has "
                f"{[w['name'] for w in self.doc['workloads']]}")
        per_layer = {m["name"]: m["unit"] for m in self.doc["per_layer"]
                     if self._in_cell(m, name)}
        return Cell(
            name=name, chips=w["chips"], config_name=w["config"],
            config=_load(self.config_file(w["config"])),
            traffic_name=w["traffic"],
            traffic=_load(self.traffic_file(w["traffic"])),
            end_to_end={m["name"]: m["unit"] for m in self.doc["end_to_end"]
                        if self._in_cell(m, name)},
            per_layer=per_layer,
            readers={m: _load(self.reader_file(m)) for m in per_layer})

    def problems(self) -> list[str]:
        """What is wrong with the manifest and its data files; [] if sound.
        Covers what this harness relies on, plus the contract's rules on
        names and units (the driver checks the rest of the contract)."""
        d, bad = self.doc, []
        names = lambda rows: [r["name"] for r in rows]  # noqa: E731
        for kind in ("configs", "workloads", "end_to_end", "per_layer"):
            for n in names(d[kind]):
                if not NAME.match(n):
                    bad.append(f"{kind}: {n!r} is not a name")
            if len(set(names(d[kind]))) != len(d[kind]):
                bad.append(f"{kind}: a name appears twice")
        if set(names(d["end_to_end"])) & set(names(d["per_layer"])):
            bad.append("a metric is both end-to-end and per-layer")
        if "setup_s" not in names(d["end_to_end"]):
            bad.append("end_to_end lacks setup_s")
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT.match(m["unit"]):
                bad.append(f"metric {m['name']}: {m['unit']!r} is not a unit")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"metric {m['name']}: better is {m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"metric {m['name']}: source {m['source']!r}")
            for w in m.get("workloads", ()):
                if w not in names(d["workloads"]):
                    bad.append(f"metric {m['name']} lists unknown cell {w!r}")
        for m in d["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                bad.append(f"end-to-end metric {m['name']} read from the program")
            if not 0 < m["bound"] <= 0.1:
                bad.append(f"metric {m['name']}: bound {m['bound']}")
        used = set()
        for w in d["workloads"]:
            used.add(w["config"])
            if w["config"] not in names(d["configs"]):
                bad.append(f"cell {w['name']}: no configuration {w['config']!r}")
                continue
            if not NAME.match(w["traffic"]):
                bad.append(f"cell {w['name']}: traffic {w['traffic']!r}")
            if not os.path.exists(self.traffic_file(w["traffic"])):
                bad.append(f"cell {w['name']}: no traffic file for {w['traffic']!r}")
                continue
            if w["chips"] not in (1, 4):
                bad.append(f"cell {w['name']}: chips {w['chips']}")
            try:
                cell = self.cell(w["name"])
            except (OSError, ValueError, KeyError) as e:
                bad.append(f"cell {w['name']}: {type(e).__name__}: {e}")
                continue
            if cell.config.get("chips") != w["chips"]:
                bad.append(f"cell {w['name']}: asks for {w['chips']} chips, its "
                           f"configuration for {cell.config.get('chips')}")
            runner = cell.traffic.get("runner", "")
            if not os.path.exists(os.path.join(HERE, "runners", runner + ".py")):
                bad.append(f"cell {w['name']}: no runner {runner!r}")
            env = cell.traffic.get("worker_env", {})
            if not (isinstance(env, dict) and all(
                    isinstance(k, str) and isinstance(v, str) for k, v in env.items())):
                bad.append(f"cell {w['name']}: its mix's worker_env is not a "
                           f"table of strings")
            if set(cell.end_to_end) <= {"setup_s"}:
                bad.append(f"cell {w['name']} reports no end-to-end metric but setup_s")
            if not cell.per_layer:
                bad.append(f"cell {w['name']} reports no per-layer metric")
            for m in d["per_layer"]:
                if m["name"] not in cell.per_layer:
                    continue
                reader = cell.readers[m["name"]]
                if m["moves"] not in cell.end_to_end:
                    bad.append(f"{m['name']} moves {m['moves']}, which cell "
                               f"{w['name']} does not report")
                for k in ("layer", "unit", "moves"):
                    if reader.get(k) != m[k]:
                        bad.append(f"{m['name']}: {k} differs between its "
                                   f"reader file and BENCHMARK.json")
        for c in d["configs"]:
            if c["name"] not in used:
                bad.append(f"configuration {c['name']} is used by no cell")
            if not c["file"].startswith(tuple(p + "/" for p in d["paths"])):
                bad.append(f"configuration file {c['file']} lies outside paths")
        return bad
