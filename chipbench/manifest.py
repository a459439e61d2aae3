"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name in ``BENCHMARK.json``:
``configs/<config>.json`` (its ``file``), ``traffic/<traffic>.json``,
``layer_metrics/<metric>.json``. Their ``kind`` / ``reader`` names a
module under ``builders/``, ``drivers/``, ``readers/``. A later PR adds
files and entries and edits none.
"""
from __future__ import annotations

import importlib
import json
import os
import re
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter",
           "host_clock")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load(path: str = os.path.join(ROOT, "BENCHMARK.json")) -> dict:
    return load_json(path)


def reports(metric: dict, cell: str) -> bool:
    """A metric with no ``workloads`` key is reported by every cell."""
    return "workloads" not in metric or cell in metric["workloads"]


def cell(bench: dict, name: str) -> dict:
    """One cell with its files loaded: the workload entry, the
    configuration, the traffic mix, the names of its end-to-end
    metrics and its per-layer metrics with their reader files."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(
            f"chipbench: no workload {name!r} in BENCHMARK.json "
            f"(have {sorted(by_name)})")
    w = by_name[name]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == w["config"])
    layers = []
    for m in bench["per_layer"]:
        if reports(m, name):
            spec = load_json(os.path.join(
                HERE, "layer_metrics", m["name"] + ".json"))
            layers.append(dict(spec, name=m["name"], unit=m["unit"]))
    return {
        "workload": w,
        "config": load_json(os.path.join(ROOT, cfg_entry["file"])),
        "traffic": load_json(os.path.join(
            HERE, "traffic", w["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"]
                       if reports(m, name)],
        "per_layer": layers,
    }


def module(group: str, name: str):
    """``builders/<kind>.py``, ``drivers/<kind>.py`` or
    ``readers/<reader>.py``, found by name."""
    if not NAME_RE.match(name):
        raise SystemExit(f"chipbench: bad {group} name {name!r}")
    return importlib.import_module(f"chipbench.{group}.{name}")


def check(bench: dict) -> List[str]:
    """Every fault found in the manifest and its files (empty = sound):
    each workload resolves to files, each ``moves`` names an end-to-end
    metric that every cell of the per-layer metric reports, names and
    units use only the allowed characters, every file's kind or reader
    resolves to a module."""
    bad: List[str] = []
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    cfgs = {c["name"]: c for c in bench["configs"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s among end_to_end")
    for m in list(bench["end_to_end"]) + list(bench["per_layer"]):
        if not NAME_RE.match(m["name"]):
            bad.append(f"metric name {m['name']!r}")
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"unit {m['unit']!r} of {m['name']}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"better of {m['name']}")
        if m["source"] not in SOURCES:
            bad.append(f"source of {m['name']}")
        for c in m.get("workloads", ()):
            if c not in cells:
                bad.append(f"{m['name']} lists unknown cell {c}")
    for m in bench["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {m['name']} source {m['source']}")
    for w in bench["workloads"]:
        for key in ("name", "config", "traffic"):
            if not NAME_RE.match(w[key]):
                bad.append(f"workload {key} {w[key]!r}")
        if w["config"] not in cfgs:
            bad.append(f"{w['name']}: unknown config {w['config']}")
            continue
        try:
            c = cell(bench, w["name"])
            module("builders", c["config"]["kind"])
            module("drivers", c["traffic"]["kind"])
            if c["config"].get("chips", 1) != w["chips"]:
                bad.append(f"{w['name']}: chips differ from its config")
        except (OSError, KeyError, ValueError, SystemExit,
                ImportError) as e:
            bad.append(f"{w['name']}: {type(e).__name__}: {e}")
            continue
        mine = [m["name"] for m in c["end_to_end"]]
        if "setup_s" not in mine or len(mine) < 2:
            bad.append(f"{w['name']}: needs setup_s and one more "
                       f"end-to-end metric, has {mine}")
        if not c["per_layer"]:
            bad.append(f"{w['name']}: no per-layer metric")
    for m in bench["per_layer"]:
        path = os.path.join(HERE, "layer_metrics", m["name"] + ".json")
        try:
            spec = load_json(path)
            module("readers", spec["reader"])
        except (OSError, KeyError, ValueError, SystemExit,
                ImportError) as e:
            bad.append(f"{m['name']}: {type(e).__name__}: {e}")
            continue
        for key in ("layer", "unit", "moves"):
            if spec.get(key) != m[key]:
                bad.append(f"{m['name']}: {key} differs between "
                           f"BENCHMARK.json and its file")
        if m["moves"] not in e2e:
            bad.append(f"{m['name']} moves unknown {m['moves']}")
            continue
        for c in cells:
            if reports(m, c) and not reports(e2e[m["moves"]], c):
                bad.append(f"{m['name']} is reported in {c}, which "
                           f"does not report {m['moves']}")
    for c in bench["configs"]:
        if not any(w["config"] == c["name"] for w in bench["workloads"]):
            bad.append(f"config {c['name']} is used by no cell")
    return bad
