"""BENCHMARK.json and the data files it names, loaded and cross-checked.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the NAME the manifest
gives it:

    benchmark/configs/<config>.json          sizes as run, source, changes
    benchmark/traffic/<traffic>.json         parameters of one mix; its
                                             ``kind`` picks benchmark/runners/<kind>.py
    benchmark/layer_metrics/<metric>.json    layer, unit, moves, the reader
                                             (benchmark/readers/<reader>.py) and its args

so a later PR adds a cell, a mix, a configuration or a metric by adding
files and one entry to BENCHMARK.json, editing nothing that exists.
"""
from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def traffic(name: str) -> dict:
    return _json(os.path.join(HERE, "traffic", f"{name}.json"))


def layer_metric(name: str) -> dict:
    return _json(os.path.join(HERE, "layer_metrics", f"{name}.json"))


def cell(manifest: dict, name: str) -> Dict[str, Any]:
    """One workload with its configuration and traffic files loaded and
    the metrics that apply to it."""
    w = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if w is None:
        raise SystemExit(f"no workload {name!r}; known: "
                         f"{[w['name'] for w in manifest['workloads']]}")
    c = next(c for c in manifest["configs"] if c["name"] == w["config"])

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {"name": name, "chips": int(w["chips"]), "config_name": c["name"],
            "config": _json(os.path.join(ROOT, c["file"])),
            "traffic_name": w["traffic"], "traffic": traffic(w["traffic"]),
            "end_to_end": [m for m in manifest["end_to_end"] if applies(m)],
            "per_layer": [m for m in manifest["per_layer"] if applies(m)]}


def problems(manifest: dict) -> List[str]:
    """Every way the manifest and its files disagree with the contract's
    local rules or with each other (benchmark/selftest.py wants none)."""
    out: List[str] = []
    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for c in manifest["configs"]:
        if not os.path.exists(os.path.join(ROOT, c["file"])):
            out.append(f"config {c['name']}: no file {c['file']}")
        elif not any(c["file"].startswith(p.rstrip("/") + "/") for p in manifest["paths"]):
            out.append(f"config {c['name']}: {c['file']} is outside paths")
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    for n in set(names):
        if names.count(n) > 1:
            out.append(f"metric {n} defined twice")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not NAME.match(m["name"]):
            out.append(f"metric name {m['name']!r} has characters outside the contract")
        if not UNIT.match(m["unit"]):
            out.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher") or m["source"] not in SOURCES:
            out.append(f"metric {m['name']}: better/source")
        for w in m.get("workloads", []):
            if w not in [x["name"] for x in manifest["workloads"]]:
                out.append(f"metric {m['name']}: unknown workload {w}")
    for m in manifest["end_to_end"]:
        if not 0.01 <= m["bound"] <= 0.1 or m["source"] not in ("host_clock", "device_trace"):
            out.append(f"end-to-end {m['name']}: bound or source")
    for m in manifest["per_layer"]:
        if m["moves"] not in e2e:
            out.append(f"per-layer {m['name']}: moves unknown metric {m['moves']}")
        try:
            spec = layer_metric(m["name"])
        except OSError:
            out.append(f"per-layer {m['name']}: no benchmark/layer_metrics/{m['name']}.json")
            continue
        for k in ("layer", "unit", "moves"):
            if spec.get(k) != m[k]:
                out.append(f"per-layer {m['name']}: {k} differs between manifest and file")
        if not os.path.exists(os.path.join(HERE, "readers", spec["reader"] + ".py")):
            out.append(f"per-layer {m['name']}: no reader {spec['reader']}")
    seen = set()
    for w in manifest["workloads"]:
        if not NAME.match(w["name"]) or not NAME.match(w["traffic"]):
            out.append(f"workload {w['name']}: name characters")
        if w["config"] not in configs:
            out.append(f"workload {w['name']}: unknown config {w['config']}")
        if (w["config"], w["traffic"]) in seen:
            out.append(f"workload {w['name']}: pair appears twice")
        seen.add((w["config"], w["traffic"]))
        if len(w["why"]) > 200 or w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: why too long or chips")
        try:
            t = traffic(w["traffic"])
        except OSError:
            out.append(f"workload {w['name']}: no traffic file {w['traffic']}")
            continue
        if not os.path.exists(os.path.join(HERE, "runners", t["kind"] + ".py")):
            out.append(f"workload {w['name']}: no runner for kind {t['kind']}")
        c = cell(manifest, w["name"])
        if "setup_s" not in [m["name"] for m in c["end_to_end"]] or len(c["end_to_end"]) < 2:
            out.append(f"workload {w['name']}: needs setup_s and one more end-to-end metric")
        if not c["per_layer"]:
            out.append(f"workload {w['name']}: no per-layer metric")
        for m in c["per_layer"]:
            if m["moves"] not in [x["name"] for x in c["end_to_end"]]:
                out.append(f"workload {w['name']}: {m['name']} moves {m['moves']}, "
                           f"which this cell does not report")
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        out.append(f"{four} four-chip cells of {len(manifest['workloads'])}")
    return out
