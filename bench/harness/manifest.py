"""``BENCHMARK.json``: its checks, and how a cell's files are found.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in files of its own, found by name:

* configuration ``<c>``: the file the manifest names (``bench/configs/
  <c>.json``), its plain reference ``<c>.ref.py`` and the adapter that hands
  it to the program, ``<c>.serve.py``, beside it;
* traffic mix ``<t>``: ``bench/traffic/<t>.json``;
* cell ``<w>``: how it serves its mix (``serve``), how much its correctness
  check reads (``check``) and that check's limits, ``bench/cells/<w>.json``;
* per-layer metric ``<m>``: its reader, ``bench/metrics/<m>.py``.

Adding any of them needs new files and new entries, and no edit here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")         # the system under test

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def traffic_path(name: str) -> str:
    return os.path.join(BENCH, "traffic", f"{name}.json")


def cell_path(name: str) -> str:
    return os.path.join(BENCH, "cells", f"{name}.json")


def metric_path(name: str) -> str:
    return os.path.join(BENCH, "metrics", f"{name}.py")


def config_sibling(config_file: str, suffix: str, root: str = ROOT) -> str:
    return os.path.join(root, config_file[: -len(".json")] + suffix)


def cells_reporting(m: dict, metric: dict) -> list[str]:
    """The cells that report ``metric``: its ``workloads``, or every cell."""
    return metric.get("workloads") or [w["name"] for w in m["workloads"]]


def check(m: dict, root: str = ROOT) -> list[str]:
    """What is wrong with manifest ``m``: names and units outside their
    alphabets, files that do not resolve, a per-layer metric whose cells do
    not all report the end-to-end metric it moves.  Empty when sound."""
    errs: list[str] = []
    configs = {c["name"]: c for c in m["configs"]}
    cells = {w["name"]: w for w in m["workloads"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    names = ([c["name"] for c in m["configs"]] + list(cells)
             + [e["name"] for e in m["end_to_end"]]
             + [p["name"] for p in m["per_layer"]]
             + [w[k] for w in m["workloads"] for k in ("config", "traffic")]
             + [k for c in m["configs"] for k in c["reduced"]])
    errs += [f"bad name {n!r}" for n in names if not NAME.match(n)]
    for kind, items in (("config", m["configs"]), ("cell", m["workloads"]),
                        ("metric", m["end_to_end"] + m["per_layer"])):
        seen = [i["name"] for i in items]
        errs += [f"duplicate {kind} {n!r}" for n in set(seen) if seen.count(n) > 1]
    for metric in m["end_to_end"] + m["per_layer"]:
        if not UNIT.match(metric["unit"]):
            errs.append(f"bad unit {metric['unit']!r} of {metric['name']}")
        if metric["better"] not in ("lower", "higher"):
            errs.append(f"{metric['name']}: better is {metric['better']!r}")
    for metric in m["end_to_end"]:
        if metric["source"] not in SOURCES_E2E:
            errs.append(f"{metric['name']}: end-to-end source {metric['source']!r}")
    if "setup_s" not in e2e:
        errs.append("no setup_s")
    for c in m["configs"]:
        if not os.path.isfile(os.path.join(root, c["file"])):
            errs.append(f"config {c['name']}: no file {c['file']}")
        for suffix in (".ref.py", ".serve.py"):
            if not os.path.isfile(config_sibling(c["file"], suffix, root)):
                errs.append(f"config {c['name']}: no {suffix} beside {c['file']}")
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    errs += [f"pair {p} appears twice" for p in set(pairs) if pairs.count(p) > 1]
    for w in m["workloads"]:
        if w["config"] not in configs:
            errs.append(f"cell {w['name']}: unknown config {w['config']!r}")
        if not os.path.isfile(traffic_path(w["traffic"])):
            errs.append(f"cell {w['name']}: no traffic file for {w['traffic']!r}")
        if not os.path.isfile(cell_path(w["name"])):
            errs.append(f"cell {w['name']}: no cell file (limits of its check)")
    for metric in m["per_layer"]:
        if metric["source"] not in SOURCES:
            errs.append(f"{metric['name']}: source {metric['source']!r}")
        if not os.path.isfile(metric_path(metric["name"])):
            errs.append(f"metric {metric['name']}: no reader")
        moved = e2e.get(metric["moves"])
        if moved is None:
            errs.append(f"{metric['name']} moves unknown {metric['moves']!r}")
            continue
        for w in metric.get("workloads") or list(cells):
            if w not in cells:
                errs.append(f"{metric['name']}: unknown cell {w!r}")
            elif w not in cells_reporting(m, moved):
                errs.append(f"{metric['name']}: cell {w} does not report {moved['name']}")
    for w in cells:
        if not any(w in cells_reporting(m, p) for p in m["per_layer"]):
            errs.append(f"cell {w}: no per-layer metric")
        if not any(w in cells_reporting(m, e) for e in m["end_to_end"]
                   if e["name"] != "setup_s"):
            errs.append(f"cell {w}: no end-to-end metric besides setup_s")
    return errs


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    cfg: dict
    traffic_name: str
    spec: dict                 # bench/cells/<name>.json
    end_to_end: list           # manifest entries this cell reports
    per_layer: list
    ref: object                # <config>.ref.py
    serve: object              # <config>.serve.py
    readers: dict              # per-layer metric name -> reader module

    @property
    def limits(self) -> dict:
        return self.spec.get("limits", {})


def resolve(m: dict, workload: str, root: str = ROOT) -> Cell:
    errs = check(m, root)
    if errs:
        raise SystemExit("BENCHMARK.json: " + "; ".join(errs))
    cells = {w["name"]: w for w in m["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    w = cells[workload]
    centry = next(c for c in m["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, centry["file"])) as f:
        cfg = json.load(f)
    with open(cell_path(workload)) as f:
        spec = json.load(f)
    tag = re.sub(r"\W", "_", w["config"])
    per_layer = [p for p in m["per_layer"] if workload in cells_reporting(m, p)]
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"], cfg=cfg,
        traffic_name=w["traffic"], spec=spec,
        end_to_end=[e for e in m["end_to_end"] if workload in cells_reporting(m, e)],
        per_layer=per_layer,
        ref=load_module(config_sibling(centry["file"], ".ref.py", root), f"ref_{tag}"),
        serve=load_module(config_sibling(centry["file"], ".serve.py", root), f"serve_{tag}"),
        readers={p["name"]: load_module(metric_path(p["name"]), f"metric_{p['name']}")
                 for p in per_layer},
    )
