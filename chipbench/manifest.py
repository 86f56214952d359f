"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a mix or a metric by name:

* the configuration of a workload is the file its ``configs`` entry names;
  the file names its plain reference, ``references/<reference>.py``;
* the cell's engine settings and load are ``cells/<workload>.json``;
* the traffic mix is ``traffic/<traffic>.json``;
* a per-layer metric is read by ``metrics/<metric>.py``;
* the peaks of a device kind are in ``peaks.json``.

A later change adds a cell, a mix or a metric as new files and entries,
and edits no file that is here.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Dict, List

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> Dict:
    return load_json(root / "BENCHMARK.json")


def module(path: pathlib.Path):
    """Import the Python file at ``path`` as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def workload(bench: Dict, name: str, root: pathlib.Path = ROOT) -> Dict:
    """The workload entry ``name`` with its configuration, cell file, mix
    and reference resolved. Raises KeyError or FileNotFoundError when any
    of them is missing."""
    wl = {w["name"]: w for w in bench["workloads"]}[name]
    conf = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    spec = load_json(root / conf["file"])
    return {
        "entry": wl,
        "config": conf,
        "spec": spec,
        "cell": load_json(HERE / "cells" / f"{name}.json"),
        "mix": load_json(HERE / "traffic" / f"{wl['traffic']}.json"),
        "reference": HERE / "references" / f"{spec['reference']}.py",
    }


def end_to_end(bench: Dict, name: str) -> List[Dict]:
    """End-to-end metrics the workload reports."""
    return [m for m in bench["end_to_end"]
            if name in m.get("workloads", [name])]


def per_layer(bench: Dict, name: str) -> List[Dict]:
    """Per-layer metrics the workload reports: those that list it, and
    those without a list whose moved metric the workload reports."""
    moved = {m["name"] for m in end_to_end(bench, name)}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(metric: str):
    """The reader module of per-layer metric ``metric``."""
    return module(HERE / "metrics" / f"{metric}.py")


def peaks(kind: str) -> Dict:
    """Published peaks of device kind ``kind``; an unknown kind is an
    error, never a default."""
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]
