"""``BENCHMARK.json`` and the data files it names, read without JAX.

A cell is one entry of ``workloads``: a configuration under a traffic mix.
Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``, so a later PR adds a cell or a metric by adding files
and entries and edits nothing (benchmark/README.md):

    <dir>/configs/<config>.json    the configuration (its `file`)
    <dir>/traffic/<traffic>.json   job name + parameters of the mix
    <dir>/metrics/<metric>.json    reader (`module.function` under
                                   benchmark/readers/) + its arguments

``<dir>`` is the first entry of ``paths``. Code (jobs, readers, roofline
functions) is always this package's; data can come from another root, which
is how the tests add a throw-away cell in a temporary directory.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")


def _read(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    config_doc: Dict
    traffic_name: str
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]     # each with its metric file under "file"
    run_seconds: int


def load_benchmark(root: str = ROOT) -> Dict:
    return _read(os.path.join(root, "BENCHMARK.json"))


def data_dir(bench: Dict, root: str) -> str:
    return os.path.join(root, bench["paths"][0])


def applies(metric: Dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: "
                       f"{[w['name'] for w in bench['workloads']]})")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    base = data_dir(bench, root)
    per_layer = []
    for metric in bench["per_layer"]:
        if applies(metric, name):
            spec = _read(os.path.join(base, "metrics",
                                      f"{metric['name']}.json"))
            per_layer.append({**metric, "file": spec})
    return Cell(
        name=name, chips=int(entry["chips"]), why=entry["why"],
        config_name=entry["config"],
        config_doc=_read(os.path.join(root, config["file"])),
        traffic_name=entry["traffic"],
        traffic=_read(os.path.join(base, "traffic",
                                   f"{entry['traffic']}.json")),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=per_layer, run_seconds=int(bench["run_seconds"]))


def problems(root: str = ROOT) -> List[str]:
    """Everything in ``BENCHMARK.json`` that does not resolve: a name out
    of bounds or used twice, a missing file, a job or a reader that does
    not exist, a metric that moves nothing. Empty when all is well.
    (Imports the jobs and readers it names, and JAX with them.)"""
    import importlib
    bench = load_benchmark(root)
    out: List[str] = []
    names: List[str] = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names += [e["name"] for e in bench[group]]
    out += [f"bad name {n!r}" for n in names if not NAME.match(n)]
    out += [f"name used twice: {n!r}" for n in set(names)
            if names.count(n) > 1]
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        out += [f"{metric['name']}: unknown workload {w!r}"
                for w in metric.get("workloads", []) if w not in cells]
    for metric in bench["per_layer"]:
        if metric["moves"] not in end_to_end:
            out.append(f"{metric['name']} moves unknown {metric['moves']!r}")
    for w in bench["workloads"]:
        try:
            cell = load_cell(w["name"], root)
        except (OSError, KeyError, StopIteration, ValueError) as exc:
            out.append(f"{w['name']}: {type(exc).__name__}: {exc}")
            continue
        try:
            importlib.import_module(f"benchmark.jobs.{cell.traffic['job']}")
        except (ImportError, KeyError) as exc:
            out.append(f"{w['name']}: job: {exc}")
        if not any(m["name"] == "setup_s" for m in cell.end_to_end):
            out.append(f"{w['name']}: does not report setup_s")
        if len(cell.end_to_end) < 2:
            out.append(f"{w['name']}: no end-to-end metric beside setup_s")
        if not cell.per_layer:
            out.append(f"{w['name']}: no per-layer metric")
        reported = {m["name"] for m in cell.end_to_end}
        for metric in cell.per_layer:
            if metric["moves"] not in reported:
                out.append(f"{w['name']}: {metric['name']} moves "
                           f"{metric['moves']}, which the cell lacks")
            try:
                resolve_reader(metric["file"]["reader"])
            except (ImportError, AttributeError, ValueError) as exc:
                out.append(f"{metric['name']}: reader: {exc}")
    for config in bench["configs"]:
        if not any(w["config"] == config["name"]
                   for w in bench["workloads"]):
            out.append(f"config {config['name']!r} is used by no cell")
    return out


def resolve_reader(spec: str):
    """`module.function` -> the function of benchmark/readers/<module>.py."""
    import importlib
    module, _, function = spec.partition(".")
    return getattr(importlib.import_module(f"benchmark.readers.{module}"),
                   function)
