"""Finds a cell's files by the names in BENCHMARK.json, so that a later PR
adds a configuration, a traffic mix, a metric or a driver by adding files
and entries only:

    BENCHMARK.json workload   -> config name, traffic name, chips
    configs[name].file        -> the configuration (JSON), its templates
                                 and its plain reference beside it
    traffic/<traffic>.json    -> the mix's parameters; its "driver" key
                                 names drivers/<driver>.py
    metrics/<metric>.py       -> one reader per metric, end-to-end or
                                 per-layer: read(obs) -> number or None
    gen/constraints/<g>.py    -> a configuration's constraint entry
                                 {"generator": g, ...}: build(entry,
                                 config, seed) -> constraints
    gen/inventory/<g>.py      -> the same for an inventory entry:
                                 build(entry, config, seed) -> objects
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, plus "dir"
    traffic: dict         # the mix's parameters, plus "name"
    end_to_end: list      # metric entries this cell reports, --trace 0
    per_layer: list       # metric entries this cell reports, --trace 1
    root: str = field(default=CHECKOUT)


def load_module(path: str, name: str):
    """Import a file by path (names may hold '.' and '-')."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str) -> bool:
    wl = metric.get("workloads")
    return wl is None or cell in wl


def resolve(workload: str, root: str = CHECKOUT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_path = os.path.join(root, configs[w["config"]]["file"])
    with open(cfg_path) as f:
        config = json.load(f)
    config["dir"] = os.path.dirname(cfg_path)
    bench_dir = os.path.join(root, bench["paths"][0])
    with open(os.path.join(bench_dir, "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    traffic["name"] = w["traffic"]
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload) and m["moves"] in e2e_names]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer,
                root=bench_dir)


def driver_for(cell: Cell):
    return load_module(
        os.path.join(cell.root, "drivers", cell.traffic["driver"] + ".py"),
        "bench_driver_" + cell.traffic["driver"])


def reference_for(cell: Cell):
    return load_module(
        os.path.join(cell.config["dir"], cell.config["reference"]),
        "bench_reference_" + cell.config["name"].replace("-", "_"))


def reader_for(root: str, metric: str):
    return load_module(os.path.join(root, "metrics", metric + ".py"),
                       "bench_metric_" + metric.replace(".", "_"))


def read_metrics(cell: Cell, entries: list, obs: dict) -> dict:
    """{name: {"value", "unit"}} for each metric whose reader found
    something to read; a reader that returns None is left out."""
    out = {}
    for m in entries:
        value = reader_for(cell.root, m["name"]).read(obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
