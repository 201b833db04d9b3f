"""A benchmark root at a size a CPU test can hold: the real BENCHMARK.json,
drivers, metrics, traffic mixes and generators, and each configuration's
directory linked entry by entry, with only its file rewritten with the
counts cut. A configuration's relative paths (templates, references, any
file of its own) so resolve in the tiny root without this module knowing
its keys."""

from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)

# a cluster inventory's counts are divided by this; a tenth of each
# kind breaks a rule (at least one)
SHRINK = 200


def _cut(entries: list) -> None:
    for e in entries:
        if "counts" in e:
            e["counts"] = {k: max(1, n // SHRINK)
                           for k, n in e["counts"].items()}
            e["breakers"] = {k: max(1, n // 10)
                             for k, n in e["counts"].items()}


def _link(src: str, dst: str) -> None:
    if not os.path.lexists(dst):
        os.symlink(src, dst)


def make_root(tmp: str, extra_cells: list | None = None) -> str:
    """Write <tmp>/BENCHMARK.json and <tmp>/benchmark/ (links to the
    real drivers, metrics, traffic mixes and generators, and to every
    entry of each configuration's directory but its cut file); return
    tmp."""
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    broot = os.path.join(tmp, "benchmark")
    os.makedirs(broot, exist_ok=True)
    for sub in ("drivers", "metrics", "traffic", "gen"):
        _link(os.path.join(BENCH, sub), os.path.join(broot, sub))
    for c in bench["configs"]:
        src = os.path.join(CHECKOUT, c["file"])
        dst = os.path.join(tmp, c["file"])
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        for entry in os.listdir(os.path.dirname(src)):
            _link(os.path.join(os.path.dirname(src), entry),
                  os.path.join(os.path.dirname(dst), entry))
        with open(src) as f:
            cfg = json.load(f)
        _cut(cfg["constraints"])
        _cut(cfg["inventory"])
        # the link to the real file goes first: writing through it would
        # change the real configuration
        os.remove(dst)
        with open(dst, "w") as f:
            json.dump(cfg, f)
    bench["workloads"] += extra_cells or []
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
