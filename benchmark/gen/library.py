"""A configuration's library from its file and the run's seed: templates,
constraints and inventory, each entry drawn by a generator that a file of
its own holds, found by the name the entry gives:

    "templates":   paths (JSON or YAML) relative to the configuration
    "constraints": [{"generator": g, ...}]: gen/constraints/<g>.py
    "inventory":   [{"generator": g, ...}]: gen/inventory/<g>.py

Each generator file has `build(entry, config, seed) -> list`, and reads
its own keys from the entry. A configuration with new constraints or a
new inventory adds a generator file beside these; this module does not
change.
"""

from __future__ import annotations

import json
import os

from benchmark import spec


def _load(path: str) -> dict:
    with open(path) as f:
        if path.endswith(".json"):
            return json.load(f)
        import yaml

        return yaml.safe_load(f)


def _generator(root: str, group: str, name: str):
    """The module of gen/<group>/<name>.py under the benchmark root."""
    path = os.path.join(root, "gen", group, name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"unknown {group} generator {name!r}: no file "
                         f"{path}")
    return spec.load_module(path, f"bench_gen_{group}_"
                            + name.replace("-", "_").replace(".", "_"))


def _draw(root: str, group: str, config: dict, seed: int) -> list:
    return [x for e in config[group]
            for x in _generator(root, group, e["generator"]).build(
                e, config, seed)]


def build(config: dict, seed: int, root: str = spec.HERE) -> dict:
    """{"templates", "constraints", "inventory"} for the seed; `root` is
    the benchmark root the generators are found under."""
    return {
        "templates": [_load(os.path.join(config["dir"], p))
                      for p in config["templates"]],
        "constraints": _draw(root, "constraints", config, seed),
        "inventory": _draw(root, "inventory", config, seed),
    }
