"""Constraints written out in the configuration file, one per item of
the entry's list, each with its kind, name, match and, where it has
them, parameters:

    {"generator": "listed",
     "constraints": [{"kind", "name", "match", "parameters"?}]}
"""

from __future__ import annotations

import copy


def build(entry: dict, config: dict, seed: int) -> list:
    out = []
    for item in entry["constraints"]:
        spec = {"match": copy.deepcopy(item["match"])}
        if "parameters" in item:
            spec["parameters"] = copy.deepcopy(item["parameters"])
        out.append({"apiVersion": "constraints.gatekeeper.sh/v1beta1",
                    "kind": item["kind"],
                    "metadata": {"name": item["name"]}, "spec": spec})
    return out
