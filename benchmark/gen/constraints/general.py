"""The general library's constraints: one per GENERAL_CONSTRAINTS entry,
matched as the entry says for its kind.

    {"generator": "general", "match_by_kind": {kind: spec.match}}
"""

from __future__ import annotations

import copy

from benchmark.gen import workload


def build(entry: dict, config: dict, seed: int) -> list:
    out = []
    for kind, name, params in workload.GENERAL_CONSTRAINTS:
        spec = {"match": copy.deepcopy(entry["match_by_kind"][kind])}
        if params:
            spec["parameters"] = copy.deepcopy(params)
        out.append({"apiVersion": "constraints.gatekeeper.sh/v1beta1",
                    "kind": kind, "metadata": {"name": name},
                    "spec": spec})
    return out
