"""A cluster's Namespaces, Pods and Services with exact counts and
breakers: `workload.cluster_fixed` on the run's seed plus the entry's
offset.

    {"generator": "cluster", "counts", "breakers", "seed_offset"}
"""

from __future__ import annotations

from benchmark.gen import workload


def build(entry: dict, config: dict, seed: int) -> list:
    return workload.cluster_fixed(entry["counts"], entry["breakers"],
                                  seed + entry.get("seed_offset", 0))
