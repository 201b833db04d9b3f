"""The cells BENCHMARK.json declares: each (configuration, traffic) pair
names one cell, and no two cells share a name."""

import json
import os
from collections import Counter

from benchmark.tests import tiny


def workloads() -> list:
    with open(os.path.join(tiny.CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)["workloads"]


def test_no_two_cells_share_config_and_traffic():
    pairs = Counter((w["config"], w["traffic"]) for w in workloads())
    assert [p for p, n in pairs.items() if n > 1] == []
    names = Counter(w["name"] for w in workloads())
    assert [n for n, k in names.items() if k > 1] == []
