"""audit.match_s: the window's `match` phase over its audits, and
nothing where the program has no such phase or no audit ran."""

import pytest

from benchmark import spec
from benchmark.tests import tiny


def read(obs):
    return spec.reader_for(tiny.BENCH, "audit.match_s").read(obs)


def test_match_over_audits():
    obs = {"audit_walls_s": [1.5, 1.7, 1.6],
           "phases_s": {"match": 0.3, "materialize": 1.0}}
    assert read(obs) == pytest.approx(0.1)


@pytest.mark.parametrize("obs", [
    {},
    {"audit_walls_s": [], "phases_s": {"match": 0.3}},
    {"audit_walls_s": [1.0], "phases_s": {}},
    {"audit_walls_s": [1.0], "phases_s": {"materialize": 0.6}},
])
def test_nothing_to_read(obs):
    assert read(obs) is None
