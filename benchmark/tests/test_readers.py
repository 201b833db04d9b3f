"""The readers of the audit's program-span metrics on a hand-made window:
each phase's delta over the window's audits, the audit time no phase
covers, and nothing where the program has no such phase or no audit ran."""

import pytest

from benchmark import spec
from benchmark.tests import tiny

READERS = ["audit.join_s", "audit.join_materialize_s",
           "audit.handle_violations_s", "audit.unattributed_s"]

OBS = {"audit_walls_s": [1.5, 1.7],
       "phases_s": {"join": 0.04, "join_materialize": 1.6,
                    "handle_violations": 0.2, "materialize": 1.0,
                    "device_sweep": 0.01, "encode": 0.05}}


def read(name, obs):
    return spec.reader_for(tiny.BENCH, name).read(obs)


def test_phase_readers():
    assert read("audit.join_s", OBS) == pytest.approx(0.02)
    assert read("audit.join_materialize_s", OBS) == pytest.approx(0.8)
    assert read("audit.handle_violations_s", OBS) == pytest.approx(0.1)
    # walls 3.2 s, phases 2.9 s, over 2 audits
    assert read("audit.unattributed_s", OBS) == pytest.approx(0.15)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read(name):
    assert read(name, {}) is None
    assert read(name, {"audit_walls_s": [], "phases_s": OBS["phases_s"]}) \
        is None
    assert read(name, {"audit_walls_s": [1.0], "phases_s": {}}) is None


@pytest.mark.parametrize("name", READERS[:3])
def test_a_program_without_the_phase(name):
    """The parent program has no join, join_materialize or
    handle_violations phase: the reader leaves its metric out."""
    old = {"audit_walls_s": [1.6],
           "phases_s": {"materialize": 0.6, "device_sweep": 0.005}}
    assert read(name, old) is None
    assert read("audit.unattributed_s", old) == pytest.approx(0.995)
