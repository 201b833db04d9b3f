"""Platform-keyed comparability in the perf-trend watchdog.

Rounds measured on different JAX backends (`jax_backend` in the bench
headline) must not gate each other: r03/r04 ran on accelerator hosts,
r06 on a 1-core CPU container, and device-bound walls differ ~20x by
host class alone. A platform change restarts every series baseline;
same-platform regressions still fail --check.
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "tools"))
import bench_trend as bt  # noqa: E402


def _round(label, value, platform, unit=None):
    return {"round": label, "path": label,
            "metrics": {"sweep_wall_s": value}, "errors": {},
            "units": ({"sweep_wall_s": unit} if unit else {}),
            "platform": platform}


def test_same_platform_regression_gates():
    rounds = [_round("r01", 1.0, "cpu"), _round("r02", 2.0, "cpu")]
    regs = bt.find_regressions(rounds)
    assert [r["metric"] for r in regs] == ["sweep_wall_s"]


def test_platform_change_restarts_series():
    rounds = [_round("r01", 1.0, "tpu"), _round("r02", 2.0, "cpu")]
    assert bt.find_regressions(rounds) == []


def test_legacy_rounds_compare_among_themselves():
    # rounds predating the jax_backend field carry platform None and
    # still gate each other — history stays watched
    rounds = [_round("r01", 1.0, None), _round("r02", 2.0, None)]
    assert bt.find_regressions(rounds)
    # ...but a None round never anchors a platform-carrying one
    rounds = [_round("r01", 1.0, None), _round("r02", 2.0, "cpu")]
    assert bt.find_regressions(rounds) == []


def test_unit_change_still_restarts_within_platform():
    rounds = [_round("r01", 1.0, "cpu", unit="objects/s @ 1k"),
              _round("r02", 2.0, "cpu", unit="objects/s @ 10k")]
    assert bt.find_regressions(rounds) == []


def test_loader_extracts_platform(tmp_path):
    p = tmp_path / "BENCH_r01.json"
    p.write_text(json.dumps(
        {"parsed": {"metric": "m", "value": 1.0,
                    "sweep_wall_s": 0.5, "jax_backend": "cpu"}}))
    rounds = bt.load_rounds([str(p)])
    assert rounds[0]["platform"] == "cpu"
    assert rounds[0]["metrics"]["sweep_wall_s"] == 0.5


def test_repo_history_check_passes():
    # the committed BENCH_r*.json history must be green: --check runs
    # in CI on every PR
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rc = bt.main(["--dir", repo, "--check"])
    assert rc == 0
