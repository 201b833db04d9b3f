"""The four-chip cell `audit.general-thresholds.mesh4` as BENCHMARK.json
declares it, cut small (tiny.make_root) and run through run.main on a
4-device CPU mesh: every window audit on mesh(data=4), every kind on the
device, the answer equal to the reference, and the mesh's own per-layer
metrics (`audit.place_s`, `audit.mesh_reassemble_s`) read from a traced
run. The CPU trace has no TPU plane to reduce, so the traced run's
profiler is a stub there; the program spans the readers use are the
program's own."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import spec
from benchmark.tests import tiny

CELL = "audit.general-thresholds.mesh4"
NEW_READERS = ["audit.place_s", "audit.mesh_reassemble_s"]

MESH4_RUN = """\
import sys
sys.path.insert(0, {checkout!r})
from benchmark import run, trace
from benchmark.tests import tiny


class NoPlane:
    # a CPU trace holds no TPU plane: the traced run reduces to nothing
    def __init__(self, log_dir):
        pass

    def start(self):
        pass

    def stop(self):
        pass

    def reduce(self):
        return {{"busy_s": 0.0, "window_s": 1.0, "device_ops": [],
                 "idle_gaps": []}}


trace.Profile = NoPlane
root = tiny.make_root({tmp!r})
for t in ("0", "1"):
    run.main(["--workload", {cell!r}, "--seed", "3000000031",
              "--seconds", "1", "--trace", t], root=root,
             require_chip=False)
"""


def test_cell_resolves_to_the_mesh_mix():
    cell = spec.resolve(CELL)
    assert cell.chips == 4
    assert cell.config["name"] == "general-thresholds.mesh4"
    assert cell.traffic["name"] == "full-sweep.mesh4"
    assert cell.traffic["driver"] == "audit"
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= names
    assert {"audit.join_s", "audit.materialize_s",
            "audit.device_idle_share"} <= names


def test_config_is_the_one_chip_cluster_on_another_host():
    """The mesh4 configuration names its own host in its source, and
    judges general-thresholds's cluster: the same templates, constraint
    and inventory entries, and the same reference file."""
    mesh4 = spec.resolve(CELL).config
    one = spec.resolve("audit.general-thresholds").config
    assert mesh4["source"] != one["source"]
    assert mesh4["reduced"] == one["reduced"] == []
    assert mesh4["layout"]["mesh"] == {"data": 4}
    for key in ("constraints", "inventory"):
        assert mesh4[key] == one[key], key

    def real(cfg, rel):
        return os.path.realpath(os.path.join(cfg["dir"], rel))

    assert [real(mesh4, p) for p in mesh4["templates"]] == \
        [real(one, p) for p in one["templates"]]
    assert real(mesh4, mesh4["reference"]) == real(one, one["reference"])


def test_mesh4_cell_judged_on_a_cpu_mesh(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               GATEKEEPER_TPU_MESH_MIN_REVIEWS="64")
    p = subprocess.run(
        [sys.executable, "-c", MESH4_RUN.format(
            checkout=tiny.CHECKOUT, tmp=str(tmp_path), cell=CELL)],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    untraced, traced = [json.loads(ln) for ln in p.stdout.splitlines()
                        if ln.startswith("{")]
    for line in (untraced, traced):
        assert line["correct"] is True, line["checks"]
        assert line["checks"]["audits_not_mesh_path"]["value"] == 0
        assert line["checks"]["kinds_off_device"]["value"] == 0
        assert line["checks"]["result_mismatches"]["value"] == 0
    assert set(untraced["metrics"]) == {"audit_s", "setup_s"}
    for name in NEW_READERS:
        assert traced["metrics"][name]["value"] > 0, name
        assert traced["metrics"][name]["unit"] == "s"


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_without_their_phase(name):
    reader = spec.reader_for(tiny.BENCH, name)
    assert reader.read({}) is None
    assert reader.read({"audit_walls_s": [1.0],
                        "phases_s": {"materialize": 0.6}}) is None
    assert reader.read({"audit_walls_s": [], "phases_s": {
        "place": 0.1, "reassemble": 0.1}}) is None


def test_new_readers_over_audits():
    obs = {"audit_walls_s": [0.5, 0.7],
           "phases_s": {"place": 0.002, "reassemble": 0.006,
                        "materialize": 0.9}}
    assert spec.reader_for(tiny.BENCH, "audit.place_s").read(obs) == \
        pytest.approx(0.001)
    assert spec.reader_for(tiny.BENCH,
                           "audit.mesh_reassemble_s").read(obs) == \
        pytest.approx(0.003)
