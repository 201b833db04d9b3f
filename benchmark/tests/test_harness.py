"""The harness end to end on the CPU at tiny sizes: a cell found by its
name alone, a configuration made of new files alone, the audit path a
cell's chips imply, the contract's last line, no result without a TPU,
and the reference agreeing with the program."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, run, spec
from benchmark.tests import tiny

audit_driver = spec.load_module(os.path.join(tiny.BENCH, "drivers",
                                             "audit.py"), "bench_driver_audit")

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "checks"]
WORKLOAD = "audit.general-thresholds"


def last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def run_tiny(tmp_path, capsys, workload, *extra) -> dict:
    root = tiny.make_root(str(tmp_path))
    rc = run.main(["--workload", workload, "--seed", "2147483653",
                   "--seconds", "2", "--trace", "0", *extra],
                  root=root, require_chip=False)
    assert rc == 0
    return last_line(capsys)


def test_dummy_cell_found_by_name(tmp_path, capsys):
    """A cell, its configuration, mix, driver and metric, all new files
    and entries: no harness code changes."""
    root = tmp_path
    (root / "benchmark" / "traffic").mkdir(parents=True)
    (root / "benchmark" / "drivers").mkdir()
    (root / "benchmark" / "metrics").mkdir()
    (root / "benchmark" / "configs" / "dummy").mkdir(parents=True)
    (root / "benchmark" / "configs" / "dummy" / "config.json").write_text(
        json.dumps({"name": "dummy", "size": 3}))
    (root / "benchmark" / "traffic" / "idle.json").write_text(
        json.dumps({"driver": "dummy", "per_run": 5}))
    (root / "benchmark" / "drivers" / "dummy.py").write_text(
        "from benchmark import harness\n"
        "def run(cell, seed, seconds, trace, control, t_start):\n"
        "    c = harness.Checks(); c.max('wrong', 0, 0)\n"
        "    n = cell.config['size'] * cell.traffic['per_run']\n"
        "    return {'obs': {'n': n}, 'checks': c, 'attempted': n,\n"
        "            'failed': 0, 'memory_peak_bytes': 0}\n")
    (root / "benchmark" / "metrics" / "things.py").write_text(
        "def read(obs):\n    return obs['n']\n")
    (root / "benchmark" / "metrics" / "nothing.py").write_text(
        "def read(obs):\n    return None\n")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 1,
        "configs": [{"name": "dummy", "source": "x",
                     "file": "benchmark/configs/dummy/config.json",
                     "reduced": [], "why": "x"}],
        "workloads": [{"name": "dummy.idle", "config": "dummy",
                       "traffic": "idle", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "things", "unit": "n", "better": "higher",
                        "bound": 0.1, "source": "host_clock"},
                       {"name": "nothing", "unit": "n", "better": "higher",
                        "bound": 0.1, "source": "host_clock"}],
        "per_layer": []}))
    cell = spec.resolve("dummy.idle", str(root))
    assert cell.config["size"] == 3 and cell.traffic["driver"] == "dummy"
    assert [m["name"] for m in cell.end_to_end] == ["things", "nothing"]
    rc = run.main(["--workload", "dummy.idle", "--seed", "1", "--seconds",
                   "1", "--trace", "0"], root=str(root), require_chip=False)
    assert rc == 0
    line = last_line(capsys)
    assert line["metrics"] == {"things": {"value": 15, "unit": "n"}}
    assert line["correct"] is True


TOY_TEMPLATE = """\
apiVersion: templates.gatekeeper.sh/v1beta1
kind: ConstraintTemplate
metadata:
  name: k8spspprivilegedcontainer
spec:
  crd:
    spec:
      names:
        kind: K8sPSPPrivilegedContainer
  targets:
    - target: admission.k8s.gatekeeper.sh
      rego: |
        package k8spspprivileged

        pod_containers[c] {
          c := input.review.object.spec.containers[_]
        }

        pod_containers[c] {
          c := input.review.object.spec.initContainers[_]
        }

        violation[{"msg": msg, "details": {}}] {
          c := pod_containers[_]
          c.securityContext.privileged
          msg := sprintf("Privileged container is not allowed: %v, securityContext: %v", [c.name, c.securityContext])
        }
"""

TOY_CONSTRAINTS = """\
def build(entry, config, seed):
    return [{"apiVersion": "constraints.gatekeeper.sh/v1beta1",
             "kind": "K8sPSPPrivilegedContainer",
             "metadata": {"name": entry["name"]},
             "spec": {"match": {"kinds": [{"apiGroups": [""],
                                           "kinds": ["Pod"]}]}}}]
"""

TOY_INVENTORY = """\
import random

def build(entry, config, seed):
    n = entry["count"]
    bad = set(random.Random(seed).sample(range(n), entry["privileged"]))
    return [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": f"p-{i}", "namespace": "default"},
             "spec": {"containers": [{
                 "name": "main", "image": "nginx",
                 "securityContext": {"privileged": i in bad}}]}}
            for i in range(n)]
"""

TOY_REFERENCE = """\
from collections import Counter

from benchmark.refcommon import fmt_v, ident, matches, review_of


def audit(objects, constraints, cap_per_constraint=None):
    out = Counter()
    for obj in objects:
        review = review_of(obj)
        for c in constraints:
            if not matches(c["spec"].get("match") or {}, review):
                continue
            for field in ("containers", "initContainers"):
                for ct in obj["spec"].get(field, []):
                    sc = ct.get("securityContext") or {}
                    if sc.get("privileged"):
                        out[(ident(obj), c["metadata"]["name"],
                             "Privileged container is not allowed: "
                             f"{ct['name']}, securityContext: "
                             f"{fmt_v(sc)}")] += 1
    return out
"""


def _tree_digest(top: str) -> dict:
    out = {}
    for d, dirs, files in os.walk(top):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.relpath(os.path.join(d, f), top)] = \
                    hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_configuration_no_harness_edit(tmp_path, capsys):
    """A configuration with its own constraint generator, inventory
    generator, template and reference, run through the real `full-sweep`
    mix and audit driver: all new files and entries, no harness file
    touched."""
    before = _tree_digest(tiny.BENCH)
    b = tmp_path / "benchmark"
    (b / "gen" / "constraints").mkdir(parents=True)
    (b / "gen" / "inventory").mkdir()
    for sub in ("drivers", "metrics", "traffic"):
        os.symlink(os.path.join(tiny.BENCH, sub), b / sub)
    cdir = b / "configs" / "toy"
    (cdir / "templates").mkdir(parents=True)
    (cdir / "templates" / "privileged.yaml").write_text(TOY_TEMPLATE)
    (cdir / "reference.py").write_text(TOY_REFERENCE)
    (b / "gen" / "constraints" / "privileged.py").write_text(
        TOY_CONSTRAINTS)
    (b / "gen" / "inventory" / "pods.py").write_text(TOY_INVENTORY)
    (cdir / "config.json").write_text(json.dumps({
        "name": "toy", "templates": ["templates/privileged.yaml"],
        "constraints": [{"generator": "privileged",
                         "name": "no-privileged"}],
        "inventory": [{"generator": "pods", "count": 60,
                       "privileged": 6}],
        "reference": "reference.py"}))
    with open(os.path.join(tiny.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "toy", "source": "x",
                         "file": "benchmark/configs/toy/config.json",
                         "reduced": [], "why": "x"}]
    bench["workloads"] = [{"name": "audit.toy", "config": "toy",
                           "traffic": "full-sweep", "chips": 1,
                           "why": "x"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["audit.toy"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    rc = run.main(["--workload", "audit.toy", "--seed", "2147483659",
                   "--seconds", "1", "--trace", "0"],
                  root=str(tmp_path), require_chip=False)
    assert rc == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"audit_s", "setup_s"}
    assert '"objects": 60' in out and '"violations": 6' in out
    assert _tree_digest(tiny.BENCH) == before


@pytest.mark.parametrize("chips,paths,off", [
    (1, ["single", "single"], 0),
    (1, ["single", "mesh(data=4)"], 1),
    (4, ["mesh(data=4)", "mesh(data=4)"], 0),
    (4, ["mesh(data=4)", "single"], 1),
    (4, ["single", "single"], 2),
])
def test_expected_path(chips, paths, off):
    """1 chip expects `single`, 4 chips `mesh(data=4)`; the check keeps
    its name on one chip."""
    checks = harness.Checks()
    audit_driver.check_paths(checks, chips, paths)
    name = "audits_not_single_path" if chips == 1 else \
        "audits_not_mesh_path"
    assert checks.items == {name: {"value": off, "limit": 0,
                                   "rule": "max"}}
    assert checks.ok() is (off == 0)


MESH_RUN = """\
import json, sys
sys.path.insert(0, {checkout!r})
from benchmark import run
from benchmark.tests import tiny
root = tiny.make_root({tmp!r}, extra_cells=[dict(
    name="audit.general-thresholds.mesh4", config="general-thresholds",
    traffic="full-sweep", chips=4, why="x")])
for w in ("audit.general-thresholds.mesh4", "audit.general-thresholds"):
    run.main(["--workload", w, "--seed", "2147483653", "--seconds", "1",
              "--trace", "0"], root=root, require_chip=False)
"""


def test_mesh_cell_judged_on_a_cpu_mesh(tmp_path):
    """With four devices the program shards the audit itself: a 4-chip
    cell's audits pass the path check, and a 1-chip cell's fail it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               GATEKEEPER_TPU_MESH_MIN_REVIEWS="64")
    p = subprocess.run(
        [sys.executable, "-c", MESH_RUN.format(checkout=tiny.CHECKOUT,
                                               tmp=str(tmp_path))],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    mesh4, single = [json.loads(ln) for ln in p.stdout.splitlines()
                     if ln.startswith("{")]
    assert mesh4["checks"]["audits_not_mesh_path"]["value"] == 0
    assert mesh4["correct"] is True
    assert mesh4["checks"]["kinds_off_device"]["value"] == 0
    assert single["checks"]["audits_not_single_path"]["value"] > 0
    assert single["correct"] is False


def test_real_cells_resolve():
    with open(os.path.join(tiny.CHECKOUT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    assert WORKLOAD in names
    for name in names:
        cell = spec.resolve(name)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert os.path.exists(os.path.join(cell.root, "metrics",
                                               m["name"] + ".py"))


def test_last_line_has_contract_keys(tmp_path, capsys):
    line = run_tiny(tmp_path, capsys, WORKLOAD)
    assert list(line) == CONTRACT_KEYS
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    names = set(line["metrics"])
    assert "setup_s" in names and len(names) >= 2
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # the reference agrees with the program; the device-side run checks
    # need a chip, so only the comparison's own checks are asserted
    first = next(iter(line["checks"]))
    assert line["checks"][first]["value"] == 0


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(tiny.BENCH, "run.py"),
                        "--workload", WORKLOAD, "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_without_the_program_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    import shutil

    shutil.copy(os.path.join(tiny.CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        WORKLOAD, "--seed", "1", "--seconds", "1",
                        "--trace", "0"],
                       capture_output=True, text=True, timeout=300,
                       cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=""))
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
