"""psp-thresholds on the CPU at tiny sizes: the cell through the real
`full-sweep` mix and audit driver comes out correct with every one of the
16 constraints firing; each breaker fires exactly the constraint it was
dealt and no other Pod fires any; the plain reference agrees with the
repo's Rego interpreter; and the library is pinned by digest."""

import hashlib
import json
import os
from collections import Counter

import pytest

from benchmark import run, spec
from benchmark.gen import library
from benchmark.tests import tiny

WORKLOAD = "audit.psp-thresholds"
SEEDS = [0, 7, 2**31 + 5]

# sha256 of the tiny library's canonical JSON (sorted keys, no spaces),
# as first built
TINY = {
    0: "124e274af022f1cd1e932ffc00afa84b71bddcb574a1d4944604a1a6df3ab0e5",
    7: "57f2bb2014b2046a4f19a2cf7b2c3baf09aab35eb771620e4f03f28521e29f9a",
    2**31 + 5:
        "d210f68c072b4261b2371bed66dfd4d07cba1b83b520f224169ad763d5826f27",
}

psp_cluster = spec.load_module(
    os.path.join(tiny.BENCH, "gen", "inventory", "psp-cluster.py"),
    "bench_gen_inventory_psp_cluster")


def digest(lib: dict) -> str:
    return hashlib.sha256(json.dumps(lib, sort_keys=True,
                                     separators=(",", ":")).encode()
                          ).hexdigest()


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("psp")))


def tiny_cell(root):
    return spec.resolve(WORKLOAD, root)


def by_constraint(answer: Counter) -> Counter:
    out = Counter()
    for (_, c, _), n in answer.items():
        out[c] += n
    return out


def test_cell_correct_and_every_constraint_fires(tiny_root, capsys):
    rc = run.main(["--workload", WORKLOAD, "--seed", "2147483653",
                   "--seconds", "2", "--trace", "0"],
                  root=tiny_root, require_chip=False)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["correct"] is True
    assert line["checks"]["result_mismatches"]["value"] == 0
    assert line["checks"]["kinds_off_device"]["value"] == 0
    cell = tiny_cell(tiny_root)
    lib = library.build(cell.config, 2147483653, root=cell.root)
    want = spec.reference_for(cell).audit(lib["inventory"],
                                          lib["constraints"])
    names = [c["metadata"]["name"] for c in lib["constraints"]]
    assert len(names) == 16
    assert set(by_constraint(want)) == set(names)


@pytest.mark.parametrize("seed", SEEDS)
def test_each_breaker_breaks_its_constraint_alone(tiny_root, seed):
    cell = tiny_cell(tiny_root)
    lib = library.build(cell.config, seed, root=cell.root)
    entry = cell.config["inventory"][0]
    pods = [o for o in lib["inventory"] if o["kind"] == "Pod"]
    broken = psp_cluster.breaks(len(pods), entry["breakers"]["Pod"], seed,
                                entry["sidecar_every"])
    want = spec.reference_for(cell).audit(lib["inventory"],
                                          lib["constraints"])
    fired: dict = {}
    for (who, cname, _msg), n in want.items():
        fired.setdefault(who, Counter())[cname] += n
    for i, p in enumerate(pods):
        who = ("Pod", p["metadata"]["namespace"], p["metadata"]["name"])
        got = fired.get(who, Counter())
        if i in broken:
            assert got == Counter({broken[i][0]: 1}), (i, broken[i], got)
        else:
            assert not got, (i, got)
    # only Pods are judged, and the breakers cover every constraint
    assert {who[0] for who in fired} == {"Pod"}
    assert sum(want.values()) == len(broken)
    assert {b for b, _, _ in broken.values()} == set(psp_cluster.BREAKS)
    # some breakers break an init container
    assert any(on_init for _, on_init, _ in broken.values())


def test_breaks_follow_the_configuration():
    cell = spec.resolve(WORKLOAD)
    names = [c["name"] for e in cell.config["constraints"]
             for c in e["constraints"]]
    assert psp_cluster.BREAKS == names


def test_reference_agrees_with_rego_interpreter(tiny_root):
    """The reference against the repo's Rego interpreter (no device
    path), on the tiny inventory plus Pods that leave out the fields
    the library reads."""
    from gatekeeper_tpu.client import Backend, RegoDriver
    from gatekeeper_tpu.target import K8sValidationTarget

    cell = tiny_cell(tiny_root)
    lib = library.build(cell.config, 11, root=cell.root)
    objects = lib["inventory"] + bare_pods()
    client = Backend(RegoDriver()).new_client([K8sValidationTarget()])
    for t in lib["templates"]:
        client.add_template(t)
    for c in lib["constraints"]:
        client.add_constraint(c)
    for o in objects:
        client.add_data(o)
    got = Counter()
    for r in client.audit().results():
        meta = r.resource.get("metadata") or {}
        got[((r.resource.get("kind"), meta.get("namespace", ""),
              meta.get("name")), r.constraint["metadata"]["name"],
             r.msg)] += 1
    want = spec.reference_for(cell).audit(objects, lib["constraints"])
    assert got == want
    # every bare Pod fires something, and every constraint fires
    bare = {("Pod", "edge", p["metadata"]["name"]) for p in bare_pods()}
    assert bare <= {who for who, _, _ in want}
    assert len(by_constraint(want)) == 16


def bare_pods() -> list:
    """Pods without the fields the library reads, or with them in
    shapes the generator never makes."""
    def pod(name, spec, annotations=None):
        meta = {"name": name, "namespace": "edge"}
        if annotations is not None:
            meta["annotations"] = annotations
        return {"apiVersion": "v1", "kind": "Pod", "metadata": meta,
                "spec": spec}
    return [
        pod("no-context", {"containers": [{"name": "a", "image": "x"}]}),
        pod("empty-context", {"securityContext": {},
                              "containers": [{"name": "a", "image": "x",
                                              "securityContext": {}}]}),
        pod("pod-user", {"securityContext": {"runAsUser": 0,
                                             "fsGroup": 70000},
                         "containers": [{"name": "a", "image": "x",
                                         "securityContext": {
                                             "capabilities": {}}}]}),
        pod("host-net", {"hostNetwork": True, "hostIPC": True,
                         "containers": [{"name": "a", "image": "x",
                                         "ports": [{"hostPort": 9001}]}],
                         "initContainers": [{"name": "i", "image": "x",
                                             "securityContext": {
                                                 "procMount": "Default",
                                                 "privileged": False}}]},
            {"seccomp.security.alpha.kubernetes.io/pod": "docker/default",
             "container.seccomp.security.alpha.kubernetes.io/a":
                 "unconfined"}),
        pod("volumes", {"volumes": [
            {"name": "h", "hostPath": {"path": "/var/log/x/../y"}},
            {"name": "g", "hostPath": {"path": "/var/logs"}},
            {"name": "f", "flexVolume": {"driver": "example/lvm"}},
            {"name": "p", "projected": {}}],
            "securityContext": {"sysctls": [{"name": "vm.swappiness",
                                             "value": "10"}]},
            "containers": [{"name": "a", "image": "x",
                            "volumeMounts": [{"name": "h",
                                              "mountPath": "/h"}]}]}),
        pod("container-seccomp", {"containers": [{"name": "a",
                                                  "image": "x"}]},
            {"container.seccomp.security.alpha.kubernetes.io/a":
                 "runtime/default"}),
    ]


@pytest.mark.parametrize("seed", sorted(TINY))
def test_tiny_library_unchanged(tiny_root, seed):
    cell = tiny_cell(tiny_root)
    lib = library.build(cell.config, seed, root=cell.root)
    assert len(lib["inventory"]) == 850
    assert digest(lib) == TINY[seed]


def test_full_count_is_the_stated_one():
    """At full size the reference gives the violations per audit the
    configuration states: one per breaker."""
    cell = spec.resolve(WORKLOAD)
    lib = library.build(cell.config, 2**31 + 11, root=cell.root)
    assert Counter(o["kind"] for o in lib["inventory"]) == \
        cell.config["inventory"][0]["counts"]
    want = spec.reference_for(cell).audit(lib["inventory"],
                                          lib["constraints"])
    assert sum(want.values()) == cell.config["violations_per_audit"]
    counts = by_constraint(want)
    assert min(counts.values()) == 187 and max(counts.values()) == 188


def test_control_fails(tmp_path, capsys, monkeypatch):
    """The control (each constraint's violations capped at Gatekeeper's
    default of 20) comes out not correct, at a twentieth of the
    cluster, where each constraint fires 46 or 47 times."""
    monkeypatch.setattr(tiny, "SHRINK", 20)
    root = tiny.make_root(str(tmp_path))
    rc = run.main(["--workload", WORKLOAD, "--seed", "2147483653",
                   "--seconds", "1", "--trace", "0", "--control", "1"],
                  root=root, require_chip=False)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    # 750 violations, 16 x 20 of them kept
    assert line["checks"]["result_mismatches"]["value"] == \
        line["checks"]["audits_checked"]["value"] * (750 - 16 * 20)


def test_program_without_audit_host_budget_refused(tiny_root, monkeypatch):
    """A program that judges each cold kind alone would interpret the
    whole first audit on the host, past a run's time limit: the cell
    refuses it before building anything."""
    from gatekeeper_tpu.ir import TpuDriver

    monkeypatch.delattr(TpuDriver, "AUDIT_HOST_BUDGET_S")
    cell = tiny_cell(tiny_root)
    with pytest.raises(SystemExit, match="AUDIT_HOST_BUDGET_S"):
        library.build(cell.config, 0, root=cell.root)
