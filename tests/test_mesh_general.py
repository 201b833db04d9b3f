"""The general library's audit on a four-device mesh, as a four-chip host
runs it: the Pod and Namespace kinds sweep split over mesh(data=4), the
Service selector join stays on one device, and the answer equals the
one-device driver's and the interpreter's.

A steady audit places nothing anew (the sharded placement cache hits),
reassembles the per-shard blocks under its `reassemble` phase, and
gatekeeper_tpu_audit_kind_sweeps_total{path} counts every kind's sweep
by where it ran."""

import random

import jax
import pytest

from gatekeeper_tpu import policies
from gatekeeper_tpu.client import Backend, RegoDriver
from gatekeeper_tpu.control.metrics import REGISTRY
from gatekeeper_tpu.ir import TpuDriver
from gatekeeper_tpu.parallel.mesh import make_mesh
from gatekeeper_tpu.parallel.workload import GENERAL_CONSTRAINTS
from gatekeeper_tpu.target import K8sValidationTarget
from gatekeeper_tpu.utils import profiling

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs four CPU devices")

SWEEPS = "gatekeeper_tpu_audit_kind_sweeps_total"
TEMPLATES = ("allowedrepos", "containerlimits", "containerresourceratios",
             "httpsonly", "requiredlabels", "uniqueingresshost",
             "uniqueserviceselector")
POD = {"kinds": [{"apiGroups": [""], "kinds": ["Pod"]}]}
INGRESS = {"kinds": [{"apiGroups": ["extensions", "networking.k8s.io"],
                      "kinds": ["Ingress"]}]}
MATCH = {"K8sAllowedRepos": POD, "K8sContainerLimits": POD,
         "K8sContainerRatios": POD, "K8sHttpsOnly": INGRESS,
         "K8sRequiredLabels": {"kinds": [{"apiGroups": [""],
                                          "kinds": ["Namespace"]}]},
         "K8sUniqueIngressHost": INGRESS,
         "K8sUniqueServiceSelector": {"kinds": [{"apiGroups": [""],
                                                 "kinds": ["Service"]}]}}
N_NS, N_POD, N_SVC = 160, 600, 40
# the four kinds that sweep on the mesh, and the one join
MESH_KINDS = 4
JOIN_KINDS = 1


def _inventory(seed: int = 31) -> list:
    """Namespaces, Pods and Services, 3% of Namespaces and Pods breaking
    a rule, and one Service that reuses its neighbour's selector."""
    rng = random.Random(seed)
    out = []
    for i in range(N_NS):
        meta = {"name": f"ns-{i}"}
        if rng.random() >= 0.03:
            meta["labels"] = {"owner": "team.corp.example"}
        out.append({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": meta})
    for i in range(N_POD):
        bad = rng.random() < 0.03
        out.append({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"pod-{i}", "namespace": f"ns-{i % N_NS}",
                         "labels": {"app": f"app{i % 50}"}},
            "spec": {"containers": [{
                "name": "main",
                "image": (f"docker.io/evil{i}:latest" if bad
                          else f"gcr.io/corp/svc{i % 20}:v1"),
                "resources": {
                    "limits": {"cpu": "4" if bad else "900m",
                               "memory": "512Mi"},
                    "requests": {"cpu": "250m", "memory": "256Mi"}}}]}})
    for i in range(N_SVC):
        app = f"app{i - 1 if i == N_SVC - 1 else i}"
        out.append({"apiVersion": "v1", "kind": "Service",
                    "metadata": {"name": f"svc-{i}",
                                 "namespace": f"ns-{i % N_NS}"},
                    "spec": {"selector": {"app": app},
                             "ports": [{"port": 80}]}})
    return out


def _client(driver):
    client = Backend(driver).new_client([K8sValidationTarget()])
    for name in TEMPLATES:
        client.add_template(policies.load(f"general/{name}"))
    for kind, name, params in GENERAL_CONSTRAINTS:
        spec = {"match": MATCH[kind]}
        if params:
            spec["parameters"] = params
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind, "metadata": {"name": name}, "spec": spec})
    for o in _inventory():
        client.add_data(o)
    return client


def _answer(results) -> list:
    return sorted((r.constraint["metadata"]["name"],
                   r.resource["kind"], r.resource["metadata"]["name"],
                   r.msg) for r in results)


def _sweeps() -> dict:
    ent = REGISTRY.snapshot((SWEEPS,)).get(SWEEPS, {})
    vals = {tuple(k): v for k, v in ent.get("values", [])}
    return {p: vals.get((p,), 0.0) for p in ("mesh", "single")}


def _full_audit(driver, client) -> list:
    """One full sweep, the results delta cache dropped first."""
    driver.drop_audit_results()
    return _answer(client.audit().results())


@pytest.fixture(scope="module")
def mesh_run():
    drv = TpuDriver(mesh=make_mesh(devices=jax.devices()[:4]))
    drv.MESH_MIN_REVIEWS = 64
    client = _client(drv)
    s0 = _sweeps()
    first = _full_audit(drv, client)
    s1 = _sweeps()
    first_path = drv.last_audit_path
    return drv, client, first, first_path, s0, s1


def test_mesh_audit_equals_one_device_and_interpreter(mesh_run):
    drv, _client_, got, path, _s0, _s1 = mesh_run
    assert path == "mesh(data=4)"
    assert {k: set(v) for k, v in drv.eval_counts().items()
            if k in MATCH and v} == {
        "K8sAllowedRepos": {"device"}, "K8sContainerLimits": {"device"},
        "K8sContainerRatios": {"device"}, "K8sRequiredLabels": {"device"},
        "K8sUniqueServiceSelector": {"join"}}

    one = TpuDriver()
    one._mesh = None
    single = _full_audit(one, _client(one))
    assert one.last_audit_path == "single"
    interp = _answer(_client(RegoDriver()).audit().results())
    assert got == single == interp
    names = {c for c, *_ in got}
    assert {"repos-allowed", "limits-capped", "ratio-capped", "must-own",
            "unique-selectors"} <= names, "non-vacuous"
    assert sum(c == "unique-selectors" for c, *_ in got) == 2


def test_kind_sweeps_counted_by_path(mesh_run):
    *_rest, s0, s1 = mesh_run
    assert s1["mesh"] - s0["mesh"] == MESH_KINDS
    assert s1["single"] - s0["single"] == JOIN_KINDS


def test_steady_audit_places_nothing_and_reassembles(mesh_run,
                                                     monkeypatch):
    drv, client, first, *_ = mesh_run
    _full_audit(drv, client)  # every leaf placed by now
    puts = []
    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda *a, **k: puts.append(a) or real_put(*a, **k))
    before = profiling.timers().snapshot()
    s0 = _sweeps()
    again = _full_audit(drv, client)
    phases = profiling.PhaseTimers.diff(before,
                                        profiling.timers().snapshot())
    s1 = _sweeps()
    assert again == first
    assert drv.last_audit_path == "mesh(data=4)"
    assert puts == [], "a steady mesh audit re-placed arrays"
    assert 0 < phases["place"] < 0.05
    assert phases["reassemble"] > 0
    assert s1["mesh"] - s0["mesh"] == MESH_KINDS
    assert s1["single"] - s0["single"] == JOIN_KINDS


def test_one_device_audit_has_no_reassemble_phase():
    drv = TpuDriver()
    drv._mesh = None
    client = _client(drv)
    _full_audit(drv, client)
    before = profiling.timers().snapshot()
    s0 = _sweeps()
    _full_audit(drv, client)
    phases = profiling.PhaseTimers.diff(before,
                                        profiling.timers().snapshot())
    s1 = _sweeps()
    assert drv.last_audit_path == "single"
    assert "reassemble" not in phases
    assert "place" in phases
    assert s1["mesh"] == s0["mesh"]
    assert s1["single"] - s0["single"] == MESH_KINDS + JOIN_KINDS


def test_placement_bound_below_one_audit_keeps_its_leaves(monkeypatch):
    """DEV_MESH_CACHE_MAX far below the leaves one audit places: the
    bound evicts only leaves of earlier audits, so the next steady audit
    still re-places nothing."""
    drv = TpuDriver(mesh=make_mesh(devices=jax.devices()[:4]))
    drv.MESH_MIN_REVIEWS = 64
    drv.DEV_MESH_CACHE_MAX = 16
    client = _client(drv)
    first = _full_audit(drv, client)
    assert len(drv._dev_mesh_cache) > drv.DEV_MESH_CACHE_MAX
    puts = []
    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda *a, **k: puts.append(a) or real_put(*a, **k))
    assert _full_audit(drv, client) == first
    assert drv.last_audit_path == "mesh(data=4)"
    assert puts == []
