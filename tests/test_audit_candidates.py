"""An audit sweep's candidate rows are a view over the inventory's
review list (client/drivers.py CandidateRows): it reads like
`[reviews[i] for i in cand]`, and a steady-state audit builds no such
list. gatekeeper_tpu_audit_candidate_rows_total{use} counts the rows
handed out one by one ("resolved") and listed in bulk ("listed")."""

import copy

import numpy as np
import pytest

from gatekeeper_tpu.client import Backend
from gatekeeper_tpu.client.drivers import CandidateRows, take_rows
from gatekeeper_tpu.control.metrics import REGISTRY
from gatekeeper_tpu.ir import TpuDriver
from gatekeeper_tpu.parallel.workload import PSP_CONSTRAINTS, synth_pods_psp
from gatekeeper_tpu import policies
from gatekeeper_tpu.target import K8sValidationTarget

TARGET = "admission.k8s.gatekeeper.sh"
METRIC = "gatekeeper_tpu_audit_candidate_rows_total"
# two kinds with a message plan (witness columns) and one without
KINDS = {"K8sPSPForbiddenSysctls": "forbidden-sysctls",
         "K8sPSPHostNamespace": "host-namespaces",
         "K8sPSPPrivilegedContainer": "privileged-containers"}
N_PODS = 300


def _rows_counter() -> dict:
    ent = REGISTRY.snapshot((METRIC,)).get(METRIC, {})
    vals = {tuple(k): v for k, v in ent.get("values", [])}
    return {use: vals.get((use,), 0.0) for use in ("resolved", "listed")}


@pytest.mark.parametrize("cand", [[], [4], [0, 3, 4, 9], list(range(10))],
                         ids=["none", "one", "sparse", "every"])
def test_view_reads_as_the_candidate_list(cand):
    reviews = [{"i": i} for i in range(10)]
    cand = np.asarray(cand, dtype=np.int64)
    want = [reviews[i] for i in cand]
    view = CandidateRows(reviews, cand)

    assert len(view) == len(want)
    assert all(view[i] is want[i] for i in range(len(want)))
    assert all(view[-i] is want[-i] for i in range(1, len(want) + 1))
    before = _rows_counter()
    assert list(view) == want
    assert view.tolist() == want
    mid = _rows_counter()
    assert mid["listed"] - before["listed"] == 2 * len(want)
    for rows in (np.arange(len(want)), np.arange(len(want))[::2],
                 np.zeros(0, dtype=np.int64)):
        got = view.take(rows)
        assert got == [want[r] for r in rows]
        assert all(g is w for g, w in zip(got, [want[r] for r in rows]))
        assert take_rows(want, rows) == got
    after = _rows_counter()
    assert after["listed"] == mid["listed"]
    assert after["resolved"] - mid["resolved"] == \
        len(want) + len(want[::2])


def _client(pods):
    drv = TpuDriver()
    client = Backend(drv).new_client([K8sValidationTarget()])
    params = {k: p for k, _n, p in PSP_CONSTRAINTS}
    for kind, name in KINDS.items():
        client.add_template(policies.load(f"pod-security-policy/{name}"))
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind, "metadata": {"name": name},
            "spec": ({"parameters": params[kind]} if params[kind]
                     else {})})
    for p in pods:
        client.add_data(p)
    return drv, client


def _audit(drv, client):
    """One full sweep, as the benchmark's back-to-back audits run it:
    the results delta cache dropped first."""
    drv.drop_audit_results()
    res = client.audit().results()
    assert all(v.get("device", 0) and not v.get("delta", 0)
               for k, v in drv.eval_counts().items() if k in KINDS)
    return sorted((r.constraint["kind"], r.msg,
                   r.resource["metadata"]["name"], r.enforcement_action)
                  for r in res)


def test_steady_audit_lists_no_candidate():
    drv, client = _client(synth_pods_psp(N_PODS))
    first = _audit(drv, client)
    assert first
    before = _rows_counter()
    second = _audit(drv, client)
    after = _rows_counter()
    assert second == first
    assert after["listed"] == before["listed"]
    # the firing rows, and only they, are resolved
    assert 0 < after["resolved"] - before["resolved"] <= len(second)


def _replaced(pods):
    """The inventory with one clean Pod turned into a breaker (a
    forbidden sysctl, a privileged container) and one breaker cleaned,
    every list keeping its length."""
    pods = copy.deepcopy(pods)
    bad, good = pods[1], pods[0]
    bad["spec"]["securityContext"]["sysctls"] = [
        {"name": "kernel.msgmax", "value": "1"}]
    bad["spec"]["containers"][0]["securityContext"]["privileged"] = True
    good["spec"]["securityContext"]["sysctls"] = [
        {"name": "net.ipv4.ip_local_port_range", "value": "1024 65535"}]
    return pods, (bad, good)


def test_replaced_object_audits_as_a_fresh_driver():
    pods = synth_pods_psp(N_PODS)
    drv, client = _client(pods)
    _audit(drv, client)
    first = _audit(drv, client)
    now, changed = _replaced(pods)
    for p in changed:
        client.add_data(p)
    before = _rows_counter()
    patched = _audit(drv, client)
    after = _rows_counter()
    # the mask replay and the feature patch serve the change: nothing
    # is listed in bulk
    assert after["listed"] == before["listed"]
    fresh = _audit(*_client(now))
    assert patched == fresh
    fired = {(k, n) for k, _m, n, _e in first}
    assert ("K8sPSPForbiddenSysctls", "pod-0") in fired
    assert ("K8sPSPForbiddenSysctls", "pod-1") not in fired
    fired = {(k, n) for k, _m, n, _e in patched}
    assert ("K8sPSPForbiddenSysctls", "pod-0") not in fired
    assert {("K8sPSPForbiddenSysctls", "pod-1"),
            ("K8sPSPPrivilegedContainer", "pod-1")} <= fired


def test_witness_columns_build_over_the_inventory_list():
    drv, client = _client(synth_pods_psp(N_PODS))
    _audit(drv, client)
    full = drv._inventory_reviews(TARGET)
    assert drv._witcols
    for _key, ent in drv._witcols.items():
        assert ent[1] == (id(full), len(full))
    cols = {k: ent[2] for k, ent in drv._witcols.items()}
    _audit(drv, client)
    # the second sweep reuses every column
    assert drv._witcols.keys() == cols.keys()
    assert all(drv._witcols[k][2] is a for k, a in cols.items())
