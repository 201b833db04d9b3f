"""The pod-security-policy library on Pods shaped as a service mesh
injects them: one to three containers and up to two init containers, a
rule broken on the main container, on a sidecar or on an init container.
The audit on the device path equals the Rego interpreter's, message for
message, and every kind is dispatched on the device."""

from __future__ import annotations

import copy
import time
from collections import Counter

import pytest

from gatekeeper_tpu import policies
from gatekeeper_tpu.client import Backend, RegoDriver
from gatekeeper_tpu.ir import TpuDriver
from gatekeeper_tpu.parallel.workload import PSP_CONSTRAINTS
from gatekeeper_tpu.target import K8sValidationTarget

# the repo's parameters, with flex drivers listed (an empty list allows
# every driver) and flexVolume among the allowed volume types
PARAMS = {"flex": {"allowedFlexVolumes": [{"driver": "example/lvm"}]},
          "volumes": {"volumes": ["configMap", "secret", "emptyDir",
                                  "hostPath", "flexVolume"]}}

# container-level breaks: securityContext fields, the apparmor
# annotation, a host port
BREAKS = [
    ("allowPrivilegeEscalation", True), ("privileged", True),
    ("readOnlyRootFilesystem", False), ("runAsUser", 0),
    ("procMount", "Unmasked"), ("seLinuxOptions", {"level": "s0:c1"}),
    ("capabilities", {"add": ["SYS_ADMIN"], "drop": ["ALL"]}),
    ("capabilities", {"drop": []}), ("apparmor", "unconfined"),
    ("hostPort", 80),
]


def _container(name: str) -> dict:
    return {"name": name, "image": f"registry.corp.example/{name}:v1",
            "securityContext": {"allowPrivilegeEscalation": False,
                                "readOnlyRootFilesystem": True,
                                "runAsUser": 1000,
                                "capabilities": {"drop": ["ALL"]}},
            "ports": [{"containerPort": 8080}]}


def pods(where: str) -> list:
    """One Pod per (containers, init containers, break): the break on
    the main container, the last sidecar or the last init container."""
    out = []
    for n_c in (1, 2, 3):
        for n_i in (0, 1, 2):
            for b, (field, value) in enumerate(BREAKS + [(None, None)]):
                cs = [_container("main")] + \
                    [_container(f"side{k}") for k in range(1, n_c)]
                inits = [_container(f"init{k}") for k in range(n_i)]
                name = f"p{n_c}{n_i}-{b}"
                ann = {"seccomp.security.alpha.kubernetes.io/pod":
                       "runtime/default"}
                for c in cs + inits:
                    ann["container.apparmor.security.beta.kubernetes.io/"
                        + c["name"]] = "runtime/default"
                target = {"main": cs[0], "sidecar": cs[-1],
                          "init": inits[-1] if inits else None}[where]
                if field is not None and target is not None:
                    if field == "apparmor":
                        ann["container.apparmor.security.beta.kubernetes"
                            ".io/" + target["name"]] = value
                    elif field == "hostPort":
                        target["ports"][0]["hostPort"] = value
                    else:
                        target["securityContext"][field] = \
                            copy.deepcopy(value)
                spec = {"securityContext": {
                            "fsGroup": 2000,
                            "seLinuxOptions": {"level": "s0:c123,c456"}},
                        "containers": cs,
                        "volumes": [{"name": "cfg",
                                     "configMap": {"name": "c"}}]}
                if inits:
                    spec["initContainers"] = inits
                out.append({"apiVersion": "v1", "kind": "Pod",
                            "metadata": {"name": name, "namespace": "mesh",
                                         "annotations": ann},
                            "spec": spec})
    return out


def _client(driver, objects):
    client = Backend(driver).new_client([K8sValidationTarget()])
    for name in policies.names():
        if name.startswith("pod-security-policy/"):
            client.add_template(policies.load(name))
    for kind, cname, params in PSP_CONSTRAINTS:
        spec = {"match": {"kinds": [{"apiGroups": [""],
                                     "kinds": ["Pod"]}]}}
        params = PARAMS.get(cname, params)
        if params:
            spec["parameters"] = params
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind, "metadata": {"name": cname}, "spec": spec})
    for o in objects:
        client.add_data(o)
    return client


def _answer(client) -> Counter:
    return Counter((r.resource["metadata"]["name"],
                    r.constraint["metadata"]["name"], r.msg)
                   for r in client.audit().results())


def _audit(driver, objects) -> Counter:
    return _answer(_client(driver, objects))


@pytest.mark.parametrize("where", ["main", "sidecar", "init"])
def test_device_audit_equals_interpreter(where):
    objects = pods(where)
    drv = TpuDriver()
    got = _audit(drv, objects)
    want = _audit(RegoDriver(), objects)
    assert got == want
    counts = drv.eval_counts()
    assert len(counts) == 16
    assert all(set(paths) == {"device"} for paths in counts.values()), \
        counts
    # the breaks fire: every container-level rule broken somewhere
    fired = {c for _, c, _ in want}
    assert {"no-escalation", "no-privileged", "ro-root", "users",
            "procmount", "selinux", "caps", "apparmor-default",
            "host-ports"} <= fired


def test_first_audit_bounds_host_time_across_kinds():
    """With background compiles on, a first audit serves cold kinds
    from the host only while the audit's host time stays under
    AUDIT_HOST_BUDGET_S. Past it, the remaining kinds are not
    interpreted one after another: their compiles all start, and they
    dispatch on the device within the same audit, with the same
    answer."""
    objects = pods("init")
    want = _audit(RegoDriver(), objects)
    drv = TpuDriver()
    drv.async_warm = True
    # every kind's own estimate is negligible, as at audit scale, where
    # the estimate fell far under the host's real time
    drv._host_pair_rate = 1e9
    drv.AUDIT_HOST_BUDGET_S = 1e-4
    client = _client(drv, objects)
    assert _answer(client) == want
    counts = drv.eval_counts()
    host = [k for k, paths in counts.items() if "interp" in paths]
    device = [k for k, paths in counts.items() if "device" in paths]
    assert len(host) == 1 and len(device) == 15, counts
    assert drv.last_audit_path == "single"
    deadline = time.time() + 120
    while drv.warm_status()["compiling"] and time.time() < deadline:
        time.sleep(0.05)
    assert drv.warm_status()["warm"] == 16
    drv.drop_audit_results()
    assert _answer(client) == want
