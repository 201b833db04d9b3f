"""The thresholds cluster with Pods that carry every field the
pod-security-policy library reads: `workload.cluster_fixed`'s Namespaces
and Services (no breaker among them), and Pods dealt over the Namespaces
the same way, each passing all 16 PSP constraints but exactly
breakers["Pod"] of them, each of which breaks one constraint's rule and
no other's.

    {"generator": "psp-cluster", "counts", "breakers", "sidecar_every",
     "node_agent_every", "seed_offset"}

Breakers sit at seeded positions and are dealt round-robin over BREAKS,
the order of the configuration's constraints; a container's break falls
on the main container or, on every other breaker of a Pod with
sidecars, on its init container.
"""

from __future__ import annotations

import random

from benchmark.gen import workload

# the constraint each breaker breaks, by name, in the configuration's order
BREAKS = ["no-escalation", "apparmor-default", "caps", "flex", "sysctls",
          "fsgroup", "hostfs", "no-host-ns", "host-ports", "no-privileged",
          "procmount", "ro-root", "seccomp", "selinux", "users", "volumes"]
# the breaks that fall on one container
CONTAINER_BREAKS = {"no-escalation", "apparmor-default", "caps",
                    "host-ports", "no-privileged", "procmount", "ro-root",
                    "selinux", "users"}

SECCOMP_POD = "seccomp.security.alpha.kubernetes.io/pod"
APPARMOR = "container.apparmor.security.beta.kubernetes.io/"
PROXY_IMAGE = "registry.corp.example/istio/proxyv2:1.20"


def _container(name: str, image: str, uid: int, port: int | None,
               mounts: list) -> dict:
    c = {"name": name, "image": image,
         "securityContext": {"allowPrivilegeEscalation": False,
                             "readOnlyRootFilesystem": True,
                             "runAsUser": uid,
                             "capabilities": {"drop": ["ALL"]}}}
    if port is not None:
        c["ports"] = [{"containerPort": port, "protocol": "TCP"}]
    if mounts:
        c["volumeMounts"] = mounts
    return c


def pod(i: int, namespace: str, rng: random.Random, sidecar: bool,
        agent: int | None, brk: str | None = None,
        on_init: bool = False, alt: bool = False) -> dict:
    """Pod i: one main container (with an istio-proxy sidecar and an
    istio-init init container when `sidecar`), a host log reader with
    hostPort `agent` when that is given, and, when `brk` names a
    constraint, that constraint's rule broken (on the init container
    when `on_init`; `alt` picks the second way of breaking hostfs)."""
    uid = 1000 + i % 1000
    main = _container(
        "main", rng.choice(workload.REPOS) + f"svc{i % 20}:v1", uid, 8080,
        [{"name": "config", "mountPath": "/etc/app", "readOnly": True},
         {"name": "creds", "mountPath": "/var/run/creds", "readOnly": True},
         {"name": "tmp", "mountPath": "/tmp"}])
    main["resources"] = {"limits": {"cpu": "900m", "memory": "512Mi"},
                         "requests": {"cpu": "250m", "memory": "256Mi"}}
    containers, inits = [main], []
    if sidecar:
        containers.append(_container("istio-proxy", PROXY_IMAGE, 1337,
                                     15090, [{"name": "tmp",
                                              "mountPath": "/etc/istio"}]))
        inits.append(_container("istio-init", PROXY_IMAGE, 1337, None, []))
    volumes = [{"name": "config", "configMap": {"name": "app-config"}},
               {"name": "creds", "secret": {"secretName": "app-creds"}},
               {"name": "tmp", "emptyDir": {}}]
    if agent is not None:
        main["ports"][0]["hostPort"] = agent
        volumes.append({"name": "varlog", "hostPath": {"path": "/var/log"}})
        main["volumeMounts"].append({"name": "varlog",
                                     "mountPath": "/var/log",
                                     "readOnly": True})
    annotations = {SECCOMP_POD: "runtime/default"}
    for c in containers + inits:
        annotations[APPARMOR + c["name"]] = "runtime/default"
    pod_ctx = {"fsGroup": 2000,
               "seLinuxOptions": {"level": "s0:c123,c456"},
               "sysctls": [{"name": "net.ipv4.ip_local_port_range",
                            "value": "1024 65535"}]}
    spec = {"securityContext": pod_ctx, "containers": containers,
            "volumes": volumes}
    if inits:
        spec["initContainers"] = inits
    out = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": f"pod-{i}", "namespace": namespace,
                        "labels": {"owner": "team.corp.example",
                                   "app": f"app{i % 50}"},
                        "annotations": annotations},
           "spec": spec}
    if brk is not None:
        _break(out, brk, inits[0] if on_init else main, alt)
    return out


def _break(p: dict, brk: str, c: dict, alt: bool) -> None:
    """Break constraint `brk`'s rule in Pod p, on container c where the
    rule is a container's."""
    spec, ctx = p["spec"], c["securityContext"]
    if brk == "no-escalation":
        ctx["allowPrivilegeEscalation"] = True
    elif brk == "apparmor-default":
        p["metadata"]["annotations"][APPARMOR + c["name"]] = "unconfined"
    elif brk == "caps":
        ctx["capabilities"]["add"] = ["SYS_ADMIN"]
    elif brk == "flex":
        spec["volumes"].append({"name": "flex",
                                "flexVolume": {"driver": "example/nfs"}})
    elif brk == "sysctls":
        spec["securityContext"]["sysctls"].append(
            {"name": "kernel.msgmax", "value": "65536"})
    elif brk == "fsgroup":
        spec["securityContext"]["fsGroup"] = 100
    elif brk == "hostfs":
        name, path = ("host-log", "/var/log/app") if alt else \
            ("host-etc", "/etc")
        spec["volumes"].append({"name": name, "hostPath": {"path": path}})
        spec["containers"][0]["volumeMounts"].append(
            {"name": name, "mountPath": "/host", "readOnly": not alt})
    elif brk == "no-host-ns":
        spec["hostPID"] = True
    elif brk == "host-ports":
        ports = c.setdefault("ports", [{"containerPort": 80}])
        ports[0]["hostPort"] = 80
    elif brk == "no-privileged":
        ctx["privileged"] = True
    elif brk == "procmount":
        ctx["procMount"] = "Unmasked"
    elif brk == "ro-root":
        ctx["readOnlyRootFilesystem"] = False
    elif brk == "seccomp":
        p["metadata"]["annotations"][SECCOMP_POD] = "unconfined"
    elif brk == "selinux":
        ctx["seLinuxOptions"] = {"level": "s0:c1,c2"}
    elif brk == "users":
        ctx["runAsUser"] = 0
    elif brk == "volumes":
        spec["volumes"].append({"name": "shared",
                                "nfs": {"server": "nfs.corp.example",
                                        "path": "/exports"}})
    else:
        raise ValueError(f"no break for constraint {brk!r}")


def breaks(n_pod: int, n_break: int, seed: int, sidecar_every: int) -> dict:
    """{pod index: (constraint name, on the init container, alt)} of the
    breakers: seeded positions, dealt round-robin over BREAKS."""
    rng = random.Random(seed)
    out = {}
    for k, i in enumerate(workload._positions(n_pod, n_break, rng)):
        brk = BREAKS[k % len(BREAKS)]
        second = (k // len(BREAKS)) % 2 == 1
        on_init = second and brk in CONTAINER_BREAKS and \
            i % sidecar_every == 0
        out[i] = (brk, on_init, second)
    return out


def require_audit_host_budget() -> None:
    """Refuse, at once, a program whose first audit would not end
    within a run's time limit: without an audit-wide host budget
    (`TpuDriver.AUDIT_HOST_BUDGET_S`) the first audit of this cluster
    interprets the 16 cold kinds on the host one after another, 552-569 s
    on a one-chip v5e host."""
    from gatekeeper_tpu.ir import TpuDriver

    if not hasattr(TpuDriver, "AUDIT_HOST_BUDGET_S"):
        raise SystemExit("psp-cluster: the program has no audit-wide host "
                         "budget (TpuDriver.AUDIT_HOST_BUDGET_S); its first "
                         "audit of this cell would interpret every kind on "
                         "the host for ~10 minutes")


def build(entry: dict, config: dict, seed: int) -> list:
    require_audit_host_budget()
    counts = entry["counts"]
    seed += entry.get("seed_offset", 0)
    extra = set(counts) - {"Namespace", "Pod", "Service"}
    if extra:
        raise ValueError(f"psp-cluster makes no {sorted(extra)}")
    n_ns, n_pod = counts.get("Namespace", 0), counts.get("Pod", 0)
    if n_pod and not n_ns:
        raise ValueError("Pods need Namespaces")
    rest = workload.cluster_fixed(
        {k: n for k, n in counts.items() if k != "Pod"}, {}, seed)
    sidecar_every = entry["sidecar_every"]
    agent_every = entry["node_agent_every"]
    broken = breaks(n_pod, entry["breakers"].get("Pod", 0), seed,
                    sidecar_every)
    rng = random.Random(seed + 1)
    pods = []
    for i in range(n_pod):
        agent = 8000 + (i // agent_every) % 1001 \
            if i % agent_every == 0 else None
        brk, on_init, alt = broken.get(i, (None, False, False))
        pods.append(pod(i, f"ns-{i % n_ns}", rng, i % sidecar_every == 0,
                        agent, brk, on_init, alt))
    return [o for o in rest if o["kind"] == "Namespace"] + pods + \
        [o for o in rest if o["kind"] != "Namespace"]
