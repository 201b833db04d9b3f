#!/usr/bin/env python
"""Chip smoke: the policy engine's audit and admission paths, end to end,
on one TPU chip, in one process.

    python chip_smoke.py            # one chip: audit + admission
    python chip_smoke.py --chips 4  # four chips: mesh audit vs one chip,
                                    # and the N-engine admission plane

One seeded inventory at the sizes of BASELINE configs 2-4, held at once:
100k Namespaces under 500 K8sRequiredLabels constraints, 10k mixed
Pods/Deployments/Services/Ingresses under the general library (both
inventory-join templates included), and 50k Pods under the 16
pod-security-policy templates — all 23 shipped templates.

Phases (each prints one `name: {json}` line):
  device     platform, device_kind, device count (a non-TPU device exits
             non-zero here, before anything else runs)
  sizes, generate, reference_setup
             the counts (any cut is listed here), inventory generation,
             the reference client's ingest
  audit      Client ingest, client.audit() cold, then warm once
             background compiles are done; compiles by source; per-kind
             device dispatch counts
  reference  a seeded sample of >= 200 objects re-reviewed by the plain
             RegoDriver client must report the same (constraint, msg) set
  admission  control.main.Runtime (--fake-kube, plain HTTP) with the same
             library; single requests and concurrent bursts to /v1/admit;
             every verdict equal to the reference review of the request;
             >= 1 batch evaluated on the device by the cost model's choice
  regex      whether the device regex scan kernel ran

The run fails (non-zero, no "ok": true) when a phase raises, a kind is
interpreter-bound, demoted or quarantined, a kind with candidates was
served on the host in the warm audit, no admission batch ran on the
device, or any verdict disagrees with the reference. The last line is
{"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import http.client
import json
import random
import sys
import threading
import time
from dataclasses import dataclass

SEED = 21


@dataclass(frozen=True)
class Sizes:
    namespaces: int = 100_000       # config 4
    label_constraints: int = 500    # config 4
    mixed: int = 10_000             # config 2
    psp_pods: int = 50_000          # config 3
    sample: int = 240               # reference sample, >= 200
    singles: int = 8                # single admission requests
    burst_clients: int = 64         # config 5's closed-loop clients
    burst_per_client: int = 24      # requests per client per burst


FULL = Sizes()


class SmokeFailure(Exception):
    pass


def say(phase: str, **fields) -> None:
    print(f"{phase}: " + json.dumps(fields, sort_keys=True, default=str),
          flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu(chips: int) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say("device", **dev)
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev['platform']}")
    if dev["count"] < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX found {dev['count']}")
    return dev


# ---------------------------------------------------------------- library


def _match(*kinds) -> dict:
    return {"kinds": [{"apiGroups": list(groups), "kinds": [k]}
                      for groups, k in kinds]}


POD = ([""], "Pod")
GENERAL_MATCH = {
    "K8sAllowedRepos": _match(POD),
    "K8sContainerLimits": _match(POD),
    "K8sContainerRatios": _match(POD),
    "K8sHttpsOnly": _match((["extensions", "networking.k8s.io"],
                            "Ingress")),
    "K8sRequiredLabels": _match((["apps"], "Deployment"),
                                ([""], "Service"),
                                (["extensions", "networking.k8s.io"],
                                 "Ingress")),
    "K8sUniqueIngressHost": _match((["extensions", "networking.k8s.io"],
                                    "Ingress")),
    "K8sUniqueServiceSelector": _match(([""], "Service")),
}


def _constraint(kind: str, name: str, params, match: dict) -> dict:
    spec = {"match": match}
    if params:
        spec["parameters"] = params
    return {"apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind, "metadata": {"name": name}, "spec": spec}


def build_library(sizes: Sizes, seed: int = SEED) -> dict:
    """All 23 shipped templates, their constraints and the inventory.
    Each library's constraints match the kinds its config targets."""
    from gatekeeper_tpu import policies
    from gatekeeper_tpu.parallel.workload import (
        GENERAL_CONSTRAINTS, PSP_CONSTRAINTS, synth_constraints,
        synth_mixed_objects, synth_objects, synth_pods_psp)

    constraints = []
    for c in synth_constraints(sizes.label_constraints, seed=seed):
        c["spec"]["match"] = _match(([""], "Namespace"))
        constraints.append(c)
    for kind, name, params in GENERAL_CONSTRAINTS:
        constraints.append(_constraint(kind, name, params,
                                       GENERAL_MATCH[kind]))
    for kind, name, params in PSP_CONSTRAINTS:
        constraints.append(_constraint(kind, name, params, _match(POD)))
    pods = synth_pods_psp(sizes.psp_pods, seed=seed + 2)
    for p in pods:  # the mixed set names its pods pod-<i> too
        p["metadata"]["name"] = "psp-" + p["metadata"]["name"]
    return {
        "templates": [policies.load(n) for n in policies.names()],
        "constraints": constraints,
        "namespaces": synth_objects(sizes.namespaces, violate_frac=0.01,
                                    seed=seed),
        "mixed": synth_mixed_objects(sizes.mixed, seed=seed + 1),
        "pods": pods,
    }


def without_psp(lib: dict) -> dict:
    """The library minus the pod-security-policy templates and pods:
    the --chips 4 cut (its 16 kinds never reach the mesh's row floor on
    their own and cost ~25 s of TPU compile each, twice over — once per
    driver — at four times the chip price)."""
    def general(kind: str) -> bool:
        return not kind.startswith("K8sPSP")

    return {**lib, "pods": [],
            "templates": [t for t in lib["templates"] if general(
                t["spec"]["crd"]["spec"]["names"]["kind"])],
            "constraints": [c for c in lib["constraints"]
                            if general(c["kind"])]}


def inventory(lib: dict) -> list:
    return lib["namespaces"] + lib["mixed"] + lib["pods"]


def load_client(client, lib: dict) -> None:
    for t in lib["templates"]:
        client.add_template(t)
    for c in lib["constraints"]:
        client.add_constraint(c)
    for o in inventory(lib):
        client.add_data(o)


def ident(obj: dict) -> tuple:
    meta = obj.get("metadata") or {}
    return (obj.get("kind"), meta.get("namespace", ""), meta.get("name"))


def verdict_key(r) -> tuple:
    c = r.constraint or {}
    return (c.get("kind"), (c.get("metadata") or {}).get("name"), r.msg)


def new_client(driver):
    from gatekeeper_tpu.client import Backend
    from gatekeeper_tpu.target import K8sValidationTarget

    return Backend(driver).new_client([K8sValidationTarget()])


def reference_client(lib: dict):
    """The plain reference: a RegoDriver client over the same templates,
    constraints and inventory."""
    from gatekeeper_tpu.client import RegoDriver

    ref = new_client(RegoDriver())
    load_client(ref, lib)
    return ref


# ------------------------------------------------------------------ audit


def wait_compiles(driver, timeout_s: float = 900.0) -> float:
    t0 = time.monotonic()
    while driver.warm_status()["compiling"]:
        check(time.monotonic() - t0 < timeout_s,
              f"background compiles still running after {timeout_s}s")
        time.sleep(0.1)
    return time.monotonic() - t0


def device_health(driver) -> dict:
    """Interpreter-bound kinds, demotions and quarantines: all must be
    empty for a run that claims the chip did the work."""
    return {"fallback": driver.fallback_reasons(),
            "demotions": driver.demotion_counts(),
            "quarantined": driver.quarantine_status()}


def _count_delta(before: dict, after: dict) -> dict:
    out = {}
    for kind, paths in after.items():
        d = {p: n - before.get(kind, {}).get(p, 0) for p, n in paths.items()}
        d = {p: n for p, n in d.items() if n}
        if d:
            out[kind] = d
    return out


def audit_phase(lib: dict, driver=None, label: str = "audit") -> dict:
    """Ingest, then client.audit() cold and warm through the public
    Client API. Returns the warm results and per-phase facts."""
    from gatekeeper_tpu.ir import TpuDriver

    t0 = time.monotonic()
    driver = driver if driver is not None else TpuDriver()
    client = new_client(driver)
    load_client(client, lib)
    setup_s = time.monotonic() - t0
    kinds = client.template_kinds()

    t0 = time.monotonic()
    cold = client.audit().results()
    cold_s = time.monotonic() - t0
    compile_wait_s = wait_compiles(driver)
    cold_status = driver.warm_status()

    # the warm audit sweeps every kind in full: the results delta cache
    # would otherwise answer it without touching the device
    driver.drop_audit_results()
    before = driver.eval_counts()
    t0 = time.monotonic()
    warm = client.audit().results()
    warm_s = time.monotonic() - t0
    served = _count_delta(before, driver.eval_counts())
    wait_compiles(driver)  # no XLA compile may outlive the process
    health = device_health(driver)
    facts = {
        "setup_s": setup_s, "cold_audit_s": cold_s,
        "compile_wait_s": compile_wait_s, "warm_audit_s": warm_s,
        "objects": len(inventory(lib)),
        "constraints": len(lib["constraints"]), "kinds": len(kinds),
        "violations_cold": len(cold), "violations_warm": len(warm),
        "path": driver.last_audit_path,
        "compiles_after_cold": cold_status,
        "compiles_after_warm": driver.warm_status(),
        "warm_served_by_kind": served, **health,
    }
    say(label, **facts)
    check(not any(health.values()), f"{label}: device health {health}")
    check(sorted(map(verdict_key, cold)) == sorted(map(verdict_key, warm)),
          f"{label}: cold and warm audits disagree")
    on_host = {k: p for k, p in served.items()
               if set(p) - {"device", "join"}}
    check(not on_host, f"{label}: kinds served on the host in the warm "
                       f"audit: {on_host}")
    missing = sorted(set(kinds) - set(served))
    check(not missing, f"{label}: kinds with no device dispatch in the "
                       f"warm audit: {missing}")
    check(driver.last_audit_path != "host",
          f"{label}: last_audit_path says host")
    return {"driver": driver, "client": client, "results": warm,
            **facts}


def sample_objects(lib: dict, results: list, n: int,
                   seed: int = SEED) -> list:
    """Seeded sample covering every library's kinds: per kind group,
    half from objects the audit flagged, half from the rest."""
    rng = random.Random(seed)
    flagged = {ident(r.resource) for r in results if r.resource}
    groups: dict = {}
    for o in inventory(lib):
        psp = o["metadata"]["name"].startswith("psp-")
        groups.setdefault((o["kind"], psp), []).append(o)
    per = max(2, n // len(groups))
    out = []
    for key in sorted(groups):
        objs = groups[key]
        bad = [o for o in objs if ident(o) in flagged]
        good = [o for o in objs if ident(o) not in flagged]
        k_bad = min(len(bad), max(per // 2, per - len(good)))
        out += rng.sample(bad, k_bad)
        out += rng.sample(good, min(len(good), per - k_bad))
    return out


def reference_phase(ref, sample: list, results: list,
                    label: str = "reference") -> dict:
    """The (constraint, msg) set the device audit reported per sampled
    object must equal the reference review of that object."""
    by_obj: dict = {}
    for r in results:
        if r.resource:
            by_obj.setdefault(ident(r.resource), set()).add(verdict_key(r))
    t0 = time.monotonic()
    agree = 0
    bad = []
    n_viol = 0
    for obj in sample:
        want = {verdict_key(r) for r in ref.review(obj).results()}
        got = by_obj.get(ident(obj), set())
        n_viol += len(want)
        if want == got:
            agree += 1
        elif len(bad) < 5:
            bad.append({"object": ident(obj), "device": sorted(got),
                        "reference": sorted(want)})
    kinds = sorted({o["kind"] for o in sample})
    facts = {"sampled": len(sample), "agree": agree,
             "sample_violations": n_viol, "sample_kinds": kinds,
             "reference_s": time.monotonic() - t0, "disagreements": bad}
    say(label, **facts)
    check(agree == len(sample), f"{label}: {len(sample) - agree} "
                                "sampled objects disagree")
    check(len(kinds) >= 4 and n_viol > 0,
          f"{label}: sample misses kinds or violations")
    return facts


# -------------------------------------------------------------- admission


def admission_review(obj: dict, uid: str) -> dict:
    group, _, version = (obj.get("apiVersion") or "").rpartition("/")
    meta = obj["metadata"]
    req = {"uid": uid, "operation": "CREATE", "name": meta["name"],
           "kind": {"group": group, "version": version,
                    "kind": obj["kind"]},
           "userInfo": {"username": "chip-smoke"}, "object": obj}
    if meta.get("namespace"):
        req["namespace"] = meta["namespace"]
    return {"apiVersion": "admission.k8s.io/v1beta1",
            "kind": "AdmissionReview", "request": req}


def admission_objects(sizes: Sizes, n: int, tag: str, seed: int) -> list:
    """Fresh objects for admission: half Namespaces (500 constraints
    each), a third PSP pods, the rest mixed general-library objects
    (their Ingress hosts and Service selectors collide with the
    inventory's, so the join templates fire)."""
    from gatekeeper_tpu.parallel.workload import (
        synth_mixed_objects, synth_objects, synth_pods_psp)

    n_ns = n // 2
    n_pod = n // 3
    objs = synth_objects(n_ns, violate_frac=0.05, seed=seed)
    objs += synth_pods_psp(n_pod, seed=seed + 1)
    objs += synth_mixed_objects(n - n_ns - n_pod, seed=seed + 2)
    for i, o in enumerate(objs):
        o["metadata"]["name"] = f"{tag}-{i}-{o['metadata']['name']}"
    random.Random(seed).shuffle(objs)
    return objs


def start_runtime(lib: dict, extra_args=()):
    """control.main.Runtime over the in-memory API server, plain HTTP,
    serving /v1/admit in this process. The library is applied through
    the API server; Services and Ingresses are synced into its inventory
    for the join templates."""
    from gatekeeper_tpu.control.main import Runtime, build_parser

    args = build_parser().parse_args([
        "--fake-kube", "--port", "0", "--prometheus-port", "0",
        "--health-addr", ":0", "--disable-cert-rotation",
        "--operation", "webhook", "--exempt-namespace",
        "gatekeeper-system", *extra_args])
    rt = Runtime(args)
    rt.args.metrics_backend = "none"
    rt.kube.register_kind(("networking.k8s.io", "v1beta1", "Ingress"),
                          namespaced=True)
    namespaces = {o["metadata"].get("namespace")
                  for o in inventory(lib)} - {None}
    ns_objs = {}
    for name in sorted(namespaces | {"gatekeeper-system"}):
        ns_objs[name] = {"apiVersion": "v1", "kind": "Namespace",
                         "metadata": {"name": name}}
        rt.kube.create(ns_objs[name])
    rt.kube.create({
        "apiVersion": "config.gatekeeper.sh/v1alpha1", "kind": "Config",
        "metadata": {"name": "config", "namespace": "gatekeeper-system"},
        "spec": {"sync": {"syncOnly": [
            {"group": "", "version": "v1", "kind": "Service"},
            {"group": "networking.k8s.io", "version": "v1beta1",
             "kind": "Ingress"}]}}})
    rt.start()
    for t in lib["templates"]:
        rt.kube.create(t)
    rt.manager.drain()
    for c in lib["constraints"]:
        rt.kube.create(c)
    for o in lib["mixed"]:
        if o["kind"] in ("Service", "Ingress"):
            rt.kube.create(o)
    rt.manager.drain()
    return rt, ns_objs


def post_admit(port: int, review: dict) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("POST", "/v1/admit", json.dumps(review),
                     {"Content-Type": "application/json"})
        return json.loads(conn.getresponse().read())["response"]
    finally:
        conn.close()


def burst(port: int, reviews: list, clients: int) -> tuple:
    """`clients` concurrent closed-loop clients over the reviews."""
    out = [None] * len(reviews)
    errors = []

    def run(k: int) -> None:
        try:
            for i in range(k, len(reviews), clients):
                out[i] = post_admit(port, reviews[i])
        except Exception as e:
            errors.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=run, args=(k,))
               for k in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    check(not errors, f"admission burst errors: {errors[:3]}")
    return out, time.monotonic() - t0


def admission_phase(lib: dict, ref, sizes: Sizes) -> dict:
    from gatekeeper_tpu.control.webhook import verdict_response
    from gatekeeper_tpu.target.handler import AugmentedReview

    t0 = time.monotonic()
    rt, ns_objs = start_runtime(lib)
    try:
        driver = rt.opa.driver
        setup_s = time.monotonic() - t0
        check(set(rt.opa.template_kinds())
              == {t["spec"]["crd"]["spec"]["names"]["kind"]
                  for t in lib["templates"]},
              "runtime did not ingest the whole library")
        port = rt.webhook.port
        n_burst = sizes.burst_clients * sizes.burst_per_client
        singles = [admission_review(o, f"single-{i}") for i, o in
                   enumerate(admission_objects(sizes, sizes.singles,
                                               "single", SEED + 10))]
        rounds = [[admission_review(o, f"burst{r}-{i}") for i, o in
                   enumerate(admission_objects(sizes, n_burst,
                                               f"burst{r}",
                                               SEED + 20 + r))]
                  for r in range(2)]

        t0 = time.monotonic()
        got = [post_admit(port, rv) for rv in singles]
        singles_s = time.monotonic() - t0
        got_cold, cold_s = burst(port, rounds[0], sizes.burst_clients)
        compile_wait_s = wait_compiles(driver)
        got_warm, warm_s = burst(port, rounds[1], sizes.burst_clients)
        wait_compiles(driver)
        counts = driver.eval_counts()
        health = device_health(driver)

        reviews = singles + rounds[0] + rounds[1]
        responses = got + got_cold + got_warm
        t0 = time.monotonic()
        agree = denied = 0
        bad = []
        for review, resp in zip(reviews, responses):
            req = review["request"]
            ns = ns_objs.get(req.get("namespace"))
            results = ref.review(AugmentedReview(req, ns)).results()
            want = verdict_response((r.enforcement_action, r.msg)
                                    for r in results)
            have = {k: v for k, v in resp.items() if k != "uid"}
            denied += not want["allowed"]
            if have == want:
                agree += 1
            elif len(bad) < 5:
                bad.append({"uid": req["uid"], "webhook": have,
                            "reference": want})
        by_path: dict = {}
        for paths in counts.values():
            for p, n in paths.items():
                if p.startswith("batch-"):
                    by_path[p] = by_path.get(p, 0) + n
        device_kinds = sorted(k for k, p in counts.items()
                              if p.get("batch-device")
                              or p.get("batch-join"))
        facts = {"setup_s": setup_s, "singles": len(singles),
                 "singles_s": singles_s, "burst_requests": n_burst,
                 "burst_clients": sizes.burst_clients,
                 "cold_burst_s": cold_s, "compile_wait_s": compile_wait_s,
                 "warm_burst_s": warm_s,
                 "warm_burst_rps": n_burst / warm_s,
                 "requests": len(reviews), "agree": agree,
                 "denied": denied, "reference_s": time.monotonic() - t0,
                 "batch_evals_by_path": by_path,
                 "device_batch_kinds": device_kinds,
                 "compiles": driver.warm_status(),
                 "disagreements": bad, **health}
        say("admission", **facts)
    finally:
        rt.stop()
    check(not any(health.values()), f"admission: device health {health}")
    check(agree == len(reviews), f"admission: {len(reviews) - agree} "
                                 "verdicts disagree with the reference")
    check(denied > 0 and denied < len(reviews),
          "admission: verdicts are all-allow or all-deny")
    check(by_path.get("batch-device", 0) > 0,
          "admission: no batch ran on the device")
    return facts


def regex_phase() -> dict:
    from gatekeeper_tpu.ops import regex_nfa

    facts = {"device_scans": regex_nfa.DEVICE_SCANS,
             "crossover": regex_nfa.DEVICE_CROSSOVER}
    facts["note"] = ("the device regex scan ran" if facts["device_scans"]
                     else "the device regex scan never ran under the "
                          "current crossover (re-tuning needs a chip "
                          "measurement)")
    say("regex", **facts)
    return facts


# ------------------------------------------------------------- four chips


def results_key(results: list) -> list:
    return sorted(ident(r.resource) + verdict_key(r) for r in results)


def mesh_spread(driver, chips: int) -> dict:
    """Where the mesh audit put its row-sharded feature buffers: each
    must hold a non-empty shard on every one of the mesh's devices."""
    import jax

    devs = jax.devices()[:chips]
    rows = {d.id: 0 for d in devs}
    n_bufs = 0
    # the driver's mesh placements: (host array ref, device buffer)
    for _ref, buf in list(driver._dev_mesh_cache.values()):
        spec = tuple(buf.sharding.spec)
        if not spec or spec[0] != "data":
            continue  # replicated parameters and tables
        n_bufs += 1
        held = {s.device.id: s.data.shape[0]
                for s in buf.addressable_shards}
        check(set(held) == set(rows) and min(held.values()) > 0,
              f"a row-sharded buffer {buf.shape} has rows {held} by "
              f"device, not a share on each of {sorted(rows)}")
        for i, n in held.items():
            rows[i] += n
    facts = {"row_sharded_buffers": n_bufs,
             "rows_by_device": [rows[d.id] for d in devs],
             "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use")
                              for d in devs]}
    say("mesh_placement", **facts)
    check(n_bufs > 0, "the mesh audit left no row-sharded buffer")
    return facts


def mesh_phase(lib: dict, ref, sizes: Sizes, chips: int) -> dict:
    """The default (mesh) audit on every chip against a one-chip
    TpuDriver(device=0) in the same process, plus the reference
    sample. The mesh's resident buffers must span every device."""
    import jax

    from gatekeeper_tpu.ir import TpuDriver

    mesh = audit_phase(lib, TpuDriver(), label="mesh_audit")
    check(mesh["path"] == f"mesh(data={chips})",
          f"mesh audit took {mesh['path']}")
    placement = mesh_spread(mesh["driver"], chips)
    if jax.devices()[0].platform == "tpu":  # the CPU keeps no stats
        check(all(placement["bytes_in_use"]),
              f"a chip reports no memory in use: {placement}")
    single = audit_phase(lib, TpuDriver(device=0), label="single_audit")
    check(single["path"] == "single", f"single audit took "
                                      f"{single['path']}")
    same = results_key(mesh["results"]) == results_key(single["results"])
    say("mesh_vs_single", equal=same,
        mesh_warm_audit_s=mesh["warm_audit_s"],
        single_warm_audit_s=single["warm_audit_s"])
    check(same, "mesh audit differs from the one-chip audit")
    sample = sample_objects(lib, mesh["results"], sizes.sample)
    reference_phase(ref, sample, mesh["results"], label="mesh_reference")
    return {"mesh": mesh["path"], "equal": same}


def engines_phase(lib: dict) -> dict:
    """--admission-engines / --audit-shards > 1 start one child per
    chip from a parent that holds every chip: they must refuse at start
    with a clear message, never hang or time-share."""
    out = {}
    for flag in ("--admission-engines", "--audit-shards"):
        try:
            rt, _ = start_runtime(lib, extra_args=(flag, "4"))
        except SystemExit as e:
            out[flag] = str(e)
            continue
        rt.stop()
        raise SmokeFailure(f"{flag} 4 started on a TPU host")
    say("engines", refused=out)
    return out


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the multi-chip paths (mesh audit "
                        "vs one chip, N-engine admission plane)")
    args = p.parse_args(argv)
    dev = require_tpu(args.chips)
    sizes = FULL
    say("sizes", **sizes.__dict__)
    try:
        t0 = time.monotonic()
        lib = build_library(sizes)
        say("generate", seconds=time.monotonic() - t0,
            objects=len(inventory(lib)),
            constraints=len(lib["constraints"]),
            templates=len(lib["templates"]))
        if args.chips > 1:
            lib = without_psp(lib)
            say("cut", psp_library="dropped for --chips 4",
                objects=len(inventory(lib)),
                constraints=len(lib["constraints"]))
        t0 = time.monotonic()
        ref = reference_client(lib)
        say("reference_setup", seconds=time.monotonic() - t0)
        if args.chips > 1:
            mesh_phase(lib, ref, sizes, args.chips)
            engines_phase(lib)
        else:
            audit = audit_phase(lib)
            sample = sample_objects(lib, audit["results"], sizes.sample)
            reference_phase(ref, sample, audit["results"])
            del audit
            admission_phase(lib, ref, sizes)
            regex_phase()
    except SmokeFailure as e:
        say("failed", reason=str(e))
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
