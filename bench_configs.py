#!/usr/bin/env python
"""BASELINE.md configs #1, #2, #3, #5, #6, #7, #8 (config #4 is
bench.py's headline).

One JSON line per config:
  #1 requiredlabels x 1k Namespaces     — full audit wall-clock + the
     measured local interpreter (local-OPA stand-in) audit baseline
  #2 full shipped general library x 10k mixed objects — full audit
  #3 full shipped pod-security-policy library x 50k Pods (regex-heavy)
     — full audit
  #5 streaming admission vs the FULL general library, in tiers:
     pre-batched engine throughput (driver.review_batch), the same
     batches over the real gRPC wire (ReviewBatch RPC), the 64-client
     closed-loop micro-batcher harness, an OPEN-LOOP multi-process
     HTTP sweep against the real webhook server, and the serving
     plane (pre-forked frontends over the shared batching backplane)
     at 1/2/4 workers — the `admission_rps` headline
  #6 steady-state audit @ 1% churn — PSP library x 50k pods with ~1% of
     objects mutated between sweeps: incremental (journal-patched)
     sweep vs the full re-encode sweep
  #7 mutating admission: micro-batched /v1/mutate throughput + p50 at
     three mutator-library sizes (batched applicability matching +
     host apply-to-convergence + RFC-6902 patch generation)
  #8 resilience under overload: a 64-thread closed loop against a
     deliberately slowed flusher with a bounded queue and 2s propagated
     deadlines — shed/deadline fractions plus the worst decision
     latency as a fraction of the deadline (must stay < 1.0)
  #9 warm restart vs cold boot: time-to-ready at the config-6 inventory
     scale — restore the durable state snapshots (vocab + library +
     encoded inventory + tracker) and re-validate vs a live list,
     against the cold library-ingest + full list/encode resync path
  #10 multichip audit promotion at 1M+ objects: the default (no-flag)
     mesh-sharded audit path vs the forced single-device path, each in
     a fresh subprocess (on a 1-device host the mesh run forces 8
     host-platform devices so the slab pipeline is exercised)
  #12 compiler-widening speedup: per-kind steady audit latency on the
     extended-form corpus (upstream-canonical Rego shapes that were
     interpreter-bound before the PR 10 widening), interpreter driver
     vs the newly device-compiled path, plus the shipped general
     library's device coverage (general_library_compiled_fraction
     must read 1.0)
  #13 sharded inventory plane at 10M * BENCH_SCALE objects
     (BENCH_C13_OBJECTS overrides): the inventory consistent-hashed
     across 1/2/4 audit shard processes — spawn + slice-sync wall,
     the full composed-round wall (objects_per_sec headline), and the
     steady incremental round under ~0.1% routed churn, vs the
     unsharded single-client sweep
  #14 adaptive serving controller: an edge-bound closed loop from cold
     mis-tuned defaults (max_wait 50ms) with the controller armed must
     converge to within ~10% of the config-5 hand-tuned optimum's rps
     with the actuation flip count gated, survive a mid-burst engine
     kill with zero unanswered admissions, and restore the baseline
     knobs bit-exactly on the kill switch

All audits run steady-state through client.audit() (warm caches), same
contract as bench.py. Run: python bench_configs.py [1 2 3 5 6 7 8 9]
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

from gatekeeper_tpu.parallel.workload import (  # noqa: E402,F401
    GENERAL_CONSTRAINTS,
    PSP_CONSTRAINTS,
    synth_mixed_objects,
    synth_pods_psp,
)

TARGET = "admission.k8s.gatekeeper.sh"
SCALE = float(os.environ.get("BENCH_SCALE", 1.0))  # shrink for smoke runs


def new_client(driver=None):
    from gatekeeper_tpu.client import Backend
    from gatekeeper_tpu.ir import TpuDriver
    from gatekeeper_tpu.target import K8sValidationTarget

    driver = driver or TpuDriver()
    return driver, Backend(driver).new_client([K8sValidationTarget()])


def compiled_coverage(drv, client) -> dict:
    """Per-library device coverage (ROADMAP item 4's tracked number):
    the fraction of ingested template kinds served by a device program
    (dense CompiledTemplate or inventory-join) rather than interpreter
    fallback — a compiler regression that silently demotes a kind shows
    up here as a fraction drop, not just a latency creep."""
    kinds = client.template_kinds()
    device = [k for k in kinds
              if (hasattr(drv, "compiled_for")
                  and drv.compiled_for(k) is not None)
              or (hasattr(drv, "join_for")
                  and drv.join_for(k) is not None)]
    return {
        "device_compiled_kinds": len(device),
        "total_kinds": len(kinds),
        "device_compiled_fraction":
            round(len(device) / max(1, len(kinds)), 3),
        "interpreter_kinds": sorted(set(kinds) - set(device)),
    }


def steady_audit(client, iters=3):
    t0 = time.time()
    resp = client.audit()
    first = time.time() - t0
    best = float("inf")
    for _ in range(iters):
        t0 = time.time()
        resp = client.audit()
        best = min(best, time.time() - t0)  # min-of-N: noise-robust
    return best, first, len(resp.results())


def audit_phase_breakdown(drv, client, iters=2) -> dict:
    """Per-phase attribution of one full (non-delta) steady sweep:
    sweep_wall_s (time blocked on the device), materialize_s (violation
    message assembly), status_write_s (streamed constraint-status
    publishing — 0 without an audit manager), and the headline
    materialize_vs_sweep ratio (ROADMAP item 3's gate: <= 1.0 means
    the steady audit is sweep-bound, not host-bound). The results
    delta cache is dropped per iteration so the full pipeline runs."""
    from gatekeeper_tpu.utils import profiling

    best: dict = {}
    best_wall = float("inf")
    for _ in range(iters):
        if hasattr(drv, "drop_audit_results"):
            drv.drop_audit_results()
        snap0 = profiling.timers().snapshot()
        t0 = time.time()
        client.audit()
        wall = time.time() - t0
        phases = profiling.PhaseTimers.diff(snap0,
                                            profiling.timers().snapshot())
        if wall < best_wall:
            best_wall = wall
            best = phases
    sweep = best.get("device_sweep", 0.0)
    mat = best.get("materialize", 0.0)
    return {
        "full_sweep_wall_s": round(best_wall, 4),
        "sweep_wall_s": round(sweep, 4),
        "materialize_s": round(mat, 4),
        "status_write_s": round(best.get("status_write", 0.0), 4),
        "materialize_vs_sweep":
            round(mat / sweep, 3) if sweep > 0 else None,
        "interp_eval_s": round(best.get("interp_eval", 0.0), 4),
    }


# --------------------------------------------------------------- config 1


def config1():
    from gatekeeper_tpu import policies
    from gatekeeper_tpu.client import RegoDriver
    from gatekeeper_tpu.parallel.workload import synth_objects

    n = int(1000 * SCALE)
    constraint = {
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sRequiredLabels", "metadata": {"name": "must-own"},
        "spec": {"parameters": {"labels": [
            {"key": "owner", "allowedRegex": "^[a-z]+.corp.example$"}]}},
    }
    objs = synth_objects(n, violate_frac=0.02, seed=7)

    _, client = new_client()
    client.add_template(policies.load("general/requiredlabels"))
    client.add_constraint(constraint)
    for o in objs:
        client.add_data(o)
    audit_s, first, nres = steady_audit(client)

    # the local-OPA stand-in baseline, measured on the SAME workload
    # (pure interpreter: codegen disabled)
    base_driver = RegoDriver()
    base_driver._codegen_for = lambda *a, **k: None
    _, base_client = new_client(base_driver)
    base_client.add_template(policies.load("general/requiredlabels"))
    base_client.add_constraint(constraint)
    for o in objs:
        base_client.add_data(o)
    t0 = time.time()
    base_n = len(base_client.audit().results())
    base_s = time.time() - t0
    assert base_n == nres
    print(json.dumps({
        "config": 1, "metric": "audit_wall_clock_s", "value": round(audit_s, 4),
        "unit": f"s (requiredlabels x {n} namespaces, steady state)",
        "baseline_interpreter_s": round(base_s, 3),
        "vs_baseline": round(base_s / audit_s, 1),
        "first_audit_s": round(first, 2), "violations": nres,
    }))


# --------------------------------------------------------------- config 2


def config2():
    from gatekeeper_tpu import policies

    n = int(10_000 * SCALE)
    drv, client = new_client()
    for name in policies.names():
        if name.startswith("general/"):
            client.add_template(policies.load(name))
    for kind, cname, params in GENERAL_CONSTRAINTS:
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind, "metadata": {"name": cname},
            "spec": ({"parameters": params} if params else {}),
        })
    for o in synth_mixed_objects(n):
        client.add_data(o)
    audit_s, first, nres = steady_audit(client)
    phases = audit_phase_breakdown(drv, client)
    print(json.dumps({
        "config": 2, "metric": "audit_wall_clock_s",
        "value": round(audit_s, 3),
        "unit": f"s (full general library, {len(GENERAL_CONSTRAINTS)} "
                f"constraints x {n} mixed objects, steady state)",
        "first_audit_s": round(first, 2), "violations": nres,
        **phases,
        **compiled_coverage(drv, client),
    }))


# --------------------------------------------------------------- config 3


def config3():
    from gatekeeper_tpu import policies

    n = int(50_000 * SCALE)
    drv, client = new_client()
    for name in policies.names():
        if name.startswith("pod-security-policy/"):
            client.add_template(policies.load(name))
    for kind, cname, params in PSP_CONSTRAINTS:
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind, "metadata": {"name": cname},
            "spec": ({"parameters": params} if params else {}),
        })
    for o in synth_pods_psp(n):
        client.add_data(o)
    audit_s, first, nres = steady_audit(client)
    phases = audit_phase_breakdown(drv, client)
    # the tentpole's tracked number: cold restart (no cache volume) vs
    # warm restart (populated XLA cache + AOT program store) first
    # audit, each in a fresh subprocess
    coldwarm = coldwarm_probe("3")
    print(json.dumps({
        "config": 3, "metric": "audit_wall_clock_s",
        "value": round(audit_s, 3),
        "unit": f"s (full pod-security-policy library, "
                f"{len(PSP_CONSTRAINTS)} constraints x {n} pods, "
                f"steady state)",
        "first_audit_s": round(first, 2), "violations": nres,
        **phases,
        **compiled_coverage(drv, client),
        **coldwarm,
    }))


# ------------------------------------------------ cold vs warm first audit


def _coldwarm_child(workload: str) -> None:
    """Child process for the cold-vs-warm first-audit probe: build the
    named workload, run ONE audit, print first-audit wall clock + the
    compile source counts (aot=deserialized executable, cache=
    persistent-XLA-cache compile, fresh=cold compile). The parent
    controls cold vs warm purely through the env cache dirs
    (JAX_COMPILATION_CACHE_DIR + GATEKEEPER_TPU_AOT_DIR): an empty dir
    is a cold boot, a populated one is exactly how a restarted pod with
    a cache volume boots."""
    from gatekeeper_tpu.ir import aot

    drv, client = new_client()
    if workload == "3":
        from gatekeeper_tpu import policies

        n = int(50_000 * SCALE)
        for name in policies.names():
            if name.startswith("pod-security-policy/"):
                client.add_template(policies.load(name))
        for kind, cname, params in PSP_CONSTRAINTS:
            client.add_constraint({
                "apiVersion": "constraints.gatekeeper.sh/v1beta1",
                "kind": kind, "metadata": {"name": cname},
                "spec": ({"parameters": params} if params else {}),
            })
        for o in synth_pods_psp(n):
            client.add_data(o)
    else:  # "4": the bench.py headline workload
        from gatekeeper_tpu.parallel.workload import (
            REQUIRED_LABELS_TEMPLATE,
            synth_constraints,
            synth_objects,
        )

        n = int(int(os.environ.get("BENCH_OBJECTS", 100_000)) * SCALE)
        ncons = int(os.environ.get("BENCH_CONSTRAINTS", 500))
        client.add_template(REQUIRED_LABELS_TEMPLATE)
        for c in synth_constraints(ncons, seed=1):
            client.add_constraint(c)
        for o in synth_objects(n, violate_frac=0.01, seed=0):
            client.add_data(o)
    if drv.aot.programs_count():
        # warm boot: give the ingest-time background prewarm a beat to
        # deserialize + adopt the stored sweep signatures (a cold boot
        # has nothing to load and proceeds immediately)
        time.sleep(1.0)
    t0 = time.time()
    resp = client.audit()
    first = time.time() - t0
    # drain background compiles so this run's store is fully populated
    # before the parent launches the warm run against it
    t0w = time.time()
    while drv.warm_status()["compiling"] and time.time() - t0w < 600:
        time.sleep(0.2)
    print(json.dumps({"first_audit_s": round(first, 3),
                      "violations": len(resp.results()),
                      "compile_sources": dict(aot.COMPILE_COUNTS)}))


def coldwarm_probe(workload: str) -> dict:
    """Cold-vs-warm first-audit measurement (the tentpole's tracked
    number): run the workload child twice in fresh subprocesses against
    the same initially-empty compile-cache + AOT dirs. Run 1 pays every
    XLA compile (cold restart with no cache volume); run 2 boots the
    way a restarted pod with the populated volume does — deserialize
    and go."""
    import shutil
    import subprocess
    import tempfile

    tmp = tempfile.mkdtemp(prefix="gk-coldwarm-")
    out: dict = {}
    try:
        env = dict(os.environ)
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(tmp, "xla")
        env["GATEKEEPER_TPU_AOT_DIR"] = os.path.join(tmp, "aot")
        for run in ("cold", "warm"):
            try:
                r = subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--coldwarm-child", workload],
                    capture_output=True, text=True, env=env,
                    timeout=int(os.environ.get("BENCH_COLDWARM_TIMEOUT",
                                               1800)))
            except subprocess.TimeoutExpired:
                out[f"{run}_error"] = "timeout"
                break
            lines = [ln for ln in r.stdout.splitlines()
                     if ln.startswith("{")]
            if not lines:
                out[f"{run}_error"] = (r.stderr or "")[-300:]
                break
            d = json.loads(lines[-1])
            out[f"{run}_first_audit_s"] = d["first_audit_s"]
            out[f"{run}_compile_sources"] = d["compile_sources"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# --------------------------------------------------------------- config 6


def config6():
    """Steady-state audit under churn (the recurring-sweep reality: most
    of the cluster does NOT change between 60s sweeps). PSP library x
    50k pods; ~1% of objects mutate between sweeps. Incremental sweep
    (the driver's journal patches dirty feature/mask rows in place)
    vs the full re-encode sweep (drop_inventory_caches: re-flatten,
    re-extract, re-upload everything) on the same client."""
    import copy

    n = int(50_000 * SCALE)
    churn = max(1, int(n * 0.01))
    drv, client = new_client()
    from gatekeeper_tpu import policies

    for name in policies.names():
        if name.startswith("pod-security-policy/"):
            client.add_template(policies.load(name))
    for kind, cname, params in PSP_CONSTRAINTS:
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind, "metadata": {"name": cname},
            "spec": ({"parameters": params} if params else {}),
        })
    pods = synth_pods_psp(n)
    for o in pods:
        client.add_data(o)
    _warm, first, nres = steady_audit(client, iters=1)
    # wait out the async device warm-up: both timed paths below must
    # measure the steady-state device pipeline, not the host fallback
    # that serves while XLA compiles in the background
    t0 = time.time()
    while hasattr(drv, "warm_status") and \
            drv.warm_status()["compiling"] and time.time() - t0 < 600:
        time.sleep(0.2)
    client.audit()

    rng = random.Random(6)

    def mutate(round_):
        """In-place replacement of ~1% of pods: churned label values
        (vocabulary-growing strings) but unchanged structure, the shape
        the patch journal must absorb without a rebuild."""
        for i in rng.sample(range(n), churn):
            pod = copy.deepcopy(pods[i])
            pod["metadata"].setdefault("labels", {})["churn"] = \
                f"r{round_}-{i}"
            client.add_data(pod)

    inc_s = float("inf")
    for k in range(3):
        mutate(k)
        t0 = time.time()
        r = client.audit()
        inc_s = min(inc_s, time.time() - t0)
    n_inc = len(r.results())

    full_s = float("inf")
    for k in range(3):
        mutate(100 + k)
        drv.drop_inventory_caches()
        t0 = time.time()
        r = client.audit()
        full_s = min(full_s, time.time() - t0)
    n_full = len(r.results())

    print(json.dumps({
        "config": 6, "metric": "churn_audit_wall_clock_s",
        "value": round(inc_s, 3),
        "unit": f"s (pod-security-policy library, {len(PSP_CONSTRAINTS)} "
                f"constraints x {n} pods, ~1% churn between sweeps, "
                "incremental steady state)",
        "full_reencode_s": round(full_s, 3),
        "speedup_vs_full": round(full_s / inc_s, 1),
        "churned_objects": churn,
        "first_audit_s": round(first, 2),
        "violations": n_inc,
        "violations_full_path": n_full,
    }))


# --------------------------------------------------------------- config 9


def config9():
    """Warm restart vs cold boot (statestore tentpole): time-to-ready at
    the config-6 inventory scale. Cold boot = ingest the PSP library and
    full-resync the tracker (list every object, add_data each through
    the target handler: the O(cluster) path every restart used to pay).
    Warm boot = restore the vocab/library/inventory snapshots + tracker
    state, then re-validate against a live (uid, resourceVersion) diff
    list — no per-object re-encode. Also reports both first-audit times
    (the warm one can adopt the snapshotted encoded feature rows)."""
    import shutil
    import tempfile

    from gatekeeper_tpu import policies
    from gatekeeper_tpu.control.audit import AuditManager
    from gatekeeper_tpu.control.kube import FakeKube
    from gatekeeper_tpu.control.statestore import (
        StateStore,
        restore_section,
    )

    n = int(50_000 * SCALE)
    kube = FakeKube()
    kube.register_kind(("", "v1", "Pod"))
    kube.register_kind(("", "v1", "Namespace"))
    for i in range(40):
        kube.create({"apiVersion": "v1", "kind": "Namespace",
                     "metadata": {"name": f"ns{i}"}})
    for pod in synth_pods_psp(n):
        kube.create(pod)

    def ingest_library(client):
        for name in policies.names():
            if name.startswith("pod-security-policy/"):
                client.add_template(policies.load(name))
        for kind, cname, params in PSP_CONSTRAINTS:
            client.add_constraint({
                "apiVersion": "constraints.gatekeeper.sh/v1beta1",
                "kind": kind, "metadata": {"name": cname},
                "spec": ({"parameters": params} if params else {}),
            })

    # ---- cold boot: the path every restart used to pay -------------
    t0 = time.time()
    drv, client = new_client()
    ingest_library(client)
    am = AuditManager(kube, client, incremental=True,
                      gc_stale_statuses=False)
    from gatekeeper_tpu.control.audit import (
        InventoryTracker,
        _auditable_gvks,
    )

    am.tracker = InventoryTracker(kube, client)
    am.tracker.full_resync(_auditable_gvks(kube))
    cold_s = time.time() - t0
    t0 = time.time()
    client.audit()
    cold_audit_s = time.time() - t0

    # ---- snapshot (what the periodic/drain snapshot persists) ------
    state_dir = tempfile.mkdtemp(prefix="gk-state-")
    try:
        store = StateStore(state_dir)
        inv = {"tree": drv.inventory_snapshot() or {},
               "tracker": am.tracker.snapshot()}
        store.save_blob("inventory", inv, codec="marshal")
        store.save("library", client.snapshot_library())
        rows = drv.encoded_rows_snapshot()
        if rows:
            store.save_blob("rows", rows)
        store.save("vocab", drv.vocab_snapshot())
        am.tracker.stop()

        # ---- warm boot: restore + live-list re-validation ----------
        # min of 3 restore cycles, like every other warm measurement
        # here: a single sample of a sub-second restore on a shared
        # 1-core host is GC/scheduler-bimodal (0.4s vs 3.5s observed
        # back to back at identical code)
        import gc

        warm_samples = []
        drv2 = client2 = am2 = None
        retired_drivers = []
        for _ in range(3):
            if am2 is not None:
                am2.tracker.stop()
                retired_drivers.append(drv2)
            gc.collect()  # the cold path's garbage must not bill here
            t0 = time.time()
            drv2, client2 = new_client()
            vocab_ok = restore_section(store, "vocab",
                                       drv2.vocab_restore)
            restore_section(store, "library", client2.restore_library)
            am2 = AuditManager(kube, client2, incremental=True,
                              gc_stale_statuses=False)

            def apply_inventory(snap):
                drv2.inventory_restore(snap.get("tree") or {})
                am2.restore_state(snap.get("tracker") or {})

            restored = restore_section(store, "inventory",
                                       apply_inventory, blob=True)
            if am2.tracker is None:
                # restore fell back (corrupt/torn snapshot): the bench
                # must degrade to the cold path like the product, not
                # crash
                am2.tracker = InventoryTracker(kube, client2)
                am2.tracker.full_resync(_auditable_gvks(kube))
            stats = am2.tracker.apply_pending()  # (uid, rv) re-valid.
            warm_samples.append(time.time() - t0)
        warm_s = min(warm_samples)
        # encoded rows load rides a background thread in the runtime
        # (first-audit optimization, not a readiness dependency) —
        # restored synchronously here so the adopted-rows first audit
        # below is deterministic
        if restored and vocab_ok and rows:
            restore_section(store, "rows", drv2.encoded_rows_restore,
                            blob=True)
        t0 = time.time()
        client2.audit()
        warm_audit_s = time.time() - t0
        adopted = getattr(drv2, "restored_rows_adopted", 0)
        am2.tracker.stop()
        # wait out any background device warm-up before teardown (an
        # XLA compile thread killed at interpreter exit aborts)
        t0 = time.time()
        for d in (drv, drv2, *retired_drivers):
            while hasattr(d, "warm_status") and \
                    d.warm_status()["compiling"] and time.time() - t0 < 600:
                time.sleep(0.2)
    finally:
        shutil.rmtree(state_dir, ignore_errors=True)

    print(json.dumps({
        "config": 9, "metric": "warm_boot_s",
        "value": round(warm_s, 3),
        "unit": f"s (restore snapshots + live-list re-validation to "
                f"ready, min of 3 restore cycles, PSP library x {n} "
                "pods; cold = library ingest + full list/encode "
                "resync)",
        "cold_boot_s": round(cold_s, 3),
        "speedup_vs_cold": round(cold_s / warm_s, 1) if warm_s else None,
        "warm_first_audit_s": round(warm_audit_s, 3),
        "cold_first_audit_s": round(cold_audit_s, 3),
        "encoded_row_kinds_adopted": adopted,
        "revalidated_dirty": stats["dirty"],
        "inventory": stats["total"],
    }))


# --------------------------------------------------------------- config 7


def _synth_mutators(n: int) -> list[dict]:
    """A mutator library shaped like real fleets: imagePullPolicy /
    metadata-label / toleration mutators with varied match selectors so
    applicability actually discriminates across the batch."""
    out = []
    for i in range(n):
        shape = i % 3
        if shape == 0:
            out.append({
                "apiVersion": "mutations.gatekeeper.sh/v1alpha1",
                "kind": "Assign",
                "metadata": {"name": f"pull-policy-{i}"},
                "spec": {
                    "applyTo": [{"groups": [""], "versions": ["v1"],
                                 "kinds": ["Pod"]}],
                    "match": {"kinds": [{"apiGroups": [""],
                                         "kinds": ["Pod"]}],
                              "namespaces": [f"ns{i % 20}"]},
                    "location": "spec.containers[name: *].imagePullPolicy",
                    "parameters": {"assign": {"value": "IfNotPresent"}},
                },
            })
        elif shape == 1:
            out.append({
                "apiVersion": "mutations.gatekeeper.sh/v1alpha1",
                "kind": "AssignMetadata",
                "metadata": {"name": f"owner-label-{i}"},
                "spec": {
                    "match": {"labelSelector":
                              {"matchLabels": {"app": f"app{i % 50}"}}},
                    "location": f"metadata.labels.injected-{i}",
                    "parameters": {"assign": {"value": f"v{i}"}},
                },
            })
        else:
            out.append({
                "apiVersion": "mutations.gatekeeper.sh/v1alpha1",
                "kind": "ModifySet",
                "metadata": {"name": f"tolerations-{i}"},
                "spec": {
                    "applyTo": [{"groups": [""], "versions": ["v1"],
                                 "kinds": ["Pod"]}],
                    "match": {"kinds": [{"apiGroups": [""],
                                         "kinds": ["Pod"]}]},
                    "location": "spec.tolerations",
                    "parameters": {
                        "operation": "merge",
                        "values": {"fromList": [
                            {"key": f"pool-{i % 4}",
                             "operator": "Exists"}]},
                    },
                },
            })
    return out


def config7():
    """Mutating admission (micro-batched /v1/mutate) at three
    mutator-library sizes: per-batch applicability rides the vectorized
    matcher once per micro-batch, the host applies matched mutators to
    convergence, and the handler emits the RFC-6902 patch. Headline
    `mutate_s` is the wall-clock of one 512-review batched mutation
    pass at the largest library; p50 comes from a 32-thread closed loop
    through the real MutationHandler (envelope + patch encode
    included)."""
    import threading

    from gatekeeper_tpu.control.webhook import MutationHandler
    from gatekeeper_tpu.mutation import MutationSystem

    sizes = [max(1, int(s * SCALE)) for s in (30, 150, 600)]
    n_reviews = max(16, int(512 * SCALE))
    reviews = _mixed_reviews(n_reviews, seed=11)
    per_size = []
    mutate_s = None
    p50_ms = None
    for n_mut in sizes:
        system = MutationSystem()
        for m in _synth_mutators(n_mut):
            system.upsert(m)
        assert not system.conflicts(), "synthetic library must be clean"
        # --- batched engine path: one vectorized applicability sweep +
        # host convergence for the whole batch
        system.mutate_batch(reviews)  # warm matcher signature caches
        best = float("inf")
        n_batched = 0
        t_all = time.time()
        while time.time() - t_all < 2.0:
            t0 = time.time()
            outs = system.mutate_batch(reviews)
            best = min(best, time.time() - t0)
            n_batched += len(outs)
        batched_rps = n_batched / (time.time() - t_all)
        # --- closed loop through the real handler (micro-batcher +
        # JSONPatch emission), 32 in-process clients
        handler = MutationHandler(system, batch_max_wait=0.003)
        payloads = [{"apiVersion": "admission.k8s.io/v1",
                     "kind": "AdmissionReview",
                     "request": dict(r, uid=f"u{k}",
                                     userInfo={"username": "bench"})}
                    for k, r in enumerate(reviews)]
        handler.handle(payloads[0])  # warm the flusher
        lats: list = []
        lock = threading.Lock()
        n_req = max(64, int(4000 * SCALE))
        n_threads = 32

        def worker(k: int):
            mine = []
            for j in range(n_req // n_threads):
                t0 = time.time()
                handler.handle(payloads[(k * 131 + j) % len(payloads)])
                mine.append(time.time() - t0)
            with lock:
                lats.extend(mine)

        t0 = time.time()
        ths = [threading.Thread(target=worker, args=(k,))
               for k in range(n_threads)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        wall = time.time() - t0
        handler.batcher.stop()
        lats.sort()
        entry = {
            "mutators": n_mut,
            "mutate_s": round(best, 4),
            "batched_reviews_per_sec": round(batched_rps),
            "handler_rps": round(len(lats) / wall),
            "p50_ms": round(lats[len(lats) // 2] * 1000, 2),
            "p99_ms": round(lats[int(len(lats) * 0.99)] * 1000, 2),
        }
        per_size.append(entry)
        mutate_s = entry["mutate_s"]  # largest library wins (last)
        p50_ms = entry["p50_ms"]
    print(json.dumps({
        "config": 7, "metric": "mutate_batch_wall_clock_s",
        "value": mutate_s,
        "unit": f"s (one {n_reviews}-review micro-batch mutated vs a "
                f"{sizes[-1]}-mutator library: vectorized applicability "
                "+ host convergence)",
        "mutate_s": mutate_s,
        "p50_ms": p50_ms,
        "reviews_per_batch": n_reviews,
        "sizes": per_size,
    }))


# --------------------------------------------------------------- config 5


def _general_library_client():
    from gatekeeper_tpu import policies

    driver, client = new_client()
    for name in policies.names():
        if name.startswith("general/"):
            client.add_template(policies.load(name))
    for kind, cname, params in GENERAL_CONSTRAINTS:
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind, "metadata": {"name": cname},
            "spec": ({"parameters": params} if params else {}),
        })
    return driver, client


def _mixed_reviews(n=512, seed=3):
    reviews = []
    for o in synth_mixed_objects(n, seed=seed):
        meta = o.get("metadata", {})
        r = {"kind": {"group": o["apiVersion"].rpartition("/")[0],
                      "version": o["apiVersion"].rpartition("/")[2],
                      "kind": o["kind"]},
             "name": meta.get("name", ""), "object": o,
             "operation": "CREATE"}
        if "namespace" in meta:
            r["namespace"] = meta["namespace"]
        reviews.append(r)
    return reviews


def _loadgen_child(port: int, rate: float, duration: float,
                   seed: int, out_path: str) -> None:
    """OPEN-LOOP load generator (run as its own process so client work
    never shares the server's GIL): arrivals on a fixed schedule at
    `rate` req/s regardless of response latency; each arrival is fired
    by a pool thread and its latency recorded. Unsustained rates show
    up as queue growth -> unbounded p99, not as a throttled client.

    The client is a RAW keep-alive HTTP/1.1 socket, not http.client:
    at webhook payload sizes the stdlib client costs more CPU than the
    request being measured, and on a small host that skews every rate
    downward (the loadgen starves the server it is probing)."""
    import gc
    import queue as _q
    import socket as _socket
    import threading

    reviews = _mixed_reviews(256, seed=seed)
    payloads = []
    for k, r in enumerate(reviews):
        body = json.dumps({
            "apiVersion": "admission.k8s.io/v1beta1",
            "kind": "AdmissionReview",
            "request": dict(r, uid=f"u{k}",
                            userInfo={"username": "bench"})}).encode()
        payloads.append(
            b"POST /v1/admit HTTP/1.1\r\nHost: bench\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
    n = max(1, int(rate * duration))
    lat: list = []
    errors = [0]
    lock = threading.Lock()
    work: "_q.Queue" = _q.Queue()
    # the loadgen allocates no cycles (append-only latency lists); a
    # gen-2 GC pause here would be RECORDED as server latency
    gc.disable()

    def runner():
        conn = rfile = None

        def connect():
            nonlocal conn, rfile
            conn = _socket.create_connection(("127.0.0.1", port),
                                             timeout=30)
            conn.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
            rfile = conn.makefile("rb", 65536)

        while True:
            item = work.get()
            if item is None:
                return
            t_sched, payload = item
            try:
                if conn is None:
                    connect()
                conn.sendall(payload)
                line = rfile.readline(65537)
                if not line:
                    raise ConnectionError("server closed")
                clen = 0
                while True:
                    h = rfile.readline(65537)
                    if h in (b"\r\n", b"\n", b""):
                        break
                    if h[:15].lower() == b"content-length:":
                        clen = int(h[15:])
                if clen:
                    rfile.read(clen)
            except (OSError, ValueError):
                # count, reconnect, keep the thread alive — a dead pool
                # thread would silently skew the whole rate's numbers
                with lock:
                    errors[0] += 1
                try:
                    if conn is not None:
                        conn.close()
                except OSError:
                    pass
                conn = rfile = None
                continue
            now = time.time()
            with lock:
                lat.append((now - t_sched, now))

    # enough pool threads that the schedule never starves on slow
    # responses (open-loop: concurrency grows when the server lags)
    pool = [threading.Thread(target=runner, daemon=True)
            for _ in range(64)]
    for t in pool:
        t.start()
    t0 = time.time()
    # loadgen honesty: track how far the SCHEDULER itself fell behind
    # its arrival schedule. On a small host the generator shares cores
    # with the server it probes; when the enqueue loop lags, "achieved
    # < offered" is the GENERATOR's ceiling, not the serving plane's —
    # the sweep records that explicitly instead of letting a loadgen
    # limit masquerade as an edge limit (the BENCH_r05 1-core trap)
    sched_lag = 0.0
    for j in range(n):
        t_sched = t0 + j / rate
        now = time.time()
        if t_sched > now:
            time.sleep(t_sched - now)
        elif now - t_sched > sched_lag:
            sched_lag = now - t_sched
        work.put((t_sched, payloads[j % len(payloads)]))
    deadline = time.time() + 30
    while not work.empty() and time.time() < deadline:
        time.sleep(0.05)
    for _ in pool:
        work.put(None)
    for t in pool:
        t.join(timeout=5)
    # threads that outlived the join timeout may still append: snapshot
    # under the lock so done/latencies/last_done agree with each other
    with lock:
        snap = list(lat)
        n_err = errors[0]
    with open(out_path, "w") as f:
        json.dump({"sent": n, "done": len(snap), "t0": t0,
                   "errors": n_err,
                   "sched_lag_s": round(sched_lag, 4),
                   "latencies": [x[0] for x in snap],
                   "last_done": max((x[1] for x in snap), default=t0)}, f)


def _engine_child(socket_path: str, decision_cache: bool = True) -> None:
    """The serving plane's ENGINE process: full general-library client
    + the shared MicroBatcher behind a BackplaneEngine on a Unix
    socket. Pre-forked frontends (control/backplane.py __main__)
    forward parsed-but-undecoded reviews here, so requests from every
    frontend coalesce into the same device micro-batches.

    decision_cache=False spawns the evaluation-honest variant: the
    bulk tier's repeated payload shapes would otherwise serve from the
    generation-keyed cache, so the gated series would measure cache
    hits, not evaluation (the PR 14 tiers_note caveat)."""
    import threading

    from gatekeeper_tpu.control.backplane import BackplaneEngine
    from gatekeeper_tpu.control.webhook import (
        MicroBatcher, NamespaceLabelHandler, ValidationHandler)

    from gatekeeper_tpu.control import metrics as gmetrics

    _, client = _general_library_client()
    batcher = MicroBatcher(client, max_wait=0.003, max_batch=256)
    validation = ValidationHandler(
        client, kube=None, batcher=batcher,
        decision_cache_size=4096 if decision_cache else 0)
    # warm the evaluator, then signal readiness on stdout
    client.driver.review_batch(TARGET, _mixed_reviews(64, seed=9))
    import gc
    gc.collect()
    gc.freeze()
    engine = BackplaneEngine(socket_path, validation=validation,
                             ns_label=NamespaceLabelHandler(()))
    engine.start()
    # capacity attribution during the bench: this engine serves
    # /metrics (ephemeral port, announced on the READY line) with the
    # saturation probes armed, so one scrape mid-sweep reads batch
    # fill/seal reasons, queue depths, and the eval duty cycle
    gmetrics.register_saturation_probe(
        "admission-queue",
        lambda: gmetrics.report_queue_depth("admission",
                                            batcher.pending()))
    drv = client.driver
    if hasattr(drv, "duty_cycle"):
        gmetrics.register_saturation_probe(
            "engine-duty-cycle",
            lambda: gmetrics.report_duty_cycle(drv.duty_cycle()))
    mport = 0
    try:
        mserver = gmetrics.serve(0, addr="127.0.0.1")
        mport = mserver.server_address[1]
    except OSError:
        pass
    print(f"READY {mport}", flush=True)
    threading.Event().wait()


def _run_sweep(port, rates, n_procs, duration, here):
    import subprocess
    import tempfile

    sweep = []
    sustained = None
    for total_rate in rates:
        outs = []
        procs = []
        for k in range(n_procs):
            f = tempfile.NamedTemporaryFile("w", suffix=".json",
                                            delete=False)
            f.close()
            outs.append(f.name)
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--loadgen", str(port),
                 str(total_rate / n_procs), str(duration), str(k),
                 f.name],
                cwd=here))
        for p in procs:
            try:
                p.wait(timeout=duration + 90)
            except subprocess.TimeoutExpired:
                # a wedged loadgen must not lose the whole config: kill
                # the stragglers and fold in whatever results exist
                for q in procs:
                    if q.poll() is None:
                        q.kill()
                break
        lats: list = []
        sent = done = n_err = 0
        span = duration
        sched_lag = 0.0
        for path in outs:
            try:
                with open(path) as f:
                    d = json.load(f)
                sent += d["sent"]
                done += d["done"]
                n_err += d.get("errors", 0)
                sched_lag = max(sched_lag, d.get("sched_lag_s", 0.0))
                lats.extend(d["latencies"])
                span = max(span, d["last_done"] - d["t0"])
            except ValueError:
                pass  # killed child: empty/partial file
            finally:
                os.unlink(path)
        lats.sort()
        if not lats:
            break
        achieved = round(done / span)
        p99 = round(lats[int(len(lats) * 0.99)] * 1000, 1)
        entry = {"offered_rps": total_rate,
                 "achieved_rps": achieved,
                 "p50_ms": round(lats[len(lats) // 2] * 1000, 1),
                 "p99_ms": p99,
                 "completed": done, "sent": sent, "errors": n_err}
        if sched_lag > 0.25:
            entry["sched_lag_s"] = round(sched_lag, 3)
        # the GENERATOR topped out, not the plane: everything it sent
        # completed, fast, yet the achieved rate undershot the offer —
        # the arrival schedule itself fell behind. An edge improvement
        # must not be judged against (or masked by) this entry.
        if (achieved < 0.9 * total_rate and n_err == 0
                and done >= 0.95 * sent
                and (p99 < 100 or sched_lag > 0.25)):
            entry["loadgen_limited"] = True
        sweep.append(entry)
        # SLO: p99 under 100ms and the offered schedule kept up with
        if entry["p99_ms"] < 100 and done >= 0.95 * sent:
            sustained = entry
        elif sustained is not None:
            break  # past the knee: stop sweeping
    return sweep, sustained


def c5_skip_record(counts: list, cores: int, forced: bool,
                   env_key: str, what: str):
    """Why a config-5 subprocess sweep will not run on this host, as an
    explicit {"skipped": reason} record — or None to run it. Every
    skip path MUST produce a record: a silent [] in the headline JSON
    is indistinguishable from "measured and got nothing" (exactly what
    hid the single-core gap in BENCH_r05)."""
    if not counts:
        return {"skipped": f"{env_key} is empty"}
    if cores < 2 and not forced:
        return {"skipped": f"{cores} host core(s): {what} would "
                           f"time-share one core (set {env_key} to "
                           "force)"}
    return None


def sweep_or_skip(entries: list, what: str) -> list:
    """Backstop for the headline JSON: a sweep list that somehow ended
    up empty ships an explicit record instead of a bare []."""
    if not entries:
        entries.append({"skipped": f"{what} produced no entries "
                                   "(unexpected: no skip record was "
                                   "recorded either)"})
    return entries


def config5():
    """Streaming admission (BASELINE config #5) measured three ways:
    1. engine: pre-batched reviews through driver.review_batch — the
       evaluator's capability with batching amortized (the gRPC
       service's pre-batched ingest path);
    2. open-loop HTTP: multi-process load generators with scheduled
       arrivals against the real webhook server, swept upward until
       p99 degrades — one worker's sustainable rate, then an
       SO_REUSEPORT multi-worker group's (the one-node replica story);
    3. the serving plane: 1/2/4 pre-forked frontends over the shared
       batching backplane (the --admission-workers topology), swept
       open-loop — the headline `admission_rps`;
    4. the documented ceiling: highest swept rate meeting the SLO.
    """
    import subprocess

    driver, client = _general_library_client()
    reviews = _mixed_reviews(512, seed=3)

    # --- 1. engine capability: pre-batched throughput ------------------
    driver_batches = [reviews[i:i + 256]
                      for i in range(0, len(reviews), 256)]
    for b in driver_batches:  # warm codegen/memos/EMAs
        driver.review_batch(TARGET, b)
    import gc
    gc.collect()
    gc.freeze()
    n_eng = 0
    t0 = time.time()
    while time.time() - t0 < 3.0:
        for b in driver_batches:
            driver.review_batch(TARGET, b)
            n_eng += len(b)
    engine_rps = n_eng / (time.time() - t0)

    # the same pre-batched reviews over the REAL gRPC wire (the
    # production comm backend at the Driver seam): adds JSON + protobuf
    # framing and the localhost round-trip. The STREAM tier pipelines
    # the same batches over one bidirectional HTTP/2 stream
    # (ReviewStream) — no per-RPC round trip between batches; it is
    # the bulk-ingest successor path the trend watchdog gates.
    grpc_rps = None
    grpc_stream_rps = None
    server = rc = None
    try:
        from gatekeeper_tpu.service import RemoteClient, make_server

        server, port = make_server(client=client)
        server.start()
        rc = RemoteClient(f"127.0.0.1:{port}")
        # plain review dicts ride the "raw" wire path, so the server
        # evaluates byte-identical reviews to the engine tier — the
        # delta between the two numbers is wire framing + RPC, nothing
        # else
        for wb in driver_batches:  # warm the wire path
            rc.review_batch(wb)
        n_wire = 0
        t0 = time.time()
        while time.time() - t0 < 3.0:
            for wb in driver_batches:
                rc.review_batch(wb)
                n_wire += len(wb)
        grpc_rps = n_wire / (time.time() - t0)

        def stream_batches(stop_at):
            while time.time() < stop_at:
                for wb in driver_batches:
                    yield wb

        for _ in rc.review_stream(stream_batches(time.time() + 0.5)):
            pass  # warm the stream path
        n_stream = 0
        t0 = time.time()
        for resp in rc.review_stream(stream_batches(t0 + 3.0)):
            n_stream += len(resp)
        grpc_stream_rps = n_stream / (time.time() - t0)
    except Exception as e:
        err = f"unavailable: {e}"[:120]
        if grpc_rps is None:
            grpc_rps = err
        if grpc_stream_rps is None:
            grpc_stream_rps = err
    finally:
        # leaked server/channel threads would skew every later tier;
        # stop() returns an event — WAIT for teardown to finish
        if rc is not None:
            rc.close()
        if server is not None:
            server.stop(grace=None).wait(timeout=30)

    # --- 2. batcher closed-loop (continuity with earlier rounds): 64 in-process
    # threads through batcher.submit — no HTTP, measures the engine +
    # micro-batching frontier sharing one GIL with its clients
    import threading

    from gatekeeper_tpu.control.webhook import (
        MicroBatcher, NamespaceLabelHandler, ValidationHandler,
        WebhookServer)

    batcher = MicroBatcher(client, max_wait=0.003, max_batch=256)
    batcher.submit(reviews[0])  # warm the flusher
    lat_cl: list = []
    cl_lock = threading.Lock()
    n_req = int(10_000 * SCALE)
    n_threads = 64

    def cl_worker(k: int):
        lats = []
        for j in range(n_req // n_threads):
            r = reviews[(k * 131 + j) % len(reviews)]
            t0 = time.time()
            batcher.submit(r)
            lats.append(time.time() - t0)
        with cl_lock:
            lat_cl.extend(lats)

    t0 = time.time()
    ths = [threading.Thread(target=cl_worker, args=(k,))
           for k in range(n_threads)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    cl_wall = time.time() - t0
    lat_cl.sort()
    closed_loop = {
        "rps": round(len(lat_cl) / cl_wall),
        "p50_ms": round(lat_cl[len(lat_cl) // 2] * 1000, 2),
        "p99_ms": round(lat_cl[int(len(lat_cl) * 0.99)] * 1000, 2),
    }

    # --- 3. open-loop HTTP sweep (separate loadgen processes) ----------
    cores = os.cpu_count() or 1
    validation = ValidationHandler(client, kube=None, batcher=batcher)
    server = WebhookServer(validation, NamespaceLabelHandler(()), port=0)
    server.start()
    # re-freeze: the closed-loop tier allocated past the first freeze,
    # and a gen-2 GC scan of the policy heap is a >1s serving stall
    gc.collect()
    gc.freeze()
    here = os.path.dirname(os.path.abspath(__file__))
    n_procs = max(1, min(4, cores))
    duration = float(os.environ.get("BENCH_C5_SECONDS", 4.0))
    sweep, sustained = _run_sweep(
        server.port, (500, 1000, 1500, 2000, 3000, 5000, 8000, 12000),
        n_procs, duration, here)
    server.server.shutdown()
    batcher.stop()

    # --- 4. serving plane: pre-forked frontends over the shared
    # batching backplane. ONE engine process owns the evaluator and the
    # micro-batcher; 1/2/4 accept/parse-only frontend processes bind
    # one SO_REUSEPORT port and forward reviews (bytes, undecoded) over
    # a Unix socket, so every worker's trickle coalesces into shared
    # micro-batches. The decision cache (generation-keyed) serves
    # repeated object shapes without re-evaluation — the open-loop
    # payload set models exactly the DaemonSet-storm case it targets.
    import tempfile

    from gatekeeper_tpu.control.backplane import FrontendSupervisor

    def _spawn_engines(n: int, tag: str, extra_args: tuple = ()) -> tuple:
        """Spawn n --serve-engine children, each on its own socket.
        Returns (procs, socket_paths, metrics_ports); raises with the
        child's stderr tail when one fails to come up (the caller
        records an explicit skip — a silent empty sweep hid exactly
        this in BENCH_r05). The READY line carries each engine's
        /metrics port (0 = unavailable) for the mid-sweep
        saturation scrape."""
        procs, socks, mports = [], [], []
        try:
            for k in range(n):
                sp = os.path.join(
                    tempfile.gettempdir(),
                    f"gk-bench-bp-{os.getpid()}-{tag}{k}.sock")
                socks.append(sp)
                procs.append(subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__),
                     "--serve-engine", sp, *extra_args],
                    cwd=here, stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE, text=True))
            for k, proc in enumerate(procs):
                line = proc.stdout.readline()
                if "READY" not in (line or ""):
                    err = (proc.stderr.read() or "")[-300:]
                    raise RuntimeError(
                        f"backplane engine {k} failed to start: "
                        f"{err or 'no stderr'}")
                parts = (line or "").split()
                try:
                    mports.append(int(parts[1]))
                except (IndexError, ValueError):
                    mports.append(0)
                # drain later output so a full pipe can never block
                import threading as _th
                _th.Thread(target=proc.stdout.read, daemon=True).start()
                _th.Thread(target=proc.stderr.read, daemon=True).start()
            return procs, socks, mports
        except Exception:
            for p in procs:
                p.kill()
            raise

    def _scrape_raw(mport: int) -> dict:
        """Raw attribution counters/gauges from one /metrics scrape of
        the serving engine (admission plane only)."""
        import re as _re
        import urllib.request

        text = urllib.request.urlopen(
            f"http://127.0.0.1:{mport}/metrics",
            timeout=5).read().decode()
        out: dict = {"seals": {}, "fill_sum": 0.0, "fill_count": 0,
                     "queue_depth": {}, "duty": None}
        for m in _re.finditer(
                r'gatekeeper_tpu_batch_seal_total\{plane="admission",'
                r'reason="([^"]+)"\} (\S+)', text):
            out["seals"][m.group(1)] = int(float(m.group(2)))
        fs = _re.search(r'gatekeeper_tpu_batch_fill_ratio_sum'
                        r'\{plane="admission"\} (\S+)', text)
        fc = _re.search(r'gatekeeper_tpu_batch_fill_ratio_count'
                        r'\{plane="admission"\} (\S+)', text)
        if fs and fc:
            out["fill_sum"] = float(fs.group(1))
            out["fill_count"] = int(float(fc.group(1)))
        for m in _re.finditer(
                r'gatekeeper_tpu_queue_depth\{[^}]*queue="([^"]+)"\}'
                r' (\S+)', text):
            out["queue_depth"][m.group(1)] = float(m.group(2))
        m = _re.search(
            r'gatekeeper_tpu_device_duty_cycle\{engine="[^"]*"\} (\S+)',
            text)
        if m:
            out["duty"] = float(m.group(1))
        return out

    def _attribution_delta(before: dict, after: dict) -> dict:
        """This topology's attribution read: seal/fill counter DELTAS
        between the scrape before and after its rate sweep (one
        long-lived engine serves every topology, so cumulative totals
        would smear earlier topologies' traffic in), plus the
        post-sweep duty cycle (its sample window spans the sweep) and
        queue depths."""
        seals = {r: after["seals"].get(r, 0) - before["seals"].get(r, 0)
                 for r in set(after["seals"]) | set(before["seals"])}
        out: dict = {"batch_seal_reasons":
                     {r: n for r, n in sorted(seals.items()) if n > 0}}
        dn = after["fill_count"] - before["fill_count"]
        if dn > 0:
            out["batch_fill_ratio_mean"] = round(
                (after["fill_sum"] - before["fill_sum"]) / dn, 4)
            out["batches_sealed"] = dn
        out["queue_depth"] = after["queue_depth"]
        if after["duty"] is not None:
            out["device_duty_cycle"] = after["duty"]
        return out

    worker_counts = [int(w) for w in os.environ.get(
        "BENCH_C5_WORKERS", "1,2,4").split(",") if w.strip()]
    mw_sweep: list = []
    mw_sustained = None
    base = sustained["offered_rps"] if sustained else 500
    mw_skip = c5_skip_record(worker_counts, cores,
                             "BENCH_C5_WORKERS" in os.environ,
                             "BENCH_C5_WORKERS",
                             "pre-forked frontend + engine + loadgen "
                             "processes")
    bulk_rps = None
    bulk_nocache_rps = None
    if mw_skip is not None:
        mw_sweep.append(mw_skip)
        bulk_rps = mw_skip.get("skipped")
        bulk_nocache_rps = mw_skip.get("skipped")
    else:
        engine_procs: list = []
        try:
            engine_procs, socks, mports = _spawn_engines(1, "w")
            # BULK binary ingest tier: pre-framed reviews over the
            # backplane B frame straight into the engine child's
            # MicroBatcher — the edge path with no HTTP at all (what a
            # CI scanner speaks). Cross-process, unlike the in-process
            # engine tier above.
            try:
                from gatekeeper_tpu.control.backplane import (
                    BackplaneClient as _BC)

                bulk_payloads = [json.dumps({
                    "apiVersion": "admission.k8s.io/v1beta1",
                    "kind": "AdmissionReview",
                    "request": dict(r, uid=f"bk{k}",
                                    userInfo={"username": "bench"})},
                ).encode() for k, r in enumerate(reviews[:256])]
                bulk_chunks = [bulk_payloads[i:i + 64]
                               for i in range(0, len(bulk_payloads), 64)]
                bc = _BC(socks[0], worker_id="bulk")
                for ch in bulk_chunks:  # warm
                    bc.review_bulk(ch, timeout_s=30.0)
                n_bulk = 0
                t0 = time.time()
                while time.time() - t0 < 3.0:
                    for ch in bulk_chunks:
                        bc.review_bulk(ch, timeout_s=30.0)
                        n_bulk += len(ch)
                bulk_rps = round(n_bulk / (time.time() - t0))
                bc.close()
            except Exception as e:
                bulk_rps = f"unavailable: {e}"[:120]
            # same tier against a --no-decision-cache engine: the
            # repeated payload shapes above serve mostly from the
            # generation-keyed decision cache, so the cached number
            # measures cache hits; THIS series measures evaluation
            # (the PR 14 tiers_note caveat, fixed as its own gated
            # metric)
            nc_procs: list = []
            try:
                nc_procs, nc_socks, _nc_mp = _spawn_engines(
                    1, "wnc", extra_args=("--no-decision-cache",))
                bc = _BC(nc_socks[0], worker_id="bulknc")
                for ch in bulk_chunks:  # warm
                    bc.review_bulk(ch, timeout_s=30.0)
                n_bulk = 0
                t0 = time.time()
                while time.time() - t0 < 3.0:
                    for ch in bulk_chunks:
                        bc.review_bulk(ch, timeout_s=30.0)
                        n_bulk += len(ch)
                bulk_nocache_rps = round(n_bulk / (time.time() - t0))
                bc.close()
            except Exception as e:
                bulk_nocache_rps = f"unavailable: {e}"[:120]
            finally:
                for p in nc_procs:
                    p.kill()
            for n_workers in worker_counts:
                fronts = FrontendSupervisor(n_workers, socks[0],
                                            port=0, addr="127.0.0.1")
                fronts.start()
                scrape: dict = {}
                try:
                    mults = (1, 2, 3, 4, 6, 8) if n_workers > 1 \
                        else (1, 2)
                    rates = sorted({int(base * m) for m in mults})
                    # counter DELTAS across this topology's own sweep:
                    # one long-lived engine serves every worker count,
                    # so cumulative totals would smear topologies
                    pre = None
                    if mports and mports[0]:
                        try:
                            pre = _scrape_raw(mports[0])
                        except Exception:
                            pre = None
                    sweep_n, sus_n = _run_sweep(fronts.port, rates,
                                                n_procs, duration,
                                                here)
                    if pre is not None:
                        try:
                            scrape = _attribution_delta(
                                pre, _scrape_raw(mports[0]))
                        except Exception as e:
                            scrape = {"error": str(e)[:200]}
                finally:
                    fronts.stop()
                best_n = sus_n or (max(sweep_n,
                                       key=lambda e: e["achieved_rps"])
                                   if sweep_n else {})
                mw_sweep.append({
                    "workers": n_workers,
                    "admission_rps": best_n.get("achieved_rps", 0),
                    "slo_met": sus_n is not None,
                    "p50_ms": best_n.get("p50_ms"),
                    "p99_ms": best_n.get("p99_ms"),
                    "saturation": scrape or None,
                    "sweep": sweep_n,
                })
                if sus_n is not None and (
                        mw_sustained is None
                        or sus_n["achieved_rps"]
                        > mw_sustained["achieved_rps"]):
                    mw_sustained = sus_n
        except Exception as e:  # an EXPLICIT record, never a silent []
            mw_sweep.append({"skipped": str(e)[:300]})
        finally:
            for p in engine_procs:
                p.kill()

    # --- 5. N-engine plane (--admission-engines): K engine processes,
    # one per chip, frontends routing least-load across them — the
    # scale-with-chips topology. Each engine child self-ingests the
    # general library; 2 frontends route over all K sockets.
    engine_counts = [int(c) for c in os.environ.get(
        "BENCH_C5_ENGINES", "1,2").split(",") if c.strip()]
    me_sweep: list = []
    me_sustained = None
    me_skip = c5_skip_record(engine_counts, cores,
                             "BENCH_C5_ENGINES" in os.environ,
                             "BENCH_C5_ENGINES",
                             "N JAX engine processes")
    if me_skip is not None:
        me_sweep.append(me_skip)
    else:
        for n_engines in engine_counts:
            engine_procs = []
            try:
                engine_procs, socks, _mp = _spawn_engines(
                    n_engines, f"e{n_engines}-")
                fronts = FrontendSupervisor(2, socks, port=0,
                                            addr="127.0.0.1")
                fronts.start()
                try:
                    rates = sorted({int(base * m)
                                    for m in (1, 2, 4, 6, 8)})
                    sweep_n, sus_n = _run_sweep(fronts.port, rates,
                                                n_procs, duration,
                                                here)
                finally:
                    fronts.stop()
                best_n = sus_n or (max(sweep_n,
                                       key=lambda e: e["achieved_rps"])
                                   if sweep_n else {})
                me_sweep.append({
                    "engines": n_engines,
                    "admission_rps": best_n.get("achieved_rps", 0),
                    "slo_met": sus_n is not None,
                    "p50_ms": best_n.get("p50_ms"),
                    "p99_ms": best_n.get("p99_ms"),
                    "sweep": sweep_n,
                })
                if sus_n is not None and (
                        me_sustained is None
                        or sus_n["achieved_rps"]
                        > me_sustained["achieved_rps"]):
                    me_sustained = sus_n
            except Exception as e:
                me_sweep.append({"engines": n_engines,
                                 "skipped": str(e)[:300]})
            finally:
                for p in engine_procs:
                    p.kill()

    all_entries = sweep + [e for m in mw_sweep + me_sweep
                           for e in m.get("sweep", [])]
    best_sus = max((s for s in (mw_sustained, me_sustained, sustained)
                    if s is not None),
                   key=lambda s: s["achieved_rps"], default=None)
    best = (best_sus
            or (max(all_entries, key=lambda e: e["achieved_rps"])
                if all_entries else {}))
    print(json.dumps({
        "config": 5, "metric": "admission_rps",
        "value": best.get("achieved_rps", 0),
        "admission_rps": best.get("achieved_rps", 0),
        "unit": "req/s (open-loop multi-process HTTP vs full general "
                "library; highest offered rate with p99<100ms, else "
                "the measured host ceiling; best across the serving-"
                "plane worker counts)",
        "slo_met": best_sus is not None,
        "p50_ms": best.get("p50_ms"), "p99_ms": best.get("p99_ms"),
        "host_cores": cores,
        "worker_counts": worker_counts,
        "engine_batched_reviews_per_sec": round(engine_rps),
        # the ISSUE-14 headline gap: best open-loop edge rate as a
        # fraction of the engine's pre-batched ceiling (acceptance:
        # >= 0.5 on the bench host). Tracked by bench_trend.
        "edge_vs_engine_ratio": (
            round(best.get("achieved_rps", 0) / engine_rps, 3)
            if engine_rps else None),
        # the generator topped out on the headline entry: the edge
        # number is a loadgen floor, not a serving-plane ceiling
        "loadgen_limited": bool(best.get("loadgen_limited", False)),
        "grpc_batched_reviews_per_sec": (round(grpc_rps)
                                         if isinstance(grpc_rps, float)
                                         else grpc_rps),
        # pipelined ReviewStream over one HTTP/2 stream — the bulk-
        # ingest successor of the unary batched tier (gated >= r04's
        # 5,067/s by bench_trend once two rounds carry it)
        "grpc_stream_reviews_per_sec": (
            round(grpc_stream_rps)
            if isinstance(grpc_stream_rps, float) else grpc_stream_rps),
        # length-prefixed B frames over the backplane socket into a
        # separate engine process — the no-HTTP binary ingest path
        "backplane_bulk_reviews_per_sec": bulk_rps,
        # the same tier against a --no-decision-cache engine: every
        # review evaluates (gated alongside the cached series, so a
        # cache-hit speedup can't mask an evaluation regression)
        "backplane_bulk_reviews_per_sec_nocache": bulk_nocache_rps,
        "batcher_closed_loop": closed_loop,
        "tiers_note": "engine = pre-batched driver.review_batch (the "
                      "gRPC pre-batched ingest path); closed_loop = "
                      "64 in-process clients on batcher.submit (r4's "
                      "harness); HTTP sweeps are OPEN-LOOP with "
                      "separate loadgen processes — on a small host "
                      "they measure the serving plane sharing cores "
                      "with the load generators; multi_worker_sweep = "
                      "pre-forked frontends over the shared batching "
                      "backplane (--admission-workers). The bulk and "
                      "HTTP tiers ride the engine's generation-keyed "
                      "decision cache on repeated shapes (the "
                      "DaemonSet-storm case they model); the engine, "
                      "gRPC, and bulk-nocache tiers evaluate every "
                      "review",
        # the attribution read (ISSUE 13 acceptance): seal-reason /
        # fill / queue-depth / duty-cycle deltas across one topology's
        # open-loop sweep — the topology whose sweep actually drove
        # the batcher (later topologies can serve entirely from the
        # decision cache and seal nothing new)
        "saturation_scrape": max(
            (e["saturation"] for e in mw_sweep if e.get("saturation")),
            key=lambda s: s.get("batches_sealed", 0), default=None),
        "sweep": sweep,
        "multi_worker_sweep": sweep_or_skip(mw_sweep,
                                            "multi_worker_sweep"),
        # K engine processes (the --admission-engines topology), 2
        # frontends routing least-load across all K sockets; entries
        # are per engine count, or one explicit skip record
        "multi_engine_sweep": sweep_or_skip(me_sweep,
                                            "multi_engine_sweep"),
    }))


# -------------------------------------------------------------- config 10


def _mesh_audit_child(n_objects: int, n_constraints: int) -> None:
    """--mesh-audit child: one audit-scaling measurement in a fresh
    process (the parent sets GATEKEEPER_TPU_MESH / XLA_FLAGS before
    JAX initializes here). Prints one JSON line."""
    import jax

    from gatekeeper_tpu.client import Backend
    from gatekeeper_tpu.ir import TpuDriver
    from gatekeeper_tpu.parallel.workload import (
        REQUIRED_LABELS_TEMPLATE, synth_constraints, synth_objects)
    from gatekeeper_tpu.target import K8sValidationTarget

    driver = TpuDriver()
    client = Backend(driver).new_client([K8sValidationTarget()])
    client.add_template(REQUIRED_LABELS_TEMPLATE)
    for c in synth_constraints(n_constraints, seed=1):
        client.add_constraint(c)
    for o in synth_objects(n_objects, violate_frac=0.002, seed=0):
        client.add_data(o)
    t0 = time.time()
    resp = client.audit()
    first_s = time.time() - t0
    t_warm = time.time()
    while driver.warm_status()["compiling"] and \
            time.time() - t_warm < 600:
        time.sleep(0.2)
    audit_s = float("inf")
    for _ in range(3):
        cache = getattr(driver, "_audit_results_cache", None)
        if cache is not None:
            cache.clear()  # measure the full sweep, not the delta hit
        t0 = time.time()
        resp = client.audit()
        audit_s = min(audit_s, time.time() - t0)
    print(json.dumps({
        "audit_s": round(audit_s, 3),
        "first_audit_s": round(first_s, 2),
        "path": driver.last_audit_path,
        "violations": len(resp.results()),
        "n_devices": len(jax.devices())}))


def config10():
    """Multichip audit promotion at 1M+ objects: the DEFAULT no-flag
    audit path must report mesh(data=N) whenever more than one device
    is visible, and wall-clock must improve against the forced
    single-device path. Each measurement runs in a fresh subprocess so
    the device topology (GATEKEEPER_TPU_MESH, XLA_FLAGS) binds before
    JAX initializes; on a 1-device host the mesh run forces 8
    host-platform devices so the sharded slab pipeline is exercised
    (those time-share the same cores — the record says which it was,
    so a CPU ratio is read as path validation, not chip scaling)."""
    import subprocess

    n_objects = int(os.environ.get("BENCH_C10_OBJECTS",
                                   int(1_000_000 * SCALE)))
    n_cons = int(os.environ.get("BENCH_C10_CONSTRAINTS", 100))
    here = os.path.dirname(os.path.abspath(__file__))
    import jax
    n_dev = len(jax.devices())
    forced = n_dev < 2

    def run_child(mesh_cfg: str) -> dict:
        env = dict(os.environ)
        env["GATEKEEPER_TPU_MESH"] = mesh_cfg
        if forced:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mesh-audit",
             str(n_objects), str(n_cons)],
            cwd=here, capture_output=True, text=True, env=env,
            timeout=int(os.environ.get("BENCH_C10_TIMEOUT", 1800)))
        for line in proc.stdout.splitlines():
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
        raise RuntimeError(f"mesh-audit child ({mesh_cfg}) failed: "
                           f"{(proc.stderr or '')[-300:]}")

    out = {"config": 10, "metric": "mesh_audit_wall_clock_s",
           "objects": n_objects, "constraints": n_cons,
           "host_devices": n_dev,
           "mesh_platform": ("8 forced host-platform (cpu) devices"
                             if forced else f"{n_dev} devices")}
    try:
        mesh = run_child("auto")
        single = run_child("off")
        out.update({
            "value": mesh["audit_s"],
            "unit": f"s (one client.audit(), min of 3 warm sweeps; "
                    f"{n_cons} constraints x {n_objects} objects, "
                    "default no-flag mesh path)",
            "audit_path": mesh["path"],
            "first_audit_s": mesh["first_audit_s"],
            "violations": mesh["violations"],
            "single_device_s": single["audit_s"],
            "single_first_audit_s": single["first_audit_s"],
            "vs_single_device": (round(single["audit_s"]
                                       / mesh["audit_s"], 2)
                                 if mesh["audit_s"] else None),
        })
        if forced:
            out["note"] = ("host-platform devices time-share the same "
                           "CPU cores: vs_single_device here validates "
                           "the sharded path, not chip scaling")
    except Exception as e:  # an explicit record, never a lost config
        out.update({"value": None, "skipped": str(e)[:300]})
    print(json.dumps(out))


# --------------------------------------------------------------- config 8


def config8():
    """Resilience under overload: a 64-thread closed loop drives the
    micro-batched ValidationHandler through a flusher slowed to well
    below the offered load, with a bounded queue and 2s propagated
    deadlines. Measures the shed fraction, the deadline-answer fraction,
    and — the resilience headline — the WORST decision latency as a
    fraction of the deadline: every request must be answered before the
    API server would have given up, no matter the overload."""
    import threading

    from gatekeeper_tpu.control.webhook import (
        MicroBatcher,
        ValidationHandler,
    )

    _, client = _general_library_client()
    reviews = _mixed_reviews(max(64, int(256 * SCALE)), seed=5)
    inner = None

    def slowed(batch):
        time.sleep(0.05)  # force overload: capacity ~20 batches/s
        return inner(batch)

    batcher = MicroBatcher(client, max_wait=0.002, max_batch=16,
                           evaluate=slowed, max_queue=64)
    inner = batcher._evaluate_violations
    handler = ValidationHandler(client, batcher=batcher)
    timeout_s = 2
    payloads = [{"apiVersion": "admission.k8s.io/v1",
                 "kind": "AdmissionReview",
                 "request": dict(r, uid=f"u{k}", timeoutSeconds=timeout_s,
                                 userInfo={"username": "bench"})}
                for k, r in enumerate(reviews)]
    handler.handle(payloads[0])
    counts: dict[str, int] = {}
    lats: list = []
    lock = threading.Lock()
    n_threads = 64
    duration = 4.0 * max(SCALE, 0.25)
    stop = time.time() + duration

    def worker(k: int):
        mine: list = []
        mcounts: dict[str, int] = {}
        j = 0
        while time.time() < stop:
            t0 = time.time()
            out = handler.handle(payloads[(k * 131 + j) % len(payloads)])
            dt = time.time() - t0
            mine.append(dt)
            resp = out["response"]
            code = (resp.get("status") or {}).get("code")
            key = {429: "shed", 504: "deadline"}.get(code, "decided")
            mcounts[key] = mcounts.get(key, 0) + 1
            j += 1
        with lock:
            lats.extend(mine)
            for key, n in mcounts.items():
                counts[key] = counts.get(key, 0) + n

    ths = [threading.Thread(target=worker, args=(k,))
           for k in range(n_threads)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    batcher.stop()
    lats.sort()
    total = len(lats)
    worst_frac = (lats[-1] / timeout_s) if lats else 0.0
    print(json.dumps({
        "config": 8, "metric": "overload_worst_latency_deadline_frac",
        "value": round(worst_frac, 3),
        "unit": f"worst decision latency / {timeout_s}s deadline under "
                f"{n_threads}-thread overload (must stay < 1.0: every "
                "request answered before the API server gives up)",
        "requests": total,
        "decided_frac": round(counts.get("decided", 0) / max(total, 1), 3),
        "shed_frac": round(counts.get("shed", 0) / max(total, 1), 3),
        "deadline_frac": round(counts.get("deadline", 0) / max(total, 1),
                               3),
        "p50_ms": round(lats[total // 2] * 1000, 2) if lats else None,
        "p99_ms": round(lats[int(total * 0.99)] * 1000, 2) if lats
        else None,
    }))


# -------------------------------------------------------------- config 11


def config11():
    """Streaming audit + what-if preview (the PR-9 tentpole numbers).

    Part 1 — violation DETECTION latency (watch event -> the constraint-
    status write reflecting it) at config-6 churn scale (PSP library x
    50k pods), measured two ways on the same warm pipeline:
      interval: the reference line's polling sweep — events land at
        uniform offsets across one --audit-interval window and are
        detected by the sweep at the tick (latency ~ U(0, I) + sweep);
      streaming: --stream-audit — the tracker's watch events debounce-
        flush through the delta pipeline; p50/p99 from the
        event-receipt -> status-write clock inside the flush.
    The headline gate: streaming p99 beats the interval line's by >=10x.

    Part 2 — `whatif_preview_s`: a candidate constraint swept against a
    100k+-object encoded inventory via /v1/preview's engine. Cold call
    serves host while XLA warms off-path; the headline is the WARM
    sweep (< 1s gate)."""
    import threading

    from gatekeeper_tpu import policies
    from gatekeeper_tpu.control.audit import AuditManager
    from gatekeeper_tpu.control.kube import FakeKube

    n = int(50_000 * SCALE)
    interval_s = float(os.environ.get("BENCH_C11_INTERVAL", 10.0))
    kube = FakeKube()
    kube.register_kind(("", "v1", "Namespace"), namespaced=False)
    kube.register_kind(("", "v1", "Pod"), namespaced=True)
    pods = synth_pods_psp(n)
    for i, pod in enumerate(pods):
        pod["metadata"]["uid"] = f"c11-{i}"
        kube.create(pod)
    drv, client = new_client()
    for name in policies.names():
        if name.startswith("pod-security-policy/"):
            client.add_template(policies.load(name))
    for kind, cname, params in PSP_CONSTRAINTS:
        con = {"apiVersion": "constraints.gatekeeper.sh/v1beta1",
               "kind": kind, "metadata": {"name": cname},
               "spec": ({"parameters": params} if params else {})}
        client.add_constraint(con)
        kube.apply(dict(con))

    mgr = AuditManager(kube, client, incremental=True,
                       interval=3600, stream_audit=True,
                       stream_window_s=0.025)
    t0 = time.time()
    mgr.audit_once()  # builds the tracker + encodes the inventory
    first = time.time() - t0
    t0 = time.time()
    while hasattr(drv, "warm_status") and \
            drv.warm_status()["compiling"] and time.time() - t0 < 600:
        time.sleep(0.2)
    t0 = time.time()
    mgr.audit_once()
    sweep_s = time.time() - t0  # warm steady-state sweep

    rng = random.Random(11)

    def churn(round_, k):
        """k pod replacements; ~half flip violation state (privileged
        toggle), half are healthy label churn — the stream flush must
        both rewrite statuses and confirm no-ops."""
        import copy
        for j, i in enumerate(rng.sample(range(n), k)):
            pod = copy.deepcopy(pods[i])
            if j % 2:
                ctx = pod["spec"]["containers"][0]["securityContext"]
                ctx["privileged"] = not ctx.get("privileged", False)
            else:
                pod["metadata"].setdefault("labels", {})["churn"] = \
                    f"r{round_}-{i}"
            kube.apply(pod)

    # --- interval line: events at uniform offsets across one window,
    # detected by the sweep at the tick (driven inline — this IS what
    # the polling loop does, without burning a thread to wait on)
    k_events = 60
    offsets = sorted(rng.uniform(0.0, interval_s * 0.95)
                     for _ in range(k_events))
    t_window = time.time()
    event_times = []
    for j, off in enumerate(offsets):
        time.sleep(max(0.0, t_window + off - time.time()))
        churn(1000 + j, 1)
        event_times.append(time.time())
    time.sleep(max(0.0, t_window + interval_s - time.time()))
    mgr.audit_once()  # the tick
    t_done = time.time()
    int_lat = sorted(t_done - te for te in event_times)
    interval_ms = {
        "p50": round(int_lat[len(int_lat) // 2] * 1e3, 1),
        "p99": round(int_lat[int(len(int_lat) * 0.99)] * 1e3, 1),
        "interval_s": interval_s,
    }

    # --- streaming line: the stream loop flushes dirty rows as the
    # watch delivers them; latencies come from the flush's own
    # event-receipt -> status-write clock
    stream_lat: list = []
    lat_lock = threading.Lock()

    def on_flush(lat, writes):
        with lat_lock:
            stream_lat.extend(lat)

    mgr.on_flush = on_flush
    mgr.start()
    t0 = time.time()
    while mgr.tracker is not None and not mgr.tracker.track_event_times \
            and time.time() - t0 < 10:
        time.sleep(0.02)  # stream loop arming the tracker hooks
    time.sleep(0.3)
    rounds = 40
    burst = max(1, int(n * 0.01) // rounds)  # ~1% churn total
    for r in range(rounds):
        churn(r, burst)
        time.sleep(0.15)  # past the debounce window: distinct flushes
    t0 = time.time()
    while time.time() - t0 < 10:
        with lat_lock:
            if len(stream_lat) >= rounds * burst:
                break
        time.sleep(0.05)
    mgr.stop()
    with lat_lock:
        s_lat = sorted(stream_lat)
    if not s_lat:
        s_lat = [float("nan")]
    stream_ms = {
        "p50": round(s_lat[len(s_lat) // 2] * 1e3, 1),
        "p99": round(s_lat[int(len(s_lat) * 0.99)] * 1e3, 1),
    }

    # --- what-if preview over a 100k+-object encoded inventory -------
    from gatekeeper_tpu.control.preview import PreviewEngine
    from gatekeeper_tpu.parallel.workload import (
        REQUIRED_LABELS_TEMPLATE, synth_objects)

    n_pv = int(100_000 * SCALE)
    drv2, client2 = new_client()
    client2.add_template(REQUIRED_LABELS_TEMPLATE)
    for o in synth_objects(n_pv, violate_frac=0.01, seed=0):
        client2.add_data(o)
    pv = PreviewEngine(client2)
    candidate = {
        "kind": "K8sRequiredLabels", "metadata": {"name": "whatif"},
        "spec": {"match": {"kinds": [{"apiGroups": [""],
                                      "kinds": ["Namespace"]}]},
                 "parameters": {"labels": [{"key": "cost-center"}]}},
    }
    out = pv.preview({"constraint": candidate, "limit": 5})
    cold_s = out["duration_s"]
    t0 = time.time()
    while out["path"] != "device" and time.time() - t0 < 300:
        time.sleep(1.0)  # background XLA warm for the alias kind
        out = pv.preview({"constraint": candidate, "limit": 5})
    warm_s = float("inf")
    for _ in range(3):
        out = pv.preview({"constraint": candidate, "limit": 5})
        warm_s = min(warm_s, out["duration_s"])

    print(json.dumps({
        "config": 11, "metric": "violation_detection_ms_p99",
        "value": stream_ms["p99"],
        "unit": f"ms (watch event -> constraint-status write, "
                f"--stream-audit, PSP library x {n} pods, ~1% churn "
                f"in {rounds} bursts)",
        "violation_detection_ms": stream_ms,
        "detection_events": len(s_lat),
        "stream_stats": mgr.stream_stats,
        "interval_detection_ms": interval_ms,
        "detection_speedup_p99": (
            round(int_lat[int(len(int_lat) * 0.99)] * 1e3
                  / max(stream_ms["p99"], 1e-9), 1)),
        "steady_sweep_s": round(sweep_s, 3),
        "first_audit_s": round(first, 2),
        "whatif_preview_s": round(warm_s, 4),
        "whatif_preview_cold_s": round(cold_s, 4),
        "preview_reviewed": out["reviewed"],
        "preview_violations": out["violations"],
        "preview_path": out["path"],
    }))


# ----------------------------------------- config 12: compiler widening


def _xtemplate(kind: str, rego: str) -> dict:
    return {"apiVersion": "templates.gatekeeper.sh/v1beta1",
            "kind": "ConstraintTemplate",
            "metadata": {"name": kind.lower()},
            "spec": {"crd": {"spec": {"names": {"kind": kind}}},
                     "targets": [{"target": TARGET, "rego": rego}]}}


# Upstream-canonical Rego forms the PR 10 compiler widening brought onto
# the device path — before it, every one of these kinds audited on the
# interpreter (the `Uncompilable` wall each one used to hit is noted).
# Shared with tests/test_compile_coverage.py, which differential-tests
# each against the interpreter driver.
EXTENDED_FORM_TEMPLATES = [
    # param key-set comprehension (was: "param key-set comprehension")
    ("XRequiredLabelKeys", _xtemplate("XRequiredLabelKeys", """
package xrequiredlabelkeys

violation[{"msg": msg}] {
  provided := {k | input.review.object.metadata.labels[k]}
  required := {k | input.parameters.labels[k]}
  missing := required - provided
  count(missing) > 0
  msg := sprintf("missing required label keys: %v", [missing])
}
"""), {"labels": {"owner": "", "app": "", "team": ""}}),
    # non-var comprehension head + computed set membership
    # (was: "unbound var c" / "unsupported set bracket")
    ("XBannedImages", _xtemplate("XBannedImages", """
package xbannedimages

violation[{"msg": msg}] {
  images := {c.image | c := input.review.object.spec.containers[_]}
  images[input.parameters.banned]
  msg := sprintf("banned image <%v> in use", [input.parameters.banned])
}
"""), {"banned": "docker.io/evil7:latest"}),
    # multi-literal filter body over the generator element + lower()
    # derived column (was: "unbound base var c" / "unsupported call lower")
    ("XRootfulPrefixed", _xtemplate("XRootfulPrefixed", """
package xrootfulprefixed

violation[{"msg": msg}] {
  bad := {c.name | c := input.review.object.spec.containers[_]; startswith(lower(c.image), input.parameters.prefix); not c.securityContext.runAsNonRoot}
  count(bad) > 0
  msg := sprintf("containers from <%v> must set runAsNonRoot: %v", [input.parameters.prefix, bad])
}
"""), {"prefix": "docker.io/"}),
    # `some`-decls + 2-arg identical(obj, review) canonical join body
    # (was: "join: some-decl")
    ("XUniqueIngressHostCanon", _xtemplate("XUniqueIngressHostCanon", """
package xuniqueingresshostcanon

identical(obj, review) {
  obj.metadata.namespace == review.object.metadata.namespace
  obj.metadata.name == review.object.metadata.name
}

violation[{"msg": msg}] {
  input.review.kind.kind == "Ingress"
  re_match("^(extensions|networking.k8s.io)$", input.review.kind.group)
  some ns, apiv, name
  host := input.review.object.spec.rules[_].host
  other := data.inventory.namespace[ns][apiv]["Ingress"][name]
  re_match("^(extensions|networking.k8s.io)/.+$", apiv)
  other.spec.rules[_].host == host
  not identical(other, input.review)
  msg := sprintf("ingress host conflicts with an existing ingress <%v>", [host])
}
"""), None),
    # inline inventory generator + inline self-exclusion disequality
    # (was: "join: generator must bind a var" / "unsupported mixed
    # literal")
    ("XUniqueSelectorInline", _xtemplate("XUniqueSelectorInline", """
package xuniqueselectorinline

violation[{"msg": msg}] {
  input.review.kind.kind == "Service"
  sel := input.review.object.spec.selector
  data.inventory.namespace[ns][_]["Service"][name].spec.selector == sel
  name != input.review.object.metadata.name
  msg := sprintf("same selector as service <%v>", [name])
}
"""), None),
]


def config12():
    """Per-kind audit latency, interpreter vs the newly device-compiled
    path, for the extended-form corpus (kinds that PR 10's compiler
    widening moved off the interpreter). Dense kinds run at config-6
    inventory scale; the cross-object join kinds run both sides at a
    reduced N the interpreter's O(N*M) rescan can finish at all —
    speedups are apples-to-apples at each kind's own N. Also reports
    the shipped general library's device coverage (the
    `general_library_compiled_fraction` headline: must read 1.0)."""
    from gatekeeper_tpu import policies
    from gatekeeper_tpu.client import Backend, RegoDriver
    from gatekeeper_tpu.target import K8sValidationTarget

    n_dense = int(50_000 * SCALE)  # config-6 inventory scale
    n_join = int(4_000 * SCALE)
    objs_dense = synth_mixed_objects(n_dense, seed=12)
    for i, o in enumerate(objs_dense):
        if i % 50:  # ~2% violating tail: keep materialization sparse
            o["metadata"]["labels"]["team"] = "core"
    # join-kind inventory: mostly-unique hosts/selectors with a ~2%
    # colliding tail, so the cross-object filter does real work but the
    # exact-message materialization (same cost on both sides) stays off
    # the critical path
    objs_join = []
    for i in range(n_join):
        if i % 2:
            host = (f"dup{i % 10}.corp.example" if i % 50 == 1
                    else f"h{i}.corp.example")
            objs_join.append({
                "apiVersion": "networking.k8s.io/v1", "kind": "Ingress",
                "metadata": {"name": f"ing-{i}", "namespace": f"ns{i % 9}"},
                "spec": {"rules": [{"host": host}]}})
        else:
            sel = ({"app": f"dupapp{i % 10}"} if i % 50 == 0
                   else {"app": f"app{i}"})
            objs_join.append({
                "apiVersion": "v1", "kind": "Service",
                "metadata": {"name": f"svc-{i}", "namespace": f"ns{i % 9}"},
                "spec": {"selector": sel}})

    # shipped-library coverage: the ratcheted headline numbers
    drv, client = new_client()
    for name in policies.names():
        if name.startswith("general/"):
            client.add_template(policies.load(name))
    cov = compiled_coverage(drv, client)

    per_kind = {}
    best = 0.0
    for kind, tmpl, params in EXTENDED_FORM_TEMPLATES:
        is_join = kind.startswith("XUnique")
        objs = objs_join if is_join else objs_dense
        con = {"apiVersion": "constraints.gatekeeper.sh/v1beta1",
               "kind": kind, "metadata": {"name": kind.lower()},
               "spec": ({"parameters": params} if params else {})}
        row = {"objects": len(objs), "path": None}
        for side in ("interpreter", "device"):
            drv2 = RegoDriver() if side == "interpreter" else None
            if drv2 is None:
                drv2, client2 = new_client()
            else:
                client2 = Backend(drv2).new_client([K8sValidationTarget()])
            client2.add_template(tmpl)
            client2.add_constraint(con)
            for o in objs:
                client2.add_data(o)
            client2.audit()  # warm-up (device: background XLA compile)
            if side == "device":
                t0 = time.time()
                while hasattr(drv2, "warm_status") and \
                        drv2.warm_status()["compiling"] and \
                        time.time() - t0 < 600:
                    time.sleep(0.2)
                assert drv2.compiled_for(kind) is not None or \
                    drv2.join_for(kind) is not None, \
                    f"{kind} fell back: {drv2.fallback_reasons()}"
                row["path"] = "join" if drv2.join_for(kind) else "device"
            best_s = float("inf")
            for _ in range(2):
                # measure the full per-kind sweep, not PR 1's unchanged-
                # rows delta shortcut (which answers from cache in ~0s)
                if hasattr(drv2, "_audit_results_cache"):
                    drv2._audit_results_cache.clear()
                t0 = time.time()
                nres = len(client2.audit().results())
                best_s = min(best_s, time.time() - t0)
            row[f"{side}_audit_s"] = round(best_s, 4)
            row[f"{side}_violations"] = nres
        assert row["interpreter_violations"] == row["device_violations"], \
            f"{kind}: verdict count diverged"
        row["speedup"] = round(
            row["interpreter_audit_s"] / max(row["device_audit_s"], 1e-9), 1)
        best = max(best, row["speedup"])
        per_kind[kind] = row

    print(json.dumps({
        "config": 12, "metric": "compile_widening_speedup",
        "value": best,
        "unit": ("x (best per-kind steady audit speedup, interpreter vs "
                 "newly device-compiled path, extended-form corpus; "
                 f"dense kinds at {n_dense} objects, join kinds at "
                 f"{n_join})"),
        "general_library_compiled_fraction":
            cov["device_compiled_fraction"],
        "general_library_interpreter_kinds": cov["interpreter_kinds"],
        "per_kind": per_kind,
    }))


# -------------------------------------------------------------- config 13


def config13():
    """Sharded inventory plane (the PR-16 tentpole): the audit
    inventory consistent-hashed by (GVK, namespace) across N audit
    shard PROCESSES, each sweeping only its slice, the leader
    composing per-shard results into one audit round. At each shard
    count over the SAME leader inventory it measures: the spawn +
    slice-sync wall (what a respawned shard pays end to end), the
    full-slice re-sweep wall right after a resync (the orphaned-
    partition path — the `objects_per_sec` headline), and the steady
    incremental round under ~0.1% routed churn (the recurring state).
    Defaults to 10M * BENCH_SCALE objects (BENCH_C13_OBJECTS
    overrides). On a small host the shard children time-share the
    cores, so shards>1 validates the sharded path, not core scaling —
    the record says which it was."""
    import shutil
    import tempfile

    from gatekeeper_tpu import policies
    from gatekeeper_tpu.control.audit import ShardedAuditPlane
    from gatekeeper_tpu.control.backplane import AuditShardSupervisor
    from gatekeeper_tpu.control.kube import FakeKube
    from gatekeeper_tpu.parallel.workload import REQUIRED_LABELS_TEMPLATE

    n = int(os.environ.get("BENCH_C13_OBJECTS",
                           int(10_000_000 * SCALE)))
    shard_counts = [int(s) for s in os.environ.get(
        "BENCH_C13_SHARDS", "1 2 4").split()]
    n_ns = max(16, min(8192, n // 100))
    n_ing = max(4, n // 1000)
    churn = max(1, min(1000, n // 1000))
    cores = os.cpu_count() or 1

    def pod(i, tag=None):
        # ~0.1% violating tail keeps materialization off the critical
        # path; churn tags mutate labels without changing verdicts
        labels = {"team": "core"} if i % 1000 else {"app": "x"}
        if tag:
            labels["churn"] = tag
        return {"apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": f"p-{i}",
                             "namespace": f"ns{i % n_ns}",
                             "labels": labels}}

    drv, leader = new_client()
    leader.add_template(REQUIRED_LABELS_TEMPLATE)
    # the FIXED-kind join template: Ingresses broadcast their join
    # columns to every shard, Pods stay owner-only (the broadcast
    # pruning this plane exists for)
    leader.add_template(policies.load("general/uniqueingresshost"))
    leader.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sRequiredLabels",
        "metadata": {"name": "pods-need-team"},
        "spec": {"match": {"kinds": [{"apiGroups": [""],
                                      "kinds": ["Pod"]}]},
                 "parameters": {"labels": [{"key": "team"}]}}})
    leader.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sUniqueIngressHost",
        "metadata": {"name": "unique-hosts"}, "spec": {}})

    t0 = time.time()
    for i in range(n_ns):
        leader.add_data({"apiVersion": "v1", "kind": "Namespace",
                         "metadata": {"name": f"ns{i}"}})
    for i in range(n):
        leader.add_data(pod(i))
    for i in range(n_ing):
        host = (f"dup{i % 8}.corp.example" if i % 100 == 1
                else f"h{i}.corp.example")
        leader.add_data({"apiVersion": "networking.k8s.io/v1",
                         "kind": "Ingress",
                         "metadata": {"name": f"ing-{i}",
                                      "namespace": f"ns{i % n_ns}"},
                         "spec": {"rules": [{"host": host}]}})
    ingest_s = time.time() - t0
    total = n + n_ns + n_ing

    # unsharded reference on the leader itself (same client, same
    # inventory): full re-evaluation wall, delta cache dropped so the
    # steady-state shortcut can't answer from cache
    leader.audit()  # warm-up (background XLA compile)
    t0 = time.time()
    while hasattr(drv, "warm_status") and \
            drv.warm_status()["compiling"] and time.time() - t0 < 600:
        time.sleep(0.2)
    uns_s = float("inf")
    for _ in range(2):
        if hasattr(drv, "drop_audit_results"):
            drv.drop_audit_results()
        t0 = time.time()
        uns_n = len(leader.audit().results())
        uns_s = min(uns_s, time.time() - t0)

    per_shards = []
    tmp = tempfile.mkdtemp(prefix="gk-c13-")
    try:
        for shards in shard_counts:
            sock = os.path.join(tmp, f"s{shards}.sock")
            plane_box: list = []
            sup = AuditShardSupervisor(
                shards,
                socket_for=lambda k, s=sock: f"{s}.{k}",
                spawn_args=["--log-level", "WARNING"],
                snapshot_provider=lambda k: plane_box[0].sync_snapshot(k))
            plane = ShardedAuditPlane(FakeKube(), leader, sup, shards)
            plane_box.append(plane)
            row: dict = {"shards": shards}
            try:
                t0 = time.time()
                sup.start()  # spawn children + bulk per-slice sync
                row["spawn_sync_s"] = round(time.time() - t0, 3)
                t0 = time.time()
                res, _ = plane.sweep(None)  # slice encode + XLA warm
                row["first_round_s"] = round(time.time() - t0, 3)
                row["violations"] = len(res)
                # orphaned-partition re-sweep: fresh slice sync (warm
                # device programs), then one FULL composed round
                for k in range(shards):
                    sup._resync(k)
                t0 = time.time()
                res, stats = plane.sweep(None)
                wall = time.time() - t0
                row["full_sweep_wall_s"] = round(wall, 4)
                row["objects_per_sec"] = round(total / max(wall, 1e-9))
                row["shard_eval_max_s"] = stats.get("shard_eval_max_s")
                # steady incremental round under routed churn: live
                # deltas route owner-only over the backplane, shards
                # re-evaluate dirty rows, the leader recomposes
                plane.attach()
                steady = float("inf")
                for r in range(2):
                    for j in range(churn):
                        leader.add_data(pod((j * 997) % n, tag=f"r{r}"))
                    t0 = time.time()
                    res2, _ = plane.sweep(None)
                    steady = min(steady, time.time() - t0)
                row["steady_churn_sweep_s"] = round(steady, 4)
                row["steady_violations"] = len(res2)
            except Exception as e:
                row["error"] = f"{type(e).__name__}: {e}"[:200]
            finally:
                sup.stop()
                plane.stop()
            per_shards.append(row)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    ok = [r for r in per_shards if "error" not in r]
    best = max(ok, key=lambda r: r["objects_per_sec"]) if ok else None
    out = {
        "config": 13, "metric": "sharded_audit_objects_per_sec",
        "value": best["objects_per_sec"] if best else None,
        "unit": (f"objects/s (sharded inventory plane full composed "
                 f"round, best of shards={shard_counts}; requiredlabels "
                 f"+ uniqueingresshost x {total} objects across {n_ns} "
                 "namespaces)"),
        "objects": total, "host_cores": cores,
        "leader_ingest_s": round(ingest_s, 2),
        "unsharded_sweep_s": round(uns_s, 4),
        "unsharded_violations": uns_n,
        "per_shards": per_shards,
    }
    if best:
        out["best_shards"] = best["shards"]
        out["sweep_wall_s"] = best["full_sweep_wall_s"]
        out["vs_unsharded"] = round(uns_s /
                                    max(best["full_sweep_wall_s"], 1e-9),
                                    2)
        if cores < max(shard_counts):
            out["note"] = (f"{cores} host core(s): shard children "
                           "time-share the core, so shards>1 validates "
                           "the sharded path, not core scaling")
    print(json.dumps(out))


# -------------------------------------------------------------- config 14


def config14():
    """Adaptive serving controller (the PR-18 tentpole), three legs on
    the in-process closed-loop harness:

    Leg A — reference: the config-5 hand-tuned optimum (max_wait=3ms,
    max_batch=256) under an 8-thread closed loop -> `ref_rps`. Low
    concurrency puts the plane in the edge-bound trickle regime PR
    14's scrape showed for the real deployment — the regime the
    controller's max_wait rule exists for (at 64 threads the plane is
    flusher-bound and batch amortization, not the wait window, sets
    the throughput).

    Leg B — convergence: the SAME loop against deliberately mis-tuned
    cold defaults (max_wait=50ms, max_batch=1024 — every batch seals
    on the wait window at ~1% fill, so the wait is pure added latency)
    measured first WITHOUT the controller (`cold_rps`, the gap the
    loop must close), then with an armed AdaptiveController ticked on
    a fixed cadence until `max_wait` lands at its floor. The steady
    window after convergence must reach within ~10% of `ref_rps`
    (`adaptive_converged_frac`, the headline — gate >= 0.9) with zero
    sustained oscillation (actuation-direction flip count gated <= 2)
    and the degradation ladder never leaving rung 0. The kill switch
    (`disarm(restore=True)`) must then restore every knob to the cold
    baseline bit-exactly.

    Leg C — chaos: the test_resilience engine-kill storm with the
    controller ARMED on the serving batcher: a 60-caller admission
    burst over FrontendServer -> BackplaneClient -> BackplaneEngine,
    the engine aborted (the in-process kill -9 analog) mid-burst with
    the `backplane.engine` fault point held down — zero unanswered
    admissions, every caller gets an AdmissionReview per the fail-open
    stance, and the armed controller disarms clean afterwards."""
    import http.client
    import threading

    from gatekeeper_tpu.control.adaptive import AdaptiveController
    from gatekeeper_tpu.control.backplane import (
        BackplaneClient,
        BackplaneEngine,
        FrontendServer,
        default_socket_path,
    )
    from gatekeeper_tpu.control.webhook import (
        MicroBatcher,
        ValidationHandler,
    )
    from gatekeeper_tpu.utils.faults import FAULTS

    _, client = _general_library_client()
    reviews = _mixed_reviews(max(64, int(256 * SCALE)), seed=14)
    n_threads = 8
    window_s = max(1.0, 2.5 * min(SCALE, 1.0))
    tick_s = 0.2

    def closed_loop(batcher, stop_evt, counts):
        def worker(k):
            j = 0
            while not stop_evt.is_set():
                batcher.submit(reviews[(k * 131 + j) % len(reviews)])
                j += 1
                counts[k] += 1  # per-thread slot: no lock on the hot path
        ths = [threading.Thread(target=worker, args=(k,), daemon=True)
               for k in range(n_threads)]
        for t in ths:
            t.start()
        return ths

    def measure_window(counts, duration):
        before = sum(counts)
        t0 = time.time()
        time.sleep(duration)
        return (sum(counts) - before) / (time.time() - t0)

    # --- leg A: hand-tuned reference (config-5 closed-loop optimum)
    batcher_a = MicroBatcher(client, max_wait=0.003, max_batch=256)
    batcher_a.submit(reviews[0])  # warm the flusher + XLA programs
    stop_a = threading.Event()
    counts_a = [0] * n_threads
    ths = closed_loop(batcher_a, stop_a, counts_a)
    time.sleep(0.3)  # let the loop fill before the timed window
    ref_rps = measure_window(counts_a, window_s)
    stop_a.set()
    for t in ths:
        t.join(10)
    batcher_a.stop()

    # --- leg B: cold mis-tuned defaults, then the armed controller
    cold = {"max_wait": 0.05, "max_batch": 1024, "max_queue": 0}
    batcher_b = MicroBatcher(client, **cold)
    ctrl = AdaptiveController(batcher=batcher_b, interval=999.0,
                              cooldown_s=0.1, hysteresis_s=1.0,
                              relax_after_s=1e9, min_seals=2)
    stop_b = threading.Event()
    counts_b = [0] * n_threads
    ths = closed_loop(batcher_b, stop_b, counts_b)
    time.sleep(0.3)
    cold_rps = measure_window(counts_b, window_s)
    ctrl.arm()  # interval=999: the tick thread parks; ticks are manual
    ctrl._sample(time.monotonic())  # prime counter deltas: leg A's
    # seal/shed series live in the same process registry — the first
    # sample must not read their lifetime totals as one tick's delta
    ticks = 0
    wait_floor = ctrl.knobs["batch_max_wait"].lo
    while batcher_b.max_wait > 1.5 * wait_floor and ticks < 60:
        time.sleep(tick_s)
        ctrl.tick()
        ticks += 1
    converged_rps = measure_window(counts_b, window_s)
    stop_b.set()
    for t in ths:
        t.join(10)
    conv_frac = converged_rps / max(ref_rps, 1e-9)
    flips = ctrl.flip_count()
    rung_after = ctrl.ladder.rung
    converged_wait = batcher_b.max_wait
    trail = ctrl.actuations()[-12:]  # already wire-shape dicts
    ctrl.disarm(restore=True)  # the kill switch: bit-exact restore
    restore_exact = (batcher_b.max_wait == cold["max_wait"]
                     and batcher_b.max_batch == cold["max_batch"]
                     and batcher_b.max_queue == cold["max_queue"])
    batcher_b.stop()
    assert conv_frac >= 0.9, \
        f"controller converged to {conv_frac:.2f}x of the hand-tuned " \
        f"reference (gate: within ~10%)"
    assert flips <= 2, f"actuation oscillation: {flips} direction flips"
    assert restore_exact, "kill switch did not restore the baseline " \
        f"bit-exactly: {batcher_b.knob_values()} != {cold}"

    # --- leg C: mid-burst engine kill with the controller armed
    def slow_eval(batch):
        time.sleep(0.05)  # keep a healthy backlog in flight at the kill
        return client.driver.review_batch(TARGET, batch)

    batcher_c = MicroBatcher(client, max_wait=0.002, max_batch=8,
                             evaluate=slow_eval)
    ctrl_c = AdaptiveController(batcher=batcher_c, interval=0.1,
                                cooldown_s=0.1, hysteresis_s=1.0)
    validation = ValidationHandler(client, kube=None, batcher=batcher_c,
                                   decision_cache_size=0,
                                   ladder=ctrl_c.ladder)
    sock = default_socket_path() + ".bench14"
    engine = BackplaneEngine(sock, validation=validation)
    engine.start()
    bc = BackplaneClient(sock, worker_id="bench14")
    frontend = FrontendServer(bc, port=0, addr="127.0.0.1")
    frontend.start()
    ctrl_c.arm()  # the real tick thread rides the kill
    n = 60
    answered: dict = {}
    errors: list = []
    lock = threading.Lock()

    def fire(i):
        obj = {"apiVersion": "v1", "kind": "Pod",
               "metadata": {"name": f"b14-{i}", "namespace": "bench"}}
        payload = {"apiVersion": "admission.k8s.io/v1",
                   "kind": "AdmissionReview",
                   "request": {"uid": f"b14-{i}", "operation": "CREATE",
                               "kind": {"group": "", "version": "v1",
                                        "kind": "Pod"},
                               "name": f"b14-{i}", "namespace": "bench",
                               "userInfo": {"username": "bench"},
                               "object": obj, "timeoutSeconds": 3}}
        try:
            conn = http.client.HTTPConnection("127.0.0.1",
                                              frontend.port, timeout=15)
            conn.request("POST", "/v1/admit?timeout=3s",
                         json.dumps(payload),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read())
            conn.close()
            with lock:
                answered[i] = (resp.status, body["response"])
        except Exception as e:  # noqa: BLE001
            with lock:
                errors.append((i, repr(e)))

    threads = [threading.Thread(target=fire, args=(i,))
               for i in range(n)]
    try:
        for t in threads:
            t.start()
        # let part of the burst land real verdicts, then kill the
        # engine under the rest; the fault point keeps the reconnect
        # path down for the stragglers
        deadline = time.time() + 10
        while len(answered) < n // 6 and time.time() < deadline:
            time.sleep(0.01)
        FAULTS.inject("backplane.engine", mode="error")
        engine.abort()
        for t in threads:
            t.join(20)
    finally:
        frontend.stop(drain_timeout=2.0)
        ctrl_c.disarm(restore=True)
        batcher_c.stop()
        FAULTS.reset()
    stance = sum(1 for _, resp in answered.values()
                 if (resp.get("status") or {}).get("code") in (503, 504))
    assert not errors, errors[:3]
    assert len(answered) == n, \
        f"unanswered admissions after engine kill: {n - len(answered)}"

    print(json.dumps({
        "config": 14, "metric": "adaptive_converged_frac",
        "value": round(conv_frac, 3),
        "unit": ("x of the hand-tuned config-5 knobs' rps on the same "
                 "edge-bound closed loop, reached from cold defaults "
                 "(max_wait 50ms) by the armed controller; gates: "
                 ">= 0.9, flip count <= 2, zero unanswered admissions "
                 "through a mid-burst engine kill, kill-switch restore "
                 "bit-exact"),
        "ref_rps": round(ref_rps),
        "cold_rps": round(cold_rps),
        "converged_rps": round(converged_rps),
        "ticks_to_converge": ticks,
        "converged_max_wait_ms": round(converged_wait * 1000, 3),
        "flip_count": flips,
        "rung_after": rung_after,
        "kill_switch_restore_exact": restore_exact,
        "actuations": trail,
        "chaos": {"callers": n, "answered": len(answered),
                  "stance_answers": stance, "errors": len(errors)},
    }))


def config15():
    """Chaos MTTR matrix (the PR-19 tentpole): six fault scenarios,
    each injected twice against a live plane, with recovery measured
    HARNESS-side as t_healthy - t_inject (the supervisor's own
    fault_recovery histogram measures detection->resync; this number
    adds detection latency, which is the part an operator feels):

      engine-kill     SIGKILL the child admission engine; healthy =
                      replacement spawned + resynced
      engine-pause    SIGSTOP it (gray failure: alive to waitpid,
                      wedged to callers) — detection must come from
                      the heartbeat deadline, nobody sends SIGCONT
      frontend-kill   SIGKILL one pre-forked frontend slot; healthy =
                      full worker fan-out serving again
      shard-kill      SIGKILL an audit shard child; healthy = slice
                      rebuilt AND the next composed round bit-equal
                      to a clean single-process oracle
      leader-kill     expire the incumbent's lease (the crashed-
                      leader analog); healthy = a candidate holds
                      the lease again
      apiserver-flap  a burst of 5xx on kube writes; healthy = the
                      next status write round-trips

    An admission trickle rides every serve-plane scenario and the
    crash-consistency verifier (gatekeeper_tpu.control.chaos) checks
    the side effects: zero unanswered admissions, audit bit-equality,
    no leaked children/fds//dev/shm segments, no stale gauge series.

    Headlines: `chaos_mttr_p99_s` (max MTTR across the matrix — p99
    over this sample count IS the max; lower-better, gated by
    bench_trend via the c15 series) and `chaos_invariant_violations`
    (asserted == 0 in-bench, so a violation fails the config rather
    than shipping as a number).

    Engine/shard children run on JAX_PLATFORMS=cpu: MTTR measures the
    supervisory plane (detect/kill/respawn/resync), not eval speed,
    and child processes must not fight the parent for an accelerator.
    """
    prev_platform = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        _config15_body()
    finally:
        if prev_platform is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = prev_platform


def _config15_body():
    import tempfile
    import threading

    import tools.chaos_verify as cv
    from gatekeeper_tpu.control.chaos import (
        CheckResult,
        LeakBaseline,
        PlaneHandles,
        Verifier,
    )
    from gatekeeper_tpu.control.main import Runtime, build_parser
    from gatekeeper_tpu.utils.faults import FAULTS

    REPEATS = 2
    verifier = Verifier()
    matrix: dict = {}
    probe_seq = [0]
    answered: dict = {}
    errors: list = []
    lock = threading.Lock()

    def sample(name, fn):
        samples = matrix.setdefault(name, {"samples_s": []})["samples_s"]
        for _ in range(REPEATS):
            samples.append(round(fn(), 3))
            time.sleep(0.3)  # settle between repeats
        matrix[name]["p99_s"] = max(samples)

    def wait_until(pred, timeout=45.0, tag=""):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return
            time.sleep(0.05)
        raise AssertionError(f"no recovery within {timeout}s: {tag}")

    # ---- serve plane: engine-kill / engine-pause / frontend-kill ----
    args = build_parser().parse_args([
        "--fake-kube", "--port", "0", "--prometheus-port", "0",
        "--disable-cert-rotation", "--health-addr", ":0",
        "--operation", "webhook", "--admission-workers", "2",
        "--admission-engines", "2"])
    rt = Runtime(args)
    rt.args.metrics_backend = "none"
    baseline = LeakBaseline(PlaneHandles(kube=rt.kube)).capture()
    rt.start()
    rt.frontends.heartbeat_deadline_s = 3.0
    rt.engines.heartbeat_deadline_s = 2.0
    try:
        wait_until(lambda: rt.backplane.connected >= 2, 30,
                   "frontends never connected")
        baseline.plane.frontends = rt.frontends
        baseline.plane.engines = rt.engines
        baseline.track_children()

        def trickle(n=12):
            """An admission load thread riding one outage window."""
            base = probe_seq[0]
            probe_seq[0] += n
            ids = [f"m15-{base + i}" for i in range(n)]
            t = threading.Thread(
                target=cv._load_worker,
                args=(rt.frontends.port, ids, answered, errors, lock),
                daemon=True)
            t.start()
            return t

        def engines_converged():
            return (rt.engines.alive_count()
                    == len(rt.engines.engine_ids)
                    and not any(rt.engines._dirty.values()))

        def engine_fault(pause):
            victim = rt.engines._procs[rt.engines.engine_ids[0]]
            load = trickle()
            t0 = time.monotonic()
            k = rt.engines.engine_ids[0]
            (rt.engines.pause_engine if pause
             else rt.engines.kill_engine)(k)
            wait_until(lambda: rt.engines._procs.get(k) is not victim
                       and engines_converged(),
                       tag="engine pause" if pause else "engine kill")
            mttr = time.monotonic() - t0
            load.join(60)
            return mttr

        def frontend_kill():
            slot = 0
            victim_pid = rt.frontends.child_pids()[slot]
            load = trickle()
            t0 = time.monotonic()
            rt.frontends.kill_child(slot)
            wait_until(lambda: rt.frontends.child_pids().get(slot)
                       not in (None, victim_pid)
                       and rt.frontends.alive()
                       and rt.backplane.connected >= 2,
                       tag="frontend kill")
            mttr = time.monotonic() - t0
            load.join(60)
            return mttr

        sample("engine-kill", lambda: engine_fault(pause=False))
        sample("engine-pause", lambda: engine_fault(pause=True))
        sample("frontend-kill", frontend_kill)

        baseline.track_children()
        verifier.check_admissions(probe_seq[0], answered, errors,
                                  fail_closed=bool(args.fail_closed))
    finally:
        rt.stop()
    verifier.check_leaks(baseline)

    # ---- audit plane: shard-kill --------------------------------------
    from gatekeeper_tpu.client import Backend
    from gatekeeper_tpu.control.audit import AuditManager, ShardedAuditPlane
    from gatekeeper_tpu.control.backplane import AuditShardSupervisor
    from gatekeeper_tpu.ir import TpuDriver
    from gatekeeper_tpu.target import K8sValidationTarget

    objs = cv._cluster_objects()
    okube = cv._cluster_kube(objs)
    oracle_client = Backend(TpuDriver()).new_client([K8sValidationTarget()])
    cv._library(oracle_client)
    oracle_results = [cv._result_key(r) for r in AuditManager(
        okube, oracle_client, interval=3600,
        incremental=True).audit_once()]

    kube = cv._cluster_kube(objs)
    leader = Backend(TpuDriver()).new_client([K8sValidationTarget()])
    sock = os.path.join(tempfile.mkdtemp(prefix="bench15-"), "audit.sock")
    plane_box: list = []
    sup = AuditShardSupervisor(
        2, socket_for=lambda k: f"{sock}.{k}",
        spawn_args=["--log-level", "WARNING"],
        snapshot_provider=lambda k: plane_box[0].sync_snapshot(k),
        heartbeat_deadline_s=2.0)
    splane = ShardedAuditPlane(kube, leader, sup, 2)
    plane_box.append(splane)
    splane.attach()
    cv._library(leader)
    mgr = AuditManager(kube, leader, interval=3600, shard_plane=splane)
    sup.start()
    try:
        round0 = [cv._result_key(r) for r in mgr.audit_once()]
        pre = CheckResult("bench15_audit_clean")
        if round0 != oracle_results:
            pre.violations.append(
                "pre-chaos sharded round differs from oracle")
        verifier.results.append(pre)

        def shard_kill():
            victim = sup._procs[1]
            t0 = time.monotonic()
            sup.kill_engine(1)
            wait_until(lambda: sup._procs.get(1) is not victim
                       and sup.alive_count() == 2
                       and not any(sup._dirty.values()),
                       tag="shard kill")
            mttr = time.monotonic() - t0
            verifier.check_audit_bitequal(
                [cv._result_key(r) for r in mgr.audit_once()],
                oracle_results)
            return mttr

        sample("shard-kill", shard_kill)
    finally:
        sup.stop()
        splane.stop()

    # ---- control plane: leader-kill / apiserver-flap ------------------
    from gatekeeper_tpu.control.kube import FakeKube, LEASE_GVK, LeaseElector

    lkube = FakeKube()
    lkube.register_kind(LEASE_GVK)
    electors = [LeaseElector(lkube, identity=i, lease_duration=0.6,
                             namespace="gk") for i in ("pod-a", "pod-b")]
    for e in electors:
        e.start()
    try:
        wait_until(lambda: any(e.is_leader for e in electors), 15,
                   "no initial leader")

        def leader_kill():
            incumbent = next(e for e in electors if e.is_leader)
            t0 = time.monotonic()
            FAULTS.inject("kube.lease", mode="expire", count=1,
                          match={"identity": incumbent.identity})
            wait_until(lambda: not incumbent.is_leader, 15,
                       "incumbent never deposed")
            wait_until(lambda: any(e.is_leader for e in electors), 15,
                       "no successor elected")
            return time.monotonic() - t0

        sample("leader-kill", leader_kill)
    finally:
        for e in electors:
            e.stop()
        FAULTS.reset()

    from gatekeeper_tpu.control.resilience import GuardedKube

    fkube = FakeKube()
    fkube.register_kind(("constraints.gatekeeper.sh", "v1beta1",
                         "K8sRequiredLabels"))
    fkube.apply({"apiVersion": "constraints.gatekeeper.sh/v1beta1",
                 "kind": "K8sRequiredLabels",
                 "metadata": {"name": "flap-target", "uid": "c-flap"},
                 "spec": {}})
    # the kube.write point lives in GuardedKube's mutating verbs — the
    # flap is felt (and retried through) exactly where production
    # status writes go
    gkube = GuardedKube(fkube)

    def apiserver_flap():
        gvk = ("constraints.gatekeeper.sh", "v1beta1",
               "K8sRequiredLabels")
        t0 = time.monotonic()
        FAULTS.inject("kube.write", mode="error", param="503", count=5)
        while True:
            try:
                obj = fkube.get(gvk, "flap-target")
                obj["status"] = {"probedAt": len(matrix)}
                gkube.update(obj, subresource="status")
                return time.monotonic() - t0
            except Exception:
                if time.monotonic() - t0 > 15:
                    raise
                time.sleep(0.02)

    try:
        sample("apiserver-flap", apiserver_flap)
    finally:
        FAULTS.reset()

    verifier.check_stale_gauges()
    violations = verifier.violation_count()
    mttr_p99 = max(v["p99_s"] for v in matrix.values())
    report = verifier.report()

    print(json.dumps({
        "config": 15, "metric": "chaos_mttr_p99_s",
        "value": round(mttr_p99, 3),
        "unit": ("s, worst harness-measured MTTR (t_healthy - "
                 "t_inject, incl. detection) across engine-kill/"
                 "engine-pause/frontend-kill/shard-kill/leader-kill/"
                 "apiserver-flap x2 repeats; heartbeat deadlines "
                 "2-3s; cpu children; gated alongside "
                 "chaos_invariant_violations == 0"),
        "chaos_invariant_violations": violations,
        "matrix": matrix,
        "checks": [{"name": c["name"], "violations": c["violations"]}
                   for c in report["checks"]],
        "probes": {"submitted": probe_seq[0], "answered": len(answered),
                   "errors": len(errors)},
    }), flush=True)
    assert violations == 0, \
        f"crash-consistency violations under the MTTR matrix: {report}"


# -------------------------------------------------------------- config 16


def _scan_child(cfg_path: str) -> None:
    """--scan-child: one fleet-scan run through the REAL CLI
    (control.scan.scan_main) in a fresh process — cold vs warm AOT is
    a process-boundary property, so each measurement must boot its own
    interpreter. Prints the scan summary JSON line on stdout."""
    import tempfile

    from gatekeeper_tpu.control.scan import scan_main

    with open(cfg_path) as f:
        cfg = json.load(f)
    sf = tempfile.NamedTemporaryFile("w", suffix=".json", delete=False)
    sf.close()
    argv = [cfg["jsonl"], "--format", "jsonl",
            "--loaders", str(cfg.get("loaders", 2)),
            "--batch", str(cfg.get("batch", 256)),
            "--depth", str(cfg.get("depth", 2)),
            "--dedupe", str(cfg.get("dedupe", 65536)),
            "--output", os.devnull, "--summary", sf.name]
    if cfg.get("socket"):
        argv += ["--backplane", cfg["socket"]]
    for p in cfg.get("policies") or []:
        argv += ["--policies", p]
    if cfg.get("aot_dir"):
        argv += ["--aot-dir", cfg["aot_dir"]]
    if cfg.get("compile_cache_dir"):
        argv += ["--compile-cache-dir", cfg["compile_cache_dir"]]
    rc = scan_main(argv)
    with open(sf.name) as f:
        summary = json.load(f)
    os.unlink(sf.name)
    summary["exit"] = rc
    print(json.dumps(summary), flush=True)


def config16():
    """Fleet scan (ISSUE 20): manifests/s through the full
    loader/dedupe/bulk-feed pipeline at 1M+ clusterless manifests
    (BENCH_SCALE-scaled), cold vs warm AOT on the in-process tier plus
    the cross-process backplane tier. The headline is the best warm
    tier — the loader pipeline must keep up with the PR 14 bulk wire
    ceiling, not become the new bottleneck."""
    import shutil
    import subprocess
    import tempfile

    import yaml

    n = int(os.environ.get("BENCH_C16_MANIFESTS",
                           str(int(1_000_000 * SCALE))))
    dup = max(1, int(os.environ.get("BENCH_C16_DUP", "8")))
    unique = max(1, n // dup)
    n = unique * dup
    loaders = int(os.environ.get("BENCH_C16_LOADERS",
                                 str(min(4, os.cpu_count() or 1))))
    work = tempfile.mkdtemp(prefix="gk-bench-scan-")
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        # the inventory export: `unique` distinct objects, each
        # appearing `dup` times (repo trees repeat identical objects
        # heavily — the shape the dedupe tier exists for), in a
        # deterministic shuffle so duplicates interleave instead of
        # clustering
        blobs = [json.dumps(o).encode()
                 for o in synth_mixed_objects(unique, seed=16)]
        order = list(range(unique)) * dup
        random.Random(16).shuffle(order)
        jsonl = os.path.join(work, "inventory.jsonl")
        with open(jsonl, "wb") as f:
            for i in order:
                f.write(blobs[i])
                f.write(b"\n")
        del order
        constraints_yaml = os.path.join(work, "constraints.yaml")
        with open(constraints_yaml, "w") as f:
            yaml.safe_dump_all(
                [{"apiVersion": "constraints.gatekeeper.sh/v1beta1",
                  "kind": kind, "metadata": {"name": cname},
                  "spec": ({"parameters": params} if params else {})}
                 for kind, cname, params in GENERAL_CONSTRAINTS], f)
        policies_dir = os.path.join(
            here, "gatekeeper_tpu", "policies", "general")
        base_cfg = {
            "jsonl": jsonl, "loaders": loaders,
            "policies": [policies_dir, constraints_yaml],
            "aot_dir": os.path.join(work, "aot"),
            "compile_cache_dir": os.path.join(work, "xla-cache"),
        }

        def _run_child(cfg: dict, tag: str) -> dict:
            cfg_path = os.path.join(work, f"scan-{tag}.json")
            with open(cfg_path, "w") as f:
                json.dump(cfg, f)
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--scan-child", cfg_path],
                cwd=here, capture_output=True, text=True, timeout=3600)
            line = (proc.stdout.strip().splitlines() or [""])[-1]
            try:
                return json.loads(line)
            except ValueError:
                return {"error": f"scan child {tag} failed: "
                        + (proc.stderr or "no stderr")[-300:]}

        # cold: empty AOT store + XLA cache, every program compiles
        # inside the measured wall (the short-lived CI invocation)
        cold = _run_child(base_cfg, "cold")
        # warm: same dirs — programs deserialize instead of compiling
        warm = _run_child(base_cfg, "warm")

        # cross-process tier: the scan feeding a separate serving
        # engine over backplane B frames (loader processes pre-encode
        # the envelope bytes)
        bp = {}
        engine = None
        sock = os.path.join(tempfile.gettempdir(),
                            f"gk-bench-scan-{os.getpid()}.sock")
        try:
            engine = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--serve-engine", sock],
                cwd=here, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True)
            line = engine.stdout.readline()
            if "READY" not in (line or ""):
                raise RuntimeError(
                    "scan engine failed to start: "
                    + (engine.stderr.read() or "")[-300:])
            import threading as _th
            _th.Thread(target=engine.stdout.read, daemon=True).start()
            _th.Thread(target=engine.stderr.read, daemon=True).start()
            bp = _run_child({"jsonl": jsonl, "loaders": loaders,
                             "socket": sock}, "backplane")
        except Exception as e:
            bp = {"error": str(e)[:300]}
        finally:
            if engine is not None:
                engine.kill()

        def _rate(s: dict):
            return s.get("manifests_per_sec") if not s.get("error") \
                else None

        warm_rates = [r for r in (_rate(warm), _rate(bp))
                      if r is not None]
        best = max(warm_rates, default=0)
        cold_r, warm_r = _rate(cold), _rate(warm)
        print(json.dumps({
            "config": 16, "metric": "fleet_scan_manifests_per_sec",
            "value": best,
            "unit": f"manifests/s (offline fleet scan, {n} JSONL "
                    f"manifests, {unique} unique x{dup}, general "
                    "library, best warm tier)",
            "fleet_scan_manifests_per_sec": best,
            "manifests": n, "unique": unique, "dup_factor": dup,
            "loaders": loaders,
            "scan_cold_manifests_per_sec": cold_r,
            "scan_warm_manifests_per_sec": warm_r,
            # PR 8's AOT story for short-lived CI invocations: the
            # warm boot must beat the cold one (compile inside vs
            # deserialize) — recorded as the cold->warm speedup
            "cold_warm_speedup": (round(warm_r / cold_r, 2)
                                  if cold_r and warm_r else None),
            "scan_backplane_manifests_per_sec": _rate(bp),
            "tiers": {"inproc_cold": cold, "inproc_warm": warm,
                      "backplane": bp},
            # verdict honesty across tiers: same manifests, same
            # library -> identical deny counts and zero error records
            "denied": warm.get("denied"),
            "tier_verdicts_agree": (
                warm.get("denied") == bp.get("denied")
                if not (warm.get("error") or bp.get("error"))
                else None),
            "errors": (warm.get("errors", 0) or 0)
            + (cold.get("errors", 0) or 0) + (bp.get("errors", 0) or 0),
        }))
    finally:
        shutil.rmtree(work, ignore_errors=True)


def run(which: list[int]) -> int:
    """Run the named configs. A config-level exception no longer kills
    the remaining configs OR vanishes into the log: it prints an
    explicit `{"config": N, "error": ...}` JSON line (bench.py records
    it in the output JSON, so tools/bench_trend.py can tell
    "regressed" from "didn't run") and the process still exits
    nonzero at the end so a blocking CI step on one config fails."""
    table = {1: config1, 2: config2, 3: config3, 5: config5, 6: config6,
             7: config7, 8: config8, 9: config9, 10: config10,
             11: config11, 12: config12, 13: config13, 14: config14,
             15: config15, 16: config16}
    failed = 0
    for c in which:
        if c not in table:
            sys.exit(f"unknown bench config {c}: choose from "
                     f"{sorted(table)} (config 4 is bench.py's headline — "
                     "run `python bench.py` with no --config)")
        try:
            table[c]()
        except Exception as e:
            failed += 1
            import traceback
            traceback.print_exc()
            print(json.dumps({
                "config": c,
                "error": f"{type(e).__name__}: {e}"[:300]}), flush=True)
    return failed


def main() -> None:
    if sys.argv[1:2] == ["--loadgen"]:
        port, rate, duration, seed, out = sys.argv[2:7]
        _loadgen_child(int(port), float(rate), float(duration),
                       int(seed), out)
        return
    if sys.argv[1:2] == ["--serve-engine"]:
        _engine_child(sys.argv[2],
                      decision_cache="--no-decision-cache"
                                     not in sys.argv[3:])
        return
    if sys.argv[1:2] == ["--scan-child"]:
        _scan_child(sys.argv[2])
        return
    if sys.argv[1:2] == ["--mesh-audit"]:
        _mesh_audit_child(int(sys.argv[2]), int(sys.argv[3]))
        return
    if sys.argv[1:2] == ["--coldwarm-child"]:
        _coldwarm_child(sys.argv[2])
        return
    failed = run([int(a) for a in sys.argv[1:]] or [1, 2, 3, 5, 6, 7])
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
