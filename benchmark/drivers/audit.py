"""Audit traffic: back-to-back full audits through the public client,
`Backend(TpuDriver()).new_client(...).audit()`, over a static inventory.

The results delta cache is dropped before each audit, so every audit
sweeps and materializes in full. The mix file names this driver and
holds no parameter.

Set-up: generate the library from the seed, ingest it, run the first
audit (which loads every program from the compile cache) and wait out
background compiles. Window: audits start until --seconds have passed;
the last one started runs to its end. Check: every audit of the window
against the configuration's reference; the window ran on the device (on
the path the cell's chips imply, every kind with objects to judge
dispatched there, nothing demoted) and compiled nothing.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import tempfile
import time
from collections import Counter

from benchmark import harness, spec, trace as btrace
from benchmark.gen import library
from benchmark.refcommon import group_version, matches

# every (kind, path) an audit may take on the device
DEVICE_PATHS = {"device", "join"}
# the control's per-constraint cap on reported violations: Gatekeeper's
# default --constraint-violations-limit
CONTROL_CAP = 20


def _key(r) -> tuple:
    res = r.resource or {}
    meta = res.get("metadata") or {}
    c = r.constraint or {}
    return ((res.get("kind"), meta.get("namespace", ""), meta.get("name")),
            (c.get("metadata") or {}).get("name"), r.msg)


def pack_keys(keys) -> bytes:
    """A multiset of answer keys as one canonical bytes object."""
    return "\n".join(sorted(json.dumps(k) for k in keys)).encode()


def pack(results: list) -> bytes:
    """An audit's answer as one bytes object, taken between audits: the
    window would otherwise hold every audit's Result objects alive, and
    the collector's full passes over them land inside later audits
    (80k per audit alternated 0.93 s and 1.2 s walls on the chip,
    PR 22)."""
    return pack_keys(_key(r) for r in results)


def unpack(blob: bytes) -> Counter:
    if not blob:
        return Counter()
    return Counter(tuple((tuple(k[0]), k[1], k[2])) for k in
                   map(json.loads, blob.decode().split("\n")))


def mismatches(got: Counter, want: Counter) -> int:
    """Size of the multiset symmetric difference."""
    return sum(((got - want) + (want - got)).values())


def expected_path(chips: int) -> str:
    """The audit path (`TpuDriver.last_audit_path`) a cell's chips imply:
    on more than one chip the program shards the sweep over a data axis
    of every chip it sees."""
    return "single" if chips == 1 else f"mesh(data={chips})"


def check_paths(checks, chips: int, paths: list) -> None:
    """Every window audit took the path the cell's chips imply."""
    want = expected_path(chips)
    checks.max("audits_not_single_path" if chips == 1
               else "audits_not_mesh_path",
               sum(p != want for p in paths), 0)


def kinds_with_objects(lib: dict) -> set:
    """The constraint kinds that match some object of the inventory."""
    seen = {(group_version(o)[0], o.get("kind")) for o in lib["inventory"]}
    return {c["kind"] for c in lib["constraints"] for group, kind in seen
            if matches((c.get("spec") or {}).get("match") or {},
                       {"kind": {"group": group, "kind": kind}})}


def run(cell, seed: int, seconds: float, trace: bool, control: bool,
        t_start: float) -> dict:
    from gatekeeper_tpu.client import Backend
    from gatekeeper_tpu.ir import TpuDriver
    from gatekeeper_tpu.target import K8sValidationTarget
    from gatekeeper_tpu.utils import profiling

    t0 = time.monotonic()
    lib = library.build(cell.config, seed, root=cell.root)
    gen_s = time.monotonic() - t0

    t0 = time.monotonic()
    driver = TpuDriver()
    client = Backend(driver).new_client([K8sValidationTarget()])
    for t in lib["templates"]:
        client.add_template(t)
    for c in lib["constraints"]:
        client.add_constraint(c)
    for o in lib["inventory"]:
        client.add_data(o)
    ingest_s = time.monotonic() - t0
    xla0 = harness.xla_cache()
    t0 = time.monotonic()
    client.audit().results()
    first_audit_s = time.monotonic() - t0
    compile_wait_s = harness.wait_compiles(driver)
    kinds = sorted(client.template_kinds())
    harness.say("setup", generate_s=gen_s, ingest_s=ingest_s,
                first_audit_s=first_audit_s, compile_wait_s=compile_wait_s,
                objects=len(lib["inventory"]),
                constraints=len(lib["constraints"]), kinds=kinds,
                xla_cache=harness.xla_cache(), xla_cache_before=xla0,
                warm=driver.warm_status()["warm"])

    prof = btrace.Profile(os.path.join(tempfile.gettempdir(),
                                       f"bench-trace-{os.getpid()}")) \
        if trace else None
    xla_w0 = harness.xla_cache()
    counts0 = driver.eval_counts()
    phases0 = profiling.timers().snapshot()
    gc0 = gc.get_stats()
    # equal answers are kept once: {packed answer: audits that gave it}
    walls, answers, paths, sizes = [], Counter(), [], []
    if prof:
        prof.start()
    t_window = time.monotonic()
    with btrace.maybe(trace, "window"):
        while time.monotonic() - t_window < seconds:
            with btrace.maybe(trace, "between audits"):
                driver.drop_audit_results()
            a0 = time.monotonic()
            with btrace.maybe(trace, "audit call"):
                results = client.audit().results()
            walls.append(time.monotonic() - a0)
            paths.append(driver.last_audit_path)
            with btrace.maybe(trace, "between audits"):
                sizes.append(len(results))
                answers[pack(results)] += 1
                del results
    t_end = time.monotonic()
    # the collector's passes per generation inside the window
    gc_passes = [b["collections"] - a["collections"]
                 for a, b in zip(gc0, gc.get_stats())]
    if prof:
        prof.stop()
    phases = profiling.PhaseTimers.diff(phases0,
                                        profiling.timers().snapshot())
    served = harness.counts_delta(counts0, driver.eval_counts())
    xla_w1 = harness.xla_cache()
    harness.wait_compiles(driver)
    hl = harness.health(driver)
    peak = harness.memory_peak_bytes(cell.chips)
    window_compiles = xla_w1.get("requests", 0) - xla_w0.get("requests", 0)
    harness.say("window", audits=len(walls), walls_s=walls,
                gc_passes=gc_passes, paths=Counter(paths),
                seconds=t_end - t_window,
                phases_s=phases, served=served,
                violations=sorted(Counter(sizes).items()),
                xla_cache_requests=window_compiles, health=hl)
    obs = {"setup_s": t_window - t_start, "audit_walls_s": walls,
           "phases_s": phases, "window_s": t_end - t_window}
    if prof:
        obs["trace"] = prof.reduce()

    # the program's state goes before the reference runs
    del client, driver
    gc.collect()

    t0 = time.monotonic()
    ref = spec.reference_for(cell)
    want = ref.audit(lib["inventory"], lib["constraints"])
    if control:
        capped = ref.audit(lib["inventory"], lib["constraints"],
                           cap_per_constraint=CONTROL_CAP)
        answers = Counter({pack_keys(capped.elements()): len(walls)})
    want_blob = pack_keys(want.elements())
    bad = [0 if blob == want_blob else mismatches(unpack(blob), want)
           for blob, n in answers.items() for _ in range(n)]
    harness.say("reference", seconds=time.monotonic() - t0,
                violations=sum(want.values()), mismatches=sum(bad),
                audits_with_mismatches=sum(b > 0 for b in bad),
                control=control)

    on_host = sorted(k for k, p in served.items() if set(p) - DEVICE_PATHS)
    missing = sorted(kinds_with_objects(lib) - set(served))
    checks = harness.Checks()
    checks.max("result_mismatches", sum(bad), 0)
    checks.min("audits_checked", len(bad), 1)
    check_paths(checks, cell.chips, paths)
    checks.max("kinds_off_device", len(on_host) + len(missing), 0)
    checks.max("demoted_or_quarantined",
               len(hl["demotions"]) + len(hl["quarantined"])
               + len(hl["fallback"]), 0)
    checks.max("window_compiles", window_compiles, 0)
    print(f"audit: kinds off the device {on_host}, without a device "
          f"dispatch {missing}", file=sys.stderr, flush=True)
    return {"obs": obs, "checks": checks, "attempted": len(walls),
            "failed": 0, "memory_peak_bytes": peak}
