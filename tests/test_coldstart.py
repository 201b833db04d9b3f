"""Cold-start elimination tests (ISSUE 8).

Covers the three tentpole legs: constraint-count (C-axis) power-of-two
bucketing (a library edit inside a bucket re-hits every cached device
program; results stay bit-equal to the unbucketed shapes, including the
mesh slab path), the AOT serialized-program store (a warm boot
deserializes instead of recompiling, and adopts the recorded sweep
signatures so the first sweep dispatches straight onto the device), and
the compile-cache observability satellites (enable_compile_cache returns
its status instead of swallowing failures; /debug/templates reports
per-kind compile provenance; the warm-cache prepack CLI).
"""

import json
import os
import time

import numpy as np
import pytest

from gatekeeper_tpu.client import Backend
from gatekeeper_tpu.ir import TpuDriver
from gatekeeper_tpu.ir import aot as aotmod
from gatekeeper_tpu.ir.driver import _pad_cbucket, enable_compile_cache
from gatekeeper_tpu.ir.features import _bucket
from gatekeeper_tpu.target import K8sValidationTarget

LABEL_KEYS = ["owner", "team", "env", "cost", "tier",
              "zone", "org", "app", "rel", "stage"]


def _counts():
    return dict(aotmod.COMPILE_COUNTS)


def _delta(before, after):
    return {k: after[k] - before.get(k, 0) for k in after}


def _single_device_driver(aot_dir=None):
    """Single-device driver with the cost model pinned to the device
    path (the adaptive EMA must not route these small test sweeps back
    to the host and make the compile-count assertions vacuous)."""
    drv = TpuDriver(aot_dir=aot_dir)
    drv._mesh = None
    drv._dev_batch_lat_s = 1e-4
    return drv


@pytest.fixture
def fresh_xla_cache(tmp_path, monkeypatch):
    """Isolate the persistent XLA compilation cache per test: an
    executable XLA loaded from its own cache may serialize to a corrupt
    payload (see AotStore.save's round-trip probe), so warm-boot tests
    asserting source=aot need their first compiles genuinely fresh —
    not cache hits against the process-wide cache earlier tests
    populated."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "xla"))
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def _add_constraint(client, k):
    client.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sRequiredLabels",
        "metadata": {"name": f"need-{LABEL_KEYS[k]}"},
        "spec": {"parameters": {"labels": [{"key": LABEL_KEYS[k]}]}}})


def _labels_client(drv, n, n_cons):
    from gatekeeper_tpu import policies

    client = Backend(drv).new_client([K8sValidationTarget()])
    client.add_template(policies.load("general/requiredlabels"))
    for k in range(n_cons):
        _add_constraint(client, k)
    for i in range(n):
        labels = {LABEL_KEYS[j]: "x" for j in range(len(LABEL_KEYS))
                  if (i + j) % 3}
        client.add_data({"apiVersion": "v1", "kind": "Namespace",
                         "metadata": {"name": f"ns{i:05d}",
                                      "labels": labels}})
    return client


def _key(results):
    return sorted((r.msg, (r.resource or {}).get("metadata", {})
                   .get("name", "")) for r in results)


# ------------------------------------------------------ C-axis bucketing


def test_pad_cbucket_pads_to_bucket_replicating_edge():
    enc = {"slot": {"x": np.arange(12, dtype=np.int32).reshape(3, 4)}}
    out = _pad_cbucket(enc, 3)
    a = out["slot"]["x"]
    assert a.shape == (4, 4)
    assert (a[:3] == enc["slot"]["x"]).all()
    assert (a[3] == a[2]).all(), "padding replicates the LAST constraint"
    # exact power of two: no copy, no padding
    enc4 = {"slot": {"x": np.zeros((4, 2), np.int32)}}
    assert _pad_cbucket(enc4, 4) is enc4
    # parameterless programs have no encoded params to pad
    assert _pad_cbucket({}, 3) == {}


def test_cbucket_library_edit_within_bucket_zero_compiles(tmp_path):
    """Adding a constraint INSIDE the current power-of-two C bucket must
    re-hit every cached device program: zero XLA compiles, zero AOT
    store loads (the live executable serves). Crossing the bucket
    boundary acquires the new-shape program exactly once."""
    drv = _single_device_driver(aot_dir=str(tmp_path / "aot"))
    assert drv.cbucket and drv.aot.enabled
    client = _labels_client(drv, 2048, 5)  # C=5 -> bucket 8

    base = _counts()
    got5 = _key(client.audit().results())
    d = _delta(base, _counts())
    assert d["fresh"] + d["cache"] >= 1, \
        "first sweep must actually compile on the device path"
    assert drv._eval_counts.get(("K8sRequiredLabels", "device"))

    # within-bucket edit: 5 -> 6 constraints, still bucket 8
    _add_constraint(client, 5)
    base = _counts()
    got6 = _key(client.audit().results())
    d = _delta(base, _counts())
    assert d["fresh"] == 0 and d["cache"] == 0 and d["aot"] == 0, \
        f"within-bucket edit must not touch XLA: {d}"
    assert drv.last_audit_path == "single", \
        "the edited library must have re-swept (not the delta cache)"
    assert len(got6) > len(got5), "new constraint must add violations"

    # crossing the boundary: 6 -> 9 constraints -> bucket 16
    for k in range(6, 9):
        _add_constraint(client, k)
    base = _counts()
    got9 = _key(client.audit().results())
    d = _delta(base, _counts())
    assert d["fresh"] + d["cache"] == 1, \
        f"bucket crossing must compile exactly once: {d}"
    assert len(got9) > len(got6)

    # ... and only once: the next sweep at the new size is free
    # (healthy-value churn forces a real re-sweep past the delta cache)
    client.add_data({"apiVersion": "v1", "kind": "Namespace",
                     "metadata": {"name": "ns00000",
                                  "labels": {k: "y" for k in LABEL_KEYS}}})
    base = _counts()
    client.audit()
    d = _delta(base, _counts())
    assert d["fresh"] == 0 and d["cache"] == 0 and d["aot"] == 0


def test_cbucket_bit_equal_unbucketed_including_mesh_slab():
    """Bucketed C results must be bit-equal to the unbucketed shapes
    (GATEKEEPER_TPU_CBUCKET=0) and the interpreter — on the mesh SLAB
    path too, where the C slicing rides the per-shard decode."""
    from gatekeeper_tpu.client import RegoDriver
    from gatekeeper_tpu.ir.evaljax import _MeshSlabPairs

    N, NC = 4096, 5
    assert _bucket(NC) != NC, "non-vacuous: C must actually pad"

    dm = TpuDriver()
    assert dm._mesh is not None, "8-device platform must yield a mesh"
    assert dm.cbucket
    dm.MESH_MIN_REVIEWS = 64
    dm._dev_batch_lat_s = 1e-4
    dm.sweep_chunk = 64
    dm.mesh_slab_local = 256  # n_loc = 512 -> 2 slabs per shard
    cm = _labels_client(dm, N, NC)
    handles = []
    orig = dm._dispatch_handle

    def spy(*a, **k):
        h = orig(*a, **k)
        handles.append(h)
        return h

    dm._dispatch_handle = spy
    got_mesh = _key(cm.audit().results())
    dm._dispatch_handle = orig
    assert dm.last_audit_path == "mesh(data=8)", dm.last_audit_path
    assert any(isinstance(h, _MeshSlabPairs) for h in handles), \
        "audit did not take the mesh slab loop"

    os.environ["GATEKEEPER_TPU_CBUCKET"] = "0"
    try:
        ds = _single_device_driver()
        assert not ds.cbucket
    finally:
        del os.environ["GATEKEEPER_TPU_CBUCKET"]
    cs = _labels_client(ds, N, NC)
    got_single = _key(cs.audit().results())

    ci = Backend(RegoDriver()).new_client([K8sValidationTarget()])
    from gatekeeper_tpu import policies
    ci.add_template(policies.load("general/requiredlabels"))
    for k in range(NC):
        _add_constraint(ci, k)
    for i in range(N):
        labels = {LABEL_KEYS[j]: "x" for j in range(len(LABEL_KEYS))
                  if (i + j) % 3}
        ci.add_data({"apiVersion": "v1", "kind": "Namespace",
                     "metadata": {"name": f"ns{i:05d}",
                                  "labels": labels}})
    got_interp = _key(ci.audit().results())

    assert got_mesh == got_single == got_interp
    assert got_mesh, "non-vacuous: some violations must fire"


# -------------------------------------------------------- AOT store


def test_aot_store_warm_boot_deserializes(tmp_path, fresh_xla_cache):
    """Second driver on the same AOT dir: every device program
    deserializes (source=aot), zero XLA compiles, bit-equal results;
    /debug/templates reports the provenance."""
    aot_dir = str(tmp_path / "aot")
    d1 = _single_device_driver(aot_dir=aot_dir)
    c1 = _labels_client(d1, 2048, 5)
    got1 = _key(c1.audit().results())
    assert d1.aot.programs_count() >= 1, \
        "first boot must persist serialized executables"

    base = _counts()
    d2 = _single_device_driver(aot_dir=aot_dir)
    c2 = _labels_client(d2, 2048, 5)
    got2 = _key(c2.audit().results())
    d = _delta(base, _counts())
    assert got1 == got2
    assert d["aot"] >= 1 and d["fresh"] == 0 and d["cache"] == 0, \
        f"warm boot must deserialize, not compile: {d}"
    st = d2.warm_status()
    assert st["aot"]["aot"] >= 1 and st["aot"]["enabled"]

    dbg = d2.templates_debug()
    ev = dbg["templates"]["K8sRequiredLabels"]["compile"]
    assert ev and ev[-1]["source"] == "aot" and \
        ev[-1]["outcome"] == "ok" and "bucket_key" in ev[-1]


def test_aot_warm_boot_adopts_sweep_sigs(tmp_path, fresh_xla_cache):
    """With async compilation ON, a warm boot's ingest-time prewarm
    must deserialize the stored programs AND adopt the recorded sweep
    signatures, so the first sweep dispatches straight onto the device
    (no host-fallback round, no compile gate)."""
    aot_dir = str(tmp_path / "aot")
    d1 = _single_device_driver(aot_dir=aot_dir)
    c1 = _labels_client(d1, 2048, 5)
    got1 = _key(c1.audit().results())

    os.environ["GATEKEEPER_TPU_ASYNC_COMPILE"] = "1"
    try:
        d2 = _single_device_driver(aot_dir=aot_dir)
        assert d2.async_warm
        c2 = _labels_client(d2, 2048, 5)
    finally:
        os.environ["GATEKEEPER_TPU_ASYNC_COMPILE"] = "0"
    deadline = time.time() + 30
    while time.time() < deadline and not d2.warm_status()["warm"]:
        time.sleep(0.05)
    assert d2.warm_status()["warm"] >= 1, \
        "prewarm must mark stored sweep signatures warm before a sweep"
    base = _counts()
    got2 = _key(c2.audit().results())
    d = _delta(base, _counts())
    assert got1 == got2
    assert d["fresh"] == 0 and d["cache"] == 0
    assert d2._eval_counts.get(("K8sRequiredLabels", "device")), \
        "first sweep must dispatch on the device, not the host fallback"
    assert not d2._eval_counts.get(("K8sRequiredLabels", "interp"))


# the default chunk makes the 2048-row sweep one lazy chunk (run under
# the guard by _dispatch_guarded); 256 makes it a slab dispatch
@pytest.mark.parametrize("sweep_chunk", [8192, 256])
def test_adopted_sig_without_executable_serves_host_not_inline_compile(
        tmp_path, fresh_xla_cache, sweep_chunk):
    """A warm-boot-adopted sweep signature whose backing executable is
    gone (store GC'd, save refused on the previous boot) must NOT stall
    the serving path on an inline XLA compile: the sig is un-adopted,
    the host/interpreter answers this round, and the program re-warms
    in the background."""
    aot_dir = str(tmp_path / "aot")
    d1 = _single_device_driver(aot_dir=aot_dir)
    d1.sweep_chunk = sweep_chunk
    c1 = _labels_client(d1, 2048, 5)
    got1 = _key(c1.audit().results())
    assert d1.aot.programs_count() >= 1

    # simulate the executables vanishing while the manifest's sigs
    # survive (bounded-store eviction, manual cleanup, partial volume)
    for root, _dirs, files in os.walk(aot_dir):
        for fn in files:
            if fn.endswith(".aotx"):
                os.unlink(os.path.join(root, fn))

    os.environ["GATEKEEPER_TPU_ASYNC_COMPILE"] = "1"
    try:
        d2 = _single_device_driver(aot_dir=aot_dir)
        d2.sweep_chunk = sweep_chunk
        # pin the host model fast so the block-when-cheaper rule picks
        # the host fallback (the guard's outcome is then observable as
        # an interp eval instead of a waited-out background warm)
        d2._host_pair_rate = 1e9
        c2 = _labels_client(d2, 2048, 5)
        ct = d2.compiled_for("K8sRequiredLabels")
        # force the adoption a partially-loaded store would perform
        # (entries for the missing blobs were dropped at manifest load,
        # so the background prewarm alone would not adopt)
        d2._mark_stored_sigs_warm(ct.fingerprint, {"eval": 1})
        assert d2._warm_restored, "adoption precondition"
        got2 = _key(c2.audit().results())
    finally:
        os.environ["GATEKEEPER_TPU_ASYNC_COMPILE"] = "0"
    assert got2 == got1, "host fallback must still answer correctly"
    # the stale sig was un-adopted instead of inline-compiled: the
    # first audit served off the interpreter/host path while the
    # background thread re-warmed the program
    assert d2._eval_counts.get(("K8sRequiredLabels", "interp")), \
        "first audit must have served from the interpreter/host path"
    # background warm converges: a later audit runs on the device
    deadline = time.time() + 60
    while time.time() < deadline and d2.warm_status()["compiling"]:
        time.sleep(0.05)
    # a library edit (same C bucket) invalidates the results delta
    # cache, forcing a real re-sweep at the re-warmed shape; restore a
    # realistic host model so the cost model prefers the device again
    d2._host_pair_rate = 100.0
    _add_constraint(c2, 5)
    c2.audit()
    assert d2._eval_counts.get(("K8sRequiredLabels", "device")), \
        "re-warmed program must serve later audits on the device"


def test_aot_store_bounded_eviction_and_compaction(tmp_path,
                                                   fresh_xla_cache):
    """The store caps serialized programs (FIFO): oldest .aotx blobs
    are deleted, the manifest is compacted, and a reload sees only the
    survivors — a churn-heavy deployment can't fill the state volume."""
    import jax.numpy as jnp

    from gatekeeper_tpu.ir.aot import AotJit, AotStore

    # apply the fixture's fresh JAX_COMPILATION_CACHE_DIR to the live
    # jax config (no TpuDriver is constructed here to do it): compiles
    # must be genuinely fresh or save's round-trip probe refuses them
    enable_compile_cache()
    store = AotStore(str(tmp_path / "aot"))
    assert store.enabled
    store.max_programs = 2
    jit = AotJit(lambda x: jnp.sum(x) + 1, store=store,
                 fingerprint="fp-test", tag="t", kind="k")
    for n in (8, 16, 32):  # three distinct shapes -> three entries
        jit(np.zeros((n,), np.float32))
    assert store.programs_count() == 2, store.stats_snapshot()
    aotx = [f for f in os.listdir(store.dir) if f.endswith(".aotx")]
    assert len(aotx) == 2, "evicted blob must be deleted from disk"

    reloaded = AotStore(str(tmp_path / "aot"))
    assert reloaded.programs_count() == 2
    # survivors (the two NEWEST shapes) still deserialize
    loaded = 0
    for ent in reloaded.entries_for("fp-test"):
        key = reloaded.entry_key("fp-test", ent["tag"], ent["static"],
                                 ent["asig"])
        loaded += reloaded.load(key) is not None
    assert loaded == 2


def test_aot_store_survives_unusable_dir(tmp_path):
    """A file where the AOT dir should be: the store stays disabled and
    the driver serves normally (degrade, never break)."""
    bad = tmp_path / "occupied"
    bad.write_text("not a directory")
    drv = _single_device_driver(aot_dir=str(bad))
    assert not drv.aot.enabled
    client = _labels_client(drv, 256, 2)
    assert len(client.audit().results()) > 0


# ------------------------------------------------- compile cache gauge


def test_enable_compile_cache_reports_failure(tmp_path):
    """An unusable cache dir returns False (and is logged + gauged)
    instead of being silently swallowed; a usable one restores True."""
    import gatekeeper_tpu.ir.driver as drvmod

    occupied = tmp_path / "file"
    occupied.write_text("x")
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(occupied / "sub")
    drvmod._cache_warned = False
    try:
        assert enable_compile_cache() is False
    finally:
        if old is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = old
    assert enable_compile_cache() is True


# ------------------------------------------------- warm-cache prepack


def test_warm_cache_cli_prepacks_from_snapshots(tmp_path, capsys):
    """`gatekeeper-tpu warm-cache --state-dir D`: restores the
    vocab/library/inventory snapshots, compiles inline, and persists
    serialized programs into <state-dir>/aot — the image/volume
    prepack path."""
    import logging as _logging

    from gatekeeper_tpu.control.main import warm_cache_main
    from gatekeeper_tpu.control.statestore import StateStore

    drv = _single_device_driver()
    client = _labels_client(drv, 2048, 5)
    client.audit()
    state = str(tmp_path / "state")
    store = StateStore(state)
    store.save("vocab", drv.vocab_snapshot())
    store.save("library", client.snapshot_library())
    store.save_blob("inventory",
                    {"tree": drv.inventory_snapshot() or {},
                     "tracker": {}}, codec="marshal")

    # warm_cache_main is a CLI entrypoint: its glog.setup() flips the
    # "gatekeeper" logger to propagate=False, which would blind caplog
    # for every later in-process test — snapshot and restore
    gklog = _logging.getLogger("gatekeeper")
    saved = (gklog.handlers[:], gklog.propagate, gklog.level)
    try:
        rc = warm_cache_main(["--state-dir", state])
    finally:
        gklog.handlers[:], gklog.propagate, gklog.level = saved
    out = [ln for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("{")]
    assert rc == 0 and out
    summary = json.loads(out[-1])
    assert summary["restored"]["library"] and summary["objects"] == 2048
    assert summary["programs_stored"] >= 1
    assert os.path.isdir(os.path.join(state, "aot"))


@pytest.mark.parametrize("ncpu,limits,slots", [
    (224, (1.0, 2 << 30), 1),    # the chart's pod on a large host
    (224, (None, None), 4),      # no limits: capped
    (13, (None, 40 << 30), 4),   # the one-chip machine
    (4, (None, None), 2),
    (224, (8.0, 4 << 30), 2),    # memory binds before the CPU quota
])
def test_warm_compile_slots_follow_the_process_limits(monkeypatch, ncpu,
                                                      limits, slots):
    """Background compiles are sized from the CPUs and memory this
    process may use (affinity, cgroup quota and limit), not from the
    machine's core count."""
    from gatekeeper_tpu.ir import driver as drv_mod

    monkeypatch.setattr(drv_mod.os, "sched_getaffinity",
                        lambda _pid: set(range(ncpu)))
    monkeypatch.setattr(drv_mod, "_cgroup_limits", lambda: limits)
    assert drv_mod.warm_compile_slots() == slots
