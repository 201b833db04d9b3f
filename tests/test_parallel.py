"""Multi-device sharding tests (virtual 8-device CPU mesh via conftest).

Pin the distributed audit path that the driver's dryrun_multichip
exercises: shard_map + psum over a data×model mesh must agree
bit-for-bit with the single-device sweep — including uneven batch
padding, constraint (model-axis) sharding, and derived vocab columns
flowing through the shard_map as replicated operands.
"""

import jax
import numpy as np
import pytest

from gatekeeper_tpu.client import Backend
from gatekeeper_tpu.ir import TpuDriver
from gatekeeper_tpu.ir.features import extract_batch
from gatekeeper_tpu.ir.params import encode_params
from gatekeeper_tpu.parallel.collectives import make_audit_step
from gatekeeper_tpu.parallel.mesh import (
    make_mesh,
    pad_batch,
    shard_features,
    shard_params,
)
from gatekeeper_tpu.parallel.workload import build_eval_setup
from gatekeeper_tpu.target import K8sValidationTarget

TARGET = "admission.k8s.gatekeeper.sh"

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU platform")


def device_setup(template, constraints, objects):
    """Generic analog of workload.build_eval_setup for any template."""
    driver = TpuDriver()
    client = Backend(driver).new_client([K8sValidationTarget()])
    client.add_template(template)
    for c in constraints:
        client.add_constraint(c)
    kind = constraints[0]["kind"]
    ct = driver.compiled_for(kind)
    assert ct is not None, f"{kind} must device-compile"
    reviews = []
    for o in objects:
        r = {"kind": {"group": "", "version": o.get("apiVersion", "v1"),
                      "kind": o["kind"]},
             "name": o["metadata"]["name"], "object": o}
        if "namespace" in o["metadata"]:
            r["namespace"] = o["metadata"]["namespace"]
        reviews.append(r)
    feats, _, _ = extract_batch(ct.program, driver.strtab, reviews)
    cons = driver._constraints(TARGET)
    pd = [(c.get("spec") or {}).get("parameters") or {} for c in cons]
    params = encode_params(ct.program, pd, driver.strtab,
                           driver.match_tables)
    derived = driver._derived_arrays(kind, ct)
    table = driver.match_tables.materialize_packed()
    return ct, feats, params, table, derived


def run_sharded(ct, feats, params, table, derived, data, model,
                n_valid=None, shard_c=None):
    """n_valid: true object count (the extractor pow2-buckets N, so the
    feature dim may exceed it; rows >= n_valid are masked on device)."""
    mesh = make_mesh(devices=jax.devices()[: data * model], data=data,
                     model=model)
    feats, n_feat = pad_batch(feats, data)
    if n_valid is None:
        n_valid = n_feat
    feats = shard_features(feats, mesh)
    params = shard_params(params, mesh,
                          shard_c=(model > 1 if shard_c is None
                                   else shard_c))
    step = make_audit_step(ct._eval, mesh)
    fires, counts = step(feats, params, table, derived, np.int32(n_valid))
    return np.asarray(fires)[:n_valid], np.asarray(counts)


def test_sharded_equals_single_device():
    _, ct, feats, params, table, derived, reviews, cons = build_eval_setup(
        n_objects=64, n_constraints=8, violate_frac=0.4)
    expected = ct.fires(feats, params, table, derived)
    fires, counts = run_sharded(ct, feats, params, table, derived,
                                data=8, model=1)
    assert (fires == expected).all()
    assert (counts == expected.sum(axis=0)).all()
    assert counts.sum() > 0


def test_uneven_batch_padding_masked():
    """N not divisible by the data axis: padding rows would fire absence
    clauses (empty objects have no labels) — n_valid masking must keep
    them out of both verdicts and psum'd counts."""
    _, ct, feats, params, table, derived, reviews, cons = build_eval_setup(
        n_objects=53, n_constraints=4, violate_frac=0.5)
    expected = ct.fires(feats, params, table, derived)[:53]
    fires, counts = run_sharded(ct, feats, params, table, derived,
                                data=8, model=1, n_valid=53)
    assert fires.shape == (53, len(cons))
    assert (fires == expected).all()
    assert (counts == expected.sum(axis=0)).all()
    assert counts.sum() > 0


def test_model_axis_constraint_sharding():
    """C sharded over the model axis (4x2 mesh): parameter tensors split
    across devices, verdict columns reassembled, counts replicated."""
    _, ct, feats, params, table, derived, reviews, cons = build_eval_setup(
        n_objects=32, n_constraints=6, violate_frac=0.5)
    expected = ct.fires(feats, params, table, derived)
    fires, counts = run_sharded(ct, feats, params, table, derived,
                                data=4, model=2)
    assert (fires == expected).all()
    assert (counts == expected.sum(axis=0)).all()
    assert counts.sum() > 0


def test_derived_columns_through_shard_map():
    """A to_number-derived vocab column (host-precomputed lookup table)
    must flow through shard_map as a replicated operand and agree with
    the single-device sweep."""
    template = {
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "k8smaxreplicas"},
        "spec": {
            "crd": {"spec": {"names": {"kind": "K8sMaxReplicas"}}},
            "targets": [{"target": TARGET, "rego": """
package k8smaxreplicas
violation[{"msg": "too many replicas"}] {
  to_number(input.review.object.metadata.labels.replicas) > input.parameters.max
}
"""}],
        },
    }
    constraints = [{
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sMaxReplicas", "metadata": {"name": f"c{i}"},
        "spec": {"parameters": {"max": m}},
    } for i, m in enumerate([2, 5, 7])]
    objects = [{"apiVersion": "v1", "kind": "Namespace",
                "metadata": {"name": f"n{i}",
                             "labels": {"replicas": str(i)}}}
               for i in range(24)]
    ct, feats, params, table, derived = device_setup(template, constraints,
                                                     objects)
    assert derived, "template must actually produce a derived column"
    expected = ct.fires(feats, params, table, derived)[:24]
    fires, counts = run_sharded(ct, feats, params, table, derived,
                                data=8, model=1, n_valid=24)
    assert (fires == expected).all()
    assert (counts == expected.sum(axis=0)).all()
    assert counts.sum() > 0
    # sanity vs ground truth: replicas i violates max m iff i > m
    want = np.array([[i > m for m in [2, 5, 7]] for i in range(24)])
    assert (expected == want).all()


def test_make_mesh_validates_factorization():
    with pytest.raises(ValueError):
        make_mesh(devices=jax.devices()[:6], data=4, model=2)
    mesh = make_mesh(devices=jax.devices()[:8], model=2)
    assert dict(mesh.shape) == {"data": 4, "model": 2}


def _mesh_driver(n_min=64):
    """TpuDriver with the production mesh path forced on: low review
    threshold and a pinned device-latency EMA so the adaptive cost model
    cannot route the sweep back to the host mid-test."""
    drv = TpuDriver()
    assert drv._mesh is not None, "8-device platform must yield a mesh"
    drv.MESH_MIN_REVIEWS = n_min
    drv._dev_batch_lat_s = 1e-4
    return drv


def _labels_workload(client, n):
    from gatekeeper_tpu import policies

    client.add_template(policies.load("general/requiredlabels"))
    client.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sRequiredLabels", "metadata": {"name": "need-owner"},
        "spec": {"parameters": {"labels": [{"key": "owner"}]}}})
    for i in range(n):
        o = {"apiVersion": "v1", "kind": "Namespace",
             "metadata": {"name": f"ns{i:05d}"}}
        if i % 3 == 0:
            o["metadata"]["labels"] = {"owner": "me"}
        client.add_data(o)


def _audit_key(results):
    return sorted((r.msg, (r.resource or {}).get("metadata", {})
                   .get("name", "")) for r in results)


def test_driver_mesh_audit_equals_single_device():
    """The PRODUCTION audit path sharded over the mesh (TpuDriver with
    >1 device, through client.audit()) must equal the single-device
    TpuDriver and the interpreter driver exactly — and must actually
    take the mesh path (asserted via last_audit_path, so this cannot
    go vacuous)."""
    from gatekeeper_tpu.client import RegoDriver

    N = 2048
    dm = _mesh_driver()
    cm = Backend(dm).new_client([K8sValidationTarget()])
    _labels_workload(cm, N)
    got_mesh = _audit_key(cm.audit().results())
    assert dm.last_audit_path == "mesh(data=8)", dm.last_audit_path

    ds = TpuDriver()
    ds._mesh = None
    ds._dev_batch_lat_s = 1e-4
    cs = Backend(ds).new_client([K8sValidationTarget()])
    _labels_workload(cs, N)
    got_single = _audit_key(cs.audit().results())
    assert ds.last_audit_path == "single"

    ci = Backend(RegoDriver()).new_client([K8sValidationTarget()])
    _labels_workload(ci, N)
    got_interp = _audit_key(ci.audit().results())

    assert got_mesh == got_single == got_interp
    assert len(got_mesh) == N - (N + 2) // 3, "non-vacuous"

    # steady state re-audit: nothing changed, so the results delta cache
    # answers without re-dispatching the sweep
    assert _audit_key(cm.audit().results()) == got_mesh
    assert dm.last_audit_path == "delta(1/1)", dm.last_audit_path

    # single-object churn via DELETE: the journal breaks, the delta
    # cache is bypassed, and the full mesh sweep must run again over
    # rebuilt sharded buffers
    for c in (cm, cs):
        c.remove_data({"apiVersion": "v1", "kind": "Namespace",
                       "metadata": {"name": "ns00001"}})
    got_mesh2 = _audit_key(cm.audit().results())
    assert dm.last_audit_path == "mesh(data=8)"
    assert got_mesh2 == _audit_key(cs.audit().results())
    assert len(got_mesh2) == len(got_mesh) - 1


def test_driver_mesh_gather_capacity_retry(monkeypatch):
    """Every object violating: the per-shard firing-row gather must
    overflow its initial capacity and re-run at a larger one without
    losing rows."""
    from gatekeeper_tpu.ir import evaljax

    monkeypatch.setattr(evaljax, "ALL_PAIRS", 0)  # capacity-bound slabs
    dm = _mesh_driver()
    cm = Backend(dm).new_client([K8sValidationTarget()])
    from gatekeeper_tpu import policies

    cm.add_template(policies.load("general/requiredlabels"))
    cm.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sRequiredLabels", "metadata": {"name": "need-owner"},
        "spec": {"parameters": {"labels": [{"key": "owner"}]}}})
    N = 4096  # 512 firing rows per shard > the 256 initial capacity
    for i in range(N):
        cm.add_data({"apiVersion": "v1", "kind": "Namespace",
                     "metadata": {"name": f"ns{i:05d}"}})
    out = cm.audit().results()
    assert dm.last_audit_path == "mesh(data=8)"
    assert len(out) == N, f"{len(out)} != {N} (rows lost in retry?)"
    ct = dm.compiled_for("K8sRequiredLabels")
    assert ct._pcap[True] >= 512


def test_driver_mesh_gather_capacity_ratchets(monkeypatch):
    """Alternating small/large mesh sweeps: the per-shard gather
    capacity must RATCHET (like the single-device slab path) instead of
    resetting to each sweep's count — a shrink must not make the next
    grow re-trip the overflow re-run."""
    from gatekeeper_tpu.ir import evaljax

    monkeypatch.setattr(evaljax, "ALL_PAIRS", 0)  # capacity-bound slabs
    dm = _mesh_driver()
    cm = Backend(dm).new_client([K8sValidationTarget()])
    from gatekeeper_tpu import policies

    cm.add_template(policies.load("general/requiredlabels"))
    cm.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sRequiredLabels", "metadata": {"name": "need-owner"},
        "spec": {"parameters": {"labels": [{"key": "owner"}]}}})
    N = 4096  # 512 firing rows per shard > the 256 initial capacity
    for i in range(N):
        cm.add_data({"apiVersion": "v1", "kind": "Namespace",
                     "metadata": {"name": f"ns{i:05d}"}})
    assert len(cm.audit().results()) == N
    assert dm.last_audit_path == "mesh(data=8)"
    ct = dm.compiled_for("K8sRequiredLabels")
    cap_grown = ct._pcap[True]
    assert cap_grown >= 512

    def relabel(owner: bool):
        # in-place churn (same N, same buckets) so every sweep stays on
        # the mesh path with identical tensor shapes
        for i in range(N):
            o = {"apiVersion": "v1", "kind": "Namespace",
                 "metadata": {"name": f"ns{i:05d}"}}
            if owner:
                o["metadata"]["labels"] = {"owner": "me"}
            cm.add_data(o)
        dm._audit_results_cache.clear()  # force the device sweep
        dm._dev_batch_lat_s = 1e-4  # re-pin: the consume path's real
        # CPU latency sample would route the next sweep to the host

    relabel(owner=True)  # shrink: ~0 firing rows
    assert cm.audit().results() == []
    assert dm.last_audit_path == "mesh(data=8)"
    assert ct._pcap[True] >= cap_grown, \
        "gather capacity shrank after a small sweep"

    relabel(owner=False)  # grow again: 512 firing rows per shard
    jit_calls = []
    orig = ct._sweep_jit

    def counting(*a, **k):
        jit_calls.append(a)
        return orig(*a, **k)

    ct._sweep_jit = counting
    out = cm.audit().results()
    ct._sweep_jit = orig
    assert dm.last_audit_path == "mesh(data=8)"
    assert len(out) == N
    # dispatch resolves the jit exactly once; with the pre-ratchet reset
    # the overflow retry loop would resolve it a second time mid-consume
    assert len(jit_calls) == 1, \
        f"overflow re-run re-triggered: {len(jit_calls)} jit lookups"


def test_driver_mesh_respects_min_reviews():
    """Below the mesh threshold the driver stays single-device."""
    dm = _mesh_driver(n_min=1 << 30)
    dm._dev_batch_lat_s = 1e-4
    cm = Backend(dm).new_client([K8sValidationTarget()])
    _labels_workload(cm, 2048)
    out = cm.audit().results()
    assert dm.last_audit_path == "single"
    assert len(out) == 2048 - (2048 + 2) // 3


def test_sharded_inventory_join_membership():
    """The inventory-join membership kernel (ir/join.py: searchsorted
    over the unique-key table with count/identity rules) sharded over
    the mesh's data axis must agree with the single-device answer —
    review keys shard across chips; the key table rides replicated."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from gatekeeper_tpu.ir.join import IK_MULTI, IK_REV_MISSING, KEY_PAD
    from gatekeeper_tpu.parallel.mesh import make_mesh

    try:
        from jax import shard_map as _shard_map
        shard_map = _shard_map.shard_map if hasattr(
            _shard_map, "shard_map") else _shard_map
    except ImportError:
        from jax.experimental.shard_map import shard_map

    rng = np.random.default_rng(0)
    kb, n, h = 256, 64, 4
    u = np.sort(rng.choice(10_000, size=200, replace=False)).astype(
        np.int32)
    u_p = np.full(kb, np.iinfo(np.int32).max, dtype=np.int32)
    u_p[: len(u)] = u
    cnt_p = np.zeros(kb, dtype=np.int32)
    cnt_p[: len(u)] = rng.integers(1, 3, size=len(u))
    sik_p = np.full(kb, IK_MULTI, dtype=np.int32)
    single = cnt_p[: len(u)] == 1
    sik_p[: len(u)][single] = rng.integers(100, 110,
                                           size=int(single.sum()))
    karr = np.where(rng.random((n, h)) < 0.25,
                    rng.choice(u, size=(n, h)),
                    KEY_PAD).astype(np.int32)
    iks = rng.integers(100, 110, size=n).astype(np.int32)

    def kernel(u_p, cnt_p, sik_p, karr, iks):
        pos = jnp.clip(jnp.searchsorted(u_p, karr), 0, u_p.shape[0] - 1)
        found = (u_p[pos] == karr) & (karr != KEY_PAD)
        fire = found & ((cnt_p[pos] >= 2) | (sik_p[pos] != iks[:, None]))
        return jnp.any(fire, axis=1)

    want = np.asarray(jax.jit(kernel)(u_p, cnt_p, sik_p, karr, iks))

    mesh = make_mesh(devices=jax.devices()[:8], data=8, model=1)
    sharded = jax.jit(shard_map(
        kernel, mesh=mesh,
        in_specs=(P(), P(), P(), P("data", None), P("data")),
        out_specs=P("data")))
    got = np.asarray(sharded(u_p, cnt_p, sik_p, karr, iks))
    assert (got == want).all()
    assert want.any() and not want.all(), "non-vacuous membership split"


def test_driver_mesh_slab_loop_equals_mono(monkeypatch):
    """The double-buffered mesh SLAB loop (per-shard materialization
    overlapping the next slab's device sweep) must produce exactly the
    single-device sweep's results, in the same global row-major
    order, across multiple slabs per shard — including a pair
    capacity overflow inside one slab."""
    from gatekeeper_tpu.ir import evaljax

    N = 16384
    monkeypatch.setattr(evaljax, "CHUNK", 256)  # n_loc 2048: 8 slabs
    monkeypatch.setattr(evaljax, "ALL_PAIRS", 0)  # capacity-bound slabs
    dm = _mesh_driver()
    cm = Backend(dm).new_client([K8sValidationTarget()])
    _labels_workload(cm, N)
    # ~170 firing rows in a 256-row slab of a shard: overflows 64
    dm.compiled_for("K8sRequiredLabels")._pcap[True] = 64
    handles = []
    orig = dm._dispatch_handle

    def spy(*a, **k):
        h = orig(*a, **k)
        handles.append(h)
        return h

    dm._dispatch_handle = spy
    got = cm.audit().results()
    dm._dispatch_handle = orig
    assert dm.last_audit_path == "mesh(data=8)", dm.last_audit_path
    assert any(h._mesh is not None and h._n_slabs > 1
               for h in handles), "audit did not take the slab loop"
    assert dm.compiled_for("K8sRequiredLabels")._pcap[True] > 64, \
        "no slab overflowed its pair capacity"

    ds = TpuDriver()
    ds._mesh = None
    ds._dev_batch_lat_s = 1e-4
    cs = Backend(ds).new_client([K8sValidationTarget()])
    _labels_workload(cs, N)
    want = cs.audit().results()
    # exact order parity, not just set equality: the slab loop's blocks
    # interleave shards, and the consume loop must reassemble global
    # row-major order
    assert [(r.msg, (r.resource or {}).get("metadata", {}).get("name"))
            for r in got] == \
        [(r.msg, (r.resource or {}).get("metadata", {}).get("name"))
         for r in want]
    assert len(got) == N - (N + 2) // 3, "non-vacuous"


def test_mesh_slab_dispatch_direct_overflow_and_order(monkeypatch):
    """A mesh sweep with a forced small slab: every object firing
    overflows an 8-per-shard pair capacity inside each slab; the retry
    must lose no rows and the capacity must ratchet."""
    from gatekeeper_tpu.ir import evaljax

    monkeypatch.setattr(evaljax, "CHUNK", 64)  # n_loc=512 -> 8 slabs
    monkeypatch.setattr(evaljax, "ALL_PAIRS", 0)  # capacity-bound slabs
    driver, ct, feats, params, table, derived, reviews, cons = \
        build_eval_setup(n_objects=4096, n_constraints=1,
                         violate_frac=1.0)
    mesh = make_mesh(devices=jax.devices()[:8], data=8, model=1)
    n_feat = next(iter(next(iter(feats.values())).values())).shape[0]
    assert n_feat % 8 == 0
    ct._pcap[True] = 8  # force the per-slab overflow retry
    handle = ct.fires_pairs_dispatch(feats, params, table, derived,
                                     n_true=len(reviews), mesh=mesh)
    assert handle._n_slabs == 8
    rows = np.concatenate([r for _s, r, _c in handle.pairs()])
    expected = ct.fires(feats, params, table, derived)[: len(reviews)]
    want_rows = np.flatnonzero(expected.any(axis=1))
    assert sorted(rows.tolist()) == want_rows.tolist()
    assert len(want_rows) > 64, "non-vacuous overflow workload"
    assert ct._pcap[True] > 8, "pair capacity did not ratchet"


# rows are padded to n_bucket[shards]; 3,008 rows on one device make
# the second slab's clamped start overlap the first by 64 rows
@pytest.mark.parametrize("n,n_bucket,slabs", [
    (40, {1: 64, 4: 64}, {1: 1, 4: 1}),          # below one chunk
    (200, {1: 256, 4: 256}, {1: 1, 4: 1}),       # a chunk to one slab
    (3000, {1: 3008, 4: 4096}, {1: 2, 4: 8}),    # several slabs
], ids=["below-a-chunk", "one-slab", "several-slabs"])
@pytest.mark.parametrize("shards", [1, 4], ids=["one-device", "mesh4"])
def test_pair_sweep_equals_dense_verdicts(monkeypatch, n, n_bucket, slabs,
                                          shards):
    """fires_pairs_dispatch against the dense fires_chunked verdicts,
    exactly: extraction padding rows masked, C padding columns masked,
    slab overlap emitted once, and every pair kept through a forced
    capacity overflow, on one device and on the 4-device CPU mesh."""
    from gatekeeper_tpu.ir import evaljax

    monkeypatch.setattr(evaljax, "CHUNK", 64)
    monkeypatch.setattr(evaljax, "ALL_PAIRS", 0)  # capacity-bound slabs
    driver, ct, feats, params, table, derived, reviews, cons = \
        build_eval_setup(n, 3, n_bucket=n_bucket[shards], violate_frac=0.5)
    mesh = None if shards == 1 else \
        make_mesh(devices=jax.devices()[:4], data=4, model=1)
    want = np.nonzero(ct.fires_chunked(feats, params, table, derived)[:n])
    ct._pcap[mesh is not None] = 1  # overflows where a slab fires twice
    handle = ct.fires_pairs_dispatch(feats, params, table, derived,
                                     n_true=n, mesh=mesh)
    assert handle._n_slabs == slabs[shards]
    blocks = list(handle.pairs())
    assert {b[0] for b in blocks} == \
        ({None} if mesh is None else set(range(shards)))
    # a mesh's blocks come (slab, shard) ordered: reassemble by first row
    blocks.sort(key=lambda b: b[1][0] if len(b[1]) else -1)
    rows = np.concatenate([b[1] for b in blocks])
    cols = np.concatenate([b[2] for b in blocks])
    assert (rows == want[0]).all() and (cols == want[1]).all()
    assert len(rows) == len(want[0]) > 1, "non-vacuous overflow workload"
    assert ct._pcap[mesh is not None] > 1, "capacity did not ratchet"


def test_review_batch_sparse_mesh_equals_interpreter():
    """Discovery-mode audits stage the whole cluster through
    review_batch: at audit scale it must route through the sparse
    gather (mesh-sharded here) and agree exactly with the interpreter
    driver."""
    from gatekeeper_tpu.client import RegoDriver

    N = 2048
    dm = _mesh_driver()
    dm.SPARSE_BATCH_MIN = 256
    dm.async_warm = False
    cm = Backend(dm).new_client([K8sValidationTarget()])
    _labels_workload(cm, 0)  # template + constraint only

    ri = Backend(RegoDriver()).new_client([K8sValidationTarget()])
    _labels_workload(ri, 0)

    def reviews():
        out = []
        for i in range(N):
            o = {"apiVersion": "v1", "kind": "Namespace",
                 "metadata": {"name": f"ns{i:05d}"}}
            if i % 3 == 0:
                o["metadata"]["labels"] = {"owner": "me"}
            out.append({"kind": {"group": "", "version": "v1",
                                 "kind": "Namespace"},
                        "name": o["metadata"]["name"], "object": o})
        return out

    got = dm.review_batch(TARGET, reviews())
    want = [ri.driver.query(("hooks", TARGET, "violation"),
                            {"review": r}).results
            for r in reviews()]
    assert [sorted(r.msg for r in per) for per in got] == \
        [sorted(r.msg for r in per) for per in want]
    n_fired = sum(1 for per in got if per)
    assert n_fired == N - (N + 2) // 3, "non-vacuous"


# ------------------------------------------------- mesh edge conditions


def test_pad_batch_non_divisible_counts():
    """pad_batch must zero-pad every [N, ...] leaf up to the next
    multiple of the data axis and report the TRUE row count."""
    feats = {"slot": {"a": np.arange(10, dtype=np.int32),
                      "b": np.ones((10, 3), dtype=np.float32)}}
    out, n_true = pad_batch(feats, 8)
    assert n_true == 10
    assert out["slot"]["a"].shape == (16,)
    assert out["slot"]["b"].shape == (16, 3)
    assert (out["slot"]["a"][:10] == np.arange(10)).all()
    assert (out["slot"]["a"][10:] == 0).all()
    assert (out["slot"]["b"][10:] == 0).all()
    # already divisible: returned arrays are unpadded
    out2, n2 = pad_batch(feats, 5)
    assert n2 == 10 and out2["slot"]["a"].shape == (10,)


def test_build_mesh_rounds_down_to_power_of_two(monkeypatch, caplog):
    """6 visible devices must shard over 4 (with a warning), not
    silently never take the mesh path — the divisibility gate checks
    power-of-two extraction buckets against the data axis."""
    monkeypatch.setenv("GATEKEEPER_TPU_MESH", "6")
    import logging

    with caplog.at_level(logging.WARNING,
                         logger="gatekeeper_tpu.ir.driver"):
        drv = TpuDriver()
    assert drv._mesh is not None
    assert dict(drv._mesh.shape) == {"data": 4, "model": 1}
    assert any("rounded down" in r.message for r in caplog.records)


def test_build_mesh_off_and_capped(monkeypatch):
    monkeypatch.setenv("GATEKEEPER_TPU_MESH", "off")
    assert TpuDriver()._mesh is None
    monkeypatch.setenv("GATEKEEPER_TPU_MESH", "2")
    assert dict(TpuDriver()._mesh.shape) == {"data": 2, "model": 1}
    monkeypatch.setenv("GATEKEEPER_TPU_MESH", "1")
    assert TpuDriver()._mesh is None  # one device is not a mesh


def test_shard_and_replicate_specs_on_host_mesh():
    """Placement spec correctness on the 8-device host-platform mesh:
    features split on "data" along the leading axis, params replicated
    by default (sharded over "model" when asked), scalars replicated."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(devices=jax.devices()[:8], data=4, model=2)
    feats = {"s": {"a": np.zeros((16, 5), np.int32),
                   "v": np.zeros(16, np.int32)}}
    params = {"s": {"p": np.zeros((8, 3), np.int32)}}
    sf = shard_features(feats, mesh)
    assert sf["s"]["a"].sharding.spec == P("data", None)
    assert sf["s"]["v"].sharding.spec == P("data")
    sp = shard_params(params, mesh)
    assert sp["s"]["p"].sharding.spec == P(None, None)
    sp_c = shard_params(params, mesh, shard_c=True)
    assert sp_c["s"]["p"].sharding.spec == P("model", None)
    from gatekeeper_tpu.parallel.mesh import replicate

    r = replicate(np.float32(3.0), mesh)
    assert r.sharding.spec == P()
    # the placements actually address every device in the mesh
    assert len(sf["s"]["a"].sharding.device_set) == 8


def test_dev_mesh_cache_lru_bounded():
    """TpuDriver._dev_mesh_cache must not grow without bound on a
    churn-heavy audit: live host arrays past DEV_MESH_CACHE_MAX are
    LRU-evicted, and a hit refreshes recency."""
    drv = _mesh_driver()
    drv.DEV_MESH_CACHE_MAX = 8
    keep = [np.full((16,), i, np.int32) for i in range(12)]  # pin alive
    first = keep[0]
    drv._dev_mesh({"s": {"a": first}}, data_leading=True)
    assert (id(first), True) in drv._dev_mesh_cache
    for a in keep[1:8]:
        drv._dev_mesh({"s": {"a": a}}, data_leading=True)
        # touch the first entry so it stays most-recent
        drv._dev_mesh({"s": {"a": first}}, data_leading=True)
    assert len(drv._dev_mesh_cache) == 8
    for a in keep[8:]:
        drv._dev_mesh({"s": {"a": a}}, data_leading=True)
    assert len(drv._dev_mesh_cache) == drv.DEV_MESH_CACHE_MAX
    # the repeatedly-touched entry survived; the single-use early ones
    # were evicted oldest-first
    assert (id(first), True) in drv._dev_mesh_cache
    assert (id(keep[1]), True) not in drv._dev_mesh_cache
    # a hit on a surviving entry still returns the resident buffer
    again = drv._dev_mesh({"s": {"a": first}}, data_leading=True)
    assert np.asarray(again["s"]["a"]).tolist() == first.tolist()
