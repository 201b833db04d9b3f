"""Regressions for compiler/driver defects found in review.

Each case pins an under-fire or crash scenario: duplicate-sensitive set
counts, count of set comprehensions, bare scalar guards, large-integer
equality, autoreject union semantics, and template-update cache staleness.
"""

import pytest

from gatekeeper_tpu.client import Backend, RegoDriver
from gatekeeper_tpu.ir import TpuDriver
from gatekeeper_tpu.target import AugmentedUnstructured, K8sValidationTarget


def mk(template_rego, kind="K8sTest", name=None):
    return {
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": name or kind.lower()},
        "spec": {
            "crd": {"spec": {"names": {"kind": kind}}},
            "targets": [{"target": "admission.k8s.gatekeeper.sh",
                         "rego": template_rego}],
        },
    }


def constraint(kind, name, params=None):
    return {
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": kind, "metadata": {"name": name},
        "spec": {"parameters": params or {}},
    }


def both_clients(template, constraints, objects):
    clients = []
    drivers = []
    for cls in (RegoDriver, TpuDriver):
        d = cls()
        c = Backend(d).new_client([K8sValidationTarget()])
        c.add_template(template)
        for con in constraints:
            c.add_constraint(con)
        for o in objects:
            c.add_data(o)
        drivers.append(d)
        clients.append(c)
    return drivers, clients


def names(results):
    return sorted(r.resource["metadata"]["name"] for r in results)


def test_dup_sensitive_count_never_underfires():
    """count(required - provided) == 1 with duplicated required values must
    not be compiled with a duplicate-counting sum."""
    rego = """
package k8stest
violation[{"msg": "exactly one missing"}] {
  required := {l | l := input.parameters.labels[_]}
  provided := {l | input.review.object.metadata.labels[l]}
  missing := required - provided
  count(missing) == 1
}
"""
    objs = [{"apiVersion": "v1", "kind": "Namespace",
             "metadata": {"name": "n0"}}]
    (rd, td), (rc, tc) = both_clients(
        mk(rego), [constraint("K8sTest", "c", {"labels": ["a", "a"]})], objs)
    assert names(rc.audit().results()) == names(tc.audit().results()) == ["n0"]


def test_count_of_set_comprehension():
    rego = """
package k8stest
violation[{"msg": "no labels"}] {
  provided := {l | input.review.object.metadata.labels[l]}
  count(provided) == 0
}
"""
    objs = [
        {"apiVersion": "v1", "kind": "Namespace", "metadata": {"name": "bare"}},
        {"apiVersion": "v1", "kind": "Namespace",
         "metadata": {"name": "labeled", "labels": {"x": "y"}}},
    ]
    (rd, td), (rc, tc) = both_clients(mk(rego), [constraint("K8sTest", "c")],
                                      objs)
    assert names(rc.audit().results()) == names(tc.audit().results()) == ["bare"]


def test_bare_scalar_guard():
    rego = """
package k8stest
violation[{"msg": "hit"}] {
  true
  input.review.object.metadata.name == "target"
}
"""
    objs = [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "target", "namespace": "d"}},
            {"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "other", "namespace": "d"}}]
    (rd, td), (rc, tc) = both_clients(mk(rego), [constraint("K8sTest", "c")],
                                      objs)
    assert names(rc.audit().results()) == names(tc.audit().results()) == \
        ["target"]


def test_large_integer_equality_exact():
    rego = """
package k8stest
violation[{"msg": "uid mismatch"}] {
  input.review.object.spec.uid != input.parameters.uid
}
"""
    objs = [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "big", "namespace": "d"},
             "spec": {"uid": 16777216}},
            {"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "same", "namespace": "d"},
             "spec": {"uid": 16777217}}]
    (rd, td), (rc, tc) = both_clients(
        mk(rego), [constraint("K8sTest", "c", {"uid": 16777217})], objs)
    assert names(rc.audit().results()) == names(tc.audit().results()) == \
        ["big"]


def test_autoreject_unions_with_matching():
    """For a Namespace-kind review, autoreject AND template violations both
    surface (reference hook rules 1+2 union) on every driver path."""
    rego = """
package k8stest
violation[{"msg": "always"}] { input.review.object.metadata.name }
"""
    con = {
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sTest", "metadata": {"name": "c"},
        "spec": {"match": {"namespaceSelector": {
            "matchLabels": {"team": "a"}}}},
    }
    ns = {"apiVersion": "v1", "kind": "Namespace",
          "metadata": {"name": "n", "labels": {"team": "a"}}}
    for cls in (RegoDriver, TpuDriver):
        d = cls()
        c = Backend(d).new_client([K8sValidationTarget()])
        c.add_template(mk(rego))
        c.add_constraint(con)
        msgs = sorted(r.msg for r in c.review(AugmentedUnstructured(ns)).results())
        assert msgs == ["Namespace is not cached in OPA.", "always"], \
            f"{cls.__name__}: {msgs}"


def test_template_update_invalidates_param_cache():
    """Updating a template's rego with unchanged constraints must re-encode
    parameters for the new program."""
    rego_a = """
package k8stest
violation[{"msg": "a"}] {
  c := input.review.object.spec.containers[_]
  startswith(c.image, input.parameters.prefix)
}
"""
    rego_b = """
package k8stest
violation[{"msg": "b"}] {
  input.review.object.metadata.name == input.parameters.name
}
"""
    d = TpuDriver()
    c = Backend(d).new_client([K8sValidationTarget()])
    c.add_template(mk(rego_a))
    c.add_constraint(constraint("K8sTest", "c",
                                {"prefix": "evil/", "name": "p2"}))
    c.add_data({"apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": "p1", "namespace": "d"},
                "spec": {"containers": [{"image": "evil/x"}]}})
    c.add_data({"apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": "p2", "namespace": "d"},
                "spec": {"containers": [{"image": "good/x"}]}})
    assert names(c.audit().results()) == ["p1"]
    c.add_template(mk(rego_b))
    assert names(c.audit().results()) == ["p2"]


def test_demotion_is_logged_and_counted(caplog):
    """A device-lowering failure must never be silent (VERDICT r2 weak #5:
    a bare-except demotion hid a broken lowering for a whole round). The
    fallback still answers correctly, but emits a warning log and bumps
    gatekeeper_tpu_device_demotions_total."""
    import logging

    from gatekeeper_tpu.control.metrics import REGISTRY
    from gatekeeper_tpu.ir.prog import DerivedSpec

    d = TpuDriver()
    c = Backend(d).new_client([K8sValidationTarget()])
    c.add_template(mk("""
package k8stest
violation[{"msg": "m"}] {
  input.review.object.metadata.name == input.parameters.name
}
"""))
    # corrupt the compiled program with a derived kind the driver cannot
    # lower (stands in for any future compile.py/driver.py drift)
    from dataclasses import replace
    prog = d._programs["K8sTest"]
    d._programs["K8sTest"] = replace(
        prog, derived=prog.derived + (DerivedSpec(99, "no-such-kind", "x"),))

    def metric() -> float:
        m = REGISTRY._metrics.get("gatekeeper_tpu_device_demotions_total")
        return sum(m.values.values()) if m else 0.0

    before = metric()
    with caplog.at_level(logging.WARNING, "gatekeeper_tpu.ir.driver"):
        assert d.compiled_for("K8sTest") is None
    assert metric() == before + 1
    assert any("demoted" in r.message and "K8sTest" in r.message
               for r in caplog.records)

    # the interpreter fallback still audits correctly
    c.add_constraint(constraint("K8sTest", "c", {"name": "p1"}))
    c.add_data({"apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": "p1", "namespace": "d"}})
    assert names(c.audit().results()) == ["p1"]


def test_trim_empty_cutset_is_identity():
    """Rego trim(s, "") strips nothing; the pattern-transform table must
    not fall back to Python's whitespace strip (ADVICE r2)."""
    rego = """
package k8stest
violation[{"msg": "prefix"}] {
  startswith(input.review.object.metadata.name, trim(input.parameters.p, ""))
}
"""
    objs = [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": " padded", "namespace": "d"}},
            {"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "padded", "namespace": "d"}}]
    (rd, td), (rc, tc) = both_clients(
        mk(rego), [constraint("K8sTest", "c", {"p": " pad"})], objs)
    assert names(rc.audit().results()) == names(tc.audit().results()) == \
        [" padded"]


def test_trim_cutset_containing_at_sign():
    """Transform args are escaped into the op@tag:arg encoding, so a
    cutset containing "@" must not corrupt tag parsing (ADVICE r2)."""
    rego = """
package k8stest
violation[{"msg": "prefix"}] {
  startswith(input.review.object.metadata.name, trim(input.parameters.p, "@"))
}
"""
    objs = [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "core-x", "namespace": "d"}},
            {"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "other", "namespace": "d"}}]
    (rd, td), (rc, tc) = both_clients(
        mk(rego), [constraint("K8sTest", "c", {"p": "@core@"})], objs)
    assert names(rc.audit().results()) == names(tc.audit().results()) == \
        ["core-x"]


def test_fires_pairs_matches_dense_with_padding_and_regather(monkeypatch):
    """The sparse device pair-extraction path must agree exactly with the
    dense verdict tensor — including extraction bucket padding (empty
    padding objects legitimately fire absence clauses and must be masked
    on device) and a deliberately undersized pair capacity (forces the
    count-miss re-run loop)."""
    import numpy as np
    from gatekeeper_tpu.ir import evaljax
    from gatekeeper_tpu.parallel.workload import build_eval_setup

    monkeypatch.setattr(evaljax, "CHUNK", 1024)
    n, c = 3000, 40
    driver, ct, feats, params, table, derived, reviews, cons = \
        build_eval_setup(n, c, n_bucket=4096, violate_frac=0.3)
    dense = ct.fires_chunked(feats, params, table, derived)
    want = np.nonzero(dense[:n])

    def pairs():
        blocks = list(ct.fires_pairs_dispatch(feats, params, table, derived,
                                              n_true=n).pairs())
        return (np.concatenate([b[1] for b in blocks]),
                np.concatenate([b[2] for b in blocks]))

    ct._pcap[False] = 16  # force at least one capacity re-run
    rows, cols = pairs()
    assert rows.shape == want[0].shape
    assert (rows == want[0]).all() and (cols == want[1]).all()
    assert ct._pcap[False] >= len(np.unique(rows))
    # steady state: second call reuses the remembered capacity
    rows2, cols2 = pairs()
    assert (rows2 == rows).all() and (cols2 == cols).all()


def test_audit_results_identical_across_drivers_after_pairs_path():
    """End-to-end: the TpuDriver audit (sparse pairs + codegen
    materialization) returns byte-identical results to the interpreter
    driver on a mixed violating/clean workload."""
    from gatekeeper_tpu.parallel.workload import (
        REQUIRED_LABELS_TEMPLATE, synth_constraints, synth_objects)

    objs = synth_objects(60, violate_frac=0.4, seed=3)
    constraints = synth_constraints(10, seed=4)
    (rd, td), (rc, tc) = both_clients(REQUIRED_LABELS_TEMPLATE, constraints,
                                      objs)
    a = [(r.resource["metadata"]["name"],
          r.constraint["metadata"]["name"], r.msg)
         for r in rc.audit().results()]
    b = [(r.resource["metadata"]["name"],
          r.constraint["metadata"]["name"], r.msg)
         for r in tc.audit().results()]
    assert sorted(a) == sorted(b) and len(a) > 0


def test_parameterless_template_fires_for_every_constraint():
    """A parameterless program's device verdicts are [N, 1]
    (constraint-independent); the sparse pairs path must expand firing
    rows to ALL constraints like the dense [N,1] & mask[N,C] broadcast
    did (r3 code-review finding: only cons[0] was materialized)."""
    rego = """
package k8stest
violation[{"msg": "no owner"}] {
  not input.review.object.metadata.labels.owner
}
"""
    objs = [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": f"p{i}", "namespace": "d"}}
            for i in range(40)]  # > MIN_DEVICE_BATCH and forces device path
    constraints = [constraint("K8sTest", "c1"), constraint("K8sTest", "c2")]
    (rd, td), (rc, tc) = both_clients(mk(rego), constraints, objs)
    a = sorted((r.resource["metadata"]["name"],
                r.constraint["metadata"]["name"])
               for r in rc.audit().results())
    b = sorted((r.resource["metadata"]["name"],
                r.constraint["metadata"]["name"])
               for r in tc.audit().results())
    assert a == b
    assert len(b) == 80  # every (object, constraint) pair


def test_vocab_stabilizes_across_audits():
    """Derived-column materialization must not intern new vocab entries
    forever (r3 finding: each audit re-derived the previous audit's
    outputs, growing the vocab 32 strings/audit, reshaping the match
    table, and forcing a full XLA recompile EVERY audit)."""
    from gatekeeper_tpu import policies

    d = TpuDriver()
    c = Backend(d).new_client([K8sValidationTarget()])
    c.add_template(policies.load("general/containerlimits"))
    c.add_constraint(constraint("K8sContainerLimits", "cl",
                                {"cpu": "2", "memory": "1Gi"}))
    for i in range(30):
        c.add_data({"apiVersion": "v1", "kind": "Pod",
                    "metadata": {"name": f"p{i}", "namespace": "d"},
                    "spec": {"containers": [{
                        "name": "m", "image": "img",
                        "resources": {"limits": {
                            "cpu": f"{100 + i}m",
                            "memory": f"{i + 1}Gi"}}}]}})
    sizes = []
    for _ in range(4):
        c.audit()
        sizes.append(len(d.strtab))
    # growth must stop (bounded chain depth), not continue per audit
    assert sizes[-1] == sizes[-2], sizes


def test_computed_key_bracket_compiles_and_matches():
    """m[<computed key>] (labels[spec.key]) desugars to iteration + key
    equality on the device path and must agree with the interpreter."""
    rego = """
package k8stest
violation[{"msg": "bad value"}] {
  spec := input.parameters.entries[_]
  val := input.review.object.metadata.labels[spec.key]
  val != spec.want
}
"""
    objs = [
        {"apiVersion": "v1", "kind": "Namespace",
         "metadata": {"name": "ok", "labels": {"env": "prod", "x": "y"}}},
        {"apiVersion": "v1", "kind": "Namespace",
         "metadata": {"name": "wrong", "labels": {"env": "dev"}}},
        {"apiVersion": "v1", "kind": "Namespace",
         "metadata": {"name": "absent", "labels": {"x": "y"}}},
    ]
    cons = [constraint("K8sTest", "c",
                       {"entries": [{"key": "env", "want": "prod"}]})]
    (rd, td), (rc, tc) = both_clients(mk(rego), cons, objs)
    assert td.compiled_for("K8sTest") is not None, \
        "computed-key bracket must device-compile"
    assert names(rc.audit().results()) == names(tc.audit().results()) == \
        ["wrong"]


def test_async_warm_serves_host_then_hot_swaps():
    """Async device compile: the first audit at a new sweep shape must
    return CORRECT results immediately from the host path while the
    device program warms in the background; once warm, the same audit
    takes the device path and agrees exactly."""
    import time

    from gatekeeper_tpu import policies
    from gatekeeper_tpu.client import Backend, RegoDriver
    from gatekeeper_tpu.ir import TpuDriver
    from gatekeeper_tpu.target import K8sValidationTarget

    def load(client, n=600):
        client.add_template(policies.load("general/requiredlabels"))
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "K8sRequiredLabels", "metadata": {"name": "owner"},
            "spec": {"parameters": {"labels": [{"key": "owner"}]}}})
        for i in range(n):
            o = {"apiVersion": "v1", "kind": "Namespace",
                 "metadata": {"name": f"ns{i:04d}"}}
            if i % 2 == 0:
                o["metadata"]["labels"] = {"owner": "me"}
            client.add_data(o)

    drv = TpuDriver()
    drv._mesh = None
    drv.async_warm = True
    drv._dev_batch_lat_s = 1e-4  # cost model would pick the device
    client = Backend(drv).new_client([K8sValidationTarget()])
    load(client)

    ref = Backend(RegoDriver()).new_client([K8sValidationTarget()])
    load(ref)
    want = sorted((r.msg, r.resource["metadata"]["name"])
                  for r in ref.audit().results())

    # first audit: host path (warm kicked off in the background)
    got1 = sorted((r.msg, r.resource["metadata"]["name"])
                  for r in client.audit().results())
    assert got1 == want and len(want) == 300
    st = drv.warm_status()
    assert st["warm"] + st["compiling"] >= 1, "no warm-up was started"

    # wait for the hot-swap, then the device path must serve and agree
    deadline = time.time() + 120
    while time.time() < deadline:
        if drv.warm_status()["warm"] >= 1:
            break
        time.sleep(0.05)
    assert drv.warm_status()["warm"] >= 1, "device program never warmed"
    # the results delta cache would (correctly) answer this unchanged
    # re-audit without dispatching; drop it so the test exercises the
    # post-warm DEVICE sweep it exists to pin
    drv._audit_results_cache.clear()
    got2 = sorted((r.msg, r.resource["metadata"]["name"])
                  for r in client.audit().results())
    assert got2 == want
    # non-vacuous: the device consume path updates the latency EMA,
    # proving the post-warm audit actually ran on the device
    assert drv._dev_batch_lat_s != 1e-4, "post-warm audit stayed on host"
