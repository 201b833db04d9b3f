"""Full-library device compilation: coverage, bit-equality, reason codes.

PR 10's contract: every shipped kind — both libraries, including the
cross-object join templates — evaluates through a device program, with
the interpreter demoted to a quarantine-only escape hatch. This suite
holds that with three instruments:

  * coverage: every shipped kind compiles (dense or join), and the
    checked-in `compiled_coverage.json` ratchet can only move up;
  * bit-equality: a library-wide differential sweep over a churned
    synthetic inventory — verdicts AND messages must equal the
    interpreter driver's for every kind, with the eval-path counters
    proving the device/join paths actually served;
  * reason codes: an interpreter-bound kind records a STABLE Uncompilable
    reason code (bounded metric label set, asserted on codes not prose)
    through driver state, /debug/templates, and the
    gatekeeper_tpu_compile_fallback_total metric.

The extended-form corpus (bench_configs.EXTENDED_FORM_TEMPLATES) pins
the newly compiled upstream-canonical shapes: param key-set
comprehensions, non-var comprehension heads, multi-literal filter
bodies, derived unary builtins, `some`-decl + 2-arg-identical joins,
and inline-generator joins. Conftest pins GATEKEEPER_TPU_ASYNC_COMPILE=0
so dispatch is deterministic (device programs compile inline — forced
device, no host-warming rounds).
"""

import copy
import json
import random
import re
from pathlib import Path

import pytest

import bench_configs
from gatekeeper_tpu import policies
from gatekeeper_tpu.client import Backend, RegoDriver
from gatekeeper_tpu.control.metrics import REGISTRY
from gatekeeper_tpu.ir import TpuDriver
from gatekeeper_tpu.ir.compile import REASON_CODES, Uncompilable
from gatekeeper_tpu.target import AugmentedUnstructured, K8sValidationTarget

LIBS = {
    "general": bench_configs.GENERAL_CONSTRAINTS,
    "pod-security-policy": bench_configs.PSP_CONSTRAINTS,
}


def mk_client(driver):
    return Backend(driver).new_client([K8sValidationTarget()])


def load_library(client, lib: str) -> list:
    kinds = []
    for name in policies.names():
        if name.startswith(lib + "/"):
            t = policies.load(name)
            client.add_template(t)
            kinds.append(t["spec"]["crd"]["spec"]["names"]["kind"])
    for kind, cname, params in LIBS[lib]:
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind, "metadata": {"name": cname},
            "spec": ({"parameters": params} if params else {})})
    return sorted(kinds)


def coverage_of(drv, kinds):
    device = [k for k in kinds
              if drv.compiled_for(k) is not None
              or drv.join_for(k) is not None]
    return {"device_compiled_kinds": len(device),
            "total_kinds": len(kinds),
            "interpreter_kinds": sorted(set(kinds) - set(device))}


def lib_objects(lib: str, n: int):
    if lib == "general":
        objs = bench_configs.synth_mixed_objects(n, seed=7)
    else:
        objs = bench_configs.synth_pods_psp(n, seed=7)
    return objs


def result_key(r):
    return (r.msg, r.constraint["metadata"]["name"],
            r.constraint["kind"],
            (r.resource or {}).get("metadata", {}).get("name"),
            r.enforcement_action)


def churn(objs, rng):
    """~3% replacements (field flips) + a couple of removals, applied
    identically to every driver under comparison."""
    replaced = []
    for i in rng.sample(range(len(objs)), max(2, len(objs) // 33)):
        o = copy.deepcopy(objs[i])
        o["metadata"].setdefault("labels", {})["churned"] = "yes"
        spec = o.get("spec", {})
        for c in spec.get("containers", []) or []:
            c["image"] = "docker.io/churned:latest"
        replaced.append(o)
    removed = [objs[i] for i in rng.sample(range(len(objs)), 2)]
    return replaced, removed


# ------------------------------------------------------------- coverage


@pytest.mark.parametrize("lib", sorted(LIBS))
def test_library_device_coverage(lib):
    """Every shipped kind compiles to a device program (dense or join);
    no fallback reason is recorded for any of them."""
    drv = TpuDriver()
    client = mk_client(drv)
    kinds = load_library(client, lib)
    cov = coverage_of(drv, kinds)
    assert cov["interpreter_kinds"] == [], \
        f"{lib}: interpreter-bound kinds {cov['interpreter_kinds']} " \
        f"(reasons: {drv.fallback_reasons()})"
    assert cov["device_compiled_kinds"] == cov["total_kinds"]
    assert drv.fallback_reasons() == {}


def test_coverage_ratchet():
    """compiled_coverage.json is a two-way ratchet: regressing a kind to
    the interpreter fails, and raising coverage must update the file in
    the same PR (so the recorded floor always matches reality)."""
    recorded = json.loads(
        (Path(__file__).resolve().parent.parent / "compiled_coverage.json")
        .read_text())
    for lib in sorted(LIBS):
        drv = TpuDriver()
        client = mk_client(drv)
        kinds = load_library(client, lib)
        cov = coverage_of(drv, kinds)
        want = recorded[lib]
        assert cov == want, (
            f"{lib}: device coverage moved — measured {cov}, ratchet "
            f"records {want}. A REGRESSION (kind newly on the "
            "interpreter) must be fixed; RAISED coverage must update "
            "compiled_coverage.json in this same PR.")


# ------------------------------------------------- differential sweeps


@pytest.mark.parametrize("lib,n", [("general", 360),
                                   ("pod-security-policy", 240)])
def test_library_differential_sweep(lib, n):
    """Library-wide bit-equality: audit the full library over a churned
    synthetic inventory with the device path forced, and compare every
    verdict AND message against the interpreter driver — including the
    join kinds. The eval-path counters must show no kind served from
    the interpreter fallback."""
    rng = random.Random(5)
    objs = lib_objects(lib, n)
    dev = TpuDriver()
    # force the device path: the cost model would otherwise keep a
    # test-sized sweep on the host codegen path (legitimate in
    # production, but this test exists to prove the DEVICE programs)
    dev._use_device_for_batch = lambda pairs: True
    drivers = {"interp": RegoDriver(), "device": dev}
    clients = {}
    for name, drv in drivers.items():
        client = mk_client(drv)
        kinds = load_library(client, lib)
        for o in objs:
            client.add_data(o)
        clients[name] = client

    def results(client):
        return sorted(result_key(r) for r in client.audit().results())

    first = {name: results(c) for name, c in clients.items()}
    assert first["interp"] == first["device"]
    assert first["interp"], f"{lib}: vacuous sweep (no violations)"

    # churn both inventories identically, re-audit, compare again (the
    # delta path and the join-table invalidation must stay bit-equal)
    replaced, removed = churn(objs, rng)
    for client in clients.values():
        for o in replaced:
            client.add_data(copy.deepcopy(o))
        for o in removed:
            client.remove_data(copy.deepcopy(o))
    second = {name: results(c) for name, c in clients.items()}
    assert second["interp"] == second["device"]

    # forced-device proof: no library kind ever served via the
    # interpreter fallback path
    interp_served = sorted({k for (k, p) in dev._eval_counts
                            if p == "interp" and k in kinds})
    assert interp_served == [], \
        f"{lib}: kinds served from the interpreter: {interp_served}"


XOBJS = [
    {"apiVersion": "v1", "kind": "Pod",
     "metadata": {"name": f"p{i}", "namespace": f"ns{i % 5}",
                  "labels": ({"owner": "a", "app": "b", "team": "c"}
                             if i % 4 else {"owner": "a"})},
     "spec": {"containers": [
         {"name": "main",
          "image": ("docker.io/evil7:latest" if i % 11 == 0 else
                    "Docker.IO/app:v1" if i % 7 == 0 else
                    "gcr.io/corp/app:v1"),
          **({} if i % 3 == 0 else
             {"securityContext": {"runAsNonRoot": i % 2 == 0}})}]}}
    for i in range(80)
] + [
    {"apiVersion": "networking.k8s.io/v1", "kind": "Ingress",
     "metadata": {"name": f"ing{i}", "namespace": f"ns{i % 3}",
                  "uid": f"uid-ing{i}"},
     "spec": {"rules": [{"host": f"h{i % 6}.example.com"}]}}
    for i in range(16)
] + [
    {"apiVersion": "v1", "kind": "Service",
     "metadata": {"name": f"svc{i}", "namespace": f"ns{i % 3}"},
     "spec": {"selector": {"app": f"app{i % 5}"}}}
    for i in range(12)
]

XREVIEWS = [
    {"apiVersion": "networking.k8s.io/v1", "kind": "Ingress",
     "metadata": {"name": "new", "namespace": "ns9"},
     "spec": {"rules": [{"host": "h0.example.com"}]}},
    {"apiVersion": "networking.k8s.io/v1", "kind": "Ingress",
     "metadata": {"name": "ing1", "namespace": "ns1", "uid": "uid-ing1"},
     "spec": {"rules": [{"host": "h-solo.example.com"}]}},
    {"apiVersion": "v1", "kind": "Service",
     "metadata": {"name": "svc1", "namespace": "ns1"},
     "spec": {"selector": {"app": "app1"}}},
    {"apiVersion": "v1", "kind": "Pod",
     "metadata": {"name": "naked", "namespace": "ns0",
                  "labels": {"owner": "a"}},
     "spec": {"containers": [{"name": "m",
                              "image": "docker.io/evil7:latest"}]}},
]


@pytest.mark.parametrize(
    "kind", [k for k, _, _ in bench_configs.EXTENDED_FORM_TEMPLATES])
def test_extended_form_differential(kind):
    """Each newly compiled upstream-canonical form is bit-equal to the
    interpreter across audit AND admission, and actually lands on the
    device (dense) or join path — not the interpreter fallback."""
    tmpl, params = next((t, p) for k, t, p
                        in bench_configs.EXTENDED_FORM_TEMPLATES
                        if k == kind)
    outs = {}
    for name, drv_cls in (("interp", RegoDriver), ("device", TpuDriver)):
        drv = drv_cls()
        client = mk_client(drv)
        client.add_template(tmpl)
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind, "metadata": {"name": "x"},
            "spec": ({"parameters": params} if params else {})})
        for o in XOBJS:
            client.add_data(copy.deepcopy(o))
        out = [sorted(result_key(r) for r in client.audit().results())]
        for rv in XREVIEWS:
            out.append(sorted(
                r.msg for r in client.review(
                    AugmentedUnstructured(copy.deepcopy(rv))).results()))
        outs[name] = out
        if drv_cls is TpuDriver:
            assert (drv.compiled_for(kind) is not None
                    or drv.join_for(kind) is not None), \
                f"{kind} interpreter-bound: {drv.fallback_reasons()}"
    assert outs["interp"] == outs["device"]
    assert any(any(x) for x in outs["interp"]), f"{kind}: vacuous scenario"


def test_multiclause_identity_differential():
    """An identity fn with TWO clauses (ns/name OR uid) — the exclusion
    must hold when EITHER clause identifies the review's own stored
    copy, on both the host probe and the device membership path."""
    rego = """
package xuniquehostmulti

identical(obj, review) {
  obj.metadata.namespace == review.object.metadata.namespace
  obj.metadata.name == review.object.metadata.name
}

identical(obj, review) {
  obj.metadata.uid == review.uid
}

violation[{"msg": msg}] {
  input.review.kind.kind == "Ingress"
  host := input.review.object.spec.rules[_].host
  other := data.inventory.namespace[ns][apiv]["Ingress"][name]
  other.spec.rules[_].host == host
  not identical(other, input.review)
  msg := sprintf("host conflict <%v>", [host])
}
"""
    tmpl = bench_configs._xtemplate("XUniqueHostMulti", rego)
    outs = {}
    for drv_cls in (RegoDriver, TpuDriver):
        drv = drv_cls()
        client = mk_client(drv)
        client.add_template(tmpl)
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "XUniqueHostMulti", "metadata": {"name": "m"},
            "spec": {}})
        for o in XOBJS:
            client.add_data(copy.deepcopy(o))
        out = [sorted(result_key(r) for r in client.audit().results())]
        # own copy via ns/name; own copy via uid only (renamed); true
        # conflict
        for rv in [
            {"apiVersion": "networking.k8s.io/v1", "kind": "Ingress",
             "metadata": {"name": "ing2", "namespace": "ns2",
                          "uid": "uid-ing2"},
             "spec": {"rules": [{"host": "solo-h.example.com"}]}},
            {"apiVersion": "networking.k8s.io/v1", "kind": "Ingress",
             "metadata": {"name": "renamed", "namespace": "nsX",
                          "uid": "uid-ing3"},
             "spec": {"rules": [{"host": "h3.example.com"}]}},
            {"apiVersion": "networking.k8s.io/v1", "kind": "Ingress",
             "metadata": {"name": "clash", "namespace": "nsY",
                          "uid": "uid-clash"},
             "spec": {"rules": [{"host": "h0.example.com"}]}},
        ]:
            res = client.review(AugmentedUnstructured(rv)).results()
            out.append(sorted(r.msg for r in res))
        outs[drv_cls.__name__] = out
        if drv_cls is TpuDriver:
            jc = drv.join_for("XUniqueHostMulti")
            assert jc is not None
            assert len(jc.prog.clauses[0].rev_ident) == 2
    assert outs["RegoDriver"] == outs["TpuDriver"]
    # the scenario must be non-vacuous in both directions
    assert outs["RegoDriver"][3], "true conflict must fire"


# -------------------------------------------------------------- reason codes


def test_fallback_reason_codes():
    """An interpreter-bound kind records a STABLE reason code — in
    fallback_reasons(), /debug/templates, and the bounded-label
    gatekeeper_tpu_compile_fallback_total metric."""
    drv = TpuDriver()
    client = mk_client(drv)
    # review-pure kind outside the subset: dense reason is actionable
    client.add_template(bench_configs._xtemplate("XUnsupportedCall", """
package xunsupportedcall

violation[{"msg": msg}] {
  x := object.get(input.review.object, "spec", {})
  x.hostNetwork
  msg := "no host network"
}
"""))
    # data-reading kind outside the join shape: join reason wins
    client.add_template(bench_configs._xtemplate("XNegatedGenerator", """
package xnegatedgenerator

violation[{"msg": msg}] {
  not data.inventory.cluster["v1"]["Namespace"][input.review.object.metadata.namespace]
  msg := "namespace not synced"
}
"""))
    reasons = drv.fallback_reasons()
    assert reasons["XUnsupportedCall"]["reason"] == "call"
    assert reasons["XUnsupportedCall"]["dense"]["code"] == "call"
    assert reasons["XNegatedGenerator"]["reason"] == "join-generator"
    for ent in reasons.values():
        assert ent["reason"] in REASON_CODES
        assert ent["dense"]["code"] in REASON_CODES
        if ent["join"] is not None:
            assert ent["join"]["code"] in REASON_CODES
    # /debug/templates carries the same record per kind
    debug = drv.templates_debug()["templates"]
    assert debug["XUnsupportedCall"]["state"] == "interpreter"
    assert debug["XUnsupportedCall"]["fallback"]["reason"] == "call"
    assert debug["XNegatedGenerator"]["fallback"]["join"]["code"] == \
        "join-generator"
    # device-compiled kinds carry no fallback record
    client.add_template(policies.load("general/httpsonly"))
    assert drv.templates_debug()["templates"]["K8sHttpsOnly"][
        "fallback"] is None
    # the metric labels on the bounded code set
    text = REGISTRY.render()
    rows = re.findall(
        r'gatekeeper_tpu_compile_fallback_total\{([^}]*)\} (\d+)', text)
    got = {}
    for labels, val in rows:
        kind = re.search(r'kind="([^"]*)"', labels).group(1)
        reason = re.search(r'reason="([^"]*)"', labels).group(1)
        got[kind] = reason
        assert reason in REASON_CODES
    assert got.get("XUnsupportedCall") == "call"
    assert got.get("XNegatedGenerator") == "join-generator"


def test_multiline_raise_sites_carry_real_codes():
    """The two historically multi-line raise sites must report their
    dedicated codes, not the 'internal' drift guard: a parameterized
    join template → join-input, a non-emptiness set count → count."""
    drv = TpuDriver()
    client = mk_client(drv)
    client.add_template(bench_configs._xtemplate("XParamJoin", """
package xparamjoin

violation[{"msg": msg}] {
  input.parameters.enabled == true
  other := data.inventory.namespace[ns][apiv]["Ingress"][name]
  other.spec.rules[_].host == input.review.object.spec.rules[_].host
  msg := "conflict"
}
"""))
    client.add_template(bench_configs._xtemplate("XNonEmptyCount", """
package xnonemptycount

violation[{"msg": msg}] {
  provided := {k | input.review.object.metadata.labels[k]}
  count(provided) > 1
  msg := "too many labels"
}
"""))
    reasons = drv.fallback_reasons()
    assert reasons["XParamJoin"]["reason"] == "join-input"
    assert reasons["XParamJoin"]["join"]["code"] == "join-input"
    assert reasons["XNonEmptyCount"]["reason"] == "count"
    assert reasons["XNonEmptyCount"]["dense"]["code"] == "count"
    assert not any(e["reason"] == "internal" for e in reasons.values())


def test_unknown_reason_code_folds_to_internal():
    """Reason-code drift (a raise site with a stray code) must not widen
    the metric label set — it folds into the stable 'internal' code."""
    e = Uncompilable("no-such-code", "something odd")
    assert e.code == "internal"
    assert "no-such-code" in e.detail
    e2 = Uncompilable("guard", "prose")
    assert e2.code == "guard" and str(e2) == "guard: prose"


def test_template_update_clears_fallback():
    """Re-ingesting a kind with a now-compilable body drops its
    fallback record (and the debug state flips to compiled)."""
    drv = TpuDriver()
    client = mk_client(drv)
    bad = bench_configs._xtemplate("XFlips", """
package xflips

violation[{"msg": msg}] {
  x := object.get(input.review.object, "spec", {})
  x.bad
  msg := "bad"
}
""")
    client.add_template(bad)
    assert "XFlips" in drv.fallback_reasons()
    good = bench_configs._xtemplate("XFlips", """
package xflips

violation[{"msg": msg}] {
  input.review.object.spec.bad == true
  msg := "bad"
}
""")
    client.add_template(good)
    assert "XFlips" not in drv.fallback_reasons()
    assert drv.compiled_for("XFlips") is not None


# ------------------------------------------------- match-table widening


def test_match_table_vectorized_rows_bit_equal():
    """The numpy-vectorized string-family row construction must be
    bit-equal to the per-string host path (which remains the fallback
    for oversize-string windows)."""
    import numpy as np

    from gatekeeper_tpu.ops.strtab import MatchTables, StringTable

    def build(vector: bool):
        t = StringTable()
        m = MatchTables(t)
        if not vector:
            m.MAX_VECTOR_STRLEN = 0  # force the per-string path
        for i in range(4000):
            t.intern(f"reg-{i % 37}.example.com/app-{i}:v{i % 5}")
        t.intern("")            # empty string
        t.intern("x" * 600)     # oversize row (vetoes vectorization)
        for i in range(7):
            m.row("startswith", f"reg-{i}.example.com/")
            m.row("endswith", f":v{i % 5}")
            m.row("contains", f"app-{i * 13}")
            m.row("eq", f"reg-1.example.com/app-{i}:v0")
            m.row("glob", f"reg-{i}.*:v1")
        return m.materialize()

    a, b = build(True), build(False)
    assert a.shape == b.shape
    assert (a == b).all()
    assert a.any(), "vacuous: no pattern matched anything"
