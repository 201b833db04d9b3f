"""Differential tests: compiled (TpuDriver) vs interpreter (RegoDriver).

The compiled filter + host materialization must produce exactly the same
result multiset as the interpreter driver for compilable templates — on
randomized object/constraint populations covering the edge shapes the
compiler reasons about (missing fields, null labels, empty lists, DELETE
reviews, dryrun actions, regex params, prefix params).
"""

import random

import pytest
import yaml

from gatekeeper_tpu.client import Backend, RegoDriver
from gatekeeper_tpu.ir import TpuDriver
from gatekeeper_tpu.target import AugmentedUnstructured, K8sValidationTarget

from .conftest import REFERENCE, requires_reference


def mk_client(driver):
    return Backend(driver).new_client([K8sValidationTarget()])


def load_ref_template(path):
    return yaml.safe_load((REFERENCE / path).read_text())


def result_key(r):
    return (
        r.msg,
        r.constraint["metadata"]["name"],
        (r.resource or {}).get("metadata", {}).get("name"),
        r.enforcement_action,
    )


def assert_same_results(res_a, res_b):
    a = sorted(result_key(r) for r in res_a)
    b = sorted(result_key(r) for r in res_b)
    assert a == b


def run_both(template, constraints, objects):
    out = []
    for drv_cls in (RegoDriver, TpuDriver):
        drv = drv_cls()
        client = mk_client(drv)
        client.add_template(template)
        for c in constraints:
            client.add_constraint(c)
        for o in objects:
            client.add_data(o)
        out.append((drv, client))
    (drv_a, client_a), (drv_b, client_b) = out
    if isinstance(template, dict):
        kind = template["spec"]["crd"]["spec"]["names"]["kind"]
        assert kind in drv_b.compiled_kinds(), f"{kind} did not compile"
    assert_same_results(client_a.audit().results(), client_b.audit().results())
    # review path parity on each object too
    for o in objects[: 20]:
        assert_same_results(
            client_a.review(AugmentedUnstructured(o)).results(),
            client_b.review(AugmentedUnstructured(o)).results(),
        )


# ----------------------------------------------------------- requiredlabels


NS_LABEL_POOL = ["owner", "team", "env", "cost-center", "tier"]
VAL_POOL = ["me.agilebank.demo", "you.agilebank.demo", "###", "", "web",
            "prod", "a" * 40]


def random_namespace(rng, i):
    labels = None
    if rng.random() < 0.8:
        labels = {
            k: rng.choice(VAL_POOL)
            for k in rng.sample(NS_LABEL_POOL, rng.randint(0, 4))
        }
        if rng.random() < 0.1:
            labels = {}
    o = {"apiVersion": "v1", "kind": "Namespace",
         "metadata": {"name": f"ns-{i}"}}
    if labels is not None:
        o["metadata"]["labels"] = labels
    return o


def requiredlabels_constraint(rng, i):
    labels = []
    for k in rng.sample(NS_LABEL_POOL, rng.randint(1, 3)):
        entry = {"key": k}
        roll = rng.random()
        if roll < 0.4:
            entry["allowedRegex"] = rng.choice(
                ["^[a-zA-Z]+.agilebank.demo$", "^prod$", "", "^[a-z]+$"])
        elif roll < 0.5:
            entry["allowedRegex"] = ""
        labels.append(entry)
    spec = {"parameters": {"labels": labels}}
    if rng.random() < 0.3:
        spec["parameters"]["message"] = f"custom message {i}"
    if rng.random() < 0.3:
        spec["enforcementAction"] = "dryrun"
    return {
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sRequiredLabels",
        "metadata": {"name": f"req-{i}"},
        "spec": spec,
    }


@requires_reference
def test_requiredlabels_differential():
    template = load_ref_template("library/general/requiredlabels/template.yaml")
    rng = random.Random(7)
    constraints = [requiredlabels_constraint(rng, i) for i in range(12)]
    objects = [random_namespace(rng, i) for i in range(60)]
    run_both(template, constraints, objects)


# ------------------------------------------------------------- allowedrepos


def random_pod(rng, i):
    def container(j):
        c = {"name": f"c{j}"}
        if rng.random() < 0.95:
            c["image"] = rng.choice([
                "gcr.io/safe/app:v1", "docker.io/evil/app", "openpolicyagent/opa",
                "gcr.io/other/thing", "", "quay.io/x/y:2",
            ])
        return c

    spec = {}
    if rng.random() < 0.9:
        spec["containers"] = [container(j) for j in range(rng.randint(0, 4))]
    if rng.random() < 0.4:
        spec["initContainers"] = [container(j) for j in range(rng.randint(0, 2))]
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"pod-{i}", "namespace": "default"},
            "spec": spec}


def allowedrepos_constraint(rng, i):
    repos = rng.sample(["gcr.io/", "quay.io/", "docker.io/", "openpolicyagent"],
                       rng.randint(0, 3))
    return {
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sAllowedRepos",
        "metadata": {"name": f"repos-{i}"},
        "spec": {"parameters": {"repos": repos}},
    }


@requires_reference
def test_allowedrepos_differential():
    template = load_ref_template("library/general/allowedrepos/template.yaml")
    rng = random.Random(11)
    constraints = [allowedrepos_constraint(rng, i) for i in range(8)]
    objects = [random_pod(rng, i) for i in range(50)]
    run_both(template, constraints, objects)


# --------------------------------------------------------------- httpsonly


def random_ingress(rng, i):
    o = {
        "apiVersion": rng.choice(["extensions/v1beta1",
                                  "networking.k8s.io/v1", "v1"]),
        "kind": rng.choice(["Ingress", "Service"]),
        "metadata": {"name": f"ing-{i}", "namespace": "default"},
    }
    if rng.random() < 0.7:
        o["metadata"]["annotations"] = {
            "kubernetes.io/ingress.allow-http":
                rng.choice(["false", "true", ""])
        }
    if rng.random() < 0.7:
        o["spec"] = {"tls": [{"secretName": "x"}] if rng.random() < 0.7 else []}
    else:
        o["spec"] = {}
    return o


@requires_reference
def test_httpsonly_differential():
    template = load_ref_template("library/general/httpsonly/template.yaml")
    rng = random.Random(13)
    constraints = [{
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sHttpsOnly",
        "metadata": {"name": "https-only"},
        "spec": {"match": {"kinds": [
            {"apiGroups": ["extensions", "networking.k8s.io"],
             "kinds": ["Ingress"]}]}},
    }]
    objects = [random_ingress(rng, i) for i in range(60)]
    run_both(template, constraints, objects)


# ---------------------------------------------------- match-mask batched path


@requires_reference
def test_matched_subset_only():
    """Constraints with kind/namespace/label matches: the batched mask must
    agree with the per-review matcher through the full driver stack."""
    template = load_ref_template("library/general/requiredlabels/template.yaml")
    rng = random.Random(17)
    constraints = []
    for i in range(6):
        c = requiredlabels_constraint(rng, i)
        match = {}
        roll = rng.random()
        if roll < 0.3:
            match["kinds"] = [{"apiGroups": [""], "kinds": ["Namespace"]}]
        elif roll < 0.5:
            match["kinds"] = [{"apiGroups": [""], "kinds": ["Pod"]}]
        if rng.random() < 0.4:
            match["namespaces"] = ["default", "prod"]
        if rng.random() < 0.3:
            match["labelSelector"] = {"matchExpressions": [
                {"key": "env", "operator": "Exists"}]}
        if match:
            c["spec"]["match"] = match
        constraints.append(c)
    objects = [random_namespace(rng, i) for i in range(30)]
    objects += [random_pod(rng, i) for i in range(20)]
    run_both(template, constraints, objects)


def test_uncompilable_template_falls_back():
    """A template using `with` stays on the interpreter and still works."""
    template = {
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "k8sweird"},
        "spec": {
            "crd": {"spec": {"names": {"kind": "K8sWeird"}}},
            "targets": [{"target": "admission.k8s.gatekeeper.sh", "rego": """
package k8sweird
violation[{"msg": "weird"}] {
  c := count(deny) with input as {"x": 1}
  c >= 0
  input.review.object.metadata.name == "target-me"
}
deny[m] { input.x > 0; m := "d" }
"""}],
        },
    }
    drv = TpuDriver()
    client = mk_client(drv)
    client.add_template(template)
    assert drv.compiled_for("K8sWeird") is None
    client.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sWeird", "metadata": {"name": "w"}, "spec": {}})
    client.add_data({"apiVersion": "v1", "kind": "Pod",
                     "metadata": {"name": "target-me", "namespace": "d"}})
    client.add_data({"apiVersion": "v1", "kind": "Pod",
                     "metadata": {"name": "other", "namespace": "d"}})
    res = client.audit().results()
    assert [r.resource["metadata"]["name"] for r in res] == ["target-me"]


# --------------------------------------------------- numeric precision ties

BIGNUM_TEMPLATE = {
    "apiVersion": "templates.gatekeeper.sh/v1beta1",
    "kind": "ConstraintTemplate",
    "metadata": {"name": "bignum"},
    "spec": {
        "crd": {"spec": {"names": {"kind": "BigNum"}}},
        "targets": [{
            "target": "admission.k8s.gatekeeper.sh",
            "rego": """
package bignum
violation[{"msg": msg}] {
  provided := input.review.object.spec.replicas
  maximum := input.parameters.max
  provided > maximum
  msg := "too many replicas"
}
""",
        }],
    },
}


def test_f32_tie_does_not_underfire():
    """Regression (ADVICE r1): 16777217 > 16777216 is a tie in float32;
    the device filter must over-fire on exact-id mismatch so the host
    re-check decides, never silently dropping the violation."""
    constraint = {
        "apiVersion": "constraints.gatekeeper.sh/v1beta1", "kind": "BigNum",
        "metadata": {"name": "c1"},
        "spec": {"parameters": {"max": 16777216}},
    }
    objs = [
        {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": "over", "namespace": "default"},
         "spec": {"replicas": 16777217}},
        {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": "at-limit", "namespace": "default"},
         "spec": {"replicas": 16777216}},
        {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": "under", "namespace": "default"},
         "spec": {"replicas": 3}},
    ]
    run_both(BIGNUM_TEMPLATE, [constraint], objs)


NEGATED_BIGNUM_TEMPLATE = {
    "apiVersion": "templates.gatekeeper.sh/v1beta1",
    "kind": "ConstraintTemplate",
    "metadata": {"name": "bignumneg"},
    "spec": {
        "crd": {"spec": {"names": {"kind": "BigNumNeg"}}},
        "targets": [{
            "target": "admission.k8s.gatekeeper.sh",
            "rego": """
package bignumneg
violation[{"msg": msg}] {
  provided := input.review.object.spec.replicas
  maximum := input.parameters.max
  not provided < maximum
  msg := "not under the limit"
}
""",
        }],
    },
}


def test_f32_tie_does_not_underfire_under_negation():
    """Regression (r2 code review): over-fire bias at a comparison leaf is
    flipped by `not` — uncertainty must propagate as a (lo, hi) pair so
    negation swaps bounds instead of inverting the over-approximation."""
    constraint = {
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "BigNumNeg", "metadata": {"name": "c1"},
        "spec": {"parameters": {"max": 16777217}},
    }
    objs = [
        # 16777216 < 16777217 exactly, but ties in f32: `not <` must not
        # drop the uncertainty (interpreter says no violation; and the
        # device filter may fire, host re-check settles it)
        {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": "tie-under", "namespace": "default"},
         "spec": {"replicas": 16777216}},
        {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": "over", "namespace": "default"},
         "spec": {"replicas": 16777218}},
        {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": "under", "namespace": "default"},
         "spec": {"replicas": 3}},
    ]
    run_both(NEGATED_BIGNUM_TEMPLATE, [constraint], objs)


def test_f32_tie_negated_exact_violation_found():
    """The exact case from the review: replicas == max ties in f32; `not
    provided < maximum` holds exactly (equal), so the violation must
    surface on the device path."""
    constraint = {
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "BigNumNeg", "metadata": {"name": "c1"},
        "spec": {"parameters": {"max": 16777216}},
    }
    objs = [
        {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": "at-tie", "namespace": "default"},
         "spec": {"replicas": 16777217}},
    ]
    run_both(NEGATED_BIGNUM_TEMPLATE, [constraint], objs)


def test_compiled_hlo_introspection():
    """The device program of any compiled template can be dumped at
    jaxpr / StableHLO / optimized-HLO stages (aux-subsystem parity with
    the reference's pprof-style introspection)."""
    from gatekeeper_tpu.parallel.workload import build_eval_setup
    from gatekeeper_tpu.utils.profiling import compiled_hlo

    _, ct, feats, params, table, derived, _, _ = build_eval_setup(8, 2)
    jx = compiled_hlo(ct, feats, params, table, derived, stage="jaxpr")
    assert "lambda" in jx or "let" in jx
    hlo = compiled_hlo(ct, feats, params, table, derived, stage="hlo")
    assert "func" in hlo or "HloModule" in hlo
    opt = compiled_hlo(ct, feats, params, table, derived,
                       stage="optimized")
    assert "HloModule" in opt or "func" in opt


def test_phase_timers():
    import time as _t

    from gatekeeper_tpu.utils.profiling import PhaseTimers

    pt = PhaseTimers()
    with pt.phase("sweep"):
        _t.sleep(0.01)
    with pt.phase("sweep"):
        pass
    snap = pt.snapshot()
    assert snap["sweep"][1] == 2 and snap["sweep"][0] >= 0.01

    # phases do not nest: one opened inside another suspends it, so each
    # instant is charged to one phase and the sum stays within the wall
    pt = PhaseTimers()
    t0 = _t.monotonic()
    with pt.phase("outer"):
        _t.sleep(0.01)
        with pt.phase("inner"):
            _t.sleep(0.02)
        _t.sleep(0.01)
    wall = _t.monotonic() - t0
    snap = pt.snapshot()
    assert snap["outer"][1] == 1 and snap["inner"][1] == 1
    assert 0.02 <= snap["outer"][0] < 0.03
    assert 0.02 <= snap["inner"][0]
    assert snap["outer"][0] + snap["inner"][0] <= wall
