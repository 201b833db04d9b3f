"""Inventory-join compiler (ir/join.py) differential tests.

Cross-object templates (uniqueingresshost / uniqueserviceselector —
reference library/general/*/src.rego) must produce byte-identical results
through the aggregated-key join path and the interpreter driver, across
audit and admission, including the `not identical` own-copy exclusion.
"""

import pytest

from gatekeeper_tpu import policies
from gatekeeper_tpu.client import Backend, RegoDriver
from gatekeeper_tpu.ir import TpuDriver
from gatekeeper_tpu.target import AugmentedUnstructured, K8sValidationTarget


def ingress(name, ns, hosts, group="networking.k8s.io"):
    return {"apiVersion": f"{group}/v1", "kind": "Ingress",
            "metadata": {"name": name, "namespace": ns},
            "spec": {"rules": [{"host": h} for h in hosts]}}


def service(name, ns, sel):
    return {"apiVersion": "v1", "kind": "Service",
            "metadata": {"name": name, "namespace": ns},
            "spec": {"selector": sel}}


OBJS = [
    ingress("a", "ns1", ["x.com", "y.com"]),
    ingress("b", "ns2", ["x.com"]),           # conflicts with a
    ingress("c", "ns3", ["unique.com"]),      # no conflict
    ingress("d", "ns3", ["y.com", "z.com"]),  # conflicts with a on y.com
    service("s1", "ns1", {"app": "web", "tier": "fe"}),
    service("s2", "ns2", {"tier": "fe", "app": "web"}),  # same flattened
    service("s3", "ns2", {"app": "db"}),
    service("s4", "ns1", {}),
]

REVIEWS = [
    ingress("new", "ns9", ["x.com"]),          # CREATE conflicting
    ingress("c", "ns3", ["unique.com"]),       # UPDATE: own copy only
    ingress("c", "ns3", ["x.com"]),            # UPDATE into a conflict
    service("snew", "ns5", {"tier": "fe", "app": "web"}),
    service("s3", "ns2", {"app": "db"}),       # own copy only
]


def _run(driver):
    client = Backend(driver).new_client([K8sValidationTarget()])
    client.add_template(policies.load("general/uniqueingresshost"))
    client.add_template(policies.load("general/uniqueserviceselector"))
    for kind, name in (("K8sUniqueIngressHost", "unique-hosts"),
                       ("K8sUniqueServiceSelector", "unique-selectors")):
        client.add_constraint({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind, "metadata": {"name": name}, "spec": {}})
    for o in OBJS:
        client.add_data(o)
    out = [sorted((r.msg,
                   (r.resource or {}).get("metadata", {}).get("name", ""))
                  for r in client.audit().results())]
    # run the audit twice: the steady-state (cached inv tables / keys)
    # second sweep must agree with the first
    out.append(sorted((r.msg,
                       (r.resource or {}).get("metadata", {}).get("name",
                                                                  ""))
                      for r in client.audit().results()))
    for rv in REVIEWS:
        out.append(sorted(
            r.msg for r in client.review(
                AugmentedUnstructured(rv)).results()))
    # mutate: delete the conflicting ingress, re-audit (cache invalidation)
    client.remove_data(OBJS[1])
    out.append(sorted((r.msg,
                       (r.resource or {}).get("metadata", {}).get("name",
                                                                  ""))
                      for r in client.audit().results()))
    return out


def test_join_templates_compile():
    drv = TpuDriver()
    client = Backend(drv).new_client([K8sValidationTarget()])
    client.add_template(policies.load("general/uniqueingresshost"))
    client.add_template(policies.load("general/uniqueserviceselector"))
    assert sorted(drv._join_progs) == ["K8sUniqueIngressHost",
                                       "K8sUniqueServiceSelector"]
    assert drv.join_for("K8sUniqueIngressHost") is not None
    assert drv.join_for("K8sUniqueServiceSelector") is not None


def test_join_differential_audit_and_admission():
    a = _run(RegoDriver())
    b = _run(TpuDriver())
    assert a == b
    # the scenario must be non-vacuous: conflicts exist and resolve
    assert any(a[0]), "audit found no conflicts"
    assert a[2] and a[4], "admission conflicts missing"
    assert a[3] == [] and a[6] == [], "own-copy exclusion failed"


def test_join_device_path_matches_host_path():
    """The device searchsorted join and the host dict probe must agree
    on the same key tables."""
    import numpy as np

    from gatekeeper_tpu.utils.values import freeze

    drv = TpuDriver()
    client = Backend(drv).new_client([K8sValidationTarget()])
    client.add_template(policies.load("general/uniqueingresshost"))
    client.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sUniqueIngressHost", "metadata": {"name": "u"},
        "spec": {}})
    for i in range(64):
        client.add_data(ingress(f"i{i}", f"ns{i % 8}",
                                [f"h{i % 24}.com", f"only{i}.com"]))
    jc = drv.join_for("K8sUniqueIngressHost")
    reviews = drv._inventory_reviews("admission.k8s.gatekeeper.sh")
    frz = [freeze(r) for r in reviews]
    inv = drv._inventory_tree("admission.k8s.gatekeeper.sh")
    host, on_device = jc.fires(frz, inv, drv._data_gen, device=False)
    assert not on_device
    jc._jit = None
    dev, on_device = jc.fires(frz, inv, drv._data_gen, device=True)
    assert on_device
    assert (np.asarray(host) == np.asarray(dev)).all()
    assert host.any(), "non-vacuous: some host collisions must fire"


def test_join_device_cache_not_keyed_by_shape_alone():
    """Two same-size review batches with different membership must get
    different fires through the device path — regression for the device
    input cache being keyed only by (data_gen, n, h, kb), which reused
    the previous batch's key tensors and silently under-fired."""
    import numpy as np

    from gatekeeper_tpu.utils.values import freeze

    drv = TpuDriver()
    client = Backend(drv).new_client([K8sValidationTarget()])
    client.add_template(policies.load("general/uniqueingresshost"))
    client.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sUniqueIngressHost", "metadata": {"name": "u"},
        "spec": {}})
    client.add_data(ingress("base", "ns0", ["dup.com"]))
    jc = drv.join_for("K8sUniqueIngressHost")
    inv = drv._inventory_tree("admission.k8s.gatekeeper.sh")
    # batch A: 4 reviews, none colliding; batch B: same size/shape, all
    # colliding with the stored dup.com host
    def rv(name, ns, hosts):
        return freeze({"kind": {"group": "networking.k8s.io",
                                "version": "v1", "kind": "Ingress"},
                       "name": name, "namespace": ns,
                       "object": ingress(name, ns, hosts)})

    batch_a = [rv(f"a{i}", "nsA", [f"free{i}.com"]) for i in range(4)]
    batch_b = [rv(f"b{i}", "nsB", ["dup.com"]) for i in range(4)]
    fa, _ = jc.fires(batch_a, inv, drv._data_gen, device=True)
    fb, _ = jc.fires(batch_b, inv, drv._data_gen, device=True)
    # and back again, to also catch reuse in the other direction
    fa2, _ = jc.fires(batch_a, inv, drv._data_gen, device=True)
    assert not np.asarray(fa).any(), "batch A has no collisions"
    assert np.asarray(fb).all(), "batch B must all fire"
    assert not np.asarray(fa2).any(), "stale device tensors reused"


def test_join_inv_tables_keyed_by_tree_identity():
    """Two different inventory trees at the same data generation must
    not share join tables — regression for the per-data_gen-only cache
    (second registered target reused the first target's tables)."""
    from gatekeeper_tpu.utils.values import freeze

    drv = TpuDriver()
    client = Backend(drv).new_client([K8sValidationTarget()])
    client.add_template(policies.load("general/uniqueingresshost"))
    jc = drv.join_for("K8sUniqueIngressHost")
    tree_a = freeze({"cluster": {}, "namespace": {
        "ns1": {"networking.k8s.io/v1": {"Ingress": {
            "a": ingress("a", "ns1", ["x.com"])}}}}})
    tree_b = freeze({"cluster": {}, "namespace": {}})
    tabs_a = jc.inv_tables(tree_a, 7)
    tabs_b = jc.inv_tables(tree_b, 7)
    assert len(tabs_a[0][0]) == 1, "tree A has one join key"
    assert len(tabs_b[0][0]) == 0, "tree B is empty, must not reuse A"
    assert jc.inv_tables(tree_a, 7) is tabs_a, "cache hit expected"


# ------------------------------------------- holder-tree rendering (audit)

TARGET = "admission.k8s.gatekeeper.sh"


def pod(name, ns):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": ns},
            "spec": {"containers": [{"name": "c", "image": "nginx"}]}}


def port_service(name, ns, ports):
    return {"apiVersion": "v1", "kind": "Service",
            "metadata": {"name": name, "namespace": ns},
            "spec": {"ports": [{"port": p} for p in ports]}}


def _template(kind, rego):
    return {"apiVersion": "templates.gatekeeper.sh/v1beta1",
            "kind": "ConstraintTemplate",
            "metadata": {"name": kind.lower()},
            "spec": {"crd": {"spec": {"names": {"kind": kind}}},
                     "targets": [{"target": TARGET, "rego": rego}]}}


def _constraint(kind, name, match=None):
    spec = {"match": match} if match else {}
    return {"apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind, "metadata": {"name": name}, "spec": spec}


# numeric join keys: canonical sids may part values Rego equality joins
PORT_TEMPLATE = _template("K8sUniquePort", """
package k8suniqueport

is_self(other) {
  other.metadata.namespace == input.review.object.metadata.namespace
  other.metadata.name == input.review.object.metadata.name
}

violation[{"msg": msg}] {
  input.review.kind.kind == "Service"
  port := input.review.object.spec.ports[_].port
  other := data.inventory.namespace[ns][_]["Service"][name]
  other.spec.ports[_].port == port
  not is_self(other)
  msg := sprintf("port <%v> also served by <%v/%v>", [port, ns, name])
}
""")

# the message counts the inventory: a head-only literal reading data
# outside the generator, so the holders alone would render it wrong
COUNTING_TEMPLATE = _template("K8sUniqueAppCounted", """
package k8suniqueappcounted

is_self(other) {
  other.metadata.namespace == input.review.object.metadata.namespace
  other.metadata.name == input.review.object.metadata.name
}

violation[{"msg": msg}] {
  input.review.kind.kind == "Service"
  mine := input.review.object.spec.selector.app
  other := data.inventory.namespace[ns][_]["Service"][name]
  other.spec.selector.app == mine
  not is_self(other)
  msg := sprintf("app <%v> shared with <%v/%v>, %v namespaces synced",
                 [mine, ns, name, count(data.inventory.namespace)])
}
""")

LIB = {
    "selector": (policies.load("general/uniqueserviceselector"),
                 "K8sUniqueServiceSelector"),
    "ingress": (policies.load("general/uniqueingresshost"),
                "K8sUniqueIngressHost"),
    "port": (PORT_TEMPLATE, "K8sUniquePort"),
    "counting": (COUNTING_TEMPLATE, "K8sUniqueAppCounted"),
}

# name -> (templates, extra constraints, objects, objects removed one per
# re-audit, pairs each audit fires that render nothing)
RENDER_CASES = {
    # three Services and more share one selector, among Pods whose
    # selector key is the empty string; a scoped second constraint
    # expands one firing review to two pairs
    "shared-selector": (
        ["selector"],
        [_constraint("K8sUniqueServiceSelector", "only-b",
                     {"namespaces": ["b"]})],
        [service("s1", "a", {"app": "web"}),
         service("s2", "b", {"app": "web"}),
         service("s3", "c", {"app": "web"}),
         service("s4", "c", {"app": "web"}),
         service("db", "a", {"app": "db"}),
         service("none", "b", {}),
         pod("p1", "a"), pod("p2", "b"), pod("p3", "c")],
        [], 0),
    # several hosts per Ingress, each colliding with a different object
    "ingress-hosts": (
        ["ingress"], [],
        [ingress("a", "ns1", ["x.com", "y.com", "w.com"]),
         ingress("b", "ns2", ["x.com"]),
         ingress("d", "ns3", ["y.com", "z.com"]),
         ingress("e", "ns4", ["z.com", "w.com"], group="extensions"),
         ingress("c", "ns3", ["unique.com"]),
         pod("p", "ns1")],
        [], 0),
    # a review's own stored copies hold its key but never fire on it:
    # one Ingress stored under two groups, one repeating its own host,
    # a Service alone with its selector. The two copies share one
    # identity, so the join over-fires both (module docstring) and
    # their rendering finds nothing
    "own-copy": (
        ["ingress", "selector"], [],
        [ingress("twin", "ns1", ["t.com"]),
         ingress("twin", "ns1", ["t.com"], group="extensions"),
         ingress("rep", "ns2", ["r.com", "r.com"]),
         ingress("other", "ns3", ["r.com"]),
         service("solo", "a", {"app": "solo"}),
         service("x1", "a", {"app": "x"}),
         service("x2", "b", {"app": "x"})],
        [], 2),
    # re-audits after remove_data: a stale index would name the removed
    # object or keep a pair firing
    "remove-data": (
        ["selector", "ingress"], [],
        [service("s1", "a", {"app": "web"}),
         service("s2", "b", {"app": "web"}),
         service("s3", "c", {"app": "web"}),
         ingress("i1", "ns1", ["h.com"]),
         ingress("i2", "ns2", ["h.com"])],
        [service("s2", "b", {"app": "web"}),
         ingress("i2", "ns2", ["h.com"]),
         service("s3", "c", {"app": "web"})], 0),
}

FALLBACK_CASES = {
    "numeric-key": (
        ["port"], [],
        [port_service("web", "a", [80, 443]),
         port_service("web2", "b", [80]),
         port_service("api", "c", [443, 8080]),
         port_service("solo", "c", [9000])],
        [], 0),
    "msg-reads-inventory": (
        ["counting"], [],
        [service("s1", "a", {"app": "web"}),
         service("s2", "b", {"app": "web"}),
         service("s3", "c", {"app": "db"}),
         pod("p", "d")],
        [], 0),
}


def _join_renders():
    from gatekeeper_tpu.control.metrics import REGISTRY

    name = "gatekeeper_tpu_audit_join_render_total"
    ent = REGISTRY.snapshot((name,)).get(name, {})
    vals = {tuple(k): v for k, v in ent.get("values", [])}
    return vals.get(("holders",), 0.0), vals.get(("inventory",), 0.0)


def _audit_case(driver, case):
    """Per audit: (sorted results, render counter deltas)."""
    libs, extra, objs, removals, _ = case
    client = Backend(driver).new_client([K8sValidationTarget()])
    for lib in libs:
        tmpl, kind = LIB[lib]
        client.add_template(tmpl)
        client.add_constraint(_constraint(kind, "all"))
    for c in extra:
        client.add_constraint(c)
    for o in objs:
        client.add_data(o)

    def audit():
        before = _join_renders()
        rows = sorted(
            (r.msg, r.constraint["kind"], r.constraint["metadata"]["name"],
             r.resource["kind"], r.resource["metadata"]["namespace"],
             r.resource["metadata"]["name"])
            for r in client.audit().results())
        after = _join_renders()
        return rows, (after[0] - before[0], after[1] - before[1])

    # twice: the second sweep serves from the cached index and keys
    out = [audit(), audit()]
    for o in removals:
        client.remove_data(o)
        out.append(audit())
    return out


def _firing_pairs(rows) -> int:
    return len({r[1:] for r in rows})


def _assert_rendered(case, scope):
    want = _audit_case(RegoDriver(), case)
    got = _audit_case(TpuDriver(), case)
    assert [rows for rows, _ in got] == [rows for rows, _ in want]
    assert want[0][0], "non-vacuous: the first audit has violations"
    for rows, (holders, inventory) in got:
        n = _firing_pairs(rows) + case[4]
        assert (holders, inventory) == ((n, 0) if scope == "holders"
                                        else (0, n))


@pytest.mark.parametrize("case", sorted(RENDER_CASES))
def test_join_holder_render_matches_interpreter(case):
    """Each firing review renders against the holders of its join keys
    alone, with messages byte-equal to the interpreter's whole-inventory
    rendering, and every firing pair counts under scope="holders"."""
    _assert_rendered(RENDER_CASES[case], "holders")


@pytest.mark.parametrize("case", sorted(FALLBACK_CASES))
def test_join_render_falls_back_to_inventory(case):
    """Where the cut is not provably exact (a non-string join key; a
    message reading data.inventory outside the generator) the review
    renders against the whole inventory, equal to the interpreter, and
    counts under scope="inventory"."""
    _assert_rendered(FALLBACK_CASES[case], "inventory")


def test_join_holder_tree_shares_leaves():
    """The holder tree keeps the inventory's nesting and its frozen leaf
    instances, and holds exactly the holders of the review's keys."""
    from gatekeeper_tpu.utils.values import freeze

    drv = TpuDriver()
    client = Backend(drv).new_client([K8sValidationTarget()])
    client.add_template(policies.load("general/uniqueserviceselector"))
    for o in (service("s1", "a", {"app": "web"}),
              service("s2", "b", {"app": "web"}),
              service("s3", "b", {"app": "db"}), pod("p", "a")):
        client.add_data(o)
    jc = drv.join_for("K8sUniqueServiceSelector")
    assert jc.prog.holders_exact
    inv = drv._inventory_tree(TARGET)
    review = freeze({"kind": {"group": "", "version": "v1",
                              "kind": "Service"},
                     "name": "new", "namespace": "z",
                     "object": service("new", "z", {"app": "web"})})
    tree = jc.holder_tree(inv, drv._data_gen, review)
    assert {ns: sorted(tree["namespace"][ns]["v1"]["Service"])
            for ns in tree["namespace"]} == {"a": ["s1"], "b": ["s2"]}
    assert "cluster" not in tree
    for ns, name in (("a", "s1"), ("b", "s2")):
        assert tree["namespace"][ns]["v1"]["Service"][name] is \
            inv["namespace"][ns]["v1"]["Service"][name]
