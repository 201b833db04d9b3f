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
