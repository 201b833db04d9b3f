"""The main path's device kernels compiled for a described TPU v5e.

Nothing runs: each test lowers one kernel at the size a deployment
sends it and compiles it for a v5e chip (or a 2x2 v5e mesh) that is
described, not attached, then checks that what the compiler reports
fits a chip's 16 GB. This catches what interpret-free CPU tests cannot
see — tiling refusals, memory that does not fit, partitioning the
compiler rejects — at no chip time.

The topology is described inside a module-scoped fixture (only one
process at a time may load the TPU library, so nothing here touches it
while modules are imported), and the persistent compilation cache is off
around the compiles (a cache entry written for a described chip cannot
be read back without one).
"""

import numpy as np
import pytest

from gatekeeper_tpu import policies
from gatekeeper_tpu.client import Backend
from gatekeeper_tpu.ir import TpuDriver
from gatekeeper_tpu.target import K8sValidationTarget

TARGET = "admission.k8s.gatekeeper.sh"
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _shapes(tree, sharding, n=None):
    """ShapeDtypeStructs of a tree of arrays, the leading axis set to n."""
    import jax

    def spec(a):
        shape = tuple(a.shape)
        if n is not None:
            shape = (n,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=sharding)

    return jax.tree_util.tree_map(spec, tree)


def _scalars(sharding, k=3):
    import jax
    import jax.numpy as jnp

    return [jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)] * k


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    print(f"memory_analysis: args {m.argument_size_in_bytes} out "
          f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} "
          f"code {m.generated_code_size_in_bytes}")
    assert total < HBM_BYTES, f"{total} bytes do not fit one v5e chip"
    return total


def _setup(template: str, kind: str, constraints: list, objects: list):
    """A small real setup of one template: its device program and the
    (feature, parameter, table, derived) trees whose shapes the tests
    scale to deployment size."""
    drv = TpuDriver()
    drv._mesh = None
    client = Backend(drv).new_client([K8sValidationTarget()])
    client.add_template(policies.load(template))
    for c in constraints:
        client.add_constraint(c)
    for o in objects:
        client.add_data(o)
    ct = drv.compiled_for(kind)
    assert ct is not None, f"{kind} must compile"
    trees = drv._prepare_eval(ct, kind, drv._inventory_reviews(TARGET),
                              drv._constraints(TARGET), None)
    return drv, ct, trees


def _labels_setup():
    from gatekeeper_tpu.parallel.workload import (synth_constraints,
                                                  synth_objects)

    return _setup("general/requiredlabels", "K8sRequiredLabels",
                  synth_constraints(500, seed=1),
                  synth_objects(64, seed=0))


def test_slab_pairs_requiredlabels_131072x512(one_chip):
    """The config-4 sweep: 100k Namespaces (bucket 131072) x 500
    constraints (bucket 512) through the single-device slab kernel."""
    drv, ct, (feats, enc, table, derived) = _labels_setup()
    assert next(iter(next(iter(enc.values())).values())).shape[0] == 512
    fn = ct._slab_pairs_jit(8192, drv._sweep_slab(100_000), ct._rows_cap)
    compiled = fn.lower(_shapes(feats, one_chip, 131072),
                        _shapes(enc, one_chip), _shapes(table, one_chip),
                        _shapes(derived, one_chip),
                        *_scalars(one_chip)).compile()
    _fits(compiled)


def test_slab_pairs_psp_capabilities_65536(one_chip):
    """One pod-security-policy sweep at the config-3 bucket (50k Pods:
    65536): the capabilities template (set differences over containers)."""
    from gatekeeper_tpu.parallel.workload import synth_pods_psp

    drv, ct, (feats, enc, table, derived) = _setup(
        "pod-security-policy/capabilities", "K8sPSPCapabilities",
        [{"apiVersion": "constraints.gatekeeper.sh/v1beta1",
          "kind": "K8sPSPCapabilities", "metadata": {"name": "caps"},
          "spec": {"parameters": {
              "allowedCapabilities": ["NET_BIND_SERVICE"],
              "requiredDropCapabilities": ["ALL"]}}}],
        synth_pods_psp(64, seed=0))
    fn = ct._slab_pairs_jit(8192, drv._sweep_slab(50_000), ct._rows_cap)
    compiled = fn.lower(_shapes(feats, one_chip, 65536),
                        _shapes(enc, one_chip), _shapes(table, one_chip),
                        _shapes(derived, one_chip),
                        *_scalars(one_chip)).compile()
    _fits(compiled)


def test_join_kernel_uniqueingresshost(one_chip):
    """The inventory-join membership kernel at the config-2 size: 1,667
    Ingresses (bucket 2048) probed against a 2048-key table."""
    import jax

    drv = TpuDriver()
    client = Backend(drv).new_client([K8sValidationTarget()])
    client.add_template(policies.load("general/uniqueingresshost"))
    jc = drv.join_for("K8sUniqueIngressHost")
    assert jc is not None
    i32 = np.int32
    n, h, g, kb = 2048, 2, 1, 2048

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, i32, sharding=one_chip)

    compiled = jc._jit_wrapper().lower(s(kb), s(kb), s(g, kb), s(n, h),
                                       s(n, g)).compile()
    _fits(compiled)


def test_regex_dfa_scan_max_len(one_chip):
    """The device regex scan at MAX_LEN over a 64k-string vocab for the
    library's 16 patterns."""
    import jax
    import jax.numpy as jnp

    from gatekeeper_tpu.ops import regex_nfa

    p, states, v = 16, 32, 65536
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in (((p, states, 256), jnp.int32),
                              ((p, states), jnp.bool_),
                              ((p,), jnp.int32), ((p,), jnp.int32),
                              ((v, regex_nfa.MAX_LEN), jnp.uint8))]
    _fits(regex_nfa.scan_program().lower(*args).compile())


def test_mesh_slab_pairs_2x2(topo):
    """The mesh-slab pair kernel over a 2x2 v5e mesh: the config-4 sweep
    split four ways on the data axis; each chip must hold its share."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gatekeeper_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(devices=list(topo.devices))
    assert dict(mesh.shape) == {"data": 4, "model": 1}
    drv, ct, (feats, enc, table, derived) = _labels_setup()
    rep = NamedSharding(mesh, P())

    def data_split(tree, n):
        import jax

        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                (n,) + tuple(a.shape[1:]), a.dtype,
                sharding=NamedSharding(
                    mesh, P("data", *([None] * (a.ndim - 1))))), tree)

    n = 131072
    lslab = max(8192, n // 4 // ct.MESH_SLABS)
    fn = ct._mesh_slab_pairs_jit(mesh, 8192, lslab, ct._rows_cap_mesh)
    compiled = fn.lower(data_split(feats, n), _shapes(enc, rep),
                        _shapes(table, rep), _shapes(derived, rep),
                        *_scalars(rep)).compile()
    _fits(compiled)
