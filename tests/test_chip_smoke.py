"""chip_smoke.py's phases at a tiny size on the CPU.

The smoke itself refuses any device but a TPU; here its phase functions
run directly, with the same checks: the device audit agrees with the
plain RegoDriver reference on a sample covering every library's kinds,
every kind with candidates is dispatched to the device in the warm
audit, and the webhook's verdicts equal the reference's with at least
one admission batch evaluated on the device by the cost model.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402

TINY = cs.Sizes(namespaces=64, label_constraints=500, mixed=60,
                psp_pods=40, sample=48, singles=4, burst_clients=16,
                burst_per_client=4)


@pytest.fixture(scope="module")
def lib():
    return cs.build_library(TINY)


@pytest.fixture(scope="module")
def ref(lib):
    return cs.reference_client(lib)


def test_library_covers_all_shipped_templates(lib):
    from gatekeeper_tpu import policies

    assert len(lib["templates"]) == len(policies.names()) == 23
    kinds = {t["spec"]["crd"]["spec"]["names"]["kind"]
             for t in lib["templates"]}
    assert kinds == {c["kind"] for c in lib["constraints"]}
    assert len({cs.ident(o) for o in cs.inventory(lib)}) == \
        len(cs.inventory(lib)), "inventory identities must be unique"


def test_audit_and_reference_agree(lib, ref):
    audit = cs.audit_phase(lib)
    assert audit["kinds"] == 23
    assert set(audit["warm_served_by_kind"]) == {
        c["kind"] for c in lib["constraints"]}
    sample = cs.sample_objects(lib, audit["results"], TINY.sample)
    facts = cs.reference_phase(ref, sample, audit["results"])
    assert facts["agree"] == facts["sampled"] > 0
    assert facts["sample_violations"] > 0


def test_admission_verdicts_agree_and_use_the_device(lib, ref):
    facts = cs.admission_phase(lib, ref, TINY)
    assert facts["agree"] == facts["requests"]
    assert facts["batch_evals_by_path"].get("batch-device", 0) > 0
    assert 0 < facts["denied"] < facts["requests"]


def test_audit_check_fails_when_a_kind_served_on_host(lib, monkeypatch):
    """The smoke's own check is not vacuous: a kind routed to the host
    path in the warm audit fails the phase."""
    from gatekeeper_tpu.ir import TpuDriver

    drv = TpuDriver()
    drv.compiled_for = lambda kind: None  # every kind on the host path
    drv.join_for = lambda kind: None
    with pytest.raises(cs.SmokeFailure):
        cs.audit_phase(lib, drv)


def test_mesh_phase_splits_rows_over_four_devices(lib, monkeypatch):
    """The --chips 4 path, tiny, on four of the CPU's virtual devices:
    the mesh audit equals the one-device audit and the reference, and
    every row-sharded buffer holds a share on each device."""
    monkeypatch.setenv("GATEKEEPER_TPU_MESH", "4")
    monkeypatch.setenv("GATEKEEPER_TPU_MESH_MIN_REVIEWS", "32")
    cut = cs.without_psp(lib)
    facts = cs.mesh_phase(cut, cs.reference_client(cut), TINY, 4)
    assert facts == {"mesh": "mesh(data=4)", "equal": True}


def test_mesh_spread_refuses_rows_on_one_device():
    """The spread check is not vacuous: rows placed on one device, or
    no row-sharded buffer at all, fail it."""
    import types

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gatekeeper_tpu.parallel.mesh import make_mesh

    one = make_mesh(devices=jax.devices()[:1])
    buf = jax.device_put(np.zeros((8, 2), np.int32),
                         NamedSharding(one, P("data", None)))
    drv = types.SimpleNamespace(_dev_mesh_cache={0: (None, buf)})
    with pytest.raises(cs.SmokeFailure, match="not a share"):
        cs.mesh_spread(drv, 4)
    drv._dev_mesh_cache = {}
    with pytest.raises(cs.SmokeFailure, match="no row-sharded"):
        cs.mesh_spread(drv, 4)


def test_smoke_refuses_cpu():
    """Without a TPU the script exits non-zero before any phase and
    never prints "ok": true."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=120, cwd=ROOT)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert '"platform": "cpu"' in proc.stdout


def test_process_per_chip_planes_refuse_on_accelerators(monkeypatch):
    """--admission-engines / --audit-shards > 1 fail at start on an
    accelerator host (children could not reach their chips) and still
    start on the CPU backend."""
    import jax

    from gatekeeper_tpu.control.main import (_refuse_process_per_chip,
                                             build_parser)

    for flags in (["--admission-engines", "4"], ["--audit-shards", "2"]):
        args = build_parser().parse_args(["--fake-kube", *flags])
        _refuse_process_per_chip(args)  # CPU: allowed
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(SystemExit, match="one child process per chip"):
            _refuse_process_per_chip(args)
        monkeypatch.undo()
    args = build_parser().parse_args(["--fake-kube"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _refuse_process_per_chip(args)  # the defaults start anywhere
