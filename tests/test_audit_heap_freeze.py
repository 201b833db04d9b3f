"""An audit that follows a bulk load freezes the heap it read, so the
collector's full passes stop rescanning the inventory: once after the
load, again only after as many further writes as half the inventory
(and at least GC_FREEZE_MIN_WRITES), and never for a small inventory."""

from __future__ import annotations

import pytest

from gatekeeper_tpu.client import Backend, RegoDriver
from gatekeeper_tpu.client import drivers
from gatekeeper_tpu.ir import TpuDriver
from gatekeeper_tpu.target import K8sValidationTarget


class RecordingGC:
    """Stands in for the gc module: records the calls, freezes nothing."""

    def __init__(self):
        self.calls = []

    def __getattr__(self, name):
        return lambda *a: self.calls.append(name)


def namespace(i: int) -> dict:
    return {"apiVersion": "v1", "kind": "Namespace",
            "metadata": {"name": f"ns-{i}"}}


@pytest.fixture
def rec(monkeypatch):
    fake = RecordingGC()
    monkeypatch.setattr(drivers, "gc", fake)
    return fake


@pytest.mark.parametrize("driver_cls", [RegoDriver, TpuDriver])
def test_freeze_after_bulk_load_then_after_half_the_inventory(rec,
                                                               driver_cls):
    driver = driver_cls()
    driver.GC_FREEZE_MIN_WRITES = 4
    client = Backend(driver).new_client([K8sValidationTarget()])
    for i in range(10):
        client.add_data(namespace(i))
    client.audit()
    assert rec.calls == ["unfreeze", "collect", "freeze"]
    client.audit()
    # 8 writes: over the minimum, under half of 18 objects
    for i in range(10, 18):
        client.add_data(namespace(i))
    client.audit()
    assert len(rec.calls) == 3
    # 9 writes, half of 19 objects (rounded down)
    client.add_data(namespace(18))
    client.audit()
    assert rec.calls == ["unfreeze", "collect", "freeze"] * 2


def test_small_inventory_never_freezes(rec):
    client = Backend(RegoDriver()).new_client([K8sValidationTarget()])
    for i in range(50):
        client.add_data(namespace(i))
    client.audit()
    client.audit()
    assert rec.calls == []
