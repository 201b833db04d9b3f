"""Audit phases as program spans: the phases of one Client.audit() are
disjoint and cover its wall; with a profiler recording, each is a host
span gk/<phase> under gk/audit on the trace's clock; device programs are
named jit_<tag> after their AotJit tag; tools/trace_gaps reduces a trace
to idle gaps by gk/ span and device time by program."""

import glob
import os
import signal
import time

import jax
import jax.numpy as jnp
import pytest

from gatekeeper_tpu import policies
from gatekeeper_tpu.client import Backend
from gatekeeper_tpu.ir import TpuDriver
from gatekeeper_tpu.ir.aot import AotJit
from gatekeeper_tpu.target import K8sValidationTarget
from gatekeeper_tpu.utils import profiling
from tools import trace_gaps

PER_TEST_TIMEOUT_S = 240

# every phase one audit of a compiled kind and a join kind passes through
AUDIT_PHASES = {"match", "encode", "dispatch", "device_sweep", "materialize",
                "join", "join_materialize", "delta_store",
                "handle_violations"}


@pytest.fixture(autouse=True)
def _hard_timeout():
    def boom(signum, frame):  # pragma: no cover - only on a real hang
        raise TimeoutError(
            f"test exceeded the {PER_TEST_TIMEOUT_S}s hard timeout")

    old = signal.signal(signal.SIGALRM, boom)
    signal.alarm(PER_TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _client():
    """Required labels over Namespaces (a device sweep) and the unique
    Service selector join, over enough objects that the phases' work
    outweighs the calls between them: every tenth Namespace lacks its
    label, and 20 Service pairs share a selector."""
    drv = TpuDriver()
    drv._mesh = None
    client = Backend(drv).new_client([K8sValidationTarget()])
    client.add_template(policies.load("general/requiredlabels"))
    client.add_template(policies.load("general/uniqueserviceselector"))
    client.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sRequiredLabels", "metadata": {"name": "need-team"},
        "spec": {"match": {"kinds": [{"apiGroups": [""],
                                      "kinds": ["Namespace"]}]},
                 "parameters": {"labels": [{"key": "team"}]}}})
    client.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sUniqueServiceSelector",
        "metadata": {"name": "unique-selectors"}, "spec": {}})
    for i in range(3000):
        labels = {} if i % 10 == 0 else {"team": "a"}
        client.add_data({"apiVersion": "v1", "kind": "Namespace",
                         "metadata": {"name": f"ns{i:05d}",
                                      "labels": labels}})
    for i in range(300):
        app = f"pair{i // 2}" if i < 40 else f"app{i}"
        client.add_data({"apiVersion": "v1", "kind": "Service",
                         "metadata": {"name": f"svc{i}",
                                      "namespace": f"ns{i:05d}"},
                         "spec": {"selector": {"app": app}}})
    client.audit()  # compiles the device programs
    drv.drop_audit_results()
    return client, drv


def _timed_audit(client, drv):
    timers = profiling.timers()
    drv.drop_audit_results()
    before = timers.snapshot()
    t0 = time.monotonic()
    results = client.audit().results()
    wall = time.monotonic() - t0
    return results, wall, profiling.PhaseTimers.diff(before,
                                                     timers.snapshot())


def test_audit_phases_are_disjoint_and_cover_the_wall():
    client, drv = _client()
    results, wall, phases = _timed_audit(client, drv)
    assert len(results) == 300 + 40
    assert drv.last_audit_path == "single"
    assert AUDIT_PHASES <= set(phases)
    # disjoint phases on the audit's thread sum to at most its wall, and
    # the audit has no long step outside them
    covered = sum(phases.values())
    assert 0.9 * wall <= covered <= wall


def _trace_one_audit(client, drv, log_dir):
    timers = profiling.timers()
    drv.drop_audit_results()
    before = timers.snapshot()
    with profiling.device_trace(log_dir):
        client.audit()
    phases = profiling.PhaseTimers.diff(before, timers.snapshot())
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(line.name, e.name, e.start_ns, e.end_ns)
                           for e in line.events]
    return phases, events


def test_audit_phases_are_spans_under_gk_audit(tmp_path):
    client, drv = _client()
    phases, events = _trace_one_audit(client, drv, str(tmp_path))
    roots = [(ln, s, e) for ln, n, s, e in events if n == "gk/audit"]
    assert len(roots) == 1
    line, r0, r1 = roots[0]
    spans = [(n[len("gk/"):], s, e) for ln, n, s, e in events
             if ln == line and n.startswith("gk/") and n != "gk/audit"]
    assert {n for n, _, _ in spans} >= AUDIT_PHASES
    assert all(r0 <= s and e <= r1 for _, s, e in spans)
    # no phase opens inside another on the audit's thread
    ordered = sorted((s, e) for _, s, e in spans)
    assert all(e0 <= s1 for (_, e0), (s1, _) in zip(ordered, ordered[1:]))
    for name in ("join_materialize", "materialize", "handle_violations"):
        traced = sum(e - s for n, s, e in spans if n == name) / 1e9
        assert traced == pytest.approx(phases[name], abs=1e-3)
    # the audit's device programs are named after their AotJit tags
    programs = {n[len("PjitFunction("):-1] for ln, n, s, e in events
                if n.startswith("PjitFunction(")}
    assert "join" in programs and "run" not in programs


@pytest.mark.parametrize("tag", ["eval", "scan", "slabp", "pairsg", "meshp",
                                 "mesh-slabp", "join", "rows_update"])
def test_aot_tag_names_the_module(tag):
    def run(x):
        return x + 1

    text = AotJit(run, tag=tag).lower(jnp.ones(4)).as_text()
    assert text.splitlines()[0].startswith(
        (f"module @jit_{tag} ", f'module @"jit_{tag}" '))


def test_span_is_a_no_op_without_a_profiler():
    assert profiling.span("audit") is profiling._NO_SPAN


def test_trace_gaps_attributes_gaps_to_the_innermost_span():
    ms = 1_000_000
    devices = {"/device:TPU:0": {
        "ops": [(10 * ms, 30 * ms), (20 * ms, 40 * ms), (60 * ms, 70 * ms)],
        "programs": [("jit_slabp", 10 * ms, 40 * ms),
                     ("jit_join", 60 * ms, 70 * ms)]}}
    spans = [("gk/audit", 0, 100 * ms),
             ("gk/device_sweep", 8 * ms, 45 * ms),
             ("gk/join_materialize", 70 * ms, 90 * ms)]
    r = trace_gaps.reduce_events(devices, spans)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.04)
    # 0-10 and 40-60 under gk/audit only (mids 5 and 50), 70-100 (mid 85)
    # under gk/join_materialize
    assert r["idle_gaps"] == pytest.approx({"gk/audit": 0.03,
                                            "gk/join_materialize": 0.03})
    assert r["programs"] == pytest.approx({"jit_slabp": 0.03,
                                           "jit_join": 0.01})
    assert "| `gk/audit` | 0.030000 | 50.0% |" in trace_gaps.table(r)
    # a CPU trace has no device plane: nothing to reduce
    with pytest.raises(ValueError, match="no device plane"):
        trace_gaps.reduce_events({}, spans)


def test_trace_gaps_reads_a_chip_trace():
    """A trace recorded on one v5e (benchmark/tools/record_trace.py):
    three calls of one jitted lambda, 90,018 ns of fusion each."""
    fixture = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "tests", "data", "fixture.xplane.pb")
    devices, spans = trace_gaps.read_xplane(fixture)
    assert list(devices) == ["/device:TPU:0"] and spans == []
    r = trace_gaps.reduce_events(devices, spans)
    assert set(r["programs"]) == {"jit__lambda"}
    assert r["busy_s"] == pytest.approx(r["programs"]["jit__lambda"],
                                        rel=0.01)
