"""Observability (PR 6 tentpole): end-to-end request tracing, latency
attribution, and the flight recorder.

Covers:
  * W3C traceparent parse/format + stride sampling + the preallocated
    no-op context (an UNSAMPLED request must allocate zero span
    objects on the hot path — asserted via the module allocation
    counter);
  * a traced admission request through 2 real subprocess frontends:
    one trace whose stage spans are complete, ordered, and sum to
    ~wall clock; inbound traceparent honored; X-Trace-Id answered;
    /debug/traces + /debug/templates + /metrics scraped over HTTP and
    validated (what the CI `observability` job boots);
  * audit-plane sweep traces with phase spans + stage histograms;
  * the Registry bucket-skew regression (bounds frozen at first
    registration, mismatch raises);
  * process self-metrics (start time, RSS, FDs, threads, GC);
  * a STRICT text-exposition parse of a loaded Runtime's full /metrics
    output (HELP/TYPE present, +Inf == _count, label escaping);
  * ISSUE 13 (saturation/SLO/trend): OpenMetrics exemplar round-trip
    + content negotiation, batch seal reasons/fill ratio, queue-depth
    saturation probes, engine duty-cycle EMA, build-info gauge, SLO
    burn-rate math + /debug/slo, and the bench_trend watchdog
    (passes on committed history, fails on a synthetic regression,
    unit-change series restarts, did-not-run error records).

Every test runs under a hard SIGALRM timeout.
"""

from __future__ import annotations

import http.client
import json
import re
import signal
import time

import pytest

from gatekeeper_tpu.client import Backend, RegoDriver
from gatekeeper_tpu.control import metrics as gm
from gatekeeper_tpu.control import trace as gt
from gatekeeper_tpu.control.backplane import _StatsAccumulator
from gatekeeper_tpu.control.webhook import (
    MicroBatcher,
    NamespaceLabelHandler,
    ValidationHandler,
    WebhookServer,
)
from gatekeeper_tpu.target import K8sValidationTarget
from gatekeeper_tpu.utils.faults import FAULTS

TARGET = "admission.k8s.gatekeeper.sh"
PER_TEST_TIMEOUT_S = 120


@pytest.fixture(autouse=True)
def _hard_timeout_and_tracer_reset():
    def boom(signum, frame):  # pragma: no cover - only on a real hang
        raise TimeoutError(
            f"test exceeded the {PER_TEST_TIMEOUT_S}s hard timeout")

    old = signal.signal(signal.SIGALRM, boom)
    signal.alarm(PER_TEST_TIMEOUT_S)
    FAULTS.reset()
    rate, slow = gt.TRACER.sample_rate, gt.TRACER.slow_threshold_s
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
        gt.TRACER.configure(rate, slow)
        gt.TRACER.recorder.clear()
        FAULTS.reset()


def _policy_client():
    client = Backend(RegoDriver()).new_client([K8sValidationTarget()])
    client.add_template({
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "k8sneedowner"},
        "spec": {
            "crd": {"spec": {"names": {"kind": "K8sNeedOwner"}}},
            "targets": [{"target": TARGET, "rego": """
package k8sneedowner
violation[{"msg": "no owner label"}] {
  not input.review.object.metadata.labels.owner
}
"""}]},
    })
    client.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sNeedOwner", "metadata": {"name": "need-owner"},
        "spec": {}})
    return client


def _review(name, labels=None, uid=None):
    obj = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": name, "namespace": "d"}}
    if labels:
        obj["metadata"]["labels"] = labels
    return {"apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
            "request": {"uid": uid or f"uid-{name}", "operation": "CREATE",
                        "kind": {"group": "", "version": "v1",
                                 "kind": "Pod"},
                        "name": name, "namespace": "d",
                        "userInfo": {"username": "obs"}, "object": obj}}


# ------------------------------------------------------ traceparent + sampling


def test_traceparent_parse_and_format():
    tid = "4bf92f3577b34da6a3ce929d0e0e4736"
    parsed, sampled = gt.parse_traceparent(
        f"00-{tid}-00f067aa0ba902b7-01")
    assert parsed == tid and sampled is True
    parsed, sampled = gt.parse_traceparent(
        f"00-{tid}-00f067aa0ba902b7-00")
    assert parsed == tid and sampled is False
    # malformed / all-zero never raise, never sample
    for bad in (None, "", "junk", "00-short-x-01", "00-" + "0" * 32
                + "-00f067aa0ba902b7-01", "zz-" + tid + "-gg-01"):
        assert gt.parse_traceparent(bad) == (None, False)
    # STRICT hex ids only: int(x, 16) would accept these, but they
    # would blow up bytes.fromhex when the context rides the backplane
    # frame (regression: 500 + a leaked frontend waiter per request)
    for evil in ("0x" + "a" * 30, "a_" * 16, "+" + "a" * 31,
                 " " + "a" * 31):
        assert gt.parse_traceparent(
            f"00-{evil}-00f067aa0ba902b7-01") == (None, False), evil
    # uppercase ids normalize to lowercase (fromhex-safe either way)
    assert gt.parse_traceparent(
        "00-" + "AB" * 16 + "-00f067aa0ba902b7-01")[0] == "ab" * 16
    hdr = gt.format_traceparent(tid)
    assert gt.parse_traceparent(hdr) == (tid, True)


def test_stride_sampling_and_forced_traceparent():
    tracer = gt.Tracer(sample_rate=0.5, metrics_sink=False)
    kinds = [tracer.start("admission") is gt.NOOP for _ in range(10)]
    assert kinds.count(False) == 5  # every 2nd samples
    tracer.configure(0.0)
    assert tracer.start("admission") is gt.NOOP
    # an inbound sampled traceparent forces tracing past rate 0 AND
    # carries its trace id through
    tid = "ab" * 16
    tr = tracer.start("admission", f"00-{tid}-00f067aa0ba902b7-01")
    assert tr is not gt.NOOP and tr.trace_id == tid
    tr.finish()
    # sample_context (the frontend edge) agrees
    assert tracer.sample_context() is None
    assert tracer.sample_context(f"00-{tid}-00f067aa0ba902b7-01") == tid


def test_unsampled_request_allocates_no_span_objects():
    """The acceptance bar for hot-path cost: with sampling off, a full
    admission round trip through the real HTTP server must not
    construct a single Span/Trace object."""
    gt.TRACER.configure(0.0)
    client = _policy_client()
    handler = ValidationHandler(client, kube=None,
                                batcher=MicroBatcher(client,
                                                     max_wait=0.001))
    server = WebhookServer(handler, NamespaceLabelHandler(()), port=0)
    server.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=10)
        conn.request("POST", "/v1/admit",
                     json.dumps(_review("warm", {"owner": "x"})),
                     {"Content-Type": "application/json"})
        conn.getresponse().read()  # warm the path first
        before = gt.ALLOCATIONS
        for i in range(20):
            conn.request("POST", "/v1/admit",
                         json.dumps(_review(f"p{i}", {"owner": "x"})),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            assert resp.getheader("X-Trace-Id") is None
        assert gt.ALLOCATIONS == before, \
            "unsampled requests allocated span objects on the hot path"
    finally:
        server.stop(drain_timeout=1.0)


# ---------------------------------------------------------- flight recorder


def _mk_trace(tracer, duration, plane="admission"):
    tr = tracer.start(plane, force=True)
    tr.add_span("evaluate", tr.t0, tr.t0 + duration)
    tr.t1 = tr.t0 + duration
    return tr


def test_flight_recorder_keeps_recent_and_slowest():
    rec = gt.FlightRecorder(keep=3)
    tracer = gt.Tracer(sample_rate=1.0, recorder=rec,
                       metrics_sink=False, slow_threshold_s=0)
    durations = [0.1, 5.0, 0.2, 4.0, 0.3, 3.0, 0.05]
    for d in durations:
        tr = _mk_trace(tracer, d)
        rec.record(tr)
    dump = rec.dump()["planes"]["admission"]
    assert len(dump["recent"]) == 3 and len(dump["slowest"]) == 3
    # recent = last three, oldest first
    assert [t["duration_s"] for t in dump["recent"]] == [0.3, 3.0, 0.05]
    # slowest = global top three, slowest first — the 5.0s outlier is
    # retained long after it aged out of the recent ring
    assert [t["duration_s"] for t in dump["slowest"]] == [5.0, 4.0, 3.0]
    # per-plane isolation
    rec.record(_mk_trace(tracer, 9.0, plane="audit"))
    planes = rec.dump()["planes"]
    assert planes["audit"]["slowest"][0]["duration_s"] == 9.0
    assert planes["admission"]["slowest"][0]["duration_s"] == 5.0


def test_slow_trace_logs_structured_line(caplog):
    import logging as pylog

    tracer = gt.Tracer(sample_rate=1.0, slow_threshold_s=0.0001,
                       metrics_sink=False)
    with caplog.at_level(pylog.WARNING, logger="gatekeeper.trace"):
        tr = tracer.start("admission", force=True)
        time.sleep(0.002)
        tr.finish()
    assert any("slow request trace" in r.getMessage()
               for r in caplog.records)


# ------------------------------------------------- registry bucket freeze


def test_histogram_buckets_freeze_at_first_registration():
    """Regression for the bucket-skew bug: two call sites passing
    different bounds for the same metric silently mis-bucketed counts
    against stale lists (m.buckets was re-assigned on every observe)."""
    reg = gm.Registry()
    reg.observe("skew_test_seconds", "h", 0.3, buckets=(0.1, 1.0))
    with pytest.raises(ValueError, match="skew_test_seconds"):
        reg.observe("skew_test_seconds", "h", 0.3,
                    buckets=(0.5, 2.0, 10.0))
    # the original bounds survived, counts landed against them
    reg.observe("skew_test_seconds", "h", 0.05, buckets=(0.1, 1.0))
    text = reg.render()
    assert 'skew_test_seconds_bucket{le="0.1"} 1' in text
    assert 'skew_test_seconds_bucket{le="1"} 2' in text
    assert 'le="0.5"' not in text
    # observe_bucketed enforces the same freeze
    with pytest.raises(ValueError):
        reg.observe_bucketed("skew_test_seconds", "h", (9.9,), [1, 0],
                             0.1, 1)


def test_label_values_are_escaped():
    reg = gm.Registry()
    reg.counter_add("esc_total", "c", kind='we"ird\\na\nme')
    text = reg.render()
    assert 'kind="we\\"ird\\\\na\\nme"' in text


# ---------------------------------------------------- process self-metrics


def test_process_self_metrics_exposed():
    reg = gm.Registry()
    gm.update_process_metrics(reg)
    text = reg.render()
    for name in ("process_start_time_seconds",
                 "process_resident_memory_bytes", "process_open_fds",
                 "process_threads", "python_gc_objects_tracked"):
        assert name in text, f"{name} missing from exposition"
    start = float(re.search(
        r"^process_start_time_seconds (\S+)$", text, re.M).group(1))
    assert 0 < start <= time.time()
    rss = float(re.search(
        r"^process_resident_memory_bytes (\S+)$", text, re.M).group(1))
    assert rss > 1 << 20  # a live interpreter holds > 1MiB


# -------------------------------------------------- exposition strict parse


_EXEMPLAR_RE = re.compile(
    r' # \{trace_id="((?:[^"\\]|\\.)*)"\} (\S+) (\S+)$')


def _parse_exposition_strict(text: str, openmetrics: bool = False
                             ) -> dict:
    """Strict text-format parse: every sample must belong to an
    announced metric family (HELP + TYPE first), histogram +Inf bucket
    must equal _count, label values must round-trip the escaping.
    `openmetrics=True` additionally requires the terminal `# EOF` and
    accepts (collecting) per-bucket exemplar clauses.
    Returns {family: {"type", "samples": [(name, labels, value)]}}
    plus, under the reserved "__exemplars__" key, every
    (sample_line_prefix, trace_id, value, ts) exemplar found."""
    families: dict = {}
    exemplars: list = []
    cur = None
    sample_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})? (\S+)$")
    label_re = re.compile(
        r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"(,|$)')
    lines = text.splitlines()
    if openmetrics:
        assert lines and lines[-1] == "# EOF", \
            "OpenMetrics exposition must end with # EOF"
        lines = lines[:-1]
    orig_lines = lines
    lines = []
    for line in orig_lines:
        m = _EXEMPLAR_RE.search(line)
        if m:
            assert openmetrics, \
                "exemplar syntax leaked into the plain text format"
            exemplars.append((line[: m.start()], m.group(1),
                              float(m.group(2)), float(m.group(3))))
            line = line[: m.start()]
        lines.append(line)
    for line in lines:
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            name = line.split()[2]
            assert name not in families, f"duplicate HELP for {name}"
            families[name] = {"type": None, "samples": []}
            cur = name
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            assert parts[2] == cur, "TYPE does not follow its HELP"
            assert families[cur]["type"] is None, "duplicate TYPE"
            assert parts[3] in ("counter", "gauge", "histogram")
            families[cur]["type"] = parts[3]
            continue
        assert not line.startswith("#"), f"unknown comment: {line}"
        m = sample_re.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name, _, labeltext, value = m.groups()
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        fam = name if name in families else base
        if fam not in families and openmetrics and \
                name.endswith("_total"):
            # OpenMetrics counter naming: the family drops _total, the
            # sample carries it — and the spec REQUIRES counters to
            # sample as _total
            fam = name[:-6]
            assert families.get(fam, {}).get("type") == "counter", \
                f"{name}: _total sample without a counter family"
        if openmetrics and families.get(fam, {}).get("type") \
                == "counter":
            assert name.endswith("_total"), \
                f"OpenMetrics counter sample {name} must end _total"
        assert fam in families, f"sample {name} has no HELP/TYPE"
        assert families[fam]["type"] is not None
        labels = {}
        if labeltext:
            consumed = 0
            for lm in label_re.finditer(labeltext):
                labels[lm.group(1)] = lm.group(2)
                consumed = lm.end()
            assert consumed == len(labeltext), \
                f"bad label syntax: {labeltext!r}"
        float(value)  # must be numeric
        families[fam]["samples"].append((name, labels, float(value)))
    # histogram invariants
    for fam, data in families.items():
        if data["type"] != "histogram":
            continue
        series: dict = {}
        for name, labels, value in data["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items()
                               if k != "le"))
            ent = series.setdefault(key, {})
            if name.endswith("_bucket"):
                ent.setdefault("buckets", {})[labels["le"]] = value
            elif name.endswith("_count"):
                ent["count"] = value
            elif name.endswith("_sum"):
                ent["sum"] = value
        for key, ent in series.items():
            assert "+Inf" in ent.get("buckets", {}), \
                f"{fam}{dict(key)} missing +Inf bucket"
            assert ent["buckets"]["+Inf"] == ent["count"], \
                f"{fam}{dict(key)}: +Inf bucket != _count"
            # cumulative buckets must be monotonic
            prev = 0.0
            for le, v in sorted(
                    ent["buckets"].items(),
                    key=lambda kv: float("inf") if kv[0] == "+Inf"
                    else float(kv[0])):
                assert v >= prev, f"{fam}: non-monotonic buckets"
                prev = v
    families["__exemplars__"] = {"type": "reserved",
                                 "samples": exemplars}
    return families


def test_full_runtime_exposition_parses_strictly():
    """The whole /metrics output of a LOADED runtime — histograms,
    escaped labels, merged bucketed deltas — must satisfy a strict
    text-format parser, so malformed series can never ship again."""
    gt.TRACER.configure(1.0, 10.0)
    client = _policy_client()
    handler = ValidationHandler(client, kube=None,
                                batcher=MicroBatcher(client,
                                                     max_wait=0.001))
    for i in range(5):
        handler.handle(_review(f"ok{i}", {"owner": "me"}))
        handler.handle(_review(f"bad{i}"))
    # a pre-aggregated delta merge (the backplane stats path)
    gm.report_backplane_forward(
        "w0", [1] * (len(gm.FORWARD_BUCKETS) + 1), 0.5,
        len(gm.FORWARD_BUCKETS) + 1)
    gm.report_stage_bucketed(
        "admission", "frontend_parse",
        [2] * (len(gm.STAGE_BUCKETS) + 1), 0.1,
        2 * (len(gm.STAGE_BUCKETS) + 1))
    # a label value that needs escaping
    gm.REGISTRY.counter_add("gatekeeper_tpu_test_escape_total", "t",
                            kind='K8s"Weird\\Kind')
    gm.update_process_metrics()
    families = _parse_exposition_strict(gm.REGISTRY.render())
    assert families["request_duration_seconds"]["type"] == "histogram"
    assert families["gatekeeper_tpu_stage_duration_seconds"]["type"] \
        == "histogram"
    esc = families["gatekeeper_tpu_test_escape_total"]["samples"]
    assert esc[0][1]["kind"] == 'K8s\\"Weird\\\\Kind'
    handler.batcher.stop()


# ----------------------------------------------- single-process trace path


def test_single_process_trace_decomposition_and_header():
    gt.TRACER.configure(1.0, slow_threshold_s=0)
    gt.TRACER.recorder.clear()
    client = _policy_client()
    handler = ValidationHandler(client, kube=None,
                                batcher=MicroBatcher(client,
                                                     max_wait=0.002))
    server = WebhookServer(handler, None, port=0)
    server.start()
    tid = "4bf92f3577b34da6a3ce929d0e0e4736"
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=10)
        conn.request("POST", "/v1/admit",
                     json.dumps(_review("t1", {"owner": "x"})),
                     {"Content-Type": "application/json",
                      "traceparent": f"00-{tid}-00f067aa0ba902b7-01"})
        resp = conn.getresponse()
        resp.read()
        assert resp.getheader("X-Trace-Id") == tid, \
            "inbound traceparent trace id not honored"
        dump = gt.TRACER.recorder.dump()["planes"]["admission"]
        trace = next(t for t in dump["recent"] if t["trace_id"] == tid)
        stages = [s["stage"] for s in trace["spans"]]
        for want in ("frontend_parse", "batch_seal", "evaluate",
                     "serialize"):
            assert want in stages, f"stage {want} missing: {stages}"
        assert trace["status"] == "allow"
    finally:
        server.stop(drain_timeout=1.0)


def test_cache_hit_stage_replaces_evaluate():
    gt.TRACER.configure(1.0, slow_threshold_s=0)
    gt.TRACER.recorder.clear()
    client = _policy_client()
    handler = ValidationHandler(client, kube=None,
                                batcher=MicroBatcher(client,
                                                     max_wait=0.001))
    handler.handle(_review("same", {"owner": "x"}, uid="u1"))
    tr = gt.TRACER.start(gt.ADMISSION, force=True)
    handler.handle(_review("same", {"owner": "x"}, uid="u2"), trace=tr)
    tr.finish()
    stages = [s["stage"] for s in tr.to_dict()["spans"]]
    assert "cache_hit" in stages and "evaluate" not in stages
    handler.batcher.stop()


# ------------------------------------------------- backplane stats plumbing


def test_stats_accumulator_ships_stage_deltas():
    acc = _StatsAccumulator()
    acc.observe(0.001)
    acc.observe_stage("frontend_parse", 0.0002)
    acc.observe_stage("frontend_parse", 0.3)
    acc.observe_stage("some_other_stage", 0.001)
    out = acc.drain("w3")
    assert out["count"] == 1
    stages = out["stages"]
    assert stages["frontend_parse"]["count"] == 2
    assert abs(stages["frontend_parse"]["sum"] - 0.3002) < 1e-6
    assert sum(stages["frontend_parse"]["buckets"]) == 2
    assert stages["some_other_stage"]["count"] == 1
    # drained clean
    assert acc.drain("w3") is None


# --------------------------------------------------------- audit plane trace


def test_audit_sweep_trace_phases_and_histograms():
    from gatekeeper_tpu.control.audit import AuditManager
    from gatekeeper_tpu.control.kube import FakeKube

    gt.TRACER.recorder.clear()
    kube = FakeKube()
    for gvk, namespaced in [(("", "v1", "Namespace"), False),
                            (("", "v1", "Pod"), True)]:
        kube.register_kind(gvk, namespaced=namespaced)
    kube.create({"apiVersion": "v1", "kind": "Namespace",
                 "metadata": {"name": "d"}})
    for i in range(4):
        kube.create({"apiVersion": "v1", "kind": "Pod",
                     "metadata": {"name": f"p{i}", "namespace": "d"}})
    client = _policy_client()
    mgr = AuditManager(kube, client, incremental=True,
                       gc_stale_statuses=False)
    mgr.audit_once()
    mgr.audit_once()  # one incremental sweep too
    mgr.stop()
    dump = gt.TRACER.recorder.dump()["planes"]
    assert "audit" in dump, "audit sweeps must always trace"
    stages_seen = set()
    for t in dump["audit"]["recent"]:
        stages_seen.update(s["stage"] for s in t["spans"])
    for want in ("list_delta_apply", "evaluate", "status_writes"):
        assert want in stages_seen
    statuses = {t["status"] for t in dump["audit"]["recent"]}
    assert {"full_resync", "incremental"} <= statuses
    text = gm.REGISTRY.render()
    assert 'gatekeeper_tpu_stage_duration_seconds_count' \
        '{engine="",plane="audit",stage="evaluate"}' in text


def test_failed_sweep_still_records_error_trace():
    """A sweep that blows up mid-evaluation must still land in the
    flight recorder with status=error — the failing sweeps are exactly
    the ones worth diagnosing after the fact."""
    from gatekeeper_tpu.control.audit import AuditManager
    from gatekeeper_tpu.control.kube import FakeKube

    gt.TRACER.recorder.clear()
    client = _policy_client()

    def boom():
        raise RuntimeError("device on fire")

    client.audit = boom
    mgr = AuditManager(FakeKube(), client, audit_from_cache=True,
                       gc_stale_statuses=False)
    with pytest.raises(RuntimeError):
        mgr.audit_once()
    dump = gt.TRACER.recorder.dump()["planes"]["audit"]["recent"]
    assert dump and dump[-1]["status"] == "error"
    assert "device on fire" in dump[-1]["attrs"]["error"]


# ------------------------------------- full plane: subprocess frontends


def _get(conn_host, port, path):
    conn = http.client.HTTPConnection(conn_host, port, timeout=10)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def test_traced_request_through_subprocess_frontends():
    """The acceptance path: a Runtime with 2 pre-forked frontend
    PROCESSES at sample rate 1.0 — a traced request yields ONE trace
    whose stage spans are complete, ordered, and sum to ~wall clock;
    /metrics and /debug/* validate over real HTTP."""
    from gatekeeper_tpu.control.main import Runtime, build_parser

    args = build_parser().parse_args([
        "--fake-kube", "--port", "0", "--prometheus-port", "0",
        "--disable-cert-rotation", "--health-addr", ":0",
        "--operation", "webhook", "--admission-workers", "2",
        "--trace-sample-rate", "1.0", "--trace-slow-threshold", "0"])
    rt = Runtime(args)
    rt.start()
    # load a real template so the traced request evaluates something
    # and /debug/templates has per-kind state to report
    rt.opa.add_template({
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": "k8sneedowner"},
        "spec": {
            "crd": {"spec": {"names": {"kind": "K8sNeedOwner"}}},
            "targets": [{"target": TARGET, "rego": """
package k8sneedowner
violation[{"msg": "no owner label"}] {
  not input.review.object.metadata.labels.owner
}
"""}]}})
    rt.opa.add_constraint({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sNeedOwner", "metadata": {"name": "need-owner"},
        "spec": {}})
    tid = "aabbccddeeff00112233445566778899"
    try:
        deadline = time.monotonic() + 10
        while rt.backplane.connected < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert rt.backplane.connected == 2
        mport = rt.metrics_server.server_address[1]
        hport = rt.health.port
        conn = http.client.HTTPConnection("127.0.0.1", rt.frontends.port,
                                          timeout=15)
        conn.request("POST", "/v1/admit?timeout=10s",
                     json.dumps(_review("traced")),
                     {"Content-Type": "application/json",
                      "traceparent": f"00-{tid}-00f067aa0ba902b7-01"})
        resp = conn.getresponse()
        resp.read()
        assert resp.getheader("X-Trace-Id") == tid
        # the engine records the trace at respond time; poll the dump
        trace = None
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            status, body = _get("127.0.0.1", mport, "/debug/traces")
            assert status == 200
            planes = json.loads(body).get("planes", {})
            for t in planes.get("admission", {}).get("recent", []):
                if t["trace_id"] == tid:
                    trace = t
                    break
            if trace:
                break
            time.sleep(0.1)
        assert trace is not None, "traced request never reached the " \
            "flight recorder"
        stages = [s["stage"] for s in trace["spans"]]
        # >= 5 named stages spanning frontend -> backplane -> engine ->
        # eval path
        for want in ("frontend_parse", "backplane_forward",
                     "batch_seal", "evaluate", "serialize", "respond"):
            assert want in stages, f"{want} missing from {stages}"
        assert len(stages) >= 5
        # complete + ordered: spans start in order, live inside the
        # trace window, and sum to ~wall clock (sequential stages;
        # small gaps for untimed glue are expected)
        starts = [s["start_s"] for s in trace["spans"]]
        assert starts == sorted(starts), "stage spans out of order"
        total = trace["duration_s"]
        span_sum = sum(s["duration_s"] for s in trace["spans"])
        assert all(0 <= s["start_s"] <= total + 1e-6
                   for s in trace["spans"])
        assert span_sum <= total * 1.10 + 1e-4
        assert span_sum >= total * 0.5, \
            f"spans cover too little of the trace: {span_sum} / {total}"
        # a second, uid-churned request serves from the decision cache
        # and still decomposes (cache_hit path)
        conn.request("POST", "/v1/admit?timeout=10s",
                     json.dumps(_review("traced", uid="uid-2")),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.read()
        tid2 = resp.getheader("X-Trace-Id")
        assert tid2 and tid2 != tid
        # /metrics: engine-side stages appear immediately; the
        # frontend-shipped stage deltas land within one S-frame
        # interval (2s)
        deadline = time.monotonic() + 8
        text = ""
        while time.monotonic() < deadline:
            status, body = _get("127.0.0.1", mport, "/metrics")
            assert status == 200
            text = body.decode()
            if ('stage="frontend_parse"' in text
                    and 'stage="evaluate"' in text):
                break
            time.sleep(0.2)
        for frag in ('plane="admission"', 'stage="evaluate"',
                     'stage="frontend_parse"', 'stage="respond"',
                     "gatekeeper_tpu_traces_total",
                     "process_resident_memory_bytes",
                     # ISSUE 13: the capacity-attribution families a
                     # single scrape of a loaded plane must carry
                     "gatekeeper_tpu_batch_seal_total",
                     "gatekeeper_tpu_batch_fill_ratio_bucket",
                     'gatekeeper_tpu_queue_depth'
                     '{engine="",queue="admission"}',
                     'gatekeeper_tpu_queue_depth'
                     '{engine="0",queue="backplane_engine"}',
                     "gatekeeper_tpu_device_duty_cycle",
                     "gatekeeper_tpu_build_info",
                     "gatekeeper_tpu_slo_burn_rate",
                     "gatekeeper_tpu_slo_target"):
            assert frag in text, f"{frag} missing from /metrics"
        _parse_exposition_strict(text)
        # OpenMetrics negotiation on the same loaded runtime: a stage
        # histogram bucket must carry a trace-id exemplar that
        # RESOLVES in the flight recorder (/debug/traces)
        conn2 = http.client.HTTPConnection("127.0.0.1", mport,
                                           timeout=10)
        conn2.request("GET", "/metrics",
                      headers={"Accept":
                               "application/openmetrics-text"})
        resp = conn2.getresponse()
        om = resp.read().decode()
        conn2.close()
        assert resp.getheader("Content-Type").startswith(
            "application/openmetrics-text")
        fams = _parse_exposition_strict(om.rstrip("\n"),
                                        openmetrics=True)
        ex_tids = {e[1] for e in fams["__exemplars__"]["samples"]
                   if e[0].startswith(
                       "gatekeeper_tpu_stage_duration_seconds")}
        assert ex_tids, "no stage bucket carries a trace-id exemplar"
        _status, tr_body = _get("127.0.0.1", mport, "/debug/traces")
        recorded = {t["trace_id"] for t in json.loads(tr_body)
                    .get("planes", {}).get("admission", {})
                    .get("recent", [])}
        assert ex_tids & recorded, \
            f"exemplar ids {ex_tids} resolve to no recorded trace"
        # /debug/templates on the metrics port, /debug/traces on the
        # health port (same registry), unknown endpoints 404
        status, body = _get("127.0.0.1", mport, "/debug/templates")
        assert status == 200
        tmpl = json.loads(body)
        assert "K8sNeedOwner" in tmpl["templates"]
        status, _ = _get("127.0.0.1", hport, "/debug/traces")
        assert status == 200
        # /debug/slo answers the compliance picture
        status, body = _get("127.0.0.1", mport, "/debug/slo")
        assert status == 200
        slo = json.loads(body)
        names = {o["name"] for o in slo["objectives"]}
        assert "admission_p99_latency" in names
        assert "availability" in names
        status, body = _get("127.0.0.1", mport, "/debug/nope")
        assert status == 404
        assert "available" in json.loads(body)
    finally:
        rt.stop()


# ----------------------------------- exemplars + OpenMetrics negotiation


def test_openmetrics_exemplar_round_trip():
    """An observation carrying a trace-id exemplar renders in the
    OpenMetrics dialect on exactly the bucket it landed in, round-trips
    the strict parser, and never leaks into the plain text format."""
    reg = gm.Registry()
    tid = "4bf92f3577b34da6a3ce929d0e0e4736"
    reg.observe("om_stage_seconds", "h", 0.03, buckets=(0.01, 0.1, 1.0),
                exemplar=tid, stage="evaluate")
    reg.observe("om_stage_seconds", "h", 5.0, buckets=(0.01, 0.1, 1.0),
                exemplar="ff" * 16, stage="evaluate")  # +Inf overflow
    reg.observe("om_stage_seconds", "h", 0.02, buckets=(0.01, 0.1, 1.0),
                stage="evaluate")  # unsampled: no exemplar attached
    om = reg.render(openmetrics=True)
    fams = _parse_exposition_strict(om, openmetrics=True)
    exemplars = fams["__exemplars__"]["samples"]
    assert len(exemplars) == 2, om
    by_tid = {e[1]: e for e in exemplars}
    line, _tid, value, ts = by_tid[tid]
    assert 'le="0.1"' in line  # the bucket 0.03 landed in
    assert value == 0.03 and ts > 0
    assert 'le="+Inf"' in by_tid["ff" * 16][0]
    # the LATEST exemplar per bucket wins
    tid2 = "ab" * 16
    reg.observe("om_stage_seconds", "h", 0.05, buckets=(0.01, 0.1, 1.0),
                exemplar=tid2, stage="evaluate")
    om2 = reg.render(openmetrics=True)
    assert tid2 in om2 and tid not in om2
    # plain text format: identical series, zero exemplar syntax
    text = reg.render()
    _parse_exposition_strict(text)
    assert "trace_id" not in text and "# EOF" not in text


def test_metrics_content_negotiation_over_http():
    """GET /metrics honors Accept: a scraper asking for
    application/openmetrics-text gets the exemplar-bearing dialect
    (+ # EOF); everyone else gets the classic text format."""
    reg = gm.Registry()
    reg.observe("nego_seconds", "h", 0.3, buckets=(0.1, 1.0),
                exemplar="cd" * 16)
    # counters in BOTH naming styles: the OpenMetrics dialect must
    # sample every counter as <family>_total (strict scrapers —
    # Prometheus's openmetrics parser included — reject the whole
    # exposition otherwise), while the text format keeps legacy names
    reg.counter_add("legacy_count", "c", 3)
    reg.counter_add("modern_total", "c", 4)
    server = gm.serve(0, registry=reg, addr="127.0.0.1")
    try:
        port = server.server_address[1]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/metrics",
                     headers={"Accept": "application/openmetrics-text; "
                                        "version=1.0.0"})
        resp = conn.getresponse()
        body = resp.read().decode()
        assert resp.getheader("Content-Type").startswith(
            "application/openmetrics-text")
        assert body.rstrip().endswith("# EOF")
        assert 'trace_id="' + "cd" * 16 + '"' in body
        assert "# TYPE legacy_count counter" in body
        assert "\nlegacy_count_total 3" in body
        assert "# TYPE modern counter" in body
        assert "\nmodern_total 4" in body
        _parse_exposition_strict(body.rstrip("\n"), openmetrics=True)
        # no Accept (or a plain one): classic text format, no exemplars,
        # legacy counter names untouched
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = resp.read().decode()
        assert resp.getheader("Content-Type").startswith("text/plain")
        assert "trace_id" not in body and "# EOF" not in body
        assert "\nlegacy_count 3" in body
        assert "legacy_count_total" not in body
        conn.close()
    finally:
        server.shutdown()


def test_trace_span_feeds_stage_exemplar():
    """A finished sampled trace attaches its id to the stage-histogram
    buckets it observed (control/trace.py -> report_stage exemplar)."""
    tid = "1234567890abcdef1234567890abcdef"
    tr = gt.TRACER.start(gt.ADMISSION, f"00-{tid}-00f067aa0ba902b7-01")
    with tr.span("evaluate"):
        time.sleep(0.001)
    tr.finish()
    om = gm.REGISTRY.render(openmetrics=True)
    stage_lines = [ln for ln in om.splitlines()
                   if ln.startswith("gatekeeper_tpu_stage_duration_"
                                    "seconds_bucket") and tid in ln]
    assert stage_lines, "trace id never reached a stage bucket exemplar"


# ------------------------------------------ batch economics + saturation


def _seal_counts(plane="admission"):
    snap = gm.REGISTRY.snapshot(("gatekeeper_tpu_batch_seal_total",))
    ent = snap.get("gatekeeper_tpu_batch_seal_total") or {}
    # label values ordered by sorted label names: (plane, reason)
    return {k[1]: v for k, v in
            ((tuple(lk), v) for lk, v in ent.get("values") or [])
            if k[0] == plane}


def test_batch_seal_reasons_and_fill_ratio():
    evaluate = lambda reviews: [[] for _ in reviews]  # noqa: E731

    # FULL: two submits against max_batch=2 seal a full batch
    before = _seal_counts()
    b = MicroBatcher(None, max_wait=0.5, max_batch=2, evaluate=evaluate)
    import threading as _threading
    t = _threading.Thread(
        target=lambda: b.submit(_review("f1", {"owner": "x"}),
                                timeout=10))
    t.start()
    b.submit(_review("f2", {"owner": "x"}), timeout=10)
    t.join(10)
    b.stop()
    after = _seal_counts()
    assert after.get("full", 0) > before.get("full", 0), (before, after)

    # MAX_WAIT: a lone submit with a far deadline seals when the
    # collection window elapses
    before = after
    b = MicroBatcher(None, max_wait=0.01, max_batch=64,
                     evaluate=evaluate)
    b.submit(_review("w1", {"owner": "x"}), timeout=30)
    b.stop()
    after = _seal_counts()
    assert after.get("max_wait", 0) > before.get("max_wait", 0), \
        (before, after)

    # DEADLINE: a tight member deadline forces the seal well before
    # the (long) collection window
    before = after
    b = MicroBatcher(None, max_wait=5.0, max_batch=64,
                     evaluate=evaluate)
    b.submit(_review("d1", {"owner": "x"}),
             deadline=time.monotonic() + 0.8)
    b.stop()
    after = _seal_counts()
    assert after.get("deadline", 0) > before.get("deadline", 0), \
        (before, after)

    # fill-ratio histogram populated alongside
    text = gm.REGISTRY.render()
    assert "gatekeeper_tpu_batch_fill_ratio_bucket" in text
    m = re.search(r'gatekeeper_tpu_batch_fill_ratio_count'
                  r'\{plane="admission"\} (\d+)', text)
    assert m and int(m.group(1)) >= 3


def test_queue_depth_probe_and_stream_pending_gauge():
    calls = []
    gm.register_saturation_probe(
        "test-probe", lambda: calls.append(1) or gm.report_queue_depth(
            "admission", 7))
    try:
        gm.run_saturation_probes()
        assert calls
        text = gm.REGISTRY.render()
        assert ('gatekeeper_tpu_queue_depth'
                '{engine="",queue="admission"} 7') in text
    finally:
        gm.unregister_saturation_probe("test-probe")
    # a raising probe must not fail the scrape
    gm.register_saturation_probe(
        "test-bad", lambda: (_ for _ in ()).throw(RuntimeError("x")))
    try:
        gm.run_saturation_probes()
    finally:
        gm.unregister_saturation_probe("test-bad")
    # stream backlog gauge (satellite: was logs-only)
    gm.report_stream_pending(42)
    assert ('gatekeeper_tpu_audit_stream_pending_events 42'
            in gm.REGISTRY.render())


def test_build_info_gauge():
    gm.report_build_info()
    text = gm.REGISTRY.render()
    m = re.search(r'^gatekeeper_tpu_build_info\{(.*)\} 1$', text, re.M)
    assert m, "build info gauge missing"
    labels = m.group(1)
    for want in ("version=", "jax_version=", "platform=",
                 "device_count="):
        assert want in labels, labels


# ------------------------------------------------------ duty cycle (EMA)


def test_duty_cycle_ema():
    from gatekeeper_tpu.ir import TpuDriver

    drv = TpuDriver()
    # saturate the first sample window: raw clamps to 1.0 and seeds
    # the EMA directly (no decay from a meaningless zero)
    drv.note_busy(100.0)
    time.sleep(0.06)
    first = drv.duty_cycle()
    assert first == pytest.approx(1.0)
    # a scrape storm (second sample inside the window) reuses the
    # sample — the window is widened here so a loaded CI runner's
    # scheduler stall between the two calls can't flake the assert
    assert drv.duty_cycle(min_window_s=30.0) == pytest.approx(first)
    # an idle window decays the EMA toward zero at alpha=0.3
    time.sleep(0.06)
    second = drv.duty_cycle()
    assert second == pytest.approx(0.7, abs=0.01)
    # eval paths actually accumulate busy time (note_eval seconds arg)
    drv.note_eval("K8sX", "device", seconds=0.5)
    time.sleep(0.06)
    assert drv.duty_cycle() > second


# ----------------------------------------------------------- SLO layer


def _slo_registry():
    reg = gm.Registry()
    for v in (0.01, 0.02, 0.05, 0.05, 0.05):  # all under 0.1
        reg.observe("request_duration_seconds", "h", v,
                    admission_status="allow")
    reg.counter_add("request_count", "c", 100, admission_status="allow")
    return reg


def test_slo_burn_rates_multi_window():
    from gatekeeper_tpu.control.slo import SloEngine, default_objectives

    reg = _slo_registry()
    eng = SloEngine(default_objectives(admission_p99_s=0.1,
                                       availability_target=0.99),
                    registry=reg, sample_interval_s=15)
    eng.sample(now=0.0)
    # healthy traffic: zero burn on every objective/window
    rates = eng.burn_rates(now=400.0)
    for slo, by_window in rates.items():
        for w, ent in by_window.items():
            assert ent["burn_rate"] == 0.0, (slo, w, ent)
    # 10 good + 10 shed in the next window: bad fraction 0.5 against a
    # 1% budget = burn 50 on both windows (the 1h anchor is the same
    # sample while history is short — lifetime-honest)
    reg.counter_add("request_count", "c", 10, admission_status="allow")
    reg.counter_add("request_count", "c", 10, admission_status="shed")
    rates = eng.burn_rates(now=400.0)
    av = rates["availability"]
    assert av["5m"]["burn_rate"] == pytest.approx(50.0)
    assert av["5m"]["bad"] == 10 and av["5m"]["total"] == 20
    # latency: 3 of 8 in-window requests past the 0.1s threshold burn
    # the p99 budget (bad fraction 0.375 over a 1% budget = 37.5)
    for _ in range(5):
        reg.observe("request_duration_seconds", "h", 0.05,
                    admission_status="allow")
    for _ in range(3):
        reg.observe("request_duration_seconds", "h", 2.0,
                    admission_status="allow")
    rates = eng.burn_rates(now=401.0)
    lat = rates["admission_p99_latency"]["5m"]
    assert lat["bad"] == 3 and lat["total"] == 8
    assert lat["burn_rate"] == pytest.approx(37.5)
    # export refreshes the gauges
    eng.export(now=402.0)
    text = reg.render()
    assert 'gatekeeper_tpu_slo_burn_rate{slo="availability"' \
        ',window="5m"}' in text
    assert 'gatekeeper_tpu_slo_target{slo="admission_p99_latency"} ' \
        '0.99' in text


def test_slo_window_anchoring_prefers_full_window():
    """With enough history, the 5m window reads a 5m-old anchor while
    the 1h window reads an older one — the two burn rates diverge when
    the bad traffic is recent."""
    from gatekeeper_tpu.control.slo import SloEngine, default_objectives

    reg = _slo_registry()
    eng = SloEngine(default_objectives(availability_target=0.99),
                    registry=reg, sample_interval_s=15)
    eng.sample(now=0.0)
    # an hour of healthy samples
    for t in range(1, 240):
        eng.sample(now=t * 15.0)
    # a recent burst of bad traffic (inside the last 5m)
    reg.counter_add("request_count", "c", 10, admission_status="error")
    now = 240 * 15.0
    rates = eng.burn_rates(now=now)
    av = rates["availability"]
    # both windows see the same 10 bad events, but over different
    # anchors; the FAST window must see a full-strength burn
    assert av["5m"]["burn_rate"] > 0
    assert av["5m"]["window_actual_s"] >= 300
    assert av["1h"]["window_actual_s"] >= 3600
    assert av["5m"]["total"] <= av["1h"]["total"]


def test_slo_objective_validation():
    from gatekeeper_tpu.control.slo import SloObjective

    with pytest.raises(ValueError):
        SloObjective("x", "latency", 1.0, "m", threshold_s=0.1)
    with pytest.raises(ValueError):
        SloObjective("x", "latency", 0.99, "m")  # no threshold
    with pytest.raises(ValueError):
        SloObjective("x", "weird", 0.99, "m")


def test_debug_slo_provider_shape():
    from gatekeeper_tpu.control.slo import SloEngine, default_objectives

    reg = _slo_registry()
    eng = SloEngine(default_objectives(), registry=reg,
                    sample_interval_s=15)
    eng.sample(now=0.0)
    status = eng.status(now=10.0)
    names = {o["name"] for o in status["objectives"]}
    assert names == {"admission_p99_latency", "availability",
                     "violation_detection_p99"}
    for o in status["objectives"]:
        assert "windows" in o and "target" in o
    assert status["alert_reference_burn_rates"]["5m"] == 14.4


# ------------------------------------------------- perf-trend watchdog


def _bench_trend():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "bench_trend.py")
    spec = importlib.util.spec_from_file_location("bench_trend", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_round(d, n, doc):
    import os
    with open(os.path.join(d, f"BENCH_r{n:02d}.json"), "w") as f:
        json.dump({"parsed": doc}, f)


def test_bench_trend_check_passes_on_committed_history():
    """The acceptance gate: the committed BENCH_r05-r07 trajectory must
    pass --check (scale changes between rounds restart series via the
    unit string; they are not regressions)."""
    import io
    import os
    from contextlib import redirect_stdout

    bt = _bench_trend()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bt.main(["--dir", root, "--check"])
    assert rc == 0, buf.getvalue()
    report = buf.getvalue()
    assert "# Benchmark trend" in report
    assert "r05" in report and "r07" in report


def test_bench_trend_fails_on_synthetic_regression(tmp_path):
    import io
    from contextlib import redirect_stdout

    bt = _bench_trend()
    d = str(tmp_path)
    _write_round(d, 1, {"metric": "full_audit_wall_clock_s",
                        "value": 1.0, "unit": "u"})
    _write_round(d, 2, {"metric": "full_audit_wall_clock_s",
                        "value": 1.1, "unit": "u"})
    _write_round(d, 3, {"metric": "full_audit_wall_clock_s",
                        "value": 1.6, "unit": "u"})  # >25% vs best=1.0
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bt.main(["--dir", d, "--check"])
    assert rc == 1
    assert "full_audit_wall_clock_s" in buf.getvalue()
    # higher-is-better direction flags drops (config series, which
    # carries a unit — the top-level admission_rps COPY is ungated)
    c5 = {"metric": "admission_requests_per_sec", "unit": "rps"}
    _write_round(d, 4, {"metric": "full_audit_wall_clock_s",
                        "value": 1.0, "unit": "u", "admission_rps": 900,
                        "configs": {"5": {**c5, "value": 1000}}})
    _write_round(d, 5, {"metric": "full_audit_wall_clock_s",
                        "value": 1.0, "unit": "u", "admission_rps": 350,
                        "configs": {"5": {**c5, "value": 400}}})
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bt.main(["--dir", d, "--check"])
    assert rc == 1
    # exactly the unit-carrying config series flagged, not the copy
    assert "c5.admission_requests_per_sec" in buf.getvalue()
    assert "**350" not in buf.getvalue()


def test_bench_trend_unit_change_restarts_series(tmp_path):
    import io
    from contextlib import redirect_stdout

    bt = _bench_trend()
    d = str(tmp_path)
    _write_round(d, 1, {"metric": "audit_wall_clock_s", "value": 0.1,
                        "unit": "s (x 1000 objects)"})
    # 10x slower, but at 10x the scale: a series restart, not a
    # regression
    _write_round(d, 2, {"metric": "audit_wall_clock_s", "value": 1.0,
                        "unit": "s (x 10000 objects)"})
    with redirect_stdout(io.StringIO()):
        rc = bt.main(["--dir", d, "--check"])
    assert rc == 0
    # same unit, same slowdown: NOW it flags
    _write_round(d, 3, {"metric": "audit_wall_clock_s", "value": 2.0,
                        "unit": "s (x 10000 objects)"})
    with redirect_stdout(io.StringIO()):
        rc = bt.main(["--dir", d, "--check"])
    assert rc == 1


def test_bench_trend_error_configs_reported_not_regressed(tmp_path):
    import io
    from contextlib import redirect_stdout

    bt = _bench_trend()
    d = str(tmp_path)
    _write_round(d, 1, {"metric": "full_audit_wall_clock_s",
                        "value": 1.0, "unit": "u", "configs": {
                            "5": {"metric": "admission_requests_per_sec",
                                  "value": 1000, "unit": "rps"}}})
    # config 5 DID NOT RUN in round 2: an error record, not a zero —
    # must be listed as such and must not flag a regression
    _write_round(d, 2, {"metric": "full_audit_wall_clock_s",
                        "value": 1.0, "unit": "u", "configs": {
                            "5": {"config": 5,
                                  "error": "loadgen crashed"}}})
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bt.main(["--dir", d, "--check"])
    assert rc == 0, buf.getvalue()
    out = buf.getvalue()
    assert "Did not run" in out and "loadgen crashed" in out


def test_bench_trend_recovers_truncated_tail():
    """The r05 shape: parsed=null and the headline JSON line truncated
    at the FRONT inside the captured tail — the loader recovers the
    trailing top-level fields instead of dropping the round."""
    bt = _bench_trend()
    doc = bt._recover_fragment(
        'path": "single", "mutate_audit_s": 1.132, "setup_s": 2.8, '
        '"configs": {"3": {"config": 3, "metric": "audit_wall_clock_s", '
        '"value": 7.666, "unit": "s (50000 pods)"}}}')
    assert doc is not None
    assert doc["mutate_audit_s"] == 1.132
    assert doc["configs"]["3"]["value"] == 7.666
    metrics, errors, units = bt.flatten_round(doc)
    assert metrics["c3.audit_wall_clock_s"] == 7.666
    assert units["c3.audit_wall_clock_s"] == "s (50000 pods)"


def test_bench_trend_ended_series_never_gates(tmp_path):
    """A metric whose series ended before the newest round (config
    dropped/renamed) is immutable history — its old final regression
    must not fail every future --check forever."""
    import io
    from contextlib import redirect_stdout

    bt = _bench_trend()
    d = str(tmp_path)
    _write_round(d, 1, {"metric": "audit_wall_clock_s", "value": 1.0,
                        "unit": "u"})
    _write_round(d, 2, {"metric": "audit_wall_clock_s", "value": 2.0,
                        "unit": "u"})  # regressed... in history
    # newest round no longer carries the metric at all
    _write_round(d, 3, {"metric": "other_wall_clock_s", "value": 5.0,
                        "unit": "v"})
    with redirect_stdout(io.StringIO()):
        rc = bt.main(["--dir", d, "--check"])
    assert rc == 0
    # --all-history still SHOWS it in the report
    buf = io.StringIO()
    with redirect_stdout(buf):
        bt.main(["--dir", d, "--all-history"])
    assert "audit_wall_clock_s" in buf.getvalue()


def test_slo_engine_stop_zeroes_burn_gauges():
    from gatekeeper_tpu.control.slo import SloEngine, default_objectives

    reg = _slo_registry()
    eng = SloEngine(default_objectives(availability_target=0.99),
                    registry=reg, sample_interval_s=15)
    eng.sample(now=0.0)
    reg.counter_add("request_count", "c", 10, admission_status="shed")
    eng.export(now=400.0)
    m = re.search(r'gatekeeper_tpu_slo_burn_rate\{slo="availability",'
                  r'window="5m"\} (\S+)', reg.render())
    assert m and float(m.group(1)) > 0
    eng.stop()
    text = reg.render()
    for line in text.splitlines():
        if line.startswith("gatekeeper_tpu_slo_burn_rate"):
            assert line.endswith(" 0"), line
