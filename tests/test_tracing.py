"""Tracing subsystem tests (serving/tracing.py): W3C traceparent parsing,
seeded deterministic ids, OTLP encoding, the drop-never-block exporter
contract (including the chaos ``span_export`` faults), and the GOLDEN SPAN
TREE — a seeded router + seeded server driving a real request through a
429-shedding first hop so the tree is byte-reproducible: router root → 2
dispatch hops (hop 2 a ``retry_429``) → server request → five phase
children, with the hop-2 ``deadline.remaining_ms`` strictly smaller than
hop 1's (the gateway forwards only the REMAINING budget).
"""

import json
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.serving import chaos, tracing
from aws_k8s_ansible_provisioner_tpu.serving.router import (
    BackendPool, RouterHandler, RouterMetrics)
from aws_k8s_ansible_provisioner_tpu.serving.server import build_state, serve
from aws_k8s_ansible_provisioner_tpu.utils.tokenizer import ByteTokenizer

MODEL_NAME = "tiny-qwen3"
ENGINE_PORT = 18250
SHED_PORT = 18251


# -- traceparent (W3C) -------------------------------------------------------


def test_traceparent_roundtrip():
    ctx = tracing.SpanContext("0af7651916cd43dd8448eb211c80319c",
                              "b7ad6b7169203331", sampled=True)
    hdr = tracing.format_traceparent(ctx)
    assert hdr == ("00-0af7651916cd43dd8448eb211c80319c-"
                   "b7ad6b7169203331-01")
    back = tracing.parse_traceparent(hdr)
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    assert back.sampled
    # unsampled flag survives the round trip too
    ctx.sampled = False
    back = tracing.parse_traceparent(tracing.format_traceparent(ctx))
    assert back is not None and not back.sampled
    # uppercase input is normalized (the wire format is case-insensitive)
    assert tracing.parse_traceparent(hdr.upper()).trace_id == ctx.trace_id


@pytest.mark.parametrize("bad", [
    None, "", "garbage",
    "00-abc-def-01",                                            # short ids
    "00-" + "0" * 32 + "-b7ad6b7169203331-01",                  # zero trace
    "00-0af7651916cd43dd8448eb211c80319c-" + "0" * 16 + "-01",  # zero span
    "ff-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01",  # version ff
    "00-zzf7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01",  # non-hex
    "0-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01",   # bad version
    "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331",     # no flags
])
def test_traceparent_malformed_treated_as_absent(bad):
    assert tracing.parse_traceparent(bad) is None


# -- seeded ids / sampling ---------------------------------------------------


class _Recorder:
    """Exporter stand-in: records (span, service) synchronously."""

    def __init__(self):
        self.items = []

    def export(self, span, service_name):
        self.items.append((span, service_name))
        return True


def test_seeded_tracers_draw_identical_id_sequences():
    a = tracing.Tracer("svc", seed=42)
    b = tracing.Tracer("svc", seed=42)
    for _ in range(5):
        sa, sb = a.start_span("x"), b.start_span("x")
        assert sa.context.trace_id == sb.context.trace_id
        assert sa.context.span_id == sb.context.span_id
        assert len(sa.context.trace_id) == 32
        assert len(sa.context.span_id) == 16
        int(sa.context.trace_id, 16), int(sa.context.span_id, 16)
    # unseeded tracers must NOT collide (entropy ids)
    c, d = tracing.Tracer("svc"), tracing.Tracer("svc")
    assert c.start_span("x").context.trace_id \
        != d.start_span("x").context.trace_id


def test_parent_based_sampling_and_unsampled_not_exported():
    rec = _Recorder()
    never = tracing.Tracer("svc", exporter=rec, sample=0.0, seed=1)
    root = never.start_span("root")
    assert not root.context.sampled
    # the unsampled child inherits the decision; ids still exist (they are
    # echoed into responses for log correlation) but nothing is exported
    child = never.start_span("child", parent=root.context)
    assert not child.context.sampled
    never.finish(child)
    never.finish(root)
    assert rec.items == []
    # a sampled parent's child exports even through a sample=0.0 tracer
    # (parent-based policy: the ROOT decided once, the tree follows)
    always = tracing.Tracer("svc", exporter=rec, sample=1.0, seed=2)
    up = always.start_span("upstream")
    assert up.context.sampled
    cont = never.start_span("continued", parent=up.context)
    assert cont.context.sampled
    never.finish(cont)
    assert [s.name for s, _ in rec.items] == ["continued"]


def test_finish_clamps_end_before_start():
    t = tracing.Tracer("svc", seed=3)
    s = t.start_span("x", start_ns=1000)
    t.finish(s, end_ns=500)
    assert s.end_ns == s.start_ns == 1000


# -- OTLP/JSON encoding ------------------------------------------------------


def test_encode_spans_otlp_shape_and_attr_typing():
    t = tracing.Tracer("svc-a", seed=4)
    s1 = t.start_span("op", kind=tracing.KIND_SERVER, start_ns=10,
                      attributes={"b": True, "i": 7, "f": 1.5, "s": "x"})
    s1.error("boom")
    t.finish(s1, end_ns=20)
    parent = t.start_span("p", start_ns=5)
    s2 = t.start_span("child", parent=parent.context, start_ns=11)
    t.finish(s2, end_ns=12)
    req = tracing.encode_spans([(s1, "svc-a"), (s2, "svc-b")])
    assert len(req["resourceSpans"]) == 2     # grouped per service
    by_svc = {}
    for rs in req["resourceSpans"]:
        svc = rs["resource"]["attributes"][0]["value"]["stringValue"]
        by_svc[svc] = rs["scopeSpans"][0]["spans"]
    d1 = by_svc["svc-a"][0]
    assert d1["kind"] == tracing.KIND_SERVER
    assert d1["startTimeUnixNano"] == "10"    # proto JSON: int64 as string
    assert d1["endTimeUnixNano"] == "20"
    assert d1["status"] == {"code": 2, "message": "boom"}
    attrs = {a["key"]: a["value"] for a in d1["attributes"]}
    assert attrs["b"] == {"boolValue": True}      # bool BEFORE int: bool is
    assert attrs["i"] == {"intValue": "7"}        # an int subclass
    assert attrs["f"] == {"doubleValue": 1.5}
    assert attrs["s"] == {"stringValue": "x"}
    d2 = by_svc["svc-b"][0]
    assert d2["parentSpanId"] == parent.context.span_id
    assert "status" not in d2


# -- the exporter: batch, drop-on-failure, never-block -----------------------


class _FakeCollector(BaseHTTPRequestHandler):
    """Minimal OTLP/HTTP receiver: stores parsed /v1/traces payloads."""
    received = None     # set per-instance-class in _collector()
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        payload = json.loads(self.rfile.read(n)) if n else {}
        if self.path == "/v1/traces":
            type(self).received.append(payload)
        body = b"{}"
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def _collector():
    """A fresh fake-collector server on an ephemeral port."""
    cls = type("Collector", (_FakeCollector,), {"received": []})
    srv = ThreadingHTTPServer(("127.0.0.1", 0), cls)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, cls.received


def _span_names(payloads):
    names = []
    for p in payloads:
        for rs in p.get("resourceSpans", []):
            for ss in rs.get("scopeSpans", []):
                names += [s["name"] for s in ss.get("spans", [])]
    return names


def test_exporter_batches_to_collector():
    srv, received = _collector()
    exp = tracing.OTLPHTTPExporter(f"http://127.0.0.1:{srv.server_port}",
                                   flush_interval_s=0.05)
    try:
        before = tracing.metrics.spans_exported.total()
        t = tracing.Tracer("svc", exporter=exp, seed=5)
        for i in range(3):
            t.finish(t.start_span(f"op{i}"))
        assert exp.flush(5.0)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline \
                and len(_span_names(received)) < 3:
            time.sleep(0.01)
        assert sorted(_span_names(received)) == ["op0", "op1", "op2"]
        assert tracing.metrics.spans_exported.total() - before == 3
    finally:
        exp.shutdown()
        srv.shutdown()


def test_exporter_dead_endpoint_drops_and_counts():
    """A collector that refuses connections costs telemetry, never raises
    into (or blocks) the caller."""
    exp = tracing.OTLPHTTPExporter("http://127.0.0.1:1",     # nothing listens
                                   flush_interval_s=0.05, timeout_s=0.5)
    try:
        d0 = tracing.metrics.spans_dropped.total()
        f0 = tracing.metrics.export_failures.total()
        t = tracing.Tracer("svc", exporter=exp, seed=6)
        t0 = time.monotonic()
        for i in range(4):
            t.finish(t.start_span(f"op{i}"))
        assert time.monotonic() - t0 < 0.5      # enqueue-only on this side
        assert exp.flush(5.0)
        assert tracing.metrics.spans_dropped.total() - d0 == 4
        assert tracing.metrics.export_failures.total() - f0 >= 1
    finally:
        exp.shutdown()


def test_exporter_full_queue_drops_without_blocking():
    exp = tracing.OTLPHTTPExporter("http://127.0.0.1:1", queue_max=2,
                                   flush_interval_s=0.05)
    # park the worker first so the bounded queue actually fills
    exp._stop.set()
    exp._q.put_nowait(None)
    exp._thread.join(timeout=5.0)
    assert not exp._thread.is_alive()
    d0 = tracing.metrics.spans_dropped.total()
    t = tracing.Tracer("svc", seed=7)     # exporter driven directly below
    assert exp.export(t.finish(t.start_span("a")), "svc")
    assert exp.export(t.finish(t.start_span("b")), "svc")
    assert not exp.export(t.finish(t.start_span("c")), "svc")   # full: drop
    assert tracing.metrics.spans_dropped.total() - d0 == 1


@pytest.mark.parametrize("mode,params", [
    ("refuse", {}),
    ("5xx", {}),
    ("hang", {"hang_s": 0.05}),
])
def test_chaos_span_export_faults_drop_not_fail(mode, params):
    """All three collector misbehaviors (refuse / hang / 5xx) resolve to
    dropped-and-counted spans on the BACKGROUND thread; the export() side
    never blocks or raises, and a later batch (fault disarmed) delivers."""
    srv, received = _collector()
    chaos.reset()
    chaos.get().inject("span_export", mode=mode, times=1, **params)
    exp = tracing.OTLPHTTPExporter(f"http://127.0.0.1:{srv.server_port}",
                                   flush_interval_s=0.05)
    try:
        d0 = tracing.metrics.spans_dropped.total()
        t = tracing.Tracer("svc", exporter=exp, seed=8)
        t0 = time.monotonic()
        t.finish(t.start_span("victim"))
        assert time.monotonic() - t0 < 0.5      # hang mode: worker-only
        assert exp.flush(5.0)
        assert tracing.metrics.spans_dropped.total() - d0 == 1
        assert chaos.get().stats()["span_export"]["fired"] == 1
        assert "victim" not in _span_names(received)
        # fault consumed: the next batch reaches the collector
        t.finish(t.start_span("survivor"))
        assert exp.flush(5.0)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline \
                and "survivor" not in _span_names(received):
            time.sleep(0.01)
        assert "survivor" in _span_names(received)
    finally:
        chaos.reset()
        exp.shutdown()
        srv.shutdown()


# -- the golden span tree ----------------------------------------------------


class SheddingBackend(BaseHTTPRequestHandler):
    """A replica that sheds EVERY completion at admission (429 +
    Retry-After) — nothing generated, so the router's retry is safe and the
    hop settles as ``shed_429`` with the next hop a ``retry_429``."""
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(n)
        body = json.dumps({"error": {"message": "shed", "type": "overloaded",
                                     "code": "engine_overloaded"}}).encode()
        self.send_response(429)
        self.send_header("Content-Type", "application/json")
        self.send_header("Retry-After", "1")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


class ShedFirstPool(BackendPool):
    """Deterministic candidate order: the shedding replica first, always —
    the golden tree needs hop 1 = shed, hop 2 = the real engine."""

    def __init__(self, shed_addr, real_addr):
        super().__init__(f"{shed_addr},{real_addr}", cooldown_s=30.0)
        self._order = [shed_addr, real_addr]

    def pick(self, affinity_key=None):
        return list(self._order)


@pytest.fixture(scope="module")
def traced_stack():
    """One real engine + one always-shedding stub behind the real router,
    with injectable tracers (the tests install fresh seeded ones)."""
    tok = ByteTokenizer()
    cfg = tiny_qwen3(vocab_size=tok.vocab_size, eos_token_id=tok.eos_token_id)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    serving = ServingConfig(weights_dtype="bf16", model=MODEL_NAME,
                            max_decode_slots=4, max_cache_len=128,
                            prefill_buckets=(16, 32, 64), dtype="float32")
    state = build_state(serving, model_cfg=cfg, params=params, tokenizer=tok)
    ready, stop = threading.Event(), threading.Event()
    threading.Thread(target=serve,
                     args=(state, "127.0.0.1", ENGINE_PORT, ready, stop),
                     daemon=True).start()
    assert ready.wait(30)
    shed = ThreadingHTTPServer(("127.0.0.1", SHED_PORT), SheddingBackend)
    threading.Thread(target=shed.serve_forever, daemon=True).start()
    old = (RouterHandler.pool, RouterHandler.metrics, RouterHandler.tracer)
    RouterHandler.pool = ShedFirstPool(f"127.0.0.1:{SHED_PORT}",
                                       f"127.0.0.1:{ENGINE_PORT}")
    RouterHandler.metrics = RouterMetrics()
    router = ThreadingHTTPServer(("127.0.0.1", 0), RouterHandler)
    threading.Thread(target=router.serve_forever, daemon=True).start()
    yield router, state
    router.shutdown()
    shed.shutdown()
    stop.set()
    (RouterHandler.pool, RouterHandler.metrics, RouterHandler.tracer) = old


def _run_golden(router, state):
    """One traced request through shed → retry → engine with FRESH
    identically-seeded tracers; returns (recorded spans, response body)."""
    rec = _Recorder()
    RouterHandler.tracer = tracing.Tracer("tpu-serve-router", exporter=rec,
                                          seed=1234)
    state.tracer = tracing.Tracer("tpu-serve-engine", exporter=rec,
                                  seed=5678)
    req = urllib.request.Request(
        f"http://127.0.0.1:{router.server_port}/v1/completions",
        data=json.dumps({"model": MODEL_NAME, "prompt": "golden trace",
                         "max_tokens": 4, "seed": 1}).encode(),
        headers={"Content-Type": "application/json",
                 "X-Request-Deadline-Ms": "30000"})
    with urllib.request.urlopen(req, timeout=120) as r:
        body = json.loads(r.read())
    # Quiescence before the tracers go: both handlers finish their spans
    # AFTER writing the response (server: phase children then
    # server.request; router: the hop, then router.request last), so the
    # client can hold the body while they are still emitting — and
    # RouterHandler reads its class-level tracer again at finish. Returning
    # early lost the tail of the tree in a loaded run (PRs 21, 23).
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        names = [s.name for s, _ in list(rec.items)]
        if "router.request" in names and "server.request" in names:
            break
        time.sleep(0.005)
    RouterHandler.tracer = None
    state.tracer = None
    return list(rec.items), body


def _tree(items):
    spans = {"router.dispatch": [], "phases": [], "engine": []}
    for s, svc in items:
        if s.name.startswith("engine."):
            # the engine loop's own spans (one per device dispatch, ids
            # from the dispatch record's seq): not part of the request tree
            spans["engine"].append(s)
        elif s.name == "router.request":
            spans["root"] = s
            assert svc == "tpu-serve-router"
        elif s.name == "router.dispatch":
            spans["router.dispatch"].append(s)
        elif s.name == "server.request":
            spans["server"] = s
            assert svc == "tpu-serve-engine"
        else:
            spans["phases"].append(s)
    spans["router.dispatch"].sort(
        key=lambda s: s.attributes["dispatch.index"])
    return spans


def test_golden_span_tree(traced_stack):
    router, state = traced_stack
    items, body = _run_golden(router, state)
    t = _tree(items)
    root, hops, server = t["root"], t["router.dispatch"], t["server"]
    phases = t["phases"]

    # -- identity: one trace, W3C wire widths, ids echoed to the client
    trace_id = root.context.trace_id
    assert len(trace_id) == 32 and int(trace_id, 16) != 0
    for s in [root, server] + hops + phases:
        assert s.context.trace_id == trace_id
        assert len(s.context.span_id) == 16 and int(s.context.span_id, 16)
    assert body["usage"]["trace_id"] == trace_id
    assert body["usage"]["span_id"] == server.context.span_id

    # -- topology: root → 2 hops; the RETRY hop parents the server span,
    # whose five phase children complete the tree
    assert not root.parent_span_id and root.kind == tracing.KIND_SERVER
    assert len(hops) == 2
    for h in hops:
        assert h.parent_span_id == root.context.span_id
        assert h.kind == tracing.KIND_CLIENT
    assert server.parent_span_id == hops[1].context.span_id
    assert server.kind == tracing.KIND_SERVER
    assert [p.name for p in phases] == ["admission", "queue_wait",
                                        "prefill", "decode", "stream_out"]
    for p in phases:
        assert p.parent_span_id == server.context.span_id

    # -- hop semantics: first attempt shed, second is the 429 retry
    assert hops[0].attributes["dispatch.kind"] == "first"
    assert hops[0].attributes["dispatch.outcome"] == "shed_429"
    assert hops[0].attributes["backend.addr"] == f"127.0.0.1:{SHED_PORT}"
    assert hops[1].attributes["dispatch.kind"] == "retry_429"
    assert hops[1].attributes["dispatch.outcome"] == "relayed"
    assert hops[1].attributes["backend.addr"] == f"127.0.0.1:{ENGINE_PORT}"
    assert hops[1].attributes["http.status_code"] == 200
    assert root.attributes["http.status_code"] == 200

    # -- the deadline SHRINKS across hops: the shed attempt + backoff ate
    # real budget the retry hop (and the backend) must not see again
    d1 = hops[0].attributes["deadline.remaining_ms"]
    d2 = hops[1].attributes["deadline.remaining_ms"]
    assert d2 < d1 <= 30000
    assert server.attributes["deadline.remaining_ms"] <= d2

    # -- phases: a monotonic non-overlapping chain covering the request
    assert server.start_ns <= phases[0].start_ns
    for prev, cur in zip(phases, phases[1:]):
        assert prev.end_ns == cur.start_ns        # boundaries shared exactly
        assert cur.start_ns <= cur.end_ns
    assert phases[-1].end_ns <= server.end_ns
    assert phases[2].end_ns > phases[2].start_ns    # prefill did real work
    assert phases[3].end_ns > phases[3].start_ns    # decode did real work


def test_golden_span_tree_is_reproducible(traced_stack):
    """Two runs under identically-seeded fresh tracers produce the SAME
    ids for the SAME tree positions (timestamps differ; identity must not)."""
    router, state = traced_stack

    def skeleton(items):
        t = _tree(items)
        spans = ([t["root"]] + t["router.dispatch"] + [t["server"]]
                 + t["phases"])
        return [(s.name, s.context.trace_id, s.context.span_id,
                 s.parent_span_id) for s in spans]

    items_a, _ = _run_golden(router, state)
    items_b, _ = _run_golden(router, state)
    assert skeleton(items_a) == skeleton(items_b)
