"""OpenAI-compatible HTTP front end for the TPU serving engine.

This is the API surface the reference smoke-tests through its gateway
(`llm-d-test.yaml`): ``GET /v1/models`` must list the served model (the repo's one
hard assertion, ``llm-d-test.yaml:54-59``) and ``POST /v1/completions`` must
complete a prompt (``:61-78``). We implement the same OpenAI wire format vLLM
exposes, plus ``/v1/chat/completions`` with wired-in chat templates (an explicit
improvement — the reference ships templates it never applies, SURVEY.md §7 item 7)
and Prometheus ``/metrics`` on the same port (the scrape contract at
``otel-observability-setup.yaml:359-368``).

stdlib-only (ThreadingHTTPServer): the serving pod needs no web framework, and
request threads only tokenize/detokenize + block on queues — all compute batches
inside the engine thread.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import queue
import tempfile
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import List, Optional

from aws_k8s_ansible_provisioner_tpu.serving import (autoscaler, capacity,
                                                     devmon, flightrec,
                                                     metrics, slo, tracing)
from aws_k8s_ansible_provisioner_tpu.serving.engine import (
    ContextLengthExceeded, EngineOverloaded)

log = logging.getLogger("tpu_serve")

# Wire names for the end-to-end deadline (relative milliseconds): the router
# forwards the header unchanged and bounds its own read timeout by it, the
# server parses either form into Request.deadline_s, the engine enforces it.
DEADLINE_HEADER = "X-Request-Deadline-Ms"
DEADLINE_FIELD = "deadline_ms"


def _now() -> int:
    # API `created` fields are true wall-clock stamps — the one sanctioned
    # wall-clock path (tpulint R1); everything deadline-shaped in this file
    # is time.monotonic().
    return int(tracing.wall_clock())


def _bubble_pct(eng) -> Optional[float]:
    """Host-bubble share of the decode timeline: bubble / (bubble + busy)."""
    bubble = eng.metrics.decode_bubble_seconds.total()
    busy = eng.metrics.device_busy_seconds.total()
    if bubble + busy <= 0:
        return None
    return round(100.0 * bubble / (bubble + busy), 2)


def _device_health() -> dict:
    """Compact device block for /healthz (the fleet poller relays it to
    /debug/fleet and tputop): HBM occupancy + drift verdict, duty cycle,
    and the decode program's MFU. Full table lives at /debug/roofline."""
    snap = devmon.get().snapshot()
    hbm = snap["hbm"]
    dec = snap["programs"].get("decode") or {}
    return {
        "hbm_drift": hbm["verdict"],
        "hbm_live_bytes": int(hbm["live_bytes"]),
        "hbm_compiled_bytes": int(hbm["compiled_bytes"]),
        "hbm_drift_bytes": int(hbm["drift_bytes"]),
        "duty_cycle": round(snap["duty_cycle"], 4),
        "mfu": round(dec.get("mfu", 0.0), 4),
        "membw_util": round(dec.get("membw_util", 0.0), 4),
        "dma_wait_fraction": round(snap["dma_wait_fraction"], 4),
    }


class _NotifyQueue(queue.Queue):
    """Request out_queue that signals a shared Event on every put.

    A multi-choice (n > 1) stream handler can't block on n stdlib queues at
    once; blocking on this one shared event replaces the ~100 Hz nonblocking
    poll-and-sleep sweep that burned CPU per concurrent stream (advisor r4).
    """

    def __init__(self, event: threading.Event):
        super().__init__()
        self.event = event

    def put(self, item, *a, **kw):
        super().put(item, *a, **kw)
        self.event.set()


class ServerState:
    """Everything the handler needs: engine, tokenizer, templater, identity."""

    def __init__(self, engine, tokenizer, templater, model_name: str):
        self.engine = engine
        self.tokenizer = tokenizer
        self.templater = templater
        self.model_name = model_name
        self.started = _now()
        # Request tracing (set by build_state from serving config; tests
        # inject seeded tracers). None = spans off entirely.
        self.tracer: Optional[tracing.Tracer] = None
        # Serializes /debug/profile captures (one JAX trace at a time).
        self.profile_lock = threading.Lock()
        # Graceful drain (r8): set by serve() so the SIGTERM handler /
        # /admin/drain can stop the process after the drain quiesces.
        self.stop_event: Optional[threading.Event] = None
        self._drain_lock = threading.Lock()
        self._drain_watcher: Optional[threading.Thread] = None
        # /v1/* requests currently inside a handler thread: the drain
        # watcher exits only when the ENGINE is idle AND every handler has
        # finished writing its response — zero dropped in-flight requests.
        self._inflight = 0
        self._inflight_lock = threading.Lock()

    def inflight_inc(self):
        with self._inflight_lock:
            self._inflight += 1

    def inflight_dec(self):
        with self._inflight_lock:
            self._inflight -= 1

    @property
    def inflight(self) -> int:
        with self._inflight_lock:
            return self._inflight

    def begin_drain(self, timeout_s: Optional[float] = None,
                    exit_when_idle: bool = True) -> float:
        """Flip the engine to draining and (by default) arm the watcher that
        stops the server once in-flight work finishes — the SIGTERM /
        preStop path. ``exit_when_idle=False`` drains WITHOUT scheduling an
        exit (operator takes a replica out of rotation but keeps the
        process; /admin/undrain reverses it). Idempotent."""
        t = self.engine.begin_drain(timeout_s)
        if not exit_when_idle:
            return t
        with self._drain_lock:
            if self._drain_watcher is None:
                self._drain_watcher = threading.Thread(
                    target=self._drain_watch, daemon=True,
                    name="drain-watcher")
                self._drain_watcher.start()
        return t

    def end_drain(self):
        self.engine.end_drain()

    def _drain_watch(self):
        """Stop the server once the drain quiesces: engine idle (no active
        slots, no queue, no chunk walk) and no /v1 handler still writing.
        Past the drain deadline (+grace for the deadline reaper to finish
        the stragglers it cancelled) the stop is forced — the reaper
        guarantees slots/pages were released exactly once either way."""
        eng = self.engine
        while True:
            if not eng.draining:        # drain cancelled via /admin/undrain
                with self._drain_lock:
                    self._drain_watcher = None
                return
            idle = (not eng._active_slots() and not eng.pending
                    and eng._chunk is None and self.inflight == 0)
            if idle or time.monotonic() > eng._drain_deadline + 5.0:
                break
            time.sleep(0.05)
        log.info("drain complete (inflight=%d active=%d queued=%d); "
                 "stopping server", self.inflight,
                 len(eng._active_slots()), len(eng.pending))
        if self.stop_event is not None:
            self.stop_event.set()


def _format_logprobs(tokenizer, ids, lp_data, k: int, chat: bool,
                     text_len: int = -1, base_offset: int = 0):
    """OpenAI logprobs payloads. Completions: {tokens, token_logprobs,
    top_logprobs, text_offset}; chat: {content: [{token, logprob,
    top_logprobs}]}. Token strings decode per-id (lossy for multi-byte
    merges — the same behavior as vLLM's per-token decode). ``text_len``
    truncates the payload to the tokens whose text survived a stop-string
    cut, so logprobs and choices[].text stay aligned; ``base_offset``
    shifts text_offset past an echoed prompt."""
    toks = [tokenizer.decode([t]) for t in ids]
    offsets, pos = [], base_offset
    for t in toks:
        offsets.append(pos)
        pos += len(t)
    n = len(toks)
    if text_len >= 0:
        # text_len counts GENERATED text only; offsets start at base_offset
        n = sum(1 for o in offsets if o - base_offset < text_len) \
            if text_len else 0
        n = max(n, 0)
    toks, offsets = toks[:n], offsets[:n]
    lp_data = lp_data[:n]
    own = [None if d is None else d[0] for d in lp_data]

    def top_list(d):
        if d is None:
            return []
        return [(tokenizer.decode([tid]), v) for tid, v in d[1][:k]]

    if chat:
        return {"content": [
            {"token": toks[i], "logprob": own[i],
             "top_logprobs": [{"token": t, "logprob": v}
                              for t, v in top_list(lp_data[i])]}
            for i in range(min(len(toks), len(lp_data)))]}
    return {"tokens": toks,
            "token_logprobs": own,
            "top_logprobs": [dict(top_list(d)) for d in lp_data],
            "text_offset": offsets}


def _wait_budget_s(engine, req) -> Optional[float]:
    """Server-side cap for a blocking collect: the request's own deadline
    plus grace — the ENGINE owns deadline enforcement (cancel + slot/page
    release + "timeout" finish); this budget is only the backstop that
    keeps a handler thread from hanging on a wedged engine loop. Without a
    deadline the configured default (request_timeout_s) applies; a config
    of 0 means genuinely unbounded (None), not some other magic constant."""
    if req.t_deadline:
        return max(1.0, req.t_deadline - time.monotonic()) + 30.0
    cap = float(engine.serving.request_timeout_s or 0)
    return cap + 30.0 if cap > 0 else None


def _apply_stop_strings(text: str, stops: List[str]) -> Optional[str]:
    """Return text truncated at the earliest stop string, or None if no match."""
    cut = None
    for s in stops:
        if s:
            i = text.find(s)
            if i >= 0 and (cut is None or i < cut):
                cut = i
    return text[:cut] if cut is not None else None


class Handler(BaseHTTPRequestHandler):
    state: ServerState  # set by serve()
    protocol_version = "HTTP/1.1"
    # Per-request trace context (class default so keep-alive connections
    # never leak a previous request's ids into an untraced one).
    _trace_ctx: Optional[tracing.SpanContext] = None

    # -- plumbing -----------------------------------------------------------

    def log_message(self, fmt, *args):
        log.debug("%s - %s", self.address_string(), fmt % args)

    def _json(self, code: int, obj: dict, headers: Optional[dict] = None):
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()
        self.wfile.write(body)

    def _error(self, code: int, message: str,
               err_type: str = "invalid_request_error",
               err_code: Optional[str] = None,
               headers: Optional[dict] = None):
        err = {"message": message, "type": err_type,
               "code": err_code if err_code else code}
        if self._trace_ctx is not None:
            # log correlation: the ids to paste into Tempo / grep from the
            # collector when a request fails
            err["trace_id"] = self._trace_ctx.trace_id
            err["span_id"] = self._trace_ctx.span_id
        # ring-only black-box breadcrumb: 5xx edges land in /debug/events
        # beside the engine's own events (4xx are client errors — noise)
        if code >= 500:
            flightrec.record("http_error", None, code=code, type=err_type)
        self._json(code, {"error": err}, headers=headers)

    def _overloaded(self, e: EngineOverloaded):
        """429 + Retry-After: the structured load-shed answer. The router
        treats this as a routable signal (another replica may have room);
        clients back off by the hint. A DRAINING shed is 503 instead (the
        replica is leaving, not full) with the X-TPU-Draining marker the
        router keys on to re-route without dead-marking — shed at
        admission, so re-routing is always safe."""
        if e.reason == "draining":
            return self._error(503, str(e), "unavailable_error",
                               err_code="draining",
                               headers={"Retry-After":
                                        str(int(e.retry_after_s + 0.5)),
                                        "X-TPU-Draining": "1"})
        self._error(429, str(e), "overloaded_error",
                    err_code=f"engine_overloaded:{e.reason}",
                    headers={"Retry-After": str(int(e.retry_after_s + 0.5))})

    def _read_body(self) -> Optional[dict]:
        try:
            n = int(self.headers.get("Content-Length", 0))
            raw = self.rfile.read(n) if n else b"{}"
            return json.loads(raw or b"{}")
        except (ValueError, json.JSONDecodeError):
            self._error(400, "request body is not valid JSON")
            return None

    # -- GET ----------------------------------------------------------------

    def do_GET(self):
        path = self.path.split("?")[0]
        if path == "/v1/models":
            base = {
                "id": self.state.model_name,
                "object": "model",
                "created": self.state.started,
                "owned_by": "tpu-serve",
                "max_model_len": self.state.engine.max_len,
            }
            # LoRA adapters are served as model ids (the vLLM --enable-lora
            # contract): request model == adapter name routes to it
            adapters = [{**base, "id": name, "parent": self.state.model_name}
                        for name in self.state.engine.lora_names]
            self._json(200, {"object": "list", "data": [base] + adapters})
        elif path == "/metrics":
            # Engine metrics + per-chip HBM gauges from THIS process's
            # runtime (the engine owns the chips; the node exporter derives
            # tpu_duty_cycle_percent from our busy-seconds counter).
            from aws_k8s_ansible_provisioner_tpu.k8s.metrics_exporter import (
                render_engine_chips)

            slo.get().export()       # refresh the burn-rate gauges
            devmon.get().export()    # refresh the tpu_device_* family
            capacity.get().export()  # refresh tpu_capacity_* (drop-not-fail)
            autoscaler.get().export()  # refresh tpu_autoscale_* (R12: the
            # replica process has no controller, so these render at their
            # defaults — same both-routes contract as the gateway families)
            # Content negotiation: OpenMetrics (exemplars + # EOF) when the
            # scraper asks for it, classic Prometheus text otherwise.
            om = "application/openmetrics-text" in \
                (self.headers.get("Accept") or "")
            text = (self.state.engine.metrics.registry.render(om)
                    + tracing.metrics.registry.render(om)
                    + flightrec.metrics.registry.render(om)
                    + slo.metrics.registry.render(om)
                    + devmon.metrics.registry.render(om)
                    + capacity.metrics.registry.render(om)
                    + autoscaler.metrics.registry.render(om)
                    + metrics.pipeline.registry.render(om)
                    + metrics.compile_stages.registry.render(om)
                    + metrics.params_by_part.registry.render(om)
                    + metrics.window_pool.registry.render(om)
                    + render_engine_chips())
            if om:
                text += "# EOF\n"
                ctype = ("application/openmetrics-text; version=1.0.0; "
                         "charset=utf-8")
            else:
                ctype = "text/plain; version=0.0.4"
            body = text.encode()
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif path in ("/health", "/healthz", "/ping"):
            eng = self.state.engine
            stalled = eng.stalled_for_s
            status = "ok"
            if eng.last_error:
                status = "degraded"
            if eng.draining:
                # deliberate lifecycle state, not a failure: /healthz stays
                # 200 so the K8s LIVENESS probe never kills a pod
                # mid-drain; readiness (/readyz) is what flips to 503
                status = "draining"
            if stalled:
                # a wedged device dispatch hangs inside step(); K8s liveness
                # keys off this to restart the pod (the engine thread cannot
                # recover a hung XLA call itself)
                status = "stalled"
            dev = _device_health()
            self._json(503 if stalled else 200, {
                "status": status,
                "draining": bool(eng.draining),
                "model": self.state.model_name,
                "uptime_s": _now() - self.state.started,
                "active_requests": len(eng._active_slots()),
                "queue_depth": len(eng.pending),
                # /v1 requests inside a handler thread (parse/tokenize/
                # stream-out) are invisible to the two engine counters
                # above; external drain orchestration (deploy/probes.py
                # rolling_restart) needs the same inflight==0 signal the
                # in-process drain watcher uses before it may kill the
                # process
                "inflight": self.state.inflight,
                "stalled_for_s": round(stalled, 1) or None,
                "last_error": eng.last_error or None,
                # the autotuned decode batch-block (ISSUE r6): operators can
                # confirm the served kernel config without scraping metrics
                "decode_bblock": getattr(eng, "decode_bblock", None),
                # decode pipeline (r9): knob state plus the host-bubble share
                # of device wall time — sync mode shows the real gap the
                # pipeline would hide; pipelined steady state trends to 0.
                "decode_pipeline": eng.serving.decode_pipeline,
                "decode_bubble_pct": _bubble_pct(eng),
                # Ragged mixed-batch attention (ISSUE 14): knob state plus
                # the pipeline drain ledger — drains by reason and the
                # drain rate (drains per dispatch). Mixed traffic on the
                # ragged path should hold drain_rate ~0 where the legacy
                # path pays one drain per admission.
                "ragged_attention": eng.serving.ragged_attention,
                # Feature paths riding the ragged pipeline (ISSUE 16):
                # 0 means spec/LoRA/guided still de-pipeline to the sync
                # floor (the PR-14 fallback arm).
                "ragged_features": eng.serving.ragged_features,
                "pipeline": metrics.pipeline.snapshot(),
                "weights_dtype": eng.serving.weights_dtype,
                "kv_dtype": eng.serving.kv_dtype,
                "paged": eng.paged,
                # AOT manifest adoption summary (serving/aot.py): operators
                # confirm the replica serves a pre-verified program set (and
                # its HBM ledger headroom) straight off the probe; null means
                # no manifest was loaded (plain lazy/warmup compilation).
                "aot": getattr(eng, "aot", None),
                # Robustness counters (r7): operators (and the chaos suite)
                # read shed/deadline/stall/preemption totals here without a
                # /metrics scrape+parse.
                "shed_total": int(eng.metrics.requests_shed.total()),
                "deadline_expired_total":
                    int(eng.metrics.deadline_expired.total()),
                "watchdog_stalls_total":
                    int(eng.metrics.watchdog_stalls.total()),
                "preemptions_total": int(eng.metrics.preemptions.total()),
                "max_queue_depth": eng.serving.max_queue_depth or None,
                "request_timeout_s": eng.serving.request_timeout_s or None,
                # Fleet-view block (this PR): the router's /debug/fleet and
                # tools/tputop.py read throughput, pool pressure, SLO burn
                # rates, and the flight recorder's last anomaly from the
                # SAME probe the reconcile loop already polls — no extra
                # scrape+parse round trip per replica.
                "tokens_per_second":
                    round(eng.metrics.tokens_per_second.value(), 2),
                "kv_pages_total": int(eng.metrics.kv_pages_total.value()),
                "kv_pages_in_use": int(eng.metrics.kv_pages_in_use.value()),
                # Evictable share + tier-2 ledger (ISSUE 20): "pool full" vs
                # "pool full of reusable prefixes" are different capacity
                # situations, and the tier split says where prefix hits are
                # actually being served from (hbm share / host restore /
                # miss) without a /metrics scrape+parse.
                "kv_pages_evictable":
                    int(eng.metrics.kv_pages_evictable.value()),
                "prefix_tier_hits": {
                    t: int(eng.metrics.prefix_tier_hits.value(tier=t))
                    for t in ("hbm", "host", "miss")},
                "kv_host_tier": (
                    eng.host_tier.stats()
                    if getattr(eng, "host_tier", None) is not None else None),
                "slo": slo.get().snapshot(),
                "slo_burning": slo.get().burning(),
                "flight": flightrec.get().summary(),
                # Device panel (serving/devmon.py): HBM occupancy + drift
                # verdict and the roofline headline numbers, for the
                # router's fleet poller / tputop / probes.py L3. The drift
                # verdict WARNS, never kills: a ledger miss is a diagnosis,
                # not a liveness failure.
                "device": dev,
                "hbm_drift": dev["hbm_drift"],
                # Capacity block (serving/capacity.py): offered load vs the
                # ceiling, saturation, and the seconds-to-saturation
                # forecast — relayed by the router's poller into its
                # /debug/capacity fleet aggregation. Recommendation-only:
                # nothing in-process actuates on it.
                "capacity": capacity.get().snapshot(),
            })
        elif path == "/readyz":
            # Readiness, distinct from liveness (r8): a DRAINING replica is
            # alive (finishing streams; liveness must not kill it) but not
            # ready (K8s stops routing Service traffic to it; the preStop +
            # SIGTERM path relies on this ordering). Stalled is both.
            eng = self.state.engine
            if eng.draining:
                self._json(503, {"status": "draining"},
                           headers={"X-TPU-Draining": "1"})
            elif eng.stalled_for_s:
                self._json(503, {"status": "stalled"})
            else:
                self._json(200, {"status": "ready"})
        elif path == "/load":
            # Tiny load snapshot for the gateway's ~1 Hz poller (router.py
            # load-aware routing — VERDICT r3 next #5): kept separate from
            # /health (which runs stall diagnostics) and /metrics (whose
            # render cost scales with series count). ``draining`` removes
            # the replica from the router's rotation without dead-marking
            # it (it re-enters within one poll of draining going false).
            eng = self.state.engine
            self._json(200, {"active": len(eng._active_slots()),
                             "queued": len(eng.pending),
                             "slots": eng.num_slots,
                             "draining": bool(eng.draining)})
        elif path == "/admin/drain":
            # K8s lifecycle httpGet handlers can only GET; same semantics
            # as the POST (default timeout, exit when idle)
            self._admin_drain({})
        elif path == "/debug/profile":
            self._profile()
        elif path == "/debug/roofline":
            # Per-program roofline attribution table (serving/devmon.py):
            # measured s/step vs the analytical floor, MFU, bandwidth
            # utilization, dma-wait share, plus the live HBM ledger — the
            # PERF.md model rendered against production traffic.
            self._json(200, devmon.get().snapshot())
        elif path == "/debug/capacity":
            # This replica's capacity/saturation/forecast view
            # (serving/capacity.py) — the per-replica drill-down under the
            # router's fleet-level /debug/capacity aggregation.
            self._json(200, capacity.get().snapshot())
        elif path == "/debug/events":
            # the flight recorder's live ring, oldest first (?last=N caps it)
            import urllib.parse

            n, q = 100, self.path.split("?", 1)
            if len(q) == 2:
                vals = urllib.parse.parse_qs(q[1]).get("last")
                if vals and vals[0].isdigit():
                    n = min(int(vals[0]), 4096)
            self._json(200, {"events": flightrec.get().tail(n)})
        elif path.startswith("/debug/flight/"):
            # anomaly snapshot (or live timeline) for one request id
            rid = path[len("/debug/flight/"):]
            dump = flightrec.get().dump_for(rid)
            if dump is None and rid.isdigit():
                # engine request ids are ints; the URL hands us a string
                dump = flightrec.get().dump_for(int(rid))
            if dump is None:
                return self._error(404, f"no flight timeline for {rid!r} "
                                        "(snapshots keep the last anomalies "
                                        "only; see /debug/events)")
            self._json(200, dump)
        else:
            self._error(404, f"no route for GET {path}")

    def _profile(self):
        """Capture a JAX/XLA device trace while the engine serves.

        The reference's trace pipeline accepts and drops traces (its only
        exporter is `debug`, otel-observability-setup.yaml:633-636 — SURVEY.md
        §5 tracing gap); here profiling is real: a perfetto/TensorBoard-
        compatible trace is written server-side and its path returned.
        `?ms=N` controls the capture window (default 1000, max 30000).
        """
        import urllib.parse

        import jax as _jax

        q = self.path.split("?", 1)
        ms = 1000
        if len(q) == 2:
            vals = urllib.parse.parse_qs(q[1]).get("ms")
            if vals and vals[0].isdigit():
                ms = min(int(vals[0]), 30000)
        out_dir = os.path.join(
            tempfile.gettempdir(), "tpu-serve-profile",
            f"{time.strftime('%Y%m%d-%H%M%S')}-{uuid.uuid4().hex[:8]}")
        with self.state.profile_lock:
            try:
                _jax.profiler.start_trace(out_dir)
                time.sleep(ms / 1000.0)
            finally:
                try:
                    _jax.profiler.stop_trace()
                # tpulint: disable=R3 admin endpoint — a failed profiler stop is reported to the caller as a 500, not propagated into the handler thread
                except Exception as e:
                    self._error(500, f"profiler stop failed: {e}",
                                "internal_error")
                    return
        self._json(200, {"trace_dir": out_dir, "window_ms": ms,
                         "view": "tensorboard --logdir <trace_dir> "
                                 "(Profile tab) or perfetto"})

    # -- POST ---------------------------------------------------------------

    def do_POST(self):
        self._trace_ctx = None      # keep-alive: clear the previous
        path = self.path.split("?")[0]          # request's trace identity
        body = self._read_body()
        if body is None:
            return
        track = path.startswith("/v1/")
        if track:
            # the drain watcher waits for this to hit zero: a response still
            # being written is in-flight work a graceful shutdown must not
            # cut (admin/probe traffic deliberately doesn't count)
            self.state.inflight_inc()
        try:
            if path == "/v1/completions":
                self._completions(body, chat=False)
            elif path == "/v1/chat/completions":
                self._completions(body, chat=True)
            elif path == "/admin/drain":
                self._admin_drain(body)
            elif path == "/admin/undrain":
                self.state.end_drain()
                self._json(200, {"status": "ok", "draining": False})
            else:
                self._error(404, f"no route for POST {path}")
        except BrokenPipeError:
            pass
        # tpulint: disable=R3 request boundary — engine errors surface as 500s; the handler thread must outlive any single request
        except Exception as e:
            log.exception("request failed")
            try:
                self._error(500, f"{type(e).__name__}: {e}", "internal_error")
            # tpulint: disable=R3 best-effort error write — the client may already have hung up; nothing left to report to
            except Exception:
                pass
        finally:
            if track:
                self.state.inflight_dec()

    def _admin_drain(self, body: dict):
        """Begin a graceful drain (the preStop hook's target; SIGTERM takes
        the same path): stop admitting, finish in-flight work up to
        ``timeout_s`` (default drain_timeout_s), then stop the server —
        unless ``exit: false`` (drain for rotation-removal only;
        /admin/undrain reverses it)."""
        eng = self.state.engine
        try:
            timeout_s = body.get("timeout_s")
            if timeout_s is not None:
                timeout_s = float(timeout_s)
        except (TypeError, ValueError):
            return self._error(400, "'timeout_s' must be a number")
        exit_when_idle = bool(body.get("exit", True))
        t = self.state.begin_drain(timeout_s, exit_when_idle=exit_when_idle)
        log.info("drain requested (timeout %.1fs, exit=%s): %d active, "
                 "%d queued", t, exit_when_idle,
                 len(eng._active_slots()), len(eng.pending))
        self._json(200, {"status": "draining", "drain_timeout_s": t,
                         "exit_when_idle": exit_when_idle,
                         "active_requests": len(eng._active_slots()),
                         "queue_depth": len(eng.pending)})

    def _completions(self, body: dict, chat: bool):
        """Span-lifecycle wrapper around the real handler: continues the
        router's propagated ``traceparent`` into a ``server.request`` span,
        then reconstructs the five phase children (admission, queue_wait,
        prefill, decode, stream_out) retroactively from the engine Request's
        monotonic timestamps once the response is written — the engine's hot
        loop carries timestamps, never tracer calls."""
        st = self.state
        tracer = st.tracer
        if tracer is None:
            return self._completions_impl(body, chat)
        t0_mono = time.monotonic()
        parent = tracing.parse_traceparent(
            self.headers.get(tracing.TRACEPARENT_HEADER))
        span = tracer.start_span(
            "server.request", parent=parent, kind=tracing.KIND_SERVER,
            start_ns=tracing.mono_ns(t0_mono),
            attributes={"http.route": ("/v1/chat/completions" if chat
                                       else "/v1/completions"),
                        "request.stream": bool(body.get("stream", False))})
        raw_ddl = body.get(DEADLINE_FIELD, self.headers.get(DEADLINE_HEADER))
        if raw_ddl is not None:
            try:
                span.set_attribute("deadline.remaining_ms",
                                   int(float(raw_ddl)))
            except (TypeError, ValueError):
                pass
        self._trace_ctx = span.context
        self._trace_reqs = None
        try:
            return self._completions_impl(body, chat)
        except Exception as e:
            span.error(f"{type(e).__name__}: {e}")
            raise
        finally:
            self._emit_phase_spans(tracer, span, t0_mono)

    def _emit_phase_spans(self, tracer, span, t0_mono: float):
        """Phase children + request-span finish. Boundaries are the engine
        Request's own transition timestamps, clamped to a monotonic chain
        (an unset 0.0 collapses that phase to zero width at the previous
        boundary — e.g. a non-streamed request ends stream_out ≈ t_done),
        so consumers can rely on non-overlapping phases."""
        end_mono = time.monotonic()
        reqs = getattr(self, "_trace_reqs", None)
        if reqs:
            r = reqs[0]     # choice 0 == the n=1 request's timeline
            bounds = [t0_mono, r.t_submit, r.t_prefill_start,
                      r.t_first_token, r.t_done, end_mono]
            for i in range(1, len(bounds)):
                if bounds[i] <= 0.0 or bounds[i] < bounds[i - 1]:
                    bounds[i] = bounds[i - 1]
            names = ("admission", "queue_wait", "prefill", "decode",
                     "stream_out")
            for name, lo, hi in zip(names, bounds, bounds[1:]):
                tracer.emit_span(name, span.context, tracing.mono_ns(lo),
                                 tracing.mono_ns(hi),
                                 attributes={"phase.ms":
                                             round((hi - lo) * 1e3, 3)})
            span.set_attribute("request.n_choices", len(reqs))
            if r.finish_reason:
                span.set_attribute("request.finish_reason", r.finish_reason)
        tracer.finish(span, end_ns=tracing.mono_ns(end_mono))

    def _completions_impl(self, body: dict, chat: bool):
        st = self.state
        model = body.get("model") or st.model_name
        lora_name = model if model in st.engine.lora_names else None
        if model != st.model_name and lora_name is None:
            return self._error(404, f"model {model!r} not found; serving "
                                    f"{st.model_name!r} (adapters: "
                                    f"{st.engine.lora_names})",
                               "model_not_found")

        if chat:
            messages = body.get("messages")
            if not isinstance(messages, list) or not messages:
                return self._error(400, "'messages' must be a non-empty list")
            prompt_text = st.templater.render(messages, add_generation_prompt=True)
        else:
            prompt = body.get("prompt", "")
            if isinstance(prompt, list):
                prompt = prompt[0] if prompt else ""
            if not isinstance(prompt, str):
                return self._error(400, "'prompt' must be a string")
            prompt_text = prompt

        try:
            max_tokens = int(body.get("max_tokens",
                                      st.engine.serving.max_tokens_default))
            temperature = float(body.get("temperature", 1.0 if chat else 0.0))
            top_p = float(body.get("top_p", 1.0))
            top_k = int(body.get("top_k", 0))
            presence_penalty = float(body.get("presence_penalty", 0.0))
            frequency_penalty = float(body.get("frequency_penalty", 0.0))
            repetition_penalty = float(body.get("repetition_penalty", 1.0))
        except (TypeError, ValueError):
            return self._error(400, "sampling parameters must be numeric")
        if not (-2.0 <= presence_penalty <= 2.0
                and -2.0 <= frequency_penalty <= 2.0):
            return self._error(400, "penalties must be in [-2, 2]")
        if not (0.0 < repetition_penalty <= 10.0):
            return self._error(400, "'repetition_penalty' must be in "
                                    "(0, 10]")
        # a continuation's max_tokens means REMAINING budget (the router
        # decrements it by the already-relayed tokens), so 0 is legal there
        min_mt = 0 if body.get("resume_token_ids") is not None else 1
        if max_tokens < min_mt or max_tokens > st.engine.max_len:
            return self._error(400, f"max_tokens must be in [{min_mt}, "
                                    f"{st.engine.max_len}]")
        stops = body.get("stop") or []
        if isinstance(stops, str):
            stops = [stops]
        # vLLM extras: stop_token_ids (token-level stops beside the string
        # ones) and min_tokens (stop tokens masked from sampling until N
        # tokens generated)
        raw_stop_ids = body.get("stop_token_ids") or []
        if not isinstance(raw_stop_ids, list):
            # a string would silently iterate character-wise
            return self._error(400, "'stop_token_ids' must be a list of "
                                    "integers")
        try:
            stop_token_ids = tuple(int(t) for t in raw_stop_ids)
            min_tokens = int(body.get("min_tokens", 0))
        except (TypeError, ValueError):
            return self._error(400, "'stop_token_ids' must be integers and "
                                    "'min_tokens' an integer")
        if min_tokens < 0:
            return self._error(400, "'min_tokens' must be >= 0")
        stream = bool(body.get("stream", False))
        # End-to-end deadline (r7): relative milliseconds via the
        # X-Request-Deadline-Ms header (router-forwarded) or the deadline_ms
        # body field (body wins). The engine caps it at request_timeout_s,
        # enforces it across queue wait + decode, and expiry answers 408.
        raw_ddl = body.get(DEADLINE_FIELD, self.headers.get(DEADLINE_HEADER))
        deadline_s = None
        if raw_ddl is not None:
            try:
                deadline_s = float(raw_ddl) / 1000.0
            except (TypeError, ValueError):
                return self._error(400, f"'{DEADLINE_FIELD}' must be a "
                                        "number of milliseconds")
            if deadline_s <= 0:
                return self._error(400, f"'{DEADLINE_FIELD}' must be > 0")
        # vLLM ``ignore_eos``: generate to the max_tokens budget regardless
        # of eos (bench/load harnesses depend on it for deterministic sizes)
        ignore_eos = bool(body.get("ignore_eos", False))
        try:
            n_choices = int(body.get("n", 1))
        except (TypeError, ValueError):
            return self._error(400, "'n' must be an integer")
        if n_choices < 1 or n_choices > 8:
            return self._error(400, "'n' must be in [1, 8]")

        # OpenAI ``seed``: deterministic sampling (engine keys each draw by
        # (seed, position) — ops/sampling.per_slot_keys). Sibling choices get
        # seed + i so n > 1 still returns distinct samples, with choice 0
        # equal to the n=1 stream.
        seed = body.get("seed")
        if seed is not None:
            try:
                seed = int(seed)
            except (TypeError, ValueError):
                return self._error(400, "'seed' must be an integer")
        # OpenAI ``echo`` (completions only): prepend the prompt text to each
        # choice's text. Logprobs cover GENERATED tokens only (prompt
        # logprobs are not computed — vLLM subset); offsets account for the
        # echoed prompt.
        echo = bool(body.get("echo", False))
        if echo and chat:
            return self._error(400, "'echo' is not supported on chat "
                                    "completions")
        # OpenAI ``best_of`` (completions only): generate best_of candidates
        # server-side, return the n best by cumulative logprob. Candidates
        # ride the same continuous batch; ranking uses the engine's
        # chosen-token logprobs (requested internally when the client
        # didn't ask for logprobs).
        try:
            best_of = int(body.get("best_of", n_choices))
        except (TypeError, ValueError):
            return self._error(400, "'best_of' must be an integer")
        if chat:
            best_of = n_choices
        if best_of < n_choices or best_of > 8:
            return self._error(400, f"'best_of' must be in [n, 8], got "
                                    f"{best_of}")
        if stream and best_of > n_choices:
            return self._error(400, "best_of > n with stream=true is not "
                                    "supported (ranking needs complete "
                                    "candidates)")
        # vLLM ``prompt_logprobs``: per-prompt-position logprobs (position
        # 0 is null). OpenAI legacy echo+logprobs implies it (the prompt
        # part of the echoed logprobs payload).
        raw_plp = body.get("prompt_logprobs")
        try:
            plp = None if raw_plp is None else int(raw_plp)
        except (TypeError, ValueError):
            return self._error(400, "'prompt_logprobs' must be an integer")
        # OpenAI logprobs: completions take an int ``logprobs`` (0 = chosen-
        # token only — still enabled; absent/null = off); chat takes
        # ``logprobs: true`` + ``top_logprobs: N`` (explicit 0 respected).
        # Capped at the engine's static LOGPROB_K; streaming responses carry
        # per-token logprob chunks (vLLM's streamed-logprobs shape).
        from aws_k8s_ansible_provisioner_tpu.serving.engine import LOGPROB_K
        try:
            if chat:
                lp_n = int(body.get("top_logprobs", 0)) \
                    if bool(body.get("logprobs", False)) else None
            else:
                raw_lp = body.get("logprobs", None)
                if raw_lp is False:
                    raw_lp = None   # explicit false unambiguously means off
                elif isinstance(raw_lp, bool):
                    # bool is an int subclass: the chat-style {"logprobs":
                    # true} on /v1/completions is a client bug, not a 1
                    return self._error(400, "completions 'logprobs' is an "
                                            "integer, not a boolean")
                lp_n = None if raw_lp is None else int(raw_lp)
        except (TypeError, ValueError):
            return self._error(400, "'logprobs' must be numeric")
        if lp_n is not None and (lp_n < 0 or lp_n > LOGPROB_K):
            return self._error(400, f"logprobs must be in [0, {LOGPROB_K}]")
        if plp is not None:
            if not (0 <= plp <= LOGPROB_K):
                return self._error(400, f"prompt_logprobs must be in "
                                        f"[0, {LOGPROB_K}]")
            if stream:
                return self._error(400, "prompt_logprobs with stream=true "
                                        "is not supported")
        # OpenAI ``logit_bias``: {token_id: bias} map, additive on logits
        # before every sampling decision (±100 act as force/ban). vLLM
        # behind the reference's gateway accepts it; BIAS_K caps entries.
        from aws_k8s_ansible_provisioner_tpu.serving.engine import BIAS_K
        raw_bias = body.get("logit_bias") or {}
        if not isinstance(raw_bias, dict):
            return self._error(400, "'logit_bias' must be an object mapping "
                                    "token ids to bias values")
        try:
            logit_bias = tuple(sorted((int(k), float(v))
                                      for k, v in raw_bias.items()))
        except (TypeError, ValueError):
            return self._error(400, "'logit_bias' keys must be token ids "
                                    "and values numbers")
        if len(logit_bias) > BIAS_K:
            return self._error(400, f"'logit_bias' supports at most "
                                    f"{BIAS_K} entries")
        if any(t < 0 for t, _ in logit_bias):
            return self._error(400, "'logit_bias' token ids must be >= 0")
        if any(not (-100.0 <= v <= 100.0) for _, v in logit_bias):
            return self._error(400, "'logit_bias' values must be in "
                                    "[-100, 100]")
        # OpenAI ``stream_options``: include_usage adds a final usage-only
        # chunk to the SSE stream (and a null usage field on every chunk).
        so = body.get("stream_options") or {}
        if not isinstance(so, dict):
            return self._error(400, "'stream_options' must be an object")
        if so and not stream:
            return self._error(400, "'stream_options' requires stream=true")
        include_usage = bool(so.get("include_usage", False))
        # Mid-stream failover continuation (r8): the router re-issues a
        # dying stream carrying the token ids it already relayed
        # (resume_token_ids) and how much generated text the client already
        # received (resume_text_chars). The engine re-prefills
        # prompt + resume as a cache rebuild; the seeded draws continue at
        # the exact positions the dead replica would have used, and
        # _stream_response splices only NEW bytes to the client. max_tokens
        # in a continuation body is the REMAINING budget; the engine's is
        # total generated, so the resume length is added back (a body
        # without max_tokens keeps the default as the TOTAL budget —
        # exactly the original request's).
        raw_resume = body.get("resume_token_ids")
        resume_ids: tuple = ()
        resume_chars = 0
        if raw_resume is not None:
            if not isinstance(raw_resume, list):
                return self._error(400, "'resume_token_ids' must be a list "
                                        "of token ids")
            try:
                resume_ids = tuple(int(t) for t in raw_resume)
                resume_chars = int(body.get("resume_text_chars", 0))
            except (TypeError, ValueError):
                return self._error(400, "'resume_token_ids' must be integers"
                                        " and 'resume_text_chars' an "
                                        "integer")
            if resume_chars < 0:
                return self._error(400, "'resume_text_chars' must be >= 0")
            if not stream:
                return self._error(400, "'resume_token_ids' requires "
                                        "stream=true")
            if n_choices != 1 or best_of != 1:
                return self._error(400, "continuation supports a single "
                                        "choice (n=1, best_of=1)")
            if echo:
                return self._error(400, "continuation cannot combine with "
                                        "'echo' (the prompt was already "
                                        "streamed)")
            if plp is not None:
                return self._error(400, "continuation cannot carry "
                                        "prompt_logprobs")
            if "max_tokens" in body:
                max_tokens += len(resume_ids)
        # Constrained output via the grammar-mask sampler (serving/guided.py):
        # OpenAI ``response_format`` (json_object/json_schema) plus vLLM's
        # guided_json / guided_regex / guided_choice extensions. Compiled
        # grammars are cached per (tokenizer, spec); each sibling request
        # gets its own FSM cursor (engine.submit wraps the grammar).
        rf = body.get("response_format")
        if rf is not None and not isinstance(rf, dict):
            return self._error(400, "'response_format' must be an object")
        from aws_k8s_ansible_provisioner_tpu.serving.guided import (
            grammar_for_request)
        try:
            guided = grammar_for_request(st.tokenizer, body,
                                         sorted(st.engine._eos_set))
        except ValueError as e:
            return self._error(400, f"guided decoding: {e}")

        prompt_ids = st.tokenizer.encode(prompt_text)
        if not prompt_ids:
            prompt_ids = [st.engine.eos_token_id]
        if echo and lp_n is not None and plp is None and not stream \
                and len(prompt_ids) <= max(st.engine.buckets or (0,)):
            # OpenAI legacy echo+logprobs implies prompt logprobs — but only
            # when the request can honor them (non-stream, bucket-sized
            # prompt); otherwise keep the pre-r5 generated-only payload
            # instead of breaking previously-working requests (review r5)
            plp = lp_n
        if raw_resume is not None:
            # A relayed prefix that ALREADY satisfies a stop condition must
            # not decode further (the engine would generate past the point
            # the undisturbed stream stopped — only the finish chunk was
            # lost with the dead replica). Mirrors _emit's stop logic.
            fin = None
            if resume_ids:
                last = resume_ids[-1]
                if (((last in st.engine._eos_set and not ignore_eos)
                     or last in stop_token_ids)
                        and len(resume_ids) > min_tokens):
                    fin = "stop"
            if fin is None and len(resume_ids) >= max_tokens:
                fin = "length"
            if fin is not None:
                rid = ("chatcmpl-" if chat else "cmpl-") \
                    + uuid.uuid4().hex[:24]
                return self._finished_stream(
                    rid, chat, model, fin, n_prompt=len(prompt_ids),
                    n_gen=len(resume_ids), include_usage=include_usage)
        # best_of ranking needs each candidate's chosen-token logprobs; ask
        # the engine for them even when the client didn't (the response
        # strips them again — lp_requested below).
        rank = best_of > n_choices
        eng_lp = lp_n if lp_n is not None else (0 if rank else None)
        reqs = []
        try:
            # n/best_of: independent engine requests riding the same
            # continuous batch — the OpenAI semantics; identical for
            # temperature=0. Each sibling prefills the prompt itself (the
            # prefix cache only consults on ISOLATED arrivals, and the
            # siblings queue together), so n multiplies prefill cost.
            # Multi-choice streams share one wakeup event across the sibling
            # out_queues so the handler blocks instead of polling n queues.
            notify = threading.Event() if (stream and best_of > 1) else None
            for i in range(best_of):
                reqs.append(st.engine.generate(
                    prompt_ids, max_tokens=max_tokens,
                    temperature=temperature,
                    top_k=top_k, top_p=top_p, stream=stream, logprobs=eng_lp,
                    presence_penalty=presence_penalty,
                    frequency_penalty=frequency_penalty,
                    repetition_penalty=repetition_penalty,
                    stop_token_ids=stop_token_ids, min_tokens=min_tokens,
                    logit_bias=logit_bias, guided=guided,
                    ignore_eos=ignore_eos,
                    lora=lora_name, prompt_logprobs=plp,
                    deadline_s=deadline_s, resume_ids=resume_ids,
                    seed=None if seed is None else seed + i,
                    **({"out_queue": _NotifyQueue(notify)} if notify else {})))
        except EngineOverloaded as e:
            # a later sibling can shed as the queue fills — don't strand the
            # already-queued ones
            for r in reqs:
                st.engine.cancel(r)
            return self._overloaded(e)
        except ContextLengthExceeded as e:
            # Same wire shape the reference's vLLM returns for an oversized
            # prompt (VERDICT r1: silent tail-truncation answered a different
            # question than the client asked).
            return self._error(400, str(e),
                               err_code="context_length_exceeded")
        except ValueError as e:
            # engine-side request validation (e.g. min_tokens ban-list cap)
            return self._error(400, str(e))

        rid = ("chatcmpl-" if chat else "cmpl-") + uuid.uuid4().hex[:24]
        # hand the engine requests to the tracing wrapper: their monotonic
        # timestamps become the phase spans after the response is written
        self._trace_reqs = reqs
        if self._trace_ctx is not None:
            # bind the span identity into each engine request's flight
            # timeline: an anomaly dump hoists these to its top level, so
            # /debug/flight/<id> hands back the exact ids to paste into
            # Tempo beside the PR 5 phase spans
            for r in reqs:
                # also onto the request itself: the engine's histogram
                # observe points use it as the OpenMetrics exemplar
                r.trace_id = self._trace_ctx.trace_id
                flightrec.record("trace", r.id,
                                 trace_id=self._trace_ctx.trace_id,
                                 span_id=self._trace_ctx.span_id,
                                 api_id=rid)
        if stream:
            self._stream_response(reqs, rid, chat, stops, model=model,
                                  n_prompt=len(prompt_ids),
                                  include_usage=include_usage,
                                  echo_text=prompt_text if echo else None,
                                  lp_k=lp_n, resume_ids=resume_ids,
                                  resume_chars=resume_chars,
                                  is_resume=raw_resume is not None)
        else:
            self._full_response(reqs, rid, chat, stops, len(prompt_ids),
                                model=model,
                                n_choices=n_choices,
                                lp_requested=lp_n is not None,
                                echo_text=prompt_text if echo else None)

    def _full_response(self, reqs, rid: str, chat: bool, stops: List[str],
                       n_prompt: int = 0, model: Optional[str] = None,
                       n_choices: Optional[int] = None,
                       lp_requested: bool = True,
                       echo_text: Optional[str] = None):
        """Collect finished candidates into the response. When ``reqs``
        exceeds ``n_choices`` (best_of), rank candidates by cumulative
        chosen-token logprob and keep the best n. ``lp_requested=False``
        strips the internal ranking logprobs from the payload; ``echo_text``
        (completions ``echo``) prepends the prompt to each choice."""
        st = self.state
        n_choices = len(reqs) if n_choices is None else n_choices
        done = []
        completion_tokens = 0
        for req in reqs:
            try:
                ids = req.wait(timeout=_wait_budget_s(st.engine, req))
            except TimeoutError:
                # backstop only: the engine normally reaps the deadline
                # itself and this wait returns with finish_reason "timeout"
                for other in reqs:
                    st.engine.cancel(other)
                return self._error(408, "request timed out awaiting the "
                                        "engine", "timeout",
                                   err_code="deadline_exceeded")
            if req.finish_reason in ("error", "timeout"):
                for other in reqs:   # don't strand the sibling choices'
                    if other is not req:   # slots generating to max_tokens
                        st.engine.cancel(other)
                if req.finish_reason == "timeout":
                    return self._error(
                        408, "request deadline exceeded before completion "
                             "(slot and pages released)", "timeout",
                        err_code="deadline_exceeded")
                return self._error(500, "engine failure: "
                                   + (st.engine.last_error or "unknown"),
                                   "internal_error")
            completion_tokens += len(ids)
            done.append((req, ids))
        if len(done) > n_choices:
            # OpenAI best_of ranking: highest cumulative log probability of
            # the sampled tokens wins (the vLLM ordering)
            def score(pair):
                return sum(d[0] for d in pair[0].logprob_data
                           if d is not None)
            done.sort(key=score, reverse=True)
            done = done[:n_choices]
        choices = []
        for idx, (req, ids) in enumerate(done):
            text = st.tokenizer.decode(ids)
            finish = req.finish_reason
            cut = _apply_stop_strings(text, stops)
            if cut is not None:
                text, finish = cut, "stop"
            lp_obj = None
            if req.logprobs is not None and lp_requested:
                # align with a stop-string cut only when one happened: per-
                # token decode lengths can exceed the merged text's length
                # (multi-byte sequences), so unconditional truncation would
                # drop tail tokens
                lp_obj = _format_logprobs(
                    st.tokenizer, ids, req.logprob_data, req.logprobs, chat,
                    text_len=len(text) if cut is not None else -1,
                    base_offset=len(echo_text) if echo_text else 0)
            if echo_text is not None and req.prompt_logprob_data \
                    and lp_obj is not None and not chat:
                # OpenAI legacy echo+logprobs: the payload covers PROMPT +
                # generated; position 0 carries null (no context to score)
                ptoks = [st.tokenizer.decode([i]) for i in req.prompt_ids]
                poffs, p0 = [], 0
                for t in ptoks:
                    poffs.append(p0)
                    p0 += len(t)
                tail = req.prompt_logprob_data[1:]
                k = req.logprobs or 0
                pown = [None] + [d[0] for d in tail]
                ptop = [None] + [
                    {st.tokenizer.decode([tid]): v for tid, v in d[1][:k]}
                    for d in tail]
                lp_obj = {"tokens": ptoks + lp_obj["tokens"],
                          "token_logprobs": pown + lp_obj["token_logprobs"],
                          "top_logprobs": ptop + lp_obj["top_logprobs"],
                          "text_offset": poffs + lp_obj["text_offset"]}
            if echo_text is not None:
                text = echo_text + text
            if chat:
                choice = {"index": idx, "message": {"role": "assistant",
                                                    "content": text},
                          "finish_reason": finish}
                if lp_obj is not None:
                    choice["logprobs"] = lp_obj
            else:
                choice = {"index": idx, "text": text, "logprobs": lp_obj,
                          "finish_reason": finish}
            if req.prompt_logprob_data:
                # vLLM-style field: list over prompt positions; each entry
                # maps decoded token -> logprob (chosen + top-k)
                pl = [None]
                for t, d in enumerate(req.prompt_logprob_data[1:], start=1):
                    entry = {st.tokenizer.decode([req.prompt_ids[t]]): d[0]}
                    for tid, v in d[1][:req.prompt_logprobs or 0]:
                        entry.setdefault(st.tokenizer.decode([tid]), v)
                    pl.append(entry)
                choice["prompt_logprobs"] = pl
            choices.append(choice)
        usage = {"prompt_tokens": n_prompt,
                 "completion_tokens": completion_tokens,
                 "total_tokens": n_prompt + completion_tokens}
        if self._trace_ctx is not None:
            # log correlation without header plumbing: the ids a client
            # pastes into Tempo to find this request's span tree
            usage["trace_id"] = self._trace_ctx.trace_id
            usage["span_id"] = self._trace_ctx.span_id
        self._json(200, {"id": rid,
                         "object": "chat.completion" if chat
                         else "text_completion",
                         "created": _now(),
                         "model": model or st.model_name,
                         "choices": choices, "usage": usage})

    def _finished_stream(self, rid: str, chat: bool, model: Optional[str],
                         finish: str, n_prompt: int, n_gen: int,
                         include_usage: bool):
        """Degenerate continuation: the relayed prefix already satisfied a
        stop condition — only the finish chunk (+usage, [DONE]) was lost
        with the dead replica, so answer those directly without admitting
        anything to the engine."""
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        obj = "chat.completion.chunk" if chat else "text_completion"

        def raw_write(data: bytes):
            self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
            self.wfile.flush()

        payload = {"index": 0, "finish_reason": finish}
        if chat:
            payload["delta"] = {}
        else:
            payload["text"] = ""
        body = {"id": rid, "object": obj, "created": _now(),
                "model": model or self.state.model_name,
                "choices": [payload]}
        if include_usage:
            body["usage"] = None
        raw_write(f"data: {json.dumps(body)}\n\n".encode())
        if include_usage:
            usage = {"prompt_tokens": n_prompt,
                     "completion_tokens": n_gen,
                     "total_tokens": n_prompt + n_gen}
            if self._trace_ctx is not None:
                usage["trace_id"] = self._trace_ctx.trace_id
                usage["span_id"] = self._trace_ctx.span_id
            raw_write(("data: " + json.dumps({
                "id": rid, "object": obj, "created": _now(),
                "model": model or self.state.model_name, "choices": [],
                "usage": usage,
                "failover": True}) + "\n\n").encode())
        raw_write(b"data: [DONE]\n\n")
        self.wfile.write(b"0\r\n\r\n")
        self.wfile.flush()

    def _stream_response(self, reqs, rid: str, chat: bool, stops: List[str],
                         model: Optional[str] = None,
                         n_prompt: int = 0, include_usage: bool = False,
                         echo_text: Optional[str] = None,
                         lp_k: Optional[int] = None,
                         resume_ids: tuple = (), resume_chars: int = 0,
                         is_resume: bool = False):
        """SSE streaming with incremental detokenization (n choices).

        Correctness over eagerness: text is held back while it could still be
        (a) the tail of an incomplete multi-byte character (detokenizer handles
        this) or (b) a prefix of a stop string (``hold`` chars withheld), so a
        client never sees bytes that a later token retroactively changes.
        A broken pipe cancels the engine request so the decode slot frees.

        Every content chunk carries the generated ``token_ids`` it covers —
        the router buffers them per stream so a replica death mid-stream can
        fail over as a deterministic continuation. A continuation
        (``is_resume``) pre-feeds the detokenizer with the already-relayed
        ``resume_ids`` and SKIPS the first ``resume_chars`` of generated
        text: the client receives only chunks it hasn't seen, and the
        concatenated stream is byte-identical to an undisturbed run.
        """
        from aws_k8s_ansible_provisioner_tpu.utils.tokenizer import (
            IncrementalDetokenizer)
        from aws_k8s_ansible_provisioner_tpu.serving import chaos as _chaos

        st = self.state
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        # Events gather here and leave with ONE write + flush per queue
        # item (flush(), below): a logprobs stream sends a chunk a token,
        # and each flush wakes the reader on the other end.
        outbuf: List[bytes] = []

        def raw_write(data: bytes):
            outbuf.append(f"{len(data):x}\r\n".encode() + data + b"\r\n")

        def flush():
            if outbuf:
                self.wfile.write(b"".join(outbuf))
                outbuf.clear()
                self.wfile.flush()

        obj = "chat.completion.chunk" if chat else "text_completion"
        _sent = {"chunks": 0}

        def chunk(idx: int, delta_text: Optional[str],
                  finish_reason: Optional[str], role: bool = False,
                  lp: Optional[dict] = None,
                  tok_ids: Optional[List[int]] = None):
            payload = {"index": idx, "finish_reason": finish_reason}
            if chat:
                d = {}
                if role:
                    d["role"] = "assistant"
                if delta_text:
                    d["content"] = delta_text
                payload["delta"] = d
            else:
                payload["text"] = delta_text or ""
            if lp is not None:
                payload["logprobs"] = lp
            if tok_ids:
                # failover bookkeeping (r8): the generated token ids this
                # chunk covers. OpenAI clients ignore the extra field; the
                # router accumulates them so a mid-stream replica death can
                # re-issue the request as a deterministic continuation.
                payload["token_ids"] = [int(t) for t in tok_ids]
            body = {"id": rid, "object": obj, "created": _now(),
                    "model": model or st.model_name,
                    "choices": [payload]}
            if include_usage:
                # OpenAI stream_options.include_usage: every content chunk
                # carries usage: null; the final stats ride a dedicated
                # choices-less chunk before [DONE]
                body["usage"] = None
            raw_write(f"data: {json.dumps(body)}\n\n".encode())
            if delta_text or tok_ids:
                _sent["chunks"] += 1
                ch = _chaos.get()
                if ch.enabled:
                    # kill_replica_after_chunks fault point: may RST the
                    # connection and raise (unwound like a broken pipe) —
                    # after the chunks counted so far have left
                    flush()
                    ch.on_stream_chunk(self, _sent["chunks"])

        def consume_skip(s, text: str) -> str:
            """Drop the leading chars a failed-over client already received
            (continuation streams only; no-op otherwise)."""
            if s["skip"] and text:
                k = min(s["skip"], len(text))
                s["skip"] -= k
                text = text[k:]
            return text

        # Per-choice state: the n > 1 sibling requests ride the same
        # continuous batch, so their tokens arrive interleaved — each choice
        # detokenizes, stop-string-holds, and finishes independently, tagged
        # by its chunk "index" (the OpenAI multi-choice stream shape).
        hold = max((len(s) for s in stops if s), default=1) - 1
        base_off = len(echo_text) if echo_text else 0
        states = [{"req": r, "detok": IncrementalDetokenizer(st.tokenizer),
                   "pending": "", "finish": None, "n_lp": 0, "skip": 0,
                   "carry": "", "tok_pending": [],
                   "acc": "", "offset": base_off} for r in reqs]
        multi = len(states) > 1
        if is_resume and states:
            # Continuation: rebuild the detokenizer over the already-relayed
            # tokens so the first NEW token's delta merges correctly, then
            # arm the skip that drops what the client already has. The
            # flushed prior text re-enters the normal pending/hold pipeline
            # (non-lp) or the first chunk's carry (lp) — whatever the dead
            # replica had flushed-but-held arrives with the first new chunk.
            s = states[0]
            prior = "".join(s["detok"].push(int(t)) for t in resume_ids)
            skip = min(int(resume_chars), len(prior))
            s["acc"] = prior
            s["offset"] = base_off + len(prior)
            if lp_k is not None:
                s["carry"] = prior
            else:
                s["pending"] = prior
            s["skip"] = skip

        def token_lp(s, token: int, delta: str):
            """Per-token logprob payload for a streamed chunk — the vLLM
            shape: completions carry parallel one-element arrays, chat a
            one-element content list. logprob_data[k] is guaranteed present
            before the k-th token reaches the queue (engine._emit order)."""
            d = s["req"].logprob_data[s["n_lp"]] \
                if s["n_lp"] < len(s["req"].logprob_data) else None
            s["n_lp"] += 1
            tok_str = st.tokenizer.decode([token])
            own = None if d is None else d[0]
            tops = [] if d is None else \
                [(st.tokenizer.decode([tid]), v) for tid, v in d[1][:lp_k]]
            if chat:
                return {"content": [{
                    "token": tok_str, "logprob": own,
                    "top_logprobs": [{"token": t, "logprob": v}
                                     for t, v in tops]}]}
            off = s["offset"]
            s["offset"] += len(delta)
            return {"tokens": [tok_str], "token_logprobs": [own],
                    "top_logprobs": [dict(tops)], "text_offset": [off]}

        def drain(i: int, block_s: float) -> bool:
            """Advance choice i by at most one queue item — the token ids
            one engine dispatch produced for it, or None at the end — and
            send what it makes ready in one write. Returns whether an item
            arrived."""
            s = states[i]
            try:
                item = s["req"].out_queue.get(timeout=block_s)
            except queue.Empty:
                return False
            if lp_k is not None:
                drain_lp(i, s, item)
            else:
                drain_text(i, s, item)
            flush()
            return True

        def drain_lp(i: int, s: dict, item) -> None:
            # Per-TOKEN chunks so the logprob arrays align with their
            # token: each id emits one chunk carrying that token's text
            # delta (possibly "" while a multi-byte sequence is
            # incomplete) and its logprob record. Stop strings cut the
            # accumulated text without holdback (the already-sent token
            # entries stand — vLLM's streamed behavior has the same
            # artifact).
            if item is None:
                tail = s["detok"].finish()
                s["finish"] = s["req"].finish_reason or "stop"
                tail = consume_skip(s, s["carry"] + tail)
                s["carry"] = ""
                if tail:
                    chunk(i, tail, None)
                chunk(i, None, s["finish"])
                return
            for tok in item:
                delta = s["detok"].push(tok)
                # windowed stop scan: only the region a NEW stop match could
                # end in (delta + the longest stop's tail) — scanning the
                # whole accumulated text would be O(len^2) per stream
                # (review r4). Matches wholly inside older text were caught
                # on earlier tokens.
                window = (s["acc"][-hold:] if hold else "") + delta
                s["acc"] += delta
                cut = _apply_stop_strings(window, stops)
                if cut is not None:
                    overshoot = len(window) - len(cut)
                    delta = delta[:len(delta) - overshoot] \
                        if overshoot <= len(delta) else ""
                    s["finish"] = "stop"
                    st.engine.cancel(s["req"])
                if s["carry"]:
                    # continuation: the rebuilt prior text (beyond what the
                    # client already has — consume_skip drops that part)
                    # rides the first new token's chunk
                    delta, s["carry"] = s["carry"] + delta, ""
                delta = consume_skip(s, delta)
                chunk(i, delta, None, lp=token_lp(s, tok, delta),
                      tok_ids=[int(tok)])
                if s["finish"]:
                    # the rest of the item is past the cut: discarded
                    chunk(i, None, s["finish"])
                    return

        def drain_text(i: int, s: dict, item) -> None:
            # The ids pass the detokenizer and the stop scan ONE AT A TIME
            # and stop at a cut, so text and token_ids end on the token
            # they would end on alone; what that makes ready leaves as ONE
            # chunk. An id never leaves ahead of its text: trailing ids
            # whose bytes the detokenizer still holds (an incomplete
            # multi-byte tail) wait in tok_pending for the chunk their text
            # rides, as when each id was an item — the router's failover
            # counts on it (ids it holds beyond the text would be replayed
            # as done, and their text lost with the dead replica).
            cut_text = None
            n_ids = 0       # of tok_pending: up to the last id that gave text
            if item is None:
                s["pending"] += s["detok"].finish()
                s["finish"] = s["req"].finish_reason or "stop"
                cut_text = _apply_stop_strings(s["pending"], stops)
            else:
                for tok in item:
                    delta = s["detok"].push(tok)
                    s["pending"] += delta
                    s["tok_pending"].append(int(tok))
                    cut_text = _apply_stop_strings(s["pending"], stops)
                    if cut_text is not None:
                        break        # the rest is past the cut: discarded
                    if delta and len(s["pending"]) > hold:
                        n_ids = len(s["tok_pending"])
            if cut_text is not None:
                s["pending"], s["finish"] = cut_text, "stop"
                st.engine.cancel(s["req"])  # free the slot; rest discarded
            if s["finish"]:
                ready, n_ids = s["pending"], len(s["tok_pending"])
            else:
                ready = s["pending"][:len(s["pending"]) - hold] if hold \
                    else s["pending"]
            if ready:
                send = consume_skip(s, ready)
                if send or n_ids:
                    chunk(i, send, None, tok_ids=s["tok_pending"][:n_ids])
                    del s["tok_pending"][:n_ids]
                s["pending"] = s["pending"][len(ready):]
            if s["finish"]:
                chunk(i, None, s["finish"], tok_ids=s["tok_pending"])
                s["tok_pending"] = []

        # No-progress backstop (r7): the configured deadline default, not a
        # hardcoded 600 — the engine reaps per-request deadlines and sends
        # sentinels, so this only guards against a wedged engine loop.
        # Config 0 = unbounded (capped at threading's wait ceiling, ~49
        # days, because queue.get cannot take infinity).
        stall_s = float(st.engine.serving.request_timeout_s or 0)
        if stall_s <= 0:
            stall_s = threading.TIMEOUT_MAX
        try:
            for i in range(len(states)):
                if is_resume:
                    # the client got the role/echo chunk from the replica
                    # that died; a continuation re-sending it would splice
                    # duplicate bytes into the stream
                    break
                if chat:
                    chunk(i, "", None, role=True)
                elif echo_text:
                    # completions echo+stream: the prompt leads each
                    # choice's stream (vLLM's behavior)
                    chunk(i, echo_text, None)
            flush()
            last_progress = time.monotonic()
            while any(s["finish"] is None for s in states):
                progressed = False
                for i, s in enumerate(states):
                    if s["finish"] is not None:
                        continue
                    if multi:
                        # drain every available item without blocking — a
                        # per-choice blocking slice would cap a fast
                        # choice's delta rate at one token per idle-sibling
                        # timeout (review r4); the single sleep below is
                        # the only wait when ALL queues are empty
                        while s["finish"] is None and drain(i, 0.0):
                            progressed = True
                    else:
                        progressed |= drain(i, stall_s)
                if progressed:
                    last_progress = time.monotonic()
                elif multi:
                    if time.monotonic() - last_progress > stall_s:
                        raise TimeoutError(
                            f"no stream progress in {stall_s:.0f}s")
                    ev = getattr(states[0]["req"].out_queue, "event", None)
                    if ev is not None:
                        # wait → clear → re-drain: a put racing the clear
                        # leaves its item in the queue for the drain sweep,
                        # and a put after the clear re-sets the event, so no
                        # wakeup is ever lost.
                        ev.wait(timeout=1.0)
                        ev.clear()
                    else:
                        # siblings submitted without the shared event (direct
                        # callers constructing their own reqs)
                        time.sleep(0.01)
                elif time.monotonic() - last_progress > stall_s:
                    raise TimeoutError(
                        f"no stream progress in {stall_s:.0f}s")
            if include_usage:
                # generated includes the resume prefix on a continuation, so
                # usage matches the undisturbed run; ``failover: true`` is
                # the client-visible marker that this stream was failed over
                n_gen = sum(len(s["req"].generated) for s in states)
                usage = {"prompt_tokens": n_prompt,
                         "completion_tokens": n_gen,
                         "total_tokens": n_prompt + n_gen}
                if self._trace_ctx is not None:
                    usage["trace_id"] = self._trace_ctx.trace_id
                    usage["span_id"] = self._trace_ctx.span_id
                final = {
                    "id": rid, "object": obj, "created": _now(),
                    "model": model or st.model_name, "choices": [],
                    "usage": usage,
                }
                if is_resume:
                    final["failover"] = True
                raw_write(("data: " + json.dumps(final) + "\n\n").encode())
            raw_write(b"data: [DONE]\n\n")
            outbuf.append(b"0\r\n\r\n")
            flush()
        except (BrokenPipeError, ConnectionResetError):
            for s in states:
                st.engine.cancel(s["req"])
        except Exception:
            # headers already sent: can't switch to a JSON error response now;
            # free the slots and drop the connection.
            log.exception("stream failed mid-flight")
            for s in states:
                st.engine.cancel(s["req"])
            raise BrokenPipeError  # handled (ignored) by do_POST


# ---------------------------------------------------------------------------
# Assembly
# ---------------------------------------------------------------------------


def build_state(serving_cfg=None, model_cfg=None, params=None,
                tokenizer=None) -> ServerState:
    """Wire tokenizer + params + engine + templater into a ServerState.

    With a checkpoint dir: real weights + real tokenizer. Without: random weights
    + byte tokenizer (offline dry-run mode — BASELINE.json config #1's CPU-only
    path needs the full stack to run with zero downloads).
    """
    import jax
    import jax.numpy as jnp

    from aws_k8s_ansible_provisioner_tpu.config import (
        MODEL_REGISTRY, ServingConfig, tiny_qwen3)
    from aws_k8s_ansible_provisioner_tpu.models import (
        config_from_hf_dir, init_params)
    from aws_k8s_ansible_provisioner_tpu.serving.chat_template import ChatTemplater
    from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine
    from aws_k8s_ansible_provisioner_tpu.utils.tokenizer import load_tokenizer

    serving = serving_cfg or ServingConfig()
    ckpt = serving.checkpoint_dir or None

    if tokenizer is None:
        tokenizer = load_tokenizer(ckpt)

    if model_cfg is None:
        if ckpt:
            model_cfg = config_from_hf_dir(ckpt)
        elif serving.model in MODEL_REGISTRY:
            model_cfg = MODEL_REGISTRY[serving.model]
        elif serving.model == "tiny-qwen3":
            # offline dry-run model sized to the byte tokenizer
            model_cfg = tiny_qwen3(vocab_size=tokenizer.vocab_size,
                                   eos_token_id=tokenizer.eos_token_id,
                                   num_layers=4, hidden_size=128,
                                   intermediate_size=256)
        elif serving.model == "tiny-qwen3-moe":
            from aws_k8s_ansible_provisioner_tpu.config import tiny_qwen3_moe

            model_cfg = tiny_qwen3_moe(vocab_size=tokenizer.vocab_size,
                                       eos_token_id=tokenizer.eos_token_id,
                                       num_layers=4, hidden_size=128)
        elif serving.model == "tiny-gemma":
            from aws_k8s_ansible_provisioner_tpu.config import tiny_gemma

            model_cfg = tiny_gemma(vocab_size=tokenizer.vocab_size,
                                   eos_token_id=tokenizer.eos_token_id,
                                   num_layers=4, hidden_size=128)
        elif serving.model == "tiny-mistral":
            from aws_k8s_ansible_provisioner_tpu.config import tiny_mistral

            model_cfg = tiny_mistral(vocab_size=tokenizer.vocab_size,
                                     eos_token_id=tokenizer.eos_token_id,
                                     num_layers=4, hidden_size=128,
                                     sliding_window=32)
        elif serving.model == "tiny-solar":
            # the hybrid dry-run model: gated NoPE GQA + KDA layers 1:3,
            # an expert share (4 of 16 held) and a shared expert
            from aws_k8s_ansible_provisioner_tpu.config import tiny_solar

            model_cfg = tiny_solar(vocab_size=tokenizer.vocab_size,
                                   eos_token_id=tokenizer.eos_token_id)
        elif serving.model == "tiny-sala":
            # the dry-run model whose layer kinds are a LIST: selecting
            # attention ("s") and Lightning layers ("l"), muP scales
            from aws_k8s_ansible_provisioner_tpu.config import tiny_sala

            ps = serving.page_size      # a selected block is a page
            model_cfg = tiny_sala(vocab_size=tokenizer.vocab_size,
                                  eos_token_id=tokenizer.eos_token_id,
                                  sparse_block_size=ps,
                                  sparse_kernel_size=ps // 2,
                                  sparse_kernel_stride=ps // 4,
                                  sparse_window_size=2 * ps,
                                  sparse_dense_len=4 * ps)
        elif serving.model == "tiny-trinity":
            # the dry-run list with window ("w") layers beside full ones,
            # leading dense FFNs, then routed ones with a shared expert
            from aws_k8s_ansible_provisioner_tpu.config import tiny_trinity

            model_cfg = tiny_trinity(vocab_size=tokenizer.vocab_size,
                                     eos_token_id=tokenizer.eos_token_id,
                                     sliding_window=2 * serving.page_size)
        elif serving.model == "tiny-lfm2":
            # the dry-run list of gated short convolutions ("c") and GQA
            # layers, leading dense FFNs, then routed ones
            from aws_k8s_ansible_provisioner_tpu.config import tiny_lfm2

            model_cfg = tiny_lfm2(vocab_size=tokenizer.vocab_size,
                                  eos_token_id=tokenizer.eos_token_id)
        elif serving.model == "tiny-falcon-h1":
            # the dry-run list of blocks with two mixers ("h": a state-space
            # mixer beside GQA attention at a query group of 5)
            from aws_k8s_ansible_provisioner_tpu.config import tiny_falcon_h1

            model_cfg = tiny_falcon_h1(vocab_size=tokenizer.vocab_size,
                                       eos_token_id=tokenizer.eos_token_id)
        else:
            raise ValueError(f"unknown model {serving.model!r} and no checkpoint")

    dtype = jnp.bfloat16 if serving.dtype == "bfloat16" else jnp.float32
    # Build the serving mesh BEFORE loading weights so an 8B checkpoint can
    # load directly sharded (per-device transfer = the shard; no chip ever
    # holds the full model — the --tp 8 / v5e-8 path, SURVEY.md §7 #3).
    mesh = Engine._build_mesh(serving)
    if params is None:
        if ckpt:
            # Cached conversion: first start converts safetensors and writes an
            # orbax cache next to the checkpoint; restarts restore directly
            # (sharded restore when a mesh is configured).
            from aws_k8s_ansible_provisioner_tpu.models.checkpoint import (
                load_checkpoint_cached)

            params = load_checkpoint_cached(ckpt, model_cfg, dtype, mesh=mesh)
        else:
            log.warning("no checkpoint_dir: serving RANDOM weights (%s) — "
                        "dry-run/benchmark mode only", model_cfg.name)
            params = init_params(model_cfg, jax.random.PRNGKey(0), dtype)

    draft = None
    if serving.spec_decode and serving.spec_method == "draft":
        if not serving.draft_checkpoint_dir:
            raise ValueError("spec_method='draft' requires "
                             "--draft-checkpoint-dir")
        from aws_k8s_ansible_provisioner_tpu.models.checkpoint import (
            load_checkpoint_cached)

        draft_cfg = config_from_hf_dir(serving.draft_checkpoint_dir)
        # the draft is small by design: load unsharded (serving/draft.py
        # runs it replicated beside the sharded target)
        draft_params = load_checkpoint_cached(serving.draft_checkpoint_dir,
                                              draft_cfg, dtype, mesh=None)
        draft = (draft_cfg, draft_params)
        log.info("draft model: %s (%s)", draft_cfg.name,
                 serving.draft_checkpoint_dir)
    lora = None
    if serving.lora_adapters:
        lora = {}
        for spec in serving.lora_adapters:
            name, sep, path = spec.partition("=")
            if not sep or not name or not path:
                raise ValueError(f"--lora expects name=path, got {spec!r}")
            if name in lora:
                raise ValueError(f"duplicate LoRA adapter name {name!r}")
            if name == serving.model:
                raise ValueError(f"LoRA adapter name {name!r} would shadow "
                                 f"the served base model id")
            lora[name] = path
    engine = Engine(model_cfg, params, serving,
                    eos_token_id=tokenizer.eos_token_id, mesh=mesh,
                    draft=draft, lora=lora)
    templater = ChatTemplater(model_cfg.name, tokenizer,
                              template_path=serving.chat_template or None)
    state = ServerState(engine, tokenizer, templater, serving.model)
    # Tracing: config endpoint wins; empty falls back to the manifest's
    # $OTEL_EXPORTER_OTLP_ENDPOINT; neither set = spans created (ids still
    # echo into responses) but never exported.
    state.tracer = tracing.build_tracer(
        "tpu-serve-engine",
        endpoint=getattr(serving, "otlp_endpoint", "") or None,
        sample=getattr(serving, "trace_sample", 1.0))
    # the engine loop exports its engine.dispatch spans through whatever
    # tracer (and exporter) the server holds at that moment: tests and the
    # benchmark install theirs on state.tracer after start
    engine.tracer_source = lambda: state.tracer
    # Flight recorder + SLO engine: module singletons the engine's record/
    # finish shorthands already write through — configure() swaps in the
    # served settings (spool dir, objectives) atomically.
    flightrec.configure(
        spool_dir=getattr(serving, "flight_spool_dir", "") or "")
    slo.configure(
        ttft_p95_ms=getattr(serving, "slo_ttft_p95_ms", 0.0),
        error_rate=getattr(serving, "slo_error_rate", 0.01))
    # Device telemetry: configure() carries over the cost model + HBM
    # samplers the engine installed during construction above.
    devmon.configure(
        device_kind=jax.devices()[0].device_kind,
        enabled=getattr(serving, "devmon_enabled", True),
        peak_tflops=getattr(serving, "devmon_peak_tflops", 197.0),
        hbm_gbps=getattr(serving, "devmon_peak_hbm_gbps", 819.0),
        hbm_tolerance_mb=getattr(serving, "devmon_hbm_tolerance_mb", 64.0))
    # Capacity estimator: configure() carries over the engine closures
    # (queue depth, throughput fallback) installed during construction.
    capacity.configure(
        enabled=getattr(serving, "capacity_enabled", True),
        headroom_s=getattr(serving, "capacity_headroom_s", 5.5),
        window_s=getattr(serving, "capacity_window_s", 60.0),
        trend_window_s=getattr(serving, "capacity_trend_window_s", 300.0))
    return state


def serve(state: ServerState, host: str, port: int,
          ready_event: Optional[threading.Event] = None,
          stop_event: Optional[threading.Event] = None):
    """Run engine thread + HTTP server until stop_event (or forever).

    The HTTP server always runs on its own thread and this function blocks
    on ``stop_event`` — the one shape that lets a SIGTERM handler or
    POST /admin/drain stop the process from any thread after a graceful
    drain (state.begin_drain sets the stop once in-flight work finishes)."""
    stop = stop_event or threading.Event()
    state.stop_event = stop
    engine_thread = threading.Thread(
        target=state.engine.run_forever, args=(stop,), daemon=True,
        name="engine-loop")
    engine_thread.start()

    class BoundHandler(Handler):
        pass

    BoundHandler.state = state
    httpd = ThreadingHTTPServer((host, port), BoundHandler)
    httpd.daemon_threads = True
    log.info("serving %s on %s:%d (%d slots, cache %d)", state.model_name,
             host, port, state.engine.num_slots, state.engine.max_len)
    server_thread = threading.Thread(target=httpd.serve_forever,
                                     daemon=True, name="http")
    server_thread.start()
    # ready: from here on a step program that traces or compiles stalls
    # live streams (tpu_serve_serving_compiles_total, flight "compile")
    metrics.compile_stages.serving = True
    if ready_event is not None:
        ready_event.set()
    try:
        stop.wait()
    except KeyboardInterrupt:
        stop.set()
    httpd.shutdown()
    # Close the LISTENING socket too: shutdown() only stops the accept
    # loop, leaving connects to land in the kernel backlog and black-hole
    # — a stopped replica must refuse connections so a gateway's
    # connect-phase failover (router.py) sees it dead immediately.
    httpd.server_close()


def build_parser() -> argparse.ArgumentParser:
    """The server's command line. ``main`` and anything that must serve the
    SAME configuration a user's flags produce (chip_smoke.py) parse here."""
    p = argparse.ArgumentParser(description="TPU-native OpenAI-compatible "
                                            "LLM server")
    p.add_argument("--model", default="Qwen/Qwen3-0.6B")
    p.add_argument("--checkpoint-dir", default="")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--max-decode-slots", type=int, default=32)
    p.add_argument("--max-cache-len", type=int, default=2048)
    p.add_argument("--dtype", default="bfloat16",
                   choices=["bfloat16", "float32"])
    p.add_argument("--kv-dtype", default="auto", choices=["auto", "int8"],
                   help="KV-cache storage dtype; int8 halves cache HBM "
                        "footprint/bandwidth (~2x the decode slots per chip)")
    p.add_argument("--weights-dtype", default="int8",
                   choices=["int8", "bf16", "auto"],
                   help="weight storage dtype; int8 (the shipped default) "
                        "halves the weight HBM stream — the dominant "
                        "bytes/token term at small batch (weights-only "
                        "per-channel quantization; compute stays bf16 on "
                        "the MXU). 'bf16' (alias 'auto') is the explicit "
                        "full-precision opt-out")
    p.add_argument("--decode-bblock", type=int, default=0,
                   help="decode kernel batch-block (slots per grid step); "
                        "0 = autotune over {1,4,8} at startup (TPU only)")
    p.add_argument("--decode-pipeline", type=int, default=1,
                   help="one-deep asynchronous decode pipeline: dispatch "
                        "N+1 is enqueued before N's tokens are fetched, "
                        "hiding host emit/SSE time behind device compute "
                        "(seeded streams stay byte-identical). 0 restores "
                        "the synchronous dispatch-fetch-emit loop")
    p.add_argument("--decode-horizon", type=int, default=8,
                   help="AT MOST this many tokens a slot a fused decode "
                        "dispatch generates (ServingConfig.decode_horizon): "
                        "the engine runs them all while every slot holds a "
                        "stream that outlasts what is in flight, and the "
                        "fewest substeps that keep the device fed while an "
                        "admission can follow the dispatch (a slot free, a "
                        "stream about to end, a request waiting), so a "
                        "caller waits behind a few decode steps, not a "
                        "horizon")
    p.add_argument("--ragged-attention", type=int, default=1,
                   help="ragged mixed-batch attention: chunked prefill "
                        "packs into the SAME dispatch as the decode batch "
                        "(one program, paged pool), so admissions stop "
                        "draining the decode pipeline. 0 restores the "
                        "legacy serialized chunk walk (sync escape hatch; "
                        "seeded streams stay byte-identical)")
    p.add_argument("--ragged-features", type=int, default=1,
                   help="feature paths ride the ragged pipeline: guided "
                        "decoding's FSM mask becomes a device-resident "
                        "per-row operand, LoRA rows select adapters inside "
                        "the packed layout, and spec-decode verify hands "
                        "the carry off without draining. 0 restores the "
                        "per-feature sync fallback (byte-identity A/B arm)")
    p.add_argument("--kv-host-tier-bytes", type=int, default=256 * 2**20,
                   help="byte budget for the tier-2 host-RAM KV store: "
                        "evicted prefix pages spill here and restore via "
                        "one batched device_put instead of re-prefilling "
                        "(paged mode only). 0 disables the tier — the "
                        "byte-identity escape hatch")
    p.add_argument("--chat-template", default="",
                   help="path to a Jinja chat template file")
    p.add_argument("--platform", default="",
                   help="force a JAX platform (e.g. cpu for dry-run)")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel degree (shards heads/MLP over the "
                        "ICI mesh; needs tp devices)")
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel degree (shards decode slots)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel degree (MoE models: shards experts "
                        "over the mesh; GSPMD emits the dispatch collectives)")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="chunked prefill size; 0 disables (long prompts "
                        "then cap at the largest bucket)")
    p.add_argument("--prefill-buckets", default="",
                   help="comma-separated prompt-length buckets of the "
                        "whole-prompt prefill programs (default: powers of "
                        "two from 32 to 2048). With --prefill-chunk a prompt "
                        "longer than the chunk is chunked whatever the "
                        "buckets say, so a bucket above the chunk compiles "
                        "nothing: it names a length the deployment serves")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable automatic prompt-prefix K/V reuse")
    p.add_argument("--spec-decode", action="store_true",
                   help="prompt-lookup speculative decoding (greedy-lossless "
                        "multi-token steps; runs single-device and under "
                        "pure-tp meshes)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="draft tokens per speculative step")
    p.add_argument("--spec-method", default="prompt_lookup",
                   choices=["prompt_lookup", "draft"],
                   help="proposal source: context n-gram matching, or a "
                        "small draft LM (--draft-checkpoint-dir)")
    p.add_argument("--draft-checkpoint-dir", default="",
                   help="HF checkpoint dir of the draft model "
                        "(spec_method=draft)")
    p.add_argument("--lora", action="append", default=[],
                   metavar="NAME=PATH",
                   help="register a peft LoRA adapter dir, served as model "
                        "id NAME (repeatable; vLLM --enable-lora parity)")
    p.add_argument("--request-timeout", type=float, default=600.0,
                   help="default/maximum end-to-end deadline in seconds "
                        "(per-request X-Request-Deadline-Ms / deadline_ms "
                        "is capped by it; 0 disables)")
    p.add_argument("--max-queue-depth", type=int, default=256,
                   help="bounded engine queue: admissions past this depth "
                        "are shed with 429 + Retry-After (0 = unbounded)")
    p.add_argument("--drain-timeout", type=float, default=30.0,
                   help="graceful-drain budget in seconds: on SIGTERM or "
                        "POST /admin/drain, stop admitting (503 draining, "
                        "/readyz 503) and let in-flight requests finish up "
                        "to this long before exiting 0; stragglers are "
                        "cancelled through the deadline path")
    p.add_argument("--admission-max-wait", type=float, default=0.0,
                   help="shed admissions whose estimated queue wait "
                        "(seconds) exceeds this (0 disables)")
    p.add_argument("--otlp-endpoint", default="",
                   help="OTLP/HTTP trace collector base URL (spans POST to "
                        "<endpoint>/v1/traces); empty falls back to "
                        "$OTEL_EXPORTER_OTLP_ENDPOINT, neither = tracing "
                        "stays local (ids still echo in responses)")
    p.add_argument("--trace-sample", type=float, default=1.0,
                   help="root-span sampling probability in [0, 1]; "
                        "propagated contexts keep the caller's decision")
    p.add_argument("--slo-ttft-p95-ms", type=float, default=0.0,
                   help="TTFT p95 objective in milliseconds: first tokens "
                        "slower than this burn the 5%% latency error budget "
                        "(tpu_serve_slo_burn_rate{objective=\"ttft_p95\"}); "
                        "0 disables the objective")
    p.add_argument("--slo-error-rate", type=float, default=0.01,
                   help="error-rate SLO budget: the allowed fraction of "
                        "requests finishing error/timeout; burn rate 1.0 "
                        "means failing exactly at budget (0 disables)")
    p.add_argument("--flight-spool-dir", default="",
                   help="directory for the flight recorder's anomaly dump "
                        "spool (capped JSONL; rolled at 16 MiB); empty "
                        "keeps dumps in memory only (/debug/flight/<id>)")
    p.add_argument("--devmon-peak-tflops", type=float, default=197.0,
                   help="per-chip peak TFLOP/s the tpu_device_mfu gauges "
                        "divide by (default: v5e bf16; set per TPU "
                        "generation)")
    p.add_argument("--devmon-peak-hbm-gbps", type=float, default=819.0,
                   help="per-chip peak HBM GB/s the tpu_device_membw_util "
                        "gauges divide by (default: v5e)")
    p.add_argument("--devmon-hbm-tolerance-mb", type=float, default=64.0,
                   help="live-vs-compiled HBM drift tolerance in MB before "
                        "the /healthz hbm_drift verdict flips to 'warn' "
                        "(warn-only; never fails probes)")
    p.add_argument("--no-devmon", action="store_true",
                   help="disable device telemetry recording (the "
                        "tpu_device_* gauges freeze at their defaults)")
    p.add_argument("--capacity-headroom-s", type=float, default=5.5,
                   help="forecast headroom the recommended_replicas figure "
                        "buys, in seconds — set to the AOT registry's "
                        "measured ready-time (BENCH_coldstart_r01: 5.5 s) "
                        "so a replica started on the signal is serving "
                        "before the projected demand lands")
    p.add_argument("--capacity-window-s", type=float, default=60.0,
                   help="sliding window for the offered-load and "
                        "utilization rates (tpu_capacity_offered_tps)")
    p.add_argument("--capacity-trend-window-s", type=float, default=300.0,
                   help="longer window the saturation forecast fits its "
                        "EWMA + linear trend over")
    p.add_argument("--no-capacity", action="store_true",
                   help="disable the capacity estimator (the "
                        "tpu_capacity_* gauges freeze at their defaults; "
                        "/healthz keeps an empty-ish capacity block)")
    p.add_argument("--no-warmup", action="store_true")
    p.add_argument("--aot-manifest", default="",
                   help="AOT compile manifest (serving/aot.py) to adopt: "
                        "fingerprint-checked against this engine, HBM fit "
                        "enforced, ledger surfaced on /healthz and the "
                        "tpu_serve_hbm_compiled_bytes gauge")
    p.add_argument("-v", "--verbose", action="store_true")
    return p


def serving_config_from_args(args):
    """The ServingConfig the parsed flags describe."""
    from aws_k8s_ansible_provisioner_tpu.config import (MeshConfig,
                                                        ServingConfig)

    return ServingConfig(
        model=args.model, port=args.port, host=args.host,
        max_decode_slots=args.max_decode_slots,
        max_cache_len=args.max_cache_len, dtype=args.dtype,
        kv_dtype=args.kv_dtype, weights_dtype=args.weights_dtype,
        decode_bblock=args.decode_bblock,
        decode_horizon=args.decode_horizon,
        decode_pipeline=args.decode_pipeline,
        ragged_attention=args.ragged_attention,
        ragged_features=args.ragged_features,
        kv_host_tier_bytes=args.kv_host_tier_bytes,
        checkpoint_dir=args.checkpoint_dir, chat_template=args.chat_template,
        prefill_chunk=args.prefill_chunk,
        **({"prefill_buckets": tuple(sorted(
            int(b) for b in args.prefill_buckets.split(",")))}
           if args.prefill_buckets else {}),
        prefix_cache=not args.no_prefix_cache,
        spec_decode=args.spec_decode, spec_k=args.spec_k,
        spec_method=args.spec_method,
        draft_checkpoint_dir=args.draft_checkpoint_dir,
        lora_adapters=tuple(args.lora),
        request_timeout_s=args.request_timeout,
        max_queue_depth=args.max_queue_depth,
        admission_max_wait_s=args.admission_max_wait,
        drain_timeout_s=args.drain_timeout,
        otlp_endpoint=args.otlp_endpoint,
        trace_sample=args.trace_sample,
        slo_ttft_p95_ms=args.slo_ttft_p95_ms,
        slo_error_rate=args.slo_error_rate,
        flight_spool_dir=args.flight_spool_dir,
        devmon_enabled=not args.no_devmon,
        devmon_peak_tflops=args.devmon_peak_tflops,
        devmon_peak_hbm_gbps=args.devmon_peak_hbm_gbps,
        devmon_hbm_tolerance_mb=args.devmon_hbm_tolerance_mb,
        capacity_enabled=not args.no_capacity,
        capacity_headroom_s=args.capacity_headroom_s,
        capacity_window_s=args.capacity_window_s,
        capacity_trend_window_s=args.capacity_trend_window_s,
        mesh=MeshConfig(dp=args.dp, tp=args.tp, ep=args.ep))


def main(argv=None):
    args = build_parser().parse_args(argv)

    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    # Persistent XLA compilation cache: warmup compiles ~20 programs; a
    # CONTAINER restart (the liveness probe's stall-recovery kick) must not
    # pay that again. The serving manifest backs the path with an emptyDir
    # and pins JAX_COMPILATION_CACHE_DIR to it — pod-level restarts
    # (rollout, node drain) start cold; back the path with a PVC if rollout
    # survival matters. Placement rule: utils/compile_cache.py.
    try:
        from aws_k8s_ansible_provisioner_tpu.utils.compile_cache import (
            enable_compile_cache)

        log.info("persistent compile cache: %s", enable_compile_cache())
    # tpulint: disable=R3 startup nicety — a missing compile cache slows warmup but must never block serving; warning carries the traceback
    except Exception:
        log.warning("persistent compile cache unavailable", exc_info=True)

    serving = serving_config_from_args(args)
    state = build_state(serving)
    if args.aot_manifest:
        # Fail fast BEFORE warmup: a mismatched or no-fit manifest means the
        # deploy pipeline compiled a different program set than this engine
        # would dispatch — compiling anyway just delays the error to OOM.
        aot = state.engine.load_aot_manifest(args.aot_manifest)
        log.info("AOT manifest adopted: %d programs, %.1fs compile on "
                 "%s, HBM %.2f GiB/chip (headroom %.2f GiB)",
                 aot["programs"], aot["total_compile_seconds"],
                 aot["platform"], aot["hbm_total_bytes"] / 2**30,
                 aot["hbm_headroom_bytes"] / 2**30)
    if not args.no_warmup:
        log.info("warmup: compiling %d prefill buckets + decode ...",
                 len(state.engine.buckets))
        t0 = time.monotonic()
        state.engine.warmup()
        log.info("warmup done in %.1fs", time.monotonic() - t0)
    # Graceful termination (r8): SIGTERM (k8s pod deletion, after the
    # preStop hook's explicit /admin/drain) flips the engine to draining —
    # new requests shed 503, /readyz 503 so the Service stops routing here,
    # in-flight requests finish up to drain_timeout_s — then serve()'s stop
    # fires and the process exits 0 with zero dropped in-flight requests.
    import signal

    def _on_sigterm(signum, frame):
        log.info("SIGTERM: graceful drain (timeout %.1fs)",
                 args.drain_timeout)
        state.begin_drain()

    signal.signal(signal.SIGTERM, _on_sigterm)
    serve(state, args.host, args.port)
    log.info("drained and stopped; exiting 0")


if __name__ == "__main__":
    main()
