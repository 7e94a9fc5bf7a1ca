"""Minimal Prometheus metrics registry (text exposition format, no deps).

The scrape contract comes from the reference's observability layer: the OTEL
collector discovers pods by annotation and scrapes ``/metrics`` on port 8000
(``otel-observability-setup.yaml:337-391``), and its printed PromQL cookbook
queries ``vllm_request_total``-style counters and duration histogram buckets
(``:754-761``). We emit the same *shapes* under the ``tpu_serve_`` prefix plus
vllm-compatible aliases so the unchanged dashboards/cookbook keep working
(SURVEY.md §7 capability contract item 6).

Both exposition formats are supported from the same registries: classic
Prometheus text (``text/plain; version=0.0.4``, the default) and OpenMetrics
(``application/openmetrics-text``) when the scraper's Accept header asks for
it. OpenMetrics mode adds exemplars to histogram *bucket* lines only — the
``# {trace_id="..."} v`` tail that lets Grafana jump from a burning latency
bucket straight to the Tempo trace (and from there to the flight dump). The
route handler appends the single ``# EOF`` terminator after concatenating
every registry; ``render()`` never writes it so registries stay composable.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

_LabelKey = Tuple[Tuple[str, str], ...]


class Counter:
    def __init__(self, name: str, help_: str, labelnames: Sequence[str] = ()):
        self.name, self.help = name, help_
        self.labelnames = tuple(labelnames)
        self._values: Dict[_LabelKey, float] = {}
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def total(self) -> float:
        """Sum over all label combinations (bench/test introspection)."""
        with self._lock:
            return sum(self._values.values())

    def value(self, **labels) -> float:
        """One label combination's count (/healthz tier splits, tests)."""
        key = tuple(sorted(labels.items()))
        with self._lock:
            return self._values.get(key, 0.0)

    def collect(self, openmetrics: bool = False) -> List[str]:
        # OpenMetrics names the counter FAMILY without the _total suffix
        # (samples keep it); classic text uses the full name everywhere.
        fam = self.name
        if openmetrics and fam.endswith("_total"):
            fam = fam[:-len("_total")]
        out = [f"# HELP {fam} {self.help}", f"# TYPE {fam} counter"]
        for key, val in sorted(self._values.items()):
            out.append(f"{self.name}{_fmt_labels(key)} {val}")
        if not self._values:
            out.append(f"{self.name} 0")
        return out


class Gauge:
    """Gauge, optionally labeled (e.g. tpu_serve_slo_burn_rate{objective,
    window}). The unlabeled form keeps the original single-value behavior:
    it always renders exactly one sample, 0.0 until the first set()."""

    def __init__(self, name: str, help_: str, labelnames: Sequence[str] = ()):
        self.name, self.help = name, help_
        self.labelnames = tuple(labelnames)
        self._values: Dict[_LabelKey, float] = {}
        self._lock = threading.Lock()

    def set(self, v: float, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = float(v)

    def add(self, v: float, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + v

    def value(self, **labels) -> float:
        """Current value (admission-control wait estimation, tests)."""
        key = tuple(sorted(labels.items()))
        with self._lock:
            return self._values.get(key, 0.0)

    def collect(self, openmetrics: bool = False) -> List[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} gauge"]
        with self._lock:
            for key, val in sorted(self._values.items()):
                out.append(f"{self.name}{_fmt_labels(key)} {val}")
            if not self._values:
                out.append(f"{self.name} 0.0")
        return out


class Histogram:
    """Prometheus histogram with explicit buckets (for request/TTFT latency)."""

    DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                       10.0, 30.0, 60.0)

    def __init__(self, name: str, help_: str,
                 buckets: Optional[Sequence[float]] = None):
        self.name, self.help = name, help_
        self.buckets = tuple(buckets or self.DEFAULT_BUCKETS)
        self._counts = [0] * (len(self.buckets) + 1)
        # last exemplar per bucket (incl +Inf): (trace_id, observed value).
        # One slot per bucket — "most recent wins", the standard client
        # behavior; rendered only in OpenMetrics mode, on bucket lines only.
        self._exemplars: List[Optional[Tuple[str, float]]] = \
            [None] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._total = 0
        self._lock = threading.Lock()

    def observe(self, v: float, trace_id: Optional[str] = None):
        with self._lock:
            self._sum += v
            self._total += 1
            placed = False
            for i, b in enumerate(self.buckets):
                if v <= b:
                    self._counts[i] += 1
                    if trace_id and not placed:
                        # exemplar lives on the LOWEST bucket containing
                        # the observation (where it "falls")
                        self._exemplars[i] = (str(trace_id), v)
                        placed = True
            self._counts[-1] += 1  # +Inf
            if trace_id and not placed:
                self._exemplars[-1] = (str(trace_id), v)

    def _exemplar_tail(self, i: int, openmetrics: bool) -> str:
        ex = self._exemplars[i]
        if not openmetrics or ex is None:
            return ""
        tid, v = ex
        return f' # {{trace_id="{_escape_label_value(tid)}"}} {v}'

    def collect(self, openmetrics: bool = False) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        for i, b in enumerate(self.buckets):
            out.append(f'{self.name}_bucket{{le="{b}"}} {self._counts[i]}'
                       + self._exemplar_tail(i, openmetrics))
        out.append(f'{self.name}_bucket{{le="+Inf"}} {self._counts[-1]}'
                   + self._exemplar_tail(len(self.buckets), openmetrics))
        out.append(f"{self.name}_sum {self._sum}")
        out.append(f"{self.name}_count {self._total}")
        return out


def _escape_label_value(v) -> str:
    """Exposition-format label-value escaping (shared by both formats):
    backslash, double-quote, and line-feed must be escaped or a crafted
    value (a model name, a trace id) corrupts the whole scrape."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_labels(key: _LabelKey) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{_escape_label_value(v)}"' for k, v in key)
    return "{" + inner + "}"


class Registry:
    def __init__(self):
        self._metrics: List = []
        self._lock = threading.Lock()

    def register(self, m):
        with self._lock:
            self._metrics.append(m)
        return m

    def render(self, openmetrics: bool = False) -> str:
        lines: List[str] = []
        with self._lock:
            for m in self._metrics:
                lines.extend(m.collect(openmetrics))
        return "\n".join(lines) + "\n"


class EngineMetrics:
    """The engine's metric set; names mirror the vLLM ones the reference scrapes."""

    def __init__(self):
        self.registry = Registry()
        r = self.registry
        self.request_total = r.register(Counter(
            "tpu_serve_request_total", "Total requests", ("status",)))
        # vllm-compatible alias so the reference's PromQL cookbook
        # (otel-observability-setup.yaml:758-761) works unchanged.
        self.vllm_request_total = r.register(Counter(
            "vllm_request_total", "Total requests (vllm-compatible alias)",
            ("status",)))
        self.active_requests = r.register(Gauge(
            "tpu_serve_active_requests", "Requests currently in decode slots"))
        self.queue_depth = r.register(Gauge(
            "tpu_serve_queue_depth", "Requests waiting for a slot"))
        self.generated_tokens = r.register(Counter(
            "tpu_serve_generated_tokens_total", "Generated tokens"))
        # one item = the tokens ONE dispatch produced for ONE stream = one
        # wake-up of that stream's handler thread: generated_tokens_total /
        # stream_items_total is the tokens a wake-up carries (the decode
        # horizon under steady load, 1 where the horizon is 1)
        self.stream_items = r.register(Counter(
            "tpu_serve_stream_items_total",
            "Queue items put to streamed requests (tokens of one dispatch "
            "for one stream; one handler wake-up each)"))
        self.prompt_tokens = r.register(Counter(
            "tpu_serve_prompt_tokens_total", "Prompt tokens prefilled"))
        # the sampled rows alone (1, N, 1, slots + 1 a dispatch); every padded
        # row only where prompt_logprobs reads them all
        self.head_rows = r.register(Counter(
            "tpu_serve_head_rows_total",
            "Rows the model's head (final norm + vocabulary matmul) ran "
            "over in prefill-type dispatches", ("program",)))
        self.request_duration = r.register(Histogram(
            "tpu_serve_request_duration_seconds", "End-to-end request latency"))
        self.vllm_request_duration = r.register(Histogram(
            "vllm_request_duration_seconds",
            "End-to-end request latency (vllm-compatible alias)"))
        self.ttft = r.register(Histogram(
            "tpu_serve_time_to_first_token_seconds", "Time to first token"))
        self.tokens_per_second = r.register(Gauge(
            "tpu_serve_tokens_per_second", "Recent decode throughput"))
        # Decode pipeline (perf_opt r9): bubble = device idle between a
        # dispatch completing with nothing enqueued behind it and the next
        # enqueue (host emit/SSE/scheduling time). Synchronous mode pays it
        # every dispatch; the one-deep pipeline hides it behind device
        # compute, so bubble-rate ~0 is the success signal.
        self.decode_bubble_seconds = r.register(Counter(
            "tpu_serve_decode_bubble_seconds_total",
            "Device idle seconds between decode dispatches (host bubble)"))
        self.pipeline_depth = r.register(Gauge(
            "tpu_serve_pipeline_depth",
            "Decode dispatches currently in flight past the fetched one "
            "(1 = pipelined steady state, 0 = synchronous/drained)"))
        # How the slot of an admission that ended a ragged mixed chunk walk
        # joined the batch (programs._advance_chunk_mixed): "in_flight" —
        # the final mixed dispatch stayed in flight, the slot joined from
        # its device carry and the first token went out at its fetch —, or
        # "settled" — the dispatch was fetched before anything else was
        # enqueued (a resume, penalties, a guided request, prompt_logprobs,
        # spec decode, a draining engine), which idles the device for the
        # host's turn and books it as decode bubble above.
        self.activations = r.register(Counter(
            "tpu_serve_activations_total",
            "Admissions that ended a ragged mixed chunk walk, by how the "
            "slot joined the batch", ("path",)))
        # Wall time spent inside device dispatches (prefill + decode). The
        # node metrics exporter scrapes this across the process boundary and
        # derives tpu_duty_cycle_percent from its rate — the engine process
        # owns the chips, so only it can measure busy time (VERDICT r1
        # missing #5: the exporter published constant zeros in production).
        self.device_busy_seconds = r.register(Counter(
            "tpu_serve_device_busy_seconds_total",
            "Seconds spent in device dispatches (duty-cycle source)"))
        # MoE models only (the counters stay empty for a dense model): what
        # the expert layers of the decode and mixed dispatches were given,
        # from the dispatch record (programs._dispatch_close). Bytes an MoE
        # step streams follow the experts HIT, not the expert count:
        # experts_hit_total / forward_passes_total is the mean a layer
        # streamed, routed_rows_total / forward_passes_total the rows it had.
        self.moe_routed_rows = r.register(Counter(
            "tpu_serve_moe_routed_rows_total",
            "(token, expert) rows of live tokens routed through the expert "
            "layers, per layer, by step program", ("program",)))
        self.moe_experts_hit = r.register(Counter(
            "tpu_serve_moe_experts_hit_total",
            "Experts with at least one live row, mean over layers, summed "
            "over forward passes, by step program", ("program",)))
        self.moe_forward_passes = r.register(Counter(
            "tpu_serve_moe_forward_passes_total",
            "Forward passes (decode substeps, mixed steps) the two MoE "
            "totals above sum over, by step program", ("program",)))
        self.moe_group_rows_max = r.register(Gauge(
            "tpu_serve_moe_group_rows_max",
            "Rows of the largest expert group in the last decode or mixed "
            "dispatch"))
        self.moe_rows_held = r.register(Counter(
            "tpu_serve_moe_rows_held_total",
            "(token, expert) rows of live tokens that landed on an expert "
            "held on this chip (an expert share routes over more experts "
            "than it holds), per layer, by step program", ("program",)))
        # live / walked is the fill of the decode kernel's page walk: a grid
        # step walks the pages of its block's longest row for every row, and
        # a row copies only the pages it holds (copied == live)
        self.decode_attn_pages = r.register(Counter(
            "tpu_serve_decode_attn_pages_total",
            "Pages of the plain decode dispatches' attention, per attending "
            "layer, summed over substeps: kind=\"live\" the pages the rows "
            "hold, kind=\"walked\" the pages their blocks walk (block rows "
            "x the block's longest row, blocks cut in order of length), "
            "kind=\"copied\" the pages the kernel's copies fetch (a row "
            "past its own pages starts no copy)",
            ("kind",)))
        self.window_attn_pages = r.register(Counter(
            "tpu_serve_window_attn_pages_total",
            "tpu_serve_decode_attn_pages_total for the WINDOW layers of a "
            "list that also holds full ones (which that family then "
            "counts), per window layer: the pages inside the rows' "
            "windows, those their blocks walk and those the copies fetch",
            ("kind",)))
        self.ragged_page_steps = r.register(Counter(
            "tpu_serve_ragged_page_steps_total",
            "Page steps (one page fetched and folded into a flash state) "
            "the ragged kernel walks for the chunk rows of the mixed "
            "dispatches, over every attending layer: path=\"tile\" as its "
            "grid steps are cut — a tile of chunk rows is one walk —, "
            "path=\"by8\" what blocks of decode_bblock rows walk for the "
            "same rows; their ratio is how much wider the tile is where "
            "it engages", ("path",)))
        self.sample_dispatches = r.register(Counter(
            "tpu_serve_sample_dispatches_total",
            "Dispatches by what their sampler ran, by step program: "
            "path=\"greedy\" no sampled row had a temperature above zero "
            "(idle slots read zero), so it took the argmax alone; "
            "path=\"candidates\" a row drew, so every row's top-64 sort, "
            "nucleus and draws ran", ("program", "path")))
        self.decode_dispatches = r.register(Counter(
            "tpu_serve_decode_dispatches_total",
            "Fused decode dispatches by the substeps they ran: "
            "substeps=\"whole\" the configured horizon (no admission could "
            "follow the dispatch), substeps=\"short\" fewer (one could, or "
            "a path capped the count)", ("substeps",)))
        self.decode_substeps = r.register(Counter(
            "tpu_serve_decode_substeps_total",
            "Substeps the fused decode dispatches ran; over "
            "tpu_serve_decode_dispatches_total the mean count a dispatch"))
        self.mixed_steps = r.register(Counter(
            "tpu_serve_mixed_steps_total",
            "Mixed dispatches by the body of the ONE mixed_step program "
            "that ran: body=\"narrow\" the layers over the slots and half "
            "a chunk's rows (the chunk's tokens fit them), body=\"wide\" "
            "over the whole chunk's", ("body",)))
        self.kda_rows = r.register(Counter(
            "tpu_serve_kda_rows_total",
            "Rows that advanced a KDA layer's recurrent state, per layer, by "
            "step program (a model with KDA layers; any recurrent kind: "
            "tpu_serve_state_rows_total)", ("program",)))
        self.state_rows = r.register(Counter(
            "tpu_serve_state_rows_total",
            "Rows that advanced a recurrent state, per layer, by the kind "
            "of layer that keeps it (KDA, Lightning, conv, SSM) and step "
            "program",
            ("kind", "program")))
        self.recurrent_state_bytes = r.register(Gauge(
            "tpu_serve_recurrent_state_bytes",
            "Bytes of per-slot recurrent state held beside the KV pool, by "
            "the kind of layer that keeps it", ("kind",)))
        self.selector_cache_bytes = r.register(Gauge(
            "tpu_serve_selector_cache_bytes",
            "Bytes of the pool's selector cache (run sums of keys a page: "
            "a model whose attention selects its pages)"))
        self.sparse_pages = r.register(Counter(
            "tpu_serve_sparse_pages_total",
            "Pages of the decode and mixed dispatches' selecting attention, "
            "per selecting layer and (row, KV head), summed over substeps: "
            "kind=\"live\" the pages the rows hold, kind=\"selected\" the "
            "pages they read", ("kind",)))
        self.kda_state_bytes = r.register(Gauge(
            "tpu_serve_kda_state_bytes",
            "Bytes of per-slot recurrent state held beside the KV pool"))
        self.conv_state_bytes = r.register(Gauge(
            "tpu_serve_conv_state_bytes",
            "Bytes of the gated short convolutions' per-slot tails (the "
            "conv_taps - 1 rows before a span, float32) held beside the KV "
            "pool"))
        self.ssm_state_bytes = r.register(Gauge(
            "tpu_serve_ssm_state_bytes",
            "Bytes of the state-space mixers' per-slot leaves (the float32 "
            "[heads, d_state, d_head] state and the conv tail) held beside "
            "the KV pool"))
        self.ssm_span_rows = r.register(Counter(
            "tpu_serve_ssm_span_rows_total",
            "Chunk rows a mixed step ran through the state-space mixers' "
            "span form, per layer"))
        self.prefix_lookups_skipped = r.register(Counter(
            "tpu_serve_prefix_lookups_skipped_total",
            "Admissions that did not consult the prefix index, by reason",
            ("reason",)))
        self.prefix_cache_hits = r.register(Counter(
            "tpu_serve_prefix_cache_hits_total",
            "Requests that reused a cached prompt prefix"))
        self.prefix_tokens_reused = r.register(Counter(
            "tpu_serve_prefix_tokens_reused_total",
            "Prompt tokens served from the prefix cache instead of prefill"))
        self.spec_drafted_tokens = r.register(Counter(
            "tpu_serve_spec_drafted_tokens_total",
            "Draft tokens proposed (prompt-lookup or draft-model)"))
        self.spec_accepted_tokens = r.register(Counter(
            "tpu_serve_spec_accepted_tokens_total",
            "Draft tokens accepted by the verify pass"))
        self.spec_acceptance_rate = r.register(Gauge(
            "tpu_serve_spec_acceptance_rate",
            "Cumulative accepted/drafted ratio of speculative decoding"))
        # Paged-KV pool health (vLLM publishes the same trio as
        # vllm:num_preemptions/gpu_cache_usage_perc): preemption spikes or a
        # pinned-high page gauge mean the pool is undersized for the load.
        self.preemptions = r.register(Counter(
            "tpu_serve_preemptions_total",
            "Requests preempted (pages reclaimed; resumed by recompute)"))
        self.kv_pages_total = r.register(Gauge(
            "tpu_serve_kv_pages_total", "Physical KV pages in the pool"))
        self.kv_pages_in_use = r.register(Gauge(
            "tpu_serve_kv_pages_in_use",
            "KV pages currently referenced by live requests"))
        # The evictable share (ISSUE 20 satellite): "pool full" and "pool
        # full of reusable prefixes" are different capacity situations —
        # evictable pages reclaim on demand but still serve prefix hits
        # (free = total - in use - evictable: no family of its own).
        self.kv_pages_evictable = r.register(Gauge(
            "tpu_serve_kv_pages_evictable",
            "Refcount-zero KV pages retained for prefix reuse "
            "(reclaimable on demand)"))
        # Tier-2 KV (host-RAM prefix-page store, ISSUE 20): where each
        # admission's prefix lookup resolved, and the PCIe traffic the tier
        # moves. restore_bytes replaces re-prefill FLOPs; dropped counts
        # corrupted/truncated entries that fell back to re-prefill.
        self.prefix_tier_hits = r.register(Counter(
            "tpu_serve_prefix_tier_hits_total",
            "Paged admissions by prefix-lookup outcome tier",
            ("tier",)))
        self.kv_spill_bytes = r.register(Counter(
            "tpu_serve_kv_spill_bytes_total",
            "KV bytes spilled from reclaimed HBM pages to the host tier"))
        self.kv_restore_bytes = r.register(Counter(
            "tpu_serve_kv_restore_bytes_total",
            "KV bytes restored from the host tier instead of re-prefilled"))
        self.kv_restore_dropped = r.register(Counter(
            "tpu_serve_kv_restore_dropped_total",
            "Host-tier entries dropped at restore (corrupt/truncated/raced "
            "away; the span re-prefilled instead)"))
        # Batch-block size the decode kernels run with (autotuned at engine
        # start per (batch, page_size, kv_dtype) — see
        # Engine._resolve_decode_bblock). A dashboard seeing 1 on a TPU pod
        # means the autotuner was pinned or guarded off.
        self.decode_bblock = r.register(Gauge(
            "tpu_serve_decode_bblock",
            "Decode kernel batch-block size (slots per grid step)"))
        # Cold-start observability (serving/aot.py): warmup compile wall time
        # and the AOT manifest's per-chip HBM ledger. A restart whose compile
        # counter climbs by minutes is missing its persistent compilation
        # cache / AOT manifest; a zero hbm gauge means no manifest was loaded.
        self.compile_seconds = r.register(Counter(
            "tpu_serve_compile_seconds_total",
            "Wall seconds spent compiling programs at warmup"))
        self.hbm_compiled_bytes = r.register(Gauge(
            "tpu_serve_hbm_compiled_bytes",
            "Per-chip HBM bytes the AOT manifest ledger accounts "
            "(params + KV pool + max program temp)"))
        # Robustness layer (r7): overload shedding, end-to-end deadlines,
        # and the stall watchdog each get an explicit first-class signal —
        # a dashboard must distinguish "we refused work by design" from
        # "work failed" (DeepServe: the overload path is the product).
        self.requests_shed = r.register(Counter(
            "tpu_serve_requests_shed_total",
            "Requests rejected at admission (429), by reason",
            ("reason",)))
        self.deadline_expired = r.register(Counter(
            "tpu_serve_deadline_expired_total",
            "Requests cancelled because their end-to-end deadline passed"))
        self.watchdog_stalls = r.register(Counter(
            "tpu_serve_watchdog_stalls_total",
            "Stalled decode steps the watchdog aborted (requests failed, "
            "process kept alive)"))
        self.admission_preemptions = r.register(Counter(
            "tpu_serve_admission_preemptions_total",
            "Lowest-progress requests preempted to unwedge page-starved "
            "admission"))
        # Replica lifecycle (r8): 1 while the engine is draining (rejecting
        # new admissions, finishing in-flight work) — the readiness signal
        # /readyz and the router's /load poller key off the same state.
        self.draining = r.register(Gauge(
            "tpu_serve_draining",
            "1 while the engine is draining (new admissions shed with "
            "reason=draining)"))

    def mark_request(self, status: str, duration_s: float,
                     trace_id: Optional[str] = None):
        self.request_total.inc(status=status)
        self.vllm_request_total.inc(status=status)
        self.request_duration.observe(duration_s, trace_id=trace_id)
        self.vllm_request_duration.observe(duration_s, trace_id=trace_id)
        # Every terminal edge already funnels through here — feed the SLO
        # burn-rate engine from the same single point (serving/slo.py; the
        # deferred import breaks the metrics <- slo module cycle and costs a
        # cached-module dict lookup per request).
        from aws_k8s_ansible_provisioner_tpu.serving import slo as _slo

        _slo.get().observe_request(status, duration_s)


class PipelineMetrics:
    """Process-wide decode-pipeline health counters, shared by every engine
    in the process and rendered by BOTH /metrics routes (engine server and
    router) — same singleton pattern as flightrec/slo/devmon.

    The decode pipeline's whole value is staying ON under mixed traffic
    (PERF.md): every drain discharges the in-flight dispatch early and the
    next decode pays the full host bubble again. This counter makes the
    ragged-attention win — mixed prefill+decode steps riding the pipeline
    instead of killing it — measurable in production, by reason:

    - ``prefill``: a prefill admission / activation invalidated the carry
      (the legacy per-admission drain the ragged path removes);
    - ``chunk``:   a chunked-prefill walk forced the synchronous branch;
    - ``spec``:    speculative decode needed current host mirrors;
    - ``guided``:  a grammar-guided slot forced per-token dispatch;
    - ``drain``:   engine drain / idle settle (intentional, not a loss);
    - ``fail``:    a failed fetch discarded the in-flight dispatch.
    """

    def __init__(self):
        self.registry = Registry()
        r = self.registry
        self.drains = r.register(Counter(
            "tpu_serve_pipeline_drains_total",
            "Decode-pipeline drains (in-flight dispatch discharged early), "
            "by reason",
            ("reason",)))
        self.dispatches = r.register(Counter(
            "tpu_serve_pipeline_dispatches_total",
            "Decode/mixed dispatches enqueued (drain-rate denominator)"))

    def snapshot(self) -> dict:
        """Drain totals by reason + the drain rate (drains per dispatch) for
        /healthz and tpu-top — the one number that says whether the pipeline
        is actually staying open under the current traffic mix."""
        with self.drains._lock:
            by_reason = {(dict(key).get("reason") or "other"): int(val)
                         for key, val in self.drains._values.items()}
        total = sum(by_reason.values())
        dispatched = self.dispatches.total()
        return {
            "drains_total": total,
            "drains_by_reason": by_reason,
            "dispatches_total": int(dispatched),
            "drain_rate": round(total / dispatched, 4) if dispatched else 0.0,
        }


pipeline = PipelineMetrics()


class CompileMetrics:
    """Process-wide compile time by program and stage, fed by the
    ``jax.monitoring`` duration listeners serving/programs.py registers once
    per process; rendered by BOTH /metrics routes beside
    ``tpu_serve_compile_seconds_total`` (which is one number around
    ``warmup()``).

    ``program`` is a closed set: the step programs' names
    (programs.STEP_PROGRAMS) and ``other`` for everything else (nested
    Pallas wrappers, eager ops, a caller's own jits). A nested trace lands
    in ``other`` AND inside the enclosing step program's ``trace`` seconds,
    so sum stages per program, never across ``other``. ``stage`` is
    ``trace`` (Python tracing to a jaxpr), ``lower`` (jaxpr to MLIR, Pallas
    lowering included), ``backend`` (XLA compile, without any cache load)
    or ``cache_load`` (persistent-cache retrieval).

    ``serving`` flips when the server reports ready (server.serve): from
    then on a step program that traces or compiles stalls live streams, and
    each such event counts in ``serving_compiles`` by program.
    """

    def __init__(self):
        self.registry = Registry()
        r = self.registry
        self.stage_seconds = r.register(Counter(
            "tpu_serve_compile_stage_seconds_total",
            "Seconds spent tracing, lowering, compiling or cache-loading "
            "programs, by step program (or other) and stage",
            ("program", "stage")))
        self.serving_compiles = r.register(Counter(
            "tpu_serve_serving_compiles_total",
            "Step programs first traced after the server reported ready "
            "(each stalls every live stream while it compiles)",
            ("program",)))
        self.serving = False

    def stage_totals(self) -> Dict[Tuple[str, str], float]:
        """{(program, stage): seconds} (benchmark readers, tests)."""
        with self.stage_seconds._lock:
            return {(dict(k).get("program", ""), dict(k).get("stage", "")): v
                    for k, v in self.stage_seconds._values.items()}


compile_stages = CompileMetrics()


class ParamMetrics:
    """Process-wide: what each part of the SERVED parameter tree weighs
    (models/parts.py: the closed set of part names the step programs'
    operations carry into the device trace). Set once at engine start-up
    from the tree after quantisation, LoRA attach and sharding, per chip
    (``parts.param_weights``); a process serves one tree, the last engine
    built wins. The one number a weight-stream roofline needs that neither
    the trace nor a dispatch record holds — bytes ÷ HBM bandwidth is the
    floor of the part's device time in a decode step, 2 x rows x elements
    its matmul flops in a prefill — taken from the leaves that are served,
    not from config arithmetic. Rendered by BOTH /metrics routes, as every
    process-wide set is (tpulint R2); empty in a router-only process.
    """

    def __init__(self):
        self.registry = Registry()
        r = self.registry
        self.bytes = r.register(Gauge(
            "tpu_serve_param_bytes",
            "Bytes one chip holds of the parameter leaves a part of the "
            "model reads in one forward pass (kernels with their scales; a "
            "tied embedding under head)", ("part",)))
        self.elements = r.register(Gauge(
            "tpu_serve_param_elements",
            "Matmul elements (kernel leaves) one chip holds, by the part "
            "of the model that multiplies by them", ("part",)))

    def publish(self, weights: Dict[str, Tuple[int, int]]) -> None:
        """Replace both families with ``{part: (bytes, elements)}``."""
        for gauge, i in ((self.bytes, 0), (self.elements, 1)):
            with gauge._lock:
                gauge._values = {(("part", part),): float(w[i])
                                 for part, w in weights.items()}

    def by_part(self) -> Dict[str, Tuple[float, float]]:
        """{part: (bytes, elements)} as published (benchmark readers,
        tests); empty before an engine was built."""
        with self.bytes._lock:
            nbytes = {dict(k)["part"]: v
                      for k, v in self.bytes._values.items()}
        return {part: (b, self.elements.value(part=part))
                for part, b in nbytes.items()}


params_by_part = ParamMetrics()


class WindowPoolMetrics:
    """Process-wide: the second page inventory of a list with window layers
    beside full ones (serving/paged_kv.py; Engine._win_cover writes it) —
    the window layers' pages, which go back as a slot's context passes
    them. ``tpu_serve_kv_pages_*`` keep reading the inventory that grows
    with the context. Process-wide so that a reader without the engine in
    hand finds it (the benchmark's ``win_pages_held_pct``); a process
    serves one model, the last engine built wins. Rendered by BOTH /metrics
    routes (tpulint R2); every family reads 0 for any other model."""

    def __init__(self):
        self.registry = Registry()
        r = self.registry
        self.total = r.register(Gauge(
            "tpu_serve_kv_window_pages_total",
            "Physical pages of the window layers' inventory"))
        self.in_use = r.register(Gauge(
            "tpu_serve_kv_window_pages_in_use",
            "Window-layer pages currently held by slots"))
        self.in_use_peak = r.register(Gauge(
            "tpu_serve_kv_window_pages_in_use_peak",
            "Most window-layer pages held at once, over all slots"))
        self.unreleased_at_peak = r.register(Gauge(
            "tpu_serve_kv_window_pages_unreleased_at_peak",
            "Pages the same slots would have held for the window layers at "
            "that moment with nothing released (their contexts, in pages)"))
        self.slot_peak = r.register(Gauge(
            "tpu_serve_kv_window_pages_slot_peak",
            "Most window-layer pages ONE slot has held at once: bounded by "
            "window + the chunk in flight + a page, whatever its context"))
        self.released = r.register(Counter(
            "tpu_serve_kv_window_pages_released_total",
            "Window-layer pages given back because every row that could "
            "still read them is behind the window (a slot's pages at its "
            "end are not counted)"))

    def reset(self, total: int) -> None:
        """A new engine's inventory of ``total`` pages (0: no such model)."""
        self.total.set(total)
        for g in (self.in_use, self.in_use_peak, self.unreleased_at_peak,
                  self.slot_peak):
            g.set(0)


window_pool = WindowPoolMetrics()
