"""One process, one server, one or more measured windows.

``Session`` does the set-up once (device check, compile cache, native
scheduler, seeded weights, the server's own start sequence, warm-up through
HTTP); ``measure`` runs one plan through a window and returns the end-to-end
numbers, and with ``trace=True`` also what the layer-metric readers need.
run.py makes one measurement; sweep.py several at different rates.
"""

from __future__ import annotations

import os
import shutil
import threading
import time
from dataclasses import dataclass
from typing import List

from benchlib import files, loadgen, stats, trafficgen
from benchlib import server_under_test as sut
from benchlib import warmup as wu

TRACE_DIR = os.path.join(files.ROOT, ".bench_tmp", "trace")


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


class CompileCounter:
    """Programs first compiled or loaded from the persistent cache, with the
    instant: none may fall inside a measured window."""

    def __init__(self):
        self.times: List[float] = []
        self.names: List[tuple] = []      # (instant, "jit(<name>) ...")
        self.hits = self.misses = 0

    def install(self):
        import logging

        import jax

        # JAX names what it compiles only in its log: keep the names, so a
        # shape the warm-up missed can be told from the line of a run
        jax.config.update("jax_log_compiles", True)
        counter = self

        class Names(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    counter.names.append((time.monotonic(), msg[10:90]))

        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
            logging.getLogger(name).addHandler(Names())

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                self.times.append(time.monotonic())
            elif event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(event, duration, **kw):
            # without a persistent cache (rehearsal) count backend compiles
            if event == "/jax/core/compile/backend_compile_duration":
                self.times.append(time.monotonic())

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def inside(self, t0: float, t1: float) -> int:
        """Compile markers in [t0, t1): cache requests and JAX's own
        "Compiling ..." log lines (tracing comes before either, so callers
        ask from a few seconds before the window to the end of its tail)."""
        return sum(1 for t in self.times if t0 <= t < t1) \
            + sum(1 for t, _ in self.names if t0 <= t < t1)


class SpanCollector:
    """An in-memory exporter for the server's tracer (its exporter
    interface: ``export(span, service_name)``), installed for the traced run
    only."""

    def __init__(self):
        self.spans: List[tuple] = []

    def export(self, span, service_name) -> bool:
        self.spans.append((time.monotonic(), span.name, span.start_ns,
                           span.end_ns, dict(span.attributes)))
        return True


@dataclass
class LayerContext:
    """What a layer-metric reader may read. A reader that does not find its
    source returns None and the metric is left out of the line."""
    cell: object
    mc: dict
    peaks: dict
    chips: int
    t0: float
    t1: float
    counters: dict                     # program counters, delta over window
    traced_counters: dict              # delta over the traced slice
    spans: List[tuple]
    samples: List[tuple]               # (t, pages_in_use, pages_total,
                                       #  n_active, sum_ctx_tokens)
    dispatches: List[tuple]            # (kind, t, tokens, steps) in window
    trace: object                      # trace_reduce.Trace or None
    trace_t0: float
    trace_t1: float
    engine: dict                       # horizon, slots, page, itemsizes
    memory_peak_bytes: int
    client: dict                       # the window's client-side numbers


def _counters(eng) -> dict:
    m = eng.metrics
    return {"generated_tokens": m.generated_tokens.total(),
            "prompt_tokens": m.prompt_tokens.total(),
            "prefix_tokens_reused": m.prefix_tokens_reused.total(),
            "prefix_cache_hits": m.prefix_cache_hits.total(),
            "preemptions": m.preemptions.total(),
            "requests": m.request_total.total()}


def _dispatches(t0: float, t1: float) -> List[tuple]:
    """The program's per-dispatch records (serving/devmon.py keeps, per
    program kind, (t, device_s, flops, bytes, tokens, steps) for 60 s; only
    the COUNTS are read here, never its host-timed seconds or its modelled
    flops/bytes). Empty if the record is not there to read."""
    try:
        from aws_k8s_ansible_provisioner_tpu.serving import devmon

        mon = devmon.get()
        with mon._lock:
            return [(kind, e[0], e[4], e[5]) for kind, dq in mon._acc.items()
                    for e in dq if t0 <= e[0] < t1]
    except (ImportError, AttributeError, IndexError):
        return []


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


class Session:
    def __init__(self, cell: files.Cell, seed: int, rehearsal: bool,
                 t_start: float):
        self.cell, self.seed, self.rehearsal = cell, int(seed), rehearsal
        self.t_start = t_start
        import jax

        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": cell.chips}
        if devs[0].platform != "tpu" and not rehearsal:
            raise SystemExit(f"JAX found no TPU (platform "
                             f"{devs[0].platform!r}): the benchmark measures "
                             f"on the chip or not at all")
        if len(devs) < cell.chips:
            raise SystemExit(f"cell {cell.name} needs {cell.chips} chip(s), "
                             f"JAX sees {len(devs)}")
        self.devices = devs[:cell.chips]
        self.peaks = None if rehearsal else files.peaks_for(
            devs[0].device_kind)
        self.compiles = CompileCounter()
        self.compiles.install()
        if not rehearsal:
            # the repo's one rule: JAX_COMPILATION_CACHE_DIR if set, else the
            # fixed .jax_compile_cache/ at the checkout root. The CPU
            # rehearsal leaves it off (serializing interpret-mode Pallas
            # executables has segfaulted: tests/conftest.py).
            from aws_k8s_ansible_provisioner_tpu.utils.compile_cache import (
                enable_compile_cache)

            d = enable_compile_cache(min_compile_secs=0.0)
            say(f"compile cache: {d} "
                f"({len(os.listdir(d)) if os.path.isdir(d) else 0} entries)")
        say(f"device: {devs[0].device_kind} x{len(devs)} "
            f"({devs[0].platform}), cell uses {cell.chips}; jax "
            f"{jax.__version__}")
        sut.build_native_scheduler(say)
        cfg = cell.config
        t0 = time.monotonic()
        maker = files.load_module("weight_makers", cfg["weights_maker"])
        quant = cfg["weights_dtype"] == "int8"
        self.tree = maker.make(
            cfg["model_config"], (int(cfg["weights_seed"]) + self.seed),
            quant, out_shardings=self._shardings(maker, quant))
        jax.block_until_ready(self.tree)
        say(f"weights: {cfg['weights_maker']} {cfg['weights_dtype']} made on "
            f"the device in {time.monotonic() - t0:.1f}s")
        self.srv = sut.Server(cfg, self.tree, say, rehearsal)
        eng = self.srv.engine
        if not rehearsal and type(eng.sched).__name__ != "NativeScheduler":
            raise SystemExit("the native scheduler was built but not loaded")
        self.warm = wu.warm(self.srv, cell.traffic, self.seed, say)
        self.slots = int(eng.num_slots)

    def _shardings(self, maker, quant):
        tp = 1
        flags = self.cell.config["server_flags"]
        if "--tp" in flags:
            tp = int(flags[flags.index("--tp") + 1])
        if tp == 1:
            return None
        # each leaf is created under the sharding the server's own rule
        # (parallel/sharding.param_pspecs) gives it, on the server's mesh
        from aws_k8s_ansible_provisioner_tpu.parallel.sharding import (
            param_shardings)
        from aws_k8s_ansible_provisioner_tpu.serving import server
        from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine

        args = server.build_parser().parse_args(list(flags))
        mesh = Engine._build_mesh(server.serving_config_from_args(args))
        return param_shardings(mesh, sut.model_config_of(self.cell.config),
                               quant_weights=quant)

    # -- one measured window -------------------------------------------------

    def measure(self, seconds: float, trace: bool, traffic: dict = None,
                trace_seconds: float = 3.0) -> dict:
        import jax

        eng = self.srv.engine
        plan = trafficgen.make_plan(traffic or self.cell.traffic, self.seed,
                                    seconds, self.slots)
        run = loadgen.Run(plan, self.srv.port, self.srv.served_model, seconds)
        snap: dict = {}
        samples: List[tuple] = []
        collector = SpanCollector() if trace else None
        stop_sampling = threading.Event()

        def sample_loop():
            m = eng.metrics
            while not stop_sampling.is_set():
                act = [i for i, r in enumerate(eng.slot_req) if r is not None]
                samples.append((
                    time.monotonic(), m.kv_pages_in_use.value(),
                    m.kv_pages_total.value(), len(act),
                    float(eng.lengths[act].sum()) if act else 0.0))
                time.sleep(0.05)

        def on_open():
            snap["c0"] = _counters(eng)
            if trace:
                self.srv.state.tracer.exporter = collector
                threading.Thread(target=sample_loop, daemon=True).start()

        def during(run_):
            if not trace:
                return
            ts = min(trace_seconds, max(0.2, 0.5 * seconds))
            time.sleep(max(0.0, run_.t0 + 0.25 * (seconds - ts)
                           - time.monotonic()))
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            os.makedirs(TRACE_DIR, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            snap["tc0"] = _counters(eng)
            snap["trace_t0"] = time.monotonic()
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            time.sleep(ts)
            snap["trace_t1"] = time.monotonic()
            snap["tc1"] = _counters(eng)
            jax.profiler.stop_trace()
            snap["trace_stop_s"] = time.monotonic() - snap["trace_t1"]

        def on_close():
            snap["c1"] = _counters(eng)
            stop_sampling.set()
            snap["dispatches"] = _dispatches(run.t0, run.t1)

        run.run(on_window_open=on_open, during_window=during,
                on_window_close=on_close)
        out = self._reduce(run, plan, seconds)
        if trace:
            self.srv.state.tracer.exporter = None
            out["layer_context"] = self._layer_context(
                run, snap, samples, collector,
                dict(out["values"], **out["extra"]))
        return out

    def measure_valid(self, seconds: float, trace: bool) -> dict:
        """``measure``, once more if a program compiled inside the window.

        One shape cannot be warmed from outside: ``decode_steps`` at horizon
        1, which the engine picks when a request is submitted between its
        admission pass and its read of the queue depth (a race of a few
        microseconds per arrival; about one window in six here). Its first
        use traces and loads a program inside the window. Such a window is
        void: it becomes set-up (``setup_s`` then includes it) and the
        window is measured again with every shape warm."""
        res = self.measure(seconds, trace)
        if res["compiles_in_window"]:
            say(f"{res['compiles_in_window']} program(s) compiled inside "
                f"the window: window void, counted as set-up; measuring "
                f"again")
            self.srv.wait_idle(60.0)
            # other prompts than the void window's (same sizes, another
            # order): the same ones again would hit the prefix cache it filled
            self.seed += 7919
            res = self.measure(seconds, trace)
        return res

    def _layer_context(self, run, snap, samples, collector,
                       client: dict) -> LayerContext:
        from benchlib import trace_reduce

        eng = self.srv.engine
        tr = None
        path = trace_reduce.find_xplane(TRACE_DIR)
        if path is not None:
            t0 = time.monotonic()
            tr = trace_reduce.load(path)
            say(f"trace: {path} ({os.path.getsize(path) / 2**20:.1f} MiB) "
                f"read in {time.monotonic() - t0:.1f}s; stop_trace took "
                f"{snap.get('trace_stop_s', 0):.1f}s; "
                f"{len(tr.devices)} device plane(s)")
        cfg = self.cell.config
        return LayerContext(
            cell=self.cell, mc=cfg["model_config"], peaks=self.peaks,
            chips=self.cell.chips, t0=run.t0, t1=run.t1,
            counters=_delta(snap["c0"], snap["c1"]),
            traced_counters=_delta(snap["tc0"], snap["tc1"])
            if "tc1" in snap else {},
            spans=[s for s in collector.spans if s[0] >= run.t0],
            samples=[s for s in samples if run.t0 <= s[0] < run.t1],
            dispatches=snap.get("dispatches", []), trace=tr,
            trace_t0=snap.get("trace_t0", 0.0),
            trace_t1=snap.get("trace_t1", 0.0),
            engine={"horizon": int(eng.serving.decode_horizon),
                    "slots": int(eng.num_slots),
                    "page_size": int(eng.serving.page_size),
                    "kv_itemsize": 1 if eng.kv_quant else 2,
                    "w_itemsize": 1 if cfg["weights_dtype"] == "int8" else 2},
            memory_peak_bytes=self.memory_peak_bytes(), client=client)

    def _reduce(self, run: loadgen.Run, plan, seconds: float) -> dict:
        meas = [r for r in run.results if r.measured]
        ok = [r for r in meas if r.ok]
        failed = [r for r in meas if not r.ok]
        malformed = [r for r in meas if r.status == 200 and r.done
                     and r.n_out != r.want_out]
        base = (lambda r: r.due_t) if plan.loop == "open" \
            else (lambda r: r.send_t)
        ttft = [(r.t_first - base(r)) * 1e3 for r in meas
                if r.t_first is not None and not r.aborted]
        tpot = [v for v in (stats.tpot_ms(r.t_first, r.t_last, r.n_out)
                            for r in ok) if v is not None]
        good = [r for r in run.results if not r.error
                and r.status in (None, 200)]
        toks = sum(stats.tokens_in_window(r.chunks, run.t0, run.t1)
                   for r in good)
        for r in failed[:5]:
            say(f"failed request {r.idx}: status {r.status} done {r.done} "
                f"tokens {r.n_out}/{r.want_out} error {r.error!r}")
        late = sorted(run.late_s)
        say(f"window: {seconds:.0f}s, {plan.loop} loop "
            f"{plan.meta}; measured {len(meas)} requests, {len(ok)} ok, "
            f"{len(failed)} failed; ttft samples {len(ttft)}, tpot samples "
            f"{len(tpot)}; tokens delivered in the window {toks}; "
            f"compile markers from 3 s before the window to the end of its "
            f"tail {self.compiles.inside(max(run.t_start, run.t0 - 3.0), time.monotonic())} "
            f"(since traffic began, "
            f"at seconds from the window's opening: "
            f"{[(round(t - run.t0, 1), n) for t, n in self.compiles.names if t >= run.t_start]})"
            + (f"; generator late p50 {stats.percentile(late, 50) * 1e3:.2f}"
               f" ms max {late[-1] * 1e3:.2f} ms over {len(late)} sends"
               if late else "")
            + ("; PLAN EXHAUSTED (raise max_requests)" if run.exhausted
               and plan.loop == "closed" else ""))
        return {
            "attempted": len(meas), "failed": len(failed),
            "malformed": len(malformed),
            "compiles_in_window": self.compiles.inside(
                max(run.t_start, run.t0 - 3.0), time.monotonic()),
            "values": {
                "ttft_p50_ms": stats.percentile(ttft, 50),
                "ttft_p95_ms": stats.percentile(ttft, 95),
                "tpot_p95_ms": stats.percentile(tpot, 95),
                "out_tok_s": toks / seconds if toks else None,
                "setup_s": run.t0 - self.t_start,
            },
            "extra": {"tpot_p50_ms": stats.percentile(tpot, 50),
                      "ttft_samples": len(ttft),
                      # sent before the window closed, not finished by then
                      "in_flight_at_t1": sum(
                          1 for r in run.results if r.send_t < run.t1
                          and not (r.done and r.t_last <= run.t1))},
        }

    def memory_peak_bytes(self) -> int:
        peak = 0
        for d in self.devices:
            st = d.memory_stats() or {}
            peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
        return peak
