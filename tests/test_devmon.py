"""Device telemetry & roofline attribution (serving/devmon.py).

The numbers under test are EXACT, not approximate: DevMon takes an
injectable monotonic clock (slo.py discipline) and a hand-built CostModel,
so every MFU / bandwidth-utilization / dma-wait figure on /debug/roofline
is a deterministic function of the scripted dispatches — the assertions
below carry the hand-computed arithmetic in literals.

Contracts pinned here:

- golden /debug/roofline table under a fake clock (hand-computed MFU,
  membw_util, dma-wait, duty cycle; window expiry forgets);
- HBM drift: inflating the live ledger past the AOT compiled ledger flips
  the /healthz verdict to "warn" and moves tpu_device_hbm_drift_bytes while
  requests keep succeeding (warn-never-kill);
- seeded streams are BYTE-IDENTICAL devmon on vs off (note() is
  observability, never control flow);
- OpenMetrics content negotiation: exemplars render on histogram bucket
  lines only (lowest containing bucket, last-wins), label values escape
  backslash/quote/newline, counter families drop _total, the OM route ends
  with one `# EOF`, the classic route carries none of it.

`make devmon-smoke` runs this file alone; tier-1 runs the same tests via
the ``devmon_smoke`` marker.
"""

import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.serving import devmon, flightrec, slo
from aws_k8s_ansible_provisioner_tpu.serving.devmon import CostModel, DevMon
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request
from aws_k8s_ansible_provisioner_tpu.serving.metrics import (
    Counter, Gauge, Histogram)
from aws_k8s_ansible_provisioner_tpu.serving.server import build_state, serve
from aws_k8s_ansible_provisioner_tpu.utils.tokenizer import ByteTokenizer

pytestmark = pytest.mark.devmon_smoke

MODEL = "tiny-qwen3"
_PORTS = iter(range(18700, 18760))


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


@pytest.fixture(autouse=True)
def fresh_state():
    devmon.reset()
    flightrec.reset()
    slo.reset()
    yield
    devmon.reset()
    flightrec.reset()
    slo.reset()


@pytest.fixture(scope="module")
def model():
    tok = ByteTokenizer()
    cfg = tiny_qwen3(vocab_size=tok.vocab_size, eos_token_id=tok.eos_token_id)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return tok, cfg, params


def _engine(model, **over):
    tok, cfg, params = model
    base = dict(weights_dtype="bf16", model=MODEL, max_decode_slots=2,
                max_cache_len=128, page_size=32,
                prefill_buckets=(16, 32, 64, 128), dtype="float32",
                derived_seed=0)
    base.update(over)
    return Engine(cfg, params, ServingConfig(**base))


def _drain(eng, limit=20000):
    for _ in range(limit):
        if not eng.step():
            return
    raise AssertionError("engine failed to quiesce")


# ---------------------------------------------------------------------------
# Golden roofline arithmetic on a scripted clock
# ---------------------------------------------------------------------------

# Hand-built model: 1 GFLOP per token, 100 MB of weights per step, 1 kB of
# KV per context row. Peaks are clamped to 1 TFLOP/s and 1 GB/s, so every
# ratio below is exact decimal arithmetic.
_CM = CostModel(flops_per_token=1e9, weight_bytes=1e8, kv_row_bytes=1e3)


def _mon(clk, **over):
    kw = dict(peak_tflops=1.0, hbm_gbps=1.0, hbm_tolerance_mb=0.0,
              window_s=60.0, clock=clk)
    kw.update(over)
    m = DevMon(**kw)
    m.install_cost_model(_CM)
    return m


def test_golden_roofline_snapshot_hand_computed():
    clk = FakeClock(1000.0)
    m = _mon(clk)
    clk.t = 1010.0
    # decode: 8 tokens, mean context 100 rows, 4 steps, 0.5 s on device
    #   flops = 8e9;  bytes = 4*1e8 + 8*100*1e3 = 4.008e8
    #   floor = max(8e9/1e12, 4.008e8/1e9) = 0.4008 s  (bandwidth-bound)
    m.note("decode", 0.5, batch=2, tokens=8, ctx_rows=100.0, steps=4)
    # prefill: 64 tokens in one step, 0.25 s on device
    #   flops = 64e9;  bytes = 1e8 + 64e3 = 1.00064e8
    #   floor = max(0.064, 0.100064) = 0.100064 s
    m.note("prefill", 0.25, batch=1, tokens=64)
    clk.t = 1020.0
    snap = m.snapshot()

    d = snap["programs"]["decode"]
    assert d["dispatches"] == 1 and d["tokens"] == 8
    assert d["device_seconds"] == pytest.approx(0.5)
    assert d["measured_s_per_step"] == pytest.approx(0.125)
    assert d["predicted_floor_s_per_step"] == pytest.approx(0.1002)
    assert d["mfu"] == pytest.approx(8e9 / (0.5 * 1e12))          # 0.016
    assert d["membw_util"] == pytest.approx(4.008e8 / (0.5 * 1e9))  # 0.8016
    assert d["dma_wait_fraction"] == pytest.approx((0.5 - 0.4008) / 0.5)

    p = snap["programs"]["prefill"]
    assert p["mfu"] == pytest.approx(0.256)
    assert p["membw_util"] == pytest.approx(0.400256)
    assert p["dma_wait_fraction"] == pytest.approx(
        (0.25 - 0.100064) / 0.25)

    # duty: 0.75 busy seconds over the 20 s since construction
    assert snap["duty_cycle"] == pytest.approx(0.75 / 20.0)
    # aggregate dma-wait: device-second-weighted mean of the two programs
    excess = (0.5 - 0.4008) + (0.25 - 0.100064)
    assert snap["dma_wait_fraction"] == pytest.approx(excess / 0.75)
    # deterministic: same clock reading, same table
    assert m.snapshot() == snap

    # the window forgets: jump past it and the table is empty
    clk.t = 1075.0
    late = m.snapshot()
    assert late["programs"] == {}
    assert late["duty_cycle"] == 0.0
    assert late["dma_wait_fraction"] == 0.0


def test_kv_restore_is_pure_dma_and_disabled_noop():
    clk = FakeClock()
    m = _mon(clk)
    # kv_restore: one HBM write of 32 rows = 32*1e3 bytes, zero flops
    m.note("kv_restore", 0.001, tokens=32)
    s = m.program_stats()["kv_restore"]
    assert s["mfu"] == 0.0
    assert s["membw_util"] == pytest.approx(32e3 / (0.001 * 1e9))
    # disabled monitor records nothing, snapshot still renders
    off = _mon(clk, enabled=False)
    off.note("decode", 1.0, tokens=8)
    assert off.program_stats() == {}
    assert off.snapshot()["enabled"] is False
    # unknown program kinds are dropped (bounded label cardinality)
    m.note("mystery_kernel", 1.0)
    assert "mystery_kernel" not in m.program_stats()


def test_hbm_drift_verdict_and_export_gauges():
    clk = FakeClock()
    m = _mon(clk)
    live = {"params": 100.0, "kv_pages": 50.0}
    m.install_hbm(lambda: dict(live), lambda: 120.0)
    h = m.hbm_snapshot()
    assert h["components"] == live
    assert h["live_bytes"] == 150.0 and h["compiled_bytes"] == 120.0
    assert h["drift_bytes"] == pytest.approx(30.0)
    assert h["verdict"] == "warn"          # 150 > 120 + 0 tolerance
    # under the ledger -> ok, drift goes negative (over-promise is fine)
    m.install_hbm(lambda: dict(live), lambda: 200.0)
    h = m.hbm_snapshot()
    assert h["verdict"] == "ok" and h["drift_bytes"] == pytest.approx(-50.0)
    # no compiled ledger -> drift pinned to 0, never warns
    m.install_hbm(lambda: dict(live), lambda: 0.0)
    h = m.hbm_snapshot()
    assert h["verdict"] == "ok" and h["drift_bytes"] == 0.0
    # a broken sampler degrades to an empty ledger, never raises
    m.install_hbm(lambda: 1 / 0, lambda: 120.0)
    assert m.hbm_snapshot()["components"] == {}

    # export() writes the gauges (the single R10 writer site)
    mon = devmon.configure(peak_tflops=1.0, hbm_gbps=1.0,
                           hbm_tolerance_mb=0.0, clock=clk)
    mon.install_cost_model(_CM)
    mon.install_hbm(lambda: dict(live), lambda: 120.0)
    mon.note("decode", 0.5, batch=2, tokens=8, ctx_rows=100.0, steps=4)
    mon.export()
    text = devmon.metrics.registry.render()
    assert 'tpu_device_mfu{program="decode"} 0.016' in text
    assert 'tpu_device_hbm_live_bytes{component="params"} 100.0' in text
    assert 'tpu_device_hbm_live_bytes{component="kv_pages"} 50.0' in text
    assert "tpu_device_hbm_drift_bytes 30.0" in text


def test_configure_carries_engine_wiring():
    """build_state configures AFTER Engine.__init__ installs the cost model
    and HBM samplers — the swap must not drop them."""
    mon = devmon.get()
    mon.install_cost_model(_CM)
    mon.install_hbm(lambda: {"params": 7.0}, lambda: 3.0)
    new = devmon.configure(peak_tflops=2.0)
    assert new.cost_model is _CM
    assert new.hbm_snapshot()["live_bytes"] == 7.0
    assert new.peak_flops == 2.0 * 1e12


# ---------------------------------------------------------------------------
# Byte-identity: devmon on vs off
# ---------------------------------------------------------------------------


def _stream_bytes(req):
    lp = None
    if req.logprob_data is not None:
        lp = tuple((own, tuple(alts)) for own, alts in req.logprob_data)
    return (tuple(req.generated), req.finish_reason, lp)


def test_seeded_streams_byte_identical_devmon_on_off(model):
    """note() is observability, never control flow: the token stream is a
    pure function of the seed whether or not attribution is recording."""
    specs = [
        dict(prompt_ids=[5, 9, 2], max_tokens=10, temperature=0.9,
             ignore_eos=True, seed=42),
        dict(prompt_ids=[7, 7, 3], max_tokens=12, temperature=0.8, seed=11,
             ignore_eos=True, logprobs=3),
        dict(prompt_ids=[23, 42], max_tokens=8, temperature=0.0,
             ignore_eos=True),
    ]
    devmon.configure(enabled=True)
    eng_on = _engine(model)
    on = [eng_on.submit(Request(**dict(s))) for s in specs]
    _drain(eng_on)
    assert devmon.get().program_stats(), \
        "enabled monitor must have recorded dispatches"
    devmon.configure(enabled=False)
    eng_off = _engine(model)
    off = [eng_off.submit(Request(**dict(s))) for s in specs]
    _drain(eng_off)
    assert devmon.get().program_stats() == {}
    for a, b in zip(on, off):
        assert _stream_bytes(a) == _stream_bytes(b), \
            "stream must be byte-identical devmon on vs off"


# ---------------------------------------------------------------------------
# OpenMetrics exposition: exemplars, escaping, family names
# ---------------------------------------------------------------------------


def test_exemplar_on_lowest_bucket_last_wins_and_escaping():
    h = Histogram("tpu_serve_x_seconds", "x", buckets=(1.0, 2.0))
    h.observe(0.5, trace_id="aaa")
    h.observe(0.4, trace_id='b\\c"d\ne')   # nasty: backslash, quote, LF
    h.observe(5.0, trace_id="inf-side")
    om = "\n".join(h.collect(openmetrics=True))
    # lowest containing bucket carries the exemplar; last observation wins
    assert ('tpu_serve_x_seconds_bucket{le="1.0"} 2 '
            '# {trace_id="b\\\\c\\"d\\ne"} 0.4') in om
    # the le="2.0" bucket counts the observations but carries NO exemplar
    # (they fell into the lower bucket)
    assert 'tpu_serve_x_seconds_bucket{le="2.0"} 2\n' in om + "\n"
    assert ('tpu_serve_x_seconds_bucket{le="+Inf"} 3 '
            '# {trace_id="inf-side"} 5.0') in om
    # sum/count lines never carry exemplars
    for line in om.splitlines():
        if "_sum" in line or "_count" in line:
            assert "#" not in line
    # classic mode renders the same counts with zero exemplar syntax
    classic = "\n".join(h.collect())
    assert "trace_id" not in classic
    assert 'tpu_serve_x_seconds_bucket{le="1.0"} 2' in classic


def test_observe_without_trace_id_renders_no_exemplar():
    h = Histogram("tpu_serve_y_seconds", "y", buckets=(1.0,))
    h.observe(0.5)
    assert "trace_id" not in "\n".join(h.collect(openmetrics=True))


def test_counter_family_drops_total_suffix_only_in_openmetrics():
    c = Counter("tpu_serve_reqs_total", "n")
    c.inc()
    om = c.collect(openmetrics=True)
    assert om[0] == "# HELP tpu_serve_reqs n"
    assert om[1] == "# TYPE tpu_serve_reqs counter"
    assert om[2] == "tpu_serve_reqs_total 1.0"   # samples keep the suffix
    classic = c.collect()
    assert classic[0] == "# HELP tpu_serve_reqs_total n"
    assert classic[1] == "# TYPE tpu_serve_reqs_total counter"


def test_label_values_escape_in_both_formats():
    g = Gauge("tpu_serve_z", "z")
    g.set(1.0, model='a\\b"c\nd')
    want = 'tpu_serve_z{model="a\\\\b\\"c\\nd"} 1.0'
    assert want in g.collect()
    assert want in g.collect(openmetrics=True)


# ---------------------------------------------------------------------------
# End-to-end: /debug/roofline, /healthz drift verdict, both /metrics formats
# ---------------------------------------------------------------------------


def test_server_roofline_metrics_and_drift_warn(model):
    tok, cfg, params = model
    serving = ServingConfig(
        weights_dtype="bf16", model=MODEL, max_decode_slots=2,
        max_cache_len=128, page_size=32,
        prefill_buckets=(16, 32, 64, 128), dtype="float32", derived_seed=0)
    state = build_state(serving, model_cfg=cfg, params=params, tokenizer=tok)
    port = next(_PORTS)
    ready, stop = threading.Event(), threading.Event()
    threading.Thread(target=serve,
                     args=(state, "127.0.0.1", port, ready, stop),
                     daemon=True).start()
    assert ready.wait(10)
    try:
        def get(path, headers=None):
            req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                         headers=headers or {})
            with urllib.request.urlopen(req, timeout=30) as r:
                return r.status, r.headers.get("Content-Type", ""), r.read()

        body = json.dumps({"model": MODEL, "prompt": "hi", "max_tokens": 4,
                           "ignore_eos": True}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200

        # /debug/roofline: engine-installed cost model attributed the work
        st, _, raw = get("/debug/roofline")
        roof = json.loads(raw)
        assert st == 200 and roof["enabled"] is True
        assert "decode" in roof["programs"]
        assert roof["programs"]["decode"]["device_seconds"] > 0.0
        assert 0.0 <= roof["programs"]["decode"]["mfu"] <= 1.0
        assert roof["hbm"]["components"].get("params", 0.0) > 0.0

        # classic /metrics: gauges present, no OM syntax
        st, ctype, raw = get("/metrics")
        text = raw.decode()
        assert st == 200 and "openmetrics" not in ctype
        assert 'tpu_device_mfu{program="decode"}' in text
        assert "tpu_device_duty_cycle" in text
        assert "# EOF" not in text
        # OpenMetrics negotiation: stripped counter families, one EOF
        st, ctype, raw = get(
            "/metrics", {"Accept": "application/openmetrics-text"})
        om = raw.decode()
        assert st == 200
        assert ctype.startswith("application/openmetrics-text")
        assert om.endswith("# EOF\n") and om.count("# EOF") == 1
        assert "# TYPE tpu_serve_request counter" in om
        assert "tpu_serve_request_total" in om

        # inflate the live ledger past the compiled ledger: /healthz flips
        # to warn, the drift gauge moves, requests KEEP succeeding
        mon = devmon.get()
        mon.install_hbm(lambda: {"params": 3e9}, lambda: 1e9)
        st, _, raw = get("/healthz")
        h = json.loads(raw)
        assert h["hbm_drift"] == "warn"
        assert h["device"]["hbm_drift_bytes"] == 2_000_000_000
        assert h["device"]["hbm_live_bytes"] == 3_000_000_000
        with urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/completions", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=120) as r:
            assert r.status == 200, "drift warns, never kills"
        st, _, raw = get("/metrics")
        assert "tpu_device_hbm_drift_bytes 2000000000.0" in raw.decode()
    finally:
        stop.set()
        time.sleep(0.1)
