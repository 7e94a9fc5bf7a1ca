"""The readers of what the engine loop reports of itself
(benchlib/engine_loop.py, layer_metrics/{mixed_chunk_fill_pct,
ragged_attn_roofline_pct, engine_host_ms_per_dispatch, setup_*}.py): on fixed
event lists, on a program that reports nothing (the parent of the PR that
added them), on the recorded slice of a chip run with its dispatch records
(tests/data/mixed_slice.*), and end to end in the CPU rehearsal."""

import gzip
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchlib import engine_loop as el
from benchlib import files
from benchlib import trace_reduce as tr
from benchlib.session import LayerContext

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MC = {"num_heads": 16, "num_kv_heads": 8, "head_dim": 128, "num_layers": 28}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("mixed_chunk_fill_pct", "ragged_attn_roofline_pct",
       "engine_host_ms_per_dispatch", "setup_trace_lower_s",
       "setup_backend_compile_s")


def _ctx(spans=(), trace=None, **kw):
    base = dict(cell=None, mc=MC, peaks=PEAKS, chips=1, t0=0.0, t1=40.0,
                counters={}, traced_counters={}, spans=list(spans),
                samples=[], dispatches=[], trace=trace, trace_t0=0.0,
                trace_t1=0.0, engine={"kv_itemsize": 2, "horizon": 8},
                memory_peak_bytes=0, client={})
    base.update(kw)
    return LayerContext(**base)


def _span(seq, program, start, end, **attrs):
    return (0.0, "engine.dispatch", start, end,
            dict(attrs, seq=seq, program=program))


def test_flatten_names_the_innermost_phase_and_host_time_leaves_waits_out():
    phases = [("engine.reap", 0, 10, {}),
              ("engine.admit", 10, 100, {}),
              ("engine.dispatch", 20, 10, {"seq": 1, "program": "_copy"}),
              ("engine.fetch", 30, 50, {}),          # a settle inside admit
              ("engine.operands", 120, 10, {}),
              ("engine.dispatch", 130, 20, {"seq": 2, "program": "mixed"}),
              ("engine.fetch", 150, 100, {}),
              ("engine.emit", 250, 30, {}),
              ("engine.idle", 300, 500, {})]
    assert el.flatten(phases) == [
        ("engine.reap", 0, 10), ("engine.admit", 10, 20),
        ("engine.dispatch", 20, 30), ("engine.fetch", 30, 80),
        ("engine.admit", 80, 110), ("engine.operands", 120, 130),
        ("engine.dispatch", 130, 150), ("engine.fetch", 150, 250),
        ("engine.emit", 250, 280), ("engine.idle", 300, 800)]
    # 10 + 10 + 10 + 30 + 10 + 20 + 30 = 120 ns over two dispatches
    assert el.host_seconds_per_dispatch(phases) == pytest.approx(60e-9)
    assert el.host_seconds_per_dispatch([]) is None


def _device(modules, ops):
    t = tr.Trace()
    d = tr.DeviceTrace(0)
    d.modules, d.ops = modules, ops
    t.devices = [d]
    return t


def test_idle_time_is_split_over_the_phases_open_in_each_gap():
    us = 1000
    trace = _device([], [("%a = f()", 0, 100 * us), ("%b = f()", 200 * us,
                                                     100 * us),
                         ("%c = f()", 310 * us, 90 * us)])
    phases = [("engine.fetch", 90 * us, 60 * us, {}),
              ("engine.emit", 150 * us, 30 * us, {})]
    idle = el.idle_by_phase(trace, phases)
    # gap 100-200 us: fetch 50, emit 30, nothing 20; gap 300-310 is short
    assert idle == {"engine.fetch": pytest.approx(50e-6),
                    "engine.emit": pytest.approx(30e-6),
                    "-": pytest.approx(20e-6),
                    "<20us gaps": pytest.approx(10e-6)}


def test_join_takes_executions_in_order_and_leaves_the_cut_edge_unjoined():
    ms = 1_000_000
    off = 7_000 * ms                    # xplane clock = span clock + off
    spans = [_span(5, "mixed_step", 0, 90 * ms),      # before the slice
             _span(6, "decode_steps", 95 * ms, 190 * ms),
             _span(7, "mixed_step", 100 * ms, 300 * ms),
             _span(8, "mixed_step", 290 * ms, 500 * ms)]
    recs = el.dispatch_records(spans)
    phases = [("engine.dispatch", 100 * ms + off, ms,
               {"seq": 7, "program": "mixed_step"}),
              ("engine.dispatch", 290 * ms + off, ms,
               {"seq": 8, "program": "mixed_step"})]
    assert el.clock_offset_ns(recs, phases) == off
    mods = [("jit_mixed_step(1)", 60 * ms + off, 25 * ms),     # of seq 5
            ("jit_decode_steps(2)", 96 * ms + off, 90 * ms),
            ("jit_mixed_step(1)", 190 * ms + off, 100 * ms),   # of seq 7
            ("jit_mixed_step(1)", 300 * ms + off, 190 * ms),   # of seq 8
            ("jit_mixed_step(1)", 600 * ms + off, 50 * ms)]    # no record
    joined = el.join_executions(_device(mods, []), recs, phases, "mixed_step")
    assert [r and r["seq"] for _, r in joined] == [5, 7, 8, None]
    assert el.join_executions(_device(mods, []), recs, [], "mixed_step") == []


def test_ragged_call_counts_each_kv_row_once_and_the_causal_pairs():
    rec = {"active": 2, "ctx_tokens": 100, "carry_steps": 8, "chunk_n": 4,
           "chunk_off": 10, "chunk_rows": 2048}
    flops, byts = el.ragged_attention_call(MC, rec, kv_itemsize=2)
    dec_keys = 100 + 2 * 9
    pairs = dec_keys + 4 * 10 + 10                  # 11 + 12 + 13 + 14 = 50
    assert flops == 4.0 * pairs * 16 * 128
    assert byts == (dec_keys + 14) * 4096 + 2 * 6 * 16 * 128 * 2
    f4, b4 = el.ragged_attention_call(MC, rec, kv_itemsize=2, chips=4)
    assert (f4, b4) == (flops / 4, byts / 4)


def test_chunk_fill_reads_the_mixed_records_of_the_window():
    fill = files.load_module("layer_metrics", "mixed_chunk_fill_pct")
    spans = [_span(1, "mixed_step", 0, 1, chunk_n=512, chunk_rows=2048),
             _span(2, "decode_steps", 1, 2, horizon=8),
             _span(3, "mixed_step", 2, 3, chunk_n=1024, chunk_rows=2048),
             (0.0, "queue_wait", 0, 1, {"phase.ms": 3.0})]
    assert fill.read(_ctx(spans)) == pytest.approx(37.5)


def test_a_program_that_reports_nothing_gives_none_and_never_raises(
        tmp_path, monkeypatch):
    """The parent of the PR that added the records: no engine.* span, no
    annotation in the trace, no compile-stage counter."""
    from benchlib import session

    # not whatever trace an earlier traced run left under .bench_tmp/
    monkeypatch.setattr(session, "TRACE_DIR", str(tmp_path))
    bare = _ctx([(0.0, "queue_wait", 0, 1, {"phase.ms": 3.0})],
                trace=_device([("jit_mixed_step(1)", 0, 100)],
                              [("%ragged_attend_pallas_paged.1 = x", 1, 9)]))
    for name in NEW[:3]:
        assert files.load_module("layer_metrics", name).read(bare) is None
    assert el.compile_stage_seconds({"nonesuch"}) is None


def _recorded():
    out = os.path.join(DATA, "_mixed_slice.xplane.pb")
    with gzip.open(os.path.join(DATA, "mixed_slice.xplane.pb.gz")) as f, \
            open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    with open(os.path.join(DATA, "mixed_slice.records.json")) as f:
        side = json.load(f)
    return out, side


def test_readers_on_the_recorded_slice_of_a_chip_run():
    path, side = _recorded()
    try:
        phases = el.load_phases(path)
        trace = tr.load(path, host_lines=False)
    finally:
        os.remove(path)
    assert {p[0] for p in phases} <= {
        "engine.reap", "engine.admit", "engine.operands", "engine.dispatch",
        "engine.fetch", "engine.emit", "engine.idle"}
    assert all("seq" in p[3] for p in phases if p[0] == "engine.dispatch")
    spans = [tuple(s) for s in side["spans"]]
    recs = el.dispatch_records(spans)
    # the join: every execution wholly inside the slice maps to one record,
    # at most one unjoined per program at each edge
    for program in ("mixed_step", "decode_steps"):
        joined = el.join_executions(trace, recs, phases, program)
        assert len(joined) >= 2, program
        inner = joined[1:-1]
        assert all(r is not None for _, r in inner), program
        seqs = [r["seq"] for _, r in joined if r is not None]
        assert seqs == sorted(set(seqs))
        assert all(r["program"] == program for _, r in joined if r)
    host_ms = el.host_seconds_per_dispatch(phases) * 1e3
    assert 0.1 < host_ms < 100.0
    idle = el.idle_by_phase(trace, phases)
    big = {k: v for k, v in idle.items() if k != "<20us gaps"}
    # recorded before run_forever opened engine.operands around the whole
    # step: 13 % of this slice's idle time is host work between the phases
    assert sum(v for k, v in big.items() if k != "-") \
        >= 0.85 * sum(big.values())
    # the readers themselves, handed the slice the way a run hands it
    ctx = _ctx(spans, trace=trace, mc=side["mc"], engine=side["engine"])
    fill = files.load_module("layer_metrics", "mixed_chunk_fill_pct")
    assert 5.0 < fill.read(ctx) <= 100.0
    roof = files.load_module("layer_metrics", "ragged_attn_roofline_pct")
    real = el.phases_of
    el.phases_of = lambda _ctx: phases          # the run's trace directory
    try:
        share = roof.read(ctx)
    finally:
        el.phases_of = real
    assert 0.1 < share < 100.0


def test_rehearsal_prints_the_new_metrics_on_the_cpu():
    bench = os.path.join(files.BENCH_DIR, "tests", "rehearsal",
                         "BENCHMARK.engine_loop.json")
    p = subprocess.run(
        [sys.executable, os.path.join(files.BENCH_DIR, "run.py"),
         "--rehearsal", bench, "--workload", "tiny.open", "--seed",
         "3000000011", "--seconds", "3", "--trace", "1"],
        cwd=files.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    got = line["metrics"]
    # no device plane on the CPU: the device-trace metric stays out
    assert set(NEW) - set(got) == {"ragged_attn_roofline_pct"}
    assert 0 < got["mixed_chunk_fill_pct"]["value"] <= 100
    assert got["engine_host_ms_per_dispatch"]["value"] > 0
    assert got["setup_trace_lower_s"]["value"] > 0
    assert got["setup_backend_compile_s"]["value"] > 0
    names = [m["name"] for m in files.load_json(bench)["per_layer"]]
    assert names[-5:] == list(NEW)
    real = [m["name"] for m in files.load_json(
        os.path.join(files.ROOT, "BENCHMARK.json"))["per_layer"]]
    assert real[-5:] == list(NEW)
