"""The engine loop reporting itself (serving/programs.py, serving/engine.py):

- ONE dispatch record per device dispatch, written at one site and feeding
  the three sinks that exist: devmon's window, one ``dispatch`` flight event,
  one ``engine.dispatch`` span through whatever exporter the server's tracer
  holds at that moment;
- the phases of ``Engine.step`` as ``jax.profiler.TraceAnnotation`` on the
  engine thread (a closed set of seven names, ``seq`` on ``engine.dispatch``);
- compile time by program and stage from ``jax.monitoring``, and the
  serving-time compile signal;
- the names the benchmark's trace readers match (``jit_<program>``), so a
  rename fails here instead of silently emptying a metric.
"""

import glob
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.serving import aot
from aws_k8s_ansible_provisioner_tpu.serving import devmon as _devmon
from aws_k8s_ansible_provisioner_tpu.serving import flightrec as _flight
from aws_k8s_ansible_provisioner_tpu.serving import metrics as _metrics
from aws_k8s_ansible_provisioner_tpu.serving import programs as _programs
from aws_k8s_ansible_provisioner_tpu.serving import tracing
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request
from aws_k8s_ansible_provisioner_tpu.serving.server import build_state
from aws_k8s_ansible_provisioner_tpu.utils.tokenizer import ByteTokenizer

MODEL = "tiny-qwen3"


@pytest.fixture(scope="module")
def model():
    tok = ByteTokenizer()
    cfg = tiny_qwen3(vocab_size=tok.vocab_size, eos_token_id=tok.eos_token_id)
    return tok, cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


@pytest.fixture(autouse=True)
def fresh_sinks():
    _flight.reset()
    _devmon.reset()
    yield
    _flight.reset()
    _devmon.reset()


def _serving(**over):
    base = dict(weights_dtype="bf16", model=MODEL, max_decode_slots=4,
                max_cache_len=128, page_size=32,
                prefill_buckets=(16, 32, 64), dtype="float32",
                prefix_cache=False, decode_horizon=4)
    base.update(over)
    return ServingConfig(**base)


def _drain(eng, limit=20000):
    for _ in range(limit):
        if not eng.step():
            return
    raise AssertionError("engine failed to quiesce")


def _req(n_prompt, max_tokens, start=3):
    return Request(prompt_ids=[start + (i % 200) for i in range(n_prompt)],
                   max_tokens=max_tokens, ignore_eos=True)


class _Recorder:
    def __init__(self):
        self.items = []

    def export(self, span, service_name):
        self.items.append((span, service_name))
        return True


def _watch_enqueues(eng):
    """What the host mirrors held when each record was opened, computed
    apart from the helper: the sum of ``lengths`` over the live decode rows
    (a slot mid chunk walk is not live)."""
    seen = {}
    real = eng._dispatch_open

    def spy(program, kind, active=(), **given):
        rows = eng._active_slots()
        want = int(eng.lengths[rows].sum()) if rows else 0
        rec = real(program, kind, active, **given)
        seen[rec["seq"]] = (want, len(rows))
        return rec

    eng._dispatch_open = spy
    return seen


# (id, serving overrides, traffic, program, devmon kind, tokens devmon books)
def _one_prompt(eng):
    eng.submit(_req(9, 6))


def _three_prompts(eng):
    for i in range(3):
        eng.submit(_req(7 + i, 3, start=10 * i + 3))


def _long_prompt(eng):
    eng.submit(_req(40, 3))


def _admit_under_decode(eng):
    eng.submit(_req(9, 24))
    for _ in range(3):
        eng.step()                  # a decode dispatch is in flight
    eng.submit(_req(20, 4, start=50))


def _looping_prompt(eng):
    pat = [7, 11, 13, 17]
    eng.submit(Request(prompt_ids=pat * 4, max_tokens=12, ignore_eos=True))


KINDS = [
    ("prefill", dict(), _one_prompt, "prefill_step", "prefill",
     lambda r: r["prompt_tokens"]),
    # padding rows of a batched prefill carry true_len 1 (as before this PR)
    ("batched-prefill", dict(), _three_prompts, "prefill_batch_step",
     "prefill_batch", lambda r: r["prompt_tokens"] + r["rows"] - 3),
    ("chunk", dict(prefill_chunk=16), _long_prompt,
     "prefill_chunk_step", "prefill_chunk", lambda r: r["chunk_n"]),
    ("mixed", dict(decode_pipeline=1, ragged_attention=1),
     _admit_under_decode, "mixed_step", "mixed_step",
     lambda r: r["active"] * r["horizon"] + r["chunk_n"]),
    ("decode", dict(), _one_prompt, "decode_steps", "decode",
     lambda r: r["active"] * r["horizon"]),
    ("spec", dict(spec_decode=True, spec_k=4, spec_ngram=3,
                  attention_impl="xla"), _looping_prompt,
     "spec_decode_step", "spec_decode", lambda r: r["active"] * r["rows"]),
]


@pytest.mark.parametrize("serving_kw,traffic,program,kind,tokens",
                         [k[1:] for k in KINDS], ids=[k[0] for k in KINDS])
def test_every_dispatch_leaves_one_record(model, serving_kw, traffic,
                                          program, kind, tokens):
    _, cfg, params = model
    eng = Engine(cfg, params, _serving(**serving_kw))
    rec = _Recorder()
    tracer = tracing.Tracer("tpu-serve-engine", exporter=rec)
    eng.tracer_source = lambda: tracer
    enq = _watch_enqueues(eng)
    traffic(eng)
    _drain(eng)

    events = [e for e in _flight.get().tail(4096) if e["type"] == "dispatch"]
    # one record per dispatch, the same one in all three sinks
    assert len(events) == len(enq) > 0
    seqs = [e["seq"] for e in events]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert sorted(enq) == seqs
    spans = [s for s, _ in rec.items]
    assert [s.name for s in spans] == ["engine.dispatch"] * len(events)
    assert [s.attributes["seq"] for s in spans] == seqs
    mon = _devmon.get()
    with mon._lock:
        booked = {k: [e[4] for e in dq] for k, dq in mon._acc.items() if dq}
    assert sum(len(v) for v in booked.values()) == len(events)
    for e in events:
        want_ctx, want_active = enq[e["seq"]]
        assert e["ctx_tokens"] == want_ctx
        assert e["active"] == want_active
        assert e["ctx_max"] <= e["ctx_tokens"]
        assert e["t_ready"] >= e["t_enqueue"]
        if "chunk_n" in e:
            assert 0 < e["chunk_n"] <= e["chunk_rows"]
        # a mixed step's chunk is written a page window at a time: the
        # record says how many hold one of its rows (page_size 32 here)
        assert ("write_pages" in e) == (e["program"] == "mixed_step")
        if "write_pages" in e:
            first, last = e["chunk_off"], e["chunk_off"] + e["chunk_n"] - 1
            assert e["write_pages"] == len({r // 32 for r in
                                            range(first, last + 1)})
        if "prompt_tokens" in e and "padded_tokens" in e:
            assert e["prompt_tokens"] <= e["padded_tokens"]
        # nobody streams here: tokens are emitted and no queue item is put
        assert e["puts"] == 0 <= e["emitted"]
    # the kind under test dispatched, and devmon booked it under the same
    # kind with the token count it booked before this PR
    mine = [e for e in events if e["program"] == program]
    assert mine, f"{program} never dispatched: {[e['program'] for e in events]}"
    assert all(e["kind"] == kind for e in mine)
    assert booked[kind] == [tokens(e) for e in mine]
    assert not any(e["type"] in ("pipeline_dispatch", "pipeline_fetch")
                   for e in _flight.get().tail(4096))
    # span ids come from the record, never from the tracer's generator
    assert all(s.context.span_id == format(s.attributes["seq"], "016x")
               for s in spans)


@pytest.mark.parametrize("horizon", [1, 4])
def test_record_counts_puts_and_the_counter_counts_items(model, horizon):
    """``puts`` on the dispatch record (beside ``emitted``) and
    tpu_serve_stream_items_total (beside tpu_serve_generated_tokens_total):
    a stream is handed ONE queue item for what a dispatch gave it, so the
    tokens a handler wake-up carries are the horizon, and 1 at horizon 1."""
    _, cfg, params = model
    # (three slots for three streams: every slot held, so the dispatches
    # run the whole horizon; with one free they run a measured few)
    eng = Engine(cfg, params, _serving(decode_horizon=horizon,
                                       max_decode_slots=3))
    rec = _Recorder()
    tracer = tracing.Tracer("tpu-serve-engine", exporter=rec)
    eng.tracer_source = lambda: tracer
    # the activation's token, then three fused horizons
    reqs = [Request(prompt_ids=[3 + i, 9, 11], max_tokens=1 + horizon * 3,
                    ignore_eos=True, stream=True) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    _drain(eng)
    events = [e for e in _flight.get().tail(4096) if e["type"] == "dispatch"]
    spans = [s.attributes for s, _ in rec.items]
    assert [(s["seq"], s["puts"], s["emitted"]) for s in spans] == \
        [(e["seq"], e["puts"], e["emitted"]) for e in events]
    decodes = [e for e in events if e["program"] == "decode_steps"
               and e["emitted"]]
    assert decodes
    for e in decodes:
        # every live stream got its horizon of tokens as one item
        assert e["puts"] == e["active"] == 3
        assert e["emitted"] == e["puts"] * e["horizon"]
    assert [e["horizon"] for e in decodes] == [horizon] * 3
    # an activation's item is put outside any record (a prefill's record
    # closes before its emit phase, as ``emitted`` 0 there always said)
    assert all(e["puts"] == 0 for e in events
               if e["program"].startswith("prefill"))
    items = eng.metrics.stream_items.total()
    assert items == len(reqs) + sum(e["puts"] for e in events)
    tokens = eng.metrics.generated_tokens.total()
    assert tokens == sum(len(r.generated) for r in reqs) \
        == 3 * (1 + horizon * 3)
    # tokens a wake-up carries, past the one-token item a stream starts
    # with: the horizon
    assert (tokens - len(reqs)) / (items - len(reqs)) == horizon
    text = eng.metrics.registry.render()
    assert f"tpu_serve_stream_items_total {float(items)}" in text \
        or f"tpu_serve_stream_items_total {int(items)}" in text


def test_exporter_installed_after_start_gets_spans_and_removed_gets_none(
        model):
    tok, cfg, params = model
    state = build_state(_serving(), model_cfg=cfg, params=params,
                        tokenizer=tok)
    eng = state.engine
    assert state.tracer.exporter is None
    eng.submit(_req(9, 4))
    _drain(eng)                                 # no exporter: nothing to see
    rec = _Recorder()
    state.tracer.exporter = rec                 # as the benchmark does
    eng.submit(_req(9, 4, start=40))
    _drain(eng)
    n = len(rec.items)
    assert n >= 2 and {s.name for s, _ in rec.items} == {"engine.dispatch"}
    assert {svc for _, svc in rec.items} == {"tpu-serve-engine"}
    span = rec.items[0][0]
    assert span.end_ns >= span.start_ns
    assert span.attributes["program"] == "prefill_step"
    state.tracer.exporter = None
    eng.submit(_req(9, 4, start=80))
    _drain(eng)
    assert len(rec.items) == n
    # a tracer swapped in later (tests inject seeded ones) is the one read
    rec2 = _Recorder()
    state.tracer = tracing.Tracer("tpu-serve-engine", exporter=rec2, seed=7)
    eng.submit(_req(9, 4, start=120))
    _drain(eng)
    assert rec2.items and len(rec.items) == n
    # ... and its seeded generator was not drawn from
    assert state.tracer._hex(64) == tracing.Tracer("x", seed=7)._hex(64)


def test_profiler_capture_holds_the_engine_phases(model, tmp_path):
    from jax.profiler import ProfileData

    _, cfg, params = model
    eng = Engine(cfg, params, _serving(decode_pipeline=1, ragged_attention=1))
    eng.submit(_req(9, 4))
    _drain(eng)                                 # compile outside the capture
    stop = threading.Event()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    th = threading.Thread(target=eng.run_forever, args=(stop,),
                          name="engine-loop", daemon=True)
    th.start()
    try:
        reqs = [eng.submit(_req(9, 6, start=20 * i + 3)) for i in range(2)]
        for r in reqs:
            r.wait(timeout=60)
        time.sleep(0.1)                         # an idle wait inside
    finally:
        stop.set()
        th.join(10)
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    lines = [ln for pl in ProfileData.from_file(path).planes
             if pl.name.startswith("/host:CPU") for ln in pl.lines]
    per_line = [[e for e in ln.events if e.name.startswith("engine.")]
                for ln in lines]
    (evs,) = [e for e in per_line if e]         # one thread: the engine's
    assert {e.name for e in evs} == set(_programs.ENGINE_PHASES)
    assert len(_programs.ENGINE_PHASES) == 7
    dispatches = [dict(e.stats) for e in evs if e.name == "engine.dispatch"]
    seqs = [d["seq"] for d in dispatches]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert {d["program"] for d in dispatches} <= set(
        _programs.STEP_PROGRAMS)
    ring = {e["seq"] for e in _flight.get().tail(4096)
            if e["type"] == "dispatch"}
    assert set(seqs) <= ring


def _stage_seconds():
    return dict(_metrics.compile_stages.stage_totals())


def _moved(before, after):
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v - before.get(k, 0.0) > 0.0}


def test_compile_stage_counters_by_program(model):
    tok = ByteTokenizer()
    # a shape no other test of this process has compiled
    cfg = tiny_qwen3(vocab_size=tok.vocab_size + 5,
                     eos_token_id=tok.eos_token_id)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = Engine(cfg, params, _serving())
    cm = _metrics.compile_stages
    was, c0 = cm.serving, _stage_seconds()
    try:
        cm.serving = False
        eng.submit(_req(9, 6))
        _drain(eng)
        first = _moved(c0, _stage_seconds())
        for prog in ("prefill_step", "decode_steps"):
            for stage in ("trace", "lower", "backend"):
                assert first.get((prog, stage), 0.0) > 0.0, (prog, stage)
        n_serving = cm.serving_compiles.total()
        c1 = _stage_seconds()
        eng.submit(_req(9, 6, start=30))        # same shapes: nothing moves
        _drain(eng)
        again = _moved(c1, _stage_seconds())
        assert not [k for k in again if k[0] != "other"], again

        c2 = _stage_seconds()

        def foreign_fn(x):
            return x * 3 + 1

        jax.jit(foreign_fn)(jnp.arange(7.0)).block_until_ready()
        foreign = _moved(c2, _stage_seconds())
        assert foreign and {k[0] for k in foreign} == {"other"}
        assert cm.serving_compiles.total() == n_serving

        # after readiness: a bucket never used before compiles under load
        cm.serving = True
        _flight.reset()
        eng.submit(_req(30, 2, start=60))       # bucket 32: a new program
        _drain(eng)
        assert cm.serving_compiles.value(program="prefill_step") >= 1
        assert cm.serving_compiles.total() > n_serving
        ring = _flight.get().tail(4096)
        comp = [e for e in ring if e["type"] == "compile"]
        assert comp and {e["program"] for e in comp} == {"prefill_step"}
        assert {e["stage"] for e in comp} >= {"trace", "lower", "backend"}
        assert all(e["seconds"] >= 0.0 for e in comp)
        marked = [e for e in ring if e["type"] == "dispatch"
                  and e["first_use"]]
        assert [e["program"] for e in marked] == ["prefill_step"]
        assert {e["seq"] for e in comp} == {marked[0]["seq"]}
    finally:
        cm.serving = was
    text = _metrics.compile_stages.registry.render()
    assert 'tpu_serve_compile_stage_seconds_total{program="decode_steps"' \
        in text
    assert "tpu_serve_serving_compiles_total" in text


def test_trace_names_the_benchmark_readers_match():
    """``jit_<function>`` is how the device trace names a program's
    executions (benchmark/benchlib/trace_reduce.program_of); the kernel
    wrappers' names are pinned in tests/test_tpu_compile.py, where the
    chip's compiler prints them."""
    serving = _serving(spec_decode=True, spec_k=3, decode_pipeline=1,
                       ragged_attention=1)
    plan = aot.ProgramPlan(tiny_qwen3(), serving)
    params, cache = aot._abstract_state(plan, None)
    seen = {}
    for _, fn, args, kwargs in aot.enumerate_programs(plan, None, params,
                                                      cache):
        if fn.__name__ in seen:
            continue
        text = fn.lower(*args, **kwargs).as_text()
        seen[fn.__name__] = text.split("{", 1)[0]
    assert set(seen) == set(_programs.STEP_PROGRAMS)
    for name, head in seen.items():
        assert f"module @jit_{name} " in head, (name, head)
    for name in ("jit_decode_steps", "jit_mixed_step", "jit_prefill_step",
                 "jit_prefill_batch_step"):
        assert name[4:] in seen


# -- the chunk's page steps (PR 40) ------------------------------------------


def _page_steps_row_by_row(B, C, off, n, bb, tile, ps, pages, window):
    """``_chunk_page_steps`` again, a grid step at a time as the kernel's
    body reads it: (as the tiles walk, as blocks of ``bb`` walk)."""
    limits = [0] * B + [off + j + 1 if j < n else 0 for j in range(C)]

    def walk(rows):
        live = [x for x in rows if x > 0]
        if not live:
            return 0
        hi = max(min(-(-x // ps), pages) - 1 for x in live)
        lo = min(max(x - window, 0) // ps for x in live) if window else 0
        return hi - lo + 1

    by8 = [walk(limits[i:i + bb]) for i in range(0, B + C, bb)]
    tiles = 0
    for g in range(0, B + C, tile):
        if g < B:       # holds decode rows: block by block
            tiles += sum(by8[g // bb:(g + tile) // bb])
        else:
            tiles += walk(limits[g:g + tile])
    return tiles, sum(by8)


@pytest.mark.parametrize("slots,chunk,off,n,window", [
    (8, 112, 0, 100, 0),        # 120 rows: tiles of 40, the worked case
    (8, 112, 0, 9, 0),          # a one-page prompt
    (8, 112, 70, 112, 0),       # a later chunk, from mid page
    (8, 112, 70, 40, 64),       # under a window
    (8, 160, 300, 160, 64),     # 168 rows: tiles of 56, a window behind
    (4, 16, 0, 16, 0),          # 20 rows: no wider tile than a block of 4
    (40, 80, 5, 80, 0),         # a whole tile of decode rows
])
def test_chunk_page_steps_mirror_the_kernels_grid(model, slots, chunk, off,
                                                  n, window):
    import dataclasses

    from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
        _resolve_bb, _tile_rows)

    _, cfg, params = model
    cfg = dataclasses.replace(cfg, sliding_window=window, max_seq_len=512)
    eng = Engine(cfg, params, _serving(
        max_decode_slots=slots, max_cache_len=512, prefill_chunk=chunk,
        decode_bblock=8, decode_pipeline=1, ragged_attention=1))
    got = eng._chunk_page_steps(chunk, off, n)
    bb = _resolve_bb(8, slots + chunk)
    tile = _tile_rows(slots + chunk, bb, cfg.num_heads, cfg.head_dim, 32,
                      jnp.float32)
    want = _page_steps_row_by_row(slots, chunk, off, n, bb, tile, 32,
                                  eng.pages_per_slot, window)
    assert (got["chunk_page_steps"], got["chunk_page_steps_by8"]) == want
    assert got["chunk_page_steps_by8"] >= got["chunk_page_steps"] > 0
    if (slots, chunk, off, n) == (8, 112, 0, 100):
        assert tile == 40 and want == (4 + 3 + 4, 4 + 11 + 13)
    if tile == bb:
        assert want[0] == want[1]


@pytest.mark.parametrize("slots,chunk,off,n,tile", [
    (24, 240, 0, 240, 24),      # 264 rows: 24, the first tile the decode rows
    (24, 240, 100, 90, 24),     # a later chunk, part dead
    (8, 112, 70, 112, 40),      # decode rows beside chunk rows in a tile
    (8, 128, 0, 128, 8),        # 136 = 8 x 17 rows: no wider tile
])
def test_a_selecting_models_chunk_page_steps_mirror_its_kernels_grid(
        slots, chunk, off, n, tile):
    """Since PR 49 the selecting ragged entry cuts its grid steps as the
    plain one does (a sharing tile walks its pages once, the selection a
    mask over its lanes), and the host's count follows it."""
    from aws_k8s_ansible_provisioner_tpu.config import tiny_sala
    from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
        _resolve_bb, _tile_rows)

    cfg = tiny_sala(max_seq_len=512)
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32),
                 _serving(model="tiny-sala", max_decode_slots=slots,
                          max_cache_len=512, page_size=8, prefill_chunk=chunk,
                          prefill_buckets=(16, 32), decode_bblock=8,
                          decode_pipeline=1, ragged_attention=1,
                          kv_host_tier_bytes=0))
    assert eng.cfg.selects
    got = eng._chunk_page_steps(chunk, off, n)
    bb = _resolve_bb(8, slots + chunk)
    assert _tile_rows(slots + chunk, bb, cfg.num_heads, cfg.head_dim, 8,
                      jnp.float32) == tile
    want = _page_steps_row_by_row(slots, chunk, off, n, bb, tile, 8,
                                  eng.pages_per_slot, 0)
    assert (got["chunk_page_steps"], got["chunk_page_steps_by8"]) == want
    assert got["chunk_page_steps_by8"] >= got["chunk_page_steps"] > 0
    assert (want[0] < want[1]) == (tile > bb)


def test_mixed_record_and_metrics_carry_the_chunks_page_steps(model):
    """A mixed dispatch's record says how many page steps its chunk rows
    walk as the kernel's tiles are cut and how many blocks of 8 would have,
    and ``/metrics`` counts both over the attending layers; no other
    program's record carries them."""
    _, cfg, params = model
    eng = Engine(cfg, params, _serving(
        max_decode_slots=8, max_cache_len=256, prefill_chunk=112,
        decode_bblock=8, decode_pipeline=1, ragged_attention=1))
    eng.submit(_req(9, 24))
    for _ in range(3):
        eng.step()                  # a decode dispatch is in flight
    eng.submit(_req(100, 4, start=50))
    _drain(eng)
    events = [e for e in _flight.get().tail(4096) if e["type"] == "dispatch"]
    mixed = [e for e in events if e["program"] == "mixed_step"]
    assert mixed and all(("chunk_page_steps" in e) == (e in mixed)
                         for e in events)
    rec, = mixed
    assert (rec["chunk_off"], rec["chunk_n"], rec["chunk_rows"]) \
        == (0, 100, 112)
    assert (rec["chunk_page_steps"], rec["chunk_page_steps_by8"]) == (11, 28)
    text = eng.metrics.registry.render()
    layers = cfg.num_attn_layers
    for path, steps in (("tile", 11), ("by8", 28)):
        line, = [ln for ln in text.splitlines() if ln.startswith(
            f'tpu_serve_ragged_page_steps_total{{path="{path}"}}')]
        assert float(line.split()[-1]) == layers * steps
