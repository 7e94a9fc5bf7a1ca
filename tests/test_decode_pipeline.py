"""One-deep asynchronous decode pipeline (serving/programs.py): dispatch N+1
is enqueued before dispatch N's tokens are fetched, so the host gap hides
behind device execution. These tests pin the correctness contract:

- seeded streams are BYTE-IDENTICAL pipeline on vs off (sampled, logprobs,
  penalties, guided, logit_bias) — per-(seed, position) keys make the token
  stream a pure function of position, not of dispatch boundaries;
- lifecycle edges drain or discard correctly: mid-stream cancel discards the
  surplus tokens of the in-flight dispatch, deadlines reap at most one
  dispatch late, chunked prefill admission drains the pipeline first,
  graceful drain finishes in-flight streams;
- the injected ``pipeline_fetch_error`` chaos fault discards the in-flight
  dispatch, fails requests with "error", releases slots/pages exactly once,
  and the engine keeps serving (chaos.py docstring contract);
- the new metrics (tpu_serve_decode_bubble_seconds_total,
  tpu_serve_pipeline_depth) register, move, and render on /metrics, and
  /healthz reports the knob plus the bubble percentage;
- ragged mixed-batch attention (ISSUE 14, ``ragged_smoke`` marker):
  interleaved chunked-prefill admissions hold the pipeline OPEN (zero
  admission-edge drains on tpu_serve_pipeline_drains_total where the legacy
  path drains once per admission), seeded streams are byte-identical ragged
  vs legacy across sampled/logprobs/penalties, and the injected
  ``ragged_dispatch_error`` fault drops the mixed dispatch without killing
  the engine;
- feature paths ride the ragged pipeline (ISSUE 16, same marker): guided,
  LoRA, and spec-decode traffic stays pipelined under ``ragged_features=1``
  with seeded streams byte-identical to the ``ragged_features=0`` sync
  fallback, zero spec/guided-reason drains on
  tpu_serve_pipeline_drains_total, and the injected ``ragged_feature_error``
  fault (corrupted guided-mask upload / spec verify row, ``kind=...``
  selectable) discards the dispatch un-emitted while the engine keeps
  serving — including a chaos-seasoned workload mixing all features at
  once.

`make pipeline-smoke` runs this file LockSan-instrumented (TPU_LOCKSAN=1);
`make ragged-smoke` runs the ragged subset; tier-1 runs it bare via the
``pipeline_smoke`` marker.
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
from aws_k8s_ansible_provisioner_tpu.serving import chaos as _chaos
from aws_k8s_ansible_provisioner_tpu.serving import metrics as _metrics
from aws_k8s_ansible_provisioner_tpu.serving.engine import (
    Engine, EngineOverloaded, Request)
from aws_k8s_ansible_provisioner_tpu.serving.guided import grammar_for
from aws_k8s_ansible_provisioner_tpu.serving.server import build_state, serve
from aws_k8s_ansible_provisioner_tpu.utils.tokenizer import ByteTokenizer

from test_engine import _queue_items

pytestmark = pytest.mark.pipeline_smoke

MODEL = "tiny-qwen3"
_PORTS = iter(range(18500, 18560))

SEEDED = dict(prompt_ids=[5, 9, 2], max_tokens=10, temperature=0.9,
              ignore_eos=True, seed=42)

# completion pressure for the guided test (same rationale as test_guided):
# bias a random-weight model toward closing its JSON inside the budget.
_EOS = ByteTokenizer.EOS
_PRESSURE = ((ord(' '), -50.0), (ord('\t'), -50.0), (ord('\n'), -50.0),
             (ord('\r'), -50.0), (ord('['), -20.0),
             (ord('\\'), -100.0), (ord('"'), 30.0), (ord('}'), 20.0),
             (ord(']'), 15.0), (ord(':'), 20.0), (ord(','), 5.0),
             (_EOS, 100.0))


@pytest.fixture(autouse=True)
def fresh_chaos():
    _chaos.reset()
    yield
    _chaos.reset()


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


def _settled(eng, timeout_s=30.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = eng.sched.stats()
        if st.active_slots == 0 and st.queue_depth == 0 \
                and not eng.pending and eng._chunk is None:
            return st
        time.sleep(0.05)
    raise AssertionError(f"engine never settled: {eng.sched.stats()}")


def _assert_released(eng, n_terminal=None):
    st = _settled(eng)
    assert st.active_slots == 0, st
    for a in eng.allocators:
        assert a.stats()["pages_live"] == 0, a.stats()
    if n_terminal is not None:
        assert st.finished_total + st.cancelled_total == n_terminal, st
    # the pipeline itself must be fully retired too (a run_forever thread
    # drains the surplus dispatch on its step AFTER the last emit — allow it
    # one scheduling quantum)
    deadline = time.monotonic() + 10.0
    while eng._inflight is not None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert eng._inflight is None
    assert eng.metrics.pipeline_depth.value() == 0.0
    return st


def _run_set(eng, specs):
    """Submit every request spec, run to quiescence, return the requests."""
    reqs = [eng.submit(Request(**s)) for s in specs]
    _drain(eng)
    return reqs


def _stream_bytes(req):
    """Everything a client could observe from this request, as one tuple."""
    lp = None
    if req.logprob_data is not None:
        lp = tuple((own, tuple(alts)) for own, alts in req.logprob_data)
    return (tuple(req.generated), req.finish_reason, lp)


# -- byte-identity: pipeline on vs off ---------------------------------------


def test_seeded_streams_byte_identical_pipeline_on_off(model):
    """The golden contract: the one-deep pipeline changes WHEN tokens reach
    the host, never WHICH tokens — sampled, logprobs, penalties, bias."""
    specs = [
        dict(SEEDED),
        dict(prompt_ids=[7, 7, 3], max_tokens=12, temperature=0.8, seed=11,
             ignore_eos=True, logprobs=3),
        dict(prompt_ids=[4, 8, 15, 16], max_tokens=12, temperature=0.7,
             seed=99, ignore_eos=True, presence_penalty=0.6,
             frequency_penalty=0.4, repetition_penalty=1.2),
        dict(prompt_ids=[23, 42], max_tokens=8, temperature=0.0,
             ignore_eos=True, logit_bias=((5, 4.0), (9, -100.0))),
    ]
    pipelined = _run_set(_engine(model, decode_pipeline=1), list(specs))
    sync = _run_set(_engine(model, decode_pipeline=0), list(specs))
    for p, s in zip(pipelined, sync):
        assert _stream_bytes(p) == _stream_bytes(s), \
            "pipelined stream must be byte-identical to the sync stream"
    assert all(r.finish_reason == "length" for r in pipelined)


def test_guided_request_and_neighbor_identical_pipeline_on_off(model):
    """Guided slots ride the pipeline (ISSUE 16: the mask is a per-row
    operand, settled-then-dispatched for FSM freshness); the handover must
    be byte-exact AND leave the unguided neighbor's seeded stream intact."""
    tok, _, _ = model

    def run(pipeline):
        eng = _engine(model, decode_pipeline=pipeline)
        g = grammar_for(tok, {"type": "json_object"}, [tok.eos_token_id])
        guided = eng.generate(tok.encode("json:"), guided=g, max_tokens=100,
                              temperature=0.0, logit_bias=_PRESSURE)
        neighbor = eng.submit(Request(**SEEDED))
        _drain(eng)
        return eng, guided, neighbor

    eng1, g1, n1 = run(1)
    eng0, g0, n0 = run(0)
    assert g1.finish_reason == "stop"
    assert isinstance(json.loads(tok.decode(g1.generated)), dict)
    assert _stream_bytes(g1) == _stream_bytes(g0)
    assert _stream_bytes(n1) == _stream_bytes(n0)
    _assert_released(eng1)
    _assert_released(eng0)


def test_chunked_prefill_admission_drains_pipeline_first(model):
    """A long prompt that needs chunked prefill arrives mid-decode: the
    engine must drain the in-flight dispatch before starting the chunk
    (the chunk rewrites cache pages the dispatch could still be reading's
    host mirrors of) — and the streams still match the sync engine."""
    long_prompt = [(i % 200) + 5 for i in range(120)]

    def run(pipeline):
        eng = _engine(model, decode_pipeline=pipeline, prefill_chunk=32,
                      max_cache_len=256)
        first = eng.submit(Request(**SEEDED, ))
        # get the first stream decoding (and, pipelined, an in-flight
        # dispatch) before the chunked prompt shows up
        for _ in range(6):
            eng.step()
        late = eng.submit(Request(prompt_ids=long_prompt, max_tokens=8,
                                  temperature=0.9, seed=7, ignore_eos=True))
        _drain(eng)
        return eng, first, late

    eng1, f1, l1 = run(1)
    eng0, f0, l0 = run(0)
    assert _stream_bytes(f1) == _stream_bytes(f0)
    assert _stream_bytes(l1) == _stream_bytes(l0)
    assert l1.finish_reason == "length" and len(l1.generated) >= 6
    _assert_released(eng1)


# -- lifecycle edges ---------------------------------------------------------


def test_mid_stream_cancel_discards_surplus_neighbor_unperturbed(model):
    """Cancel one stream mid-flight: its slot's surplus tokens from the
    in-flight dispatch are discarded (never emitted), release happens
    exactly once, and the surviving seeded neighbor's bytes are identical
    to a solo run."""
    solo = _engine(model, decode_pipeline=1)
    r_solo = solo.submit(Request(**SEEDED))
    _drain(solo)

    eng = _engine(model, decode_pipeline=1)
    victim = eng.submit(Request(prompt_ids=[9] * 4, max_tokens=64,
                                temperature=1.1, ignore_eos=True))
    keeper = eng.submit(Request(**SEEDED))
    # run until the victim is visibly mid-stream (pipeline in flight)
    for _ in range(1000):
        eng.step()
        if len(victim.generated) >= 4:
            break
    assert len(victim.generated) >= 4
    n_at_cancel = len(victim.generated)
    eng.cancel(victim)
    _drain(eng)
    assert victim.finish_reason == "cancelled"
    # surplus discard: at most the already-fetched prefix plus the one
    # dispatch that was in flight at cancel time may land, never more
    assert len(victim.generated) <= n_at_cancel + 2 * eng.serving.decode_horizon
    assert keeper.generated == r_solo.generated, \
        "a neighbor's cancel must not perturb a seeded stream"
    _assert_released(eng)


def test_deadline_reaps_at_most_one_dispatch_late(model):
    """Deadlines are enforced between dispatches; with the pipeline the
    expiry check can land one dispatch later — bounded, and the slot/pages
    still release exactly once with finish_reason 'timeout'."""
    # a sequence budget large enough that the stream CANNOT finish by length
    # inside the deadline on CPU (tiny_qwen3's default max_seq_len=128 caps
    # the budget at ~124 tokens, which decodes in milliseconds here)
    tok, _, _ = model
    cfg = tiny_qwen3(vocab_size=tok.vocab_size,
                     eos_token_id=tok.eos_token_id, max_seq_len=4096)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = Engine(cfg, params, ServingConfig(
        weights_dtype="bf16", model=MODEL, max_decode_slots=2,
        max_cache_len=4096, page_size=32, prefill_buckets=(16, 32),
        dtype="float32", derived_seed=0, decode_pipeline=1))
    t0 = time.monotonic()
    req = eng.submit(Request(prompt_ids=[3, 1, 4], max_tokens=100000,
                             temperature=0.9, ignore_eos=True,
                             deadline_s=0.25))
    _drain(eng)
    assert req.finish_reason == "timeout"
    # reap latency is bounded by roughly one extra dispatch, not unbounded
    assert time.monotonic() - t0 < 30.0
    assert eng.metrics.deadline_expired.total() >= 1
    _assert_released(eng, 1)


def test_graceful_drain_finishes_inflight_pipeline(model):
    """begin_drain with a dispatch in flight: streams finish normally,
    admissions shed with 'draining', the pipeline retires, and the
    draining→sync handover emits each in-flight token EXACTLY once — the
    drained streams are byte-identical to an undisturbed run (a re-fetch
    of the in-flight dispatch would duplicate tokens and double-advance
    the length mirrors)."""
    ref = _engine(model, decode_pipeline=1)
    ref_reqs = [ref.submit(Request(prompt_ids=[5 + i] * 4, max_tokens=16,
                                   temperature=0.9, seed=i, ignore_eos=True))
                for i in range(2)]
    _drain(ref)

    eng = _engine(model, decode_pipeline=1)
    stop = threading.Event()
    t = threading.Thread(target=eng.run_forever, args=(stop,), daemon=True)
    t.start()
    try:
        reqs = [eng.generate([5 + i] * 4, max_tokens=16, temperature=0.9,
                             seed=i, ignore_eos=True) for i in range(2)]
        # wait until both streams are actually decoding
        deadline = time.monotonic() + 20
        while (not all(len(r.generated) >= 2 for r in reqs)
               and time.monotonic() < deadline):
            time.sleep(0.01)
        eng.begin_drain(timeout_s=30.0)
        with pytest.raises(EngineOverloaded) as ei:
            eng.submit(Request(prompt_ids=[1, 2], max_tokens=4))
        assert ei.value.reason == "draining"
        for r, ref_r in zip(reqs, ref_reqs):
            assert r.wait(timeout=30.0)
            assert r.finish_reason == "length"
            assert r.generated == ref_r.generated, \
                "drain handover must emit in-flight tokens exactly once"
        _assert_released(eng)
    finally:
        stop.set()
        t.join(timeout=10)


# -- chaos: injected fetch failure ------------------------------------------


def test_pipeline_fetch_error_discards_inflight_and_recovers(model):
    """chaos.py contract for ``pipeline_fetch_error``: the in-flight
    dispatch is discarded un-emitted, affected requests fail with
    finish_reason 'error', slots/pages release exactly once, and the
    engine keeps serving the next request."""
    _chaos.get().inject("pipeline_fetch_error", after=2, times=1)
    eng = _engine(model, decode_pipeline=1)
    stop = threading.Event()
    t = threading.Thread(target=eng.run_forever, args=(stop,), daemon=True)
    t.start()
    try:
        doomed = [eng.generate([7 + i] * 4, max_tokens=48, temperature=1.0,
                               ignore_eos=True) for i in range(2)]
        for r in doomed:
            assert r.wait(timeout=30.0)
            assert r.finish_reason == "error", r.finish_reason
        # the in-flight dispatch was discarded, not emitted or leaked
        assert eng._inflight is None
        assert eng.metrics.pipeline_depth.value() == 0.0
        # recovery: the same engine completes a fresh request normally
        ok = eng.generate([2, 4, 6], max_tokens=6, temperature=0.0,
                          ignore_eos=True)
        assert ok.wait(timeout=30.0)
        assert ok.finish_reason == "length"
        assert len(ok.generated) == 6
        _assert_released(eng)
    finally:
        stop.set()
        t.join(timeout=10)


# -- ragged mixed-batch attention (ISSUE 14) ---------------------------------


def _edge_drains() -> int:
    """Admission-edge drains: the prefill + chunk reasons of the process-wide
    tpu_serve_pipeline_drains_total ledger — exactly the drains the ragged
    mixed path exists to eliminate (end-of-run idle settles count under
    'drain' and are expected either way)."""
    by = _metrics.pipeline.snapshot()["drains_by_reason"]
    return by.get("prefill", 0) + by.get("chunk", 0)


_LONG_A = [(i % 150) + 4 for i in range(100)]
_LONG_B = [(i % 90) + 6 for i in range(80)]
_SHORT = [(i % 40) + 7 for i in range(20)]      # one chunk of 32


def _ragged_engine(model, ragged: int, **over):
    # horizon pinned small so the background stream is still decoding (an
    # in-flight dispatch live) when the chunked admissions arrive — the
    # whole point of the mixed-traffic cases
    return _engine(model, decode_pipeline=1, ragged_attention=ragged,
                   prefill_chunk=32, max_cache_len=256, decode_horizon=4,
                   **over)


@pytest.mark.ragged_smoke
def test_mixed_traffic_pipeline_stays_open_and_byte_identical(model):
    """The tentpole contract: interleaved chunked-prefill admissions ride
    the SAME dispatch as the decode batch, so the pipeline never drains on
    an admission edge (the legacy path drains once per admission) — and
    every seeded stream is byte-identical to the legacy engine's."""

    def run(ragged):
        eng = _ragged_engine(model, ragged)
        first = eng.submit(Request(prompt_ids=[5, 9, 2], max_tokens=100,
                                   temperature=0.9, seed=42,
                                   ignore_eos=True))
        # get the first stream decoding (pipelined: an in-flight dispatch)
        for _ in range(6):
            eng.step()
        # the background stream must still be mid-decode with a dispatch in
        # flight, or the admission edges below exercise nothing
        assert eng._inflight is not None
        before = _edge_drains()
        # a ONE-chunk admission under the live batch: its only mixed
        # dispatch is the walk's final one
        short = eng.submit(Request(prompt_ids=list(_SHORT), max_tokens=6,
                                   temperature=0.0, ignore_eos=True))
        eng.step()
        if ragged:
            # ... which stays in flight, the slot joined and its first
            # token still on the device; the step AFTER finds a dispatch in
            # flight too (the next decode, enqueued behind it)
            rec = eng._inflight
            assert eng._chunk is None and rec is not None
            assert rec["first"][0] is short and short.generated == []
            eng.step()
            assert eng._inflight is not None and eng._inflight is not rec
            assert len(short.generated) >= 1
        while not short.finish_reason:
            eng.step()
        late_a = eng.submit(Request(prompt_ids=list(_LONG_A), max_tokens=8,
                                    temperature=0.9, seed=7,
                                    ignore_eos=True))
        for _ in range(10):
            eng.step()
        late_b = eng.submit(Request(prompt_ids=list(_LONG_B), max_tokens=8,
                                    temperature=0.8, seed=13,
                                    ignore_eos=True))
        _drain(eng)
        return eng, (first, short, late_a, late_b), _edge_drains() - before

    eng1, ragged_streams, ragged_edge = run(1)
    eng0, legacy_streams, legacy_edge = run(0)
    for r, s in zip(ragged_streams, legacy_streams):
        assert _stream_bytes(r) == _stream_bytes(s), \
            "ragged mixed stream must be byte-identical to the legacy path"
    assert all(r.finish_reason == "length" for r in ragged_streams)
    # zero drains across interleaved admissions on the ragged path; the
    # legacy path pays at least one per chunked admission
    assert ragged_edge == 0, \
        f"ragged path drained the pipeline {ragged_edge}x on admission edges"
    assert legacy_edge > 0, \
        "legacy path should drain on chunked admissions (test is vacuous)"
    _assert_released(eng1)
    _assert_released(eng0)


@pytest.mark.ragged_smoke
def test_mixed_step_drops_the_padding_rows_of_a_short_chunk(model):
    """A chunk shorter than the program's C rides padded: the padding rows
    are dead (write row -1, limit 0), so a mixed dispatch changes NO pool
    row of the chunking slot at or past ``off + len(chunk)`` — dropped, not
    parked on the slot's later rows or its scratch page — while the rows
    the chunk owns are written. Streams stay the legacy walk's."""
    import numpy as np

    def run(ragged, spy=None):
        eng = _ragged_engine(model, ragged)
        if spy is not None:
            real = eng._mixed_dispatch

            def spying(st, chunk, tok_in, len_in):
                before = {n: np.asarray(a) for n, a in eng.cache.items()}
                rec = real(st, chunk, tok_in, len_in)
                spy.append((st["C"], st["off"], len(chunk),
                            eng.table[st["slot"]].copy(), before,
                            {n: np.asarray(a) for n, a in eng.cache.items()}))
                return rec

            eng._mixed_dispatch = spying
        first = eng.submit(Request(prompt_ids=[5, 9, 2], max_tokens=60,
                                   temperature=0.9, seed=42,
                                   ignore_eos=True))
        for _ in range(6):
            eng.step()
        assert eng._inflight is not None
        late = eng.submit(Request(prompt_ids=list(_LONG_A), max_tokens=8,
                                  temperature=0.9, seed=7, ignore_eos=True))
        _drain(eng)
        _assert_released(eng)
        return first, late

    seen: list = []
    ragged, legacy = run(1, seen), run(0)
    for r, s in zip(ragged, legacy):
        assert _stream_bytes(r) == _stream_bytes(s)
    short = [d for d in seen if d[2] < d[0]]
    assert short, "no mixed dispatch carried a padded chunk (test is vacuous)"
    for C, off, n, pages, before, after in short:
        ps = before["k"].shape[3]
        rows = np.arange(len(pages) * ps)

        def view(pool, name):      # the slot's logical rows: [rows, L, Hkv, D]
            return pool[name][:, pages[rows // ps], :, rows % ps]

        for name in ("k", "v"):
            was, now = view(before, name), view(after, name)
            assert np.array_equal(was[off + n:], now[off + n:]), \
                f"{name}: a padding row's write landed past row {off + n}"
            assert not np.array_equal(was[off:off + n], now[off:off + n])


@pytest.mark.ragged_smoke
def test_ragged_vs_legacy_parity_sampled_logprobs_penalties(model):
    """Feature parity through the mixed program: sampled, logprobs, and
    penalties requests produce byte-identical streams ragged vs legacy."""
    specs = [
        dict(prompt_ids=list(_LONG_A), max_tokens=10, temperature=0.8,
             seed=3, ignore_eos=True, logprobs=3),
        dict(prompt_ids=[4, 8, 15], max_tokens=16, temperature=0.7, seed=5,
             ignore_eos=True, presence_penalty=0.5, frequency_penalty=0.3,
             repetition_penalty=1.15),
        dict(prompt_ids=list(_LONG_B), max_tokens=10, temperature=0.9,
             seed=8, ignore_eos=True, repetition_penalty=1.2),
    ]
    ragged = _run_set(_ragged_engine(model, 1), [dict(s) for s in specs])
    legacy = _run_set(_ragged_engine(model, 0), [dict(s) for s in specs])
    for r, s in zip(ragged, legacy):
        assert _stream_bytes(r) == _stream_bytes(s)
    assert all(r.finish_reason == "length" for r in ragged)


@pytest.mark.ragged_smoke
def test_ragged_dispatch_error_drops_dispatch_keeps_serving(model):
    """chaos.py contract for ``ragged_dispatch_error``: the in-flight mixed
    dispatch is discarded un-emitted, the half-prefilled slot's pages
    release exactly once, affected requests fail with 'error', and the
    engine keeps serving the next request (drop-not-fail)."""
    _chaos.get().inject("ragged_dispatch_error", after=1, times=1)
    eng = _ragged_engine(model, 1)
    stop = threading.Event()
    t = threading.Thread(target=eng.run_forever, args=(stop,), daemon=True)
    t.start()
    try:
        decoding = eng.generate([7] * 4, max_tokens=64, temperature=1.0,
                                ignore_eos=True)
        deadline = time.monotonic() + 20
        while len(decoding.generated) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        chunked = eng.generate(list(_LONG_A), max_tokens=8, temperature=0.9,
                               ignore_eos=True)
        # the live decode stream had tokens before the fault; the chunk-walk
        # request dies un-emitted (wait returns its empty generated list)
        assert decoding.wait(timeout=30.0)
        chunked.wait(timeout=30.0)
        assert chunked.finish_reason == "error", chunked.finish_reason
        assert chunked.generated == [], "discarded dispatch must not emit"
        # the in-flight mixed dispatch was discarded, not emitted or leaked
        assert eng._inflight is None
        assert eng.metrics.pipeline_depth.value() == 0.0
        # recovery: the same engine completes a fresh request normally
        ok = eng.generate([2, 4, 6], max_tokens=6, temperature=0.0,
                          ignore_eos=True)
        assert ok.wait(timeout=30.0)
        assert ok.finish_reason == "length"
        assert len(ok.generated) == 6
        _assert_released(eng)
    finally:
        stop.set()
        t.join(timeout=10)


# -- a stream gets one queue item a dispatch (ISSUE 31) ----------------------


def _plain_streams(model, eng):
    return [eng.submit(Request(prompt_ids=[5, 9, 2 + i], max_tokens=21,
                               temperature=0.9, seed=42 + i, ignore_eos=True,
                               stream=True)) for i in range(2)], None


def _mixed_streams(model, eng):
    first = eng.submit(Request(prompt_ids=[5, 9, 2], max_tokens=60,
                               temperature=0.9, seed=42, ignore_eos=True,
                               stream=True))

    def late():     # a chunked admission under the live stream: mixed_step
        return eng.submit(Request(prompt_ids=list(_LONG_A), max_tokens=12,
                                  temperature=0.9, seed=7, ignore_eos=True,
                                  stream=True))
    return [first], late


def _spec_streams(model, eng):
    tok = model[0]
    return [eng.submit(Request(prompt_ids=tok.encode("ab" * 8),
                               max_tokens=40, temperature=0.0,
                               ignore_eos=True, stream=True)),
            eng.submit(Request(stream=True, **SEEDED))], None


def _guided_streams(model, eng):
    tok = model[0]
    g = grammar_for(tok, {"type": "json_object"}, [tok.eos_token_id])
    return [eng.submit(Request(prompt_ids=[5, 9, 2], max_tokens=40,
                               temperature=0.9, seed=42, ignore_eos=True,
                               stream=True)),
            eng.generate(tok.encode("json:"), guided=g, max_tokens=60,
                         temperature=0.0, logit_bias=_PRESSURE,
                         stream=True)], None


_ITEM_KINDS = {
    "plain": (dict(decode_pipeline=1), _plain_streams, "decode_steps"),
    "mixed": (dict(decode_pipeline=1, ragged_attention=1, prefill_chunk=32,
                   max_cache_len=256), _mixed_streams, "mixed_step"),
    "spec": (dict(decode_pipeline=1, spec_decode=True, spec_k=4,
                  spec_ngram=3), _spec_streams, "spec_decode_step"),
    "guided": (dict(decode_pipeline=1), _guided_streams, "decode_steps"),
}


@pytest.mark.parametrize("horizon", [1, 8])
@pytest.mark.parametrize("kind", list(_ITEM_KINDS))
def test_stream_items_one_a_dispatch_and_concatenate(model, kind, horizon):
    """The unit the engine hands a stream is what ONE dispatch produced for
    it: the items of a stream concatenate to ``generated``, the activation
    token is an item of its own and in the queue when its step returns, no
    token waits for a later step, and a dispatch's record counts one put
    for each stream it gave tokens (``puts``, beside ``emitted``)."""
    over, traffic, program = _ITEM_KINDS[kind]
    if kind == "spec" and horizon == 1:
        program = "decode_steps"    # the verify path needs a horizon above 1
    eng = _engine(model, decode_horizon=horizon, **over)
    closed = []                     # (record, streams given tokens, tokens)
    reqs = []

    def spy(name):
        real = getattr(eng, name)
        real_close = eng._dispatch_close

        def wrapped(*a, **kw):
            got = {}
            eng._dispatch_close = lambda rec, *ca, **ckw: (
                got.update(rec=rec), real_close(rec, *ca, **ckw))[1]
            before = {r.id: len(r.generated) for r in reqs}
            try:
                return real(*a, **kw)
            finally:
                eng._dispatch_close = real_close
                # (a first token that rode a mixed record goes out at its
                # fetch, as the activation's own item: not the record's)
                rode = a[0].get("first") if isinstance(a[0], dict) else None
                grew = [len(r.generated) - before[r.id] for r in reqs
                        if r.id in before
                        and (rode is None or rode[0] is not r)]
                closed.append((got["rec"], sum(1 for g in grew if g),
                               sum(grew)))
        setattr(eng, name, wrapped)

    spy("_decode_fetch")
    spy("_do_spec_decode")
    first_seen = {}
    streams, late = traffic(model, eng)
    reqs.extend(streams)
    for n in range(20000):
        if late is not None and n == 6:
            assert eng._inflight is not None    # or nothing rides mixed_step
            reqs.append(late())
        did = eng.step()
        for r in reqs:
            assert r.pending == [], "a token waits for a later step()"
            if r.t_first_token and r.id not in first_seen:
                # put at once: alone, ahead of everything else
                first_seen[r.id] = list(r.out_queue.queue)[0]
        if not did:
            break
    assert len(first_seen) == len(reqs)
    for r in reqs:
        items, nones = _queue_items(r)
        assert nones == 1
        assert [t for it in items for t in it] == r.generated
        assert items[0] == [r.generated[0]] == first_seen[r.id]
        assert all(it for it in items)
        if kind != "spec":
            assert all(len(it) <= horizon for it in items)
    # the records: emitted and puts are what the dispatch did, counted here
    # from the requests themselves
    assert any(rec["program"] == program for rec, _, _ in closed), \
        [rec["program"] for rec, _, _ in closed]
    for rec, n_streams, n_tokens in closed:
        assert rec["emitted"] == n_tokens
        assert rec["puts"] == n_streams
    if horizon == 8 and kind == "plain":
        # one item a stream a dispatch, 8 ids each
        assert any(rec["puts"] == 2 and rec["emitted"] == 16
                   for rec, _, _ in closed)
    if horizon == 1 and kind != "spec":
        assert all(rec["emitted"] == rec["puts"] for rec, _, _ in closed)
    total = sum(rec["puts"] for rec, _, _ in closed) + len(reqs)
    assert eng.metrics.stream_items.total() == total
    _assert_released(eng)


class _ArrivalEvent:
    """Stands in for the engine's work event: ``wait`` records the timeout
    it was given and returns at once, after letting ``on_wait`` happen (an
    arrival DURING the wait) — so the test runs on events, not on a clock."""

    def __init__(self, real):
        self.real, self.waits, self.on_wait = real, [], None

    def wait(self, timeout=None):
        self.waits.append(timeout)
        if self.on_wait is not None:
            arrive, self.on_wait = self.on_wait, None
            arrive()
        return True

    def __getattr__(self, name):        # set / clear / is_set
        return getattr(self.real, name)


def test_next_dispatch_is_bound_late_for_an_arrival(model):
    """With a dispatch running, a slot free and nobody queued, the step
    gives an arrival the first half of the running dispatch's expected time
    before it enqueues what comes next — so a request that shows up a
    moment after a finish rides the NEXT dispatch (mixed_step) and not the
    one after it. A full batch, a waiting request or an idle device wait
    for nothing."""
    eng = _engine(model, decode_horizon=8, decode_pipeline=1,
                  ragged_attention=1, prefill_chunk=32, max_cache_len=256)
    first = eng.submit(Request(prompt_ids=[5, 9, 2], max_tokens=100,
                               ignore_eos=True))
    for _ in range(5):
        eng.step()
    assert eng._inflight is not None
    key = "decode_steps"        # (kept A SUBSTEP, whatever count ran)
    # what was measured so far has no compile in it (a loaded host may not
    # have caught a dispatch still running yet: then nothing is measured)
    assert all(0 < v < 5.0 for v in eng._dispatch_s.values())
    ev = eng._work_event = _ArrivalEvent(eng._work_event)

    def step(expect=4.0):
        # as if the dispatch in flight took so long
        if eng._inflight is not None:
            eng._dispatch_s[key] = expect / eng._inflight["horizon"]
        eng._busy_watermark = time.monotonic()   # ... and has just begun
        before = len(ev.waits)
        eng.step()
        return ev.waits[before:]

    late = Request(prompt_ids=list(_LONG_B), max_tokens=4, ignore_eos=True)
    ev.on_wait = lambda: eng.submit(late)
    (timeout,) = step()                       # ONE wait: half of 4 s at most
    assert 0 < timeout <= 2.0
    assert eng._chunk is not None and eng._chunk["req"] is late \
        and eng._chunk["mixed"], "admitted in the step that waited for it"
    while eng._chunk is not None:             # the walk: steps wait for nothing
        assert step() == []
    # both slots busy: nothing could be admitted, so nothing is waited for
    assert eng.sched.stats().active_slots == 2
    assert step() == []
    _drain(eng)
    assert len(first.generated) == 100 and len(late.generated) == 4
    # an expectation that was too high comes down by itself: a dispatch that
    # had finished before the host looked took at most that long
    assert eng._dispatch_s[key] < 4.0 / eng.serving.decode_horizon
    # nothing in flight: an idle device is never made to wait
    eng.submit(Request(prompt_ids=[7, 7], max_tokens=2, ignore_eos=True))
    assert eng._inflight is None and step() == []
    _drain(eng)
    _assert_released(eng)


# -- feature paths ride the ragged pipeline (ISSUE 16) -----------------------


def _feature_drains() -> int:
    """Fallback-tax drains: the spec + guided reasons of the process-wide
    tpu_serve_pipeline_drains_total ledger — exactly the drains the
    feature-path refactor (``ragged_features=1``) exists to eliminate
    (end-of-run idle settles count under 'drain' and are expected)."""
    by = _metrics.pipeline.snapshot()["drains_by_reason"]
    return by.get("spec", 0) + by.get("guided", 0)


@pytest.mark.ragged_smoke
def test_guided_streams_byte_identical_ragged_features_on_off(model):
    """ragged_features=1 keeps guided slots ON the pipeline (the FSM mask is
    a device-resident per-row operand, settled-then-dispatched for
    freshness); ragged_features=0 restores the PR-14 sync gating. Guided,
    unguided-neighbor, and chunked-admission streams must be byte-identical
    across the two arms, with ZERO guided- and admission-reason drains on
    the riding arm. The fallback arm never restarts the pipeline while a
    guided slot is live, so it dispatches strictly less — asserted as the
    vacuousness guard."""
    tok, _, _ = model

    def run(feats):
        eng = _ragged_engine(model, 1, ragged_features=feats)
        # (the whole horizon with a slot free too: the arms' dispatch
        # counts compare the pipelines, not two measured short counts)
        eng._short_horizon = lambda: eng.serving.decode_horizon
        g = grammar_for(tok, {"type": "json_object"}, [tok.eos_token_id])
        first = eng.submit(Request(prompt_ids=[5, 9, 2], max_tokens=100,
                                   temperature=0.9, seed=42,
                                   ignore_eos=True))
        # get the neighbor decoding — pipelined, so the guided admission
        # below lands with a dispatch in flight (the handover under test)
        for _ in range(6):
            eng.step()
        snap = _metrics.pipeline.snapshot()
        before = (_feature_drains(), _edge_drains(),
                  snap["dispatches_total"])
        guided = eng.generate(tok.encode("json:"), guided=g, max_tokens=100,
                              temperature=0.0, logit_bias=_PRESSURE)
        for _ in range(10):
            eng.step()
        late = eng.submit(Request(prompt_ids=list(_LONG_A), max_tokens=8,
                                  temperature=0.9, seed=7, ignore_eos=True))
        _drain(eng)
        snap = _metrics.pipeline.snapshot()
        after = (_feature_drains(), _edge_drains(), snap["dispatches_total"])
        return eng, (first, guided, late), \
            tuple(b - a for a, b in zip(before, after))

    eng1, on, (on_feat, on_edge, on_disp) = run(1)
    eng0, off, (_, _, off_disp) = run(0)
    assert on[1].finish_reason == "stop"
    assert isinstance(json.loads(tok.decode(on[1].generated)), dict)
    for a, b in zip(on, off):
        assert _stream_bytes(a) == _stream_bytes(b), \
            "guided traffic on the pipeline must match the sync fallback"
    assert on_feat == 0, \
        f"guided slot de-pipelined {on_feat}x on the riding arm"
    assert on_edge == 0, \
        f"guided admission paid {on_edge} edge drains on the riding arm"
    assert on_disp > off_disp, \
        "riding arm should out-dispatch the sync fallback (test is vacuous)"
    _assert_released(eng1)
    _assert_released(eng0)


@pytest.mark.ragged_smoke
def test_lora_streams_byte_identical_ragged_features_on_off(model, tmp_path):
    """Adapter rows ride the mixed dispatch via the per-row adapter-index
    operand (packed ``[1, B+C]`` A/B deltas); ragged_features=0 de-pipelines
    them to the per-slot legacy path. Tuned, base-neighbor, and
    chunked-tuned streams must be byte-identical across the two arms."""
    from test_lora import _write_adapter
    tok, cfg, params = model
    path = _write_adapter(tmp_path, "ad", cfg, seed=3)

    def run(feats):
        serving = ServingConfig(
            weights_dtype="bf16", model=MODEL, max_decode_slots=2,
            max_cache_len=256, page_size=32,
            prefill_buckets=(16, 32, 64, 128), dtype="float32",
            derived_seed=0, decode_pipeline=1, ragged_attention=1,
            ragged_features=feats, prefill_chunk=32, decode_horizon=4)
        eng = Engine(cfg, params, serving, lora={"ad": path})
        tuned = eng.submit(Request(prompt_ids=[5, 9, 2], max_tokens=12,
                                   temperature=0.9, seed=11,
                                   ignore_eos=True, lora="ad"))
        base = eng.submit(Request(**SEEDED))
        for _ in range(4):
            eng.step()
        late = eng.submit(Request(prompt_ids=list(_LONG_B), max_tokens=8,
                                  temperature=0.8, seed=13, ignore_eos=True,
                                  lora="ad"))
        _drain(eng)
        return eng, (tuned, base, late)

    eng1, on = run(1)
    eng0, off = run(0)
    for a, b in zip(on, off):
        assert _stream_bytes(a) == _stream_bytes(b), \
            "LoRA traffic on the pipeline must match the per-slot fallback"
    assert all(r.finish_reason == "length" for r in on)
    _assert_released(eng1)
    _assert_released(eng0)


@pytest.mark.ragged_smoke
def test_spec_streams_byte_identical_ragged_features_on_off(model):
    """Spec verify rides the ragged dispatch family via the
    carry-generation handoff (ragged_features=1) where ragged_features=0
    keeps the PR-14 mandatory pre-spec pipeline drain. Greedy spec-friendly
    streams, a seeded sampled neighbor, and a chunked admission must be
    byte-identical across the arms; the riding arm drafts real tokens and
    pays ZERO spec-reason drains."""
    tok, _, _ = model

    def run(feats):
        eng = _ragged_engine(model, 1, ragged_features=feats,
                             spec_decode=True, spec_k=4, spec_ngram=3)
        before = _feature_drains()
        rep = eng.submit(Request(prompt_ids=tok.encode("ab" * 8),
                                 max_tokens=40, temperature=0.0,
                                 ignore_eos=True))
        neighbor = eng.submit(Request(**SEEDED))
        for _ in range(6):
            eng.step()
        late = eng.submit(Request(prompt_ids=list(_LONG_B), max_tokens=8,
                                  temperature=0.8, seed=13,
                                  ignore_eos=True))
        _drain(eng)
        drafted = eng.metrics.spec_drafted_tokens.total()
        return eng, (rep, neighbor, late), _feature_drains() - before, drafted

    eng1, on, on_drains, on_drafted = run(1)
    eng0, off, _, off_drafted = run(0)
    for a, b in zip(on, off):
        assert _stream_bytes(a) == _stream_bytes(b), \
            "spec traffic on the pipeline must match the drain-first arm"
    assert on_drafted > 0 and off_drafted > 0, \
        "spec decode never proposed drafts (test is vacuous)"
    assert on_drains == 0, \
        f"spec verify drained the pipeline {on_drains}x on the riding arm"
    _assert_released(eng1)
    _assert_released(eng0)


@pytest.mark.ragged_smoke
@pytest.mark.parametrize("kind", ["guided", "spec"])
def test_ragged_feature_error_drops_dispatch_keeps_serving(model, kind):
    """chaos.py contract for ``ragged_feature_error``: a corrupted guided
    mask upload / spec verify-row transfer discards the dispatch UN-EMITTED,
    affected requests fail with 'error', slots/pages release exactly once,
    and the engine keeps serving (drop-not-fail)."""
    tok, _, _ = model
    _chaos.get().inject("ragged_feature_error", times=1, kind=kind)
    eng = _ragged_engine(model, 1,
                         **(dict(spec_decode=True, spec_k=4, spec_ngram=3)
                            if kind == "spec" else {}))
    stop = threading.Event()
    t = threading.Thread(target=eng.run_forever, args=(stop,), daemon=True)
    t.start()
    try:
        if kind == "guided":
            g = grammar_for(tok, {"type": "json_object"},
                            [tok.eos_token_id])
            victim = eng.generate(tok.encode("json:"), guided=g,
                                  max_tokens=100, temperature=0.0,
                                  logit_bias=_PRESSURE)
        else:
            victim = eng.generate(tok.encode("ab" * 8), max_tokens=40,
                                  temperature=0.0, ignore_eos=True)
        victim.wait(timeout=30.0)
        assert victim.finish_reason == "error", victim.finish_reason
        st = _chaos.get().stats()["ragged_feature_error"]
        assert st["fired"] == 1, st
        # tokens streamed by dispatches BEFORE the fault stay; the faulted
        # dispatch itself was discarded un-emitted — nothing may surface
        # after the error lands (a late emit would mean the record leaked)
        frozen = list(victim.generated)
        assert len(frozen) < victim.max_tokens
        assert eng._inflight is None
        assert eng.metrics.pipeline_depth.value() == 0.0
        # recovery: the same engine completes a fresh request normally
        ok = eng.generate([2, 4, 6], max_tokens=6, temperature=0.0,
                          ignore_eos=True)
        assert ok.wait(timeout=30.0)
        assert ok.finish_reason == "length"
        assert len(ok.generated) == 6
        assert victim.generated == frozen, \
            "discarded dispatch emitted after the error"
        _assert_released(eng)
    finally:
        stop.set()
        t.join(timeout=10)


@pytest.mark.ragged_smoke
def test_chaos_seasoned_mixed_features_zero_feature_drains(model, tmp_path):
    """The acceptance workload: spec + guided + LoRA + chunked prefill all
    concurrently, seasoned with a mid-run ``ragged_feature_error`` — the
    drain ledger stays at ZERO for every reason except the deliberate ones
    ('fail' for the injected fault, 'drain' for idle settles), and the
    engine finishes a clean follow-up wave after the fault."""
    from test_lora import _write_adapter
    tok, cfg, params = model
    path = _write_adapter(tmp_path, "ad", cfg, seed=3)
    serving = ServingConfig(
        weights_dtype="bf16", model=MODEL, max_decode_slots=2,
        max_cache_len=256, page_size=32,
        prefill_buckets=(16, 32, 64, 128), dtype="float32",
        derived_seed=0, decode_pipeline=1, ragged_attention=1,
        ragged_features=1, prefill_chunk=32, decode_horizon=4,
        spec_decode=True, spec_k=4, spec_ngram=3)
    eng = Engine(cfg, params, serving, lora={"ad": path})
    g = grammar_for(tok, {"type": "json_object"}, [tok.eos_token_id])
    by0 = dict(_metrics.pipeline.snapshot()["drains_by_reason"])
    _chaos.get().inject("ragged_feature_error", after=2, times=1)
    stop = threading.Event()
    t = threading.Thread(target=eng.run_forever, args=(stop,), daemon=True)
    t.start()
    try:
        def wave():
            reqs = [
                eng.generate(tok.encode("ab" * 8), max_tokens=24,
                             temperature=0.0, ignore_eos=True, lora="ad"),
                eng.generate(tok.encode("json:"), guided=g, max_tokens=60,
                             temperature=0.0, logit_bias=_PRESSURE),
                eng.generate(list(_LONG_A), max_tokens=8, temperature=0.9,
                             ignore_eos=True),
            ]
            for r in reqs:
                r.wait(timeout=60.0)
            return reqs

        first = wave()          # the armed fault fires somewhere in here
        again = wave()          # post-fault: everything serves clean
        for r in again:
            assert r.finish_reason in ("stop", "length"), r.finish_reason
        # at least one wave-1 victim died on the injected fault; nothing
        # hangs, nothing double-releases
        assert all(r.finish_reason for r in first)
        by1 = _metrics.pipeline.snapshot()["drains_by_reason"]
        for reason in ("prefill", "chunk", "spec", "guided"):
            got = by1.get(reason, 0) - by0.get(reason, 0)
            assert got == 0, \
                f"feature workload paid {got} '{reason}' pipeline drains"
        _assert_released(eng)
    finally:
        stop.set()
        t.join(timeout=10)


# -- the final chunk of an admission stays in flight (ISSUE 44) --------------


def _live(eng, n=100):
    """A background stream that is decoding with a dispatch in flight."""
    live = eng.submit(Request(prompt_ids=[5, 9, 2], max_tokens=n,
                              temperature=0.9, seed=42, ignore_eos=True))
    for _ in range(6):
        eng.step()
    assert eng._inflight is not None
    return live


def _admit_in_flight(eng, **spec):
    """Submit a one-chunk request under the live batch and step ONCE: the
    walk's final (only) mixed dispatch is left in flight, the slot has
    joined the batch and nothing of the request has been seen yet."""
    req = eng.submit(Request(**spec))
    eng.step()
    rec = eng._inflight
    assert eng._chunk is None and rec is not None and rec.get("mixed")
    assert rec["drec"]["activation"] == "in_flight"
    slot = rec["first"][1]
    assert rec["first"][0] is req and eng.slot_req[slot] is req
    assert req.generated == [] and not req.t_first_token
    return req, slot


def _paths(eng):
    m = eng.metrics.activations
    return int(m.value(path="in_flight")), int(m.value(path="settled"))


_UNDER_LIVE = {
    "greedy": dict(prompt_ids=list(_SHORT), max_tokens=9, temperature=0.0,
                   ignore_eos=True),
    "seeded": dict(prompt_ids=list(_SHORT), max_tokens=9, temperature=0.9,
                   top_p=0.9, seed=7, ignore_eos=True),
    "logprobs": dict(prompt_ids=list(_SHORT), max_tokens=9, temperature=0.8,
                     seed=11, ignore_eos=True, logprobs=3),
    "min_tokens_bias": dict(prompt_ids=list(_SHORT), max_tokens=9,
                            temperature=0.0, min_tokens=4,
                            logit_bias=((_EOS, 100.0),)),
}


@pytest.mark.ragged_smoke
@pytest.mark.parametrize("kind", list(_UNDER_LIVE))
def test_in_flight_final_chunk_streams_are_the_legacy_engines(model, kind):
    """Closed mixed traffic: a request admitted under a live batch whose
    final chunk stays in flight streams byte for byte what the legacy walk
    streams — greedy, seeded, with logprobs, with the first token under a
    min_tokens ban and a bias — and so does its neighbour."""

    def run(ragged):
        eng = _ragged_engine(model, ragged)
        live = _live(eng)
        late = eng.submit(Request(**_UNDER_LIVE[kind]))
        _drain(eng)
        _assert_released(eng, 2)
        return eng, live, late

    eng1, live1, late1 = run(1)
    _, live0, late0 = run(0)
    assert _paths(eng1) == (1, 0)
    if kind == "logprobs":
        # the VALUES come from two programs (mixed_step and the legacy
        # chunk program; one decode batch and two) and differ in the last
        # float32 digit, at the parent commit as here: ids exact, values
        # to 1e-5
        assert late1.generated == late0.generated
        for (own1, alts1), (own0, alts0) in zip(late1.logprob_data,
                                                late0.logprob_data):
            assert [t for t, _ in alts1] == [t for t, _ in alts0]
            assert [own1] + [v for _, v in alts1] == pytest.approx(
                [own0] + [v for _, v in alts0], abs=1e-5)
    else:
        assert _stream_bytes(late1) == _stream_bytes(late0)
    assert _stream_bytes(live1) == _stream_bytes(live0)
    assert late1.t_first_token and eng1.metrics.ttft._total == 2


@pytest.mark.ragged_smoke
def test_two_waiting_requests_walk_one_behind_the_other(model):
    """Two requests wait at once beside a live stream: the second meets the
    first's final chunk in flight and takes the chunk walk too — its mixed
    dispatch is enqueued behind, decodes the first's row from the device
    carry, and the first's token goes out where ITS dispatch is fetched.
    No prefill program runs on an idle pipeline; the streams are the legacy
    engine's."""
    specs = [dict(prompt_ids=list(_SHORT), max_tokens=9, temperature=0.9,
                  seed=7, ignore_eos=True),
             dict(prompt_ids=list(_SHORT[3:]), max_tokens=9,
                  temperature=0.0, ignore_eos=True)]

    def run(ragged):
        eng = _ragged_engine(model, ragged, max_decode_slots=3)
        live = _live(eng)
        a, b = (eng.submit(Request(**s)) for s in specs)
        if ragged:
            eng.step()
            m1 = eng._inflight
            assert m1["first"][0] is a and eng._chunk is None
            eng.step()
            m2 = eng._inflight
            assert m2["first"][0] is b and m2["drec"]["carry_steps"] == 1
            assert m1["first"][1] in m2["active"], \
                "the first's row must decode in the second's mixed dispatch"
            assert len(a.generated) == 1 and b.generated == []
            eng.step()
            assert len(a.generated) == 2 and len(b.generated) == 1
        _drain(eng)
        _assert_released(eng, 3)
        return eng, (live, a, b)

    eng, ragged = run(1)
    _, legacy = run(0)
    assert _paths(eng) == (2, 0)
    for r, s in zip(ragged, legacy):
        assert _stream_bytes(r) == _stream_bytes(s)


@pytest.mark.ragged_smoke
@pytest.mark.parametrize("how", ["max_tokens_1", "eos"])
def test_first_token_that_ends_its_request_finishes_once(model, how):
    """A first token that ends its request (the budget, EOS) finishes the
    slot at the mixed dispatch's fetch, once; the rows the FOLLOWING
    dispatch already computed for the slot are surplus and discarded, and
    the slot's next occupant receives none of them."""
    spec = dict(prompt_ids=list(_SHORT), temperature=0.0, stream=True)
    if how == "eos":
        spec.update(max_tokens=8, logit_bias=((_EOS, 100.0),))
    else:
        spec.update(max_tokens=1, ignore_eos=True)
    follower = dict(prompt_ids=[8, 3, 1, 4], max_tokens=7, temperature=0.9,
                    seed=5, ignore_eos=True, stream=True)
    solo = _run_set(_ragged_engine(model, 1), [dict(follower)])[0]

    eng = _ragged_engine(model, 1)
    live = _live(eng)
    finished0 = eng.sched.stats().finished_total
    req, slot = _admit_in_flight(eng, **spec)
    eng.step()      # the next decode enqueued WITH the slot, then M's fetch
    assert req.finish_reason == ("stop" if how == "eos" else "length")
    assert len(req.generated) == 1 and eng.slot_req[slot] is None
    assert (how == "eos") == (req.generated == [_EOS])
    assert eng.sched.stats().finished_total == finished0 + 1
    surplus = eng._inflight
    assert surplus is not None and slot in surplus["active"], \
        "no dispatch holds surplus rows of the slot (test is vacuous)"
    # the next occupant of the SAME slot, admitted under that dispatch
    nxt, slot2 = _admit_in_flight(eng, **follower)
    assert slot2 == slot
    _drain(eng)
    items, nones = _queue_items(req)
    assert (items, nones) == ([req.generated], 1), "finished more than once"
    assert nxt.generated == solo.generated, \
        "the slot's next occupant received its predecessor's surplus rows"
    items, nones = _queue_items(nxt)
    assert nones == 1 and [t for it in items for t in it] == nxt.generated
    assert items[0] == nxt.generated[:1]
    assert live.finish_reason == "length"
    _assert_released(eng, 3)


@pytest.mark.ragged_smoke
@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_request_reaped_while_its_final_chunk_is_in_flight(model, how):
    """A cancel or a deadline between the join and the fetch: the request
    is reaped as any active slot is, the chunk token that comes back is
    discarded (never emitted), slot and pages go back exactly once and the
    neighbour's seeded stream is what it is alone."""
    alone = _ragged_engine(model, 1)
    ref = _live(alone)
    _drain(alone)

    eng = _ragged_engine(model, 1)
    live = _live(eng)
    req, slot = _admit_in_flight(eng, prompt_ids=list(_SHORT), max_tokens=9,
                                 temperature=0.0, ignore_eos=True,
                                 stream=True)
    if how == "cancel":
        eng.cancel(req)
    else:
        req.t_deadline = time.monotonic() - 1e-3
    eng.step()
    assert req.finish_reason == ("cancelled" if how == "cancel"
                                 else "timeout")
    assert eng.slot_req[slot] is None
    _drain(eng)
    assert req.generated == [] and not req.t_first_token
    assert _queue_items(req) == ([], 1)
    assert _stream_bytes(live) == _stream_bytes(ref)
    assert eng.metrics.ttft._total == 1
    _assert_released(eng, 2)


@pytest.mark.ragged_smoke
@pytest.mark.parametrize("fault", ["pipeline_fetch_error",
                                   "ragged_dispatch_error"])
def test_fetch_error_while_a_final_chunk_is_in_flight(model, fault):
    """The mixed dispatch's fetch raises with the slot already joined: the
    failover finishes it as the active slot it is — slot and pages released
    exactly once, nothing emitted — and the engine keeps serving."""
    eng = _ragged_engine(model, 1)
    live = _live(eng)
    req, slot = _admit_in_flight(eng, prompt_ids=list(_SHORT), max_tokens=9,
                                 temperature=0.0, ignore_eos=True,
                                 stream=True)
    n_live = len(live.generated)
    _chaos.get().inject(fault, after=0, times=1)
    with pytest.raises(_chaos.InjectedFault):
        eng.step()
    eng._fail_all("injected")           # what run_forever does with it
    assert req.finish_reason == live.finish_reason == "error"
    assert req.generated == [] and _queue_items(req) == ([], 1)
    assert len(live.generated) == n_live, "the failed fetch emitted"
    assert eng._inflight is None and eng._chunk is None
    _assert_released(eng, 2)
    ok = eng.submit(Request(prompt_ids=[2, 4, 6], max_tokens=6,
                            temperature=0.0, ignore_eos=True))
    _drain(eng)
    assert ok.finish_reason == "length" and len(ok.generated) == 6
    _assert_released(eng, 3)


@pytest.mark.ragged_smoke
def test_preempted_before_its_first_token_was_seen(model):
    """The pool runs dry and the newest request — the one whose final chunk
    is still in flight — is preempted: the carry generation moves, the
    pipeline drains, the unseen first token is discarded, and the request
    comes back as the fresh admission it still is (nothing of it was
    emitted), sampling that token again under the same key: its stream is
    the unpreempted one."""
    spec = dict(prompt_ids=list(_SHORT), max_tokens=9, temperature=0.9,
                seed=7, ignore_eos=True, stream=True)

    def run(preempt):
        eng = _ragged_engine(model, 1)
        live = _live(eng)
        req, slot = _admit_in_flight(eng, **spec)
        if preempt:
            drains = _edge_drains()
            eng._preempt(slot)
            assert req.id not in eng._resume_ctx
            eng.step()
            assert _edge_drains() == drains + 1 and req.generated == []
        _drain(eng)
        _assert_released(eng, 3 if preempt else 2)
        return eng, live, req

    eng, live, req = run(True)
    _, live0, req0 = run(False)
    assert int(eng.metrics.preemptions.total()) == 1
    assert _stream_bytes(req) == _stream_bytes(req0)
    assert _stream_bytes(live) == _stream_bytes(live0)
    items, nones = _queue_items(req)
    assert nones == 1 and [t for it in items for t in it] == req.generated
    assert eng.metrics.ttft._total == 2


def _settling_penalised(model, eng):
    return eng.submit(Request(prompt_ids=list(_SHORT), max_tokens=9,
                              temperature=0.7, seed=5, ignore_eos=True,
                              presence_penalty=0.5, frequency_penalty=0.3,
                              repetition_penalty=1.15))


def _settling_guided(model, eng):
    tok = model[0]
    g = grammar_for(tok, {"type": "json_object"}, [tok.eos_token_id])
    return eng.generate(tok.encode("json:"), guided=g, max_tokens=40,
                        temperature=0.0, logit_bias=_PRESSURE)


def _settling_prompt_logprobs(model, eng):
    # (never a walk: the chunk programs return no prompt logprobs, so the
    # admission settles the pipeline and prefills whole, Engine._admit_round)
    return eng.submit(Request(prompt_ids=list(_SHORT), max_tokens=6,
                              temperature=0.0, ignore_eos=True,
                              prompt_logprobs=2))


def _settling_resumed(model, eng):
    req = eng.submit(Request(prompt_ids=list(_SHORT), max_tokens=12,
                             temperature=0.9, seed=7, ignore_eos=True))
    while len(req.generated) < 3:
        eng.step()
    eng._preempt(eng.slot_req.index(req))
    assert req.id in eng._resume_ctx
    return req


# what tpu_serve_activations_total{path} counts meanwhile: (in_flight, settled)
_SETTLING = {"penalised": (_settling_penalised, (0, 1)),
             "guided": (_settling_guided, (0, 1)),
             "prompt_logprobs": (_settling_prompt_logprobs, (0, 0)),
             # (its FIRST admission rides in flight; the resume settles)
             "resumed": (_settling_resumed, (1, 1))}


@pytest.mark.ragged_smoke
@pytest.mark.parametrize("kind", list(_SETTLING))
def test_final_chunk_settles_where_the_token_is_needed_at_once(model, kind):
    """A resumed walk, a penalised, a guided and a ``prompt_logprobs``
    request: the next dispatch cannot be built before the token (or the
    state its emit leaves) is on the host, so their final chunk settles as
    it always did — counted as such — and their streams are the legacy
    engine's."""

    def run(ragged):
        eng = _ragged_engine(model, ragged)
        live = _live(eng, n=140)
        before = _paths(eng)
        req = _SETTLING[kind][0](model, eng)
        while not req.finish_reason and not any(r is req
                                                for r in eng.slot_req):
            eng.step()      # ... until its walk has ended
        walked = _paths(eng)
        assert req.generated, "activated with its token on the host"
        _drain(eng)
        _assert_released(eng)
        return (live, req), tuple(b - a for a, b in zip(before, walked))

    ragged, paths = run(1)
    legacy, _ = run(0)
    assert paths == _SETTLING[kind][1], paths
    for r, s in zip(ragged, legacy):
        assert _stream_bytes(r) == _stream_bytes(s)
    assert ragged[1].prompt_logprob_data == legacy[1].prompt_logprob_data
    assert ragged[1].finish_reason in ("length", "stop")


@pytest.mark.ragged_smoke
def test_back_to_back_admissions_under_a_live_batch_book_no_bubble(model):
    """Ten admissions one after the other beside a live stream: each ends
    its walk in flight (``tpu_serve_activations_total{path}``), the device
    always has the next dispatch behind the one that runs, and the host
    bubble counter — which every settle used to feed — does not move."""
    eng = _engine(model, decode_pipeline=1, ragged_attention=1,
                  prefill_chunk=32, max_cache_len=512, decode_horizon=4)
    live = _live(eng, n=400)
    bubble = eng.metrics.decode_bubble_seconds.total()
    drains = _edge_drains()
    reqs = []
    for i in range(10):
        reqs.append(eng.submit(Request(
            prompt_ids=[(7 * i + j) % 60 + 4 for j in range(12 + i)],
            max_tokens=3, temperature=0.9, seed=i, ignore_eos=True)))
        while not reqs[-1].finish_reason:
            eng.step()
            assert eng._inflight is not None
    assert not live.finish_reason, "the batch went idle (vacuous)"
    assert _paths(eng) == (10, 0)
    assert eng.metrics.decode_bubble_seconds.total() == bubble
    assert _edge_drains() == drains
    assert all(len(r.generated) == 3 for r in reqs)
    # a penalised request is the other path, and its settle is a bubble
    pen = _settling_penalised(model, eng)
    while not pen.finish_reason:
        eng.step()
    assert _paths(eng) == (10, 1)
    assert eng.metrics.decode_bubble_seconds.total() > bubble
    eng.cancel(live)
    _drain(eng)
    _assert_released(eng)


@pytest.mark.ragged_smoke
def test_empty_slots_carry_lanes_do_not_grow_under_an_open_pipeline(model):
    """The step programs run every slot, and a lane of the device carry
    grows a step a token whoever holds the slot. The mirrors' upload after
    each activation used to zero the empty slots' lanes; now that
    admissions join from the carry the pipeline stays open for good, so
    the carry itself is told which slots are empty where it is consumed:
    an empty slot's lane never reads more than one dispatch's steps, while
    a stream decodes for hundreds of tokens and requests come and go."""
    import numpy as np

    tok, _, _ = model
    cfg = tiny_qwen3(vocab_size=tok.vocab_size,
                     eos_token_id=tok.eos_token_id, max_seq_len=1024)
    eng = Engine(cfg, model[2], ServingConfig(
        weights_dtype="bf16", model=MODEL, max_decode_slots=6,
        max_cache_len=1024, page_size=32, prefill_buckets=(16, 32),
        dtype="float32", derived_seed=0, decode_pipeline=1,
        ragged_attention=1, prefill_chunk=32, decode_horizon=4))
    live = _live(eng, n=700)
    worst, was_empty, uploads = 0, set(), 0
    for step in range(160):
        if step % 5 == 0:
            eng.submit(Request(prompt_ids=list(_SHORT), max_tokens=6,
                               temperature=0.9, seed=step, ignore_eos=True))
        eng.step()
        uploads += not eng._carry_valid()
        if eng._carry_valid():
            lens = np.asarray(eng._pipe_carry[1])
            empty = {s for s, r in enumerate(eng.slot_req) if r is None}
            # (a slot emptied by THIS step's fetch is zeroed at the next
            # build, and one mid-walk holds its chunk frontier)
            seen = empty & was_empty - {(eng._chunk or {}).get("slot")}
            worst = max([worst] + [int(lens[s]) for s in seen])
            was_empty = empty
    # (slots are free throughout, so a dispatch runs a measured 1 to 4
    # substeps: 160 steps give the stream 160 tokens at the least)
    assert not live.finish_reason and len(live.generated) > 120
    assert _paths(eng)[0] >= 20 and uploads == 0, \
        "the pipeline closed between admissions (test is vacuous)"
    assert worst <= eng.serving.decode_horizon, worst
    eng.cancel(live)
    _drain(eng)
    _assert_released(eng)


def test_activations_counter_renders_on_metrics(model):
    eng = _ragged_engine(model, 1)
    _live(eng)
    eng.submit(Request(**_UNDER_LIVE["greedy"]))
    _drain(eng)
    text = "\n".join(eng.metrics.registry.render().splitlines())
    assert 'tpu_serve_activations_total{path="in_flight"} 1.0' in text


# -- metrics and observability ----------------------------------------------


def test_pipeline_depth_gauge_and_bubble_accounting(model):
    """pipeline_depth rides 0→1→0 across a pipelined run; the sync engine
    accrues host-bubble seconds that the pipelined engine hides."""
    pipe = _engine(model, decode_pipeline=1)
    saw_depth_one = False
    reqs = [pipe.submit(Request(prompt_ids=[3 + i] * 4, max_tokens=24,
                                temperature=0.9, seed=i, ignore_eos=True))
            for i in range(2)]
    for _ in range(20000):
        alive = pipe.step()
        if pipe.metrics.pipeline_depth.value() == 1.0:
            saw_depth_one = True
        if not alive:
            break
    assert saw_depth_one, "pipelined decode never reached depth 1"
    assert all(r.finish_reason == "length" for r in reqs)
    _assert_released(pipe)

    sync = _engine(model, decode_pipeline=0)
    _run_set(sync, [dict(prompt_ids=[3 + i] * 4, max_tokens=24,
                         temperature=0.9, seed=i, ignore_eos=True)
                    for i in range(2)])
    sync_bubble = sync.metrics.decode_bubble_seconds.total()
    pipe_bubble = pipe.metrics.decode_bubble_seconds.total()
    assert sync_bubble > 0.0, \
        "sync decode must account a host bubble between dispatches"
    assert pipe_bubble < sync_bubble, (pipe_bubble, sync_bubble)
    # device-time accounting moved too
    assert sync.metrics.device_busy_seconds.total() > 0.0
    assert pipe.metrics.device_busy_seconds.total() > 0.0


def test_http_healthz_and_metrics_expose_pipeline(model):
    """/healthz reports the knob and the bubble share; /metrics renders both
    new series (R2: registered AND rendered)."""
    tok, cfg, params = model
    state = build_state(
        ServingConfig(weights_dtype="bf16", model=MODEL, max_decode_slots=2,
                      max_cache_len=128, page_size=32,
                      prefill_buckets=(16, 32, 64, 128), dtype="float32",
                      derived_seed=0, decode_pipeline=1),
        model_cfg=cfg, params=params, tokenizer=tok)
    port = next(_PORTS)
    ready, stop = threading.Event(), threading.Event()
    threading.Thread(target=serve,
                     args=(state, "127.0.0.1", port, ready, stop),
                     daemon=True).start()
    assert ready.wait(10)
    try:
        body = json.dumps({"model": MODEL, "prompt": "hi", "max_tokens": 6,
                           "ignore_eos": True}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert health["decode_pipeline"] == 1
        assert "decode_bubble_pct" in health
        # ragged mixed-batch knob + the drain ledger (ISSUE 14)
        assert health["ragged_attention"] == 1
        assert "drain_rate" in health["pipeline"]
        assert "drains_by_reason" in health["pipeline"]

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
            text = r.read().decode()
        assert "tpu_serve_decode_bubble_seconds_total" in text
        assert "tpu_serve_pipeline_depth" in text
        assert "tpu_serve_pipeline_drains_total" in text
        assert "tpu_serve_pipeline_dispatches_total" in text
    finally:
        stop.set()
        time.sleep(0.1)
