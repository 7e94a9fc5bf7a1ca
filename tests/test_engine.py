"""Engine correctness: cached decode == full-context recompute, batching, stops.

This is the in-repo analogue of the reference's only functional gate — the live
completion POST (`llm-d-test.yaml:61-78`) — but as a deterministic offline test:
greedy generation through the continuous-batching engine (prefill into cache +
per-token decode) must equal token-by-token full-forward recomputation with no
cache at all. Any KV-cache write/mask/position bug breaks this equality.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_opt, tiny_phi, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params, model_forward
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request


from aws_k8s_ansible_provisioner_tpu.models.layers import causal_attend

_PAD = 64


def _padded_last_logits(params, cfg, ids):
    """Full-context forward at a fixed padded width (one compile for all steps)."""
    n = len(ids)
    tokens = np.zeros((1, _PAD), np.int32)
    tokens[0, :n] = ids
    pos = jnp.arange(_PAD, dtype=jnp.int32)[None]
    seq = jnp.asarray([n], jnp.int32)

    def attend(q, k, v, cache):
        return causal_attend(q, k, v, seq_lens=seq), cache

    logits, _ = model_forward(params, cfg, jnp.asarray(tokens), pos,
                              attend=attend)
    return logits[0, n - 1]


def naive_greedy(params, cfg, prompt, n):
    """Reference decode: full recompute each step, no KV cache."""
    ids = list(prompt)
    out = []
    for _ in range(n):
        nxt = int(jnp.argmax(_padded_last_logits(params, cfg, ids)))
        out.append(nxt)
        ids.append(nxt)
    return out


@pytest.fixture(scope="module", params=["qwen3", "phi", "opt"])
def setup(request):
    cfg = {"qwen3": tiny_qwen3, "phi": tiny_phi, "opt": tiny_opt}[request.param]()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    serving = ServingConfig(weights_dtype="bf16", max_decode_slots=4, max_cache_len=64,
                            prefill_buckets=(8, 16, 32), dtype="float32")
    return cfg, params, serving


def run_engine(engine, reqs):
    for r in reqs:
        engine.submit(r)
    for _ in range(10000):
        if not engine.step():
            break
    return reqs


def test_engine_matches_naive_greedy(setup):
    cfg, params, serving = setup
    engine = Engine(cfg, params, serving)
    rng = np.random.default_rng(0)
    prompt = rng.integers(2, cfg.vocab_size, 11).tolist()

    req = Request(prompt_ids=list(prompt), max_tokens=12, ignore_eos=True)
    run_engine(engine, [req])
    expected = naive_greedy(params, cfg, prompt, 12)
    assert req.generated == expected
    assert req.finish_reason == "length"


def test_concurrent_requests_match_sequential(setup):
    """3 interleaved requests (continuous batching) == each run alone."""
    cfg, params, serving = setup
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist() for n in (3, 9, 17)]

    engine = Engine(cfg, params, serving)
    reqs = [Request(prompt_ids=list(p), max_tokens=8, ignore_eos=True)
            for p in prompts]
    run_engine(engine, reqs)

    for p, r in zip(prompts, reqs):
        assert r.generated == naive_greedy(params, cfg, p, 8), \
            f"batched output diverged for prompt len {len(p)}"


def test_eos_stops_generation(setup):
    cfg, params, serving = setup
    engine = Engine(cfg, params, serving)
    rng = np.random.default_rng(2)
    prompt = rng.integers(2, cfg.vocab_size, 5).tolist()
    expected = naive_greedy(params, cfg, prompt, 16)
    # pick an eos whose FIRST occurrence in the expected stream is known
    # (greedy decode of a random tiny model can repeat tokens)
    stop_at = next((i for i in range(1, len(expected))
                    if expected[i] not in expected[:i]), None)
    if stop_at is None:
        pytest.skip("degenerate stream: all tokens identical")
    eos = expected[stop_at]

    engine2 = Engine(cfg, params, serving, eos_token_id=eos)
    req = Request(prompt_ids=list(prompt), max_tokens=16)
    run_engine(engine2, [req])
    assert req.generated == expected[:stop_at + 1]
    assert req.finish_reason == "stop"


def test_extra_eos_ids_stop_generation(setup):
    """Llama-3 Instruct ships a LIST of eos ids; any member must stop the
    stream (review r2: only eos_token_id[0] was honored, so chat turns never
    stopped at <|eot_id|>)."""
    cfg, params, serving = setup
    rng = np.random.default_rng(2)
    prompt = rng.integers(2, cfg.vocab_size, 5).tolist()
    expected = naive_greedy(params, cfg, prompt, 16)
    stop_at = next((i for i in range(1, len(expected))
                    if expected[i] not in expected[:i]), None)
    if stop_at is None:
        pytest.skip("degenerate stream: all tokens identical")
    # the stopping id arrives via extra_eos_token_ids, NOT the primary eos —
    # whose placeholder must not itself appear in the expected stream (the
    # phi family's greedy stream opens with vocab_size - 1, which made the
    # old hard-coded placeholder a REAL stop at position 0)
    placeholder = next(v for v in range(cfg.vocab_size - 1, -1, -1)
                       if v not in expected)
    cfg2 = cfg.scaled(eos_token_id=placeholder,
                      extra_eos_token_ids=(expected[stop_at],))
    engine = Engine(cfg2, params, serving)
    req = Request(prompt_ids=list(prompt), max_tokens=16)
    run_engine(engine, [req])
    assert req.generated == expected[:stop_at + 1]
    assert req.finish_reason == "stop"


def test_more_requests_than_slots(setup):
    """Queueing: 6 requests through 4 slots all complete correctly."""
    cfg, params, serving = setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, cfg.vocab_size, 4 + i).tolist() for i in range(6)]
    engine = Engine(cfg, params, serving)
    reqs = [Request(prompt_ids=list(p), max_tokens=5, ignore_eos=True)
            for p in prompts]
    run_engine(engine, reqs)
    for p, r in zip(prompts, reqs):
        assert r.generated == naive_greedy(params, cfg, p, 5)


def test_streaming_and_wait(setup):
    cfg, params, serving = setup
    engine = Engine(cfg, params, serving)
    prompt = [5, 6, 7]
    req = Request(prompt_ids=prompt, max_tokens=4, ignore_eos=True, stream=True)
    engine.submit(req)
    while engine.step():
        pass
    streamed = []
    while True:
        item = req.out_queue.get_nowait()
        if item is None:
            break
        streamed.extend(item)       # an item: one dispatch's ids
    assert streamed == req.generated
    assert len(streamed) == 4


def _queue_items(req):
    """Everything in a stream's queue: (items ahead of the sentinel, number
    of sentinels). Nothing may follow a sentinel."""
    import queue

    items, nones = [], 0
    while True:
        try:
            it = req.out_queue.get_nowait()
        except queue.Empty:
            return items, nones
        if it is None:
            nones += 1
        else:
            assert nones == 0, "an item after the sentinel"
            items.append(it)


def _end_by_length(engine, req):
    run_engine(engine, [])


def _end_by_cancel(engine, req):
    engine.cancel(req)
    run_engine(engine, [])


def _end_by_deadline(engine, req):
    import time

    req.t_deadline = time.monotonic() - 1.0
    run_engine(engine, [])


def _end_by_fail_all(engine, req):
    engine._fail_all("boom")


def _end_by_error_inside_the_emit_loop(engine, req):
    """A step that dies with tokens of its dispatch already recorded: they
    reach the stream before the sentinel run_forever's failover puts."""
    real, calls = engine._emit, {"n": 0}

    def emit(slot, token, lp=None):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("boom")
        return real(slot, token, lp)

    engine._emit = emit
    with pytest.raises(RuntimeError):
        for _ in range(100):
            engine.step()
    assert req.pending == []        # flushed on the way out of the phase
    engine._fail_all("boom")


@pytest.mark.parametrize("horizon", [1, 8])
@pytest.mark.parametrize("end,reason", [
    (_end_by_length, "length"), (_end_by_cancel, "cancelled"),
    (_end_by_deadline, "timeout"), (_end_by_fail_all, "error"),
    (_end_by_error_inside_the_emit_loop, "error")],
    ids=["length", "cancel", "deadline", "fail_all", "emit_error"])
def test_stream_gets_its_tokens_then_one_sentinel(setup, end, reason,
                                                  horizon):
    """A stream's queue carries ITEMS — the ids one dispatch gave it — and
    ends, whatever ends the request, with every generated token delivered
    and exactly one sentinel behind them."""
    cfg, params, serving = setup
    # (one slot: the stream holds every slot, so its dispatches run the
    # whole horizon — with a slot free they run a measured few)
    engine = Engine(cfg, params, dataclasses.replace(
        serving, decode_horizon=horizon, max_decode_slots=1))
    req = Request(prompt_ids=[5, 6, 7], max_tokens=21, ignore_eos=True,
                  stream=True)
    engine.submit(req)
    assert engine.step()                    # the prefill, and activation
    # the first token is an item of its own, in the queue when the step
    # that produced it returns
    assert list(req.out_queue.queue) == [[req.generated[0]]]
    # the pipeline fetches a dispatch a step after it was enqueued: two
    # horizons are in the queue and a third is in flight when the end comes
    for _ in range(3):
        engine.step()
        assert req.pending == []            # nothing waits for a later step
    end(engine, req)
    assert req.finish_reason == reason
    items, nones = _queue_items(req)
    assert nones == 1
    assert [t for it in items for t in it] == req.generated
    assert req.pending == []
    assert items[0] == [req.generated[0]]
    assert all(1 <= len(it) <= horizon for it in items)
    if horizon == 8:
        # a dispatch of 8 substeps puts ONE item of 8 ids
        assert max(len(it) for it in items) == 8
    assert engine.metrics.stream_items.total() == len(items)
    assert all(r is None for r in engine.slot_req)


def test_close_stream_puts_what_is_pending_first(setup):
    """The one way a queue ends (_close_stream: cancel, deadline, _fail_all
    and the chunk-error arms all go through it): pending ids, then None."""
    cfg, params, serving = setup
    engine = Engine(cfg, params, serving)
    req = Request(prompt_ids=[5, 6, 7], stream=True)
    req.pending = [11, 12]
    engine._close_stream(req)
    assert list(req.out_queue.queue) == [[11, 12], None]
    assert req.pending == []


def test_sampling_reproducible_and_bounded(setup):
    """Temperature sampling stays in-vocab and is deterministic per engine seed."""
    cfg, params, serving = setup
    prompt = [5, 6, 7, 8]

    outs = []
    # pin derived_seed: unseeded sampling is reproducible only under an
    # explicit engine seed (the production default draws from os.urandom so
    # restarts/replicas diverge — ADVICE r3)
    pinned = dataclasses.replace(serving, derived_seed=0)
    for _ in range(2):
        engine = Engine(cfg, params, pinned)
        req = Request(prompt_ids=list(prompt), max_tokens=10, temperature=0.9,
                      top_k=8, top_p=0.95, ignore_eos=True)
        run_engine(engine, [req])
        assert all(0 <= t < cfg.vocab_size for t in req.generated)
        outs.append(req.generated)
    assert outs[0] == outs[1]


def test_unseeded_engines_diverge_across_restarts(setup):
    """Production default (derived_seed=None): two engine instances must NOT
    replay the identical unseeded sample sequence — vLLM/OpenAI
    nondeterministic behavior (ADVICE r3)."""
    cfg, params, serving = setup
    prompt = [7, 3, 11]
    outs = []
    for _ in range(2):
        engine = Engine(cfg, params, serving)
        req = Request(prompt_ids=list(prompt), max_tokens=12, temperature=0.9,
                      top_k=8, top_p=0.95, ignore_eos=True)
        run_engine(engine, [req])
        outs.append(req.generated)
    # 12 sampled tokens colliding across independent 64-bit seeds is ~never
    assert outs[0] != outs[1]


def test_long_prompt_rejected_not_truncated(setup):
    """Oversized prompt raises ContextLengthExceeded (VERDICT r1: silent
    tail-truncation served an answer to a different question)."""
    from aws_k8s_ansible_provisioner_tpu.serving.engine import (
        ContextLengthExceeded)

    cfg, params, serving = setup
    engine = Engine(cfg, params, serving)
    prompt = list(np.random.default_rng(4).integers(2, cfg.vocab_size, 500))
    req = Request(prompt_ids=[int(x) for x in prompt], max_tokens=4,
                  ignore_eos=True)
    with pytest.raises(ContextLengthExceeded) as ei:
        engine.submit(req)
    assert ei.value.n_prompt == 500
    assert ei.value.limit == engine.prompt_limit
    # a fitting prompt still serves
    ok = Request(prompt_ids=[int(x) for x in prompt[:engine.prompt_limit]],
                 max_tokens=4, ignore_eos=True)
    engine.submit(ok)
    run_engine(engine, [])
    assert len(ok.generated) == 4


def test_cancel_frees_slot(setup):
    cfg, params, serving = setup
    engine = Engine(cfg, params, serving)
    req = Request(prompt_ids=[5, 6, 7], max_tokens=1000, ignore_eos=True,
                  stream=True)
    engine.submit(req)
    for _ in range(5):
        engine.step()
    assert any(r is not None for r in engine.slot_req)
    engine.cancel(req)
    engine.step()
    assert all(r is None for r in engine.slot_req)
    assert req.finish_reason == "cancelled"
    # sentinel delivered
    items = []
    while True:
        it = req.out_queue.get_nowait()
        if it is None:
            break
        items.extend(it)
    assert items == req.generated


def test_engine_error_fails_requests_not_loop(setup):
    """A poisoned step must fail in-flight requests loudly, then keep serving."""
    import threading

    cfg, params, serving = setup
    engine = Engine(cfg, params, serving)
    real_step = engine.step
    calls = {"n": 0}

    def poisoned_step():
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("boom")
        return real_step()

    engine.step = poisoned_step
    stop = threading.Event()
    t = threading.Thread(target=engine.run_forever, args=(stop,), daemon=True)
    t.start()
    bad = Request(prompt_ids=[1, 2, 3], max_tokens=50, ignore_eos=True)
    engine.submit(bad)
    bad.wait(timeout=30)
    assert bad.finish_reason == "error"
    assert "boom" in engine.last_error
    # engine still alive: a new request completes
    ok = Request(prompt_ids=[1, 2, 3], max_tokens=3, ignore_eos=True)
    engine.submit(ok)
    ok.wait(timeout=60)
    assert len(ok.generated) == 3
    stop.set()


def test_max_tokens_clamped_to_cache_budget(setup):
    cfg, params, serving = setup
    engine = Engine(cfg, params, serving)
    req = Request(prompt_ids=[1] * 10, max_tokens=10_000, ignore_eos=True)
    engine.submit(req)
    # prompt kept intact; max_tokens clamped to what the slot can hold
    assert len(req.prompt_ids) == 10
    assert req.max_tokens == serving.max_cache_len - 10 - 1
    run_engine(engine, [])
    assert req.finish_reason == "length"


def test_prefill_failure_releases_scheduler_slot(setup):
    """A prefill exception must release the scheduler-assigned slot and notify
    the client (review finding: capacity leaked and waiters hung)."""
    cfg, params, serving = setup
    engine = Engine(cfg, params, serving)
    orig = engine._do_prefill
    boom = {"armed": True}

    def bad_prefill(req, slot):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("prefill boom")
        return orig(req, slot)

    engine._do_prefill = bad_prefill
    r1 = Request(prompt_ids=[1, 2], max_tokens=2, ignore_eos=True)
    engine.submit(r1)
    try:
        engine.step()
        raise AssertionError("expected RuntimeError")
    except RuntimeError:
        pass
    assert r1.finish_reason == "error"
    assert r1.out_queue.get(timeout=5) is None
    assert engine.sched.stats().active_slots == 0  # slot released
    # capacity intact: a new request completes normally
    r2 = Request(prompt_ids=[1, 2], max_tokens=2, ignore_eos=True)
    engine.submit(r2)
    while engine.pending or any(s is not None for s in engine.slot_req):
        engine.step()
    assert len(r2.generated) == 2


def test_awkward_cache_len_rounded_for_kernel(setup):
    cfg, params, serving = setup
    import dataclasses
    # give the model enough position range that only the rounding applies
    cfg = cfg.scaled(max_seq_len=2048)
    odd = dataclasses.replace(serving, max_cache_len=509)
    engine = Engine(cfg, params, odd)
    assert engine.max_len == 512


def test_stop_token_ids_and_min_tokens(setup):
    """vLLM stop_token_ids: per-request token-level stops; min_tokens defers
    ALL stops until that many tokens generated."""
    cfg, params, serving = setup
    eng = Engine(cfg, params, serving)

    def run(**kw):
        # seeded sampling: diverse tokens (greedy on random weights tends to
        # repeat one token, which would make the stop point ambiguous) and
        # deterministic across the three runs
        r = eng.submit(Request(prompt_ids=[5, 9, 2], max_tokens=6,
                               ignore_eos=True, temperature=1.2, seed=123,
                               **kw))
        while (any(s is not None for s in eng.slot_req) or eng.pending
               or eng._chunk is not None):
            eng.step()
        return r

    base = run()
    assert len(base.generated) == 6
    # a stop token whose FIRST occurrence is past position 0, so the
    # truncation point is unambiguous even with repeated tokens
    idx = next((i for i, t in enumerate(base.generated)
                if i > 0 and t not in base.generated[:i]), None)
    if idx is None:
        pytest.skip("degenerate stream: every token repeats position 0")
    stop_tok = base.generated[idx]
    stopped = run(stop_token_ids=(stop_tok,))
    # ignore_eos does NOT disable per-request stop_token_ids (vLLM semantics)
    assert stopped.finish_reason == "stop"
    assert stopped.generated == base.generated[:idx + 1]
    # min_tokens MASKS the stop token from sampling (vLLM semantics): it is
    # never produced while suppressed — the stream DIVERGES at the banned
    # position instead of carrying a dead stop token — and generation runs
    # to the budget
    deferred = run(stop_token_ids=(stop_tok,), min_tokens=6)
    assert len(deferred.generated) == 6
    assert deferred.finish_reason == "length"
    assert stop_tok not in deferred.generated
    assert deferred.generated[:idx] == base.generated[:idx]


@pytest.mark.parametrize("gives_back", ["finish", "preempt"])
def test_a_returned_slot_never_holds_the_samplers_gate_open(setup, gives_back):
    """The sampler's gate (ops/sampling.sample: the candidates run only if
    some row's temperature is above zero) reads EVERY slot's row, idle ones
    too. So a slot given back — finished, or preempted — must read zero
    again: the greedy requests that follow a seeded sampled one, the slot it
    held among theirs, dispatch with ``sample_rows == 0`` and count on the
    ``greedy`` path. And the gate keeps the seed contract: the sampled
    request beside greedy ones draws what it draws served alone."""
    from aws_k8s_ansible_provisioner_tpu.serving import flightrec

    cfg, params, serving = setup
    drawn = dict(prompt_ids=[5, 9, 2, 11], max_tokens=10, temperature=0.8,
                 top_p=0.9, seed=7, ignore_eos=True)
    alone = run_engine(Engine(cfg, params, serving),
                       [Request(**drawn)])[0].generated

    eng = Engine(cfg, params, serving)
    seen, orig = [], flightrec.record

    def tap(*a, **rec):
        if a[0] == "dispatch":
            seen.append(dict(rec))
        return orig(*a, **rec)

    def greedy(n):
        return [Request(prompt_ids=[3 + i, 7, 8], max_tokens=6,
                        ignore_eos=True) for i in range(n)]

    path = eng.metrics.sample_dispatches
    flightrec.record = tap
    try:
        mixed = [Request(**drawn)] + greedy(2)
        for r in mixed:
            eng.submit(r)
        while len(mixed[0].generated) < 4:
            assert eng.step()
        slot = next(s for s, rq in enumerate(eng.slot_req) if rq is mixed[0])
        assert eng.temps[slot] > 0
        assert any(r["sample_rows"] == 1 for r in seen
                   if r["kind"] == "decode")
        if gives_back == "preempt":
            eng._preempt(slot)
            assert eng.temps[slot] == 0.0
        run_engine(eng, [])
        assert mixed[0].generated == alone
        assert not eng.temps.any()
        drew = path.value(program="decode_steps", path="candidates")
        assert drew > 0
        del seen[:]
        reused = False
        for r in greedy(serving.max_decode_slots):
            eng.submit(r)
        while eng.step():
            reused |= eng.slot_req[slot] is not None
    finally:
        flightrec.record = orig
    assert reused and seen and all(r["sample_rows"] == 0 for r in seen)
    assert path.value(program="decode_steps", path="candidates") == drew
    assert sum(path.value(program=p, path="greedy")
               for p in {r["program"] for r in seen}) >= len(seen)
    assert ('tpu_serve_sample_dispatches_total{path="greedy",'
            'program="decode_steps"}') in eng.metrics.registry.render()
