"""The fused decode dispatch's substep count is an OPERAND of one program,
and the engine chooses it from what it knows: the whole horizon while no
admission can follow the dispatch, the fewest substeps that keep the device
fed while one can (EnginePrograms._decode_horizon)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aws_k8s_ansible_provisioner_tpu.config import (ServingConfig, tiny_olmoe,
                                                    tiny_qwen3)
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp
from aws_k8s_ansible_provisioner_tpu.serving import flightrec as _flight
from aws_k8s_ansible_provisioner_tpu.serving import programs as pg
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request
from aws_k8s_ansible_provisioner_tpu.serving.guided import grammar_for
from aws_k8s_ansible_provisioner_tpu.utils.tokenizer import ByteTokenizer

B, PPS, PS, N = 3, 4, 16, 8
MODELS = {"qwen3": tiny_qwen3, "olmoe": tiny_olmoe}


# -- (a) one program, the count an operand -----------------------------------


@pytest.fixture(scope="module", params=sorted(MODELS))
def tiny(request):
    cfg = MODELS[request.param]()
    return cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def _decode(cfg, params, n_steps, variant, **kw):
    """One ``decode_steps`` call on fresh operands (slot 1 draws, slot 2 is
    idle); ``variant``: plain / logprobs / penalties."""
    table = jnp.asarray([[1 + s * PPS + p for p in range(PPS)]
                         for s in range(B)], jnp.int32)
    V = cfg.vocab_size
    if variant == "penalties":
        kw.update(penalties=True, counts=jnp.zeros((B, V), jnp.int32),
                  presence=jnp.asarray([0.5, 0.0, 0.0]),
                  frequency=jnp.asarray([0.0, 0.3, 0.0]),
                  repetition=jnp.asarray([1.0, 1.3, 1.0]),
                  prompt_mask=jnp.zeros((B, V), jnp.bool_))
    out = pg.decode_steps(
        cfg, n_steps, params,
        kvp.init_pool(cfg, B * PPS + 1, PS, jnp.float32),
        jnp.asarray([5, 6, 7], jnp.int32), jnp.asarray([0, 3, 0], jnp.int32),
        jax.random.PRNGKey(1), jnp.asarray([0.0, 0.7, 0.0]),
        jnp.asarray([0, 5, 0], jnp.int32), jnp.ones(B, jnp.float32),
        table=table, impl="xla", logprobs=variant == "logprobs",
        seeds=jnp.ones(B, jnp.uint32),
        bias_ids=jnp.full((B, pg.BIAS_K), 2**31 - 1, jnp.int32),
        bias_vals=jnp.zeros((B, pg.BIAS_K), jnp.float32),
        ban_ids=jnp.full((B, pg.BAN_K), 2**31 - 1, jnp.int32),
        ban_until=jnp.zeros(B, jnp.int32),
        live=jnp.asarray([True, True, False]) if cfg.num_experts else None,
        **kw)
    return jax.tree.map(np.asarray, out)


@pytest.mark.parametrize("variant", ["plain", "logprobs", "penalties"])
def test_count_operand_runs_the_static_programs_substeps(tiny, variant):
    """``decode_steps(cfg, 8, ..., steps=n)`` gives the tokens, lengths,
    cache, penalty counts, logprobs and routing summary of the program
    whose loop is static at ``n`` (no ``steps``: what the parent compiled a
    count), for n = 1, 3 and 8 — from ONE compiled variant: the jit cache
    does not grow across the three."""
    cfg, params = tiny
    got = {n: _decode(cfg, params, N, variant, steps=jnp.int32(n))
           for n in (N, 1, 3)}
    size = pg.decode_steps._cache_size()
    got[N] = _decode(cfg, params, N, variant, steps=jnp.int32(N))
    assert pg.decode_steps._cache_size() == size    # one variant, met first
    for n in (1, 3, N):
        cache, cnts, out, tok, lens, moe = got[n]
        wcache, wcnts, wout, wtok, wlens, wmoe = _decode(cfg, params, n,
                                                         variant)
        if variant == "logprobs":
            (out, lps), (wout, wlps) = out, wout
            for have, want in zip(lps, wlps):
                np.testing.assert_allclose(have[:n], want, rtol=1e-5,
                                           atol=1e-5)
                assert not have[n:].any()
        assert (out[:n] == wout).all() and not out[n:].any()
        assert (tok == wtok).all() and (lens == wlens).all()
        assert (lens == np.asarray([0, 3, 0]) + n).all()
        assert (cnts == wcnts).all()
        for leaf in wcache:
            np.testing.assert_allclose(cache[leaf], wcache[leaf], rtol=1e-5,
                                       atol=1e-5)
        if cfg.num_experts:
            np.testing.assert_allclose(moe, wmoe, rtol=1e-6)
        else:
            assert moe is None and wmoe is None


# -- (b) the engine's choice --------------------------------------------------


@pytest.fixture(scope="module")
def served():
    tok = ByteTokenizer()
    cfg = tiny_qwen3(vocab_size=tok.vocab_size, eos_token_id=tok.eos_token_id)
    return tok, cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


@pytest.fixture(autouse=True)
def fresh_flight():
    _flight.reset()
    yield
    _flight.reset()


def _engine(served, short=3, **over):
    """An engine whose short count is scripted (the measured one is
    ``_short_horizon``'s own test)."""
    _, cfg, params = served
    base = dict(weights_dtype="bf16", max_decode_slots=2, max_cache_len=128,
                page_size=32, prefill_buckets=(16, 32), dtype="float32",
                prefix_cache=False, decode_horizon=N)
    base.update(over)
    eng = Engine(cfg, params, ServingConfig(**base))
    eng._short_horizon = lambda: short
    return eng


def _req(max_tokens, start=3, **kw):
    return Request(prompt_ids=[start, 9, 11], max_tokens=max_tokens,
                   ignore_eos=True, **kw)


def _drain(eng, limit=20000):
    for _ in range(limit):
        if not eng.step():
            return
    raise AssertionError("engine failed to quiesce")


def _decodes():
    return [e for e in _flight.get().tail(4096)
            if e["type"] == "dispatch" and e["program"] == "decode_steps"]


def _counts(eng):
    m = eng.metrics
    return (m.decode_dispatches.value(substeps="whole"),
            m.decode_dispatches.value(substeps="short"),
            m.decode_substeps.total())


def test_whole_while_every_slot_is_held_short_while_one_is_free(served):
    """Both slots hold streams whose budgets reach past what is in flight:
    every dispatch runs the whole horizon. One slot free: every dispatch
    runs the short count (an arrival's mixed step can only follow what is
    enqueued)."""
    eng = _engine(served)
    a, b = eng.submit(_req(60)), eng.submit(_req(60, start=20))
    for _ in range(5):
        eng.step()
    recs = _decodes()
    assert recs and all((e["horizon"], e["horizon_why"]) == (N, "whole")
                        for e in recs)
    a.cancelled = True
    for _ in range(4):
        eng.step()
    after = _decodes()[len(recs) + 1:]      # (one was sized before the reap)
    assert after and all((e["horizon"], e["horizon_why"]) == (3, "slot_free")
                         for e in after)
    b.cancelled = True
    _drain(eng)


def test_budget_that_ends_in_flight_makes_the_next_dispatch_short(served):
    """A stream whose ``max_tokens`` ends inside the dispatch IN FLIGHT
    frees its slot by the time the next one starts: that next dispatch is
    short (``budget_ends``) — its predecessor, enqueued while the budget
    still reached past what was in flight, was whole — and dispatches are
    whole again once the slot is refilled."""
    eng = _engine(served)
    # 1 token at activation + 8 + 8: the budget ends with the second dispatch
    a = eng.submit(_req(1 + 2 * N))
    b = eng.submit(_req(200, start=20))
    while not a.finish_reason:
        eng.step()
    recs = _decodes()
    assert [(e["horizon"], e["horizon_why"]) for e in recs[:2]] \
        == [(N, "whole")] * 2
    # the dispatch enqueued behind the one that ends a's budget
    assert eng._inflight["drec"]["horizon_why"] == "budget_ends"
    assert eng._inflight["horizon"] == 3
    c = eng.submit(_req(200, start=40))     # the caller comes back
    while c.t_first_token == 0.0:
        eng.step()
    n = len(_decodes())
    for _ in range(3):
        eng.step()
    assert [(e["horizon"], e["horizon_why"]) for e in _decodes()[n + 1:]] \
        == [(N, "whole")] * 2
    b.cancelled = c.cancelled = True
    _drain(eng)
    assert len(a.generated) == 1 + 2 * N


def test_cache_window_is_a_budget_too(served):
    """The stream that runs into ``max_cache_len`` ends like one that runs
    out of ``max_tokens``: the dispatch behind the one that ends it is
    short."""
    eng = _engine(served, max_cache_len=32, page_size=16,
                  prefill_buckets=(16,))
    a = eng.submit(_req(500))
    b = eng.submit(_req(500, start=20))
    _drain(eng)
    assert a.finish_reason == b.finish_reason == "length"
    whys = [e["horizon_why"] for e in _decodes()]
    assert whys[0] == "whole" and whys[-1] == "budget_ends"


@pytest.mark.parametrize("case", ["fair", "draft", "guided", "chunk-walk",
                                  "cap-below-short"])
def test_forced_counts_are_values_of_the_operand(served, case):
    """Every path that forces a count hands it to the ONE program:
    ``fair_horizon`` the whole horizon with a slot free, a draft model's
    plain dispatch at most ``spec_k + 1``, a pure-guided batch 1, the
    non-ragged chunk walk's interleaved decode 1, and ``decode_horizon``
    caps the short count."""
    tok = served[0]
    if case == "fair":
        eng = _engine(served)
        eng.submit(_req(40))
        eng.step()
        eng._do_decode(fair_horizon=True)
        want = (N, "whole")
    elif case == "draft":
        _, cfg, params = served
        eng = Engine(cfg, params, ServingConfig(
            weights_dtype="bf16", max_decode_slots=1, max_cache_len=128,
            page_size=32, prefill_buckets=(16, 32), dtype="float32",
            prefix_cache=False, decode_horizon=N, spec_decode=True,
            spec_k=2, spec_method="draft"), draft=(cfg, params))
        eng._propose_drafts = lambda active: None   # (falls back to plain)
        eng.submit(_req(40))
        eng.step()
        eng.step()
        want = (3, "capped")
    elif case == "guided":
        eng = _engine(served, short=5)
        g = grammar_for(tok, {"type": "json_object"}, [tok.eos_token_id])
        eng.generate(tok.encode("json:"), guided=g, max_tokens=30)
        eng.step()
        eng.step()
        want = (1, "capped")
    elif case == "chunk-walk":
        eng = _engine(served, short=5, ragged_attention=0, prefill_chunk=16)
        eng.submit(_req(40))
        eng.step()
        eng.submit(Request(prompt_ids=list(range(3, 43)), max_tokens=4,
                           ignore_eos=True))
        while eng._chunk is None:
            eng.step()
        n = len(_decodes())
        while len(_decodes()) == n:
            eng.step()
        assert eng._chunk is not None
        want = (1, "capped")
    else:
        eng = _engine(served, short=5, decode_horizon=2)
        eng.submit(_req(40))
        eng.step()
        eng.step()
        want = (2, "slot_free")
    size = pg.decode_steps._cache_size()
    rec = eng._inflight["drec"] if eng._inflight is not None \
        else _decodes()[-1]
    assert (rec["horizon"], rec["horizon_why"]) == want
    for r in eng.slot_req:
        if r is not None:
            r.cancelled = True
    _drain(eng)
    assert pg.decode_steps._cache_size() == size


# -- (c) the record and the counters ------------------------------------------


def test_record_horizon_is_the_substeps_run_and_the_counters_add_up(served):
    """A stream of 1 + 8 + 3 + ... tokens: every decode record's
    ``horizon`` is what the dispatch RAN — the tokens it emitted a live
    stream, the pages its rows walked — ``horizon_why`` is one of the
    rule's reasons, and ``tpu_serve_decode_dispatches_total`` /
    ``tpu_serve_decode_substeps_total`` sum the records."""
    eng = _engine(served)
    a = eng.submit(_req(1 + N + 3 + 3 + 2))
    b = eng.submit(_req(1 + N))
    _drain(eng)
    recs = _decodes()
    assert {e["horizon_why"] for e in recs} \
        <= {"whole", "slot_free", "budget_ends", "waiting", "capped"}
    assert [e["horizon"] for e in recs][:2] == [N, 3]
    # the first ran both streams for 8 substeps; b's budget ended in it
    assert recs[0]["emitted"] == 2 * N and recs[1]["emitted"] == 3
    assert recs[1]["horizon_why"] == "budget_ends"
    # (the last dispatch runs 3 substeps for a stream with 2 tokens left)
    assert sum(e["emitted"] for e in recs) == N + 3 + 3 + 2 + N
    # the pages the rows hold, summed over the substeps RUN: both rows sit
    # in their first page throughout
    assert all(e["attn_pages_live"] == 2 * e["horizon"] for e in recs)
    whole, short, substeps = _counts(eng)
    assert whole == sum(e["horizon"] == N for e in recs) == 1
    assert short == len(recs) - 1
    assert substeps == sum(e["horizon"] for e in recs)
    assert len(a.generated) == 1 + N + 3 + 3 + 2 and len(b.generated) == 1 + N
    text = eng.metrics.registry.render()
    assert 'tpu_serve_decode_dispatches_total{substeps="short"}' in text
    assert "tpu_serve_decode_substeps_total " in text


# -- (d) nothing the rule picks compiles --------------------------------------


@pytest.mark.parametrize("model", sorted(MODELS))
def test_an_arrival_racing_the_queue_read_compiles_nothing(model):
    """A request that waits with a slot free when the decode dispatch is
    sized (the race between the admission pass and the queue read, and a
    page-starved head) gets the SHORT count of the same program the whole
    horizon runs: ``waiting`` in the record, and the jit cache is what the
    first dispatch left. (The parent picked a one-step program here, which
    no outside warm-up reached: a compile inside a measured window.)"""
    cfg = MODELS[model]()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = Engine(cfg, params, ServingConfig(
        weights_dtype="bf16", max_decode_slots=2, max_cache_len=128,
        page_size=32, prefill_buckets=(16, 32), dtype="float32",
        prefix_cache=False, decode_horizon=N))
    eng.submit(_req(60))
    for _ in range(3):
        eng.step()
    assert eng._inflight["horizon"] == 1    # nothing measured yet: one
    size = pg.decode_steps._cache_size()
    for short in (1, 2, 5, N, 3 * N):
        eng._short_horizon = lambda: short
        eng._do_decode(prefill_possible=True)
        rec = eng._inflight["drec"]
        assert (rec["horizon"], rec["horizon_why"]) \
            == (min(short, N), "waiting")
    assert pg.decode_steps._cache_size() == size
    eng.slot_req[eng._active_slots()[0]].cancelled = True
    _drain(eng)


# -- the two measurements the short count is made of -------------------------


@pytest.mark.parametrize("host_ms,step_ms,want", [
    (0.0, 6.0, 1), (16.0, 0.0, 1), (16.1, 6.45, 3), (9.0, 14.3, 1),
    (25.7, 16.0, 2), (51.4, 22.0, 3), (500.0, 6.0, 84)])
def test_short_horizon_covers_the_hosts_work(served, host_ms, step_ms, want):
    """The fewest substeps whose device time covers the host's seconds a
    dispatch; 1 while either is unmeasured; ``_decode_horizon`` caps it."""
    eng = _engine(served)
    del eng._short_horizon                  # the engine's own
    eng._host_s = host_ms / 1e3
    if step_ms:
        eng._dispatch_s["decode_steps"] = step_ms / 1e3
    assert eng._short_horizon() == want
    assert eng._decode_horizon(None, [0], False) \
        == (min(want, N), "slot_free")


def test_host_seconds_leave_out_the_wait_and_count_its_share(served):
    """``_note_host``: from the last fetch's return to the enqueue's end,
    less what ``_await_arrival`` waited — but never under what follows the
    wait scaled to the share of a dispatch the wait leaves. The admission
    of a walk's first chunk behind a short decode dispatch SETS the
    estimate, any other turn-around only raises it, and an enqueue that
    compiled measures nothing."""
    import time

    eng = _engine(served)

    def note(since_fetch, waited, since_await, behind=None,
             program="decode_steps", first_use=False):
        now = time.monotonic()
        eng._t_fetched, eng._waited_s = now - since_fetch, waited
        eng._t_awaited = now - since_await
        eng._inflight = None if behind is None \
            else {"drec": {"horizon_why": behind} if behind != "mixed" else {}}
        eng._note_host({"first_use": first_use, "program": program})
        assert eng._t_fetched == 0.0
        eng._inflight = None
        return eng._host_s

    assert 0.020 <= note(0.050, 0.030, 0.004) < 0.030   # 50 ms less 30
    # 20 ms of work after the wait, in the half the wait leaves
    assert note(0.050, 0.020, 0.020) >= 0.020 / (1 - pg.AWAIT_SHARE)
    # an admission behind a short dispatch SETS (a stamp from before the
    # fetch: this step passed no wait point)
    was = note(0.030, 0.0, 5.0, "budget_ends", "mixed_step")
    assert 0.030 <= was < 0.035
    for behind, program in (("whole", "decode_steps"), ("mixed", "mixed_step"),
                            ("slot_free", "decode_steps"),
                            ("whole", "mixed_step"), (None, "decode_steps")):
        assert note(0.012, 0.0, 5.0, behind, program) == was   # lower: kept
    assert 0.060 <= note(0.060, 0.0, 5.0, "whole") < 0.065     # higher: up
    assert 0.012 <= note(0.012, 0.0, 5.0, "slot_free", "mixed_step") < 0.017
    was = eng._host_s
    assert note(30.0, 0.0, 5.0, first_use=True) == was


def test_dispatch_seconds_are_kept_a_substep(served):
    """``_dispatch_s`` holds a program's device seconds A SUBSTEP — so the
    expectation of a dispatch in flight is that times the substeps it runs,
    whatever count the last one ran: taken where the host waited for the
    dispatch, only lowered where it found it done, untouched by one that
    compiled."""
    eng = _engine(served)

    def close(steps, took, waited_from, **given):
        rec = eng._dispatch_open("decode_steps", "decode", [0],
                                 horizon=steps, horizon_why="whole", **given)
        rec["first_use"] = given.get("first_use", False)
        t0 = max(rec["t_enqueue"], eng._busy_watermark)
        eng._dispatch_close(rec, t0 + took, steps=steps,
                            t_wait=t0 + waited_from)
        return eng._dispatch_s.get("decode_steps")

    assert close(8, 30.0, 0.0, first_use=True) is None
    assert close(8, 0.4, 0.1) == pytest.approx(0.05)
    assert close(2, 0.12, 0.01) == pytest.approx(0.06)
    assert close(4, 0.8, 0.79) == pytest.approx(0.06)     # found done: <=
    assert close(4, 0.1, 0.099) == pytest.approx(0.025)
    assert eng._t_fetched > 0 and eng._waited_s == 0.0
    assert _counts(eng) == (2.0, 3.0, 8 + 8 + 2 + 4 + 4)
