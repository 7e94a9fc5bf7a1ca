"""Automatic prefix caching: K/V reuse across requests sharing a prompt
prefix, at page granularity.

The vLLM feature of the same name (inside the reference's serving pods): a
prompt whose leading WHOLE pages hash-match pages still in the pool shares
them (refcounted, no copy) and prefills only its suffix through the chunk
program. Every test is token-parity against a prefix-cache-disabled engine —
reuse must be invisible in the output stream. (Sharing without copying, the
preemption-resume hit and the follow-up-turn hit on generated pages are
tests/test_paged_engine.py's; adapters never sharing is tests/test_lora.py's.)
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request

PS = 8


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    serving = ServingConfig(weights_dtype="bf16", max_decode_slots=4,
                            max_cache_len=128, page_size=PS,
                            prefill_buckets=(16, 64), dtype="float32")
    return cfg, params, serving


def _drain(engine):
    for _ in range(10000):
        if not engine.step():
            break


def _run(engine, prompts, max_tokens=6):
    reqs = [Request(prompt_ids=list(p), max_tokens=max_tokens,
                    ignore_eos=True) for p in prompts]
    for r in reqs:
        engine.submit(r)
    _drain(engine)
    return [r.generated for r in reqs]


def _expected(cfg, params, serving, schedule, max_tokens=6):
    """Reference outputs from a prefix-cache-disabled engine."""
    off = dataclasses.replace(serving, prefix_cache=False)
    engine = Engine(cfg, params, off)
    out = []
    for group in schedule:
        out.extend(_run(engine, group, max_tokens))
    return out


def test_prefix_hit_token_parity_and_counters(setup):
    """B shares a 24-token prefix (3 whole pages) with finished request A: B
    must reuse it (hit counter, reused tokens) and still produce exactly the
    no-reuse tokens."""
    cfg, params, serving = setup
    rng = np.random.default_rng(0)
    shared = rng.integers(2, cfg.vocab_size, 24).tolist()
    a = shared + rng.integers(2, cfg.vocab_size, 6).tolist()
    b = shared + rng.integers(2, cfg.vocab_size, 9).tolist()

    want = _expected(cfg, params, serving, [[a], [b]])

    engine = Engine(cfg, params, serving)
    got_a = _run(engine, [a])
    got_b = _run(engine, [b])
    assert got_a + got_b == want
    assert engine.metrics.prefix_cache_hits.total() == 1
    assert engine.metrics.prefix_tokens_reused.total() == 24


def test_prefix_hit_from_active_slot(setup):
    """The source may still be decoding — a prompt's whole pages are indexed
    when its prefill lands and are immutable from then on, so an in-flight
    request's pages are a valid prefix source (shared, refcount 2)."""
    cfg, params, serving = setup
    rng = np.random.default_rng(1)
    shared = rng.integers(2, cfg.vocab_size, 20).tolist()
    a = shared + rng.integers(2, cfg.vocab_size, 4).tolist()
    b = shared + rng.integers(2, cfg.vocab_size, 7).tolist()

    off = dataclasses.replace(serving, prefix_cache=False)
    ref = Engine(cfg, params, off)
    ra = ref.submit(Request(prompt_ids=list(a), max_tokens=10,
                            ignore_eos=True))
    ref.step()   # prefill a
    rb = ref.submit(Request(prompt_ids=list(b), max_tokens=10,
                            ignore_eos=True))
    _drain(ref)

    engine = Engine(cfg, params, serving)
    ga = engine.submit(Request(prompt_ids=list(a), max_tokens=10,
                               ignore_eos=True))
    engine.step()   # prefill a — a's pages are now a live prefix source
    gb = engine.submit(Request(prompt_ids=list(b), max_tokens=10,
                               ignore_eos=True))
    _drain(engine)
    assert [ga.generated, gb.generated] == [ra.generated, rb.generated]
    assert engine.metrics.prefix_cache_hits.total() == 1
    assert engine.metrics.prefix_tokens_reused.total() == 2 * PS


def test_prefix_survives_interleaved_decodes(setup):
    """After A finishes, OTHER requests keep decoding (every decode dispatch
    writes a garbage row for every idle slot) before B reuses A's pages —
    the released pages must not be corrupted (an idle slot's table points
    at the scratch page)."""
    cfg, params, serving = setup
    rng = np.random.default_rng(2)
    shared = rng.integers(2, cfg.vocab_size, 16).tolist()
    a = shared + rng.integers(2, cfg.vocab_size, 3).tolist()
    c = rng.integers(2, cfg.vocab_size, 5).tolist()   # unrelated, long decode
    b = shared + rng.integers(2, cfg.vocab_size, 5).tolist()

    want = _expected(cfg, params, serving, [[a], [c], [b]], max_tokens=8)

    engine = Engine(cfg, params, serving)
    got_a = _run(engine, [a], max_tokens=8)
    got_c = _run(engine, [c], max_tokens=8)   # 8 decode steps after A freed
    got_b = _run(engine, [b], max_tokens=8)
    assert got_a + got_c + got_b == want
    assert engine.metrics.prefix_cache_hits.total() == 1


def test_prefix_shorter_than_a_page_not_reused(setup):
    cfg, params, serving = setup
    rng = np.random.default_rng(3)
    shared = rng.integers(2, cfg.vocab_size, PS - 1).tolist()
    a = shared + rng.integers(2, cfg.vocab_size, 6).tolist()
    b = shared + rng.integers(2, cfg.vocab_size, 8).tolist()

    engine = Engine(cfg, params, serving)
    _run(engine, [a])
    _run(engine, [b])
    assert engine.metrics.prefix_cache_hits.total() == 0


def test_evicted_pages_no_longer_match(setup):
    """Once a released prompt's pages are reclaimed for a new prompt, the
    old prompt must no longer be offered as a prefix source."""
    cfg, params, serving = setup
    # one slot, a pool of exactly one window: the second prompt can only be
    # placed by reclaiming the first one's evictable pages (host tier off —
    # with it on the reclaimed pages spill and the old prompt restores them,
    # which the host-tier tests below cover)
    tight = dataclasses.replace(serving, max_decode_slots=1,
                                max_cache_len=32, prefill_buckets=(32,),
                                kv_pool_pages=4, kv_host_tier_bytes=0)
    rng = np.random.default_rng(4)
    old = rng.integers(2, cfg.vocab_size, 20).tolist()
    new = rng.integers(2, cfg.vocab_size, 20).tolist()
    again_old = old + rng.integers(2, cfg.vocab_size, 3).tolist()

    want = _expected(cfg, params, tight, [[old], [new], [again_old]])

    engine = Engine(cfg, params, tight)
    got = _run(engine, [old])
    assert engine.allocators[0].stats()["pages_evictable"] >= 2
    got += _run(engine, [new]) + _run(engine, [again_old])
    assert got == want
    assert engine.metrics.prefix_cache_hits.total() == 0


@pytest.mark.parametrize("order", ["unrelated-first", "extension-first",
                                   "twins"])
def test_same_round_admission_never_matches_pages_being_written(setup, order):
    """Pages are indexed when a prefill LANDS, never at admission: a request
    admitted in the same round as another must not match pages the round's
    prefill has yet to write. ``twins``: two prompts sharing a prefix with
    each other and nothing resident arrive together — neither may borrow
    from the other. The other two orders put an extension of a resident
    prompt beside an unrelated one. Parity against a cache-off engine is the
    oracle."""
    cfg, params, serving = setup
    two_slot = dataclasses.replace(serving, max_decode_slots=2)
    rng = np.random.default_rng(6)
    p = rng.integers(2, cfg.vocab_size, 16).tolist()
    a = rng.integers(2, cfg.vocab_size, 14).tolist()          # unrelated
    b = p + rng.integers(2, cfg.vocab_size, 5).tolist()       # extends p
    if order == "twins":
        first = [p + [7, 8, 9], p + [10, 11]]
        want = _expected(cfg, params, two_slot, [first])
        engine = Engine(cfg, params, two_slot)
        assert _run(engine, first) == want
        assert engine.metrics.prefix_cache_hits.total() == 0
        return
    pair = [a, b] if order == "unrelated-first" else [b, a]
    want = _expected(cfg, params, two_slot, [[p], pair])
    engine = Engine(cfg, params, two_slot)
    assert _run(engine, [p]) + _run(engine, pair) == want


def test_burst_keeps_batched_prefill(setup, monkeypatch):
    """A match under ``prefix_reuse_min_pages`` must never break up batched
    prefill: a burst of prompts sharing ONE page with a resident prompt
    prefills in ONE batched dispatch with zero reuse — the serialized chunk
    walk (one dispatch a request) costs more than the recompute it saves.
    The same burst arriving one at a time (isolated) does reuse."""
    cfg, params, serving = setup
    assert serving.prefix_reuse_min_pages == 2
    rng = np.random.default_rng(7)
    shared = rng.integers(2, cfg.vocab_size, PS + 3).tolist()   # one page
    p = shared + rng.integers(2, cfg.vocab_size, 3).tolist()
    burst = [shared + rng.integers(2, cfg.vocab_size, k).tolist()
             for k in (4, 5, 6)]

    engine = Engine(cfg, params, serving)
    _run(engine, [p])

    batch_calls = []
    orig = Engine._do_prefill_batch
    monkeypatch.setattr(Engine, "_do_prefill_batch",
                        lambda self, batch: (batch_calls.append(len(batch)),
                                             orig(self, batch))[1])
    got = _run(engine, burst)
    assert all(g for g in got)
    assert engine.metrics.prefix_cache_hits.total() == 0
    assert batch_calls == [3]
    for q in burst:
        _run(engine, [q + [5]])
    assert engine.metrics.prefix_cache_hits.total() == 3


def test_burst_with_a_long_shared_prefix_reuses(setup):
    """At or over ``prefix_reuse_min_pages`` whole pages the match is kept
    even under a burst: skipping the shared compute beats the batch slot."""
    cfg, params, serving = setup
    rng = np.random.default_rng(8)
    shared = rng.integers(2, cfg.vocab_size, 3 * PS).tolist()
    p = shared + rng.integers(2, cfg.vocab_size, 3).tolist()
    burst = [shared + rng.integers(2, cfg.vocab_size, k).tolist()
             for k in (4, 5, 6)]

    want = _expected(cfg, params, serving, [[p], burst])
    engine = Engine(cfg, params, serving)
    assert _run(engine, [p]) + _run(engine, burst) == want
    assert engine.metrics.prefix_cache_hits.total() == 3
    assert engine.metrics.prefix_tokens_reused.total() == 3 * 3 * PS


def test_prefix_hit_with_chunked_suffix(setup):
    """Prefix reuse composes with chunked prefill: a long suffix still walks
    the chunk program from the reuse offset."""
    cfg, params, serving = setup
    chunked = dataclasses.replace(serving, prefill_chunk=16)
    rng = np.random.default_rng(5)
    shared = rng.integers(2, cfg.vocab_size, 24).tolist()
    a = shared + rng.integers(2, cfg.vocab_size, 4).tolist()
    b = shared + rng.integers(2, cfg.vocab_size, 40).tolist()  # 40-tok suffix

    want = _expected(cfg, params, chunked, [[a], [b]])

    engine = Engine(cfg, params, chunked)
    got = _run(engine, [a]) + _run(engine, [b])
    assert got == want
    assert engine.metrics.prefix_cache_hits.total() == 1


def test_prefix_hit_suffix_rides_the_mixed_program(setup):
    """A hit arriving beside a live stream: its suffix is prefilled by
    ``mixed_step`` from the reuse offset (one dispatch with the decode
    batch, the pipeline stays open), byte-identical to the cache-off
    engine."""
    cfg, params, serving = setup
    rng = np.random.default_rng(9)
    shared = rng.integers(2, cfg.vocab_size, 3 * PS).tolist()
    a = shared + rng.integers(2, cfg.vocab_size, 4).tolist()
    live = rng.integers(2, cfg.vocab_size, 6).tolist()
    b = shared + rng.integers(2, cfg.vocab_size, 9).tolist()

    def schedule(engine, spy=None):
        out = _run(engine, [a])
        rl = engine.submit(Request(prompt_ids=list(live), max_tokens=24,
                                   ignore_eos=True))
        for _ in range(4):
            engine.step()            # live is decoding, a dispatch in flight
        if spy is not None:
            real = engine._mixed_dispatch
            engine._mixed_dispatch = lambda st, *x: (spy.append(st["off"]),
                                                     real(st, *x))[1]
        rb = engine.submit(Request(prompt_ids=list(b), max_tokens=6,
                                   ignore_eos=True))
        _drain(engine)
        return out + [rl.generated, rb.generated]

    want = schedule(Engine(cfg, params,
                           dataclasses.replace(serving, prefix_cache=False)))
    offs = []
    engine = Engine(cfg, params, serving)
    assert schedule(engine, offs) == want
    assert engine.metrics.prefix_cache_hits.total() == 1
    assert offs and offs[0] == 3 * PS, offs


# ---------------------------------------------------------------------------
# Host tier: eviction spills prefix pages to host RAM; a later
# request whose prefix is gone from HBM restores the pages instead of
# re-prefilling. Every test is token-parity: tier traffic must be invisible
# in the output stream.
# ---------------------------------------------------------------------------

from aws_k8s_ansible_provisioner_tpu.serving import chaos as _chaos

PS = 8


def _paged_engine(model, **kw):
    cfg, params = model
    base = dict(max_decode_slots=4, max_cache_len=64, page_size=PS,
                prefill_buckets=(8, 16, 32, 64), dtype="float32",
                kv_pool_pages=10, kv_host_tier_bytes=1 << 22)
    base.update(kw)
    return Engine(cfg, params, ServingConfig(weights_dtype="bf16", **base))


@pytest.fixture(scope="module")
def paged_model():
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _paged_drain(eng):
    while (any(s is not None for s in eng.slot_req) or eng.pending
           or eng._chunk is not None):
        eng.step()


def _paged_run(eng, prompt, max_tokens=6):
    r = eng.submit(Request(prompt_ids=list(prompt), max_tokens=max_tokens,
                           ignore_eos=True))
    _paged_drain(eng)
    return r.generated


def _tier_prompts(seed=11):
    """One reusable prompt + two fillers, each 33 tokens = 5 pages with the
    decode tail. Pool is 10 pages, so running A then B then C forces A's
    indexed prefix pages off HBM (into the host tier when one is attached)."""
    rng = np.random.default_rng(seed)
    a = rng.integers(2, 128, 33).tolist()
    b = rng.integers(2, 128, 33).tolist()
    c = rng.integers(2, 128, 33).tolist()
    return a, b, c


def test_host_tier_spill_restore_token_parity(paged_model):
    """After A's pages are evicted to host, re-running A must restore from
    host RAM (tier hit + restore bytes) and emit exactly the cold tokens."""
    a, b, c = _tier_prompts()
    eng = _paged_engine(paged_model)
    cold = _paged_run(eng, a)
    _paged_run(eng, b)
    _paged_run(eng, c)                       # evicts A's prefix pages -> spill

    tier = eng.host_tier
    assert tier is not None and tier.spilled_pages > 0
    assert eng.metrics.kv_spill_bytes.total() > 0

    warm = _paged_run(eng, a)
    assert warm == cold                       # byte-identical stream
    assert eng.metrics.prefix_tier_hits.value(tier="host") >= 1
    assert eng.metrics.kv_restore_bytes.total() > 0
    assert tier.restored_pages > 0
    for alloc in eng.allocators:
        assert alloc.stats()["pages_live"] == 0


def test_host_tier_zero_budget_byte_identity(paged_model):
    """--kv-host-tier-bytes 0 is the escape hatch: no tier object, no host
    hits, and the stream is byte-identical to the tier-on engine's."""
    a, b, c = _tier_prompts(seed=12)
    on = _paged_engine(paged_model)
    outs_on = [_paged_run(on, p) for p in (a, b, c, a)]

    off = _paged_engine(paged_model, kv_host_tier_bytes=0)
    assert off.host_tier is None
    outs_off = [_paged_run(off, p) for p in (a, b, c, a)]

    assert outs_off == outs_on
    assert off.metrics.prefix_tier_hits.value(tier="host") == 0
    assert off.metrics.kv_spill_bytes.total() == 0
    for alloc in off.allocators:
        assert "host_tier" not in alloc.stats()


def test_host_tier_restore_races_concurrent_hit(paged_model):
    """Two requests sharing the evicted prefix admitted back-to-back: each
    restore must take its own pages with clean refcounts — after drain every
    page is released exactly once (pages_live == 0) and both streams match
    the cold run."""
    a, b, c = _tier_prompts(seed=13)
    eng = _paged_engine(paged_model)
    cold = _paged_run(eng, a)
    _paged_run(eng, b)
    _paged_run(eng, c)

    r1 = eng.submit(Request(prompt_ids=list(a), max_tokens=6, ignore_eos=True))
    r2 = eng.submit(Request(prompt_ids=list(a), max_tokens=6, ignore_eos=True))
    _paged_drain(eng)
    assert r1.generated == cold
    assert r2.generated == cold
    for alloc in eng.allocators:
        st = alloc.stats()
        assert st["pages_live"] == 0
        assert st["pages_free"] + st["pages_evictable"] == st["pages_total"]


def test_kv_offload_error_drops_not_corrupts(paged_model):
    """Chaos 'kv_offload_error' corrupts the host entries mid-restore: the
    engine must detect the damage, drop the restore, and fall back to a full
    re-prefill — wrong tokens are never an option."""
    a, b, c = _tier_prompts(seed=14)
    _chaos.reset()
    try:
        eng = _paged_engine(paged_model)
        cold = _paged_run(eng, a)
        _paged_run(eng, b)
        _paged_run(eng, c)
        assert eng.host_tier.spilled_pages > 0

        _chaos.get().inject("kv_offload_error", times=1)
        warm = _paged_run(eng, a)
        assert warm == cold                   # fell back, did not corrupt
        assert eng.metrics.kv_restore_dropped.total() >= 1
        assert eng.host_tier.dropped_invalid >= 1
        assert eng.metrics.prefix_tier_hits.value(tier="host") == 0
    finally:
        _chaos.reset()
