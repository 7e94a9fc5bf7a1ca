"""Paged-KV engine behavior: capacity scales with ACTUAL lengths, preemption
resumes losslessly, prefix pages are shared (not copied), admission is gated
by pages.

This is the VERDICT r2 "done" criterion for the paged cache (missing #2 /
next #3): a pool smaller than slots x window must admit and correctly serve every request whose true
lengths fit, matching the on-demand block behavior of the vLLM engine the
reference delegates to (SURVEY.md §2.2 row 1).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import (init_params,
                                                            model_forward)
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request

PS = 8


@pytest.fixture(scope="module")
def model():
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _engine(model, **kw):
    cfg, params = model
    base = dict(max_decode_slots=8, max_cache_len=64, page_size=PS,
                prefill_buckets=(8, 16, 32), dtype="float32")
    base.update(kw)
    return Engine(cfg, params, ServingConfig(weights_dtype="bf16", **base))


def _drain(eng):
    while (any(s is not None for s in eng.slot_req) or eng.pending
           or eng._chunk is not None):
        eng.step()


_FWD = jax.jit(lambda params, cfg, toks, pos: model_forward(
    params, cfg, toks, pos)[0], static_argnums=1)


def _greedy_reference(model, prompt, n):
    """Greedy continuation by the plain full-context float32 forward: no
    cache, no engine — every token re-reads the whole context (one padded
    shape; causality keeps the padding out of row len - 1)."""
    cfg, params = model
    ctx = list(prompt)
    pos = jnp.arange(64, dtype=jnp.int32)[None]
    for _ in range(n):
        toks = np.zeros((1, 64), np.int32)
        toks[0, :len(ctx)] = ctx
        logits = _FWD(params, cfg, jnp.asarray(toks), pos)
        ctx.append(int(jnp.argmax(logits[0, len(ctx) - 1])))
    return ctx[len(prompt):]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_generation_matches_full_context_forward(model, kv_dtype, impl):
    """Same greedy tokens through the engine and the cache-free forward (the
    whole paged machinery — pool writers, block-table kernels, scratch page,
    and with int8 the per-row scales — must be invisible to generation)."""
    prompts = [[3, 5, 7, 11, 13], [2] * 17, [9, 8, 7, 6, 5, 4, 3, 2, 1]]
    eng = _engine(model, kv_dtype=kv_dtype, attention_impl=impl,
                  page_size=32 if kv_dtype == "int8" else PS)
    reqs = [eng.submit(Request(prompt_ids=list(p), max_tokens=6,
                               ignore_eos=True)) for p in prompts]
    _drain(eng)
    for p, r in zip(prompts, reqs):
        assert r.generated == _greedy_reference(model, p, 6), p


@pytest.mark.parametrize("dp", [1, 2])
def test_sequence_parallel_mesh_is_refused(model, dp):
    """A page is a contiguous row run: a mesh that shards the sequence axis
    is refused at start-up, with the flag that does serve long contexts
    across chips."""
    from aws_k8s_ansible_provisioner_tpu.config import MeshConfig
    from aws_k8s_ansible_provisioner_tpu.parallel import make_mesh

    cfg, params = model
    mesh = make_mesh(MeshConfig(dp=dp, tp=1, sp=2),
                     devices=jax.devices()[:2 * dp])
    with pytest.raises(ValueError, match="--tp"):
        Engine(cfg, params, ServingConfig(max_decode_slots=2,
                                          max_cache_len=64), mesh=mesh)


def test_capacity_scales_with_actual_lengths(model):
    """THE paged capacity property: 8 slots x 64-token window would need 64
    pages dense; a 24-page pool (3 windows' worth) must still serve 8
    CONCURRENT short requests — more in-flight sequences than the dense
    layout could hold in the same HBM."""
    eng = _engine(model, kv_pool_pages=24)
    # 8 concurrent requests, each prompt 5 + gen 6 = 11 rows = 2 pages -> 16
    # pages in flight <= 24; dense sizing would demand 64.
    reqs = [eng.submit(Request(prompt_ids=[i + 2] * 5, max_tokens=6,
                               ignore_eos=True)) for i in range(8)]
    # step until all are ACTIVE at once (admission must not serialize them)
    for _ in range(64):
        eng.step()
        if all(s is not None for s in eng.slot_req):
            break
    assert all(s is not None for s in eng.slot_req), \
        "pool must admit all 8 concurrent short requests"
    _drain(eng)
    for i, r in enumerate(reqs):
        assert len(r.generated) == 6
        assert r.generated == _greedy_reference(model, [i + 2] * 5, 6)
    st = eng.allocators[0].stats()
    assert st["pages_live"] == 0       # everything released at finish


def test_admission_gated_by_pages_not_slots(model):
    """With 1 free page and 7 free slots, a 9-token prompt (2 pages) must
    WAIT, and be admitted once a finishing request frees pages."""
    eng = _engine(model, max_cache_len=32, kv_pool_pages=4)  # 4-page window
    big = eng.submit(Request(prompt_ids=[4] * 17, max_tokens=2,
                             ignore_eos=True))     # needs 3 pages
    small = eng.submit(Request(prompt_ids=[5] * 9, max_tokens=2,
                               ignore_eos=True))   # needs 2 > 1 left: waits
    eng.step()                                     # admits+prefills big only
    assert eng.slot_req.count(None) == eng.num_slots - 1
    assert small.t_first_token == 0.0
    _drain(eng)                                    # big finishes, small runs
    assert len(big.generated) == 2 and len(small.generated) == 2


def test_preemption_resumes_losslessly(model):
    """Grow three streams until the pool runs dry: the newest request gets
    preempted (pages reclaimed), resumed by recompute when pages free, and
    its final token sequence is IDENTICAL to an unconstrained run."""
    # window 64 rows = 8 pages/slot; pool of 12 pages forces pressure once
    # 3 streams each pass ~4 pages (32 rows)
    eng = _engine(model, kv_pool_pages=12)
    gens = 40
    reqs = [eng.submit(Request(prompt_ids=[i + 3] * 4, max_tokens=gens,
                               ignore_eos=True)) for i in range(3)]
    _drain(eng)
    assert int(eng.metrics.preemptions.total()) > 0, \
        "12 pages cannot hold 3 x ceil(44/8) pages — preemption must fire"
    for i, r in enumerate(reqs):
        assert len(r.generated) == gens
        assert r.generated == _greedy_reference(model, [i + 3] * 4, gens), \
            f"stream {i} diverged after preemption/resume"


def test_prefix_pages_shared_no_copy(model):
    """A follow-up prompt sharing full leading pages must hash-hit them:
    prefix_tokens_reused grows, pages_live stays below two full prompts'
    worth while both are active (sharing, not copying)."""
    eng = _engine(model, kv_pool_pages=24)
    seed = list(range(2, 2 + 2 * PS))              # exactly 2 full pages
    r1 = eng.submit(Request(prompt_ids=list(seed), max_tokens=1,
                            ignore_eos=True))
    _drain(eng)
    reused0 = eng.metrics.prefix_tokens_reused.total()
    r2 = eng.submit(Request(prompt_ids=list(seed) + [50, 51, 52],
                            max_tokens=1, ignore_eos=True))
    _drain(eng)
    assert eng.metrics.prefix_tokens_reused.total() - reused0 == 2 * PS
    assert r2.generated == _greedy_reference(
        model, seed + [50, 51, 52], 1)


def test_preempted_resume_hits_its_own_pages(model):
    """Preemption indexes the victim's full pages before releasing them, so
    a resume with pool headroom re-prefills only the tail — observable as
    prefix reuse. (Under real pressure those evictable pages may be
    reclaimed by the survivors — then the resume recomputes, which the
    lossless test above covers; here the preemption is forced white-box so
    the pages provably survive.)"""
    eng = _engine(model, kv_pool_pages=24)
    r = eng.submit(Request(prompt_ids=[3] * 4, max_tokens=40,
                           ignore_eos=True))
    # run until the stream holds >= 2 full pages of context
    for _ in range(200):
        eng.step()
        if len(r.generated) >= 2 * PS:
            break
    assert len(r.generated) >= 2 * PS
    slot = next(s for s, rq in enumerate(eng.slot_req) if rq is r)
    gen_at_preempt = len(r.generated)
    eng._preempt(slot)
    reused0 = eng.metrics.prefix_tokens_reused.total()
    _drain(eng)
    assert int(eng.metrics.preemptions.total()) == 1
    assert eng.metrics.prefix_tokens_reused.total() - reused0 >= PS, \
        "resume should hash-hit the preempted context's full pages"
    assert len(r.generated) == 40
    assert r.generated == _greedy_reference(model, [3] * 4, 40), \
        f"diverged (preempted at {gen_at_preempt} generated)"


def test_preemption_preserves_penalty_counts(model):
    """A penalized request preempted mid-stream must keep penalizing the
    tokens it generated BEFORE the preemption — _activate restores the
    counts row from req.generated on resume. Equality against an
    unconstrained penalized run is the oracle."""
    def run(preempt_after):
        eng = _engine(model, kv_pool_pages=24)
        r = eng.submit(Request(prompt_ids=[3] * 4, max_tokens=30,
                               ignore_eos=True, presence_penalty=0.9,
                               frequency_penalty=0.5))
        for _ in range(400):
            eng.step()
            if preempt_after and len(r.generated) >= preempt_after:
                slot = next((s for s, rq in enumerate(eng.slot_req)
                             if rq is r), None)
                if slot is not None:
                    eng._preempt(slot)
                    preempt_after = 0     # once
            if r.finish_reason:
                break
        _drain(eng)
        return r.generated

    baseline = run(0)
    preempted = run(10)
    assert len(baseline) == 30
    assert preempted == baseline, \
        "penalty state diverged across preemption/resume"


def test_followup_turn_hits_generated_pages(model):
    """Multi-turn page reuse (ADVICE r3): a follow-up prompt containing the
    PRIOR RESPONSE must prefix-hit past the original prompt — _finish now
    indexes the generated region's full pages (minus the pending last row),
    not just the prompt pages _activate indexed."""
    eng = _engine(model)
    # turn 1: one full prompt page (8 toks), 12 generated -> ids = 20 toks,
    # full WRITTEN pages = floor((20 - 1) / 8) = 2 — the second page is
    # entirely generated tokens
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    a = eng.submit(Request(prompt_ids=list(prompt), max_tokens=12,
                           ignore_eos=True))
    _drain(eng)
    assert len(a.generated) == 12
    reused0 = eng.metrics.prefix_tokens_reused.total()
    # turn 2 (isolated arrival): prompt = turn-1 context + a new question
    follow = prompt + a.generated + [7, 7, 7]
    b = eng.submit(Request(prompt_ids=list(follow), max_tokens=4,
                           ignore_eos=True))
    _drain(eng)
    assert len(b.generated) == 4
    reused = eng.metrics.prefix_tokens_reused.total() - reused0
    # 2 pages = 16 rows reused: past the 8-row prompt page, INTO the
    # generated region
    assert reused >= 2 * PS, f"only {reused} rows reused"
    # and the reuse is correct: the follow-up's continuation matches a
    # fresh engine given the identical full prompt
    assert b.generated == _greedy_reference(model, follow, 4)


def test_prefill_fairness_floor_keeps_decode_flowing(model):
    """VERDICT r3 weak #5: under a sustained admission stream, prefill
    priority alone holds running streams at a trickle. With the fairness
    floor, a long-running request makes materially more progress over the
    same number of steps."""
    cfg, params = model

    def run(fairness):
        eng = Engine(cfg, params, ServingConfig(weights_dtype="bf16", 
            max_decode_slots=2, max_cache_len=64, page_size=PS,
            prefill_buckets=(8, 16, 32), dtype="float32",
            decode_horizon=8, prefill_fairness=fairness,
            prefix_cache=False))
        long = eng.submit(Request(prompt_ids=[5, 4, 3], max_tokens=40,
                                  ignore_eos=True))
        shorts = []
        for i in range(30):
            # one new arrival per step: admission work never dries up
            shorts.append(eng.submit(Request(prompt_ids=[7 + i % 9] * 4,
                                             max_tokens=1, ignore_eos=True)))
            eng.step()
        return len(long.generated)

    starved = run(fairness=0)       # pure prefill priority (pre-r4)
    fair = run(fairness=2)
    assert fair > starved, (starved, fair)
    # with a floor of 2, every third dispatch is a full-horizon (8) decode:
    # 30 steps -> ~10 forced decodes -> tens of tokens, vs a trickle
    assert fair >= starved + 8, (starved, fair)
