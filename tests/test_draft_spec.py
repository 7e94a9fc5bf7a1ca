"""Draft-model speculative decoding (serving/draft.py; VERDICT r4 next #7).

The load-bearing property is the same as prompt-lookup speculation: an
engine WITH a draft model emits byte-identical greedy streams to one
without — accepted drafts are exactly the tokens plain decode would have
produced. On top of that, the draft path must keep its own KV cache
coherent across catch-up (plain-path interleaves), stop conditions, and
slot recycling, and must export the acceptance-rate metric.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request

CFG = tiny_qwen3()


def _params(seed):
    return init_params(CFG, jax.random.PRNGKey(seed), jnp.float32)


def _serving(**over):
    base = dict(max_decode_slots=4, max_cache_len=128, prefill_buckets=(32,),
                dtype="float32", prefix_cache=False, decode_horizon=6)
    base.update(over)
    return ServingConfig(weights_dtype="bf16", **base)


def _drive(eng, reqs):
    for _ in range(10000):
        if not eng.step():
            break
    return [r.generated for r in reqs]


def _submit(eng, prompts, **kw):
    return [eng.submit(Request(prompt_ids=list(p), max_tokens=24,
                               ignore_eos=True, **kw)) for p in prompts]


PROMPTS = [[5, 6, 7, 8, 9, 10], [11, 3, 2, 13, 2, 7, 9]]


def test_draft_requires_model():
    with pytest.raises(ValueError, match="draft"):
        Engine(CFG, _params(0),
               _serving(spec_decode=True, spec_method="draft"))


def test_bad_spec_method_rejected():
    with pytest.raises(ValueError, match="spec_method"):
        Engine(CFG, _params(0), _serving(spec_method="beam"))


def test_perfect_draft_full_acceptance_and_parity():
    """Draft == target: every draft token matches the target argmax, so all
    spec_k drafts verify each round (acceptance 1.0) and the stream is
    byte-identical to plain decode."""
    params = _params(0)
    ref = _drive(*(lambda e: (e, _submit(e, PROMPTS)))(
        Engine(CFG, params, _serving())))
    spec = _serving(spec_decode=True, spec_k=4, spec_method="draft")
    eng = Engine(CFG, params, spec, draft=(CFG, params))
    got = _drive(eng, _submit(eng, PROMPTS))
    assert got == ref
    drafted = eng.metrics.spec_drafted_tokens.total()
    accepted = eng.metrics.spec_accepted_tokens.total()
    assert drafted > 0
    assert accepted == drafted, "a self-draft must be fully accepted"
    assert eng.metrics.spec_acceptance_rate.value() == pytest.approx(1.0)


def test_divergent_draft_still_lossless():
    """A draft that provably disagrees (its embedding table is rolled one
    vocab row, so its repeat-token attractor repeats a DIFFERENT token)
    proposes wrong tokens; the verify pass must reject them and the emitted
    stream must STILL equal plain greedy decode exactly. (Two independently
    random tiny models genuinely agree ~100% — both collapse to the
    repeat-last-token attractor — so disagreement must be constructed.)"""
    params = _params(0)
    ref = _drive(*(lambda e: (e, _submit(e, PROMPTS)))(
        Engine(CFG, params, _serving())))
    # rolling a TIED table permutes input and output identically (the roll
    # cancels), so untie: the draft's lm_head maps every argmax one vocab
    # row off the target's
    dcfg = tiny_qwen3(tie_embeddings=False)
    dparams = dict(_params(0))
    dparams["lm_head"] = {
        "kernel": jnp.roll(dparams["embed"]["weight"], 1, axis=0).T}
    spec = _serving(spec_decode=True, spec_k=4, spec_method="draft")
    eng = Engine(CFG, params, spec, draft=(dcfg, dparams))
    got = _drive(eng, _submit(eng, PROMPTS))
    assert got == ref
    drafted = eng.metrics.spec_drafted_tokens.total()
    accepted = eng.metrics.spec_accepted_tokens.total()
    assert drafted > 0
    assert accepted < drafted, "rolled-embedding draft cannot fully agree"


def test_sampled_neighbor_keeps_seeded_stream():
    """A temperature > 0 slot is never drafted (accepts nothing) and its
    seeded stream must match the no-spec engine's exactly."""
    params = _params(0)
    kw = dict(temperature=0.8, seed=7)
    e0 = Engine(CFG, params, _serving())
    r0 = [e0.submit(Request(prompt_ids=list(PROMPTS[0]), max_tokens=24,
                            ignore_eos=True, **kw))]
    ref = _drive(e0, r0)
    spec = _serving(spec_decode=True, spec_k=4, spec_method="draft")
    eng = Engine(CFG, params, spec, draft=(CFG, params))
    greedy = eng.submit(Request(prompt_ids=list(PROMPTS[1]), max_tokens=24,
                                ignore_eos=True))
    sampled = eng.submit(Request(prompt_ids=list(PROMPTS[0]), max_tokens=24,
                                 ignore_eos=True, **kw))
    _drive(eng, [greedy, sampled])
    assert sampled.generated == ref[0]
    assert len(greedy.generated) == 24


def test_catch_up_after_plain_interleave():
    """A logprobs slot forces alternating plain dispatches (spec-ineligible),
    so drafted neighbors drift behind by the capped horizon and must
    teacher-force the gap — parity proves the catch-up writes are
    coherent."""
    params = _params(0)
    e0 = Engine(CFG, params, _serving())
    reqs0 = [e0.submit(Request(prompt_ids=list(PROMPTS[0]), max_tokens=24,
                               ignore_eos=True)),
             e0.submit(Request(prompt_ids=list(PROMPTS[1]), max_tokens=24,
                               ignore_eos=True, logprobs=2))]
    ref = _drive(e0, reqs0)
    spec = _serving(spec_decode=True, spec_k=4, spec_method="draft")
    eng = Engine(CFG, params, spec, draft=(CFG, params))
    reqs = [eng.submit(Request(prompt_ids=list(PROMPTS[0]), max_tokens=24,
                               ignore_eos=True)),
            eng.submit(Request(prompt_ids=list(PROMPTS[1]), max_tokens=24,
                               ignore_eos=True, logprobs=2))]
    got = _drive(eng, reqs)
    assert got == ref
    assert eng.metrics.spec_drafted_tokens.total() > 0
    assert all(lp is not None for lp in reqs[1].logprob_data)


def test_slot_recycling_reprefills_draft():
    """A finished slot's draft rows are garbage for the next occupant; the
    draft prefill on re-admission must restore coherence (parity on the
    second wave)."""
    params = _params(0)
    spec = _serving(spec_decode=True, spec_k=4, spec_method="draft",
                    max_decode_slots=2)
    eng = Engine(CFG, params, spec, draft=(CFG, params))
    _drive(eng, _submit(eng, PROMPTS))          # wave 1 fills both slots
    wave2 = _submit(eng, [PROMPTS[1], PROMPTS[0]])   # recycled slots
    got = _drive(eng, wave2)
    e0 = Engine(CFG, params, _serving(max_decode_slots=2))
    ref = _drive(e0, _submit(e0, [PROMPTS[1], PROMPTS[0]]))
    assert got == ref


def test_draft_under_tp_mesh(cpu_devices):
    """The shared spec machinery is mesh-gated identically for both proposal
    sources; a tp mesh must hold parity with drafts firing."""
    from aws_k8s_ansible_provisioner_tpu.config import MeshConfig
    from aws_k8s_ansible_provisioner_tpu.parallel.mesh import make_mesh

    cfg = tiny_qwen3(num_heads=4, num_kv_heads=2, vocab_size=256)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    base = _serving(attention_impl="pallas")
    e0 = Engine(cfg, params, base)
    ref = _drive(e0, _submit(e0, PROMPTS))
    spec = dataclasses.replace(base, spec_decode=True, spec_k=4,
                               spec_method="draft")
    mesh = make_mesh(MeshConfig(dp=1, tp=2), devices=jax.devices("cpu"))
    eng = Engine(cfg, params, spec, mesh=mesh, draft=(cfg, params))
    got = _drive(eng, _submit(eng, PROMPTS))
    assert got == ref
    assert eng.metrics.spec_drafted_tokens.total() > 0


@pytest.mark.parametrize("n_prompts", [1, 3])
def test_draft_pool_holds_the_rows_the_target_wrote(n_prompts):
    """The draft keeps a pool of its own under a static identity block table
    (slot i owns pages [i * MP, (i + 1) * MP)): with the draft network equal
    to the target, a shared prompt's K/V rows read back the same from both
    pools — the draft's through its identity table, the target's through the
    engine's allocated one. One prompt takes prefill_step, three the batched
    program (whose padding row must drop)."""
    from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp

    params = _params(0)
    eng = Engine(CFG, params,
                 _serving(spec_decode=True, spec_method="draft", spec_k=3,
                          page_size=16),
                 draft=(CFG, params))
    mp = eng.pages_per_slot
    np.testing.assert_array_equal(
        eng.draft.table,
        np.arange(eng.num_slots * mp).reshape(eng.num_slots, mp))
    prompts = (PROMPTS + [[4, 4, 9, 1, 17]])[:n_prompts]
    reqs = _submit(eng, prompts)
    eng.step()                                     # the admission's prefill
    slots = {id(r): s for s, r in enumerate(eng.slot_req) if r is not None}
    assert len(slots) == n_prompts
    ps = eng.serving.page_size
    mine = kvp.gather_dense(eng.draft.cache, jnp.asarray(eng.draft.table), ps)
    theirs = kvp.gather_dense(eng.cache, jnp.asarray(eng.table), ps)
    for r in reqs:
        s, n = slots[id(r)], len(r.prompt_ids)
        assert eng.draft.lens[s] == n
        for name in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(mine[name][:, s, :, :n]),
                np.asarray(theirs[name][:, s, :, :n]), rtol=0, atol=1e-6,
                err_msg=name)
    # a slot nobody was admitted to: its pages are untouched (a padding row
    # of the batched prefill carries an all-OOB table and drops)
    idle = next(s for s in range(eng.num_slots) if s not in slots.values())
    assert not np.asarray(mine["k"][:, idle]).any()
    _drive(eng, reqs)
