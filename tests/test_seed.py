"""OpenAI ``seed`` semantics at the engine level: a seeded request's sampled
stream is a pure function of (seed, prompt, sampling params) — independent of
batch composition, scheduling order, and restarts. This is stronger than
vLLM's per-request generator (which is still order-dependent within a batch)
and is what per-(seed, position) keys buy (ops/sampling.per_slot_keys)."""

import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request


@pytest.fixture(scope="module")
def model():
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _drain(eng):
    while (any(s is not None for s in eng.slot_req) or eng.pending
           or eng._chunk is not None):
        eng.step()


def _engine(model):
    cfg, params = model
    return Engine(cfg, params, ServingConfig(weights_dtype="bf16", 
        max_decode_slots=4, max_cache_len=64, prefill_buckets=(8, 16),
        dtype="float32"))


SEEDED = dict(prompt_ids=[5, 9, 2], max_tokens=10, temperature=0.9,
              ignore_eos=True, seed=42)


def test_seeded_stream_reproducible_across_engines(model):
    a = _engine(model)
    r1 = a.submit(Request(**SEEDED))
    _drain(a)
    b = _engine(model)
    r2 = b.submit(Request(**SEEDED))
    _drain(b)
    assert r1.generated == r2.generated


def test_seeded_stream_independent_of_batch_composition(model):
    alone = _engine(model)
    r_alone = alone.submit(Request(**SEEDED))
    _drain(alone)

    crowded = _engine(model)
    others = [crowded.submit(Request(prompt_ids=[i + 3] * 4, max_tokens=10,
                                     temperature=1.2, ignore_eos=True))
              for i in range(3)]
    r_crowded = crowded.submit(Request(**SEEDED))
    _drain(crowded)
    assert r_crowded.generated == r_alone.generated, \
        "seeded stream must not depend on who else is in the batch"
    assert all(len(o.generated) == 10 for o in others)


def test_different_seeds_diverge(model):
    eng = _engine(model)
    r1 = eng.submit(Request(**{**SEEDED, "seed": 1}))
    r2 = eng.submit(Request(**{**SEEDED, "seed": 2}))
    _drain(eng)
    assert r1.generated != r2.generated


def test_unseeded_requests_still_randomized(model):
    eng = _engine(model)
    unseeded = dict(SEEDED)
    del unseeded["seed"]
    r1 = eng.submit(Request(**unseeded))
    r2 = eng.submit(Request(**unseeded))
    _drain(eng)
    assert r1.generated != r2.generated


def test_greedy_ignores_seed(model):
    eng = _engine(model)
    g1 = eng.submit(Request(prompt_ids=[5, 9, 2], max_tokens=8,
                            temperature=0.0, ignore_eos=True, seed=7))
    g2 = eng.submit(Request(prompt_ids=[5, 9, 2], max_tokens=8,
                            temperature=0.0, ignore_eos=True, seed=8))
    _drain(eng)
    assert g1.generated == g2.generated


def test_seeded_stream_survives_preemption(model):
    """The seed contract's hardest case: a seeded SAMPLED request preempted
    mid-stream must resume onto the exact same continuation (resume is a
    pure cache rebuild; the draw counter convention makes position keys
    identical either way)."""
    cfg, params = model
    mk = lambda: Engine(cfg, params, ServingConfig(weights_dtype="bf16", 
        max_decode_slots=4, max_cache_len=64, page_size=8,
        prefill_buckets=(8, 16), dtype="float32",
        kv_pool_pages=32))
    base_eng = mk()
    base = base_eng.submit(Request(**{**SEEDED, "max_tokens": 24}))
    _drain(base_eng)

    eng = mk()
    r = eng.submit(Request(**{**SEEDED, "max_tokens": 24}))
    for _ in range(400):
        eng.step()
        if len(r.generated) >= 9:
            break
    slot = next(s for s, rq in enumerate(eng.slot_req) if rq is r)
    eng._preempt(slot)
    _drain(eng)
    assert int(eng.metrics.preemptions.total()) == 1
    assert r.generated == base.generated, \
        "seeded stream changed across preemption/resume"
