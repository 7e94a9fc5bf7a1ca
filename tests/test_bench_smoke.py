"""Tier-1-safe bench smoke: construct and run the EXACT program family the
benchmark's shipped default measures — paged pool + double-buffered
batch-blocked Pallas decode (interpret mode) + int8 weights — one decode
step end to end under JAX_PLATFORMS=cpu.

This is the `make bench-smoke` target's payload (also tier-1: it is not
marked slow). It exists to catch PROGRAM-CONSTRUCTION regressions — a
BlockSpec/scratch-shape/scalar-prefetch mismatch in the bblock decode path
dies here in seconds instead of zeroing a 900s TPU bench window.
"""

import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request


@pytest.mark.bench_smoke
@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_bench_default_decode_program_constructs(kv_dtype):
    """One decode step through the paged + bblock program builder: the
    served default config shape (paged pool, int8 weights, pinned bb=4,
    pallas kernels in interpret mode)."""
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    serving = ServingConfig(
        model="tiny-qwen3", max_decode_slots=4, max_cache_len=128,
        page_size=32, dtype="float32", prefill_buckets=(16,),
        kv_dtype=kv_dtype, weights_dtype="int8",
        decode_bblock=4, decode_horizon=2, attention_impl="pallas")
    engine = Engine(cfg, params, serving)
    assert engine.decode_bblock == 4
    reqs = [engine.submit(Request(prompt_ids=[7 + i, 9, 11], max_tokens=3,
                                  ignore_eos=True)) for i in range(2)]
    for _ in range(24):
        if all(r.finish_reason for r in reqs):
            break
        engine.step()
    for r in reqs:
        assert len(r.generated) == 3, (r.finish_reason, r.generated)


@pytest.mark.bench_smoke
def test_bench_spec_verify_program_constructs():
    """The spec-verify multi-query variant of the same program family
    (prompt-lookup drafts through the paged + bblock verify kernel)."""
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    serving = ServingConfig(
        model="tiny-qwen3", max_decode_slots=4, max_cache_len=128,
        page_size=32, dtype="float32", prefill_buckets=(32,),
        weights_dtype="int8", decode_bblock=4,
        decode_horizon=4, attention_impl="pallas",
        spec_decode=True, spec_k=2, spec_ngram=2)
    engine = Engine(cfg, params, serving)
    # a self-repeating prompt guarantees the prompt-lookup proposer fires,
    # constructing the paged+bblock spec_decode_step program
    pat = [5, 6] * 6
    req = engine.submit(Request(prompt_ids=pat, max_tokens=6,
                                ignore_eos=True))
    for _ in range(40):
        if req.finish_reason:
            break
        engine.step()
    assert len(req.generated) == 6
    assert engine.metrics.spec_drafted_tokens.total() > 0, \
        "spec verify path never dispatched — smoke covered nothing"
