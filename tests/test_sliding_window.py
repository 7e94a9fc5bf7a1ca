"""Sliding-window attention: kernel/engine parity across every path.

Mistral-v0.1-style windows run through the same masks everywhere — prefill
(whole/batched/chunked), XLA decode fallback, the Pallas decode kernels
(where sub-window chunks are DMA-skipped), and speculative verify. These
tests pin cross-path agreement at lengths well beyond the window, where the
mask is load-bearing.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aws_k8s_ansible_provisioner_tpu.config import (MeshConfig, ServingConfig,
                                                    tiny_mistral)
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa
from aws_k8s_ansible_provisioner_tpu.ops.attention import decode_attend
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request


@pytest.mark.parametrize("bb", [1, 3])
def test_pallas_windowed_attend_matches_xla(bb):
    """``bb`` 3: one block holds a row inside its first page beside rows
    whose windows start at pages 1 and 3 — above the block's first page, so
    their buffers are read (masked) before any copy of theirs fills them,
    and the first row stops copying after page 0 (PR 45: a row outside its
    range starts no copy; interpret mode's scratch starts as NaN)."""
    L, B, Hkv, S, D, Hq, W = 2, 3, 2, 64, 16, 4, 8
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(0, 1, (L, B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (L, B, Hkv, S, D)), jnp.float32)
    lengths = jnp.asarray([5, 33, 64], jnp.int32)   # below / beyond window
    q = jnp.asarray(rng.normal(0, 1, (B, 1, Hq, D)), jnp.float32)
    # the logical rows cut into 16-row pages under an identity table
    PS = 16

    def pages(a):
        a = a.reshape(L, B, Hkv, S // PS, PS, D)
        return jnp.moveaxis(a, 3, 2).reshape(L, B * (S // PS), Hkv, PS, D)

    table = jnp.arange(B * (S // PS), dtype=jnp.int32).reshape(B, S // PS)
    got = pa.decode_attend_pallas_paged(q, pages(k), pages(v), lengths,
                                        jnp.int32(1), table, interpret=True,
                                        window=W, bblock=bb)
    ref = decode_attend(q, k[1], v[1], lengths, window=W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    # and the window must actually matter at these lengths
    full = decode_attend(q, k[1], v[1], lengths, window=0)
    assert np.abs(np.asarray(full) - np.asarray(ref)).max() > 1e-3


def _run(cfg, params, serving, prompts, max_tokens=30):
    eng = Engine(cfg, params, serving)
    reqs = [eng.submit(Request(prompt_ids=list(p), max_tokens=max_tokens,
                               ignore_eos=True)) for p in prompts]
    for _ in range(10000):
        if not eng.step():
            break
    return [r.generated for r in reqs]


@pytest.mark.parametrize("kv", ["auto", "int8"])
def test_engine_windowed_decode_parity_pallas_vs_xla(kv):
    """Generations run ~4 windows past W: every decode step's mask and the
    DMA low-chunk clamp must agree with the XLA reference path."""
    cfg = tiny_mistral()   # window 8
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist() for n in (3, 13)]
    base = ServingConfig(weights_dtype="bf16", max_decode_slots=2, max_cache_len=64,
                         prefill_buckets=(16,), dtype="float32",
                         attention_impl="xla", kv_dtype=kv,
                         prefix_cache=False)
    ref = _run(cfg, params, base, prompts)
    got = _run(cfg, params,
               dataclasses.replace(base, attention_impl="pallas"), prompts)
    assert got == ref
    assert all(len(g) == 30 for g in got)


def test_engine_windowed_chunked_prefill_parity():
    """A long prompt through chunked prefill (window-masked chunk attends)
    must match whole-prompt prefill."""
    cfg = tiny_mistral()
    params = init_params(cfg, jax.random.PRNGKey(2), jnp.float32)
    rng = np.random.default_rng(3)
    prompt = rng.integers(2, cfg.vocab_size, 40).tolist()
    base = ServingConfig(weights_dtype="bf16", max_decode_slots=2, max_cache_len=64,
                         prefill_buckets=(64,), dtype="float32",
                         attention_impl="xla", prefix_cache=False)
    ref = _run(cfg, params, base, [prompt], max_tokens=6)
    got = _run(cfg, params, dataclasses.replace(base, prefill_chunk=16),
               [prompt], max_tokens=6)
    assert got == ref


def test_spec_decode_windowed_stream_identity():
    cfg = tiny_mistral()
    params = init_params(cfg, jax.random.PRNGKey(4), jnp.float32)
    pat = [3, 4, 5, 6] * 4
    base = ServingConfig(weights_dtype="bf16", max_decode_slots=2, max_cache_len=64,
                         prefill_buckets=(16,), dtype="float32",
                         attention_impl="pallas", prefix_cache=False,
                         decode_horizon=4)
    ref = _run(cfg, params, base, [pat], max_tokens=24)
    got = _run(cfg, params,
               dataclasses.replace(base, spec_decode=True, spec_k=4,
                                   spec_ngram=3), [pat], max_tokens=24)
    assert got == ref


def test_windowed_decode_parity_under_dp_mesh(cpu_devices):
    """A sliding-window model under a dp mesh: the window's low-page clamp
    rides the per-group table rebase — token parity with one device, ~4
    windows past W."""
    from aws_k8s_ansible_provisioner_tpu.parallel import make_mesh

    cfg = tiny_mistral()
    params = init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    serving = ServingConfig(weights_dtype="bf16", max_decode_slots=4, max_cache_len=64,
                            prefill_buckets=(16,), dtype="float32",
                            attention_impl="pallas")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist() for n in (3, 13)]
    want = _run(cfg, params, serving, prompts)
    mesh = make_mesh(MeshConfig(dp=2), devices=cpu_devices[:2])
    eng = Engine(cfg, params, serving, mesh=mesh)
    reqs = [eng.submit(Request(prompt_ids=list(p), max_tokens=30,
                               ignore_eos=True)) for p in prompts]
    for _ in range(10000):
        if not eng.step():
            break
    assert [r.generated for r in reqs] == want
