"""Int8 KV-cache quantization: math parity + engine end-to-end.

The reference's serving pods get this feature from vLLM (``kv_cache_dtype=
int8``); here it is in-repo (ops/kv_pool.py quantize_rows, the quantizing
Pallas kernels in ops/pallas_attention.py). The load-bearing property is that
the XLA write paths (prefill) and the Pallas write kernel (decode) quantize
BIT-FOR-BIT identically, so rows written by either are interchangeable, and
that the engine produces identical tokens whichever backend touches the
quantized cache.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvc
from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa
from aws_k8s_ansible_provisioner_tpu.ops.attention import decode_attend
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3.0, (4, 7, 128)).astype(np.float32))
    q, s = kvc.quantize_rows(x)
    assert q.dtype == jnp.int8 and s.shape == (4, 7)
    deq = kvc.dequantize(q, s)
    # symmetric per-row quantization: |err| <= scale/2 elementwise
    assert np.all(np.abs(np.asarray(deq - x)) <= np.asarray(s)[..., None] * 0.5 + 1e-7)


def test_quant_cache_decode_close_to_float():
    """XLA path: dequantized int8 cache attends within ~1% of the f32 cache."""
    L, B, Hkv, S, D, Hq = 2, 3, 2, 32, 16, 4
    rng = np.random.default_rng(1)
    cfg_like = type("C", (), {"num_layers": L, "num_kv_heads": Hkv,
                              "head_dim": D})
    fcache = {"k": jnp.asarray(rng.normal(0, 1, (L, B, Hkv, S, D)), dtype=jnp.float32),
              "v": jnp.asarray(rng.normal(0, 1, (L, B, Hkv, S, D)), dtype=jnp.float32)}
    qk, ks = kvc.quantize_rows(fcache["k"])
    qv, vs = kvc.quantize_rows(fcache["v"])
    lengths = jnp.asarray([5, 17, 32], jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (B, 1, Hq, D)), dtype=jnp.float32)
    for layer in range(L):
        ref = decode_attend(q, fcache["k"][layer], fcache["v"][layer], lengths)
        got = decode_attend(q, kvc.dequantize(qk[layer], ks[layer]),
                            kvc.dequantize(qv[layer], vs[layer]), lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=0.05, rtol=0.05)


@pytest.mark.parametrize("bb", [1, 4])
def test_pallas_quant_attend_matches_xla_dequant(bb):
    """The int8 Pallas kernel (interpret) == XLA attend over the dequantized
    rows, to float tolerance — the scales fold exactly. The pool is the
    logical cache cut into pages under an identity table, its scale leaves
    lane-padded as the engine allocates them. ``bb`` 4: one block holds two
    one-page rows beside two-page ones — past their page they copy neither
    rows nor scales (PR 45), and the V-scale slot nothing filled is zeroed
    (interpret mode's scratch starts as NaN; 0 x NaN would reach P.V)."""
    L, B, Hkv, S, D, Hq, PS = 3, 4, 2, 64, 32, 4, 32
    rng = np.random.default_rng(2)
    k = jnp.asarray(rng.normal(0, 1, (L, B, Hkv, S, D)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (L, B, Hkv, S, D)), dtype=jnp.float32)
    qk, ks = kvc.quantize_rows(k)
    qv, vs = kvc.quantize_rows(v)

    def pages(a):
        # [L, B, Hkv, S, ...] -> [L, B * S/PS, Hkv, PS, ...]
        a = a.reshape(L, B, Hkv, S // PS, PS, *a.shape[4:])
        return jnp.moveaxis(a, 3, 2).reshape(L, B * (S // PS), Hkv, PS,
                                             *a.shape[5:])

    pad = [(0, 0)] * 3 + [(0, kvc.scale_lanes(PS) - PS)]
    table = jnp.arange(B * (S // PS), dtype=jnp.int32).reshape(B, S // PS)
    lengths = jnp.asarray([1, 9, 33, 64], jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (B, 1, Hq, D)), dtype=jnp.float32)
    for layer in [0, 2]:
        got = pa.decode_attend_pallas_paged(
            q, pages(qk), pages(qv), lengths, jnp.int32(layer), table,
            interpret=True, pool_ks=jnp.pad(pages(ks), pad),
            pool_vs=jnp.pad(pages(vs), pad), bblock=bb)
        ref = decode_attend(q, kvc.dequantize(qk[layer], ks[layer]),
                            kvc.dequantize(qv[layer], vs[layer]), lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def _run_engine(cfg, params, serving, prompts, max_tokens=6):
    eng = Engine(cfg, params, serving)
    reqs = [eng.submit(Request(prompt_ids=list(p), max_tokens=max_tokens,
                               ignore_eos=True)) for p in prompts]
    for _ in range(10000):
        if not eng.step():
            break
    return [r.generated for r in reqs], eng


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_engine_int8_token_parity_across_backends(impl):
    """Same quantized math in both backends ⇒ identical tokens. (int8-vs-bf16
    token equality is NOT asserted anywhere: a tiny random model's near-
    uniform logits flip under quantization noise by design.)"""
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist() for n in (3, 9, 14)]
    base = ServingConfig(weights_dtype="bf16", max_decode_slots=4, max_cache_len=64,
                         prefill_buckets=(16,), dtype="float32",
                         kv_dtype="int8", attention_impl="xla",
                         prefix_cache=False)
    import dataclasses
    ref, _ = _run_engine(cfg, params, base, prompts)
    got, eng = _run_engine(
        cfg, params, dataclasses.replace(base, attention_impl=impl), prompts)
    assert got == ref
    assert all(len(g) == 6 for g in got)
    assert eng.cache["k"].dtype == jnp.int8


@pytest.mark.parametrize("dp,tp", [(2, 1), (1, 2), (2, 2)])
def test_engine_int8_mesh_token_parity(cpu_devices, dp, tp):
    """Mesh + int8 together: the shard_map'd quantized pool (pages over dp,
    KV heads over tp, the scale leaves with them) and the quantizing Pallas
    write kernel per shard — token parity with the single-device int8
    engine."""
    from aws_k8s_ansible_provisioner_tpu.config import MeshConfig
    from aws_k8s_ansible_provisioner_tpu.parallel import make_mesh

    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist() for n in (3, 9, 14)]
    base = ServingConfig(weights_dtype="bf16", max_decode_slots=4, max_cache_len=64,
                         prefill_buckets=(16,), dtype="float32",
                         kv_dtype="int8", attention_impl="pallas",
                         prefix_cache=False)
    ref, _ = _run_engine(cfg, params, base, prompts)
    mesh = make_mesh(MeshConfig(dp=dp, tp=tp), devices=jax.devices()[:dp * tp])
    eng = Engine(cfg, params, base, mesh=mesh)
    reqs = [eng.submit(Request(prompt_ids=list(p), max_tokens=6,
                               ignore_eos=True)) for p in prompts]
    for _ in range(10000):
        if not eng.step():
            break
    assert [r.generated for r in reqs] == ref


def test_engine_int8_prefix_hit_shares_scales():
    """A shared page carries its scale rows with its int8 rows: a prefix hit
    into the quantized pool serves the same tokens as a cold engine."""
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(6)
    seed = rng.integers(2, cfg.vocab_size, 40).tolist()
    ext = seed + rng.integers(2, cfg.vocab_size, 6).tolist()
    serving = ServingConfig(weights_dtype="bf16", max_decode_slots=4, max_cache_len=64,
                            prefill_buckets=(64,), dtype="float32",
                            kv_dtype="int8", attention_impl="xla",
                            prefix_cache=True, page_size=32)
    eng = Engine(cfg, params, serving)
    eng.submit(Request(prompt_ids=list(seed), max_tokens=2, ignore_eos=True))
    while eng.pending or any(s is not None for s in eng.slot_req) \
            or eng._chunk is not None:
        eng.step()
    r2 = eng.submit(Request(prompt_ids=list(ext), max_tokens=4,
                            ignore_eos=True))
    while eng.pending or any(s is not None for s in eng.slot_req) \
            or eng._chunk is not None:
        eng.step()
    assert eng.metrics.prefix_cache_hits.total() >= 1
    # cold engine on the same extended prompt must match
    cold, _ = _run_engine(cfg, params,
                          __import__("dataclasses").replace(
                              serving, prefix_cache=False),
                          [ext], max_tokens=4)
    assert r2.generated == cold[0]
