"""Weights-only int8 quantization (models/quant.py): HF logit parity within
quantization tolerance, engine integration, tp-mesh parity, and the HBM
claim the bench roofline consumes.

VERDICT r3 next #7: below batch ~64 the weight stream dominates bytes/token;
int8 weights halve that term. The vLLM engine inside the reference's serving
pods exposes the same capability as ``--quantization`` (SURVEY.md §2.2 row 1).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aws_k8s_ansible_provisioner_tpu.config import (MeshConfig, ServingConfig,
                                                    tiny_qwen3)
from aws_k8s_ansible_provisioner_tpu.models import (convert_state_dict,
                                                    model_forward)
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.models.quant import (quantize_params,
                                                          weights_quantized)
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request


def test_quantized_logits_close_to_hf():
    """Quantized JAX logits vs the HF torch reference: within the error
    budget weights-only int8 buys (per-weight error <= 1/254), top-1
    agreement stays near-perfect. This is the 'HF logit-parity tolerance
    test' of VERDICT r3 next #7."""
    torch = pytest.importorskip("torch")
    from tests.test_model_parity import _hf_qwen3

    cfg = tiny_qwen3()
    model = _hf_qwen3(cfg)
    params = convert_state_dict(cfg, dict(model.state_dict()),
                                dtype=jnp.float32)
    qparams = quantize_params(params, cfg)
    assert weights_quantized(qparams) and not weights_quantized(params)

    rng = np.random.default_rng(0)
    B, T = 2, 17
    tokens = rng.integers(0, cfg.vocab_size, (B, T))
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.float().numpy()
    positions = np.broadcast_to(np.arange(T), (B, T))
    logits, _ = model_forward(qparams, cfg, jnp.asarray(tokens, jnp.int32),
                              jnp.asarray(positions, jnp.int32))
    got = np.asarray(logits, np.float32)

    # normalized error bound: int8 noise accumulates over layers but must
    # stay a small fraction of the logit dynamic range
    err = np.max(np.abs(got - ref)) / max(1e-6, np.max(np.abs(ref)))
    assert err < 0.06, f"quantized logits off by {err:.3f} of logit range"
    agree = (got.argmax(-1) == ref.argmax(-1)).mean()
    assert agree >= 0.9, f"top-1 agreement {agree:.2f}"


def test_quantized_weight_bytes_halved():
    """The roofline input: the quantized tree must stream roughly half the
    bytes (int8 kernels + small f32 scales vs bf16)."""
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    qparams = quantize_params(params, cfg)
    full = sum(x.nbytes for x in jax.tree.leaves(params))
    quant = sum(x.nbytes for x in jax.tree.leaves(qparams))
    assert quant < 0.62 * full, f"{quant}/{full} bytes"


def test_quantized_pspecs_match_structure():
    """param_pspecs(quant_weights=True) must mirror quantize_params' tree so
    mesh placement (shard_params) maps every leaf — including scales."""
    from jax.sharding import PartitionSpec as P

    from aws_k8s_ansible_provisioner_tpu.parallel.sharding import param_pspecs

    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    qparams = quantize_params(params, cfg)
    specs = param_pspecs(cfg, quant_weights=True)
    # tree_map raises on structure mismatch
    jax.tree.map(lambda a, s: None, qparams, specs,
                 is_leaf=lambda x: isinstance(x, P))


def _run(engine, prompts, max_tokens=10):
    reqs = [engine.submit(Request(prompt_ids=list(p), max_tokens=max_tokens,
                                  ignore_eos=True)) for p in prompts]
    for _ in range(10000):
        if not engine.step():
            break
    return [r.generated for r in reqs]


def test_quantized_engine_generates_and_is_deterministic():
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    serving = ServingConfig(max_decode_slots=4, max_cache_len=64,
                            prefill_buckets=(16,), dtype="float32",
                            weights_dtype="int8", prefix_cache=False)
    prompts = [[3, 5, 7], [11, 2, 9, 4]]
    a = _run(Engine(cfg, params, serving), prompts)
    b = _run(Engine(cfg, params, serving), prompts)
    assert a == b
    assert all(len(g) == 10 for g in a)
    # quantization actually happened inside the engine
    eng = Engine(cfg, params, serving)
    assert weights_quantized(eng.params)


def test_prequantized_tree_not_requantized():
    """An already-int8 tree handed to an int8 engine must pass through
    untouched: re-quantizing would treat the int8 kernels as values and
    overwrite the scale leaves — silent weight corruption (advisor r4)."""
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    q = quantize_params(params, cfg)
    serving = ServingConfig(max_decode_slots=4, max_cache_len=64,
                            prefill_buckets=(16,), dtype="float32",
                            weights_dtype="int8", prefix_cache=False)
    prompts = [[3, 5, 7], [11, 2, 9, 4]]
    from_fp = _run(Engine(cfg, params, serving), prompts)
    from_q = _run(Engine(cfg, q, serving), prompts)
    assert from_fp == from_q


def test_quantized_under_tp_mesh_token_parity(cpu_devices):
    """Same quantized weights, tp=2-sharded vs single-device: the scale
    leaves shard with their kernels' out axes (parallel/sharding.py) and the
    streams must be token-identical."""
    from aws_k8s_ansible_provisioner_tpu.parallel.mesh import make_mesh

    cfg = tiny_qwen3(num_heads=4, num_kv_heads=2, vocab_size=256)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    serving = ServingConfig(max_decode_slots=4, max_cache_len=64,
                            prefill_buckets=(8, 16), dtype="float32",
                            weights_dtype="int8")
    rng = np.random.default_rng(4)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist() for n in (3, 7, 12)]

    expected = _run(Engine(cfg, params, serving), prompts, max_tokens=8)
    mesh = make_mesh(MeshConfig(dp=1, tp=2), devices=jax.devices("cpu"))
    got = _run(Engine(cfg, params, serving, mesh=mesh), prompts, max_tokens=8)
    assert got == expected

    # and the sharded scale really is distributed: lm-head/embed scales are
    # vocab-sharded over tp
    eng = Engine(cfg, params, serving, mesh=mesh)
    s = eng.params["embed"]["scale"]
    assert s.addressable_shards[0].data.shape[0] == cfg.vocab_size // 2


def test_quantized_greedy_stream_mostly_tracks_fp():
    """Not bit-parity (quantization legitimately perturbs near-ties) but the
    quantized greedy stream must track the fp stream closely on a tiny
    model — a layout/scale bug diverges immediately and completely."""
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    base = ServingConfig(weights_dtype="bf16", max_decode_slots=2, max_cache_len=64,
                         prefill_buckets=(16,), dtype="float32",
                         prefix_cache=False)
    q = dataclasses.replace(base, weights_dtype="int8")
    prompts = [[5, 9, 2, 8]]
    fp = _run(Engine(cfg, params, base), prompts, max_tokens=12)[0]
    qs = _run(Engine(cfg, params, q), prompts, max_tokens=12)[0]
    match = sum(a == b for a, b in zip(fp, qs)) / len(fp)
    assert match >= 0.5, f"quantized stream diverged immediately: {match:.2f}"


def test_host_and_device_quantization_agree():
    """The host (numpy, leaf-wise — used before mesh sharding so no chip
    holds the full unquantized tree) and jitted device paths must produce
    identical int8 kernels and scales."""
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    dev = quantize_params(params, cfg, host=False)
    host = quantize_params(params, cfg, host=True)
    flat_d = jax.tree.leaves(dev)
    flat_h = jax.tree.leaves(host)
    assert len(flat_d) == len(flat_h)
    for a, b in zip(flat_d, flat_h):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        if a.dtype == np.int8:
            # XLA vs numpy reduce/divide differ in the last ulp of the
            # scale, which can flip a handful of exactly-half roundings by
            # ±1 — semantically identical quantizations
            diff = np.abs(a.astype(np.int32) - b.astype(np.int32))
            assert diff.max(initial=0) <= 1
            assert (diff > 0).mean() < 1e-3
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5)


def test_all_features_compose():
    """Kitchen sink: int8 KV cache + int8 weights + speculative
    decoding + prefix cache in ONE engine — the full shipped-default stack
    plus every bandwidth lever — must generate the same stream as the same
    quantized engine with each subsystem individually disabled (the
    quantized PLAIN engine is the oracle; int8 weights legitimately perturb
    streams vs fp, but the other subsystems must be invisible)."""
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    oracle_cfg = ServingConfig(max_decode_slots=4, max_cache_len=128,
                               prefill_buckets=(32,), dtype="float32",
                               weights_dtype="int8", prefix_cache=False)
    sink_cfg = dataclasses.replace(oracle_cfg, page_size=32,
                                   kv_dtype="int8", spec_decode=True,
                                   spec_k=4, spec_ngram=3, prefix_cache=True,
                                   attention_impl="pallas")
    rng = np.random.default_rng(11)
    pat = rng.integers(2, cfg.vocab_size, 4).tolist()
    prompts = [pat * 4, rng.integers(2, cfg.vocab_size, 9).tolist()]

    oracle = _run(Engine(cfg, params, oracle_cfg), prompts, max_tokens=16)
    sink_eng = Engine(cfg, params, sink_cfg)
    assert weights_quantized(sink_eng.params)
    got = _run(sink_eng, prompts, max_tokens=16)
    assert got == oracle


def test_quantized_moe_logits_close_to_fp():
    """MoE expert kernels quantize with per-(expert, out-channel) scales;
    the exact ragged path's logits must stay within the int8 error budget of
    the fp forward (experts are ~95% of Qwen3-30B-A3B's weight bytes — the
    whole point of quantizing them)."""
    from aws_k8s_ansible_provisioner_tpu.config import tiny_qwen3_moe

    cfg = tiny_qwen3_moe()
    params = init_params(cfg, jax.random.PRNGKey(2), jnp.float32)
    qparams = quantize_params(params, cfg)
    assert "scale" in qparams["layers"]["w_gate"]
    assert qparams["layers"]["w_gate"]["kernel"].dtype == jnp.int8

    rng = np.random.default_rng(5)
    B, T = 2, 9
    tokens = rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
    positions = np.broadcast_to(np.arange(T, dtype=np.int32), (B, T))
    ref, _ = model_forward(params, cfg, jnp.asarray(tokens),
                           jnp.asarray(positions))
    got, _ = model_forward(qparams, cfg, jnp.asarray(tokens),
                           jnp.asarray(positions))
    ref, got = np.asarray(ref, np.float32), np.asarray(got, np.float32)
    err = np.max(np.abs(got - ref)) / max(1e-6, np.max(np.abs(ref)))
    assert err < 0.06, f"quantized MoE logits off by {err:.3f}"
    agree = (got.argmax(-1) == ref.argmax(-1)).mean()
    assert agree >= 0.9, f"top-1 agreement {agree:.2f}"


def test_quantized_moe_gshard_matches_ragged(cpu_devices):
    """Quantized gshard (the ep-sharded distributed path) vs quantized exact
    ragged on the same weights: the dispatch einsums' scale fold must not
    change the math (ample capacity → no drops)."""
    from jax.sharding import NamedSharding

    from aws_k8s_ansible_provisioner_tpu.config import tiny_qwen3_moe
    from aws_k8s_ansible_provisioner_tpu.parallel.mesh import make_mesh
    from aws_k8s_ansible_provisioner_tpu.parallel.sharding import (
        param_shardings, tokens_pspec)

    cfg = tiny_qwen3_moe(num_heads=4, num_kv_heads=2,
                         moe_capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    qparams = quantize_params(params, cfg)

    rng = np.random.default_rng(6)
    B, T = 2, 8
    tokens = rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
    positions = np.broadcast_to(np.arange(T, dtype=np.int32), (B, T))
    ref, _ = model_forward(qparams, cfg.scaled(moe_impl="ragged"),
                           jnp.asarray(tokens), jnp.asarray(positions))

    mesh = make_mesh(MeshConfig(dp=1, ep=2, tp=2),
                     devices=jax.devices("cpu")[:4])
    shardings = param_shardings(mesh, cfg, quant_weights=True)
    sharded = jax.tree.map(jax.device_put, qparams, shardings)
    gcfg = cfg.scaled(moe_impl="gshard")
    fwd = jax.jit(lambda p, t, pos: model_forward(p, gcfg, t, pos)[0],
                  in_shardings=(shardings,
                                NamedSharding(mesh, tokens_pspec()),
                                NamedSharding(mesh, tokens_pspec())))
    got = fwd(sharded, jnp.asarray(tokens), jnp.asarray(positions))
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(ref))))
    assert err < 1e-3, f"ep-sharded quantized MoE diverged: max err {err}"


def test_quantized_moe_engine_generates():
    """MoE + int8 weights through the full serving engine (the ragged
    expert path inside the fused decode scan, expert scales gathered per
    sorted row): generates the full budget and matches its own rerun."""
    from aws_k8s_ansible_provisioner_tpu.config import tiny_qwen3_moe

    cfg = tiny_qwen3_moe()
    params = init_params(cfg, jax.random.PRNGKey(2), jnp.float32)
    serving = ServingConfig(max_decode_slots=2, max_cache_len=64,
                            prefill_buckets=(16,), dtype="float32",
                            weights_dtype="int8", prefix_cache=False)
    prompts = [[4, 9, 2], [7, 3, 5, 1]]
    a = _run(Engine(cfg, params, serving), prompts, max_tokens=8)
    b = _run(Engine(cfg, params, serving), prompts, max_tokens=8)
    assert a == b and all(len(g) == 8 for g in a)
