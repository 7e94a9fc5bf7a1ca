"""OLMoE (allenai/OLMoE-1B-7B-0125-Instruct) at a tiny size on the CPU: the
served block against the benchmark's plain reference, on LOGITS.

The reference (benchmark/reference/olmoe.py) is float32 at matmul precision
"highest", imports nothing from the program and routes on its own
activations. The served side is the code the step programs run: the paged
pool, the carry-path attends, ``model_forward_carry`` and ops/moe.py, in
bf16 with bf16 or int8 expert stacks from the benchmark's seeded maker.

Two precisions, two tolerances (|logit difference|, logits of std 0.64):

- **float32 activations** (the same trees, their bf16 leaves widened; int8
  stacks stay int8): the served mathematics IS the reference's, so every
  row of every case agrees to TOL_F32 = 5e-4 (measured 3e-6 to 4e-5: the
  orders of summation differ). Nothing wrong with the block survives that.
- **bf16 activations, as served**: every matmul, norm and top-k weight
  rounds to 8 bits of mantissa, and a row reads 0.01-0.05 off. Where the
  reference's own 2nd and 3rd router logits are within that rounding the
  served path may pick the other expert, and at top-2 of 8 the swapped
  experts weigh ~0.2 each (at the published top-8 of 64 the 8th weighs
  ~0.02: benchmark/weight_makers/olmoe.py), so such a row moves by tenths:
  about one row in ten here. So TOL_BF16 = 0.08 is held by the 80th
  percentile of the rows' maxima, not by the worst row. Each way of getting
  the block wrong — a per-head q/k norm, a renormalised top-k, a dropped
  expert — moves EVERY row, and ``test_tolerance_catches`` holds the MEDIAN
  row of each to at least 3 x TOL_BF16.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from benchlib import files  # noqa: E402

from aws_k8s_ansible_provisioner_tpu.config import (  # noqa: E402
    MODEL_REGISTRY, ServingConfig, tiny_olmoe)
from aws_k8s_ansible_provisioner_tpu.models import hf_loader  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.models.layers import (  # noqa: E402
    init_params, model_forward_carry)
from aws_k8s_ansible_provisioner_tpu.models.quant import (  # noqa: E402
    quantize_params)
from aws_k8s_ansible_provisioner_tpu.ops import moe  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.ops.attention import (  # noqa: E402
    make_decode_attend_carry_paged, make_mixed_attend_carry_paged,
    make_prefill_attend_paged_carry)
from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.serving import programs as pg  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.serving.engine import (  # noqa: E402
    Engine, Request)

TOL_F32, TOL_BF16 = 5e-4, 0.08
PS, PPS = 16, 4             # page size, pages per slot (64-row window)
CFG = tiny_olmoe()
MC = dataclasses.asdict(CFG)
MAKER = files.load_module("weight_makers", "olmoe")
REF = files.load_module("reference", "olmoe")
# The maker's sigma (0.02) is sized for a hidden width of 2,048, where a
# projection of an RMS-normed input has std 0.9 and the layers' writes
# dwarf the embedding. At this width 0.11 gives the same 0.9, so the
# attention and expert paths carry the logits here too; at 0.02 the
# embedding alone would, and no wrong block could fail.
SIGMA = 0.11


def _make(seed, quant):
    return MAKER.make(MC, seed, quant, sigma=SIGMA)


@pytest.fixture(scope="module", params=["bf16", "int8"])
def tree(request):
    return _make(26, request.param == "int8")


def _widen(tree):
    """The same numbers with float32 activations: bf16 leaves (norms,
    router, bf16 kernels) widened, int8 kernels and their scales as served
    (layers._embed_inputs takes the activation dtype from the norms)."""
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


def _close(got, want, f32):
    """The module docstring's two tolerances."""
    rows = np.abs(np.asarray(got, np.float32) - want).reshape(
        -1, want.shape[-1]).max(-1)
    return rows.max() < TOL_F32 if f32 else \
        np.quantile(rows, 0.8) < TOL_BF16


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(2, CFG.vocab_size, n).tolist()


def _ref_rows(tree, ids):
    """Reference logits: row j predicts the token after ids[:j + 1]."""
    return np.asarray(REF.logits(MC, tree, list(ids) + [0], len(ids)))


def _prefill(cfg, tree, pool, pages, ids, bucket=32):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(ids)] = ids
    attend = make_prefill_attend_paged_carry(jnp.asarray(pages, jnp.int32),
                                             jnp.int32(len(ids)))
    logits, pool = model_forward_carry(
        tree, cfg, jnp.asarray(toks),
        jnp.arange(bucket, dtype=jnp.int32)[None], pool, attend)
    return np.asarray(logits[0, :len(ids)], np.float32), pool


def _pool(cfg, tree):
    return kvp.init_pool(cfg, 2 * PPS + 1, PS,
                         tree["final_norm"]["weight"].dtype)


def _served_rows(cfg, tree, ids, n_prompt):
    """Prefill ids[:n_prompt] into the paged pool, then decode the rest one
    token at a time through the cache (teacher forcing): [len(ids), V]."""
    pool = _pool(cfg, tree)
    pages = list(range(PPS))
    rows, pool = _prefill(cfg, tree, pool, pages, ids[:n_prompt])
    table = jnp.asarray([pages], jnp.int32)
    out = [rows]
    for t in range(n_prompt, len(ids)):
        lens = jnp.asarray([t], jnp.int32)
        attend = make_decode_attend_carry_paged(lens, table, impl="xla")
        logits, pool = model_forward_carry(
            tree, cfg, jnp.asarray([[ids[t]]], jnp.int32), lens[:, None],
            pool, attend)
        out.append(np.asarray(logits[:, 0], np.float32))
    return np.concatenate(out)


@pytest.mark.parametrize("f32", [True, False], ids=["f32", "bf16"])
def test_prefill_then_paged_decode_logits_match_the_reference(tree, f32):
    ids = _ids(30)
    got = _served_rows(CFG, _widen(tree) if f32 else tree, ids, n_prompt=19)
    want = _ref_rows(tree, ids)
    assert got.shape == want.shape == (30, CFG.vocab_size)
    assert _close(got, want, f32)


def _mixed_rows(cfg, tree, live_ids, chunk_ids, C=16):
    """The mixed program's packed layout, built as mixed_step builds it:
    slot 0 decodes (its prompt already in the pool), slot 1 is the chunking
    slot (a dead passenger), slot 2 is idle, then C chunk rows of which
    len(chunk_ids) hold a token. Returns (decode row logits [V], chunk rows'
    logits [n, V], per-layer routing counts [L, 2])."""
    pool = _pool(cfg, tree)
    _, pool = _prefill(cfg, tree, pool, list(range(PPS)), live_ids[:-1])
    n, t = len(chunk_ids), len(live_ids) - 1
    table = jnp.asarray([list(range(PPS)), list(range(PPS, 2 * PPS)),
                         [2 * PPS] * PPS], jnp.int32)
    lengths = jnp.asarray([t, 0, 0], jnp.int32)
    is_p = jnp.arange(3) == 1
    crows = jnp.arange(C, dtype=jnp.int32)
    is_pad = crows >= n
    row_limits = jnp.concatenate([jnp.where(is_p, 0, lengths + 1),
                                  jnp.where(is_pad, 0, crows + 1)])
    row_map = jnp.concatenate([jnp.arange(3), jnp.full((C,), 1)])
    ptok = np.zeros(C, np.int32)
    ptok[:n] = chunk_ids
    packed = jnp.concatenate(
        [jnp.asarray([live_ids[-1], 0, 0], jnp.int32), jnp.asarray(ptok)])
    positions = jnp.concatenate([jnp.where(is_p, 0, lengths), crows])
    live = jnp.concatenate([jnp.asarray([True, False, False]), ~is_pad])
    attend = make_mixed_attend_carry_paged(
        jnp.where(is_p, -1, lengths), jnp.int32(0), jnp.int32(n), row_limits,
        table, row_map, impl="xla")
    with moe.routed_rows(live) as routing:
        logits, _ = model_forward_carry(tree, cfg, packed[None],
                                        positions[None], pool, attend)
    logits = np.asarray(logits[0], np.float32)
    return logits[0], logits[3:3 + n], np.asarray(routing["stats"])


@pytest.mark.parametrize("f32", [True, False], ids=["f32", "bf16"])
def test_mixed_layout_logits_match_the_reference(tree, f32):
    live_ids, chunk_ids = _ids(21, seed=1), _ids(11, seed=2)
    dec, chunk, stats = _mixed_rows(CFG, _widen(tree) if f32 else tree,
                                    live_ids, chunk_ids)
    assert _close(np.concatenate([dec[None], chunk]), np.concatenate(
        [_ref_rows(tree, live_ids)[-1:], _ref_rows(tree, chunk_ids)]), f32)
    # 12 live rows x top-2 = 24 routed rows a layer: the padding rows, the
    # dead passenger and the idle slot belong to no group
    E = CFG.num_experts
    assert stats.shape == (CFG.num_layers, 2)
    assert ((1 <= stats[:, 0]) & (stats[:, 0] <= E)).all()
    assert ((24 // E <= stats[:, 1]) & (stats[:, 1] <= 12)).all()


def _per_head_norm(cfg, tree):
    """Qwen3's form on OLMoE's weights: norm each head with its own slice."""
    layers = dict(tree["layers"])
    for name in ("q_norm", "k_norm"):
        layers[name] = {"weight": layers[name]["weight"][:, :cfg.head_dim]}
    return cfg.scaled(qk_norm_span="head"), dict(tree, layers=layers)


WRONG = {
    "per-head-qk-norm": _per_head_norm,
    "renormalised-top-k": lambda c, t: (c.scaled(norm_topk_prob=True), t),
    "dropped-expert": lambda c, t: (c.scaled(num_experts_per_tok=1), t),
}


@pytest.mark.parametrize("how", sorted(WRONG))
def test_tolerance_catches(tree, how):
    ids = _ids(30)
    cfg, wrong_tree = WRONG[how](CFG, tree)
    got = _served_rows(cfg, wrong_tree, ids, n_prompt=19)
    rows = np.abs(got - _ref_rows(tree, ids)).max(-1)
    assert np.median(rows) > 3 * TOL_BF16, how


@pytest.mark.parametrize("quant", [True, False], ids=["int8", "bf16"])
def test_seeded_tree_has_the_programs_layout(quant):
    def theirs():
        p = init_params(CFG, jax.random.PRNGKey(0), jnp.bfloat16)
        return quantize_params(p, CFG) if quant else p

    def flat(t):
        return {jax.tree_util.keystr(k): (tuple(v.shape), str(v.dtype))
                for k, v in jax.tree_util.tree_leaves_with_path(t)}

    want = flat(jax.eval_shape(theirs))
    got = MAKER.make(MC, 5, quant)
    assert flat(got) == want
    assert {"".join(f"['{p}']" for p in k): v
            for k, v in MAKER.tree_spec(MC, quant).items()} == want
    again = MAKER.make(MC, 5, quant)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(got), jax.tree.leaves(again)))
    other = MAKER.make(MC, 6, quant)
    assert not bool((got["layers"]["w_up"]["kernel"]
                     == other["layers"]["w_up"]["kernel"]).all())


def test_router_spread_is_what_the_maker_states():
    """Router logits of std ~2, so the top-k weights differ: they hold about
    two thirds of the mass and their squares sum to ~0.12 (the maker's
    docstring)."""
    cfg = tiny_olmoe(num_experts=64, num_experts_per_tok=8, hidden_size=256,
                     num_heads=4, num_kv_heads=4, head_dim=64)
    tree = MAKER.make(dataclasses.asdict(cfg), 3, False)
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 256), jnp.float32)
    logits = x @ tree["layers"]["router"]["kernel"][0].astype(jnp.float32)
    assert 1.7 < float(logits.std()) < 2.3
    w, _ = jax.lax.top_k(jax.nn.softmax(logits, -1), 8)
    assert 0.5 < float(w.sum(-1).mean()) < 0.8
    assert 0.08 < float((w * w).sum(-1).mean()) < 0.2


# -- the normal path: registry, loader, engine -------------------------------


def test_registry_entry_equals_the_benchmarks_configuration_file():
    cfg = files.load_json(os.path.join(files.BENCH_DIR, "configs",
                                       "olmoe-1b-7b-int8.json"))
    reg = MODEL_REGISTRY[cfg["registry_name"]]
    assert reg == type(reg)(**cfg["model_config"])
    hf = cfg["hf_config"]
    assert {k: cfg[k] for k in hf} == hf      # the published keys, top level
    assert (reg.num_experts, reg.num_experts_per_tok,
            reg.moe_intermediate_size, reg.norm_topk_prob) == (
        hf["num_experts"], hf["num_experts_per_tok"],
        hf["intermediate_size"], hf["norm_topk_prob"])
    assert reg.head_dim * reg.num_heads == reg.hidden_size
    assert reg.qk_norm and reg.qk_norm_span == "projection"
    assert reg.moe_impl == "ragged"
    bench = files.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "olmoe-1b-7b-int8")
    assert entry["source"] == cfg["source"] and entry["reduced"] == []


def _olmoe_state_dict(cfg, rng):
    """HF ``OlmoeForCausalLM`` names and shapes ([out, in] Linears)."""
    H, q, kv = cfg.hidden_size, cfg.q_size, cfg.kv_size
    Im, E = cfg.moe_intermediate_size, cfg.num_experts
    sd = {"model.embed_tokens.weight": (cfg.vocab_size, H),
          "model.norm.weight": (H,), "lm_head.weight": (cfg.vocab_size, H)}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        sd.update({
            p + "input_layernorm.weight": (H,),
            p + "post_attention_layernorm.weight": (H,),
            p + "self_attn.q_proj.weight": (q, H),
            p + "self_attn.k_proj.weight": (kv, H),
            p + "self_attn.v_proj.weight": (kv, H),
            p + "self_attn.o_proj.weight": (H, q),
            p + "self_attn.q_norm.weight": (q,),
            p + "self_attn.k_norm.weight": (kv,),
            p + "mlp.gate.weight": (E, H)})
        for e in range(E):
            sd[p + f"mlp.experts.{e}.gate_proj.weight"] = (Im, H)
            sd[p + f"mlp.experts.{e}.up_proj.weight"] = (Im, H)
            sd[p + f"mlp.experts.{e}.down_proj.weight"] = (H, Im)
    return {k: rng.standard_normal(s).astype(np.float32) * 0.05
            for k, s in sd.items()}


def test_hf_loader_maps_an_olmoe_checkpoint(tmp_path):
    import json

    hf = {"model_type": "olmoe", "vocab_size": 128, "hidden_size": 64,
          "intermediate_size": 32, "num_hidden_layers": 2,
          "num_attention_heads": 4, "num_key_value_heads": 4,
          "max_position_embeddings": 128, "rope_theta": 10000.0,
          "rms_norm_eps": 1e-5, "num_experts": 8, "num_experts_per_tok": 2,
          "norm_topk_prob": False, "tie_word_embeddings": False,
          "eos_token_id": 1, "clip_qkv": None, "_name_or_path": "x/olmoe"}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = hf_loader.config_from_hf_dir(str(tmp_path))
    assert cfg == tiny_olmoe(name="x/olmoe", hf_repo="x/olmoe")
    (tmp_path / "config.json").write_text(json.dumps(dict(hf, clip_qkv=8.0)))
    with pytest.raises(ValueError, match="clip_qkv"):
        hf_loader.config_from_hf_dir(str(tmp_path))

    sd = _olmoe_state_dict(cfg, np.random.default_rng(0))
    params = hf_loader.convert_state_dict(cfg, sd, dtype=jnp.float32)
    want = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    assert jax.tree.map(lambda a: a.shape, params) == \
        jax.tree.map(lambda a: a.shape, want)
    lay = params["layers"]
    assert lay["q_norm"]["weight"].shape == (2, cfg.q_size)
    assert lay["k_norm"]["weight"].shape == (2, cfg.kv_size)
    np.testing.assert_array_equal(
        lay["router"]["kernel"][1], sd["model.layers.1.mlp.gate.weight"].T)
    np.testing.assert_array_equal(
        lay["w_down"]["kernel"][1, 5],
        sd["model.layers.1.mlp.experts.5.down_proj.weight"].T)
    np.testing.assert_array_equal(
        lay["q_norm"]["weight"][0],
        sd["model.layers.0.self_attn.q_norm.weight"])


@pytest.fixture(scope="module")
def engine_run():
    """Two requests through the Engine on the ragged pipeline: the second
    arrives under the first's live stream, so it is admitted by mixed_step.
    Returns (engine, [requests], dispatch records)."""
    from aws_k8s_ansible_provisioner_tpu.serving import flightrec

    params = _make(26, True)
    eng = Engine(CFG, params, ServingConfig(
        max_decode_slots=4, max_cache_len=64, prefill_buckets=(16, 32),
        dtype="bfloat16", weights_dtype="int8", prefix_cache=False,
        decode_horizon=2, page_size=16, decode_pipeline=1,
        ragged_attention=1, attention_impl="xla"))
    seen = []
    orig = flightrec.record

    def tap(*a, **rec):
        if a[0] == "dispatch":
            seen.append(dict(rec))
        return orig(*a, **rec)

    flightrec.record = tap
    try:
        a = eng.submit(Request(prompt_ids=_ids(12, 3), max_tokens=14,
                               ignore_eos=True, logprobs=0))
        for _ in range(3):
            eng.step()
        b = eng.submit(Request(prompt_ids=_ids(20, 4), max_tokens=6,
                               ignore_eos=True, logprobs=0))
        for _ in range(10000):
            if not eng.step():
                break
    finally:
        flightrec.record = orig
    return eng, [a, b], seen


def test_engine_serves_olmoe_and_mixed_step_agrees_with_the_reference(
        engine_run):
    eng, reqs, seen = engine_run
    assert [len(r.generated) for r in reqs] == [14, 6]
    assert any(r["program"] == "mixed_step" for r in seen)
    for r in reqs:
        ids = r.prompt_ids + r.generated
        rows = jax.nn.log_softmax(
            jnp.asarray(_ref_rows(eng.params, ids)), axis=-1)
        rows = np.asarray(rows)[len(r.prompt_ids) - 1:-1]
        ref_lp = rows[np.arange(len(r.generated)), r.generated]
        served = np.asarray([lp[0] for lp in r.logprob_data], np.float32)
        assert np.quantile(np.abs(served - ref_lp), 0.8) < TOL_BF16
        # greedy: the served token is (within bf16) the reference's argmax
        assert np.quantile(rows.max(-1) - ref_lp, 0.8) < TOL_BF16


def test_dispatch_records_and_metrics_carry_the_routing_counts(engine_run):
    eng, reqs, seen = engine_run
    k, E = CFG.num_experts_per_tok, CFG.num_experts
    moe_recs = [r for r in seen if "moe_rows" in r]
    assert {r["program"] for r in moe_recs} == {"decode_steps", "mixed_step"}
    assert all("moe_rows" in r for r in seen
               if r["program"] in ("decode_steps", "mixed_step"))
    for r in moe_recs:
        # live rows only: idle slots and the chunk's padding are not routed
        assert r["moe_rows"] == k * (r["horizon"] * r["active"]
                                     + r.get("chunk_n", 0))
        assert 1 <= r["moe_experts_hit"] <= min(E, max(r["moe_rows"], 1))
        assert r["moe_group_max"] * E >= r["moe_rows"] // r["horizon"]
        assert r["moe_group_max"] <= r["active"] + r.get("chunk_n", 0)
    m = eng.metrics
    assert m.moe_routed_rows.total() == sum(r["moe_rows"] for r in moe_recs)
    assert m.moe_forward_passes.total() == sum(r["horizon"]
                                               for r in moe_recs)
    hit = m.moe_experts_hit.total() / m.moe_forward_passes.total()
    assert 1 <= hit <= E
    text = m.registry.render()
    for name in ("tpu_serve_moe_routed_rows_total",
                 "tpu_serve_moe_experts_hit_total",
                 "tpu_serve_moe_forward_passes_total",
                 "tpu_serve_moe_group_rows_max"):
        assert name in text


def test_dense_models_step_programs_take_no_live_operand():
    from aws_k8s_ansible_provisioner_tpu.config import tiny_qwen3

    cfg = tiny_qwen3()
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32),
                 ServingConfig(max_decode_slots=2, max_cache_len=64,
                               prefill_buckets=(16,), dtype="float32",
                               weights_dtype="bf16", prefix_cache=False))
    assert eng._live_rows([0, 1]) is None
    assert pg._moe_summary(None) is None
    eng.submit(Request(prompt_ids=_ids(5), max_tokens=3, ignore_eos=True))
    for _ in range(100):
        if not eng.step():
            break
    assert eng.metrics.moe_forward_passes.total() == 0
    assert "tpu_serve_moe_routed_rows_total{" not in \
        eng.metrics.registry.render()
