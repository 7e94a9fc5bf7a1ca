"""The Trinity list (window layers that rotate and see the last 8 keys, full
layers without positions, two leading dense FFNs and four routed ones with a
shared expert, norms on both sides of each branch, ``route_scale``) at a tiny
size on the CPU: hidden 64, 4 heads / 2 KV heads of 16, page 8, window 8, the
list ``w w | w g w g`` (dense | routed), 8 experts top-2.

The reference (benchmark/reference/trinity_mini.py) is float32 at matmul
precision "highest", attends every query against every key under the mask,
computes every expert for every token, imports nothing from the program and
routes on its own activations. The served side is the code the step programs
run: the paged pool with ONE set of leaves, one table and one page inventory a
KIND of attending layer, ``model_forward_carry`` over runs of equal (kind,
FFN), the paged kernels (interpret mode), ops/moe.py.

Tolerances, LOGITS of std 0.62. With float32 activations the served
mathematics IS the reference's — pages for a dense sequence, the order of
summation, the every-expert form differ — so every row agrees to TOL_F32 =
5e-4 (measured 3e-6 to 6e-6: in float32 no near-tie flips a choice between
the two). In bfloat16 (activations; the weights widened exactly) the MEDIAN
row of the forward pass sits within TOL_BF16 = 0.25 of it (measured 0.03-0.18
over three seeds) — but the worst row reads 0.36-0.82: with 2 of 8 experts a
token, a 2nd/3rd score that bfloat16 and float32 order differently swaps
HALF of a layer's routed sum (at the served size one of eight). Each
mechanism left out moves the worst row by 1 to 3 (``test_tolerance_catches``).
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
    MODEL_REGISTRY, ServingConfig, tiny_trinity)
from aws_k8s_ansible_provisioner_tpu.models import layers as L  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.models import parts  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.models.quant import (  # noqa: E402
    quantize_params, weights_quantized)
from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.ops import moe  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.serving import (  # noqa: E402
    metrics as metrics_mod)
from aws_k8s_ansible_provisioner_tpu.serving.engine import (  # noqa: E402
    Engine, Request)

TOL_F32, TOL_BF16 = 5e-4, 0.25
PS, WINDOW, CHUNK = 8, 8, 32
CFG = tiny_trinity()
MC = dataclasses.asdict(CFG)
MAKER = files.load_module("weight_makers", "trinity_mini")
REF = files.load_module("reference", "trinity_mini")


def _widen(tree):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


@pytest.fixture(scope="module", params=["bf16", "int8"])
def tree(request):
    """Seeded weights, float32 activations (int8 kernels stay int8)."""
    return _widen(_make(32, request.param == "int8"))


def _make(seed, quant):
    """The benchmark's maker as the cell serves it: the routed branches as
    large as the others, a top-heavy router, a non-zero selection bias — a
    mechanism of the routed FFN left out has to show here."""
    return MAKER.make(MC, seed, quant)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(2, CFG.vocab_size, n).tolist()


def _forward(tree, ids, cfg=CFG):
    with jax.default_matmul_precision("highest"):
        logits, _ = L.model_forward(tree, cfg, jnp.asarray([ids]),
                                    jnp.arange(len(ids))[None])
    return np.asarray(logits[0], np.float32)


# -- (a) the forward pass -----------------------------------------------------


def test_full_forward_matches_the_reference(tree):
    ids = _ids(40)
    ref = np.asarray(REF.logits(MC, tree, ids, 39))
    assert 0.4 < ref.std() < 0.9
    assert np.abs(_forward(tree, ids)[:-1] - ref).max() < TOL_F32


def test_the_forward_pass_in_bfloat16_stays_inside_its_tolerance():
    tree = _make(32, False)
    ids = _ids(40, 1)
    ref = np.asarray(REF.logits(MC, _widen(tree), ids, 39))
    logits, _ = L.model_forward(tree, CFG, jnp.asarray([ids]),
                                jnp.arange(40)[None])
    rows = np.abs(np.asarray(logits[0].astype(jnp.float32))[:-1]
                  - ref).max(-1)
    assert 1e-3 < np.median(rows) < TOL_BF16 and rows.max() < 1.5


WRONG = {
    "the window ignored in w layers": dict(wrong="no_window"),
    "RoPE applied in g layers": dict(wrong="rope_in_full"),
    "route_scale left out": dict(wrong="route_scale_1"),
    "float8 activations": dict(lower="act"),
}


@pytest.mark.parametrize("how", sorted(WRONG))
def test_tolerance_catches(tree, how):
    """Each control of the reference is another model: far outside both
    tolerances at every one of the last 16 rows' worst."""
    ids = _ids(40, 2)
    ref = np.asarray(REF.logits(MC, tree, ids, 16))
    off = np.asarray(REF.forward(MC, tree, ids, 16, **WRONG[how])[0])
    assert np.abs(off - ref).max() > 2 * TOL_BF16


def test_the_program_without_a_mechanism_is_outside_the_tolerance(tree):
    """The same from the program's side: a config that drops the window,
    rotates the full layers, or leaves the scale out is caught."""
    ids = _ids(40, 2)
    ref = np.asarray(REF.logits(MC, tree, ids, 39))
    for over in (dict(sliding_window=4096), dict(attn_use_rope=True),
                 dict(route_scale=1.0), dict(embed_scale=False)):
        got = _forward(tree, ids, CFG.scaled(**over))[:-1]
        assert np.abs(got - ref).max() > 2 * TOL_BF16, over


def test_routed_ffn_with_scale_and_selection_bias_is_the_references(tree):
    """One routed layer's FFN alone: sigmoid scores, top-2 by score + a
    NON-ZERO bias (which the maker seeds), renormalised, x 2.826, beside the
    shared expert — and the bias and the scale each change the answer."""
    fp = jax.tree.map(lambda a: a[1], tree["layers"]["ffn_moe"])
    assert float(jnp.abs(fp["router"]["bias"]).max()) > 0.01
    m = jax.random.normal(jax.random.PRNGKey(3), (1, 24, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(L._mlp(CFG, m, fp)[0])
        want, idx = REF._routed(MC, m[0], fp, None, "")
        no_scale, _ = REF._routed(MC, m[0], fp, None, "route_scale_1")
        zero = {**fp, "router": {**fp["router"],
                                 "bias": 0 * fp["router"]["bias"]}}
        _, idx0 = REF._routed(MC, m[0], zero, None, "")
    assert np.abs(got - np.asarray(want)).max() < 1e-4
    assert np.abs(got - np.asarray(no_scale)).max() > 0.1
    assert bool((np.sort(np.asarray(idx)) != np.sort(np.asarray(idx0))).any())
    w, _ = moe.route(CFG, m[0], fp["router"]["kernel"], fp["router"]["bias"])
    assert np.allclose(np.asarray(w).sum(-1), CFG.route_scale, atol=1e-4)


# -- (b) the paged path: two inventories, pages released ----------------------


def _params(seed=32):
    return _widen(_make(seed, False))


def _engine(params, **over):
    kw = dict(max_decode_slots=4, max_cache_len=256, prefill_buckets=(16, 32),
              dtype="float32", weights_dtype="bf16", prefix_cache=True,
              decode_horizon=2, page_size=PS, decode_pipeline=1,
              ragged_attention=1, attention_impl="xla",
              kv_host_tier_bytes=0, derived_seed=7, prefill_chunk=CHUNK)
    kw.update(over)
    return Engine(CFG, params, ServingConfig(**kw))


def _drain(eng, each=None):
    for _ in range(10000):
        if not eng.step():
            return
        if each is not None:
            each()
    raise AssertionError("engine did not drain")


def _two_streams(eng, each=None):
    """A 90-token prompt (three chunks of 32: eleven windows) arrives under
    a live stream that decodes past seven windows."""
    a = eng.submit(Request(prompt_ids=_ids(20, 3), max_tokens=60,
                           ignore_eos=True, logprobs=0))
    for _ in range(3):
        eng.step()
    b = eng.submit(Request(prompt_ids=_ids(90, 4), max_tokens=12,
                           ignore_eos=True, logprobs=0))
    _drain(eng, each)
    return a, b


def _ref_logprobs(params, r):
    ids = r.prompt_ids + r.generated
    rows = REF.logprobs(MC, params, ids, len(r.generated))
    return rows, rows[np.arange(len(r.generated)), r.generated]


@pytest.fixture(scope="module", params=["xla", "pallas"])
def mixed_run(request):
    from aws_k8s_ansible_provisioner_tpu.serving import flightrec

    params = _params()
    eng = _engine(params, attention_impl=request.param)
    seen, held, orig = [], [], flightrec.record

    def tap(*a, **rec):
        if a[0] == "dispatch":
            seen.append(dict(rec))
        return orig(*a, **rec)

    flightrec.record = tap
    try:
        reqs = _two_streams(
            eng, lambda: held.append(max(map(len, eng._slot_wpages))))
    finally:
        flightrec.record = orig
    pool = metrics_mod.window_pool
    return params, eng, reqs, seen, held, {
        "released": pool.released.total(), "slot_peak": pool.slot_peak.value(),
        "in_use_peak": pool.in_use_peak.value(),
        "unreleased": pool.unreleased_at_peak.value()}


def test_prefill_then_decode_past_several_windows_is_the_references(
        mixed_run):
    """prefill_step, three chunks of mixed_step beside a live row, decode
    steps: both streams are the reference's, with pages of the window
    layers released on the way (with ``pallas`` the kernels under their
    window names, one table row a slot in the ragged one)."""
    params, eng, reqs, seen, held, pool = mixed_run
    assert [r["chunk_n"] for r in seen if r["program"] == "mixed_step"] \
        == [32, 32, 26]
    assert pool["released"] >= 15
    for r in reqs:
        rows, ref_lp = _ref_logprobs(params, r)
        served = np.asarray([lp[0] for lp in r.logprob_data], np.float32)
        assert np.abs(served - ref_lp).max() < TOL_F32
        assert (rows.max(-1) - ref_lp).max() < TOL_F32


def test_a_slots_window_pages_stay_bounded_while_its_context_grows_tenfold():
    """20 -> 240 tokens: the slot never holds more than window + two decode
    horizons + a page of the window layers, the full layers' run grows with
    the context, and the chunking slot of ``mixed_run`` never more than
    window + chunk + a page."""
    eng = _engine(_params())
    r = eng.submit(Request(prompt_ids=_ids(20, 5), max_tokens=220,
                           ignore_eos=True))
    held, full = [], []
    _drain(eng, lambda: (held.append(len(eng._slot_wpages[0])),
                         full.append(len(eng._slot_pages[0]))))
    assert len(r.generated) == 220
    assert max(held) <= -(-(WINDOW + 2 * 2) // PS) + 1 == eng._win_slot_pages
    assert max(full) == -(-240 // PS)
    assert eng.win_allocator.pages_in_use == 0


def test_the_chunking_slot_holds_window_plus_chunk_plus_a_page(mixed_run):
    *_, held, pool = mixed_run
    bound = (WINDOW + CHUNK) // PS + 1
    assert max(held) <= bound and pool["slot_peak"] <= bound
    assert 0 < pool["in_use_peak"] < pool["unreleased"]


def test_a_stream_after_releases_is_the_stream_with_nothing_released(
        monkeypatch):
    """The same model, the same requests, served once as it is and once by
    an engine that gives no page back (every slot a whole run of the window
    inventory): token for token the same streams."""
    params = _params()
    a, b = _two_streams(_engine(params))
    cover = Engine._win_cover
    monkeypatch.setattr(kvp, "window_inventory",
                        lambda cfg, slots, pps, *_: (pps, slots * pps + 1))
    monkeypatch.setattr(Engine, "_win_cover",
                        lambda self, slot, n, upto: cover(self, slot, 0,
                                                          upto))
    before = metrics_mod.window_pool.released.total()
    assert before >= 15
    keep = _engine(params)
    a2, b2 = _two_streams(keep)
    assert metrics_mod.window_pool.released.total() == before
    assert metrics_mod.window_pool.slot_peak.value() >= 90 // PS
    assert a.generated == a2.generated and b.generated == b2.generated
    for x, y in ((a, a2), (b, b2)):
        assert np.abs(np.asarray([lp[0] for lp in x.logprob_data])
                      - np.asarray([lp[0] for lp in y.logprob_data])
                      ).max() < 1e-5


def test_the_chunk_program_apart_and_a_second_occupant_read_the_same():
    """``ragged_attention=0`` (chunks through ``prefill_chunk_step``), then
    two more requests through the slots the first two left: released and
    reused pages hold nothing stale."""
    params = _params()
    eng = _engine(params, ragged_attention=0, max_decode_slots=2)
    reqs = list(_two_streams(eng))
    for seed, n in ((11, 50), (12, 9)):
        reqs.append(eng.submit(Request(prompt_ids=_ids(n, seed),
                                       max_tokens=5, ignore_eos=True,
                                       logprobs=0)))
        _drain(eng)
    for r in reqs:
        _, ref_lp = _ref_logprobs(params, r)
        assert np.abs(np.asarray([lp[0] for lp in r.logprob_data])
                      - ref_lp).max() < TOL_F32


def test_preempt_then_resume_reproduces_the_stream():
    """A full-layer inventory of 20 pages under three growing streams: the
    newest is preempted, its window pages go back with the others, it
    resumes by a walk from token 0 (no prefix hit), and every stream is
    what an unconstrained engine gives."""
    params = _params()
    eng = _engine(params, kv_pool_pages=20, max_decode_slots=3,
                  max_cache_len=128)
    reqs = [eng.submit(Request(prompt_ids=_ids(6, 30 + i), max_tokens=60,
                               ignore_eos=True)) for i in range(3)]
    _drain(eng)
    assert int(eng.metrics.preemptions.total()) > 0
    assert eng.metrics.prefix_tokens_reused.total() == 0
    assert eng.win_allocator.pages_in_use == 0
    free = _engine(params, max_decode_slots=3, max_cache_len=128)
    for i, r in enumerate(reqs):
        f = free.submit(Request(prompt_ids=_ids(6, 30 + i), max_tokens=60,
                                ignore_eos=True))
        _drain(free)
        assert r.generated == f.generated, f"stream {i} diverged"


def test_dispatch_records_and_metrics_tell_the_kinds_apart(mixed_run):
    params, eng, reqs, seen, held, pool = mixed_run
    dec = [r for r in seen if r["program"] == "decode_steps"]
    assert dec
    for r in seen:
        if r["program"] in ("decode_steps", "mixed_step"):
            assert (r["attn_layers_full"], r["attn_layers_window"]) == (2, 4)
            assert r["moe_rows"] == 2 * (r["horizon"] * r["active"]
                                         + r.get("chunk_n", 0))
    for r in dec:
        # a window layer's rows hold at most the window + a page each
        assert r["win_pages_live"] <= r["attn_pages_live"]
        assert r["win_pages_live"] <= r["horizon"] * 4 * (WINDOW // PS + 1)
        assert r["win_pages_walked"] >= r["win_pages_live"]
        # a row copies the pages it holds and no other (PR 45), either kind
        assert r["win_pages_copied"] == r["win_pages_live"]
        assert r["attn_pages_copied"] == r["attn_pages_live"]
    late = dec[-1]
    assert late["win_pages_live"] < late["attn_pages_live"]
    m = eng.metrics
    assert m.window_attn_pages.total() == sum(
        2 * r["win_pages_live"] + r["win_pages_walked"] for r in dec)
    assert m.decode_attn_pages.total() == sum(
        2 * r["attn_pages_live"] + r["attn_pages_walked"] for r in dec)
    assert m.window_attn_pages.value(kind="copied") \
        == m.window_attn_pages.value(kind="live")
    assert m.prefix_tokens_reused.total() == 0
    text = m.registry.render() + metrics_mod.window_pool.registry.render()
    for name in ('tpu_serve_window_attn_pages_total{kind="live"}',
                 'tpu_serve_window_attn_pages_total{kind="copied"}',
                 'tpu_serve_decode_attn_pages_total{kind="copied"}',
                 'tpu_serve_prefix_lookups_skipped_total{reason='
                 '"window_pages"}',
                 "tpu_serve_kv_window_pages_total 17",
                 "tpu_serve_kv_window_pages_slot_peak",
                 "tpu_serve_kv_window_pages_released_total",
                 "tpu_serve_kv_window_pages_in_use_peak",
                 "tpu_serve_kv_window_pages_unreleased_at_peak"):
        assert name in text, name


def test_the_start_up_log_states_both_inventories(caplog):
    import logging

    with caplog.at_level(logging.INFO):
        eng = _engine(L.init_params(CFG, jax.random.PRNGKey(0), jnp.float32))
    line = next(r.getMessage() for r in caplog.records
                if "KV pool" in r.getMessage())
    assert "the 2 full layers' inventory" in line
    assert "the 4 window layers'" in line and "18 pages" in line
    assert eng.cache["wk"].shape == (4, 18, 2, PS, 16)
    assert eng.cache["k"].shape == (2, 4 * 32 + 1, 2, PS, 16)
    assert kvp.pool_bytes(CFG, 129, PS, jnp.float32, win_pages=18) == sum(
        a.size * a.dtype.itemsize for a in eng.cache.values())


# -- start-up refusals and validation ---------------------------------------

REFUSED = {
    "spec-decode": (dict(spec_decode=True), "one table a slot"),
    "host-tier": (dict(kv_host_tier_bytes=1 << 20), "which are gone"),
    "int8-kv": (dict(kv_dtype="int8", page_size=32), "no scale leaves"),
    "mesh": (dict(mesh=dataclasses.replace(ServingConfig().mesh, tp=2)),
             "no partition by dp group"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_start_up_refuses(what):
    over, sentence = REFUSED[what]
    with pytest.raises(ValueError, match=sentence) as e:
        _engine(L.init_params(CFG, jax.random.PRNGKey(0), jnp.float32),
                **over)
    assert "window layers beside full ones" in str(e.value)


def test_start_up_refuses_lora_adapters():
    with pytest.raises(ValueError, match="keeps no prefix index"):
        Engine(CFG, L.init_params(CFG, jax.random.PRNGKey(0), jnp.float32),
               ServingConfig(max_decode_slots=2, max_cache_len=64,
                             page_size=PS, kv_host_tier_bytes=0),
               lora={"a": "/nowhere"})


BAD = {
    "w comes with g alone": (dict(layer_pattern="wwslwg"), "no other kind"),
    "one character a layer": (dict(layer_pattern="wwg", num_dense_layers=0),
                              "names 3 layers, num_layers=6"),
    "w needs a window": (dict(sliding_window=0), "sliding_window > 0"),
    "dense layers need a list": (dict(layer_pattern=""), "differs by layer"),
    "dense layers need experts": (dict(num_experts=0), "differs by layer"),
}


@pytest.mark.parametrize("what", sorted(BAD))
def test_layer_list_is_validated(what):
    over, sentence = BAD[what]
    with pytest.raises(ValueError, match=sentence):
        tiny_trinity(**over)


def test_the_list_form_counts_its_kinds():
    assert CFG.layer_list and CFG.windowed and not CFG.recurrent
    assert (CFG.num_attn_layers, CFG.num_window_layers) == (2, 4)
    assert CFG.attn_window == 0 and CFG.sliding_window == WINDOW
    plan = L.layer_plan(CFG)
    assert [(k, f, n) for k, f, _, _, _, n in plan] == [
        ("w", "ffn_dense", 2), ("w", "ffn_moe", 1), ("g", "ffn_moe", 1),
        ("w", "ffn_moe", 1), ("g", "ffn_moe", 1)]
    stage = MODEL_REGISTRY["arcee-ai/Trinity-Mini-pp4-stage0"]
    assert [(k, f, n) for k, f, _, _, _, n in L.layer_plan(stage)] == [
        ("w", "ffn_dense", 2), ("w", "ffn_moe", 1), ("g", "ffn_moe", 1),
        ("w", "ffn_moe", 3), ("g", "ffn_moe", 1)]
    whole = stage.scaled(num_layers=32, layer_pattern="wwwg" * 8)
    assert len(L.layer_plan(whole)) == 17
    # a model without the new kind reads the old defaults
    old = tiny_trinity(layer_pattern="", num_dense_layers=0)
    assert not old.windowed and old.attn_window == WINDOW \
        and old.num_window_layers == 0


def test_the_layer_body_is_traced_once_a_run_not_once_a_layer(monkeypatch):
    """The served stage ``ww|wgwwwg`` has five runs of equal (kind, FFN):
    tracing its forward pass traces five layer bodies, not eight — two of
    them dense, three routed."""
    cfg = tiny_trinity(layer_pattern="wwwgwwwg", num_layers=8)
    calls = {"block": 0, "moe": 0}
    block, moe_mlp = L.decoder_block, moe.moe_mlp

    def counted(kind, fn):
        def wrapper(*a, **k):
            calls[kind] += 1
            return fn(*a, **k)
        return wrapper

    monkeypatch.setattr(L, "decoder_block", counted("block", block))
    monkeypatch.setattr(moe, "moe_mlp", counted("moe", moe_mlp))
    params = jax.eval_shape(
        lambda: L.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    jax.make_jaxpr(
        lambda p: L.model_forward(p, cfg, jnp.zeros((1, 16), jnp.int32),
                                  jnp.arange(16)[None])[0])(params)
    assert calls == {"block": 5, "moe": 4}


# -- layout, bytes, quantisation --------------------------------------------


@pytest.mark.parametrize("quant", [True, False], ids=["int8", "bf16"])
def test_seeded_tree_has_the_programs_layout(quant):
    def theirs():
        p = L.init_params(CFG, jax.random.PRNGKey(0), jnp.bfloat16)
        return quantize_params(p, CFG) if quant else p

    want = jax.eval_shape(theirs)
    got = MAKER.make(MC, 5, quant)
    flat = lambda t: {jax.tree_util.keystr(k): (tuple(v.shape), str(v.dtype))
                      for k, v in jax.tree_util.tree_leaves_with_path(t)}
    assert flat(got) == flat(want)
    assert {"".join(f"['{p}']" for p in k): v
            for k, v in MAKER.tree_spec(MC, quant).items()} == flat(want)
    assert weights_quantized(got) == quant


def test_param_weights_tell_the_dense_ffn_from_the_expert_stacks():
    tree = _make(5, True)
    w = parts.param_weights(tree, CFG)
    H, I, Im, E = 64, 96, 32, 8
    assert w["experts"][1] == 4 * E * 3 * H * Im
    assert w["mlp"][1] == 2 * 3 * H * I + 4 * 3 * H * Im    # dense + shared
    assert w["router"][1] == 4 * H * E
    assert sum(b for b, _ in w.values()) == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def test_aot_plan_sizes_both_inventories_and_names_the_window_tables():
    from aws_k8s_ansible_provisioner_tpu.serving import aot

    serving = ServingConfig(model="tiny-trinity", max_decode_slots=4,
                            max_cache_len=256, page_size=PS,
                            prefill_buckets=(16, 32), prefill_chunk=CHUNK,
                            decode_horizon=2, weights_dtype="int8")
    plan = aot.ProgramPlan(CFG, serving)
    eng = _engine(_params())
    assert plan.win_pages == eng.win_pages == 18
    params, cache = aot._abstract_state(plan, None)
    assert params["layers"]["ffn_moe"]["w_up"]["kernel"].dtype == jnp.int8
    assert {n: tuple(a.shape) for n, a in cache.items()} == {
        n: tuple(a.shape) for n, a in eng.cache.items()}
    progs = {name: kw for name, _, _, kw in aot.enumerate_programs(
        plan, None, params, cache)}
    assert progs["decode_fused_h2"]["wtable"].shape == (4, 32)
    assert progs[f"mixed_c{CHUNK}"]["wtable"].shape == (4, 32)
    assert progs["prefill_b16"]["wpages"].shape == (32,)
    assert progs["prefill_batch_n4_b16"]["wtables"].shape == (4, 32)


def test_the_window_bound_follows_the_horizon_the_server_is_given():
    """A slot's window pages cover window + the dispatches in flight + a
    page, whatever ``ServingConfig.decode_horizon`` is. ``--decode-horizon``
    hands the server that field and changes no default: without it every
    deployment serves at 8 (the benchmark's check refused the new cell at 8:
    its median TTFT falls between two rungs of a ladder whose step is one
    dispatch; PERF.md section 6, PR 39)."""
    from aws_k8s_ansible_provisioner_tpu.serving import server

    parse = server.build_parser().parse_args
    assert server.serving_config_from_args(parse([])).decode_horizon \
        == ServingConfig().decode_horizon == 8
    assert server.serving_config_from_args(
        parse(["--decode-horizon", "4"])).decode_horizon == 4
    eng = _engine(L.init_params(CFG, jax.random.PRNGKey(0), jnp.float32),
                  decode_horizon=6)
    assert eng._win_slot_pages == -(-(WINDOW + 12) // PS) + 1


def test_the_dry_run_server_knows_the_list():
    from aws_k8s_ansible_provisioner_tpu.serving import server

    args = server.build_parser().parse_args(
        ["--model", "tiny-trinity", "--max-decode-slots", "2",
         "--max-cache-len", "512", "--kv-host-tier-bytes", "0"])
    # (the dry-run presets keep their max_seq_len of 256)
    serving = server.serving_config_from_args(args)
    eng = server.build_state(serving).engine
    assert eng.cfg.layer_pattern == "wwwgwg" and eng.cfg.windowed
    assert eng.cfg.sliding_window == 2 * serving.page_size
    r = eng.submit(Request(prompt_ids=[5 + i % 90 for i in range(200)],
                           max_tokens=50, ignore_eos=True))
    first = []
    _drain(eng, lambda: first.append(int(eng._wfirst.max())))
    assert len(r.generated) == 50
    # the prompt's first page was never held for the window layers
    assert max(first) == 1 and metrics_mod.window_pool.slot_peak.value() == 3
