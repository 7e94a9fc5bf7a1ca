"""The LFM2 list (gated short convolutions 3:1 with GQA layers, two leading
dense FFNs, then experts chosen by biased sigmoid scores, tied embeddings) at
a tiny size on the CPU: hidden 64, 4 heads / 2 KV heads, page 8, the list
``c c | g c c c  g c c c | g c c  g c c`` (dense | routed; two folded groups
of two periods each), 8 experts top-2.

The reference (benchmark/reference/lfm2_moe.py) is float32 at matmul
precision "highest", recomputes every sequence whole from its token ids,
computes every expert for every token, imports nothing from the program and
routes on its own activations. The served side is the code the step programs
run: the conv tails beside the paged pool in the donated cache,
``model_forward_carry`` over folded periods of runs, the paged kernels
(interpret mode), ops/moe.py.

Tolerance, LOGITS of std 0.65. With float32 activations the served
mathematics IS the reference's — pages for a dense sequence, the order of
summation, the every-expert form, a tail carried across a chunk boundary in
float32 — so every row agrees to TOL_F32 = 5e-4 (measured 1e-5 to 4e-5: in
float32 no near-tie flips a choice between the two). Each mechanism left
out moves the worst row by 2 to 3 (``test_tolerance_catches``).
"""

import dataclasses
import json
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
    MODEL_REGISTRY, ModelConfig, ServingConfig, tiny_lfm2, tiny_sala,
    tiny_solar, tiny_trinity)
from aws_k8s_ansible_provisioner_tpu.models import layers as L  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.models import parts  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.models.quant import (  # noqa: E402
    quantize_params)
from aws_k8s_ansible_provisioner_tpu.ops import (  # noqa: E402
    linear_attention as la)
from aws_k8s_ansible_provisioner_tpu.ops import moe  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.serving import flightrec  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.serving.engine import (  # noqa: E402
    Engine, Request)

TOL_F32 = 5e-4
PS, CHUNK = 8, 32
CFG = tiny_lfm2()
# the served head shape in small: 64-wide heads, two a pool row
WIDE = tiny_lfm2(head_dim=64)
BIG = MODEL_REGISTRY["LiquidAI/LFM2-8B-A1B"]
MAKER = files.load_module("weight_makers", "lfm2_moe")
REF = files.load_module("reference", "lfm2_moe")


def _widen(tree):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


def _params(cfg=CFG, seed=32, quant=False):
    """Seeded weights, float32 activations (int8 kernels stay int8)."""
    return _widen(MAKER.make(dataclasses.asdict(cfg), seed, quant))


@pytest.fixture(scope="module", params=["bf16", "int8"])
def tree(request):
    return _params(quant=request.param == "int8")


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
    ref = np.asarray(REF.logits(dataclasses.asdict(CFG), tree, ids, 39))
    assert 0.4 < ref.std() < 0.9
    # float32 on both sides, the same mathematics: TOL_F32 (module docstring)
    assert np.abs(_forward(tree, ids)[:-1] - ref).max() < TOL_F32


@pytest.mark.parametrize("how", sorted(
    list(REF.CONTROLS) + ["expert_bias left out of the choice"]))
def test_tolerance_catches(tree, how):
    """Each control of the reference is another model: far outside the
    tolerance at the worst of the last 16 rows."""
    ids = _ids(40, 2)
    mc = dataclasses.asdict(CFG)
    ref = np.asarray(REF.logits(mc, tree, ids, 16))
    off = np.asarray(REF.forward(
        mc, tree, ids, 16,
        **{**REF.CONTROLS, **REF.CONTROLS_REPORTED}[how])[0])
    # (the selection bias decides near-ties alone: left out it swaps the
    # second of two experts in a tenth of the rows — another model, nearer)
    assert np.abs(off - ref).max() > (0.5 if how in REF.CONTROLS else 0.2)


def test_the_program_without_a_mechanism_is_outside_the_tolerance(tree):
    """The same from the program's side: another epsilon in the router's
    renormalisation is no other model at these scores, but two taps for
    three, an untied head's absence of a q/k norm or another theta is."""
    ids = _ids(40, 2)
    ref = np.asarray(REF.logits(dataclasses.asdict(CFG), tree, ids, 39))
    for over in (dict(rope_theta=10000.0), dict(qk_norm=False),
                 dict(norm_topk_prob=False)):
        got = _forward(tree, ids, CFG.scaled(**over))[:-1]
        assert np.abs(got - ref).max() > 0.05, over


def test_routed_ffn_with_selection_bias_and_epsilon_is_the_references(tree):
    """One routed layer's FFN alone: sigmoid scores, top-2 by score + a
    NON-ZERO bias (which the maker seeds), renormalised with the MODEL's
    epsilon — and the bias changes the choice."""
    mc = dataclasses.asdict(CFG)
    fp = jax.tree.map(lambda a: a[1], tree["layers"]["ffn_moe"])
    assert float(jnp.abs(fp["router"]["bias"]).max()) > 0
    assert float(jnp.abs(tree["layers"]["ffn_moe"]["router"]["bias"]).max()) \
        < 0.01                      # near-ties alone: no expert held out
    m = jax.random.normal(jax.random.PRNGKey(3), (1, 24, CFG.hidden_size))
    same = lambda a: a                                      # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = np.asarray(L._mlp(CFG, m, fp)[0])
        want, idx = REF._routed(mc, m[0], fp, None, "", same)
        _, idx0 = REF._routed(mc, m[0], fp, None, "no_expert_bias", same)
    assert np.abs(got - np.asarray(want)).max() < 1e-4
    assert bool((np.sort(np.asarray(idx)) != np.sort(np.asarray(idx0))).any())
    # the epsilon is the model's, read by route (Trinity's stays 1e-20)
    w, _ = moe.route(CFG, m[0], fp["router"]["kernel"], fp["router"]["bias"])
    s = jax.nn.sigmoid(m[0] @ fp["router"]["kernel"].astype(jnp.float32))
    chosen = jnp.take_along_axis(s, idx, -1).sum(-1)
    assert np.allclose(np.asarray(w).sum(-1),
                       np.asarray(chosen / (chosen + 1e-6)), atol=1e-6)
    assert CFG.route_norm_eps == BIG.route_norm_eps == 1e-6
    assert MODEL_REGISTRY["arcee-ai/Trinity-Mini-pp4-stage0"] \
        .route_norm_eps == ModelConfig.__dataclass_fields__[
            "route_norm_eps"].default == 1e-20


def test_the_maker_bias_changes_the_choice_in_a_stated_share_of_rows(tree):
    """With ``expert_bias`` left out of the choice the chosen set differs in
    a part of the rows of the routed layers — the near-ties (the maker's
    docstring: 22 % at top-4 of 32): here, top-2 of 8, between 3 % and a
    half."""
    ids = _ids(64, 5)
    mc = dataclasses.asdict(CFG)
    _, own = REF.forward(mc, tree, ids, 8)
    _, bare = REF.forward(mc, tree, ids, 8, wrong="no_expert_bias")
    differ = (np.sort(np.asarray(own), -1)
              != np.sort(np.asarray(bare), -1)).any(-1).mean(-1)
    assert differ.shape == (CFG.num_layers - CFG.num_dense_layers,)
    assert 0.03 < differ.mean() < 0.5


# -- (b) the short convolution: one primitive, every span form ----------------


def test_kda_convolution_is_bit_equal_after_sharing_the_primitive():
    """``conv_qkv`` before this PR, written out here as it stood, against
    ``conv_qkv`` over ``short_conv``: the same bits, bfloat16 and float32
    windows alike."""

    def before(window, taps, H, d):
        K = taps.shape[0]
        T = window.shape[-2] - (K - 1)
        w = taps.astype(jnp.float32)
        win = window.astype(jnp.float32)
        y = sum(w[i] * jax.lax.slice_in_dim(win, i, i + T, axis=-2)
                for i in range(K))
        y = jax.nn.silu(y)
        q, k, v = (a.reshape(a.shape[:-1] + (H, d))
                   for a in jnp.split(y, 3, axis=-1))
        return la._l2norm(q) * (d ** -0.5), la._l2norm(k), v

    H, d, T = 4, 16, 37
    for dt in (jnp.bfloat16, jnp.float32):
        k1, k2 = jax.random.split(jax.random.PRNGKey(7))
        window = jax.random.normal(k1, (3, T + la.CONV_TAPS - 1, 3 * H * d),
                                   dt)
        taps = jax.random.normal(k2, (la.CONV_TAPS, 3 * H * d), dt)
        for a, b in zip(before(window, taps, H, d),
                        la.conv_qkv(window, taps, H, d)):
            assert a.dtype == b.dtype and bool((a == b).all())


def test_short_conv_is_the_published_sum_of_taps():
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 11, 5))
    taps = jax.random.normal(jax.random.PRNGKey(2), (3, 5))
    window = jnp.pad(z, [(0, 0), (2, 0), (0, 0)])
    got = np.asarray(la.short_conv(window, taps))
    zn, wn = np.asarray(window), np.asarray(taps)
    want = np.stack([sum(wn[j] * zn[:, t + j] for j in range(3))
                     for t in range(11)], axis=1)
    assert np.allclose(got, want, atol=1e-6)


def _conv_ref(taps, bcx):
    """The gated convolution of whole sequences, from zeros."""
    return la.recur_from_zero.conv(taps, bcx, ({},))[0]


def _tail_state(n_layers=2, slots=3, H=8, K=3):
    return {"conv_tail": jnp.full((n_layers, slots, K - 1, H), 7.0)}


def test_conv_span_forms_agree_with_the_whole_sequence():
    """One sequence whole; the same in two spans (the second from the
    carried tail); token by token through the decode form; a packed batch
    of two prompts in two slots; the mixed form (decode rows then a chunk).
    A stale tail (7.0 everywhere) in every slot is never read by a span
    that starts at position 0."""
    H, K, T = 8, 3, 21
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    taps = jax.random.normal(k1, (K, H))
    bcx = jax.random.normal(k2, (2, T, 3 * H))
    want = np.asarray(_conv_ref(taps, bcx))                  # [2, T, H]
    i = jnp.int32(1)

    # two spans of slot 2: rows [0, 13) then [13, 21), 3 rows of padding
    rec = _tail_state()
    o1, rec = la.make_recur_span(2, 0, 13).conv(
        taps, jnp.pad(bcx[:1, :13], [(0, 0), (0, 3), (0, 0)]), (rec, i))
    o2, rec = la.make_recur_span(2, 13, 8).conv(taps, bcx[:1, 13:], (rec, i))
    got = np.concatenate([np.asarray(o1)[0, :13], np.asarray(o2)[0]])
    assert np.abs(got - want[0]).max() < 1e-6
    # the other layer's and the other slots' tails are as they were
    assert float(jnp.abs(rec["conv_tail"][0] - 7.0).max()) == 0.0
    assert float(jnp.abs(rec["conv_tail"][1, :2] - 7.0).max()) == 0.0

    # a packed batch of two prompts (true lengths 21 and 9): no leak
    # between rows, and a padding row's slot id is out of range
    rec = _tail_state()
    slots = jnp.asarray([1, 0, 99], jnp.int32)
    lens = jnp.asarray([21, 9, 0], jnp.int32)
    batch = jnp.concatenate([bcx, bcx[:1]])
    ob, rec = la.make_recur_batch(slots, lens).conv(taps, batch, (rec, i))
    assert np.abs(np.asarray(ob)[0] - want[0]).max() < 1e-6
    assert np.abs(np.asarray(ob)[1, :9] - want[1, :9]).max() < 1e-6
    assert float(jnp.abs(rec["conv_tail"][1, 2] - 7.0).max()) == 0.0

    # token by token after the batch: slot 0 goes on from row 9 of seq 1
    live = jnp.asarray([True, False, False])
    row = jnp.zeros((3, 1, 3 * H)).at[0, 0].set(bcx[1, 9])
    before = rec["conv_tail"]
    od, rec = la.make_recur_decode(live).conv(taps, row, (rec, i))
    assert np.abs(np.asarray(od)[0, 0] - want[1, 9]).max() < 1e-6
    # a dead row leaves the tail it found
    assert bool((rec["conv_tail"][1, 1:] == before[1, 1:]).all())

    # mixed: 3 decode rows (slot 0 live: row 10 of seq 1) then a chunk of
    # slot 2 from a carried tail (rows [13, 21) of seq 0)
    rec0 = _tail_state()
    _, rec0 = la.make_recur_span(2, 0, 13).conv(taps, bcx[:1, :13],
                                                (rec0, i))
    rec = {"conv_tail": rec0["conv_tail"].at[1, 0].set(
        rec["conv_tail"][1, 0])}
    packed = jnp.concatenate(
        [jnp.zeros((1, 3, 3 * H)).at[0, 0].set(bcx[1, 10]), bcx[:1, 13:]],
        axis=1)
    om, rec = la.make_recur_mixed(3, live, 2, 13, 8).conv(taps, packed,
                                                         (rec, i))
    assert np.abs(np.asarray(om)[0, 0] - want[1, 10]).max() < 1e-6
    assert np.abs(np.asarray(om)[0, 3:] - want[0, 13:]).max() < 1e-6


# -- (c) the list: plan, folded periods, parameters ---------------------------


def test_the_published_layer_types_give_the_plan():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b-int8.json"), encoding="utf-8") as f:
        types = json.load(f)["layer_types"]
    pattern = "".join({"conv": "c", "full_attention": "g"}[t] for t in types)
    assert pattern == BIG.layer_pattern and len(pattern) == BIG.num_layers
    assert [i for i, k in enumerate(pattern) if k == "g"] \
        == [2, 6, 10, 14, 18, 21]
    plan = L.layer_plan(BIG)
    assert len(plan) == 13
    assert [(k, f, n) for k, f, _, _, _, n in plan[:3]] == [
        ("c", "ffn_dense", 2), ("g", "ffn_moe", 1), ("c", "ffn_moe", 3)]
    groups = L.layer_periods(plan)
    assert [(len(runs), reps) for runs, reps, _ in groups] \
        == [(1, 1), (2, 4), (2, 2)]
    # a copy lies a whole period further in every stack: (attn, pool, ffn)
    # of the "g" run, (conv, tails, ffn) of the "c" run
    assert groups[1][2] == ((1, 1, 4), (3, 3, 4))
    assert groups[2][2] == ((1, 1, 3), (2, 2, 3))
    # every layer is walked once, in order
    walked = [(kind, first + t * sf + r)
              for runs, reps, strides in groups for t in range(reps)
              for (kind, _, first, _, _, n), (sf, _, _) in zip(runs, strides)
              for r in range(n)]
    seen = {"c": 0, "g": 0}
    for kind, (got_kind, got_index) in zip(pattern, walked):
        assert (kind, seen[kind]) == (got_kind, got_index)
        seen[kind] += 1
    assert BIG.layer_list and BIG.recurrent and BIG.recurrent_kinds == "conv"
    assert (BIG.num_attn_layers, BIG.num_recurrent_layers) == (6, 18)


def test_the_accepted_lists_fold_nothing():
    """Every list served before this one comes out run by run, each alone
    and once: their step programs are what they were."""
    from types import SimpleNamespace

    stage = L.layer_plan(MODEL_REGISTRY["arcee-ai/Trinity-Mini-pp4-stage0"])
    sala = L.layer_plan(SimpleNamespace(layer_pattern="slllllls",
                                        num_experts=0))
    assert (len(stage), len(sala)) == (5, 3)
    for plan in (stage, sala):
        assert [(len(r), n) for r, n, _ in L.layer_periods(plan)] \
            == [(1, 1)] * len(plan)


def test_the_layer_bodies_are_traced_once_a_folded_run(monkeypatch):
    """16 layers, 7 runs, 3 groups: the conv body is traced 3 times (the
    dense pair, then once a folded group) and the attention body twice, not
    12 and 4 times."""
    calls = {"c": 0, "g": 0}
    conv, block = L.conv_block, L.decoder_block

    def count(kind, fn):
        def wrapped(*a, **kw):
            calls[kind] += 1
            return fn(*a, **kw)
        return wrapped

    monkeypatch.setattr(L, "conv_block", count("c", conv))
    monkeypatch.setattr(L, "decoder_block", count("g", block))
    tree = jax.eval_shape(lambda: L.init_params(CFG, jax.random.PRNGKey(0)))
    jax.make_jaxpr(lambda t: L.model_forward(
        t, CFG, jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None]))(tree)
    groups = L.layer_periods(L.layer_plan(CFG))
    assert [(len(r), n) for r, n, _ in groups] == [(1, 1), (2, 2), (2, 2)]
    assert calls == {"c": 3, "g": 2}


def test_the_uncut_parameter_count_is_the_published_total():
    mc = dataclasses.asdict(BIG)
    counts = MAKER.param_counts(mc)
    assert counts["total"] == 8_339_828_736          # the published 8.3B
    assert counts["active"] == 1_557_639_168         # the published A1.5B
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b-int8.json"), encoding="utf-8") as f:
        stated = json.load(f)["parameters"]
    for name, n in counts.items():
        assert stated[name] == n, name
    # the program's own tree, by shape: the same count + the norms (2 a
    # layer + the q/k norms + the final one) and the routers' bias
    tree = jax.eval_shape(lambda: L.init_params(BIG, jax.random.PRNGKey(0)))
    H = BIG.hidden_size
    extra = 2 * 24 * H + H + 6 * 2 * BIG.head_dim + 22 * BIG.num_experts
    assert L.param_count(tree) == counts["total"] + extra


@pytest.mark.parametrize("quant", [True, False], ids=["int8", "bf16"])
def test_seeded_tree_has_the_programs_layout(quant):
    def theirs():
        p = L.init_params(CFG, jax.random.PRNGKey(0), jnp.bfloat16)
        return quantize_params(p, CFG) if quant else p

    def flat(t):
        return {jax.tree_util.keystr(k): (tuple(v.shape), str(v.dtype))
                for k, v in jax.tree_util.tree_leaves_with_path(t)}

    want = flat(jax.eval_shape(theirs))
    mc = dataclasses.asdict(CFG)
    assert flat(MAKER.make(mc, 5, quant)) == want
    assert {"".join(f"['{p}']" for p in k): v
            for k, v in MAKER.tree_spec(mc, quant).items()} == want
    # W_in / W_out go to int8 like any projection; taps, norms, the router
    # and its bias stay float
    if quant:
        assert want["['layers']['conv']['w_in']['kernel']"][1] == "int8"
        assert want["['layers']['conv']['wo']['kernel']"][1] == "int8"
        assert want["['layers']['conv']['conv']['weight']"][1] == "bfloat16"
        assert want["['layers']['ffn_moe']['router']['bias']"][1] == "float32"


def test_the_parts_table_weighs_the_new_leaves():
    tree = jax.eval_shape(lambda: quantize_params(
        L.init_params(CFG, jax.random.PRNGKey(0), jnp.bfloat16), CFG))
    w = parts.param_weights(tree, CFG)
    H, nc = CFG.hidden_size, CFG.layer_pattern.count("c")
    na = CFG.layer_pattern.count("g")
    # recur: the taps alone; attn.proj holds W_in, attn.out W_out
    assert w["recur"] == (nc * CFG.conv_taps * H * 2, 0)
    assert w["attn.out"][1] == nc * H * H + na * CFG.q_size * H
    assert w["attn.proj"][1] == nc * 3 * H * H \
        + na * H * (CFG.q_size + 2 * CFG.kv_size)
    assert set(w) == {"norm", "attn.proj", "attn.out", "mlp", "router",
                      "experts", "recur", "head"}


def test_config_refuses_what_the_kind_cannot_be():
    for over, sentence in (
            (dict(conv_taps=0), "conv_taps"),
            (dict(layer_pattern="ccgw" * 4), "window")):
        with pytest.raises(ValueError, match=sentence):
            tiny_lfm2(**over)
    # a model without the new kind reads the old defaults
    plain = dataclasses.asdict(tiny_solar())
    for field in ("conv_taps", "route_norm_eps"):
        assert plain[field] == ModelConfig.__dataclass_fields__[field].default


@pytest.mark.parametrize("cfg,pool", [
    (WIDE, (1, 128)),                               # 2 heads of 64: one row
    (BIG, (4, 128)),                                # 8 heads of 64
    (CFG, (2, 16)),                                 # 8 x 16 lanes, 2 heads
    (tiny_lfm2(head_dim=32, num_kv_heads=4), (1, 128)),
    (tiny_lfm2(head_dim=64, num_heads=3, num_kv_heads=3), (3, 64)),
    (tiny_lfm2(head_dim=128), (2, 128)),
    (MODEL_REGISTRY["meta-llama/Llama-3.2-1B"], (4, 128)),
    (MODEL_REGISTRY["microsoft/phi-2"], (32, 80)),  # 80 divides no row
    (MODEL_REGISTRY["Qwen/Qwen3-0.6B"], (8, 128)),
    (tiny_sala(), (2, 16)),
    (tiny_sala(head_dim=64), (2, 64)),              # selecting: a head a row
    (tiny_trinity(head_dim=64), (2, 64)),           # window layers: the same
], ids=lambda v: v.name if hasattr(v, "name") else str(v))
def test_pool_rows_hold_whole_heads_where_they_fill_128_lanes(cfg, pool):
    """The pool's geometry is READ OFF the head's width (no config key):
    heads narrower than a 128-lane row lie 128 // head_dim a row where that
    many fill it exactly and divide the KV heads; every other model — wider
    heads, a width that divides no row, a head count that does not split, a
    selecting or window kind — keeps a head a row, and tensor parallelism
    splits the pool's rows."""
    from aws_k8s_ansible_provisioner_tpu.parallel.sharding import (
        check_tp_divisibility)

    assert (cfg.pool_kv_heads, cfg.pool_head_dim) == pool
    assert cfg.pool_kv_heads * cfg.pool_head_dim \
        == cfg.num_kv_heads * cfg.head_dim
    assert "kv_lane_pack" not in ModelConfig.__dataclass_fields__
    if cfg is BIG:
        check_tp_divisibility(cfg, 4)
        with pytest.raises(ValueError, match="pool_kv_heads"):
            check_tp_divisibility(cfg, 8)


# -- (d) the served path -------------------------------------------------------


def _engine(params, cfg=CFG, **over):
    kw = dict(max_decode_slots=4, max_cache_len=256, prefill_buckets=(16, 32),
              dtype="float32", weights_dtype="bf16", prefix_cache=True,
              decode_horizon=2, page_size=PS, decode_pipeline=1,
              ragged_attention=1, attention_impl="xla",
              kv_host_tier_bytes=0, derived_seed=7, prefill_chunk=CHUNK)
    kw.update(over)
    return Engine(cfg, params, ServingConfig(**kw))


def _drain(eng):
    for _ in range(10000):
        if not eng.step():
            return
    raise AssertionError("engine did not drain")


def _streams(eng):
    """A 90-token prompt (three chunks of 32, the later ones from a carried
    tail) arrives under a live stream, and two short prompts behind it: each
    a one-chunk walk whose dispatch stays in flight, as the 90-token
    prompt's last does (the conv tail of a slot that joins from the device
    carry); two more queue together on the idle engine (a packed batch);
    then a request takes a slot another left."""
    def submit(n, s, max_tokens=12):
        return eng.submit(Request(prompt_ids=_ids(n, s), ignore_eos=True,
                                  max_tokens=max_tokens, logprobs=0))

    a = submit(20, 3, 40)
    for _ in range(3):
        eng.step()
    reqs = [a] + [submit(n, s) for n, s in ((90, 4), (9, 5), (11, 6))]
    _drain(eng)
    reqs += [submit(n, s) for n, s in ((10, 8), (12, 9))]
    _drain(eng)
    reqs.append(submit(13, 7, 6))
    _drain(eng)
    return reqs


def _ref_logprobs(cfg, params, r):
    ids = r.prompt_ids + r.generated
    rows = REF.logprobs(dataclasses.asdict(cfg), params, ids,
                        len(r.generated))
    return rows, rows[np.arange(len(r.generated)), r.generated]


@pytest.fixture(scope="module", params=["xla", "pallas", "wide-xla",
                                        "wide-pallas"])
def served(request):
    cfg = WIDE if request.param.startswith("wide") else CFG
    params = _params(cfg)
    eng = _engine(params, cfg, attention_impl=request.param.split("-")[-1])
    seen, orig = [], flightrec.record

    def tap(*a, **rec):
        if a[0] == "dispatch":
            seen.append(dict(rec))
        return orig(*a, **rec)

    flightrec.record = tap
    try:
        reqs = _streams(eng)
    finally:
        flightrec.record = orig
    return cfg, params, eng, reqs, seen


def test_prefill_then_decode_through_the_cache_is_the_references(served):
    """prefill_step, prefill_batch_step, three chunks of mixed_step beside
    a live row and two one-chunk walks behind them (every final chunk left
    in flight), decode steps, a reused slot: every stream is the
    reference's full pass — with 64-wide heads two a pool row too (the
    "wide" cases; with ``pallas`` through the paged kernels)."""
    cfg, params, eng, reqs, seen = served
    mixed = [r for r in seen if r["program"] == "mixed_step"]
    assert [r["chunk_n"] for r in mixed] == [32, 32, 26, 9, 11]
    assert [r.get("activation") for r in mixed] \
        == [None, None] + ["in_flight"] * 3
    assert "prefill_batch_step" in {r["program"] for r in seen}
    for r in reqs:
        rows, ref_lp = _ref_logprobs(cfg, params, r)
        got = np.asarray([lp[0] for lp in r.logprob_data], np.float32)
        # float32 both sides: TOL_F32 on the served token's logprob and on
        # its distance from the reference's maximum (module docstring)
        assert np.abs(got - ref_lp).max() < TOL_F32
        assert (rows.max(-1) - ref_lp).max() < TOL_F32


def test_dispatch_records_and_metrics_carry_the_new_names(served):
    cfg, params, eng, reqs, seen = served
    dec = [r for r in seen if r["program"] == "decode_steps"]
    mix = [r for r in seen if r["program"] == "mixed_step"]
    assert dec and mix
    for r in dec + mix:
        assert r["state_kind"] == "conv" and "kda_rows" not in r
        assert {"state_rows", "state_slots", "moe_rows",
                "moe_experts_hit"} <= set(r)
    assert all("attn_pages_live" in r for r in dec)
    assert any(r["state_rows"] == r["horizon"] * r["active"] for r in dec)
    text = eng.metrics.registry.render()
    tail = eng.cache["conv_tail"]
    assert tail.shape == (cfg.layer_pattern.count("c"), 4, 2,
                          cfg.hidden_size) and tail.dtype == jnp.float32
    assert f"tpu_serve_conv_state_bytes {float(tail.nbytes)}" in text \
        or f"tpu_serve_conv_state_bytes {tail.nbytes}" in text
    assert 'tpu_serve_state_rows_total{kind="conv",program="decode_steps"}' \
        in text
    assert 'tpu_serve_recurrent_state_bytes{kind="conv"}' in text
    # a recurrent model consults no prefix index, and says so
    assert 'tpu_serve_prefix_lookups_skipped_total{reason="recurrent_state"}' \
        in text
    if cfg.kv_lane_pack == 2:
        assert eng.cache["k"].shape[2:] == (1, PS, 128)


def test_the_start_up_log_states_the_tails_bytes(caplog):
    import logging

    with caplog.at_level(logging.INFO):
        eng = _engine(_params())
    line = next(r.getMessage() for r in caplog.records
                if "conv tails" in r.getMessage())
    n = eng.cache["conv_tail"].nbytes
    assert f"conv tails {n} bytes (12 conv layers x 4 slots x 2 rows of 64" \
        in line and "beside the KV pool's" in line
    assert f"{2 * 64 * 4} bytes a slot and layer" in line


def test_a_reused_slots_tail_is_reset(monkeypatch):
    """One slot, two requests one after the other: the second reads zeros
    before its position 0, not what the first left — and an engine whose
    spans are never ``fresh`` is caught by the same comparison."""
    params = _params()
    eng = _engine(params, max_decode_slots=1)
    first = eng.submit(Request(prompt_ids=_ids(25, 8), max_tokens=8,
                               ignore_eos=True, logprobs=0))
    _drain(eng)
    assert float(jnp.abs(eng.cache["conv_tail"]).max()) > 0
    second = eng.submit(Request(prompt_ids=_ids(9, 9), max_tokens=8,
                                ignore_eos=True, logprobs=0))
    _drain(eng)
    for r in (first, second):
        _, ref_lp = _ref_logprobs(CFG, params, r)
        got = np.asarray([lp[0] for lp in r.logprob_data], np.float32)
        assert np.abs(got - ref_lp).max() < TOL_F32
    # the control: the tail is never reset
    window = la._span_window
    monkeypatch.setattr(
        la, "_span_window",
        lambda leaf, at, fresh, x: window(leaf, at, fresh & False, x))
    jax.clear_caches()
    bad = _engine(params, max_decode_slots=1)
    for n, s in ((25, 8), (9, 9)):
        r = bad.submit(Request(prompt_ids=_ids(n, s), max_tokens=8,
                               ignore_eos=True, logprobs=0))
        _drain(bad)
    _, ref_lp = _ref_logprobs(CFG, params, r)
    got = np.asarray([lp[0] for lp in r.logprob_data], np.float32)
    assert np.abs(got - ref_lp).max() > 0.05
    jax.clear_caches()


REFUSED = {
    "spec": (dict(spec_decode=True), "speculative decoding"),
    "host-tier": (dict(kv_host_tier_bytes=1 << 20), "host KV tier"),
    "int8-kv": (dict(kv_dtype="int8"), "int8 KV"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_start_up_refuses_what_it_refuses_for_the_other_recurrent_kinds(
        what):
    over, sentence = REFUSED[what]
    with pytest.raises(ValueError, match=sentence) as e:
        _engine(_params(), **over)
    assert "recurrent (conv) layers" in str(e.value)


def test_the_dry_run_server_knows_the_list():
    from aws_k8s_ansible_provisioner_tpu.serving import server

    args = server.build_parser().parse_args(
        ["--model", "tiny-lfm2", "--max-decode-slots", "2",
         "--max-cache-len", "128", "--kv-host-tier-bytes", "0"])
    state = server.build_state(server.serving_config_from_args(args))
    assert state.engine.cfg.layer_pattern == CFG.layer_pattern
    assert "conv_tail" in state.engine.cache


# -- (e) the checkpoint's names ------------------------------------------------


def _lfm2_state_dict(tree, cfg):
    """The tree under the checkpoint's names, torch layouts ([out, in]
    Linears, the depthwise convolution [H, 1, K])."""
    lay, nd, sd = tree["layers"], cfg.num_dense_layers, {}
    seen = {"c": 0, "g": 0}
    for i, kind in enumerate(cfg.layer_pattern):
        pre = f"model.layers.{i}."
        at = seen[kind]
        seen[kind] += 1
        p = jax.tree.map(lambda a: a[at],
                         lay["conv" if kind == "c" else "attn"])
        sd[pre + "operator_norm.weight"] = p["input_norm"]["weight"]
        sd[pre + "ffn_norm.weight"] = p["post_norm"]["weight"]
        if kind == "c":
            sd[pre + "conv.in_proj.weight"] = p["w_in"]["kernel"].T
            sd[pre + "conv.conv.weight"] = p["conv"]["weight"].T[:, None, :]
            sd[pre + "conv.out_proj.weight"] = p["wo"]["kernel"].T
        else:
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wo", "out_proj")):
                sd[pre + f"self_attn.{theirs}.weight"] = p[ours]["kernel"].T
            sd[pre + "self_attn.q_layernorm.weight"] = p["q_norm"]["weight"]
            sd[pre + "self_attn.k_layernorm.weight"] = p["k_norm"]["weight"]
        names = (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2"))
        if i < nd:
            for ours, theirs in names:
                sd[pre + f"feed_forward.{theirs}.weight"] = \
                    lay["ffn_dense"][ours]["kernel"][i].T
            continue
        f = jax.tree.map(lambda a: a[i - nd], lay["ffn_moe"])
        sd[pre + "feed_forward.gate.weight"] = f["router"]["kernel"].T
        sd[pre + "feed_forward.expert_bias"] = f["router"]["bias"]
        for e in range(cfg.num_experts):
            for ours, theirs in names:
                sd[pre + f"feed_forward.experts.{e}.{theirs}.weight"] = \
                    f[ours]["kernel"][e].T
    sd["model.embed_tokens.weight"] = tree["embed"]["weight"]
    sd["model.embedding_norm.weight"] = tree["final_norm"]["weight"]
    return sd


def test_hf_loader_maps_the_checkpoints_names_onto_the_tree():
    """A seeded state dict under the names of ``transformers``' lfm2_moe
    (from memory: benchmark/configs/lfm2-8b-a1b-int8.json, ``assumed``) at
    a tiny shape: every leaf of the program's tree is filled, transposed
    where torch's Linear is, the taps in the convolution's order."""
    from aws_k8s_ansible_provisioner_tpu.models import hf_loader

    tree = jax.tree.map(np.asarray, L.init_params(
        CFG, jax.random.PRNGKey(11), jnp.float32))
    tree["layers"]["ffn_moe"]["router"]["bias"] = np.asarray(
        jax.random.normal(jax.random.PRNGKey(12), (14, CFG.num_experts)))
    sd = _lfm2_state_dict(tree, CFG)
    assert sd["model.layers.0.conv.conv.weight"].shape == (64, 1, 3)
    assert sd["model.layers.0.conv.in_proj.weight"].shape == (192, 64)
    assert sd["model.layers.2.self_attn.q_layernorm.weight"].shape == (16,)
    assert sd["model.layers.2.feed_forward.experts.7.w2.weight"].shape \
        == (64, 32)
    assert sd["model.layers.2.feed_forward.expert_bias"].shape == (8,)
    assert "model.layers.0.feed_forward.w1.weight" in sd
    assert "lm_head.weight" not in sd
    back = hf_loader.convert_state_dict(CFG, sd, jnp.float32)

    def flat(t):
        return {jax.tree_util.keystr(k): np.asarray(v) for k, v in
                jax.tree_util.tree_leaves_with_path(t)}

    a, b = flat(tree), flat(back)
    assert set(a) == set(b)
    for name in a:
        assert a[name].shape == b[name].shape and np.allclose(
            a[name], b[name]), name
    # and the loaded tree runs: the same logits as the tree it came from
    ids = _ids(12, 3)
    assert np.abs(_forward(back, ids) - _forward(
        jax.tree.map(jnp.asarray, tree), ids)).max() < 1e-5
