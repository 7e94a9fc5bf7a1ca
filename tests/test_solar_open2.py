"""The Solar-Open2 hybrid (gated NoPE GQA layers and KDA linear-attention
layers 1:3, a sigmoid router over more experts than this chip holds, a
shared expert) at a tiny size on the CPU: hidden 64, 4 heads of 16, two
periods, 16 routed experts of which 4 are held.

The reference (benchmark/reference/solar_open2.py) is float32 at matmul
precision "highest", runs the recurrence token by token, imports nothing
from the program and routes on its own activations. The served side is the
code the step programs run: the paged pool for the two attending layers,
the per-slot recurrent state beside it, ``model_forward_carry`` over
periods, ops/linear_attention.py and ops/moe.py.

Tolerance, LOGITS of std 0.64: with float32 activations the served
mathematics IS the reference's — the block form of the recurrence, the
algebraic form of a decode step's output and the orders of summation
differ — so every row agrees to TOL_F32 = 5e-4 (measured 2e-6 to 3e-5).
Each way of getting the block wrong moves every row by tenths
(``test_tolerance_catches``).
"""

import dataclasses
import hashlib
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
    MeshConfig, ServingConfig, tiny_olmoe, tiny_qwen3, tiny_solar)
from aws_k8s_ansible_provisioner_tpu.models.layers import (  # noqa: E402
    init_params)
from aws_k8s_ansible_provisioner_tpu.models.quant import (  # noqa: E402
    quantize_params)
from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.ops import (  # noqa: E402
    linear_attention as la)
from aws_k8s_ansible_provisioner_tpu.ops import moe  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.serving import programs as pg  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.serving.engine import (  # noqa: E402
    Engine, Request)

TOL_F32 = 5e-4
PS, PPS, SLOTS = 16, 4, 3       # page size, pages per slot, slots
CFG = tiny_solar()
MC = dataclasses.asdict(CFG)
MAKER = files.load_module("weight_makers", "solar_open2")
REF = files.load_module("reference", "solar_open2")
# the maker's sigma (0.02) is sized for a hidden width of 4,096; at 64 the
# same projection std needs 0.11 (tests/test_olmoe.py's argument)
SIGMA = 0.11


def _widen(tree):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


@pytest.fixture(scope="module", params=["bf16", "int8"])
def tree(request):
    """Seeded weights, float32 activations (int8 kernels stay int8)."""
    return _widen(MAKER.make(MC, 32, request.param == "int8", sigma=SIGMA))


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(2, CFG.vocab_size, n).tolist()


def _ref_rows(tree, ids, mc=MC):
    """Reference logits: row j predicts the token after ids[:j + 1]."""
    return np.asarray(REF.logits(mc, tree, list(ids) + [0], len(ids)))


def _cache(cfg=CFG):
    c = kvp.init_pool(cfg, SLOTS * PPS + 1, PS, jnp.float32)
    c.update(la.init_state(cfg, SLOTS, jnp.float32))
    return c


def _table():
    return jnp.asarray([[1 + s * PPS + p for p in range(PPS)]
                        for s in range(SLOTS)], jnp.int32)


def _sampling(n=None):
    """(rng, temperature, top_k, top_p) greedy operands, scalar or [n]."""
    shape = () if n is None else (n,)
    return (jax.random.PRNGKey(0), jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape, jnp.int32), jnp.ones(shape, jnp.float32))


def _bias_kw(n=None):
    lead = () if n is None else (n,)
    return dict(bias_ids=jnp.full(lead + (pg.BIAS_K,), 2**31 - 1, jnp.int32),
                bias_vals=jnp.zeros(lead + (pg.BIAS_K,), jnp.float32),
                ban_ids=jnp.full(lead + (pg.BAN_K,), 2**31 - 1, jnp.int32),
                ban_until=jnp.zeros(lead, jnp.int32))


def _prefill(cfg, tree, cache, slot, ids, bucket=32):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(ids)] = ids
    out = pg.prefill_step(
        cfg, tree, cache, jnp.asarray(toks), jnp.int32(len(ids)),
        *_sampling(), pages=_table()[slot], seed=jnp.uint32(1),
        rep=jnp.float32(1.0), slot=jnp.int32(slot), prompt_logprobs=True,
        **_bias_kw())
    return out[0], int(out[1]), out


def _decode(cfg, tree, cache, tokens, lengths, live, n_steps=1):
    """decode_steps over all SLOTS rows; returns (cache, out [n, B],
    logprob top-k of the chosen tokens)."""
    B = SLOTS
    out = pg.decode_steps(
        cfg, n_steps, tree, cache, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(lengths, jnp.int32), *_sampling(B), table=_table(),
        impl="xla", logprobs=True, seeds=jnp.ones(B, jnp.uint32),
        live=jnp.asarray(live), **_bias_kw(B))
    return out[0], out[2]


# -- (a) the reference against prefill_step then decode_steps ---------------


def test_prefill_then_decode_through_the_cache_match_the_reference(tree):
    """A prompt through ``prefill_step`` into slot 1, then one
    ``decode_steps`` dispatch a token through the pool and the recurrent
    state (teacher forcing): the chosen token's logprob and the argmax at
    every position are the reference's."""
    ids = _ids(30)
    n_prompt = 19
    want = np.asarray(jax.nn.log_softmax(_ref_rows(tree, ids), axis=-1))
    cache, tok, out = _prefill(CFG, tree, _cache(), 1, ids[:n_prompt])
    assert tok == int(want[n_prompt - 1].argmax())
    # prompt logprobs: token t's logprob under row t - 1
    plp = np.asarray(out[2][0][0][:n_prompt - 1])
    assert np.abs(plp - want[np.arange(n_prompt - 1),
                             ids[1:n_prompt]]).max() < TOL_F32
    for t in range(n_prompt, len(ids)):
        tokens, lengths = [0, ids[t], 0], [0, t, 0]
        cache, (toks, (lp, _, _)) = _decode(CFG, tree, cache, tokens,
                                            lengths, [False, True, False])
        assert int(toks[0, 1]) == int(want[t].argmax())
        assert abs(float(lp[0, 1]) - want[t].max()) < TOL_F32


WRONG = {
    "no-attention-gate": lambda c: c.scaled(attn_output_gate=False),
    "no-shared-expert": lambda c: c.scaled(n_shared_experts=0),
    "softmax-router": lambda c: c.scaled(router_scoring="softmax"),
    "experts-at-the-wrong-offset": lambda c: c.scaled(expert_offset=4),
}


@pytest.mark.parametrize("how", sorted(WRONG))
def test_tolerance_catches(tree, how):
    ids = _ids(24)
    want = np.asarray(jax.nn.log_softmax(_ref_rows(tree, ids), axis=-1))
    _, _, out = _prefill(WRONG[how](CFG), tree, _cache(), 0, ids)
    plp = np.asarray(out[2][0][0][:len(ids) - 1])
    gap = np.abs(plp - want[np.arange(len(ids) - 1), ids[1:]])
    assert np.median(gap) > 100 * TOL_F32, how


# -- the reference's two instruments (chip_smoke.py's routing-cause phase
#    and lower-precision controls read them) --------------------------------


def test_reference_handed_its_own_choices_is_the_plain_reference(tree):
    """``forward`` returns the experts it chose, [layers, T, k] over the
    ROUTER's width; handed back they change nothing, and the benchmark's
    ``logits`` is that call."""
    ids = _ids(24) + [0]
    plain, chosen = REF.forward(MC, tree, ids, 24)
    assert chosen.shape == (CFG.num_layers, 25, CFG.num_experts_per_tok)
    assert int(chosen.max()) >= CFG.num_experts    # experts held elsewhere
    again, handed = REF.forward(MC, tree, ids, 24, routing=chosen)
    assert np.array_equal(np.asarray(handed), np.asarray(chosen))
    assert np.abs(np.asarray(again) - np.asarray(plain)).max() < 1e-5
    assert np.array_equal(np.asarray(REF.logits(MC, tree, ids, 24)),
                          np.asarray(plain))


@pytest.mark.parametrize("what", ["dropped", "wrong"])
def test_reference_handed_other_choices_moves_the_logits(tree, what):
    """Routing handed in is routing used: a held expert taken out of every
    token's choices (an id held elsewhere in its place), or its neighbour
    in its place, moves the rows — a flip is that, in one token-layer."""
    ids = _ids(24) + [0]
    plain, chosen = REF.forward(MC, tree, ids, 24)
    chosen = np.asarray(chosen)
    e = int(np.bincount(chosen[chosen < CFG.num_experts]).argmax())
    other = CFG.router_width - 1 if what == "dropped" \
        else (e + 1) % CFG.num_experts
    moved, _ = REF.forward(MC, tree, ids, 24,
                           routing=np.where(chosen == e, other, chosen))
    assert np.abs(np.asarray(moved) - np.asarray(plain)).max() \
        > 100 * TOL_F32


@pytest.mark.parametrize("lower,least", [("state", 1e-4), ("act", 1e-2)])
def test_reference_one_precision_lower_is_another_answer(tree, lower, least):
    """The controls: a bfloat16 KDA state, float8 activations. Each moves
    the logprobs by more than float32 noise; how far at the served size is
    chip_smoke.py's reading."""
    ids = _ids(40) + [0]
    want = REF.logprobs(MC, tree, ids, 40)
    got = REF.logprobs(MC, tree, ids, 40, lower=lower)
    assert np.all(np.isfinite(got))
    assert np.abs(got - want).max() > least


# -- (b) the block form of the recurrence against token by token ------------


def _kda_inputs(N, T, H, d, decay, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = la._l2norm(jax.random.normal(ks[0], (N, T, H, d))) * d ** -0.5
    k = la._l2norm(jax.random.normal(ks[1], (N, T, H, d)))
    v = jax.random.normal(ks[2], (N, T, H, d))
    g = -jax.random.uniform(ks[3], (N, T, H, d)) * decay
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (N, T, H)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (N, H, d, d))


@pytest.mark.parametrize("decay", [0.05, 1.6, 12.0])
def test_block_form_equals_the_token_by_token_recurrence(decay):
    """Two chunks (the second starts from the first's state, ``pstart`` >
    0), the second padded with identity rows. At decay 12 a channel loses up
    to e^-192 inside a 16-row block: exp(-cumsum g) overflows float32 (its
    largest finite value is e^88) and the naive form gives NaN; the pairwise
    form does not."""
    N, T, H, d = 2, 80, 3, 8
    q, k, v, g, beta, S0 = _kda_inputs(N, T, H, d, decay)
    want_o, want_S = la.kda_scan(S0, q, k, v, g, beta)
    cut, pad = 48, 16
    o1, S1 = la.kda_span(S0, *(a[:, :cut] for a in (q, k, v, g, beta)))
    rest = [jnp.pad(a[:, cut:], [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, g, beta)]       # padding: g = 0, beta = 0
    o2, S2 = la.kda_span(S1, *rest)
    got_o = jnp.concatenate([o1, o2[:, :T - cut]], axis=1)
    assert bool(jnp.isfinite(got_o).all())
    assert float(jnp.abs(got_o - want_o).max()) < 2e-5
    assert float(jnp.abs(S2 - want_S).max()) < 2e-5
    if decay * la.BLOCK > 100:
        G = jnp.cumsum(g[:, :la.BLOCK], axis=1)
        assert not bool(jnp.isfinite(jnp.exp(-G)).all())


def test_decode_step_equals_the_definition():
    """kda_step takes o from S' (one pass); the definition takes it from
    S_t."""
    q, k, v, g, beta, S = (a[:, 0] if a.ndim > 4 or i < 5 else a
                           for i, a in enumerate(_kda_inputs(4, 1, 3, 8, 1.0)))
    o, S_new = la.kda_step(S, q, k, v, g, beta)
    Sd = S * jnp.exp(g)[..., None]
    want = Sd + beta[..., None, None] * k[..., None] * (
        v - jnp.einsum("bhkv,bhk->bhv", Sd, k))[..., None, :]
    assert float(jnp.abs(S_new - want).max()) < 1e-6
    assert float(jnp.abs(
        o - jnp.einsum("bhkv,bhk->bhv", want, q)).max()) < 1e-6


# -- (c), (f), (g), (h): through the Engine ---------------------------------


def _params(seed=32):
    return _widen(MAKER.make(MC, seed, False, sigma=SIGMA))


def _engine(params, **over):
    kw = dict(max_decode_slots=4, max_cache_len=64, prefill_buckets=(16, 32),
              dtype="float32", weights_dtype="bf16", prefix_cache=True,
              decode_horizon=2, page_size=16, decode_pipeline=1,
              ragged_attention=1, attention_impl="xla",
              kv_host_tier_bytes=0, derived_seed=7)
    kw.update(over)
    return Engine(CFG, params, ServingConfig(**kw))


def _drain(eng):
    for _ in range(10000):
        if not eng.step():
            return
    raise AssertionError("engine did not drain")


def _two_streams(eng):
    """The second request arrives under the first's live stream."""
    a = eng.submit(Request(prompt_ids=_ids(12, 3), max_tokens=14,
                           ignore_eos=True, logprobs=0))
    for _ in range(3):
        eng.step()
    b = eng.submit(Request(prompt_ids=_ids(20, 4), max_tokens=6,
                           ignore_eos=True, logprobs=0))
    _drain(eng)
    return a, b


def _ref_logprobs(params, r):
    ids = r.prompt_ids + r.generated
    rows = np.asarray(jax.nn.log_softmax(_ref_rows(params, ids), axis=-1))
    rows = rows[len(r.prompt_ids) - 1:-1]
    return rows, rows[np.arange(len(r.generated)), r.generated]


@pytest.fixture(scope="module")
def mixed_run():
    from aws_k8s_ansible_provisioner_tpu.serving import flightrec

    params = _params()
    eng = _engine(params)
    seen, orig = [], flightrec.record

    def tap(*a, **rec):
        if a[0] == "dispatch":
            seen.append(dict(rec))
        return orig(*a, **rec)

    flightrec.record = tap
    try:
        reqs = _two_streams(eng)
    finally:
        flightrec.record = orig
    return params, eng, reqs, seen


def test_mixed_step_streams_equal_the_separate_programs(mixed_run):
    """(c) decode rows + chunk rows in ONE program against the chunk and
    decode programs dispatched apart: the same tokens, and both the
    reference's."""
    params, eng, reqs, seen = mixed_run
    assert any(r["program"] == "mixed_step" for r in seen)
    apart = _engine(params, ragged_attention=0)
    for r, s in zip(reqs, _two_streams(apart)):
        assert r.generated == s.generated
        rows, ref_lp = _ref_logprobs(params, r)
        served = np.asarray([lp[0] for lp in r.logprob_data], np.float32)
        assert np.abs(served - ref_lp).max() < TOL_F32
        assert (rows.max(-1) - ref_lp).max() < TOL_F32


def test_dispatch_records_and_metrics_carry_the_new_fields(mixed_run):
    params, eng, reqs, seen = mixed_run
    k = CFG.num_experts_per_tok
    for r in seen:
        assert "kda_rows" in r and "kda_slots" in r
        if r["program"] in ("decode_steps", "mixed_step"):
            assert r["kda_slots"] == r["active"]
            assert r["kda_rows"] == r["horizon"] * r["active"] \
                + r.get("chunk_n", 0)
            assert 0 <= r["moe_rows_held"] <= r["moe_rows"]
            assert r["moe_experts_hit"] <= CFG.num_experts
        else:
            assert r["kda_rows"] == r["prompt_tokens"]
    held = sum(r["moe_rows_held"] for r in seen if "moe_rows" in r)
    chosen = sum(r["moe_rows"] for r in seen if "moe_rows" in r)
    # 4 of 16 experts held: about a quarter of the chosen pairs land here
    assert 0.1 < held / chosen < 0.45
    m = eng.metrics
    assert m.moe_rows_held.total() == pytest.approx(held)
    assert m.kda_rows.total() == sum(r["kda_rows"] for r in seen)
    assert m.kda_state_bytes.value() == la.state_bytes(CFG, 4, jnp.float32)
    assert m.kda_state_bytes.value() == sum(
        a.size * a.dtype.itemsize for n, a in eng.cache.items()
        if la.is_state(n))
    text = m.registry.render()
    for name in ("tpu_serve_kda_state_bytes", "tpu_serve_kda_rows_total",
                 "tpu_serve_moe_rows_held_total",
                 'tpu_serve_prefix_lookups_skipped_total{reason='
                 '"recurrent_state"}'):
        assert name in text
    assert k * sum(len(r.generated) for r in reqs) > 0


def test_a_slots_second_occupant_reads_no_stale_state():
    """(f) four requests through ONE slot: each reproduces what a fresh
    engine gives it, so no occupant starts from its predecessor's state."""
    params = _params()
    eng = _engine(params, max_decode_slots=1)
    prompts = [_ids(9, 11), _ids(17, 12), _ids(30, 13), _ids(5, 14)]
    served = []
    for p in prompts:
        r = eng.submit(Request(prompt_ids=p, max_tokens=5, ignore_eos=True,
                               logprobs=0))
        _drain(eng)
        served.append(r)
    for p, r in zip(prompts, served):
        fresh = _engine(params, max_decode_slots=1)
        f = fresh.submit(Request(prompt_ids=p, max_tokens=5, ignore_eos=True,
                                 logprobs=0))
        _drain(fresh)
        assert r.generated == f.generated
        assert np.allclose([lp[0] for lp in r.logprob_data],
                           [lp[0] for lp in f.logprob_data], atol=1e-6)
        _, ref_lp = _ref_logprobs(params, r)
        assert np.abs(np.asarray([lp[0] for lp in r.logprob_data])
                      - ref_lp).max() < TOL_F32


def test_the_same_prompt_twice_reuses_no_prefix():
    """(g) two whole pages of shared prompt, the prefix cache ON: a model
    with recurrent layers is never handed a prefix hit, and both answers
    are equal."""
    eng = _engine(_params())
    prompt = _ids(2 * PS, 21)
    outs = []
    for _ in range(2):
        r = eng.submit(Request(prompt_ids=list(prompt), max_tokens=4,
                               ignore_eos=True, logprobs=0))
        _drain(eng)
        outs.append(r)
    assert eng.metrics.prefix_tokens_reused.total() == 0
    assert eng.metrics.prefix_cache_hits.total() == 0
    assert eng.metrics.prefix_lookups_skipped.total() == 2
    assert outs[0].generated == outs[1].generated
    assert np.allclose([lp[0] for lp in outs[0].logprob_data],
                       [lp[0] for lp in outs[1].logprob_data], atol=1e-6)
    # nothing was indexed: a finished request's pages go back free
    assert all(a.stats()["pages_evictable"] == 0 for a in eng.allocators)


def test_preempt_then_resume_reproduces_the_stream():
    """(h) a pool of 6 pages under three growing streams: the newest is
    preempted, resumed by a prefill from token 0 (its state rebuilt, no
    prefix hit), and every stream is what an unconstrained engine gives."""
    params = _params()
    eng = _engine(params, kv_pool_pages=6, max_decode_slots=3)
    gens = 40
    reqs = [eng.submit(Request(prompt_ids=_ids(4, 30 + i), max_tokens=gens,
                               ignore_eos=True)) for i in range(3)]
    _drain(eng)
    assert int(eng.metrics.preemptions.total()) > 0
    assert eng.metrics.prefix_tokens_reused.total() == 0
    free = _engine(params, max_decode_slots=3)
    for i, r in enumerate(reqs):
        f = free.submit(Request(prompt_ids=_ids(4, 30 + i), max_tokens=gens,
                                ignore_eos=True))
        _drain(free)
        assert r.generated == f.generated, f"stream {i} diverged"


# -- (d), (e): the expert layer --------------------------------------------


def _ffn_params(cfg, key, lead=()):
    from aws_k8s_ansible_provisioner_tpu.models.layers import (
        _init_ffn_params)

    p = _init_ffn_params(cfg, key, jnp.float32, lead)
    p["router"]["kernel"] = p["router"]["kernel"] * 50.0   # logits of std ~8
    return p


def test_every_shares_part_plus_the_shared_expert_once_is_the_uncut_layer():
    """(d) the model-configs guide's tie: 16 experts over 8 chips, 2 held
    each. The 8 shares' routed parts and the shared expert counted ONCE sum
    to the layer that holds all 16, in both forms of the expert sum."""
    from aws_k8s_ansible_provisioner_tpu.models.layers import _mlp

    whole = tiny_solar(num_experts=16, n_routed_experts=16)
    p = _ffn_params(whole, jax.random.PRNGKey(3))
    p["router"]["bias"] = jax.random.normal(jax.random.PRNGKey(4), (16,)) * .3
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 12, whole.hidden_size))
    want = _mlp(whole, x, p)
    for form_max in (moe.EVERY_EXPERT_MAX_ROW_EXPERTS, 0):
        old, moe.EVERY_EXPERT_MAX_ROW_EXPERTS = \
            moe.EVERY_EXPERT_MAX_ROW_EXPERTS, form_max
        try:
            routed = 0
            for c in range(8):
                share = tiny_solar(num_experts=2, n_routed_experts=16,
                                   expert_offset=2 * c, n_shared_experts=0)
                ps = {"router": p["router"], **{
                    n: {"kernel": p[n]["kernel"][2 * c:2 * c + 2]}
                    for n in ("w_gate", "w_up", "w_down")}}
                routed = routed + _mlp(share, x, ps)
            only_shared = _mlp(whole, x, p) - _mlp(
                whole.scaled(n_shared_experts=0), x, p)
        finally:
            moe.EVERY_EXPERT_MAX_ROW_EXPERTS = old
        assert float(jnp.abs(routed + only_shared - want).max()) < 1e-5


def test_the_selection_bias_changes_who_is_chosen_and_no_weight():
    """(e) with a bias the top-k differ from the unbiased top-k, and every
    chosen expert's weight is its own score over the chosen scores' sum —
    the bias appears in no weight."""
    cfg = tiny_solar()
    x = jax.random.normal(jax.random.PRNGKey(0), (64, cfg.hidden_size))
    kern = jax.random.normal(jax.random.PRNGKey(1),
                             (cfg.hidden_size, 16)) * 0.2
    bias = jax.random.normal(jax.random.PRNGKey(2), (16,)) * 0.5
    w0, i0 = moe.route(cfg, x, kern, jnp.zeros(16))
    w1, i1 = moe.route(cfg, x, kern, bias)
    assert bool((jnp.sort(i0, -1) != jnp.sort(i1, -1)).any())
    scores = jax.nn.sigmoid(x @ kern)
    chosen = jnp.take_along_axis(scores, i1, axis=-1)
    assert float(jnp.abs(
        w1 - chosen / chosen.sum(-1, keepdims=True)).max()) < 1e-6
    # the biased choice maximises score + bias, not score
    top = jnp.sort(jax.lax.top_k(scores + bias, 2)[1], -1)
    assert bool((jnp.sort(i1, -1) == top).all())


# -- (i) what cannot be right yet is refused at start-up --------------------


REFUSED = {
    "tp": (dict(mesh=MeshConfig(tp=2)), "no sharding rule"),
    "spec-decode": (dict(spec_decode=True), "no snapshot exists"),
    "host-tier": (dict(kv_host_tier_bytes=1 << 20), "without the recurrent"),
    "int8-kv": (dict(kv_dtype="int8", page_size=32), "float32"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_start_up_refuses(what):
    over, sentence = REFUSED[what]
    with pytest.raises(ValueError, match=sentence):
        _engine(init_params(CFG, jax.random.PRNGKey(0), jnp.float32), **over)


def test_start_up_refuses_lora_and_gshard():
    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    with pytest.raises(ValueError, match="no adapter layout"):
        Engine(CFG, params, ServingConfig(
            max_decode_slots=2, max_cache_len=64, kv_host_tier_bytes=0),
            lora={"a": "/nonexistent"})
    cfg = CFG.scaled(moe_impl="gshard")
    with pytest.raises(ValueError, match="held elsewhere"):
        Engine(cfg, params, ServingConfig(
            max_decode_slots=2, max_cache_len=64, kv_host_tier_bytes=0))


# -- (j) the models the benchmark has: their step programs did not change ---

# sha256 of str(jax.make_jaxpr(...)) at the parent commit (45277cc), taken
# with this very function: every new operand, field and branch is behind a
# configuration key these models do not set. The four ``mixed_step`` /
# ``prefill_step`` hashes were re-taken by PR 35 (the head over the sampled
# rows: tests/test_minicpm_sala.py says what changed in them), and all six
# by PR 38 (the sampler's candidates behind one ``cond``: the same file); the
# two ``mixed_step`` hashes again by PR 46 (one table row a slot and a row
# map for every model; the fallback gathers ``table[row_map]``); the two
# ``decode_steps`` hashes by PR 50, in the served form (the ``steps``
# operand: tests/test_minicpm_sala.py says what changed in them).
PINNED = {
    ("tiny-qwen3", "decode_steps"):
        "988ed9e0463c4593",
    ("tiny-qwen3", "mixed_step"):
        "87bf59bd4df3281b",
    ("tiny-olmoe", "decode_steps"):
        "f8640e998e7a20d6",
    ("tiny-olmoe", "mixed_step"):
        "c6e13478d3763fe2",
    ("tiny-qwen3", "prefill_step"):
        "34d3281612f23ac5",
    ("tiny-olmoe", "prefill_step"):
        "fe74d853601263b8",
}


def jaxpr_hash(cfg, program):
    """sha256 of one step program's jaxpr at a fixed tiny shape."""
    B, C, pps, ps = 2, 16, 4, 16
    params = jax.eval_shape(
        lambda: quantize_params(init_params(cfg, jax.random.PRNGKey(0),
                                            jnp.bfloat16), cfg))
    cache = jax.eval_shape(lambda: kvp.init_pool(cfg, B * pps + 1, ps))
    sds = jax.ShapeDtypeStruct
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    live = sds((B,), jnp.bool_) if cfg.num_experts > 0 else None
    row = dict(table=sds((B, pps), i32), seeds=sds((B,), u32),
               ban_ids=sds((B, pg.BAN_K), i32), ban_until=sds((B,), i32),
               bias_ids=sds((B, pg.BIAS_K), i32),
               bias_vals=sds((B, pg.BIAS_K), f32), live=live)
    if program == "decode_steps":
        fn = lambda p, c, *a, **k: pg.decode_steps(cfg, 2, p, c, *a,
                                                   impl="xla", **k)
        args = (params, cache, sds((B,), i32), sds((B,), i32), rng,
                sds((B,), f32), sds((B,), i32), sds((B,), f32))
        kw = dict(row, steps=sds((), i32))      # the served form: a count
    elif program == "mixed_step":
        fn = lambda p, c, *a, **k: pg.mixed_step(cfg, p, c, *a, impl="xla",
                                                 **k)
        args = (params, cache, sds((B,), i32), sds((B,), i32),
                sds((1, C), i32), sds((), i32), sds((), i32), sds((), i32),
                sds((), f32), sds((cfg.vocab_size,), jnp.bool_),
                sds((), u32), sds((), f32), sds((), i32), sds((), f32), rng,
                sds((B,), f32), sds((B,), i32), sds((B,), f32))
        kw = row
    else:
        fn = lambda p, c, *a, **k: pg.prefill_step(cfg, p, c, *a, **k)
        args = (params, cache, sds((1, C), i32), sds((), i32), rng,
                sds((), f32), sds((), i32), sds((), f32))
        kw = dict(pages=sds((pps,), i32), seed=sds((), u32),
                  ban_ids=sds((pg.BAN_K,), i32), ban_until=sds((), i32),
                  bias_ids=sds((pg.BIAS_K,), i32),
                  bias_vals=sds((pg.BIAS_K,), f32), rep=sds((), f32))
    text = str(jax.make_jaxpr(fn)(*args, **kw))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("model,program", sorted(PINNED))
def test_existing_models_step_program_jaxprs_are_unchanged(model, program):
    cfg = {"tiny-qwen3": tiny_qwen3, "tiny-olmoe": tiny_olmoe}[model]()
    assert jaxpr_hash(cfg, program) == PINNED[(model, program)]


# -- layout and bytes -------------------------------------------------------


@pytest.mark.parametrize("quant", [True, False], ids=["int8", "bf16"])
def test_seeded_tree_has_the_programs_layout(quant):
    def theirs():
        p = init_params(CFG, jax.random.PRNGKey(0), jnp.bfloat16)
        return quantize_params(p, CFG) if quant else p

    want = jax.eval_shape(theirs)
    got = MAKER.make(MC, 5, quant)
    flat = lambda t: {jax.tree_util.keystr(k): (tuple(v.shape), str(v.dtype))
                      for k, v in jax.tree_util.tree_leaves_with_path(t)}
    assert flat(got) == flat(want)
    assert {"".join(f"['{p}']" for p in k): v
            for k, v in MAKER.tree_spec(MC, quant).items()} == flat(want)


def test_pool_holds_only_the_attending_layers_and_bytes_count_both():
    pool = kvp.init_pool(CFG, 9, PS, jnp.bfloat16)
    assert pool["k"].shape[0] == CFG.num_periods == 2
    assert kvp.pool_bytes(CFG, 9, PS) == sum(
        a.size * a.dtype.itemsize for a in pool.values())
    state = la.init_state(CFG, 5)
    assert state["kda_state"].shape == (2, 3, 5, 4, 16, 16)
    assert state["kda_state"].dtype == jnp.float32
    assert state["kda_conv"].shape == (2, 3, 5, 3, 3 * 64)
    assert la.state_bytes(CFG, 5) == sum(
        a.size * a.dtype.itemsize for a in state.values())
    assert la.state_bytes(tiny_qwen3(), 5) == 0


def test_aot_plan_sizes_the_state_beside_the_pool_and_no_spec_table_is_read():
    """The ahead-of-time plan of such a model: its abstract operands are the
    engine's (int8 tree, pool + per-slot state), the ledger counts the state
    with the pool, and no PartitionSpec table is asked for (there is none:
    ``param_pspecs`` says so instead of guessing)."""
    from aws_k8s_ansible_provisioner_tpu.config import ServingConfig
    from aws_k8s_ansible_provisioner_tpu.parallel.sharding import (
        param_pspecs)
    from aws_k8s_ansible_provisioner_tpu.serving import aot

    serving = ServingConfig(model="tiny-solar", max_decode_slots=4,
                            max_cache_len=64, page_size=8,
                            prefill_buckets=(16, 32), weights_dtype="int8")
    plan = aot.ProgramPlan(CFG, serving)
    params, cache = aot._abstract_state(plan, None)
    assert params["layers"]["kda"]["wq"]["kernel"].dtype == jnp.int8
    assert cache["kda_state"].shape[:3] == (2, 3, plan.num_slots)
    ledger = aot.build_ledger(plan, None, params, cache, [])
    assert ledger["kv_bytes_per_chip"] == kvp.pool_bytes(
        CFG, plan.total_pages, serving.page_size) \
        + la.state_bytes(CFG, plan.num_slots)
    assert ledger["params_bytes_per_chip"] == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    with pytest.raises(ValueError, match="no rule says how"):
        param_pspecs(CFG)


def test_layer_pattern_is_validated():
    with pytest.raises(ValueError, match="one 'g'"):
        tiny_solar(layer_pattern="ggkk")
    with pytest.raises(ValueError, match="whole number of periods"):
        tiny_solar(num_layers=6)
    assert tiny_qwen3().num_attn_layers == tiny_qwen3().num_layers
    assert not tiny_qwen3().recurrent and not tiny_olmoe().expert_share


# -- the decode update's kernel ---------------------------------------------


def test_decode_kernel_equals_the_xla_step_in_place():
    """``kda_decode_update`` (interpret mode here) against ``kda_step`` on
    one layer of a [P, n_k, B, H, d, d] leaf: the addressed layer's slots
    change, a dead row (g = 0, beta = 0) and every other layer do not."""
    P, nk, B, H, d = 2, 3, 3, 16, 128
    q, k, v, g, beta, _ = (a[:, 0] if a.ndim > 3 else a
                           for a in _kda_inputs(B, 1, H, d, 1.0))
    g, beta = g.at[1].set(0.0), beta[:, 0].at[1].set(0.0)
    state = jax.random.normal(jax.random.PRNGKey(9), (P, nk, B, H, d, d))
    want_o, want_S = la.kda_step(state[1, 2], q, k, v, g, beta)
    got_o, got = la.kda_decode_update(state, jnp.int32(1), 2, q, k, v, g,
                                      beta, interpret=True)
    assert float(jnp.abs(got_o - want_o).max()) < 1e-5
    assert float(jnp.abs(got[1, 2] - want_S).max()) < 1e-5
    assert bool((got[0] == state[0]).all())
    assert bool((got[1, :2] == state[1, :2]).all())
    assert bool((got[1, 2, 1] == state[1, 2, 1]).all())
