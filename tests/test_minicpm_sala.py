"""The MiniCPM-SALA hybrid (selecting attention layers and Lightning
linear-attention layers in a LIST that is no period, muP scales) at a tiny
size on the CPU: hidden 64, 4 heads / 2 KV heads of 16, page 8, top-4 blocks
of 8, a local window of 2 blocks, dense length 32, the list ``s l l s s l``.

The reference (benchmark/reference/minicpm_sala.py) is float32 at matmul
precision "highest", runs the Lightning recurrence token by token and the
selection exactly as published (pooled keys by mean, a window-overlap matrix,
a stable sort), imports nothing from the program and selects on its own
activations. The served side is the code the step programs run: the paged
pool for the three attending layers with the selector's run sums beside K/V,
the per-slot Lightning state, ``model_forward_carry`` over runs of a kind,
ops/sparse_attention.py, ops/linear_attention.py and the paged kernels
(interpret mode).

Tolerance, LOGITS of std 0.64: with float32 activations the served
mathematics IS the reference's — the block form of the recurrence, run sums
for means, the orders of summation differ — so every row agrees to TOL_F32 =
5e-4 (measured 2e-6 to 6e-6; the maker's q/k gains keep block scores apart,
so no near-tie flips a selection between the two). Each way of getting a
block wrong moves every row by far more (``test_tolerance_catches``).
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
    ModelConfig, ServingConfig, tiny_olmoe, tiny_qwen3, tiny_sala,
    tiny_solar)
from aws_k8s_ansible_provisioner_tpu.models import layers as L  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.models.quant import (  # noqa: E402
    quantize_params, weights_quantized)
from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.ops import (  # noqa: E402
    linear_attention as la)
from aws_k8s_ansible_provisioner_tpu.ops import (  # noqa: E402
    pallas_attention as pa)
from aws_k8s_ansible_provisioner_tpu.ops import (  # noqa: E402
    sparse_attention as sa)
from aws_k8s_ansible_provisioner_tpu.serving import programs as pg  # noqa: E402
from aws_k8s_ansible_provisioner_tpu.serving.engine import (  # noqa: E402
    Engine, Request)

TOL_F32 = 5e-4
PS, PPS, SLOTS = 8, 16, 3       # page size, pages per slot, slots
CFG = tiny_sala()
MC = dataclasses.asdict(CFG)
MAKER = files.load_module("weight_makers", "minicpm_sala")
REF = files.load_module("reference", "minicpm_sala")


def _widen(tree):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


@pytest.fixture(scope="module", params=["bf16", "int8"])
def tree(request):
    """Seeded weights, float32 activations (int8 kernels stay int8)."""
    return _widen(MAKER.make(MC, 32, request.param == "int8"))


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(2, CFG.vocab_size, n).tolist()


def _ref(tree, ids, mc=MC, **kw):
    """Reference log-softmax rows (row j predicts the token after
    ids[:j + 1]) and the blocks it chose."""
    if "selection" in kw:       # (the trailing token's row: anything)
        kw["selection"] = np.concatenate(
            [kw["selection"], kw["selection"][:, -1:]], axis=1)
    lg, sel = REF.forward(mc, tree, list(ids) + [0], len(ids), **kw)
    return (np.asarray(jax.nn.log_softmax(lg, axis=-1)),
            np.asarray(sel)[:, :len(ids)])


def _cache(cfg=CFG):
    c = kvp.init_pool(cfg, SLOTS * PPS + 1, PS, jnp.float32)
    c.update(la.init_state(cfg, SLOTS, jnp.float32))
    return c


def _table():
    return jnp.asarray([[1 + s * PPS + p for p in range(PPS)]
                        for s in range(SLOTS)], jnp.int32)


def _sampling(n=None):
    shape = () if n is None else (n,)
    return (jax.random.PRNGKey(0), jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape, jnp.int32), jnp.ones(shape, jnp.float32))


def _bias_kw(n=None):
    lead = () if n is None else (n,)
    return dict(bias_ids=jnp.full(lead + (pg.BIAS_K,), 2**31 - 1, jnp.int32),
                bias_vals=jnp.zeros(lead + (pg.BIAS_K,), jnp.float32),
                ban_ids=jnp.full(lead + (pg.BAN_K,), 2**31 - 1, jnp.int32),
                ban_until=jnp.zeros(lead, jnp.int32))


def _prefill(cfg, tree, cache, slot, ids, bucket=64):
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(ids)] = ids
    out = pg.prefill_step(
        cfg, tree, cache, jnp.asarray(toks), jnp.int32(len(ids)),
        *_sampling(), pages=_table()[slot], seed=jnp.uint32(1),
        rep=jnp.float32(1.0), slot=jnp.int32(slot), prompt_logprobs=True,
        **_bias_kw())
    return out[0], int(out[1]), out


def _decode(cfg, tree, cache, tokens, lengths, live, impl):
    B = SLOTS
    out = pg.decode_steps(
        cfg, 1, tree, cache, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(lengths, jnp.int32), *_sampling(B), table=_table(),
        impl=impl, logprobs=True, seeds=jnp.ones(B, jnp.uint32),
        live=jnp.asarray(live), **_bias_kw(B))
    return out[0], out[2], out[5]


# -- (a) the reference against prefill_step then decode_steps ---------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_the_cache_match_the_reference(tree,
                                                                   impl):
    """61 tokens through ``prefill_step`` into slot 1 (past the dense length
    of 32: the bucket's later rows select), then one ``decode_steps``
    dispatch a token through the pool, the selector's runs and the Lightning
    state, to 110: every pooled key completes mid-decode (one every 2
    tokens), six page boundaries are crossed, and with ``pallas`` the kernel
    walks the LISTS of selected pages (interpret mode). Every chosen
    logprob is the reference's, and the reference's selections differ
    between the two KV heads."""
    ids = _ids(110)
    n_prompt = 61
    want, sel = _ref(tree, ids)
    past = np.arange(len(ids)) >= CFG.sparse_dense_len
    assert (sel[:, past, 0] != sel[:, past, 1]).any(axis=-1).mean() > 0.3
    assert sel[:, past].sum(axis=-1).max() == CFG.sparse_topk
    cache, tok, out = _prefill(CFG, tree, _cache(), 1, ids[:n_prompt])
    assert tok == int(want[n_prompt - 1].argmax())
    plp = np.asarray(out[2][0][0][:n_prompt - 1])
    assert np.abs(plp - want[np.arange(n_prompt - 1),
                             ids[1:n_prompt]]).max() < TOL_F32
    for t in range(n_prompt, len(ids)):
        cache, (toks, (lp, _, _)), aux = _decode(
            CFG, tree, cache, [0, ids[t], 0], [0, t, 0],
            [False, True, False], impl)
        assert int(toks[0, 1]) == int(want[t].argmax())
        assert abs(float(lp[0, 1]) - want[t].max()) < TOL_F32
    # the program's own count of the last step: one live row, 3 selecting
    # layers x 2 KV heads, ceil(110 / 8) live pages, top-4 read
    assert [int(x) for x in aux] == [3 * 2 * 14, 3 * 2 * 4]


def _no_decay(monkeypatch):
    monkeypatch.setattr(L, "lightning_slopes",
                        lambda n: jnp.zeros((n,), jnp.float32))
    return CFG.scaled(name="tiny-sala-no-decay")    # (another jit key)


WRONG = {
    "no-selection": lambda mp: CFG.scaled(sparse_dense_len=10**6),
    "no-decay": _no_decay,
    "no-attention-gate": lambda mp: CFG.scaled(attn_output_gate=False),
    "no-residual-scale": lambda mp: CFG.scaled(scale_depth=0.0),
    "no-embedding-scale": lambda mp: CFG.scaled(scale_emb=1.0),
    "no-logit-scale": lambda mp: CFG.scaled(dim_model_base=0),
    "rope-on-the-attention-layers": lambda mp: CFG.scaled(attn_use_rope=True),
}


@pytest.mark.parametrize("how", sorted(WRONG))
def test_tolerance_catches(tree, how, monkeypatch):
    ids = _ids(60)
    want, _ = _ref(tree, ids)
    _, _, out = _prefill(WRONG[how](monkeypatch), tree, _cache(), 0, ids)
    plp = np.asarray(out[2][0][0][:len(ids) - 1])
    gap = np.abs(plp - want[np.arange(len(ids) - 1), ids[1:]])
    # (rows under the dense length select everything either way)
    rows = slice(CFG.sparse_dense_len, None) if how == "no-selection" \
        else slice(None)
    assert np.median(gap[rows]) > 20 * TOL_F32, how


# -- the reference's two instruments ----------------------------------------


def test_reference_handed_its_own_choices_is_the_plain_reference(tree):
    ids = _ids(70, 5)
    want, sel = _ref(tree, ids)
    again, took = _ref(tree, ids, selection=sel)
    assert (took == sel).all()
    assert np.abs(again - want).max() < 1e-6


@pytest.mark.parametrize("what", ["forced-only", "other-head"])
def test_reference_handed_other_choices_moves_the_logits(tree, what):
    ids = _ids(70, 5)
    want, sel = _ref(tree, ids)
    if what == "other-head":
        handed = sel[:, :, ::-1]
    else:
        t = np.arange(len(ids))[:, None]
        blk = np.arange(sel.shape[-1])[None, :]
        own = t // CFG.sparse_block_size
        forced = (blk < 1) | ((blk > own - 2) & (blk <= own))
        handed = sel & (forced | (t < CFG.sparse_dense_len))[None, :, None]
    got, _ = _ref(tree, ids, selection=handed)
    past = slice(CFG.sparse_dense_len, None)
    assert np.abs(got - want)[past].max() > 100 * TOL_F32


@pytest.mark.parametrize("lower,least", [("state", 1e-4), ("act", 1e-2)])
def test_reference_one_precision_lower_is_another_answer(tree, lower, least):
    ids = _ids(50, 6)
    want, _ = _ref(tree, ids)
    got, _ = _ref(tree, ids, lower=lower)
    assert np.abs(got - want).max() > least


def test_the_program_handed_the_references_selection_reads_it(tree):
    """``make_stateless_attend_select(handed=...)``: the instrument
    chip_smoke.py's ``check_selection_cause`` hands selections with."""
    ids = _ids(64, 7)
    _, sel = _ref(tree, ids)
    lp = jax.tree.map(lambda a: a[1], tree["layers"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, CFG.hidden_size))
    zero = jnp.zeros((1, 64, 0))
    run = lambda attend: L.decoder_block(
        CFG, lp, x, zero, zero, lambda q, k, v, c: attend(q, k, v, None),
        None)
    own_x, own_sel = run(sa.make_stateless_attend_select(CFG))
    other = jnp.asarray(sel[1][None, :, ::-1])
    took_x, took_sel = run(sa.make_stateless_attend_select(CFG,
                                                           handed=other))
    assert (np.asarray(took_sel) == np.asarray(other)).all()
    assert float(jnp.abs(took_x - own_x)[0, CFG.sparse_dense_len:].max()) \
        > 100 * TOL_F32
    same_x, _ = run(sa.make_stateless_attend_select(CFG, handed=own_sel))
    assert float(jnp.abs(same_x - own_x).max()) < 1e-6


# -- the selection, unit by unit --------------------------------------------

SEL = tiny_sala(sparse_topk=3, sparse_window_size=8)     # 1 local block


def _select(scores, T, cfg=SEL):
    s = jnp.asarray(scores, jnp.float32)[None, None]
    return np.asarray(sa.select_blocks(cfg, s, jnp.asarray([T])))[0, 0]


def test_forced_blocks_are_always_read():
    """Block 0 (``init_blocks``) and the query's own (the local window) are
    selected whatever they score; one learned block fills the top-3."""
    scores = [0.0, 0.1, 0.9, 0.2, 0.3, 0.0, -np.inf, -np.inf]
    assert _select(scores, T=48).tolist() == [
        True, False, True, False, False, True, False, False]


def test_ties_go_to_the_lower_index():
    scores = [0.0, 0.5, 0.5, 0.5, 0.1, 0.0, -np.inf, -np.inf]
    assert _select(scores, T=48).nonzero()[0].tolist() == [0, 1, 5]
    wide = tiny_sala(sparse_topk=4, sparse_window_size=8)
    assert _select(scores, T=48, cfg=wide).nonzero()[0].tolist() \
        == [0, 1, 2, 5]


def test_a_context_under_the_dense_length_reads_every_block():
    scores = [0.0, 0.9, 0.1, 0.5, 0.0, 0.0, 0.0, 0.0]
    assert _select(scores, T=31).tolist() == [True] * 4 + [False] * 4
    assert _select(scores, T=32).sum() == 3          # dense_len is 32
    assert not _select(scores, T=0).any()            # a dead row


def test_a_block_that_starts_after_the_query_is_never_read():
    scores = [0.0, 0.1, 0.2, 0.3, 0.4, 9.0, 9.0, 9.0]
    assert _select(scores, T=33).nonzero()[0].tolist() == [0, 3, 4]


def test_block_scores_pool_the_windows_that_overlap_a_block():
    """One KV head, one query head, a key pattern whose pooled logits are
    known: run r holds the key ``r * e0`` (twice: stride 2), so window j
    (runs j, j + 1) pools to ``(2 j + 1) / 2 * e0``; the block's score is its
    best window's probability, and the window that starts in the block
    BEFORE counts too."""
    cfg = tiny_sala(num_heads=1, num_kv_heads=1)
    M, D = 16, cfg.head_dim
    runs = jnp.zeros((1, M, D)).at[0, :, 0].set(2.0 * jnp.arange(M))
    q = jnp.zeros((1, 1, D)).at[0, 0, 0].set(4.0)
    T = 21      # windows j = 0 .. 8 are whole (2 j + 4 <= 21)
    b = np.asarray(sa.block_scores(cfg, q, runs, jnp.asarray([T])))[0, 0]
    logit = 4.0 * (2 * np.arange(9) + 1) / 2 / np.sqrt(D)
    p = np.exp(logit - logit.max())
    p /= p.sum()
    assert np.allclose(b[0], p[:4].max(), atol=1e-6)
    assert np.allclose(b[1], p[3:8].max(), atol=1e-6)      # 3 = the one before
    assert np.allclose(b[2], p[7:9].max(), atol=1e-6)
    assert (b[3:] == -np.inf).all()


def test_the_selectors_cache_restarts_a_run_its_key_opens():
    """The row add (kernel and XLA form alike) against sums taken afresh: a
    run's first key replaces what a page's last occupant left, later keys
    add; a dropped row (-1) and an untouched page keep their content."""
    cfg = CFG
    B, MP, L = 4, 3, 2
    stale = jax.random.normal(jax.random.PRNGKey(0),
                              (L, B * MP + 1, 2, PS // 2, 16))
    table = jnp.asarray(np.arange(1, B * MP + 1).reshape(B, MP), jnp.int32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, 2, 16))
    rows = jnp.asarray([8, 13, -1, 23], jnp.int32)
    want = np.asarray(stale).copy()
    want[1, 2, :, 0] = np.asarray(k[0])                    # row 8 opens a run
    want[1, 5, :, 2] += np.asarray(k[1])                   # row 13 continues
    want[1, 12, :, 3] += np.asarray(k[3])                  # row 23 continues
    for impl in ("xla", "pallas"):
        got = sa.add_rows(cfg, stale + 0, jnp.int32(1), rows, table, k, impl)
        assert np.abs(np.asarray(got) - want).max() < 1e-6, impl
    # a span from an unaligned start, over three pages of slot 1
    ks = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 2, 16))
    got = np.asarray(sa.add_span(cfg, stale + 0, jnp.int32(0), table[1:2],
                                 jnp.int32(5), ks, jnp.int32(13)))
    want = np.asarray(stale).copy()
    for i in range(13):
        t = 5 + i
        page, run = int(table[1, t // PS]), (t % PS) // 2
        if t % 2 == 0:              # this key opens its run
            want[0, page, :, run] = 0.0
        want[0, page, :, run] += np.asarray(ks[0, i])
    assert np.abs(got - want).max() < 1e-5


# -- Lightning: span = scan = steps -----------------------------------------


@pytest.mark.parametrize("T", [64, 150])
def test_block_form_equals_the_token_by_token_recurrence(T):
    N, H, d = 2, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v = (jax.random.normal(kk, (N, T, H, d)) for kk in ks[:3])
    S0 = jax.random.normal(ks[3], (N, H, d, d))
    live = jnp.arange(T)[None] < jnp.asarray([T, T - 9])[:, None]
    g, beta = la._lin_decay(L.lightning_slopes(H), live)
    want_o, want_S = la.lightning_scan(S0, q, k, v, g, beta)
    qp, kp, vp, gp, bp = la._pad_to(la.LIN_BLOCK, (q, k, v, g, beta), T)
    got_o, got_S = la.lightning_span(S0, qp, kp, vp, gp, bp)
    scale = float(jnp.abs(want_o).max())        # outputs of tens
    assert float(jnp.abs(got_o[:, :T] - want_o).max()) < 1e-5 * scale
    assert float(jnp.abs(got_S - want_S).max()) < 1e-5 * scale
    # the definition, token by token, in numpy
    lam = np.exp(-np.asarray(L.lightning_slopes(H)))[:, None, None]
    S = np.asarray(S0[0])
    for t in range(T):
        S = lam * S + np.asarray(k[0, t])[:, :, None] \
            * np.asarray(v[0, t])[:, None, :]
    assert np.abs(S - np.asarray(want_S[0])).max() < 1e-5 * scale


def test_decode_kernel_without_the_delta_rule_is_the_lightning_step():
    """``kda_decode_update(delta_rule=False)`` (interpret mode) against
    ``lightning_step`` on one layer of a [n_l, 1, B, H, d, d] leaf: a dead
    row and every other layer do not change."""
    nl, B, H, d = 3, 3, 16, 128
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q, k, v = (jax.random.normal(kk, (B, H, d)) for kk in ks[:3])
    state = jax.random.normal(ks[3], (nl, 1, B, H, d, d))
    g, beta = la._lin_decay(L.lightning_slopes(H),
                            jnp.asarray([True, False, True]))
    want_o, want_S = la.lightning_step(state[2, 0], q, k, v, g, beta)
    got_o, got = la.kda_decode_update(
        state, jnp.int32(2), 0, q, k, v,
        jnp.broadcast_to(g[..., None], q.shape), beta, interpret=True,
        delta_rule=False)
    assert float(jnp.abs(got_o - want_o).max()) < 1e-4
    assert float(jnp.abs(got[2, 0] - want_S).max()) < 1e-4
    assert bool((got[:2] == state[:2]).all())
    assert bool((got[2, 0, 1] == state[2, 0, 1]).all())


def test_ragged_select_kernel_splits_rows_that_overflow_its_prefetch(
        monkeypatch):
    """``ragged_attend_pallas_paged_select`` (interpret mode): packed rows
    whose bit words do not fit the SMEM budget go in further calls of whole
    blocks (an 8,192-row chunk at a 512-page window on the chip); the
    answer is the one call's, and the dense form's."""
    B, C, Hq, Hkv, D, ps, MP, bb = 4, 12, 4, 2, 16, 8, 6, 4
    rng = np.random.default_rng(5)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    pool = {n: jax.random.normal(k, (1, B * MP + 1, Hkv, ps, D),
                                 jnp.bfloat16)
            for n, k in zip("kv", ks)}
    table = jnp.asarray((rng.permutation(B * MP) + 1).reshape(B, MP),
                        jnp.int32)
    pslot, off = 2, 21
    lims = np.concatenate([[40, 9, 0, 33], off + 1 + np.arange(C)])
    lims[-3:] = 0                                   # the chunk's padding
    row_map = jnp.asarray([0, 1, 2, 3] + [pslot] * C, jnp.int32)
    sel = rng.random((B + C, Hkv, MP)) < 0.6
    sel[:, :, 0] = True
    q = jax.random.normal(ks[2], (B + C, Hq, D), jnp.bfloat16)
    fn = pa.ragged_attend_pallas_paged_select.__wrapped__
    args = (q, pool["k"], pool["v"], jnp.asarray(lims, jnp.int32),
            jnp.int32(0), table, row_map, sa.as_bits(jnp.asarray(sel)))
    one = fn(*args, interpret=True, bblock=bb)
    calls = []
    real = pa._paged_flash_db
    monkeypatch.setattr(pa, "_paged_flash_db", lambda q, *a, **kw: (
        calls.append(q.shape[0]), real(q, *a, **kw))[1])
    monkeypatch.setattr(pa, "SELECT_PREFETCH_BYTES", 4 * table.size + 200)
    split = fn(*args, interpret=True, bblock=bb)
    assert calls == [8, 8]
    assert bool((split == one).all())
    dense = kvp.gather_layer_dense(pool, jnp.int32(0), table)
    want = jnp.stack([sa._attend_rows(
        q[r][None], dense["k"][row_map[r]], dense["v"][row_map[r]],
        jnp.asarray(lims[r:r + 1]), jnp.asarray(sel[r:r + 1]), ps)[0]
        for r in range(B + C)])
    assert float(jnp.abs(split.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) < 2e-2


# -- (e) mixed_step: chunks beside live rows, the state handed over ---------


def _params(seed=32):
    return _widen(MAKER.make(MC, seed, False))


def _engine(params, **over):
    kw = dict(max_decode_slots=4, max_cache_len=256, prefill_buckets=(16, 32),
              dtype="float32", weights_dtype="bf16", prefix_cache=True,
              decode_horizon=2, page_size=PS, decode_pipeline=1,
              ragged_attention=1, attention_impl="xla",
              kv_host_tier_bytes=0, derived_seed=7, prefill_chunk=32)
    kw.update(over)
    return Engine(CFG, params, ServingConfig(**kw))


def _drain(eng):
    for _ in range(10000):
        if not eng.step():
            return
    raise AssertionError("engine did not drain")


def _two_streams(eng):
    """A 90-token prompt (three chunks of 32, past the dense length) arrives
    under a live stream."""
    a = eng.submit(Request(prompt_ids=_ids(20, 3), max_tokens=30,
                           ignore_eos=True, logprobs=0))
    for _ in range(3):
        eng.step()
    b = eng.submit(Request(prompt_ids=_ids(90, 4), max_tokens=8,
                           ignore_eos=True, logprobs=0))
    _drain(eng)
    return a, b


def _ref_logprobs(params, r):
    ids = r.prompt_ids + r.generated
    rows = _ref(params, ids)[0][len(r.prompt_ids) - 1:-1]
    return rows, rows[np.arange(len(r.generated)), r.generated]


@pytest.fixture(scope="module", params=["xla", "pallas"])
def mixed_run(request):
    from aws_k8s_ansible_provisioner_tpu.serving import flightrec

    params = _params()
    eng = _engine(params, attention_impl=request.param)
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


def test_mixed_step_streams_are_the_references(mixed_run):
    """Decode rows + chunk rows in ONE program, three chunks a prompt (the
    Lightning state handed over between them, the later chunks' rows
    selecting; with ``pallas`` the ragged kernel under bit masks, one table
    row a slot): both streams are the reference's."""
    params, eng, reqs, seen = mixed_run
    mixed = [r for r in seen if r["program"] == "mixed_step"]
    assert [r["chunk_n"] for r in mixed] == [32, 32, 26]
    for r in reqs:
        rows, ref_lp = _ref_logprobs(params, r)
        served = np.asarray([lp[0] for lp in r.logprob_data], np.float32)
        assert np.abs(served - ref_lp).max() < TOL_F32
        assert (rows.max(-1) - ref_lp).max() < TOL_F32


def test_an_idle_engines_long_prompt_walks_the_mixed_program_too():
    """No live row, nothing in flight: a model that selects still walks its
    chunks through ``mixed_step`` (the one chunk program that reads a long
    window), and the answer is the reference's."""
    from aws_k8s_ansible_provisioner_tpu.serving import flightrec

    params = _params()
    eng = _engine(params)
    seen, orig = [], flightrec.record
    flightrec.record = lambda *a, **rec: (
        seen.append(rec["program"]) if a[0] == "dispatch" else None,
        orig(*a, **rec))[1]
    try:
        r = eng.submit(Request(prompt_ids=_ids(70, 9), max_tokens=6,
                               ignore_eos=True, logprobs=0))
        _drain(eng)
    finally:
        flightrec.record = orig
    assert seen.count("mixed_step") == 3 and "prefill_chunk_step" not in seen
    _, ref_lp = _ref_logprobs(params, r)
    assert np.abs(np.asarray([lp[0] for lp in r.logprob_data])
                  - ref_lp).max() < TOL_F32


def test_the_chunk_program_apart_gives_the_same_streams():
    """``ragged_attention=0``: chunks through ``prefill_chunk_step`` and
    decode steps dispatched apart."""
    params = _params()
    apart = _engine(params, ragged_attention=0)
    for r in _two_streams(apart):
        _, ref_lp = _ref_logprobs(params, r)
        assert np.abs(np.asarray([lp[0] for lp in r.logprob_data])
                      - ref_lp).max() < TOL_F32


def test_dispatch_records_and_metrics_carry_the_new_fields(mixed_run):
    params, eng, reqs, seen = mixed_run
    for r in seen:
        assert r["state_kind"] == "Lightning" and "kda_rows" not in r
        if r["program"] in ("decode_steps", "mixed_step"):
            assert r["state_slots"] == r["active"]
            assert r["state_rows"] == r["horizon"] * r["active"] \
                + r.get("chunk_n", 0)
            assert r["sparse_rows"] == r["state_rows"]
            assert 0 < r["sparse_pages_selected"] <= r["sparse_pages_live"]
            assert "attn_pages_walked" not in r
        else:
            assert r["state_rows"] == r["prompt_tokens"]
    # the second prompt's last chunk: 26 rows at 64..89, top-4 of 9-12 pages
    last = [r for r in seen if r["program"] == "mixed_step"][-1]
    assert last["sparse_pages_selected"] < 0.6 * last["sparse_pages_live"]
    m = eng.metrics
    live = sum(r.get("sparse_pages_live", 0) for r in seen)
    assert m.sparse_pages.total() == pytest.approx(
        live + sum(r.get("sparse_pages_selected", 0) for r in seen))
    assert m.state_rows.total() == sum(r["state_rows"] for r in seen)
    assert m.kda_rows.total() == 0
    state = la.state_bytes(CFG, 4, jnp.float32)
    assert m.kda_state_bytes.value() == state == sum(
        a.size * a.dtype.itemsize for n, a in eng.cache.items()
        if la.is_state(n))
    assert m.selector_cache_bytes.value() == eng.cache["kc"].size * 4 \
        == kvp.selector_bytes(CFG, eng.cache["kc"].shape[1], PS)
    text = m.registry.render()
    for name in ('tpu_serve_sparse_pages_total{kind="live"}',
                 'tpu_serve_sparse_pages_total{kind="selected"}',
                 'tpu_serve_state_rows_total{kind="Lightning"',
                 'tpu_serve_recurrent_state_bytes{kind="Lightning"}',
                 "tpu_serve_selector_cache_bytes",
                 'tpu_serve_prefix_lookups_skipped_total{reason='
                 '"recurrent_state"}'):
        assert name in text, name


def test_the_start_up_log_states_the_three_sizes(caplog):
    import logging

    with caplog.at_level(logging.INFO):
        _engine(L.init_params(CFG, jax.random.PRNGKey(0), jnp.float32))
    line = next(r.getMessage() for r in caplog.records
                if "KV pool" in r.getMessage())
    assert "selector's cache" in line and "Lightning layers x 4 slots" in line
    assert "3 attending layers" in line


def test_a_slots_second_occupant_reads_no_stale_state_or_runs():
    """Three requests through ONE slot, pages and runs reused: each
    reproduces what a fresh engine gives it."""
    params = _params()
    eng = _engine(params, max_decode_slots=1)
    prompts = [_ids(50, 11), _ids(70, 12), _ids(9, 13)]
    for p in prompts:
        r = eng.submit(Request(prompt_ids=p, max_tokens=5, ignore_eos=True,
                               logprobs=0))
        _drain(eng)
        _, ref_lp = _ref_logprobs(params, r)
        assert np.abs(np.asarray([lp[0] for lp in r.logprob_data])
                      - ref_lp).max() < TOL_F32


def test_preempt_then_resume_reproduces_the_stream():
    """A pool of 20 pages under three growing streams: the newest is
    preempted, resumed by a walk from token 0 over all but its last token
    (state and runs rebuilt, no prefix hit), and every stream is what an
    unconstrained engine gives."""
    params = _params()
    eng = _engine(params, kv_pool_pages=20, max_decode_slots=3,
                  max_cache_len=128)
    gens = 60
    reqs = [eng.submit(Request(prompt_ids=_ids(6, 30 + i), max_tokens=gens,
                               ignore_eos=True)) for i in range(3)]
    _drain(eng)
    assert int(eng.metrics.preemptions.total()) > 0
    assert eng.metrics.prefix_tokens_reused.total() == 0
    free = _engine(params, max_decode_slots=3, max_cache_len=128)
    for i, r in enumerate(reqs):
        f = free.submit(Request(prompt_ids=_ids(6, 30 + i), max_tokens=gens,
                                ignore_eos=True))
        _drain(free)
        assert r.generated == f.generated, f"stream {i} diverged"


# -- start-up refusals and validation ---------------------------------------

REFUSED = {
    "page-size": (dict(page_size=16), "a block is a page"),
    "spec-decode": (dict(spec_decode=True), "walks every page of a row"),
    "host-tier": (dict(kv_host_tier_bytes=1 << 20), "without the recurrent"),
    "int8-kv": (dict(kv_dtype="int8", page_size=32,), "bf16 pool|a page"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_start_up_refuses(what):
    over, sentence = REFUSED[what]
    with pytest.raises(ValueError, match=sentence) as e:
        _engine(L.init_params(CFG, jax.random.PRNGKey(0), jnp.float32),
                **over)
    assert "recurrent (Lightning) layers" in str(e.value)


def test_the_refusal_names_the_kind_that_is_there():
    with pytest.raises(ValueError, match=r"recurrent \(KDA\) layers"):
        Engine(tiny_solar(), L.init_params(tiny_solar(),
                                           jax.random.PRNGKey(0),
                                           jnp.float32),
               ServingConfig(max_decode_slots=2, max_cache_len=64,
                             kv_host_tier_bytes=0, spec_decode=True))
    only_s = tiny_sala(layer_pattern="ss", num_layers=2)
    with pytest.raises(ValueError, match="attention that selects its pages"):
        Engine(only_s, L.init_params(only_s, jax.random.PRNGKey(0),
                                     jnp.float32),
               ServingConfig(max_decode_slots=2, max_cache_len=64,
                             page_size=PS, kv_host_tier_bytes=0,
                             spec_decode=True))


BAD = {
    "a period needs one g": (dict(layer_pattern="ggkk", num_layers=8),
                             "one 'g'"),
    "k comes in a period": (dict(layer_pattern="slk", num_layers=3),
                            "come in a period"),
    "one character a layer": (dict(layer_pattern="sl", num_layers=6),
                              "names 2 layers, num_layers=6"),
    "g and s do not mix": (dict(layer_pattern="gsl", num_layers=3),
                           "all select"),
    "l needs its heads": (dict(lightning_num_heads=0), "lightning_num_heads"),
    "s needs its sizes": (dict(sparse_topk=0), "sparse_topk"),
    "a window is two strides": (dict(sparse_kernel_size=6),
                                "a window is two strides"),
    "whole local blocks": (dict(sparse_window_size=12),
                           "whole number of blocks"),
}


@pytest.mark.parametrize("what", sorted(BAD))
def test_layer_list_is_validated(what):
    over, sentence = BAD[what]
    with pytest.raises(ValueError, match=sentence):
        tiny_sala(**over)


def test_the_list_form_counts_its_kinds():
    assert CFG.layer_list and CFG.selects and CFG.recurrent
    assert CFG.num_attn_layers == 3 and CFG.num_recurrent_layers == 3
    assert CFG.recurrent_kinds == "Lightning"
    assert L.layer_runs("sllssl") == [("s", 0, 1), ("l", 0, 2), ("s", 1, 2),
                                      ("l", 2, 1)]
    assert L.layer_runs("slllllls") == [("s", 0, 1), ("l", 0, 6),
                                        ("s", 1, 1)]
    assert abs(CFG.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    assert CFG.logit_scale == 0.25
    solar = tiny_solar()
    assert not solar.layer_list and not solar.selects
    assert solar.num_recurrent_layers == 6 and solar.recurrent_kinds == "KDA"
    plain = tiny_qwen3()
    assert plain.residual_scale == 1.0 and plain.logit_scale == 1.0
    assert not plain.layer_list and plain.num_recurrent_layers == 0
    # a list of plain attention layers is a list too (no selector, no state)
    gg = tiny_qwen3(layer_pattern="gg", num_layers=2)
    assert gg.layer_list and gg.num_attn_layers == 2 and not gg.recurrent


def test_the_layer_body_is_traced_once_a_run_not_once_a_layer(monkeypatch):
    """The published 32-layer list has 9 runs, 5 of them of selecting
    layers: tracing its forward pass traces 5 selecting layer bodies, not
    the list's 8, and 4 Lightning bodies, not 24."""
    pat = "s" + "l" * 8 + "s" + "l" * 6 + "ss" + "llll" + "s" + "l" * 6 \
        + "sss"
    cfg = tiny_sala(layer_pattern=pat, num_layers=32)
    assert len(L.layer_runs(pat)) == 9
    calls = {"s": 0, "l": 0}
    select, span = sa.select_blocks, la.lightning_span

    def counted(kind, fn):
        def wrapper(*a, **k):
            calls[kind] += 1
            return fn(*a, **k)
        return wrapper

    monkeypatch.setattr(sa, "select_blocks", counted("s", select))
    monkeypatch.setattr(la, "lightning_span", counted("l", span))
    params = jax.eval_shape(
        lambda: L.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    jax.make_jaxpr(
        lambda p: L.model_forward(p, cfg, jnp.zeros((1, 16), jnp.int32),
                                  jnp.arange(16)[None])[0])(params)
    assert calls == {"s": 5, "l": 4}


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


def test_pool_holds_the_attending_layers_the_runs_and_bytes_count_all():
    pool = kvp.init_pool(CFG, 9, PS, jnp.bfloat16)
    assert pool["k"].shape == (3, 9, 2, PS, 16)
    assert pool["kc"].shape == (3, 9, 2, PS // 2, 16) \
        and pool["kc"].dtype == jnp.float32
    assert kvp.pool_bytes(CFG, 9, PS) == sum(
        a.size * a.dtype.itemsize for a in pool.values())
    assert "kc" not in kvp.init_pool(tiny_qwen3(), 9, PS)
    state = la.init_state(CFG, 5)
    assert set(state) == {"lin_state"}
    assert state["lin_state"].shape == (3, 1, 5, 4, 16, 16)
    assert la.state_bytes(CFG, 5) == 3 * 5 * 4 * 16 * 16 * 4
    assert la.is_state("lin_state") and la.is_state("kda_conv") \
        and not la.is_state("kc")


def test_aot_plan_sizes_state_and_selector_beside_the_pool():
    from aws_k8s_ansible_provisioner_tpu.serving import aot

    serving = ServingConfig(model="tiny-sala", max_decode_slots=4,
                            max_cache_len=64, page_size=PS,
                            prefill_buckets=(16, 32), weights_dtype="int8")
    plan = aot.ProgramPlan(CFG, serving)
    params, cache = aot._abstract_state(plan, None)
    assert params["layers"]["lightning"]["wq"]["kernel"].dtype == jnp.int8
    assert cache["lin_state"].shape[:3] == (3, 1, plan.num_slots)
    assert cache["kc"].shape[:2] == (3, plan.total_pages)
    ledger = aot.build_ledger(plan, None, params, cache, [])
    assert ledger["kv_bytes_per_chip"] == kvp.pool_bytes(
        CFG, plan.total_pages, PS) + la.state_bytes(CFG, plan.num_slots)


# -- the models the benchmark has: their programs and kernels did not change -

# sha256 of str(jax.make_jaxpr(...)) at the parent commit (4838e1e), taken
# with these very functions in a checkout of it: every new operand, field
# and branch is behind a configuration key these models do not set, and the
# kernels' selection operands are compiled out where none is given.
# PR 35 re-took the nine ``mixed_step`` / ``prefill_step`` hashes at its own
# tree (parent 4d75cc9): those programs hand ``model_forward_carry`` the rows
# they sample, so their jaxprs gained the row index and the gather of the
# hidden state before the head, lost the take of one row from every-row
# logits, and changed in nothing else (tests/test_head_rows.py holds them to
# their every-row form). PR 38 re-took all fifteen at its own tree (parent
# cab5f9b): every step program ends in ops/sampling.sample, whose candidates
# (top_k, the masks, the draws) moved into one ``cond`` on
# ``any(temperature > 0)`` — the programs' own code did not change
# (tests/test_sampling.py holds the gated sampler to the ungated one token for
# token). The four kernels' hashes are PR 34's, byte for byte.
# PR 45 re-took the six ``pallas`` hashes and the "decode" / "ragged"
# kernels' at its own tree (parent cb17104): the paged body's per-row walk
# is its subject — a row's copies start and wait under its own range's
# predicate and the V slots nothing fills are zeroed; the ``xla`` programs,
# "write" and "kda" did not move, and the SELECTING entries' jaxprs are the
# parent's (tests/test_tpu_compile.py pins them).
#
# PR 46 re-took the six ``mixed_step`` hashes, pallas AND xla, and ``ragged``:
# every model's mixed step hands its attend callback ``table`` (one row a
# SLOT) and ``row_map`` [B + C] where these three built a table row a packed
# ROW; the ragged entry takes the pair, the decode rows' writers ``table``
# itself, the fallback gathers ``table[row_map]``. Every ``decode_steps`` and
# ``prefill_step`` hash and "decode", "write" and "kda" are the parent's.
#
# PR 50 re-took the six ``decode_steps`` hashes, in the SERVED form (the
# ``steps`` operand given): the token loop runs as many substeps as that
# operand says and writes each substep's outputs into its row of buffers
# sized by the static ``n_steps`` (a ``while`` where the scan was); the
# substep's body is the parent's (tests/test_decode_horizon.py holds the
# operand form to the static program at 1, 3 and 8 substeps). Every
# ``mixed_step`` / ``prefill_step`` hash and all four kernels' did not move.
PINNED = {
    ("tiny-olmoe", "decode_steps", "pallas"): "c38058a42fb84b66",
    ("tiny-olmoe", "decode_steps", "xla"): "f8640e998e7a20d6",
    ("tiny-olmoe", "mixed_step", "pallas"): "08fec05b72a19584",
    ("tiny-olmoe", "mixed_step", "xla"): "c6e13478d3763fe2",
    ("tiny-olmoe", "prefill_step", "xla"): "fe74d853601263b8",
    ("tiny-qwen3", "decode_steps", "pallas"): "5b0517df3dfac48f",
    ("tiny-qwen3", "decode_steps", "xla"): "988ed9e0463c4593",
    ("tiny-qwen3", "mixed_step", "pallas"): "53bad8ae3f34e008",
    ("tiny-qwen3", "mixed_step", "xla"): "87bf59bd4df3281b",
    ("tiny-qwen3", "prefill_step", "xla"): "34d3281612f23ac5",
    ("tiny-solar", "decode_steps", "pallas"): "85f1c90d3e598ae3",
    ("tiny-solar", "decode_steps", "xla"): "f021e8d594e15038",
    ("tiny-solar", "mixed_step", "pallas"): "0e8bf0085994ae6b",
    ("tiny-solar", "mixed_step", "xla"): "99f92aa1b81146f5",
    ("tiny-solar", "prefill_step", "xla"): "8da44bc738dc28b0",
}
# ("ragged" moved with PR 40, whose subject it is: a sharing block keeps its
# rows on lanes and the share fact is read per block AND per tile; the
# decode entry, traced by the same body, did not move; both moved with PR 45)
PINNED_KERNELS = {"decode": "81c80a049add0383", "ragged": "1f791b720959c9cf",
                  "write": "5ca71686a40fa563", "kda": "9fb3d56211d04454"}
MODELS = {"tiny-qwen3": tiny_qwen3, "tiny-olmoe": tiny_olmoe,
          "tiny-solar": tiny_solar}


def program_hash(cfg, program, impl):
    B, C, pps, ps = 2, 16, 4, 16
    params = jax.eval_shape(
        lambda: quantize_params(L.init_params(cfg, jax.random.PRNGKey(0),
                                              jnp.bfloat16), cfg))

    def mk():
        c = kvp.init_pool(cfg, B * pps + 1, ps)
        if cfg.recurrent:
            c.update(la.init_state(cfg, B))
        return c

    cache = jax.eval_shape(mk)
    sds = jax.ShapeDtypeStruct
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    live = sds((B,), jnp.bool_) \
        if cfg.num_experts > 0 or cfg.recurrent else None
    row = dict(table=sds((B, pps), i32), seeds=sds((B,), u32),
               ban_ids=sds((B, pg.BAN_K), i32), ban_until=sds((B,), i32),
               bias_ids=sds((B, pg.BIAS_K), i32),
               bias_vals=sds((B, pg.BIAS_K), f32), live=live)
    if program == "decode_steps":
        fn = lambda p, c, *a, **k: pg.decode_steps(cfg, 2, p, c, *a,
                                                   impl=impl, **k)
        args = (params, cache, sds((B,), i32), sds((B,), i32), rng,
                sds((B,), f32), sds((B,), i32), sds((B,), f32))
        kw = dict(row, steps=sds((), i32))      # the served form: a count
    elif program == "mixed_step":
        fn = lambda p, c, *a, **k: pg.mixed_step(cfg, p, c, *a, impl=impl,
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
        if cfg.recurrent:
            kw["slot"] = sds((), i32)
    text = str(jax.make_jaxpr(fn)(*args, **kw))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def kernel_hash(entry):
    sds = jax.ShapeDtypeStruct
    Lyr, P, Hkv, ps, D, B, MP, Hq = 2, 9, 2, 16, 16, 4, 2, 4
    kv = sds((Lyr, P, Hkv, ps, D), jnp.bfloat16)
    i32 = jnp.int32
    if entry == "decode":
        f = lambda *a: pa.decode_attend_pallas_paged(*a, interpret=True,
                                                     bblock=2)
        args = (sds((B, 1, Hq, D), jnp.bfloat16), kv, kv, sds((B,), i32),
                sds((), i32), sds((B, MP), i32))
    elif entry == "ragged":
        f = lambda *a: pa.ragged_attend_pallas_paged(*a, interpret=True,
                                                     bblock=2)
        args = (sds((B, Hq, D), jnp.bfloat16), kv, kv, sds((B,), i32),
                sds((), i32), sds((B, MP), i32), sds((B,), i32))
    elif entry == "write":
        f = lambda *a: pa.cache_write_row_paged(*a, interpret=True)
        args = (kv, sds((B, Hkv, D), jnp.bfloat16), sds((B,), i32),
                sds((B, MP), i32), sds((), i32))
    else:
        f = lambda *a: la.kda_decode_update(a[0], a[1], 1, *a[2:],
                                            interpret=True)
        H, d = 8, 16
        args = (sds((2, 3, B, H, d, d), jnp.float32), sds((), i32)) \
            + tuple(sds((B, H, d), jnp.float32) for _ in range(4)) \
            + (sds((B, H), jnp.float32),)
    return hashlib.sha256(str(jax.make_jaxpr(f)(*args)).encode()) \
        .hexdigest()[:16]


@pytest.mark.parametrize("model,program,impl", sorted(PINNED))
def test_older_models_step_program_jaxprs_are_unchanged(model, program,
                                                        impl):
    assert program_hash(MODELS[model](), program, impl) \
        == PINNED[(model, program, impl)]


@pytest.mark.parametrize("entry", sorted(PINNED_KERNELS))
def test_older_models_kernel_calls_are_unchanged(entry):
    assert kernel_hash(entry) == PINNED_KERNELS[entry]


def test_the_new_model_config_fields_default_to_the_old_behaviour():
    plain = dataclasses.asdict(tiny_qwen3())
    for field in ("attn_use_rope", "scale_emb", "scale_depth", "mup_depth",
                  "dim_model_base", "sparse_topk", "lightning_num_heads"):
        assert plain[field] == ModelConfig.__dataclass_fields__[field].default
    assert plain["attn_use_rope"] is True and plain["scale_emb"] == 1.0


def test_the_server_takes_its_prefill_buckets_from_a_flag():
    from aws_k8s_ansible_provisioner_tpu.serving import server

    parse = server.build_parser().parse_args
    got = server.serving_config_from_args(parse(
        ["--model", "x", "--prefill-buckets", "2048,512,16384",
         "--prefill-chunk", "2048"]))
    assert got.prefill_buckets == (512, 2048, 16384)
    assert server.serving_config_from_args(parse(["--model", "x"])) \
        .prefill_buckets == ServingConfig().prefill_buckets
    # a bucket above the chunk names a length, not a program: such a prompt
    # is chunked
    eng = _engine(L.init_params(CFG, jax.random.PRNGKey(0), jnp.float32),
                  prefill_buckets=(16, 32, 128))
    assert eng.buckets == (16, 32, 128)
    assert eng._should_chunk(Request(prompt_ids=[3] * 100, max_tokens=1))
    assert not eng._should_chunk(Request(prompt_ids=[3] * 32, max_tokens=1))


def test_the_dry_run_server_knows_the_list_hybrid():
    from aws_k8s_ansible_provisioner_tpu.serving import server

    serving = server.serving_config_from_args(server.build_parser().parse_args(
        ["--model", "tiny-sala", "--max-decode-slots", "2",
         "--max-cache-len", "512", "--kv-host-tier-bytes", "0"]))
    cfg = server.build_state(serving).engine.cfg
    assert cfg.selects and cfg.layer_pattern == "sllssl"
    assert cfg.sparse_block_size == serving.page_size
    assert cfg.sparse_dense_len == 4 * serving.page_size


def test_prompt_logprobs_asked_under_a_dispatch_in_flight_are_returned():
    """Any model: an admission under an in-flight dispatch takes the chunk
    walk, which returns no prompt logprobs — a request that asks for them
    settles the pipeline and prefills whole (the echo+logprobs flake of
    tests/test_server.py, one run in two under load before PR 34)."""
    cfg = tiny_qwen3()
    params = L.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = Engine(cfg, params, ServingConfig(
        max_decode_slots=4, max_cache_len=64, prefill_buckets=(16, 32),
        dtype="float32", weights_dtype="bf16", decode_horizon=2, page_size=8,
        decode_pipeline=1, ragged_attention=1, attention_impl="xla",
        kv_host_tier_bytes=0))
    eng.submit(Request(prompt_ids=_ids(12, 1), max_tokens=24,
                       ignore_eos=True))
    for _ in range(3):
        eng.step()
    assert eng._inflight is not None
    asked = eng.submit(Request(prompt_ids=_ids(9, 2), max_tokens=3,
                               ignore_eos=True, logprobs=0,
                               prompt_logprobs=0))
    plain = eng.submit(Request(prompt_ids=_ids(9, 3), max_tokens=3,
                               ignore_eos=True))
    _drain(eng)
    assert len(asked.prompt_logprob_data) == 9
    assert asked.prompt_logprob_data[0] is None
    assert len(asked.generated) == len(plain.generated) == 3
