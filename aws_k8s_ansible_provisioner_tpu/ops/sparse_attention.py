"""Block-sparse attention that SELECTS the pages a query reads (InfLLM-v2,
arXiv:2509.24663; MiniCPM-SALA's ``minicpm4`` layers): the selection, the
selector's cache, and the ``attend`` callbacks of the five step programs for
a model whose attending layers select (``ModelConfig.selects``).

For a query at position ``t`` (context ``T = t + 1``) and KV head ``g``,
with blocks of ``B`` tokens (= the pool's page), pooling windows of ``K``
keys every ``S`` (``K = 2 S``):

1. pooled keys ``Kc_j = mean(K[S j : S j + K])``, ``j`` with ``S j + K <= T``;
2. ``p_h = softmax_j(q_h . Kc_j / sqrt(D))`` for each query head of the
   group, ``a_j = sum_h p_h[j]``;
3. block score ``b_B = max{a_j : window j overlaps block B}``;
4. the first ``init_blocks`` blocks and the last ``window_size / B`` blocks up
   to the query's own score ``+inf``;
5. the ``topk`` best blocks that start at or before ``t`` are selected (ties
   to the lower index); a context under ``dense_len`` selects every block;
6. softmax attention of the group's heads over the tokens ``<= t`` of the
   selected blocks.

THE SELECTOR'S CACHE is a leaf of the paged pool (``kc``,
ops/kv_pool.selector_shape): per physical page and KV head the float32 SUMS
of the page's keys over runs of ``S`` tokens. ``Kc_j`` is then
``(run_j + run_{j+1}) / K`` — the second run may lie on the next page — so a
decode step scores from ``B / S`` vectors a page instead of re-reading the
keys, and a new key is one add into its run (which a run's first key zeroes:
a page's next occupant adds to nothing stale). Pages that are freed take
their sums with them; the allocator knows nothing of this.

WHO READS WHAT. A decode row hands the kernel a LIST of its selected pages a
KV head and walks those (pallas_attention.decode_attend_pallas_paged_select:
64 page steps at any context). The rows of a prefill chunk hand it a BITMASK
and walk every live page under it
(ragged_attend_pallas_paged_select) — the first form: a chunk's rows share
one page stream a TILE of rows (24 of the served 24 + 4,608), the mask a
term of the tile's column mask; a kernel that walks only what a tile's rows
chose is ROADMAP M6's open half. Off the chip the same selection masks a
dense gather (tests).
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Tuple

import jax
import jax.numpy as jnp

from aws_k8s_ansible_provisioner_tpu.config import ModelConfig
from aws_k8s_ansible_provisioner_tpu.models import parts
from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp
from aws_k8s_ansible_provisioner_tpu.ops.attention import (decode_attend,
                                                           resolve_impl)

_HI = jax.lax.Precision.HIGHEST


def _selecting(fn):
    """``fn``'s operations carry the part ``select`` (models/parts.py): the
    selection and the selector's cache, inside the ``attn.core`` of the
    ``attend`` callback that calls them. A scope a call: tracing is
    re-entrant and runs on more than one thread."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(parts.SELECT):
            return fn(*args, **kwargs)
    return scoped


# ---------------------------------------------------------------------------
# The selection
# ---------------------------------------------------------------------------


@_selecting
def block_scores(cfg: ModelConfig, q: jnp.ndarray, runs: jnp.ndarray,
                 T: jnp.ndarray) -> jnp.ndarray:
    """Steps 1-3. q: [R, Hq, D] (as attention gets it: normed); runs:
    [R, Hkv, M, D] or [Hkv, M, D] (every row the same sequence) float32 run
    sums in logical order; T: [R] context lengths. Returns block scores
    [R, Hkv, M // runs_per_block] float32, ``-inf`` where no complete window
    overlaps a block."""
    R, Hq, D = q.shape
    Hkv, M = runs.shape[-3], runs.shape[-2]
    st, per = cfg.sparse_kernel_stride, \
        cfg.sparse_block_size // cfg.sparse_kernel_stride
    qg = q.reshape(R, Hkv, Hq // Hkv, D).astype(jnp.float32)
    sub = "rkmd" if runs.ndim == 4 else "kmd"
    u = jnp.einsum(f"rkgd,{sub}->rkgm", qg, runs, precision=_HI)
    # window j = runs j and j + 1; complete where its last key is written
    w = (u + jnp.roll(u, -1, axis=-1)) * (D ** -0.5 / cfg.sparse_kernel_size)
    j = jnp.arange(M)
    ok = (st * j + cfg.sparse_kernel_size <= T[:, None])[:, None, None, :]
    p = jax.nn.softmax(jnp.where(ok, w, -1e30), axis=-1)
    a = jnp.where(ok[:, :, 0], jnp.where(ok, p, 0.0).sum(axis=2), -jnp.inf)
    # block B overlaps windows [per B - 1, per B + per): the one that starts
    # in the block before and ends here, and those that start here
    own = a.reshape(R, Hkv, M // per, per).max(axis=-1)
    before = jnp.concatenate(
        [jnp.full((R, Hkv, 1), -jnp.inf), a[..., per - 1::per][..., :-1]],
        axis=-1)
    return jnp.maximum(own, before)


@_selecting
def select_blocks(cfg: ModelConfig, scores: jnp.ndarray,
                  T: jnp.ndarray) -> jnp.ndarray:
    """Steps 4-5. scores: [R, Hkv, NB]; T: [R]. Returns the selection
    [R, Hkv, NB] bool (a row with T = 0 selects nothing)."""
    NB = scores.shape[-1]
    bs = cfg.sparse_block_size
    own = ((T - 1) // bs)[:, None, None]
    blk = jnp.arange(NB)[None, None, :]
    valid = (blk <= own) & (T > 0)[:, None, None]
    forced = (blk < cfg.sparse_init_blocks) \
        | (blk > own - cfg.sparse_window_size // bs)
    s = jnp.where(valid, jnp.where(forced, jnp.inf, scores), -jnp.inf)
    # a block's rank: how many come before it by (score descending, index
    # ascending) — one compare-and-count over the block pairs, which fuses
    # into a reduction (a top_k here is a sort of every row: 9 % of the
    # device's time in the long-prompt cell's first trace, PERF.md PR 34)
    ahead = (s[..., None, :] > s[..., :, None]) \
        | ((s[..., None, :] == s[..., :, None])
           & (jnp.arange(NB)[None, :] < jnp.arange(NB)[:, None]))
    sel = ahead.sum(axis=-1) < cfg.sparse_topk
    dense = (T < cfg.sparse_dense_len)[:, None, None]
    return jnp.where(dense, valid, sel & valid)


@_selecting
def as_list(cfg: ModelConfig, sel: jnp.ndarray) -> Tuple[jnp.ndarray,
                                                         jnp.ndarray]:
    """The selection as ascending lists: (pages [R, Hkv, K] int32, count
    [R, Hkv] int32), K = ``cfg.sparse_select_width``."""
    NB = sel.shape[-1]
    K = min(cfg.sparse_select_width, NB)
    idx = jnp.where(sel, jnp.arange(NB, dtype=jnp.int32), NB)
    return jnp.sort(idx, axis=-1)[..., :K], sel.sum(axis=-1).astype(jnp.int32)


@_selecting
def as_bits(sel: jnp.ndarray) -> jnp.ndarray:
    """The selection as int32 words: bit p % 32 of word p // 32 = page p."""
    R, Hkv, NB = sel.shape
    W = -(-NB // 32)
    sel = jnp.pad(sel, [(0, 0), (0, 0), (0, W * 32 - NB)])
    words = (sel.reshape(R, Hkv, W, 32).astype(jnp.uint32)
             << jnp.arange(32, dtype=jnp.uint32)).sum(axis=-1,
                                                      dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def _columns(sel: jnp.ndarray, ps: int) -> jnp.ndarray:
    """[R, Hkv, NB] blocks -> [R, Hkv, NB * ps] key columns."""
    return jnp.repeat(sel, ps, axis=-1)


def _attend_rows(q, kd, vd, limits, sel, ps: int):
    """R query rows of ONE sequence, dense under their selections (off the
    chip, and the stateless form). q: [R, Hq, D]; kd, vd: [Hkv, S, D]; limits
    [R]: each row's context length (0: a dead row, zeros out); sel
    [R, Hkv, >= S / ps]."""
    R, Hq, D = q.shape
    Hkv, S = kd.shape[:2]
    see = _columns(sel, ps)[..., :S] \
        & (jnp.arange(S)[None, :] < limits[:, None])[:, None, :]
    qg = q.reshape(R, Hkv, Hq // Hkv, D).astype(jnp.float32)
    s = jnp.einsum("rkgd,ksd->rkgs", qg, kd.astype(jnp.float32)) \
        * (D ** -0.5)
    p = jax.nn.softmax(jnp.where(see[:, :, None, :], s, -1e30), axis=-1)
    ctx = jnp.einsum("rkgs,ksd->rkgd", p, vd.astype(jnp.float32))
    return jnp.where((limits > 0)[:, None, None],
                     ctx.reshape(R, Hq, D), 0).astype(q.dtype)


@_selecting
def count(sel: jnp.ndarray, T: jnp.ndarray, ps: int) -> jnp.ndarray:
    """[2] int32: the (row, KV head) pairs' live pages and selected pages —
    what the dispatch record sums (``sparse_pages_live/selected``)."""
    live = (-(-T // ps))[:, None] * jnp.ones(sel.shape[:2], jnp.int32)
    return jnp.stack([live.sum(), sel.sum()]).astype(jnp.int32)


# The tally of ``count`` rides the POOL through the layer walk as the leaf
# ``sel_n`` ([2] int32: models/layers._list_forward_carry puts it in and takes
# it out, so it crosses every scan in the carry); what a forward pass summed
# is left for the step program that asked (ops/moe.routed_rows' pattern: a
# program that does not ask — the prefill programs — is left nothing).
_COUNTS = threading.local()
TALLY = "sel_n"


@contextlib.contextmanager
def counting():
    """Yields a dict whose ``pages`` is, after a model_forward_carry inside,
    [2] int32 — the (live, selected) pages of that forward pass, summed over
    its selecting layers — or None for a model that does not select."""
    prev = getattr(_COUNTS, "ctx", None)
    ctx = _COUNTS.ctx = {"pages": None}
    try:
        yield ctx
    finally:
        _COUNTS.ctx = prev


def put_counts(total) -> None:
    ctx = getattr(_COUNTS, "ctx", None)
    if ctx is not None:
        ctx["pages"] = total


def _tally(pool, sel, T, ps):
    return pool[TALLY] + count(sel, T, ps) if TALLY in pool else None


def _pool(kv, kc, tally):
    out = {**kv, "kc": kc}
    if tally is not None:
        out[TALLY] = tally
    return out


# ---------------------------------------------------------------------------
# The selector's cache
# ---------------------------------------------------------------------------


@_selecting
def add_rows(cfg: ModelConfig, kc, layer, rows, table, knew, impl: str):
    """One new key a slot (decode rows). knew: [B, Hkv, D]; rows: [B]
    logical row (out of range: dropped); table: [B, max_pages]."""
    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention

    st = cfg.sparse_kernel_stride
    if impl == "pallas":
        return pallas_attention.selector_add_row_paged(
            kc, knew, rows, table, layer, stride=st,
            interpret=not pallas_attention.supported())
    ps = kc.shape[3] * st
    B = knew.shape[0]
    idx = rows // ps
    ok = (rows >= 0) & (idx < table.shape[1])
    pg = jnp.where(ok, table[jnp.arange(B),
                             jnp.clip(idx, 0, table.shape[1] - 1)],
                   kvp.OOB_PAGE)
    run = jnp.where(ok, (rows % ps) // st, 0)
    old = kc.at[layer, pg, :, run].get(mode="clip")          # [B, Hkv, D]
    kept = jnp.where((rows % st == 0)[:, None, None], 0.0, old)
    return kc.at[layer, pg, :, run].set(
        kept + knew.astype(kc.dtype), mode="drop")


@_selecting
def add_span(cfg: ModelConfig, kc, layer, tables, start, k, n_valid):
    """Rows [start, start + n_valid) of N sequences, a page window at a time
    (kv_pool._write_span_by_page's windows: contiguous in the leaf's own
    layout). tables: [N, max_pages]; k: [N, T, Hkv, D]; start: scalar;
    n_valid: [N] or scalar. A run the span opens restarts from zero, a run it
    continues is added to, a run it does not touch keeps its sum."""
    st = cfg.sparse_kernel_stride
    per = kc.shape[3]
    ps = per * st
    N, T = k.shape[:2]
    aligned = isinstance(start, int) and start % ps == 0
    n = max(2, -(-T // ps) + (0 if aligned else 1))
    start = jnp.asarray(start, jnp.int32)
    n_valid = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (N,))
    p0 = start // ps
    delta = p0 * ps - start                      # in (-ps, 0]
    lp = p0 + jnp.arange(n, dtype=jnp.int32)
    pg = jnp.where((lp < tables.shape[1])[None],
                   tables[:, jnp.clip(lp, 0, tables.shape[1] - 1)],
                   kvp.OOB_PAGE)                 # [N, n]
    tok = jnp.arange(n * ps, dtype=jnp.int32) + delta        # span index
    live = (tok >= 0)[None] & (tok[None] < n_valid[:, None])  # [N, n ps]
    pad = [(0, 0), (ps, n * ps - T), (0, 0), (0, 0)]
    win = jax.lax.dynamic_slice_in_dim(
        jnp.pad(k.astype(kc.dtype), pad), ps + delta, n * ps, axis=1)
    win = jnp.where(live[:, :, None, None], win, 0.0)
    sums = win.reshape(N, n, per, st, *win.shape[2:]).sum(axis=3)
    sums = jnp.moveaxis(sums, 2, 3)              # [N, n, Hkv, per, D]
    opens = live[:, ::st].reshape(N, n, 1, per, 1)   # the run's first key
    old = kc.at[layer, pg].get(mode="clip")
    return kc.at[layer, pg].set(jnp.where(opens, 0.0, old) + sums,
                                mode="drop")


@_selecting
def _runs_of(kc, layer, table):
    """One layer's run sums in logical order: [B, Hkv, M, D]."""
    g = jax.lax.dynamic_index_in_dim(kc, layer, 0, keepdims=False)[table]
    g = jnp.moveaxis(g, 2, 1)                    # [B, Hkv, n, runs, D]
    return g.reshape(g.shape[:2] + (-1,) + g.shape[4:])


# ---------------------------------------------------------------------------
# attend callbacks: the ones ops/attention.py makes, for a model that selects
# (one device; bf16 pool)
# ---------------------------------------------------------------------------


def _kv_only(pool):
    return {n: pool[n] for n in ("k", "v")}


def make_decode_attend_select(cfg: ModelConfig, lengths, table,
                              impl: str = "auto", bblock: int = 1,
                              live=None):
    """decode_steps: write the row (K, V and its run), score the slot's
    pooled keys, select, read the selected pages. ``live`` [B] bool: the
    slots that hold a request — an idle slot's row is written like any (to
    its scratch page) and reads nothing."""
    resolved = resolve_impl(impl)
    T = lengths + 1 if live is None else jnp.where(live, lengths + 1, 0)

    def attend(q, k, v, cache_l):
        from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention

        pool, layer = cache_l
        ps = pool["k"].shape[3]
        kc = add_rows(cfg, pool["kc"], layer, lengths, table, k[:, 0],
                      resolved)
        sel = select_blocks(cfg, block_scores(
            cfg, q[:, 0], _runs_of(kc, layer, table), T), T)
        tally = _tally(pool, sel, T, ps)
        if resolved == "pallas":
            interpret = not pallas_attention.supported()
            ck = pallas_attention.cache_write_row_paged(
                pool["k"], k[:, 0], lengths, table, layer,
                interpret=interpret)
            cv = pallas_attention.cache_write_row_paged(
                pool["v"], v[:, 0], lengths, table, layer,
                interpret=interpret)
            pages, cnt = as_list(cfg, sel)
            ctx = pallas_attention.decode_attend_pallas_paged_select(
                q, ck, cv, T, layer, table, pages, cnt, interpret=interpret,
                bblock=bblock)
            return ctx, (_pool({"k": ck, "v": cv}, kc, tally), layer)
        kv = kvp.write_token_layer_paged(_kv_only(pool), layer, lengths,
                                         table, k, v, ps)
        dense = kvp.gather_layer_dense(kv, layer, table)
        ctx = decode_attend(q, dense["k"], dense["v"], T,
                            allowed=_columns(sel, ps))
        return ctx, (_pool(kv, kc, tally), layer)

    return attend


def make_mixed_attend_select(cfg: ModelConfig, dec_rows, chunk_start,
                             chunk_len, row_limits, table, row_map,
                             impl: str = "auto", bblock: int = 1,
                             live=None):
    """mixed_step's packed [1, B + C]: B decode rows, then the C chunk rows
    of one slot (attention.make_mixed_attend_carry_paged's contract:
    ``table`` [B, max_pages] one row a SLOT, ``row_map`` [B + C] the row of
    it each packed row reads — the chunk rows all name the chunking
    slot's). All writes land first; every row then selects over its own
    slot's pooled keys at its own length and reads under its mask."""
    resolved = resolve_impl(impl)
    B = dec_rows.shape[0]
    pslot = row_map[B]
    if live is not None:        # an idle slot's decode row reads nothing
        row_limits = row_limits.at[:B].set(
            jnp.where(live, row_limits[:B], 0))

    def attend(q, k, v, cache_l):
        from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention

        pool, layer = cache_l
        ps = pool["k"].shape[3]
        q3, knew, vnew = q[0], k[0], v[0]
        ptab = table[pslot][None]
        kc = add_rows(cfg, pool["kc"], layer, dec_rows, table, knew[:B],
                      resolved)
        kc = add_span(cfg, kc, layer, ptab, chunk_start, knew[None, B:],
                      chunk_len)
        runs = _runs_of(kc, layer, table)                    # [B, Hkv, M, D]
        sel = jnp.concatenate([
            select_blocks(cfg, block_scores(cfg, q3[:B], runs,
                                            row_limits[:B]), row_limits[:B]),
            select_blocks(cfg, block_scores(cfg, q3[B:], runs[pslot],
                                            row_limits[B:]), row_limits[B:])])
        tally = _tally(pool, sel, row_limits, ps)
        kv = _kv_only(pool)
        interpret = not pallas_attention.supported()
        if resolved == "pallas":
            kv = {n: pallas_attention.cache_write_row_paged(
                kv[n], new[:B], dec_rows, table, layer, interpret=interpret)
                for n, new in (("k", knew), ("v", vnew))}
        else:
            kv = kvp.write_token_layer_paged(kv, layer, dec_rows, table,
                                             knew[:B, None], vnew[:B, None],
                                             ps)
        kv = kvp.write_chunk_paged_layer(kv, layer, ptab[0], chunk_start,
                                         knew[None, B:], vnew[None, B:], ps,
                                         n_valid=chunk_len)
        if resolved == "pallas":
            ctx = pallas_attention.ragged_attend_pallas_paged_select(
                q3, kv["k"], kv["v"], row_limits, layer, table, row_map,
                as_bits(sel), interpret=interpret, bblock=bblock)
        else:
            dense = kvp.gather_layer_dense(kv, layer, table)
            ctx = jnp.concatenate([
                jax.vmap(lambda q1, k1, v1, l1, s1: _attend_rows(
                    q1[None], k1, v1, l1[None], s1[None], ps)[0])(
                        q3[:B], dense["k"], dense["v"], row_limits[:B],
                        sel[:B]),
                _attend_rows(q3[B:], dense["k"][pslot], dense["v"][pslot],
                             row_limits[B:], sel[B:], ps)])
        return ctx[None], (_pool(kv, kc, tally), layer)

    return attend


def _span_attend(cfg: ModelConfig, q, kv, kc, layer, tables, limits):
    """N sequences' rows against their cached prefix (already written),
    dense under the selection (the prefill programs off the ragged path:
    small windows). q: [N, T, Hq, D]; tables: [N, max_pages]; limits
    [N, T]: each row's context length, 0 for a row that carries no token."""
    ps = kv["k"].shape[3]
    dense = kvp.gather_layer_dense(kv, layer, tables)
    runs = _runs_of(kc, layer, tables)

    def one(qn, kn, vn, rn, lim):
        sel = select_blocks(cfg, block_scores(cfg, qn, rn, lim), lim)
        return _attend_rows(qn, kn, vn, lim, sel, ps), count(sel, lim, ps)

    ctx, counts = jax.vmap(one)(q, dense["k"], dense["v"], runs, limits)
    return ctx, counts.sum(axis=0)


def make_prefill_attend_select(cfg: ModelConfig, tables, true_lens):
    """prefill_step / prefill_batch_step: N whole prompts from position 0
    (a padding row's table is all OOB_PAGE: its writes drop and its rows are
    dead). A bucket under the dense length selects every block of every
    row, a static fact: plain causal attention over the bucket, and the
    runs are written for the decode steps that follow."""
    from aws_k8s_ansible_provisioner_tpu.models.layers import causal_attend

    def attend(q, k, v, cache_l):
        pool, layer = cache_l
        ps = pool["k"].shape[3]
        T = q.shape[1]
        kv = kvp.write_prompts_paged_layer(_kv_only(pool), layer, tables, k,
                                           v, ps)
        kc = add_span(cfg, pool["kc"], layer, tables, 0, k, true_lens)
        if T < cfg.sparse_dense_len:
            ctx = causal_attend(q, k, v, seq_lens=true_lens)
            return ctx, (_pool(kv, kc, pool.get(TALLY)), layer)
        pos = jnp.arange(T, dtype=jnp.int32)[None]
        limits = jnp.where(pos < true_lens[:, None], pos + 1, 0)
        # a padding row gathers nothing real: clip its table for the read
        safe = jnp.where(tables == kvp.OOB_PAGE, 0, tables)
        ctx, n = _span_attend(cfg, q, kv, kc, layer, safe, limits)
        return ctx, (_pool(kv, kc, pool[TALLY] + n if TALLY in pool
                           else None), layer)

    return attend


def make_chunk_prefill_attend_select(cfg: ModelConfig, pages, start,
                                     chunk_len):
    """prefill_chunk_step: one chunk of one slot, rows [start, start +
    chunk_len) (an engine with the ragged program on walks its chunks
    through mixed_step instead, and that is the path a long window takes)."""

    def attend(q, k, v, cache_l):
        pool, layer = cache_l
        ps = pool["k"].shape[3]
        C = q.shape[1]
        kv = kvp.write_chunk_paged_layer(_kv_only(pool), layer, pages, start,
                                         k, v, ps, n_valid=chunk_len)
        kc = add_span(cfg, pool["kc"], layer, pages[None], start, k,
                      chunk_len)
        pos = jnp.arange(C, dtype=jnp.int32)[None]
        limits = jnp.where(pos < chunk_len, start + pos + 1, 0)
        ctx, n = _span_attend(cfg, q, kv, kc, layer, pages[None], limits)
        return ctx, (_pool(kv, kc, pool[TALLY] + n if TALLY in pool
                           else None), layer)

    return attend


def make_stateless_attend_select(cfg: ModelConfig, rows: int = 512,
                                 handed=None):
    """No cache kept: every sequence [N, T, ...] whole, from position 0
    (model_forward without a cache: tests, chip_smoke's handed-over
    selection) — the run sums straight from ``k``, the same selection, dense
    attention under it, ``rows`` query rows at a time. Returns ``(context,
    the selection [N, T, Hkv, blocks] bool)``; ``handed`` (the same shape)
    is read in place of the rows' own choice."""
    st, bs = cfg.sparse_kernel_stride, cfg.sparse_block_size

    def one(q, k, v, given):                # [T, H*, D]; [T, Hkv, NB]
        T, Hq, D = q.shape
        Hkv = k.shape[1]
        Tp = -(-T // bs) * bs
        pad = [(0, Tp - T), (0, 0), (0, 0)]
        kp, vp = jnp.pad(k, pad), jnp.pad(v, pad)
        runs = jnp.moveaxis(kp.astype(jnp.float32).reshape(
            Tp // st, st, Hkv, D).sum(1), 0, 1)                # [Hkv, M, D]
        kd, vd = jnp.moveaxis(kp, 0, 1), jnp.moveaxis(vp, 0, 1)  # [Hkv,Tp,D]

        def block(args):
            qb, lim, given = args           # [r, Hq, D], [r], [r, Hkv, NB]
            sel = given if handed is not None else select_blocks(
                cfg, block_scores(cfg, qb, runs, lim), lim)
            return _attend_rows(qb, kd, vd, lim, sel, bs), sel

        r = min(rows, T)
        Tq = -(-T // r) * r
        qp = jnp.pad(q, [(0, Tq - T), (0, 0), (0, 0)])
        lim = jnp.where(jnp.arange(Tq) < T, jnp.arange(Tq) + 1, 0)
        gp = jnp.pad(given, [(0, Tq - T), (0, 0), (0, 0)])
        ctx, sel = jax.lax.map(block, (
            qp.reshape(Tq // r, r, Hq, D), lim.reshape(Tq // r, r),
            gp.reshape((Tq // r, r) + given.shape[1:])))
        return ctx.reshape(Tq, Hq, D)[:T], \
            sel.reshape((Tq,) + sel.shape[2:])[:T]

    def attend(q, k, v, cache):
        N, T = q.shape[:2]
        given = handed if handed is not None else jnp.zeros(
            (N, T, k.shape[2], -(-T // bs)), bool)
        return jax.vmap(one)(q, k, v, given)

    return attend
