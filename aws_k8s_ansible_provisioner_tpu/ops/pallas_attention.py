"""Pallas TPU kernels: ragged attention and row writes over the paged KV pool.

This is the hot loop of the whole framework — the TPU-native equivalent of the
paged-attention CUDA kernels inside the reference's external vLLM engine
(SURVEY.md §3.3: "the true hot loop (token-by-token decode on the GPU) lives
entirely inside the external vLLM container"; §7 hard part #1). The pool
(ops/kv_pool.py: ``[L, P, Hkv, page, D]``) streams HBM→VMEM a page at a time
with flash-style online softmax, so per-step cost is cache-bandwidth-bound
with no [B, S] float32 logits materialization in HBM.

Raggedness (every row at a different length) is handled two ways:
- masking: key columns ≥ the row's limit contribute -inf logits;
- *page skipping*: the in-kernel page loop runs over a block's live page
  range only, so a slot at length 130 reads 3 pages of 64, not the window.

WHO ORDERS THE ROWS: a block of ``bblock`` rows walks the pages of its
LONGEST row with a masked update for every row; a row COPIES only the pages
it holds itself (past its own range it starts no copy), so the bytes follow
the rows and the page steps follow the blocks: how rows are grouped decides
how many of a walk's updates have a page to fold. The kernels take the rows
as given; the decode
program sorts them by length around the call (ops/attention._length_order,
in plain XLA ops: two small sorts a substep, two row gathers a layer) and
un-permutes the context. Not in here: the order is the same for
every layer and one call is one layer; a permutation outside leaves the body,
its DMAs and its buffers as they are, where tier-1 can pin it bitwise; and
the other entries (spec, ragged) arrange their blocks on other grounds.

GQA grouping stays in-kernel: per KV head h, the G=Hq/Hkv query rows attend to
one [page, D] K/V stream — no repeat_kv copy ever exists (the same design as
the XLA fallback in ops/attention.py, here with explicit VMEM control).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from aws_k8s_ansible_provisioner_tpu.ops.kv_pool import quantize_rows

NEG_INF = -1e30


def _per_slot(vals, shape, axis: int = 0, each: int = 1):
    """Broadcast BB per-slot int32 SCALARS along ``axis`` of ``shape``
    (slot i owns indices [i*each, (i+1)*each) there) with an iota select —
    the layout Mosaic accepts for the batch-blocked kernels' per-slot column
    masks."""
    out = jnp.full(shape, vals[0], jnp.int32)
    if len(vals) > 1:
        slot = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        if each > 1:
            slot = slot // each
        for i in range(1, len(vals)):
            out = jnp.where(slot == i, vals[i], out)
    return out


def _per_pair(vals, shape, hkv: int, groups: int, tiled: bool):
    """Broadcast BB x Hkv int32 SCALARS (``vals[i * hkv + h]``: row i, KV
    head h) over ``shape`` — ``[bb, hq, *]`` (row, query head), or with
    ``tiled`` the sharing path's ``[hkv, bb * groups, *]`` (KV head, tile
    row = row * groups + group). The iota-select form of
    :func:`_per_slot`."""
    a0 = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    a1 = jax.lax.broadcasted_iota(jnp.int32, shape, 1) // groups
    pair = a1 * hkv + a0 if tiled else a0 * hkv + a1
    out = jnp.full(shape, vals[0], jnp.int32)
    for i in range(1, len(vals)):
        out = jnp.where(pair == i, vals[i], out)
    return out


# ---------------------------------------------------------------------------
# Attention over the physical page pool + per-slot block tables,
# DOUBLE-BUFFERED
# ---------------------------------------------------------------------------
#
# The kernels OWN their data movement: the pools stay in HBM
# (memory_space=ANY) and the kernel streams pages through a two-slot VMEM
# buffer with explicit async copies — page c+1's DMAs are issued BEFORE page
# c's flash update runs, so the fetch of the next page overlaps the compute of
# the current one instead of serializing behind it at a grid-step boundary.
#
# Why not the implicit grid pipeline (the pre-r6 implementation): with grid
# (B, max_pages) every (slot, page) pair is its own grid step, and the r5
# decomposition (PERF.md) measured ~14k such steps per fused substep, each
# moving only ~0.5 MB — fixed per-step cost (DMA issue + kernel dispatch,
# ~1 µs class) rivaled the stream time itself and pinned decode at ~36% of
# the HBM roofline. Here the grid is (B/BB,): one step per BLOCK of BB
# slots, the page loop lives inside the kernel (a loop over the block's live
# page range, bounds read from the lengths), and each buffer fill issues BB
# page copies back-to-back —
# BBx larger transfers in flight, BBx fewer grid steps, and dead pages
# (beyond a block's longest slot, or below its sliding-window start) are
# skipped outright rather than clamp-refetched. This is the TPU analogue of
# vLLM's paged-attention block indirection (SURVEY.md §2.2 row 1) crossed
# with the Ragged Paged Attention amortization argument (PAPERS.md): the
# page gather is done by the DMA engine, overlapped, in block-sized batches.
#
# The body's contract is RAGGED: a grid row is an arbitrary (table row,
# live-column limit) pair, not intrinsically "slot i decoding". The
# per-slot decode/spec entry points below are the identity-indirection
# special case; ragged_attend_pallas_paged exposes the general form — a
# packed mix of decode rows and prefill-chunk rows served by ONE dispatch
# (serving/programs.mixed_step rides it to keep the decode pipeline open
# across prefill admissions). Its work follows the live (row, page) pairs:
# rows with limit 0 cost nothing, and a block whose rows share one table row
# streams each page once.
#
# ``bblock`` (BB) is the knob the engine autotunes at startup
# (Engine._resolve_decode_bblock: one-shot microbench over {1, 4, 8} per
# (batch, page_size, kv_dtype)); 1 remains valid and still double-buffers.


def _paged_db_body(lengths_ref, layer_ref, table_ref, share_ref, wide_ref,
                   rowmap_ref, sel_ref, cnt_ref, bits_ref, bitsat_ref, q_ref,
                   lanebits_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf,
                   v_buf, ks_buf,
                   vs_buf, acc_ref, m_ref, l_ref, sem, acc_t, m_t, l_t,
                   wide_state,
                   *, ps: int, groups: int, scale: float, R: int, bb: int,
                   num_pages: int, window: int, spec: bool, tile: int):
    """Shared double-buffered paged flash body (decode R=1 / spec-verify R>1,
    bf16 / int8 pools, full / sliding-window attention).

    One grid step handles BB rows end to end: init flash state, then walk
    the block's live logical pages [lo_min, hi_max] (a loop with dynamic
    bounds: pages no row needs cost nothing) with a two-slot VMEM buffer —
    issue page c+1's copies, wait page c's, accumulate page c. The table is
    scalar-prefetched (SMEM, FLATTENED row-major — see _paged_flash_db), so
    physical ids resolve in-kernel with no HBM round trip. Per-row
    raggedness inside a block rides the column mask
    (shorter rows' dead columns contribute exp(-1e30 - m) == 0 exactly once
    any live column has been seen — bit-identical to the skip-based
    single-slot accumulation). A row OUTSIDE its own live range
    [lo[i], hi[i]] at page step c — past its last page, below its window,
    or dead — STARTS NO COPY and waits for none (``fetches``: one predicate
    of the lengths, the same at start and wait time): a mixed block never
    fetches a neighbor's garbage table entries, and the bytes a row does
    not hold are time the step does not take. Its masked update still runs
    on what its buffer slot holds — its own earlier page, or zeros: the V
    (and V-scale) slots that no copy of the block fills before their first
    read are zeroed where the block starts (``zero_unfilled``), because
    0 x NaN in P.V is NaN and nothing may lean on what VMEM held before the
    call. The selecting entries keep the older rule (every row of a live
    block copies, its page index clamped into its own range).

    A row with NO live column (limit 0: an idle slot, a padding row of a
    prefill chunk) is DEAD: it has no live page, widens no block's range,
    none of its table entries is ever used as a page id, and its output is
    exactly zero. A block of dead rows issues no DMA and runs no flash
    update.

    ``share_ref`` (ragged entry, bb > 1; else None and compiled out) holds
    per block the row whose table every live row of the block shares, or -1.
    A sharing block fetches page c ONCE (not once per row) and runs the
    flash update with the block as one query tile per KV head — the chunk
    rows of one prefill are the case; each row still masks to its own limit
    and window. The tile's state keeps its rows on lanes
    (``rows_on_lanes``); a BLOCK's under an int8 pool or a page selection
    is the [bb*groups]-row tile of ``shared``.

    ``tile`` > bb with ``wide_ref`` and ``wide_state`` (the ragged entries
    over a bf16 pool; elsewhere ``tile`` == bb, both None and all of this
    compiled out): a grid step holds ``tile`` rows,
    ``tile // bb`` blocks, and ``wide_ref`` says per step what they are —
    the row whose table every live row of the STEP shares (then the step is
    ONE sharing tile of ``tile`` rows: page c is fetched once for all of
    them, ``tile // bb`` times fewer page steps than its blocks would walk;
    the chunk rows of a mixed step are the case), -1 (rows of several
    tables — the step that holds the decode rows: its blocks run one after
    the other as they would have alone) or -2 (no live row: zeros out,
    nothing fetched). A row's arithmetic is the same in a tile of any
    width: the pages outside its own range that a wider tile walks are
    fully masked for it, which leaves its state bit for bit where it was.

    SELECTION (a model whose attention reads chosen pages only,
    ops/sparse_attention.py; every operand None and compiled out
    otherwise), per row AND KV head, in two forms:

    - ``sel_ref`` / ``cnt_ref`` (the decode entry): a LIST of logical pages
      in ascending order, ``cnt`` of them. The walk runs over list
      POSITIONS — step c fetches, for each row and KV head apart, the page
      its list names at c (one DMA a head: the heads of a row read
      different pages) — so a row past the dense length costs ``topk``
      page steps whatever its context; a list shorter than the block's
      longest re-copies its last page under a mask (the older rule).
    - a BITMASK over logical pages (the ragged entry), read in two
      layouts. The walk is the plain one over [lo_min, hi_max] and every
      routed page of a row is read under the row's own limit.
      ``lanebits_ref`` (VMEM, blocked a grid step: [1, Hkv, W, tile *
      groups] int32, word ``c // 32`` of each (row, KV head) spread over
      the lanes its query rows take in ``rows_on_lanes``) serves a SHARING
      TILE — the chunk rows of a prefill: page c is live for a lane where
      bit ``c % 32`` of its word is set, one more term of the column mask,
      a vector shift and compare a page step; no scalar is read and no
      page skips its update (a page that none of 24 rows x 2 heads chose
      is rare, and past the dense length a third of the pages are forced).
      ``bits_ref`` (SMEM, flat [rows, Hkv, W]) serves the BLOCKS of a step
      whose rows read several tables (the decode rows) and every block of
      a call that tiles no wider: at page c a row's limit for a KV head is
      its own where the bit is set and 0 where it is not (``chosen``:
      bb x Hkv scalar reads and an iota-select chain a page step), and a
      page no row of the block selects skips its flash update. With
      ``wide_ref`` the words in SMEM are those of the steps that read them
      only, ``bitsat_ref[g]`` naming step g's first row there.

    ``rowmap_ref`` (the ragged entries; None for a decode entry, whose row
    i reads table row i): packed row -> row of ``table_ref``, so the chunk
    rows of one slot name ONE table row instead of each carrying a copy
    (2,072 rows x 512 pages do not fit SMEM).
    """
    g = pl.program_id(0)
    lay = layer_ref[0]
    quant = ks_hbm is not None
    hq = q_ref.shape[1] // R
    d = q_ref.shape[2]
    hkv = k_buf.shape[2]
    ext = R if spec else 0      # spec rows see up to R columns past lengths
    # a sharing block's, or tile's, flash state keeps its rows on lanes
    # (rows_on_lanes) unless pages are selected or scaled (_paged_flash_db)
    on_lanes = share_ref is not None and not quant and bits_ref is None

    def trow(row):
        """Offset of packed row ``row``'s page run in the flat table."""
        if rowmap_ref is not None:
            row = rowmap_ref[row]
        return row * num_pages

    def last_pages(lens):
        """Per row its last live logical page (-1 = dead row), and the
        largest of them."""
        hi = [jnp.minimum(pl.cdiv(ln + ext, ps), num_pages) - 1
              for ln in lens]
        return hi, functools.reduce(jnp.maximum, hi)

    def first_pages(lens, alive):
        """Per row the first logical page inside its window, and the
        smallest over the live rows (``num_pages`` where none is)."""
        if window == 0:
            return [jnp.int32(0)] * len(lens), jnp.int32(0)
        lo = [jnp.maximum(ln + (1 if spec else 0) - window, 0) // ps
              for ln in lens]
        return lo, functools.reduce(
            jnp.minimum, [jnp.where(a, x, num_pages)
                          for a, x in zip(alive, lo)])

    def walk(lo_min, hi_max, pages, update, fetches=None):
        """The double-buffered walk over [lo_min, hi_max]: ``pages(c)`` names
        page c's copies, ``update(c, buf)`` folds buffer ``buf`` into the
        flash state. ``fetches(i, c)`` (the per-row path): does buffer row i
        copy page c at all — a row outside its own range starts nothing and
        waits for nothing; None: every named copy runs."""

        def copies(c, act):
            # created identically at start and wait time — the documented
            # make_async_copy pattern —, and a row's under ONE predicate of
            # the same scalars both times: a wait nobody signals hangs
            slot = c % 2
            for i, pg in pages(c):
                if sel_ref is not None:     # (row, KV head): one head's rows
                    i, h = i
                    act(pltpu.make_async_copy(
                        k_hbm.at[lay, pg, h], k_buf.at[slot, i, h],
                        sem.at[slot, i, 2 * h]))
                    act(pltpu.make_async_copy(
                        v_hbm.at[lay, pg, h], v_buf.at[slot, i, h],
                        sem.at[slot, i, 2 * h + 1]))
                    continue
                row = [pltpu.make_async_copy(
                    k_hbm.at[lay, pg], k_buf.at[slot, i], sem.at[slot, i, 0]),
                    pltpu.make_async_copy(
                    v_hbm.at[lay, pg], v_buf.at[slot, i], sem.at[slot, i, 1])]
                if quant:
                    row += [pltpu.make_async_copy(
                        ks_hbm.at[lay, pg], ks_buf.at[slot, i],
                        sem.at[slot, i, 2]),
                        pltpu.make_async_copy(
                        vs_hbm.at[lay, pg], vs_buf.at[slot, i],
                        sem.at[slot, i, 3])]

                def all_of(row=row):
                    for dma in row:
                        act(dma)

                if fetches is None:
                    all_of()
                else:
                    pl.when(fetches(i, c))(all_of)

        def start(dma):
            dma.start()

        def wait(dma):
            dma.wait()

        @pl.when(lo_min <= hi_max)
        def _prologue():                   # first page in flight
            copies(lo_min, start)

        def step(c, carry):
            @pl.when(c < hi_max)
            def _prefetch():               # fetch page c+1 while computing c
                copies(c + 1, start)

            copies(c, wait)
            update(c, c % 2)
            return carry

        jax.lax.fori_loop(lo_min, hi_max + 1, step, 0)

    def flash(s, col, limit, m_ref, l_ref, acc_ref, sl, pv_of):
        """One online-softmax update of state rows ``sl`` from the masked
        logits of page columns ``col``; ``pv_of(p)`` is the page's P.V."""
        live_col = col < limit
        if window > 0:
            live_col &= col >= limit - window
        s = jnp.where(live_col, s, NEG_INF)
        m_prev = m_ref[:, sl, :1]
        l_prev = l_ref[:, sl, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:, sl] = acc_ref[:, sl] * corr + pv_of(p)
        m_ref[:, sl, :1] = m_cur
        l_ref[:, sl, :1] = l_cur

    def reset(acc, m, l):
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, NEG_INF)
        l[:] = jnp.zeros_like(l)

    def rows_on_lanes(row, q_ref, lens, lo_min, hi_max):
        """All live rows read table row ``row`` (a bf16 or float32 pool):
        ONE copy a page step into buffer row 0 for a tile of ``len(lens)``
        rows, whatever their number. The flash state is kept
        TRANSPOSED — a page's keys on sublanes, the tile's rows * groups
        query rows on lanes: logits [Hkv, page, n], context [Hkv, D, n] —
        so m, l and the column masks are lane vectors of n entries (not n
        sublane rows of one live lane each), the max and the sum over a
        page's keys run down vregs, and a 64-key page wastes no lane; the
        MXU takes both products transposed. Q and K meet it as the values
        they are stored as and the scale falls on the logits: the same
        products (a bf16 pair's is exact in float32) in one pass, where a
        float32 pair takes six. Per row the arithmetic is one order of
        pages, one order of keys within a page, float32 throughout: a
        row's result does not depend on the width of its tile. Under a
        page selection (``lanebits_ref``, a whole grid step's tile) a
        lane's columns of page c are live only where its word has bit c.
        Returns [rows, Hq, D], dead rows zero."""
        rows = len(lens)
        n = rows * groups
        acc, m, l = (acc_t, m_t, l_t) if rows == bb else wide_state
        stored = k_buf.dtype == q_ref.dtype
        qt = q_ref[:].astype(jnp.float32)           # [rows, Hq, D] ->
        qt = qt.reshape(rows, hkv, groups, d).transpose(1, 0, 2, 3) \
            .reshape(hkv, n, d)                     # [Hkv, n, D]
        if stored:
            qt = qt.astype(q_ref.dtype)
        # one chain of selects a tile: the column masks and the dead rows'
        limit = _per_slot(lens, (1, 1, n), axis=2, each=groups)

        def update(c, buf):
            k3, v3 = k_buf[buf, 0], v_buf[buf, 0]             # [Hkv, ps, D]
            if not stored:
                k3 = k3.astype(jnp.float32)
            s = jax.lax.dot_general(
                k3, qt, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale   # [Hkv, ps, n]
            col = c * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps, n), 1)
            live_col = col < limit
            if window > 0:
                live_col &= col >= limit - window
            if lanebits_ref is not None:
                word = lanebits_ref[0, :, pl.ds(c // 32, 1), :]
                live_col &= ((word >> (c % 32)) & 1) > 0      # [Hkv, 1, n]
            s = jnp.where(live_col, s, NEG_INF)
            m_prev, l_prev = m[:], l[:]                       # [Hkv, 1, n]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l[:] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            m[:] = m_cur
            acc[:] = acc[:] * corr + jax.lax.dot_general(
                v3.astype(jnp.float32), p, (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)           # [Hkv, D, n]

        reset(acc, m, l)
        walk(lo_min, hi_max, lambda c: [(0, table_ref[trow(row) + c])],
             update)
        # dead rows hold whatever rode through their lanes: exactly zero out
        ctx = jnp.where(limit > 0, acc[:] / jnp.maximum(l[:], 1e-9), 0.0)
        return jnp.swapaxes(ctx, 1, 2).reshape(hkv, rows, groups, d) \
            .transpose(1, 0, 2, 3).reshape(rows, hq, d)

    def block(blk, q_ref, o_ref, bits_row=None):
        """Rows [blk * bb, (blk + 1) * bb): a block of a call that tiles no
        wider, or one of a step whose rows read several tables (then
        ``bits_row`` is its first row among the bit words held in SMEM)."""
        # BB per-row SCALARS (see _per_slot: a stacked scalar vector
        # reshaped to [BB, 1, 1] is a shape cast Mosaic refuses)
        lens = [lengths_ref[blk * bb + i] for i in range(bb)]
        alive = [ln + ext > 0 for ln in lens]
        hi, hi_max = last_pages(lens)
        pairs = [(i, h) for i in range(bb) for h in range(hkv)]
        if sel_ref is not None:
            nsel = sel_ref.shape[0] // cnt_ref.shape[0]
            cnts = [jnp.where(alive[i], cnt_ref[(blk * bb + i) * hkv + h], 0)
                    for i, h in pairs]
            hi_max = functools.reduce(jnp.maximum, cnts) - 1

            def listed(c):
                """The logical page each (row, KV head) reads at list
                position c: clamped into its own list; 0 for an empty
                one."""
                return [sel_ref[((blk * bb + i) * hkv + h) * nsel
                                + jnp.clip(c, 0, jnp.maximum(n - 1, 0))]
                        for (i, h), n in zip(pairs, cnts)]

        def chosen(c):
            """Per (row, KV head): is page c in its selection (bits
            form)."""
            first = blk * bb if bits_row is None else bits_row
            nw = bits_ref.shape[0] // (lengths_ref.shape[0] * hkv) \
                if lanebits_ref is None else lanebits_ref.shape[2]
            return [(bits_ref[((first + i) * hkv + h) * nw + c // 32]
                     >> (c % 32)) & 1 for i, h in pairs]
        lo, lo_min = first_pages(lens, alive)

        # the selecting ragged entry (the bits form) keeps every row of a
        # live block copying, clamped: past the dense length there is
        # nothing to skip, and its program stays as it was
        skips = sel_ref is None and bits_ref is None

        def fetches(i, c):
            """Does row i copy page c: only a live row, inside its OWN
            range. Read off the lengths, like the ranges themselves."""
            return alive[i] & (lo[i] <= c) & (c <= hi[i])

        def row_pages(c):
            """(buffer row, physical page) of every row's page-c copy.
            Table entries past a row's live range may be anything valid
            (scratch, stale) and those below its window released pages:
            never fetch them. Where rows skip (``fetches``) an entry
            outside the range is read and not used; elsewhere a row clamps
            into its range and a dead row in a live block rides along on
            physical page 0 (always in the pool)."""
            if skips:
                return [(i, table_ref[trow(blk * bb + i) + c])
                        for i in range(bb)]
            return [(i, jnp.where(
                alive[i],
                table_ref[trow(blk * bb + i)
                          + jnp.clip(c, lo[i], jnp.maximum(hi[i], 0))], 0))
                    for i in range(bb)]

        def zero_unfilled():
            """A row that copies nothing at one of the walk's first two
            steps (a one-page row's second buffer slot; a dead row; a
            window row above the block's first page) would leave that
            slot of its V buffer as the call found it, and the masked
            update still multiplies it: p is 0 there — or 1 before the
            row's first live column, which ``corr`` = 0 wipes later — and
            0 x NaN is NaN (K is behind the select on ``live_col``). Zero
            those slots; every later read finds the row's own copy or
            this."""
            for c in (lo_min, lo_min + 1):
                for i in range(bb):
                    @pl.when((c <= hi_max) & ~fetches(i, c))
                    def _zero(c=c, i=i):
                        v_buf[c % 2, i] = jnp.zeros(v_buf.shape[2:],
                                                    v_buf.dtype)
                        if quant:
                            vs_buf[c % 2, i] = jnp.zeros(vs_buf.shape[2:],
                                                         vs_buf.dtype)

        def listed_pages(c):
            """row_pages for the list form: ((row, KV head), physical
            page)."""
            return [((i, h), jnp.where(
                n > 0, table_ref[trow(blk * bb + i) + lp], 0))
                    for (i, h), n, lp in zip(pairs, cnts, listed(c))]

        def per_row():
            """Every row streams its own pages: BB copies a page step,
            BB*Hkv matmuls of ``groups`` rows."""
            lens_b = _per_slot(lens, (bb, hq, ps))
            q3s = [(q_ref[:, r * hq:(r + 1) * hq].astype(jnp.float32)
                    * scale).reshape(bb * hkv, groups, d) for r in range(R)]

            def update(c, buf):
                k3 = k_buf[buf].astype(jnp.float32).reshape(bb * hkv, ps, d)
                v3 = v_buf[buf].astype(jnp.float32).reshape(bb * hkv, ps, d)
                if quant:
                    # scale pages arrive lane-padded (kv_pool.scale_lanes);
                    # only the first ``ps`` lanes are rows of this page
                    kscale = ks_buf[buf][:, :, :ps].reshape(bb * hkv, ps)
                    vscale = vs_buf[buf][:, :, :ps].reshape(bb * hkv, ps)
                col = c * ps + jax.lax.broadcasted_iota(jnp.int32,
                                                        (bb, hq, ps), 2)
                limit_b = lens_b
                if sel_ref is not None:
                    # a column is where its (row, head)'s listed page puts
                    # it; past the end of a list: nowhere
                    base = [jnp.where(c < n, lp * ps, num_pages * ps)
                            for n, lp in zip(cnts, listed(c))]
                    col = jax.lax.broadcasted_iota(jnp.int32,
                                                   (bb, hq, ps), 2) \
                        + _per_pair(base, (bb, hq, ps), hkv, groups, False)
                elif bits_ref is not None:
                    limit_b = _per_pair(
                        [jnp.where(b > 0, lens[i], 0)
                         for (i, _), b in zip(pairs, chosen(c))],
                        (bb, hq, ps), hkv, groups, False)
                for r in range(R):         # static unroll over draft rows
                    sl = slice(r * hq, (r + 1) * hq)
                    s = jax.lax.dot_general(
                        q3s[r], k3, (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32)  # [BB*Hkv,G,ps]
                    if quant:
                        s = s * kscale[:, None, :]

                    def pv_of(p):
                        p3 = p.reshape(bb * hkv, groups, ps)
                        if quant:
                            p3 = p3 * vscale[:, None, :]
                        return jax.lax.dot_general(
                            p3, v3, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32
                        ).reshape(bb, hq, d)                 # [BB*Hkv,G,d]

                    flash(s.reshape(bb, hq, ps), col,
                          limit_b + (1 + r if spec else 0), m_ref, l_ref,
                          acc_ref, sl, pv_of)

            reset(acc_ref, m_ref, l_ref)
            if skips:
                zero_unfilled()
            walk(lo_min, hi_max,
                 row_pages if sel_ref is None else listed_pages, update,
                 fetches if skips else None)
            return acc_ref[:] / jnp.maximum(l_ref[:, :, :1], 1e-9)

        def shared(row):
            """All live rows read table row ``row`` (an int8 pool, or a
            page selection a row and KV head): ONE copy a page step into
            buffer row 0, Hkv matmuls of BB*groups rows (tile row =
            b*groups + group)."""
            n = bb * groups

            def tile(x):       # [BB, Hq, *] -> [Hkv, BB*groups, *]
                return x.reshape(bb, hkv, groups, -1).transpose(1, 0, 2, 3) \
                    .reshape(hkv, n, -1)

            qt = tile(q_ref[:].astype(jnp.float32) * scale)
            limit = _per_slot(lens, (hkv, n, ps), axis=1, each=groups)

            def update(c, buf):
                if bits_ref is None:
                    return fold(c, buf, limit)
                picks = chosen(c)

                @pl.when(functools.reduce(jnp.bitwise_or, picks) > 0)
                def _some_row_chose_it():
                    fold(c, buf, _per_pair(
                        [jnp.where(b > 0, lens[i], 0)
                         for (i, _), b in zip(pairs, picks)],
                        (hkv, n, ps), hkv, groups, True))

            def fold(c, buf, limit):
                k3 = k_buf[buf, 0].astype(jnp.float32)        # [Hkv, ps, d]
                v3 = v_buf[buf, 0].astype(jnp.float32)
                s = jax.lax.dot_general(
                    qt, k3, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)       # [Hkv, n, ps]
                if quant:
                    s = s * ks_buf[buf, 0][:, :ps][:, None, :]

                def pv_of(p):
                    if quant:
                        p = p * vs_buf[buf, 0][:, :ps][:, None, :]
                    return jax.lax.dot_general(
                        p, v3, (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32)   # [Hkv, n, d]

                col = c * ps + jax.lax.broadcasted_iota(jnp.int32,
                                                        (hkv, n, ps), 2)
                flash(s, col, limit, m_t, l_t, acc_t, slice(None), pv_of)

            reset(acc_t, m_t, l_t)
            walk(lo_min, hi_max, lambda c: [(0, table_ref[trow(row) + c])],
                 update)
            out = acc_t[:] / jnp.maximum(l_t[:, :, :1], 1e-9)
            return out.reshape(hkv, bb, groups, d).transpose(1, 0, 2, 3) \
                .reshape(bb, hq, d)

        def emit(ctx):
            # dead rows hold whatever rode through their lanes: exactly
            # zero out
            live_row = _per_slot(lens, ctx.shape) + ext > 0
            o_ref[:] = jnp.where(live_row, ctx, 0.0).astype(o_ref.dtype)

        if share_ref is None:
            emit(per_row())
        else:
            row = share_ref[blk]

            @pl.when(row >= 0)
            def _shared():
                if on_lanes:
                    o_ref[:] = rows_on_lanes(row, q_ref, lens, lo_min,
                                             hi_max).astype(o_ref.dtype)
                else:
                    emit(shared(row))

            @pl.when(row < 0)
            def _per_row():
                emit(per_row())

    if wide_ref is None:
        return block(g, q_ref, o_ref)
    shares = wide_ref[g]

    @pl.when(shares >= 0)
    def _one_tile():
        lens = [lengths_ref[g * tile + i] for i in range(tile)]
        _, hi_max = last_pages(lens)
        # a window tile starts at its LOWEST row's first live page: nothing
        # below every row's window (released pages) is ever fetched
        _, lo_min = first_pages(lens, [ln > 0 for ln in lens])
        o_ref[:] = rows_on_lanes(shares, q_ref, lens, lo_min,
                                 hi_max).astype(o_ref.dtype)

    @pl.when(shares == -1)
    def _block_by_block():
        def one(j, carry):
            rows = pl.ds(pl.multiple_of(j * bb, bb), bb)
            block(g * (tile // bb) + j, q_ref.at[rows], o_ref.at[rows],
                  None if bitsat_ref is None else bitsat_ref[g] + j * bb)
            return carry

        jax.lax.fori_loop(0, tile // bb, one, 0)

    @pl.when(shares < -1)
    def _no_live_row():
        o_ref[:] = jnp.zeros_like(o_ref)


def _paged_db_kernel(*refs, quant: bool, share: bool, wide: bool = False,
                     rowmap: bool = False, sel: bool = False,
                     bits: bool = False, **kw):
    """Name the pallas_call's positional refs (scalar prefetch, inputs,
    output, scratch, in _paged_flash_db's order) for _paged_db_body; what a
    bf16 pool or a call without a share fact leaves out is None."""
    it = iter(refs)

    def take(n, present=True):
        return [next(it) if present else None for _ in range(n)]

    lengths_ref, layer_ref, table_ref = take(3)
    share_ref, = take(1, share)
    wide_ref, = take(1, wide)
    rowmap_ref, = take(1, rowmap)
    sel_ref, cnt_ref = take(2, sel)
    bits_ref, = take(1, bits)
    bitsat_ref, = take(1, bits and wide)
    q_ref, = take(1)
    lanebits_ref, = take(1, bits and wide)
    k_hbm, v_hbm = take(2)
    ks_hbm, vs_hbm = take(2, quant)
    o_ref, k_buf, v_buf = take(3)
    ks_buf, vs_buf = take(2, quant)
    acc_ref, m_ref, l_ref, sem = take(4)
    acc_t, m_t, l_t = take(3, share)
    wide_state = take(3, wide)
    _paged_db_body(lengths_ref, layer_ref, table_ref, share_ref, wide_ref,
                   rowmap_ref, sel_ref, cnt_ref, bits_ref, bitsat_ref, q_ref,
                   lanebits_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf,
                   v_buf, ks_buf, vs_buf, acc_ref, m_ref, l_ref, sem, acc_t,
                   m_t, l_t, wide_state, **kw)


def _resolve_bb(bblock, B: int) -> int:
    """Largest divisor of B not exceeding the requested block (>= 1)."""
    bb = max(1, min(int(bblock or 1), B))
    while B % bb:
        bb -= 1
    return bb


# The widest query tile of a ragged call, in packed rows: a tile walks the
# pages of its longest row for every row, and consecutive chunk rows are a
# token apart, so up to a page's width (64 served) a wider tile adds at
# most one part-masked page step to what its rows need.
TILE_ROWS = 64
# VMEM a wide tile may take: its float32 context, the queries, the logits,
# P and P.V of one page step, and the pipeline's two buffers each of its q
# and o blocks — beside the page buffers, inside the 16 MiB a kernel gets
# by default on a v5e.
TILE_VMEM_BYTES = 8 * 1024 * 1024


def _tile_rows(N: int, bb: int, hq: int, d: int, ps: int, dtype) -> int:
    """Packed rows a grid step of a ragged call holds: the largest multiple
    of ``bb`` that divides N, is no wider than TILE_ROWS and keeps a wide
    tile's working set inside TILE_VMEM_BYTES (2,080 rows of 16 heads ->
    40; 2,064 -> 48; 2,072 and 4,144 -> 56; the selecting model's 4,632 =
    8 x 3 x 193 -> 24, its only multiple of 8 past 8); ``bb`` where none is
    wider, where blocks are one row (nothing is shared) and under an int8
    pool (its scales ride a page's lanes: the blocks of ``shared``). From
    the call's shapes and the pool's ``dtype`` alone — under a ``tp`` mesh
    the shard's. The plain and the selecting ragged entries cut their grid
    steps by it alike (a selecting tile's lane words, 2 x Hkv x W x rows x
    groups x 4 bytes in the pipeline's buffers, are 0.1 MiB at 24 rows)."""
    dtype = jnp.dtype(dtype)
    if bb == 1 or dtype == jnp.int8:
        return bb
    per_row = hq * (4 * (2 * d + 2 * ps) + 5 * d * dtype.itemsize)
    return max([t for t in range(bb, TILE_ROWS + 1, bb)
                if N % t == 0 and t * per_row <= TILE_VMEM_BYTES],
               default=bb)


# The narrowest tile a plain ragged call settles for before it pads its rows
TILE_MIN = TILE_ROWS // 2


def _ragged_pad(N: int, bblock, hq: int, d: int, ps: int, dtype) -> int:
    """Packed rows a plain ragged call RUNS for the ``N`` it is handed: ``N``
    where its tile (:func:`_tile_rows`) is at least TILE_MIN rows, or where
    blocks are the unit anyway (one-row blocks, an int8 pool) or the call is
    no wider than one tile; else ``N``
    rounded up to a whole number of TILE_ROWS — dead rows behind the last,
    which cost a grid step nothing — where that gives a wider tile. 24 +
    1,024 = 8 x 131 rows have no tile past a block of 8 and 48 + 2,048 =
    16 x 131 none past 16 (the narrow bodies of serving/programs.mixed_step
    in two cells): 1,088 and 2,112 rows run as tiles of 64. Every count a
    cell served before keeps its own (40-64). From the shapes alone; the
    engine's record counts its page steps by the same function."""
    bb = _resolve_bb(bblock, N)
    tile = _tile_rows(N, bb, hq, d, ps, dtype)
    if (bb == 1 or jnp.dtype(dtype) == jnp.int8 or tile >= TILE_MIN
            or N <= TILE_ROWS):
        return N
    rows = -(-N // TILE_ROWS) * TILE_ROWS
    wider = _tile_rows(rows, _resolve_bb(bblock, rows), hq, d, ps, dtype)
    return rows if wider > tile else N


def _shared_row(live, keys, width: int, dead: int = -1):
    """Per run of ``width`` packed rows: its first live row if every live
    row's key (``row_map``'s entry, [N]: the slot it names) equals that
    row's — then any page one of them needs is at the same entry of that
    slot's table row —, -1 if they differ, ``dead`` if no row is live. (The
    comparison takes keys of any trailing width, [N, max_pages] table rows
    too; every caller hands [N].)"""
    N = live.shape[0]
    live = live.reshape(N // width, width)
    keys = keys.reshape(N // width, width, -1)
    first = jnp.argmax(live, axis=1).astype(jnp.int32)
    lead = jnp.take_along_axis(keys, first[:, None, None], axis=1)
    same = jnp.all((keys == lead) | ~live[:, :, None], axis=(1, 2))
    row = jnp.arange(N // width, dtype=jnp.int32) * width + first
    return jnp.where(live.any(axis=1), jnp.where(same, row, -1), dead)


def _share_facts(q, pool_k, row_limits, keys, bb: int):
    """(share, wide) of a ragged call (_paged_flash_db): who shares a page
    stream, per block of ``bb`` rows and per tile of :func:`_tile_rows`
    rows — read off the rows' keys, per call, not set by anyone."""
    if bb == 1:
        return None, None
    (N, hq, d), live = q.shape, row_limits > 0
    tile = _tile_rows(N, bb, hq, d, pool_k.shape[3], pool_k.dtype)
    return (_shared_row(live, keys, bb),
            _shared_row(live, keys, tile, dead=-2) if tile > bb else None)


def _paged_flash_db(q2, pool_k, pool_v, lengths, layer_arr, table,
                    *, bb: int, R: int, spec: bool, window: int,
                    interpret: bool, pool_ks, pool_vs, share=None,
                    wide=None, row_map=None, sel=None, cnt=None, bits=None,
                    bits_at=None, lanebits=None):
    """Build + dispatch the double-buffered paged flash call.

    q2: [B, R*Hq, D] (R=1 for plain decode). Grid is (B // bb,); the pools
    ride as ANY-memory-space operands (never blocked by Pallas — the kernel
    DMAs exactly the live pages), q/o are VMEM-blocked per slot block.
    ``share`` [B // bb] int32 (ragged entry only): per block, the row whose
    table its live rows all share, or -1 — see _paged_db_body.
    ``wide`` [B // tile] int32 (with ``share``): the same fact per run of
    ``tile`` rows, ``tile`` a multiple of bb read off its length — a grid
    step is then ``tile`` rows (_paged_db_body; :func:`_tile_rows`).
    ``row_map`` [B]: the row of ``table`` each packed row reads (None: its
    own). ``sel`` [B, Hkv, K] with ``cnt`` [B, Hkv], or ``bits``
    [B, Hkv, ceil(pages / 32)] int32: the pages each row and KV head
    reads, as a list or as a mask (bf16 pool, no window). With ``wide`` the
    mask comes twice: ``lanebits`` [B // tile, Hkv, W, tile * groups], a
    step's words on the lanes of its sharing tile (VMEM-blocked), and
    ``bits`` [rows, Hkv, W] for the steps whose blocks run one by one
    only, ``bits_at`` [B // tile] naming each such step's first row in it.
    """
    B, RHq, D = q2.shape
    Hkv, ps = pool_k.shape[2], pool_k.shape[3]
    groups = (RHq // R) // Hkv
    num_pages = table.shape[1]
    quant = pool_ks is not None
    tile = bb if wide is None else B // wide.shape[0]

    in_specs = [pl.BlockSpec(memory_space=pltpu.ANY)] * 2    # the pools
    operands = [q2, pool_k, pool_v]
    if lanebits is not None:
        in_specs.insert(0, pl.BlockSpec((1,) + lanebits.shape[1:],
                                        lambda g, *prefetched: (g, 0, 0, 0)))
        operands.insert(1, lanebits)
    if quant:
        in_specs += [pl.BlockSpec(memory_space=pltpu.ANY)] * 2
        operands += [pool_ks, pool_vs]
    scratch = [
        pltpu.VMEM((2, bb, Hkv, ps, D), pool_k.dtype),     # k page buffers
        pltpu.VMEM((2, bb, Hkv, ps, D), pool_v.dtype),     # v page buffers
    ]
    if quant:
        # Scale pages move whole: [Hkv, lanes] with lanes = the scale leaf's
        # minor dim (kv_pool.scale_lanes pads page_size up to the 128-lane
        # tile — Mosaic refuses a DMA slice whose minor dim is narrower).
        scratch += [pltpu.VMEM((2, bb, Hkv, pool_ks.shape[3]),
                               pool_ks.dtype)] * 2
    scratch += [
        pltpu.VMEM((bb, RHq, D), jnp.float32),             # acc
        pltpu.VMEM((bb, RHq, 128), jnp.float32),           # m
        pltpu.VMEM((bb, RHq, 128), jnp.float32),           # l
        # (the list form copies a page a KV head at a time)
        pltpu.SemaphoreType.DMA((2, bb, 4 if quant else
                                 2 * Hkv if sel is not None else 2)),
    ]
    # The table rides SMEM flattened: a 2-D s32[S, max_pages] operand pads
    # its minor dim to 128 lanes there (a 32-page row to four times its
    # bytes, of the chip's 1 MiB).
    prefetch = [lengths, layer_arr, table.reshape(-1)]
    if sel is not None or bits is not None:
        assert not quant and window == 0 and not spec, \
            "page selection: bf16 pool, full attention, one row a query"
    if share is not None:
        prefetch.append(share)
    if wide is not None:
        prefetch.append(wide)
    if row_map is not None:
        prefetch.append(row_map)
    if sel is not None:
        prefetch += [sel.reshape(-1), cnt.reshape(-1)]
    if bits is not None:
        prefetch.append(bits.reshape(-1))
    if bits_at is not None:
        prefetch.append(bits_at)
    if share is not None:
        # the sharing blocks' flash state: rows on sublanes under scales or
        # a selection (``shared``), else on lanes like a wide tile's
        lanes = (tile,) if tile > bb else ()
        if quant or bits is not None:
            scratch += [
                pltpu.VMEM((Hkv, bb * groups, D), jnp.float32),
                pltpu.VMEM((Hkv, bb * groups, 128), jnp.float32),
                pltpu.VMEM((Hkv, bb * groups, 128), jnp.float32),
            ]
        else:
            lanes = (bb,) + lanes
        for rows in lanes:
            scratch += [
                pltpu.VMEM((Hkv, D, rows * groups), jnp.float32),
                pltpu.VMEM((Hkv, 1, rows * groups), jnp.float32),
                pltpu.VMEM((Hkv, 1, rows * groups), jnp.float32),
            ]

    def q_map(g, *prefetched):
        return (g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B // tile,),
        in_specs=[pl.BlockSpec((tile, RHq, D), q_map)] + in_specs,
        out_specs=pl.BlockSpec((tile, RHq, D), q_map),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _paged_db_kernel, quant=quant, share=share is not None,
        wide=wide is not None, rowmap=row_map is not None,
        sel=sel is not None, bits=bits is not None, ps=ps, groups=groups,
        scale=1.0 / (D ** 0.5), R=R, bb=bb, tile=tile,
        num_pages=num_pages, window=window, spec=spec)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, RHq, D), q2.dtype),
        interpret=interpret,
    )(*prefetch, *operands)


@functools.partial(jax.jit, static_argnames=("interpret", "window", "bblock"))
def decode_attend_pallas_paged(q: jnp.ndarray, pool_k: jnp.ndarray,
                               pool_v: jnp.ndarray, lengths: jnp.ndarray,
                               layer: jnp.ndarray, table: jnp.ndarray,
                               interpret: bool = False,
                               pool_ks: jnp.ndarray = None,
                               pool_vs: jnp.ndarray = None,
                               window: int = 0,
                               bblock: int = 1):
    """Double-buffered flash decode attention over one layer of the PAGED
    pool.

    q: [B, 1, Hq, D]; pool_k/v: [L, P, Hkv, page, D]; lengths: [B] (counting
    the just-written token); layer: scalar int32; table: [B, max_pages] int32
    physical page ids (row b maps slot b's logical pages; entries at or past
    the slot's live range may be any valid id — no copy of them starts).
    Returns [B, 1, Hq, D]. pool_ks/vs switch the int8 scale-folding
    body. ``bblock`` slots share each grid step
    (resolved to the largest divisor of B); page i+1 prefetches while page i
    computes regardless of bblock — see _paged_db_body. Rows are served in
    the order given, ``bblock`` consecutive rows a grid step, and a step
    walks the pages of its longest row with an update for all of them (a
    row copies its own pages only): the caller that
    wants a full walk hands neighbours in length (the decode program does:
    ops/attention.make_decode_attend_carry_paged sorts rows, lengths and
    table alike and un-permutes the result; a row's output does not depend
    on its block-mates, bit for bit). A slot of length 0
    (idle) is a dead row: nothing is fetched for it and its output row is
    exactly zero (it used to be the mean of its first page's V rows).
    """
    B = q.shape[0]
    lengths = lengths.astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    out = _paged_flash_db(
        q[:, 0], pool_k, pool_v, lengths, layer_arr, table.astype(jnp.int32),
        bb=_resolve_bb(bblock, B), R=1, spec=False, window=window,
        interpret=interpret, pool_ks=pool_ks, pool_vs=pool_vs)
    return out[:, None]


@functools.partial(jax.jit, static_argnames=("interpret", "window", "bblock"))
def ragged_attend_pallas_paged(q: jnp.ndarray, pool_k: jnp.ndarray,
                               pool_v: jnp.ndarray, row_limits: jnp.ndarray,
                               layer: jnp.ndarray, table: jnp.ndarray,
                               row_map: jnp.ndarray,
                               interpret: bool = False,
                               pool_ks: jnp.ndarray = None,
                               pool_vs: jnp.ndarray = None,
                               window: int = 0,
                               bblock: int = 1) -> jnp.ndarray:
    """RAGGED paged flash attention: N query-token-packed rows, each with its
    OWN (page run, live-column count) — one program serves a mixed batch of
    single-token decode rows and prefill-chunk rows in a single dispatch
    (PAPERS.md "Ragged Paged Attention").

    The key move is that the double-buffered body (_paged_db_body) never
    cared that row i belonged to slot i — its math is entirely driven by the
    (table row, limit) pair it is handed per query row. ``table``
    [S, max_pages] holds ONE row a slot and ``row_map`` [N] names the row of
    it each packed row reads (a row a packed row would be 266 KB of the
    chip's 1 MiB of SMEM at 2,080 rows x 32 pages and 2.4 MB at 48 + 4,096
    rows of 144 pages; a row a slot and the map are 12 KB). That turns the
    per-slot decode kernel into a variable-length-rows kernel with zero
    changes to the flash accumulation, the page-clamp raggedness handling,
    or the two-slot DMA pipeline:

    - a DECODE row names its slot's table row and limit = context + 1;
    - a PREFILL-CHUNK row at position p names the chunking slot's table
      row and limit = p + 1 (plain causality), so C chunk rows of one slot
      pack alongside B decode rows of B other slots and every row masks to
      exactly its own live columns;
    - a row with limit 0 (the chunk's padding rows, the chunking slot's own
      decode row) is DEAD: it costs no fetch and no flash update, the table
      row it names is never read and may hold anything, and its output is
      zero.

    The work follows the live (row, page) pairs. Where the live rows of one
    grid step all name the same slot — the chunk rows of a prefill do — the
    step fetches each page ONCE and updates its rows as one query tile
    (_paged_db_body's sharing path); that is read off ``row_map`` here, per
    call, not set by anyone. A grid step is a TILE of :func:`_tile_rows`
    rows (40-64 where the row count has such a divisor, from the shapes
    alone; ``bblock`` under an int8 pool): 4,096 chunk rows stream their
    pages 73 times where blocks of 8 streamed them 512 times. A step whose
    live rows name several slots (the one that holds the decode rows) runs
    its ``bblock``-row blocks one after the other: a block of chunk rows as
    a tile of its own, decode rows of distinct slots a page per row, as the
    decode entry does.

    q: [N, Hq, D] packed query rows; row_limits: [N] live columns per row;
    table: [S, max_pages] int32 (entries at or past a row's live range may
    be any valid id — no copy of them starts — and those below its window
    released pages); row_map: [N] int32; layer: scalar. Returns [N, Hq, D].
    pool_ks/vs switch the int8 scale-folding body; ``window`` > 0 applies
    per-row sliding-window masking off each row's own limit, and a block's
    walk then starts at its lowest row's first live page. ``bblock``
    (resolved to the largest divisor of N) is the width of the blocks that
    stream a page per row.
    """
    N, hq, d = q.shape
    bb = _resolve_bb(bblock, N)
    row_map = row_map.astype(jnp.int32)
    row_limits = row_limits.astype(jnp.int32)
    # A tile's blocks are SLICES of its [rows, Hq, D] queries in VMEM, and
    # Mosaic slices whole sublane tiles: past one tile of 8 heads, where Hq is
    # no multiple of 8 (20 heads in groups of 5: ``memref<64x24x128> ->
    # 8x20x128`` is refused), every KV head's group gets dead query heads of
    # zeros, the fewest that make it one (5 -> 6), and their outputs are
    # dropped. Read off the shapes: up to 8 heads and at every multiple of 8
    # (every group served before) the call is what it was.
    hkv = pool_k.shape[2]
    groups = padded = hq // hkv
    while hq > 8 and (hkv * padded) % 8:
        padded += 1
    if padded != groups:
        q = jnp.pad(q.reshape(N, hkv, groups, d),
                    ((0, 0), (0, 0), (0, padded - groups), (0, 0))
                    ).reshape(N, hkv * padded, d)
    # a row count without a tile of its own gets dead rows behind it
    rows = _ragged_pad(N, bblock, q.shape[1], d, pool_k.shape[3],
                       pool_k.dtype)
    if rows != N:
        bb = _resolve_bb(bblock, rows)
        q = jnp.pad(q, ((0, rows - N), (0, 0), (0, 0)))
        row_limits = jnp.pad(row_limits, (0, rows - N))
        row_map = jnp.pad(row_map, (0, rows - N))
    # a block, or a tile, whose live rows all name one slot shares it
    share, wide = _share_facts(q, pool_k, row_limits, row_map, bb)
    out = _paged_flash_db(
        q, pool_k, pool_v, row_limits,
        jnp.asarray(layer, jnp.int32).reshape(1), table.astype(jnp.int32),
        bb=bb, R=1, spec=False, window=window, interpret=interpret,
        pool_ks=pool_ks, pool_vs=pool_vs, share=share, wide=wide,
        row_map=row_map)[:N]
    if padded != groups:
        out = out.reshape(N, hkv, padded, d)[:, :, :groups].reshape(N, hq, d)
    return out


# -- the entry points of a list that holds window AND full layers -------------
#
# The same bodies under jits of their own, so that the device trace tells a
# window layer's calls from a full layer's (a Pallas call is named after the
# innermost jitted wrapper around it: ``_named_entry``).


def _named_entry(name: str, fn, doc: str):
    """``fn`` under a jit of its own that the device trace calls ``name``."""
    @functools.wraps(fn)
    def entry(*args, **kw):
        return fn(*args, **kw)

    entry.__name__ = entry.__qualname__ = name
    entry.__doc__ = doc
    del entry.__wrapped__
    return jax.jit(entry, static_argnames=("interpret", "window", "bblock"))


decode_attend_pallas_paged_window = _named_entry(
    "decode_attend_pallas_paged_window", decode_attend_pallas_paged.__wrapped__,
    """:func:`decode_attend_pallas_paged` for the WINDOW layers of a list
    that also holds full ones: their own leaves, their own table (entries
    below a row's window may be anything: released pages), the static
    ``window``. bf16 pool.""")
ragged_attend_pallas_paged_window = _named_entry(
    "ragged_attend_pallas_paged_window", ragged_attend_pallas_paged.__wrapped__,
    """:func:`ragged_attend_pallas_paged` for the WINDOW layers of such a
    list, likewise.""")


# SMEM the ragged selecting entry lets its prefetched operands take in one
# call (of the chip's 1 MiB; the compiler keeps scalars of its own there)
SELECT_PREFETCH_BYTES = 768 * 1024


@functools.partial(jax.jit, static_argnames=("interpret", "bblock"))
def decode_attend_pallas_paged_select(q, pool_k, pool_v, lengths, layer,
                                      table, sel, cnt,
                                      interpret: bool = False,
                                      bblock: int = 1):
    """:func:`decode_attend_pallas_paged` over SELECTED pages: row b's KV
    head h reads the ``cnt[b, h]`` logical pages ``sel[b, h, :cnt]``
    (ascending; entries past ``cnt`` are never read) and nothing else —
    the walk is over list positions, so its length is the longest list of
    the block, not the longest context (_paged_db_body). q: [B, 1, Hq, D];
    sel: [B, Hkv, K] int32; cnt: [B, Hkv] int32; ``lengths`` counts the
    just-written token and masks the last page's tail. bf16 pool."""
    B = q.shape[0]
    out = _paged_flash_db(
        q[:, 0], pool_k, pool_v, lengths.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), table.astype(jnp.int32),
        bb=_resolve_bb(bblock, B), R=1, spec=False, window=0,
        interpret=interpret, pool_ks=None, pool_vs=None,
        sel=sel.astype(jnp.int32), cnt=cnt.astype(jnp.int32))
    return out[:, None]


def _select_held(n: int, bb: int, hq: int, d: int, ps: int, dtype,
                 slots: int) -> tuple:
    """(tile, rows whose words ride SMEM) of a selecting ragged call of
    ``n`` rows."""
    tile = _tile_rows(n, bb, hq, d, ps, dtype)
    return tile, n if tile == bb else min(n, slots * tile)


def select_fits_one_call(n: int, bb: int, hq: int, d: int, ps: int, dtype,
                         table_shape: tuple, hkv: int, words: int) -> bool:
    """Do the prefetched operands of ONE selecting ragged call of ``n``
    rows fit the SMEM it may take (``SELECT_PREFETCH_BYTES``)? From the
    shapes alone: ragged_attend_pallas_paged_select cuts its rows by it,
    and serving/programs.mixed_narrow_rows reads it."""
    slots, pages = table_shape
    held = _select_held(n, bb, hq, d, ps, dtype, slots)[1]
    return n <= bb or 4 * (slots * pages + 3 * n + held * hkv * words) \
        <= SELECT_PREFETCH_BYTES


@functools.partial(jax.jit, static_argnames=("interpret", "bblock"))
def ragged_attend_pallas_paged_select(q, pool_k, pool_v, row_limits, layer,
                                      table, row_map, bits,
                                      interpret: bool = False,
                                      bblock: int = 1):
    """:func:`ragged_attend_pallas_paged` under a page SELECTION a row and
    KV head: ``bits`` [N, Hkv, W] int32, bit p % 32 of word p // 32 set
    where the row's head reads logical page p. Every live page of a grid
    step is still walked; what a row did not choose is masked. ``table``
    [S, max_pages] holds ONE row a slot and ``row_map`` [N] names each
    packed row's — the chunk rows of a prefill share an entry, which is
    also how a sharing tile is recognised, as in the plain entry: a grid
    step is a TILE of :func:`_tile_rows` rows (24 of 24 + 4,608), one that
    shares is one page stream with the selection as a mask over its lanes
    (``lanebits``, laid out here once a call), one whose live rows name
    several slots (the decode rows) runs its ``bblock``-row blocks with
    their words in SMEM. A slot's live rows are CONSECUTIVE packed rows
    (every packed batch: a decode row a slot, a chunk one run), so at most
    one step a slot straddles two and SMEM holds the words of that many.
    q: [N, Hq, D]. bf16 pool. More rows than one call's prefetched operands
    fit in SMEM (``SELECT_PREFETCH_BYTES``) are walked by further calls."""
    N, hq, d = q.shape
    bb = _resolve_bb(bblock, N)
    row_limits = row_limits.astype(jnp.int32)
    row_map = row_map.astype(jnp.int32)
    table = table.astype(jnp.int32)
    bits = bits.astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    hkv, words = bits.shape[1:]

    def call(lo, hi):
        rows = slice(lo, hi)
        share, wide = _share_facts(q[rows], pool_k, row_limits[rows],
                                   row_map[rows], bb)
        smem, kw = bits[rows], {}
        if wide is not None:
            tile, keep = _select_held(hi - lo, bb, hq, d, pool_k.shape[3],
                                      pool_k.dtype, table.shape[0])
            by_tile = smem.reshape(-1, tile, hkv, words)
            blocks = wide == -1       # the steps that run block by block
            smem = by_tile[jnp.argsort(~blocks, stable=True)[:keep // tile]]
            at = jnp.minimum(jnp.cumsum(blocks) - 1, keep // tile - 1)
            kw = dict(bits_at=jnp.where(blocks, at * tile, 0)
                      .astype(jnp.int32),
                      lanebits=jnp.repeat(by_tile.transpose(0, 2, 3, 1),
                                          hq // hkv, axis=3))
        return _paged_flash_db(
            q[rows], pool_k, pool_v, row_limits[rows], layer_arr, table,
            bb=bb, R=1, spec=False, window=0, interpret=interpret,
            pool_ks=None, pool_vs=None, share=share, wide=wide,
            row_map=row_map[rows], bits=smem, **kw)

    def calls(lo, hi):
        """The prefetched operands ride SMEM: the table, a packed row's
        limit, map entry and share fact, and Hkv x W bit words a row held
        (8,216 rows x 2 x 16 words alone are the chip's 1 MiB). Rows are
        independent, so more rows than fit go in further calls, each of
        whole blocks."""
        n = hi - lo
        if select_fits_one_call(n, bb, hq, d, pool_k.shape[3], pool_k.dtype,
                                table.shape, hkv, words):
            return [call(lo, hi)]
        mid = lo + -(-n // (2 * bb)) * bb
        return calls(lo, mid) + calls(mid, hi)

    out = calls(0, N)
    return out[0] if len(out) == 1 else jnp.concatenate(out)


@functools.partial(jax.jit, static_argnames=("interpret", "stride"))
def selector_add_row_paged(kc, new, rows, table, layer, *, stride: int,
                           interpret: bool = False):
    """Add one new key a slot into the SELECTOR's cache, in place: ``kc``
    [L, P, Hkv, runs, D] float32 keeps, a page, the sums of its keys over
    runs of ``stride`` tokens; the key at logical row ``rows[b]`` is added
    to its run — which first drops to zero where the key opens it, so a
    page's next occupant never adds to its predecessor's sums. One row a
    slot, a grid step a slot, the whole page's runs one block (the
    contract of cache_write_row_paged; rows outside the table drop)."""
    L, P, Hkv, runs, D = kc.shape
    ps = runs * stride
    rows = rows.astype(jnp.int32)
    table = table.astype(jnp.int32)
    MP = table.shape[1]
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)

    def new_map(b, rws, lay, tab):
        return (b, 0, 0)

    def blk_map(b, rws, lay, tab):
        r = jnp.clip(rws[b], 0, MP * ps - 1)
        return (lay[0], tab[b * MP + r // ps], 0, 0, 0)

    def kernel(rows_ref, layer_ref, table_ref, new_ref, cin_ref, cout_ref):
        r = rows_ref[pl.program_id(0)]
        ok = (r >= 0) & (r < MP * ps)
        r = jnp.clip(r, 0, MP * ps - 1)
        run = jnp.where(ok, (r % ps) // stride, -1)
        idx = jax.lax.broadcasted_iota(jnp.int32, (Hkv, runs, D), 1)
        old = cin_ref[0, 0]
        kept = jnp.where(r % stride == 0, 0.0, old)
        cout_ref[0, 0] = jnp.where(idx == run, kept + new_ref[0][:, None, :],
                                   old)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(new.shape[0],),
        in_specs=[pl.BlockSpec((1, Hkv, D), new_map),
                  pl.BlockSpec((1, 1, Hkv, runs, D), blk_map)],
        out_specs=pl.BlockSpec((1, 1, Hkv, runs, D), blk_map))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(kc.shape, kc.dtype),
        input_output_aliases={4: 0}, interpret=interpret,
    )(rows, layer_arr, table.reshape(-1), new.astype(kc.dtype), kc)


@functools.partial(jax.jit, static_argnames=("interpret", "window", "bblock"))
def decode_attend_pallas_spec_paged(q: jnp.ndarray, pool_k: jnp.ndarray,
                                    pool_v: jnp.ndarray, lengths: jnp.ndarray,
                                    layer: jnp.ndarray, table: jnp.ndarray,
                                    interpret: bool = False,
                                    pool_ks: jnp.ndarray = None,
                                    pool_vs: jnp.ndarray = None,
                                    window: int = 0,
                                    bblock: int = 1) -> jnp.ndarray:
    """Paged speculative-verify attention: R query rows per slot, one pass,
    double-buffered page streaming (see _paged_db_body).

    q: [B, R, Hq, D]; row r masks to columns < lengths + 1 + r. The caller
    has already written all R rows (their pages allocated up front — the
    engine's ensure-pages step covers lengths + R). One page stream serves
    all R queries — and with
    ``bblock`` > 1, all BB slots of a block. No row is dead here: a slot of
    length 0 still verifies R drafts over its first R rows.
    """
    B, R, Hq, D = q.shape
    lengths = lengths.astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    out = _paged_flash_db(
        q.reshape(B, R * Hq, D), pool_k, pool_v, lengths, layer_arr,
        table.astype(jnp.int32), bb=_resolve_bb(bblock, B), R=R, spec=True,
        window=window, interpret=interpret, pool_ks=pool_ks, pool_vs=pool_vs)
    return out.reshape(B, R, Hq, D)


def _write_block(b, rows, tab, *, MP: int, ps: int, ROWS: int):
    """(physical page, row block) grid step b of a paged row write opens.
    tab: the table FLATTENED row-major (SMEM; see _paged_flash_db)."""
    r = jnp.clip(rows[b], 0, MP * ps - 1)
    return tab[b * MP + r // ps], (r % ps) // ROWS


@functools.partial(jax.jit, static_argnames=("interpret",))
def cache_write_row_paged(pool: jnp.ndarray, new: jnp.ndarray,
                          rows: jnp.ndarray, table: jnp.ndarray,
                          layer: jnp.ndarray,
                          interpret: bool = False) -> jnp.ndarray:
    """Write one new K (or V) row per slot into the PAGED pool, IN PLACE.

    pool: [L, P, Hkv, page, D]; new: [B, Hkv, D]; rows: [B] logical row per
    slot; table: [B, max_pages] int32; layer: scalar. Rows outside
    [0, max_pages*page) DROP (surplus-write invariant). Returns the updated
    pool — same buffer.

    Why a kernel for a 2 KB-per-slot write: the functional alternatives all
    copy. A ``.at[...].set(...)`` lowers to scatter, and XLA's copy-insertion
    around scatters in while-loop carries materializes full-cache copies
    (measured on the slot-contiguous cache this pool replaced: 7 copies of
    3.6 GB per decode step, 330 ms/token). ``input_output_aliases`` lowers to
    a custom call with output-operand aliasing, which buffer assignment MUST
    honor — the buffer is never copied; each grid step DMAs one 8-row block.
    This is the TPU equivalent of vLLM's in-place ``cache_kernel`` CUDA
    writes (reference SURVEY.md §2.2 row 1).

    ONE ROW A SLOT is the contract: every grid step opens a block of its
    own. Two steps on the same 8-row block would lose the first one's row
    (Pallas neither refetches the input block nor writes the output block
    back between consecutive steps that revisit it — on the chip one row in
    eight of a chunk landed, PR 25), so several rows of ONE slot go through
    kv_pool.write_chunk_paged_layer, a page window at a time, as the mixed
    program's chunk does.
    """
    L, P, Hkv, ps, D = pool.shape
    rows = rows.astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    table = table.astype(jnp.int32)
    MP = table.shape[1]
    S_v = MP * ps
    ROWS = 8 if ps % 8 == 0 else ps

    block = functools.partial(_write_block, MP=MP, ps=ps, ROWS=ROWS)

    def new_map(b, lens, lay, tab):
        return (b, 0, 0)

    def blk_map(b, lens, lay, tab):
        pg, rb = block(b, lens, tab)
        return (lay[0], pg, 0, rb, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(new.shape[0],),
        in_specs=[
            pl.BlockSpec((1, Hkv, D), new_map),
            pl.BlockSpec((1, 1, Hkv, ROWS, D), blk_map),
        ],
        out_specs=pl.BlockSpec((1, 1, Hkv, ROWS, D), blk_map),
    )

    def kernel(lengths_ref, layer_ref, table_ref, new_ref, cin_ref,
               cout_ref):
        b = pl.program_id(0)
        tgt = lengths_ref[b]
        in_window = (tgt >= 0) & (tgt < S_v)
        # ROWS divides page_size, so the in-block row is tgt % ROWS
        r = jnp.where(in_window, jnp.clip(tgt, 0, S_v - 1) % ROWS, -1)
        row = jax.lax.broadcasted_iota(jnp.int32, (Hkv, ROWS, D), 1)
        cout_ref[0, 0] = jnp.where(row == r, new_ref[0][:, None, :],
                                   cin_ref[0, 0])

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # the pool: operand 4, after the three scalars and ``new``
        input_output_aliases={4: 0},
        interpret=interpret,
    )(rows, layer_arr, table.reshape(-1), new, pool)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cache_write_row_quant_paged(pool: jnp.ndarray, scales: jnp.ndarray,
                                new: jnp.ndarray, rows: jnp.ndarray,
                                table: jnp.ndarray, layer: jnp.ndarray,
                                interpret: bool = False):
    """Quantizing paged row write: int8 pool + per-row scales, both aliased.

    pool: [L, P, Hkv, page, D] int8; scales: [L, P, Hkv, lanes >= page] f32
    (kv_pool.scale_lanes); new: [B, Hkv, D] float. Same quantizer as the
    XLA writers (kv_pool.quantize_rows) so prefilled and decoded rows are
    interchangeable. One row a slot, as cache_write_row_paged. Returns
    (pool, scales) — same buffers.
    """
    L, P, Hkv, ps, D = pool.shape
    lanes = scales.shape[3]     # >= ps: lane-padded (kv_pool.scale_lanes)
    rows = rows.astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    table = table.astype(jnp.int32)
    MP = table.shape[1]
    S_v = MP * ps
    ROWS = 32 if ps % 32 == 0 else ps

    block = functools.partial(_write_block, MP=MP, ps=ps, ROWS=ROWS)

    def new_map(b, lens, lay, tab):
        return (b, 0, 0)

    def blk_map(b, lens, lay, tab):
        pg, rb = block(b, lens, tab)
        return (lay[0], pg, 0, rb, 0)

    def scale_map(b, lens, lay, tab):
        return (lay[0], block(b, lens, tab)[0], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(new.shape[0],),
        in_specs=[
            pl.BlockSpec((1, Hkv, D), new_map),
            pl.BlockSpec((1, 1, Hkv, ROWS, D), blk_map),
            pl.BlockSpec((1, 1, Hkv, lanes), scale_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Hkv, ROWS, D), blk_map),
            pl.BlockSpec((1, 1, Hkv, lanes), scale_map),
        ],
    )

    def kernel(lengths_ref, layer_ref, table_ref, new_ref, cin_ref, sin_ref,
               cout_ref, sout_ref):
        b = pl.program_id(0)
        tgt = lengths_ref[b]
        in_window = (tgt >= 0) & (tgt < S_v)
        r = jnp.where(in_window, jnp.clip(tgt, 0, S_v - 1) % ROWS, -1)
        # the one shared quantizer (plain jnp ops, valid inside Pallas):
        # XLA-prefilled and Pallas-decoded rows MUST quantize identically
        q8, sc = quantize_rows(new_ref[0])                    # [Hkv,D],[Hkv]
        row = jax.lax.broadcasted_iota(jnp.int32, (Hkv, ROWS, D), 1)
        cout_ref[0, 0] = jnp.where(row == r, q8[:, None, :], cin_ref[0, 0])
        # scale block spans one whole page: target column = tgt % page
        rs = jax.lax.broadcasted_iota(jnp.int32, (Hkv, lanes), 1)
        tgt_col = jnp.where(in_window, jnp.clip(tgt, 0, S_v - 1) % ps, -1)
        sout_ref[0, 0] = jnp.where(rs == tgt_col, sc[:, None], sin_ref[0, 0])

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            jax.ShapeDtypeStruct(scales.shape, scales.dtype),
        ],
        # pool and scales: operands 4, 5 (after three scalars and ``new``)
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(rows, layer_arr, table.reshape(-1), new, pool, scales)


def supported() -> bool:
    """True when the kernels compile natively (the process's default backend
    is a TPU); elsewhere they run in interpret mode. The ONE place the
    attention layer asks — ``ops/attention.resolve_impl('auto')`` and every
    ``interpret=`` argument read it — so a deviceless compile for a described
    chip (tests/test_tpu_compile.py, a scratch script) steers a single
    function instead of the JAX backend."""
    return jax.default_backend() == "tpu"
