"""Serving attention ops: cached decode + prefill-with-cache-write.

XLA-fallback implementations (portable to CPU for tests); the Pallas TPU kernel in
``ops/pallas_attention.py`` is the performance path behind the same interface.
These are the TPU-native equivalents of the paged-attention CUDA kernels inside
the reference's external vLLM engine (SURVEY.md §3.3: "the true hot loop ... lives
entirely inside the external vLLM container").

Design notes (TPU/HBM-first):
- Decode reads the cache **in place**: the GQA einsum groups query heads over
  shared KV heads (``bkgd,bskd->bkgs``) so no ``repeat_kv`` copy and no page
  gather materializes in HBM — the whole step stays at cache-bandwidth cost.
- Raggedness is a ``lengths`` mask, never a dynamic shape.
- Softmax in float32 on the VPU; matmuls in bf16 on the MXU.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from aws_k8s_ansible_provisioner_tpu.models import parts
from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp


def decode_attend(q: jnp.ndarray, cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                  lengths: jnp.ndarray, window: int = 0,
                  allowed: jnp.ndarray = None) -> jnp.ndarray:
    """Cached decode attention, R query rows per slot (plain decode: R = 1;
    speculative verify: R > 1).

    q: [B, R, Hq, D]; cache_k/v: [B, Hkv, S, D] head-major (already containing
    the new tokens' k/v — the caller writes first); lengths: [B] = number of
    valid rows per slot seen by query row 0 (including its own token); query
    row r sees columns < lengths + r. ``window`` > 0 = sliding-window
    attention (only the last ``window`` rows are live). ``allowed``
    [B, Hkv, S] bool (ops/sparse_attention.py): the columns each KV head's
    group may read at all — a slot's selected blocks. Returns
    [B, R, Hq, D].
    """
    B, R, Hq, D = q.shape
    Hkv, S = cache_k.shape[1], cache_k.shape[2]
    G = Hq // Hkv
    # [B, Hkv, R*G, D]: the R rows ride the group axis of the GQA einsum
    qg = q.reshape(B, R, Hkv, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, R * G, D).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum("bkgd,bksd->bkgs", qg, cache_k.astype(jnp.float32)) * scale
    limit = lengths[:, None] + jnp.arange(R)[None, :]          # [B, R]
    cols = jnp.arange(S)[None, None, :]
    valid = cols < limit[:, :, None]                           # [B, R, S]
    if window > 0:
        valid = valid & (cols >= limit[:, :, None] - window)
    valid = jnp.repeat(valid, G, axis=1)                       # [B, R*G, S]
    valid = valid[:, None, :, :]
    if allowed is not None:
        valid = valid & allowed[:, :, None, :]
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bkgs,bksd->bkgd", probs, cache_v.astype(jnp.float32))
    ctx = ctx.reshape(B, Hkv, R, G, D).transpose(0, 2, 1, 3, 4)
    return ctx.reshape(B, R, Hq, D).astype(q.dtype)


def resolve_impl(impl: str = "auto") -> str:
    """Resolve the decode-attention backend: 'pallas' on TPU, 'xla' elsewhere.

    'auto' picks the Pallas flash kernel exactly when it compiles natively
    (TPU); CPU tests exercise it explicitly via interpret mode. The
    TPU_SERVE_ATTENTION_IMPL env var overrides for A/B perf comparison.
    """
    import os

    impl = os.environ.get("TPU_SERVE_ATTENTION_IMPL", impl)
    if impl == "auto":
        from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention

        return "pallas" if pallas_attention.supported() else "xla"
    return impl


def chunk_attend(q: jnp.ndarray, ck: jnp.ndarray, cv: jnp.ndarray,
                 start: jnp.ndarray, window: int = 0) -> jnp.ndarray:
    """Attention for one prefill chunk against the slot's cache prefix.

    q: [1, C, Hq, D] (chunk queries, already rotary-encoded at positions
    start..start+C); ck/cv: [Hkv, S, D] (the slot's cache, containing rows
    [0, start+C) — the prefix from earlier chunks plus this chunk, written by
    the caller BEFORE attending); start: scalar. Causal mask: query row i may
    see cache cols <= start + i. Same GQA in-place read as decode_attend —
    no repeat_kv materialization.
    """
    _, C, Hq, D = q.shape
    Hkv, S = ck.shape[0], ck.shape[1]
    G = Hq // Hkv
    qg = q[0].reshape(C, Hkv, G, D).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum("ckgd,ksd->ckgs", qg, ck.astype(jnp.float32)) * scale
    cols = jnp.arange(S)[None, :]                     # [1, S]
    rows = start + jnp.arange(C)[:, None]             # [C, 1]
    mask = cols <= rows                               # [C, S]
    if window > 0:
        mask = mask & (cols > rows - window)
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("ckgs,ksd->ckgd", probs, cv.astype(jnp.float32))
    return ctx.reshape(C, Hq, D)[None].astype(q.dtype)


# ---------------------------------------------------------------------------
# Attend callbacks over the paged pool (ops/kv_pool.py pool + block
# tables): physical addressing goes through the per-slot page table. They
# compose with tp meshes (heads sharded over the pool) and dp meshes (page
# axis partitioned per dp group; tables carry GLOBAL ids the shard_map bodies
# rebase — parallel/sharding.pool_pspecs).
# ---------------------------------------------------------------------------


def lane_packed(cfg, attend):
    """``attend`` over a pool that holds ``cfg.kv_lane_pack`` K/V heads side
    by side in one row (config.py says why): K and V rows are folded so —
    neighbouring heads are neighbours in memory, a reshape —, every query
    head is widened to the row with ZEROS in the other heads' lanes (its
    product with a packed K row is its product with its own head), scaled by
    sqrt(n) because the kernels divide by the square root of the width they
    see, and takes its own head's lanes of the output. Everything under it —
    the pool, the writers, the paged kernels, the XLA fallbacks — sees ``Hkv
    / n`` heads of ``n x head_dim``. No pack: ``attend`` as it is."""
    n = cfg.kv_lane_pack
    if n == 1:
        return attend
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    # query head i reads KV head i // groups: lanes [own * D, own * D + D)
    own = (jnp.arange(Hq) // (Hq // Hkv)) % n

    def packed(q, k, v, cache_l):
        lead = q.shape[:-2]
        lanes = jax.nn.one_hot(own, n, dtype=q.dtype)[:, :, None]  # [Hq,n,1]
        qs = (q.astype(jnp.float32) * (n ** 0.5)).astype(q.dtype)
        ctx, cache_l = attend(
            (qs[..., None, :] * lanes).reshape(lead + (Hq, n * D)),
            k.reshape(lead + (Hkv // n, n * D)),
            v.reshape(lead + (Hkv // n, n * D)), cache_l)
        return (ctx.reshape(lead + (Hq, n, D)) * lanes.astype(ctx.dtype)
                ).sum(-2), cache_l

    return packed


def attend_by_kind(make, table, wtable, window: int):
    """The attend callback of a model whose list holds window ("w") layers
    beside full ("g") ones: ``make(table, window, of_window_kind)`` builds
    one callback a kind — the full layers' over ``table`` with no window,
    the window layers' over ``wtable`` (their own page inventory's table,
    same width) with the static ``window`` — and each is handed its kind's
    leaves of the pool under the names it knows (kv_pool.view). Returned as
    the full
    layers' callback with the other as its ``.window``
    (models/layers._list_forward_carry picks by kind)."""

    def on(leaves, inner):
        def attend(q, k, v, cache_l):
            pool, layer = cache_l
            ctx, (part, _) = inner(q, k, v, (kvp.view(pool, leaves), layer))
            return ctx, (kvp.with_view(pool, leaves, part), layer)

        return attend

    attend = on(kvp.FULL_LEAVES, make(table, 0, False))
    attend.window = on(kvp.WINDOW_LEAVES, make(wtable, window, True))
    return attend


def _length_order(lengths: jnp.ndarray, table: jnp.ndarray, dp: int,
                  bblock: int) -> tuple:
    """The decode kernel's rows in ONE stable ascending order of length:
    ``(order, inverse, limits, table)`` — the permutation, its inverse, and
    ``lengths + 1`` and ``table`` already in that order — or ``()`` where the
    order cannot matter: blocks of one row, or one block (static facts).

    Why: a grid step of decode_attend_pallas_paged serves ``bblock`` rows
    and walks the pages of its LONGEST one, every shorter row running a
    masked flash update to the end (since PR 45 without a copy: a row past
    its own pages fetches nothing; pallas_attention._paged_db_body). In slot
    order a block's rows are
    strangers — a slot's context is wherever its request stands — and three
    tenths of the pages walked lie beyond some row's end (PERF.md, PR 33).
    Cut from the sorted order a block's rows are neighbours. A row's flash
    state does not depend on its block-mates (a masked page adds
    exp(-1e30 - m) = 0 and scales by exp(0) = 1), so the context is BITWISE
    the slot-order call's. Under a ``dp`` mesh each shard orders its own
    rows (indices local to the shard). Idle slots (short rows) sort to the
    front and fill whole blocks of one page."""
    from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
        _resolve_bb)

    rows = lengths.shape[0] // dp
    bb = _resolve_bb(bblock, rows)
    if bb == 1 or rows == bb:
        return ()
    lens = lengths.reshape(dp, rows)
    order = jnp.argsort(lens, axis=1, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order, axis=1).astype(jnp.int32)
    limits = jnp.take_along_axis(lens, order, axis=1) + 1
    tab = jnp.take_along_axis(table.reshape(dp, rows, -1),
                              order[:, :, None], axis=1)
    return (order.reshape(-1), inverse.reshape(-1), limits.reshape(-1),
            tab.reshape(table.shape))


def make_decode_attend_carry_paged(lengths: jnp.ndarray, table: jnp.ndarray,
                                   impl: str = "auto", mesh=None,
                                   window: int = 0, bblock: int = 1,
                                   of_window_kind: bool = False):
    """Carry-path decode attend over the PAGED pool: cache_l is
    ``(pool, layer_idx)``; ``table`` [B, max_pages] int32 maps each slot's
    logical pages to physical pool pages. The engine guarantees every row in
    [0, lengths[b] + 1) — and the row being written — lives in an allocated
    page (Engine._ensure_pages).

    With a ``mesh``, the pool shards its KV-HEAD axis over ``tp``
    (parallel/sharding.pool_pspecs) and shard_map runs the paged kernels on
    each chip's head slice of every page — the block table, lengths, and
    allocator are head-independent and shared verbatim. The tp flagship
    config (Qwen3-8B over v5e-8 ICI) thus keeps on-demand paging; under a
    dp mesh the table's GLOBAL page ids are rebased per shard.

    The kernel gets its rows IN ORDER OF LENGTH (_length_order, taken here,
    once a substep: the layers share it), so the ``bblock`` rows of a grid
    step walk the pages of neighbours; the rows are WRITTEN in slot order
    (one grid step a slot either way) and the context comes back in slot
    order."""
    resolved = resolve_impl(impl)

    dp = mesh.shape.get("dp", 1) if mesh is not None else 1
    # taken outside the layers' ``attend`` (whose scope models/layers.py
    # opens), so it names itself
    with jax.named_scope(parts.ATTN_CORE):
        by_len = _length_order(lengths, table, dp, bblock) \
            if resolved == "pallas" else ()

    def _write_attend_paged(q, pool, knew, vnew, lens, tab, layer, *by_len):
        from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention

        interpret = not pallas_attention.supported()
        if dp > 1:
            # The table carries GLOBAL page ids; this shard's pool slice is
            # its dp group's partition — rebase to local ids. OOB_PAGE
            # (INT32_MAX) stays far out of range after the subtraction, so
            # padding writes still drop.
            rebase = jax.lax.axis_index("dp").astype(jnp.int32) \
                * pool["k"].shape[1]
            tab = tab - rebase
            by_len = by_len and by_len[:3] + (by_len[3] - rebase,)
        ck, cv = pool["k"], pool["v"]
        if "ks" in pool:
            ck, ks = pallas_attention.cache_write_row_quant_paged(
                ck, pool["ks"], knew, lens, tab, layer, interpret=interpret)
            cv, vs = pallas_attention.cache_write_row_quant_paged(
                cv, pool["vs"], vnew, lens, tab, layer, interpret=interpret)
            pool = {"k": ck, "v": cv, "ks": ks, "vs": vs}
            scale_kw = dict(pool_ks=ks, pool_vs=vs)
        else:
            ck = pallas_attention.cache_write_row_paged(
                ck, knew, lens, tab, layer, interpret=interpret)
            cv = pallas_attention.cache_write_row_paged(
                cv, vnew, lens, tab, layer, interpret=interpret)
            pool = {"k": ck, "v": cv}
            scale_kw = {}
        read = functools.partial(
            pallas_attention.decode_attend_pallas_paged_window
            if of_window_kind else
            pallas_attention.decode_attend_pallas_paged, interpret=interpret,
            window=window, bblock=bblock, **scale_kw)
        if not by_len:
            return read(q, ck, cv, lens + 1, layer, tab), pool
        order, inverse, limits, sorted_tab = by_len
        # rows gathered through a 2-D [B, Hq*D] view: gathered as [B, Hq, D]
        # the TPU compiler gives q a head-major layout and, to feed it,
        # copies the whole transposed wq stack every dispatch (604 MB at
        # the 8B; deviceless compile, PR 33)
        rows = q.shape[0]
        ctx = read(q.reshape(rows, -1)[order].reshape(q.shape), ck, cv,
                   limits, layer, sorted_tab)
        return ctx.reshape(rows, -1)[inverse].reshape(ctx.shape), pool

    def attend(q, k, v, cache_l) -> Tuple[jnp.ndarray, tuple]:
        pool, layer = cache_l
        ps = pool["k"].shape[3]
        if resolved == "pallas":
            knew, vnew = k[:, 0], v[:, 0]
            if mesh is not None:
                from jax.sharding import PartitionSpec as P

                from aws_k8s_ansible_provisioner_tpu.parallel.sharding import (
                    pool_pspecs)

                pool_spec = pool_pspecs(quant="ks" in pool)
                fn = jax.shard_map(
                    _write_attend_paged, mesh=mesh,
                    in_specs=(P("dp", None, "tp", None),  # q [B,1,Hq,D]
                              pool_spec,                  # pool leaf dict
                              P("dp", "tp", None),        # knew [B,Hkv,D]
                              P("dp", "tp", None),        # vnew
                              P("dp"),                    # lengths [B]
                              P("dp", None),              # table (slot rows)
                              P())                        # layer scalar
                    # the order in length: each dp shard's own rows (tp
                    # shards heads and shares it)
                    + (P("dp"), P("dp"), P("dp"), P("dp", None))[:len(by_len)],
                    out_specs=(P("dp", None, "tp", None), pool_spec),
                    check_vma=False,
                )
            else:
                fn = _write_attend_paged
            ctx, pool = fn(q, pool, knew, vnew, lengths, table, layer,
                           *by_len)
            return ctx, (pool, layer)
        pool = kvp.write_token_layer_paged(pool, layer, lengths, table, k, v,
                                           ps)
        dense = kvp.gather_layer_dense(pool, layer, table)
        ck, cv = dense["k"], dense["v"]
        if "ks" in dense:
            ck = kvp.dequantize(ck, dense["ks"], dtype=q.dtype)
            cv = kvp.dequantize(cv, dense["vs"], dtype=q.dtype)
        ctx = decode_attend(q, ck, cv, lengths + 1, window=window)
        return ctx, (pool, layer)

    return attend


def make_spec_attend_carry_paged(lengths: jnp.ndarray, table: jnp.ndarray,
                                 impl: str = "auto", mesh=None,
                                 window: int = 0, bblock: int = 1):
    """Paged speculative verify: R rows written across pages, one flash pass
    answers all R queries (pages covering lengths + R pre-allocated by the
    engine). With a ``mesh``, the pool's head axis shards over ``tp`` and the
    block table/lengths are shard-invariant — same contract as
    make_decode_attend_carry_paged."""
    resolved = resolve_impl(impl)

    spec_dp = mesh.shape.get("dp", 1) if mesh is not None else 1

    def _write_attend_spec_paged(q, pool, k, v, lens, tab, layer):
        from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention

        interpret = not pallas_attention.supported()
        if spec_dp > 1:
            # global→local page-id rebase, same as _write_attend_paged
            tab = tab - jax.lax.axis_index("dp").astype(jnp.int32) \
                * pool["k"].shape[1]
        R = q.shape[1]
        ck, cv = pool["k"], pool["v"]
        if "ks" in pool:
            ks, vs = pool["ks"], pool["vs"]
            for r in range(R):
                ck, ks = pallas_attention.cache_write_row_quant_paged(
                    ck, ks, k[:, r], lens + r, tab, layer,
                    interpret=interpret)
                cv, vs = pallas_attention.cache_write_row_quant_paged(
                    cv, vs, v[:, r], lens + r, tab, layer,
                    interpret=interpret)
            pool = {"k": ck, "v": cv, "ks": ks, "vs": vs}
            scale_kw = dict(pool_ks=ks, pool_vs=vs)
        else:
            for r in range(R):
                ck = pallas_attention.cache_write_row_paged(
                    ck, k[:, r], lens + r, tab, layer,
                    interpret=interpret)
                cv = pallas_attention.cache_write_row_paged(
                    cv, v[:, r], lens + r, tab, layer,
                    interpret=interpret)
            pool = {"k": ck, "v": cv}
            scale_kw = {}
        ctx = pallas_attention.decode_attend_pallas_spec_paged(
            q, ck, cv, lens, layer, tab, interpret=interpret,
            window=window, bblock=bblock, **scale_kw)
        return ctx, pool

    def attend(q, k, v, cache_l) -> Tuple[jnp.ndarray, tuple]:
        pool, layer = cache_l
        ps = pool["k"].shape[3]
        R = q.shape[1]
        if resolved == "pallas":
            if mesh is not None:
                from jax.sharding import PartitionSpec as P

                from aws_k8s_ansible_provisioner_tpu.parallel.sharding import (
                    pool_pspecs)

                pool_spec = pool_pspecs(quant="ks" in pool)
                fn = jax.shard_map(
                    _write_attend_spec_paged, mesh=mesh,
                    in_specs=(P("dp", None, "tp", None),  # q [B,R,Hq,D]
                              pool_spec,                  # pool leaf dict
                              P("dp", None, "tp", None),  # k [B,R,Hkv,D]
                              P("dp", None, "tp", None),  # v
                              P("dp"),                    # lengths [B]
                              P("dp", None),              # table (slot rows)
                              P()),                       # layer scalar
                    out_specs=(P("dp", None, "tp", None), pool_spec),
                    check_vma=False,
                )
                ctx, pool = fn(q, pool, k, v, lengths, table, layer)
            else:
                ctx, pool = _write_attend_spec_paged(q, pool, k, v, lengths,
                                                     table, layer)
            return ctx, (pool, layer)
        for r in range(R):
            pool = kvp.write_token_layer_paged(pool, layer, lengths + r,
                                               table, k[:, r:r + 1],
                                               v[:, r:r + 1], ps)
        dense = kvp.gather_layer_dense(pool, layer, table)
        ck, cv = dense["k"], dense["v"]
        if "ks" in dense:
            ck = kvp.dequantize(ck, dense["ks"], dtype=q.dtype)
            cv = kvp.dequantize(cv, dense["vs"], dtype=q.dtype)
        ctx = decode_attend(q, ck, cv, lengths + 1, window=window)
        return ctx, (pool, layer)

    return attend


def make_mixed_attend_carry_paged(dec_rows: jnp.ndarray, chunk_start,
                                  chunk_len, row_limits: jnp.ndarray,
                                  table: jnp.ndarray, row_map: jnp.ndarray,
                                  impl: str = "auto", mesh=None,
                                  window: int = 0, bblock: int = 1,
                                  of_window_kind: bool = False):
    """RAGGED mixed-batch attend over the PAGED pool: the packed sequence
    holds B single-token decode rows followed by C prefill-chunk rows of one
    chunking slot, and ONE program serves them all (serving/programs
    .mixed_step — the dispatch that lets the decode pipeline ride across
    prefill admissions instead of draining).

    The caller provides:
    - ``dec_rows`` [B]: the pool row each decode token's K/V lands at (the
      slot's context length; -1 DROPS the write — used to suppress the
      chunking slot's garbage decode row);
    - ``chunk_start``, ``chunk_len`` (scalars): the C chunk rows are rows
      [chunk_start, chunk_start + C) of the chunking slot, of which the
      first ``chunk_len`` carry a token — the padding behind them is not
      written;
    - ``row_limits`` [N]: live columns packed row i attends over (decode:
      context + 1; chunk: p + 1 — plain causality; 0 = a DEAD row — the
      chunk's padding, the chunking slot's decode row — which fetches
      nothing and returns zeros, from the kernel and the fallback alike);
    - ``table`` [B, max_pages], one page run a SLOT, and ``row_map`` [N]:
      the row of ``table`` packed row i reads (decode row b: b; every chunk
      row: the chunking slot's).

    Two needs, two writers: a decode row is one row in each of B page runs
    (the row kernel decode_steps uses, one grid step a slot), the chunk is
    one span of one run (kv_pool.write_chunk_paged_layer, the prefill
    programs' writer, one page window at a time). All writes land before
    any row attends; causality then reduces to the per-row column mask, so
    a chunk row sees exactly its prefix (earlier chunks + this chunk's
    earlier rows) and a decode row sees exactly its own slot —
    byte-identical math to the separate decode_attend/chunk_attend programs
    it replaces. Mesh support mirrors make_decode_attend_carry_paged's tp
    sharding (heads over ``tp``; table and map whole on every shard); the
    engine gates ragged dispatch to mesh None / pure-tp, so no dp rebase
    rides here. ``of_window_kind`` as in make_decode_attend_carry_paged:
    the trace name of the kernel call, nothing else."""
    resolved = resolve_impl(impl)
    B = dec_rows.shape[0]

    def _write_chunk(pool, knew, vnew, start, n_valid, tab, rmap, layer):
        return kvp.write_chunk_paged_layer(
            pool, layer, tab[rmap[B]], start, knew[None, B:], vnew[None, B:],
            pool["k"].shape[3], n_valid=n_valid)

    def _write_attend_mixed(q3, pool, knew, vnew, drows, start, n_valid,
                            limits, tab, rmap, layer):
        from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention

        interpret = not pallas_attention.supported()
        ck, cv = pool["k"], pool["v"]
        if "ks" in pool:
            ck, ks = pallas_attention.cache_write_row_quant_paged(
                ck, pool["ks"], knew[:B], drows, tab, layer,
                interpret=interpret)
            cv, vs = pallas_attention.cache_write_row_quant_paged(
                cv, pool["vs"], vnew[:B], drows, tab, layer,
                interpret=interpret)
            pool = {"k": ck, "v": cv, "ks": ks, "vs": vs}
        else:
            ck = pallas_attention.cache_write_row_paged(
                ck, knew[:B], drows, tab, layer, interpret=interpret)
            cv = pallas_attention.cache_write_row_paged(
                cv, vnew[:B], drows, tab, layer, interpret=interpret)
            pool = {"k": ck, "v": cv}
        pool = _write_chunk(pool, knew, vnew, start, n_valid, tab, rmap,
                            layer)
        ragged = pallas_attention.ragged_attend_pallas_paged_window \
            if of_window_kind else pallas_attention.ragged_attend_pallas_paged
        ctx = ragged(q3, pool["k"], pool["v"], limits, layer, tab, rmap,
                     interpret=interpret, pool_ks=pool.get("ks"),
                     pool_vs=pool.get("vs"), window=window, bblock=bblock)
        return ctx, pool

    def attend(q, k, v, cache_l) -> Tuple[jnp.ndarray, tuple]:
        pool, layer = cache_l
        ps = pool["k"].shape[3]
        if resolved == "pallas":
            # packed layout: batch axis is 1, rows live on the seq axis
            q3, knew, vnew = q[0], k[0], v[0]        # [N, H*, D]
            if mesh is not None:
                from jax.sharding import PartitionSpec as P

                from aws_k8s_ansible_provisioner_tpu.parallel.sharding import (
                    pool_pspecs)

                pool_spec = pool_pspecs(quant="ks" in pool)
                fn = jax.shard_map(
                    _write_attend_mixed, mesh=mesh,
                    in_specs=(P(None, "tp", None),    # q3 [N,Hq,D]
                              pool_spec,              # pool leaf dict
                              P(None, "tp", None),    # knew [N,Hkv,D]
                              P(None, "tp", None),    # vnew
                              P(None),                # dec_rows [B]
                              P(), P(),               # chunk start, len
                              P(None),                # row_limits [N]
                              P(None, None),          # table (slot rows)
                              P(None),                # row_map [N]
                              P()),                   # layer scalar
                    out_specs=(P(None, "tp", None), pool_spec),
                    check_vma=False,
                )
            else:
                fn = _write_attend_mixed
            ctx, pool = fn(q3, pool, knew, vnew, dec_rows, chunk_start,
                           chunk_len, row_limits, table, row_map, layer)
            return ctx[None], (pool, layer)
        pool = kvp.write_token_layer_paged(pool, layer, dec_rows, table,
                                           k[0][:B, None], v[0][:B, None], ps)
        pool = _write_chunk(pool, k[0], v[0], chunk_start, chunk_len, table,
                            row_map, layer)
        dense = kvp.gather_layer_dense(pool, layer, table[row_map])
        ck, cv = dense["k"], dense["v"]
        if "ks" in dense:
            ck = kvp.dequantize(ck, dense["ks"], dtype=q.dtype)
            cv = kvp.dequantize(cv, dense["vs"], dtype=q.dtype)
        ctx = decode_attend(q[0][:, None], ck, cv, row_limits,
                            window=window)[:, 0]
        ctx = jnp.where((row_limits > 0)[:, None, None], ctx, 0)
        return ctx[None], (pool, layer)

    return attend


def make_prefill_attend_paged_carry(pages: jnp.ndarray, seq_len: jnp.ndarray,
                                    window: int = 0):
    """CARRY-path paged single-prompt prefill: the full pool rides the layer
    scan's carry (in place via loop aliasing) instead of xs→ys, whose
    restack buffer OOMed the batch-128 paged program on the real chip
    (round 5; see kv_pool.write_prompts_paged_layer)."""
    from aws_k8s_ansible_provisioner_tpu.models.layers import causal_attend

    def attend(q, k, v, cache_l):
        cache, layer = cache_l
        ps = cache["k"].shape[3]
        ctx = causal_attend(q, k, v, seq_lens=seq_len[None], window=window)
        cache = kvp.write_chunk_paged_layer(cache, layer, pages, 0, k, v,
                                            ps)
        return ctx, (cache, layer)

    return attend


def make_prefill_attend_batch_paged_carry(tables: jnp.ndarray,
                                          seq_lens: jnp.ndarray,
                                          window: int = 0):
    """CARRY-path paged batched prefill (see make_prefill_attend_paged_carry
    for the memory rationale). Padding rows carry all-OOB_PAGE tables."""
    from aws_k8s_ansible_provisioner_tpu.models.layers import causal_attend

    def attend(q, k, v, cache_l):
        cache, layer = cache_l
        ps = cache["k"].shape[3]
        ctx = causal_attend(q, k, v, seq_lens=seq_lens, window=window)
        cache = kvp.write_prompts_paged_layer(cache, layer, tables, k, v, ps)
        return ctx, (cache, layer)

    return attend


def make_chunk_prefill_attend_paged_carry(pages: jnp.ndarray, start,
                                          window: int = 0):
    """CARRY-path paged chunked prefill: write the chunk's rows through the
    full-pool scatter, then attend over the slot's gathered page prefix
    (the gather materializes ONE slot's view per layer — a prefill-only
    cost, exactly as the xs/ys form paid)."""

    def attend(q, k, v, cache_l):
        cache, layer = cache_l
        ps = cache["k"].shape[3]
        cache = kvp.write_chunk_paged_layer(cache, layer, pages, start,
                                            k, v, ps)

        def gl(name):
            sl = jax.lax.dynamic_index_in_dim(cache[name], layer, 0,
                                              keepdims=False)
            return kvp.gather_slot({name: sl}, pages, ps, name)

        ck, cv = gl("k"), gl("v")
        if "ks" in cache:
            ck = kvp.dequantize(ck, gl("ks"), dtype=q.dtype)
            cv = kvp.dequantize(cv, gl("vs"), dtype=q.dtype)
        ctx = chunk_attend(q, ck, cv, start, window=window)
        return ctx, (cache, layer)

    return attend
