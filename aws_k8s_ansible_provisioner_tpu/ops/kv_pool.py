"""The paged KV pool as arrays: allocation, int8 row quantization, the XLA
writers and gathers. The ONE layout the kernels (ops/pallas_attention.py),
the attend callbacks (ops/attention.py) and the serving engine share; the
host side — page allocator, prefix index, host tier — is
serving/paged_kv.py.

- **Page pool**: ``k, v : [L, P, Hkv, page, D]`` (+ per-row scale leaves
  ``ks, vs : [L, P, Hkv, lanes]`` when int8; lanes = page rounded up to the
  128-lane tile, :func:`scale_lanes`) — P physical pages shared by all
  slots, allocated once at startup (XLA static shapes; capacity planning picks
  P, not per-slot reservations).
- **Block tables**: ``[num_slots, max_pages_per_slot]`` int32 of physical
  page ids, passed to each step program as a device array; the Pallas
  kernels read it via scalar prefetch and fetch page
  ``table[slot, logical_chunk]``.
- **A second set of leaves for layers that age differently**: the window
  ("w") layers of a list that also holds full ones keep their K/V in ``wk,
  wv : [n_w, P_w, Hkv, page, D]`` — another page count, another table a
  slot, another host inventory (serving/paged_kv.py) — so a page behind the
  window goes back while the full layers still hold theirs. Every writer,
  gather and kernel here works on ``k`` / ``v``; :func:`view` hands them
  one kind's leaves under those names.

Pages are head-major ``[Hkv, page, D]``, so each kernel page fetch DMAs one
head-contiguous block and issues a single batched MXU matmul over all heads.
page_size must satisfy Mosaic's tiling rules (multiple of 8 for bf16, 32 for
int8; the int8 scale block spans the full page axis, which is always legal).
Raggedness (every slot at a different sequence length) is a ``lengths``
vector and masking, never a dynamic shape.

**Int8** (ServingConfig.kv_dtype="int8"): K/V rows are stored int8 with one
float32 scale per (layer, page, head, row) — the standard
per-token-per-head dynamic scheme (near-lossless for attention; vLLM ships the
same option as ``kv_cache_dtype=int8``). Decode is cache-bandwidth-bound, so
halving the bytes/row both halves the hot-loop HBM traffic and doubles the
rows a chip's HBM can hold; the Pallas kernels dequantize in VMEM by folding
the scales into the flash accumulation, so the f32 cache never exists in HBM.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from aws_k8s_ansible_provisioner_tpu.config import ModelConfig

# Drop sentinel for page-table entries that must never be written (padding
# rows of a batched prefill, out-of-window rows). Must be a LARGE POSITIVE
# id: jnp scatters treat negative indices as wrapped (in-bounds!) — a -1
# would silently write the pool's last page — while indices >= the pool size
# are dropped by mode='drop'.
OOB_PAGE = np.int32(2**31 - 1)


def scale_lanes(page_size: int) -> int:
    """Minor dim of the int8 pool's scale leaves: ``page_size`` rounded up to
    the TPU's 128-lane tile. The paged decode kernel moves one page's scales
    per DMA, and Mosaic refuses a DMA slice whose minor dim is not
    128-aligned (page 64 died there); lanes >= page_size are padding no
    reader indexes. HBM cost on the chip is nil — XLA's tiled layout already
    padded a 64-wide f32 minor dim to 128."""
    return -(-page_size // 128) * 128


# the leaves of each kind of attending layer, under the names every writer,
# gather and kernel knows
FULL_LEAVES = {"k": "k", "v": "v"}
WINDOW_LEAVES = {"k": "wk", "v": "wv"}


def view(pool: dict, leaves: dict) -> dict:
    """One kind's leaves of ``pool`` as a pool of its own."""
    return {name: pool[src] for name, src in leaves.items()}


def with_view(pool: dict, leaves: dict, part: dict) -> dict:
    """``pool`` with one kind's leaves replaced by :func:`view`'s ``part``
    as a callback left it."""
    return {**pool, **{src: part[name] for name, src in leaves.items()}}


def window_inventory(cfg: ModelConfig, num_slots: int, pages_per_slot: int,
                     page_size: int, horizon: int, chunk: int) -> tuple:
    """(pages a slot can hold at most while it decodes, pages of the whole
    inventory) for the WINDOW layers of a list that also holds full ones —
    a page behind the window goes back (Engine._win_cover), so a slot holds
    the window + two decode horizons (the dispatch in flight and the one
    being enqueued) + a page, the one slot that is chunking the chunk on
    top; + the scratch page. Sized for every slot's bound at once: its
    allocation never fails. (0, 0) for any other model."""
    if not cfg.windowed:
        return 0, 0
    a_slot = min(pages_per_slot,
                 -(-(cfg.sliding_window + 2 * horizon) // page_size) + 1)
    return a_slot, num_slots * a_slot + min(
        pages_per_slot, -(-chunk // page_size) + 1) + 1


def init_pool(cfg: ModelConfig, num_pages: int, page_size: int,
              dtype=jnp.bfloat16, quant: bool = False,
              win_pages: int = 0) -> dict:
    """Allocate the physical page pool. Leaves carry a leading [L] axis:
    the layers that ATTEND (all of them, or one a period of a model with a
    layer pattern — its other layers keep per-slot state instead,
    ops/linear_attention.init_state); ``kv_lane_pack`` heads lie side by
    side in one row of ``pool_head_dim``. A model with window layers beside
    full ones gets ``win_pages`` pages of ``wk`` / ``wv`` for them."""
    shape = (cfg.num_attn_layers, num_pages, cfg.pool_kv_heads, page_size,
             cfg.pool_head_dim)
    if cfg.windowed:
        wshape = (cfg.num_window_layers, win_pages) + shape[2:]
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                "wk": jnp.zeros(wshape, dtype),
                "wv": jnp.zeros(wshape, dtype)}
    if quant:
        sshape = shape[:3] + (scale_lanes(page_size),)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "ks": jnp.zeros(sshape, jnp.float32),
            "vs": jnp.zeros(sshape, jnp.float32),
        }
    pool = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if cfg.selects:
        pool["kc"] = jnp.zeros(selector_shape(cfg, num_pages, page_size),
                               jnp.float32)
    return pool


def selector_shape(cfg: ModelConfig, num_pages: int, page_size: int) -> tuple:
    """The selector's cache of a model whose attention selects its pages
    (ops/sparse_attention.py): per physical page and KV head the SUMS of the
    page's keys over runs of ``sparse_kernel_stride`` tokens, float32 — a
    pooled key is the mean of two neighbouring runs. A leaf of the pool, so
    it lives and dies with the page: the allocator never hears of it."""
    return (cfg.num_attn_layers, num_pages, cfg.num_kv_heads,
            page_size // cfg.sparse_kernel_stride, cfg.head_dim)


def selector_bytes(cfg: ModelConfig, num_pages: int, page_size: int) -> int:
    if not cfg.selects:
        return 0
    return 4 * int(np.prod(selector_shape(cfg, num_pages, page_size)))


def pool_bytes(cfg: ModelConfig, num_pages: int, page_size: int,
               dtype=jnp.bfloat16, quant: bool = False,
               win_pages: int = 0) -> int:
    """Bytes of the pool's leaves: ``num_pages`` of the attending layers'
    and, for a model with window layers, ``win_pages`` of theirs."""
    heads = 2 * (cfg.num_attn_layers * num_pages
                 + cfg.num_window_layers * win_pages) * cfg.num_kv_heads
    if quant:
        return heads * (page_size * cfg.head_dim
                        + 4 * scale_lanes(page_size))
    return heads * page_size * cfg.head_dim * jnp.dtype(dtype).itemsize \
        + selector_bytes(cfg, num_pages, page_size)


def quantize_rows(x: jnp.ndarray):
    """Per-row symmetric int8 quantization over the trailing head_dim axis.

    x: [..., D] float → (int8 [..., D], float32 scale [...]) with
    ``x ≈ q * scale``. Round-half-even, the same rule as the in-kernel
    quantization in ops/pallas_attention.cache_write_row_quant_paged, so
    XLA-prefilled rows and Pallas-decoded rows are interchangeable (agreement
    to 1 int8 step; compiled-program fusion may differ by 1 ulp of scale).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.round(xf / scale[..., None]).astype(jnp.int8)
    return q, scale


def dequantize(q: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.float32):
    """Inverse of quantize_rows: q [..., D] int8, scale [...] → float [..., D]."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)



def _write_kv(pool: dict, update, k_val: jnp.ndarray, v_val: jnp.ndarray) -> dict:
    """Apply one ``update(arr, val)`` indexing expression to the k and v
    leaves — quantizing the values first and updating the scale leaves with
    the SAME expression when the pool is int8 (a scale's target is its row's
    target minus the trailing head_dim axis, which quantize_rows drops)."""
    if "ks" in pool:
        k_val, ks = quantize_rows(k_val)
        v_val, vs = quantize_rows(v_val)
        return {"k": update(pool["k"], k_val), "v": update(pool["v"], v_val),
                "ks": update(pool["ks"], ks), "vs": update(pool["vs"], vs)}
    return {"k": update(pool["k"], k_val), "v": update(pool["v"], v_val)}


# ---------------------------------------------------------------------------
# XLA writers (fallback + prefill paths). All take PHYSICAL page ids computed
# from the slot's block table on the host or in-program from a table array.
# ---------------------------------------------------------------------------


def write_prompt_paged(pool_l: dict, pages: jnp.ndarray, k: jnp.ndarray,
                       v: jnp.ndarray, page_size: int) -> dict:
    """Write one prefilled prompt's K/V across its pages (single layer slice).

    pool_l: {'k','v': [P, Hkv, page, D]}; pages: [max_pages] int32 physical
    page ids for the destination slot; k/v: [1, T, Hkv, D] where T is the
    BUCKET width, usually > the true prompt length.

    Token t lands at (pages[t // page_size], t % page_size): one scatter with
    advanced indices on (page, row), the head axis broadcast between them —
    mode='drop' (OOB_PAGE ids
    drop). CONTRACT: padded rows past the true prompt DO write through the
    table, so every entry of ``pages`` must name either a page owned by this
    slot or the engine's scratch page — never another slot's page (the
    engine keeps unallocated table entries at scratch page 0; padding
    garbage then lands in the slot's own partial tail page — rows >= the
    true length, which reads mask and sharing never indexes — or in
    scratch).
    """
    T = k.shape[1]
    tok = jnp.arange(T, dtype=jnp.int32)
    pg = pages[tok // page_size]                       # [T]
    off = tok % page_size
    return _write_kv(
        pool_l,
        lambda arr, val: arr.at[pg, :, off].set(val, mode="drop"),
        k[0], v[0])


def write_prompts_paged(pool_l: dict, tables: jnp.ndarray, k: jnp.ndarray,
                        v: jnp.ndarray, page_size: int) -> dict:
    """Batched prompt write: N prompts into their pages in one scatter.

    pool_l: {'k','v': [P, Hkv, page, D]}; tables: [N, max_pages] int32 (row n
    = destination pages of prompt n; PADDING rows of a power-of-two prefill
    batch carry OOB_PAGE everywhere and drop); k/v: [N, T, Hkv, D]. Same
    contract as :func:`write_prompt_paged`: rows padded past each prompt's
    true length write through the table, so entries past a prompt's own
    pages must be scratch/own pages, never another slot's.
    """
    N, T = k.shape[:2]
    tok = jnp.arange(T, dtype=jnp.int32)
    pg = tables[:, tok // page_size]                   # [N, T]
    off = jnp.broadcast_to(tok % page_size, (N, T))
    return _write_kv(
        pool_l,
        lambda arr, val: arr.at[pg, :, off].set(val, mode="drop"),
        k, v)


def _write_span_by_page(pool: dict, layer, tables: jnp.ndarray, start,
                        k: jnp.ndarray, v: jnp.ndarray,
                        page_size: int, n_valid=None) -> dict:
    """Rows [start, start+T) of N sequences into the FULL pool at ``layer``,
    one WHOLE PAGE per scatter window (read-modify-write).

    tables: [N, max_pages]; k/v: [N, T, Hkv, D]; start: scalar (python int
    or traced). Same index/drop contract as the row-granular per-layer
    writers above (logical pages past the table and OOB_PAGE entries drop;
    rows of a touched page outside the span keep their content).
    ``n_valid`` (traced scalar; the mixed program's chunk, which arrives
    padded to T): only rows [start, start+n_valid) are written, the
    padding behind them keeps the pool's content like any row outside the
    span. None writes all T.

    Why pages and not rows: a row-granular scatter on the head-major pool
    (``arr.at[layer, pg, :, off]``, window [Hkv, D] split by the page axis)
    made the chip's compiler RELAYOUT THE WHOLE POOL to [.., page, Hkv, D]
    and back around every prefill — two full-pool copies and a pool-sized
    temp in each prefill program (7.0 GiB of temp beside a 7.0 GiB pool at
    the default config, deviceless compile for v5e, PR 21). A [Hkv, page, D]
    window is contiguous in the pool's own layout, so this form compiles
    with no pool copy and ~0 temp."""
    ps = page_size
    N, T = k.shape[:2]
    aligned = isinstance(start, int) and start % ps == 0
    # logical pages touched. Never ONE: XLA rewrites a single-window scatter
    # as a dynamic-update-slice whose layout it takes from the transposed
    # update, and relayouts the whole pool again (buckets <= one page did);
    # a second window — its rows all outside the span, rewritten unchanged —
    # keeps it a scatter in the pool's own layout.
    n = max(2, -(-T // ps) + (0 if aligned else 1))
    start = jnp.asarray(start, jnp.int32)
    p0 = start // ps
    delta = p0 * ps - start                      # in (-ps, 0]
    lp = p0 + jnp.arange(n, dtype=jnp.int32)     # [n] logical page ids
    pg = jnp.where((lp < tables.shape[1])[None],
                   tables[:, jnp.clip(lp, 0, tables.shape[1] - 1)],
                   OOB_PAGE)                     # [N, n] physical ids
    # span token held by row r of touched page j; live = inside the span
    tok = (jnp.arange(n, dtype=jnp.int32)[:, None] * ps
           + jnp.arange(ps, dtype=jnp.int32)[None] + delta)     # [n, ps]
    live = (tok >= 0) & (tok < (T if n_valid is None else n_valid))

    def update(arr, val):
        # val [N, T, Hkv, (D)] -> per-page blocks [N, n, Hkv, ps, (D)]
        pad = [(0, 0)] * val.ndim
        pad[1] = (ps, n * ps - T)
        win = jax.lax.dynamic_slice_in_dim(jnp.pad(val, pad), ps + delta,
                                           n * ps, axis=1)
        new = jnp.moveaxis(win.reshape((N, n, ps) + val.shape[2:]), 2, 3)
        mask = live[None, :, None, :]
        if val.ndim == 4:
            mask = mask[..., None]
        else:                                    # scale leaf: lane padding
            lanes = arr.shape[3] - ps
            new = jnp.pad(new, [(0, 0)] * 3 + [(0, lanes)])
            mask = jnp.pad(mask, [(0, 0)] * 3 + [(0, lanes)])
        old = arr.at[layer, pg].get(mode="clip")
        return arr.at[layer, pg].set(
            jnp.where(mask, new.astype(arr.dtype), old), mode="drop")

    return _write_kv(pool, update, k, v)


def write_prompts_paged_layer(pool: dict, layer, tables: jnp.ndarray,
                              k: jnp.ndarray, v: jnp.ndarray,
                              page_size: int) -> dict:
    """FULL-pool ([L, P, ...] leaves) variant of :func:`write_prompts_paged`
    for the scan-CARRY prefill path (round 5): the pool stays in the layer
    scan's carry — XLA's loop-carry aliasing keeps it in place — instead of
    streaming xs→ys, whose re-stack held a second full-size pool buffer in
    the compiled program (the batch-128 paged HBM OOM of the round-5 chip
    run, older code, whose record is no longer in the tree). Same
    index/drop contract as the per-layer form; page-granular windows, see
    :func:`_write_span_by_page`."""
    return _write_span_by_page(pool, layer, tables, 0, k, v, page_size)


def write_chunk_paged_layer(pool: dict, layer, pages: jnp.ndarray,
                            start, k: jnp.ndarray, v: jnp.ndarray,
                            page_size: int, n_valid=None) -> dict:
    """FULL-pool variant of :func:`write_chunk_paged` (carry prefill path —
    see write_prompts_paged_layer). k/v: [1, C, Hkv, D]; ``start`` may be a
    python int (page-aligned starts then touch one page fewer); ``n_valid``
    as in :func:`_write_span_by_page` (the chunk of a mixed step)."""
    return _write_span_by_page(pool, layer, pages[None], start, k, v,
                               page_size, n_valid)


def write_chunk_paged(pool_l: dict, pages: jnp.ndarray, start: jnp.ndarray,
                      k: jnp.ndarray, v: jnp.ndarray, page_size: int) -> dict:
    """Write one prefill CHUNK's rows [start, start+C) across pages.

    pool_l: {'k','v': [P, Hkv, page, D]}; pages: [max_pages] int32 for the
    slot; start: scalar row offset; k/v: [1, C, Hkv, D]. Rows past max_pages *
    page_size drop (mode='drop' via clamped gather producing OOB_PAGE).
    """
    C = k.shape[1]
    rows = start + jnp.arange(C, dtype=jnp.int32)      # [C]
    idx = rows // page_size
    valid = idx < pages.shape[0]
    pg = jnp.where(valid, pages[jnp.clip(idx, 0, pages.shape[0] - 1)],
                   OOB_PAGE)
    off = rows % page_size
    return _write_kv(
        pool_l,
        lambda arr, val: arr.at[pg, :, off].set(val, mode="drop"),
        k[0], v[0])


def write_token_layer_paged(pool: dict, layer: jnp.ndarray,
                            lengths: jnp.ndarray, table: jnp.ndarray,
                            k: jnp.ndarray, v: jnp.ndarray,
                            page_size: int) -> dict:
    """Scatter one new token per slot into the FULL pool at a given layer
    (XLA fallback for the Pallas paged row-write kernel).

    pool: {'k','v': [L, P, Hkv, page, D]}; layer: scalar; lengths: [B] row
    index per slot; table: [B, max_pages] int32; k/v: [B, 1, Hkv, D]. Rows
    outside [0, max_pages*page_size) drop — the surplus-write invariant.
    """
    B = k.shape[0]
    idx = lengths // page_size
    valid = (lengths >= 0) & (idx < table.shape[1])
    pg = jnp.where(valid,
                   table[jnp.arange(B), jnp.clip(idx, 0, table.shape[1] - 1)],
                   OOB_PAGE)
    off = jnp.where(valid, lengths % page_size, 0)
    return _write_kv(
        pool,
        lambda arr, val: arr.at[layer, pg, :, off].set(val, mode="drop"),
        k[:, 0], v[:, 0])


def gather_slot(pool_l: dict, pages: jnp.ndarray, page_size: int,
                name: str) -> jnp.ndarray:
    """Materialize one slot's logical [Hkv, S_v, D] view from its pages
    (S_v = len(pages) * page_size). Prefill-only helper (chunk attention
    reads the cached prefix); the decode kernels never gather.
    """
    arr = pool_l[name][pages]                    # [n, Hkv, page, (D)]
    if arr.ndim == 3:
        arr = arr[..., :page_size]               # drop scale lane padding
    arr = jnp.moveaxis(arr, 1, 0)                # [Hkv, n, page, (D)]
    return arr.reshape((arr.shape[0], -1) + arr.shape[3:])


def gather_layer_dense(pool: dict, layer, table: jnp.ndarray) -> dict:
    """One layer's logical dense view from the pool (XLA-fallback decode):
    {name: [B, Hkv, S_v, (D)]}. Test/CPU path only — a full gather per step
    is exactly what the Pallas paged kernels avoid."""
    out = {}
    ps = pool["k"].shape[3]
    for name, arr in pool.items():
        al = jax.lax.dynamic_index_in_dim(arr, layer, 0, keepdims=False)
        g = al[table]                            # [B, n, Hkv, page, (D)]
        if g.ndim == 4:
            g = g[..., :ps]                      # drop scale lane padding
        g = jnp.moveaxis(g, 2, 1)                # [B, Hkv, n, page, (D)]
        out[name] = g.reshape(g.shape[:2] + (-1,) + g.shape[4:])
    return out


def gather_dense(pool: dict, table: jnp.ndarray, page_size: int) -> dict:
    """Whole logical [L, B, Hkv, S_v, (D)] cache from the pool — a stack of
    :func:`gather_layer_dense` slices, so the pool layout has exactly one
    decoding (tests compare paged results against dense references through
    this)."""
    L = pool["k"].shape[0]
    layers = [gather_layer_dense(pool, jnp.int32(l), table) for l in range(L)]
    return {name: jnp.stack([g[name] for g in layers]) for name in pool}


# ---------------------------------------------------------------------------
# Host tier (tier-2 KV): spill/restore of whole pages across PCIe
# ---------------------------------------------------------------------------


def gather_pages(pool: dict, pages: Sequence[int]) -> dict:
    """Enqueue a device-side gather of whole physical pages for spilling.

    pool: FULL-pool leaves ``[L, P, ...]``; pages: global physical ids.
    Returns ``{name: [L, k, Hkv, page, (D)]}`` — eager jnp ops only, so this
    just enqueues device work without blocking the dispatch thread (R8-safe);
    the actual PCIe copy is started with ``copy_to_host_async`` and settled
    lazily by :meth:`HostTier.flush_to_host` at the next sanctioned block
    point. The gather is enqueued BEFORE any program that overwrites the
    reclaimed pages, so XLA's data-dependency ordering guarantees it reads
    the pre-reclaim content.
    """
    idx = jnp.asarray(list(pages), jnp.int32)
    return {name: jnp.take(arr, idx, axis=1) for name, arr in pool.items()}


@functools.partial(jax.jit, donate_argnums=(0,))
def _restore_scatter(pool: dict, pages: jnp.ndarray, data: dict) -> dict:
    return {name: arr.at[:, pages].set(data[name], mode="drop")
            for name, arr in pool.items()}


def restore_pages(pool: dict, pages: Sequence[int], data: dict) -> dict:
    """Scatter host-tier page payloads back into freshly allocated pages.

    pool: FULL-pool leaves (donated — the scatter is in place, no second
    pool-sized buffer); pages: global physical ids; data: ``{name:
    [L, k, Hkv, page, (D)]}`` stacked page payloads in the same per-page
    layout ``write_prompts_paged_layer`` produces. The page axis is padded to
    the next power of two with ``OOB_PAGE`` ids (dropped by the scatter) so
    restore bursts of any size hit a log-bounded set of compiled programs.
    """
    k = len(pages)
    width = 1
    while width < k:
        width *= 2
    pg = np.full(width, OOB_PAGE, np.int32)
    pg[:k] = list(pages)
    padded = {}
    for name, arr in data.items():
        if arr.shape[1] != width:
            pad = [(0, 0)] * arr.ndim
            pad[1] = (0, width - arr.shape[1])
            arr = jnp.pad(jnp.asarray(arr), pad)
        padded[name] = jnp.asarray(arr)
    return _restore_scatter(pool, jnp.asarray(pg), padded)
