"""Paged KV cache foundation tests: allocator semantics + physical-layout
parity of every pool writer and kernel against a plain logical view.

A logical cache ``[L, B, Hkv, S, (D)]`` IS a paged one under an identity block
table, so parity is exact: scatter the logical view's pages into the pool in
a PERMUTED order, run the paged op with the matching table, and the logical
results must agree bit-for-bit (fp32 tolerance for the flash kernels). This
pins the only thing paging adds — physical addressing — independently of the
engine integration (VERDICT r2 missing #2 / next #3: the vLLM-style on-demand
block capability, SURVEY.md §2.2 row 1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ModelConfig
from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp
from aws_k8s_ansible_provisioner_tpu.serving import paged_kv as pkv
from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa
from aws_k8s_ansible_provisioner_tpu.ops.attention import decode_attend

CFG = ModelConfig(name="tiny", vocab_size=64, hidden_size=32, num_layers=2,
                  num_heads=4, num_kv_heads=2, head_dim=16,
                  intermediate_size=64, max_seq_len=256)
PS = 8          # page size
B = 3           # slots
SV = 64         # virtual window per slot (8 logical pages)
PPS = SV // PS


def _identity_layout(quant=False, seed=0, perm_seed=None):
    """Build a dense cache with random content and mirror it into a pool
    under a (optionally permuted) block table. Returns (dense, pool, table)."""
    rng = np.random.default_rng(seed)
    shape = (CFG.num_layers, B, CFG.num_kv_heads, SV, CFG.head_dim)
    if quant:
        dense = {
            n: jnp.asarray(rng.integers(-127, 128, shape, dtype=np.int8))
            for n in ("k", "v")}
        for n in ("ks", "vs"):
            dense[n] = jnp.asarray(rng.standard_normal(shape[:-1]),
                                   jnp.float32)
    else:
        dense = {n: jnp.asarray(rng.standard_normal(shape), jnp.float32)
                 for n in ("k", "v")}
    n_pages = B * PPS + 1                           # +1 scratch
    order = np.arange(1, n_pages)
    if perm_seed is not None:
        np.random.default_rng(perm_seed).shuffle(order)
    table = order.reshape(B, PPS).astype(np.int32)
    pool = {}
    for name, arr in dense.items():
        # dense [L, B, Hkv, SV, (D)] -> logical pages [L, B*PPS, Hkv, PS, (D)]
        L, _, H = arr.shape[:3]
        tail = arr.shape[4:]
        lp = arr.reshape(L, B, H, PPS, PS, *tail)
        # page index of (slot b, logical page p) is b*PPS + p
        lp = jnp.moveaxis(lp, 3, 2).reshape(L, B * PPS, H, PS, *tail)
        buf = jnp.zeros((L, n_pages, H, PS) + tail, arr.dtype)
        pool[name] = buf.at[:, table.reshape(-1)].set(lp)
    return dense, pool, jnp.asarray(table)


def _dense_write(dense, layer, slot, start, k, v):
    """Plain reference writer on the logical view: rows [start, start + T)
    of one slot in one layer take k/v [T, Hkv, D] (int8 views: quantized by
    the pool's own quantizer, scales beside them); rows past the window
    drop."""
    out = {n: np.array(a) for n, a in dense.items()}
    vals = {"k": k, "v": v}
    if "ks" in dense:
        for n in ("k", "v"):
            q, sc = kvp.quantize_rows(jnp.asarray(vals[n]))
            vals[n], vals[n + "s"] = q, sc
    for n, val in vals.items():
        val = np.asarray(val)
        for t in range(val.shape[0]):
            if 0 <= start + t < SV:
                out[n][layer, slot, :, start + t] = val[t]
    return out


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------


def test_alloc_release_roundtrip():
    p = pkv.PagePool(9, PS, first_page=1)
    assert p.free_pages == 8
    got = p.alloc(3)
    assert len(got) == 3 and 0 not in got
    assert p.free_pages == 5 and p.pages_in_use == 3
    p.release_all(got)
    assert p.free_pages == 8


def test_alloc_exhaustion_returns_none():
    p = pkv.PagePool(5, PS, first_page=1)
    assert p.alloc(5) is None
    got = p.alloc(4)
    assert got is not None and p.alloc(1) is None


def test_refcount_sharing():
    p = pkv.PagePool(5, PS, first_page=1)
    [pid] = p.alloc(1)
    p.retain(pid)
    p.release(pid)
    assert p.pages_in_use == 1          # still held by the second ref
    p.release(pid)
    assert p.pages_in_use == 0


def test_prefix_chain_lookup_and_eviction():
    p = pkv.PagePool(7, PS, first_page=1)
    prompt = list(range(20))            # 2 full pages + tail of 4
    pages = p.alloc(3)
    key = None
    for i in range(2):                  # index the full pages
        key = p.index_page(pages[i], key, tuple(prompt[i * PS:(i + 1) * PS]))
    hit, n, _ = p.lookup_prefix(prompt)
    assert hit == pages[:2] and n == 2 * PS
    # a different prompt sharing only page 0 matches one page
    other = prompt[:PS] + [99] * PS
    hit2, n2, _ = p.lookup_prefix(other)
    assert hit2 == pages[:1] and n2 == PS
    # release -> pages become evictable, still hit
    p.release_all(pages)
    assert p.free_pages == 6            # 3 free + 2 evictable + tail freed
    hit3, n3, _ = p.lookup_prefix(prompt)
    assert hit3 == hit and n3 == 2 * PS
    # retaining an evictable page revives it
    for pid in hit3:
        p.retain(pid)
    assert p.pages_in_use == 2
    p.release_all(hit3)
    # exhausting the pool reclaims evictable pages LRU-first and drops index
    got = p.alloc(6)
    assert got is not None
    assert p.lookup_prefix(prompt)[1] == 0


def test_scratch_page_reserved():
    p = pkv.PagePool(4, PS, first_page=1)
    got = p.alloc(3)
    assert 0 not in got and p.alloc(1) is None


# ---------------------------------------------------------------------------
# Writer parity (XLA paths)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quant", [False, True])
def test_write_prompt_parity(quant):
    dense, pool, table = _identity_layout(quant=quant, perm_seed=7)
    T = 19
    k = jax.random.normal(jax.random.PRNGKey(1), (1, T, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, T, 2, 16))
    slot = 1
    d1 = _dense_write(dense, 0, slot, 0, k[0], v[0])
    p1 = kvp.write_prompt_paged({n: a[0] for n, a in pool.items()},
                                table[slot], k, v, PS)
    got = {n: a[None] for n, a in p1.items()}
    gathered = kvp.gather_dense(got, table[None, slot], PS)
    for name in d1:
        np.testing.assert_array_equal(
            np.asarray(gathered[name][0, 0]), d1[name][0, slot],
            err_msg=name)


@pytest.mark.parametrize("quant", [False, True])
def test_write_prompts_batched_parity(quant):
    dense, pool, table = _identity_layout(quant=quant, perm_seed=3)
    N, T = 2, 11
    k = jax.random.normal(jax.random.PRNGKey(3), (N + 1, T, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(4), (N + 1, T, 2, 16))
    # the last row is padding: an all-OOB_PAGE table row, which drops — NOT
    # -1, which jnp scatters would wrap to the pool's last page
    tables = jnp.concatenate([table[jnp.array([2, 0])],
                              jnp.full((1, PPS), kvp.OOB_PAGE, jnp.int32)])
    d1 = _dense_write(dense, 0, 2, 0, k[0], v[0])
    d1 = _dense_write(d1, 0, 0, 0, k[1], v[1])
    p1 = kvp.write_prompts_paged({n: a[0] for n, a in pool.items()},
                                 tables, k, v, PS)
    gathered = kvp.gather_dense({n: a[None] for n, a in p1.items()},
                                table, PS)
    for name in d1:
        np.testing.assert_array_equal(
            np.asarray(gathered[name][0]), d1[name][0], err_msg=name)


@pytest.mark.parametrize("quant", [False, True])
def test_write_chunk_parity(quant):
    dense, pool, table = _identity_layout(quant=quant, perm_seed=5)
    C, start, slot = 12, 10, 2
    k = jax.random.normal(jax.random.PRNGKey(5), (1, C, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(6), (1, C, 2, 16))
    d1 = _dense_write(dense, 0, slot, start, k[0], v[0])
    p1 = kvp.write_chunk_paged({n: a[0] for n, a in pool.items()},
                               table[slot], jnp.int32(start), k, v, PS)
    gathered = kvp.gather_dense({n: a[None] for n, a in p1.items()},
                                table, PS)
    for name in d1:
        np.testing.assert_array_equal(
            np.asarray(gathered[name][0]), d1[name][0], err_msg=name)


@pytest.mark.parametrize("quant", [False, True])
def test_write_token_layer_parity(quant):
    dense, pool, table = _identity_layout(quant=quant, perm_seed=11)
    lengths = jnp.array([5, SV - 1, 23], jnp.int32)
    k = jax.random.normal(jax.random.PRNGKey(7), (B, 1, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(8), (B, 1, 2, 16))
    layer = jnp.int32(1)
    d1 = dense
    for b in range(B):
        d1 = _dense_write(d1, 1, b, int(lengths[b]), k[b], v[b])
    p1 = kvp.write_token_layer_paged(pool, layer, lengths, table, k, v, PS)
    gathered = kvp.gather_dense(p1, table, PS)
    for name in d1:
        np.testing.assert_array_equal(np.asarray(gathered[name]),
                                      d1[name], err_msg=name)


def test_write_token_out_of_range_drops():
    _, pool, table = _identity_layout(perm_seed=2)
    before = {n: np.asarray(a) for n, a in pool.items()}
    k = jnp.ones((B, 1, 2, 16))
    lengths = jnp.array([SV, SV + 5, -1], jnp.int32)   # all out of window
    p1 = kvp.write_token_layer_paged(pool, jnp.int32(0), lengths, table,
                                     k, k, PS)
    for name in before:
        np.testing.assert_array_equal(np.asarray(p1[name]), before[name])


# ---------------------------------------------------------------------------
# Pallas kernel parity (interpret mode) — permuted physical layout
# ---------------------------------------------------------------------------


def _layer_kv(dense, layer):
    """One layer's float K/V of the logical view (int8: dequantized)."""
    k, v = dense["k"][layer], dense["v"][layer]
    if "ks" in dense:
        k = kvp.dequantize(k, dense["ks"][layer])
        v = kvp.dequantize(v, dense["vs"][layer])
    return k, v


def _assert_attend_close(out, ref, quant):
    # the int8 views hold random bytes under random scales: values in the
    # hundreds, so the tolerance is relative to the output's own size
    tol = 2e-5 * (float(np.abs(np.asarray(ref)).max()) if quant else 1.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=tol)


@pytest.mark.parametrize("quant", [False, True])
def test_paged_decode_kernel_parity(quant):
    dense, pool, table = _identity_layout(quant=quant, perm_seed=13)
    Hq, D = 4, 16
    q = jax.random.normal(jax.random.PRNGKey(9), (B, 1, Hq, D))
    lengths = jnp.array([1, SV, 29], jnp.int32)
    layer = jnp.int32(1)
    ref = decode_attend(q, *_layer_kv(dense, 1), lengths)
    pkw = dict(pool_ks=pool["ks"], pool_vs=pool["vs"]) if quant else {}
    out = pa.decode_attend_pallas_paged(q, pool["k"], pool["v"], lengths,
                                        layer, table, interpret=True, **pkw)
    _assert_attend_close(out, ref, quant)


def test_paged_decode_kernel_sliding_window():
    dense, pool, table = _identity_layout(perm_seed=17)
    q = jax.random.normal(jax.random.PRNGKey(10), (B, 1, 4, 16))
    lengths = jnp.array([7, SV, 40], jnp.int32)
    W = 16
    ref = decode_attend(q, *_layer_kv(dense, 0), lengths, window=W)
    out = pa.decode_attend_pallas_paged(q, pool["k"], pool["v"], lengths,
                                        jnp.int32(0), table, interpret=True,
                                        window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quant", [False, True])
def test_paged_write_row_kernel_parity(quant):
    dense, pool, table = _identity_layout(quant=quant, perm_seed=19)
    new = jax.random.normal(jax.random.PRNGKey(11), (B, 2, 16))
    rows = jnp.array([0, 33, SV + 2], jnp.int32)   # last drops
    layer = jnp.int32(1)
    want = dense
    for b in range(B):
        want = _dense_write(want, 1, b, int(rows[b]), new[b][None],
                            new[b][None])
    if quant:
        pk, pks = pa.cache_write_row_quant_paged(pool["k"], pool["ks"], new,
                                                 rows, table, layer,
                                                 interpret=True)
        got = kvp.gather_dense({"k": pk, "ks": pks}, table, PS)
        np.testing.assert_array_equal(np.asarray(got["k"]), want["k"])
        # a compiled program's fusion may round the scale's division 1 ulp
        # from the eager quantizer
        np.testing.assert_allclose(np.asarray(got["ks"]), want["ks"],
                                   rtol=1e-6, atol=0)
    else:
        pk = pa.cache_write_row_paged(pool["k"], new, rows, table, layer,
                                      interpret=True)
        got = kvp.gather_dense({"k": pk}, table, PS)
        np.testing.assert_array_equal(np.asarray(got["k"]), want["k"])


@pytest.mark.parametrize("quant", [False, True])
def test_paged_spec_kernel_parity(quant):
    dense, pool, table = _identity_layout(quant=quant, perm_seed=23)
    R, Hq, D = 3, 4, 16
    q = jax.random.normal(jax.random.PRNGKey(12), (B, R, Hq, D))
    lengths = jnp.array([2, 17, SV - R - 1], jnp.int32)
    layer = jnp.int32(0)
    ref = decode_attend(q, *_layer_kv(dense, 0), lengths + 1)
    pkw = dict(pool_ks=pool["ks"], pool_vs=pool["vs"]) if quant else {}
    out = pa.decode_attend_pallas_spec_paged(q, pool["k"], pool["v"], lengths,
                                             layer, table, interpret=True,
                                             **pkw)
    _assert_attend_close(out, ref, quant)


@pytest.mark.parametrize("quant", [False, True])
def test_layer_writers_match_per_layer_forms(quant):
    """The carry-path FULL-pool writers (round 5: the prefill layer scan
    keeps the pool in its carry; see write_prompts_paged_layer) must write
    exactly what the per-layer reference forms write at every layer."""
    dense, pool, table = _identity_layout(quant=quant, perm_seed=7)
    L = pool["k"].shape[0]
    N, T = 2, 11
    k = jax.random.normal(jax.random.PRNGKey(7), (N, T, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(8), (N, T, 2, 16))
    tables = table[jnp.array([2, 0])]
    for layer in range(L):
        ref_l = kvp.write_prompts_paged(
            {n: a[layer] for n, a in pool.items()}, tables, k, v, PS)
        got = kvp.write_prompts_paged_layer(pool, jnp.int32(layer), tables,
                                            k, v, PS)
        for name in ref_l:
            np.testing.assert_array_equal(np.asarray(got[name][layer]),
                                          np.asarray(ref_l[name]),
                                          err_msg=f"{name} layer {layer}")
            # other layers untouched
            for other in range(L):
                if other != layer:
                    np.testing.assert_array_equal(
                        np.asarray(got[name][other]),
                        np.asarray(pool[name][other]))

    C, start, slot = 12, 10, 2
    kc = jax.random.normal(jax.random.PRNGKey(9), (1, C, 2, 16))
    vc = jax.random.normal(jax.random.PRNGKey(10), (1, C, 2, 16))
    ref_l = kvp.write_chunk_paged({n: a[1] for n, a in pool.items()},
                                  table[slot], jnp.int32(start), kc, vc, PS)
    got = kvp.write_chunk_paged_layer(pool, jnp.int32(1), table[slot],
                                      jnp.int32(start), kc, vc, PS)
    for name in ref_l:
        np.testing.assert_array_equal(np.asarray(got[name][1]),
                                      np.asarray(ref_l[name]), err_msg=name)


# ---------------------------------------------------------------------------
# Tier-2 host store (ISSUE 20): spill log, two-level lookup, LRU byte
# pressure, fetch-time verification, gather/restore round trip
# ---------------------------------------------------------------------------


def _entry_data(tokens, scale=1.0):
    """Deterministic fake page payload keyed off its tokens."""
    base = float(sum(tokens) % 97) * scale
    return {"k": np.full((2, 2, PS, 16), base, np.float32),
            "v": np.full((2, 2, PS, 16), -base, np.float32)}


ENTRY_BYTES = 2 * 2 * 2 * PS * 16 * 4
SHAPES = {"k": (2, 2, PS, 16), "v": (2, 2, PS, 16)}


def test_host_tier_spill_log_and_two_level_lookup():
    """Reclaiming an indexed page records it in evicted_log; once its
    payload sits in the tier, lookup_prefix returns it as the host
    extension past the resident chain."""
    p = pkv.PagePool(4, PS, first_page=1)
    tier = pkv.HostTier(10 * ENTRY_BYTES)
    p.host_tier = tier
    prompt = list(range(3 * PS))
    pages = p.alloc(3)
    key = None
    keys = []
    for i in range(3):
        key = p.index_page(pages[i], key, tuple(prompt[i * PS:(i + 1) * PS]))
        keys.append(key)
    p.release_all(pages)
    # reclaim the two LRU-front pages -> logged with their chain identity
    p.alloc(2)
    assert [(k, tuple(prompt[i * PS:(i + 1) * PS]))
            for i, k in enumerate(keys[:2])] \
        == [(k, t) for _, k, t in p.evicted_log]
    # engine-side drain stand-in: park the payloads in the tier
    for _, k, t in p.evicted_log:
        tier.put(k, t, _entry_data(t), ENTRY_BYTES)
    p.evicted_log = []
    res, n, host = p.lookup_prefix(prompt)
    # pages 0-1 restorable from host, page 2 still resident/evictable
    assert n == 0 and res == [] and host == keys[:2]
    # without the tier attached the host walk is off entirely
    p.host_tier = None
    assert p.lookup_prefix(prompt) == ([], 0, [])


def test_host_tier_lru_under_byte_pressure():
    tier = pkv.HostTier(2 * ENTRY_BYTES)
    toks = [tuple(range(i * PS, (i + 1) * PS)) for i in range(3)]
    keys = [pkv.PagePool.chain_key(None, t) for t in toks]
    for k, t in zip(keys, toks):
        tier.put(k, t, _entry_data(t), ENTRY_BYTES)
    # third insert evicted the FIRST (LRU) entry, not the newest
    assert len(tier) == 2 and tier.dropped_lru == 1
    assert not tier.contains(keys[0], toks[0])
    assert tier.contains(keys[1], toks[1])
    assert tier.contains(keys[2], toks[2])
    assert tier.used_bytes == 2 * ENTRY_BYTES
    # a fetch bumps recency: entry 1 survives the next pressure insert
    assert tier.fetch(keys[1], toks[1], SHAPES) is not None
    t3 = tuple(range(90, 90 + PS))
    k3 = pkv.PagePool.chain_key(None, t3)
    tier.put(k3, t3, _entry_data(t3), ENTRY_BYTES)
    assert tier.contains(keys[1], toks[1])
    assert not tier.contains(keys[2], toks[2])


def test_host_tier_fetch_verifies_and_drops():
    """Corrupted (truncated) or token-mismatched entries never come back
    from fetch — they are dropped and counted, so the caller re-prefills
    instead of restoring garbage (the kv_offload_error contract)."""
    tier = pkv.HostTier(10 * ENTRY_BYTES)
    toks = tuple(range(PS))
    key = pkv.PagePool.chain_key(None, toks)
    tier.put(key, toks, _entry_data(toks), ENTRY_BYTES)
    # token mismatch (hash collision stand-in)
    assert tier.fetch(key, tuple(range(1, PS + 1)), SHAPES) is None
    assert tier.dropped_invalid == 1 and len(tier) == 0
    # truncation via the chaos hook
    tier.put(key, toks, _entry_data(toks), ENTRY_BYTES)
    tier.corrupt(key)
    assert tier.fetch(key, toks, SHAPES) is None
    assert tier.dropped_invalid == 2 and len(tier) == 0
    assert tier.used_bytes == 0
    # a clean entry still round-trips
    tier.put(key, toks, _entry_data(toks), ENTRY_BYTES)
    got = tier.fetch(key, toks, SHAPES)
    np.testing.assert_array_equal(got["k"], _entry_data(toks)["k"])


def test_gather_restore_roundtrip():
    """gather_pages -> restore_pages moves whole pages losslessly into a
    different set of physical pages (the spill->restore data path), and the
    padded scatter touches nothing else."""
    _, pool, _ = _identity_layout(perm_seed=3)
    src, dst = [2, 5, 9], [11, 3, 7]
    before = {n: np.asarray(a) for n, a in pool.items()}
    data = kvp.gather_pages(pool, src)
    for name in data:
        assert data[name].shape[1] == 3
    # the pool is DONATED (in-place scatter) — read expectations from the
    # pre-restore snapshot, never the consumed buffers
    restored = kvp.restore_pages(pool, dst, data)
    for name in before:
        got = np.asarray(restored[name])
        np.testing.assert_array_equal(got[:, dst], before[name][:, src])
        untouched = [p for p in range(before[name].shape[1]) if p not in dst]
        np.testing.assert_array_equal(got[:, untouched],
                                      before[name][:, untouched])


MIX_PS, MIX_C = 32, 96          # page, chunk rows (three pages)


@pytest.mark.parametrize("impl", ["pallas", "xla", "pallas-tp2"])
@pytest.mark.parametrize("hkv", [8, 16])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("plen", [1, MIX_PS - 1, MIX_PS + 3, MIX_C])
@pytest.mark.parametrize("first", [0, 17])
def test_mixed_step_write_path_matches_row_by_row(first, plen, quant, hkv,
                                                  impl):
    """serving/programs.mixed_step's K/V writes as ops/attention
    .make_mixed_attend_carry_paged makes them — one row a decode slot, the
    chunk as ONE span of the chunking slot's page run, a page window at a
    time; through the row kernel (interpret mode), through the XLA writers,
    and under ``shard_map`` with the KV heads over two devices as ``--tp``
    runs it — against a plain numpy loop over the rows. The chunk starts
    ``first`` rows into a page, holds ``plen`` tokens of MIX_C (the padding
    behind them writes nothing), its slot's table is allocated only as far
    as the prompt reaches (the tail names the scratch page, as the engine
    leaves it) and its own decode row is dropped. The whole pool is
    compared, so rows of a touched page outside [pstart, pstart + plen), the
    scratch page and every other layer keep their content."""
    from aws_k8s_ansible_provisioner_tpu.ops.attention import (
        make_mixed_attend_carry_paged)

    ps, C, nB, MP, D, L = MIX_PS, MIX_C, 3, 6, 16, 2
    rng = np.random.default_rng(1000 * first + 10 * plen + hkv + quant)
    P = 1 + nB * MP
    shape = (L, P, hkv, ps, D)
    if quant:
        pool = {n: jnp.asarray(rng.integers(-127, 128, shape, dtype=np.int8))
                for n in ("k", "v")}
        for n in ("ks", "vs"):
            pool[n] = jnp.asarray(rng.random(
                (L, P, hkv, kvp.scale_lanes(ps)), dtype=np.float32))
    else:
        pool = {n: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                for n in ("k", "v")}
    # everything is compared as float32: exact for bf16 and int8 alike
    want = {n: np.array(a, np.float32) for n, a in pool.items()}
    pslot, pstart, layer = 1, ps + first, 1
    table = rng.permutation(np.arange(1, P)).reshape(nB, MP).astype(np.int32)
    table[pslot, (pstart + plen - 1) // ps + 1:] = 0     # unallocated tail
    lengths = np.array([5, 70, MP * ps - 1], np.int32)
    dec_rows = np.where(np.arange(nB) == pslot, -1, lengths).astype(np.int32)
    is_pad = np.arange(C) >= plen
    limits = np.concatenate([np.where(dec_rows < 0, 0, lengths + 1),
                             np.where(is_pad, 0, pstart + np.arange(C) + 1)])
    row_map = np.concatenate([np.arange(nB), np.full(C, pslot)])
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, nB + C, hkv, D),
                                 jnp.bfloat16) for i in (3, 4, 5))
    mesh = None
    if impl == "pallas-tp2":
        from aws_k8s_ansible_provisioner_tpu.config import MeshConfig
        from aws_k8s_ansible_provisioner_tpu.parallel.mesh import make_mesh
        if len(jax.devices()) < 2:
            pytest.skip("needs two devices")
        mesh = make_mesh(MeshConfig(tp=2))
    operands = (jnp.asarray(dec_rows), jnp.int32(pstart), jnp.int32(plen),
                jnp.asarray(limits, jnp.int32), jnp.asarray(table),
                jnp.asarray(row_map, jnp.int32))
    attend = make_mixed_attend_carry_paged(
        *operands, impl=impl.split("-")[0], mesh=mesh)
    ctx, (got, _) = jax.jit(attend)(q, k, v, (pool, jnp.int32(layer)))
    if mesh is not None and first:
        # what the rows then READ through the table and the map, operands
        # of the shard_map like the rest: a KV head's rows do not depend on
        # which shard holds them
        alone, _ = jax.jit(make_mixed_attend_carry_paged(
            *operands, impl="pallas"))(q, k, v, (pool, jnp.int32(layer)))
        np.testing.assert_array_equal(np.asarray(ctx, np.float32),
                                      np.asarray(alone, np.float32))
        assert not np.asarray(ctx, np.float32)[0][limits == 0].any()

    where = [(b, int(dec_rows[b]), table[b]) for b in range(nB)
             if dec_rows[b] >= 0]
    where += [(nB + i, pstart + i, table[pslot]) for i in range(plen)]
    for name, val in (("k", k[0]), ("v", v[0])):
        scales = None
        if quant:
            val, scales = kvp.quantize_rows(val)
        val = np.asarray(val, np.float32)
        for i, r, tab in where:
            want[name][layer, tab[r // ps], :, r % ps] = val[i]
            if quant:
                want[name + "s"][layer, tab[r // ps], :, r % ps] = scales[i]
    for name, w in want.items():
        g = np.asarray(got[name], np.float32)
        if name in ("ks", "vs"):
            # the scales: one ulp between the kernel's division and XLA's
            np.testing.assert_allclose(g, w, rtol=1e-6, err_msg=name)
        elif quant:
            # ... which may round a value the other way (quantize_rows'
            # contract: one int8 step); untouched rows are exact either way
            diff = np.abs(g - w)
            assert diff.max() <= 1, name
            assert (diff > 0).sum() <= max(4, len(where) * hkv * D // 1000)
        else:
            np.testing.assert_array_equal(g, w, err_msg=name)


# ---------------------------------------------------------------------------
# Layering: the pool's arrays and kernels sit BELOW the server
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("package", ["ops", "models"])
def test_kernel_and_model_layers_do_not_import_serving(package):
    """No module under ops/ or models/ imports serving/ — at module level or
    inside a function (the kernels and the pool layout are what the server
    is built on, never the other way round)."""
    import ast
    import pathlib

    import aws_k8s_ansible_provisioner_tpu as pkg

    root = pathlib.Path(pkg.__file__).parent
    files = sorted((root / package).glob("*.py"))
    assert files
    bad = []
    for path in files:
        for node in ast.walk(ast.parse(path.read_text())):
            names = []
            if isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
                if names[0] == pkg.__name__:
                    names = [f"{pkg.__name__}.{a.name}" for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            bad += [f"{path.name}:{node.lineno} {n}" for n in names
                    if n.startswith(f"{pkg.__name__}.serving")]
    assert not bad, bad


# ---------------------------------------------------------------------------
# Pool allocation and the write-then-read round trip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,quant,row_bytes", [
    (jnp.bfloat16, False, 16 * 2),
    (jnp.float32, False, 16 * 4),
    (jnp.bfloat16, True, 16 * 1),
], ids=["bf16", "f32", "int8"])
def test_pool_shapes_and_bytes(dtype, quant, row_bytes):
    pool = kvp.init_pool(CFG, 7, PS, dtype, quant=quant)
    shape = (CFG.num_layers, 7, CFG.num_kv_heads, PS, CFG.head_dim)
    assert pool["k"].shape == pool["v"].shape == shape
    assert pool["k"].dtype == (jnp.int8 if quant else dtype)
    rows = 2 * CFG.num_layers * 7 * CFG.num_kv_heads * PS
    if quant:
        # one f32 scale a row, the scale leaves lane-padded to the 128 tile
        lanes = kvp.scale_lanes(PS)
        assert lanes % 128 == 0 and lanes >= PS
        assert pool["ks"].shape == pool["vs"].shape == shape[:3] + (lanes,)
        assert pool["ks"].dtype == jnp.float32
        want = rows * row_bytes + rows // PS * lanes * 4
    else:
        assert set(pool) == {"k", "v"}
        want = rows * row_bytes
    assert kvp.pool_bytes(CFG, 7, PS, dtype, quant=quant) == want
    assert sum(a.size * a.dtype.itemsize for a in pool.values()) == want


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_write_prompt_then_tokens_roundtrip(quant):
    """A prompt written through one slot's run, then one decode token a
    slot, read back through the same identity table: the prompt's rows, the
    token's row, and nothing in the other slots' pages."""
    pool = kvp.init_pool(CFG, B * PPS, PS, jnp.float32, quant=quant)
    table = jnp.arange(B * PPS, dtype=jnp.int32).reshape(B, PPS)
    rng = np.random.default_rng(0)
    T, slot = 13, 2
    k = jnp.asarray(rng.normal(size=(1, T, 2, 16)), jnp.float32)
    pool = kvp.write_chunk_paged_layer(pool, jnp.int32(1), table[slot], 0, k,
                                       2 * k, PS)
    lengths = jnp.asarray([0, 0, T], jnp.int32)
    k1 = jnp.asarray(rng.normal(size=(B, 1, 2, 16)), jnp.float32)
    pool = kvp.write_token_layer_paged(pool, jnp.int32(1), lengths, table,
                                       k1, 3 * k1, PS)
    view = kvp.gather_dense(pool, table, PS)

    def rows(name, b, lo, hi):
        x = view[name][1, b, :, lo:hi]                     # [Hkv, n, D]
        if quant:
            x = kvp.dequantize(x, view[name + "s"][1, b, :, lo:hi])
        return np.swapaxes(np.asarray(x), 0, 1)            # [n, Hkv, D]

    tol = dict(rtol=0, atol=0.05) if quant else dict(rtol=0, atol=0)
    np.testing.assert_allclose(rows("k", slot, 0, T), np.asarray(k[0]), **tol)
    np.testing.assert_allclose(rows("v", slot, 0, T), 2 * np.asarray(k[0]),
                               **{**tol, "atol": 2 * tol["atol"]})
    np.testing.assert_allclose(rows("k", slot, T, T + 1),
                               np.asarray(k1[slot]), **tol)
    np.testing.assert_allclose(rows("v", 0, 0, 1), 3 * np.asarray(k1[0]),
                               **{**tol, "atol": 3 * tol["atol"]})
    # layer 0 untouched, and slot 1 holds only its one token row
    assert not any(np.asarray(a[0]).any() for a in view.values())
    assert not np.asarray(view["k"][1, 1, :, 1:]).any()
