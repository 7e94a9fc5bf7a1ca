"""Pallas paged-attention and row-write kernel parity tests (interpret mode
on CPU).

The kernels are the framework's hot loop (SURVEY.md §7 hard part #1); these
tests pin them (fp32 tolerance) against the XLA reference implementation in
ops/attention.py across page sizes, raggedness, GQA grouping, block sizes and
pool dtypes, and the row writers against the XLA scatter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp
from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa
from aws_k8s_ansible_provisioner_tpu.ops.attention import decode_attend


def test_resolve_impl_auto_is_xla_on_cpu():
    from aws_k8s_ansible_provisioner_tpu.ops.attention import resolve_impl

    assert resolve_impl("auto") in ("xla", "pallas")
    assert resolve_impl("xla") == "xla"
    assert resolve_impl("pallas") == "pallas"


# ---------------------------------------------------------------------------
# Double-buffered paged decode (r6): explicit async page prefetch, bb slots
# per grid step. Parity bar: the XLA reference attention (ops/attention.py)
# at f32 accumulate, across bb in {1, 4, 8} x {bf16, int8} x {decode, spec}.
# ---------------------------------------------------------------------------


def _paged_layout(B=8, S=128, Hkv=2, D=32, L=2, PS=32, quant=False, seed=21,
                  dtype=jnp.float32):
    """Dense [L,B,Hkv,S,D] cache + an equivalent PERMUTED page pool/table
    (physical page order shuffled so a table-indexing bug cannot hide
    behind an identity layout)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    ck = jax.random.normal(ks[0], (L, B, Hkv, S, D), dtype)
    cv = jax.random.normal(ks[1], (L, B, Hkv, S, D), dtype)
    dense = {"k": ck, "v": cv}
    if quant:
        qk, sk = kvp.quantize_rows(ck)
        qv, sv = kvp.quantize_rows(cv)
        dense = {"k": qk, "v": qv, "ks": sk, "vs": sv}
    n_pages_per_slot = S // PS
    rng = np.random.default_rng(seed)
    perm = rng.permutation(B * n_pages_per_slot) + 1
    table = perm.reshape(B, n_pages_per_slot).astype(np.int32)
    return dense, _to_pool(dense, table, PS), jnp.asarray(table)


def _to_pool(dense, table, PS):
    """Cut logical [L, B, Hkv, S, (D)] leaves into pages placed by ``table``
    (+1 page: scratch page 0 stays unused)."""
    B, mp = table.shape
    pool = {}
    for name, arr in dense.items():
        a = np.asarray(arr)
        L, _, Hkv = a.shape[:3]
        pooled = np.zeros((L, B * mp + 1, Hkv, PS) + a.shape[4:], a.dtype)
        for b in range(B):
            for c in range(mp):
                pooled[:, table[b, c]] = a[:, b, :, c * PS:(c + 1) * PS]
        pool[name] = jnp.asarray(pooled)
    return pool


def _with_nan_page(pool):
    """``pool`` with one more page at the end, all NaN (127 in an int8
    leaf): what a fetch that should not have happened brings back."""
    return {n: jnp.concatenate([a, jnp.full_like(a[:, :1], jnp.nan)
                                if a.dtype == jnp.float32
                                else jnp.full_like(a[:, :1], 127)], axis=1)
            for n, a in pool.items()}


def _slot_rows(tab, rows_of, limits, nan_page):
    """(table, row_map) of a packed call: ``tab`` [S, max_pages], one row a
    slot, and ``rows_of`` [N], the slot each packed row reads. A DEAD row
    keeps a poisoned view: it names one more slot whose table row is all
    ``nan_page`` (_with_nan_page), so a fetch through it shows."""
    dead = np.full((1, tab.shape[1]), nan_page, tab.dtype)
    return (jnp.asarray(np.concatenate([tab, dead])),
            jnp.asarray(np.where(limits > 0, rows_of, tab.shape[0]),
                        jnp.int32))


@pytest.mark.parametrize("bb", [1, 4, 8])
@pytest.mark.parametrize("quant", [False, True])
def test_paged_db_decode_parity(bb, quant):
    """Double-buffered paged decode vs the XLA reference, ragged lengths
    mixing full-window, page-boundary, and 1-token slots inside one block."""
    dense, pool, table = _paged_layout(quant=quant, seed=31)
    B, S, Hq, D = 8, 128, 4, 32
    q = jax.random.normal(jax.random.PRNGKey(1), (B, 1, Hq, D))
    lengths = jnp.asarray([1, 128, 7, 64, 33, 97, 2, 128], jnp.int32)
    if quant:
        ck = kvp.dequantize(dense["k"][0], dense["ks"][0])
        cv = kvp.dequantize(dense["v"][0], dense["vs"][0])
    else:
        ck, cv = dense["k"][0], dense["v"][0]
    ref = decode_attend(q, ck, cv, lengths)
    pkw = dict(pool_ks=pool["ks"], pool_vs=pool["vs"]) if quant else {}
    out = pa.decode_attend_pallas_paged(q, pool["k"], pool["v"], lengths,
                                        jnp.int32(0), table, interpret=True,
                                        bblock=bb, **pkw)
    tol = 4e-2 if quant else 2e-5   # int8 tolerance bounds the quant error
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bb", [1, 4, 8])
def test_paged_db_decode_bb_invariance(bb):
    """All bb values must produce IDENTICAL results (the autotuner's choice
    is a pure perf knob, never a numerics knob)."""
    _, pool, table = _paged_layout(seed=37)
    q = jax.random.normal(jax.random.PRNGKey(2), (8, 1, 4, 32))
    lengths = jnp.asarray([5, 128, 70, 1, 99, 128, 13, 40], jnp.int32)
    ref = pa.decode_attend_pallas_paged(q, pool["k"], pool["v"], lengths,
                                        jnp.int32(1), table, interpret=True,
                                        bblock=1)
    out = pa.decode_attend_pallas_paged(q, pool["k"], pool["v"], lengths,
                                        jnp.int32(1), table, interpret=True,
                                        bblock=bb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bb", [1, 4, 8])
@pytest.mark.parametrize("quant", [False, True])
def test_paged_db_spec_parity(bb, quant):
    """Multi-query spec-verify through the double-buffered path: row r of
    each slot masks to its own causal frontier (lengths + 1 + r)."""
    dense, pool, table = _paged_layout(quant=quant, seed=41)
    B, R, Hq, D = 8, 3, 4, 32
    q = jax.random.normal(jax.random.PRNGKey(3), (B, R, Hq, D))
    lengths = jnp.asarray([2, 17, 124, 0, 60, 93, 31, 8], jnp.int32)
    if quant:
        ck = kvp.dequantize(dense["k"][0], dense["ks"][0])
        cv = kvp.dequantize(dense["v"][0], dense["vs"][0])
    else:
        ck, cv = dense["k"][0], dense["v"][0]
    ref = decode_attend(q, ck, cv, lengths + 1)
    pkw = dict(pool_ks=pool["ks"], pool_vs=pool["vs"]) if quant else {}
    out = pa.decode_attend_pallas_spec_paged(q, pool["k"], pool["v"],
                                             lengths, jnp.int32(0), table,
                                             interpret=True, bblock=bb,
                                             **pkw)
    tol = 4e-2 if quant else 2e-5   # int8 tolerance bounds the quant error
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bb", [1, 4])
def test_paged_db_sliding_window_parity(bb):
    dense, pool, table = _paged_layout(seed=43)
    q = jax.random.normal(jax.random.PRNGKey(4), (8, 1, 4, 32))
    lengths = jnp.asarray([20, 128, 64, 100, 3, 47, 128, 77], jnp.int32)
    W = 48
    ref = decode_attend(q, dense["k"][0], dense["v"][0], lengths, window=W)
    out = pa.decode_attend_pallas_paged(q, pool["k"], pool["v"], lengths,
                                        jnp.int32(0), table, interpret=True,
                                        window=W, bblock=bb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_db_poisoned_dead_pages_ignored():
    """Pages beyond every slot's live range must never be fetched NOR leak
    into the output: poison them with huge values and compare."""
    _, pool, table = _paged_layout(seed=47)
    q = jax.random.normal(jax.random.PRNGKey(5), (8, 1, 4, 32))
    lengths = jnp.asarray([10, 33, 64, 5, 96, 20, 64, 31], jnp.int32)
    base = pa.decode_attend_pallas_paged(q, pool["k"], pool["v"], lengths,
                                         jnp.int32(0), table, interpret=True,
                                         bblock=4)
    # poison every page past each slot's live count
    tab = np.asarray(table)
    k_p, v_p = np.asarray(pool["k"]).copy(), np.asarray(pool["v"]).copy()
    ps = 32
    for b in range(8):
        live = -(-int(lengths[b]) // ps)
        for c in range(live, tab.shape[1]):
            k_p[:, tab[b, c]] = 1e4
            v_p[:, tab[b, c]] = -1e4
    out = pa.decode_attend_pallas_paged(q, jnp.asarray(k_p), jnp.asarray(v_p),
                                        lengths, jnp.int32(0), table,
                                        interpret=True, bblock=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# A row past its own pages starts no copy (PR 45). In the per-row path a
# block's page step c copies for row i only where lo[i] <= c <= hi[i]; the
# row's masked update still runs on whatever its buffer slot holds, so the
# kernel zeroes the V slots no copy of the block fills first. Interpret mode
# hands every scratch buffer over as NaN (pinned below), so a slot nothing
# filled shows as a NaN in the output. Blocks of ONE row never skip (a row's
# walk is its own range), so ``bblock=1`` is the program the parent ran, bit
# for bit.
# ---------------------------------------------------------------------------


def test_interpret_mode_poisons_scratch_buffers():
    """What the tests below lean on: a scratch buffer nobody wrote reads as
    NaN in interpret mode (a JAX that zero-filled it would hide an unfilled
    page buffer, as the chip does not)."""
    def kernel(o_ref, scratch):
        o_ref[:] = scratch[:]

    out = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
        interpret=True)()
    assert np.isnan(np.asarray(out)).all()


# a one-page row beside a full window, a row that ends on a page edge, a
# dead row in a live block, mid-page rows (pages of 32; the window of 48
# puts the full rows' first live page above the block's)
_SKIP_LENGTHS = [1, 128, 32, 0, 33, 97, 64, 5]


def _skip_case(kind, bb):
    """(kernel rows, live mask, XLA reference rows) of one call in which
    rows of a block hold unlike page ranges, scratch poisoned."""
    quant, window = "int8" in kind, 48 if "window" in kind else 0
    dense, pool, table = _paged_layout(quant=quant, seed=59)
    lengths = jnp.asarray(_SKIP_LENGTHS, jnp.int32)
    ck, cv = dense["k"][0], dense["v"][0]
    if quant:
        ck = kvp.dequantize(ck, dense["ks"][0])
        cv = kvp.dequantize(cv, dense["vs"][0])
    pkw = dict(pool_ks=pool["ks"], pool_vs=pool["vs"]) if quant else {}
    kw = dict(interpret=True, window=window, bblock=bb, **pkw)
    if kind.startswith("spec"):
        R = 3
        q = jax.random.normal(jax.random.PRNGKey(9), (8, R, 4, 32))
        lengths = jnp.minimum(lengths, 128 - R)
        out = pa.decode_attend_pallas_spec_paged(
            q, pool["k"], pool["v"], lengths, jnp.int32(0), table, **kw)
        ref = decode_attend(q, ck, cv, lengths + 1, window=window)
        return out, np.ones(8, bool), ref
    if kind.startswith("ragged"):
        # the decode rows of a mixed step: every slot's row (slot 2's dead:
        # it is the one chunking), then 16 chunk rows of slot 2
        j = np.arange(16)
        limits = jnp.asarray(np.concatenate(
            [np.where(np.arange(8) == 2, 0, _SKIP_LENGTHS),
             np.where(j < 11, 40 + j + 1, 0)]), jnp.int32)
        rows_of = np.concatenate([np.arange(8), np.full(16, 2)])
        q = jax.random.normal(jax.random.PRNGKey(9), (24, 4, 32))
        out = pa.ragged_attend_pallas_paged(
            q, pool["k"], pool["v"], limits, jnp.int32(0), table,
            jnp.asarray(rows_of, jnp.int32), **kw)
        ref = decode_attend(q[:, None], ck[rows_of], cv[rows_of], limits,
                            window=window)[:, 0]
        # the chunk rows of a sharing block take another arithmetic
        return out[:8], np.asarray(limits[:8]) > 0, ref[:8]
    q = jax.random.normal(jax.random.PRNGKey(9), (8, 1, 4, 32))
    out = pa.decode_attend_pallas_paged(
        q, pool["k"], pool["v"], lengths, jnp.int32(0), table, **kw)
    return out, np.asarray(lengths) > 0, \
        decode_attend(q, ck, cv, lengths, window=window)


@pytest.mark.parametrize("bb", [4, 8])
@pytest.mark.parametrize("kind", [
    "decode", "decode-int8", "decode-window", "decode-int8-window", "spec",
    "spec-int8", "spec-window", "ragged", "ragged-int8", "ragged-window"])
def test_a_row_outside_its_range_copies_nothing_and_keeps_its_bits(kind,
                                                                   bb):
    out, live, ref = (np.asarray(a, np.float32)
                      for a in _skip_case(kind, bb))
    live = live.astype(bool)
    assert np.isfinite(out).all(), "an unfilled buffer slot reached a matmul"
    alone, _, _ = _skip_case(kind, 1)
    np.testing.assert_array_equal(out, np.asarray(alone, np.float32))
    tol = 4e-2 if "int8" in kind else 2e-5
    np.testing.assert_allclose(out[live], ref[live], rtol=tol, atol=tol)
    assert not out[~live].any()


def _copy_eqns(jaxpr, under_cond=False, found=None):
    """(primitive name, is it under a ``cond``) of every copy start and
    wait in ``jaxpr``, the kernel's body included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("dma_start", "dma_wait"):
            found.append((eqn.primitive.name, under_cond))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _copy_eqns(sub, under_cond or eqn.primitive.name == "cond",
                       found)
    return found


@pytest.mark.parametrize("entry", ["decode", "decode-int8", "spec", "ragged"])
def test_a_per_row_copy_starts_and_waits_under_one_predicate(entry):
    """Every start of the per-row walk has its wait, both inside a ``cond``
    (the row's ``fetches``; the prologue and the prefetch add theirs around
    the starts): none runs for a row outside its range. (The parent's waits
    were unconditional; the selecting entries' still are — their jaxprs are
    pinned in tests/test_tpu_compile.py.)"""
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    B, Hq, Hkv, PS, D, MP = 4, 4, 2, 16, 16, 4
    kv = sds((2, 17, Hkv, PS, D), jnp.int8 if "int8" in entry
             else jnp.bfloat16)
    args = (kv, kv, sds((B,), i32), sds((), i32), sds((B, MP), i32))
    if entry == "ragged":
        args += (sds((B,), i32),)       # row_map
    pkw = {}
    if "int8" in entry:
        sc = sds((2, 17, Hkv, kvp.scale_lanes(PS)), jnp.float32)
        pkw = dict(pool_ks=sc, pool_vs=sc)
    fn, q = {"spec": (pa.decode_attend_pallas_spec_paged, (B, 3, Hq, D)),
             "ragged": (pa.ragged_attend_pallas_paged, (B, Hq, D))}.get(
        entry, (pa.decode_attend_pallas_paged, (B, 1, Hq, D)))
    found = _copy_eqns(jax.make_jaxpr(
        lambda *a, **k: fn(*a, interpret=True, bblock=2, **k))(
            sds(q, jnp.bfloat16), *args, **pkw).jaxpr)
    starts = [c for n, c in found if n == "dma_start"]
    waits = [c for n, c in found if n == "dma_wait"]
    assert starts and all(starts) and all(waits)
    # the prologue's and the prefetch's starts, one wait for both
    assert len(starts) == 2 * len(waits)


# ---------------------------------------------------------------------------
# The ragged entry: a packed batch as serving/programs.mixed_step lays it out
# — B decode rows, then C chunk rows of ONE slot at pstart + j; rows past
# the prompt (j >= plen) and the chunking slot's own decode row are DEAD
# (limit 0). Blocks of chunk rows share one page stream; a block straddling
# decode and chunk rows (B % bb != 0) streams per row.
# ---------------------------------------------------------------------------

_RAGGED_GRID = [
    pytest.param(quant, bb, window, B, 0, 11,
                 id=f"{'int8' if quant else 'f32'}-bb{bb}-w{window}-"
                    f"{'aligned' if B % 8 == 0 else 'straddle'}")
    for quant in (False, True) for bb in (1, 8) for window in (0, 48)
    for B in (8, 4)
] + [
    pytest.param(False, 8, 0, 8, 0, 16, id="f32-bb8-no-dead-row"),
    pytest.param(False, 8, 0, 8, 70, 11, id="f32-bb8-pstart-70"),
    pytest.param(True, 8, 48, 4, 70, 16, id="int8-bb8-w48-straddle-pstart-70"),
]


@pytest.mark.parametrize("quant,bb,window,B,pstart,plen", _RAGGED_GRID)
def test_ragged_mixed_layout_parity(quant, bb, window, B, pstart, plen):
    C, pslot, S, PS, Hq, D = 16 if B == 8 else 20, 2, 128, 32, 4, 32
    dense, pool, table = _paged_layout(B=B, S=S, PS=PS, quant=quant, seed=53)
    tab = np.asarray(table)
    # one more page, all NaN, that no live row's table names: a fetch
    # through a dead row's entry shows
    nan_page = pool["k"].shape[1]
    pool = _with_nan_page(pool)
    lengths = np.asarray([1, 128, 0, 64, 33, 97, 2, 128][:B], np.int32)
    j = np.arange(C)
    limits = np.concatenate([np.where(np.arange(B) == pslot, 0, lengths),
                             np.where(j < plen, pstart + j + 1, 0)])
    rows_of = np.concatenate([np.arange(B), np.full(C, pslot)])
    N = B + C
    q = jax.random.normal(jax.random.PRNGKey(7), (N, Hq, D))

    ck, cv = dense["k"][0][rows_of], dense["v"][0][rows_of]
    if quant:
        ck = kvp.dequantize(ck, dense["ks"][0][rows_of])
        cv = kvp.dequantize(cv, dense["vs"][0][rows_of])
    ref = decode_attend(q[:, None], ck, cv, jnp.asarray(limits),
                        window=window)[:, 0]
    pkw = dict(pool_ks=pool["ks"], pool_vs=pool["vs"]) if quant else {}
    out = pa.ragged_attend_pallas_paged(
        q, pool["k"], pool["v"], jnp.asarray(limits), jnp.int32(0),
        *_slot_rows(tab, rows_of, limits, nan_page), interpret=True,
        window=window, bblock=bb, **pkw)
    out, live = np.asarray(out), limits > 0
    assert live.sum() == B - 1 + plen
    tol = 4e-2 if quant else 2e-5
    np.testing.assert_allclose(out[live], np.asarray(ref)[live],
                               rtol=tol, atol=tol)
    assert np.array_equal(out[~live], np.zeros_like(out[~live]))


# ---------------------------------------------------------------------------
# The WIDE tile: where N has a divisor of 16..64 rows a grid step holds that
# many, and a step whose live rows all read one table row — the chunk's —
# is served as one query tile over one page stream. The served row counts
# scaled down: 2,080 = 52 x 40 -> 120 = 3 x 40; 2,064 = 43 x 48 -> 144;
# 2,072 and 4,144 = 37 / 74 x 56 -> 168 and 280; 576 = 9 x 64 -> 192.
# ---------------------------------------------------------------------------


def _wide_case(N, B, pstart, plen, *, groups=2, window=0, quant=False,
               PS=16, Hkv=2, D=32, seed=59):
    """A mixed step's packed rows — B decode rows (slot 2's dead: it is the
    one chunking), then N - B chunk rows of slot 2 at pstart + j, the first
    ``plen`` of them live — through the ragged entry, and the jnp reference.
    Under a window the chunking slot's pages BELOW its first row's window
    are released: their table entries name the NaN page."""
    C, pslot, Hq = N - B, 2, Hkv * groups
    S = -(-(pstart + C + 1) // PS) * PS
    dense, pool, table = _paged_layout(B=B, S=S, Hkv=Hkv, D=D, PS=PS,
                                       quant=quant, seed=seed)
    nan_page = pool["k"].shape[1]
    pool = _with_nan_page(pool)
    tab = np.asarray(table).copy()
    lengths = np.resize(np.asarray([1, S, 0, PS, 2 * PS + 1, S - 3, 2, S],
                                   np.int32), B)
    j = np.arange(C)
    limits = np.concatenate([np.where(np.arange(B) == pslot, 0, lengths),
                             np.where(j < plen, pstart + j + 1, 0)]
                            ).astype(np.int32)
    rows_of = np.concatenate([np.arange(B), np.full(C, pslot)])
    q = jax.random.normal(jax.random.PRNGKey(seed + 1), (N, Hq, D))
    ck, cv = dense["k"][0][rows_of], dense["v"][0][rows_of]
    if quant:
        ck = kvp.dequantize(ck, dense["ks"][0][rows_of])
        cv = kvp.dequantize(cv, dense["vs"][0][rows_of])
    ref = decode_attend(q[:, None], ck, cv, jnp.asarray(limits),
                        window=window)[:, 0]
    if window:      # what lies below every row's window went back
        for b, first in enumerate(lengths):
            first = pstart + 1 if b == pslot else first
            tab[b, :max(first - window, 0) // PS] = nan_page
    pkw = dict(pool_ks=pool["ks"], pool_vs=pool["vs"]) if quant else {}
    # a list's window layers call the entry under its other trace name
    fn = pa.ragged_attend_pallas_paged_window if window \
        else pa.ragged_attend_pallas_paged
    out = fn(q, pool["k"], pool["v"], jnp.asarray(limits), jnp.int32(0),
             *_slot_rows(tab, rows_of, limits, nan_page), interpret=True,
             window=window, bblock=8, **pkw)
    return np.asarray(out), np.asarray(ref), limits


_WIDE_GRID = [
    # id, N, B, pstart (chunk_off), plen, kwargs
    ("n120-t40-straddle", 120, 8, 0, 112, {}),
    ("n120-t40-part-dead-tile", 120, 8, 0, 50, {}),
    ("n120-t40-dead-tiles", 120, 8, 0, 20, {}),
    ("n120-t40-mid-page-off", 120, 8, 37, 112, {}),
    ("n120-t40-one-page-prompt", 120, 8, 0, 9, {}),
    ("n120-t40-aligned-decode", 120, 40, 21, 80, {}),
    ("n144-t48", 144, 8, 5, 136, {}),
    ("n168-t56", 168, 8, 70, 160, {}),
    ("n280-t56", 280, 8, 200, 272, {}),
    ("n192-t64", 192, 8, 33, 150, {}),
    ("n168-t56-mha", 168, 8, 70, 100, {"groups": 1}),
    ("n168-t56-groups8", 168, 8, 70, 160, {"groups": 8, "Hkv": 1}),
    ("n120-int8-keeps-blocks-of-8", 120, 8, 37, 100, {"quant": True}),
    ("n120-t40-window", 120, 8, 150, 112, {"window": 48}),
    ("n120-int8-window-released", 120, 8, 150, 100,
     {"quant": True, "window": 48}),
    ("n168-t56-window-released", 168, 8, 300, 130,
     {"window": 48, "groups": 8, "Hkv": 1}),
    ("n168-t56-window-one-page", 168, 8, 0, 11, {"window": 48}),
    # a row count with no tile of its own (8 x 11, 16 x 11: the narrow
    # bodies' 1,048 = 8 x 131 and 2,096 = 16 x 131 scaled down) runs with
    # dead rows behind it, as tiles of 64 (PR 55: _ragged_pad)
    ("n88-pads-to-128", 88, 8, 5, 70, {}),
    ("n88-pads-to-128-dead-tiles", 88, 8, 0, 9, {}),
    ("n176-pads-to-192", 176, 8, 33, 150, {}),
    ("n88-pads-to-128-window-released", 88, 8, 150, 60, {"window": 48}),
    ("n88-int8-keeps-its-rows", 88, 8, 37, 70, {"quant": True}),
]


@pytest.mark.parametrize("N,B,pstart,plen,kw",
                         [c[1:] for c in _WIDE_GRID],
                         ids=[c[0] for c in _WIDE_GRID])
def test_ragged_wide_tile_parity(N, B, pstart, plen, kw):
    out, ref, limits = _wide_case(N, B, pstart, plen, **kw)
    live = limits > 0
    assert np.isfinite(out).all()
    tol = 4e-2 if kw.get("quant") else 2e-5
    np.testing.assert_allclose(out[live], ref[live], rtol=tol, atol=tol)
    assert np.array_equal(out[~live], np.zeros_like(out[~live]))


def test_tile_rows_come_from_the_shapes():
    """The served row counts and head shapes give the widths the records
    name; a row count with no divisor of 16..64 keeps blocks of 8."""
    for n, hq, want in ((2080, 16, 40), (2064, 32, 48), (2072, 16, 56),
                        (4144, 32, 56), (2072, 4, 56), (8 * 257, 16, 8)):
        assert pa._tile_rows(n, 8, hq, 128, 64, jnp.bfloat16) == want, \
            (n, hq)
    # 64 query heads: a 64-row tile's working set outgrows the budget
    assert pa._tile_rows(576, 8, 16, 128, 64, jnp.bfloat16) == 64
    assert pa._tile_rows(576, 8, 64, 128, 64, jnp.bfloat16) == 32
    # nothing shares a stream in blocks of one row; an int8 pool's scales
    # ride a page's lanes, so its sharing blocks stay 8 rows
    assert pa._tile_rows(2080, 1, 16, 128, 64, jnp.bfloat16) == 1
    assert pa._tile_rows(2080, 8, 16, 128, 64, jnp.int8) == 8


def test_a_row_count_without_a_tile_is_padded_to_one():
    """The plain ragged entry runs a row count whose widest tile is under
    32 rows with dead rows behind it, a whole number of 64: the two narrow
    bodies of ``mixed_step`` that need it (OLMoE's 24 + 1,024, the
    window/full list's 48 + 2,048). Every count a cell served before PR 55,
    the other narrow bodies, blocks of one row and an int8 pool keep
    theirs (the SELECTING entry takes its rows as they are: 24 + 4,608
    and 24 + 2,304 run as tiles of 24)."""
    bf = jnp.bfloat16
    assert pa._ragged_pad(1048, 8, 16, 128, 64, bf) == 1088
    assert pa._ragged_pad(2096, 8, 32, 128, 64, bf) == 2112
    assert pa._tile_rows(1088, 8, 16, 128, 64, bf) == 64
    assert pa._tile_rows(2112, 8, 32, 128, 64, bf) == 64
    for n, hq in ((2080, 16), (2064, 32), (2072, 16), (4144, 32), (576, 16),
                  (640, 16), (576, 20), (1056, 16), (1040, 32), (320, 16),
                  (384, 16)):
        assert pa._ragged_pad(n, 8, hq, 128, 64, bf) == n, n
    assert pa._ragged_pad(1048, 1, 16, 128, 64, bf) == 1048
    assert pa._ragged_pad(1048, 8, 16, 128, 64, jnp.int8) == 1048
    # the traced call: 88 rows in, 128 run, 88 out
    seen, real = [], pa._paged_flash_db
    try:
        pa._paged_flash_db = lambda q, *a, **k: seen.append(q.shape[0]) \
            or real(q, *a, **k)
        jax.clear_caches()
        out, _, _ = _wide_case(88, 8, 5, 70)
    finally:
        pa._paged_flash_db = real
        jax.clear_caches()
    assert seen == [128] and out.shape[0] == 88


@pytest.mark.parametrize("case", ["n168-t56", "n120-t40-window",
                                  "n168-t56-window-released"])
def test_a_rows_output_is_bitwise_the_same_in_a_tile_of_any_width(
        case, monkeypatch):
    """Tile widths T and 8 (blocks of 8 rows: the path every row took
    before the tile widened) give every row the same bits: what a wider
    tile walks outside a row's own pages is fully masked for it. At the
    served head_dim: at a toy one the CPU backend multiplies 16 rows by
    another routine than 112 and the last bit of a product moves."""
    _, N, B, pstart, plen, kw = next(c for c in _WIDE_GRID if c[0] == case)
    kw = dict(kw, D=128)
    wide, _, _ = _wide_case(N, B, pstart, plen, **kw)
    widths = []
    real = pa._tile_rows
    monkeypatch.setattr(pa, "_tile_rows",
                        lambda *a: widths.append(real(*a)) or a[1])
    jax.clear_caches()
    by8, _, _ = _wide_case(N, B, pstart, plen, **kw)
    jax.clear_caches()
    assert widths and widths[0] > 8
    assert np.array_equal(wide, by8)


# ---------------------------------------------------------------------------
# The SELECTING ragged entry's wide tiles: a sharing tile of _tile_rows rows is
# one page stream with the selection as a mask over its lanes; the tile that
# holds the decode rows runs its blocks of 8 with their words in SMEM.
# 264 = 11 x 24 (the served width: 4,632 = 193 x 24), 120 = 3 x 40; a chunk
# from token 300 walks 34 pages of 16: two mask words a row and KV head.
# ---------------------------------------------------------------------------

_DENSE_PAGES = 4        # a row of up to this many pages reads every page


def _selection(pick, limits, PS, MP, Hkv, rng):
    """[N, Hkv, MP] bool: the pages each row and KV head reads. A live row
    always reads one live page at least (the model forces its first and its
    last blocks)."""
    N = limits.shape[0]
    page = np.arange(MP)[None, None, :]
    hi = np.maximum(-(-limits // PS) - 1, 0)[:, None, None]
    forced = (page == 0) | ((page >= hi - 1) & (page <= hi))
    if pick == "forced":
        sel = np.broadcast_to(forced, (N, Hkv, MP)).copy()
    elif pick == "disjoint":    # neighbours read ONE page each, another each
        own = (np.arange(N)[:, None] + np.arange(Hkv)[None, :])[:, :, None]
        sel = page == own % (hi + 1)
    elif pick == "hole":        # every page but one that lies on every walk
        sel = np.broadcast_to(page != 1, (N, Hkv, MP)).copy()
    else:                       # learned blocks past the dense length
        sel = forced | (rng.random((N, Hkv, MP)) < 0.4) \
            | (hi < _DENSE_PAGES)
    return sel


def _select_case(N, B, pstart, plen, pick, *, groups=2, Hkv=2, D=32, PS=16,
                 dtype=jnp.float32, decode_last=False, seed=61, tile=None):
    """_wide_case's packed rows through the SELECTING ragged entry (a tile
    of ``tile`` rows where given, else what the shapes give), and the dense
    jnp reference under the same selection (None with ``tile`` given: the
    caller has it)."""
    from aws_k8s_ansible_provisioner_tpu.ops import sparse_attention as sa

    C, pslot, Hq = N - B, 2, Hkv * groups
    S = -(-(pstart + C + 1) // PS) * PS
    dense, pool, table = _paged_layout(B=B, S=S, Hkv=Hkv, D=D, PS=PS,
                                       seed=seed, dtype=dtype)
    nan_page = pool["k"].shape[1]
    pool = _with_nan_page(pool) if dtype == jnp.float32 else pool
    lengths = np.resize(np.asarray([1, S, 0, PS, 2 * PS + 1, S - 3, 2, S],
                                   np.int32), B)
    j = np.arange(C)
    dec = np.where(np.arange(B) == pslot, 0, lengths)
    chunk = np.where(j < plen, pstart + j + 1, 0)
    limits = np.concatenate([chunk, dec] if decode_last else [dec, chunk]
                            ).astype(np.int32)
    rows_of = np.concatenate([np.full(C, pslot), np.arange(B)] if decode_last
                             else [np.arange(B), np.full(C, pslot)])
    rng = np.random.default_rng(seed)
    sel = _selection(pick, limits, PS, S // PS, Hkv, rng)
    q = jax.random.normal(jax.random.PRNGKey(seed + 1), (N, Hq, D), dtype)
    ref = None if tile else np.asarray(jax.vmap(
        lambda q1, k1, v1, l1, s1: sa._attend_rows(
            q1[None], k1, v1, l1[None], s1[None], PS)[0])(
                q, dense["k"][0][rows_of], dense["v"][0][rows_of],
                jnp.asarray(limits), jnp.asarray(sel)), np.float32)
    tab, rmap = _slot_rows(np.asarray(table), rows_of, limits, nan_page) \
        if dtype == jnp.float32 else (table, jnp.asarray(rows_of, jnp.int32))
    widths = []
    real = pa._tile_rows
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "_tile_rows", lambda *a: widths.append(
            real(*a) if tile is None else tile) or widths[-1])
        out = pa.ragged_attend_pallas_paged_select.__wrapped__(
            q, pool["k"], pool["v"], jnp.asarray(limits), jnp.int32(0), tab,
            rmap, sa.as_bits(jnp.asarray(sel)), interpret=True, bblock=8)
    return np.asarray(out, np.float32), ref, limits, widths[0]


_SELECT_GRID = [
    # id, N, B, pstart (chunk_off), plen, pick, tile, kwargs
    ("n264-t24-under-the-dense-length", 264, 24, 0, 40, "learned", 24, {}),
    ("n264-t24-straddles-the-dense-length", 264, 24, 50, 240, "learned", 24,
     {}),
    ("n264-t24-disjoint-pages", 264, 24, 300, 240, "disjoint", 24, {}),
    ("n264-t24-forced-blocks-only", 264, 24, 300, 240, "forced", 24, {}),
    ("n264-t24-a-page-no-row-chose", 264, 24, 300, 240, "hole", 24, {}),
    ("n264-t24-part-dead-and-dead-tiles", 264, 24, 70, 100, "learned", 24,
     {}),
    ("n264-t24-mid-page-off", 264, 24, 37, 230, "learned", 24, {}),
    ("n120-t40-decode-rows-beside-chunk-rows", 120, 8, 70, 112, "learned",
     40, {}),
    ("n120-t40-decode-rows-last", 120, 8, 70, 112, "learned", 40,
     {"decode_last": True}),
    ("n264-t24-mha", 264, 24, 70, 200, "learned", 24, {"groups": 1}),
    ("n264-t24-groups8", 264, 24, 70, 200, "learned", 24,
     {"groups": 8, "Hkv": 1}),
    ("n264-t24-groups16", 264, 24, 70, 200, "learned", 24, {"groups": 16}),
    ("n264-t24-bf16", 264, 24, 70, 240, "learned", 24,
     {"dtype": jnp.bfloat16, "groups": 16}),
]


@pytest.mark.parametrize("N,B,pstart,plen,pick,tile,kw",
                         [c[1:] for c in _SELECT_GRID],
                         ids=[c[0] for c in _SELECT_GRID])
def test_ragged_select_wide_tile_parity(N, B, pstart, plen, pick, tile, kw):
    """Against the dense reference under the same selection AND against the
    same call in blocks of 8 (the parent's program: every block's words in
    SMEM, a sharing block row-major), output for output."""
    out, ref, limits, width = _select_case(N, B, pstart, plen, pick, **kw)
    assert width == tile
    by8, _, _, _ = _select_case(N, B, pstart, plen, pick, tile=8, **kw)
    live = limits > 0
    assert np.isfinite(out).all()
    tol = 2e-2 if kw.get("dtype") == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out[live], ref[live], rtol=tol, atol=tol)
    np.testing.assert_allclose(out, by8, rtol=tol, atol=tol)
    assert np.array_equal(out[~live], np.zeros_like(out[~live]))


def test_ragged_select_holds_in_smem_the_words_its_blocks_read():
    """With a wide tile the SMEM operand is the words of the steps that run
    block by block (one a slot at most), the lanes operand every step's."""
    seen = {}
    real = pa._paged_flash_db
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "_paged_flash_db", lambda *a, **kw: (
            seen.update(kw), real(*a, **kw))[1])
        _select_case(264, 24, 300, 200, "learned")
    assert seen["lanebits"].shape == (11, 2, 2, 24 * 2)
    assert seen["bits"].shape == (11, 24, 2, 2)     # 24 slots >= 11 steps
    at = np.asarray(seen["bits_at"])
    assert at[0] == 0 and (np.asarray(seen["wide"])[1:] != -1).all()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "_paged_flash_db", lambda *a, **kw: (
            seen.update(kw), real(*a, **kw))[1])
        _select_case(120, 2, 70, 112, "learned", decode_last=True)
    # 2 slots (+ the dead rows' poisoned one): 3 steps' words at most, the
    # step that holds the decode rows is the LAST and its words come first
    assert seen["bits"].shape == (3, 40, 2, 1)
    assert list(np.asarray(seen["wide"]) == -1) == [False, False, True]
    assert int(np.asarray(seen["bits_at"])[2]) == 0


# ---------------------------------------------------------------------------
# The decode kernel across page sizes, groupings, lengths, layers, dtypes —
# and the row writers against the XLA scatter
# ---------------------------------------------------------------------------


def _decode_case(lengths, *, Hq=4, Hkv=2, D=32, S=128, PS=32, L=2, layer=0,
                 bb=1, seed=0, dtype=jnp.float32, pool=None):
    """(kernel output, XLA reference) for one decode call over a permuted
    pool; ``pool`` overrides the generated (dense, pool, table) triple."""
    B = len(lengths)
    dense, pl_, table = pool or _paged_layout(B=B, S=S, Hkv=Hkv, D=D, L=L,
                                              PS=PS, seed=seed, dtype=dtype)
    q = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, 1, Hq, D), dtype)
    lengths = jnp.asarray(lengths, jnp.int32)
    ref = decode_attend(q, dense["k"][layer], dense["v"][layer], lengths)
    out = pa.decode_attend_pallas_paged(q, pl_["k"], pl_["v"], lengths,
                                        jnp.int32(layer), table,
                                        interpret=True, bblock=bb)
    return out, ref


def _assert_close(out, ref, tol=2e-5):
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("ps", [16, 32, 64])
def test_decode_parity_across_page_sizes(ps):
    _assert_close(*_decode_case([1, 128, 37, 64], PS=ps))


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_decode_parity_across_gqa_groupings(groups):
    """groups = 1 is multi-head attention (OLMoE: one query head a KV
    head); 2 is the Qwen3-0.6B family (16 over 8); 4 is Qwen3-8B's."""
    _assert_close(*_decode_case([5, 64, 33], Hq=4 * groups, Hkv=4, D=16,
                                S=64))


@pytest.mark.parametrize("length", [0, 1, 31, 32, 33, 128])
def test_decode_parity_at_page_edges(length):
    """One slot at each edge of a 32-row page (empty, one token, last row
    of a page, exactly a page, first row of the next, the full window)
    beside a mid-page neighbour. A length-0 slot is a dead row: nothing is
    fetched and its output is exactly zero."""
    out, ref = _decode_case([length, 70])
    if length == 0:
        assert not np.asarray(out[0]).any()
        out, ref = out[1:], ref[1:]
    _assert_close(out, ref)


def test_decode_masks_stale_rows_inside_a_live_page():
    """Rows past a slot's length that share its last live page must not
    influence the output: poison them and compare."""
    lengths = [5, 17, 40]
    dense, pool, table = _paged_layout(B=3, S=64, seed=7)
    stale = (jnp.arange(64)[None, None, None, :, None]
             >= jnp.asarray(lengths)[None, :, None, None, None])
    ppool = _to_pool({"k": jnp.where(stale, 1e4, dense["k"]),
                      "v": jnp.where(stale, -1e4, dense["v"])},
                     np.asarray(table), 32)
    out, _ = _decode_case(lengths, S=64, seed=7, pool=(dense, pool, table))
    out_p, _ = _decode_case(lengths, S=64, seed=7,
                            pool=(dense, ppool, table))
    _assert_close(out, out_p)


def test_decode_bf16_inputs_fp32_accumulation():
    out, ref = _decode_case([9, 64, 33], Hq=8, Hkv=4, D=64, S=64,
                            dtype=jnp.bfloat16)
    assert out.dtype == jnp.bfloat16
    _assert_close(out, ref, tol=2e-2)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_decode_reads_the_indexed_layer(layer):
    _assert_close(*_decode_case([3, 64, 17, 40], S=64, L=3, layer=layer,
                                seed=3))


def test_decode_block_size_the_batch_does_not_divide():
    """B = 6 with bblock = 4: the block size falls back to a divisor of the
    batch instead of crashing or dropping slots."""
    out, ref = _decode_case([1, 64, 7, 33, 12, 50], S=64, bb=4, seed=9)
    assert out.shape[0] == 6
    _assert_close(out, ref)


def _assert_pool_equal(got, want):
    """int8/bf16 rows bit-for-bit; scale leaves to 1 ulp (a compiled
    program's fusion may round the division differently)."""
    for name in want:
        if name in ("ks", "vs"):
            np.testing.assert_allclose(np.asarray(got[name]),
                                       np.asarray(want[name]), rtol=1e-6,
                                       atol=0, err_msg=name)
        else:
            np.testing.assert_array_equal(np.asarray(got[name]),
                                          np.asarray(want[name]),
                                          err_msg=name)


def _write_case(rows, quant, S=64, PS=32):
    """(kernel result, scatter result) of one new row a slot at ``rows``
    into layer 1 of a non-empty permuted pool."""
    B = len(rows)
    _, pool, table = _paged_layout(B=B, S=S, PS=PS, quant=quant, seed=5)
    if quant:
        # the engine's int8 layout: scale leaves lane-padded past the page
        pad = [(0, 0)] * 3 + [(0, kvp.scale_lanes(PS) - PS)]
        pool["ks"] = jnp.pad(pool["ks"], pad)
        pool["vs"] = jnp.pad(pool["vs"], pad)
    new = jax.random.normal(jax.random.PRNGKey(6), (B, 2, 32), jnp.float32)
    rows = jnp.asarray(rows, jnp.int32)
    layer = jnp.int32(1)
    want = kvp.write_token_layer_paged(pool, layer, rows, table,
                                       new[:, None], 2 * new[:, None], PS)
    if quant:
        gk, gks = pa.cache_write_row_quant_paged(
            pool["k"], pool["ks"], new, rows, table, layer, interpret=True)
        gv, gvs = pa.cache_write_row_quant_paged(
            pool["v"], pool["vs"], 2 * new, rows, table, layer,
            interpret=True)
        got = {"k": gk, "v": gv, "ks": gks, "vs": gvs}
    else:
        got = {"k": pa.cache_write_row_paged(pool["k"], new, rows, table,
                                             layer, interpret=True),
               "v": pa.cache_write_row_paged(pool["v"], 2 * new, rows,
                                             table, layer, interpret=True)}
    return pool, got, want


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("rows", [[0, 7, 8, 9], [15, 16, 63, 1]])
def test_row_write_matches_scatter(rows, quant):
    """Rows on both sides of the kernel's 8-row (int8: 32-row) block edges
    and of a page edge land exactly where the XLA scatter puts them, and
    nothing else in the pool moves."""
    pool, got, want = _write_case(rows, quant)
    _assert_pool_equal(got, want)
    assert not np.array_equal(np.asarray(got["k"]), np.asarray(pool["k"]))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_row_write_drops_rows_past_the_window(quant):
    """Surplus mid-horizon writes (row >= the window) and suppressed rows
    (-1) are DROPPED — the pool is untouched by them — while an in-window
    neighbour still lands."""
    pool, got, want = _write_case([64, 200, -1, 10], quant)
    _assert_pool_equal(got, want)
    only = _write_case([-1, -1, -1, 10], quant)[1]
    np.testing.assert_array_equal(np.asarray(got["k"]), np.asarray(only["k"]))
