"""Pallas decode-attention kernel parity tests (interpret mode on CPU).

The kernel is the framework's hot loop (SURVEY.md §7 hard part #1); these
tests pin it bit-for-bit (fp32 tolerance) against the XLA reference
implementation in ops/attention.py across raggedness, GQA grouping, and
multi-chunk streaming."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aws_k8s_ansible_provisioner_tpu.ops.attention import decode_attend
from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
    decode_attend_pallas,
)


def _inputs(B=4, S=128, Hq=4, Hkv=2, D=32, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, 1, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, S + 1)
    return q, k, v, lengths


@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_parity_vs_xla_across_chunks(chunk):
    q, k, v, lengths = _inputs()
    ref = decode_attend(q, k, v, lengths)
    out = decode_attend_pallas(q, k, v, lengths, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_parity_gqa_grouping():
    # Qwen3-0.6B shape family: 16 query heads over 8 KV heads (G=2).
    q, k, v, lengths = _inputs(B=2, S=64, Hq=16, Hkv=8, D=64)
    ref = decode_attend(q, k, v, lengths)
    out = decode_attend_pallas(q, k, v, lengths, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_parity_mha_no_grouping():
    q, k, v, lengths = _inputs(B=2, S=64, Hq=4, Hkv=4, D=16)
    ref = decode_attend(q, k, v, lengths)
    out = decode_attend_pallas(q, k, v, lengths, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ragged_extremes():
    # length=1 (just-prefilled single token) and length=S (full window)
    q, k, v, _ = _inputs(B=3, S=96, Hq=4, Hkv=2, D=32)
    lengths = jnp.array([1, 96, 37])
    ref = decode_attend(q, k, v, lengths)
    out = decode_attend_pallas(q, k, v, lengths, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_empty_slot_yields_finite_values():
    # Inactive slots (length 0) must produce garbage-but-finite output, never
    # NaN that could poison debugging or downstream reductions.
    q, k, v, _ = _inputs(B=2, S=64, Hq=4, Hkv=2, D=32)
    lengths = jnp.array([0, 10])
    out = decode_attend_pallas(q, k, v, lengths, chunk=32, interpret=True)
    assert np.isfinite(np.asarray(out)).all()


def test_masking_ignores_stale_cache_rows():
    # Rows beyond `length` must not influence the output: poison them.
    q, k, v, lengths = _inputs(B=2, S=64, Hq=4, Hkv=2, D=32)
    lengths = jnp.array([5, 17])
    valid = jnp.arange(64)[None, None, :, None] < lengths[:, None, None, None]
    k_poison = jnp.where(valid, k, 1e4)
    v_poison = jnp.where(valid, v, -1e4)
    out = decode_attend_pallas(q, k, v, lengths, chunk=32, interpret=True)
    out_p = decode_attend_pallas(q, k_poison, v_poison, lengths, chunk=32,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_p),
                               rtol=2e-5, atol=2e-5)


def test_bf16_inputs_fp32_accumulation():
    q, k, v, lengths = _inputs(B=2, S=64, Hq=8, Hkv=4, D=64,
                               dtype=jnp.bfloat16)
    ref = decode_attend(q, k, v, lengths)
    out = decode_attend_pallas(q, k, v, lengths, chunk=32, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_resolve_impl_auto_is_xla_on_cpu():
    from aws_k8s_ansible_provisioner_tpu.ops.attention import resolve_impl

    assert resolve_impl("auto") in ("xla", "pallas")
    assert resolve_impl("xla") == "xla"
    assert resolve_impl("pallas") == "pallas"


def test_non_divisible_cache_len_picks_divisor_chunk():
    # e.g. --max-cache-len 96 with default chunk 256: must not crash
    q, k, v, _ = _inputs(B=2, S=96, Hq=4, Hkv=2, D=32)
    lengths = jnp.array([40, 96])
    ref = decode_attend(q, k, v, lengths)
    out = decode_attend_pallas(q, k, v, lengths, chunk=256, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Carry-path kernels: layer-indexed attend + in-place row write
# ---------------------------------------------------------------------------


def _full_cache(L=3, B=4, S=64, Hkv=2, D=32, seed=3, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    ck = jax.random.normal(ks[0], (L, B, Hkv, S, D), dtype)
    cv = jax.random.normal(ks[1], (L, B, Hkv, S, D), dtype)
    return ck, cv


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_layer_indexed_attend_matches_sliced_reference(layer):
    from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
        decode_attend_pallas_layer,
    )

    ck, cv = _full_cache()
    q, _, _, lengths = _inputs(B=4, S=64, Hq=4, Hkv=2, D=32)
    ref = decode_attend(q, ck[layer], cv[layer], lengths)
    out = decode_attend_pallas_layer(q, ck, cv, lengths, jnp.int32(layer),
                                     chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("rows", [[0, 7, 8, 9], [15, 16, 63, 1]])
def test_cache_write_row_matches_scatter(rows):
    """The aliased write kernel must land each slot's row exactly where the
    functional scatter would, including rows on 8-row block boundaries."""
    from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
        cache_write_row,
    )
    from aws_k8s_ansible_provisioner_tpu.serving import kv_cache as kvc

    L, B, S, Hkv, D = 3, 4, 64, 2, 32
    ck, cv = _full_cache(L=L, B=B, S=S, Hkv=Hkv, D=D)
    lengths = jnp.asarray(rows, jnp.int32)
    knew = jax.random.normal(jax.random.PRNGKey(9), (B, 1, Hkv, D))
    layer = jnp.int32(1)

    want = kvc.write_token_layer({"k": ck, "v": cv}, layer, lengths,
                                 knew, knew)
    got_k = cache_write_row(ck, knew[:, 0], lengths, layer, interpret=True)
    got_v = cache_write_row(cv, knew[:, 0], lengths, layer, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want["k"]))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want["v"]))


def test_cache_write_row_drops_out_of_window_rows():
    """Rows outside [0, S) are DROPPED — the scatter mode='drop' contract.
    Surplus mid-horizon writes (row == S) and sequence-parallel non-owner
    shards (negative local rows) both rely on it."""
    from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
        cache_write_row,
    )

    L, B, S, Hkv, D = 2, 3, 16, 2, 32
    ck, _ = _full_cache(L=L, B=B, S=S, Hkv=Hkv, D=D)
    lengths = jnp.asarray([S, 3, -5], jnp.int32)
    knew = jax.random.normal(jax.random.PRNGKey(4), (B, Hkv, D))
    out = cache_write_row(ck, knew, lengths, jnp.int32(0), interpret=True)
    np.testing.assert_array_equal(np.asarray(out[:, 0]),    # dropped (row S)
                                  np.asarray(ck[:, 0]))
    np.testing.assert_allclose(np.asarray(out[0, 1, :, 3]),  # written
                               np.asarray(knew[1]))
    np.testing.assert_array_equal(np.asarray(out[:, 2]),    # dropped (neg)
                                  np.asarray(ck[:, 2]))


# ---------------------------------------------------------------------------
# Batch-blocked decode (PALLAS_DECODE_BBLOCK — round 5 grid-overhead lever)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bb", [2, 4])
@pytest.mark.parametrize("chunk", [32, 64])
def test_bblock_parity_vs_unblocked(bb, chunk):
    """BB slots per grid step must be bit-equal (fp32 tol) to the per-slot
    kernel across ragged lengths — incl. blocks mixing long and short slots
    (the conservative max-length clamp must not leak dead rows)."""
    from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
        decode_attend_pallas_layer)

    q, k, v, _ = _inputs(B=8, S=128)
    lengths = jnp.asarray([1, 128, 7, 64, 33, 97, 2, 128], jnp.int32)
    ck, cv = k[None], v[None]
    ref = decode_attend_pallas_layer(q, ck, cv, lengths, jnp.int32(0),
                                     chunk=chunk, interpret=True)
    got = decode_attend_pallas_layer(q, ck, cv, lengths, jnp.int32(0),
                                     chunk=chunk, interpret=True, bblock=bb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bblock_parity_quant():
    from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
        decode_attend_pallas_layer)
    from aws_k8s_ansible_provisioner_tpu.serving import kv_cache as kvc

    q, k, v, _ = _inputs(B=8, S=128)
    lengths = jnp.asarray([5, 128, 70, 1, 99, 128, 13, 40], jnp.int32)
    kq, ks = kvc.quantize_rows(k[None])
    vq, vs = kvc.quantize_rows(v[None])
    ref = decode_attend_pallas_layer(q, kq, vq, lengths, jnp.int32(0),
                                     chunk=64, interpret=True,
                                     cache_ks=ks, cache_vs=vs)
    got = decode_attend_pallas_layer(q, kq, vq, lengths, jnp.int32(0),
                                     chunk=64, interpret=True,
                                     cache_ks=ks, cache_vs=vs, bblock=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bblock_parity_sliding_window():
    from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
        decode_attend_pallas_layer)

    q, k, v, _ = _inputs(B=4, S=128)
    lengths = jnp.asarray([20, 128, 64, 100], jnp.int32)
    ref = decode_attend_pallas_layer(q, k[None], v[None], lengths,
                                     jnp.int32(0), chunk=32, interpret=True,
                                     window=48)
    got = decode_attend_pallas_layer(q, k[None], v[None], lengths,
                                     jnp.int32(0), chunk=32, interpret=True,
                                     window=48, bblock=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bblock_non_divisible_batch_shrinks():
    """bblock larger than a divisor of B must fall back to the largest
    divisor, never crash or misindex."""
    from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
        decode_attend_pallas_layer)

    q, k, v, _ = _inputs(B=6, S=64)
    lengths = jnp.asarray([3, 64, 17, 50, 1, 64], jnp.int32)
    ref = decode_attend_pallas_layer(q, k[None], v[None], lengths,
                                     jnp.int32(0), chunk=32, interpret=True)
    got = decode_attend_pallas_layer(q, k[None], v[None], lengths,
                                     jnp.int32(0), chunk=32, interpret=True,
                                     bblock=4)   # 6 % 4 != 0 -> bb=3
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Double-buffered paged decode (r6): explicit async page prefetch, bb slots
# per grid step. Parity bar: the XLA reference attention (ops/attention.py)
# at f32 accumulate, across bb in {1, 4, 8} x {bf16, int8} x {decode, spec}.
# ---------------------------------------------------------------------------


def _paged_layout(B=8, S=128, Hkv=2, D=32, L=2, PS=32, quant=False, seed=21):
    """Dense [L,B,Hkv,S,D] cache + an equivalent PERMUTED page pool/table
    (physical page order shuffled so a table-indexing bug cannot hide
    behind an identity layout)."""
    from aws_k8s_ansible_provisioner_tpu.serving import kv_cache as kvc

    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    ck = jax.random.normal(ks[0], (L, B, Hkv, S, D), jnp.float32)
    cv = jax.random.normal(ks[1], (L, B, Hkv, S, D), jnp.float32)
    dense = {"k": ck, "v": cv}
    if quant:
        qk, sk = kvc.quantize_rows(ck)
        qv, sv = kvc.quantize_rows(cv)
        dense = {"k": qk, "v": qv, "ks": sk, "vs": sv}
    n_pages_per_slot = S // PS
    P = B * n_pages_per_slot + 1          # +1: scratch page 0 stays unused
    rng = np.random.default_rng(seed)
    perm = rng.permutation(B * n_pages_per_slot) + 1
    table = perm.reshape(B, n_pages_per_slot).astype(np.int32)
    pool = {}
    for name, arr in dense.items():
        a = np.asarray(arr)
        if a.ndim == 5:
            pooled = np.zeros((L, P, Hkv, PS, D), a.dtype)
        else:
            pooled = np.zeros((L, P, Hkv, PS), a.dtype)
        for b in range(B):
            for c in range(n_pages_per_slot):
                sl = a[:, b, :, c * PS:(c + 1) * PS]
                pooled[:, table[b, c]] = sl
        pool[name] = jnp.asarray(pooled)
    return dense, pool, jnp.asarray(table)


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
        from aws_k8s_ansible_provisioner_tpu.serving import kv_cache as kvc

        ck = kvc.dequantize(dense["k"][0], dense["ks"][0])
        cv = kvc.dequantize(dense["v"][0], dense["vs"][0])
    else:
        ck, cv = dense["k"][0], dense["v"][0]
    ref = decode_attend(q, ck, cv, lengths)
    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa

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
    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa

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
    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa

    dense, pool, table = _paged_layout(quant=quant, seed=41)
    B, R, Hq, D = 8, 3, 4, 32
    q = jax.random.normal(jax.random.PRNGKey(3), (B, R, Hq, D))
    lengths = jnp.asarray([2, 17, 124, 0, 60, 93, 31, 8], jnp.int32)
    kw = dict(cache_ks=dense["ks"], cache_vs=dense["vs"]) if quant else {}
    ref = pa.decode_attend_pallas_spec(q, dense["k"], dense["v"], lengths,
                                       jnp.int32(0), chunk=32,
                                       interpret=True, **kw)
    pkw = dict(pool_ks=pool["ks"], pool_vs=pool["vs"]) if quant else {}
    out = pa.decode_attend_pallas_spec_paged(q, pool["k"], pool["v"],
                                             lengths, jnp.int32(0), table,
                                             interpret=True, bblock=bb,
                                             **pkw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bb", [1, 4])
def test_paged_db_sliding_window_parity(bb):
    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa

    dense, pool, table = _paged_layout(seed=43)
    q = jax.random.normal(jax.random.PRNGKey(4), (8, 1, 4, 32))
    lengths = jnp.asarray([20, 128, 64, 100, 3, 47, 128, 77], jnp.int32)
    W = 48
    # reference: dense layer kernel with the same window semantics
    ref = pa.decode_attend_pallas_layer(q, dense["k"], dense["v"], lengths,
                                        jnp.int32(0), chunk=32,
                                        interpret=True, window=W)
    out = pa.decode_attend_pallas_paged(q, pool["k"], pool["v"], lengths,
                                        jnp.int32(0), table, interpret=True,
                                        window=W, bblock=bb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_db_poisoned_dead_pages_ignored():
    """Pages beyond every slot's live range must never be fetched NOR leak
    into the output: poison them with huge values and compare."""
    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa

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
    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa
    from aws_k8s_ansible_provisioner_tpu.serving import kv_cache as kvc

    C, pslot, S, PS, Hq, D = 16 if B == 8 else 20, 2, 128, 32, 4, 32
    dense, pool, table = _paged_layout(B=B, S=S, PS=PS, quant=quant, seed=53)
    tab = np.asarray(table)
    # one more page, all NaN, that no table names: an out-of-range id clamps
    # onto it in interpret mode, so a fetch through a dead row's entry shows
    pool = {n: jnp.concatenate([a, jnp.full_like(a[:, :1], jnp.nan)
                                if a.dtype == jnp.float32
                                else jnp.full_like(a[:, :1], 127)], axis=1)
            for n, a in pool.items()}
    lengths = np.asarray([1, 128, 0, 64, 33, 97, 2, 128][:B], np.int32)
    j = np.arange(C)
    limits = np.concatenate([np.where(np.arange(B) == pslot, 0, lengths),
                             np.where(j < plen, pstart + j + 1, 0)])
    rows_of = np.concatenate([np.arange(B), np.full(C, pslot)])
    row_tables = tab[rows_of].copy()
    row_tables[limits == 0] = 10_000          # dead rows: never a page id
    N = B + C
    q = jax.random.normal(jax.random.PRNGKey(7), (N, Hq, D))

    ck, cv = dense["k"][0][rows_of], dense["v"][0][rows_of]
    if quant:
        ck = kvc.dequantize(ck, dense["ks"][0][rows_of])
        cv = kvc.dequantize(cv, dense["vs"][0][rows_of])
    ref = decode_attend(q[:, None], ck, cv, jnp.asarray(limits),
                        window=window)[:, 0]
    pkw = dict(pool_ks=pool["ks"], pool_vs=pool["vs"]) if quant else {}
    out = pa.ragged_attend_pallas_paged(
        q, pool["k"], pool["v"], jnp.asarray(limits), jnp.int32(0),
        jnp.asarray(row_tables), interpret=True, window=window, bblock=bb,
        **pkw)
    out, live = np.asarray(out), limits > 0
    assert live.sum() == B - 1 + plen
    tol = 4e-2 if quant else 2e-5
    np.testing.assert_allclose(out[live], np.asarray(ref)[live],
                               rtol=tol, atol=tol)
    assert np.array_equal(out[~live], np.zeros_like(out[~live]))
