"""The decode program hands the paged attention kernel its rows in order of
length (ops/attention._length_order, PR 33): a grid step's ``bblock`` rows
walk the pages of neighbours, not of the block's longest stranger. The order
is a permutation in plain XLA ops AROUND the kernel call, so tier-1 pins it
BITWISE: through ``make_decode_attend_carry_paged`` (interpret mode,
``impl="pallas"``) the context equals ``decode_attend_pallas_paged`` called on
the same rows in slot order, the K/V rows land where the slot-order scatter
puts them, ties keep slot order, and a mesh orders each shard's own rows.
The host's witness — ``attn_pages_live`` / ``attn_pages_walked`` /
``attn_pages_copied`` on the dispatch record and /metrics — is cut exactly
as the device cuts its blocks; since PR 45 a row copies only the pages it
holds, so copied == live where it used to be walked.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp
from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa
from aws_k8s_ansible_provisioner_tpu.ops.attention import (
    _length_order, decode_attend, make_decode_attend_carry_paged)

PS, MP, HKV, D, L = 8, 24, 2, 16, 2     # page, pages a slot, KV heads, ...
LAYER = 1


def _lengths(slots: int, seed: int) -> np.ndarray:
    """Ragged lengths whose FIRST slot-order block (of 4 or of 8) holds a
    one-page row, an idle-like row of length 0 and a row that fills all 24
    pages once its token is written."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, MP * PS - 1, slots).astype(np.int32)
    lens[:4] = [PS - 2, 0, MP * PS - 1, 5 * PS]
    return lens


def _pool(slots: int, quant: bool, seed: int, dp: int = 1):
    """A random pool, and a table of GLOBAL page ids: each dp group's slots
    draw from their group's partition of the page axis, whose first page is
    the group's scratch page."""
    rng = np.random.default_rng(seed)
    per = slots // dp * MP + 1                  # pages a partition
    shape = (L, dp * per, HKV, PS, D)
    if quant:
        pool = {n: jnp.asarray(rng.integers(-127, 128, shape, dtype=np.int8))
                for n in ("k", "v")}
        for n in ("ks", "vs"):
            pool[n] = jnp.asarray(rng.random(
                shape[:3] + (kvp.scale_lanes(PS),), dtype=np.float32) / 64)
    else:
        pool = {n: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                for n in ("k", "v")}
    table = np.concatenate(
        [g * per + 1 + rng.permutation(per - 1).reshape(slots // dp, MP)
         for g in range(dp)]).astype(np.int32)
    return pool, jnp.asarray(table)


def _inputs(slots: int, groups: int, seed: int):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (slots, 1, HKV * groups, D), jnp.bfloat16)
    k = jax.random.normal(keys[1], (slots, 1, HKV, D), jnp.bfloat16)
    v = jax.random.normal(keys[2], (slots, 1, HKV, D), jnp.bfloat16)
    return q, k, v


def _slot_order(q, pool, lens, table, bb: int, window: int):
    """What the parent computed: the kernel on the rows as the slots stand."""
    skw = dict(pool_ks=pool["ks"], pool_vs=pool["vs"]) if "ks" in pool else {}
    return pa.decode_attend_pallas_paged(
        q, pool["k"], pool["v"], lens + 1, jnp.int32(LAYER), table,
        interpret=True, window=window, bblock=bb, **skw)


def _dense_reference(q, pool, lens, table, window: int):
    dense = kvp.gather_layer_dense(pool, jnp.int32(LAYER), table)
    ck, cv = dense["k"], dense["v"]
    if "ks" in dense:
        ck = kvp.dequantize(ck, dense["ks"], dtype=jnp.float32)
        cv = kvp.dequantize(cv, dense["vs"], dtype=jnp.float32)
    return decode_attend(q.astype(jnp.float32), ck, cv, lens + 1,
                         window=window)


def _assert_pool_is_the_scatter(got, pool, lens, table, k, v):
    """(b) the rows written are the slot-order ones."""
    want = kvp.write_token_layer_paged(pool, jnp.int32(LAYER), lens, table,
                                       k, v, PS)
    for name, w in want.items():
        g, w = (np.asarray(a, np.float32) for a in (got[name], w))
        if name in ("ks", "vs"):
            np.testing.assert_allclose(g, w, rtol=1e-6, err_msg=name)
        elif "ks" in want:
            # quantize_rows' contract: the kernel's division may round one
            # int8 step from XLA's
            assert np.abs(g - w).max() <= 1, name
        else:
            np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("slots,bb", [(16, 4), (16, 8), (32, 4), (32, 8)])
@pytest.mark.parametrize("quant,window,groups", [
    (False, 0, 2), (True, 0, 1), (False, 5 * PS + 3, 8), (True, 3 * PS, 2),
    (False, 2 * PS, 1), (True, 0, 8)],
    ids=lambda x: str(x))
def test_context_is_bitwise_the_slot_order_call(slots, bb, quant, window,
                                                groups):
    lens = jnp.asarray(_lengths(slots, seed=slots + bb))
    pool, table = _pool(slots, quant, seed=groups)
    q, k, v = _inputs(slots, groups, seed=window + bb)
    assert _length_order(lens, table, 1, bb), "the order is taken"
    attend = make_decode_attend_carry_paged(lens, table, impl="pallas",
                                            window=window, bblock=bb)
    ctx, (got, _) = jax.jit(attend)(q, k, v, (pool, jnp.int32(LAYER)))

    _assert_pool_is_the_scatter(got, pool, lens, table, k, v)
    want = _slot_order(q, got, lens, table, bb, window)
    np.testing.assert_array_equal(np.asarray(ctx, np.float32),
                                  np.asarray(want, np.float32))
    ref = _dense_reference(q, got, lens, table, window)
    np.testing.assert_allclose(np.asarray(ctx, np.float32), np.asarray(ref),
                               atol=0.03, rtol=0.03)


@pytest.mark.parametrize("lens", [
    np.full(16, 37), np.zeros(16), np.arange(16),
    np.array([9, 3, 9, 3, 0, 9, 3, 0] * 2), np.arange(16)[::-1]],
    ids=["equal", "zeros", "ascending", "ties", "descending"])
def test_ties_keep_slot_order(lens):
    """(c) a STABLE ascending order: equal lengths give the identity, rows of
    one length keep their slot order, and the pieces agree with one another
    (``inverse`` undoes ``order``; ``limits`` and the table are in it)."""
    lens = np.asarray(lens, np.int32)
    table = np.arange(16 * MP, dtype=np.int32).reshape(16, MP)
    order, inverse, limits, tab = (np.asarray(a) for a in _length_order(
        jnp.asarray(lens), jnp.asarray(table), 1, 4))
    np.testing.assert_array_equal(order, np.argsort(lens, kind="stable"))
    if len(set(lens.tolist())) == 1 or (np.diff(lens) >= 0).all():
        np.testing.assert_array_equal(order, np.arange(16))
    np.testing.assert_array_equal(order[inverse], np.arange(16))
    np.testing.assert_array_equal(limits, lens[order] + 1)
    np.testing.assert_array_equal(tab, table[order])


@pytest.mark.parametrize("slots,bb,dp,ordered", [
    (8, 8, 1, False), (16, 1, 1, False), (16, 8, 2, False), (6, 4, 1, True)],
    ids=["one-block", "one-row-blocks", "one-block-a-shard", "blocks-of-3"])
def test_one_block_or_one_row_blocks_emit_nothing(slots, bb, dp, ordered):
    """A batch that is one block, and blocks of one row, cannot be helped:
    static facts, and the program then holds no sort (6 slots at a block of
    4 resolve to blocks of 3: two blocks, ordered)."""
    lens = jnp.arange(slots, dtype=jnp.int32)
    table = jnp.zeros((slots, MP), jnp.int32)
    assert bool(_length_order(lens, table, dp, bb)) == ordered
    if dp == 1:
        attend = make_decode_attend_carry_paged(lens, table, impl="pallas",
                                                bblock=bb)
        pool, _ = _pool(slots, False, seed=0)
        q, k, v = _inputs(slots, 2, seed=0)
        text = str(jax.make_jaxpr(attend)(q, k, v, (pool, jnp.int32(0))))
        assert ("sort" in text) == ordered


@pytest.mark.parametrize("axis", ["tp", "dp"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_under_a_mesh_each_shard_orders_its_own_rows(axis, quant):
    """(d) two CPU devices: ``tp`` shards the KV heads and shares the order;
    ``dp`` shards the slots and the page axis, and each shard orders its own
    16 rows (indices local to the shard). Either way the context is bitwise
    the slot-order call's on the whole pool."""
    from aws_k8s_ansible_provisioner_tpu.config import MeshConfig
    from aws_k8s_ansible_provisioner_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    slots, bb, groups, dp = 32, 8, 2, 2 if axis == "dp" else 1
    mesh = make_mesh(MeshConfig(**{axis: 2}))
    lens = _lengths(slots, seed=7)
    lens[16:20] = lens[:4]        # the second shard's block is ragged too
    lens = jnp.asarray(lens)
    pool, table = _pool(slots, quant, seed=11, dp=dp)
    q, k, v = _inputs(slots, groups, seed=13)
    attend = make_decode_attend_carry_paged(lens, table, impl="pallas",
                                            mesh=mesh, bblock=bb)
    ctx, (got, _) = jax.jit(attend)(q, k, v, (pool, jnp.int32(LAYER)))
    got = jax.device_get(got)

    _assert_pool_is_the_scatter(got, pool, lens, table, k, v)
    want = _slot_order(q, {n: jnp.asarray(a) for n, a in got.items()}, lens,
                       table, bb, 0)
    np.testing.assert_array_equal(np.asarray(ctx, np.float32),
                                  np.asarray(want, np.float32))
    if dp == 2:
        order = np.asarray(_length_order(lens, table, dp, bb)[0])
        assert order.max() == slots // dp - 1


# ---------------------------------------------------------------------------
# (e) the host's witness: pages live and pages walked, on the dispatch record
# and on /metrics
# ---------------------------------------------------------------------------


def _engine(slots=8, bblock=4, window=0, **kw):
    from aws_k8s_ansible_provisioner_tpu.config import (
        ServingConfig, tiny_qwen3)
    from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
    from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine

    cfg = tiny_qwen3(sliding_window=window)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return Engine(cfg, params, ServingConfig(
        max_decode_slots=slots, max_cache_len=128, prefill_buckets=(16, 64),
        dtype="float32", prefix_cache=False, decode_horizon=4, page_size=16,
        decode_pipeline=1, ragged_attention=1, attention_impl="xla",
        decode_bblock=bblock, **kw))


def _walk_by_the_kernels_rule(lens, horizon, ps, num_pages, bb, window):
    """(live, walked, copied) as pallas_attention._paged_db_body walks: a
    block of ``bb`` rows, cut from the rows in stable order of length,
    visits pages [lo_min, hi_max] with a flash update for EVERY row and a
    copy for the rows whose own range holds the page (``fetches``)."""
    live = walked = copied = 0
    for s in range(horizon):
        limits = sorted(int(n) + 1 + s for n in lens)
        hi = [min(-(-n // ps), num_pages) - 1 for n in limits]
        lo = [max(n - window, 0) // ps if window else 0 for n in limits]
        live += sum(h - l + 1 for h, l in zip(hi, lo))
        for b in range(0, len(limits), bb):
            steps = range(min(lo[b:b + bb]), max(hi[b:b + bb]) + 1)
            walked += bb * len(steps)
            copied += sum(l <= c <= h for c in steps
                          for h, l in zip(hi[b:b + bb], lo[b:b + bb]))
    return live, walked, copied


@pytest.mark.parametrize("lens,window,bblock", [
    ([70] * 8, 0, 4), ([0] * 8, 0, 4), ([3, 100, 17, 64, 0, 33, 90, 15], 0, 4),
    ([3, 100, 17, 64, 0, 33, 90, 15], 40, 4),
    ([3, 100, 17, 64, 0, 33, 90, 15], 0, 8),
    ([3, 100, 17, 64, 0, 33, 90, 15], 0, 1),
    ([15, 16, 31, 32, 47, 48, 63, 64], 0, 2), ([120] * 7 + [1], 0, 4)],
    ids=["equal", "idle", "ragged", "window", "one-block", "one-row-blocks",
         "page-edges", "one-short"])
def test_the_page_counters_cut_blocks_as_the_device_does(lens, window,
                                                         bblock):
    eng = _engine(bblock=bblock, window=window)
    eng.lengths[:] = lens
    got = eng._attn_pages(horizon=4, carry_steps=4)
    live, walked, copied = _walk_by_the_kernels_rule(
        [n + 4 for n in lens], 4, 16, eng.pages_per_slot, bblock, window)
    assert got == {"attn_pages_live": live, "attn_pages_walked": walked,
                   "attn_pages_copied": copied}
    assert copied == live <= walked
    if len(set(lens)) == 1 or bblock == 1:
        assert live == walked


def test_decode_records_and_metrics_carry_the_page_counters():
    """Requests of unlike length through the engine: every plain decode
    record carries the three counters (copied == live <= walked), no other
    record does, and /metrics exports their sums by kind."""
    from aws_k8s_ansible_provisioner_tpu.serving import flightrec
    from aws_k8s_ansible_provisioner_tpu.serving.engine import Request

    eng = _engine()
    seen, orig = [], flightrec.record

    def tap(*a, **rec):
        if a[0] == "dispatch":
            seen.append(dict(rec))
        return orig(*a, **rec)

    flightrec.record = tap
    try:
        for n, out in ((50, 12), (3, 20), (20, 9)):
            eng.submit(Request(prompt_ids=[5 + i % 90 for i in range(n)],
                               max_tokens=out, ignore_eos=True))
        for _ in range(10000):
            if not eng.step():
                break
    finally:
        flightrec.record = orig
    decode = [r for r in seen if r["kind"] == "decode"]
    assert decode and len(decode) < len(seen)
    assert all(("attn_pages_live" in r) == (r["kind"] == "decode")
               for r in seen)
    for r in decode:
        assert 8 * r["horizon"] <= r["attn_pages_live"] \
            <= r["attn_pages_walked"]
        assert r["attn_pages_copied"] == r["attn_pages_live"]
    assert any(r["attn_pages_live"] < r["attn_pages_walked"] for r in decode)
    m = eng.metrics.decode_attn_pages
    assert m.value(kind="live") == sum(r["attn_pages_live"] for r in decode)
    assert m.value(kind="walked") == sum(r["attn_pages_walked"]
                                         for r in decode)
    assert m.value(kind="copied") == m.value(kind="live")
    text = eng.metrics.registry.render()
    for kind in ("live", "walked", "copied"):
        assert f'tpu_serve_decode_attn_pages_total{{kind="{kind}"}}' in text
