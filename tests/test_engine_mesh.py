"""Multi-chip SERVING correctness: Engine over a (dp, tp) mesh.

VERDICT r1 missing #3: the serving engine's mesh path (sharded params,
dp-sharded slots, tp-sharded KV heads, shard_map'd Pallas decode) was covered
by no test. These cases run on the 8-virtual-CPU-device mesh (conftest) and
assert TOKEN PARITY with a single-device engine on the same weights — the
distributed decode must be bit-identical under greedy sampling, not merely
finite. This is the scaled-down proof for Qwen3-8B TP over ICI
(SURVEY.md §7 hard part #3; reference §2.3: every parallelism capability is
net-new on the TPU side).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aws_k8s_ansible_provisioner_tpu.config import (
    MeshConfig, ServingConfig, tiny_qwen3)
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.parallel.mesh import make_mesh
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request


@pytest.fixture(scope="module")
def setup(cpu_devices):
    # heads/kv-heads/vocab sized so the tp=2 split is real (GQA preserved)
    cfg = tiny_qwen3(num_heads=4, num_kv_heads=2, vocab_size=256)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    serving = ServingConfig(weights_dtype="bf16", max_decode_slots=4, max_cache_len=64,
                            prefill_buckets=(8, 16), dtype="float32")
    return cfg, params, serving


def _mesh(dp, tp):
    return make_mesh(MeshConfig(dp=dp, tp=tp), devices=jax.devices("cpu"))


def _run_all(engine, prompts, max_tokens=8):
    reqs = [Request(prompt_ids=list(p), max_tokens=max_tokens, ignore_eos=True)
            for p in prompts]
    for r in reqs:
        engine.submit(r)
    for _ in range(10000):
        if not engine.step():
            break
    return [r.generated for r in reqs]


@pytest.mark.parametrize("dp,tp", [(2, 2), (1, 2), (4, 1), (4, 2)])
def test_mesh_engine_token_parity(setup, dp, tp):
    """dp×tp-sharded engine generates EXACTLY the single-device tokens."""
    cfg, params, serving = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist() for n in (3, 7, 12)]

    single = Engine(cfg, params, serving)
    expected = _run_all(single, prompts)

    meshed = Engine(cfg, params, serving, mesh=_mesh(dp, tp))
    got = _run_all(meshed, prompts)
    assert got == expected, f"dp={dp} tp={tp} diverged from single-device"


def test_mesh_engine_pallas_interpret_parity(setup):
    """The shard_map'd Pallas decode path (the real-TPU hot loop) in interpret
    mode must match the single-device XLA fallback token-for-token."""
    cfg, params, serving = setup
    serving_p = dataclasses.replace(serving, attention_impl="pallas")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist() for n in (4, 9)]

    single = Engine(cfg, params, serving)
    expected = _run_all(single, prompts)

    meshed = Engine(cfg, params, serving_p, mesh=_mesh(2, 2))
    got = _run_all(meshed, prompts)
    assert got == expected


def test_mesh_mixed_step_pallas_interpret_parity(setup):
    """``mixed_step`` under a pure-tp mesh through the kernels (interpret
    mode): a chunked admission beside a live stream, the table (one row a
    slot) and the row map operands of its ``shard_map`` like the lengths —
    token for token the unsharded engine's. (An int8 pool through the map
    under the mesh: tests/test_paged_kv.py's ``pallas-tp2`` cases.)"""
    cfg, params, serving = setup
    serving = dataclasses.replace(
        serving, attention_impl="pallas", decode_pipeline=1,
        ragged_attention=1, prefill_chunk=16, decode_horizon=4, page_size=32)
    rng = np.random.default_rng(5)
    live, late = (rng.integers(2, cfg.vocab_size, n).tolist()
                  for n in (4, 21))

    def run(mesh):
        eng = Engine(cfg, params, serving, mesh=mesh)
        mixed = []
        real = eng._mixed_dispatch
        eng._mixed_dispatch = lambda *a: mixed.append(1) or real(*a)
        first = eng.submit(Request(prompt_ids=live, max_tokens=30,
                                   ignore_eos=True))
        for _ in range(4):
            eng.step()
        second = eng.submit(Request(prompt_ids=late, max_tokens=6,
                                    ignore_eos=True))
        for _ in range(10000):
            if not eng.step():
                break
        assert len(mixed) >= 2, "the admission never rode a mixed dispatch"
        return first.generated, second.generated

    assert run(_mesh(1, 2)) == run(None)


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_mesh_pool_is_actually_sharded(setup, kv_dtype):
    """The KV pool must be allocated sharded: each device holds 1/(dp*tp)
    of it — ADVICE r1: allocating unsharded then resharding would OOM one
    chip at init. Pool pages over dp, kv heads over tp; an int8 pool's
    scale leaves shard with their rows."""
    cfg, params, serving = setup
    serving = dataclasses.replace(
        serving, kv_dtype=kv_dtype,
        page_size=32 if kv_dtype == "int8" else serving.page_size)
    engine = Engine(cfg, params, serving, mesh=_mesh(2, 2))
    P = jax.sharding.PartitionSpec
    want = {"k": P(None, "dp", "tp", None, None),
            "v": P(None, "dp", "tp", None, None)}
    if kv_dtype == "int8":
        want.update(ks=P(None, "dp", "tp", None), vs=P(None, "dp", "tp", None))
    assert set(engine.cache) == set(want)
    for name, leaf in engine.cache.items():   # [L, pages, Hkv, page, (D)]
        assert isinstance(leaf.sharding, jax.sharding.NamedSharding)
        assert leaf.sharding.spec == want[name], name
        shard = leaf.addressable_shards[0].data.shape
        assert shard[1] == engine._group_pages                # pages / dp
        assert shard[2] == cfg.num_kv_heads // 2              # heads / tp


def test_mesh_dp_divisibility_error(setup):
    cfg, params, serving = setup
    bad = dataclasses.replace(serving, max_decode_slots=3)  # 3 % dp(2) != 0
    with pytest.raises(ValueError, match="divisible by dp"):
        Engine(cfg, params, bad, mesh=_mesh(2, 2))


def test_mesh_tp_divisibility_error(setup):
    cfg, params, serving = setup
    # tp=8 does not divide num_kv_heads=2
    with pytest.raises(ValueError, match="does not divide"):
        Engine(cfg, params, serving, mesh=_mesh(1, 8))


def test_mesh_chunked_and_batched_prefill_parity(setup):
    """The new prefill paths (batched dispatch, chunked long-prompt) must hold
    token parity under a dp×tp mesh too — GSPMD has to partition the batch
    scatter and the chunk's cache-prefix gather correctly."""
    cfg, params, serving = setup
    serving_c = dataclasses.replace(serving, prefill_chunk=8)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist()
               for n in (3, 4, 5, 20)]   # 3 batched + 1 chunked

    single = Engine(cfg, params, serving_c)
    expected = _run_all(single, prompts)

    meshed = Engine(cfg, params, serving_c, mesh=_mesh(2, 2))
    got = _run_all(meshed, prompts)
    assert got == expected


def test_mesh_engine_continuous_batching_queueing(setup):
    """More requests than slots through the meshed engine: all complete and
    match single-device outputs (scheduler + mesh interaction)."""
    cfg, params, serving = setup
    rng = np.random.default_rng(2)
    prompts = [rng.integers(2, cfg.vocab_size, 4 + i).tolist()
               for i in range(6)]

    single = Engine(cfg, params, serving)
    expected = _run_all(single, prompts, max_tokens=5)

    meshed = Engine(cfg, params, serving, mesh=_mesh(2, 2))
    got = _run_all(meshed, prompts, max_tokens=5)
    assert got == expected


@pytest.mark.parametrize("dp,tp", [(2, 1), (1, 2)])
def test_mesh_long_generation_crosses_pages(setup, dp, tp):
    """Generate far past the first page's boundary, so decode rows land on
    later pages of each slot's run (under dp: of its own group's partition,
    through the table's global-to-local rebase) while attention spans all
    of them."""
    cfg, params, serving = setup
    serving_p = dataclasses.replace(serving, attention_impl="pallas",
                                    page_size=16)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist() for n in (4, 13)]

    single = Engine(cfg, params, dataclasses.replace(serving, page_size=16))
    expected = _run_all(single, prompts, max_tokens=40)   # crosses 3 pages

    meshed = Engine(cfg, params, serving_p, mesh=_mesh(dp, tp))
    assert _run_all(meshed, prompts, max_tokens=40) == expected


def test_mesh_allows_unaligned_cache(setup):
    """A meshed engine with a cache window that is no multiple of 8 rows
    (nor of the page) must keep working (code-review r2 finding #3)."""
    cfg, params, serving = setup
    odd = dataclasses.replace(serving, max_cache_len=60)   # 60 % 8 != 0
    engine = Engine(cfg, params, odd, mesh=_mesh(2, 1))
    prompts = [[5, 7, 11]]
    single = Engine(cfg, params, odd)
    assert _run_all(engine, prompts) == _run_all(single, prompts)


def test_tp_mesh_keeps_paged_cache(setup):
    """tp shards only the pool's head axis, so paging (page-gated admission,
    on-demand growth) must survive under a tp mesh — the Qwen3-8B/v5e-8
    flagship config."""
    cfg, params, serving = setup
    tp_eng = Engine(cfg, params, serving, mesh=_mesh(1, 2))
    assert tp_eng.cache["k"].ndim == 5
    assert tp_eng.cache["k"].shape[1] == \
        serving.max_decode_slots * (tp_eng.max_len // serving.page_size) + 1

    # page-gated admission works under the tp mesh: a pool of one window
    # serializes two prompts over 4 free slots
    small_pool = dataclasses.replace(serving, kv_pool_pages=4, page_size=8,
                                     max_cache_len=32,
                                     prefill_buckets=(8, 16, 32))
    eng = Engine(cfg, params, small_pool, mesh=_mesh(1, 2))
    a = eng.submit(Request(prompt_ids=[3] * 17, max_tokens=2,
                           ignore_eos=True))     # 3 pages
    b = eng.submit(Request(prompt_ids=[4] * 9, max_tokens=2,
                           ignore_eos=True))     # 2 pages > 1 left: waits
    eng.step()
    assert sum(1 for r in eng.slot_req if r is not None) == 1
    for _ in range(10000):
        if not eng.step():
            break
    assert len(a.generated) == 2 and len(b.generated) == 2


# ---------------------------------------------------------------------------
# Paged KV under dp meshes: per-group pool partitions (VERDICT r3 next #6)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["auto", "pallas"])
def test_dp_mesh_keeps_paged_cache_with_token_parity(setup, impl):
    """dp shards the pool's PAGE axis into per-group partitions with
    per-group host allocators — multi-replica-per-host dp serving must keep
    on-demand paging AND hold greedy token parity with the
    single-device paged engine."""
    cfg, params, serving = setup
    serving_i = dataclasses.replace(serving, attention_impl=impl)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist()
               for n in (3, 7, 12, 5)]

    single = Engine(cfg, params, serving_i)
    expected = _run_all(single, prompts)

    dp_eng = Engine(cfg, params, serving_i, mesh=_mesh(2, 1))
    assert dp_eng.dp_groups == 2
    # pool page axis = dp * (group_pages + 1), sharded over dp
    group_pages = (serving.max_decode_slots
                   * (dp_eng.max_len // serving.page_size)) // 2
    assert dp_eng.cache["k"].shape[1] == 2 * (group_pages + 1)
    assert _run_all(dp_eng, prompts) == expected

    dptp_eng = Engine(cfg, params, serving_i, mesh=_mesh(2, 2))
    assert _run_all(dptp_eng, prompts) == expected


def test_dp_paged_admission_and_preemption_are_group_local(setup):
    """A tiny per-group pool under dp=2: admission gates on the best group's
    headroom, preemption victims come from the starving slot's OWN group
    (another group's pages are unreachable), and every request still
    completes with the right token count."""
    cfg, params, serving = setup
    small = dataclasses.replace(serving, kv_pool_pages=8, page_size=8,
                                max_cache_len=32, prefill_buckets=(8, 16, 32))
    eng = Engine(cfg, params, small, mesh=_mesh(2, 1))
    assert eng.dp_groups == 2
    # per-group partition: 8 // 2 = 4 pages + scratch
    assert eng._group_pages == 5
    reqs = [eng.submit(Request(prompt_ids=[5 + i] * 17, max_tokens=4,
                               ignore_eos=True)) for i in range(4)]
    for _ in range(10000):
        if not eng.step():
            break
    assert all(len(r.generated) == 4 for r in reqs)
    # and parity with the single-device engine under the same tiny pool
    single = Engine(cfg, params, dataclasses.replace(small, kv_pool_pages=4))
    ref = [single.submit(Request(prompt_ids=[5 + i] * 17, max_tokens=4,
                                 ignore_eos=True)) for i in range(4)]
    for _ in range(10000):
        if not single.step():
            break
    assert [r.generated for r in reqs] == [r.generated for r in ref]


def test_mesh_guided_decoding_valid_json(setup):
    """Guided decoding under a dp x tp mesh: the [B, V/32] allow-bitmask is
    an unsharded dispatch input GSPMD must partition against the sharded
    logits — a random-weight meshed engine must still emit valid JSON."""
    import json as _json

    from aws_k8s_ansible_provisioner_tpu.serving.guided import grammar_for
    from aws_k8s_ansible_provisioner_tpu.utils.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    from aws_k8s_ansible_provisioner_tpu.config import tiny_qwen3 as _tq
    from aws_k8s_ansible_provisioner_tpu.models.layers import init_params as _ip
    import jax as _jax
    import jax.numpy as _jnp

    cfg = _tq(vocab_size=260, eos_token_id=tok.eos_token_id,
              num_heads=4, num_kv_heads=2)
    params = _ip(cfg, _jax.random.PRNGKey(0), dtype=_jnp.float32)
    serving = ServingConfig(weights_dtype="bf16", max_decode_slots=4, max_cache_len=128,
                            prefill_buckets=(16, 32), dtype="float32",
                            decode_horizon=4)
    eng = Engine(cfg, params, serving, mesh=_mesh(2, 2))
    g = grammar_for(tok, {"type": "json_object"}, [tok.eos_token_id])
    pressure = ((32, -50.0), (9, -50.0), (10, -50.0), (13, -50.0),
                (91, -20.0), (92, -100.0), (34, 30.0), (125, 20.0),
                (93, 15.0), (58, 20.0), (44, 5.0), (258, 100.0))
    req = eng.generate(tok.encode("j:"), guided=g, max_tokens=60,
                       temperature=0.0, logit_bias=pressure)
    plain = eng.generate(tok.encode("n"), max_tokens=12, temperature=0.0,
                         ignore_eos=True)
    for _ in range(10000):
        if not eng.step():
            break
    assert req.finish_reason == "stop"
    assert isinstance(_json.loads(tok.decode(req.generated)), dict)
    assert len(plain.generated) == 12
