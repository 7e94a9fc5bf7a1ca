"""Deviceless compiles for a described TPU v5e: what interpret mode cannot see.

Every other test runs the Pallas kernels in interpret mode on the CPU, which
accepts shapes the chip's compiler refuses (PR 21 found four such faults in
the default serving path: a [BB] scalar stack reshaped to [BB, 1, 1], a
64-lane scale-page DMA, a 1.04 MiB SMEM table, and a scatter that made XLA
relayout the whole KV pool). The TPU compiler is installed here and compiles
for a chip that is described, not attached
(``jax.experimental.topologies``), so these cases compile the serving
kernels at Qwen3-0.6B widths — 8 KV heads, 16 query heads, head_dim 128,
page 64, 32 slots — for ``v5e:2x2`` and assert a Mosaic kernel came out.

Nothing runs: a pass here says the chip's compiler ACCEPTS the kernel, not
that its results are right (``python chip_smoke.py`` checks those on the
chip). Skipped where the topology cannot be described. The persistent
compilation cache is off around the compiles — such an executable can be
written to it but not read back without a chip.

The window is the served 2,048 (32 pages per slot) in every case: the paged
kernels walk their pages in a loop with dynamic bounds (PR 25), so a case
compiles in about a second whatever the table's width. The ragged cases
carry the per-block share fact the entry point derives from its tables,
and reach past the 0.6B's shape: an int8 pool, the 8B's 4 query heads a KV
head, and the 2 KV heads a chip holds under ``--tp 4``.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa
from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp

L, HKV, HQ, D, PS, B = 28, 8, 16, 128, 64, 32      # Qwen3-0.6B, default server
P = B * 32 + 1                                     # default pool + scratch
CHUNK = 2048                                       # mixed program: B + CHUNK rows


@pytest.fixture(scope="module")
def chip():
    """SingleDeviceSharding on one described v5e chip, cache off."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu, or it cannot describe the chip
        pytest.skip(f"v5e:2x2 topology cannot be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _pool(chip, quant, hkv=HKV):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    kv = sds((L, P, hkv, PS, D), jnp.int8 if quant else jnp.bfloat16)
    scales = sds((L, P, hkv, kvp.scale_lanes(PS)), jnp.float32)
    return sds, kv, (dict(pool_ks=scales, pool_vs=scales) if quant else {})


def _compile(fn, *args, **kw):
    return jax.jit(lambda *a, **k: fn(*a, **k)).lower(*args, **kw).compile()


def _assert_named_after_wrapper(compiled, fn):
    """The chip's trace names a Pallas call after the jitted wrapper that
    makes it (``%decode_attend_pallas_paged.8 = ... custom-call(...)``), and
    the benchmark's kernel metrics match ``^%<wrapper>``: a rename has to
    fail here, not empty a metric (benchmark/layer_metrics/)."""
    import re

    assert re.search(rf"%{fn.__name__}(\.\d+)? = [^\n]*custom-call",
                     compiled.as_text()), fn.__name__


def _pallas_grids(fn, *args, **kw):
    """The grid of every Pallas call ``fn`` traces to."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(lambda *a, **k: fn(*a, **k))(*args, **kw).jaxpr)
    return grids


def _assert_one_call_of_wide_tiles(compiled, wrapper, fn, rows, *args, **kw):
    """A ragged entry stays ONE Pallas call under its wrapper's name
    (benchmark/layer_metrics/ragged_attn_roofline_pct.py multiplies one
    call's need by the events it finds) whose grid steps are the tiles
    pallas_attention._tile_rows reads off the shapes."""
    import re

    text = compiled.as_text()
    assert len(re.findall(rf"%{wrapper.__name__}(\.\d+)? = [^\n]*"
                          rf"custom-call", text)) == 1
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 1
    q, pool = args[0], args[1]
    tile = pa._tile_rows(rows, 8, q.shape[1], q.shape[2], pool.shape[3],
                         pool.dtype)
    assert tile > 8 and _pallas_grids(fn, *args, **kw) == [(rows // tile,)]
    return tile


CASES = [
    # (id, entry point, quant, bblock, rows, query rows per slot, Hq, Hkv)
    ("decode-bf16-bb1", "decode", False, 1, B, 1, HQ, HKV),
    ("decode-bf16-bb8", "decode", False, 8, B, 1, HQ, HKV),
    ("decode-int8-bb1", "decode", True, 1, B, 1, HQ, HKV),
    ("decode-int8-bb8", "decode", True, 8, B, 1, HQ, HKV),
    # the mixed program's packed layout: every slot plus a full chunk, one
    # table row a SLOT and a row map
    ("ragged-bf16-bb1", "ragged", False, 1, B + CHUNK, 1, HQ, HKV),
    ("ragged-bf16-bb8", "ragged", False, 8, B + CHUNK, 1, HQ, HKV),
    ("ragged-int8-bb8", "ragged", True, 8, B + CHUNK, 1, HQ, HKV),
    ("ragged-bf16-bb8-8b", "ragged", False, 8, 16 + CHUNK, 1, 32, HKV),
    ("ragged-bf16-bb8-tp4", "ragged", False, 8, B + CHUNK, 1, HQ // 4,
     HKV // 4),
    ("spec-bf16-bb1", "spec", False, 1, B, 5, HQ, HKV),
    # (blocks of 8 rows of 5 drafts, either pool: the per-row walk whose
    # copies sit under each row's own predicate since PR 45, ``ext``
    # columns past the length inside the row's range)
    ("spec-bf16-bb8", "spec", False, 8, B, 5, HQ, HKV),
    ("spec-int8-bb8", "spec", True, 8, B, 5, HQ, HKV),
    # OLMoE: multi-head attention, one query head a KV head (groups = 1),
    # 16 KV heads, 24 slots — a decode block of 8 slots gives the kernel 8
    # query rows a KV head, and a 64-token page is 262 KB each for K and V
    ("decode-bf16-bb8-mha", "decode", False, 8, 24, 1, 16, 16),
    ("ragged-bf16-bb8-mha", "ragged", False, 8, 24 + CHUNK, 1, 16, 16),
    # the KDA hybrid's attending layers: 64 slots and a 512-row chunk, 64
    # query heads — a 64-row tile's working set outgrows its VMEM budget
    ("ragged-bf16-bb8-solar", "ragged", False, 8, 64 + 512, 1, 64, 8),
]
# the width of a ragged case's tiles at block 8 (pa._tile_rows)
# (an int8 pool keeps blocks of 8: its scales ride a page's lanes)
TILES = {"ragged-bf16-bb8": 40, "ragged-bf16-bb8-8b": 48,
         "ragged-bf16-bb8-tp4": 40, "ragged-bf16-bb8-mha": 56,
         "ragged-bf16-bb8-solar": 32}


@pytest.mark.parametrize("case,entry,quant,bb,rows,R,hq,hkv", CASES,
                         ids=[c[0] for c in CASES])
def test_paged_attention_kernel_compiles_for_v5e(chip, case, entry, quant,
                                                 bb, rows, R, hq, hkv):
    sds, kv, skw = _pool(chip, quant, hkv)
    lens, lay = sds((rows,), jnp.int32), sds((), jnp.int32)
    tables = (sds((rows, 32), jnp.int32),)
    if entry == "decode":
        fn, q = pa.decode_attend_pallas_paged, sds((rows, 1, hq, D),
                                                   jnp.bfloat16)
    elif entry == "ragged":
        fn, q = pa.ragged_attend_pallas_paged, sds((rows, hq, D),
                                                   jnp.bfloat16)
        slots = rows - (512 if case.endswith("solar") else CHUNK)
        tables = (sds((slots, 32), jnp.int32), lens)    # table, row_map
    else:
        fn, q = pa.decode_attend_pallas_spec_paged, sds((rows, R, hq, D),
                                                        jnp.bfloat16)
    args = (q, kv, kv, lens, lay) + tables
    compiled = _compile(functools.partial(fn, bblock=bb), *args, **skw)
    assert "tpu_custom_call" in compiled.as_text()
    _assert_named_after_wrapper(compiled, fn)
    if case in TILES:
        assert _assert_one_call_of_wide_tiles(
            compiled, fn, functools.partial(fn, bblock=bb), rows, *args,
            **skw) == TILES[case]
    else:       # decode, spec, a block of one row, an int8 pool: blocks
        assert _pallas_grids(functools.partial(fn, bblock=bb), *args,
                             **skw) == [(rows // bb,)]


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_write_kernel_compiles_for_v5e(chip, quant):
    """One row per slot: the decode program's write, and the decode rows'
    of the mixed program."""
    sds, kv, skw = _pool(chip, quant)
    rows, lay = sds((B,), jnp.int32), sds((), jnp.int32)
    table, new = sds((B, 32), jnp.int32), sds((B, HKV, D), jnp.bfloat16)
    if quant:
        compiled = _compile(pa.cache_write_row_quant_paged, kv,
                            skw["pool_ks"], new, rows, table, lay)
    else:
        compiled = _compile(pa.cache_write_row_paged, kv, new, rows, table,
                            lay)
        _assert_named_after_wrapper(compiled, pa.cache_write_row_paged)
    assert "tpu_custom_call" in compiled.as_text()


POOL_BYTES = 2 * L * P * HKV * PS * D * 2


def test_paged_write_kernel_compiles_for_v5e_at_mha_heads(chip):
    """cache_write_row_paged at OLMoE's 16 KV heads, one row a slot."""
    sds, kv, _ = _pool(chip, False, hkv=16)
    compiled = _compile(
        pa.cache_write_row_paged, kv, sds((24, 16, D), jnp.bfloat16),
        sds((24,), jnp.int32), sds((24, 32), jnp.int32), sds((), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()
    _assert_named_after_wrapper(compiled, pa.cache_write_row_paged)


@pytest.mark.parametrize("rows,form", [(24, "every-expert"),
                                       (24 + CHUNK, "sorted")])
def test_expert_ffn_ops_are_found_by_their_stack_operand(chip, rows, form):
    """OLMoE's expert layer at published widths, int8 stacks, in both forms
    ops/moe.py gives it (a decode batch: every expert over every row; the
    mixed program's packed rows: sorted groups through XLA's ``ragged-dot``
    custom calls). The profiler names a device operation by its HLO line
    and keeps no scope metadata, so the benchmark's readers find the expert
    FFN by the one thing every form's line holds: an expert stack's type
    among the operands (benchmark/benchlib/moe_opsbytes.expert_ops_re). Also
    here: no bf16 copy of a stack is made (PR 26: an ``astype`` in front of
    ``ragged_dot`` was three 268-MB copies a layer)."""
    import dataclasses
    import os
    import re
    import sys

    from aws_k8s_ansible_provisioner_tpu.config import MODEL_REGISTRY
    from aws_k8s_ansible_provisioner_tpu.ops import moe

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    from benchlib import moe_opsbytes

    cfg = MODEL_REGISTRY["allenai/OLMoE-1B-7B-0125-Instruct"]
    E, H, Im = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    def stack(din, dout):
        return {"kernel": sds((E, din, dout), jnp.int8),
                "scale": sds((E, dout), jnp.float32)}

    p = {"router": {"kernel": sds((H, E), jnp.bfloat16)},
         "w_gate": stack(H, Im), "w_up": stack(H, Im),
         "w_down": stack(Im, H)}
    text = jax.jit(lambda x, p: moe.moe_mlp(cfg, x, p)).lower(
        sds((rows, H), jnp.bfloat16), p).compile().as_text()
    assert ("ragged-dot" in text) == (form == "sorted")
    pat = re.compile(moe_opsbytes.expert_ops_re(dataclasses.asdict(cfg)))
    # the entry computation's instructions as the trace shows them: the
    # profiler's line carries each operand's type, ``as_text`` does not
    entry = text[text.index("ENTRY"):].splitlines()[1:]
    types = dict(re.findall(r"(%[\w.\-]+) = (\w+\[[\d,]*\])", text))
    lines = [re.sub(r"(%[\w.\-]+)(?=[,)])",
                    lambda m: f"{types.get(m[1], '?')} {m[1]}", ln)
             for ln in entry if " = " in ln and "parameter(" not in ln]
    hits = [ln for ln in lines if pat.search(ln)]
    found = [ln.split(" = ")[0].split()[-1] for ln in hits]
    # between them the matched operations read all three stacks
    stacks = {m for ln in hits for m in re.findall(
        rf"s8\[{E},(?:{H},{Im}|{Im},{H})\] (%[\w.\-]+)", ln)}
    assert len(stacks) == 3, (found, stacks)
    if form == "sorted":
        assert sum(f.startswith("%ragged-dot") for f in found) == 3, found
    assert not re.search(rf"= bf16\[{E},({H},{Im}|{Im},{H})\]", text), \
        "a bf16 copy of an expert stack is materialised"


def test_paged_prefill_write_holds_no_pool_copy(chip):
    """The prompt writer inside a layer scan, pool donated, as the prefill
    programs run it: the row-granular scatter it replaced made XLA relayout
    the whole pool around the loop — a pool-sized temp (7.0 GiB beside the
    7.0 GiB default pool) in every prefill program."""
    sds, kv, _ = _pool(chip, False)
    pages = sds((32,), jnp.int32)
    rows = sds((L, 1, 2048, HKV, D), jnp.bfloat16)

    def prefill_writes(pool, pages, k, v):
        def body(carry, kv_l):
            pool, layer = carry
            pool = kvp.write_chunk_paged_layer(pool, layer, pages, 0,
                                               kv_l[0], kv_l[1], PS)
            return (pool, layer + 1), None

        (pool, _), _ = jax.lax.scan(body, (pool, jnp.int32(0)), (k, v))
        return pool

    compiled = jax.jit(prefill_writes, donate_argnums=(0,)).lower(
        {"k": kv, "v": kv}, pages, rows, rows).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < POOL_BYTES // 16


def test_smallest_prefill_program_holds_no_pool_copy(chip, monkeypatch):
    """The whole ``prefill_b32`` program of the default server, from the
    engine's own enumeration (serving/aot.py). A bucket that fits one page
    is the case XLA rewrote as a dynamic-update-slice in the transposed
    update's layout — relayouting the pool again — and it only shows with
    the model around the writer."""
    from aws_k8s_ansible_provisioner_tpu.config import (MODEL_REGISTRY,
                                                        ServingConfig)
    from aws_k8s_ansible_provisioner_tpu.serving import aot

    # compile the chip's branch (the steer the program does not offer)
    monkeypatch.setattr(pa, "supported", lambda: True)
    plan = aot.ProgramPlan(MODEL_REGISTRY["Qwen/Qwen3-0.6B"],
                           ServingConfig(model="Qwen/Qwen3-0.6B"))
    params, cache = aot._abstract_state(plan, None,
                                        next(iter(chip.device_set)))
    _, fn, args, kwargs = next(
        p for p in aot.enumerate_programs(plan, None, params, cache)
        if p[0] == "prefill_b32")
    compiled = fn.lower(*args, **kwargs).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < POOL_BYTES // 16


@pytest.mark.parametrize(
    "model,slots", [("Qwen/Qwen3-0.6B", 32), ("Qwen/Qwen3-8B", 16),
                    ("allenai/OLMoE-1B-7B-0125-Instruct", 24)],
    ids=["qwen3-0.6b", "qwen3-8b", "olmoe-1b-7b"])
def test_mixed_step_holds_no_pool_copy(chip, monkeypatch, model, slots):
    """The whole ``mixed_step`` of each benchmark cell (int8 weights, bf16
    KV, 2,048-row chunk, block 8), from the engine's own enumeration. Its
    body holds an aliased Pallas row write (the decode rows), the chunk's
    page-window scatter and the ragged kernel on ONE pool: nothing but
    those writers may produce a pool-shaped value (a copy or a relayout
    would). The head runs over the ``slots + 1`` rows that are sampled
    (PR 35), so the temporaries hold NO packed rows' logits — 1,207 MiB of
    float32 at the 0.6B's vocabulary before — only a few copies of the
    sampled rows' and the layers' activations."""
    import math
    import re

    from aws_k8s_ansible_provisioner_tpu.config import (MODEL_REGISTRY,
                                                        ServingConfig)
    from aws_k8s_ansible_provisioner_tpu.serving import aot

    monkeypatch.setattr(pa, "supported", lambda: True)
    cfg = MODEL_REGISTRY[model]
    plan = aot.ProgramPlan(cfg, ServingConfig(
        model=model, max_decode_slots=slots, max_cache_len=2048,
        weights_dtype="int8", decode_bblock=8, kv_host_tier_bytes=0))
    params, cache = aot._abstract_state(plan, None,
                                        next(iter(chip.device_set)))
    _, fn, args, kwargs = next(
        p for p in aot.enumerate_programs(plan, None, params, cache,
                                          bblock=8)
        if p[0] == f"mixed_c{CHUNK}")
    compiled = fn.lower(*args, **kwargs).compile()
    leaf = cache["k"]
    assert leaf.dtype == jnp.bfloat16
    sampled = (slots + 1) * cfg.vocab_size * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * sampled + 2 * math.prod(leaf.shape) // 8
    text = compiled.as_text()
    # no value of the packed rows' height and the vocabulary's width
    assert not re.search(rf"\[(1,)?{slots + CHUNK},{cfg.vocab_size}\]", text)
    shape = re.escape("bf16[" + ",".join(map(str, leaf.shape)) + "]")
    makers = set(re.findall(rf" = {shape}\S* ([\w\-]+)\(", text))
    assert makers <= {"parameter", "get-tuple-element", "custom-call",
                      "scatter", "fusion"}, makers
    # the decode rows still go through the row kernel, attention through
    # the ragged one (the names the benchmark's readers match)
    _assert_named_after_wrapper(compiled, pa.cache_write_row_paged)
    _assert_named_after_wrapper(compiled, pa.ragged_attend_pallas_paged)
    # and the program holds its layers at BOTH widths (PR 55): activations
    # of slots + 2,048 and of slots + 1,024 rows, a ragged call each, the
    # donated pool through both in place (the makers above) — under a
    # ``lax.cond`` the 8B's and OLMoE's wide layer loop copied each pool
    # leaf in and out at every layer, 16.7 GB asked of the chip's 15.75
    for rows in (slots + CHUNK, slots + CHUNK // 2):
        assert re.search(rf"bf16\[1,{rows},{cfg.hidden_size}\]", text), rows
        # (OLMoE's 1,048 = 8 x 131 rows run as 1,088: a tile of 64)
        ran = pa._ragged_pad(rows, 8, cfg.num_heads, cfg.pool_head_dim, PS,
                             jnp.bfloat16)
        assert ran == rows or (slots, rows) == (24, 1048)
        assert re.search(rf"ragged_attend_pallas_paged\S* = bf16\[{ran},",
                         text), rows


@pytest.mark.parametrize("cell,chunk,pool", [
    ("minicpm-sala-9b-pp4", 4608, (2, 12289, 2, 64, 128)),
    ("trinity-mini-26b-pp4", 4096, (2, 6913, 4, 64, 128))])
def test_long_chunk_cells_mixed_step_holds_both_widths_in_place(
        chip, monkeypatch, cell, chunk, pool):
    """The two cells whose chunk is longest, at their served shapes (the
    cell's own file: model, server flags): the selecting hybrid's 24 +
    4,608 / 24 + 2,304 rows (one selecting ragged call a body; tiles of 24
    at either width) and the window/full list's 48 + 4,096 / 48 + 2,048
    (two pools, two tables) compile for the chip with every pool leaf made
    by its writers alone."""
    import json
    import os
    import re

    from aws_k8s_ansible_provisioner_tpu.config import ModelConfig
    from aws_k8s_ansible_provisioner_tpu.serving import aot, server
    from aws_k8s_ansible_provisioner_tpu.serving import programs as pg

    monkeypatch.setattr(pa, "supported", lambda: True)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs", cell + ".json"),
              encoding="utf-8") as f:
        file = json.load(f)
    cfg = ModelConfig(**file["model_config"])
    serving = server.serving_config_from_args(
        server.build_parser().parse_args(file["server_flags"]))
    plan = aot.ProgramPlan(cfg, serving)
    slots = plan.num_slots
    assert pg.mixed_narrow_rows(cfg, slots, chunk, serving.page_size, 8,
                                plan.pages_per_slot, jnp.bfloat16) \
        == chunk // 2
    params, cache = aot._abstract_state(plan, None,
                                        next(iter(chip.device_set)))
    assert cache["k"].shape == pool and cache["k"].dtype == jnp.bfloat16
    _, fn, args, kwargs = next(
        p for p in aot.enumerate_programs(plan, None, params, cache,
                                          bblock=8)
        if p[0] == f"mixed_c{chunk}")
    compiled = fn.lower(*args, **kwargs).compile()
    text = compiled.as_text()
    for name in ("k", "wk"):
        if name not in cache:
            continue
        shape = re.escape("bf16[" + ",".join(map(str, cache[name].shape))
                          + "]")
        makers = set(re.findall(rf" = {shape}\S* ([\w\-]+)\(", text))
        assert makers <= {"parameter", "get-tuple-element", "custom-call",
                          "scatter", "fusion"}, (name, makers)
    for rows in (slots + chunk, slots + chunk // 2):
        assert re.search(rf"bf16\[1,{rows},{cfg.hidden_size}\]", text), rows
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30


def test_batched_prefill_holds_no_every_row_logits(chip, monkeypatch):
    """``prefill_batch_step`` at 4 rows x bucket 1,024 of the 0.6B cell
    (the closed cell's ramp and the warm-up dispatch it): the head over
    its 4 sampled rows — 2,142 MiB of temporaries before PR 35, most of
    them the 4,096 rows' bf16 and float32 logits; what is left is the
    attention's scores."""
    import re

    from aws_k8s_ansible_provisioner_tpu.config import (MODEL_REGISTRY,
                                                        ServingConfig)
    from aws_k8s_ansible_provisioner_tpu.serving import aot

    monkeypatch.setattr(pa, "supported", lambda: True)
    cfg = MODEL_REGISTRY["Qwen/Qwen3-0.6B"]
    plan = aot.ProgramPlan(cfg, ServingConfig(
        model=cfg.name, max_decode_slots=32, max_cache_len=2048,
        weights_dtype="int8", decode_bblock=8, kv_host_tier_bytes=0))
    params, cache = aot._abstract_state(plan, None,
                                        next(iter(chip.device_set)))
    _, fn, args, kwargs = next(
        p for p in aot.enumerate_programs(plan, None, params, cache)
        if p[0].startswith("prefill_batch_n"))
    rows, bucket = args[3].shape[0], 1024
    assert rows == 4
    args = args[:3] + (jax.ShapeDtypeStruct(
        (rows, bucket), jnp.int32, sharding=args[3].sharding),) + args[4:]
    compiled = fn.lower(*args, **kwargs).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * 2**20
    assert not re.search(rf"\[{rows},{bucket},{cfg.vocab_size}\]",
                         compiled.as_text())


def _compiled_decode_steps(chip, monkeypatch, model, slots):
    """``decode_fused_h8`` of a closed cell (int8 weights, block 8) from the
    engine's own enumeration, compiled for the described chip."""
    from aws_k8s_ansible_provisioner_tpu.config import (MODEL_REGISTRY,
                                                        ServingConfig)
    from aws_k8s_ansible_provisioner_tpu.serving import aot

    monkeypatch.setattr(pa, "supported", lambda: True)
    plan = aot.ProgramPlan(MODEL_REGISTRY[model], ServingConfig(
        model=model, max_decode_slots=slots, max_cache_len=2048,
        weights_dtype="int8", decode_bblock=8, kv_host_tier_bytes=0))
    params, cache = aot._abstract_state(plan, None,
                                        next(iter(chip.device_set)))
    _, fn, args, kwargs = next(
        p for p in aot.enumerate_programs(plan, None, params, cache,
                                          bblock=8)
        if p[0] == "decode_fused_h8")
    assert kwargs["steps"].shape == ()      # the count: an operand
    return fn.lower(*args, **kwargs).compile()


@pytest.mark.parametrize("model,slots,pool,temp_mib", [
    ("Qwen/Qwen3-0.6B", 32, "bf16[28,1025,8,64,128]", 2),
    ("Qwen/Qwen3-8B", 16, "bf16[36,513,8,64,128]", 150)],
    ids=["qwen3-0.6b", "qwen3-8b"])
def test_decode_steps_holds_no_pool_copy(chip, monkeypatch, model, slots,
                                         pool, temp_mib):
    """The served ``decode_steps`` of the two Qwen3 closed cells, whose
    token loop runs as many substeps as its ``steps`` operand says: the
    pool rides the loop's carry in place as it rode the static scan's —
    nothing but the program's parameters, the loops' tuples and the two
    aliased Pallas row writes produces a pool-shaped value, the
    temporaries are what the static scan's were (1.0 MiB; 145.1 at the
    8B's widths, half of one 288-MiB pool leaf) — and each substep's tokens
    land in their row of the ``[8, slots]`` output the loop carries."""
    import re

    compiled = _compiled_decode_steps(chip, monkeypatch, model, slots)
    text = compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < temp_mib * 2**20
    makers = set(re.findall(rf" = {re.escape(pool)}\S* ([\w\-]+)\(", text))
    assert {"parameter", "custom-call"} <= makers \
        <= {"parameter", "get-tuple-element", "custom-call"}, makers
    assert re.search(rf"s32\[8,{slots}\]\S* dynamic-update-slice\(", text) \
        or re.search(rf"s32\[8,{slots}\]\S* fusion\(", text)
    _assert_named_after_wrapper(compiled, pa.cache_write_row_paged)


@pytest.mark.parametrize(
    "model,slots", [("Qwen/Qwen3-0.6B", 32), ("Qwen/Qwen3-8B", 16),
                    ("allenai/OLMoE-1B-7B-0125-Instruct", 24)],
    ids=["qwen3-0.6b", "qwen3-8b", "olmoe-1b-7b"])
def test_decode_steps_orders_its_rows_once_a_substep(chip, monkeypatch,
                                                     model, slots):
    """The whole ``decode_steps`` of each closed cell (horizon 8, block 8),
    from the engine's own enumeration: the rows' order in length (PR 33:
    ops/attention._length_order) is taken in the SUBSTEP's body — two sorts,
    the order and its inverse, beside the layer loop — and the layer's body
    holds the kernel under its wrapper's name; the gathers around the call
    bring no copy of a weight stack."""
    import re

    compiled = _compiled_decode_steps(chip, monkeypatch, model, slots)
    text = compiled.as_text()
    _assert_named_after_wrapper(compiled, pa.decode_attend_pallas_paged)
    # gathered as [B, Hq, D], q came out head-major and the compiler fed it
    # from a transposed copy of the whole wq stack, made every dispatch
    assert not re.search(r" copy\(%params__layers____w[qo]__", text)
    bodies = text.split("\n\n")                     # one computation each
    by_len = rf" = \(s32\[1,{slots}\]\S* s32\[1,{slots}\]\S* sort\("
    substep, = [b for b in bodies if re.search(by_len, b)]
    assert len(re.findall(by_len, substep)) == 2 and " while(" in substep
    layer, = [b for b in bodies
              if re.search(r"%decode_attend_pallas_paged(\.\d+)? = ", b)]
    assert layer is not substep


def test_decode_steps_sorts_the_vocabulary_only_where_a_row_draws(
        chip, monkeypatch):
    """The 0.6B closed cell's ``decode_steps`` (32 slots, horizon 8): its
    substep holds ONE conditional — the sampler's "does any row draw"
    (ops/sampling.sample) — and the top-64 over the 151,936-wide logits, as a
    TopK call or as a sort, lives in a branch of it and nowhere else: a
    greedy batch takes the other branch, which is the argmax it was handed.
    The branch's operations still carry the ``sample`` part in their names,
    so the trace's reader files them where it filed them before."""
    import re

    from aws_k8s_ansible_provisioner_tpu.config import MODEL_REGISTRY
    from aws_k8s_ansible_provisioner_tpu.models import parts

    model = "Qwen/Qwen3-0.6B"
    vocab = MODEL_REGISTRY[model].vocab_size
    text = _compiled_decode_steps(chip, monkeypatch, model, B).as_text()
    bodies = {b.lstrip().split(" ", 1)[0]: b for b in text.split("\n\n")}
    gates = [(b, m.group(1).split(", ")) for b in bodies.values()
             for m in re.finditer(
                 r" conditional\(.*branch_computations=\{([^}]*)\}", b)]
    (substep, branches), = gates
    assert re.search(rf" = \(s32\[1,{B}\]\S* s32\[1,{B}\]\S* sort\(", substep)
    assert len(branches) == 2

    def called(names):
        """The computations reachable from ``names`` (a fusion's body, a
        reduction's or a custom call's comparator)."""
        seen, todo = set(), list(names)
        while todo:
            name = todo.pop()
            if name in seen or name not in bodies:
                continue
            seen.add(name)
            todo += re.findall(r"(?:calls|to_apply)=(%[^\s,)}]+)",
                               bodies[name])
            for group in re.findall(r"called_computations=\{([^}]*)\}",
                                    bodies[name]):
                todo += group.split(", ")
        return seen

    def wide_sorts(names):
        return [ln for name in names for ln in bodies[name].splitlines()
                if 'custom_call_target="TopK"' in ln
                or (" sort(" in ln and f",{vocab}]" in ln)]

    drawn = called(branches)
    inside = wide_sorts(drawn)
    assert len(inside) == 1 and f"/{parts.SAMPLE}/cond/" in inside[0]
    assert not wide_sorts(set(bodies) - drawn)


def test_kda_decode_update_compiles_in_place_under_its_own_name(chip):
    """The KDA decode step's state pass (ops/linear_attention.py, PR 32) at
    the served shape — 64 slots, 64 heads of 128, two periods of three KDA
    layers: Mosaic accepts it, the 1.5 GiB float32 state leaf is aliased and
    not copied, and the trace will name it after its wrapper, which is how
    the benchmark's readers and PERF.md find it."""
    from aws_k8s_ansible_provisioner_tpu.ops import linear_attention as la

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    periods, nk, slots, heads, d = 2, 3, 64, 64, 128
    row = sds((slots, heads, d))
    compiled = jax.jit(
        lambda s, p, q, k, v, g, b: la.kda_decode_update(s, p, 1, q, k, v,
                                                         g, b),
        donate_argnums=(0,)).lower(
        sds((periods, nk, slots, heads, d, d)), sds((), jnp.int32), row, row,
        row, row, sds((slots, heads))).compile()
    _assert_named_after_wrapper(compiled, la.kda_decode_update)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 4 * periods * nk * slots * heads * d * d


# -- the kernels of a model whose attention selects its pages (PR 34) --------
# MiniCPM-SALA's shape as served: 2 KV heads, 32 query heads (groups 16), 24
# slots, a 32k window (512-page tables), page 64, top-64 (lists of 128: a
# context under the dense length reads up to 128 pages).

S_HQ, S_HKV, S_B, S_MP, S_K = 32, 2, 24, 512, 128


def _sala_pool(chip):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    return sds, sds((2, S_B * S_MP + 1, S_HKV, PS, D), jnp.bfloat16)


@pytest.mark.parametrize("bb", [1, 8])
def test_decode_kernel_over_selected_pages_compiles_for_v5e(chip, bb):
    """The LIST form: a DMA a KV head a page, the table 4x wider than any
    other case here, ``groups`` 16."""
    import os
    import sys

    sds, kv = _sala_pool(chip)
    i32 = jnp.int32
    fn = pa.decode_attend_pallas_paged_select
    compiled = _compile(
        functools.partial(fn, bblock=bb), sds((S_B, 1, S_HQ, D), jnp.bfloat16),
        kv, kv, sds((S_B,), i32), sds((), i32), sds((S_B, S_MP), i32),
        sds((S_B, S_HKV, S_K), i32), sds((S_B, S_HKV), i32))
    assert "tpu_custom_call" in compiled.as_text()
    _assert_named_after_wrapper(compiled, fn)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    from benchlib import sala_opsbytes

    assert sala_opsbytes.DECODE_KERNEL_RE == "^%" + fn.__name__


@pytest.mark.parametrize("chunk,grids", [
    (1024, [131]), (CHUNK, [37]), (4608, [193]), (8192, [257, 171])])
def test_ragged_kernel_under_page_masks_compiles_for_v5e(chip, chunk, grids):
    """The BITMASK form with ONE table row a slot (a table row a packed row
    is 4.2 MB of SMEM at this window): 24 + chunk rows, 16 mask words a row
    and KV head. A grid step is a tile of ``_tile_rows`` rows, the selection
    a VMEM-blocked mask over its lanes: 24 of the served 4,632 rows — ONE
    custom call a layer under the wrapper's name, 193 steps —, 56 of 2,072;
    1,048 rows have no such divisor and keep blocks of 8 with every row's
    words in SMEM. At 8,216 rows (a chunk an operator may ask for; PR 34
    tried it; blocks of 8 too) those words alone are the chip's SMEM and
    the entry walks the rows in two calls, each of which tiles wider."""
    sds, kv = _sala_pool(chip)
    i32, N = jnp.int32, S_B + chunk
    fn = pa.ragged_attend_pallas_paged_select
    args = (sds((N, S_HQ, D), jnp.bfloat16), kv, kv, sds((N,), i32),
            sds((), i32), sds((S_B, S_MP), i32), sds((N,), i32),
            sds((N, S_HKV, S_MP // 32), i32))
    compiled = _compile(functools.partial(fn, bblock=8), *args)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.as_text().count("custom_call_target=\"tpu_custom_call\"") \
        == len(grids)
    _assert_named_after_wrapper(compiled, fn)
    assert _pallas_grids(functools.partial(fn, bblock=8), *args) \
        == [(g,) for g in grids]


# sha256 of str(jax.make_jaxpr(...)) of the two selecting entries. "decode"
# is the one taken at the parent of PR 45 (cb17104) with this very function
# in a checkout of it: PR 45 moved the per-row walk of every OTHER entry of
# the paged body (a row past its own pages starts no copy) and left the two —
# a list per row and KV head, a bitmask over pages: past the dense length
# nothing to skip — as they were, jaxpr for jaxpr. "ragged" moved with PR 49,
# whose subject it is (a grid step is a tile of _tile_rows rows, a sharing
# tile's selection a mask over its lanes); the decode entry, traced by the
# same body, did not.
PINNED_SELECT = {"decode": "54178aab508e23a1", "ragged": "c917292d68302a3a"}


@pytest.mark.parametrize("entry", sorted(PINNED_SELECT))
def test_selecting_entries_are_the_pinned_jaxprs(entry):
    import hashlib

    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    lyr, pgs, hkv, ps, d, b, mp, hq, k, n = 2, 17, 2, 16, 16, 4, 4, 4, 3, 8
    kv = sds((lyr, pgs, hkv, ps, d), jnp.bfloat16)
    if entry == "decode":
        fn = pa.decode_attend_pallas_paged_select
        args = (sds((b, 1, hq, d), jnp.bfloat16), kv, kv, sds((b,), i32),
                sds((), i32), sds((b, mp), i32), sds((b, hkv, k), i32),
                sds((b, hkv), i32))
    else:
        fn = pa.ragged_attend_pallas_paged_select
        args = (sds((n, hq, d), jnp.bfloat16), kv, kv, sds((n,), i32),
                sds((), i32), sds((b, mp), i32), sds((n,), i32),
                sds((n, hkv, 1), i32))
    text = str(jax.make_jaxpr(
        lambda *a: fn(*a, interpret=True, bblock=2))(*args))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PINNED_SELECT[entry]


# Trinity-Mini's shape as served: 4 KV heads, 32 query heads (groups 8), 48
# slots, a 9,216-token cache (144-page tables), page 64, a 2,048-token window,
# a 4,096-row chunk.

T_HQ, T_HKV, T_B, T_MP, T_W, T_C = 32, 4, 48, 144, 2048, 4096


def _trinity_pool(chip, pages):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    return sds, sds((6, pages, T_HKV, PS, D), jnp.bfloat16)


def test_window_decode_kernel_compiles_for_v5e_under_its_own_name(chip):
    """The window layers' decode calls carry a wrapper's name of their own:
    that is how the device trace (and benchlib/trinity_opsbytes.py) tells
    them from the full layers' calls of the same kernel."""
    import os
    import sys

    sds, kv = _trinity_pool(chip, 1698)
    i32 = jnp.int32
    fn = pa.decode_attend_pallas_paged_window
    compiled = _compile(
        functools.partial(fn, bblock=8, window=T_W),
        sds((T_B, 1, T_HQ, D), jnp.bfloat16), kv, kv, sds((T_B,), i32),
        sds((), i32), sds((T_B, T_MP), i32))
    assert "tpu_custom_call" in compiled.as_text()
    _assert_named_after_wrapper(compiled, fn)
    import re

    assert not re.search(r"%decode_attend_pallas_paged(\.\d+)? = ",
                         compiled.as_text())
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    from benchlib import trinity_opsbytes as tob

    assert tob.WINDOW_KERNEL_RE == "^%" + fn.__name__
    assert re.match(tob.FULL_KERNEL_RE,
                    "%" + pa.decode_attend_pallas_paged.__name__ + ".3 = ")


@pytest.mark.parametrize("kind", ["full", "window"])
def test_ragged_kernel_by_slot_compiles_for_v5e(chip, kind):
    """The list's shape (a table row a packed row would be 2.4 MB of SMEM
    at 48 + 4,096 rows of 144 pages), full and under the window, each under
    its own name."""
    sds, kv = _trinity_pool(chip, 6913 if kind == "full" else 1698)
    i32, N = jnp.int32, T_B + T_C
    if kind == "full":
        fn = functools.partial(pa.ragged_attend_pallas_paged, bblock=8)
        wrapper = pa.ragged_attend_pallas_paged
    else:
        fn = functools.partial(pa.ragged_attend_pallas_paged_window,
                               bblock=8, window=T_W)
        wrapper = pa.ragged_attend_pallas_paged_window
    args = (sds((N, T_HQ, D), jnp.bfloat16), kv, kv, sds((N,), i32),
            sds((), i32), sds((T_B, T_MP), i32), sds((N,), i32))
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()
    _assert_named_after_wrapper(compiled, wrapper)
    # 48 + 4,096 rows = 74 tiles of 56: the chunk's 4,096 rows stream their
    # pages 73 times a layer where blocks of 8 streamed them 512 times
    assert _assert_one_call_of_wide_tiles(compiled, wrapper, fn, N,
                                          *args) == 56


def test_selector_row_add_and_lightning_update_compile_for_v5e(chip):
    from aws_k8s_ansible_provisioner_tpu.ops import linear_attention as la

    sds, _ = _sala_pool(chip)
    i32, f32 = jnp.int32, jnp.float32
    kc = sds((2, S_B * S_MP + 1, S_HKV, PS // 16, D), f32)
    compiled = _compile(
        functools.partial(pa.selector_add_row_paged, stride=16), kc,
        sds((S_B, S_HKV, D), jnp.bfloat16), sds((S_B,), i32),
        sds((S_B, S_MP), i32), sds((), i32))
    assert "tpu_custom_call" in compiled.as_text()
    _assert_named_after_wrapper(compiled, pa.selector_add_row_paged)
    H, d = 32, 128
    row = sds((S_B, H, d), f32)
    compiled = _compile(
        lambda st, i, *a: la.kda_decode_update(st, i, 0, *a,
                                               delta_rule=False),
        sds((6, 1, S_B, H, d, d), f32), sds((), i32), row, row, row, row,
        sds((S_B, H), f32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["decode_fused_h8", f"mixed_c{CHUNK}"])
def test_part_scopes_leave_the_served_programs_as_they_were(chip,
                                                            monkeypatch,
                                                            program):
    """The names of the model's parts (models/parts.py) are METADATA: the
    0.6B's served ``decode_steps`` and ``mixed_step`` (int8, 32 slots, block
    8) compile to the same number of instructions and the same temporaries
    with ``jax.named_scope`` answering as it does and with it made a no-op —
    while only the first carries the names the trace's reader looks for."""
    import contextlib
    import re

    from aws_k8s_ansible_provisioner_tpu.config import (MODEL_REGISTRY,
                                                        ServingConfig)
    from aws_k8s_ansible_provisioner_tpu.serving import aot

    monkeypatch.setattr(pa, "supported", lambda: True)
    cfg = MODEL_REGISTRY["Qwen/Qwen3-0.6B"]
    plan = aot.ProgramPlan(cfg, ServingConfig(
        model=cfg.name, max_decode_slots=32, max_cache_len=2048,
        weights_dtype="int8", decode_bblock=8, kv_host_tier_bytes=0))
    params, cache = aot._abstract_state(plan, None,
                                        next(iter(chip.device_set)))

    def compiled():
        jax.clear_caches()
        _, fn, args, kwargs = next(
            p for p in aot.enumerate_programs(plan, None, params, cache,
                                              bblock=8) if p[0] == program)
        c = fn.lower(*args, **kwargs).compile()
        text = c.as_text()
        return (len(re.findall(r"^\s+(?:ROOT )?%\S+ = ", text, re.M)),
                c.memory_analysis().temp_size_in_bytes,
                len(re.findall(r'op_name="[^"]*/attn\.proj/', text)))

    named = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled()
    assert named[:2] == bare[:2]
    assert named[2] > 0 and bare[2] == 0


@pytest.mark.parametrize("pad", [8, 0])
def test_lfm2_decode_program_streams_its_expert_stacks_as_they_lie(
        chip, monkeypatch, pad):
    """LFM2-8B-A1B's served ``decode_steps`` (int8, 128 slots, block 8)
    compiles for the chip — 64-wide heads, two a 128-lane pool row (read off
    the head's width: ``ModelConfig.kv_lane_pack``), through the paged
    kernels as they are — with temporaries of megabytes. Without
    ``ops/moe.EVERY_EXPERT_TILE_PAD`` the 128-row batch is a whole MXU tile,
    XLA's layout assignment wants the gate / up stacks contraction-minor and
    hoists a transposed copy of both whole stacks out of the layer loop:
    5.2 GB, and the program no longer fits beside its 11.6 GB of operands."""
    from aws_k8s_ansible_provisioner_tpu.config import (MODEL_REGISTRY,
                                                        ServingConfig)
    from aws_k8s_ansible_provisioner_tpu.ops import moe
    from aws_k8s_ansible_provisioner_tpu.serving import aot

    monkeypatch.setattr(pa, "supported", lambda: True)
    monkeypatch.setattr(moe, "EVERY_EXPERT_TILE_PAD", pad)
    jax.clear_caches()      # the constant is read when the program is traced
    cfg = MODEL_REGISTRY["LiquidAI/LFM2-8B-A1B"]
    assert (cfg.pool_kv_heads, cfg.pool_head_dim) == (4, 128)
    plan = aot.ProgramPlan(cfg, ServingConfig(
        model=cfg.name, max_decode_slots=128, max_cache_len=2048,
        weights_dtype="int8", decode_bblock=8, kv_host_tier_bytes=0,
        prefill_chunk=512))
    params, cache = aot._abstract_state(plan, None,
                                        next(iter(chip.device_set)))
    assert cache["k"].shape == (6, 4097, 4, 64, 128)
    assert cache["conv_tail"].shape == (18, 128, 2, 2048)
    _, fn, args, kwargs = next(
        p for p in aot.enumerate_programs(plan, None, params, cache,
                                          bblock=8)
        if p[0] == "decode_fused_h8")
    compiled = fn.lower(*args, **kwargs).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert "tpu_custom_call" in compiled.as_text()
    if not pad:
        assert temp > 4 * 2**30
        return
    assert temp < 256 * 2**20
    # What the ``recur`` part moves through HBM, which
    # benchmark/benchlib/lfm2_opsbytes.conv_decode_dispatch counts: of the
    # operands and results of its fusions only the ``conv_tail`` leaf (sliced
    # for the read, updated in place) and the taps lie outside the compiler's
    # fast memory (``S(n)`` in a layout); W_in's rows B, C, X and the gated
    # row never pass HBM under that part's name.
    import re

    text = compiled.as_text()
    types = dict(re.findall(r"(%[\w.\-]+) = (\(?\w+\[[\d,]*\]\{[^}]*\})",
                            text))
    hbm, inside = set(), False
    for line in text.splitlines():
        if not line.startswith(" "):        # a computation's header, or "}"
            inside = "fused_computation" in line
            continue
        m = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (\(?.*?\)?) fusion\(([^)]*)\)"
                     r".*op_name=\"[^\"]*/recur/", line)
        if inside or not m:         # (a fusion's own body names no memory)
            continue
        seen = [m[1]] + [types.get(re.sub(r"/\*.*?\*/", "", a).strip(), "")
                         for a in m[2].split(",")]
        hbm |= {t.split("{")[0] for t in seen if t and "S(" not in t}
    assert hbm and hbm <= {"f32[18,128,2,2048]", "bf16[18,3,2048]"}, hbm


@pytest.mark.parametrize("program", ["decode_fused_h8", "mixed_c512"])
def test_falcon_h1_stage_compiles_at_a_query_group_of_five(
        chip, monkeypatch, program):
    """The Falcon-H1 stage's served ``decode_steps`` and ``mixed_step``
    (int8, 64 slots, block 8, a 512-row chunk) compile for the chip: 20
    query heads on 4 KV heads through the paged decode kernel as it is, the
    ragged entry with every group padded to 6 (a tile's blocks are slices
    of its [rows, Hq, D] queries and Mosaic slices whole sublane tiles: at
    20 heads it refused ``memref<64x24x128> -> 8x20x128``), the decode
    update over [256, 128] state tiles under the KDA kernel's name — beside
    11.4 GB of operands with temporaries of a few hundred MB."""
    from aws_k8s_ansible_provisioner_tpu.config import (MODEL_REGISTRY,
                                                        ServingConfig)
    from aws_k8s_ansible_provisioner_tpu.ops import linear_attention as la
    from aws_k8s_ansible_provisioner_tpu.serving import aot

    monkeypatch.setattr(pa, "supported", lambda: True)
    cfg = MODEL_REGISTRY["tiiuae/Falcon-H1-34B-Instruct-pp8-stage0"]
    plan = aot.ProgramPlan(cfg, ServingConfig(
        model=cfg.name, max_decode_slots=64, max_cache_len=2048,
        weights_dtype="int8", decode_bblock=8, kv_host_tier_bytes=0,
        prefill_chunk=512, prefill_buckets=(256, 512)))
    params, cache = aot._abstract_state(plan, None,
                                        next(iter(chip.device_set)))
    assert cache["k"].shape == (9, 2049, 4, 64, 128)
    assert cache["ssm_state"].shape == (9, 1, 64, 32, 256, 128)
    assert cache["ssm_conv"].shape == (9, 64, 3, 5120)
    _, fn, args, kwargs = next(
        p for p in aot.enumerate_programs(plan, None, params, cache,
                                          bblock=8)
        if p[0] == program)
    compiled = fn.lower(*args, **kwargs).compile()
    mem = compiled.memory_analysis()
    assert 11.3e9 < mem.argument_size_in_bytes < 11.6e9
    assert mem.temp_size_in_bytes < 768 * 2**20
    _assert_named_after_wrapper(compiled, la.kda_decode_update)
    _assert_named_after_wrapper(
        compiled, pa.decode_attend_pallas_paged if program.startswith("dec")
        else pa.ragged_attend_pallas_paged)
