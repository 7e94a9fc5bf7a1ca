"""AOT compiled-program registry tests (serving/aot.py).

Three layers, cheapest first:

- pure-plan tests: ``ProgramPlan`` sizing arithmetic pinned against a REAL
  tiny ``Engine``'s derived attributes — the AOT manifest is only trustworthy
  if its operand shapes can never drift from what the engine dispatches;
- manifest plumbing: ``verify_manifest`` schema rejection, the engine's
  ``load_aot_manifest`` fingerprint/fit gates, the CLI's non-zero no-fit
  exit, and the committed ``AOT_QWEN3_8B_v5e8.json`` artifact staying
  schema-valid with a FIT verdict;
- ``aot_smoke`` (make aot-smoke): a real deviceless host-platform compile of
  the full tiny-config program set, end to end through ``build_manifest``.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu.config import (
    MeshConfig, ServingConfig, tiny_qwen3)
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.parallel.mesh import make_mesh
from aws_k8s_ansible_provisioner_tpu.serving import aot
from aws_k8s_ansible_provisioner_tpu.serving import programs as aot_programs
from aws_k8s_ansible_provisioner_tpu.serving.aot import (
    LEDGER_FIELDS, MANIFEST_SCHEMA, PROGRAM_FIELDS, ProgramPlan,
    build_ledger, build_manifest, enumerate_programs, verify_manifest)
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_serving(**kw):
    base = dict(model="tiny-qwen3", max_decode_slots=4, max_cache_len=64,
                page_size=8, prefill_buckets=(16, 32), dtype="float32",
                weights_dtype="bf16")
    base.update(kw)
    return ServingConfig(**base)


def _mk_engine(serving, mesh=None):
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return Engine(cfg, params, serving, mesh=mesh)


# -- plan vs engine ---------------------------------------------------------


@pytest.mark.parametrize("srv_kw", [
    {},
    {"max_cache_len": 500},               # 256-rounding path
    {"kv_pool_pages": 16},                # explicit pool size
    {"max_cache_len": 100, "page_size": 16},   # the page does not divide
    {"kv_dtype": "int8", "page_size": 32},
    {"prefill_chunk": 16},
])
def test_plan_matches_real_engine_sizing(srv_kw):
    """Every derived size the AOT operand shapes hang off must equal the
    attribute the engine actually computes — drift here would make the
    manifest describe programs the engine never dispatches."""
    serving = _tiny_serving(**srv_kw)
    plan = ProgramPlan(tiny_qwen3(), serving)
    eng = _mk_engine(serving)
    assert plan.num_slots == eng.num_slots
    assert plan.max_len == eng.max_len
    assert plan.buckets == eng.buckets
    assert plan.kv_quant == eng.kv_quant
    assert plan.pages_per_slot == eng.pages_per_slot
    assert plan.total_pages == eng.cache["k"].shape[1]
    assert plan.chunk == eng._chunk_size


def test_plan_matches_mesh_engine_pool_split(cpu_devices):
    """dp meshes split the pool into per-group partitions, each with its own
    scratch page — the plan must reproduce the engine's dp-aware total."""
    serving = _tiny_serving()
    mesh = make_mesh(MeshConfig(dp=2, tp=1), devices=jax.devices("cpu")[:2])
    plan = ProgramPlan(tiny_qwen3(), serving, dp=2)
    eng = _mk_engine(serving, mesh=mesh)
    assert plan.total_pages == eng.cache["k"].shape[1]
    assert plan.num_slots == eng.num_slots


def test_plan_rejects_indivisible_layouts():
    with pytest.raises(ValueError, match="divisible by dp"):
        ProgramPlan(tiny_qwen3(), _tiny_serving(max_decode_slots=3), dp=2)
    with pytest.raises(ValueError, match="divisible by dp"):
        ProgramPlan(tiny_qwen3(), _tiny_serving(kv_pool_pages=15), dp=2)
    with pytest.raises(ValueError, match="bucket"):
        ProgramPlan(tiny_qwen3(), _tiny_serving(prefill_buckets=(4096,)))


def test_enumeration_covers_every_program_family():
    """The program set must mirror warmup's full scope: one program per
    bucket, the logprob/batch/chunk variants, the ONE fused decode program
    (its substep count is the ``steps`` operand: no one-step program beside
    it) plus the penalties and logprobs variants, and spec-verify iff
    speculation is on."""
    serving = _tiny_serving(spec_decode=True, spec_k=3)
    plan = ProgramPlan(tiny_qwen3(), serving)
    params, cache = aot._abstract_state(plan, None)
    names = [p[0] for p in enumerate_programs(plan, None, params, cache)]
    assert names.count("prefill_b16") == 1 and names.count("prefill_b32") == 1
    for expect in ("prefill_b16_logprobs", "prefill_batch_n4_b16",
                   "prefill_chunk_c32", "decode_fused_h8",
                   "decode_fused_h8_penalties", "decode_fused_h8_logprobs",
                   "spec_verify_r4"):
        assert expect in names, f"{expect} missing from {names}"
    decodes = [p for p in enumerate_programs(plan, None, params, cache)
               if p[1] is aot_programs.decode_steps]
    assert [p[0] for p in decodes] == [
        "decode_fused_h8", "decode_fused_h8_penalties",
        "decode_fused_h8_logprobs"]
    for _, _, args, kwargs in decodes:      # the horizon sizes, steps counts
        assert args[1] == 8 and kwargs["steps"].shape == () \
            and kwargs["steps"].dtype == jnp.int32
    no_spec = ProgramPlan(tiny_qwen3(), _tiny_serving())
    names2 = [p[0] for p in enumerate_programs(
        no_spec, None, *aot._abstract_state(no_spec, None))]
    assert not any(n.startswith("spec_verify") for n in names2)


def test_enumeration_holds_one_mixed_program_a_variant():
    """``mixed_step`` runs its layers at two widths INSIDE one program (the
    chunk's length, an operand, picks): the set that is compiled ahead keeps
    ONE entry a variant, at the one chunk width its operands have."""
    plan = ProgramPlan(tiny_qwen3(), _tiny_serving(
        decode_pipeline=1, ragged_attention=1, ragged_features=1))
    assert aot_programs.mixed_narrow_rows(
        plan.cfg, plan.num_slots, plan.chunk, 8, 1, plan.pages_per_slot,
        jnp.float32) == plan.chunk // 2
    mixed = [p for p in enumerate_programs(
        plan, None, *aot._abstract_state(plan, None))
        if p[1] is aot_programs.mixed_step]
    assert [p[0] for p in mixed] == [f"mixed_c{plan.chunk}",
                                     f"mixed_c{plan.chunk}_guided"]
    for _, _, args, _ in mixed:
        assert args[5].shape == (1, plan.chunk)


def test_sharded_bytes_divides_by_mesh_axes(cpu_devices):
    """Per-chip ledger bytes: tp=2 halves the KV pool (heads sharded) and
    shrinks params; replicated leaves (norms) still count whole."""
    serving = _tiny_serving()
    plan1 = ProgramPlan(tiny_qwen3(), serving)
    p1, c1 = aot._abstract_state(plan1, None)
    solo = build_ledger(plan1, None, p1, c1, [])
    plan2 = ProgramPlan(tiny_qwen3(), serving, tp=2)
    mesh = aot._mesh_for(jax.devices("cpu"), 1, 2)
    p2, c2 = aot._abstract_state(plan2, mesh)
    tp2 = build_ledger(plan2, mesh, p2, c2, [])
    assert tp2["kv_bytes_per_chip"] * 2 == solo["kv_bytes_per_chip"]
    assert tp2["params_bytes_per_chip"] < solo["params_bytes_per_chip"]
    # replication floor: tp can't shrink params below the norm/etc leaves
    assert tp2["params_bytes_per_chip"] > solo["params_bytes_per_chip"] // 4


# -- manifest plumbing ------------------------------------------------------


def _fake_manifest(plan, fit=True):
    entry = {"name": "decode_fused_h8", "compile_seconds": 1.0,
             "argument_bytes": 10, "output_bytes": 10, "temp_bytes": 100,
             "generated_code_bytes": 10}
    cap = 16 * 2**30
    total = 1000 if fit else cap + 1
    return {
        "schema": MANIFEST_SCHEMA, "platform": "host", "topology": "host:8",
        "jax_version": jax.__version__, "bblock": 1,
        "config": plan.fingerprint(), "programs": [entry],
        "hbm_ledger": {
            "capacity_bytes_per_chip": cap, "params_bytes_per_chip": total,
            "kv_bytes_per_chip": 0, "max_temp_bytes": 0,
            "total_bytes": total, "headroom_bytes": cap - total,
            "fit": fit},
        "total_compile_seconds": 1.0,
    }


def test_verify_manifest_rejects_structural_damage():
    plan = ProgramPlan(tiny_qwen3(), _tiny_serving())
    good = _fake_manifest(plan)
    verify_manifest(good)  # baseline: passes
    for breakage, match in [
            (lambda m: m.update(schema="nope"), "schema"),
            (lambda m: m.pop("hbm_ledger"), "hbm_ledger"),
            (lambda m: m.update(programs=[]), "no programs"),
            (lambda m: m["programs"][0].pop("temp_bytes"), "temp_bytes"),
            (lambda m: m["hbm_ledger"].pop("fit"), "fit")]:
        bad = json.loads(json.dumps(good))
        breakage(bad)
        with pytest.raises(ValueError, match=match):
            verify_manifest(bad)


def test_engine_adopts_matching_manifest(tmp_path):
    """load_aot_manifest: ProgramPlan's fingerprint must be accepted by an
    engine built from the same config (the plan<->engine contract), the
    ledger lands on the gauge, and the summary is /healthz-shaped."""
    serving = _tiny_serving()
    path = tmp_path / "m.json"
    path.write_text(json.dumps(
        _fake_manifest(ProgramPlan(tiny_qwen3(), serving))))
    eng = _mk_engine(serving)
    got = eng.load_aot_manifest(str(path))
    assert eng.aot is got and got["fit"] and got["programs"] == 1
    assert "tpu_serve_hbm_compiled_bytes 1000.0" \
        in eng.metrics.registry.render()


def test_engine_rejects_mismatched_or_nofit_manifest(tmp_path):
    serving = _tiny_serving()
    eng = _mk_engine(serving)
    other = _fake_manifest(
        ProgramPlan(tiny_qwen3(), _tiny_serving(page_size=16)))
    p1 = tmp_path / "mismatch.json"
    p1.write_text(json.dumps(other))
    with pytest.raises(ValueError, match="different program set"):
        eng.load_aot_manifest(str(p1))
    nofit = _fake_manifest(ProgramPlan(tiny_qwen3(), serving), fit=False)
    p2 = tmp_path / "nofit.json"
    p2.write_text(json.dumps(nofit))
    with pytest.raises(RuntimeError, match="NO-FIT"):
        eng.load_aot_manifest(str(p2))
    assert eng.aot is None


def test_cli_exits_nonzero_on_nofit(tmp_path, monkeypatch):
    """The deploy-gate contract: a no-fit ledger is a non-zero exit."""
    nofit = _fake_manifest(ProgramPlan(tiny_qwen3(), _tiny_serving()),
                           fit=False)
    monkeypatch.setattr(aot, "build_manifest", lambda *a, **k: nofit)
    out = tmp_path / "m.json"
    rc = aot.main(["--model", "tiny-qwen3", "--platform", "host",
                   "--tp", "1", "--quiet", "--out", str(out)])
    assert rc != 0
    assert json.loads(out.read_text())["hbm_ledger"]["fit"] is False


def test_committed_qwen3_manifest_is_valid_and_fits():
    """The committed v5e-8 artifact: schema-valid, built for Qwen/Qwen3-8B
    tp=8 against the 16 GiB v5e chip, every program carries a real compile
    time and TPU memory analysis, and the verdict is FIT."""
    path = os.path.join(REPO, "AOT_QWEN3_8B_v5e8.json")
    with open(path, encoding="utf-8") as f:
        m = json.load(f)
    verify_manifest(m)
    assert m["config"]["model"] == "Qwen/Qwen3-8B"
    assert m["config"]["tp"] == 8
    led = m["hbm_ledger"]
    assert led["capacity_bytes_per_chip"] == 16 * 2**30
    assert led["fit"] and led["headroom_bytes"] > 0
    assert led["total_bytes"] == (led["params_bytes_per_chip"]
                                  + led["kv_bytes_per_chip"]
                                  + led["max_temp_bytes"])
    assert all(p["compile_seconds"] > 0 for p in m["programs"])
    if m["platform"] == "tpu":
        # deviceless TPU lowering produces real per-chip memory analysis
        assert led["max_temp_bytes"] > 0


# -- the smoke: real deviceless compile of the tiny program set -------------


@pytest.mark.aot_smoke
def test_aot_smoke_deviceless_compile_and_fit(tmp_path):
    """make aot-smoke: host-platform deviceless compile of the full tiny
    program set through build_manifest — schema-checked, per-program compile
    seconds recorded, and the fit verdict asserted both ways (the tiny model
    fits 16 GiB; nothing fits a micro-budget)."""
    serving = _tiny_serving(max_decode_slots=2, prefill_buckets=(16,),
                            max_cache_len=32, decode_horizon=2,
                            max_prefill_batch=2)
    cfg = tiny_qwen3()
    m = build_manifest(cfg, serving, devices=jax.devices())
    verify_manifest(m)
    assert m["hbm_ledger"]["fit"] is True
    assert m["total_compile_seconds"] > 0
    names = [p["name"] for p in m["programs"]]
    assert "prefill_b16" in names and "decode_fused_h2" in names \
        and "decode_h1" not in names
    # the same compiled set against a micro HBM budget must flip the verdict
    plan = ProgramPlan(cfg, serving)
    params, cache = aot._abstract_state(plan, None)
    tiny_cap = build_ledger(plan, None, params, cache, m["programs"],
                            hbm_gib=1e-6)
    assert tiny_cap["fit"] is False and tiny_cap["headroom_bytes"] < 0
    # round-trips through disk + the engine's verify path
    out = tmp_path / "aot_tiny.json"
    out.write_text(json.dumps(m))
    verify_manifest(json.loads(out.read_text()))
    assert set(PROGRAM_FIELDS) <= set(m["programs"][0])
    assert set(LEDGER_FIELDS) <= set(m["hbm_ledger"])
