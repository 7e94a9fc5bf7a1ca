"""The LFM2-8B-A1B configuration file's arithmetic (parameters, ``reduced``
and ``published`` against the catalog row, key by key), the mix it runs, the
ops-and-bytes of its three mechanisms, the plain reference's own forms at a
tiny size, and the new layer-metric readers through the CPU rehearsal."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchlib import files, lfm2_opsbytes
from benchlib import server_under_test as sut

CFG = files.load_json(os.path.join(files.BENCH_DIR, "configs",
                                   "lfm2-8b-a1b-int8.json"))
BENCH = files.load_json(os.path.join(files.ROOT, "BENCHMARK.json"))
CELL = "lfm2-8b-a1b-int8.reason-closed"
NEW = ("conv_share_pct", "conv_state_roofline_pct", "experts_wide_share_pct",
       "experts_wide_roofline_pct", "attn64_roofline_pct", "attn64_share_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MAKER = files.load_module("weight_makers", "lfm2_moe")
REF = files.load_module("reference", "lfm2_moe")


def _count(pub: dict) -> dict:
    """Parameters from the published keys (the issue's recount), norms and
    the routers' selection bias left out; tied embeddings; head_dim =
    hidden / heads."""
    H = pub["hidden_size"]
    d = H // pub["num_attention_heads"]
    q, kv = pub["num_attention_heads"] * d, pub["num_key_value_heads"] * d
    conv = H * 3 * H + H * H + pub["conv_L_cache"] * H
    attn = 2 * H * q + 2 * H * kv
    expert = 3 * H * pub["moe_intermediate_size"]
    routed = pub["num_experts"] * expert + H * pub["num_experts"]
    dense = 3 * H * pub["intermediate_size"]
    types = pub["layer_types"]
    nd = pub["num_dense_layers"]
    emb = pub["vocab_size"] * H
    mixers = types.count("conv") * conv + types.count("full_attention") * attn
    return {"conv_mixer": conv, "attention_mixer": attn, "expert": expert,
            "routed_ffn": routed, "dense_ffn": dense, "embedding": emb,
            "total": emb + mixers + nd * dense + (len(types) - nd) * routed,
            "active": emb + mixers + nd * dense + (len(types) - nd) * (
                pub["num_experts_per_tok"] * expert + H * pub["num_experts"])}


def test_the_uncut_model_is_8_34_b_of_which_1_56_are_active():
    got, par = _count(CFG["published"]), CFG["parameters"]
    for name, n in got.items():
        assert par[name] == n, name
    assert abs(got["total"] / 1e9 - 8.34) < 0.005
    assert abs(got["active"] / 1e9 - 1.56) < 0.005
    assert par["int8_GB"] == 8.34
    # the maker's own count over the ModelConfig fields says the same
    assert MAKER.param_counts(CFG["model_config"]) == got


def test_nothing_is_cut_and_every_published_key_stands():
    assert CFG["reduced"] == [] and "reduced_why" not in CFG
    entry = next(c for c in BENCH["configs"] if c["name"] == CFG["name"])
    assert entry["reduced"] == [] and entry["source"] == CFG["source"]
    assert CFG["hf_config"] == CFG["published"]
    for key, value in CFG["published"].items():
        assert CFG[key] == value, key
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-8B-A1B")
        assert CFG["published"] == row["config"]
        assert CFG["source"] == row["source_url"]
    mc = sut.model_config_of(CFG)      # equals MODEL_REGISTRY's preset
    assert mc.name == CFG["registry_name"]
    assert mc.layer_pattern == "".join(
        {"conv": "c", "full_attention": "g"}[t] for t in CFG["layer_types"])
    assert mc.head_dim * mc.num_heads == mc.hidden_size
    assert mc.conv_taps == CFG["conv_L_cache"]
    assert mc.route_norm_eps == 1e-6 and mc.route_scale \
        == CFG["routed_scaling_factor"]


def test_the_cell_is_the_issues():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CFG["name"], "reason-closed", 1)
    flags = CFG["server_flags"]
    for flag, value in (("--max-decode-slots", "128"),
                        ("--max-cache-len", "2048"),
                        ("--weights-dtype", "int8"), ("--kv-dtype", "auto"),
                        ("--decode-bblock", "8"),
                        ("--kv-host-tier-bytes", "0"),
                        ("--prefill-chunk", "512")):
        assert flags[flags.index(flag) + 1] == value
    assert CFG["expect"]["slots"] == 128 and CFG["expect"]["window"] == 2048
    for name in NEW:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
    # weights + pool: 72 % of a 16-GB chip
    mc = CFG["model_config"]
    kv = 6 * 2 * mc["num_kv_heads"] * mc["head_dim"] * 2
    assert kv == 12_288
    held = CFG["parameters"]["total"] + 128 * 2048 * kv
    assert 0.70 < held / 16e9 < 0.75


def test_ops_and_bytes_of_the_three_mechanisms():
    mc = CFG["model_config"]
    rec = {"state_slots": 128, "horizon": 8, "attn_pages_live": 8 * 128 * 13,
           "moe_rows": 8 * 512, "moe_experts_hit": 32.0}
    flops, byts = lfm2_opsbytes.conv_decode_dispatch(mc, rec)
    # what the ``recur`` fusions move through HBM: the tail read and written
    # (2 x 2 x 2,048 float32 a slot) + the taps (3 x 2,048 bf16 a layer)
    assert byts == 18 * 8 * (128 * 2 * 2 * 2048 * 4 + 3 * 2048 * 2) \
        == 18 * 8 * (128 * 32_768 + 12_288)
    assert flops / byts < 1                   # bandwidth bounds it
    flops, byts = lfm2_opsbytes.attn_decode_dispatch(mc, rec, 64, 128)
    assert byts == rec["attn_pages_live"] * 6 * 131_072 \
        + 128 * 8 * 6 * 2 * 32 * 64 * 2
    assert flops / byts < 240                 # under the v5e's ridge
    assert lfm2_opsbytes.is_lfm2(mc)
    assert not lfm2_opsbytes.is_lfm2({"layer_pattern": "wwwg"})


def test_the_reference_pads_and_holds_its_controls():
    """A sequence padded to PAD_TO rows gives the rows an unpadded pass
    gives (every operator is causal); each control is another model."""
    import jax

    from aws_k8s_ansible_provisioner_tpu.config import tiny_lfm2

    mc = dataclasses.asdict(tiny_lfm2())
    tree = jax.tree.map(
        lambda a: a.astype("float32") if a.dtype == "bfloat16" else a,
        MAKER.make(mc, 11, True))
    ids = np.random.default_rng(3).integers(2, 128, 37).tolist()
    own = np.asarray(REF.logits(mc, tree, ids, 16))
    pad_to, REF.PAD_TO = REF.PAD_TO, 1
    try:
        bare = np.asarray(REF.logits(mc, tree, ids, 16))
    finally:
        REF.PAD_TO = pad_to
    assert np.abs(own - bare).max() < 1e-5
    assert set(REF.CONTROLS_SEEN_LONG) <= set(REF.CONTROLS)
    for label, kw in REF.CONTROLS.items():
        off = np.asarray(REF.forward(mc, tree, ids, 16, **kw)[0])
        assert np.abs(off - own).max() > 0.25, label
    # handed its own choices it is the plain reference
    lg, idx = REF.forward(mc, tree, ids, 16)
    again, _ = REF.forward(mc, tree, ids, 16, routing=np.asarray(idx))
    assert np.abs(np.asarray(again) - np.asarray(lg)).max() < 1e-6


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu(trace):
    p = subprocess.run(
        [sys.executable, os.path.join(files.BENCH_DIR, "run.py"),
         "--rehearsal", os.path.join(files.BENCH_DIR, "tests", "rehearsal",
                                     "BENCHMARK.lfm2.json"),
         "--workload", "tiny-lfm2.closed", "--seed", "3000000011",
         "--seconds", "3", "--trace", trace],
        cwd=files.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True,
        capture_output=True, timeout=1200)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    if trace == "1":
        # no device plane on the CPU: the six new readers find nothing,
        # return None and raise nothing
        assert not set(NEW) & set(line["metrics"])
        assert "layer metric conv_share_pct: nothing to read" in p.stdout
