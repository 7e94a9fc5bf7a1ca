"""The Falcon-H1 stage's configuration file against the catalog row (key by
key), its recount, the cut, the ``BENCHMARK.json`` entries, the ops-and-bytes
of the state-space mixers' decode update, the plain reference's own forms at
a tiny size, and the new layer-metric readers through the CPU rehearsal."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchlib import falcon_h1_opsbytes as fob
from benchlib import files
from benchlib import server_under_test as sut

CFG = files.load_json(os.path.join(files.BENCH_DIR, "configs",
                                   "falcon-h1-34b-pp8.json"))
BENCH = files.load_json(os.path.join(files.ROOT, "BENCHMARK.json"))
CELL = "falcon-h1-34b-pp8.reason-closed"
NEW = {"ssm_state_roofline_pct": "out_tok_s", "ssm_share_pct": "out_tok_s",
       "par_attn_share_pct": "out_tok_s", "ssm_span_share_pct": "ttft_p50_ms"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MAKER = files.load_module("weight_makers", "falcon_h1")
REF = files.load_module("reference", "falcon_h1")


def _count(pub: dict, layers: int) -> int:
    """Parameters from the PUBLISHED keys (the issue's recount)."""
    H, d = pub["hidden_size"], pub["head_dim"]
    q, kv = pub["num_attention_heads"] * d, pub["num_key_value_heads"] * d
    ssm, gn = pub["mamba_d_ssm"], pub["mamba_n_groups"] * pub["mamba_d_state"]
    conv = ssm + 2 * gn
    attn = 2 * H * q + 2 * H * kv
    mamba = H * (2 * ssm + 2 * gn + pub["mamba_n_heads"]) \
        + conv * pub["mamba_d_conv"] + conv + 3 * pub["mamba_n_heads"] \
        + ssm + ssm * H
    mlp = 3 * H * pub["intermediate_size"]
    return layers * (attn + mamba + mlp + 2 * H) \
        + 2 * pub["vocab_size"] * H + H


def test_the_uncut_model_is_33_64_b_and_the_stage_6_54():
    pub, par = CFG["published"], CFG["parameters"]
    assert _count(pub, 72) == par["total"] == 33_642_516_224
    assert _count(pub, 9) == par["stage_total"] == 6_544_954_208
    assert par["stage_int8_GB"] == 6.54
    # the maker's own count over the ModelConfig fields says the same
    got = MAKER.param_counts(CFG["model_config"])
    for name in ("attention_mixer", "ssm_mixer", "swiglu", "layer",
                 "embedding", "head"):
        assert par[name] == got[name], name
    assert got["total"] == par["stage_total"]
    assert MAKER.param_counts(CFG["model_config"], 72)["total"] \
        == par["total"]


def test_one_key_is_cut_and_every_other_published_key_stands():
    assert CFG["reduced"] == ["num_hidden_layers"] and CFG["reduced_why"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CFG["name"])
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    assert entry["file"] == "benchmark/configs/falcon-h1-34b-pp8.json"
    pub = CFG["published"]
    assert pub["num_hidden_layers"] == 72 and CFG["num_hidden_layers"] == 9
    for key, value in pub.items():
        if key != "num_hidden_layers":
            assert CFG[key] == value, key
    assert CFG["hf_config"] == dict(pub, num_hidden_layers=None)
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Falcon-H1-34B-Instruct")
        assert pub == row["config"] and CFG["source"] == row["source_url"]
    mc = sut.model_config_of(CFG)      # equals MODEL_REGISTRY's preset
    assert mc.name == CFG["registry_name"] and mc.layer_pattern == "h" * 9
    # every width and every multiplier is the row's
    assert (mc.hidden_size, mc.intermediate_size, mc.vocab_size) \
        == (5120, 21504, 261120)
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim) == (20, 4, 128)
    assert (mc.ssm_num_heads, mc.ssm_head_dim, mc.ssm_num_groups,
            mc.ssm_state_size, mc.conv_taps) \
        == (pub["mamba_n_heads"], pub["mamba_d_head"], pub["mamba_n_groups"],
            pub["mamba_d_state"], pub["mamba_d_conv"])
    assert mc.ssm_size == pub["mamba_d_ssm"] \
        == pub["mamba_expand"] * mc.hidden_size * 2 // 5
    for name in ("embedding_multiplier", "lm_head_multiplier",
                 "attention_in_multiplier", "attention_out_multiplier",
                 "key_multiplier", "ssm_in_multiplier",
                 "ssm_out_multiplier"):
        assert getattr(mc, name) == pub[name], name
    assert list(mc.ssm_multipliers) == pub["ssm_multipliers"]
    assert list(mc.mlp_multipliers) == pub["mlp_multipliers"]
    assert not mc.tie_embeddings and mc.rope_theta == pub["rope_theta"]
    assert set(CFG["assumed"]) >= {
        "layer_equations", "gate_then_group_norm", "ssm_multipliers_order",
        "mamba_use_mlp", "softplus", "state_precision", "eos_token_id",
        "hf_names", "weights", "tokenizer"}
    assert "eight" in CFG["deployment"].lower() and "stage 0" \
        in CFG["deployment"].lower()


def test_the_cell_is_the_issues():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CFG["name"], "reason-closed", 1)
    assert BENCH["workloads"][-1] == cell
    assert BENCH["configs"][-1]["name"] == CFG["name"]
    flags = CFG["server_flags"]
    assert flags == [
        "--model", "tiiuae/Falcon-H1-34B-Instruct-pp8-stage0",
        "--max-decode-slots", "64", "--max-cache-len", "2048",
        "--weights-dtype", "int8", "--kv-dtype", "auto",
        "--decode-bblock", "8", "--kv-host-tier-bytes", "0",
        "--prefill-chunk", "512", "--prefill-buckets", "256,512"]
    assert CFG["expect"] == {
        "attention_impl": "pallas", "paged": True, "decode_bblock": 8,
        "slots": 64, "window": 2048, "kv_int8": False, "pipeline": 1,
        "ragged": 1}
    assert CFG["correctness_prompt_lens"] == [4, 63, 300]
    assert [m["name"] for m in BENCH["per_layer"][-4:]] == list(NEW)
    for m in BENCH["per_layer"][-4:]:
        assert m["workloads"] == [CELL] and m["moves"] == NEW[m["name"]]
        assert m["source"] == "device_trace" and m["unit"] == "%"
        assert files.load_module("layer_metrics", m["name"]) is not None
    # the cell reports ttft_p50_ms, out_tok_s and setup_s; tpot_p95_ms lists
    # its own cells
    c = files.Cell(os.path.join(files.ROOT, "BENCHMARK.json"), CELL)
    assert c.metric_names("end_to_end") == ["ttft_p50_ms", "out_tok_s",
                                            "setup_s"]
    # weights + state + pool: 71 % of a 16-GB chip before temporaries
    mc = CFG["model_config"]
    kv = 9 * 2 * mc["num_kv_heads"] * mc["head_dim"] * 2
    state = 9 * (32 * 256 * 128 + 3 * 5120) * 4
    assert (kv, state) == (18_432, 9 * 4_255_744)
    held = CFG["parameters"]["stage_total"] + 64 * state + 64 * 2048 * kv
    assert 0.70 < held / 16e9 < 0.73


def test_ops_and_bytes_of_the_decode_update():
    mc = CFG["model_config"]
    rec = {"ssm_slots": 64, "horizon": 8}
    flops, byts = fob.ssm_decode_dispatch(mc, rec)
    state = 32 * 256 * 128 * 4
    assert byts == 64 * 9 * 8 * (2 * state + 4 * (3 * 32 * 256 + 3 * 32 * 128))
    # read and written once a substep: 4.83 GB of state a decode step
    assert abs(64 * 9 * 2 * state / 1e9 - 4.83) < 0.01
    assert flops / byts < 1                   # bandwidth bounds it
    assert fob.is_falcon_h1(mc)
    assert not fob.is_falcon_h1({"layer_pattern": "ccgc"})
    import re

    assert re.search(fob.KERNEL_RE, "%kda_decode_update.3 = (f32[64,32,128]")
    assert not re.search(fob.KERNEL_RE, "%kda_decode_update_x = f32[]")


def test_the_reference_pads_and_holds_its_controls():
    """A sequence padded to PAD_TO rows gives the rows an unpadded pass
    gives (every operator is causal); each control is another model."""
    import jax

    from aws_k8s_ansible_provisioner_tpu.config import tiny_falcon_h1

    mc = dataclasses.asdict(tiny_falcon_h1())
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
    assert {"the SSM branch dropped", "the attention branch dropped"} \
        <= set(REF.CONTROLS)
    assert "a bfloat16 state" in {**REF.CONTROLS, **REF.CONTROLS_REPORTED}
    for label, kw in REF.CONTROLS.items():
        off = np.asarray(REF.forward(mc, tree, ids, 16, **kw))
        assert np.abs(off - own).max() > 0.05, label


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu(trace):
    p = subprocess.run(
        [sys.executable, os.path.join(files.BENCH_DIR, "run.py"),
         "--rehearsal", os.path.join(files.BENCH_DIR, "tests", "rehearsal",
                                     "BENCHMARK.falcon_h1.json"),
         "--workload", "tiny-falcon-h1.closed", "--seed", "3000000011",
         "--seconds", "3", "--trace", trace],
        cwd=files.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True,
        capture_output=True, timeout=1200)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    if trace == "1":
        # no device plane on the CPU: the four new readers find nothing,
        # return None and raise nothing
        assert not set(NEW) & set(line["metrics"])
        assert "layer metric ssm_share_pct: nothing to read" in p.stdout
