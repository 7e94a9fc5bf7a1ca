"""The Solar-Open2 configuration file's arithmetic (parameters, bytes,
``reduced`` against the published values), the ops-and-bytes of its two new
mechanisms, and the new layer-metric readers through the CPU rehearsal."""

import json
import os
import subprocess
import sys

from benchlib import files, kda_opsbytes
from benchlib import server_under_test as sut

CFG = files.load_json(os.path.join(files.BENCH_DIR, "configs",
                                   "solar-open2-250b-ep8.json"))
BENCH = files.load_json(os.path.join(files.ROOT, "BENCHMARK.json"))
CELL = "solar-open2-250b-ep8.reason-closed"
NEW = ("kda_state_roofline_pct", "kda_share_pct",
       "moe_held_ffn_roofline_pct", "moe_held_rows_pct",
       "moe_held_share_pct", "moe_held_experts_hit_pct")


def _count(pub: dict, layers: int, held: int, vocab: int) -> dict:
    """Parameters from the published keys (the issue's recount)."""
    H, Im = pub["hidden_size"], pub["moe_intermediate_size"]
    q = pub["num_attention_heads"] * pub["head_dim"]
    kv = pub["num_key_value_heads"] * pub["head_dim"]
    lin = pub["linear_attn_config"]
    D, r = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    ffn = 3 * H * Im * pub["n_shared_experts"] \
        + H * pub["n_routed_experts"] + pub["n_routed_experts"] + 2 * H
    gqa = 2 * H * q + 2 * H * kv + q * H + ffn           # Wq, Wg; Wk, Wv; Wo
    kda = 4 * H * D + 2 * (H * r + r * D) + H * lin["num_heads"] \
        + lin["short_conv_kernel_size"] * 3 * D + lin["num_heads"] + D \
        + lin["head_dim"] + ffn
    expert = 3 * H * Im
    n_gqa = layers // (pub["gqa_interval"] + 1)
    return {"gqa": gqa, "kda": kda, "expert": expert,
            "total": n_gqa * gqa + (layers - n_gqa) * kda
            + layers * held * expert + 2 * vocab * H + H}


def test_the_published_model_is_250b_with_15b_active():
    pub = CFG["published"]
    n = _count(pub, pub["num_hidden_layers"], pub["n_routed_experts"],
               pub["vocab_size"])
    assert 250.0e9 < n["total"] < 250.6e9
    active = n["total"] - pub["num_hidden_layers"] * (
        pub["n_routed_experts"] - pub["num_experts_per_tok"]) * n["expert"]
    assert 14.5e9 < active < 15.0e9
    assert abs(n["gqa"] - 126.1e6) < 0.1e6 and abs(n["kda"] - 154.8e6) < 0.1e6


def test_the_cut_is_what_the_file_states():
    pub, par = CFG["published"], CFG["parameters"]
    n = _count(pub, CFG["num_hidden_layers"], CFG["n_routed_experts"],
               CFG["vocab_size"])
    assert n["gqa"] == par["gqa_layer_outside_routed_experts"]
    assert n["kda"] == par["kda_layer_outside_routed_experts"]
    assert n["expert"] == par["one_expert"]
    assert par["held_experts_all_layers"] == 8 * 40 * n["expert"]
    assert n["total"] == par["total_held"]
    assert abs(n["total"] / 2**30 - par["int8_GiB"]) < 0.01
    # every top-level number is the published one, or is listed in reduced
    changed = sorted(k for k, v in pub.items()
                     if isinstance(v, (int, float)) and CFG[k] != v)
    assert changed == sorted(CFG["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"]) == (8, 40, 24576)
    assert CFG["vocab_size"] * 8 == pub["vocab_size"]
    assert CFG["n_routed_experts"] * 8 == pub["n_routed_experts"]
    assert CFG["linear_attn_config"] == pub["linear_attn_config"]
    assert "8 that share each layer" in CFG["deployment"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CFG["name"])
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]


def test_model_config_follows_the_file_and_the_program_accepts_it():
    mc = sut.model_config_of(CFG)          # exits on any inconsistency
    assert (mc.num_layers, mc.num_experts, mc.router_width) == (8, 40, 320)
    assert mc.recurrent and mc.expert_share and mc.num_attn_layers == 2
    assert mc.layer_pattern == "gkkk" and mc.kda_per_period == 3
    assert hash(mc) is not None            # a jit static argument
    # the widths no cut may touch
    pub = CFG["published"]
    assert mc.hidden_size == pub["hidden_size"]
    assert mc.moe_intermediate_size == pub["moe_intermediate_size"]
    assert mc.num_experts_per_tok == pub["num_experts_per_tok"]
    assert mc.kda_size == 64 * 128 and mc.q_size == 64 * 128


def test_the_cell_and_its_metrics_are_as_the_issue_names_them():
    cell = files.Cell(os.path.join(files.ROOT, "BENCHMARK.json"), CELL)
    assert cell.chips == 1
    t = cell.traffic
    assert t["kind"] == "closed_loop" and t["clients"] == "slots"
    assert (t["prompt_len"]["min"], t["prompt_len"]["max"]) == (128, 512)
    assert (t["output_len"]["min"], t["output_len"]["max"]) == (512, 1024)
    assert (t["ramp_s"], t["first_out_min"], t["grace_s"]) == (12, 16, 30)
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= 1536
    assert set(cell.metric_names("end_to_end")) == {
        "ttft_p50_ms", "out_tok_s", "setup_s"}
    for name in NEW:
        assert cell.metric(name)["workloads"] == [CELL]
        assert files.load_module("layer_metrics", name) is not None


def test_ops_and_bytes_of_the_state_and_the_held_experts():
    mc = CFG["model_config"]
    rec = {"kda_slots": 64, "horizon": 8, "moe_rows": 8 * 64 * 8,
           "moe_rows_held": 64 * 8, "moe_experts_hit": 32.0}
    flops, byts = kda_opsbytes.decode_dispatch(mc, rec)
    # 64 slots x 6 layers x 8 substeps x (2 x 4 MiB + the rows)
    state = 64 * 6 * 8 * 2 * 4 * 2**20
    assert state < byts < 1.02 * state
    assert flops / byts < 1.0              # far under the ridge
    _, held = kda_opsbytes.held_decode_dispatch(mc, rec, 1)
    # 32 of 40 held experts hit: their three int8 stacks, 8 layers, 8 steps
    assert held > 32 * 3 * 4096 * 1280 * 8 * 8
    import re

    assert re.search(kda_opsbytes.state_ops_re(mc, 64),
                     "%fusion.7 = f32[64,64,128] fusion(f32[2,3,64,64,128,"
                     "128]{5,4,3,2,1,0} %p)")
    for operand in ("s8[2,40,4096,1280]", "s8[2,3,40,1280,4096]",
                    "s8[40,4096,1280]"):
        assert re.search(kda_opsbytes.held_expert_ops_re(mc),
                         f"%fusion.9 = bf16[40,64,1280] fusion({operand} %w)")
    assert not re.search(kda_opsbytes.held_expert_ops_re(mc),
                         "%f = bf16[64,4096] fusion(s8[4096,1280] %shared)")
    dense = {"num_layers": 2, "hidden_size": 8}
    assert kda_opsbytes.state_ops_re(dense, 4) is None


def test_the_new_readers_through_the_rehearsal():
    """run.py on the tiny hybrid, traced, on the CPU: served against the
    plain reference (``correct``), the program-span reader finds the
    records' new fields, and the device-trace readers return nothing where
    there is no device plane — never an approximation."""
    reh = os.path.join(files.BENCH_DIR, "tests", "rehearsal",
                       "BENCHMARK.solar.json")
    p = subprocess.run(
        [sys.executable, os.path.join(files.BENCH_DIR, "run.py"),
         "--rehearsal", reh, "--workload", "tiny-solar.closed", "--seed",
         "3000000019", "--seconds", "3", "--trace", "1"],
        cwd=files.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    held = line["metrics"]["moe_held_rows_pct"]["value"]
    assert 10.0 < held < 45.0              # 4 of 16 held
    hit = line["metrics"]["moe_held_experts_hit_pct"]["value"]
    assert 0.0 < hit <= 100.0              # of the 4 held
    assert line["metrics"]["prefix_hit_tok_pct"]["value"] == 0
    for name in ("kda_state_roofline_pct", "kda_share_pct",
                 "moe_held_ffn_roofline_pct", "moe_held_share_pct"):
        assert name not in line["metrics"]
