"""The Trinity-Mini configuration file's arithmetic (parameters, bytes,
``reduced`` and ``published`` against the catalog row, key by key), the mix's
lengths, the ops-and-bytes of its two kinds of attention, and the new
layer-metric readers through the CPU rehearsal."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchlib import files, trinity_opsbytes
from benchlib import server_under_test as sut

CFG = files.load_json(os.path.join(files.BENCH_DIR, "configs",
                                   "trinity-mini-26b-pp4.json"))
BENCH = files.load_json(os.path.join(files.ROOT, "BENCHMARK.json"))
CELL = "trinity-mini-26b-pp4.midctx-closed"
NEW = ("win_attn_roofline_pct", "full_attn_roofline_pct",
       "win_attn_share_pct", "full_attn_share_pct", "win_pages_held_pct",
       "decode_experts_share_pct", "decode_experts_roofline_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _count(pub: dict, layers: int) -> dict:
    """Parameters of the first ``layers`` layers, the embedding and the head
    from the published keys (the issue's recount), norms left out."""
    H = pub["hidden_size"]
    q = pub["num_attention_heads"] * pub["head_dim"]
    kv = pub["num_key_value_heads"] * pub["head_dim"]
    attn = 3 * H * q + 2 * H * kv                   # Wq, Wg, Wo; Wk, Wv
    expert = 3 * H * pub["moe_intermediate_size"]
    routed = pub["num_experts"] * expert \
        + pub["num_shared_experts"] * expert + H * pub["num_experts"]
    dense = 3 * H * pub["intermediate_size"]
    nd = min(layers, pub["num_dense_layers"])
    head = 2 * pub["vocab_size"] * H
    return {"attn": attn, "expert": expert, "routed_ffn": routed,
            "dense_ffn": dense, "head": head,
            "total": layers * attn + nd * dense + (layers - nd) * routed
            + head}


def test_the_published_model_is_26_12_b_and_the_stage_holds_5_98():
    pub, par = CFG["published"], CFG["parameters"]
    whole = _count(pub, pub["num_hidden_layers"])
    assert whole["attn"] == par["attention_layer"] == 27_262_976
    assert whole["expert"] == par["expert"] == 6_291_456
    assert whole["routed_ffn"] == par["routed_ffn"]
    assert whole["dense_ffn"] == par["dense_ffn"]
    assert par["routed_layer"] == whole["attn"] + whole["routed_ffn"]
    assert par["dense_layer"] == whole["attn"] + whole["dense_ffn"]
    assert whole["head"] == par["embedding_and_head"]
    assert whole["total"] == par["published_total"]
    assert abs(whole["total"] / 1e9 - 26.12) < 0.005
    held = _count(pub, CFG["num_hidden_layers"])
    assert held["total"] == par["total_held"]
    assert abs(held["total"] / 1e9 - 5.98) < 0.005
    assert par["int8_GB"] == round(held["total"] / 1e9, 2)
    maker = files.load_module("weight_makers", "trinity_mini")
    assert maker.param_counts(CFG["model_config"])["total"] == held["total"]


def test_published_is_the_catalog_rows_config_and_the_cut_is_in_depth():
    pub = CFG["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Mini")
        assert pub == row["config"] and CFG["source"] == row["source_url"]
        for key, value in row["config"].items():       # key by key
            assert key in CFG, key
            assert CFG[key] == value or key in CFG["reduced"], key
    changed = sorted(k for k, v in pub.items() if CFG[k] != v)
    assert changed == sorted(CFG["reduced"]) == ["num_hidden_layers"]
    assert CFG["num_hidden_layers"] == 8 and pub["num_hidden_layers"] == 32
    lo, hi = CFG["stage_layers"]
    assert (lo, hi) == (0, 8)
    assert CFG["stage_layer_types"] == pub["layer_types"][lo:hi] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert pub["layer_types"] == CFG["stage_layer_types"] * 4
    assert pub["num_dense_layers"] == 2 < hi          # both dense layers held
    entry = next(c for c in BENCH["configs"] if c["name"] == CFG["name"])
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    for item in ("output_gate", "positions", "norms", "expert_bias",
                 "router", "shared_expert", "mup_enabled", "dense_layers",
                 "eos_token_id", "weights", "tokenizer"):
        assert item in CFG["assumed"], item


def test_model_config_follows_the_file_and_the_program_accepts_it():
    mc = sut.model_config_of(CFG)          # exits on any inconsistency
    pub = CFG["published"]
    assert mc.layer_pattern == "".join(
        "w" if k == "sliding_attention" else "g"
        for k in CFG["stage_layer_types"]) == "wwwgwwwg"
    assert (mc.num_layers, mc.num_attn_layers, mc.num_window_layers) \
        == (8, 2, 6)
    assert mc.windowed and mc.layer_list and not mc.recurrent
    assert hash(mc) is not None            # a jit static argument
    assert mc.hidden_size == pub["hidden_size"]
    assert mc.intermediate_size == pub["intermediate_size"]
    assert mc.moe_intermediate_size == pub["moe_intermediate_size"]
    assert mc.vocab_size == pub["vocab_size"]
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim) == (32, 4, 128)
    assert (mc.num_experts, mc.num_experts_per_tok, mc.n_shared_experts,
            mc.num_dense_layers) == (128, 8, 1, 2)
    assert mc.sliding_window == pub["sliding_window"] == 2048
    assert mc.route_scale == pub["route_scale"] == 2.826
    assert mc.norm_topk_prob is pub["route_norm"] is True
    assert mc.router_scoring == pub["score_func"] == "sigmoid"
    assert mc.embed_scale is pub["mup_enabled"] is True
    assert mc.sandwich_norm and mc.attn_output_gate and mc.qk_norm
    assert mc.attn_use_rope is False and mc.attn_window == 0
    flags = CFG["server_flags"]
    assert flags[flags.index("--max-cache-len") + 1] == "9216"
    assert flags[flags.index("--max-decode-slots") + 1] == "48"
    assert flags[flags.index("--prefill-chunk") + 1] == "4096"
    # the driver's check refused the cell at the server's default of 8 (the
    # median TTFT fell between the callers that meet no queue and the rest):
    # the file's server_flags_why gives the runs behind 2
    assert flags[flags.index("--decode-horizon") + 1] == "2"
    short, long_ = CFG["correctness_prompt_lens"]
    assert short < mc.sliding_window < 4096 < long_ < 2 * 4096
    assert (long_ + 16) // 64 > long_ // 64      # crosses a page boundary
    # ... and a release of a window page: (n + 1 - window) // page moves
    assert (long_ + 16 + 1 - 2048) // 64 > (long_ + 1 - 2048) // 64


def test_the_two_inventories_are_the_issues_arithmetic():
    """48 slots x 9,216: the full layers' inventory 1.81 GB, the window
    layers' 1.34 GB, where one table a slot would hold 7.25 GB."""
    from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp

    mc = sut.model_config_of(CFG)
    a_slot, win = kvp.window_inventory(mc, 48, 144, 64, 4, 4096)
    assert (a_slot, win) == (34, 48 * 34 + 65 + 1)
    full = kvp.pool_bytes(mc, 48 * 144 + 1, 64)
    both = kvp.pool_bytes(mc, 48 * 144 + 1, 64, win_pages=win)
    assert abs(full / 1e9 - 1.81) < 0.01
    assert abs((both - full) / 1e9 - 1.34) < 0.01
    assert abs(4 * full / 1e9 - 7.25) < 0.01
    assert 2 * 4 * 64 * 128 * 2 == 131_072        # K and V of a page-layer


def test_the_cell_and_its_metrics_are_as_the_issue_names_them():
    cell = files.Cell(os.path.join(files.ROOT, "BENCHMARK.json"), CELL)
    assert cell.chips == 1
    t = cell.traffic
    assert t["kind"] == "closed_loop" and t["clients"] == "slots"
    assert (t["prompt_len"]["dist"], t["prompt_len"]["min"],
            t["prompt_len"]["max"]) == ("uniform", 4096, 8192)
    assert (t["output_len"]["dist"], t["output_len"]["min"],
            t["output_len"]["max"]) == ("uniform", 384, 768)
    assert (t["block"], t["ramp_s"], t["first_out_min"], t["grace_s"],
            t["max_requests"]) == (8, 16, 32, 40, 1024)
    assert "grace_why" in t
    assert sorted(t["request_extra"]["logit_bias"]) == sorted(
        str(i) for i in range(48, 112))
    # every prompt of the mix is exactly two mixed steps, every context two
    # to four windows long and inside the cache
    from benchlib import trafficgen

    for p in trafficgen._quantiles(t["prompt_len"], t["block"]):
        assert -(-p // 4096) == 2 and 2 * 2048 < p < 4 * 2048
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= 9216 - 2
    assert set(cell.metric_names("end_to_end")) == {
        "ttft_p50_ms", "out_tok_s", "setup_s"}
    for name in NEW:
        assert cell.metric(name)["workloads"] == [CELL]
        assert cell.metric(name)["unit"] == "%"
        assert cell.metric(name)["moves"] == "out_tok_s"
        assert files.load_module("layer_metrics", name) is not None
    assert len(BENCH["workloads"]) == 7 and len(BENCH["configs"]) == 6
    assert [m["name"] for m in BENCH["per_layer"][-7:]] == list(NEW)


def test_ops_and_bytes_and_the_kernel_names_of_the_two_kinds():
    mc = CFG["model_config"]
    # 48 rows x 8 substeps: a window row holds 33 pages, a full row 110
    rec = {"horizon": 8, "attn_layers_full": 2, "attn_layers_window": 6,
           "win_pages_live": 48 * 8 * 33, "attn_pages_live": 48 * 8 * 110}
    flops, byts = trinity_opsbytes.attn_decode_dispatch(mc, rec, "window",
                                                        64, 48)
    pages = 48 * 8 * 33 * 6 * 131_072
    assert pages < byts < 1.01 * pages
    assert 8 <= flops / byts <= 17         # under the ridge of 240
    _, byts_full = trinity_opsbytes.attn_decode_dispatch(mc, rec, "full",
                                                         64, 48)
    assert 48 * 8 * 110 * 2 * 131_072 < byts_full
    assert 0.9 < byts / byts_full < 1.0    # 6 x 33 against 2 x 110
    win, full = (trinity_opsbytes.WINDOW_KERNEL_RE,
                 trinity_opsbytes.FULL_KERNEL_RE)
    w_line = "%decode_attend_pallas_paged_window.3 = bf16[48,32,128] custom-call()"
    f_line = "%decode_attend_pallas_paged.8 = bf16[48,32,128] custom-call()"
    s_line = "%decode_attend_pallas_paged_select.1 = custom-call()"
    assert re.match(win, w_line) and not re.match(win, f_line)
    assert re.match(full, f_line) and not re.match(full, w_line)
    assert re.match(full, "%decode_attend_pallas_paged = custom-call()")
    assert not re.match(full, s_line) and not re.match(win, s_line)
    # the accepted ragged reader matches both kinds' mixed calls
    ragged = files.load_module("layer_metrics",
                               "ragged_attn_roofline_pct").KERNEL_RE
    for name in ("ragged_attend_pallas_paged_slots",
                 "ragged_attend_pallas_paged_slots_window"):
        assert re.match(ragged, f"%{name}.2 = custom-call()")
    # the routed experts: six layers' stacks, the experts hit once a substep
    routed = trinity_opsbytes._routed(mc)
    assert routed["num_layers"] == 6 and mc["num_layers"] == 8
    from benchlib import moe_opsbytes

    rec = {"horizon": 8, "moe_rows": 8 * 48 * 8, "moe_experts_hit": 122.0}
    flops, byts = moe_opsbytes.decode_dispatch(routed, rec, 1)
    stacks = 8 * 6 * 122 * 3 * 2048 * 1024
    assert stacks < byts < 1.02 * stacks and flops / byts < 8
    ops = moe_opsbytes.expert_ops_re(routed)
    assert re.search(ops, "%fusion.7 = bf16[384,1024] fusion(bf16[48,2048] "
                          "%p, s8[6,128,2048,1024] %w_gate)")
    assert re.search(ops, "%fusion.9 = bf16[48,2048] fusion(s8[128,1024,2048]"
                          " %w_down)")
    assert not re.search(ops, "%fusion.2 = bf16[48,1024] fusion(bf16[48,2048]"
                              " %x, s8[6,2048,1024] %shared)")
    assert not trinity_opsbytes.has_both_kinds({"layer_pattern": "slllllls"})
    assert not trinity_opsbytes.has_both_kinds({})


def test_a_program_without_the_new_fields_stops_at_once():
    """The parent of the PR that brought this configuration knows no
    ``num_dense_layers``: the weight maker builds the program's ModelConfig
    first, so such a program fails before a byte is made."""
    maker = files.load_module("weight_makers", "trinity_mini")
    with pytest.raises(TypeError, match="unexpected keyword"):
        maker.make(dict(CFG["model_config"], no_such_field=1), 1, True)


def test_the_new_readers_through_the_rehearsal():
    """run.py on the tiny list, traced, on the CPU: prompts of 2-4 windows
    in chunks of ``mixed_step`` under live rows, pages of the window layers
    released on the way, served against the plain reference (``correct``);
    the program-counter reader finds the inventory's gauges, and the
    device-trace readers return nothing where there is no device plane —
    never an approximation."""
    reh = os.path.join(files.BENCH_DIR, "tests", "rehearsal",
                       "BENCHMARK.trinity.json")
    p = subprocess.run(
        [sys.executable, os.path.join(files.BENCH_DIR, "run.py"),
         "--rehearsal", reh, "--workload", "tiny-trinity.longdoc", "--seed",
         "3000000019", "--seconds", "3", "--trace", "1"],
        cwd=files.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    held = line["metrics"]["win_pages_held_pct"]["value"]
    assert 20.0 < held < 90.0              # a window of 2 pages of 5-9
    assert line["metrics"]["prefix_hit_tok_pct"]["value"] == 0
    for name in NEW:
        if name != "win_pages_held_pct":
            assert name not in line["metrics"]


def test_the_comparison_refuses_the_controls_through_the_rehearsal():
    """benchmark/controls.py on the tiny list: ``correctness.check`` itself
    passes the plain reference and refuses each of the reference's CONTROLS
    (a mechanism left out, float8 activations); the routed experts alone in
    float8 are reported either way."""
    reh = os.path.join(files.BENCH_DIR, "tests", "rehearsal",
                       "BENCHMARK.trinity.json")
    p = subprocess.run(
        [sys.executable, os.path.join(files.BENCH_DIR, "controls.py"),
         "--rehearsal", reh, "--workload", "tiny-trinity.longdoc", "--seed",
         "3000000019"],
        cwd=files.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        text=True, capture_output=True, timeout=900)
    line = json.loads(p.stdout.strip().splitlines()[-1])
    ref = files.load_module("reference", "trinity_mini")
    assert line["plain"] is True
    assert set(line["controls"]) == set(ref.CONTROLS)
    assert set(line["reported"]) == set(ref.CONTROLS_REPORTED)
    assert all(line["controls"].values()) and line["ok"] and p.returncode == 0
