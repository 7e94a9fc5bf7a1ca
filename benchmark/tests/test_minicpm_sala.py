"""The MiniCPM-SALA configuration file's arithmetic (parameters, bytes,
``reduced`` and ``published`` against the catalog row), the mix's lengths,
the ops-and-bytes of its two mechanisms, and the new layer-metric readers
through the CPU rehearsal."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchlib import files, sala_opsbytes
from benchlib import server_under_test as sut

CFG = files.load_json(os.path.join(files.BENCH_DIR, "configs",
                                   "minicpm-sala-9b-pp4.json"))
BENCH = files.load_json(os.path.join(files.ROOT, "BENCHMARK.json"))
CELL = "minicpm-sala-9b-pp4.longdoc-closed"
NEW = ("sparse_attn_roofline_pct", "sparse_select_share_pct",
       "sparse_pages_selected_pct", "lightning_state_roofline_pct",
       "lightning_share_pct", "mixed_attn_share_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _count(pub: dict, kinds: list, vocab: int) -> dict:
    """Parameters from the published keys (the issue's recount), norms left
    out."""
    H, I = pub["hidden_size"], pub["intermediate_size"]
    q = pub["num_attention_heads"] * pub["head_dim"]
    kv = pub["num_key_value_heads"] * pub["head_dim"]
    D = pub["lightning_nh"] * pub["lightning_head_dim"]
    ffn = 3 * H * I
    lightning = 3 * H * D + H * D + D * H + ffn          # q k v; gate; out
    sparse = H * q + 2 * H * kv + H * q + q * H + ffn    # q; k v; gate; out
    n_l = kinds.count("lightning-attn")
    return {"lightning": lightning, "sparse": sparse,
            "head": 2 * vocab * H,
            "total": n_l * lightning + (len(kinds) - n_l) * sparse
            + 2 * vocab * H}


def test_the_published_model_is_9_48_b_and_the_stage_holds_2_82():
    pub, par = CFG["published"], CFG["parameters"]
    whole = _count(pub, pub["mixer_types"], pub["vocab_size"])
    assert whole["lightning"] == par["lightning_layer"] == 285_212_672
    assert whole["sparse"] == par["minicpm4_layer"] == 253_755_392
    assert whole["head"] == par["embedding_and_head"]
    assert whole["total"] == par["published_total"]
    assert abs(whole["total"] / 1e9 - 9.48) < 0.005
    held = _count(pub, CFG["stage_mixer_types"], pub["vocab_size"])
    assert held["total"] == par["total_held"]
    assert abs(held["total"] / 1e9 - 2.82) < 0.005
    assert par["int8_GB"] == round(held["total"] / 1e9, 2)
    maker = files.load_module("weight_makers", "minicpm_sala")
    assert maker.param_counts(CFG["model_config"])["total"] == held["total"]


def test_published_is_the_catalog_rows_config_and_the_cut_is_in_depth():
    pub = CFG["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "MiniCPM-SALA")
        assert pub == row["config"] and CFG["source"] == row["source_url"]
    # every top-level key is the published one, or is listed in reduced
    changed = sorted(k for k, v in pub.items() if CFG[k] != v)
    assert changed == sorted(CFG["reduced"]) == ["num_hidden_layers"]
    assert CFG["num_hidden_layers"] == 8 and pub["num_hidden_layers"] == 32
    lo, hi = CFG["stage_layers"]
    assert CFG["stage_mixer_types"] == pub["mixer_types"][lo:hi]
    assert CFG["stage_mixer_types"] == ["minicpm4"] \
        + ["lightning-attn"] * 6 + ["minicpm4"]
    # the published 1:3 ratio, both kinds at a stage edge
    assert pub["mixer_types"].count("minicpm4") * 3 \
        == pub["mixer_types"].count("lightning-attn")
    entry = next(c for c in BENCH["configs"] if c["name"] == CFG["name"])
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    for item in ("sparse_config", "selector_softmax", "block_score",
                 "lightning_order", "lightning_output_norm",
                 "lightning_slopes"):
        assert item in CFG["assumed"], item


def test_model_config_follows_the_file_and_the_program_accepts_it():
    mc = sut.model_config_of(CFG)          # exits on any inconsistency
    pub = CFG["published"]
    assert mc.layer_pattern == "".join(
        "l" if k == "lightning-attn" else "s"
        for k in CFG["stage_mixer_types"])
    assert (mc.num_layers, mc.num_attn_layers, mc.num_recurrent_layers) \
        == (8, 2, 6)
    assert mc.selects and mc.recurrent and mc.layer_list
    assert hash(mc) is not None            # a jit static argument
    # the widths no cut may touch, and the toggles
    assert mc.hidden_size == pub["hidden_size"]
    assert mc.intermediate_size == pub["intermediate_size"]
    assert mc.vocab_size == pub["vocab_size"]
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim) == (32, 2, 128)
    assert (mc.lightning_num_heads, mc.lightning_head_dim) \
        == (pub["lightning_nh"], pub["lightning_head_dim"])
    assert mc.attn_use_rope is pub["attn_use_rope"] is False
    assert mc.attn_output_gate is pub["attn_use_output_gate"] is True
    assert mc.qk_norm is pub["qk_norm"] is True
    assert (mc.scale_emb, mc.scale_depth, mc.dim_model_base) \
        == (pub["scale_emb"], pub["scale_depth"], pub["dim_model_base"])
    assert mc.mup_depth == pub["num_hidden_layers"] == 32
    assert (mc.sparse_block_size, mc.sparse_kernel_size,
            mc.sparse_kernel_stride, mc.sparse_topk, mc.sparse_init_blocks,
            mc.sparse_window_size, mc.sparse_dense_len) \
        == (64, 32, 16, 64, 1, 2048, 8192)
    flags = CFG["server_flags"]
    assert flags[flags.index("--max-cache-len") + 1] == "32768"
    assert flags[flags.index("--max-decode-slots") + 1] == "24"
    assert CFG["correctness_prompt_lens"] == [300, 12530]
    assert (12530 + 16) // 64 > 12530 // 64      # crosses a page boundary
    assert (12530 + 16) // 16 > 12530 // 16      # completes a pooled key


def test_the_cell_and_its_metrics_are_as_the_issue_names_them():
    cell = files.Cell(os.path.join(files.ROOT, "BENCHMARK.json"), CELL)
    assert cell.chips == 1
    t = cell.traffic
    assert t["kind"] == "closed_loop" and t["clients"] == "slots"
    assert (t["prompt_len"]["dist"], t["prompt_len"]["min"],
            t["prompt_len"]["max"]) == ("uniform", 8192, 16384)
    assert (t["output_len"]["dist"], t["output_len"]["min"],
            t["output_len"]["max"]) == ("uniform", 1024, 2048)
    assert (t["block"], t["ramp_s"], t["first_out_min"], t["grace_s"]) \
        == (8, 24, 64, 40)
    assert "grace_why" in t
    assert sorted(t["request_extra"]["logit_bias"]) == sorted(
        str(i) for i in range(48, 112))
    # every context is past the dense length and inside the window
    mc = CFG["model_config"]
    assert t["prompt_len"]["min"] >= mc["sparse_dense_len"]
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= 32768 - 2
    assert set(cell.metric_names("end_to_end")) == {
        "ttft_p50_ms", "out_tok_s", "setup_s"}
    for name in NEW:
        assert cell.metric(name)["workloads"] == [CELL]
        assert cell.metric(name)["unit"] == "%"
        assert files.load_module("layer_metrics", name) is not None
    assert cell.metric("mixed_attn_share_pct")["moves"] == "ttft_p50_ms"
    assert len(BENCH["workloads"]) == 6 and len(BENCH["configs"]) == 5
    # the accepted roofline shares this cell reports name kernels it has
    assert re.match(files.load_module(
        "layer_metrics", "ragged_attn_roofline_pct").KERNEL_RE,
        "%ragged_attend_pallas_paged_select.3 = custom-call()")


def test_ops_and_bytes_of_the_selected_pages_and_the_state():
    mc = CFG["model_config"]
    rec = {"state_slots": 24, "horizon": 8, "sparse_rows": 24 * 8,
           "sparse_pages_selected": 24 * 8 * 2 * 64.0,
           "sparse_pages_live": 24 * 8 * 2 * 200.0}
    flops, byts = sala_opsbytes.lightning_decode_dispatch(mc, rec)
    # 24 slots x 6 layers x 8 substeps x (2 x 2 MiB + the rows)
    state = 24 * 6 * 8 * 2 * 2 * 2**20
    assert state < byts < 1.02 * state
    assert flops / byts < 1.0              # far under the ridge
    flops, byts = sala_opsbytes.sparse_decode_dispatch(mc, rec, 64)
    # 192 rows x 2 layers x 2 KV heads x 64 pages x (K + V) 16 KiB
    pages = 192 * 2 * 2 * 64 * 2 * 16 * 2**10
    assert pages < byts < 1.01 * pages
    assert 16 <= flops / byts <= 33        # under the ridge of 240
    line = ("%fusion.7 = f32[24,32,128] fusion(f32[6,1,24,32,128,128]"
            "{5,4,3,2,1,0} %p)")
    assert re.search(sala_opsbytes.state_ops_re(mc, 24), line)
    assert not re.search(sala_opsbytes.state_ops_re(mc, 24),
                         "%f = f32[24,4096] fusion(f32[24,4096] %x)")
    sel = sala_opsbytes.select_ops_re(mc, CFG, 24, 64, rows=(2048,))
    assert sala_opsbytes.pool_pages(CFG, 24, 64) == 24 * 512 + 1
    for text in ("%selector_add_row_paged.3 = f32[2,12289,2,4,128] "
                 "custom-call(s32[24] %r, f32[2,12289,2,4,128] %kc)",
                 "%fusion.2 = f32[24,2,16,2048] fusion(bf16[24,32,128] %q, "
                 "f32[24,2,2048,128] %runs)",
                 "%sort.4 = (f32[24,2,512], s32[24,2,512]) sort(...)",
                 "%fusion.9 = f32[2048,2,512,4] fusion(f32[2048,2,2048] %a)"):
        assert re.search(sel, text), text
    for text in ("%fusion.1 = bf16[24,4096] fusion(bf16[24,4096] %x)",
                 "%f = bf16[2,12289,2,64,128] custom-call(...)"):
        assert not re.search(sel, text), text
    dense = {"num_layers": 2, "hidden_size": 8, "layer_pattern": ""}
    assert sala_opsbytes.state_ops_re(dense, 4) is None
    assert sala_opsbytes.select_ops_re(dense, CFG, 4, 64) is None


def test_a_program_without_the_new_fields_stops_at_once():
    """The parent of the PR that brought this configuration knows no
    ``sparse_*`` field: the weight maker builds the program's ModelConfig
    first, so such a program fails before a byte is made."""
    maker = files.load_module("weight_makers", "minicpm_sala")
    with pytest.raises(TypeError, match="unexpected keyword"):
        maker.make(dict(CFG["model_config"], no_such_field=1), 1, True)


def test_the_new_readers_through_the_rehearsal():
    """run.py on the tiny list hybrid, traced, on the CPU: prompts past the
    dense length in chunks of ``mixed_step`` under live rows, served against
    the plain reference (``correct``); the program-span reader finds the
    records' new fields, and the device-trace readers return nothing where
    there is no device plane — never an approximation."""
    reh = os.path.join(files.BENCH_DIR, "tests", "rehearsal",
                       "BENCHMARK.sala.json")
    p = subprocess.run(
        [sys.executable, os.path.join(files.BENCH_DIR, "run.py"),
         "--rehearsal", reh, "--workload", "tiny-sala.longdoc", "--seed",
         "3000000019", "--seconds", "3", "--trace", "1"],
        cwd=files.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    picked = line["metrics"]["sparse_pages_selected_pct"]["value"]
    assert 40.0 < picked < 100.0           # top-4 of 5-8 pages past 256
    assert line["metrics"]["prefix_hit_tok_pct"]["value"] == 0
    for name in NEW:
        if name != "sparse_pages_selected_pct":
            assert name not in line["metrics"]
