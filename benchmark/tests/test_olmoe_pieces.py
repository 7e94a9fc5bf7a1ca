"""OLMoE's benchmark pieces: the plain reference against a second, float64
numpy forward of the same published block (a loop over each token's chosen
experts, where the reference masks a dense sum); the MoE ops-and-bytes
arithmetic at the published shape; how the readers find the expert FFN in a
trace; readers that find nothing return None."""

import re
from types import SimpleNamespace

import numpy as np
import pytest

from benchlib import files, moe_opsbytes

OLMOE = files.load_json(files.os.path.join(
    files.BENCH_DIR, "configs", "olmoe-1b-7b-int8.json"))["model_config"]


def _numpy_logits(mc, tree, ids):
    import jax
    import jax.numpy as jnp

    def f(leaf):
        w = np.asarray(leaf["kernel"].astype(jnp.float32), np.float64)
        if "scale" in leaf:
            w = w * np.asarray(leaf["scale"], np.float64)[..., None, :]
        return w

    def rms(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + mc["norm_eps"]) \
            * np.asarray(w.astype(jnp.float32), np.float64)

    hq, hkv, d = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    T, pos = len(ids), np.arange(len(ids))

    def rope(v):
        inv = 1.0 / (mc["rope_theta"] ** (np.arange(0, d, 2) / d))
        ang = np.concatenate([pos[:, None] * inv[None]] * 2, -1)[:, None]
        v1, v2 = v[..., :d // 2], v[..., d // 2:]
        return v * np.cos(ang) + np.concatenate([-v2, v1], -1) * np.sin(ang)

    emb = tree["embed"]
    x = np.asarray(emb["weight"].astype(jnp.float32), np.float64)[ids]
    if "scale" in emb:
        x = x * np.asarray(emb["scale"], np.float64)[ids][:, None]
    for li in range(mc["num_layers"]):
        lp = jax.tree.map(lambda a: a[li], tree["layers"])
        n1 = rms(x, lp["input_norm"]["weight"])
        q = rope(rms(n1 @ f(lp["wq"]), lp["q_norm"]["weight"])
                 .reshape(T, hq, d))
        k = rope(rms(n1 @ f(lp["wk"]), lp["k_norm"]["weight"])
                 .reshape(T, hkv, d))
        v = (n1 @ f(lp["wv"])).reshape(T, hkv, d)
        k, v = np.repeat(k, hq // hkv, 1), np.repeat(v, hq // hkv, 1)
        sc = np.einsum("thd,shd->hts", q, k) / np.sqrt(d)
        sc = np.where(pos[None, :, None] >= pos[None, None, :], sc, -np.inf)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        h = x + np.einsum("hts,shd->thd", pr, v).reshape(T, hq * d) \
            @ f(lp["wo"])
        n2 = rms(h, lp["post_norm"]["weight"])
        logit = n2 @ np.asarray(
            lp["router"]["kernel"].astype(jnp.float32), np.float64)
        p = np.exp(logit - logit.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        wg, wu, wd = f(lp["w_gate"]), f(lp["w_up"]), f(lp["w_down"])
        out = np.zeros_like(h)
        for t in range(T):          # HF's loop: each token, its top-k experts
            for e in np.argsort(-p[t], kind="stable")[
                    :mc["num_experts_per_tok"]]:
                g = n2[t] @ wg[e]
                out[t] += p[t, e] * (((g / (1 + np.exp(-g)))
                                      * (n2[t] @ wu[e])) @ wd[e])
        x = h + out
    xs = rms(x, tree["final_norm"]["weight"])
    return xs @ f(tree["lm_head"])


@pytest.mark.parametrize("quant", [True, False], ids=["int8", "bf16"])
def test_reference_equals_float64_numpy(quant):
    ref = files.load_module("reference", "olmoe")
    maker = files.load_module("weight_makers", "olmoe")
    mc = dict(num_layers=2, hidden_size=64, vocab_size=512, head_dim=16,
              num_heads=4, num_kv_heads=4, intermediate_size=32,
              moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
              norm_topk_prob=False, tie_embeddings=False, rope_theta=1e4,
              norm_eps=1e-5)
    tree = maker.make(mc, 3, quant, sigma=0.11)
    ids = list(np.random.default_rng(0).integers(0, 256, 40))
    got = np.asarray(ref.logits(mc, tree, ids + [0], 40))
    want = _numpy_logits(mc, tree, ids)
    assert got.shape == (40, 512)
    assert float(np.abs(got - want).max()) < 1e-4
    lp = ref.logprobs(mc, tree, ids + [0], 8)
    assert lp.shape == (8, 512)
    assert np.allclose(np.exp(lp).sum(-1), 1.0, atol=1e-5)


def test_expert_ffn_need_at_the_published_shape():
    # 24 slots x top-8 = 192 routed rows a layer over all 64 experts
    flops, byts = moe_opsbytes.expert_ffn_layer(OLMOE, 192, 64, 1)
    assert flops == 192 * 3 * 2 * 2048 * 1024
    stacks = 64 * 3 * 2048 * 1024
    assert stacks * 16 == 6_442_450_944          # 6.44 GB of int8 stacks
    assert byts == stacks + 64 * (2 * 1024 + 2048) * 4 + 192 * 2 * 2048 * 2
    rec = {"moe_rows": 8 * 192, "moe_experts_hit": 51.2, "horizon": 8}
    f8, b8 = moe_opsbytes.decode_dispatch(OLMOE, rec, 1)
    f1, b1 = moe_opsbytes.expert_ffn_layer(OLMOE, 192, 51.2, 1)
    assert (f8, b8) == (f1 * 8 * 16, b1 * 8 * 16)
    # bandwidth-bound by far: 6 flops a byte against a ridge of 240
    assert flops / byts < 10


def test_expert_ops_are_found_by_their_stack_operand():
    pat = re.compile(moe_opsbytes.expert_ops_re(OLMOE))
    yes = [
        "%fusion.3 = bf16[64,24,1024]{2,1,0:T(8,128)(2,1)S(1)} fusion("
        "s8[16,64,2048,1024]{3,2,1,0:T(8,128)(4,1)} %get-tuple-element.9, "
        "f32[64,1024]{1,0} %sg.1, bf16[24,2048] %copy-done), kind=kOutput",
        "%ragged-dot-none.1 = bf16[192,1024]{1,0:T(8,128)(2,1)S(1)} "
        "custom-call(s32[1]{0:T(128)} %gte.1, bf16[192,2048]{1,0} %fusion.2,"
        " s8[64,2048,1024]{2,1,0:T(8,128)(4,1)} %dynamic-slice.4)",
        "%fusion.2 = bf16[24,2048]{1,0} fusion(s8[64,1024,2048]{2,1,0} "
        "%wd.1, bf16[64,24,1024] %bitcast.25), kind=kOutput"]
    no = [
        "%fusion.9 = bf16[64,24,1024]{2,1,0} fusion(bf16[64,24,1024] %a, "
        "bf16[64,24,1024] %b), kind=kLoop",
        "%decode_attend_pallas_paged.11 = bf16[24,16,128]{2,1,0} custom-call"
        "(s32[24]{0} %x, bf16[16,769,16,64,128]{4,3,2,1,0} %pool)",
        # the result's own type is not an operand
        "%copy.9 = s8[64,2048,1024]{2,1,0} copy(s8[4,8] %a)"]
    assert all(pat.search(s) for s in yes)
    assert not any(pat.search(s) for s in no)


@pytest.mark.parametrize("name", ["moe_experts_hit_pct", "moe_ffn_share_pct",
                                  "moe_ffn_roofline_pct"])
def test_readers_find_nothing_in_a_program_without_the_fields(name):
    reader = files.load_module("layer_metrics", name)
    span = (0.0, "engine.dispatch", 0, 10, {
        "seq": 1, "program": "decode_steps", "horizon": 8, "active": 24})
    ctx = SimpleNamespace(spans=[span], trace=None, mc=OLMOE, peaks={},
                          engine={"w_itemsize": 1})
    assert reader.read(ctx) is None


def test_experts_hit_is_weighted_by_substeps():
    reader = files.load_module("layer_metrics", "moe_experts_hit_pct")
    spans = [(0.0, "engine.dispatch", 0, 10, {
        "seq": i, "program": prog, "horizon": h, "moe_experts_hit": hit})
        for i, (prog, h, hit) in enumerate(
            [("decode_steps", 8, 48.0), ("decode_steps", 1, 64.0),
             ("mixed_step", 1, 64.0)])]
    ctx = SimpleNamespace(spans=spans, trace=None, mc=OLMOE)
    want = 100.0 * (8 * 48.0 + 64.0) / 9 / 64
    assert reader.read(ctx) == pytest.approx(want)
