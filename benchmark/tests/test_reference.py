"""The plain reference against a second, unblocked float64 numpy forward of
the same published block, on seeded tiny weights: blocking the wide matrices
and dequantizing by layer change nothing."""

import numpy as np
import pytest

from benchlib import files


def _numpy_logprobs(mc, tree, ids, n_last):
    import jax
    import jax.numpy as jnp

    def f(leaf, k="kernel"):
        w = np.asarray(leaf[k].astype(jnp.float32), np.float64)
        if "scale" in leaf:
            s = np.asarray(leaf["scale"], np.float64)
            w = w * (s[:, None] if k == "weight" else s[None, :])
        return w

    def rms(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) \
            * np.asarray(w.astype(jnp.float32), np.float64)

    hq, hkv, d = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    T = len(ids)
    pos = np.arange(T)

    def rope(v):
        inv = 1.0 / (mc["rope_theta"] ** (np.arange(0, d, 2) / d))
        ang = np.concatenate([pos[:, None] * inv[None]] * 2, -1)[:, None]
        v1, v2 = v[..., :d // 2], v[..., d // 2:]
        return v * np.cos(ang) + np.concatenate([-v2, v1], -1) * np.sin(ang)

    x = f(tree["embed"], "weight")[ids]
    for li in range(mc["num_layers"]):
        lp = jax.tree.map(lambda a: a[li], tree["layers"])
        n1 = rms(x, lp["input_norm"]["weight"])
        q = rope(rms((n1 @ f(lp["wq"])).reshape(T, hq, d),
                     lp["q_norm"]["weight"]))
        k = rope(rms((n1 @ f(lp["wk"])).reshape(T, hkv, d),
                     lp["k_norm"]["weight"]))
        v = (n1 @ f(lp["wv"])).reshape(T, hkv, d)
        k, v = np.repeat(k, hq // hkv, 1), np.repeat(v, hq // hkv, 1)
        sc = np.einsum("thd,shd->hts", q, k) / np.sqrt(d)
        sc = np.where(pos[None, :, None] >= pos[None, None, :], sc, -np.inf)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        h = x + np.einsum("hts,shd->thd", pr, v).reshape(T, hq * d) \
            @ f(lp["wo"])
        n2 = rms(h, lp["post_norm"]["weight"])
        g = n2 @ f(lp["w_gate"])
        x = h + ((g / (1 + np.exp(-g))) * (n2 @ f(lp["w_up"]))) \
            @ f(lp["w_down"])
    xs = rms(x[T - 1 - n_last:T - 1], tree["final_norm"]["weight"])
    logits = xs @ (f(tree["embed"], "weight").T if mc["tie_embeddings"]
                   else f(tree["lm_head"]))
    m = logits.max(-1, keepdims=True)
    return logits - m - np.log(np.exp(logits - m).sum(-1, keepdims=True))


@pytest.mark.parametrize("tie", [True, False])
@pytest.mark.parametrize("quant", [True, False])
def test_reference_equals_float64_numpy(tie, quant):
    ref = files.load_module("reference", "qwen3")
    maker = files.load_module("weight_makers", "qwen3_dense")
    mc = dict(num_layers=2, hidden_size=64, vocab_size=512, head_dim=16,
              num_heads=4, num_kv_heads=2, intermediate_size=128,
              tie_embeddings=tie, rope_theta=1e6)
    tree = maker.make(mc, 3, quant)
    ids = list(np.random.default_rng(0).integers(0, 256, 40))
    got = ref.logprobs(mc, tree, ids, 8)
    want = _numpy_logprobs(mc, tree, ids, 8)
    assert got.shape == (8, 512)
    assert float(np.abs(got - want).max()) < 1e-4
