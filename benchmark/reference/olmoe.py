"""Plain reference of the OLMoE decoder block, written from the published
architecture (OLMoE, arXiv:2409.02060; HF ``modeling_olmoe.py``:
``OlmoeAttention``, ``OlmoeSparseMoeBlock``, ``OlmoeDecoderLayer``):

    h   = x + Wo . Attn( RoPE(qnorm(Wq . n1)), RoPE(knorm(Wk . n1)), Wv . n1 )
    out = h + sum_{e in top8(p)} p_e . Wdown_e . ( silu(Wgate_e . n2) * (Wup_e . n2) )
    p   = softmax(Wrouter . n2)            over ALL experts, float32

with n1 = RMSNorm(x), n2 = RMSNorm(h), RMSNorm(v) = v / sqrt(mean(v^2)+eps) * w.
The q/k RMSNorm runs over the WHOLE projection (all heads' features at once,
weights of ``heads x head_dim``) BEFORE the split into heads and before
RoPE; RoPE is the rotate-half convention at theta = 10,000; attention is
multi-head (one K/V head per query head; grouped heads are handled for
completeness), causal, softmax in float32, scale 1/sqrt(D). The eight
chosen router probabilities weight the experts AS THEY ARE
(``norm_topk_prob`` false: they are not renormalised to sum to one); a
configuration that sets the key gets the renormalised form.

Straightforward ``jax.numpy`` in float32 at matmul precision "highest": no
cache, no kernels, no batching, no sort, nothing imported from the program.
The whole sequence is recomputed from the token ids (teacher forcing), and
the reference routes on ITS OWN activations: the served path's expert
choices are never read. Weights come in the tree the server was given;
int8 kernels are dequantised (kernel * scale) one layer, and inside it one
expert, at a time.

Departures from the published code, none of which changes the mathematics:
- every expert is computed for every token and the result is masked by the
  [T, E] matrix that holds p_e for the chosen experts and 0 elsewhere (HF
  loops over experts and gathers each one's tokens): the sum has the same
  terms;
- HF casts the chosen weights to the hidden dtype (bf16 in a served
  checkpoint) before the multiply; here they stay float32, as everything
  does;
- ``clip_qkv`` is null in this model's config and is not implemented;
- the output head is taken in column blocks (it is 0.4 GB in float32, and
  the reference runs beside a serving engine that holds most of the chip);
  every output column is still one full-length dot product.
"""

from __future__ import annotations

BLOCKS = 16       # column blocks of the output head


def _f32(leaf: dict):
    """[..., din, dout] kernel (* its [..., dout] scale) in float32."""
    import jax.numpy as jnp

    w = leaf["kernel"].astype(jnp.float32)
    if "scale" in leaf:
        w = w * leaf["scale"].astype(jnp.float32)[..., None, :]
    return w


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: [T, heads, D]; rotate-half convention."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _experts(mc: dict, n2, lp: dict):
    """sum over the chosen experts of p_e . expert_e(n2); n2: [T, H]."""
    import jax
    import jax.numpy as jnp

    E, k = mc["num_experts"], mc["num_experts_per_tok"]
    probs = jax.nn.softmax(
        n2 @ lp["router"]["kernel"].astype(jnp.float32), axis=-1)   # [T, E]
    w, idx = jax.lax.top_k(probs, k)                                # [T, k]
    if mc.get("norm_topk_prob", False):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    # [T, E]: p_e where expert e was chosen for the token, else 0
    weight = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                     * w[..., None], axis=1)

    def one(acc, ex):
        gate, up, down, col = ex
        y = (jax.nn.silu(n2 @ _f32(gate)) * (n2 @ _f32(up))) @ _f32(down)
        return acc + col[:, None] * y, None

    stacks = tuple({n: lp[name][n] for n in lp[name]}
                   for name in ("w_gate", "w_up", "w_down"))
    out, _ = jax.lax.scan(one, jnp.zeros_like(n2), stacks + (weight.T,))
    return out


def _layer(mc: dict, x, lp: dict):
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    hq, hkv, d = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    eps, theta = mc.get("norm_eps", 1e-5), mc["rope_theta"]
    pos = jnp.arange(T)
    n1 = _rms(x, lp["input_norm"]["weight"], eps)
    # the norm sees the whole projection; the heads are split afterwards
    q = _rms(n1 @ _f32(lp["wq"]), lp["q_norm"]["weight"], eps)
    k = _rms(n1 @ _f32(lp["wk"]), lp["k_norm"]["weight"], eps)
    q = _rope(q.reshape(T, hq, d), pos, theta)
    k = _rope(k.reshape(T, hkv, d), pos, theta)
    v = (n1 @ _f32(lp["wv"])).reshape(T, hkv, d)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(d))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    h = x + a.reshape(T, hq * d) @ _f32(lp["wo"])
    return h + _experts(mc, _rms(h, lp["post_norm"]["weight"], eps), lp)


def _head_logits(x, leaf: dict):
    """x @ W_head in vocabulary blocks; ``leaf`` = {kernel [H, V], scale}."""
    import jax
    import jax.numpy as jnp

    w = leaf["kernel"]
    V = w.shape[1]
    nb = next(b for b in (BLOCKS, 8, 4, 2, 1) if V % b == 0)
    wb = w.reshape(w.shape[0], nb, V // nb).swapaxes(0, 1)
    logits = jnp.moveaxis(
        jax.lax.map(lambda b: x @ b.astype(jnp.float32), wb),
        0, 1).reshape(x.shape[0], V)
    if "scale" in leaf:
        logits = logits * leaf["scale"].astype(jnp.float32)[None, :]
    return logits


def logits(mc: dict, tree: dict, token_ids, n_last: int):
    """float32 logit rows predicting the LAST ``n_last`` tokens of
    ``token_ids`` (row j predicts token len-n_last+j from everything before
    it), as a device array [n_last, V]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]

    def here(t):      # gather a (possibly sharded) slice to one device
        return jax.tree.map(lambda a: jax.device_put(a, dev), t)

    ids = jnp.asarray(np.asarray(token_ids, np.int32))
    T = int(ids.shape[0])
    eps = mc.get("norm_eps", 1e-5)
    with jax.default_matmul_precision("highest"):
        emb = here({k: v[ids] for k, v in tree["embed"].items()})
        x = emb["weight"].astype(jnp.float32)
        if "scale" in emb:
            x = x * emb["scale"].astype(jnp.float32)[:, None]
        layer = jax.jit(lambda x, lp: _layer(mc, x, lp))
        for li in range(mc["num_layers"]):
            x = layer(x, here(jax.tree.map(lambda a: a[li], tree["layers"])))
        x = _rms(x[T - 1 - n_last:T - 1], here(tree["final_norm"])["weight"],
                 eps)
        if mc.get("tie_embeddings", False):
            e = here(tree["embed"])
            head = {"kernel": e["weight"].T, **(
                {"scale": e["scale"]} if "scale" in e else {})}
        else:
            head = here(tree["lm_head"])
        return jax.jit(_head_logits)(x, head)


def logprobs(mc: dict, tree: dict, token_ids, n_last: int):
    """float32 log-softmax of ``logits``, as a numpy array [n_last, V]."""
    import jax
    import numpy as np

    with jax.default_matmul_precision("highest"):
        out = jax.nn.log_softmax(logits(mc, tree, token_ids, n_last),
                                 axis=-1)
    return np.asarray(jax.device_get(out))
