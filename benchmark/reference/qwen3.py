"""Plain reference of the dense Qwen3 decoder block, written from the
published architecture (Qwen3 technical report / HF ``Qwen3ForCausalLM``):

    h   = x + Wo . Attn( RoPE(qnorm(Wq . n1)), RoPE(knorm(Wk . n1)), Wv . n1 )
    out = h + Wdown . ( silu(Wgate . n2) * (Wup . n2) )

with n1 = RMSNorm(x), n2 = RMSNorm(h), RMSNorm(v) = v / sqrt(mean(v^2)+eps) * w,
per-head q/k RMSNorm over head_dim BEFORE RoPE, RoPE in the rotate-half
convention with theta = 1e6, grouped-query attention (query head i reads
key/value head i // (Hq/Hkv)), causal softmax in float32, scale 1/sqrt(D).

Straightforward ``jax.numpy`` in float32 at matmul precision "highest": no
cache, no kernels, no batching, nothing imported from the program. The whole
sequence is recomputed from the token ids (teacher forcing). Weights come in
the tree the server was given; int8 kernels are dequantized (kernel * scale)
ONE LAYER AT A TIME, so an 8B model's float32 copy never exists at once. For
a tree sharded over chips a layer's slice is gathered to one device first.

The two wide matrices are taken in column blocks, one after the other
(``lax.map``), because the reference runs beside a serving engine that holds
most of the chip: the 8B's float32 output head alone is 2.5 GB, and its MLP
kernels 0.2 GB each. Blocking changes the order nothing is summed in: every
output column is still one full-length dot product.
"""

from __future__ import annotations

BLOCKS = 16       # column blocks of the output head and of the MLP


def _f32(leaf: dict, kernel_key: str = "kernel"):
    import jax.numpy as jnp

    w = leaf[kernel_key].astype(jnp.float32)
    if "scale" in leaf:
        s = leaf["scale"].astype(jnp.float32)
        # per-out-channel: the scale runs along the kernel's last axis, except
        # for the embedding table [V, H] whose scale is per row
        w = w * (s[:, None] if kernel_key == "weight" else s[None, :])
    return w


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: [T, heads, D]; rotate-half convention."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _layer(mc: dict, x, lp: dict):
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    hq, hkv, d = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    eps, theta = mc.get("norm_eps", 1e-6), mc["rope_theta"]
    pos = jnp.arange(T)
    n1 = _rms(x, lp["input_norm"]["weight"].astype(jnp.float32), eps)
    q = (n1 @ _f32(lp["wq"])).reshape(T, hq, d)
    k = (n1 @ _f32(lp["wk"])).reshape(T, hkv, d)
    v = (n1 @ _f32(lp["wv"])).reshape(T, hkv, d)
    q = _rope(_rms(q, lp["q_norm"]["weight"].astype(jnp.float32), eps),
              pos, theta)
    k = _rope(_rms(k, lp["k_norm"]["weight"].astype(jnp.float32), eps),
              pos, theta)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(d))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    h = x + a.reshape(T, hq * d) @ _f32(lp["wo"])
    n2 = _rms(h, lp["post_norm"]["weight"].astype(jnp.float32), eps)
    return h + _mlp(n2, lp)


def _blocks(n: int) -> int:
    return next(b for b in (BLOCKS, 8, 4, 2, 1) if n % b == 0)


def _col_blocks(leaf: dict, nb: int):
    """[din, dout] kernel (+ [dout] scale) -> nb stacked column blocks."""
    k = leaf["kernel"]
    din, dout = k.shape
    out = {"kernel": k.reshape(din, nb, dout // nb).swapaxes(0, 1)}
    if "scale" in leaf:
        out["scale"] = leaf["scale"].reshape(nb, dout // nb)
    return out


def _mlp(n2, lp: dict):
    """down( silu(gate(n2)) * up(n2) ), one block of the intermediate width
    at a time; the down projection's partial products are summed."""
    import jax
    import jax.numpy as jnp

    inter = lp["w_gate"]["kernel"].shape[1]
    nb = _blocks(inter)
    gate, up = _col_blocks(lp["w_gate"], nb), _col_blocks(lp["w_up"], nb)
    dk = lp["w_down"]["kernel"]
    down = dk.reshape(nb, inter // nb, dk.shape[1])     # row blocks
    dscale = lp["w_down"].get("scale")

    def one(blk):
        g, u, d = blk
        act = jax.nn.silu(n2 @ _f32(g)) * (n2 @ _f32(u))
        return act @ d.astype(jnp.float32)

    part = jax.lax.map(one, (gate, up, down)).sum(axis=0)
    return part * dscale.astype(jnp.float32)[None, :] \
        if dscale is not None else part


def _head_logits(x, leaf: dict, kernel_key: str):
    """x @ W_head in vocabulary blocks. ``leaf`` is the untied head
    ({kernel [H, V], scale [V]}) or the tied embedding ({weight [V, H],
    scale [V]}, used transposed)."""
    import jax
    import jax.numpy as jnp

    w = leaf[kernel_key]
    V = w.shape[0] if kernel_key == "weight" else w.shape[1]
    nb = _blocks(V)
    if kernel_key == "weight":
        wb = w.reshape(nb, V // nb, w.shape[1])         # row blocks of [V,H]
        one = lambda b: x @ b.astype(jnp.float32).T
    else:
        wb = w.reshape(w.shape[0], nb, V // nb).swapaxes(0, 1)
        one = lambda b: x @ b.astype(jnp.float32)
    logits = jnp.moveaxis(jax.lax.map(one, wb), 0, 1).reshape(x.shape[0], V)
    if "scale" in leaf:
        logits = logits * leaf["scale"].astype(jnp.float32)[None, :]
    return logits


def logprobs(mc: dict, tree: dict, token_ids, n_last: int):
    """float32 log-softmax rows predicting the LAST ``n_last`` tokens of
    ``token_ids`` (row j predicts token len-n_last+j from everything before
    it). Returns a numpy array [n_last, V]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]

    def here(t):      # gather a (possibly sharded) slice to one device
        return jax.tree.map(lambda a: jax.device_put(a, dev), t)

    ids = jnp.asarray(np.asarray(token_ids, np.int32))
    T = int(ids.shape[0])
    with jax.default_matmul_precision("highest"):
        emb = here({k: v[ids] for k, v in tree["embed"].items()})
        x = emb["weight"].astype(jnp.float32)
        if "scale" in emb:
            x = x * emb["scale"].astype(jnp.float32)[:, None]
        layer = jax.jit(lambda x, lp: _layer(mc, x, lp))
        for li in range(mc["num_layers"]):
            lp = here(jax.tree.map(lambda a: a[li], tree["layers"]))
            x = layer(x, lp)
        x = _rms(x[T - 1 - n_last:T - 1],
                 here(tree["final_norm"])["weight"].astype(jnp.float32),
                 mc.get("norm_eps", 1e-6))
        if mc.get("tie_embeddings", False):
            logits = jax.jit(lambda x, e: _head_logits(x, e, "weight"))(
                x, here(tree["embed"]))
        else:
            logits = jax.jit(lambda x, e: _head_logits(x, e, "kernel"))(
                x, here(tree["lm_head"]))
        out = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(jax.device_get(out))
