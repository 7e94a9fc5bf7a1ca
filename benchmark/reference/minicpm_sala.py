"""Plain reference of the MiniCPM-SALA decoder (``model_type`` minicpm_sala,
https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json), written
from the published config's keys; the sparse layers' block geometry is the
family's (the ``sparse_config`` of the MiniCPM4 / InfLLM-v2 release,
arXiv:2509.24663), the Lightning layers' slope table Lightning-Attention-2's.

Pre-norm residual blocks under MiniCPM's muP, RMSNorm(v) = v / sqrt(mean(v^2)
+ eps) * w, r = scale_depth / sqrt(mup_depth) (the PUBLISHED depth):

    h0  = scale_emb * E[id]
    h   = x + r * Mix_l(n1),     n1 = RMSNorm(x)
    out = h + r * SwiGLU(n2),    n2 = RMSNorm(h)
    logits = W_head RMSNorm(h_L) * dim_model_base / hidden_size

The kind of layer l is ``layer_pattern[l]``: a LIST, one character a layer.

**"l": Lightning linear attention** (H heads of d): q, k, v = n1 Wq, n1 Wk,
n1 Wv; per-head RMSNorm on q and k, THEN RoPE on q and k (theta
``rope_theta``, the "rotate half" pairing, the whole head); per head a state
S [d, d], zero at position 0:

    S_t = exp(-s_h) S_{t-1} + k_t^T v_t       o_t = (q_t / sqrt(d)) S_t
    s_h = 2^(-8 (h + 1) / H)                  (h = 0 .. H - 1; not learned)

``o <- RMSNorm_head(o)``, ``o <- o * sigmoid(n1 Wg)``, Mix = o Wo. The
recurrence runs TOKEN BY TOKEN under a scan, in float32.

**"s": selecting attention** (Hq query heads, Hkv KV heads of D, G = Hq /
Hkv): q, k, v projections, per-head RMSNorm on q and k, NO position term;
for the query at position t (T = t + 1) and KV head g, with blocks of
``sparse_block_size`` (B), windows of ``sparse_kernel_size`` (K) keys every
``sparse_kernel_stride`` (S):

1. ``Kc_j = mean(k[S j : S j + K])`` for every j with ``S j + K <= T``;
2. ``p_h = softmax_j(q_h . Kc_j / sqrt(D))`` for each of the group's G
   heads, ``a_j = sum_h p_h[j]``;
3. ``b_n = max{a_j : [S j, S j + K) meets [B n, B n + B)}``;
4. blocks ``0 .. init_blocks - 1`` and the last ``window_size / B`` blocks up
   to and including the query's own get ``+inf``;
5. the ``topk`` highest-scoring blocks among those that start at or before t
   (ties to the lower index; all of them when there are no more than
   ``topk``); if ``T < dense_len`` every such block;
6. softmax attention of the group's heads over the tokens ``<= t`` of the
   selected blocks, scale 1/sqrt(D);

``o <- o * sigmoid(n1 Wg)``, Mix = o Wo. The selection is per token, per
layer and per KV head, on the reference's OWN float32 activations.

Straightforward ``jax.numpy`` in float32 at matmul precision "highest": no
cache, no kernels, no run sums, no bit masks, nothing imported from the
program. The whole sequence is recomputed from the token ids (teacher
forcing), the FFN and the selecting attention in blocks of ``ROWS`` query
rows so that 12.5k tokens fit beside one float32 layer; int8 kernels are
dequantised (kernel * scale) one layer at a time; the head in column blocks.

Two instruments beside the plain call (``forward``; ``logits`` and
``logprobs``, which the benchmark calls, pass neither):
- ``selection`` [selecting layers, T, Hkv, blocks] bool: the blocks each
  token's KV head is HANDED in each selecting layer, in place of the
  reference's own choice. With the served path's choices handed in, what is
  left of the distance is everything but selection ties; ``forward`` also
  returns the choices it made or was handed.
- ``lower``: one precision below what the configuration states — "state":
  the Lightning state is rounded to bfloat16 after every token (the
  configuration's is float32); "act": every layer's normed inputs and the
  residual stream are rounded to 4 exponent bits and 3 of mantissa (float8
  e4m3; the configuration computes in bfloat16). By ``lax.reduce_precision``
  (a convert there and back is a round trip the TPU compiler removes). A
  comparison that guards the stated precision has to refuse these.
"""

from __future__ import annotations

import math

BLOCKS = 16       # column blocks of the output head
ROWS = 256        # query rows a block of the FFN and the selecting attention


def _f32(leaf: dict):
    """[din, dout] kernel (* its [dout] scale) in float32."""
    import jax.numpy as jnp

    w = leaf["kernel"].astype(jnp.float32)
    if "scale" in leaf:
        w = w * leaf["scale"].astype(jnp.float32)[..., None, :]
    return w


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _row_blocks(fn, x, *more):
    """``fn`` over blocks of ROWS rows of ``x`` [T, ...] (and of each of
    ``more``), the results put together again."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    n = -(-T // ROWS)

    def cut(a):
        a = jnp.pad(a, [(0, n * ROWS - T)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape((n, ROWS) + a.shape[1:])

    out = jax.lax.map(lambda xs: fn(*xs), tuple(cut(a) for a in (x,) + more))
    return jax.tree.map(lambda a: a.reshape((n * ROWS,) + a.shape[2:])[:T],
                        out)


def _swiglu(n2, lp: dict):
    import jax

    wg, wu, wd = _f32(lp["w_gate"]), _f32(lp["w_up"]), _f32(lp["w_down"])
    return _row_blocks(lambda n: (jax.nn.silu(n @ wg) * (n @ wu)) @ wd, n2)


def _rope(x, theta: float):
    """x [T, H, d] at positions 0 .. T - 1: pairs (i, i + d/2) rotate by
    ``pos * theta^(-2i/d)``."""
    import jax.numpy as jnp

    T, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # [T, d/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _lightning(mc: dict, n1, lp: dict, low_state: bool = False):
    import jax
    import jax.numpy as jnp

    H, d = mc["lightning_num_heads"], mc["lightning_head_dim"]
    eps = mc.get("norm_eps", 1e-6)
    T = n1.shape[0]
    q = _rms((n1 @ _f32(lp["wq"])).reshape(T, H, d), lp["q_norm"]["weight"],
             eps)
    k = _rms((n1 @ _f32(lp["wk"])).reshape(T, H, d), lp["k_norm"]["weight"],
             eps)
    v = (n1 @ _f32(lp["wv"])).reshape(T, H, d)
    theta = mc.get("rope_theta", 10000.0)
    q, k = _rope(q, theta) / math.sqrt(d), _rope(k, theta)
    slope = jnp.exp2(-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H)
    lam = jnp.exp(-slope)[:, None, None]

    def step(S, xs):
        qt, kt, vt = xs                                  # [H, d] each
        S = lam * S + kt[:, :, None] * vt[:, None, :]
        if low_state:
            S = jax.lax.reduce_precision(S, 8, 7)
        return S, jnp.einsum("hk,hkv->hv", qt, S)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32), (q, k, v))
    o = _rms(o, lp["o_norm"]["weight"], eps).reshape(T, H * d)
    return (o * jax.nn.sigmoid(n1 @ _f32(lp["wg"]))) @ _f32(lp["wo"])


def _block_scores(mc: dict, qb, kc, t, NB: int):
    """Steps 2-3 for a block of queries. qb [R, Hkv, G, D]; kc [Hkv, J, D];
    t [R] positions. Returns b [R, Hkv, NB] (-inf: no whole window meets the
    block)."""
    import jax
    import jax.numpy as jnp

    B, K, S = (mc["sparse_block_size"], mc["sparse_kernel_size"],
               mc["sparse_kernel_stride"])
    D = qb.shape[-1]
    J = kc.shape[1]
    T = t + 1
    j = jnp.arange(J)
    whole = (S * j[None, :] + K <= T[:, None])                 # [R, J]
    s = jnp.einsum("rkgd,kjd->rkgj", qb, kc) / math.sqrt(D)
    p = jax.nn.softmax(jnp.where(whole[:, None, None, :], s, -jnp.inf),
                       axis=-1)
    a = jnp.where(whole[:, None, :], jnp.nan_to_num(p).sum(axis=2), -jnp.inf)
    n = jnp.arange(NB)
    meets = (S * j[:, None] < B * n[None, :] + B) \
        & (S * j[:, None] + K > B * n[None, :])                # [J, NB]
    return jnp.where(meets[None, None], a[..., None], -jnp.inf).max(axis=2)


def _choose(mc: dict, b, t, NB: int):
    """Steps 4-5 from block scores b [R, Hkv, NB]. Returns [R, Hkv, NB]
    bool."""
    import jax.numpy as jnp

    B = mc["sparse_block_size"]
    topk, init = mc["sparse_topk"], mc.get("sparse_init_blocks", 0)
    local = mc.get("sparse_window_size", 0) // B
    n = jnp.arange(NB)[None, None, :]
    own = (t // B)[:, None, None]
    starts = n <= own                            # starts at or before t
    forced = (n < init) | (n > own - local)
    score = jnp.where(forced, jnp.inf, b)
    score = jnp.where(starts, score, -jnp.inf)
    # rank by (score descending, index ascending): a stable sort of -score
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    sel = (rank < topk) & starts
    dense = ((t + 1) < mc.get("sparse_dense_len", 0))[:, None, None]
    return jnp.where(dense, starts, sel)


def _sparse(mc: dict, n1, lp: dict, handed=None):
    import jax
    import jax.numpy as jnp

    Hq, Hkv, D = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    G = Hq // Hkv
    B, K, S = (mc["sparse_block_size"], mc["sparse_kernel_size"],
               mc["sparse_kernel_stride"])
    eps = mc.get("norm_eps", 1e-6)
    T = n1.shape[0]
    NB = -(-T // B)
    q = _rms((n1 @ _f32(lp["wq"])).reshape(T, Hq, D), lp["q_norm"]["weight"],
             eps)
    k = _rms((n1 @ _f32(lp["wk"])).reshape(T, Hkv, D),
             lp["k_norm"]["weight"], eps)
    v = (n1 @ _f32(lp["wv"])).reshape(T, Hkv, D)
    J = max((T - K) // S + 1, 0)
    # step 1: every window that is whole inside the sequence
    win = S * jnp.arange(J)[:, None] + jnp.arange(K)[None, :]      # [J, K]
    kc = jnp.moveaxis(k[win].mean(axis=1), 0, 1)                 # [Hkv, J, D]
    kh, vh = jnp.moveaxis(k, 0, 1), jnp.moveaxis(v, 0, 1)       # [Hkv, T, D]
    col_block = jnp.arange(T) // B

    def rows(qb, t, given):
        qb = qb.reshape(-1, Hkv, G, D)
        if handed is None:
            b = _block_scores(mc, qb, kc, t, NB) if J else \
                jnp.full((qb.shape[0], Hkv, NB), -jnp.inf)
            sel = _choose(mc, b, t, NB)
        else:
            sel = given
        see = sel[:, :, col_block] \
            & (jnp.arange(T)[None, :] <= t[:, None])[:, None, :]  # [R,Hkv,T]
        s = jnp.einsum("rkgd,ktd->rkgt", qb, kh) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(see[:, :, None, :], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("rkgt,ktd->rkgd", p, vh).reshape(-1, Hq * D), sel

    given = jnp.zeros((T, Hkv, NB), bool) if handed is None \
        else jnp.asarray(handed, bool)
    o, sel = _row_blocks(rows, q, jnp.arange(T), given)
    return (o * jax.nn.sigmoid(n1 @ _f32(lp["wg"]))) @ _f32(lp["wo"]), sel


def _layer(mc: dict, kind: str, x, lp: dict, handed=None, lower=""):
    import jax

    def act(a):
        return jax.lax.reduce_precision(a, 4, 3) if lower == "act" else a

    eps = mc.get("norm_eps", 1e-6)
    r = mc.get("scale_depth", 0.0) / math.sqrt(
        mc.get("mup_depth") or mc["num_layers"]) \
        if mc.get("scale_depth") else 1.0
    n1 = act(_rms(x, lp["input_norm"]["weight"], eps))
    sel = None
    if kind == "l":
        mix = _lightning(mc, n1, lp, lower == "state")
    else:
        mix, sel = _sparse(mc, n1, lp, handed)
    h = act(x + r * mix)
    n2 = act(_rms(h, lp["post_norm"]["weight"], eps))
    return act(h + r * _swiglu(n2, lp)), sel


def _head_logits(x, leaf: dict):
    import jax.numpy as jnp

    w, sc = leaf["kernel"], leaf.get("scale")
    V = w.shape[1]
    nb = next(b for b in (BLOCKS, 8, 4, 2, 1) if V % b == 0)
    cols = V // nb
    out = []
    for b in range(nb):
        blk = w[:, b * cols:(b + 1) * cols].astype(jnp.float32)
        if sc is not None:
            blk = blk * sc[b * cols:(b + 1) * cols].astype(jnp.float32)
        out.append(x @ blk)
    return jnp.concatenate(out, axis=-1)


def forward(mc: dict, tree: dict, token_ids, n_last: int, selection=None,
            lower: str = ""):
    """float32 logit rows predicting the LAST ``n_last`` tokens of
    ``token_ids`` (row j predicts token len-n_last+j from everything before
    it), as a device array [n_last, V], and the blocks chosen, bool
    [selecting layers, T, Hkv, blocks]. ``selection`` and ``lower``: the
    module docstring's two instruments."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ids = jnp.asarray(np.asarray(token_ids, np.int32))
    T = int(ids.shape[0])
    eps = mc.get("norm_eps", 1e-6)
    with jax.default_matmul_precision("highest"):
        emb = {k: v[ids] for k, v in tree["embed"].items()}
        x = emb["weight"].astype(jnp.float32)
        if "scale" in emb:
            x = x * emb["scale"].astype(jnp.float32)[:, None]
        x = x * mc.get("scale_emb", 1.0)
        layer = {kind: jax.jit(lambda x, lp, handed, kind=kind:
                               _layer(mc, kind, x, lp, handed, lower))
                 for kind in set(mc["layer_pattern"])}
        chosen, seen = [], {"attn": 0, "lightning": 0}
        for kind in mc["layer_pattern"]:
            stack = "lightning" if kind == "l" else "attn"
            lp = jax.tree.map(lambda a, i=seen[stack]: a[i],
                              tree["layers"][stack])
            seen[stack] += 1
            handed = None
            if kind != "l" and selection is not None:
                handed = jnp.asarray(selection[len(chosen)])
            x, sel = layer[kind](x, lp, handed)
            if kind != "l":
                chosen.append(sel)
        x = _rms(x[T - 1 - n_last:T - 1], tree["final_norm"]["weight"], eps)
        if mc.get("dim_model_base"):
            x = x * (mc["dim_model_base"] / mc["hidden_size"])
        return jax.jit(_head_logits)(x, tree["lm_head"]), \
            (jnp.stack(chosen) if chosen else None)


def logits(mc: dict, tree: dict, token_ids, n_last: int):
    """``forward``'s logit rows: the plain reference, selecting on its own
    activations in float32."""
    return forward(mc, tree, token_ids, n_last)[0]


def logprobs(mc: dict, tree: dict, token_ids, n_last: int, **instruments):
    """float32 log-softmax of ``forward``'s logit rows, as a numpy array
    [n_last, V]. The benchmark passes no instrument."""
    import jax
    import numpy as np

    with jax.default_matmul_precision("highest"):
        out = jax.nn.log_softmax(
            forward(mc, tree, token_ids, n_last, **instruments)[0], axis=-1)
    return np.asarray(jax.device_get(out))
