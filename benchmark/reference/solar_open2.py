"""Plain reference of the Solar-Open2 decoder (``model_type`` solar_open2,
https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json),
written from the published config's keys and, for the linear-attention
layers, the published KDA layer those keys name (Kimi Linear,
arXiv:2510.26692: ``kda_*``, ``short_conv_kernel_size``).

Pre-norm residual blocks, RMSNorm(v) = v / sqrt(mean(v^2) + eps) * w:

    h   = x + Mix_l(n1),        n1 = RMSNorm(x)
    out = h + FFN(n2),          n2 = RMSNorm(h)

The layer kinds repeat with the period ``layer_pattern`` ("gkkk": layer 4i
is softmax attention, 4i+1..4i+3 are KDA).

**GQA layer.** q = n1 Wq (Hq x D), k = n1 Wk, v = n1 Wv (Hkv x D); NO rotary
and no other position term (``use_rope`` false); causal softmax attention in
float32, scale 1/sqrt(D), Hq/Hkv query heads a KV head;
``o = Attn(q, k, v) * sigmoid(n1 Wg)`` elementwise, Wg: H -> Hq D
(``use_gqa_gate``); Mix = o Wo.

**KDA layer.** Per head (Hk heads, d_k = d_v = d), with conv the depthwise
causal convolution of ``short_conv_kernel_size`` (4) taps (as many as the
``conv`` leaf has rows; no bias) followed by SiLU:

    q_t = L2norm(conv(n1 Wq))_t / sqrt(d)     k_t = L2norm(conv(n1 Wk))_t
    v_t = conv(n1 Wv)_t
    g_t = -exp(A_log[h]) * softplus((n1_t Fa) Fb + dt_bias)   per CHANNEL
    b_t = 2 sigmoid(n1_t Wb)                                  per head
    S'  = diag(exp(g_t)) S_{t-1};  S_t = S' + b_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t                                S_0 = 0
    Mix = (RMSNorm_head(o_t) * sigmoid((n1_t Ga) Gb)) Wo

L2norm(x) = x / sqrt(sum x^2 + 1e-6). The recurrence runs TOKEN BY TOKEN
under a scan, in float32.

**FFN (every layer).** s = sigmoid(n2 Wr) over ALL ``n_routed_experts`` in
float32; the top-k are chosen by s + b (b the router's selection bias);
w_e = s_e / sum_{top-k} s over all k chosen (``routed_scaling_factor`` is
1 as published, so no factor appears); FFN = Shared(n2) + sum_{e in top-k, held} w_e
Expert_e(n2), each a SwiGLU. This chip HOLDS ``num_experts`` of the
``n_routed_experts`` (ids ``expert_offset`` ...): what the experts held
elsewhere would add is left out, here exactly as in the program, and the
partial sum goes on to the next layer (the model-configs guide's cut of an
expert-parallel layer). The reference routes on ITS OWN activations.

Straightforward ``jax.numpy`` in float32 at matmul precision "highest": no
cache, no kernels, no batching, no sort, no blocks, nothing imported from
the program. The whole sequence is recomputed from the token ids (teacher
forcing). int8 kernels are dequantised (kernel * scale) one layer, and
inside it one expert, at a time; the head in column blocks.

Assumed (the catalog row's ``config`` is silent; each is the family's
convention): sigmoid scoring with a selection bias that changes who is
chosen and no weight (the solar_open / glm4_moe router these MoE keys come
from); the GQA gate elementwise from its own H -> Hq D projection; the KDA
low rank = head_dim; A_log per head, dt_bias per channel and the gated
per-head output norm as in the published KDA layer; the shared expert's
width = moe_intermediate_size x n_shared_experts; ``intermediate_size`` is
unused (``first_k_dense_replace`` 0).

Departures from the published code, none of which changes the mathematics:
- every HELD expert is computed for every token and masked by the [T, E]
  matrix that holds w_e for the chosen experts and 0 elsewhere (a chosen
  expert that is not held has no column at all);
- the published KDA layer runs a chunked kernel; this is the recurrence it
  implements, one token at a time;
- the chosen weights stay float32 (a served checkpoint casts them to bf16);
- the output head is taken in column blocks.

Two instruments beside the plain call, both off unless asked for
(``forward``; ``logits`` and ``logprobs``, which the benchmark calls, pass
neither):
- ``routing`` [layers, T, k]: the experts each token is HANDED in each
  layer, in place of the reference's own top-k (the weights are still the
  reference's own scores of those experts). With the served path's choices
  handed in, what is left of the distance is everything but routing ties;
  ``forward`` also returns the choices it made or was handed.
- ``lower``: the controls one precision below what the configuration states
  — "state": the KDA state is rounded to bfloat16 (8 exponent bits, 7 of
  mantissa) after every token, as a bfloat16 state leaf would keep it (the
  configuration's is float32); "act": every layer's normed inputs and the
  residual stream are rounded to 4 exponent bits and 3 of mantissa (float8
  e4m3; the configuration computes in bfloat16). By
  ``lax.reduce_precision``: a convert there and back is a round trip the
  TPU compiler removes (it allows excess precision), and the control then
  reads the plain reference to the last digit. A comparison that guards
  the stated precision has to refuse these (chip_smoke.py runs them).
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


def _l2(x):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _swiglu(n, p: dict):
    import jax

    return (jax.nn.silu(n @ _f32(p["w_gate"])) * (n @ _f32(p["w_up"]))) \
        @ _f32(p["w_down"])


def _ffn(mc: dict, n2, lp: dict, handed=None):
    """Shared(n2) + sum over the chosen AND held experts; n2: [T, H].
    Returns (the sum, the experts chosen [T, k] — ``handed`` if given)."""
    import jax
    import jax.numpy as jnp

    E, k = mc["num_experts"], mc["num_experts_per_tok"]
    off = mc.get("expert_offset", 0)
    scores = jax.nn.sigmoid(n2 @ lp["router"]["kernel"].astype(jnp.float32))
    _, idx = jax.lax.top_k(
        scores + lp["router"]["bias"].astype(jnp.float32), k)      # [T, k]
    if handed is not None:
        idx = handed
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if mc.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    # [T, E held]: w_e where held expert e was chosen for the token, else 0
    # (one_hot of an id outside [0, E) is a row of zeros)
    weight = jnp.sum(jax.nn.one_hot(idx - off, E, dtype=jnp.float32)
                     * w[..., None], axis=1)

    def one(acc, ex):
        gate, up, down, col = ex
        y = _swiglu(n2, {"w_gate": gate, "w_up": up, "w_down": down})
        return acc + col[:, None] * y, None

    stacks = tuple({n: lp[name][n] for n in lp[name]}
                   for name in ("w_gate", "w_up", "w_down"))
    out, _ = jax.lax.scan(one, jnp.zeros_like(n2), stacks + (weight.T,))
    if mc.get("n_shared_experts", 0):
        out = out + _swiglu(n2, lp["shared"])
    return out, idx


def _gqa(mc: dict, n1, lp: dict):
    import jax
    import jax.numpy as jnp

    T = n1.shape[0]
    hq, hkv, d = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    pos = jnp.arange(T)
    q = (n1 @ _f32(lp["wq"])).reshape(T, hq, d)
    k = jnp.repeat((n1 @ _f32(lp["wk"])).reshape(T, hkv, d), hq // hkv, 1)
    v = jnp.repeat((n1 @ _f32(lp["wv"])).reshape(T, hkv, d), hq // hkv, 1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(d))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    o = o.reshape(T, hq * d)
    if mc.get("attn_output_gate", False):
        o = o * jax.nn.sigmoid(n1 @ _f32(lp["wg"]))
    return o @ _f32(lp["wo"])


def _kda(mc: dict, n1, lp: dict, low_state: bool = False):
    import jax
    import jax.numpy as jnp

    T = n1.shape[0]
    H, d = mc["kda_num_heads"], mc["kda_head_dim"]
    taps = lp["conv"]["weight"].astype(jnp.float32)          # [K, 3 H d]
    K = taps.shape[0]
    pre = jnp.concatenate([n1 @ _f32(lp[n]) for n in ("wq", "wk", "wv")], -1)
    pad = jnp.concatenate([jnp.zeros((K - 1, pre.shape[1])), pre])
    conv = jax.nn.silu(sum(taps[i] * pad[i:i + T] for i in range(K)))
    q, k, v = (a.reshape(T, H, d) for a in jnp.split(conv, 3, axis=-1))
    q, k = _l2(q) / jnp.sqrt(jnp.float32(d)), _l2(k)
    f = (n1 @ _f32(lp["f_a"])) @ _f32(lp["f_b"]) \
        + lp["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(lp["A_log"].astype(jnp.float32))[None, :, None] \
        * jax.nn.softplus(f.reshape(T, H, d))                # [T, H, d]
    beta = 2.0 * jax.nn.sigmoid(n1 @ _f32(lp["w_beta"]))     # [T, H]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S                     # [H, dk, dv]
        u = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - u)[:, None, :]
        o_t = jnp.einsum("hkv,hk->hv", S, q_t)
        if low_state:       # what a bfloat16 leaf keeps of it
            S = jax.lax.reduce_precision(S, 8, 7)
        return S, o_t

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms(o, lp["o_norm"]["weight"], mc.get("norm_eps", 1e-5))
    gate = jax.nn.sigmoid((n1 @ _f32(lp["g_a"])) @ _f32(lp["g_b"]))
    return (o.reshape(T, H * d) * gate) @ _f32(lp["wo"])


def _layer(mc: dict, kind: str, x, lp: dict, handed=None, lower=""):
    """(the layer's output, the experts its FFN chose [T, k])."""
    import jax

    def r(a):       # the "act" control: float8 e4m3 activations
        return jax.lax.reduce_precision(a, 4, 3) if lower == "act" else a

    eps = mc.get("norm_eps", 1e-5)
    n1 = r(_rms(x, lp["input_norm"]["weight"], eps))
    mix = _gqa(mc, n1, lp) if kind == "g" \
        else _kda(mc, n1, lp, lower == "state")
    h = r(x + mix)
    ffn, idx = _ffn(mc, r(_rms(h, lp["post_norm"]["weight"], eps)), lp,
                    handed)
    return r(h + ffn), idx


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


def forward(mc: dict, tree: dict, token_ids, n_last: int, routing=None,
            lower: str = ""):
    """float32 logit rows predicting the LAST ``n_last`` tokens of
    ``token_ids`` (row j predicts token len-n_last+j from everything before
    it), as a device array [n_last, V], and the experts chosen, int32
    [layers, T, k]. ``routing`` and ``lower``: the module docstring's two
    instruments."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ids = jnp.asarray(np.asarray(token_ids, np.int32))
    T = int(ids.shape[0])
    eps = mc.get("norm_eps", 1e-5)
    pattern = mc["layer_pattern"]
    with jax.default_matmul_precision("highest"):
        emb = {k: v[ids] for k, v in tree["embed"].items()}
        x = emb["weight"].astype(jnp.float32)
        if "scale" in emb:
            x = x * emb["scale"].astype(jnp.float32)[:, None]
        layer = {kind: jax.jit(lambda x, lp, handed, kind=kind:
                               _layer(mc, kind, x, lp, handed, lower))
                 for kind in set(pattern)}
        chosen = []
        for period in range(mc["num_layers"] // len(pattern)):
            j = 0
            for kind in pattern:
                if kind == "g":
                    lp = jax.tree.map(lambda a: a[period],
                                      tree["layers"]["gqa"])
                else:
                    lp = jax.tree.map(lambda a, j=j: a[period, j],
                                      tree["layers"]["kda"])
                    j += 1
                handed = None if routing is None \
                    else jnp.asarray(routing[len(chosen)], jnp.int32)
                x, idx = layer[kind](x, lp, handed)
                chosen.append(idx)
        x = _rms(x[T - 1 - n_last:T - 1], tree["final_norm"]["weight"], eps)
        return jax.jit(_head_logits)(x, tree["lm_head"]), jnp.stack(chosen)


def logits(mc: dict, tree: dict, token_ids, n_last: int):
    """``forward``'s logit rows: the plain reference, routing on its own
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
