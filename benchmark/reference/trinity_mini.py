"""Plain reference of the Trinity decoder (``model_type`` afmoe,
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json), written
from the published config's keys and, for what the keys do not restate, the
family's public modelling code (the configuration file lists each such item
under ``assumed``).

Per layer i (RMSNorm(v) = v / sqrt(mean(v^2) + eps) * w; no biases):

    a     = RMSNorm_in(x)
    q,k,v = a Wq, a Wk, a Wv                  (Hq x D, Hkv x D, Hkv x D)
    q,k   = RMSNorm_q(q), RMSNorm_k(k)        per head, weight [D]
    if layer i is a WINDOW layer ("w"):  q,k = RoPE(q,k; theta, all of D,
          rotate-half pairing);           a FULL layer ("g") has NO positions
    o     = softmax(q k^T / sqrt(D) + causal mask [window layer: only keys j
          with pos - j < sliding_window]) v   float32, Hq/Hkv heads a KV head
    o     = o * sigmoid(a Wg)                 elementwise, Wg: H -> Hq D
    x     = x + RMSNorm_post_attn(o Wo)
    m     = RMSNorm_pre_mlp(x)
    f     = SwiGLU_dense(m)                                   i < num_dense_layers
          = SwiGLU_shared(m) + sum_{e in top-k} w_e SwiGLU_e(m)     otherwise
    x     = x + RMSNorm_post_mlp(f)
    router: s = sigmoid(m Wr) in float32; top-k by s + expert_bias;
            w = s[top-k] / (sum s[top-k] + 1e-20) * route_scale
    model:  x0 = Embed(tokens) * sqrt(H);  logits = RMSNorm_final(x_L) W_head

Straightforward ``jax.numpy`` in float32 at matmul precision "highest": no
cache, no pages, no kernels, no sort, nothing imported from the program. The
whole sequence is recomputed from the token ids (teacher forcing). So that a
6,500-token sequence fits beside the served state on one chip, the
attention is taken in blocks of QUERY rows (each block against every key,
under the mask: a block is the plain formula), int8 kernels are dequantised
(kernel * scale) one layer, and inside it one expert, at a time, and the
head is taken over the last rows alone, in column blocks. The reference
routes on ITS OWN activations.

Departures from the published code, none of which changes the mathematics:
every expert is computed for every token and masked by the [T, E] matrix
that holds w_e for the chosen experts and 0 elsewhere; the chosen weights
stay float32.

Instruments beside the plain call, all off unless asked for (``forward``;
``logits`` and ``logprobs``, which the benchmark calls, pass none):
- ``routing`` [routed layers, T, k]: the experts each token is HANDED in
  each routed layer, in place of the reference's own top-k (the weights are
  still the reference's own scores of those experts); ``forward`` also
  returns the choices it made or was handed.
- ``lower`` "act": the ACTIVATION operand of every matmul with a kernel and
  of the attention's two products — each layer's normed inputs, q, k and v
  (after their norms and the rotation), the gated attention output before
  the output projection, every FFN's hidden vector (dense, shared and
  routed) — and the residual stream are rounded to 4 exponent bits and 3
  of mantissa (float8 e4m3; the configuration computes in bfloat16), by
  ``lax.reduce_precision``; the softmax and its weights stay float32.
  (Rounding the normed inputs and the stream ALONE, as this file first did,
  is less than a float8 computation rounds: it read 0.17 at the long
  prompt where the routed experts alone in float8 read 0.33 — my chip run,
  PR 39.) "experts": the routed experts alone in float8 e4m3 — their
  input, their hidden vector and their three dequantised kernels — and
  nothing else.
- ``wrong``: one mechanism of the model left out, each a control the
  comparison has to refuse: "no_window" (a window layer sees every key),
  "rope_in_full" (a full layer rotates q/k too), "route_scale_1" (the
  renormalised weights as they are).
"""

from __future__ import annotations

# What benchmark/controls.py and chip_smoke.py hold against the served
# stream: each of CONTROLS has to come out NOT correct; the routed experts
# alone in float8 are six branches of sixteen and are reported (four
# comparisons in five refuse them: PERF.md section 6, PR 39).
CONTROLS = {
    "the window ignored in w layers": dict(wrong="no_window"),
    "RoPE applied in g layers": dict(wrong="rope_in_full"),
    "route_scale 1": dict(wrong="route_scale_1"),
    "float8 activations": dict(lower="act"),
}
CONTROLS_REPORTED = {
    "float8 in the routed experts alone": dict(lower="experts"),
}

BLOCKS = 16       # column blocks of the output head
Q_BLOCK = 512     # query rows of one attention block


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


def _f8(a):
    """float8 e4m3: 4 exponent bits, 3 of mantissa."""
    import jax

    return jax.lax.reduce_precision(a, 4, 3)


def _swiglu(n, p: dict, r=None):
    """``r``: a rounding of the hidden vector (the "act" control)."""
    import jax

    h = jax.nn.silu(n @ _f32(p["w_gate"])) * (n @ _f32(p["w_up"]))
    return (r(h) if r else h) @ _f32(p["w_down"])


def _rope(x, theta: float):
    """x: [T, heads, D] at positions 0..T-1; the whole head rotates, pairing
    feature i with i + D/2."""
    import jax.numpy as jnp

    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + half * sin


def _attention(mc: dict, kind: str, a, lp: dict, wrong: str, r=None):
    import jax
    import jax.numpy as jnp

    r = r or (lambda x: x)

    T = a.shape[0]
    hq, hkv, d = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    eps = mc.get("norm_eps", 1e-5)
    q = _rms((a @ _f32(lp["wq"])).reshape(T, hq, d),
             lp["q_norm"]["weight"], eps)
    k = _rms((a @ _f32(lp["wk"])).reshape(T, hkv, d),
             lp["k_norm"]["weight"], eps)
    v = (a @ _f32(lp["wv"])).reshape(T, hkv, d)
    if kind == "w" or wrong == "rope_in_full":
        theta = mc.get("rope_theta", 10000.0)
        q, k = _rope(q, theta), _rope(k, theta)
    q, k, v = r(q), r(k), r(v)
    k, v = jnp.repeat(k, hq // hkv, 1), jnp.repeat(v, hq // hkv, 1)
    window = mc["sliding_window"] \
        if kind == "w" and wrong != "no_window" else 0
    nb = -(-T // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - T), (0, 0), (0, 0))
                 ).reshape(nb, Q_BLOCK, hq, d)
    keys = jnp.arange(T)

    def block(args):
        qs, first = args
        pos = first + jnp.arange(Q_BLOCK)
        s = jnp.einsum("thd,shd->hts", qs, k) / jnp.sqrt(jnp.float32(d))
        seen = pos[:, None] >= keys[None, :]
        if window:
            seen &= pos[:, None] - keys[None, :] < window
        s = jnp.where(seen[None], s, -1e30)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (qb, jnp.arange(nb) * Q_BLOCK))
    o = o.reshape(nb * Q_BLOCK, hq * d)[:T]
    return r(o * jax.nn.sigmoid(a @ _f32(lp["wg"]))) @ _f32(lp["wo"])


def _routed(mc: dict, m, fp: dict, handed, wrong: str, lower: str = ""):
    """Shared(m) + sum over the chosen experts; m: [T, H]. Returns (the
    sum, the experts chosen [T, k] — ``handed`` if given)."""
    import jax
    import jax.numpy as jnp

    E, k = mc["num_experts"], mc["num_experts_per_tok"]
    scores = jax.nn.sigmoid(m @ fp["router"]["kernel"].astype(jnp.float32))
    _, idx = jax.lax.top_k(
        scores + fp["router"]["bias"].astype(jnp.float32), k)      # [T, k]
    if handed is not None:
        idx = handed
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if mc.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    if wrong != "route_scale_1":
        w = w * mc.get("route_scale", 1.0)
    # [T, E]: w_e where expert e was chosen for the token, else 0
    weight = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                     * w[..., None], axis=1)

    same = lambda a: a                                      # noqa: E731
    ra = _f8 if lower in ("act", "experts") else same       # activations
    rw = _f8 if lower == "experts" else same                # kernels

    def one(acc, ex):
        gate, up, down, col = ex
        n = ra(m)
        h = ra(jax.nn.silu(n @ rw(_f32(gate))) * (n @ rw(_f32(up))))
        return acc + col[:, None] * (h @ rw(_f32(down))), None

    stacks = tuple({n: fp[name][n] for n in fp[name]}
                   for name in ("w_gate", "w_up", "w_down"))
    out, _ = jax.lax.scan(one, jnp.zeros_like(m), stacks + (weight.T,))
    return out + _swiglu(m, fp["shared"],
                         _f8 if lower == "act" else None), idx


def _layer(mc: dict, kind: str, routed: bool, x, lp: dict, fp: dict,
           handed=None, lower: str = "", wrong: str = ""):
    """(the layer's output, the experts its FFN chose [T, k] or None)."""
    import jax

    act = _f8 if lower == "act" else None

    def r(a):       # the "act" control: float8 e4m3 activations
        return _f8(a) if act else a

    eps = mc.get("norm_eps", 1e-5)
    a = r(_rms(x, lp["input_norm"]["weight"], eps))
    x = r(x + _rms(_attention(mc, kind, a, lp, wrong, act),
                   lp["attn_out_norm"]["weight"], eps))
    m = r(_rms(x, lp["post_norm"]["weight"], eps))
    f, idx = _routed(mc, m, fp, handed, wrong, lower) if routed \
        else (_swiglu(m, fp, act), None)
    return r(x + _rms(f, lp["mlp_out_norm"]["weight"], eps)), idx


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
            lower: str = "", wrong: str = ""):
    """float32 logit rows predicting the LAST ``n_last`` tokens of
    ``token_ids`` (row j predicts token len-n_last+j from everything before
    it), as a device array [n_last, V], and the experts chosen, int32
    [routed layers, T, k]. ``routing``, ``lower``, ``wrong``: the module
    docstring's instruments."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ids = jnp.asarray(np.asarray(token_ids, np.int32))
    T = int(ids.shape[0])
    eps = mc.get("norm_eps", 1e-5)
    nd = mc.get("num_dense_layers", 0)
    layers = tree["layers"]
    with jax.default_matmul_precision("highest"):
        emb = {k: v[ids] for k, v in tree["embed"].items()}
        x = emb["weight"].astype(jnp.float32)
        if "scale" in emb:
            x = x * emb["scale"].astype(jnp.float32)[:, None]
        x = x * jnp.sqrt(jnp.float32(mc["hidden_size"]))
        fns = {}
        chosen = []
        for i, kind in enumerate(mc["layer_pattern"]):
            routed = i >= nd
            fn = fns.get((kind, routed))
            if fn is None:
                fn = fns[kind, routed] = jax.jit(
                    lambda x, lp, fp, handed, kind=kind, routed=routed:
                    _layer(mc, kind, routed, x, lp, fp, handed, lower, wrong))
            lp = jax.tree.map(lambda a: a[i], layers["attn"])
            fp = jax.tree.map(lambda a: a[i - nd if routed else i],
                              layers["ffn_moe" if routed else "ffn_dense"])
            handed = None if routing is None or not routed \
                else jnp.asarray(routing[len(chosen)], jnp.int32)
            x, idx = fn(x, lp, fp, handed)
            if routed:
                chosen.append(idx)
        x = _rms(x[T - 1 - n_last:T - 1], tree["final_norm"]["weight"], eps)
        return jax.jit(_head_logits)(x, tree["lm_head"]), \
            (jnp.stack(chosen) if chosen else None)


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
