"""Plain reference of the LFM2-MoE decoder (``model_type`` lfm2_moe,
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json), written
from the published config's keys and, for what the keys do not restate, the
family's public modelling code (the configuration file lists each such item
under ``assumed``).

Per layer i (RMSNorm(v) = v / sqrt(mean(v^2) + eps) * w; no biases):

    u     = RMSNorm_operator(x)
    conv layer ("c"):
      B, C, X = split3(u W_in)                 W_in: H -> 3 H, in that order
      z       = B * X
      c_t     = sum_{j=0..K-1} w_j * z_{t-(K-1)+j}     depthwise, causal,
                K = conv_L_cache taps, z_t = 0 for t < 0, no bias, NO
                activation
      op      = (C * c) W_out
    attention layer ("g"):
      q, k, v = u Wq, u Wk, u Wv               (Hq x D, Hkv x D, Hkv x D)
      q, k    = RMSNorm_q(q), RMSNorm_k(k)     per head, weight [D]
      q, k    = RoPE(q, k; theta, all of D, rotate-half pairing)
      op      = softmax(q k^T / sqrt(D) + causal mask) v  Wo
                float32, Hq / Hkv query heads a KV head
    h     = x + op
    m     = RMSNorm_ffn(h)
    f     = SwiGLU_dense(m)                               i < num_dense_layers
          = sum_{e in top-k} w_e SwiGLU_e(m)              otherwise
    x     = h + f
    router: s = sigmoid(m Wg) in float32; top-k by s + expert_bias;
            w = s[top-k] / (sum s[top-k] + route_norm_eps)
    model:  x0 = Embed(tokens);  logits = RMSNorm_final(x_L) Embed^T  (tied)

Straightforward ``jax.numpy`` in float32 at matmul precision "highest": no
cache, no pages, no kernels, no sort, nothing imported from the program. The
whole sequence is recomputed from the token ids (teacher forcing). int8
kernels are dequantised (kernel * scale) one layer, and inside it one expert,
at a time, and the head is taken over the last rows alone, in blocks of
vocabulary rows. The token ids are right-padded to a whole number of
``PAD_TO`` rows, so that the lengths of one comparison share ONE compiled
function a layer kind (a compile costs 7-10 s on the chip, and a run that
overstays its limit is lost): every operator is causal, so no row reads a
padding row and the rows of the sequence are what they are without them.
The reference routes on ITS OWN activations.

Departures from the published code, none of which changes the mathematics:
every expert is computed for every token and masked by the [T, E] matrix
that holds w_e for the chosen experts and 0 elsewhere; the chosen weights
stay float32; the convolution is written as K shifted products.

Instruments beside the plain call, all off unless asked for (``forward``;
``logits`` and ``logprobs``, which the benchmark calls, pass none):
- ``routing`` [routed layers, T, k]: the experts each token is HANDED in
  each routed layer, in place of the reference's own top-k (the weights are
  still the reference's own scores of those experts); ``forward`` also
  returns the choices it made or was handed.
- ``lower`` "act": the ACTIVATION operand of every matmul with a kernel and
  of the attention's two products — each layer's normed inputs, the gated
  rows ``z`` and ``C * c``, q, k and v (after their norms and the
  rotation), the attention output before the output projection, every
  FFN's hidden vector — and the residual stream are rounded to 4 exponent
  bits and 3 of mantissa (float8 e4m3; the configuration computes in
  bfloat16), by ``lax.reduce_precision``; the softmax and the router stay
  float32.
- ``wrong``: one mechanism of the model left out or broken, each a control
  the comparison has to refuse: "oldest_tap" (the convolution without its
  oldest tap: K - 1 taps), "stale_tail" (the K - 1 rows before position 0
  are not zeros but what another sequence left in the slot: the last rows
  of ``z`` of this same token sequence — a tail that was not reset at
  admission), "no_expert_bias" (top-k by the scores alone), "half_rope"
  (only the first half of each head's dimensions rotates),
  "expert_swapped" (in every routed layer the expert that the most tokens
  of the sequence chose computes with its neighbour's three matrices);
  ``lower`` "experts": the float8 rounding inside the routed experts alone.
"""

from __future__ import annotations

# What benchmark/controls_lfm2.py and chip_smoke.py hold against the served
# stream: each has to come out NOT correct (PERF.md section 6, PR 42, says
# which the limits see).
CONTROLS = {
    "the oldest tap dropped": dict(wrong="oldest_tap"),
    "a slot's tail not reset at admission": dict(wrong="stale_tail"),
    "RoPE over half of the head": dict(wrong="half_rope"),
    "float8 activations": dict(lower="act"),
}

# Held the same way and SHOWN, not required: faults of the routed branches
# alone, the smallest branches of the seeded model (weight_makers/lfm2_moe.py
# says why they are, and why the selection bias decides near-ties alone: left
# out of the choice it is a fault of a token's fourth expert in a fifth of the
# rows). The comparison's limits do not reliably see them: 0.06-0.12 nats for
# the bias, 0.17-0.28 for the swapped expert, 0.05-0.08 for float8 in the
# experts on the chip (PERF.md section 6, PR 42; limit 0.25).
CONTROLS_REPORTED = {
    "expert_bias left out of the choice": dict(wrong="no_expert_bias"),
    "the busiest expert computed as its neighbour":
        dict(wrong="expert_swapped"),
    "float8 activations in the routed experts alone": dict(lower="experts"),
}

# the controls the two limits refuse by a wide margin at a prompt of several
# hundred tokens too (chip_smoke.py requires these of its 700-token prompt; a
# tail that was not reset fades with the distance from position 0: 0.31-0.43
# at 300 tokens on the chip, PERF.md section 6, PR 42 — the benchmark's
# comparison holds every control at 4, 63 and 300 tokens)
CONTROLS_SEEN_LONG = ("the oldest tap dropped", "RoPE over half of the head",
                      "float8 activations")

PAD_TO = 512      # the sequence is right-padded to a multiple of this
BLOCKS = 16       # blocks of vocabulary rows of the output head
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


def _swiglu(n, p: dict, r):
    import jax

    return r(jax.nn.silu(n @ _f32(p["w_gate"])) * (n @ _f32(p["w_up"]))) \
        @ _f32(p["w_down"])


def _rope(x, theta: float, rotated: int):
    """x: [T, heads, D] at positions 0..T-1; the first ``rotated`` features
    of a head rotate, feature i paired with i + rotated/2."""
    import jax.numpy as jnp

    T = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rotated, 2, dtype=jnp.float32)
                          / rotated)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = x[..., :rotated]
    half = jnp.concatenate([-rot[..., rotated // 2:],
                            rot[..., :rotated // 2]], -1)
    return jnp.concatenate([rot * cos + half * sin, x[..., rotated:]], -1)


def _conv(mc: dict, u, lp: dict, wrong: str, r, n):
    """The gated short convolution's operator; u: [T, H], of which the
    first ``n`` rows are the sequence."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    b, c, xg = jnp.split(u @ _f32(lp["w_in"]), 3, axis=-1)
    z = r(b * xg)
    taps = lp["conv"]["weight"].astype(jnp.float32)         # [K, H]
    K = taps.shape[0]
    before = jax.lax.dynamic_slice_in_dim(z, n - (K - 1), K - 1) \
        if wrong == "stale_tail" \
        else jnp.zeros((K - 1, z.shape[1]), jnp.float32)
    window = jnp.concatenate([before, z])                   # [K - 1 + T, H]
    conv = sum(taps[j] * window[j:j + T]
               for j in range(1 if wrong == "oldest_tap" else 0, K))
    return r(c * conv) @ _f32(lp["wo"])


def _attention(mc: dict, a, lp: dict, wrong: str, r):
    import jax
    import jax.numpy as jnp

    T = a.shape[0]
    hq, hkv, d = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    eps = mc.get("norm_eps", 1e-5)
    q = _rms((a @ _f32(lp["wq"])).reshape(T, hq, d),
             lp["q_norm"]["weight"], eps)
    k = _rms((a @ _f32(lp["wk"])).reshape(T, hkv, d),
             lp["k_norm"]["weight"], eps)
    v = (a @ _f32(lp["wv"])).reshape(T, hkv, d)
    theta = mc.get("rope_theta", 10000.0)
    rotated = d // 2 if wrong == "half_rope" else d
    q, k, v = r(_rope(q, theta, rotated)), r(_rope(k, theta, rotated)), r(v)
    k, v = jnp.repeat(k, hq // hkv, 1), jnp.repeat(v, hq // hkv, 1)
    nb = -(-T // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - T), (0, 0), (0, 0))
                 ).reshape(nb, Q_BLOCK, hq, d)
    keys = jnp.arange(T)

    def block(args):
        qs, first = args
        pos = first + jnp.arange(Q_BLOCK)
        s = jnp.einsum("thd,shd->hts", qs, k) / jnp.sqrt(jnp.float32(d))
        s = jnp.where((pos[:, None] >= keys[None, :])[None], s, -1e30)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (qb, jnp.arange(nb) * Q_BLOCK))
    return r(o.reshape(nb * Q_BLOCK, hq * d)[:T]) @ _f32(lp["wo"])


def _routed(mc: dict, m, fp: dict, handed, wrong: str, r):
    """Sum over the chosen experts; m: [T, H]. Returns (the sum, the
    experts chosen [T, k] — ``handed`` if given)."""
    import jax
    import jax.numpy as jnp

    E, k = mc["num_experts"], mc["num_experts_per_tok"]
    scores = jax.nn.sigmoid(m @ fp["router"]["kernel"].astype(jnp.float32))
    by = scores if wrong == "no_expert_bias" \
        else scores + fp["router"]["bias"].astype(jnp.float32)
    _, idx = jax.lax.top_k(by, k)                           # [T, k]
    if handed is not None:
        idx = handed
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if mc.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True)
                 + mc.get("route_norm_eps", 1e-20))
    w = w * mc.get("route_scale", 1.0)
    # [T, E]: w_e where expert e was chosen for the token, else 0
    weight = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                     * w[..., None], axis=1)
    stacks = tuple({n: fp[name][n] for n in fp[name]}
                   for name in ("w_gate", "w_up", "w_down"))
    if wrong == "expert_swapped":
        busiest = jnp.argmax(jnp.sum(weight > 0, axis=0))
        stacks = jax.tree.map(
            lambda a: a.at[busiest].set(a[(busiest + 1) % E]), stacks)

    def one(acc, ex):
        gate, up, down, col = ex
        n = r(m)
        h = r(jax.nn.silu(n @ _f32(gate)) * (n @ _f32(up)))
        return acc + col[:, None] * (h @ _f32(down)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), stacks + (weight.T,))
    return out, idx


def _layer(mc: dict, kind: str, routed: bool, x, lp: dict, fp: dict, n,
           handed=None, lower: str = "", wrong: str = ""):
    """(the layer's output, the experts its FFN chose [T, k] or None); the
    first ``n`` rows of ``x`` are the sequence."""
    r = _f8 if lower == "act" else (lambda a: a)
    eps = mc.get("norm_eps", 1e-5)
    u = r(_rms(x, lp["input_norm"]["weight"], eps))
    op = _conv(mc, u, lp, wrong, r, n) if kind == "c" \
        else _attention(mc, u, lp, wrong, r)
    x = r(x + op)
    m = r(_rms(x, lp["post_norm"]["weight"], eps))
    f, idx = _routed(mc, m, fp, handed, wrong,
                     _f8 if lower == "experts" else r) if routed \
        else (_swiglu(m, fp, r), None)
    return r(x + f), idx


def _tied_logits(x, emb: dict):
    """x @ Embed^T in blocks of vocabulary rows; ``emb`` = {weight [V, H],
    scale [V]}."""
    import jax
    import jax.numpy as jnp

    w = emb["weight"]
    V = w.shape[0]
    nb = next(b for b in (BLOCKS, 8, 4, 2, 1) if V % b == 0)
    wb = w.reshape(nb, V // nb, w.shape[1])
    logits = jnp.moveaxis(
        jax.lax.map(lambda b: x @ b.astype(jnp.float32).T, wb),
        0, 1).reshape(x.shape[0], V)
    if "scale" in emb:
        logits = logits * emb["scale"].astype(jnp.float32)[None, :]
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

    T = len(token_ids)
    ids = jnp.asarray(np.pad(np.asarray(token_ids, np.int32),
                             (0, -T % PAD_TO)))
    eps = mc.get("norm_eps", 1e-5)
    nd = mc.get("num_dense_layers", 0)
    layers = tree["layers"]
    with jax.default_matmul_precision("highest"):
        emb = {k: v[ids] for k, v in tree["embed"].items()}
        x = emb["weight"].astype(jnp.float32)
        if "scale" in emb:
            x = x * emb["scale"].astype(jnp.float32)[:, None]
        fns, seen, chosen = {}, {"c": 0, "g": 0}, []
        for i, kind in enumerate(mc["layer_pattern"]):
            routed = i >= nd
            fn = fns.get((kind, routed))
            if fn is None:
                fn = fns[kind, routed] = jax.jit(
                    lambda x, lp, fp, n, handed, kind=kind, routed=routed:
                    _layer(mc, kind, routed, x, lp, fp, n, handed, lower,
                           wrong))
            at = seen[kind]
            seen[kind] += 1
            lp = jax.tree.map(lambda a: a[at],
                              layers["conv" if kind == "c" else "attn"])
            fp = jax.tree.map(lambda a: a[i - nd if routed else i],
                              layers["ffn_moe" if routed else "ffn_dense"])
            handed = None if routing is None or not routed \
                else jnp.pad(jnp.asarray(routing[len(chosen)], jnp.int32),
                             ((0, -T % PAD_TO), (0, 0)))
            x, idx = fn(x, lp, fp, jnp.int32(T), handed)
            if routed:
                chosen.append(idx[:T])
        x = _rms(jax.lax.dynamic_slice_in_dim(x, T - 1 - n_last, n_last),
                 tree["final_norm"]["weight"], eps)
        return jax.jit(_tied_logits)(x, tree["embed"]), \
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
