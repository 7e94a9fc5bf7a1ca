"""Plain reference of the Falcon-H1 decoder (``model_type`` falcon_h1,
https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json):
every layer a Mamba-2 state-space mixer (SSD, arXiv:2405.21060) AND a GQA
attention mixer in parallel on ONE normed input, then a SwiGLU — written from
the published config's keys and, for what the keys do not restate, the
published descriptions of the family (the configuration file lists each such
item under ``assumed``).

Rows ``x`` [T, hidden]; RMSNorm(v; w) = v / sqrt(mean(v^2) + eps) * w; no
biases but the convolution's. H heads of P, G groups, state N, K taps:

    n  = RMSNorm(x; w_input)
    -- state-space branch
    u  = ((n * ssm_in_multiplier) W_in) * m      m = ssm_multipliers over the
                                                 segments z | x | B | C | dt
    z, xBC, dt = split(u, [H P, H P + 2 G N, H])
    xBC = SiLU(conv(xBC) + b_conv)               depthwise, causal, K taps,
                                                 rows before position 0 = 0
    xs, B, C = split(xBC)                        head h reads group h // (H/G)
    D_ = softplus(dt + dt_bias)                  step size a head and token
    a  = exp(D_ * A),  A = -exp(A_log)
    S_t = a_t S_(t-1) + B_t (D_t xs_t)^T         S [H, N, P] float32, S_-1 = 0
    y_t = S_t^T C_t + D * xs_t
    y  = RMSNorm_groups(y * SiLU(z); w_ssm_norm) the gate FIRST, then RMS over
                                                 each group's H P / G channels
    s  = (y W_out) * ssm_out_multiplier
    -- attention branch, on the same n
    q = (n * attention_in_multiplier) Wq;  v likewise;
    k = ((n * attention_in_multiplier) Wk) * key_multiplier
    RoPE(theta, the whole head, rotate-half) on q, k; softmax(q k^T /
    sqrt(head_dim) + causal mask) v; Hq / Hkv query heads a KV head
    t  = (ctx Wo) * attention_out_multiplier
    x  = x + s + t
    h  = RMSNorm(x; w_pre_ff)
    f  = (W_down(SiLU((W_gate h) * mlp_multipliers[0]) * (W_up h)))
         * mlp_multipliers[1]
    x  = x + f
    model: x0 = Embed[token] * embedding_multiplier;
           logits = (RMSNorm(x_L; w_final) W_head) * lm_head_multiplier

Straightforward ``jax.numpy`` in float32 at matmul precision "highest": the
recurrence token by token (``lax.scan``), no cache, no pages, no kernels, no
blocks, nothing imported from the program. The whole sequence is recomputed
from the token ids (teacher forcing). int8 kernels are dequantised (kernel *
scale) one layer at a time, and the head is taken over the last rows alone,
in blocks of vocabulary columns (the float32 head is 5.3 GB whole). The token
ids are right-padded to a whole number of ``PAD_TO`` rows, so that the
lengths of one comparison share ONE compiled layer function: every operator
is causal, so no row reads a padding row.

Departures from the published code, none of which changes the mathematics:
the multipliers of the in-projection are applied as written above (scalar on
the input, vector on the output) where the family's code may fold them; the
convolution is K shifted products; attention in blocks of query rows.

Instruments (``forward``; ``logits`` and ``logprobs``, which the benchmark
calls, pass none):
- ``wrong``: one mechanism left out or broken — "no_ssm" / "no_attn" (a
  branch's output not added), "stale_state" (the state at position 0 is not
  zero but what this same sequence left: a slot's state not zeroed at
  admission), "no_tail" (the convolution of the rows that are decoded — the
  last ``n_last`` inputs — sees zeros in place of the K - 1 rows before it: a
  tail that is not carried), "no_key_multiplier", "no_D", "one_group_norm"
  (the gated RMSNorm over all H P channels as one group), "swapped_groups"
  (head h reads the OTHER group's B and C).
- ``lower``: one precision below what the configuration states — "state"
  (the SSM state rounded to bfloat16 after every token, as a bfloat16 leaf
  would keep it; the configuration's is float32), "act" (each layer's normed
  inputs, the convolved rows, q, k, v, both mixers' outputs before their
  out-projections, the FFN's hidden vector and the residual stream rounded
  to float8 e4m3; the configuration computes in bfloat16). By
  ``lax.reduce_precision``: a convert there and back is a round trip the TPU
  compiler removes.
"""

from __future__ import annotations

# What benchmark/controls_falcon_h1.py and chip_smoke.py hold against the
# served stream: each has to come out NOT correct at some prompt length.
CONTROLS = {
    "the SSM branch dropped": dict(wrong="no_ssm"),
    "the attention branch dropped": dict(wrong="no_attn"),
    "the state not zeroed at position 0": dict(wrong="stale_state"),
    "the conv tail not carried": dict(wrong="no_tail"),
    "key_multiplier left out": dict(wrong="no_key_multiplier"),
    "the D skip dropped": dict(wrong="no_D"),
    "the gated norm over one group": dict(wrong="one_group_norm"),
    "B and C read from the wrong group": dict(wrong="swapped_groups"),
    "float8 activations": dict(lower="act"),
}

# Held the same way and SHOWN, not required: the comparison's limits do NOT
# see it (0.026-0.035 nats at 4, 63 and 300 tokens against the plain
# reference's 0.025-0.029; my chip run, PR 48). Rounding the state to
# bfloat16 after every token is a random error of 2^-9 a step that adds up to
# ~1.4 % of a slow head's state over 300 tokens — the size of the bfloat16
# program's own rounding of its activations (PERF.md sections 6 and 7).
CONTROLS_REPORTED = {
    "a bfloat16 state": dict(lower="state"),
}

# the controls the two limits refuse at a prompt of several hundred tokens too
# (chip_smoke.py requires these of its 700-token prompt; a state that was not
# zeroed fades with the distance from position 0, and the benchmark's
# comparison holds every control at 4, 63 and 300 tokens)
CONTROLS_SEEN_LONG = (
    "the SSM branch dropped", "the attention branch dropped",
    "the conv tail not carried", "key_multiplier left out",
    "the D skip dropped", "B and C read from the wrong group",
    "float8 activations")

PAD_TO = 512      # the sequence is right-padded to a multiple of this
BLOCKS = 16       # blocks of vocabulary columns of the output head
Q_BLOCK = 512     # query rows of one attention block


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


def _f8(a):
    """float8 e4m3: 4 exponent bits, 3 of mantissa."""
    import jax

    return jax.lax.reduce_precision(a, 4, 3)


def _bf16(a):
    import jax

    return jax.lax.reduce_precision(a, 8, 7)


def _rope(x, theta: float):
    """x: [T, heads, D] at positions 0..T-1; feature i pairs with i + D/2."""
    import jax.numpy as jnp

    T, d = x.shape[0], x.shape[-1]
    inv = 1.0 / float(theta) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def _ssm(mc: dict, n, sp: dict, n_rows, n_last, wrong: str, lower: str, r):
    """The state-space branch; n: [T, hidden] normed rows of which the first
    ``n_rows`` are the sequence and the last ``n_last`` of those decoded."""
    import jax
    import jax.numpy as jnp

    T = n.shape[0]
    H, P, G, N = (mc["ssm_num_heads"], mc["ssm_head_dim"],
                  mc["ssm_num_groups"], mc["ssm_state_size"])
    K = mc["conv_taps"]
    m = mc.get("ssm_multipliers") or (1.0,) * 5
    widths = (H * P, H * P, G * N, G * N, H)
    mvec = jnp.concatenate([jnp.full((w,), v, jnp.float32)
                            for w, v in zip(widths, m)])
    u = ((n * mc.get("ssm_in_multiplier", 1.0)) @ _f32(sp["w_in"])) * mvec
    z, xbc, dt = jnp.split(u, [H * P, 2 * H * P + 2 * G * N], axis=-1)
    taps = sp["conv"]["weight"].astype(jnp.float32)             # [K, C]
    window = jnp.concatenate(
        [jnp.zeros((K - 1, xbc.shape[1]), jnp.float32), xbc])   # [K-1+T, C]
    conv = sum(taps[j] * window[j:j + T] for j in range(K))
    if wrong == "no_tail":
        decoded = (jnp.arange(T) >= n_rows - n_last)[:, None]
        conv = jnp.where(decoded, taps[K - 1] * xbc, conv)
    xbc = r(jax.nn.silu(conv + sp["conv"]["bias"].astype(jnp.float32)))
    xs, Bm, Cm = jnp.split(xbc, [H * P, H * P + G * N], axis=-1)
    xs = xs.reshape(T, H, P)
    Bm, Cm = Bm.reshape(T, G, N), Cm.reshape(T, G, N)
    if wrong == "swapped_groups":
        Bm, Cm = Bm[:, ::-1], Cm[:, ::-1]
    Bh, Ch = jnp.repeat(Bm, H // G, axis=1), jnp.repeat(Cm, H // G, axis=1)
    step = jax.nn.softplus(dt + sp["dt_bias"].astype(jnp.float32))  # [T, H]
    decay = jnp.exp(-jnp.exp(sp["A_log"].astype(jnp.float32)) * step)

    def token(S, row):
        x_t, b_t, c_t, d_t, a_t = row
        S = a_t[:, None, None] * S \
            + b_t[:, :, None] * (d_t[:, None] * x_t)[:, None, :]
        if lower == "state":    # what a bfloat16 leaf keeps of it
            S = _bf16(S)
        return S, jnp.sum(S * c_t[:, :, None], axis=1)          # [H, P]

    S0 = jnp.zeros((H, N, P), jnp.float32)
    if wrong == "stale_state":
        # what the sequence's own rows leave (the padding rows held still)
        live = (jnp.arange(T) < n_rows)[:, None]
        S0, _ = jax.lax.scan(token, S0, (xs, Bh, Ch,
                                         jnp.where(live, step, 0.0),
                                         jnp.where(live, decay, 1.0)))
    _, y = jax.lax.scan(token, S0, (xs, Bh, Ch, step, decay))
    if wrong != "no_D":
        y = y + sp["D"].astype(jnp.float32)[None, :, None] * xs
    y = y.reshape(T, H * P) * jax.nn.silu(z)
    groups = 1 if wrong == "one_group_norm" else G
    y = y.reshape(T, groups, -1)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                     + mc.get("norm_eps", 1e-5))
    y = y.reshape(T, H * P) * sp["o_norm"]["weight"].astype(jnp.float32)
    return (r(y) @ _f32(sp["wo"])) * mc.get("ssm_out_multiplier", 1.0)


def _attention(mc: dict, n, lp: dict, wrong: str, r):
    import jax
    import jax.numpy as jnp

    T = n.shape[0]
    hq, hkv, d = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    a = n * mc.get("attention_in_multiplier", 1.0)
    q = (a @ _f32(lp["wq"])).reshape(T, hq, d)
    k = (a @ _f32(lp["wk"])).reshape(T, hkv, d)
    if wrong != "no_key_multiplier":
        k = k * mc.get("key_multiplier", 1.0)
    v = (a @ _f32(lp["wv"])).reshape(T, hkv, d)
    theta = mc.get("rope_theta", 10000.0)
    q, k, v = r(_rope(q, theta)), r(_rope(k, theta)), r(v)
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
    return (r(o.reshape(nb * Q_BLOCK, hq * d)[:T]) @ _f32(lp["wo"])) \
        * mc.get("attention_out_multiplier", 1.0)


def _layer(mc: dict, x, lp: dict, n_rows, n_last, wrong: str = "",
           lower: str = ""):
    """One block; the first ``n_rows`` rows of ``x`` are the sequence."""
    import jax

    r = _f8 if lower == "act" else (lambda a: a)
    eps = mc.get("norm_eps", 1e-5)
    n = r(_rms(x, lp["input_norm"]["weight"], eps))
    if wrong != "no_ssm":
        x = x + _ssm(mc, n, lp["ssm"], n_rows, n_last, wrong, lower, r)
    if wrong != "no_attn":
        x = x + _attention(mc, n, lp, wrong, r)
    x = r(x)
    h = r(_rms(x, lp["post_norm"]["weight"], eps))
    m_gate, m_down = mc.get("mlp_multipliers") or (1.0, 1.0)
    f = r(jax.nn.silu((h @ _f32(lp["w_gate"])) * m_gate)
          * (h @ _f32(lp["w_up"]))) @ _f32(lp["w_down"])
    return r(x + f * m_down)


def _head_logits(x, head: dict):
    """x @ W_head in blocks of vocabulary columns; ``head`` = {kernel
    [H, V], scale [V]}."""
    import jax
    import jax.numpy as jnp

    w = head["kernel"]
    Hd, V = w.shape
    nb = next(b for b in (BLOCKS, 8, 4, 2, 1) if V % b == 0)
    wb = jnp.moveaxis(w.reshape(Hd, nb, V // nb), 1, 0)
    logits = jnp.moveaxis(
        jax.lax.map(lambda b: x @ b.astype(jnp.float32), wb),
        0, 1).reshape(x.shape[0], V)
    if "scale" in head:
        logits = logits * head["scale"].astype(jnp.float32)[None, :]
    return logits


_LAYER_FNS: dict = {}     # one compiled layer (and one head) a comparison


def forward(mc: dict, tree: dict, token_ids, n_last: int, wrong: str = "",
            lower: str = "", layers=None, hidden_in=None, head: bool = True):
    """float32 logit rows predicting the LAST ``n_last`` tokens of
    ``token_ids`` (row j predicts token len-n_last+j from everything before
    it), as a device array [n_last, V]. ``wrong``, ``lower``: the module
    docstring's instruments. ``layers`` (a range of the layers held; default
    all), ``hidden_in`` ([T, hidden]: the rows handed to the first of them in
    place of the embedding) and ``head`` False (return the rows [T, hidden]
    after the last of them) are what a test of a pipeline cut uses."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    T = len(token_ids)
    ids = jnp.asarray(np.pad(np.asarray(token_ids, np.int32),
                             (0, -T % PAD_TO)))
    eps = mc.get("norm_eps", 1e-5)
    par = tree["layers"]["par"]
    key = (json.dumps(mc, sort_keys=True, default=str), wrong, lower)
    fn = _LAYER_FNS.get(key)
    if fn is None:
        fn = _LAYER_FNS[key] = jax.jit(
            lambda x, lp, n_rows, n_gen: _layer(mc, x, lp, n_rows, n_gen,
                                                wrong, lower))
    with jax.default_matmul_precision("highest"):
        if hidden_in is None:
            emb = {k: v[ids] for k, v in tree["embed"].items()}
            x = emb["weight"].astype(jnp.float32)
            if "scale" in emb:
                x = x * emb["scale"].astype(jnp.float32)[:, None]
            x = x * mc.get("embedding_multiplier", 1.0)
        else:
            x = jnp.pad(jnp.asarray(hidden_in, jnp.float32),
                        ((0, -T % PAD_TO), (0, 0)))
        for i in (range(mc["num_layers"]) if layers is None else layers):
            x = fn(x, jax.tree.map(lambda a: a[i], par), jnp.int32(T),
                   jnp.int32(n_last))
        if not head:
            return x[:T]
        x = _rms(jax.lax.dynamic_slice_in_dim(x, T - 1 - n_last, n_last),
                 tree["final_norm"]["weight"], eps)
        head_fn = _LAYER_FNS.setdefault("head", jax.jit(_head_logits))
        return head_fn(x, tree["lm_head"]) \
            * mc.get("lm_head_multiplier", 1.0)


def logits(mc: dict, tree: dict, token_ids, n_last: int):
    """``forward``'s logit rows: the plain reference."""
    return forward(mc, tree, token_ids, n_last)


def logprobs(mc: dict, tree: dict, token_ids, n_last: int, **instruments):
    """float32 log-softmax of ``forward``'s logit rows, as a numpy array
    [n_last, V]. The benchmark passes no instrument."""
    import jax
    import numpy as np

    with jax.default_matmul_precision("highest"):
        out = jax.nn.log_softmax(
            forward(mc, tree, token_ids, n_last, **instruments), axis=-1)
    return np.asarray(jax.device_get(out))
