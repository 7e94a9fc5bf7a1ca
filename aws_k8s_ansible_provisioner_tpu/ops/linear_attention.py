"""KDA linear attention (Kimi Delta Attention, arXiv:2510.26692): the
recurrence in two exact forms, and the ``recur`` callbacks that run it over
per-slot state in the layer scan's carry.

Per head, with state ``S`` [d_k, d_v] (float32, zero at position 0), a
log-decay per CHANNEL ``g_t`` <= 0 and a step size ``beta_t`` in (0, 2):

    S'  = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

- :func:`kda_step` — one token a row (decode): the state is read, decayed,
  corrected by one rank-1 term and written. Elementwise and reductions in
  float32 on the VPU, no matmul: ``o_t`` is taken from ``S'`` by
  ``S_t^T q = S'^T q + (k.q) beta (v - S'^T k)``, so both reductions share
  one pass over the state and the update is the second. On the TPU the
  same step is :func:`kda_decode_update`, a Pallas kernel that holds a
  tile of the state in VMEM and makes it ONE pass, in place.
- :func:`kda_span` — a span of T rows (prefill, a chunk) in BLOCKS of
  ``BLOCK`` rows: inside a block every pair (t, s <= t) interacts through
  ``exp(G_t - G_s)`` (``G`` the running sum of ``g`` inside the block),
  formed from the pairwise DIFFERENCE, which is never positive — the naive
  ``exp(G_t) * exp(-G_s)`` overflows float32 once a channel has decayed by
  e^88 inside a block. The triangular system the delta rule leaves
  (``(I + A) U = beta (V - K~ S_0)``) is solved for all blocks at once
  (``T = (I + A)^-1 diag(beta)`` does not depend on the
  state), and the state is carried block to block by three small matmuls.
  A token-by-token scan over a 2,048-row chunk would read and write the
  4-MiB-a-slot state 2,048 times; this form does it T / BLOCK times.

Rows that carry no token (a chunk's padding, a dead passenger of a mixed
step, an idle slot) come with ``g`` = 0 and ``beta`` = 0: the identity on
the state in both forms.

The short convolution (depthwise, causal, ``K`` taps, SiLU) in front of
q/k/v needs the ``K - 1`` rows before a span: they are the second per-slot
leaf, ``kda_conv``. The same primitive (:func:`short_conv`, and the same
window and tail helpers) IS the mixer of a gated short-convolution layer
("c", LFM2): ``[B, C, X] = split3(u W_in)``, ``z = B * X``, ``c_t = sum_j
w_j z_(t-K+1+j)`` (no activation), output ``C * c`` — whose only state is
the ``K - 1`` rows of ``z`` before a span, the leaf ``conv_tail`` float32
``[n_c, slots, K - 1, hidden]``.

A Mamba-2 state-space mixer (SSD, arXiv:2405.21060; the "h" layers) is the
scalar-decay form below with a state that is NOT square and a decay that
depends on the token: per head ``S`` [d_state, d_head] float32,

    S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T      y_t = S_t^T C_t + D x_t

— Lightning's step with ``k = B``, ``q = C`` (a group's, shared by its
heads), ``v = x``, ``g = dt A`` and ``beta = dt`` (:func:`ssd_step`,
:func:`ssd_span`, :func:`ssd_scan`), behind a biased SiLU convolution over
x | B | C whose tail is the leaf ``ssm_conv`` float32 ``[n, slots, K - 1,
channels]``; the state is ``ssm_state`` float32 ``[n, 1, slots, H, d_state,
d_head]`` — d_head (128) on the lanes, so the decode kernel streams whole
rows.

State layout, beside the paged pool in the same ``cache`` pytree the step
programs donate: ``kda_state`` float32 ``[P, n_k, slots, H, d_k, d_v]`` and
``kda_conv`` ``[P, n_k, slots, K - 1, 3 H d_k]`` (P periods, n_k KDA layers
a period) — indexed by SLOT: row b of a decode batch is slot b, no gather.
A span that starts at position 0 starts from zeros inside the program, so
a slot's next occupant never reads its predecessor's state.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from aws_k8s_ansible_provisioner_tpu.config import ModelConfig

# rows a block of the span form; the pairwise decay tensor is
# [blocks, H, BLOCK, BLOCK, d_k], so the work inside a block grows with it
BLOCK = 16
# taps of the short convolution in front of q/k/v (the published layer's
# ``short_conv_kernel_size``): the ``kda_conv`` leaf keeps CONV_TAPS - 1 rows
CONV_TAPS = 4
_HI = jax.lax.Precision.HIGHEST


def _state_shapes(cfg: ModelConfig, num_slots: int, dtype) -> dict:
    out = {}
    if "k" in cfg.layer_pattern:
        P, nk, H, d = (cfg.num_periods, cfg.kda_per_period,
                       cfg.kda_num_heads, cfg.kda_head_dim)
        out.update({"kda_state": ((P, nk, num_slots, H, d, d), jnp.float32),
                    "kda_conv": ((P, nk, num_slots, CONV_TAPS - 1,
                                  3 * H * d), dtype)})
    if "l" in cfg.layer_pattern:
        # [n_l, 1, ...]: the KDA leaf's two leading axes, so the layer
        # helpers and the decode kernel address it as (layer, 0)
        H, d = cfg.lightning_num_heads, cfg.lightning_head_dim
        out["lin_state"] = ((cfg.layer_pattern.count("l"), 1, num_slots, H,
                             d, d), jnp.float32)
    if "c" in cfg.layer_pattern:
        # float32: a chunk that starts from a carried tail reads the very
        # numbers the rows before it held inside their own span
        out["conv_tail"] = ((cfg.layer_pattern.count("c"), num_slots,
                             cfg.conv_taps - 1, cfg.hidden_size), jnp.float32)
    if "h" in cfg.layer_pattern:
        n = cfg.layer_pattern.count("h")
        out["ssm_state"] = ((n, 1, num_slots, cfg.ssm_num_heads,
                             cfg.ssm_state_size, cfg.ssm_head_dim),
                            jnp.float32)
        out["ssm_conv"] = ((n, num_slots, cfg.conv_taps - 1,
                            cfg.ssm_conv_size), jnp.float32)
    return out


def init_state(cfg: ModelConfig, num_slots: int, dtype=jnp.bfloat16) -> dict:
    """The per-slot leaves of a model with recurrent layers: two for KDA
    layers, one for Lightning layers, one for gated short convolutions, two
    for state-space mixers."""
    return {name: jnp.zeros(shape, dt) for name, (shape, dt)
            in _state_shapes(cfg, num_slots, dtype).items()}


def state_bytes(cfg: ModelConfig, num_slots: int, dtype=jnp.bfloat16) -> int:
    if not cfg.recurrent:
        return 0
    return sum(math.prod(shape) * jnp.dtype(dt).itemsize for shape, dt
               in _state_shapes(cfg, num_slots, dtype).values())


def is_state(name: str) -> bool:
    return name.startswith(("kda_", "lin_", "conv_", "ssm_"))


# ---------------------------------------------------------------------------
# The mathematics
# ---------------------------------------------------------------------------


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def short_conv(window: jnp.ndarray, taps: jnp.ndarray) -> jnp.ndarray:
    """THE short convolution: depthwise, causal, no bias, no activation.
    ``window`` [..., T + K - 1, C] holds the K - 1 rows of history in front
    of the T rows, ``taps`` [K, C] the oldest row's tap first; returns
    [..., T, C] float32."""
    K = taps.shape[0]
    T = window.shape[-2] - (K - 1)
    w = taps.astype(jnp.float32)
    win = window.astype(jnp.float32)
    return sum(w[i] * jax.lax.slice_in_dim(win, i, i + T, axis=-2)
               for i in range(K))


def conv_qkv(window: jnp.ndarray, taps: jnp.ndarray, H: int, d: int):
    """:func:`short_conv` + SiLU over ``window`` [..., T + K - 1, 3 H d],
    then the split: ``q = L2norm(.) / sqrt(d)``, ``k = L2norm(.)``, ``v`` as
    it is — each [..., T, H, d] float32."""
    y = jax.nn.silu(short_conv(window, taps))
    q, k, v = (a.reshape(a.shape[:-1] + (H, d))
               for a in jnp.split(y, 3, axis=-1))
    return _l2norm(q) * (d ** -0.5), _l2norm(k), v


def kda_step(S, q, k, v, g, beta):
    """One token a row. S: [B, H, dk, dv] float32; q, k, g: [B, H, dk];
    v: [B, H, dv]; beta: [B, H]. Returns (o [B, H, dv], S_new)."""
    Sd = S * jnp.exp(g)[..., None]
    u = jnp.sum(Sd * k[..., None], axis=-2)             # S'^T k
    oq = jnp.sum(Sd * q[..., None], axis=-2)            # S'^T q
    delta = beta[..., None] * (v - u)
    o = oq + jnp.sum(k * q, axis=-1, keepdims=True) * delta
    return o, Sd + k[..., None] * delta[..., None, :]


def _unit_lower_inverse(A):
    """(I + A)^-1 for strictly lower-triangular ``A`` [..., C, C], C a power
    of two, by doubling: the inverses of the diagonal blocks of size s give
    those of size 2s — [[X11, 0], [-X22 L21 X11, X22]] — so log2(C) rounds
    of two small batched matmuls (forward substitution's C - 1 dependent
    row updates each rewrote the whole batch: 23 ms of a mixed step)."""
    C = A.shape[-1]
    lead = A.shape[:-2]
    X = jnp.ones(lead + (C, 1, 1), jnp.float32)          # size-1 blocks
    s = 1
    while s < C:
        nb = C // (2 * s)
        blocks = A.reshape(lead + (nb, 2 * s, nb, 2 * s))
        diag = jnp.moveaxis(jnp.diagonal(blocks, axis1=-4, axis2=-2), -1, -3)
        L21 = diag[..., s:, :s]                          # [..., nb, s, s]
        Xp = X.reshape(lead + (nb, 2, s, s))
        X11, X22 = Xp[..., 0, :, :], Xp[..., 1, :, :]
        X21 = -jnp.einsum("...ij,...jk,...kl->...il", X22, L21, X11,
                          precision=_HI)
        X = jnp.concatenate(
            [jnp.concatenate([X11, jnp.zeros_like(X11)], axis=-1),
             jnp.concatenate([X21, X22], axis=-1)], axis=-2)
        s *= 2
    return X[..., 0, :, :]


def kda_span(S0, q, k, v, g, beta, block: int = BLOCK):
    """T rows of N sequences, exact, in blocks. S0: [N, H, dk, dv] float32;
    q, k, g: [N, T, H, dk]; v: [N, T, H, dv]; beta: [N, T, H]; T a multiple
    of ``block``. Returns (o [N, T, H, dv], S after the last row)."""
    N, T, H, dk = q.shape
    C, nb = block, T // block

    def blocks(a):      # [N, T, H, ...] -> [nb, N, H, C, ...]
        a = a.reshape((N, nb, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v, g = blocks(q), blocks(k), blocks(v), blocks(g)
    beta = blocks(beta)                                  # [nb, N, H, C]
    G = jnp.cumsum(g, axis=-2)                           # [nb, N, H, C, dk]
    # pairwise decay exp(G_t - G_s), s <= t: the difference is <= 0 there;
    # masked BEFORE the exp, so no entry above the diagonal is ever formed
    tri = jnp.tril(jnp.ones((C, C), bool))
    diff = G[..., :, None, :] - G[..., None, :, :]       # [..., t, s, dk]
    decay = jnp.exp(jnp.where(tri[..., None], diff, -jnp.inf))
    # two reductions over d_k, written apart so that each takes the exp in
    # (stacked into one, the exp was used twice and XLA wrote the
    # [blocks, H, C, C, d_k] tensor out: 1 GiB a layer at a 2,048-row chunk)
    kd = k[..., None, :, :] * decay                      # k_s exp(G_t - G_s)
    A = beta[..., None] * jnp.where(
        jnp.tril(tri, -1), jnp.sum(k[..., :, None, :] * kd, axis=-1), 0)
    Bm = jnp.sum(q[..., :, None, :] * kd, axis=-1)       # q.k pairs, s <= t
    X = _unit_lower_inverse(A)
    Tm = X * beta[..., None, :]                          # (I + A)^-1 diag(b)
    eG = jnp.exp(G)
    W = jnp.einsum("...ts,...sk->...tk", Tm, k * eG, precision=_HI)
    Ut = jnp.einsum("...ts,...sv->...tv", Tm, v, precision=_HI)
    Qd = q * eG
    Gl = G[..., -1:, :]                                  # the block's total
    Kh = k * jnp.exp(Gl - G)                             # exp(G_C - G_s) <= 1
    eGl = jnp.exp(Gl[..., 0, :])[..., None]              # [nb, N, H, dk, 1]

    def step(S, xs):
        # float32 products ("highest"): at the default a TPU rounds the
        # float32 state to bf16 on every read, and what a prompt leaves
        # behind is what every later token of the answer reads
        W, Ut, Qd, Bm, Kh, eGl = xs
        U = Ut - jnp.einsum("nhtk,nhkv->nhtv", W, S, precision=_HI)
        o = jnp.einsum("nhtk,nhkv->nhtv", Qd, S, precision=_HI) \
            + jnp.einsum("nhts,nhsv->nhtv", Bm, U, precision=_HI)
        return eGl * S + jnp.einsum("nhsk,nhsv->nhkv", Kh, U,
                                    precision=_HI), o

    S, o = jax.lax.scan(step, S0, (W, Ut, Qd, Bm, Kh, eGl))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)        # [N, nb, C, H, dv]
    return o.reshape(N, T, H, -1), S


def kda_scan(S0, q, k, v, g, beta):
    """The same rows token by token (:func:`kda_step` under a scan): what
    the span form is tested against."""
    def step(S, xs):
        o, S = kda_step(S, *xs)
        return S, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    S, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1), S


# ---------------------------------------------------------------------------
# recur callbacks: (conv taps [K, 3Hd], qkv [B, T, 3Hd] before the
# convolution, g [B, T, H, d], beta [B, T, H], (rec, period, j)) ->
# (o [B, T, H, d] float32, rec). ``rec`` holds the two FULL state leaves;
# (period, j) names the layer (period traced, j static).
# ---------------------------------------------------------------------------


def _layer_get(rec, name, period, j):
    """All slots' rows of one layer, [slots, ...]."""
    arr = rec[name]
    return jax.lax.dynamic_slice(
        arr, (period, j) + (0,) * (arr.ndim - 2),
        (1, 1) + arr.shape[2:])[0, 0]


def _layer_set(rec, name, period, j, rows, slot=None):
    """Write a layer's rows: all slots (``slot`` None, rows [slots, ...]) or
    the N slots ``slot`` [N] (out-of-range ids drop)."""
    arr = rec[name]
    if slot is None:
        arr = jax.lax.dynamic_update_slice(
            arr, rows[None, None].astype(arr.dtype),
            (period, j) + (0,) * (arr.ndim - 2))
    else:
        arr = arr.at[period, j, slot].set(rows.astype(arr.dtype),
                                          mode="drop")
    return {**rec, name: arr}


def _pad_to(block: int, arrays, T: int):
    """Rows padded with zeros (g = 0, beta = 0: the identity) up to a whole
    number of blocks of ``block`` rows."""
    pad = -T % block
    if not pad:
        return arrays
    return tuple(jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                 for a in arrays)


def _pad_to_block(arrays, T: int):
    return _pad_to(BLOCK, arrays, T)


def _span_window(leaf, at, fresh, x):
    """[N, K - 1 + T, C]: each span's rows ``x`` behind the K - 1 rows its
    slot kept (``leaf[at]`` [N, K - 1, C]; zeros where ``fresh``)."""
    hist = jnp.where(fresh[:, None, None], 0, leaf[at])
    return jnp.concatenate([hist.astype(x.dtype), x], axis=1)


def _span_tail(window, n_valid, K: int):
    """The K - 1 rows in front of row ``n_valid`` [N] of each span: what
    its slot keeps (rows at or past ``n_valid`` leave none)."""
    return jax.vmap(lambda w, n: jax.lax.dynamic_slice_in_dim(
        w, n, K - 1, axis=0))(window, n_valid)


def _rows_window(hist, x):
    """[B, K, C]: one new row a slot behind the K - 1 rows it kept."""
    return jnp.concatenate([hist, x[:, None].astype(hist.dtype)], axis=1)


def _rows_tail(tail, hist, live):
    """What a slot keeps after its new row: ``tail``, the K - 1 newest rows
    of its window; a dead row (``live`` [B] False) the history it found."""
    if live is None:
        return tail
    return jnp.where(live[:, None, None], tail, hist)


def _span(rec_l, taps, qkv, g, beta, slots, fresh, n_valid):
    """N spans [N, T, ...] into slots ``slots`` [N]: each starts from zeros
    where ``fresh`` [N] (position 0) and from its slot's leaves otherwise;
    rows at or past ``n_valid`` [N] are the identity and leave no
    convolution row."""
    rec, period, j = rec_l
    N, T, H, d = g.shape
    K = taps.shape[0]
    # a padding slot id (batched prefill's padding rows) reads slot 0's
    # leaves — fresh or not, its rows are dead and its write drops
    rd = jnp.clip(slots, 0, rec["kda_state"].shape[2] - 1)
    S0 = jnp.where(fresh[:, None, None, None], 0.0,
                   rec["kda_state"][period, j, rd])
    window = _span_window(rec["kda_conv"], (period, j, rd), fresh, qkv)
    q, k, v = conv_qkv(window, taps, H, d)
    live = (jnp.arange(T)[None] < n_valid[:, None])          # [N, T]
    g = jnp.where(live[..., None, None], g, 0.0)
    beta = jnp.where(live[..., None], beta, 0.0)
    q, k, v, g, beta = _pad_to_block((q, k, v, g, beta), T)
    o, S = kda_span(S0, q, k, v, g, beta)
    tail = _span_tail(window, n_valid, K)
    rec = _layer_set(rec, "kda_state", period, j, S, slots)
    rec = _layer_set(rec, "kda_conv", period, j, tail, slots)
    return o[:, :T], rec


def _rows(rec_l, taps, qkv, g, beta, live):
    """One token for every slot (row b = slot b). ``live`` [B] bool or None:
    a dead row is the identity and leaves no convolution row."""
    rec, period, j = rec_l
    B, H, d = g.shape
    hist = _layer_get(rec, "kda_conv", period, j)            # [B, K-1, 3Hd]
    window = _rows_window(hist, qkv)
    q, k, v = conv_qkv(window, taps, H, d)
    tail = window[:, 1:]
    if live is not None:
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    tail = _rows_tail(tail, hist, live)
    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention

    if pallas_attention.supported():
        # one pass over the state, in place (the XLA form below reads it
        # twice: 55 % of its bytes' time on the chip, PERF.md PR 32)
        o, arr = kda_decode_update(rec["kda_state"], period, j, q[:, 0],
                                   k[:, 0], v[:, 0], g, beta)
        rec = {**rec, "kda_state": arr}
    else:
        o, S = kda_step(_layer_get(rec, "kda_state", period, j), q[:, 0],
                        k[:, 0], v[:, 0], g, beta)
        rec = _layer_set(rec, "kda_state", period, j, S)
    rec = _layer_set(rec, "kda_conv", period, j, tail)
    return o, rec


# ---------------------------------------------------------------------------
# The gated short convolution ("c" layers): ``recur.conv(taps [K, H], bcx
# [B, T, 3 H] — one projection's rows, split [B | C | X] —, (rec, i))`` ->
# (C * short_conv(B * X) [B, T, H] float32, rec); ``i`` (traced) the layer's
# index among the conv layers. The same span forms as above, over the one
# leaf ``conv_tail``.
# ---------------------------------------------------------------------------


def _gate_in(bcx):
    """(z = B * X, C) of the projection's rows, float32."""
    b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    return b * x, c


def _conv_span(taps, bcx, rec_l, *, slots, fresh, n_valid):
    """N spans [N, T, 3 H] into slots ``slots`` [N] (see :func:`_span`)."""
    rec, i = rec_l
    arr = rec["conv_tail"]
    z, c = _gate_in(bcx)
    rd = jnp.clip(slots, 0, arr.shape[1] - 1)
    window = _span_window(arr, (i, rd), fresh, z)
    tail = _span_tail(window, n_valid, taps.shape[0])
    return c * short_conv(window, taps), \
        {**rec, "conv_tail": arr.at[i, slots].set(tail, mode="drop")}


def _conv_rows(rec_l, taps, bcx, live):
    """One token for every slot (row b = slot b); see :func:`_rows`."""
    rec, i = rec_l
    arr = rec["conv_tail"]
    z, c = _gate_in(bcx)
    hist = jax.lax.dynamic_index_in_dim(arr, i, 0, keepdims=False)
    window = _rows_window(hist, z)
    arr = jax.lax.dynamic_update_slice(
        arr, _rows_tail(window[:, 1:], hist, live)[None], (i, 0, 0, 0))
    return c * short_conv(window, taps)[:, 0], {**rec, "conv_tail": arr}


def _conv_from_zero(taps, bcx, rec_l):
    z, c = _gate_in(bcx)
    window = jnp.pad(z, [(0, 0), (taps.shape[0] - 1, 0), (0, 0)])
    return c * short_conv(window, taps), rec_l[0]


def make_recur_decode(live=None):
    """decode_steps: x is [B, 1, ...], row b is slot b."""

    def recur(taps, qkv, g, beta, rec_l):
        o, rec = _rows(rec_l, taps, qkv[:, 0], g[:, 0], beta[:, 0], live)
        return o[:, None], rec

    def lightning(q, k, v, slopes, rec_l):
        o, rec = _lin_rows(rec_l, q[:, 0], k[:, 0], v[:, 0], slopes, live)
        return o[:, None], rec

    def conv(taps, bcx, rec_l):
        o, rec = _conv_rows(rec_l, taps, bcx[:, 0], live)
        return o[:, None], rec

    def ssm(cfg, conv_p, xbc, dt, A, D, rec_l):
        o, rec = _ssm_rows(cfg, rec_l, conv_p, xbc[:, 0], dt[:, 0], A, D,
                           live)
        return o[:, None], rec

    recur.lightning = lightning
    recur.conv = conv
    recur.ssm = ssm
    return recur


def make_recur_span(slot, start, n_valid):
    """prefill_step / prefill_chunk_step: one sequence [1, T, ...], rows
    [start, start + n_valid) of slot ``slot``."""
    slots = jnp.asarray(slot, jnp.int32)[None]
    fresh = (jnp.asarray(start, jnp.int32) == 0)[None]
    n = jnp.asarray(n_valid, jnp.int32)[None]

    def recur(taps, qkv, g, beta, rec_l):
        return _span(rec_l, taps, qkv, g, beta, slots, fresh, n)

    recur.lightning = functools.partial(_lin_span, slots=slots, fresh=fresh,
                                        n_valid=n)
    recur.conv = functools.partial(_conv_span, slots=slots, fresh=fresh,
                                   n_valid=n)
    recur.ssm = functools.partial(_ssm_span, slots=slots, fresh=fresh,
                                  n_valid=n)
    return recur


def make_recur_batch(slots, true_lens):
    """prefill_batch_step: N prompts [N, T, ...] from position 0; a padding
    row's slot id is out of range and its writes drop."""
    fresh = jnp.ones(slots.shape, bool)

    def recur(taps, qkv, g, beta, rec_l):
        return _span(rec_l, taps, qkv, g, beta, slots, fresh, true_lens)

    recur.lightning = functools.partial(_lin_span, slots=slots, fresh=fresh,
                                        n_valid=true_lens)
    recur.conv = functools.partial(_conv_span, slots=slots, fresh=fresh,
                                   n_valid=true_lens)
    recur.ssm = functools.partial(_ssm_span, slots=slots, fresh=fresh,
                                  n_valid=true_lens)
    return recur


def make_recur_mixed(B: int, live, pslot, pstart, plen):
    """mixed_step's packed [1, B + C, ...]: B decode rows (row b = slot b,
    ``live`` [B] False for the chunking slot's own row and for idle slots),
    then the C chunk rows of slot ``pslot`` from ``pstart``, ``plen`` of
    them valid. The decode rows go first: the chunking slot's row is the
    identity, so the chunk reads what the earlier chunks left."""
    span = make_recur_span(pslot, pstart, plen)

    def recur(taps, qkv, g, beta, rec_l):
        rec, period, j = rec_l
        od, rec = _rows((rec, period, j), taps, qkv[0, :B], g[0, :B],
                        beta[0, :B], live)
        oc, rec = span(taps, qkv[:, B:], g[:, B:], beta[:, B:],
                       (rec, period, j))
        return jnp.concatenate([od[None], oc], axis=1), rec

    def lightning(q, k, v, slopes, rec_l):
        od, rec = _lin_rows(rec_l, q[0, :B], k[0, :B], v[0, :B], slopes,
                            live)
        oc, rec = span.lightning(q[:, B:], k[:, B:], v[:, B:], slopes,
                                 (rec,) + tuple(rec_l[1:]))
        return jnp.concatenate([od[None], oc], axis=1), rec

    def conv(taps, bcx, rec_l):
        od, rec = _conv_rows(rec_l, taps, bcx[0, :B], live)
        oc, rec = span.conv(taps, bcx[:, B:], (rec,) + tuple(rec_l[1:]))
        return jnp.concatenate([od[None], oc], axis=1), rec

    def ssm(cfg, conv_p, xbc, dt, A, D, rec_l):
        od, rec = _ssm_rows(cfg, rec_l, conv_p, xbc[0, :B], dt[0, :B], A, D,
                            live)
        oc, rec = span.ssm(cfg, conv_p, xbc[:, B:], dt[:, B:], A, D,
                           (rec,) + tuple(rec_l[1:]))
        return jnp.concatenate([od[None], oc], axis=1), rec

    recur.lightning = lightning
    recur.conv = conv
    recur.ssm = ssm
    return recur


def recur_from_zero(taps, qkv, g, beta, rec_l):
    """No state kept: every sequence [N, T, ...] whole, from position 0
    (model_forward without a cache: tests, training)."""
    N, T, H, d = g.shape
    K = taps.shape[0]
    window = jnp.pad(qkv, [(0, 0), (K - 1, 0), (0, 0)])
    q, k, v = conv_qkv(window, taps, H, d)
    q, k, v, g, beta = _pad_to_block((q, k, v, g, beta), T)
    o, _ = kda_span(jnp.zeros((N, H, d, d), jnp.float32), q, k, v, g, beta)
    return o[:, :T], rec_l[0]


def _lightning_from_zero(q, k, v, slopes, rec_l):
    N, T, H, d = q.shape
    g, beta = _lin_decay(slopes, jnp.ones((N, T), bool))
    q, k, v, g, beta = _pad_to(LIN_BLOCK, (_lin_q(q), k.astype(jnp.float32),
                                           v.astype(jnp.float32), g, beta), T)
    o, _ = lightning_span(jnp.zeros((N, H, d, d), jnp.float32), q, k, v, g,
                          beta)
    return o[:, :T], rec_l[0]


recur_from_zero.lightning = _lightning_from_zero
recur_from_zero.conv = _conv_from_zero


def _ssm_from_zero(cfg, conv_p, xbc, dt, A, D, rec_l):
    window = jnp.pad(xbc.astype(jnp.float32),
                     [(0, 0), (conv_p["weight"].shape[0] - 1, 0), (0, 0)])
    return _ssm_rows_of_spans(cfg, conv_p, window, dt, A, D, None)[0], \
        rec_l[0]


recur_from_zero.ssm = _ssm_from_zero


# ---------------------------------------------------------------------------
# Lightning linear attention: a fixed scalar decay a head, no delta rule,
# no convolution. Per head, state S [d, d] float32:
#
#     S_t = lambda S_{t-1} + k_t^T v_t        o_t = (q_t / sqrt(d)) S_t
#
# with lambda = exp(-slope_h). The forms below take a LOG-decay per row and
# head ``g`` (-slope for a row that carries a token, 0 for a dead one) and
# ``beta`` (1 / 0 likewise), so a dead row is the identity on the state, as
# for KDA; ``q`` arrives scaled.
# ---------------------------------------------------------------------------

# rows a block of the span form: the intra-block products are [C, C] a head
LIN_BLOCK = 64


def _lin_q(q):
    return q.astype(jnp.float32) * (q.shape[-1] ** -0.5)


def _lin_decay(slopes, live):
    """(g, beta) [..., H] float32 of rows ``live`` [...] bool."""
    g = jnp.where(live[..., None], -slopes.astype(jnp.float32), 0.0)
    return g, jnp.broadcast_to(live[..., None].astype(jnp.float32), g.shape)


def lightning_step(S, q, k, v, g, beta):
    """One token a row. S: [B, H, d, d] float32; q (scaled), k, v: [B, H, d]
    float32; g, beta: [B, H]. Returns (o [B, H, d], S_new)."""
    S = S * jnp.exp(g)[..., None, None] \
        + (beta[..., None] * k)[..., None] * v[..., None, :]
    return jnp.sum(S * q[..., None], axis=-2), S


def lightning_span(S0, q, k, v, g, beta, block: int = LIN_BLOCK):
    """T rows of N sequences, exact, in blocks of ``block`` rows: inside a
    block rows (t, s <= t) interact through ``exp(G_t - G_s)`` (``G`` the
    running sum of ``g`` in the block; the difference is never positive),
    the state is carried block to block. S0: [N, H, d, d] float32; q
    (scaled), k, v: [N, T, H, d] float32; g, beta: [N, T, H]; T a multiple
    of ``block``. Returns (o [N, T, H, d], S after the last row)."""
    N, T, H, d = q.shape
    C, nb = block, T // block

    def blocks(a):      # [N, T, H, ...] -> [nb, N, H, C, ...]
        a = a.reshape((N, nb, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v = blocks(q), blocks(k), blocks(v * beta[..., None])
    G = jnp.cumsum(blocks(g), axis=-1)                   # [nb, N, H, C]
    tri = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(tri, G[..., :, None] - G[..., None, :],
                              -jnp.inf))                 # [.., t, s]
    A = jnp.einsum("...td,...sd->...ts", q, k, precision=_HI) * decay
    intra = jnp.einsum("...ts,...sv->...tv", A, v, precision=_HI)
    Qd = q * jnp.exp(G)[..., None]
    Gl = G[..., -1:]                                     # the block's total
    Kh = k * jnp.exp(Gl - G)[..., None]                  # exp(G_C - G_s) <= 1
    eGl = jnp.exp(Gl)[..., None]                         # [nb, N, H, 1, 1]

    def step(S, xs):
        Qd, intra, Kh, v, eGl = xs
        o = intra + jnp.einsum("nhtk,nhkv->nhtv", Qd, S, precision=_HI)
        return eGl * S + jnp.einsum("nhsk,nhsv->nhkv", Kh, v,
                                    precision=_HI), o

    S, o = jax.lax.scan(step, S0, (Qd, intra, Kh, v, eGl))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)        # [N, nb, C, H, d]
    return o.reshape(N, T, H, -1), S


def lightning_scan(S0, q, k, v, g, beta):
    """The same rows token by token: what the span form is tested against."""
    def step(S, xs):
        o, S = lightning_step(S, *xs)
        return S, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    S, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1), S


def _lin_span(q, k, v, slopes, rec_l, *, slots, fresh, n_valid):
    """N spans [N, T, H, d] into slots ``slots`` [N] (see :func:`_span`)."""
    rec, i, j = rec_l
    N, T = q.shape[:2]
    rd = jnp.clip(slots, 0, rec["lin_state"].shape[2] - 1)
    S0 = jnp.where(fresh[:, None, None, None], 0.0,
                   rec["lin_state"][i, j, rd])
    g, beta = _lin_decay(slopes, jnp.arange(T)[None] < n_valid[:, None])
    q, k, v, g, beta = _pad_to(LIN_BLOCK, (_lin_q(q), k.astype(jnp.float32),
                                           v.astype(jnp.float32), g, beta), T)
    o, S = lightning_span(S0, q, k, v, g, beta)
    return o[:, :T], _layer_set(rec, "lin_state", i, j, S, slots)


def _lin_rows(rec_l, q, k, v, slopes, live):
    """One token for every slot (row b = slot b); see :func:`_rows`."""
    rec, i, j = rec_l
    B = q.shape[0]
    g, beta = _lin_decay(slopes, jnp.ones((B,), bool) if live is None
                         else live)
    q, k, v = _lin_q(q), k.astype(jnp.float32), v.astype(jnp.float32)
    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention

    if pallas_attention.supported():
        # the KDA kernel without its delta rule: one pass, in place
        o, arr = kda_decode_update(
            rec["lin_state"], i, j, q, k, v,
            jnp.broadcast_to(g[..., None], q.shape), beta, delta_rule=False)
        return o, {**rec, "lin_state": arr}
    o, S = lightning_step(_layer_get(rec, "lin_state", i, j), q, k, v, g,
                          beta)
    return o, _layer_set(rec, "lin_state", i, j, S)


# ---------------------------------------------------------------------------
# The Mamba-2 state-space mixer ("h" layers): the scalar-decay forms above
# with k = B, q = C (a group's rows, repeated over its heads), v = x, g = dt A
# and beta = dt, plus the D skip. ``recur.ssm(cfg, conv {weight [K, C], bias
# [C]}, xbc [B, T, C] — x | B | C before the convolution —, dt [B, T, H]
# float32 (after the softplus), A [H] (< 0), D [H], (rec, i))`` -> (y [B, T,
# H, d_head] float32, rec); ``i`` (traced) the layer's index among the "h"
# layers. A row that carries no token comes out with dt = 0 (so g = 0): the
# identity on the state, and it leaves no convolution row.
# ---------------------------------------------------------------------------


def _ssd(form, S, x, Bm, Cm, dt, A, D, **kw):
    """``form`` — a scalar-decay step, span or scan, ``(S, q, k, v, g, beta)
    -> (o, S)`` — over Mamba-2 rows: a group's B and C rows [..., G, N]
    repeated over its H / G heads as k and q, and the D skip on top."""
    def per_head(a):
        return jnp.repeat(a, x.shape[-2] // a.shape[-2], axis=-2)

    o, S = form(S, per_head(Cm), per_head(Bm), x, dt * A, dt, **kw)
    return o + D[:, None] * x, S


def ssd_step(S, x, Bm, Cm, dt, A, D):
    """One token a row. S: [B, H, N, P] float32; x: [B, H, P]; Bm, Cm:
    [B, G, N]; dt: [B, H] (>= 0; 0 = a dead row); A (< 0), D: [H]. Returns
    (y [B, H, P], S_new)."""
    return _ssd(lightning_step, S, x, Bm, Cm, dt, A, D)


def ssd_span(S0, x, Bm, Cm, dt, A, D, block: int = 0):
    """T rows of N sequences in blocks (:func:`lightning_span`: the pairwise
    decay from the DIFFERENCE of the running sums of ``dt A``). x: [N, T, H,
    P]; Bm, Cm: [N, T, G, N]; dt: [N, T, H]; T a multiple of the block
    (``LIN_BLOCK`` rows; the published ``mamba_chunk_size`` is a hint for it,
    any block gives the same numbers)."""
    return _ssd(lightning_span, S0, x, Bm, Cm, dt, A, D,
                block=block or LIN_BLOCK)


def ssd_scan(S0, x, Bm, Cm, dt, A, D):
    """The same rows token by token: what the span form is tested against."""
    return _ssd(lightning_scan, S0, x, Bm, Cm, dt, A, D)


def _ssm_conv(conv_p, window):
    """SiLU(the biased short convolution) of ``window`` [..., K - 1 + T, C]:
    x | B | C as the recurrence takes them, float32 [..., T, C]."""
    return jax.nn.silu(short_conv(window, conv_p["weight"])
                       + conv_p["bias"].astype(jnp.float32))


def _ssm_split(cfg: ModelConfig, xbc):
    """x [..., H, P], B [..., G, N], C [..., G, N] of convolved rows."""
    H, P, G, N = (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_num_groups,
                  cfg.ssm_state_size)
    x, Bm, Cm = jnp.split(xbc, [H * P, H * P + G * N], axis=-1)
    lead = xbc.shape[:-1]
    return (x.reshape(lead + (H, P)), Bm.reshape(lead + (G, N)),
            Cm.reshape(lead + (G, N)))


def _ssm_rows_of_spans(cfg, conv_p, window, dt, A, D, S0):
    """Spans whose rows stand behind their K - 1 rows of history in
    ``window`` [N, K - 1 + T, C], from state ``S0`` (None = zeros): (y [N,
    T, H, P], S after the last row)."""
    N, T = dt.shape[:2]
    rows = _ssm_split(cfg, _ssm_conv(conv_p, window))
    if S0 is None:
        S0 = jnp.zeros((N, cfg.ssm_num_heads, cfg.ssm_state_size,
                        cfg.ssm_head_dim), jnp.float32)
    y, S = ssd_span(S0, *_pad_to(LIN_BLOCK, rows + (dt,), T), A, D)
    return y[:, :T], S


def _ssm_span(cfg, conv_p, xbc, dt, A, D, rec_l, *, slots, fresh, n_valid):
    """N spans [N, T, C] into slots ``slots`` [N] (see :func:`_span`)."""
    rec, i = rec_l
    T = xbc.shape[1]
    rd = jnp.clip(slots, 0, rec["ssm_state"].shape[2] - 1)
    S0 = jnp.where(fresh[:, None, None, None], 0.0,
                   rec["ssm_state"][i, 0, rd])
    window = _span_window(rec["ssm_conv"], (i, rd), fresh,
                          xbc.astype(jnp.float32))
    live = (jnp.arange(T)[None] < n_valid[:, None])[..., None]   # [N, T, 1]
    y, S = _ssm_rows_of_spans(cfg, conv_p, window, jnp.where(live, dt, 0.0),
                              A, D, S0)
    tail = _span_tail(window, n_valid, conv_p["weight"].shape[0])
    rec = _layer_set(rec, "ssm_state", i, 0, S, slots)
    return y, {**rec, "ssm_conv": rec["ssm_conv"].at[i, slots].set(
        tail, mode="drop")}


def _ssm_rows(cfg, rec_l, conv_p, xbc, dt, A, D, live):
    """One token for every slot (row b = slot b); see :func:`_rows`."""
    rec, i = rec_l
    arr = rec["ssm_conv"]
    hist = jax.lax.dynamic_index_in_dim(arr, i, 0, keepdims=False)
    window = _rows_window(hist, xbc)
    x, Bm, Cm = _ssm_split(cfg, _ssm_conv(conv_p, window)[:, 0])
    arr = jax.lax.dynamic_update_slice(
        arr, _rows_tail(window[:, 1:], hist, live)[None], (i, 0, 0, 0))
    if live is not None:
        dt = jnp.where(live[:, None], dt, 0.0)
    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention

    if pallas_attention.supported():
        # the KDA kernel without its delta rule, over [d_state, d_head]
        # tiles: one pass, in place on the leaf
        def in_place(state, q, k, v, g, beta):
            return kda_decode_update(
                state, i, 0, q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                beta, delta_rule=False)

        y, state = _ssd(in_place, rec["ssm_state"], x, Bm, Cm, dt, A, D)
        rec = {**rec, "ssm_state": state}
    else:
        y, S = ssd_step(_layer_get(rec, "ssm_state", i, 0), x, Bm, Cm, dt,
                        A, D)
        rec = _layer_set(rec, "ssm_state", i, 0, S)
    return y, {**rec, "ssm_conv": arr}


# ---------------------------------------------------------------------------
# The decode update as ONE pass over the state (TPU)
# ---------------------------------------------------------------------------

HEADS_PER_STEP = 8      # heads a grid step: 8 x [d, d] float32 tiles in VMEM


@functools.partial(jax.jit, static_argnames=("j", "interpret", "delta_rule"))
def kda_decode_update(state, period, j: int, q, k, v, g, beta,
                      interpret: bool = False, delta_rule: bool = True):
    """:func:`kda_step` for every slot of one layer, IN PLACE on the full
    state leaf: ``state`` [P, n_k, B, H, d_k, d_v] float32 (d_k = d_v = d
    for KDA and Lightning; [d_state, d_head] for a state-space mixer) is
    read once and
    written once (``input_output_aliases``), where the XLA form reads it
    twice (a reduce fusion for the two products with ``S'``, then the
    update fusion). q, k, g: [B, H, d_k]; v: [B, H, d_v]; beta: [B, H] (a dead
    row comes with g = 0, beta = 0: the identity). Returns (o [B, H, d]
    float32, state). ``delta_rule`` False is the Lightning layers' step on
    the same tiles: ``S_t = diag(exp(g)) S + beta k v^T`` with no correction
    by what the state already holds (``u`` is not formed).

    Grid (slots, heads / 8): a step holds 8 heads' tiles. The decay and the
    rank-1 update scale ROWS of a tile, so q, k and exp(g) arrive as
    columns — ``[B, H/8, d, 8]``, d on the sublanes, a head a lane — and v,
    beta and the output as rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P, nk, B, H, d, dv = state.shape
    hb = HEADS_PER_STEP if H % HEADS_PER_STEP == 0 else H
    nh = H // hb

    def cols(a):        # [B, H, d] -> [B, nh, d, hb]
        return jnp.swapaxes(a.reshape(B, nh, hb, d), 2, 3)

    idx = jnp.stack([jnp.asarray(period, jnp.int32), jnp.int32(j)])

    def col_map(b, h, idx):
        return (b, h, 0, 0)

    def row_map(b, h, idx):
        return (b, h, 0)

    def st_map(b, h, idx):
        return (idx[0], idx[1], b, h, 0, 0)

    def kernel(idx_ref, qc_ref, kc_ref, ec_ref, v_ref, beta_ref, s_ref,
               o_ref, s_out_ref):
        for hh in range(hb):
            S = s_ref[0, 0, 0, hh]                        # [d, d]
            kc = kc_ref[0, 0, :, hh:hh + 1]               # [d, 1]
            qc = qc_ref[0, 0, :, hh:hh + 1]
            Sd = S * ec_ref[0, 0, :, hh:hh + 1]
            if delta_rule:
                u = jnp.sum(Sd * kc, axis=0, keepdims=True)  # [1, d] = S'^T k
            oq = jnp.sum(Sd * qc, axis=0, keepdims=True)
            delta = beta_ref[0, hh:hh + 1, :] \
                * ((v_ref[0, hh:hh + 1, :] - u) if delta_rule
                   else v_ref[0, hh:hh + 1, :])           # [1, d]
            kq = jnp.sum(kc * qc, axis=0, keepdims=True)  # [1, 1]
            o_ref[0, hh:hh + 1, :] = oq + kq * delta
            s_out_ref[0, 0, 0, hh] = Sd + kc * delta

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nh),
        in_specs=[pl.BlockSpec((1, 1, d, hb), col_map),
                  pl.BlockSpec((1, 1, d, hb), col_map),
                  pl.BlockSpec((1, 1, d, hb), col_map),
                  pl.BlockSpec((1, hb, dv), row_map),
                  pl.BlockSpec((1, hb, dv), row_map),
                  pl.BlockSpec((1, 1, 1, hb, d, dv), st_map)],
        out_specs=[pl.BlockSpec((1, hb, dv), row_map),
                   pl.BlockSpec((1, 1, 1, hb, d, dv), st_map)])
    f32 = jnp.float32
    o, state = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state: operand 6, after the scalars and the five row operands
        input_output_aliases={6: 1},
        interpret=interpret,
    )(idx, cols(q.astype(f32)), cols(k.astype(f32)),
      cols(jnp.exp(g.astype(f32))), v.astype(f32),
      jnp.broadcast_to(beta.astype(f32)[..., None], (B, H, dv)),
      state)
    return o, state
