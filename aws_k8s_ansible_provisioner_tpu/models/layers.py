"""Core decoder-only transformer in functional JAX, designed TPU-first.

This is the heart of the serving engine the reference delegates to the external
vLLM CUDA container (SURVEY.md §2.2 row 1: "JAX/XLA serving engine" is the
TPU-native equivalent to build). Design choices for the TPU/XLA compilation model:

- **Scanned layers**: all layer weights are stacked with a leading ``[L, ...]`` axis
  and the decoder runs as one ``lax.scan`` over layers — one compiled layer body
  instead of 28-36 unrolled copies (compile time, HLO size) and a natural remat
  boundary (``jax.checkpoint`` over the scan body).
- **Static shapes everywhere**: no data-dependent Python control flow; masks and
  position arrays express raggedness. This is what lets XLA tile matmuls onto the
  MXU without re-specialization.
- **bfloat16 weights/activations, float32 softmax & norms**: MXU-native precision
  with numerically safe reductions.
- **Pluggable attention**: ``model_forward`` takes an ``attend`` callback so the
  same layer stack serves full causal prefill (training/parity tests), cached
  decode against the paged KV cache, and the Pallas kernel path, without
  duplicating the transformer block.

Weight layout is ``[in_features, out_features]`` (``x @ W``), i.e. transposed from
torch ``nn.Linear``; ``models/hf_loader.py`` handles the conversion.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from aws_k8s_ansible_provisioner_tpu.config import ModelConfig
from aws_k8s_ansible_provisioner_tpu.models import parts

# An attend callback: (q [B,T,Hq,D], k [B,T,Hkv,D], v [B,T,Hkv,D], layer_cache)
# -> (context [B,T,Hq,D], new_layer_cache). q/k are already RoPE'd and qk-normed.
AttendFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray, Any],
                    Tuple[jnp.ndarray, Any]]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float,
             zero_centered: bool = False) -> jnp.ndarray:
    """RMSNorm with float32 accumulation (matches HF Qwen3 semantics).

    ``zero_centered`` applies the weight as ``1 + w`` (Gemma convention: the
    checkpoint stores deviations from identity)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    if zero_centered:
        w = 1.0 + w
    return (x * w).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def apply_norm(cfg: ModelConfig, x: jnp.ndarray, p: dict) -> jnp.ndarray:
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["weight"], cfg.norm_eps,
                        zero_centered=cfg.norm_zero_centered)
    return layer_norm(x, p["weight"], p["bias"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def _llama3_scale_inv_freq(inv_freq: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Llama-3.1+ frequency-dependent RoPE scaling (HF ``rope_type: llama3``).

    High-frequency components (short wavelengths) pass through; low-frequency
    components are divided by ``rope_factor``; a band between the two corner
    wavelengths interpolates smoothly. Matches HF's
    ``_compute_llama3_parameters`` so converted checkpoints stay logit-exact.
    """
    low_wavelen = cfg.rope_original_max_pos / cfg.rope_low_freq_factor
    high_wavelen = cfg.rope_original_max_pos / cfg.rope_high_freq_factor
    wavelen = 2.0 * jnp.pi / inv_freq
    scaled = inv_freq / cfg.rope_factor
    smooth = (cfg.rope_original_max_pos / wavelen - cfg.rope_low_freq_factor) / (
        cfg.rope_high_freq_factor - cfg.rope_low_freq_factor)
    smoothed = (1.0 - smooth) * scaled + smooth * inv_freq
    out = jnp.where(wavelen > low_wavelen, scaled, inv_freq)
    mid = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
    return jnp.where(mid, smoothed, out)


def rope_cos_sin(positions: jnp.ndarray, rotary_dim: int, theta: float,
                 cfg: Optional[ModelConfig] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for given integer positions. positions: [B, T] or [T].

    ``cfg`` enables family-specific frequency scaling (``rope_scaling``);
    without it (or with ``rope_scaling == 'none'``) this is plain RoPE.
    """
    # float(): a config built from JSON gives an int, and one past int32
    # (1e11) does not parse as a weakly typed operand
    inv_freq = 1.0 / (
        float(theta) ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                         / rotary_dim)
    )
    if cfg is not None and cfg.rope_scaling == "llama3":
        inv_freq = _llama3_scale_inv_freq(inv_freq, cfg)
    # [..., T, rotary_dim/2]
    freqs = positions[..., None].astype(jnp.float32) * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # HF "rotate_half" convention
    return jnp.cos(emb), jnp.sin(emb)


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               rotary_dim: int) -> jnp.ndarray:
    """Apply (possibly partial) RoPE. x: [B, T, H, D]; cos/sin: [B, T, rotary_dim].

    Partial rotation (Phi-2's rotary_pct=0.4, HF PhiAttention behavior): only the
    first ``rotary_dim`` features of each head rotate; the rest pass through.
    """
    dtype = x.dtype
    rot = x[..., :rotary_dim].astype(jnp.float32)
    cos = cos[..., None, :]  # broadcast over heads: [B, T, 1, rotary_dim]
    sin = sin[..., None, :]
    rot = rot * cos + _rotate_half(rot) * sin
    if rotary_dim == x.shape[-1]:
        return rot.astype(dtype)
    return jnp.concatenate([rot.astype(dtype), x[..., rotary_dim:]], axis=-1)


# ---------------------------------------------------------------------------
# Dense attention (prefill / training / parity path)
# ---------------------------------------------------------------------------


def repeat_kv(k: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """[B, T, Hkv, D] -> [B, T, Hq, D] by repeating each kv head."""
    num_kv = k.shape[-2]
    if num_kv == num_heads:
        return k
    return jnp.repeat(k, num_heads // num_kv, axis=-2)


def causal_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  seq_lens: Optional[jnp.ndarray] = None,
                  window: int = 0) -> jnp.ndarray:
    """Full causal self-attention over the current window.

    q: [B, T, Hq, D]; k/v: [B, T, Hkv, D]. ``seq_lens`` optionally masks padded
    tail positions (right padding); ``window`` > 0 additionally restricts each
    query to its last ``window`` keys (sliding-window attention). float32
    softmax.
    """
    B, T, Hq, D = q.shape
    k = repeat_kv(k, Hq)
    v = repeat_kv(v, Hq)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]  # [Tq, Tk] causal
    if window > 0:
        mask = mask & (pos[None, :] > pos[:, None] - window)
    if seq_lens is not None:
        valid = pos[None, :] < seq_lens[:, None]  # [B, Tk]
        mask = mask[None, :, :] & valid[:, None, :]
        mask = mask[:, None, :, :]  # [B, 1, Tq, Tk]
    else:
        mask = mask[None, None, :, :]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return ctx.astype(q.dtype)


def _default_attend(q, k, v, cache):
    return causal_attend(q, k, v), cache


def make_default_attend(cfg: ModelConfig):
    """Full-window (training/parity) attend honoring cfg.sliding_window —
    every layer's window, or the "w" layers' alone (``attend.window``)
    where the kinds are a list."""

    def windowed(q, k, v, cache):
        return causal_attend(q, k, v, window=cfg.sliding_window), cache

    if cfg.windowed:
        def attend(q, k, v, cache):
            return causal_attend(q, k, v), cache

        attend.window = windowed
        return attend
    return windowed if cfg.sliding_window > 0 else _default_attend


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def _dense_init(key, shape, dtype, scale=0.02):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _init_ffn_params(cfg: ModelConfig, key: jax.Array, dtype, lead) -> dict:
    """Router, held expert stacks and shared expert of an expert FFN, every
    leaf with the leading axes ``lead``."""
    H, E, Im = cfg.hidden_size, cfg.num_experts, cfg.moe_intermediate_size
    ks = jax.random.split(key, 8)
    p = {"router": {"kernel": _dense_init(ks[0], lead + (H, cfg.router_width),
                                          dtype)},
         "w_gate": {"kernel": _dense_init(ks[1], lead + (E, H, Im), dtype)},
         "w_up": {"kernel": _dense_init(ks[2], lead + (E, H, Im), dtype)},
         "w_down": {"kernel": _dense_init(ks[3], lead + (E, Im, H), dtype)}}
    if cfg.router_scoring == "sigmoid":
        p["router"]["bias"] = jnp.zeros(lead + (cfg.router_width,),
                                        jnp.float32)
    if cfg.n_shared_experts:
        Is = Im * cfg.n_shared_experts
        p["shared"] = {
            "w_gate": {"kernel": _dense_init(ks[4], lead + (H, Is), dtype)},
            "w_up": {"kernel": _dense_init(ks[5], lead + (H, Is), dtype)},
            "w_down": {"kernel": _dense_init(ks[6], lead + (Is, H), dtype)}}
    return p


def init_hybrid_layer_params(cfg: ModelConfig, key: jax.Array, dtype) -> dict:
    """Stacked params of a model with a layer pattern: one sub-tree a KIND,
    ``gqa`` leaves ``[P, ...]`` (one attention layer a period) and ``kda``
    leaves ``[P, n_k, ...]`` — what the scan over periods slices."""
    from aws_k8s_ansible_provisioner_tpu.ops.linear_attention import CONV_TAPS

    P, nk, H = cfg.num_periods, cfg.kda_per_period, cfg.hidden_size
    kg, kk = jax.random.split(key)

    def dense(k, lead, din, dout):
        return {"kernel": _dense_init(k, lead + (din, dout), dtype)}

    def norm(lead, width=H):
        return {"weight": jnp.ones(lead + (width,), dtype)}

    ks = jax.random.split(kg, 8)
    gqa = {"input_norm": norm((P,)), "post_norm": norm((P,)),
           "wq": dense(ks[0], (P,), H, cfg.q_size),
           "wk": dense(ks[1], (P,), H, cfg.kv_size),
           "wv": dense(ks[2], (P,), H, cfg.kv_size),
           "wo": dense(ks[3], (P,), cfg.q_size, H),
           **_init_ffn_params(cfg, ks[4], dtype, (P,))}
    if cfg.attn_output_gate:
        gqa["wg"] = dense(ks[5], (P,), H, cfg.q_size)
    out = {"gqa": gqa}
    if nk:
        lead, D, Hk = (P, nk), cfg.kda_size, cfg.kda_num_heads
        r = cfg.kda_low_rank or cfg.kda_head_dim
        ks = jax.random.split(kk, 16)
        # the family's init: A = exp(A_log) uniform on [1, 16], and dt_bias
        # the inverse softplus of a step log-uniform on [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(ks[12], lead + (D,), jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        out["kda"] = {
            "input_norm": norm(lead), "post_norm": norm(lead),
            "wq": dense(ks[0], lead, H, D), "wk": dense(ks[1], lead, H, D),
            "wv": dense(ks[2], lead, H, D), "wo": dense(ks[3], lead, D, H),
            "conv": {"weight": _dense_init(
                ks[4], lead + (CONV_TAPS, 3 * D), dtype, 0.5)},
            "f_a": dense(ks[5], lead, H, r), "f_b": dense(ks[6], lead, r, D),
            "g_a": dense(ks[7], lead, H, r), "g_b": dense(ks[8], lead, r, D),
            "w_beta": dense(ks[9], lead, H, Hk),
            "A_log": jnp.log(jax.random.uniform(
                ks[10], lead + (Hk,), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "o_norm": norm(lead, cfg.kda_head_dim),
            **_init_ffn_params(cfg, ks[11], dtype, lead)}
    return out


def init_list_layer_params(cfg: ModelConfig, key: jax.Array, dtype) -> dict:
    """Stacked params of a model whose layer kinds are a LIST: one sub-tree
    a kind, ``attn`` leaves ``[n_a, ...]`` (the "g" / "s" / "w" layers in
    order; selecting and the window have no parameter of their own),
    ``lightning`` leaves ``[n_l, ...]``, ``conv`` leaves ``[n_c, ...]``
    (the gated short convolutions) and ``par`` leaves ``[n_h, ...]`` (the
    blocks with two mixers: the attention's leaves and, under ``ssm``, the
    state-space mixer's) — what a run of one kind scans over
    (:func:`_list_forward_carry`). Every FFN is the dense gated MLP, in its
    layer's sub-tree — but in a model with experts the FFN differs by LAYER
    and the two kinds are stacks of their own: ``ffn_dense`` ``[n_d, ...]``
    (the leading ``num_dense_layers``) and ``ffn_moe`` ``[L - n_d, ...]``
    (router, expert stacks, shared expert)."""
    H, I = cfg.hidden_size, cfg.intermediate_size
    ka, kl = jax.random.split(key)

    def dense(k, n, din, dout):
        return {"kernel": _dense_init(k, (n, din, dout), dtype)}

    def norm(n, width=H):
        return {"weight": jnp.ones((n, width), dtype)}

    def ffn(ks, n):
        return {"w_gate": dense(ks[0], n, H, I), "w_up": dense(ks[1], n, H, I),
                "w_down": dense(ks[2], n, I, H)}

    def own_ffn(ks, n):     # the FFN inside the layer's own sub-tree
        return {} if cfg.num_experts > 0 else ffn(ks, n)

    out = {}
    n = sum(cfg.layer_pattern.count(c) for c in "gsw")
    if n:
        ks = jax.random.split(ka, 8)
        out["attn"] = {
            "input_norm": norm(n), "post_norm": norm(n),
            "wq": dense(ks[0], n, H, cfg.q_size),
            "wk": dense(ks[1], n, H, cfg.kv_size),
            "wv": dense(ks[2], n, H, cfg.kv_size),
            "wo": dense(ks[3], n, cfg.q_size, H), **own_ffn(ks[5:8], n)}
        if cfg.attn_output_gate:
            out["attn"]["wg"] = dense(ks[4], n, H, cfg.q_size)
        if cfg.qk_norm:
            out["attn"]["q_norm"] = norm(n, cfg.head_dim)
            out["attn"]["k_norm"] = norm(n, cfg.head_dim)
        if cfg.sandwich_norm:
            out["attn"]["attn_out_norm"] = norm(n)
            out["attn"]["mlp_out_norm"] = norm(n)
    n = cfg.layer_pattern.count("l")
    if n:
        ks = jax.random.split(kl, 8)
        D, d = cfg.lightning_size, cfg.lightning_head_dim
        out["lightning"] = {
            "input_norm": norm(n), "post_norm": norm(n),
            "wq": dense(ks[0], n, H, D), "wk": dense(ks[1], n, H, D),
            "wv": dense(ks[2], n, H, D), "wg": dense(ks[3], n, H, D),
            "wo": dense(ks[4], n, D, H),
            "q_norm": norm(n, d), "k_norm": norm(n, d), "o_norm": norm(n, d),
            **own_ffn(ks[5:8], n)}
    n = cfg.layer_pattern.count("c")
    if n:
        ks = jax.random.split(jax.random.fold_in(key, 3), 8)
        out["conv"] = {
            "input_norm": norm(n), "post_norm": norm(n),
            "w_in": dense(ks[0], n, H, 3 * H),
            "conv": {"weight": _dense_init(ks[1], (n, cfg.conv_taps, H),
                                           dtype, 0.5)},
            "wo": dense(ks[2], n, H, H), **own_ffn(ks[5:8], n)}
    n = cfg.layer_pattern.count("h")
    if n:
        ks = jax.random.split(jax.random.fold_in(key, 4), 16)
        Hs, C = cfg.ssm_num_heads, cfg.ssm_conv_size
        # the family's init, as for KDA: A uniform on [1, 16], dt_bias the
        # inverse softplus of a step log-uniform on [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(ks[10], (n, Hs), jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        out["par"] = {
            "input_norm": norm(n), "post_norm": norm(n),
            "wq": dense(ks[0], n, H, cfg.q_size),
            "wk": dense(ks[1], n, H, cfg.kv_size),
            "wv": dense(ks[2], n, H, cfg.kv_size),
            "wo": dense(ks[3], n, cfg.q_size, H),
            # the state-space mixer's leaves, under a key of their own: its
            # out-projection is a ``wo`` too
            "ssm": {
                "w_in": dense(ks[4], n, H, cfg.ssm_in_size),
                "conv": {"weight": _dense_init(ks[5], (n, cfg.conv_taps, C),
                                               dtype, 0.5),
                         "bias": _dense_init(ks[6], (n, C), dtype, 0.1)},
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    ks[9], (n, Hs), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((n, Hs), jnp.float32),
                "o_norm": norm(n, cfg.ssm_size),
                "wo": dense(ks[7], n, cfg.ssm_size, H)},
            **own_ffn(ks[11:14], n)}
    if cfg.num_experts > 0:
        kd, km = jax.random.split(jax.random.fold_in(key, 2))
        nd = cfg.num_dense_layers
        if nd:
            out["ffn_dense"] = ffn(jax.random.split(kd, 3), nd)
        if cfg.num_layers > nd:
            out["ffn_moe"] = _init_ffn_params(cfg, km, dtype,
                                              (cfg.num_layers - nd,))
    return out


def init_layer_params(cfg: ModelConfig, key: jax.Array, dtype) -> dict:
    """Init stacked layer params: every leaf has leading [num_layers] axis
    (a model with a layer pattern: :func:`init_hybrid_layer_params`, or
    :func:`init_list_layer_params` where the pattern is a list)."""
    if cfg.layer_list:
        return init_list_layer_params(cfg, key, dtype)
    if cfg.layer_pattern:
        return init_hybrid_layer_params(cfg, key, dtype)
    L, H = cfg.num_layers, cfg.hidden_size
    ks = jax.random.split(key, 8)

    def dense(k, din, dout, bias):
        p = {"kernel": _dense_init(k, (L, din, dout), dtype)}
        if bias:
            p["bias"] = jnp.zeros((L, dout), dtype)
        return p

    def norm():
        p = {"weight": jnp.ones((L, H), dtype)}
        if cfg.norm == "layernorm":
            p["bias"] = jnp.zeros((L, H), dtype)
        return p

    params = {
        "input_norm": norm(),
        "wq": dense(ks[0], H, cfg.q_size, cfg.attention_bias),
        "wk": dense(ks[1], H, cfg.kv_size, cfg.attention_bias),
        "wv": dense(ks[2], H, cfg.kv_size, cfg.attention_bias),
        "wo": dense(ks[3], cfg.q_size, H, cfg.attention_bias),
    }
    if cfg.qk_norm:
        whole = cfg.qk_norm_span == "projection"
        params["q_norm"] = {"weight": jnp.ones(
            (L, cfg.q_size if whole else cfg.head_dim), dtype)}
        params["k_norm"] = {"weight": jnp.ones(
            (L, cfg.kv_size if whole else cfg.head_dim), dtype)}
    if cfg.num_experts > 0:  # MoE (Qwen3-MoE): router + stacked expert FFNs
        E, Im = cfg.num_experts, cfg.moe_intermediate_size
        params["router"] = {"kernel": _dense_init(ks[7], (L, H, E), dtype)}
        params["w_gate"] = {"kernel": _dense_init(ks[4], (L, E, H, Im), dtype)}
        params["w_up"] = {"kernel": _dense_init(ks[5], (L, E, H, Im), dtype)}
        params["w_down"] = {"kernel": _dense_init(ks[6], (L, E, Im, H), dtype)}
    elif cfg.gated_mlp:  # SwiGLU (Qwen/Llama) / GeGLU (Gemma)
        params["w_gate"] = dense(ks[4], H, cfg.intermediate_size, cfg.mlp_bias)
        params["w_up"] = dense(ks[5], H, cfg.intermediate_size, cfg.mlp_bias)
        params["w_down"] = dense(ks[6], cfg.intermediate_size, H, cfg.mlp_bias)
    else:  # plain 2-matmul MLP (Phi/OPT)
        params["w_up"] = dense(ks[5], H, cfg.intermediate_size, cfg.mlp_bias)
        params["w_down"] = dense(ks[6], cfg.intermediate_size, H, cfg.mlp_bias)
    if not cfg.parallel_block:
        params["post_norm"] = norm()
    return params


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    params = {
        "embed": {"weight": _dense_init(k_embed, (cfg.vocab_size, cfg.hidden_size),
                                        dtype)},
        "layers": init_layer_params(cfg, k_layers, dtype),
        "final_norm": {"weight": jnp.ones((cfg.hidden_size,), dtype)},
    }
    if cfg.pos_embed == "learned":
        # OPT convention: table indexed at position+2 (rows 0-1 are padding).
        params["pos_embed"] = {"weight": _dense_init(
            k_head, (cfg.max_seq_len + 2, cfg.hidden_size), dtype)}
    if cfg.norm == "layernorm":
        params["final_norm"]["bias"] = jnp.zeros((cfg.hidden_size,), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "kernel": _dense_init(k_head, (cfg.hidden_size, cfg.vocab_size), dtype)
        }
        if cfg.parallel_block:  # HF PhiForCausalLM lm_head has bias=True
            params["lm_head"]["bias"] = jnp.zeros((cfg.vocab_size,), dtype)
    return params


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------


import contextlib as _contextlib
import threading as _threading

# Multi-LoRA dispatch context (models/lora.py): the per-slot adapter-index
# vector is set at TRACE time by the serving step functions (engine.py) and
# read here — threading a new argument through every block/model signature
# for one serving feature would touch every call site; the context confines
# it to the two ends. The value is a tracer belonging to the SAME trace
# that calls _linear, which is the one pattern where trace-time ambient
# state is sound.
_LORA = _threading.local()


@_contextlib.contextmanager
def lora_context(idx):
    """Apply per-row LoRA adapter indices ([B] int32, 0 = base) to every
    _linear whose params carry lora_A/lora_B leaves, for the duration of
    the trace inside."""
    prev = getattr(_LORA, "idx", None)
    _LORA.idx = idx
    try:
        yield
    finally:
        _LORA.idx = prev


def _linear(x, p):
    if "scale" in p:
        # Weights-only int8 (models/quant.py): the upcast fuses into the
        # weight load (HBM streams half the bytes; the MXU still computes
        # bf16) and the per-out-channel f32 scale folds after the matmul —
        # exact because the scale is constant along the contraction axis.
        y = ((x @ p["kernel"].astype(x.dtype)) * p["scale"]).astype(x.dtype)
    else:
        y = x @ p["kernel"]
    if "lora_A" in p:
        idx = getattr(_LORA, "idx", None)
        if idx is not None:
            if idx.ndim == x.ndim - 1:
                # Per-TOKEN adapter indices ([B, T] against x [B, T, H]):
                # the ragged mixed layout packs every slot's decode row plus
                # the chunk rows into one [1, B+C] sequence, so rows of the
                # same "batch" belong to different adapters. Gather factors
                # per token and contract with token-local einsums.
                A = p["lora_A"][idx].astype(x.dtype)   # [B, T, din, r]
                Bm = p["lora_B"][idx].astype(x.dtype)  # [B, T, r, dout]
                delta = jnp.einsum("btr,btro->bto",
                                   jnp.einsum("bti,btir->btr", x, A), Bm)
            else:
                # per-row low-rank delta: gather each row's adapter factors
                # (index 0 is the all-zero base adapter) and fold x@A@B in —
                # O(B·T·r·(din+dout)) beside the base matmul
                A = p["lora_A"][idx].astype(x.dtype)       # [B, din, r]
                Bm = p["lora_B"][idx].astype(x.dtype)      # [B, r, dout]
                delta = jnp.einsum("b...r,bro->b...o",
                                   jnp.einsum("b...i,bir->b...r", x, A), Bm)
            y = y + delta.astype(y.dtype)
    if "bias" in p:
        y = y + p["bias"]
    return y


def _mlp(cfg: ModelConfig, h: jnp.ndarray, p: dict) -> jnp.ndarray:
    # MoE: router + grouped expert compute (ops/moe); where the FFN differs
    # by layer, a dense layer's params hold no router
    if cfg.num_experts > 0 and "router" in p:
        from aws_k8s_ansible_provisioner_tpu.ops.moe import moe_mlp

        B, T, H = h.shape
        out = moe_mlp(cfg, h.reshape(B * T, H), p).reshape(B, T, H)
        if cfg.n_shared_experts:   # a dense SwiGLU every token passes
            sp = p["shared"]
            with jax.named_scope(parts.MLP):
                out = out + _linear(
                    jax.nn.silu(_linear(h, sp["w_gate"]))
                    * _linear(h, sp["w_up"]), sp["w_down"])
        return out
    with jax.named_scope(parts.MLP):
        if cfg.gated_mlp:  # SwiGLU (Qwen/Llama) / GeGLU (Gemma)
            gate_act = jax.nn.silu if cfg.act == "silu" \
                else partial(jax.nn.gelu, approximate=True)  # "gelu_tanh"
            if cfg.mlp_multipliers:     # muP: the gate's input, the output
                m_gate, m_down = (jnp.asarray(m, h.dtype)
                                  for m in cfg.mlp_multipliers)
                return _linear(
                    gate_act(_linear(h, p["w_gate"]) * m_gate)
                    * _linear(h, p["w_up"]), p["w_down"]) * m_down
            return _linear(
                gate_act(_linear(h, p["w_gate"])) * _linear(h, p["w_up"]),
                p["w_down"])
        if cfg.act == "relu":  # OPT
            act = jax.nn.relu
        else:
            act = partial(jax.nn.gelu, approximate=True)  # HF "gelu_new"
        return _linear(act(_linear(h, p["w_up"])), p["w_down"])


def _add_ffn(cfg: ModelConfig, x: jnp.ndarray, h: jnp.ndarray,
             p: dict, out_norm: Optional[dict] = None) -> jnp.ndarray:
    """``x`` + the block's FFN of ``h``; the residual add carries the part
    of the FFN's last operation (an elementwise tail fuses behind the matmul
    it follows and names the fusion). ``out_norm``: the norm the FFN's
    output passes first, in a model with norms on both sides."""
    y = _mlp(cfg, h, p)
    if out_norm is not None:
        with jax.named_scope(parts.NORM):
            y = apply_norm(cfg, y, out_norm)
    with jax.named_scope(parts.ffn_tail(cfg)):
        return _residual(cfg, x, y)


def decoder_block(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                  cos: jnp.ndarray, sin: jnp.ndarray,
                  attend: AttendFn, cache_l: Any, ffn: Optional[dict] = None,
                  rope: Optional[bool] = None) -> Tuple[jnp.ndarray, Any]:
    """One transformer block. ``p`` is a per-layer slice (no leading L
    axis). ``ffn``: the FFN's params where they are no part of ``p`` (the
    FFN differs by layer: a slice of its own stack); ``rope``: whether THIS
    layer rotates q/k, where the kinds of one model differ (None = what
    the config says of every attention layer)."""
    B, T, _ = x.shape
    if rope is None:
        rope = cfg.attn_use_rope
    rotary_dim = int(cfg.head_dim * cfg.rotary_pct)

    with jax.named_scope(parts.NORM):
        h = apply_norm(cfg, x, p["input_norm"])
    with jax.named_scope(parts.ATTN_PROJ):
        q, k = _linear(h, p["wq"]), _linear(h, p["wk"])
        whole = cfg.qk_norm and cfg.qk_norm_span == "projection"
        if whole:  # OLMoE: RMSNorm over the whole projection, pre-split
            q = rms_norm(q, p["q_norm"]["weight"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"]["weight"], cfg.norm_eps)
        q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
        k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
        v = _linear(h, p["wv"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
        if cfg.qk_norm and not whole:  # per-head RMSNorm on q/k (Qwen3)
            q = rms_norm(q, p["q_norm"]["weight"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"]["weight"], cfg.norm_eps)
        if cfg.pos_embed == "rope" and rope:
            q = apply_rope(q, cos, sin, rotary_dim)
            k = apply_rope(k, cos, sin, rotary_dim)

    with jax.named_scope(parts.ATTN_CORE):
        ctx, new_cache_l = attend(q, k, v, cache_l)
        ctx = ctx.reshape(B, T, cfg.q_size)
    with jax.named_scope(parts.ATTN_OUT):
        if cfg.attn_output_gate:   # elementwise, from its own projection
            with jax.named_scope(parts.ATTN_PROJ):
                gate = jax.nn.sigmoid(_linear(h, p["wg"]))
            ctx = ctx * gate
        out = _linear(ctx, p["wo"])
        if cfg.sandwich_norm:
            with jax.named_scope(parts.NORM):
                out = apply_norm(cfg, out, p["attn_out_norm"])
        x = _residual(cfg, x, out)
    ffn = p if ffn is None else ffn
    if cfg.parallel_block:  # Phi: attn and MLP both read the same normed input
        return _add_ffn(cfg, x, h, ffn), new_cache_l
    with jax.named_scope(parts.NORM):
        h2 = apply_norm(cfg, x, p["post_norm"])
    return _add_ffn(cfg, x, h2, ffn,
                    p["mlp_out_norm"] if cfg.sandwich_norm else None), \
        new_cache_l


def _residual(cfg: ModelConfig, x: jnp.ndarray, y: jnp.ndarray):
    """``x + y``, the block's output first multiplied by the model's
    residual scale where it has one (muP; a plain add otherwise — a static
    fact, so the others' programs are what they were)."""
    if cfg.residual_scale == 1.0:
        return x + y
    return x + (cfg.residual_scale * y).astype(x.dtype)


def lightning_slopes(num_heads: int) -> jnp.ndarray:
    """The Lightning-Attention-2 slope table: head h forgets at
    ``exp(-s_h)`` a token, ``s_h = 2^(-8 (h + 1) / H)`` — fixed, not
    learned."""
    h = jnp.arange(1, num_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / num_heads)


def lightning_block(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                    cos: jnp.ndarray, sin: jnp.ndarray, recur,
                    rec_l: Any) -> Tuple[jnp.ndarray, Any]:
    """One Lightning linear-attention block (ops/linear_attention.py has the
    recurrence): per-head RMSNorm then RoPE on q and k, the decayed
    outer-product state, a per-head RMSNorm and a sigmoid gate on the
    output. ``recur.lightning`` runs the recurrence over the per-slot state
    ``rec_l`` names and returns the heads' outputs in float32."""
    B, T, _ = x.shape
    Hl, d = cfg.lightning_num_heads, cfg.lightning_head_dim
    with jax.named_scope(parts.NORM):
        h = apply_norm(cfg, x, p["input_norm"])
    with jax.named_scope(parts.ATTN_PROJ):
        q = _linear(h, p["wq"]).reshape(B, T, Hl, d)
        k = _linear(h, p["wk"]).reshape(B, T, Hl, d)
        v = _linear(h, p["wv"]).reshape(B, T, Hl, d)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"]["weight"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"]["weight"], cfg.norm_eps)
        q, k = apply_rope(q, cos, sin, d), apply_rope(k, cos, sin, d)
    with jax.named_scope(parts.RECUR):
        o, rec = recur.lightning(q, k, v, lightning_slopes(Hl), rec_l)
        o = rms_norm(o, p["o_norm"]["weight"], cfg.norm_eps).astype(x.dtype)
    with jax.named_scope(parts.ATTN_PROJ):
        gate = jax.nn.sigmoid(_linear(h, p["wg"]))
    with jax.named_scope(parts.ATTN_OUT):
        o = o.reshape(B, T, Hl * d) * gate
        x = _residual(cfg, x, _linear(o, p["wo"]))
    with jax.named_scope(parts.NORM):
        h2 = apply_norm(cfg, x, p["post_norm"])
    return _add_ffn(cfg, x, h2, p), rec


def conv_block(cfg: ModelConfig, p: dict, x: jnp.ndarray, recur, rec_l: Any,
               ffn: Optional[dict] = None) -> Tuple[jnp.ndarray, Any]:
    """One gated short-convolution block (LFM2): ``[B, C, X] = split3(n
    W_in)``, a depthwise causal convolution over ``B * X``, gated by ``C``,
    then the output projection. ``recur.conv`` (ops/linear_attention.py)
    runs the gates and the convolution over the per-slot tail ``rec_l``
    names and returns float32; ``ffn``: the FFN's params where they are no
    part of ``p`` (see :func:`decoder_block`)."""
    with jax.named_scope(parts.NORM):
        h = apply_norm(cfg, x, p["input_norm"])
    with jax.named_scope(parts.ATTN_PROJ):
        bcx = _linear(h, p["w_in"])
    with jax.named_scope(parts.RECUR):
        y, rec = recur.conv(p["conv"]["weight"], bcx, rec_l)
        y = y.astype(x.dtype)
    with jax.named_scope(parts.ATTN_OUT):
        x = _residual(cfg, x, _linear(y, p["wo"]))
    with jax.named_scope(parts.NORM):
        h2 = apply_norm(cfg, x, p["post_norm"])
    return _add_ffn(cfg, x, h2, p if ffn is None else ffn), rec


def ssm_in_scale(cfg: ModelConfig) -> jnp.ndarray:
    """What the state-space mixer's in-projection is multiplied by, a number
    a column [ssm_in_size] float32: ``ssm_in_multiplier`` (on the normed
    input: a scalar, so it commutes with the matmul) times the segment's
    entry of ``ssm_multipliers`` over z | x | B | C | dt."""
    GN = cfg.ssm_num_groups * cfg.ssm_state_size
    m = cfg.ssm_multipliers or (1.0,) * 5
    widths = (cfg.ssm_size, cfg.ssm_size, GN, GN, cfg.ssm_num_heads)
    return cfg.ssm_in_multiplier * jnp.concatenate(
        [jnp.full((w,), v, jnp.float32) for w, v in zip(widths, m)])


def gated_group_norm(cfg: ModelConfig, y: jnp.ndarray, z: jnp.ndarray,
                     weight: jnp.ndarray) -> jnp.ndarray:
    """The state-space mixer's output norm: the gate FIRST (``y * SiLU(z)``,
    ``norm_before_gate`` false), then an RMSNorm over the channels of each
    of the ``ssm_num_groups`` groups. y, z: [..., ssm_size] float32."""
    G = cfg.ssm_num_groups
    v = (y * jax.nn.silu(z)).reshape(y.shape[:-1] + (G, -1))
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    return v.reshape(y.shape) * weight.astype(jnp.float32)


def two_mixer_block(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                    cos: jnp.ndarray, sin: jnp.ndarray, attend: AttendFn,
                    cache_l: Any, recur, rec_l: Any
                    ) -> Tuple[jnp.ndarray, Any, Any]:
    """One block with TWO mixers on one normed input (falcon_h1): a Mamba-2
    state-space mixer (``p["ssm"]``; ``recur.ssm`` runs its convolution and
    recurrence over the per-slot state ``rec_l`` names, ops/
    linear_attention.py) and the GQA attention (``attend`` over ``cache_l``),
    each times its muP multipliers, BOTH added to the residual stream in one
    add, then the FFN. Returns (x, the attend's cache, rec)."""
    B, T, _ = x.shape
    s = p["ssm"]
    Hs, P = cfg.ssm_num_heads, cfg.ssm_head_dim
    with jax.named_scope(parts.NORM):
        h = apply_norm(cfg, x, p["input_norm"])
    with jax.named_scope(parts.ATTN_PROJ):
        u = _linear(h, s["w_in"]).astype(jnp.float32) * ssm_in_scale(cfg)
        z, xbc, dt = jnp.split(
            u, [cfg.ssm_size, cfg.ssm_size + cfg.ssm_conv_size], axis=-1)
        # the step size a head and token (>= 0); the decay is exp(dt A)
        dt = jax.nn.softplus(dt + s["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(s["A_log"].astype(jnp.float32))
        ha = h if cfg.attention_in_multiplier == 1.0 \
            else h * jnp.asarray(cfg.attention_in_multiplier, h.dtype)
        q = _linear(ha, p["wq"]).reshape(B, T, cfg.num_heads, cfg.head_dim)
        k = _linear(ha, p["wk"])
        if cfg.key_multiplier != 1.0:
            k = k * jnp.asarray(cfg.key_multiplier, k.dtype)
        k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
        v = _linear(ha, p["wv"]).reshape(B, T, cfg.num_kv_heads,
                                         cfg.head_dim)
        q = apply_rope(q, cos, sin, cfg.head_dim)
        k = apply_rope(k, cos, sin, cfg.head_dim)
    with jax.named_scope(parts.RECUR):
        y, rec = recur.ssm(cfg, s["conv"], xbc, dt, A,
                           s["D"].astype(jnp.float32), rec_l)
        y = gated_group_norm(cfg, y.reshape(B, T, Hs * P), z,
                             s["o_norm"]["weight"]).astype(x.dtype)
    with jax.named_scope(parts.ATTN_CORE):
        ctx, cache_l = attend(q, k, v, cache_l)
        ctx = ctx.reshape(B, T, cfg.q_size)
    with jax.named_scope(parts.ATTN_OUT):
        out = _linear(y, s["wo"]) * jnp.asarray(cfg.ssm_out_multiplier,
                                                x.dtype) \
            + _linear(ctx, p["wo"]) * jnp.asarray(
                cfg.attention_out_multiplier, x.dtype)
        x = x + out
    with jax.named_scope(parts.NORM):
        h2 = apply_norm(cfg, x, p["post_norm"])
    return _add_ffn(cfg, x, h2, p), cache_l, rec


def kda_block(cfg: ModelConfig, p: dict, x: jnp.ndarray, recur,
              rec_l: Any) -> Tuple[jnp.ndarray, Any]:
    """One KDA linear-attention block (ops/linear_attention.py has the
    recurrence). ``p`` is a per-layer slice; ``recur`` runs the short
    convolution and the recurrence over the per-slot state ``rec_l`` names
    and returns the heads' outputs in float32."""
    B, T, _ = x.shape
    Hk, d = cfg.kda_num_heads, cfg.kda_head_dim
    with jax.named_scope(parts.NORM):
        h = apply_norm(cfg, x, p["input_norm"])
    with jax.named_scope(parts.ATTN_PROJ):
        qkv = jnp.concatenate([_linear(h, p["wq"]), _linear(h, p["wk"]),
                               _linear(h, p["wv"])], axis=-1)
        # log-decay per channel (<= 0) and step size per head in (0, 2): the
        # 2 is what lets a head's transition have negative eigenvalues
        f = _linear(_linear(h, p["f_a"]), p["f_b"]).astype(jnp.float32)
        g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] \
            * jax.nn.softplus(
                (f + p["dt_bias"].astype(jnp.float32)).reshape(B, T, Hk, d))
        beta = 2.0 * jax.nn.sigmoid(
            _linear(h, p["w_beta"]).astype(jnp.float32))
    with jax.named_scope(parts.RECUR):
        o, rec = recur(p["conv"]["weight"], qkv, g, beta, rec_l)
        o = rms_norm(o, p["o_norm"]["weight"], cfg.norm_eps).astype(x.dtype)
    with jax.named_scope(parts.ATTN_PROJ):
        gate = jax.nn.sigmoid(_linear(_linear(h, p["g_a"]), p["g_b"]))
    with jax.named_scope(parts.ATTN_OUT):
        x = _residual(cfg, x, _linear(o.reshape(B, T, Hk * d) * gate,
                                      p["wo"]))
    with jax.named_scope(parts.NORM):
        h2 = apply_norm(cfg, x, p["post_norm"])
    return _add_ffn(cfg, x, h2, p), rec


def _embed_inputs(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                  positions: jnp.ndarray):
    """Shared forward preamble: token embedding + position tables."""
    with jax.named_scope(parts.EMBED):
        emb = params["embed"]
        if "scale" in emb:
            # int8 table (models/quant.py): dequantize the gathered rows with
            # their per-vocab-row scales; activations take the model compute
            # dtype, which the (never-quantized) norm weights carry.
            dt = params["final_norm"]["weight"].dtype
            x = (emb["weight"][tokens].astype(jnp.float32)
                 * emb["scale"][tokens][..., None]).astype(dt)
        else:
            x = emb["weight"][tokens]
        if cfg.embed_scale:
            # Gemma scales embeddings by sqrt(H); HF casts the scalar to the
            # embedding dtype BEFORE multiplying — match that for logit
            # parity.
            x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
        if cfg.scale_emb != 1.0:    # MiniCPM's muP
            x = x * jnp.asarray(cfg.scale_emb, x.dtype)
        if cfg.embedding_multiplier != 1.0:     # falcon_h1's
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        if cfg.pos_embed == "learned":
            # OPT: absolute learned positions, +2 offset; no rotary tables
            # needed (dummy cos/sin keep the scan signature uniform).
            x = x + params["pos_embed"]["weight"][positions + 2]
            cos = sin = jnp.zeros(positions.shape + (0,), jnp.float32)
        else:
            # the tables are the rotating layers': the attention layers',
            # or the Lightning layers' where the attention layers have no
            # positions
            rotary_dim = int(cfg.head_dim * cfg.rotary_pct) \
                if cfg.attn_use_rope or cfg.windowed \
                else cfg.lightning_head_dim
            cos, sin = rope_cos_sin(positions, rotary_dim, cfg.rope_theta,
                                    cfg)
    return x, cos, sin


def _final_logits(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                  head_rows=None) -> jnp.ndarray:
    """The head: final norm, muP scale, vocabulary matmul — all row-wise, so
    with ``head_rows`` ([R] int32 over ``x``'s flattened ``B * T`` rows) it
    runs over those rows alone and gives ``[R, V]``: the same numbers the
    every-row ``[B, T, V]`` holds there. The head carries no adapter
    (models/lora.py targets the attention and MLP projections), so the
    gathered rows need no per-token index."""
    with jax.named_scope(parts.HEAD):
        if head_rows is not None:
            x = x.reshape(-1, x.shape[-1])[head_rows]
        x = apply_norm(cfg, x, params["final_norm"])
        if cfg.logit_scale != 1.0:  # muP: logits over hidden / dim_model_base
            x = x * jnp.asarray(cfg.logit_scale, x.dtype)
        if cfg.tie_embeddings:
            emb = params["embed"]
            if "scale" in emb:
                # the tied-logits matmul re-reads the whole table every
                # decode step — the int8 stream is where the embed
                # quantization pays; per-vocab-row scales become
                # per-logit-column scales here
                return ((x @ emb["weight"].T.astype(x.dtype))
                        * emb["scale"]).astype(x.dtype)
            return x @ emb["weight"].T
        logits = _linear(x, params["lm_head"])
        if cfg.lm_head_multiplier != 1.0:
            logits = logits * jnp.asarray(cfg.lm_head_multiplier,
                                          logits.dtype)
        return logits


def model_forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,          # [B, T] int32
    positions: jnp.ndarray,       # [B, T] int32 (absolute positions for RoPE)
    cache: Any = None,            # pytree with leading [L] axis per leaf, or None
    attend: Optional[AttendFn] = None,
    remat: bool = False,
) -> Tuple[jnp.ndarray, Any]:
    """Run the decoder; returns (logits [B, T, V], updated cache)."""
    if attend is None and cfg.selects:
        from aws_k8s_ansible_provisioner_tpu.ops.sparse_attention import (
            make_stateless_attend_select)

        attend = make_stateless_attend_select(cfg)
    attend = attend or make_default_attend(cfg)
    if cfg.layer_pattern:
        if cache is not None:
            raise ValueError("a model with a layer pattern keeps its cache "
                             "in the scan carry: model_forward_carry")
        from aws_k8s_ansible_provisioner_tpu.ops.linear_attention import (
            recur_from_zero)

        # the stateless form: every sequence whole, from position 0
        def in_carry(fn):
            return lambda q, k, v, cl: (fn(q, k, v, None)[0], cl)

        fwd = _list_forward_carry if cfg.layer_list else _hybrid_forward_carry
        stateless = in_carry(attend)
        if cfg.windowed:
            stateless.window = in_carry(attend.window)
        logits, _ = fwd(params, cfg, tokens, positions, {}, stateless,
                        recur_from_zero, remat=remat)
        return logits, None
    x, cos, sin = _embed_inputs(params, cfg, tokens, positions)

    def body(x, layer_in):
        p_l, cache_l = layer_in
        x, new_cache_l = decoder_block(cfg, p_l, x, cos, sin, attend, cache_l)
        return x, new_cache_l

    if remat:
        body = jax.checkpoint(body)

    if cache is None:
        # scan needs a pytree of xs with a leading L axis; use a dummy per-layer
        # placeholder so `attend` implementations can ignore it.
        dummy = jnp.zeros((cfg.num_layers,), jnp.int32)
        x, _ = jax.lax.scan(body, x, (params["layers"], dummy))
        new_cache = None
    else:
        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))

    return _final_logits(params, cfg, x), new_cache


def model_forward_carry(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,          # [B, T] int32
    positions: jnp.ndarray,       # [B, T] int32
    cache: Any,                   # full stacked cache ([L, ...] leaves)
    attend: AttendFn,             # receives cache_l = (full_cache, layer_idx)
    recur=None,                   # KDA layers' callback (layer_pattern)
    head_rows=None,               # [R] int32: the rows whose logits are read
) -> Tuple[jnp.ndarray, Any]:
    """Decoder forward with the cache in the scan CARRY, not xs/ys.

    Streaming per-layer cache slices through the layer scan as xs and
    re-stacking them as ys (what ``model_forward`` does with a ``cache``)
    costs a full-cache copy a call — XLA cannot alias a scan's xs buffers to
    its ys buffers (for a batch-32 Qwen3-0.6B decode step that is ~7 GB of
    HBM traffic for a ~100 KB logical write; measured 24 ms vs ~4 ms of
    useful work on v5e; a prefill's restack buffer OOMed at batch 128). Here
    the FULL cache rides the carry — XLA's while-loop carry aliasing keeps it
    in place — and ``attend`` receives ``(cache, layer_idx)``, writes in
    place and reads the layer straight out of the full buffer
    (ops/attention.py's callbacks over the paged pool), so per-step HBM
    traffic is weights + live cache rows only. Every serving step program
    runs this form.

    ``head_rows`` names the rows the caller will read logits of, as indices
    over the flattened ``B * T`` rows: the hidden state is gathered to them
    BEFORE the head, and the logits come back ``[R, V]`` (a prefill-type
    program samples one row a prompt; the head over every packed row was a
    quarter of a small model's flops and gigabytes of temporaries). None
    keeps ``[B, T, V]`` — the programs that read every row.
    """
    if cfg.layer_list:
        return _list_forward_carry(params, cfg, tokens, positions, cache,
                                   attend, recur, head_rows=head_rows)
    if cfg.layer_pattern:
        return _hybrid_forward_carry(params, cfg, tokens, positions, cache,
                                     attend, recur, head_rows=head_rows)
    x, cos, sin = _embed_inputs(params, cfg, tokens, positions)
    from aws_k8s_ansible_provisioner_tpu.ops import moe

    def body(carry, p_l):
        x, cache, l = carry
        x, (cache, _) = decoder_block(cfg, p_l, x, cos, sin, attend, (cache, l))
        # an MoE layer traced under ops.moe.routed_rows leaves its routing
        # counts; they leave the scan stacked [L, 2] (None otherwise)
        return (x, cache, l + 1), moe.take_layer_stats()

    (x, cache, _), per_layer = jax.lax.scan(
        body, (x, cache, jnp.int32(0)), params["layers"])
    moe.put_stats(per_layer)
    return _final_logits(params, cfg, x, head_rows), cache


def _hybrid_forward_carry(params, cfg: ModelConfig, tokens, positions, cache,
                          attend: AttendFn, recur, remat: bool = False,
                          head_rows=None):
    """model_forward_carry for a model with a layer pattern: ONE scan over
    PERIODS whose body runs the period's kinds in order. ``cache`` holds
    the pool's K/V leaves with a leading axis of ATTENDING layers (one a
    period, so ``attend`` is handed ``(pool, period)``) and, beside them,
    the KDA layers' per-slot leaves ``[P, n_k, slots, ...]``
    (ops/linear_attention.py), which ``recur`` reads and writes as
    ``(state, period, j)``. Both ride the carry."""
    from aws_k8s_ansible_provisioner_tpu.ops import linear_attention as la
    from aws_k8s_ansible_provisioner_tpu.ops import moe

    x, cos, sin = _embed_inputs(params, cfg, tokens, positions)
    pool = {n: a for n, a in cache.items() if not la.is_state(n)}
    rec = {n: a for n, a in cache.items() if la.is_state(n)}

    layers = params["layers"]

    def layer(kind: str, *idx):
        """One layer's params, each leaf ONE dynamic slice of its whole
        stack at (period[, j]) — what a scan's xs slicing is. Handing the
        period's ``[n_k, ...]`` slab to the body and indexing ``[j]`` there
        made XLA copy every KDA weight of the period into a fresh buffer
        first (13 of a decode step's 32 ms on the chip, PERF.md PR 32)."""
        n = len(idx)
        return jax.tree.map(
            lambda a: jax.lax.dynamic_slice(
                a, idx + (0,) * (a.ndim - n),
                (1,) * n + a.shape[n:]).reshape(a.shape[n:]),
            layers[kind])

    def body(carry, _):
        x, pool, rec, period = carry
        stats, j = [], 0
        for kind in cfg.layer_pattern:
            if kind == "g":
                x, (pool, _) = decoder_block(cfg, layer("gqa", period), x,
                                             cos, sin, attend, (pool, period))
            else:
                x, rec = kda_block(cfg, layer("kda", period, j), x, recur,
                                   (rec, period, j))
                j += 1
            stats.append(moe.take_layer_stats())
        stats = None if stats[0] is None else jnp.stack(stats)
        return (x, pool, rec, period + 1), stats

    if remat:
        body = jax.checkpoint(body)
    (x, pool, rec, _), per_layer = jax.lax.scan(
        body, (x, pool, rec, jnp.int32(0)), None, length=cfg.num_periods)
    if per_layer is not None:       # [P, layers a period, n] -> [L, n]
        per_layer = per_layer.reshape((-1,) + per_layer.shape[2:])
    moe.put_stats(per_layer)
    return _final_logits(params, cfg, x, head_rows), {**pool, **rec}


def layer_plan(cfg: ModelConfig):
    """The list as RUNS of equal (kind, FFN): ``(kind, FFN stack or None,
    first, cache, ffn, length)`` — ``first`` the run's first layer among
    the layers of its PARAMS stack (``lightning``, ``conv``, ``par`` for
    the two-mixer blocks, which come in a list of their own: one index
    names a layer's params, its pool leaf and its state, or ``attn`` for
    the "g" / "s" / "w" kinds together), ``cache`` among the layers that
    share its CACHE leaves (the Lightning state; the conv tails; the pool's
    ``k`` / ``v`` for "g" / "s"; its ``wk`` / ``wv`` for "w"), ``ffn`` in
    its FFN stack
    (``ffn_dense`` / ``ffn_moe``; None = the FFN lies in the layer's own
    sub-tree). One scan body a run: "ww|wgwwwg" (dense | routed) has five,
    the published 32 layers ("wwwg" x 8, two dense) eighteen."""
    plan, seen = [], {}
    for i, kind in enumerate(cfg.layer_pattern):
        stack = {"l": "lightning", "c": "conv", "h": "par"}.get(kind, "attn")
        leaves = stack if kind in "lc" else "win" if kind == "w" else "pool"
        ffn = None if cfg.num_experts <= 0 else \
            "ffn_dense" if i < cfg.num_dense_layers else "ffn_moe"
        if plan and plan[-1][:2] == [kind, ffn]:
            plan[-1][5] += 1
        else:
            plan.append([kind, ffn, seen.get(stack, 0), seen.get(leaves, 0),
                         seen.get(ffn, 0), 1])
        for name in {stack, leaves, ffn}:
            seen[name] = seen.get(name, 0) + 1
    return [tuple(r) for r in plan]


def layer_periods(plan):
    """:func:`layer_plan`'s runs, FOLDED where a sequence of them repeats:
    ``[(runs, repeats, strides)]`` — ``runs`` consecutive runs of the plan,
    followed ``repeats - 1`` times by runs of the same (kind, FFN, length)
    whose layers lie ``strides`` (one ``(first, cache, ffn)`` a run) further
    in their stacks with each copy. The published LFM2 list, 13 runs, is
    three such groups — "cc" | ("g", "ccc") x 4 | ("g", "cc") x 2 — so its
    step programs hold five layer bodies, not thirteen; a list that
    repeats nothing (every list served before it) comes out run by run,
    each alone and once, and its programs are what they were. Greedy from
    the front: the period that covers the most runs, the shortest first."""
    sig = [(kind, ffn, n) for kind, ffn, _, _, _, n in plan]
    out, p = [], 0
    while p < len(plan):
        m, reps = 1, 1
        for width in range(1, (len(plan) - p) // 2 + 1):
            r = 1
            while sig[p + r * width:p + (r + 1) * width] == sig[p:p + width]:
                r += 1
            if r > 1 and r * width > m * reps:
                m, reps = width, r
        strides = tuple(
            tuple(b - a for a, b in zip(plan[p + j][2:5],
                                        plan[p + m + j][2:5]))
            if reps > 1 else (0, 0, 0) for j in range(m))
        out.append((tuple(plan[p:p + m]), reps, strides))
        p += m * reps
    return out


def layer_runs(pattern: str):
    """:func:`layer_plan` of a list whose FFNs lie in the layers' own
    sub-trees, as ``(kind, index of the run's first layer among the layers
    of its params stack, length)`` — "slllllls" is ``[("s", 0, 1), ("l", 0,
    6), ("s", 1, 1)]``."""
    from types import SimpleNamespace

    plan = layer_plan(SimpleNamespace(layer_pattern=pattern, num_experts=0))
    return [(kind, first, n) for kind, _, first, _, _, n in plan]


def _list_forward_carry(params, cfg: ModelConfig, tokens, positions, cache,
                        attend: AttendFn, recur, remat: bool = False,
                        head_rows=None):
    """model_forward_carry for a model whose layer kinds are a LIST (not a
    period): the list is walked RUN by run of one kind (and one FFN kind,
    :func:`layer_plan`), a run longer than one layer as ONE scan over its
    layers — so the step programs hold one layer body a run, not one a
    layer (the published 32-layer list has 9 runs; a pipeline stage
    "s llllll s" has 3). ``cache`` holds the pool's leaves with a leading
    axis of ATTENDING layers (``attend`` is handed ``(pool, index among
    the attending layers)``) and the Lightning layers' per-slot state
    ``[n_l, 1, slots, ...]``, which ``recur.lightning`` reads and writes as
    ``(state, index among the Lightning layers, 0)``; a gated short
    convolution ("c") hands ``recur.conv`` ``(state, index among the conv
    layers)`` for its tail rows. A window ("w") layer
    goes to ``attend.window`` with its index among the WINDOW layers: its
    K/V are leaves of their own in the same pool (ops/kv_pool.py), and it
    rotates q/k whatever the full layers do. All ride the carry. Each
    layer's params are one dynamic slice a leaf of the kind's whole stack
    (see :func:`_hybrid_forward_carry`)."""
    from aws_k8s_ansible_provisioner_tpu.ops import linear_attention as la
    from aws_k8s_ansible_provisioner_tpu.ops import moe
    from aws_k8s_ansible_provisioner_tpu.ops import sparse_attention as sa

    x, cos, sin = _embed_inputs(params, cfg, tokens, positions)
    pool = {n: a for n, a in cache.items() if not la.is_state(n)}
    rec = {n: a for n, a in cache.items() if la.is_state(n)}
    if cfg.selects and pool:
        # the selecting attends' tally of pages, in the carry (ops/
        # sparse_attention.put_counts hands it to the step program)
        pool[sa.TALLY] = jnp.zeros((2,), jnp.int32)
    layers = params["layers"]

    def layer(stack: str, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            layers[stack])

    def one(kind, carry, i, at=None, ffn=None):
        """Layer ``i`` of its params stack; ``at`` its index among the
        layers that share its cache leaves and ``ffn`` = (stack, index) of
        its FFN, where they are not ``i`` and the layer's own."""
        x, pool, rec = carry
        at = i if at is None else at
        if kind == "l":
            x, rec = lightning_block(cfg, layer("lightning", i), x, cos, sin,
                                     recur, (rec, at, 0))
        elif kind == "c":
            x, rec = conv_block(cfg, layer("conv", i), x, recur, (rec, at),
                                ffn=None if ffn is None else layer(*ffn))
        elif kind == "h":
            x, (pool, _), rec = two_mixer_block(
                cfg, layer("par", i), x, cos, sin, attend, (pool, at), recur,
                (rec, at))
        else:
            x, (pool, _) = decoder_block(
                cfg, layer("attn", i), x, cos, sin,
                attend.window if kind == "w" else attend, (pool, at),
                ffn=None if ffn is None else layer(*ffn),
                rope=True if kind == "w" else None)
        return x, pool, rec

    def run(carry, kind, stack, first, n, d_at, d_ffn):
        """``n`` layers of one kind from layer ``first`` of its params
        stack (a number, or traced inside a folded period); ``d_at`` /
        ``d_ffn``: how far the layer's cache and FFN indices lie from it.
        Returns (carry, the layers' routing counts [n, ...] or None)."""

        def step(carry, i):
            # (an offset of zero leaves the index as it is: the older
            # lists' programs are what they were)
            carry = one(kind, carry, i,
                        None if isinstance(d_at, int) and not d_at
                        else i + d_at,
                        (stack, i + d_ffn) if stack else None)
            return carry, moe.take_layer_stats()

        if n == 1:
            carry, got = step(carry, jnp.int32(first))
            return carry, None if got is None else got[None]
        return jax.lax.scan(
            jax.checkpoint(step) if remat else step, carry,
            first + jnp.arange(n, dtype=jnp.int32))

    carry = (x, pool, rec)
    stats = []      # an MoE layer's routing counts (ops/moe.routed_rows)
    for runs, repeats, strides in layer_periods(layer_plan(cfg)):
        if repeats == 1:
            (kind, stack, first, at, ffn, n), = runs
            carry, got = run(carry, kind, stack, first, n, at - first,
                             ffn - first)
        else:
            def period(carry, t, runs=runs, strides=strides):
                """Copy ``t`` of a folded group: its runs in order, every
                index ``t`` strides further in its stack."""
                got = []
                for (kind, stack, first, at, ffn, n), (s_first, s_at,
                                                       s_ffn) in zip(
                        runs, strides):
                    carry, g = run(carry, kind, stack, first + t * s_first,
                                   n, at - first + t * (s_at - s_first),
                                   ffn - first + t * (s_ffn - s_first))
                    got.append(g)
                return carry, None if got[0] is None \
                    else jnp.concatenate(got)

            carry, got = jax.lax.scan(
                jax.checkpoint(period) if remat else period, carry,
                jnp.arange(repeats, dtype=jnp.int32))
            if got is not None:     # [repeats, layers a period, ...]
                got = got.reshape((-1,) + got.shape[2:])
        if got is not None:
            stats.append(got)
    x, pool, rec = carry
    if sa.TALLY in pool:
        sa.put_counts(pool.pop(sa.TALLY))
    moe.put_stats(jnp.concatenate(stats) if stats else None)
    return _final_logits(params, cfg, x, head_rows), {**pool, **rec}
