"""Weights-only int8 quantization for the decode path.

Decode is HBM-bandwidth-bound and below batch ~64 the WEIGHT stream dominates
the bytes/token term (PERF.md roofline; VERDICT r3 next #7): int8 weights
halve that term, which is the single biggest single-chip lever left. The
scheme is the standard weights-only recipe the vLLM engine inside the
reference's serving pods exposes as ``--quantization`` (SURVEY.md §2.2 row
1), TPU-shaped:

- **Symmetric per-out-channel scales**: each output channel stores
  ``s = max|W[:, o]| / 127`` (float32) and ``q = round(W / s)`` (int8). No
  zero points — symmetric quantization keeps the matmul a plain dot.
- **Compute stays bf16 on the MXU**: XLA fuses the int8→bf16 upcast into the
  weight load, so HBM traffic halves while the systolic array sees its
  native dtype (int8×bf16 mixed matmuls would otherwise leave the MXU). The
  per-channel scale folds in AFTER the matmul as one fused multiply —
  ``(x @ q) * s  ==  x @ (q * s)`` exactly, because the scale is constant
  along the contraction axis.
- **Pytree-shaped like the bf16 params**: a quantized projection is the same
  dict with ``kernel`` turned int8 plus a sibling ``scale`` leaf, so the
  scan-over-layers body, shard_map specs, and checkpoint plumbing all keep
  working; ``parallel/sharding.param_pspecs(quant_weights=True)`` emits the
  matching scale specs (out-channel axes shard with their kernel's tp axis).

What gets quantized: the seven per-layer projections (wq/wk/wv/wo and the
MLP kernels), the embedding table (per-VOCAB-ROW scales — the tied-logits
matmul re-reads the whole table every decode step, ~25% of Qwen3-0.6B's
weight bytes), an untied lm_head, and MoE EXPERT kernels (per-(expert,
out-channel) scales — experts are ~95% of Qwen3-30B-A3B's bytes; both the
ragged grouped matmuls and the gshard dispatch einsums contract over the
hidden axis only, so the scale folds after them exactly, per expert row /
expert slice — ops/moe.py). Norms, biases, q/k norms, the MoE router, and
learned position tables stay in the model dtype (tiny, and
precision-critical).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from aws_k8s_ansible_provisioner_tpu.config import ModelConfig

# The per-layer kernels that go to int8. Whatever leads it ([L] of a dense
# stack, [L, E] of an expert stack, [P] / [P, n_k] of a kind's sub-tree in a
# model with a layer pattern), a kernel is [..., in, out]: the contraction
# axis is its second-to-last. ``wg`` is the attention gate, ``shared`` the
# shared expert's sub-tree, ``w_in`` / ``wo`` a gated short convolution's
# two projections (its taps stay in the model dtype), ``ssm`` a state-space
# mixer's sub-tree (its in- and out-projection, ``w_in`` / ``wo``, go to
# int8; taps and their bias, A_log, dt_bias, D and the gated norm stay in the
# model dtype). A KDA layer's low-rank decay/gate projections,
# step-size projection, convolution taps, A_log and dt_bias stay in the
# model dtype (a few MB a layer, and the decay is precision-critical), like
# the router and its bias.
_QUANT_KEYS = ("wq", "wk", "wv", "wo", "wg", "w_in", "w_gate", "w_up",
               "w_down")


def _quant_kernel(w: jnp.ndarray, in_axis: int):
    """Symmetric per-out-channel int8: returns (q int8, scale f32 with the
    ``in_axis`` reduced away). The scale floor avoids divide-by-zero on
    all-zero channels (init edge case)."""
    w32 = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w32), axis=in_axis) / 127.0
    s = jnp.maximum(s, 1e-12)
    q = jnp.clip(jnp.round(w32 / jnp.expand_dims(s, in_axis)), -127, 127)
    return q.astype(jnp.int8), s


def weights_quantized(params: dict) -> bool:
    """Whether ``params`` carries int8 weight leaves (scale siblings)."""
    try:
        layers = params["layers"]
        if "wq" not in layers:      # a layer pattern: one sub-tree a kind
            layers = next(iter(layers.values()))
        return "scale" in layers["wq"]
    except (KeyError, TypeError):
        return False


def _quantize_layers(layers: dict, kern) -> dict:
    """One stacked layer tree (a dense or MoE model's, or one kind's)."""
    out = dict(layers)
    for key in _QUANT_KEYS:
        if key in out:
            p = dict(out[key])
            p["kernel"], p["scale"] = kern(p["kernel"],
                                           in_axis=p["kernel"].ndim - 2)
            out[key] = p
    for sub in ("shared", "ssm"):
        if sub in out:
            out[sub] = _quantize_layers(out[sub], kern)
    return out


def _quant_kernel_host(w, in_axis: int):
    """numpy twin of _quant_kernel: runs leaf-by-leaf on the HOST so no
    device ever materializes the full unquantized tree."""
    w32 = np.asarray(w).astype(np.float32)
    s = np.max(np.abs(w32), axis=in_axis) / 127.0
    s = np.maximum(s, 1e-12)
    q = np.clip(np.round(w32 / np.expand_dims(s, in_axis)), -127, 127)
    return q.astype(np.int8), s.astype(np.float32)


def quantize_params(params: dict, cfg: ModelConfig,
                    host: bool = False) -> dict:
    """Quantize a bf16/f32 param pytree to weights-only int8 (see module
    docstring for exactly which leaves). Pure function — returns a new tree.

    ``host=False``: one jit-compiled fused program — right when the params
    already live (whole) on a single device (single-chip serving, bench).
    ``host=True``: leaf-by-leaf numpy on the host — REQUIRED before mesh
    sharding of a large checkpoint: the jitted path would device_put the
    full unquantized tree onto one chip first, exactly the single-device
    HBM peak the sharded loader exists to avoid (an 8B bf16 tree does not
    fit one v5e chip). Engine picks host=True whenever it has a mesh.
    """
    kern = _quant_kernel_host if host else _quant_kernel

    def _go(params):
        out = jax.tree.map(lambda x: x, params)   # shallow-ish copy
        if cfg.layer_pattern:    # one sub-tree a kind
            layers = {kind: _quantize_layers(sub, kern)
                      for kind, sub in out["layers"].items()}
        else:
            layers = _quantize_layers(out["layers"], kern)
        out["layers"] = layers
        emb = dict(out["embed"])
        # [V, H]: per-vocab-row scales — the gather dequantizes one row per
        # token; the tied-logits matmul folds them per output logit.
        q, s = kern(emb["weight"], in_axis=1)
        emb["weight"], emb["scale"] = q, s
        out["embed"] = emb
        if "lm_head" in out:
            p = dict(out["lm_head"])
            q, s = kern(p["kernel"], in_axis=0)   # [H, V] → [V]
            p["kernel"], p["scale"] = q, s
            out["lm_head"] = p
        return out

    return _go(params) if host else jax.jit(_go)(params)
