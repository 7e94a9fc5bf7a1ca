"""The model's parts: the closed set of names a step program's operations
carry into the compiled HLO and the device trace, and what each part weighs.

A part is a place in the MODEL — never a model's name, a configuration or an
option. ``jax.named_scope(part)`` is opened at the site that does the work
(models/layers.py, ops/moe.py, ops/sparse_attention.py, ops/attention.py and
the step programs' sampling tails in serving/programs.py), so every
operation's ``op_name`` metadata reads ``jit(decode_steps)/while/body/.../
attn.proj/dot_general`` and the fusion that holds it takes the name of its
root. A scope adds no equation: the jaxprs, the kernels' names and the
compiled instructions are what they were. Scopes nest and the INNERMOST
names the work (a selecting layer's ``select`` inside its ``attn.core``).

- ``embed``: the token gather, its dequantisation, muP / Gemma scale,
  learned positions, the rotary tables;
- ``norm``: a block's input and post-attention norms;
- ``attn.proj``: wq / wk / wv, the output gate's ``wg``, q/k norm and RoPE —
  and a recurrent layer's input projections (KDA's low-rank decay, gate and
  step-size projections with their activations; a gated short
  convolution's one ``w_in``; a state-space mixer's ``w_in`` with its muP
  column scale, its step size's softplus and log-decay): the same weight
  stream
  through the same ``_linear``, dequantisation inside;
- ``attn.core``: the ``attend`` callback — the kernel calls and what
  surrounds them (the length order's gathers, the row and chunk writes, the
  XLA attention of the non-pallas paths);
- ``attn.out``: the gate's multiply, wo, and the residual add behind it (a
  block with two mixers: both out-projections and their one add);
- ``mlp``: the dense SwiGLU / GELU FFN, a shared expert, the residual add;
- ``router``: scores, bias, top-k, the sort, the group sizes and the
  routing counts a step program asks for;
- ``experts``: the grouped / every-expert matmuls and their combine;
- ``recur``: KDA, Lightning, the gated short convolution and the
  state-space mixer — the convolution (its gates, taps, bias and tail
  rows), the state update with its ``D`` skip and the (gated) output norm
  (what touches the per-slot state);
- ``select``: a selecting layer's run add, pooled-key scores, the rank
  count, the page lists and bitmasks, the page tally;
- ``head``: the sampled rows' gather, final norm, muP scale, the vocabulary
  matmul;
- ``sample``: penalties, bias, bans, the allow mask, top-k / top-p, the
  draw, logprobs, the carry's token and length.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax

PARTS = (EMBED, NORM, ATTN_PROJ, ATTN_CORE, ATTN_OUT, MLP, ROUTER, EXPERTS,
         RECUR, SELECT, HEAD, SAMPLE) = (
    "embed", "norm", "attn.proj", "attn.core", "attn.out", "mlp", "router",
    "experts", "recur", "select", "head", "sample")

# The ONE leaf-to-part table: a leaf belongs to the part of the nearest key
# on its path that is listed here ("layers/wq/kernel" -> attn.proj,
# "layers/kda/shared/w_up/scale" -> mlp). ``w_gate`` / ``w_up`` / ``w_down``
# outside a ``shared`` or ``ffn_dense`` (the dense layers of a model whose
# FFN differs by layer) sub-tree are expert stacks in a model with experts.
_LEAF_PART = {
    "embed": EMBED, "pos_embed": EMBED,
    "input_norm": NORM, "post_norm": NORM,
    "attn_out_norm": NORM, "mlp_out_norm": NORM,
    "wq": ATTN_PROJ, "wk": ATTN_PROJ, "wv": ATTN_PROJ, "wg": ATTN_PROJ,
    "w_in": ATTN_PROJ,
    "q_norm": ATTN_PROJ, "k_norm": ATTN_PROJ,
    "f_a": ATTN_PROJ, "f_b": ATTN_PROJ, "g_a": ATTN_PROJ, "g_b": ATTN_PROJ,
    "w_beta": ATTN_PROJ, "A_log": ATTN_PROJ, "dt_bias": ATTN_PROJ,
    "wo": ATTN_OUT,
    "w_gate": MLP, "w_up": MLP, "w_down": MLP, "shared": MLP,
    "router": ROUTER,
    "conv": RECUR, "o_norm": RECUR, "D": RECUR,
    "final_norm": HEAD, "lm_head": HEAD,
}
_EXPERT_STACKS = ("w_gate", "w_up", "w_down")
_DENSE_FFN = ("shared", "ffn_dense")


def ffn_tail(cfg) -> str:
    """The part of an FFN's LAST operation — where the residual add behind
    it belongs: the expert combine's for a model of routed experts alone,
    the dense (or shared) MLP's otherwise."""
    return EXPERTS if cfg.num_experts > 0 and not cfg.n_shared_experts \
        else MLP


def _per_chip(leaf) -> Tuple[int, int]:
    """(elements, bytes) ONE chip holds of a leaf: its shard under a mesh."""
    shape = leaf.shape
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        shape = sharding.shard_shape(shape)
    n = math.prod(shape)
    return n, n * leaf.dtype.itemsize


def param_weights(params, cfg) -> Dict[str, Tuple[int, int]]:
    """{part: (bytes, matmul elements)} of the tree as it is SERVED (after
    quantisation, LoRA attach and sharding; per chip): the leaves each part
    reads in ONE forward pass. Bytes count every leaf once (kernels with
    their scales and biases, norms, LoRA factors), so the parts sum to the
    tree; elements count the ``kernel`` leaves, what a matmul multiplies a
    row by. A TIED embedding is the head's matmul and counts there whole:
    ``embed`` reads only the rows it gathers, which are no parameter
    stream. A part the model lacks is absent."""
    out: Dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = [getattr(k, "key", None) for k in path]
        part = next((_LEAF_PART[k] for k in reversed(keys)
                     if k in _LEAF_PART), None)
        if part is None:
            raise ValueError(f"parameter leaf {'/'.join(map(str, keys))} "
                             f"belongs to no part: list it in _LEAF_PART")
        if part == MLP and cfg.num_experts > 0 \
                and not any(k in _DENSE_FFN for k in keys) \
                and any(k in _EXPERT_STACKS for k in keys):
            part = EXPERTS
        tied_table = keys[0] == "embed" and cfg.tie_embeddings
        if tied_table:
            part = HEAD
        n, nbytes = _per_chip(leaf)
        acc = out.setdefault(part, [0, 0])
        acc[0] += nbytes
        if keys[-1] == "kernel" or (tied_table and keys[-1] == "weight"):
            acc[1] += n
    return {p: (out[p][0], out[p][1]) for p in PARTS if p in out}
