"""Sharding rules: how every parameter, activation, and cache leaf is laid out.

Megatron-style tensor parallelism expressed as ``PartitionSpec``s over the
(dp, sp, tp) mesh (parallel/mesh.py). XLA's GSPMD propagates these through the
whole program and inserts the ICI collectives — this module is the *entire*
distributed "backend" (SURVEY.md §2.3: the reference has none; §5: "no
NCCL/MPI/Gloo/UCX"; the TPU equivalent is compiler-emitted collectives).

Layout summary (weights are ``[in, out]``, layers stacked on a leading L axis):

- attention q/k/v projections: column-parallel — heads sharded over ``tp``;
  output projection ``wo``: row-parallel (partial sums psum'd by XLA).
- MLP up/gate: column-parallel on the intermediate dim; down: row-parallel.
- embedding table: vocab-sharded over ``tp`` (tied logits come out
  vocab-sharded, exactly what the loss wants); untied ``lm_head``: vocab-
  sharded on the output dim.
- norms and q/k norms (per head, or OLMoE's whole-projection form): replicated
  (tiny).
- token/position arrays: batch over ``dp``, sequence over ``sp``.
- decode KV cache ``[L, slots, Hkv, S, D]``: kv heads over ``tp``, slots over
  ``dp`` (each data-parallel group owns its slots).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from aws_k8s_ansible_provisioner_tpu.config import ModelConfig


def check_tp_divisibility(cfg: ModelConfig, tp: int, ep: int = 1) -> None:
    """TP must evenly split query heads, kv heads (the pool's rows of them:
    ``cfg.pool_kv_heads``, narrow heads lie several a row), and the MLP
    intermediate (the MoE expert intermediate when sparse); ep must split
    the experts."""
    dims = [("num_heads", cfg.num_heads),
            ("num_kv_heads", cfg.num_kv_heads),
            ("pool_kv_heads", cfg.pool_kv_heads),
            ("vocab_size", cfg.vocab_size)]
    if cfg.num_experts > 0:
        dims.append(("moe_intermediate_size", cfg.moe_intermediate_size))
    else:
        dims.append(("intermediate_size", cfg.intermediate_size))
    for name, dim in dims:
        if dim % tp != 0:
            raise ValueError(f"tp={tp} does not divide {name}={dim} "
                             f"for model {cfg.name}")
    if ep > 1 and cfg.num_experts % ep != 0:
        raise ValueError(f"ep={ep} does not divide num_experts="
                         f"{cfg.num_experts} for model {cfg.name}")


def _layer_pspecs(cfg: ModelConfig, quant_weights: bool = False) -> dict:
    """PartitionSpecs mirroring models/layers.init_layer_params structure.

    ``quant_weights`` adds the int8 scheme's per-out-channel ``scale`` leaves
    (models/quant.py): a scale shards exactly like its kernel's OUT axis —
    column-parallel kernels carry tp-sharded scales, row-parallel kernels
    replicated ones (their out axis is replicated)."""

    def col(bias: bool) -> dict:  # [L, in, out] — shard out
        p = {"kernel": P(None, None, "tp")}
        if bias:
            p["bias"] = P(None, "tp")
        if quant_weights:
            p["scale"] = P(None, "tp")      # [L, out]
        return p

    def row(bias: bool) -> dict:  # [L, in, out] — shard in, replicate out
        p = {"kernel": P(None, "tp", None)}
        if bias:
            p["bias"] = P(None, None)
        if quant_weights:
            p["scale"] = P(None, None)      # [L, out] (out replicated)
        return p

    def norm() -> dict:
        p = {"weight": P(None, None)}
        if cfg.norm == "layernorm":
            p["bias"] = P(None, None)
        return p

    specs = {
        "input_norm": norm(),
        "wq": col(cfg.attention_bias),
        "wk": col(cfg.attention_bias),
        "wv": col(cfg.attention_bias),
        "wo": row(cfg.attention_bias),
    }
    if cfg.qk_norm:
        specs["q_norm"] = {"weight": P(None, None)}
        specs["k_norm"] = {"weight": P(None, None)}
    if cfg.num_experts > 0:
        # MoE: experts sharded over ep, each expert Megatron-split over tp
        # (gate/up column-parallel on the expert intermediate, down row-
        # parallel); the tiny router replicates. GSPMD derives the gshard
        # dispatch collectives from these specs (ops/moe.py). Quantized
        # expert scales [L, E, out] shard with their kernel's expert + out
        # axes (gate/up out = tp-sharded intermediate; down out = replicated
        # hidden).
        specs["router"] = {"kernel": P(None, None, None)}
        specs["w_gate"] = {"kernel": P(None, "ep", None, "tp")}
        specs["w_up"] = {"kernel": P(None, "ep", None, "tp")}
        specs["w_down"] = {"kernel": P(None, "ep", "tp", None)}
        if quant_weights:
            specs["w_gate"]["scale"] = P(None, "ep", "tp")
            specs["w_up"]["scale"] = P(None, "ep", "tp")
            specs["w_down"]["scale"] = P(None, "ep", None)
    else:
        if cfg.gated_mlp:
            specs["w_gate"] = col(cfg.mlp_bias)
        specs["w_up"] = col(cfg.mlp_bias)
        specs["w_down"] = row(cfg.mlp_bias)
    if not cfg.parallel_block:
        specs["post_norm"] = norm()
    return specs


def param_pspecs(cfg: ModelConfig, quant_weights: bool = False) -> dict:
    """Full-parameter PartitionSpec pytree (same structure as init_params;
    with ``quant_weights`` the structure of models/quant.quantize_params,
    including MoE expert scales)."""
    if cfg.layer_pattern:
        raise ValueError(
            "no rule says how a model with a layer pattern shards (per-kind "
            "stacks, per-slot state leaves): it is served on one chip, "
            "every leaf whole")
    specs: dict = {
        "embed": {"weight": P("tp", None)},  # vocab-sharded
        "layers": _layer_pspecs(cfg, quant_weights=quant_weights),
        "final_norm": {"weight": P(None)},
    }
    if quant_weights:
        specs["embed"]["scale"] = P("tp")    # [V] per-vocab-row
    if cfg.pos_embed == "learned":
        # OPT position table: tiny, replicate.
        specs["pos_embed"] = {"weight": P(None, None)}
    if cfg.norm == "layernorm":
        specs["final_norm"]["bias"] = P(None)
    if not cfg.tie_embeddings:
        specs["lm_head"] = {"kernel": P(None, "tp")}
        if cfg.parallel_block:
            specs["lm_head"]["bias"] = P("tp")
        if quant_weights:
            specs["lm_head"]["scale"] = P("tp")   # [V]
    return specs


def pool_pspecs(quant: bool = False) -> dict:
    """Paged KV pool [L, pages, Hkv, page, D]: PAGES over dp, kv heads over
    tp. Page identity is head-independent, so block tables, lengths, and the
    host allocators are tp-shard-invariant — each tp chip holds its heads'
    slice of every page. The dp axis partitions the page POOL itself: slots
    are dp-sharded, each dp group owns one page-axis partition with its own
    host allocator, and a slot's table only ever references its group's
    partition (Engine writes GLOBAL ids = local + group * partition; the
    shard_map kernels subtract their partition base). On dp=1 meshes the dp
    axis has size 1 and this degenerates to the tp-only layout. No ``sp``
    axis: a page is a contiguous row run, and splitting it across sequence
    shards would defeat paging (the engine refuses an sp > 1 mesh)."""
    specs = {
        "k": P(None, "dp", "tp", None, None),
        "v": P(None, "dp", "tp", None, None),
    }
    if quant:
        specs["ks"] = P(None, "dp", "tp", None)
        specs["vs"] = P(None, "dp", "tp", None)
    return specs


def tokens_pspec(seq_sharded: bool = False) -> P:
    """[B, T] activations: batch over dp, optionally sequence over sp."""
    return P("dp", "sp" if seq_sharded else None)


def param_shardings(mesh: Mesh, cfg: ModelConfig,
                    quant_weights: bool = False) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_pspecs(cfg, quant_weights=quant_weights),
                        is_leaf=lambda x: isinstance(x, P))


def shard_params(params: Any, mesh: Mesh, cfg: ModelConfig) -> Any:
    """Place an (unsharded or host) param pytree onto the mesh per the rules.
    Detects int8-quantized trees (models/quant.py) and picks the matching
    spec structure."""
    from aws_k8s_ansible_provisioner_tpu.models.quant import weights_quantized

    shardings = param_shardings(mesh, cfg,
                                quant_weights=weights_quantized(params))
    return jax.tree.map(jax.device_put, params, shardings)


def make_sharded_device_put(mesh: Mesh, cfg: ModelConfig):
    """Per-leaf placement callback for ``hf_loader.load_checkpoint``.

    Maps each pytree path to its PartitionSpec and device_puts the leaf with
    that NamedSharding as it is converted: the host→device transfer per device
    is the SHARD, and no device ever holds a full-model buffer — the property
    that lets an 8B checkpoint load onto a v5e-8 slice whose chips each hold
    1/8 of the weights (SURVEY.md §7 hard part #3).
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(
        param_pspecs(cfg), is_leaf=lambda x: isinstance(x, P))
    specs = {jax.tree_util.keystr(path): s for path, s in flat}

    def put(path: str, arr):
        spec = specs.get(path)
        if spec is None:  # unexpected leaf: replicate (never silently drop)
            spec = P()
        return jax.device_put(arr, NamedSharding(mesh, spec))

    return put
