"""Mixture-of-Experts MLP: router + grouped expert compute, TPU-first.

The reference's serving stack gets MoE support from the vLLM engine inside its
pods (SURVEY.md §2.2 row 1 — the engine is external; fused-MoE CUDA kernels);
here it is in-repo for the Qwen3-MoE family (config.QWEN3_30B_A3B). Two
implementations behind one interface, selected by ``ModelConfig.moe_impl``:

- **ragged** (default; exact): every routed (token, expert) row is
  computed, none dropped, none approximated — the single-device/serving
  path (GSPMD cannot usefully partition data-dependent group boundaries).
  Two forms of the same sum, picked by the static shape
  (``EVERY_EXPERT_MAX_ROW_EXPERTS``): rows sorted by expert id through
  ``jax.lax.ragged_dot`` grouped matmuls (the MegaBlocks/MaxText
  formulation; O(N*k) flops) for many rows, and every expert over every row
  with the unchosen products meeting an exact zero (E/k times the flops, no
  sort, the weights streamed at HBM speed) for a decode batch.
- **gshard** (distributed): fixed-capacity one-hot dispatch/combine einsums —
  the GShard formulation. Every shape is static and every op is a plain
  einsum, so GSPMD partitions the expert axis over the mesh's ``ep`` axis and
  inserts the all-to-all-style collectives itself (the same
  compiler-emits-the-comms design as the rest of parallel/sharding.py).
  Tokens beyond an expert's capacity contribute nothing (their MLP output is
  zero and the residual stream carries them) — standard GShard semantics,
  tunable via ``moe_capacity_factor``.

Router math matches HF ``Qwen3MoeSparseMoeBlock``: softmax over ALL experts in
float32, top-k, optional renormalization over the k weights, weights applied
to expert outputs in the activation dtype.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp

from aws_k8s_ansible_provisioner_tpu.config import ModelConfig
from aws_k8s_ansible_provisioner_tpu.models import parts


def route(cfg: ModelConfig, x: jnp.ndarray, router_kernel: jnp.ndarray,
          router_bias=None):
    """Top-k routing. x: [N, H]; router_kernel: [H, E] with E the ROUTER's
    width (all experts of the layer, held here or not).

    Returns (weights [N, k] in x.dtype, expert_idx [N, k] int32).
    ``router_scoring`` "sigmoid": a score per expert, the top-k chosen by
    score + ``router_bias`` ([E], a selection bias: it changes who is chosen
    and never a weight), the weights the chosen SCORES, renormalised over
    all k chosen (``norm_topk_prob``; their sum + ``route_norm_eps``, a
    constant of the model), then times ``route_scale``.
    """
    logits = x.astype(jnp.float32) @ router_kernel.astype(jnp.float32)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + router_bias.astype(jnp.float32),
                               cfg.num_experts_per_tok)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if cfg.norm_topk_prob:
            w = w / (w.sum(axis=-1, keepdims=True) + cfg.route_norm_eps)
        if cfg.route_scale != 1.0:
            w = w * cfg.route_scale
        return w.astype(x.dtype), idx.astype(jnp.int32)
    probs = jax.nn.softmax(logits, axis=-1)                    # [N, E]
    w, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)     # [N, k]
    if cfg.norm_topk_prob:
        w = w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-9)
    return w.astype(x.dtype), idx.astype(jnp.int32)


def _route(cfg: ModelConfig, x: jnp.ndarray, p: dict):
    r = p["router"]
    if cfg.router_scoring == "sigmoid":
        return route(cfg, x, r["kernel"], r["bias"])
    return route(cfg, x, r["kernel"])


def _expert_ffn_ragged(x: jnp.ndarray, p: dict, group_sizes: jnp.ndarray,
                       expert_of_row=None):
    """SwiGLU over sorted token groups: x [M, H] grouped by expert;
    kernels [E, H, I] / [E, I, H]. int8 expert kernels (models/quant.py:
    sibling ``scale`` [E, out]) go to the grouped matmul AS int8: the TPU's
    ragged-dot kernel takes a bf16 x int8 pair and widens each weight tile
    on chip, where an ``astype`` in front of it is materialised as a bf16
    copy of the whole stack in HBM (PERF.md, PR 26: three 268-MB copies a
    layer, five times the bytes the step needs). int8 -> bf16 is exact, so
    the product is the same to the last bit; the per-(expert, out-channel)
    scale folds after it — ``expert_of_row`` [M] maps each sorted row to its
    expert's scale row."""

    def mm(v, q):
        if "scale" in q:
            out = jax.lax.ragged_dot(v, q["kernel"], group_sizes,
                                     preferred_element_type=v.dtype)
            return (out * q["scale"][expert_of_row]).astype(v.dtype)
        return jax.lax.ragged_dot(v, q["kernel"], group_sizes)

    g = mm(x, p["w_gate"])
    u = mm(x, p["w_up"])
    return mm(jax.nn.silu(g) * u, p["w_down"])


def _assignments(cfg: ModelConfig, idx: jnp.ndarray, live):
    """(flat expert id per (token, choice) [N*k] — E for a dead row's —,
    live rows per expert [E]). With an expert SHARE (the router scores more
    experts than the E held here) the ids are mapped to the held range and
    a chosen expert held elsewhere is a dead row too: it joins no group."""
    E = cfg.num_experts
    flat_e = idx.reshape(-1)
    if cfg.expert_share:
        flat_e = flat_e - cfg.expert_offset
        flat_e = jnp.where((flat_e >= 0) & (flat_e < E), flat_e, E)
    if live is not None:
        flat_e = jnp.where(jnp.repeat(live, idx.shape[1]), flat_e, E)
    return flat_e, jnp.zeros((E + 1,), jnp.int32).at[flat_e].add(1)[:E]


def moe_mlp_ragged(cfg: ModelConfig, x: jnp.ndarray, p: dict) -> jnp.ndarray:
    """Exact no-drop MoE MLP. x: [N, H] flattened tokens → [N, H]."""
    return _sorted_groups(cfg, x, p)[0]


def _sorted_groups(cfg: ModelConfig, x: jnp.ndarray, p: dict, live=None):
    """x: [N, H] flattened tokens → ([N, H], group_sizes [E]).

    Sort the N*k (token, expert) assignments by expert id, run three grouped
    matmuls over the contiguous groups (``ragged_dot`` keeps the MXU fed
    without materializing per-expert gathers of static worst-case size), then
    bring each token's k weighted outputs home. O(N*k) FLOPs through the
    experts — the sparse compute MoE promises, with zero dropped tokens.

    ``live`` [N] bool marks the rows that carry a token (a step program's
    padding rows and idle slots do not): a dead row is sorted behind every
    group, belongs to none — the grouped matmuls do not visit it — and its
    FFN output is zero. ``group_sizes`` counts live rows only.
    """
    N, H = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    with jax.named_scope(parts.ROUTER):
        w, idx = _route(cfg, x, p)
        flat_e, group_sizes = _assignments(cfg, idx, live)     # [N*k], [E]
        order = jnp.argsort(flat_e)                            # stable
        tok = order // k                                       # source token
    with jax.named_scope(parts.EXPERTS):
        xs = x[tok]                                            # [N*k, H]
        sorted_e = flat_e[order]
        ys = _expert_ffn_ragged(xs, p, group_sizes,
                                expert_of_row=jnp.minimum(sorted_e, E - 1))
        ys = ys * w.reshape(-1)[order][:, None]
        if live is not None or cfg.expert_share:
            # rows of no group hold whatever the kernel left
            ys = jnp.where((sorted_e < E)[:, None], ys, 0)
        # every token owns exactly k sorted rows: bring them home with the
        # inverse permutation and sum over k (a gather; the scatter-add this
        # replaces took 13 of 96 ms at 2,072 rows on the chip, PERF.md PR 26)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * k, dtype=order.dtype))
        return ys.astype(x.dtype)[inv].reshape(N, k, H).sum(axis=1), \
            group_sizes


def gshard_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    """Per-expert token capacity (static): cf * ceil(N*k/E), floor 4, rounded
    up to a multiple of 4 so the dispatched [E, C, H] block tiles cleanly."""
    mean = -(-n_tokens * cfg.num_experts_per_tok // cfg.num_experts)
    cap = max(4, int(mean * cfg.moe_capacity_factor))
    return -(-cap // 4) * 4


def moe_mlp_gshard(cfg: ModelConfig, x: jnp.ndarray, p: dict) -> jnp.ndarray:
    """Fixed-capacity dispatch MoE MLP. x: [N, H] → [N, H].

    dispatch/combine are [N, E, C] one-hot/weight tensors; every contraction
    is a static einsum, so with expert kernels sharded P(None, "ep", ...) and
    activations batch-sharded, GSPMD partitions expert compute over ``ep``
    and emits the token exchange over ICI — no hand-written all_to_all.
    """
    N, H = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    C = gshard_capacity(cfg, N)
    with jax.named_scope(parts.ROUTER):
        w, idx = _route(cfg, x, p)
        # Queue position of each (token, choice) within its expert, in flat
        # (token-major) arrival order; positions >= C overflow and drop.
        onehot_e = jax.nn.one_hot(idx.reshape(-1), E,
                                  dtype=jnp.int32)              # [N*k, E]
        pos = (jnp.cumsum(onehot_e, axis=0) - onehot_e)         # [N*k, E]
        pos = (pos * onehot_e).sum(-1).reshape(N, k)            # [N, k]
        keep = (pos < C).astype(x.dtype)
        onehot_c = jax.nn.one_hot(pos, C, dtype=x.dtype)        # [N, k, C]
        oe = onehot_e.reshape(N, k, E).astype(x.dtype)
        combine = jnp.einsum("nk,nke,nkc->nec", w * keep, oe, onehot_c)
        dispatch = jnp.einsum("nk,nke,nkc->nec", keep, oe, onehot_c)

    def mm(spec, v, q):
        # int8 expert kernels: upcast fuses into the einsum load; the
        # [E, out] scale broadcasts over the capacity axis afterwards
        if "scale" in q:
            out = jnp.einsum(spec, v, q["kernel"].astype(v.dtype))
            return (out * q["scale"][:, None, :]).astype(v.dtype)
        return jnp.einsum(spec, v, q["kernel"])

    with jax.named_scope(parts.EXPERTS):
        xe = jnp.einsum("nec,nh->ech", dispatch, x)             # [E, C, H]
        g = mm("ech,ehi->eci", xe, p["w_gate"])
        u = mm("ech,ehi->eci", xe, p["w_up"])
        y = mm("eci,eih->ech", jax.nn.silu(g) * u, p["w_down"])  # [E, C, H]
        return jnp.einsum("nec,ech->nh", combine, y).astype(x.dtype)


def _every_expert(cfg: ModelConfig, x: jnp.ndarray, p: dict, live=None):
    """The same sum with no sort, for a small batch: every expert's SwiGLU
    over ALL N rows, then each row keeps its k chosen experts' outputs,
    weighted — the unchosen ones meet an exact zero, so every routed row is
    computed and none is dropped. E/k times the flops of the sorted form,
    which at a decode batch is nothing next to the weight stream: three
    batched matmuls that XLA fuses the int8 widening into and feeds to the
    MXU with the WEIGHTS as the streamed operand (the few rows stay
    latched), so the stacks pass at HBM speed — 8.8 ms for 16 layers of
    OLMoE at 24 rows on a v5e, where the sorted form takes 34 and a kernel
    that latches weight tiles 28 (PERF.md, PR 26).
    x: [N, H] → ([N, H], group_sizes [E])."""
    E, N = cfg.num_experts, x.shape[0]
    with jax.named_scope(parts.ROUTER):
        w, idx = _route(cfg, x, p)
        flat_e, group_sizes = _assignments(cfg, idx, live)
        # [N, k, E]: a dead row's id is E, which one_hot maps to all zeros
        hot = jax.nn.one_hot(flat_e.reshape(idx.shape), E, dtype=x.dtype)
        combine = jnp.einsum("nk,nke->ne", w, hot)             # [N, E]
    pad = EVERY_EXPERT_TILE_PAD if N % 128 == 0 else 0
    if pad:
        # rows of zeros that no expert's output is kept of: they keep the
        # stacks in the layout they lie in (``EVERY_EXPERT_TILE_PAD``)
        x = jnp.pad(x, ((0, pad), (0, 0)))
        combine = jnp.pad(combine, ((0, pad), (0, 0)))

    def mm(spec, v, q):
        if "scale" in q:
            out = jnp.einsum(spec, v, q["kernel"].astype(v.dtype))
            return (out * q["scale"][:, None, :]).astype(v.dtype)
        return jnp.einsum(spec, v, q["kernel"])

    with jax.named_scope(parts.EXPERTS):
        g = mm("nh,ehi->eni", x, p["w_gate"])
        u = mm("nh,ehi->eni", x, p["w_up"])
        y = mm("eni,eih->enh", jax.nn.silu(g) * u, p["w_down"])  # [E, N, H]
        out = jnp.einsum("ne,enh->nh", combine, y).astype(x.dtype)
        return (out[:N] if pad else out), group_sizes


# -- what a step program tells the expert layer, and what it hears back ------
#
# Trace-time ambient state, as models/layers.lora_context: the step program
# opens ``routed_rows(live)`` around its forward pass; every moe_mlp traced
# inside reads ``live`` and leaves its layer's [experts hit, largest group]
# for the layer scan's body to take (``take_layer_stats``, same trace), and
# the scan hands the stacked [L, 2] back (``put_stats``) for the step program
# to read from the context it opened. Threading both through every block and
# model signature would touch every call site for one serving record.
_ROUTING = threading.local()


@contextlib.contextmanager
def routed_rows(live):
    """``live`` [N] bool: the packed rows that carry a token. Yields a dict
    whose ``stats`` is, after a model_forward_carry inside, int32 [L, 2]:
    per layer the experts with at least one live row and the rows of the
    largest group ([L, 3] for an expert share: then also the (token,
    expert) pairs that landed on a held expert). With ``live`` None (a
    dense model's step program has no
    such operand) nothing is installed and ``stats`` stays None."""
    if live is None:
        yield {"stats": None}
        return
    prev = getattr(_ROUTING, "ctx", None)
    ctx = _ROUTING.ctx = {"live": live, "layer": None, "stats": None}
    try:
        yield ctx
    finally:
        _ROUTING.ctx = prev


def take_layer_stats():
    """[experts hit, largest group] the last moe_mlp of this trace left, or
    None outside ``routed_rows``."""
    ctx = getattr(_ROUTING, "ctx", None)
    if ctx is None:
        return None
    out, ctx["layer"] = ctx["layer"], None
    return out


def put_stats(per_layer) -> None:
    ctx = getattr(_ROUTING, "ctx", None)
    if ctx is not None:
        ctx["stats"] = per_layer


# While rows x experts stays under this, every expert computes every row
# (_every_expert: E/k times the flops, no sort, the weights streamed once);
# above it the rows are sorted into groups (_sorted_groups). Measured on a
# v5e at OLMoE's widths, 16 layers, 64 experts (PERF.md, PR 26): every-expert
# takes 8.8 ms at 24 rows, 44.5 at 512, 64.9 at 768, 86.9 at 1,024; the
# sorted form never takes under 60 ms (XLA's ragged-dot walks 64 groups
# whatever they hold) and reads 75.2 at 768 and 81.8 at 1,024. Shapes are
# static, so a program holds one form.
EVERY_EXPERT_MAX_ROW_EXPERTS = 768 * 64

# Zero rows the every-expert form appends to a batch that is a whole number
# of 128-row MXU tiles. At such a count XLA's TPU layout assignment may want
# the gate / up stacks contraction-minor and hoist a transposed copy of both
# WHOLE stacks out of the layer loop (deviceless compiles, PR 42: 5.2 GB of
# temporaries in every LFM2-8B-A1B step program of 128, 256, 384, 512 or 640
# rows, 4.3 GB in OLMoE's decode program at 128 slots — a program that no
# longer fits the chip, and a copy of gigabytes a dispatch if it did); at 120,
# 136 or 192 rows the stacks stream as they lie. A shape rule, like the bound
# above: 8 rows are one sublane tile and 6 % of the smallest batch it meets.
EVERY_EXPERT_TILE_PAD = 8


def moe_mlp(cfg: ModelConfig, x: jnp.ndarray, p: dict) -> jnp.ndarray:
    """Dispatch on cfg.moe_impl and, for "ragged", on the static shape
    between its two exact forms. x: [N, H] flattened tokens."""
    ctx = getattr(_ROUTING, "ctx", None)
    live = ctx["live"] if ctx is not None else None
    if cfg.moe_impl == "gshard":
        return moe_mlp_gshard(cfg, x, p)
    if cfg.moe_impl != "ragged":
        raise ValueError(f"moe_impl={cfg.moe_impl!r}: expected 'ragged' or "
                         f"'gshard'")
    if x.shape[0] * cfg.num_experts <= EVERY_EXPERT_MAX_ROW_EXPERTS:
        out, group_sizes = _every_expert(cfg, x, p, live)
    else:
        out, group_sizes = _sorted_groups(cfg, x, p, live)
    if ctx is not None:
        with jax.named_scope(parts.ROUTER):
            stats = [(group_sizes > 0).sum(), group_sizes.max()]
            if cfg.expert_share:
                # (token, expert) pairs of live rows that landed on an
                # expert held here (the host knows how many those rows chose)
                stats.append(group_sizes.sum())
            ctx["layer"] = jnp.stack(stats).astype(jnp.int32)
    return out
