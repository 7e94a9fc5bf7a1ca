"""Single-source configuration for the whole framework.

The reference repo couples its layers by *duplicated literals* — e.g.
``kubernetes_version: "1.33"`` appears at ``kubernetes-single-node.yaml:7``, ``:226``
and ``llm-d-deploy.yaml:8``; the namespace ``llm-d`` at ``llm-d-deploy.yaml:114``,
``llm-d-test.yaml:6`` and ``otel-observability-setup.yaml:9``; the model id
``Qwen/Qwen3-0.6B`` at ``llm-d-deploy.yaml:118`` and ``llm-d-test.yaml:7`` (SURVEY.md
§1 "Key structural fact"). This module is the fix: every tunable the Python engine
uses, and every value the deploy layer shares with it, is defined exactly once here.
``python -m aws_k8s_ansible_provisioner_tpu.config --ansible-vars`` emits the same
values as Ansible-consumable YAML so the playbooks in ``deploy/`` never hard-code
them either.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Optional

# ---------------------------------------------------------------------------
# Model architecture configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for a decoder-only LM.

    One schema covers both model families the reference stack exercises:
    the served default Qwen/Qwen3-0.6B (``llm-d-deploy.yaml:118``) and the two
    chat-template targets (``templates/phi-chat-template.yaml``,
    ``templates/opt-chat-template.yaml``) — Phi-2 being the canonical "phi"
    template user. Field semantics:

    - ``norm``: "rmsnorm" (Qwen) or "layernorm" (Phi/OPT, with bias).
    - ``qk_norm``: per-head RMSNorm on q/k projections (Qwen3 innovation).
    - ``qk_norm_span``: what one q/k RMSNorm runs over when ``qk_norm`` is
      set: "head" (Qwen3: each head's ``head_dim`` after the split, weight
      ``[head_dim]``) or "projection" (OLMoE: the whole q / k projection
      BEFORE the split into heads, weights ``[q_size]`` / ``[kv_size]``).
    - ``parallel_block``: Phi-style parallel attention+MLP residual block.
    - ``rotary_pct``: fraction of head_dim that is rotated (Phi-2 uses 0.4);
      1.0 means full-dim RoPE (Qwen).
    - ``act``: "silu" → SwiGLU and "gelu_tanh" → GeGLU (both GATED 2-projection
      MLPs, see ``gated_mlp``); "gelu_new"/"relu" → plain 2-matrix MLP.
    - ``pos_embed``: "rope" or "learned" (OPT: learned absolute positions with
      the family's +2 offset).
    - ``rope_scaling``: "none" or "llama3" (the Llama-3.1+ frequency-dependent
      NTK scaling; the remaining ``rope_*`` fields are its parameters — scalar
      fields rather than a dict so the config stays hashable for jit
      static-arg use).
    """

    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_seq_len: int = 4096
    # Sliding-window attention (Mistral-v0.1 style): every position attends
    # only the last ``sliding_window`` keys; 0 = full causal. Applied
    # consistently across prefill masks, the XLA decode fallback, and the
    # Pallas decode kernels — where chunks entirely BELOW the window are
    # skipped at the DMA level, bounding per-token cache reads at long
    # contexts (ops/pallas_attention.py).
    sliding_window: int = 0
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0
    rope_scaling: str = "none"
    rope_factor: float = 1.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_pos: int = 8192
    norm: str = "rmsnorm"
    norm_eps: float = 1e-6
    # Gemma convention: RMSNorm weight is zero-centered (applied as 1 + w)
    # and the token embedding is scaled by sqrt(hidden_size).
    norm_zero_centered: bool = False
    embed_scale: bool = False
    qk_norm: bool = False
    qk_norm_span: str = "head"
    # "silu" (SwiGLU, Qwen/Llama) and "gelu_tanh" (GeGLU, Gemma) are GATED
    # two-projection MLPs; "gelu_new"/"relu" are plain two-matmul MLPs.
    act: str = "silu"
    pos_embed: str = "rope"
    attention_bias: bool = False
    mlp_bias: bool = False
    parallel_block: bool = False
    tie_embeddings: bool = False
    bos_token_id: Optional[int] = None
    eos_token_id: int = 0
    # Additional stop ids (Llama-3 Instruct checkpoints declare a LIST of eos
    # ids — e.g. <|end_of_text|> plus <|eot_id|>; chat turns end with the
    # latter). Tuple, not list, so the config stays hashable for jit.
    extra_eos_token_ids: tuple = ()
    # Mixture of Experts (Qwen3-MoE family): 0 experts = dense MLP. When
    # num_experts > 0 every layer's MLP is a router + num_experts SwiGLU
    # experts of width moe_intermediate_size, top-k per token
    # (num_experts_per_tok), with router-weight renormalization over the
    # top-k (norm_topk_prob — HF Qwen3MoeSparseMoeBlock semantics).
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    norm_topk_prob: bool = True
    # Expert-compute implementation (ops/moe.py): "ragged" = exact no-drop
    # sorted grouped matmul (jax.lax.ragged_dot; the single-device serving
    # path); "gshard" = fixed-capacity one-hot dispatch einsums — fully
    # GSPMD-partitionable over the mesh's ep axis (the distributed path;
    # tokens past an expert's capacity fall back to the residual stream).
    moe_impl: str = "ragged"
    moe_capacity_factor: float = 2.0
    # Router scoring: "softmax" over all experts (Qwen3-MoE, OLMoE) or
    # "sigmoid" per expert with a per-expert SELECTION bias (the top-k are
    # chosen by score + bias, weighted by the scores alone — the
    # glm4_moe / solar_open router).
    router_scoring: str = "softmax"
    # A chip's SHARE of an expert-parallel layer: the router keeps its
    # published width ``n_routed_experts`` (0 = ``num_experts``), the stacks
    # hold the ``num_experts`` experts with ids [expert_offset,
    # expert_offset + num_experts); a chosen expert held elsewhere adds
    # nothing here (ops/moe.py).
    n_routed_experts: int = 0
    expert_offset: int = 0
    # Shared experts: a dense SwiGLU of width n_shared_experts x
    # moe_intermediate_size every token passes, added to the routed sum.
    n_shared_experts: int = 0
    # The FFN by LAYER (a layer list of a model with experts): the first
    # num_dense_layers layers take the dense gated MLP of width
    # intermediate_size, the others the routed one.
    num_dense_layers: int = 0
    # The renormalised top-k weights times this (the afmoe router's
    # route_scale); 1.0 = as they are.
    route_scale: float = 1.0
    # What a sigmoid router's renormalisation adds to the sum of the chosen
    # scores (a constant of the model: afmoe 1e-20, lfm2_moe 1e-6).
    route_norm_eps: float = 1e-20
    # Per-layer block kinds, one character a layer: "g" softmax attention
    # (the GQA block), "k" KDA linear attention, "s" softmax attention that
    # SELECTS the pages it reads (ops/sparse_attention.py), "l" Lightning
    # linear attention (ops/linear_attention.py), "w" softmax attention
    # over the last sliding_window keys, rotated (RoPE) whatever
    # attn_use_rope says of the "g" layers beside it, its K/V in page
    # leaves and a page inventory of its own (ops/kv_pool.py), "c" a gated
    # short convolution (conv_taps taps over the hidden width; its state
    # the conv_taps - 1 rows before a span, ops/linear_attention.py), "h" a
    # block with TWO mixers on one normed input — a Mamba-2 state-space
    # mixer and the GQA attention, both added to the residual stream before
    # the FFN — which is an attending layer (a pool leaf) AND a recurrent
    # one (a per-slot state and a conv tail). Two
    # forms. A PERIOD of "g"/"k" kinds, shorter than the depth, repeats
    # over it ("gkkk"). A LIST — one character a layer, any order,
    # "g"/"s"/"l"/"c" kinds, "g"/"w" kinds or "h" alone — is the layers
    # held, as they are ("slllllls", "wwwgwwwg", "ccgcccg...", "hhhh").
    # "" = every layer the one kind the fields
    # above describe. A string, not a tuple of enums: the config is a jit
    # static argument and is built from JSON by the benchmark.
    layer_pattern: str = ""
    # Norms on BOTH sides of each branch: the attention's and the FFN's
    # OUTPUT pass an RMSNorm of their own before the residual add
    # (attn_out_norm / mlp_out_norm beside input_norm / post_norm).
    sandwich_norm: bool = False
    # "g"/"s" layers: the attention output is gated elementwise by
    # sigmoid(n . Wg) before the output projection.
    attn_output_gate: bool = False
    # "g"/"s" layers of a model whose OTHER layers rotate (pos_embed "rope"
    # is then the Lightning layers'): False = attention without positions.
    attn_use_rope: bool = True
    # "k" layers (Kimi Delta Attention, arXiv:2510.26692): heads of
    # kda_head_dim (d_k = d_v), a short depthwise causal convolution on
    # q/k/v (linear_attention.CONV_TAPS taps), low-rank decay and gate
    # projections.
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_low_rank: int = 0
    # "l" layers (Lightning Attention): heads of lightning_head_dim with a
    # fixed scalar decay a head, per-head RMSNorm on q/k, RoPE on q/k, a
    # per-head RMSNorm and a sigmoid gate on the output.
    lightning_num_heads: int = 0
    lightning_head_dim: int = 0
    # "c" layers (the LFM2 gated short convolution): [B, C, X] = split3 of
    # one hidden -> 3 x hidden projection, a depthwise causal convolution
    # of conv_taps taps over B * X (no bias, no activation), gated by C,
    # then a hidden -> hidden output projection.
    conv_taps: int = 0
    # "h" layers (falcon_h1: Mamba-2 / SSD, arXiv:2405.21060, beside the
    # attention): ssm_num_heads heads of ssm_head_dim, a float32 state
    # [ssm_state_size, ssm_head_dim] a head, B and C shared by the heads of
    # one of ssm_num_groups groups, a scalar decay a head and token, a
    # biased SiLU convolution of conv_taps taps over x | B | C, a gated
    # RMSNorm over each group's channels.
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_num_groups: int = 0
    # The falcon_h1 muP multipliers, as published (1.0 / () = none): the
    # embedding's rows, the logits, the attention's input / output and its
    # keys, the SSM's input / output, the five segments z | x | B | C | dt
    # of the SSM's in-projection, the FFN's gate and its output.
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = ()
    mlp_multipliers: tuple = ()
    # "s" layers (InfLLM-v2 block selection, arXiv:2509.24663): keys are
    # mean-pooled over windows of sparse_kernel_size every
    # sparse_kernel_stride tokens; a query scores the pooled keys, a block
    # of sparse_block_size tokens (= the pool's page) takes the best score
    # of the windows that overlap it, and the query attends the
    # sparse_topk best blocks — the first sparse_init_blocks and the last
    # sparse_window_size tokens' blocks always among them. A context
    # shorter than sparse_dense_len is attended whole.
    sparse_block_size: int = 0
    sparse_kernel_size: int = 0
    sparse_kernel_stride: int = 0
    sparse_topk: int = 0
    sparse_init_blocks: int = 0
    sparse_window_size: int = 0
    sparse_dense_len: int = 0
    # MiniCPM's muP: the embedding times scale_emb; every residual add
    # times scale_depth / sqrt(mup_depth) (mup_depth = the PUBLISHED depth,
    # also where fewer layers are held; 0 = num_layers); the logits divided
    # by hidden_size / dim_model_base. 1.0 / 0 = none of it.
    scale_emb: float = 1.0
    scale_depth: float = 0.0
    mup_depth: int = 0
    dim_model_base: int = 0
    hf_repo: str = ""

    def __post_init__(self):
        pat = self.layer_pattern
        for name in ("ssm_multipliers", "mlp_multipliers",
                     "extra_eos_token_ids"):
            # built from JSON by the benchmark: a list is not hashable
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if len(self.ssm_multipliers) not in (0, 5) \
                or len(self.mlp_multipliers) not in (0, 2):
            raise ValueError(
                f"ssm_multipliers={self.ssm_multipliers} names the in-"
                f"projection's five segments z | x | B | C | dt and "
                f"mlp_multipliers={self.mlp_multipliers} the FFN's gate and "
                f"output (or neither is given)")
        if self.num_dense_layers and not (
                self.num_experts > 0 and len(pat) == self.num_layers):
            raise ValueError(
                f"num_dense_layers={self.num_dense_layers}: the FFN differs "
                f"by layer only in a model with experts whose layers are a "
                f"list (layer_pattern one character a layer)")
        if not pat:
            return
        if "h" in pat:
            if set(pat) != {"h"} or len(pat) != self.num_layers:
                raise ValueError(
                    f"layer_pattern={pat!r}: 'h' (state-space + attention) "
                    f"layers come in a list of their own, one character a "
                    f"layer held (num_layers={self.num_layers})")
            H, G = self.ssm_num_heads, self.ssm_num_groups
            if not (H and self.ssm_head_dim and self.ssm_state_size and G) \
                    or H % G or self.conv_taps < 2:
                raise ValueError(
                    "an 'h' layer needs ssm_num_heads (a whole number of "
                    "ssm_num_groups), ssm_head_dim, ssm_state_size and "
                    "conv_taps >= 2")
            return
        if set(pat) <= set("gk"):
            if len(pat) == self.num_layers and "k" not in pat:
                pass        # a list of plain attention layers
            elif pat.count("g") != 1:
                raise ValueError(
                    f"layer_pattern={pat!r}: a period holds one 'g' "
                    f"(attention) layer and any number of 'k' (KDA) layers; "
                    f"a list (one character a layer) holds 'g', 's' "
                    f"(selecting attention) and 'l' (Lightning) layers")
            if self.num_layers % len(pat):
                raise ValueError(f"num_layers={self.num_layers} is not a "
                                 f"whole number of periods {pat!r}")
            if "k" in pat and not (self.kda_num_heads and self.kda_head_dim):
                raise ValueError("a 'k' layer needs kda_num_heads and "
                                 "kda_head_dim")
            return
        if "w" in pat:
            if set(pat) - set("gw"):
                raise ValueError(
                    f"layer_pattern={pat!r}: window ('w') layers come in "
                    f"a list with full ('g') attention layers and no other "
                    f"kind")
            if len(pat) != self.num_layers:
                raise ValueError(
                    f"layer_pattern={pat!r} names {len(pat)} layers, "
                    f"num_layers={self.num_layers}: a list gives one "
                    f"character a layer held")
            if self.sliding_window <= 0:
                raise ValueError("a 'w' layer needs sliding_window > 0")
            return
        if "g" in pat and "s" in pat:
            raise ValueError(
                f"layer_pattern={pat!r}: the attending layers of a list "
                f"either all select ('s') or none does ('g') — one attend "
                f"callback serves them all")
        if set(pat) - set("gslc"):
            raise ValueError(
                f"layer_pattern={pat!r}: a list holds 'g' (attention), 's' "
                f"(selecting attention), 'l' (Lightning) and 'c' (gated "
                f"short convolution) layers; 'k' (KDA) layers come in a "
                f"period with one 'g'")
        if len(pat) != self.num_layers:
            raise ValueError(
                f"layer_pattern={pat!r} names {len(pat)} layers, "
                f"num_layers={self.num_layers}: a list with 's', 'l' or "
                f"'c' kinds gives one character a layer held")
        if "c" in pat and self.conv_taps < 2:
            raise ValueError("a 'c' layer needs conv_taps >= 2")
        if "l" in pat and not (self.lightning_num_heads
                               and self.lightning_head_dim):
            raise ValueError("an 'l' layer needs lightning_num_heads and "
                             "lightning_head_dim")
        if "s" in pat:
            bs, ks, st = (self.sparse_block_size, self.sparse_kernel_size,
                          self.sparse_kernel_stride)
            if not (bs and ks and st and self.sparse_topk):
                raise ValueError(
                    "an 's' layer needs sparse_block_size, "
                    "sparse_kernel_size, sparse_kernel_stride and "
                    "sparse_topk")
            if ks != 2 * st or bs % st:
                raise ValueError(
                    f"sparse_kernel_size={ks}, sparse_kernel_stride={st}, "
                    f"sparse_block_size={bs}: the selector's cache keeps "
                    f"sums of stride-sized runs of keys, so a window is two "
                    f"strides and a block a whole number of them")
            if self.sparse_window_size % bs:
                raise ValueError(
                    f"sparse_window_size={self.sparse_window_size} is not "
                    f"a whole number of blocks of {bs}")

    @property
    def gated_mlp(self) -> bool:
        return self.act in ("silu", "gelu_tanh")

    @property
    def layer_list(self) -> bool:
        """The pattern is a LIST of the layers held (not a period)."""
        return bool(self.layer_pattern) and (
            bool(set(self.layer_pattern) & set("slwch"))
            or (len(self.layer_pattern) == self.num_layers
                and "k" not in self.layer_pattern))

    @property
    def recurrent(self) -> bool:
        """Some layers keep a recurrent state per sequence beside K/V."""
        return bool(set(self.layer_pattern) & set("klch"))

    @property
    def recurrent_kinds(self) -> str:
        """The recurrent kinds that are there, for a log line or a refusal."""
        names = {"k": "KDA", "l": "Lightning", "c": "conv", "h": "SSM"}
        return "/".join(v for k, v in names.items()
                        if k in self.layer_pattern)

    @property
    def windowed(self) -> bool:
        """Some layers of the list ("w") see only the last sliding_window
        keys, beside full ones: their pages are another inventory."""
        return "w" in self.layer_pattern

    @property
    def attn_window(self) -> int:
        """The window of the layers the pool's ``k`` / ``v`` leaves serve:
        sliding_window where EVERY layer is windowed (Mistral), 0 (full)
        for the "g" layers of a list with "w" layers."""
        return 0 if self.windowed else self.sliding_window

    @property
    def num_window_layers(self) -> int:
        """The "w" layers: the leading axis of the pool's ``wk`` / ``wv``."""
        return self.layer_pattern.count("w")

    @property
    def selects(self) -> bool:
        """Some attending layers select the pages a query reads."""
        return "s" in self.layer_pattern

    @property
    def num_periods(self) -> int:
        return self.num_layers // len(self.layer_pattern or "g")

    @property
    def num_attn_layers(self) -> int:
        """Layers that attend over K/V: the pool's leading axis."""
        if self.layer_list:
            return sum(self.layer_pattern.count(c) for c in "gsh")
        return self.num_periods if self.layer_pattern else self.num_layers

    @property
    def num_recurrent_layers(self) -> int:
        if self.layer_list:
            return sum(self.layer_pattern.count(c) for c in "lch")
        return self.num_periods * self.kda_per_period

    @property
    def kda_per_period(self) -> int:
        return self.layer_pattern.count("k")

    @property
    def residual_scale(self) -> float:
        """What every block's output is multiplied by before it is added
        to the residual stream (muP); 1.0 = a plain add."""
        if not self.scale_depth:
            return 1.0
        return self.scale_depth / (self.mup_depth or self.num_layers) ** 0.5

    @property
    def logit_scale(self) -> float:
        if not self.dim_model_base:
            return 1.0
        return self.dim_model_base / self.hidden_size

    @property
    def sparse_select_width(self) -> int:
        """Entries of a query's list of selected blocks: the top-k, or every
        block of a context still under sparse_dense_len."""
        bs = self.sparse_block_size
        return max(self.sparse_topk, -(-self.sparse_dense_len // bs)) \
            if bs else 0

    @property
    def kv_lane_pack(self) -> int:
        """K/V heads stored side by side in one pool row. LAYOUT, not
        mathematics, and read off the shapes: a head narrower than the 128
        lanes of a TPU vreg cannot be a pool's minor axis on the chip (the
        HBM tiling pads a 64-wide page to twice its bytes and Mosaic refuses
        to slice it), so where whole heads fill a row exactly — 128 //
        head_dim of them, dividing num_kv_heads — they share one, and the
        paged kernels read them as ONE 128-wide head
        (ops/attention.lane_packed). A list with selecting ("s") or window
        ("w") layers keeps a head a row: the selector's pooled keys are per
        head, and neither kind's attend callback is wrapped."""
        n = 128 // self.head_dim if self.head_dim < 128 else 1
        if n * self.head_dim != 128 or self.num_kv_heads % n \
                or set(self.layer_pattern) & set("sw"):
            return 1
        return n

    @property
    def pool_kv_heads(self) -> int:
        """KV heads of the pool's leaves (``kv_lane_pack`` heads a row)."""
        return self.num_kv_heads // self.kv_lane_pack

    @property
    def pool_head_dim(self) -> int:
        return self.head_dim * self.kv_lane_pack

    @property
    def kda_size(self) -> int:
        return self.kda_num_heads * self.kda_head_dim

    @property
    def ssm_size(self) -> int:
        """The SSM's inner width (``mamba_d_ssm``)."""
        return self.ssm_num_heads * self.ssm_head_dim

    @property
    def ssm_conv_size(self) -> int:
        """Channels of the SSM's convolution: x | B | C."""
        return self.ssm_size + 2 * self.ssm_num_groups * self.ssm_state_size

    @property
    def ssm_in_size(self) -> int:
        """The in-projection's width: z | x | B | C | dt."""
        return self.ssm_size + self.ssm_conv_size + self.ssm_num_heads

    @property
    def lightning_size(self) -> int:
        return self.lightning_num_heads * self.lightning_head_dim

    @property
    def router_width(self) -> int:
        return self.n_routed_experts or self.num_experts

    @property
    def expert_share(self) -> bool:
        """The stacks hold only some of the experts the router scores."""
        return self.router_width != self.num_experts

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    def scaled(self, **overrides) -> "ModelConfig":
        """Return a copy with fields overridden (used for tiny test configs)."""
        return dataclasses.replace(self, **overrides)


# Real architectures. Hyperparameters are the public HF config.json values for each
# model id (architecture facts, not code, so no copying concern).
QWEN3_0_6B = ModelConfig(
    name="Qwen/Qwen3-0.6B",
    vocab_size=151936,
    hidden_size=1024,
    intermediate_size=3072,
    num_layers=28,
    num_heads=16,
    num_kv_heads=8,
    head_dim=128,
    max_seq_len=40960,
    rope_theta=1e6,
    qk_norm=True,
    tie_embeddings=True,
    bos_token_id=151643,
    eos_token_id=151645,
    hf_repo="Qwen/Qwen3-0.6B",
)

QWEN3_8B = ModelConfig(
    name="Qwen/Qwen3-8B",
    vocab_size=151936,
    hidden_size=4096,
    intermediate_size=12288,
    num_layers=36,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    max_seq_len=40960,
    rope_theta=1e6,
    qk_norm=True,
    tie_embeddings=False,
    bos_token_id=151643,
    eos_token_id=151645,
    hf_repo="Qwen/Qwen3-8B",
)

PHI_2 = ModelConfig(
    name="microsoft/phi-2",
    vocab_size=51200,
    hidden_size=2560,
    intermediate_size=10240,
    num_layers=32,
    num_heads=32,
    num_kv_heads=32,
    head_dim=80,
    max_seq_len=2048,
    rope_theta=10000.0,
    rotary_pct=0.4,
    norm="layernorm",
    norm_eps=1e-5,
    act="gelu_new",
    attention_bias=True,
    mlp_bias=True,
    parallel_block=True,
    tie_embeddings=False,
    bos_token_id=50256,
    eos_token_id=50256,
    hf_repo="microsoft/phi-2",
)

OPT_125M = ModelConfig(
    name="facebook/opt-125m",
    vocab_size=50272,
    hidden_size=768,
    intermediate_size=3072,
    num_layers=12,
    num_heads=12,
    num_kv_heads=12,
    head_dim=64,
    max_seq_len=2048,
    norm="layernorm",
    norm_eps=1e-5,
    act="relu",
    pos_embed="learned",
    attention_bias=True,
    mlp_bias=True,
    tie_embeddings=True,
    bos_token_id=2,
    eos_token_id=2,
    hf_repo="facebook/opt-125m",
)

OPT_1_3B = ModelConfig(
    name="facebook/opt-1.3b",
    vocab_size=50272,
    hidden_size=2048,
    intermediate_size=8192,
    num_layers=24,
    num_heads=32,
    num_kv_heads=32,
    head_dim=64,
    max_seq_len=2048,
    norm="layernorm",
    norm_eps=1e-5,
    act="relu",
    pos_embed="learned",
    attention_bias=True,
    mlp_bias=True,
    tie_embeddings=True,
    bos_token_id=2,
    eos_token_id=2,
    hf_repo="facebook/opt-1.3b",
)

LLAMA_3_2_1B = ModelConfig(
    name="meta-llama/Llama-3.2-1B",
    vocab_size=128256,
    hidden_size=2048,
    intermediate_size=8192,
    num_layers=16,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    max_seq_len=131072,
    rope_theta=500000.0,
    rope_scaling="llama3",
    rope_factor=32.0,
    rope_low_freq_factor=1.0,
    rope_high_freq_factor=4.0,
    rope_original_max_pos=8192,
    tie_embeddings=True,
    bos_token_id=128000,
    eos_token_id=128001,
    hf_repo="meta-llama/Llama-3.2-1B",
)

LLAMA_3_1_8B = ModelConfig(
    name="meta-llama/Llama-3.1-8B",
    vocab_size=128256,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    max_seq_len=131072,
    rope_theta=500000.0,
    rope_scaling="llama3",
    rope_factor=8.0,
    rope_low_freq_factor=1.0,
    rope_high_freq_factor=4.0,
    rope_original_max_pos=8192,
    tie_embeddings=False,
    bos_token_id=128000,
    eos_token_id=128001,
    hf_repo="meta-llama/Llama-3.1-8B",
)

TINYLLAMA_1_1B = ModelConfig(
    name="TinyLlama/TinyLlama-1.1B-Chat-v1.0",
    vocab_size=32000,
    hidden_size=2048,
    intermediate_size=5632,
    num_layers=22,
    num_heads=32,
    num_kv_heads=4,
    head_dim=64,
    max_seq_len=2048,
    rope_theta=10000.0,
    tie_embeddings=False,
    bos_token_id=1,
    eos_token_id=2,
    hf_repo="TinyLlama/TinyLlama-1.1B-Chat-v1.0",
)

MISTRAL_7B_V01 = ModelConfig(
    name="mistralai/Mistral-7B-v0.1",
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    max_seq_len=32768,
    sliding_window=4096,
    rope_theta=10000.0,
    tie_embeddings=False,
    bos_token_id=1,
    eos_token_id=2,
    hf_repo="mistralai/Mistral-7B-v0.1",
)

GEMMA_2B = ModelConfig(
    name="google/gemma-2b",
    vocab_size=256000,
    hidden_size=2048,
    intermediate_size=16384,
    num_layers=18,
    num_heads=8,
    num_kv_heads=1,            # MQA
    head_dim=256,
    max_seq_len=8192,
    rope_theta=10000.0,
    norm_zero_centered=True,
    embed_scale=True,
    act="gelu_tanh",
    tie_embeddings=True,
    bos_token_id=2,
    eos_token_id=1,
    hf_repo="google/gemma-2b",
)

QWEN3_30B_A3B = ModelConfig(
    name="Qwen/Qwen3-30B-A3B",
    vocab_size=151936,
    hidden_size=2048,
    intermediate_size=6144,        # dense-MLP width (unused: all layers MoE)
    num_layers=48,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    max_seq_len=40960,
    rope_theta=1e6,
    qk_norm=True,
    tie_embeddings=False,
    bos_token_id=151643,
    eos_token_id=151645,
    num_experts=128,
    num_experts_per_tok=8,
    moe_intermediate_size=768,
    norm_topk_prob=True,
    hf_repo="Qwen/Qwen3-30B-A3B",
)

# allenai OLMoE (HF ``OlmoeForCausalLM``): every layer's FFN is a router over
# 64 SwiGLU experts of width 1,024 (config.json's ``intermediate_size`` IS the
# expert width), top-8 with the softmax weights used as they are, MHA, and the
# q/k RMSNorm over the whole projection. config.json has no head_dim key
# (hidden / heads) and no bos id.
OLMOE_1B_7B_0125_INSTRUCT = ModelConfig(
    name="allenai/OLMoE-1B-7B-0125-Instruct",
    vocab_size=50304,
    hidden_size=2048,
    intermediate_size=1024,
    num_layers=16,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    max_seq_len=4096,
    rope_theta=10000.0,
    norm_eps=1e-5,
    qk_norm=True,
    qk_norm_span="projection",
    tie_embeddings=False,
    eos_token_id=50279,
    num_experts=64,
    num_experts_per_tok=8,
    moe_intermediate_size=1024,
    norm_topk_prob=False,
    hf_repo="allenai/OLMoE-1B-7B-0125-Instruct",
)

# arcee-ai Trinity-Mini (``model_type`` afmoe, 26B-A3B), the FIRST of four
# pipeline stages: published layers 0-7 as they stand — window (2,048,
# RoPE) and full (no positions) attention 3:1, both leading dense layers
# and six routed ones (128 experts of width 1,024, top-8 by sigmoid score +
# selection bias, renormalised, x route_scale, beside a shared expert),
# norms on both sides of each branch, the embedding x sqrt(hidden) — and
# the head, so that it serves tokens alone (benchmark/configs/
# trinity-mini-26b-pp4.json states the cut).
TRINITY_MINI_PP4_STAGE0 = ModelConfig(
    name="arcee-ai/Trinity-Mini-pp4-stage0",
    vocab_size=200192,
    hidden_size=2048,
    intermediate_size=6144,
    num_layers=8,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    max_seq_len=131072,
    sliding_window=2048,
    rope_theta=10000.0,
    norm_eps=1e-5,
    qk_norm=True,
    embed_scale=True,
    tie_embeddings=False,
    eos_token_id=3,
    num_experts=128,
    num_experts_per_tok=8,
    moe_intermediate_size=1024,
    norm_topk_prob=True,
    router_scoring="sigmoid",
    n_shared_experts=1,
    num_dense_layers=2,
    route_scale=2.826,
    layer_pattern="wwwgwwwg",
    sandwich_norm=True,
    attn_output_gate=True,
    attn_use_rope=False,
)

# LiquidAI LFM2-8B-A1B (``model_type`` lfm2_moe), WHOLE: the published 24
# layers — 18 gated short convolutions ("c", 3 taps) and 6 GQA layers ("g":
# 32 query / 8 KV heads of 64, per-head q/k RMSNorm, RoPE) as
# ``layer_types`` lists them, the two leading layers with a dense SwiGLU of
# 7,168, the other 22 with 32 experts of 1,792, top-4 by sigmoid score +
# selection bias, renormalised (+ 1e-6), no shared expert; tied embeddings
# (benchmark/configs/lfm2-8b-a1b-int8.json states what is assumed).
LFM2_8B_A1B = ModelConfig(
    name="LiquidAI/LFM2-8B-A1B",
    vocab_size=65536,
    hidden_size=2048,
    intermediate_size=7168,
    num_layers=24,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    max_seq_len=128000,
    rope_theta=1000000.0,
    norm_eps=1e-5,
    qk_norm=True,
    tie_embeddings=True,
    eos_token_id=7,
    num_experts=32,
    num_experts_per_tok=4,
    moe_intermediate_size=1792,
    norm_topk_prob=True,
    router_scoring="sigmoid",
    num_dense_layers=2,
    route_norm_eps=1e-6,
    layer_pattern="ccgcccgcccgcccgcccgccgcc",
    conv_taps=3,
    hf_repo="LiquidAI/LFM2-8B-A1B",
)

# tiiuae Falcon-H1-34B-Instruct (``model_type`` falcon_h1), the FIRST of eight
# pipeline stages: published layers 0-8 as they stand — every layer a Mamba-2
# mixer (32 heads of 128, 2 groups, state 256, 4 taps) AND 20 query / 4 KV
# heads of 128 on one normed input, then a SwiGLU of 21,504 — the embedding
# and the untied 261,120-row head, the twelve muP multipliers as published
# (benchmark/configs/falcon-h1-34b-pp8.json states the cut and what is
# assumed).
FALCON_H1_34B_PP8_STAGE0 = ModelConfig(
    name="tiiuae/Falcon-H1-34B-Instruct-pp8-stage0",
    vocab_size=261120,
    hidden_size=5120,
    intermediate_size=21504,
    num_layers=9,
    num_heads=20,
    num_kv_heads=4,
    head_dim=128,
    max_seq_len=262144,
    rope_theta=1e11,
    norm_eps=1e-5,
    tie_embeddings=False,
    eos_token_id=11,
    layer_pattern="hhhhhhhhh",
    conv_taps=4,
    ssm_num_heads=32,
    ssm_head_dim=128,
    ssm_state_size=256,
    ssm_num_groups=2,
    embedding_multiplier=5.656854249492381,
    lm_head_multiplier=0.0078125,
    attention_in_multiplier=1.0,
    attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804,
    ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    hf_repo="tiiuae/Falcon-H1-34B-Instruct",
)

MODEL_REGISTRY = {
    "tiiuae/Falcon-H1-34B-Instruct-pp8-stage0": FALCON_H1_34B_PP8_STAGE0,
    "LiquidAI/LFM2-8B-A1B": LFM2_8B_A1B,
    "arcee-ai/Trinity-Mini-pp4-stage0": TRINITY_MINI_PP4_STAGE0,
    "Qwen/Qwen3-0.6B": QWEN3_0_6B,
    "Qwen/Qwen3-30B-A3B": QWEN3_30B_A3B,
    "allenai/OLMoE-1B-7B-0125-Instruct": OLMOE_1B_7B_0125_INSTRUCT,
    "Qwen/Qwen3-8B": QWEN3_8B,
    "microsoft/phi-2": PHI_2,
    "facebook/opt-125m": OPT_125M,
    "facebook/opt-1.3b": OPT_1_3B,
    "google/gemma-2b": GEMMA_2B,
    "mistralai/Mistral-7B-v0.1": MISTRAL_7B_V01,
    "meta-llama/Llama-3.2-1B": LLAMA_3_2_1B,
    "meta-llama/Llama-3.1-8B": LLAMA_3_1_8B,
    "TinyLlama/TinyLlama-1.1B-Chat-v1.0": TINYLLAMA_1_1B,
}


def get_model_config(name: str) -> ModelConfig:
    if name not in MODEL_REGISTRY:
        raise KeyError(
            f"unknown model {name!r}; registered: {sorted(MODEL_REGISTRY)}"
        )
    return MODEL_REGISTRY[name]


def tiny_qwen3(**overrides) -> ModelConfig:
    """A miniature Qwen3-shaped config for unit tests (CPU-fast, GQA exercised)."""
    base = dict(
        name="tiny-qwen3",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
        rope_theta=1e6,
        qk_norm=True,
        tie_embeddings=True,
        eos_token_id=1,
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_qwen3_moe(**overrides) -> ModelConfig:
    """A miniature Qwen3-MoE-shaped config (router + SwiGLU experts, GQA)."""
    base = dict(
        name="tiny-qwen3-moe",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
        rope_theta=1e6,
        qk_norm=True,
        tie_embeddings=True,
        eos_token_id=1,
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=32,
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_olmoe(**overrides) -> ModelConfig:
    """A miniature OLMoE-shaped config: MHA, q/k RMSNorm over the whole
    projection, top-2 of 8 experts with the softmax weights as they are."""
    base = dict(
        name="tiny-olmoe",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=32,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        max_seq_len=128,
        rope_theta=10000.0,
        norm_eps=1e-5,
        qk_norm=True,
        qk_norm_span="projection",
        tie_embeddings=False,
        eos_token_id=1,
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=32,
        norm_topk_prob=False,
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_solar(**overrides) -> ModelConfig:
    """A miniature Solar-Open2-shaped hybrid: two periods of one gated NoPE
    GQA layer and three KDA layers; every FFN a sigmoid router over 16
    experts of which this share holds 4, top-2, plus one shared expert."""
    base = dict(
        name="tiny-solar",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=32,
        num_layers=8,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=256,
        norm_eps=1e-5,
        pos_embed="none",
        tie_embeddings=False,
        eos_token_id=1,
        num_experts=4,
        n_routed_experts=16,
        expert_offset=0,
        num_experts_per_tok=2,
        moe_intermediate_size=32,
        norm_topk_prob=True,
        router_scoring="sigmoid",
        n_shared_experts=1,
        layer_pattern="gkkk",
        attn_output_gate=True,
        kda_num_heads=4,
        kda_head_dim=16,
        kda_low_rank=16,
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_sala(**overrides) -> ModelConfig:
    """A miniature MiniCPM-SALA-shaped hybrid: a LIST of selecting
    attention layers ("s": gated NoPE GQA that reads the top-4 blocks of 8)
    and Lightning linear-attention layers ("l"), not a period; muP scales."""
    base = dict(
        name="tiny-sala",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_layers=6,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=256,
        norm_eps=1e-6,
        qk_norm=True,
        tie_embeddings=False,
        eos_token_id=1,
        layer_pattern="sllssl",
        attn_output_gate=True,
        attn_use_rope=False,
        lightning_num_heads=4,
        lightning_head_dim=16,
        sparse_block_size=8,
        sparse_kernel_size=4,
        sparse_kernel_stride=2,
        sparse_topk=4,
        sparse_init_blocks=1,
        sparse_window_size=16,
        sparse_dense_len=32,
        scale_emb=12.0,
        scale_depth=1.4,
        mup_depth=32,
        dim_model_base=16,
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_trinity(**overrides) -> ModelConfig:
    """A miniature Trinity-shaped list: window ("w": the last 8 keys,
    rotated) and full ("g": no positions) gated GQA layers, two leading
    dense FFNs and four routed ones (8 experts, top-2 by sigmoid score +
    selection bias, x route_scale, plus a shared expert), norms on both
    sides of each branch, the embedding x sqrt(hidden)."""
    base = dict(
        name="tiny-trinity",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_layers=6,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=256,
        sliding_window=8,
        rope_theta=10000.0,
        norm_eps=1e-5,
        qk_norm=True,
        embed_scale=True,
        tie_embeddings=False,
        eos_token_id=1,
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=32,
        norm_topk_prob=True,
        router_scoring="sigmoid",
        n_shared_experts=1,
        num_dense_layers=2,
        route_scale=2.826,
        layer_pattern="wwwgwg",
        sandwich_norm=True,
        attn_output_gate=True,
        attn_use_rope=False,
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_lfm2(**overrides) -> ModelConfig:
    """A miniature LFM2-shaped list: gated short convolutions ("c", 3 taps)
    and GQA layers ("g": per-head q/k norm, RoPE) in the published order's
    shape — two leading conv layers with a dense FFN, then whole "gccc"
    periods and two shorter "gcc" ones — 8 experts top-2 by sigmoid score +
    selection bias, no shared expert, tied embeddings."""
    base = dict(
        name="tiny-lfm2",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_layers=16,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=256,
        rope_theta=1000000.0,
        norm_eps=1e-5,
        qk_norm=True,
        tie_embeddings=True,
        eos_token_id=1,
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=32,
        norm_topk_prob=True,
        router_scoring="sigmoid",
        num_dense_layers=2,
        route_norm_eps=1e-6,
        layer_pattern="ccgcccgcccgccgcc",
        conv_taps=3,
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_falcon_h1(**overrides) -> ModelConfig:
    """A miniature Falcon-H1-shaped list: every layer a Mamba-2 mixer (4
    heads of 16 in 2 groups, state 32, 4 taps) AND GQA attention at a query
    group of 5 on one normed input, an untied head, and all twelve muP
    multipliers different from 1."""
    base = dict(
        name="tiny-falcon-h1",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_layers=3,
        num_heads=5,
        num_kv_heads=1,
        head_dim=16,
        max_seq_len=256,
        rope_theta=1e11,
        norm_eps=1e-5,
        tie_embeddings=False,
        eos_token_id=1,
        layer_pattern="hhh",
        conv_taps=4,
        ssm_num_heads=4,
        ssm_head_dim=16,
        ssm_state_size=32,
        ssm_num_groups=2,
        embedding_multiplier=5.656854249492381,
        lm_head_multiplier=0.5,
        attention_in_multiplier=1.25,
        attention_out_multiplier=0.6,
        key_multiplier=0.7,
        ssm_in_multiplier=0.5,
        ssm_out_multiplier=0.8,
        ssm_multipliers=(0.7, 0.5, 0.9, 1.5, 0.8),
        mlp_multipliers=(0.6, 0.4),
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_mistral(**overrides) -> ModelConfig:
    """A miniature Mistral-shaped config (sliding-window attention, GQA)."""
    base = dict(
        name="tiny-mistral",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=128,
        sliding_window=8,
        rope_theta=10000.0,
        tie_embeddings=False,
        eos_token_id=1,
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_gemma(**overrides) -> ModelConfig:
    """A miniature Gemma-shaped config (zero-centered norms, scaled embed,
    GeGLU, MQA)."""
    base = dict(
        name="tiny-gemma",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=1,
        head_dim=16,
        max_seq_len=128,
        rope_theta=10000.0,
        norm_zero_centered=True,
        embed_scale=True,
        act="gelu_tanh",
        tie_embeddings=True,
        eos_token_id=1,
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_llama(**overrides) -> ModelConfig:
    """A miniature Llama-3-shaped config (GQA, llama3 rope scaling, no qk-norm)."""
    base = dict(
        name="tiny-llama",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        max_seq_len=256,
        rope_theta=500000.0,
        rope_scaling="llama3",
        rope_factor=8.0,
        rope_low_freq_factor=1.0,
        rope_high_freq_factor=4.0,
        rope_original_max_pos=64,
        tie_embeddings=True,
        eos_token_id=1,
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_opt(**overrides) -> ModelConfig:
    """A miniature OPT-shaped config (learned positions, ReLU MLP, pre-norm)."""
    base = dict(
        name="tiny-opt",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        max_seq_len=128,
        norm="layernorm",
        norm_eps=1e-5,
        act="relu",
        pos_embed="learned",
        attention_bias=True,
        mlp_bias=True,
        tie_embeddings=True,
        eos_token_id=1,
    )
    base.update(overrides)
    return ModelConfig(**base)


def tiny_phi(**overrides) -> ModelConfig:
    """A miniature Phi-2-shaped config (parallel block, partial rotary, biases)."""
    base = dict(
        name="tiny-phi",
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        max_seq_len=128,
        rope_theta=10000.0,
        rotary_pct=0.5,
        norm="layernorm",
        norm_eps=1e-5,
        act="gelu_new",
        attention_bias=True,
        mlp_bias=True,
        parallel_block=True,
        eos_token_id=1,
    )
    base.update(overrides)
    return ModelConfig(**base)


# ---------------------------------------------------------------------------
# Mesh / parallelism config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh (SURVEY.md §2.3: every parallelism capability is net-new).

    Axes: ``dp`` data-parallel replicas, ``tp`` tensor parallel over ICI, ``sp``
    sequence/context parallel (ring attention), ``ep`` expert parallel (MoE
    expert weights sharded; GSPMD turns the gshard dispatch einsums into
    all-to-all-style collectives). The product must equal the device count.
    The communication backend is XLA collectives emitted by the compiler
    from these shardings — nothing to install (replaces the reference stack's
    implicit NCCL, SURVEY.md §5 "Distributed communication backend").
    """

    dp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    # Pipeline stages (parallel/pipeline.py GPipe schedule over ppermute).
    pp: int = 1

    @property
    def num_devices(self) -> int:
        return self.dp * self.tp * self.sp * self.ep * self.pp

    @property
    def axis_names(self):
        return ("dp", "pp", "sp", "ep", "tp")


# ---------------------------------------------------------------------------
# Serving config (engine + deploy-layer shared values)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ServingConfig:
    """Engine runtime knobs + the values shared with the deploy layer."""

    model: str = "Qwen/Qwen3-0.6B"
    # HTTP serving port — must stay 8000: the OTEL collector's annotation-gated pod
    # scrape defaults to port 8000 (reference otel-observability-setup.yaml:359-368)
    # and our observability playbook preserves that contract.
    port: int = 8000
    host: str = "0.0.0.0"
    # Decode slots = max concurrent sequences in flight (continuous batching).
    max_decode_slots: int = 32
    # Prefill length buckets (powers of two): requests are right-padded to the
    # smallest bucket ≥ prompt length so XLA compiles a fixed set of programs.
    prefill_buckets: tuple = (32, 64, 128, 256, 512, 1024, 2048)
    # Max tokens of KV cache per slot (static decode shape).
    max_cache_len: int = 2048
    # Fused decode horizon: AT MOST this many tokens a slot per device
    # dispatch (amortizes dispatch latency; programs.decode_steps). The
    # engine runs them all while no admission can follow the dispatch and a
    # few while one can (EnginePrograms._decode_horizon).
    decode_horizon: int = 8
    # One-deep asynchronous decode pipeline: the engine enqueues decode
    # dispatch N+1 (JAX async dispatch — no block) before fetching N's
    # tokens, so the host emit/SSE/scheduling gap overlaps device compute
    # instead of leaving the chip idle for ~an RTT per dispatch. The sampled
    # token / length carry stays device-resident across dispatches (donated,
    # no host round-trip) and device operand uploads are cached behind dirty
    # flags. Seeded streams are byte-identical either way (keys are
    # position-derived). 0 restores the strictly synchronous dispatch→fetch
    # path (debugging, exact wall-clock attribution per dispatch).
    decode_pipeline: int = 1
    # Ragged mixed-batch attention: chunked prefill rides the same program
    # as the decode batch (one ragged dispatch packs the chunk's tokens
    # alongside every decode row against the paged pool), so admissions no
    # longer drain the one-deep pipeline and the chunk/decode alternation
    # disappears. Requires decode_pipeline; auto-falls-back to the
    # legacy serialized chunk path for dp meshes or a draining engine
    # (and, with ragged_features=0, for spec decode / LoRA / guided slots).
    # 0 restores the legacy path everywhere (sync escape hatch; seeded
    # streams are byte-identical either way).
    ragged_attention: int = 1
    # Feature paths ride the ragged pipeline (the "fallback tax" fix):
    # guided decoding carries its FSM mask as a device-resident per-row
    # logit-mask operand (uploaded one step ahead — no blocking host read),
    # LoRA rows select packed A/B deltas via a per-token adapter-index
    # operand inside the packed [1, B+C] layout, and spec-decode verify
    # hands the device carry off settle-style instead of draining the
    # pipeline. 0 restores the PR-14 gating (spec/LoRA/guided de-pipeline
    # to the sync floor) — the byte-identity A/B fallback arm; seeded
    # streams are byte-identical either way.
    ragged_features: int = 1
    # Paged KV (vLLM's on-demand block allocation; serving/paged_kv.py), the
    # server's only KV layout: a shared physical page pool + per-slot block
    # tables, so HBM cost tracks ACTUAL sequence lengths and admission is
    # gated by free pages, not free slots. Composes with tp meshes (heads
    # sharded over the pool) and dp meshes (pool page axis partitioned per
    # dp group, per-group host allocators).
    page_size: int = 64
    # Physical pages in the pool. 0 = max_decode_slots * ceil(max_cache_len /
    # page_size) — a full window for every slot. Sizing it SMALLER is the
    # point of paging: e.g. 4x the slots over the same pool lets 4x the
    # concurrent short requests share the HBM that a full window per slot
    # reserves for the worst case; when the
    # pool runs dry mid-decode the engine preempts the newest request
    # (vLLM-style recompute) rather than failing.
    kv_pool_pages: int = 0
    # Tier-2 KV (ISSUE 20): byte budget for the host-RAM prefix-page store.
    # When the HBM LRU reclaims an evictable page, its per-layer K/V spills
    # here (async gather, off the dispatch hot path) keyed by the same chain
    # hash; a later prompt whose prefix walks past the resident pages
    # restores the host extension with one batched device_put and prefills
    # only the suffix — eviction stops meaning re-prefill. Restore is
    # PCIe-bandwidth-bound, far cheaper than recomputing prefill FLOPs
    # (arxiv 2504.11816); fixed page shapes keep the transfer path static
    # (SnapStream, arxiv 2511.03092). 0 disables the tier entirely — the
    # byte-identity escape hatch (streams identical to a tier-less build).
    kv_host_tier_bytes: int = 256 * 2**20
    # Batched prefill: up to this many queued prompts share one prefill
    # dispatch (rounded to a power-of-two row count so XLA compiles a fixed
    # set of programs). Under a burst, TTFT p50 scales with ceil(N/batch)
    # dispatches instead of N (VERDICT r1 missing #4).
    max_prefill_batch: int = 4
    # Chunked prefill: prompts longer than this are prefilled in chunks of
    # this many tokens, with decode steps interleaved between chunks so
    # in-flight streams keep making progress during a long prefill (the vLLM
    # behavior inside the reference's serving pods). 0 disables chunking.
    prefill_chunk: int = 0
    # Automatic prefix caching (the vLLM feature of the same name): a new
    # prompt whose leading whole pages hash-match pages still in the pool
    # shares them (refcounted, no copy); only the suffix is prefilled
    # (through the chunk program).
    prefix_cache: bool = True
    # Burst economics: under a burst the batched prefill normally
    # beats a prefix hit (a hit forces the serialized chunk walk), so
    # matches are dropped — UNLESS the reusable prefix spans at least this
    # many whole pages, where skipping the shared-prefix compute (and
    # sharing the pages instead of duplicating them) outweighs losing the
    # batch slot. The router's prompt-affinity exists to produce exactly
    # these long shared prefixes, so this is what makes affinity pay under
    # concurrent load (ROUTER_BENCH.json measures the hit rate).
    prefix_reuse_min_pages: int = 2
    # Prompt-lookup speculative decoding (the vLLM feature of the same name):
    # draft the next spec_k tokens by matching the context's trailing
    # spec_ngram against its own history, verify all drafts in ONE forward
    # pass (one cache stream answers every draft — decode is bandwidth-bound,
    # so accepted drafts are nearly free tokens). Greedy-lossless: accepted
    # tokens are exactly what plain greedy decode would emit; sampled
    # (temperature > 0) slots fall back to one token per step. Single-device
    # path (per-slot accept lengths are data-dependent, which would desync
    # dp shards). Wins on repetitive continuations (code, quoting, RAG);
    # costs one extra model-width of FLOPs per step when nothing matches.
    spec_decode: bool = False
    # Proposal source: "prompt_lookup" (n-gram self-matching, zero extra
    # model) or "draft" (a small draft LM proposes every step — the vLLM
    # draft-worker pairing; pass draft=(cfg, params) to Engine). Verify,
    # eligibility, and mesh gating are shared (serving/draft.py).
    spec_method: str = "prompt_lookup"
    spec_k: int = 4
    spec_ngram: int = 3
    max_tokens_default: int = 256
    # ---- robustness layer (r7): deadlines, admission control, watchdog ----
    # Default end-to-end deadline (seconds) for requests that don't carry one
    # (X-Request-Deadline-Ms header / deadline_ms body field); also the CAP
    # on client-supplied deadlines and the server's wait budget — the single
    # knob replacing the scattered 600-second literals. 0 disables (no
    # default deadline, uncapped client deadlines; waits fall back to 600 s).
    request_timeout_s: float = 600.0
    # Bounded engine queue: admissions past this depth are shed with 429 +
    # Retry-After instead of queueing unboundedly (thread pileups, OOM, and
    # minutes-stale work under overload). 0 = unbounded (pre-r7 behavior).
    max_queue_depth: int = 256
    # Estimated-wait shedding: when > 0, a request whose estimated queue wait
    # (queue_depth x recent avg tokens/request / recent tokens/s) exceeds
    # this is shed with 429 even below max_queue_depth — the queue never
    # holds work that would blow its deadline anyway. 0 disables.
    admission_max_wait_s: float = 0.0
    # Graceful drain budget (r8): on SIGTERM / POST /admin/drain the engine
    # stops admitting (new requests shed with the routable "draining"
    # reason, 503 at the HTTP layer), /readyz flips to 503, and in-flight
    # requests get this many seconds to finish; stragglers are then
    # cancelled through the deadline path (finish "timeout", slot/pages
    # released exactly once) and the process exits 0. serving.yaml.j2
    # derives terminationGracePeriodSeconds from the same knob.
    drain_timeout_s: float = 30.0
    # Stall watchdog: a decode step executing past this is declared stalled —
    # /healthz flips to 503 and the watchdog thread arms the abort flag that
    # fails the affected requests instead of the process (host-observable
    # stalls; a truly wedged XLA call still ends at the liveness restart).
    watchdog_stall_s: float = 120.0
    # Paged admission pressure relief: when the queue head cannot be placed
    # (free slot exists, pages don't) for this long, preempt the LOWEST-
    # progress running request (recompute-resume, requeued at the back) so
    # admission degrades by policy instead of wedging on page starvation.
    # 0 disables (head waits for natural page release).
    admission_preempt_after_s: float = 1.0
    # Prefill/decode fairness: after this many CONSECUTIVE prefill dispatches
    # with decode work pending, the engine forces one full-horizon decode
    # dispatch. Prefill priority otherwise starves in-flight streams under a
    # sustained admission stream (decode only runs when no prompt can be
    # admitted, and drops to horizon 1 near one) — the vLLM
    # max-num-batched-tokens pacing concern, slot-granular (VERDICT r3 weak
    # #5). Higher = better TTFT under bursts; lower = tighter per-token
    # latency for running streams. 0 disables the floor (pure prefill
    # priority, the pre-r4 behavior).
    prefill_fairness: int = 4
    # ---- request tracing (serving/tracing.py) ----
    # OTLP/HTTP trace collector base URL (spans POST to <endpoint>/v1/traces).
    # Empty falls back to $OTEL_EXPORTER_OTLP_ENDPOINT — which the serving
    # manifest sets from ansible_vars' otlp_endpoint (the deployed Tempo's
    # OTLP receiver) — and when neither is set spans are created (trace ids
    # still echo in responses/errors for log correlation) but never exported.
    otlp_endpoint: str = ""
    # Root-span sampling probability in [0, 1]. Propagated contexts inherit
    # the caller's decision (W3C parent-based sampling), so the router's
    # knob effectively governs the whole tree.
    trace_sample: float = 1.0
    # ---- SLO burn rates + flight recorder (serving/slo.py, flightrec.py) ----
    # TTFT p95 objective in milliseconds: first tokens slower than this burn
    # the 5% latency error budget. 0 disables the objective (the shipped
    # default — a target only makes sense per deployment/model).
    slo_ttft_p95_ms: float = 0.0
    # Error-rate SLO budget: the allowed fraction of requests finishing
    # error/timeout. Burn rate 1.0 = failing at exactly this rate; the
    # Google-SRE 5m/1h windows export as tpu_serve_slo_burn_rate gauges and
    # the L3 reconcile probe reads them off /healthz. 0 disables.
    slo_error_rate: float = 0.01
    # Flight-recorder anomaly spool: a directory for capped JSONL dumps of
    # anomalous request timelines (deadline expiry, shed, watchdog failure).
    # Empty = in-memory snapshots only (/debug/flight/<id> still serves the
    # recent ones). serving.yaml.j2 backs it with the pod's emptyDir.
    flight_spool_dir: str = ""
    # ---- Device telemetry (serving/devmon.py) ----
    # Roofline peaks the MFU/bandwidth gauges divide by. Defaults are the
    # v5e per-chip numbers from PERF.md (bf16 peak, HBM bandwidth); set them
    # per accelerator generation in group_vars (serving.yaml.j2 threads
    # --devmon-peak-tflops / --devmon-peak-hbm-gbps).
    devmon_enabled: bool = True
    devmon_peak_tflops: float = 197.0
    devmon_peak_hbm_gbps: float = 819.0
    # Live-vs-compiled HBM drift tolerance (MB): the /healthz verdict flips
    # to "warn" (never kills) when live occupancy exceeds the AOT ledger by
    # more than this.
    devmon_hbm_tolerance_mb: float = 64.0
    # ---- Capacity & saturation observatory (serving/capacity.py) ----
    # Headroom the recommended_replicas forecast buys, in seconds. The
    # shipped default is the AOT registry's measured ready-time
    # (BENCH_coldstart_r01 aot_ready_s ~= 5.5 s): a replica started the
    # moment the signal fires is serving before the projected demand lands.
    capacity_enabled: bool = True
    capacity_headroom_s: float = 5.5
    # Rate window (offered load, utilization) and the longer trend window
    # the EWMA + linear-trend saturation forecast fits over.
    capacity_window_s: float = 60.0
    capacity_trend_window_s: float = 300.0
    # ---- Fleet actuation (serving/autoscaler.py — runs in the ROUTER
    # process) ----
    # The reconcile controller that consumes the capacity signal: off by
    # default (the signal plane is always on; actuation is opt-in).
    autoscale_enabled: bool = False
    # Replica floor/ceiling. Floor 0 enables scale-to-zero: an idle fleet
    # parks behind the router and the first request cold-starts it
    # (AOT-backed, hidden by the prewarmed standby pool).
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 8
    # Prewarmed standbys kept ready OUT of rotation; -1 derives the size
    # from the AOT manifest ready-time (autoscale_ready_s).
    autoscale_standby: int = -1
    # Reconcile tick; hysteresis persistence a target change must survive
    # before committing; the direction-reversal cooldown (flap
    # suppression); and the idle window before scale-to-zero parks.
    autoscale_interval_s: float = 1.0
    autoscale_stable_s: float = 5.0
    autoscale_cooldown_s: float = 30.0
    autoscale_idle_timeout_s: float = 120.0
    # Launch admission: a spawned replica must answer /readyz within this
    # (default ~10x the 5.5 s AOT ready-time — a cold compile is a bug).
    autoscale_ready_timeout_s: float = 60.0
    # The measured AOT ready-time (BENCH_coldstart_r01) the standby size
    # and cold-start budget derive from.
    autoscale_ready_s: float = 5.5
    # Seed for the engine's DERIVED sampling seeds (requests without an
    # OpenAI ``seed``). None = entropy from os.urandom at engine start, so
    # restarts and replicas draw independently (the vLLM/OpenAI
    # nondeterministic default — ADVICE r3). Set an int for reproducible
    # harnesses (the dryrun parity run and tests pin 0).
    derived_seed: object = None
    dtype: str = "bfloat16"
    # KV-cache storage dtype: "auto" follows ``dtype``; "int8" stores K/V rows
    # quantized with per-(layer, slot, head, row) float32 scales — half the
    # decode HBM streaming and half the cache footprint (so ~2x the slots fit
    # beside the weights), at near-lossless attention accuracy. The vLLM
    # engine inside the reference's serving pods ships the same knob as
    # ``kv_cache_dtype``. See ops/kv_pool.py.
    kv_dtype: str = "auto"
    # Weight storage dtype. "int8" is the SHIPPED DEFAULT (r6): weights-only
    # per-out-channel quantization at engine start (models/quant.py) halves
    # the weight HBM stream — the dominant bytes/token term below batch ~64
    # (PERF.md roofline) — while compute stays bf16 on the MXU; the vLLM
    # engine inside the reference's pods ships this as ``--quantization``.
    # "bf16" (alias "auto") is the explicit full-precision opt-out for
    # accuracy-sensitive deployments and exact-parity harnesses.
    weights_dtype: str = "int8"
    # Decode kernel batch-block: slots sharing one grid step of the
    # double-buffered paged flash-decode kernel (BBx larger page DMAs, BBx
    # fewer grid steps — ops/pallas_attention._paged_db_body). 0 = autotune
    # at engine start: a one-shot deterministic microbench over {1, 4, 8}
    # per (batch, page_size, kv_dtype), cached process-wide, TPU-only (CPU
    # and meshes stay at 1). A positive value pins it (clamped to the
    # largest divisor of max_decode_slots); the PALLAS_DECODE_BBLOCK env var
    # overrides both for A/B sweeps.
    decode_bblock: int = 0
    # Attention backend: "xla" (fused SDPA fallback) or "pallas" (custom kernel).
    attention_impl: str = "auto"
    checkpoint_dir: str = ""
    # Draft model for spec_method="draft": a (small) HF checkpoint dir; the
    # server loads it unsharded beside the target (serving/draft.py).
    draft_checkpoint_dir: str = ""
    # Multi-LoRA (models/lora.py): ("name=path", ...) peft adapter dirs,
    # served as model ids beside the base (the vLLM --enable-lora contract).
    lora_adapters: tuple = ()
    chat_template: str = ""  # path to a .jinja file; empty = model family default
    mesh: MeshConfig = field(default_factory=MeshConfig)


# ---------------------------------------------------------------------------
# Deploy-layer config (the values the reference duplicated across playbooks)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DeployConfig:
    """Values consumed by deploy/*.yaml via `--ansible-vars` emission.

    Mirrors (TPU-retargeted) the reference's per-playbook vars blocks:
    kubernetes/CRI-O versions (kubernetes-single-node.yaml:6-17), namespaces
    (llm-d-deploy.yaml:114, otel-observability-setup.yaml:7-12), the served model
    (llm-d-deploy.yaml:113-118), gateway naming (llm-d-test.yaml:5-7).
    """

    # GCP / TPU provisioning (replaces AWS vars at launch-instance.yaml:6-13).
    gcp_project: str = "CHANGE-ME"
    gcp_zone: str = "us-east5-b"
    tpu_accelerator_type: str = "v5litepod-8"
    tpu_runtime_version: str = "v2-alpha-tpuv5-lite"
    tpu_name_prefix: str = "tpu-llm"
    # (No boot-disk knob: TPU-VM boot disks are fixed-size, unlike the
    # reference's 500 GB gp3 root volume at launch-instance.yaml:27-51; model
    # weights persist in the cluster's PVCs instead.)
    ssh_user: str = "ubuntu"
    # Networking (the reference documents its SG ports, README.md:84-93; a
    # GCP project without an allow-ssh rule hangs L1 at the SSH wait —
    # VERDICT r1 weak #7). L1 ensures this ingress rule exists. Narrow
    # ssh_source_ranges to your operator CIDR in production.
    gcp_network: str = "default"
    ssh_firewall_rule: str = "tpu-llm-allow-ssh"
    ssh_source_ranges: str = "0.0.0.0/0"
    # Cluster substrate (same shape as reference kubernetes-single-node.yaml:6-12).
    kubernetes_version: str = "1.33"
    crio_version: str = "1.33"
    pod_network_cidr: str = "192.168.0.0/16"
    # Serving stack. NOTE: the served model id and port live in ServingConfig (the
    # engine is the authority); ansible_vars() merges them in — no second copy here.
    serving_namespace: str = "tpu-serve"
    gateway_name: str = "tpu-inference-gateway"
    # Container image carrying this framework (engine + k8s runtime
    # components). Built ON the node by serving-deploy.yaml from the repo's
    # Dockerfile (podman; root podman shares /var/lib/containers/storage with
    # CRI-O, so the kubelet sees it without a registry) — the reference could
    # assume public vLLM images, we serve our own code.
    framework_image: str = "localhost/aws-k8s-ansible-provisioner-tpu:latest"
    serving_replicas: int = 1
    storage_class: str = "local-path"
    model_storage_gi: int = 100
    # Observability.
    otel_namespace: str = "otel-monitoring"
    observability_namespace: str = "observability"
    cluster_name: str = "tpu-cluster"
    metrics_scrape_interval_s: int = 5


@dataclass(frozen=True)
class FrameworkConfig:
    serving: ServingConfig = field(default_factory=ServingConfig)
    deploy: DeployConfig = field(default_factory=DeployConfig)


def ansible_vars(cfg: FrameworkConfig | None = None,
                 overrides: dict | None = None) -> str:
    """Render DeployConfig (+ shared serving values) as YAML for ansible extra-vars."""
    cfg = cfg or FrameworkConfig()
    d = dataclasses.asdict(cfg.deploy)
    # Values the deploy layer shares with the engine come FROM the engine config —
    # a single source, unlike the reference's duplicated literals (SURVEY.md §1).
    d["model"] = cfg.serving.model
    d["serving_port"] = cfg.serving.port
    # Serving mesh (chips per engine pod = tp * dp * ep; serving.yaml.j2
    # passes these to the engine CLI and sizes the google.com/tpu limit).
    d["serving_tp"] = cfg.serving.mesh.tp
    d["serving_dp"] = cfg.serving.mesh.dp
    d["serving_ep"] = cfg.serving.mesh.ep
    d["serving_kv_dtype"] = cfg.serving.kv_dtype
    d["serving_weights_dtype"] = cfg.serving.weights_dtype
    d["serving_spec_decode"] = cfg.serving.spec_decode
    # Decode pipeline depth (perf_opt r9): the manifest passes it to
    # --decode-pipeline so a fleet can A/B or pin the synchronous path.
    d["serving_decode_pipeline"] = cfg.serving.decode_pipeline
    # Ragged mixed-batch attention (ISSUE 14): threaded to
    # --ragged-attention so a fleet can A/B the one-program mixed path
    # against the legacy serialized chunk walk.
    d["serving_ragged_attention"] = cfg.serving.ragged_attention
    # Tier-2 KV host-RAM budget (ISSUE 20): threaded to
    # --kv-host-tier-bytes so a fleet can size (or zero out) the host
    # prefix-page store per pod shape from the same single source.
    d["serving_kv_host_tier_bytes"] = cfg.serving.kv_host_tier_bytes
    # Robustness knobs (r7): the manifests pass these to the engine CLI so
    # the deadline/admission behavior is deploy-configurable from the same
    # single source.
    d["serving_request_timeout_s"] = cfg.serving.request_timeout_s
    d["serving_max_queue_depth"] = cfg.serving.max_queue_depth
    # Replica lifecycle (r8): the preStop hook, terminationGracePeriodSeconds
    # and the engine's --drain-timeout all derive from this one knob.
    d["serving_drain_timeout_s"] = cfg.serving.drain_timeout_s
    # Request tracing: the manifest exports this as
    # OTEL_EXPORTER_OTLP_ENDPOINT on the engine and router containers.
    # Default = the deployed Tempo Service's own OTLP/HTTP receiver
    # (otel-observability-setup.yaml exposes 4318 on the ``tempo`` Service),
    # so spans light up the trace backend with no extra wiring.
    d["otlp_endpoint"] = (cfg.serving.otlp_endpoint
                          or f"http://tempo.{cfg.deploy.otel_namespace}"
                             ".svc.cluster.local:4318")
    d["serving_trace_sample"] = cfg.serving.trace_sample
    # SLO objectives + flight recorder (this PR): the manifest threads these
    # to --slo-ttft-p95-ms / --slo-error-rate / --flight-spool-dir.
    d["serving_slo_ttft_p95_ms"] = cfg.serving.slo_ttft_p95_ms
    d["serving_slo_error_rate"] = cfg.serving.slo_error_rate
    d["serving_flight_spool_dir"] = (cfg.serving.flight_spool_dir
                                     or "/tmp/tpu-serve-flight")
    # Device telemetry roofline peaks (serving/devmon.py): the manifest
    # threads these to --devmon-peak-tflops / --devmon-peak-hbm-gbps so the
    # tpu_device_* gauges divide by the right ceilings per TPU generation.
    d["serving_devmon_peak_tflops"] = cfg.serving.devmon_peak_tflops
    d["serving_devmon_peak_hbm_gbps"] = cfg.serving.devmon_peak_hbm_gbps
    # Capacity observatory (serving/capacity.py): the manifest threads these
    # to --capacity-headroom-s / --capacity-window-s so the scaling signal's
    # forecast horizon matches the deployment's measured AOT ready-time.
    d["serving_capacity_headroom_s"] = cfg.serving.capacity_headroom_s
    d["serving_capacity_window_s"] = cfg.serving.capacity_window_s
    # Fleet actuation (serving/autoscaler.py): the manifest threads these
    # to the router's --autoscale-* flags. In-cluster the controller
    # drains/undrains and adopts what the Deployment runs; the launch
    # command template is deliberately NOT set by default (kubernetes owns
    # pod creation — a CommandLauncher only makes sense on a bare host).
    d["serving_autoscale_enabled"] = cfg.serving.autoscale_enabled
    d["serving_autoscale_min_replicas"] = cfg.serving.autoscale_min_replicas
    d["serving_autoscale_max_replicas"] = cfg.serving.autoscale_max_replicas
    d["serving_autoscale_standby"] = cfg.serving.autoscale_standby
    d["serving_autoscale_interval_s"] = cfg.serving.autoscale_interval_s
    d["serving_autoscale_stable_s"] = cfg.serving.autoscale_stable_s
    d["serving_autoscale_cooldown_s"] = cfg.serving.autoscale_cooldown_s
    d["serving_autoscale_idle_timeout_s"] = \
        cfg.serving.autoscale_idle_timeout_s
    # --set overrides (rehearsals pin model/ports); unknown keys pass
    # through — the playbooks treat group_vars as an open namespace
    d.update(overrides or {})
    lines = ["# generated by aws_k8s_ansible_provisioner_tpu.config — do not edit"]
    for k, v in d.items():
        lines.append(f"{k}: {json.dumps(v)}")
    return "\n".join(lines) + "\n"


def render_manifest(path: str, **overrides) -> str:
    """Render a deploy/ Jinja manifest with the config vars — the ONE render
    pipeline shared by the CLI (--render-manifest, used by
    deploy/rehearse-kind.sh), the playbooks' var contract, and the tests
    (StrictUndefined: a typo'd var fails the render, not the cluster)."""
    import jinja2
    import yaml as _yaml

    vars_ = _yaml.safe_load(ansible_vars())
    vars_.update(overrides)
    env = jinja2.Environment(undefined=jinja2.StrictUndefined)
    with open(path) as f:
        return env.from_string(f.read()).render(**vars_)


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--ansible-vars", action="store_true",
                   help="emit deploy-layer vars as YAML")
    p.add_argument("--render-manifest", metavar="PATH",
                   help="render a deploy/ Jinja manifest with the config "
                        "vars (the kind rehearsal uses this — the SAME "
                        "single config source the playbooks consume)")
    p.add_argument("--set", action="append", default=[], metavar="K=V",
                   help="override a var for --render-manifest/--ansible-vars")
    args = p.parse_args()
    overrides = {}
    for kv in args.set:
        k, _, v = kv.partition("=")
        try:
            overrides[k] = json.loads(v)
        except (ValueError, TypeError):
            overrides[k] = v
    if args.render_manifest:
        print(render_manifest(args.render_manifest, **overrides))
    elif args.ansible_vars:
        print(ansible_vars(overrides=overrides), end="")
    else:
        print(json.dumps(dataclasses.asdict(FrameworkConfig()), indent=2, default=str))
