"""HuggingFace checkpoint → JAX param-pytree conversion.

The reference downloads model weights once via its external installer
(``llm-d-deploy.yaml:184`` ``--download-model Qwen/Qwen3-0.6B``) into a PVC and lets
vLLM do the loading. In the TPU build, loading is in-repo: safetensors →
``models/layers.py`` layout (``[in, out]`` kernels, stacked ``[L, ...]`` layer
axes), optionally placed shard-by-shard onto a ``jax.sharding.Mesh`` so an 8B
checkpoint never materializes unsharded on one host (SURVEY.md §7 hard part #3).

Key-name maps cover the supported families:
- Qwen3*: ``model.layers.N.self_attn.{q,k,v,o}_proj``, ``q_norm``/``k_norm``,
  gated ``mlp.{gate,up,down}_proj``, RMSNorm weights.
- Qwen3-MoE and OLMoE: router ``mlp.gate``, experts
  ``mlp.experts.{e}.{gate,up,down}_proj``; OLMoE's ``q_norm``/``k_norm``
  span the whole projection (``[q_size]`` / ``[kv_size]``).
- Phi-2: ``self_attn.dense``, ``mlp.fc1/fc2`` with biases, LayerNorm
  weight+bias, ``lm_head`` with bias, no post-attention norm (parallel block).
- LFM2-MoE (``_convert_lfm2``; the names are from memory of
  ``transformers``' lfm2_moe and hold until a checkpoint says otherwise):
  ``conv.in_proj`` / ``conv.conv.weight`` ``[H, 1, K]`` / ``conv.out_proj``,
  ``self_attn.{q,k,v}_proj`` / ``out_proj`` / ``q_layernorm`` /
  ``k_layernorm``, ``operator_norm`` / ``ffn_norm``, dense
  ``feed_forward.{w1,w2,w3}``, routed ``feed_forward.gate`` /
  ``expert_bias`` / ``experts.N.{w1,w2,w3}``, ``embedding_norm``.
- Falcon-H1 (``_convert_falcon_h1``; the names are from memory of
  ``transformers``' falcon_h1 and UNTESTED until a checkpoint is in the
  repository): ``mamba.in_proj`` / ``mamba.conv1d.weight`` ``[C, 1, K]`` +
  ``.bias`` / ``mamba.dt_bias`` / ``mamba.A_log`` / ``mamba.D`` /
  ``mamba.norm`` / ``mamba.out_proj``, ``self_attn.{q,k,v,o}_proj``,
  ``feed_forward.{gate,up,down}_proj``, ``input_layernorm`` /
  ``pre_ff_layernorm``, ``final_layernorm``.
- OPT (pre-norm variants): ``model.decoder.layers.N.self_attn.*_proj``,
  ``self_attn_layer_norm``/``final_layer_norm``, ``fc1/fc2``, learned
  ``embed_positions`` (+2 offset), tied embeddings.
"""

from __future__ import annotations

import json
import os
import re
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from aws_k8s_ansible_provisioner_tpu.config import ModelConfig


def _np(x):
    """torch tensor / np array -> float32 numpy (bf16-safe)."""
    if hasattr(x, "detach"):
        x = x.detach().to("cpu")
        try:
            import torch

            if x.dtype == torch.bfloat16:
                x = x.float()
        except Exception:
            pass
        x = x.numpy()
    return np.asarray(x)


def _get(tensors: Dict[str, "np.ndarray"], key: str) -> np.ndarray:
    if key not in tensors:
        raise KeyError(f"missing weight {key!r}; have e.g. "
                       f"{sorted(tensors)[:8]} ...")
    return _np(tensors[key])


def convert_state_dict(cfg: ModelConfig, tensors: Dict[str, np.ndarray],
                       dtype=jnp.bfloat16) -> dict:
    """Convert a flat HF state dict (torch tensors or numpy) to our pytree."""
    if "c" in cfg.layer_pattern:
        return _convert_lfm2(cfg, tensors, dtype)
    if "h" in cfg.layer_pattern:
        return _convert_falcon_h1(cfg, tensors, dtype)
    phi = cfg.parallel_block
    L = cfg.num_layers

    def stack(fmt: str, transpose: bool) -> np.ndarray:
        mats = []
        for i in range(L):
            w = _get(tensors, fmt.format(i=i))
            mats.append(w.T if transpose else w)
        return np.stack(mats)

    opt = cfg.pos_embed == "learned"
    if opt:
        # Hub facebook/opt-* safetensors carry bare "decoder.*" keys (exported
        # from the base OPTModel), while OPTForCausalLM.state_dict() carries
        # "model.decoder.*". Normalize to the latter so both load.
        if ("model.decoder.embed_tokens.weight" not in tensors
                and "decoder.embed_tokens.weight" in tensors):
            tensors = {("model." + k if k.startswith("decoder.") else k): v
                       for k, v in tensors.items()}
        layer_pre = "model.decoder.layers.{i}."
        pre = layer_pre + "self_attn."
        o_name, up_name, down_name = "out_proj", "fc1", "fc2"
        input_norm = layer_pre + "self_attn_layer_norm"
        post_norm = layer_pre + "final_layer_norm"
        final_norm = "model.decoder.final_layer_norm"
        embed_key = "model.decoder.embed_tokens.weight"
    elif phi:
        layer_pre = "model.layers.{i}."
        pre = layer_pre + "self_attn."
        o_name, up_name, down_name = "dense", "mlp.fc1", "mlp.fc2"
        input_norm = layer_pre + "input_layernorm"
        post_norm = layer_pre + "post_attention_layernorm"
        final_norm = "model.final_layernorm"
        embed_key = "model.embed_tokens.weight"
    else:
        layer_pre = "model.layers.{i}."
        pre = layer_pre + "self_attn."
        o_name, up_name, down_name = "o_proj", "mlp.up_proj", "mlp.down_proj"
        input_norm = layer_pre + "input_layernorm"
        post_norm = layer_pre + "post_attention_layernorm"
        final_norm = "model.norm"
        embed_key = "model.embed_tokens.weight"

    def dense(hf_fmt: str, bias: bool) -> dict:
        p = {"kernel": stack(hf_fmt + ".weight", transpose=True)}
        if bias:
            p["bias"] = stack(hf_fmt + ".bias", transpose=False)
        return p

    def norm(hf_fmt: str) -> dict:
        p = {"weight": stack(hf_fmt + ".weight", transpose=False)}
        if cfg.norm == "layernorm":
            p["bias"] = stack(hf_fmt + ".bias", transpose=False)
        return p

    def stack_experts(proj: str) -> np.ndarray:
        """Stack HF per-expert Linears into [L, E, in, out] (transposed).

        Assigns expert-by-expert into a preallocated TARGET-dtype array so
        peak host memory is the final stacked leaf plus ONE expert matrix —
        a naive np.stack of float32 intermediates would transiently need
        ~2x-4x the checkpoint (116 GB for Qwen3-30B-A3B vs ~58 GB here).
        """
        first = _get(tensors,
                     layer_pre.format(i=0) + f"mlp.experts.0.{proj}.weight")
        out = np.empty((L, cfg.num_experts) + first.T.shape, jnp.dtype(dtype))
        for i in range(L):
            for e in range(cfg.num_experts):
                w = _get(tensors, layer_pre.format(i=i)
                         + f"mlp.experts.{e}.{proj}.weight")
                out[i, e] = w.T.astype(out.dtype)
        return out

    layers: dict = {
        "input_norm": norm(input_norm),
        "wq": dense(pre + "q_proj", cfg.attention_bias),
        "wk": dense(pre + "k_proj", cfg.attention_bias),
        "wv": dense(pre + "v_proj", cfg.attention_bias),
        "wo": dense(pre + o_name, cfg.attention_bias),
    }
    if cfg.num_experts > 0:
        # Qwen3-MoE: router = mlp.gate [E, H] → [H, E]; experts stacked.
        layers["router"] = {"kernel": stack(layer_pre + "mlp.gate.weight",
                                            transpose=True)}
        layers["w_gate"] = {"kernel": stack_experts("gate_proj")}
        layers["w_up"] = {"kernel": stack_experts("up_proj")}
        layers["w_down"] = {"kernel": stack_experts("down_proj")}
    elif cfg.gated_mlp:  # SwiGLU (Qwen/Llama) / GeGLU (Gemma): same HF names
        layers["w_gate"] = dense(layer_pre + "mlp.gate_proj", cfg.mlp_bias)
        layers["w_up"] = dense(layer_pre + "mlp.up_proj", cfg.mlp_bias)
        layers["w_down"] = dense(layer_pre + down_name, cfg.mlp_bias)
    else:
        layers["w_up"] = dense(layer_pre + up_name, cfg.mlp_bias)
        layers["w_down"] = dense(layer_pre + down_name, cfg.mlp_bias)
    if cfg.qk_norm:
        layers["q_norm"] = {"weight": stack(pre + "q_norm.weight", False)}
        layers["k_norm"] = {"weight": stack(pre + "k_norm.weight", False)}
    if not cfg.parallel_block:
        layers["post_norm"] = norm(post_norm)

    params: dict = {
        "embed": {"weight": _get(tensors, embed_key)},
        "layers": layers,
        "final_norm": {"weight": _get(tensors, final_norm + ".weight")},
    }
    if opt:
        params["pos_embed"] = {
            "weight": _get(tensors, "model.decoder.embed_positions.weight")}
    if cfg.norm == "layernorm":
        params["final_norm"]["bias"] = _get(tensors, final_norm + ".bias")
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": _get(tensors, "lm_head.weight").T}
        if "lm_head.bias" in tensors:
            params["lm_head"]["bias"] = _get(tensors, "lm_head.bias")

    return jax.tree.map(lambda x: jnp.asarray(x, dtype), params)


def _convert_lfm2(cfg: ModelConfig, tensors: Dict[str, np.ndarray],
                  dtype) -> dict:
    """The LFM2-MoE list: one stack a kind (``conv``, ``attn``) and one a
    FFN kind (``ffn_dense`` the leading ``num_dense_layers``, ``ffn_moe`` the
    rest), each in layer order (models/layers.init_list_layer_params). w1 is
    the gate, w3 the up and w2 the down projection; the depthwise
    convolution's ``[H, 1, K]`` weight becomes taps ``[K, H]``, the oldest
    row's first, as torch's causal Conv1d orders them."""
    nd = cfg.num_dense_layers
    of_kind = {k: [i for i, c in enumerate(cfg.layer_pattern) if c == k]
               for k in "cg"}

    def stack(layers, name: str, transpose: bool = False) -> np.ndarray:
        mats = [_get(tensors, f"model.layers.{i}.{name}") for i in layers]
        return np.stack([m.T if transpose else m for m in mats])

    def dense(layers, name: str) -> dict:
        return {"kernel": stack(layers, name + ".weight", transpose=True)}

    def norms(layers) -> dict:
        return {"input_norm": {"weight": stack(layers,
                                               "operator_norm.weight")},
                "post_norm": {"weight": stack(layers, "ffn_norm.weight")}}

    def experts(layers, proj: str) -> np.ndarray:
        first = _get(tensors, f"model.layers.{layers[0]}.feed_forward."
                              f"experts.0.{proj}.weight")
        out = np.empty((len(layers), cfg.num_experts) + first.T.shape,
                       jnp.dtype(dtype))
        for n, i in enumerate(layers):
            for e in range(cfg.num_experts):
                out[n, e] = _get(
                    tensors, f"model.layers.{i}.feed_forward.experts.{e}."
                             f"{proj}.weight").T.astype(out.dtype)
        return out

    conv, attn = of_kind["c"], of_kind["g"]
    routed = list(range(nd, cfg.num_layers))
    layers = {
        "conv": {**norms(conv),
                 "w_in": dense(conv, "conv.in_proj"),
                 "conv": {"weight": np.stack(
                     [_get(tensors, f"model.layers.{i}.conv.conv.weight")
                      [:, 0, :].T for i in conv])},
                 "wo": dense(conv, "conv.out_proj")},
        "attn": {**norms(attn),
                 "wq": dense(attn, "self_attn.q_proj"),
                 "wk": dense(attn, "self_attn.k_proj"),
                 "wv": dense(attn, "self_attn.v_proj"),
                 "wo": dense(attn, "self_attn.out_proj"),
                 "q_norm": {"weight": stack(
                     attn, "self_attn.q_layernorm.weight")},
                 "k_norm": {"weight": stack(
                     attn, "self_attn.k_layernorm.weight")}},
        "ffn_dense": {"w_gate": dense(range(nd), "feed_forward.w1"),
                      "w_up": dense(range(nd), "feed_forward.w3"),
                      "w_down": dense(range(nd), "feed_forward.w2")},
        "ffn_moe": {"router": {"kernel": stack(
                        routed, "feed_forward.gate.weight", transpose=True)},
                    "w_gate": {"kernel": experts(routed, "w1")},
                    "w_up": {"kernel": experts(routed, "w3")},
                    "w_down": {"kernel": experts(routed, "w2")}}}
    params = {"embed": {"weight": _get(tensors, "model.embed_tokens.weight")},
              "layers": layers,
              "final_norm": {"weight": _get(
                  tensors, "model.embedding_norm.weight")}}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": _get(tensors, "lm_head.weight").T}
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype), params)
    # the selection bias stays float32 (models/quant.py leaves it so too)
    params["layers"]["ffn_moe"]["router"]["bias"] = jnp.asarray(
        stack(routed, "feed_forward.expert_bias"), jnp.float32)
    return params


def _convert_falcon_h1(cfg: ModelConfig, tensors: Dict[str, np.ndarray],
                       dtype) -> dict:
    """The Falcon-H1 list: one stack ``par`` of the layers held (a pipeline
    stage holds the FIRST ``num_layers`` published layers), the state-space
    mixer's leaves under ``ssm`` (models/layers.init_list_layer_params). The
    depthwise convolution's ``[C, 1, K]`` weight becomes taps ``[K, C]``, the
    oldest row's first; A_log, dt_bias and D stay float32."""
    L = range(cfg.num_layers)

    def stack(name: str, f=lambda m: m) -> np.ndarray:
        return np.stack([f(_get(tensors, f"model.layers.{i}.{name}"))
                         for i in L])

    def dense(name: str) -> dict:
        return {"kernel": stack(name + ".weight", lambda m: m.T)}

    def norm(name: str) -> dict:
        return {"weight": stack(name + ".weight")}

    par = {"input_norm": norm("input_layernorm"),
           "post_norm": norm("pre_ff_layernorm"),
           "wq": dense("self_attn.q_proj"), "wk": dense("self_attn.k_proj"),
           "wv": dense("self_attn.v_proj"), "wo": dense("self_attn.o_proj"),
           "w_gate": dense("feed_forward.gate_proj"),
           "w_up": dense("feed_forward.up_proj"),
           "w_down": dense("feed_forward.down_proj"),
           "ssm": {"w_in": dense("mamba.in_proj"),
                   "conv": {"weight": stack("mamba.conv1d.weight",
                                            lambda m: m[:, 0, :].T),
                            "bias": stack("mamba.conv1d.bias")},
                   "o_norm": norm("mamba.norm"),
                   "wo": dense("mamba.out_proj")}}
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype), {
        "embed": {"weight": _get(tensors, "model.embed_tokens.weight")},
        "layers": {"par": par},
        "final_norm": {"weight": _get(tensors,
                                      "model.final_layernorm.weight")},
        "lm_head": {"kernel": _get(tensors, "lm_head.weight").T}})
    for name in ("dt_bias", "A_log", "D"):
        params["layers"]["par"]["ssm"][name] = jnp.asarray(
            stack("mamba." + name), jnp.float32)
    return params


def load_checkpoint(
    checkpoint_dir: str,
    cfg: ModelConfig,
    dtype=jnp.bfloat16,
    device_put: Optional[Callable[[str, jnp.ndarray], jnp.ndarray]] = None,
) -> dict:
    """Load all ``*.safetensors`` shards from a HF checkpoint directory.

    ``device_put(path, arr)`` optionally places each converted leaf (path is the
    pytree path string) — used by ``parallel.sharding`` to stream shards onto the
    mesh without a full host-side copy of the assembled model.
    """
    from safetensors.numpy import load_file

    tensors: Dict[str, np.ndarray] = {}
    files = sorted(
        f for f in os.listdir(checkpoint_dir) if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files in {checkpoint_dir}")
    for f in files:
        tensors.update(load_file(os.path.join(checkpoint_dir, f)))
    params = convert_state_dict(cfg, tensors, dtype)
    if device_put is not None:
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        placed = [device_put(jax.tree_util.keystr(path), leaf)
                  for path, leaf in flat]
        params = jax.tree_util.tree_unflatten(treedef, placed)
    return params


def config_from_hf_dir(checkpoint_dir: str) -> ModelConfig:
    """Build a ModelConfig from a checkpoint's config.json (registry fallback)."""
    from aws_k8s_ansible_provisioner_tpu.config import MODEL_REGISTRY

    with open(os.path.join(checkpoint_dir, "config.json")) as fh:
        hf = json.load(fh)
    name = hf.get("_name_or_path") or os.path.basename(checkpoint_dir.rstrip("/"))
    # Exact registry match only — fuzzy matching could bind e.g. a 'qwen3' dir of
    # 8B weights to the 0.6B entry; config.json is the authority otherwise.
    if name in MODEL_REGISTRY:
        return MODEL_REGISTRY[name]
    model_type = hf.get("model_type", "")
    if model_type == "qwen3_moe":
        if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
            raise ValueError("qwen3_moe variants with dense layers mixed in "
                             "(mlp_only_layers/decoder_sparse_step) are not "
                             "supported")
        return ModelConfig(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim",
                            hf["hidden_size"] // hf["num_attention_heads"]),
            max_seq_len=hf.get("max_position_embeddings", 4096),
            rope_theta=hf.get("rope_theta", 1e6),
            qk_norm=True,
            norm_eps=hf.get("rms_norm_eps", 1e-6),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            eos_token_id=(hf.get("eos_token_id") or 0),
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            norm_topk_prob=hf.get("norm_topk_prob", True),
            hf_repo=name,
        )
    if model_type == "olmoe":
        # allenai OLMoE: every layer sparse, ``intermediate_size`` is the
        # width of ONE expert, q/k RMSNorm over the whole projection, top-k
        # weights as the softmax gives them unless norm_topk_prob says so.
        # Weight names are Qwen3-MoE's (mlp.gate, mlp.experts.{e}.*_proj,
        # self_attn.{q,k}_norm at [q_size] / [kv_size]).
        if hf.get("clip_qkv") is not None:
            raise ValueError("olmoe checkpoints with clip_qkv set are not "
                             "supported")
        return ModelConfig(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads")
            or hf["num_attention_heads"],
            head_dim=hf["hidden_size"] // hf["num_attention_heads"],
            max_seq_len=hf.get("max_position_embeddings", 4096),
            rope_theta=hf.get("rope_theta", 10000.0),
            qk_norm=True,
            qk_norm_span="projection",
            norm_eps=hf.get("rms_norm_eps", 1e-5),
            attention_bias=hf.get("attention_bias", False),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            bos_token_id=hf.get("bos_token_id"),
            eos_token_id=(hf.get("eos_token_id") or 0),
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["intermediate_size"],
            norm_topk_prob=hf.get("norm_topk_prob", False),
            hf_repo=name,
        )
    if model_type == "qwen3":
        return ModelConfig(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim", hf["hidden_size"] // hf["num_attention_heads"]),
            max_seq_len=hf.get("max_position_embeddings", 4096),
            rope_theta=hf.get("rope_theta", 1e6),
            qk_norm=True,
            norm_eps=hf.get("rms_norm_eps", 1e-6),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            eos_token_id=(hf.get("eos_token_id") or 0),
            hf_repo=name,
        )
    if model_type == "llama":
        rs = hf.get("rope_scaling") or {}
        rs_type = rs.get("rope_type") or rs.get("type") or "none"
        if rs_type not in ("none", "llama3", "default"):
            raise ValueError(f"unsupported llama rope_scaling type {rs_type!r}")
        eos = hf.get("eos_token_id") or 0
        eos_list = eos if isinstance(eos, list) else [eos]
        return ModelConfig(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads") or hf["num_attention_heads"],
            head_dim=hf.get("head_dim") or
            hf["hidden_size"] // hf["num_attention_heads"],
            max_seq_len=hf.get("max_position_embeddings", 4096),
            rope_theta=hf.get("rope_theta", 10000.0),
            rope_scaling="llama3" if rs_type == "llama3" else "none",
            rope_factor=float(rs.get("factor", 1.0)),
            rope_low_freq_factor=float(rs.get("low_freq_factor", 1.0)),
            rope_high_freq_factor=float(rs.get("high_freq_factor", 4.0)),
            rope_original_max_pos=int(
                rs.get("original_max_position_embeddings", 8192)),
            norm_eps=hf.get("rms_norm_eps", 1e-5),
            attention_bias=hf.get("attention_bias", False),
            mlp_bias=hf.get("mlp_bias", False),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            bos_token_id=hf.get("bos_token_id"),
            # Llama-3 Instruct declares a LIST of eos ids; generation must
            # stop on ANY of them (chat turns end with <|eot_id|>, which is
            # NOT the first entry) — the engine checks the whole set.
            eos_token_id=eos_list[0],
            extra_eos_token_ids=tuple(eos_list[1:]),
            hf_repo=name,
        )
    if model_type == "mistral":
        eos = hf.get("eos_token_id") or 2
        eos_list = eos if isinstance(eos, list) else [eos]
        return ModelConfig(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads", 8),
            head_dim=hf.get("head_dim") or
            hf["hidden_size"] // hf["num_attention_heads"],
            max_seq_len=hf.get("max_position_embeddings", 32768),
            # v0.1 checkpoints declare 4096; v0.3+ set null (full attention)
            sliding_window=int(hf.get("sliding_window") or 0),
            rope_theta=hf.get("rope_theta", 10000.0),
            norm_eps=hf.get("rms_norm_eps", 1e-5),
            tie_embeddings=hf.get("tie_word_embeddings", False),
            bos_token_id=hf.get("bos_token_id", 1),
            eos_token_id=eos_list[0],
            extra_eos_token_ids=tuple(eos_list[1:]),
            hf_repo=name,
        )
    if model_type == "gemma":
        return ModelConfig(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads", 1),
            head_dim=hf.get("head_dim",
                            hf["hidden_size"] // hf["num_attention_heads"]),
            max_seq_len=hf.get("max_position_embeddings", 8192),
            rope_theta=hf.get("rope_theta", 10000.0),
            norm_eps=hf.get("rms_norm_eps", 1e-6),
            norm_zero_centered=True,
            embed_scale=True,
            act="gelu_tanh",
            tie_embeddings=hf.get("tie_word_embeddings", True),
            bos_token_id=hf.get("bos_token_id", 2),
            eos_token_id=(hf.get("eos_token_id") or 1),
            hf_repo=name,
        )
    if model_type == "phi":
        head_dim = hf["hidden_size"] // hf["num_attention_heads"]
        return ModelConfig(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads") or hf["num_attention_heads"],
            head_dim=head_dim,
            max_seq_len=hf.get("max_position_embeddings", 2048),
            rope_theta=hf.get("rope_theta", 10000.0),
            rotary_pct=hf.get("partial_rotary_factor", 0.4),
            norm="layernorm",
            norm_eps=hf.get("layer_norm_eps", 1e-5),
            act="gelu_new",
            attention_bias=True,
            mlp_bias=True,
            parallel_block=True,
            eos_token_id=(hf.get("eos_token_id") or 0),
            hf_repo=name,
        )
    if model_type == "opt":
        if hf.get("word_embed_proj_dim", hf["hidden_size"]) != hf["hidden_size"]:
            raise ValueError("OPT variants with embed projection (350m) are "
                             "not supported")
        if not hf.get("do_layer_norm_before", True):
            raise ValueError("post-norm OPT variants are not supported")
        head_dim = hf["hidden_size"] // hf["num_attention_heads"]
        return ModelConfig(
            name=name,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["ffn_dim"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_attention_heads"],
            head_dim=head_dim,
            max_seq_len=hf.get("max_position_embeddings", 2048),
            norm="layernorm",
            norm_eps=1e-5,
            act="relu",
            pos_embed="learned",
            attention_bias=True,
            mlp_bias=True,
            tie_embeddings=hf.get("tie_word_embeddings", True),
            bos_token_id=hf.get("bos_token_id", 2),
            eos_token_id=(hf.get("eos_token_id") or 2),
            hf_repo=name,
        )
    raise ValueError(f"unsupported model_type {model_type!r} in {checkpoint_dir}")


def download_snapshot(model: str, dest: str) -> str:
    """Download a model's safetensors snapshot from HF Hub into ``dest``.

    CLI mode used by the deploy layer's model-download Job (deploy/manifests/
    serving.yaml.j2), the in-repo replacement for the reference's
    ``llmd-installer.sh --download-model`` (reference llm-d-deploy.yaml:184).
    Auth comes from the HF_TOKEN env var, injected from a K8s Secret — never a
    command-line argument (fixes the exposure at reference llm-d-deploy.yaml:178).
    """
    import os
    from huggingface_hub import snapshot_download

    target = os.path.join(dest, model)
    os.makedirs(target, exist_ok=True)
    path = snapshot_download(
        repo_id=model,
        local_dir=target,
        token=os.environ.get("HF_TOKEN") or None,
        allow_patterns=["*.safetensors", "*.json", "*.txt", "*.jinja", "*.model"],
    )
    return path


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description="HF checkpoint downloader/converter")
    ap.add_argument("--model", required=True, help="HF repo id, e.g. Qwen/Qwen3-0.6B")
    ap.add_argument("--download-to", required=True, help="directory to place <model>/")
    args = ap.parse_args()
    out = download_snapshot(args.model, args.download_to)
    print(f"downloaded {args.model} -> {out}")
