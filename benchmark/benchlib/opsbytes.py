"""Operations and bytes a kernel or a step NEEDS, from shapes alone (the
yardstick's side of every roofline share; ``bench.py::_roofline``'s
arithmetic, copied here so a later PR cannot change it with the program).

All figures are the algorithm's minimum, not what an implementation happens
to move: a paged kernel that fetches whole pages moves more than this, and
its roofline share says so.
"""

from __future__ import annotations


def kv_bytes_per_token_layer(mc: dict, kv_itemsize: int = 2) -> int:
    """K and V rows of one context token in ONE layer."""
    return 2 * mc["num_kv_heads"] * mc["head_dim"] * kv_itemsize


def kv_bytes_per_token(mc: dict, kv_itemsize: int = 2) -> int:
    """All layers: 114,688 B for Qwen3-0.6B, 147,456 B for Qwen3-8B (bf16)."""
    return mc["num_layers"] * kv_bytes_per_token_layer(mc, kv_itemsize)


def decode_attention_call(mc: dict, sum_ctx_tokens: float, batch: int,
                          kv_itemsize: int = 2, chips: int = 1) -> tuple:
    """(flops, bytes) of ONE decode-attention call (one layer, one step) over
    a batch whose context lengths sum to ``sum_ctx_tokens``, per chip (kv
    heads shard over ``chips``). Bytes: every live K/V row once, plus q in
    and out rows (bf16). Flops: q.k and p.v, 2 flops per multiply-add."""
    hq, d = mc["num_heads"], mc["head_dim"]
    byts = (sum_ctx_tokens * kv_bytes_per_token_layer(mc, kv_itemsize)
            + 2 * batch * hq * d * 2) / chips
    flops = 4.0 * sum_ctx_tokens * hq * d / chips
    return flops, byts


def weight_bytes(mc: dict, itemsize: int) -> int:
    """Kernels of the dense block + embedding (+ untied head)."""
    h, inter, L = mc["hidden_size"], mc["intermediate_size"], mc["num_layers"]
    q = mc["num_heads"] * mc["head_dim"]
    kv = mc["num_kv_heads"] * mc["head_dim"]
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * inter
    emb = mc["vocab_size"] * h * (1 if mc.get("tie_embeddings") else 2)
    return (L * per_layer + emb) * itemsize


def decode_step_bytes(mc: dict, w_itemsize: int, sum_ctx_tokens: float,
                      kv_itemsize: int = 2) -> float:
    """HBM bytes one decode step needs: every weight once + every live KV
    row once (bench.py: bytes/token = weights/batch + ctx x KV row bytes)."""
    return weight_bytes(mc, w_itemsize) \
        + sum_ctx_tokens * kv_bytes_per_token(mc, kv_itemsize)


def roofline_seconds(flops: float, byts: float, peaks: dict) -> tuple:
    """(least seconds, which bound) against the published peaks."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = byts / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f > t_b else (t_b, "bandwidth")
