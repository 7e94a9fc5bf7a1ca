"""What the two kinds of attention of a Trinity-shaped list NEED in a decode
dispatch — window ("w") layers that read the last ``sliding_window`` keys of
a row, full ("g") layers that read them all — and how a reader finds each
kind's kernel calls in a trace (a new file beside ``sala_opsbytes.py``;
``need_and_time`` is ``kda_opsbytes``'s).

A decode row of one layer reads the pages it HOLDS for that layer's kind —
the record's ``attn_pages_live`` (full layers; per full layer, summed over
the record's substeps and slots) or ``win_pages_live`` (window layers: the
pages inside the row's window) — each ``page x head_dim`` K rows and as many
V rows a KV head in bf16, beside the rows' q in and o out. 4 flops a K/V
element read for each of the ``groups`` query heads of a KV head (q.k and
p.v): 16 flops a byte at 8 heads a group, under the v5e's ridge of 240, so
the bound is bytes over the peak HBM bandwidth. What the walk adds (a block
of 8 rows walks its longest row's pages for all 8: ``*_pages_walked``) is
NOT need: it is what the share of the roofline loses.

The device trace names a Pallas call after the jitted wrapper that makes
it: a window layer's decode calls are ``decode_attend_pallas_paged_window``
and a full layer's ``decode_attend_pallas_paged`` (ops/pallas_attention.py;
tests/test_tpu_compile.py pins both).
"""

from __future__ import annotations

from benchlib import kda_opsbytes, moe_opsbytes

WINDOW_KERNEL_RE = r"^%decode_attend_pallas_paged_window"
# (not the window wrapper, not the selecting entries)
FULL_KERNEL_RE = r"^%decode_attend_pallas_paged(?![_\w])"
KINDS = {"window": ("w", WINDOW_KERNEL_RE, "win_pages_live",
                    "attn_layers_window"),
         "full": ("g", FULL_KERNEL_RE, "attn_pages_live",
                  "attn_layers_full")}


def has_both_kinds(mc: dict) -> bool:
    pat = mc.get("layer_pattern", "")
    return "w" in pat and "g" in pat


def attn_decode_dispatch(mc: dict, rec: dict, kind: str, page: int,
                         slots: int) -> tuple:
    """(flops, bytes) the attention READS of one kind's layers in one decode
    dispatch need, all its layers and substeps, from the record."""
    _, _, field, layers_field = KINDS[kind]
    layers = rec[layers_field]
    hq, hkv, d = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    kv = rec[field] * layers * 2 * hkv * page * d * 2
    rows = slots * max(1, int(rec.get("horizon", 1)))
    qo = rows * layers * 2 * hq * d * 2
    return 4.0 * kv / 2 * (hq // hkv), float(kv + qo)


def need_and_time_of(ctx, kind: str) -> tuple:
    """(need seconds, device seconds) of one kind's decode attention over
    the joined ``decode_steps`` executions of the traced slice."""
    _, kernel_re, field, _ = KINDS[kind]
    page, slots = ctx.engine["page_size"], ctx.engine["slots"]
    return kda_opsbytes.need_and_time(
        ctx, kernel_re, field,
        lambda rec: attn_decode_dispatch(ctx.mc, rec, kind, page, slots))


def _routed(mc: dict) -> dict:
    """``mc`` as ``moe_opsbytes`` wants it for a model whose leading
    ``num_dense_layers`` are dense: the ROUTED layers are the ones that hold
    an expert stack (its leading axis) and that a dispatch's rows pass."""
    return dict(mc, num_layers=mc["num_layers"]
                - mc.get("num_dense_layers", 0))


def experts_need_and_time(ctx) -> tuple:
    """(need seconds, device seconds) of the routed experts over the joined
    ``decode_steps`` executions of the traced slice: the need is
    ``moe_opsbytes.decode_dispatch``'s over the routed layers (the record's
    ``moe_rows`` through three matmuls; the stacks of the
    ``moe_experts_hit`` experts once a layer and substep, their scales, the
    rows in and out — the shared expert and the router are not in it), the
    time that of the operations with an expert stack among their operands
    (``moe_opsbytes.expert_ops_re``, the stack's leading axis the routed
    layers)."""
    mc = _routed(ctx.mc)
    return kda_opsbytes.need_and_time(
        ctx, moe_opsbytes.expert_ops_re(mc), "moe_experts_hit",
        lambda rec: moe_opsbytes.decode_dispatch(
            mc, rec, ctx.engine["w_itemsize"]))
