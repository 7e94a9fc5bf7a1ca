"""What the three mechanisms of an LFM2-shaped list NEED in a decode
dispatch — gated short convolutions over a per-slot tail, attention over
64-wide heads, experts at a WIDE batch (16 rows an expert) — and how a reader
finds each one's operations in a trace (a new file beside
``trinity_opsbytes.py``; the joins are ``kda_opsbytes.need_and_time``'s and
``op_parts.by_execution``'s).

- The convolution: what the ``recur`` part's operations move through HBM,
  read off the compiled ``decode_steps`` (a deviceless compile for the
  described chip, PR 42; ``tests/test_tpu_compile.py`` holds the list). A
  conv layer's substep is three ``recur`` fusions and the taps' slice: (1)
  the gate ``z = B * X`` reads W_in's matmul output ``f32[slots, 3 hidden]``
  and writes ``f32[slots, 1, hidden]`` — both in the compiler's fast memory
  space (``S(1)``), no HBM byte; (2) the tail's dynamic slice reads ``f32[
  slots, K - 1, hidden]`` of the ``conv_tail`` leaf from HBM; (3) the tail's
  in-place update writes as many back; the taps are ``bf16[K, hidden]`` a
  layer. ``C * conv`` and the row handed on are no operation of their own:
  they are fused into ``attn.out``'s matmul. So the need is the tail read
  and written, 2 x (K - 1) x hidden x 4 B a live slot, plus the taps once a
  layer and substep — no B, C, X row and no output row (ISSUE 42's formula
  counted both and read 114.8 %; the first repair dropped B, C, X on an
  argument and kept the output row: 84.9 %; my chip runs, PR 42). 9 flops an
  element: bytes over the peak HBM bandwidth bound it. Its operations are
  the ``recur`` part of ``decode_steps`` (models/parts.py) and nothing else
  carries that part in this model.
- The attention: ``trinity_opsbytes.attn_decode_dispatch``'s count for the
  record's ``attn_pages_live`` over the attention layers — each page ``page x
  head_dim`` K rows and as many V rows a KV head in bf16 (131,072 B at 8 KV
  heads of 64: the pool holds two heads a 128-lane row, which moves no byte
  of the count), q in and o out. The kernel's calls are
  ``decode_attend_pallas_paged`` under its plain name.
- The experts: ``moe_opsbytes.decode_dispatch`` over the ROUTED layers — the
  routed rows through three matmuls, the stacks of the experts hit once a
  layer and substep — and max(flops / peak, bytes / peak bandwidth) of it:
  at 512 (token, expert) rows a layer the ROUTED rows are bandwidth-bound
  (0.43 ms of stacks against 0.06 ms of flops); the every-expert form
  computes eight times the flops (0.46 ms), which is what the share loses.
  Their operations are the ``experts`` part.
"""

from __future__ import annotations

from benchlib import engine_loop, kda_opsbytes, moe_opsbytes, op_parts, opsbytes

KERNEL_RE = r"^%decode_attend_pallas_paged(?![_\w])"


def is_lfm2(mc: dict) -> bool:
    return "c" in mc.get("layer_pattern", "")


def conv_decode_dispatch(mc: dict, rec: dict, act_itemsize: int = 2) -> tuple:
    """(flops, bytes) the conv layers of ONE decode dispatch need, all
    layers and substeps, from its record: ``state_slots`` live slots,
    ``horizon`` substeps; the bytes are the module docstring's list (the
    tail read and written, the taps)."""
    h, taps = mc["hidden_size"], mc["conv_taps"]
    layers = mc["layer_pattern"].count("c")
    steps = max(1, int(rec.get("horizon", 1)))
    n = rec["state_slots"] * layers * steps
    tail = 2 * (taps - 1) * h * 4 * n               # read, written in place
    return (2.0 * taps + 3) * h * n, \
        float(tail + taps * h * act_itemsize * layers * steps)


def attn_decode_dispatch(mc: dict, rec: dict, page: int, slots: int) -> tuple:
    """(flops, bytes) the attention READS of one decode dispatch need, every
    attention layer and substep, from the record's ``attn_pages_live``."""
    hq, hkv, d = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    layers = mc["layer_pattern"].count("g")
    kv = rec["attn_pages_live"] * layers * 2 * hkv * page * d * 2
    rows = slots * max(1, int(rec.get("horizon", 1)))
    qo = rows * layers * 2 * hq * d * 2
    return 4.0 * kv / 2 * (hq // hkv), float(kv + qo)


def _joined(ctx, field: str):
    return [(ev, rec) for ev, rec in engine_loop.join_executions(
        ctx.trace, engine_loop.dispatch_records(ctx.spans),
        engine_loop.phases_of(ctx), "decode_steps")
        if rec is not None and field in rec]


def part_need_and_time(ctx, parts: tuple, field: str, need_of) -> tuple:
    """(need seconds, device seconds) over the ``decode_steps`` executions
    that join a record carrying ``field``: ``need_of(record)`` gives the
    (flops, bytes), the time is that of the operations of ``parts`` inside
    the execution; an execution that shows none drops out of both sides."""
    evs = op_parts.of_context(ctx)
    if not evs:
        return 0.0, 0.0
    joined = _joined(ctx, field)
    shown = op_parts.by_execution(
        evs, "decode_steps", [(ev[1], ev[1] + ev[2]) for ev, _ in joined])
    need = secs = 0.0
    for (_, rec), by in zip(joined, shown):
        mine = sum(by.get(p, 0.0) for p in parts)
        if not mine:
            continue
        need += opsbytes.roofline_seconds(*need_of(rec), ctx.peaks)[0]
        secs += mine
    return need, secs


def conv_need_and_time(ctx) -> tuple:
    return part_need_and_time(
        ctx, ("recur",), "state_slots",
        lambda rec: conv_decode_dispatch(ctx.mc, rec))


def experts_need_and_time(ctx) -> tuple:
    mc = dict(ctx.mc, num_layers=ctx.mc["num_layers"]
              - ctx.mc.get("num_dense_layers", 0))
    return part_need_and_time(
        ctx, ("experts",), "moe_experts_hit",
        lambda rec: moe_opsbytes.decode_dispatch(
            mc, rec, ctx.engine["w_itemsize"]))


def attn_need_and_time(ctx) -> tuple:
    page, slots = ctx.engine["page_size"], ctx.engine["slots"]
    return kda_opsbytes.need_and_time(
        ctx, KERNEL_RE, "attn_pages_live",
        lambda rec: attn_decode_dispatch(ctx.mc, rec, page, slots))
