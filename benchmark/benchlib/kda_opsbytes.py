"""Bytes the KDA layers' recurrent state NEEDS in a decode dispatch, what an
expert SHARE's held stacks need, and how a reader finds both in a trace (a
new file beside ``moe_opsbytes.py``, whose ``expert_ffn_layer`` it uses).

A decode step of a KDA layer is bound by the state: each live slot's
``[H, d, d]`` float32 state is read once and written once — that is the
algorithm's need, whatever an implementation moves (XLA's form reads it
twice — its roofline share says so; the kernel ``kda_decode_update`` once) — beside the token's q, k, v, g rows and
its step size. Flops are 6 a state element (decay, two products for the
reductions, the rank-1 update): 0.75 a byte, far under the ridge.
"""

from __future__ import annotations

from benchlib import engine_loop, moe_opsbytes, opsbytes
from benchlib import trace_reduce as tr


def _periods(mc: dict) -> tuple:
    pat = mc.get("layer_pattern", "")
    if "k" not in pat:
        return 0, 0
    return mc["num_layers"] // len(pat), pat.count("k")


def state_ops_re(mc: dict, slots: int):
    """Regex for the ``XLA Ops`` events that take the state leaf
    ``kda_state`` (float32 ``[P, n_k, slots, H, d, d]``) as an OPERAND: the
    reduce fusion and the update fusion of every KDA layer's decode step,
    or a kernel that takes the leaf. None for a model without KDA layers."""
    P, nk = _periods(mc)
    if not P:
        return None
    H, d = mc["kda_num_heads"], mc["kda_head_dim"]
    return rf"\(.*\bf32\[{P},{nk},{slots},{H},{d},{d}\]"


def decode_dispatch(mc: dict, rec: dict) -> tuple:
    """(flops, bytes) the KDA layers of ONE decode dispatch need, all
    layers and substeps, from its record: ``kda_slots`` live slots,
    ``horizon`` substeps."""
    P, nk = _periods(mc)
    H, d = mc["kda_num_heads"], mc["kda_head_dim"]
    steps = max(1, int(rec.get("horizon", 1)))
    per_slot = 2 * 4 * H * d * d + 4 * (4 * H * d + H)
    n = rec["kda_slots"] * P * nk * steps
    return 6.0 * H * d * d * n, float(per_slot * n)


def held_expert_ops_re(mc: dict) -> str:
    """``moe_opsbytes.expert_ops_re`` for a model with a layer pattern: the
    held stacks are ``[P, E, H, I]`` (attention layers) and ``[P, n_k, E, H,
    I]`` (KDA layers), and the fused slices drop the leading axes."""
    P, nk = _periods(mc)
    e, h, i = (mc["num_experts"], mc["hidden_size"],
               mc["moe_intermediate_size"])
    lead = rf"(?:{P},)?(?:{nk},)?{e},"
    return rf"\(.*\b\w+\[{lead}(?:{h},{i}|{i},{h})\]"


def held_decode_dispatch(mc: dict, rec: dict, w_itemsize: int = 1) -> tuple:
    """(flops, bytes) the HELD experts' FFNs of one decode dispatch need:
    ``moe_rows_held`` (token, expert) rows a layer over the dispatch that
    landed on a held expert, ``moe_experts_hit`` held experts a layer and
    substep."""
    steps = max(1, int(rec.get("horizon", 1)))
    flops, byts = moe_opsbytes.expert_ffn_layer(
        mc, rec["moe_rows_held"] / steps, rec["moe_experts_hit"], w_itemsize)
    n = steps * mc["num_layers"]
    return flops * n, byts * n


def need_and_time(ctx, ops_re: str, field: str, need_of) -> tuple:
    """(need seconds, device seconds) over the ``decode_steps`` executions of
    the traced slice that join a dispatch record carrying ``field``
    (benchlib/engine_loop.join_executions): ``need_of(record)`` gives the
    (flops, bytes) that execution needs, the time is that of the operations
    matching ``ops_re`` inside it. What both roofline readers of PR 32 do."""
    joined = [(ev, rec) for ev, rec in engine_loop.join_executions(
        ctx.trace, engine_loop.dispatch_records(ctx.spans),
        engine_loop.phases_of(ctx), "decode_steps")
        if rec is not None and field in rec]
    calls = sorted(tr.ops_inside(ctx.trace, {"decode_steps"}, ops_re),
                   key=lambda e: e[1])
    need = secs = 0.0
    i = 0
    for ev, rec in joined:
        s, e = ev[1], ev[1] + ev[2]
        while i < len(calls) and calls[i][1] < s:
            i += 1
        mine = 0
        while i < len(calls) and calls[i][1] < e:
            mine += calls[i][2]
            i += 1
        if not mine:
            continue
        flops, byts = need_of(rec)
        need += opsbytes.roofline_seconds(flops, byts, ctx.peaks)[0]
        secs += mine / 1e9
    return need, secs

