"""What the state-space mixers of a Falcon-H1-shaped list NEED in a decode
dispatch, and how a reader finds their decode update in a trace (a new file
beside ``lfm2_opsbytes.py``; the join is ``kda_opsbytes.need_and_time``'s).

A decode step of a state-space mixer is bound by the state: each live slot's
``[H, d_state, d_head]`` float32 state (4 MiB at 32 x 256 x 128) is read once
and written once — the algorithm's need, whatever an implementation moves
(the kernel ``kda_decode_update`` streams every slot's tiles, idle ones
too, and its share says so) — beside the row's operands: C, B and the decay
a head as ``[H, d_state]`` columns, x and the step size as ``[H, d_head]``
rows, the output row. 4 flops a state element (the decay, the rank-1
update, the read-out's product and sum): 0.5 a byte, far under the ridge.
The kernel's calls carry the jitted wrapper's name.
"""

from __future__ import annotations

from benchlib import op_parts
from benchlib import trace_reduce as tr

KERNEL_RE = r"^%kda_decode_update(?![_\w])"


def is_falcon_h1(mc: dict) -> bool:
    return "h" in mc.get("layer_pattern", "")


def ssm_decode_dispatch(mc: dict, rec: dict) -> tuple:
    """(flops, bytes) the state-space mixers' decode UPDATE of one decode
    dispatch needs, all layers and substeps, from its record: ``ssm_slots``
    live slots, ``horizon`` substeps."""
    H, P, N = mc["ssm_num_heads"], mc["ssm_head_dim"], mc["ssm_state_size"]
    layers = mc["layer_pattern"].count("h")
    steps = max(1, int(rec.get("horizon", 1)))
    per_slot = 2 * 4 * H * N * P + 4 * (3 * H * N + 3 * H * P)
    n = rec["ssm_slots"] * layers * steps
    return 4.0 * H * N * P * n, float(per_slot * n)


def part_share(ctx, program: str, part: str):
    """100 x device time of ``program``'s operations of the traced slice in
    ``part`` (by NAME: benchlib/op_parts) / device time of its executions.
    None for a model without "h" layers or where nothing carries the part."""
    if not is_falcon_h1(ctx.mc):
        return None
    evs = op_parts.of_context(ctx)
    if not evs:
        return None
    n, secs = tr.module_time(ctx.trace, {program})
    mine = op_parts.seconds(evs, program, (part,))
    if not n or not secs or not mine:
        return None
    return 100.0 * mine / secs
