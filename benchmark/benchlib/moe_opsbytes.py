"""Operations and bytes the expert FFN of an MoE block NEEDS, from shapes and
from what a step's dispatch record says it was given (a new file beside
``opsbytes.py``, whose ``weight_bytes`` counts a dense block and charges
every weight once a step).

The need is the algorithm's: each routed (token, expert) row through the
three matrices of its expert, and the stacks of the experts that were HIT
read once — not what an implementation moves (one that streams every expert
whatever the routing moves more, and its roofline share says so).

Also here: how a reader finds the expert FFN's device operations in a trace.
"""

from __future__ import annotations

def expert_ops_re(mc: dict) -> str:
    """Regex for the ``XLA Ops`` events that STREAM AN EXPERT STACK: an
    event's name is its whole HLO line, operand types included, and whatever
    form the program gives the expert FFN — XLA's ``%ragged-dot`` custom
    calls over sorted rows, the fused batched matmuls of the every-expert
    form (``%fusion.12``: the trace gives them no name of their own, and the
    profiler keeps no scope metadata), a kernel — the operation that reads
    ``w_gate``/``w_up`` ``[E, H, I]`` or ``w_down`` ``[E, I, H]`` has that
    type among its operands, with the layer axis in front where the slice is
    fused in. The router, the sort and the small elementwise ops between the
    matmuls do not, and are NOT in these times (PERF.md says how small)."""
    e, h, i = (mc["num_experts"], mc["hidden_size"],
               mc["moe_intermediate_size"])
    lead = rf"(?:{mc['num_layers']},)?{e},"
    # after the first "(": among the operands, not the result's own type
    return rf"\(.*\b\w+\[{lead}(?:{h},{i}|{i},{h})\]"


def expert_ffn_layer(mc: dict, routed_rows: float, experts_hit: float,
                     w_itemsize: int = 1, act_itemsize: int = 2) -> tuple:
    """(flops, bytes) ONE layer's expert FFN needs for ``routed_rows``
    (token, expert) rows spread over ``experts_hit`` experts.

    Flops: three matmuls a routed row, 2 per multiply-add. Bytes: the three
    stacks of every expert hit once (H x I each, at the weights' item size),
    their per-out-channel float32 scales when the stacks are int8, and each
    routed row's input and output (H wide, the activation type): the gather
    reads it, the combine writes it."""
    h, inter = mc["hidden_size"], mc["moe_intermediate_size"]
    flops = routed_rows * 3 * 2.0 * h * inter
    byts = experts_hit * 3 * h * inter * w_itemsize \
        + (experts_hit * (2 * inter + h) * 4 if w_itemsize == 1 else 0) \
        + routed_rows * 2 * h * act_itemsize
    return flops, byts


def decode_dispatch(mc: dict, rec: dict, w_itemsize: int = 1) -> tuple:
    """(flops, bytes) the expert FFNs of ONE decode dispatch need, all
    layers and substeps, from its record: ``moe_rows`` routed rows a layer
    over the whole dispatch, ``moe_experts_hit`` experts a layer and substep
    (mean), ``horizon`` substeps."""
    steps = max(1, int(rec.get("horizon", 1)))
    flops, byts = expert_ffn_layer(mc, rec["moe_rows"] / steps,
                                   rec["moe_experts_hit"], w_itemsize)
    n = steps * mc["num_layers"]
    return flops * n, byts * n
