"""What the two mechanisms of a MiniCPM-SALA-shaped model NEED in a dispatch —
the selecting attention layers' selected pages, the Lightning layers'
recurrent state — and how a reader finds their operations in a trace (a new
file beside ``kda_opsbytes.py``, whose ``need_and_time`` it uses).

**Selecting attention, decode.** A row's KV head reads the pages it selected
and nothing else: ``sparse_pages_selected`` (row, KV head) pages a selecting
layer over the record's substeps, each ``page x head_dim`` K rows and as many
V rows in bf16, beside the rows' q in and o out. 4 flops a K/V element read
(q.k and p.v over the group's 16 heads: 32 flops a byte, under the v5e's
ridge of 240), so the bound is bytes over the peak HBM bandwidth. The
selection itself (scoring the pooled keys, pooling, top-k) is not part of
this need: it is the overhead ``sparse_select_share_pct`` reads.

**Lightning state, decode.** As KDA's: each live slot's ``[H, d, d]`` float32
state is read once and written once a layer and substep, beside the token's
q, k, v rows; 4 flops a state element (decay, the outer product's add, the
product with q and its sum), 0.5 a byte.
"""

from __future__ import annotations

from benchlib import kda_opsbytes

DECODE_KERNEL_RE = r"^%decode_attend_pallas_paged_select"
RAGGED_KERNEL_RE = r"^%ragged_attend_pallas_paged_select"


def _kinds(mc: dict) -> tuple:
    pat = mc.get("layer_pattern", "")
    return pat.count("s"), pat.count("l")


def _window(cfg_file: dict) -> int:
    flags = cfg_file["server_flags"]
    return int(flags[flags.index("--max-cache-len") + 1])


def pool_pages(cfg_file: dict, slots: int, page: int) -> int:
    """Physical pages of the pool as the engine sizes it: a full window a
    slot, and the scratch page."""
    return slots * -(-_window(cfg_file) // page) + 1


def select_ops_re(mc: dict, cfg_file: dict, slots: int, page: int,
                  rows=()):
    """Regex for the ``XLA Ops`` events of the SELECTION: those that take
    the selector's cache leaf (float32 ``[n_s, pages, Hkv, runs, D]``) as an
    operand — the write of a new key's run, the gather of a slot's runs —
    and those that work on what is derived from it for ``rows`` query rows
    (the slots of a decode step; with the chunk of a mixed step, both): the
    gathered runs ``[rows, Hkv, M, D]``, the pooled-key logits and
    probabilities ``[rows, Hkv, G, M]``, the block scores, the top-k and the
    page lists or bit words ``[rows, Hkv, blocks | K | words]``. None for a
    model that does not select."""
    ns, _ = _kinds(mc)
    if not ns:
        return None
    hkv, d = mc["num_kv_heads"], mc["head_dim"]
    g = mc["num_heads"] // hkv
    runs = page // mc["sparse_kernel_stride"]
    nb = -(-_window(cfg_file) // page)
    m = nb * runs
    leaf = rf"f32\[{ns},{pool_pages(cfg_file, slots, page)},{hkv},{runs},{d}\]"
    alts = [leaf]
    for r in (slots,) + tuple(rows):
        alts += [rf"\w+\[{r},{hkv},{m},{d}\]", rf"\w+\[{r},{hkv},{g},{m}\]",
                 rf"\w+\[{r},{hkv},{m}\]", rf"\w+\[{r},{hkv},{nb},{runs}\]",
                 rf"\w+\[{r},{hkv},{nb}\]", rf"\w+\[{r},{nb},{hkv},{runs},{d}\]"]
    return r"\b(?:" + "|".join(alts) + ")"


def sparse_decode_dispatch(mc: dict, rec: dict, page: int) -> tuple:
    """(flops, bytes) the selecting layers' attention READS of one decode
    dispatch need, all selecting layers and substeps, from its record."""
    ns, _ = _kinds(mc)
    d, hq = mc["head_dim"], mc["num_heads"]
    kv = rec["sparse_pages_selected"] * ns * 2 * page * d * 2
    qo = rec["sparse_rows"] * ns * 2 * hq * d * 2
    return 4.0 * kv / 2 * (hq // mc["num_kv_heads"]), float(kv + qo)


def state_ops_re(mc: dict, slots: int):
    """Regex for the events that take the Lightning state leaf (float32
    ``[n_l, 1, slots, H, d, d]``) as an operand; None without such layers."""
    _, nl = _kinds(mc)
    if not nl:
        return None
    H, d = mc["lightning_num_heads"], mc["lightning_head_dim"]
    return rf"\(.*\bf32\[{nl},1,{slots},{H},{d},{d}\]"


def lightning_decode_dispatch(mc: dict, rec: dict) -> tuple:
    """(flops, bytes) the Lightning layers of one decode dispatch need:
    ``state_slots`` live slots x the layers x ``horizon`` substeps x (the
    state read once and written once + the token's q, k, v rows in and its
    output row out, float32)."""
    _, nl = _kinds(mc)
    H, d = mc["lightning_num_heads"], mc["lightning_head_dim"]
    steps = max(1, int(rec.get("horizon", 1)))
    n = rec["state_slots"] * nl * steps
    per_slot = 2 * 4 * H * d * d + 4 * 4 * H * d
    return 4.0 * H * d * d * n, float(per_slot * n)


need_and_time = kda_opsbytes.need_and_time
