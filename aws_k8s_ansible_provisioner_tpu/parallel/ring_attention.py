"""Ring attention: causal self-attention over a sequence-sharded axis.

Long-context support the reference entirely lacks (SURVEY.md §5 "Long-context /
sequence parallelism: absent entirely"). Sequences are sharded over the ``sp``
mesh axis; each device holds one contiguous block of the sequence. K/V blocks
rotate around the ring via ``lax.ppermute`` (XLA lowers this to ICI
neighbor-to-neighbor DMA) while every device accumulates attention for its local
queries with an **online softmax** (running max / normalizer / weighted
accumulator, flash-attention style) so the full [T, T] score matrix never
materializes and memory stays O(T_local²) per device.

Causality across blocks: query block ``b_q`` attends to key block ``b_k`` iff
``b_k <= b_q``; the diagonal block applies the in-block triangular mask. Blocks
that are fully masked still traverse the ring (the schedule is static — XLA
requires it) but contribute zeros through the masked softmax.

Communication cost: (sp-1) ppermutes of the local K/V block per layer —
bandwidth-optimal for causal attention on a ring, and overlappable with the
per-block compute by XLA's async collective scheduling.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _block_attend(q32: jnp.ndarray, k_blk: jnp.ndarray, v_blk: jnp.ndarray,
                  mask: jnp.ndarray, m: jnp.ndarray, l: jnp.ndarray,
                  acc: jnp.ndarray):
    """One online-softmax accumulation step against a single K/V block.

    q32: [B, Tq, Hq, D] float32; k_blk/v_blk: [B, Tk, Hq, D] (kv already
    head-repeated); mask: [Tq, Tk] bool; m/l: [B, Hq, Tq]; acc: [B, Hq, Tq, D].
    """
    scale = 1.0 / jnp.sqrt(jnp.asarray(q32.shape[-1], jnp.float32))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q32,
                        k_blk.astype(jnp.float32)) * scale
    logits = jnp.where(mask[None, None], logits, _NEG_INF)
    m_new = jnp.maximum(m, logits.max(axis=-1))
    alpha = jnp.exp(m - m_new)                      # correction for old acc
    p = jnp.exp(logits - m_new[..., None])          # [B, H, Tq, Tk]
    l = l * alpha + p.sum(axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
    return m_new, l, acc


def _repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


def ring_attend_local(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      axis_name: str = "sp") -> jnp.ndarray:
    """Per-device body: causal ring attention over ``axis_name``.

    q: [B, Tl, Hq, D]; k/v: [B, Tl, Hkv, D] — the *local* sequence block.
    Must run inside shard_map (or any context where ``axis_name`` is bound).
    Returns the local context block [B, Tl, Hq, D].
    """
    B, Tl, Hq, D = q.shape
    Hkv = k.shape[2]
    sp = jax.lax.psum(1, axis_name)
    my_blk = jax.lax.axis_index(axis_name)

    q32 = q.astype(jnp.float32)
    k = _repeat_kv(k, Hq // Hkv)
    v = _repeat_kv(v, Hq // Hkv)

    m = jnp.full((B, Hq, Tl), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, Hq, Tl), jnp.float32)
    acc = jnp.zeros((B, Hq, Tl, D), jnp.float32)

    qpos = my_blk * Tl + jnp.arange(Tl)
    perm = [(j, (j + 1) % sp) for j in range(sp)]

    def body(i, carry):
        k_blk, v_blk, m, l, acc = carry
        src_blk = (my_blk - i) % sp                  # which block we now hold
        kpos = src_blk * Tl + jnp.arange(Tl)
        mask = qpos[:, None] >= kpos[None, :]        # causal across blocks
        m, l, acc = _block_attend(q32, k_blk, v_blk, mask, m, l, acc)
        # rotate K/V to the next device (skip after the last accumulation)
        k_blk, v_blk = jax.lax.cond(
            i < sp - 1,
            lambda kv: tuple(jax.lax.ppermute(x, axis_name, perm) for x in kv),
            lambda kv: kv,
            (k_blk, v_blk),
        )
        return k_blk, v_blk, m, l, acc

    _, _, m, l, acc = jax.lax.fori_loop(0, sp, body, (k, v, m, l, acc))
    # Every query row has attended at least its own diagonal block ⇒ l >= 1.
    out = acc / l[..., None]                         # [B, H, Tq, D]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def make_ring_attend(mesh: Mesh, axis_name: str = "sp"):
    """AttendFn (models/layers) running ring attention over ``mesh``'s sp axis.

    q/k/v arrive as *global* arrays inside jit; shard_map partitions them
    batch→dp, sequence→sp, heads→tp and binds the sp axis for the ring. The
    cache is passed through untouched (training / full-sequence path).
    """

    local = jax.shard_map(
        lambda q, k, v: ring_attend_local(q, k, v, axis_name),
        mesh=mesh,
        in_specs=(P("dp", axis_name, "tp", None),) * 3,
        out_specs=P("dp", axis_name, "tp", None),
        check_vma=False,
    )

    def attend(q, k, v, cache) -> Tuple[jnp.ndarray, object]:
        return local(q, k, v), cache

    return attend
