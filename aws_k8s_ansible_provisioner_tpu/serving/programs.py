"""Compiled-program registry: the serving engine's jit layer.

Everything XLA-compiled lives here, split out of ``serving/engine.py`` with
zero behavior change (ROADMAP / VERDICT next #7):

- the five jitted step functions — ``prefill_step`` (one program per prompt
  bucket), ``prefill_batch_step``, ``prefill_chunk_step``, ``decode_steps``
  (fused horizon), ``spec_decode_step`` — plus their pure helpers (logit
  bias, penalties, bans, logprob extraction);
- the decode batch-block autotune (``pick_decode_bblock`` and the per-config
  ``_BBLOCK_CACHE``);
- ``EnginePrograms``, the mixin ``Engine`` inherits: program-operand
  construction (dtype/quantize/shard/LoRA, paged pool),
  prefill/decode/spec dispatch, and the ``warmup`` plan that enumerates and
  compiles every program variant a config can dispatch.

``serving/aot.py`` compiles the same enumeration ahead-of-time against an
abstract topology and writes the committed manifest; ``serving/engine.py``
keeps the host-side scheduler — admission, slots, paged-pool bookkeeping,
drain, streaming queues.
"""

from __future__ import annotations

import contextlib
import itertools
import math
import os
import threading
import time
from functools import lru_cache, partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from aws_k8s_ansible_provisioner_tpu.config import ModelConfig
from aws_k8s_ansible_provisioner_tpu.models import parts
from aws_k8s_ansible_provisioner_tpu.models.layers import (
    lora_context,
    model_forward_carry,
)
from aws_k8s_ansible_provisioner_tpu.ops.attention import (
    attend_by_kind,
    lane_packed,
    make_chunk_prefill_attend_paged_carry,
    make_decode_attend_carry_paged,
    make_mixed_attend_carry_paged,
    make_prefill_attend_batch_paged_carry,
    make_prefill_attend_paged_carry,
    make_spec_attend_carry_paged,
)
from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp
from aws_k8s_ansible_provisioner_tpu.ops import linear_attention as _la
from aws_k8s_ansible_provisioner_tpu.ops import moe as _moe
from aws_k8s_ansible_provisioner_tpu.ops import sparse_attention as _sa
from aws_k8s_ansible_provisioner_tpu.ops.sampling import (apply_allow,
                                                           apply_penalties,
                                                           per_slot_keys,
                                                           sample)
from aws_k8s_ansible_provisioner_tpu.serving import chaos as _chaos
from aws_k8s_ansible_provisioner_tpu.serving import devmon as _devmon
from aws_k8s_ansible_provisioner_tpu.serving import flightrec as _flight
from aws_k8s_ansible_provisioner_tpu.serving import metrics as _metrics
from aws_k8s_ansible_provisioner_tpu.serving import paged_kv as pkv
from aws_k8s_ansible_provisioner_tpu.serving import slo as _slo
from aws_k8s_ansible_provisioner_tpu.serving import tracing as _tracing


# ---------------------------------------------------------------------------
# The engine loop reporting itself: phases, the dispatch record, compile time
# ---------------------------------------------------------------------------

# Phases of one Engine.step, as jax.profiler.TraceAnnotation on the engine
# thread: they land on /host:CPU of the same .xplane.pb as the device's XLA
# Modules / XLA Ops, so a device idle gap can be put down to a phase. A
# fixed, closed set (PERF.md section 3); outside a profiler session an
# annotation is a flag test (~0.5 us). They nest, and the innermost names
# the time: run_forever opens engine.operands around the whole step (routing
# and the host side of building a dispatch's operands: mirrors, eager
# uploads, _next_rng), and reap, admit, dispatch (the jitted call alone),
# fetch (blocked on a transfer) and emit claim their parts of it.
ENGINE_PHASES = (PH_REAP, PH_ADMIT, PH_OPERANDS, PH_DISPATCH, PH_FETCH,
                 PH_EMIT, PH_IDLE) = (
    "engine.reap", "engine.admit", "engine.operands", "engine.dispatch",
    "engine.fetch", "engine.emit", "engine.idle")
_phase = jax.profiler.TraceAnnotation

# The jitted step functions by the name the trace prints (jit_<name>): the
# closed ``program`` label set of tpu_serve_compile_stage_seconds_total.
STEP_PROGRAMS = ("prefill_step", "prefill_batch_step", "prefill_chunk_step",
                 "decode_steps", "mixed_step", "spec_decode_step")

# Process-wide sequence number of device dispatches: the k-th engine.dispatch
# annotation of a program is the k-th execution of jit_<program> after it
# (one stream, in order), and the same seq is on the engine.dispatch span.
_DISPATCH_SEQ = itertools.count(1)

# The share of a running dispatch's expected time that Engine._await_arrival
# gives an arrival to show up before the next dispatch is bound; the rest is
# kept for building and enqueueing it (_note_host sizes a short dispatch so
# that it is enough). A choice, not a swept optimum (PERF.md section 7).
AWAIT_SHARE = 0.5
# engine.dispatch spans take their ids from the record, never from the
# request tracer's seeded generator (whose draws must stay a pure function
# of the requests); the prefix keeps replicas apart in one backend.
_SPAN_PREFIX = os.urandom(8).hex()

_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
# per thread: the dispatch record whose jitted call is running (``open``),
# and a persistent-cache retrieval waiting for the backend event of the
# same compile (``load``; the retrieval event carries no fun_name)
_compile_tls = threading.local()
_compile_install_lock = threading.Lock()
_compile_installed = False


def _on_compile_event(event: str, duration: float, fun_name: str = "",
                      **_kw) -> None:
    stage = _COMPILE_STAGES.get(event)
    if stage is None:
        return
    tls = _compile_tls
    if stage == "cache_load":
        tls.load = getattr(tls, "load", 0.0) + duration
        return
    name = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
    program = name if name in STEP_PROGRAMS else "other"
    cm = _metrics.compile_stages
    if stage == "backend":
        # backend_compile_duration wraps compile_or_get_cached: take the
        # retrieval it contains out, so a warm cache reads as cache_load
        load = getattr(tls, "load", 0.0)
        if load:
            tls.load = 0.0
            cm.stage_seconds.inc(load, program=program, stage="cache_load")
            duration = max(0.0, duration - load)
    cm.stage_seconds.inc(duration, program=program, stage=stage)
    if program == "other" or not cm.serving:
        return
    # a step program first used while serving: every stream stalls for it
    rec = getattr(tls, "open", None)
    if rec is not None:
        rec["first_use"] = True
    if stage == "trace":        # every compile begins with one trace
        cm.serving_compiles.inc(program=program)
    _flight.record("compile", None, program=program, stage=stage,
                   seconds=duration,
                   seq=rec["seq"] if rec is not None else 0)


def install_compile_listeners() -> None:
    """Register the jax.monitoring duration listener, once per process
    (listeners cannot be removed; ``jax_log_compiles`` stays off)."""
    global _compile_installed
    with _compile_install_lock:
        if _compile_installed:
            return
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event)
        _compile_installed = True


class _Dispatching:
    """``engine.dispatch`` around ONE jitted call: the annotation carries
    the record's ``seq`` and ``program``, and a compile event that fires
    inside marks the record ``first_use``."""

    __slots__ = ("rec", "ann")

    def __init__(self, rec: dict):
        self.rec = rec
        self.ann = _phase(PH_DISPATCH, seq=rec["seq"],
                          program=rec["program"])

    def __enter__(self):
        _compile_tls.open = self.rec
        self.ann.__enter__()

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        _compile_tls.open = None


# ---------------------------------------------------------------------------
# Pure jitted step functions
# ---------------------------------------------------------------------------


# Static top-k width for OpenAI ``logprobs`` responses (vLLM caps similarly);
# per-request k <= this is sliced on the host.
LOGPROB_K = 8

# Static width of the per-slot banned-token list (min_tokens stop
# suppression): eos set + stop_token_ids must fit. Rows pad with an
# out-of-vocab id, which the masking scatter DROPS.
BAN_K = 8

# Static width of the per-slot OpenAI ``logit_bias`` list (OpenAI caps the
# map at 300 entries; vLLM-grade clients rarely exceed a few dozen — the
# server rejects beyond this). Padding ids are out-of-vocab and DROP.
BIAS_K = 64

# Candidate batch-block sizes for the double-buffered paged decode kernel
# (ops/pallas_attention._paged_db_body): BB slots share one grid step, so
# each step issues BBx larger page DMAs and the per-substep grid-step count
# divides by BB. The best BB depends on (batch, page_size, kv_dtype) — the
# engine microbenches these at startup (PALLAS_DECODE_BBLOCK's off-by-default
# env gate, promoted to a first-class autotuned parameter in r6).
BBLOCK_CANDIDATES = (1, 4, 8)
# (batch, page_size, kv_dtype) -> chosen bb. Module-level so a second engine
# start in the same process (replica respawn, tests, bench retries) reuses
# the choice instead of re-running the microbench.
_BBLOCK_CACHE: dict = {}


def pick_decode_bblock(candidates, bench_once, timer=time.perf_counter,
                       reps: int = 3) -> int:
    """Deterministic selection: for each candidate (ascending), one untimed
    warmup call (compile + cache fill), then ``reps`` timed calls; the
    candidate with the lowest MEDIAN wins, ties going to the SMALLER block
    (strict < — so a fixed timer sequence always yields the same choice,
    and noise can only flip a decision across a real gap, not a tie)."""
    best_bb, best_t = None, None
    for bb in candidates:
        bench_once(bb)                      # warmup: compile outside timing
        times = []
        for _ in range(max(1, reps)):
            t0 = timer()
            bench_once(bb)
            times.append(timer() - t0)
        med = sorted(times)[len(times) // 2]
        if best_t is None or med < best_t:
            best_bb, best_t = bb, med
    return best_bb


def _apply_logit_bias(logits: jnp.ndarray, bias_ids, bias_vals) -> jnp.ndarray:
    """OpenAI ``logit_bias``: add per-request offsets to selected token
    logits before any sampling (greedy included — -100/+100 act as ban/
    force, the documented semantics). Always-on scatter-add: unbiased slots
    carry out-of-vocab ids that drop. bias_ids: [B, BIAS_K] int32;
    bias_vals: [B, BIAS_K] f32."""
    if bias_ids is None:
        return logits
    B = logits.shape[0]
    return logits.at[jnp.arange(B)[:, None], bias_ids].add(
        bias_vals.astype(logits.dtype), mode="drop")


def _apply_prefill_repetition(logits: jnp.ndarray, tokens, true_lens,
                              rep) -> jnp.ndarray:
    """repetition_penalty for the PREFILL-sampled first token: the seen-set
    is the prompt itself (tokens [N, T] with true_lens [N] masking the right
    padding). Always-on (no program variant): rep == 1.0 divides/multiplies
    by exactly 1.0, an exact no-op — same design as the ban/bias rows.
    Without this the first generated token escaped the penalty (review r4),
    diverging from HF/vLLM, whose processors see the prompt from token 0."""
    if rep is None:
        return logits
    N, V = logits.shape
    T = tokens.shape[1]
    cols = jnp.arange(T, dtype=jnp.int32)[None, :]
    ids = jnp.where(cols < true_lens[:, None], tokens, jnp.int32(2**31 - 1))
    seen = jnp.zeros((N, V), jnp.bool_)
    seen = seen.at[jnp.arange(N)[:, None], ids].set(True, mode="drop")
    r = rep[:, None].astype(jnp.float32)
    out = logits.astype(jnp.float32)
    return jnp.where(seen, jnp.where(out > 0, out / r, out * r), out)


def _mask_banned(logits: jnp.ndarray, ban_ids, ban_until, lens) -> jnp.ndarray:
    """vLLM ``min_tokens`` semantics: while a slot's context length is below
    ``ban_until`` (prompt_len + min_tokens), its stop tokens are masked to
    -inf BEFORE sampling — a suppressed eos is never produced, never
    streamed, never conditions later tokens. Always-on (no program variant):
    slots with nothing to ban carry out-of-vocab ids, and the scatter drops
    them. logits: [B, V]; ban_ids: [B, BAN_K] int32; ban_until/lens: [B]."""
    if ban_ids is None:
        return logits
    B = logits.shape[0]
    active = (lens < ban_until)[:, None]
    ids = jnp.where(active, ban_ids, jnp.int32(2**31 - 1))
    return logits.at[jnp.arange(B)[:, None], ids].set(-jnp.inf, mode="drop")


def _apply_allow(logits: jnp.ndarray, allow) -> jnp.ndarray:
    """Guided-decoding allow-bitmask (serving/guided.py): token v is allowed
    iff bit (v & 31) of ``allow[b, v >> 5]`` is set; everything else drops to
    the ban floor. ``allow`` is a program variant (None = compiled out):
    unguided traffic never pays the [B, V] bit-gather. Rows for unguided
    slots are all-ones. Applied AFTER bias/ban — a +100 bias must not
    resurrect a grammar-rejected token. logits: [B, V]; allow: [B, ceil(V/32)]
    uint32."""
    if allow is None:
        return logits
    return apply_allow(logits, allow)


def _logprob_topk(logits: jnp.ndarray, chosen: jnp.ndarray):
    """(chosen logprob [B], top-k logprobs [B, K], top-k ids [B, K]) from
    raw logits [B, V] — the OpenAI ``logprobs`` payload, computed on-device
    only in the logprob program variants (log_softmax + top_k over a 152k
    vocab is real VPU work the default hot path must not pay)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    sel = jnp.take_along_axis(logp, chosen[:, None].astype(jnp.int32),
                              axis=1)[:, 0]
    vals, ids = jax.lax.top_k(logp, min(LOGPROB_K, logp.shape[-1]))
    return sel, vals, ids.astype(jnp.int32)


def _prompt_logprobs(logits, tokens):
    """Per-position PROMPT logprobs (vLLM ``prompt_logprobs`` / OpenAI
    legacy echo+logprobs): entry t scores prompt token t+1 given tokens
    <= t (position 0 has no logprob, the OpenAI None convention).

    Sequential ``lax.map`` over positions: one [N, V] log_softmax + top-k
    at a time — materializing the full [N, T, V] f32 log-softmax would hold
    gigabytes at large buckets. Returns (sel [N, T-1], vals [N, T-1, K],
    ids [N, T-1, K])."""
    lg = jnp.swapaxes(logits[:, :-1], 0, 1)      # [T-1, N, V]
    nxt = jnp.swapaxes(tokens[:, 1:], 0, 1)      # [T-1, N]

    def per_pos(args):
        lg_t, tok = args
        lp = jax.nn.log_softmax(lg_t.astype(jnp.float32), -1)
        sel = jnp.take_along_axis(lp, tok[:, None].astype(jnp.int32),
                                  1)[:, 0]
        vals, ids = jax.lax.top_k(lp, min(LOGPROB_K, lp.shape[-1]))
        return sel, vals, ids.astype(jnp.int32)

    sel, vals, ids = jax.lax.map(per_pos, (lg, nxt))
    return (jnp.swapaxes(sel, 0, 1), jnp.swapaxes(vals, 0, 1),
            jnp.swapaxes(ids, 0, 1))


def _head(logits, rows):
    """``[R, V]`` logits of ``rows`` (indices over the forward's flattened
    ``B * T`` rows): what ``model_forward_carry(head_rows=rows)`` returned,
    or those rows of an every-row ``[B, T, V]`` (a ``prompt_logprobs``
    variant, which reads every row besides)."""
    if logits.ndim == 2:
        return logits
    return logits.reshape(-1, logits.shape[-1])[rows]


def _host_lp(lp_t, row: int, k: int):
    """Slice one row of a device (sel, vals, ids) triple into the host-side
    per-token logprob record: (own_logprob, [(token_id, logprob) x k])."""
    sel, vals, ids = lp_t
    sel = float(np.asarray(sel[row]))
    vals = np.asarray(vals[row])
    ids = np.asarray(ids[row])
    k = min(k, len(ids))
    return (sel, [(int(ids[j]), float(vals[j])) for j in range(k)])


@jax.jit
def _zero_lanes(lens, dead):
    """The length carry with the lanes of the slots that hold no request
    set to 0, as their mirror is (``EnginePrograms._carry_in``)."""
    return jnp.where(dead, 0, lens)


@partial(jax.jit, donate_argnums=(0,))
def _reset_count_row(counts, slot, token):
    """Zero a recycled slot's generated-token counts and count its first
    token (penalties apply over GENERATED text; the prefill-sampled token is
    generated)."""
    counts = jax.lax.dynamic_update_slice(
        counts, jnp.zeros((1, counts.shape[1]), counts.dtype),
        (slot, jnp.int32(0)))
    return counts.at[slot, token].add(1)


@partial(jax.jit, donate_argnums=(0,))
def _set_mask_row(mask, slot, row):
    """Overwrite one slot's prompt-token presence row (repetition_penalty
    covers prompt tokens; set at activation, stale rows no-op at rep=1)."""
    return jax.lax.dynamic_update_slice(mask, row[None], (slot, jnp.int32(0)))


@partial(jax.jit, donate_argnums=(0,))
def _restore_count_row(counts, slot, row):
    """Overwrite one slot's counts row with a precomputed [V] histogram —
    restores a preempted request's penalty state on resume (its prior
    generated tokens are re-prefilled as CONTEXT, but penalties count them
    as GENERATED; without this the penalty would forget everything before
    the preemption)."""
    return jax.lax.dynamic_update_slice(
        counts, row[None].astype(counts.dtype), (slot, jnp.int32(0)))


def _moe_summary(stats, steps=None):
    """int32 [..., L, 2] per-layer (experts hit, largest group) → float32
    [2]: mean experts hit, largest group anywhere. None stays None. An
    expert share's [..., L, 3] also carries the pairs that landed on a held
    expert and gives [3]: summed over substeps, mean over layers — per
    layer, as the record's ``moe_rows`` is. ``steps``: the leading rows
    that were run (decode_steps; the rest are zeros and leave the mean)."""
    if stats is None:
        return None
    hit = stats[..., 0].astype(jnp.float32)
    out = [hit.mean() if steps is None
           else hit.sum() / (steps * stats.shape[-2]),
           stats[..., 1].max().astype(jnp.float32)]
    if stats.shape[-1] == 3:
        out.append(stats[..., 2].astype(jnp.float32).reshape(
            -1, stats.shape[-2]).sum(axis=0).mean())
    return jnp.stack(out)


def _aux(moe, picked, steps=None):
    """The step programs' last output: an MoE model's routing summary, a
    selecting model's page counts ([2] int32: live and selected pages of the
    (row, KV head) pairs, summed over substeps and selecting layers), None
    for any other (no model has both). ``steps``: see ``_moe_summary``."""
    if picked is not None:
        with jax.named_scope(parts.SELECT):
            return picked.reshape(-1, 2).sum(axis=0)
    with jax.named_scope(parts.ROUTER):
        return _moe_summary(moe, steps)


def _attend(cfg: ModelConfig, make, table, wtable):
    """A step program's attend callback over the paged pool, from
    ``make(table, window, of_window_kind)``: one for every attending layer
    of the model, or — a list with window ("w") layers beside full ones —
    one a kind, the window layers' over ``wtable``, their own inventory's
    table (ops/attention.attend_by_kind). ``wtable`` is None for any other
    model: no operand, and its jaxpr is what it was."""
    if cfg.windowed:
        return attend_by_kind(make, table, wtable, cfg.sliding_window)
    return lane_packed(cfg, make(table, cfg.sliding_window, False))


def _recur(cfg: ModelConfig, make, *args):
    """The KDA layers' callback for a step program, from the same row
    metadata its ``attend`` is built from; None for a model without
    recurrent layers (no operand, no op: its jaxpr is what it was)."""
    return make(*args) if cfg.recurrent else None


@lru_cache(maxsize=None)
def mixed_narrow_rows(cfg: ModelConfig, slots: int, C: int, page_size: int,
                      bblock: int, pages: int, kv_dtype) -> int:
    """Chunk rows of ``mixed_step``'s NARROW body — ``C // 2`` — or 0 where
    the shapes admit one width only. The program holds its layers twice, over
    ``slots + C`` and over ``slots + C // 2`` packed rows, and a chunk of at
    most ``C // 2`` tokens (``mixed_takes_narrow``) runs the narrow one: the
    rows it leaves out are dead in the wide one. One width where half a
    chunk is no whole number of pool pages (the span write's windows) or of
    the ragged kernel's row blocks at ``slots + C`` (the block, and with it
    every tile, would shrink), and for a selecting model whose ``slots + C``
    rows the ragged entry walks in several calls (the calls are cut from
    the row count: pallas_attention.select_fits_one_call). Read from the
    shapes, by the program (tracing) and by the engine (the dispatch
    record's rows) alike."""
    from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
        _resolve_bb, select_fits_one_call)

    W = C // 2
    bb = _resolve_bb(bblock, slots + C)
    if C % 2 or W == 0 or W % page_size or W % bb:
        return 0
    if cfg.selects and not select_fits_one_call(
            slots + C, bb, cfg.num_heads, cfg.pool_head_dim, page_size,
            kv_dtype, (slots, pages), cfg.pool_kv_heads, -(-pages // 32)):
        return 0
    return W


def mixed_takes_narrow(plen, narrow: int):
    """Does a chunk of ``plen`` tokens run ``mixed_step``'s narrow body?
    ``plen`` the program's traced operand or the engine's int."""
    return plen <= narrow


@partial(jax.jit, static_argnums=(0,),
         static_argnames=("logprobs", "prompt_logprobs"),
         donate_argnums=(2,))
def prefill_step(cfg: ModelConfig, params, cache, tokens, true_len, rng,
                 temperature, top_k, top_p, *, pages, logprobs: bool = False,
                 seed=None, ban_ids=None, ban_until=None,
                 bias_ids=None, bias_vals=None, rep=None, allow=None,
                 lora_idx=None, prompt_logprobs: bool = False, slot=None,
                 wpages=None):
    """Prefill one prompt into one slot; returns (cache, first sampled token).

    tokens: [1, T] right-padded to a bucket; true_len: scalar valid length;
    ``cache`` is the paged pool and ``pages`` ([max_pages] int32) the slot's
    block table, through which the rows scatter (ops/kv_pool.py). ``slot``
    (scalar; a model with recurrent layers only): whose per-slot state the
    prompt builds, from zeros. ``wpages`` (a model with window layers beside
    full ones only): the slot's table into the window layers' inventory.
    """
    T = tokens.shape[1]
    positions = jnp.arange(T, dtype=jnp.int32)[None, :]
    rows = (true_len - 1)[None]         # the one row that is sampled
    with lora_context(lora_idx):
        # carry path: the pool stays in the layer scan's carry — the xs→ys
        # restack buffer OOMed the batch-128 program on chip (r5)
        attend = _sa.make_prefill_attend_select(
            cfg, pages[None], true_len[None]) if cfg.selects \
            else _attend(
                cfg, lambda t, w, _: make_prefill_attend_paged_carry(
                    t, true_len, window=w), pages, wpages)
        logits, cache = model_forward_carry(
            params, cfg, tokens, positions, cache, attend,
            _recur(cfg, _la.make_recur_span, slot, 0, true_len),
            head_rows=None if prompt_logprobs else rows)
    with jax.named_scope(parts.SAMPLE):
        last = _head(logits, rows)                           # [1, V]
        last = _apply_prefill_repetition(
            last, tokens, true_len[None],
            rep[None] if rep is not None else None)
        if bias_ids is not None:
            last = _apply_logit_bias(last, bias_ids[None], bias_vals[None])
        if ban_ids is not None:
            last = _mask_banned(last, ban_ids[None], ban_until[None],
                                true_len[None])
        last = _apply_allow(last, allow)
        # Per-request seeded draw: key = (seed, position), so the stream
        # is reproducible across restarts/preemption (OpenAI `seed`).
        # ``rng`` is the legacy fallback when no seed rides the dispatch.
        keys = per_slot_keys(seed[None], true_len[None]) \
            if seed is not None else rng
        token = sample(last, keys, temperature[None], top_k[None],
                       top_p[None])[0]
        out = [cache, token]
        if logprobs:
            out.append(_logprob_topk(last, token[None]))
        if prompt_logprobs:
            out.append(_prompt_logprobs(logits[:1], tokens))
    return tuple(out)


@partial(jax.jit, static_argnums=(0,),
         static_argnames=("logprobs", "prompt_logprobs"),
         donate_argnums=(2,))
def prefill_batch_step(cfg: ModelConfig, params, cache, tokens, true_lens,
                       rng, temperature, top_k, top_p, *, tables,
                       logprobs: bool = False, seeds=None,
                       ban_ids=None, ban_until=None,
                       bias_ids=None, bias_vals=None, reps=None, allow=None,
                       lora_idx=None, prompt_logprobs: bool = False,
                       slots=None, wtables=None):
    """Prefill N prompts into N slots in ONE dispatch.

    tokens: [N, T] right-padded to a (row, length) bucket; true_lens/
    sampling params: [N]; ``tables`` [N, max_pages] int32 are the rows' block
    tables into the paged pool ``cache``. Padding rows carry all-OOB_PAGE
    tables (their cache writes drop) — the host ignores their sampled tokens.
    Returns (cache, first tokens [N]). One program per (N-bucket, T-bucket)
    pair; under a burst this turns N serialized prefill dispatches into
    ceil(N/batch) (VERDICT r1 missing #4).
    """
    N, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (N, T))
    # each prompt's last row, over the [N * T] rows
    rows = jnp.arange(N, dtype=jnp.int32) * T + true_lens - 1
    with lora_context(lora_idx):
        attend = _sa.make_prefill_attend_select(cfg, tables, true_lens) \
            if cfg.selects else _attend(
                cfg, lambda t, w, _: make_prefill_attend_batch_paged_carry(
                    t, true_lens, window=w), tables, wtables)
        logits, cache = model_forward_carry(
            params, cfg, tokens, positions, cache, attend,
            _recur(cfg, _la.make_recur_batch, slots, true_lens),
            head_rows=None if prompt_logprobs else rows)
    with jax.named_scope(parts.SAMPLE):
        last = _head(logits, rows)                             # [N, V]
        last = _apply_prefill_repetition(last, tokens, true_lens, reps)
        if bias_ids is not None:
            last = _apply_logit_bias(last, bias_ids, bias_vals)
        if ban_ids is not None:
            last = _mask_banned(last, ban_ids, ban_until, true_lens)
        last = _apply_allow(last, allow)
        keys = per_slot_keys(seeds, true_lens) if seeds is not None else rng
        toks = sample(last, keys, temperature, top_k, top_p)
        out = [cache, toks]
        if logprobs:
            out.append(_logprob_topk(last, toks))
        if prompt_logprobs:
            out.append(_prompt_logprobs(logits, tokens))
    return tuple(out)


@partial(jax.jit, static_argnums=(0,), static_argnames=("logprobs",),
         donate_argnums=(2,))
def prefill_chunk_step(cfg: ModelConfig, params, cache, tokens, start,
                       chunk_len, rng, temperature, top_k, top_p, *, pages,
                       logprobs: bool = False, seed=None,
                       ban_ids=None, ban_until=None,
                       bias_ids=None, bias_vals=None, rep=None,
                       rep_seen=None, allow=None, lora_idx=None, slot=None,
                       wpages=None):
    """Prefill ONE chunk of a long prompt; decode interleaves between chunks.

    tokens: [1, C] (the chunk, right-padded on the final chunk); start: row
    offset of this chunk in the slot; chunk_len: valid tokens in this chunk;
    pages: [max_pages] int32, the slot's block table.
    Returns (cache, sampled token from the chunk's last valid row) — the host
    uses the token only after the FINAL chunk (it is the request's first
    generated token); for earlier chunks it is discarded. One compiled
    program for all chunks (C static), versus one program per prompt-length
    bucket for whole-prompt prefill.
    """
    C = tokens.shape[1]
    positions = start + jnp.arange(C, dtype=jnp.int32)[None, :]
    with lora_context(lora_idx):
        attend = _sa.make_chunk_prefill_attend_select(
            cfg, pages, start, chunk_len) if cfg.selects \
            else _attend(
                cfg, lambda t, w, _: make_chunk_prefill_attend_paged_carry(
                    t, start, window=w), pages, wpages)
        logits, cache = model_forward_carry(
            params, cfg, tokens, positions, cache, attend,
            _recur(cfg, _la.make_recur_span, slot, start, chunk_len),
            head_rows=(chunk_len - 1)[None])
    with jax.named_scope(parts.SAMPLE):
        last = logits                   # [1, V]: the chunk's last valid row
        if rep is not None and rep_seen is not None:
            # chunks only carry a slice of the prompt: the seen-set over the
            # WHOLE context comes precomputed from the host ([V] bool)
            r = rep.astype(jnp.float32)
            lf = last.astype(jnp.float32)
            last = jnp.where(rep_seen[None],
                             jnp.where(lf > 0, lf / r, lf * r), lf)
        if bias_ids is not None:
            last = _apply_logit_bias(last, bias_ids[None], bias_vals[None])
        if ban_ids is not None:
            last = _mask_banned(last, ban_ids[None], ban_until[None],
                                (start + chunk_len)[None])
        last = _apply_allow(last, allow)
        # ctr = start + chunk_len = the full context length at the FINAL
        # chunk (the only one whose sample survives) — matching what
        # decode/prefill would use for the same position, so seeded streams
        # are chunking-layout independent.
        keys = per_slot_keys(seed[None], (start + chunk_len)[None]) \
            if seed is not None else rng
        token = sample(last, keys, temperature[None], top_k[None],
                       top_p[None])[0]
        if logprobs:
            return cache, token, _logprob_topk(last, token[None])
    return cache, token


@partial(jax.jit, static_argnums=(0, 1), static_argnames=("mesh", "impl",
                                                          "logprobs",
                                                          "penalties",
                                                          "bblock"),
         donate_argnums=(3, 4, 5), donate_argnames=("counts",))
def decode_steps(cfg: ModelConfig, n_steps: int, params, cache, tokens,
                 lengths, rng, temperature, top_k, top_p, *, table,
                 mesh=None, impl: str = "auto", logprobs: bool = False,
                 counts=None, presence=None, frequency=None,
                 repetition=None, prompt_mask=None,
                 penalties: bool = False, seeds=None,
                 ban_ids=None, ban_until=None, bias_ids=None,
                 bias_vals=None, allow=None, lora_idx=None,
                 bblock: int = 1, live=None, wtable=None, steps=None):
    """Up to ``n_steps`` fused decode steps for every slot, one device
    dispatch: ``steps`` of them, a traced int32 scalar in [1, n_steps]
    (None: all ``n_steps``). ONE compiled program serves every count — the
    static ``n_steps`` only sizes the outputs.

    tokens/lengths/sampling params: [B]; ``cache`` is the paged pool and
    ``table`` [B, max_pages] int32 the slots' block tables. Returns
    (cache, counts, out [n_steps, B], last_tok [B], lens [B], moe) — rows
    of ``out`` (and of the logprob arrays) at and past ``steps`` are zeros
    the host never reads; the
    final token/length carry stays device-resident so a pipelined engine can
    feed dispatch N's carry straight into dispatch N+1 (donated, no host
    round-trip; see EnginePrograms._decode_dispatch). ``moe`` is None for a
    dense model; for an MoE model ``live`` [B] bool marks the slots that
    hold a request (an idle slot's row is routed to no expert) and ``moe``
    is float32 [2]: experts with a live row (mean over the substeps run and
    the layers) and the rows of the largest group (``_moe_summary``).

    Fusing the token loop into one device loop is a TPU-first scheduling
    decision: per-dispatch host→device latency (worst over a network-attached
    chip) is paid once per *horizon* instead of once per token, and XLA keeps
    the KV cache resident in HBM across all substeps (donated carry, in
    place in the loop). What is enqueued stands between an arrival and its
    admission, so the scheduler asks for the whole horizon only while no
    admission can follow the dispatch and for the fewest substeps that keep
    the device fed while one can (EnginePrograms._decode_horizon). Slots
    that hit a stop condition mid-horizon generate a few
    surplus tokens which the host discards; surplus K/V writes past
    ``max_len`` are dropped (cache_write_row_paged masks rows outside the
    window; the XLA fallback's scatter drops them natively) — never corrupt
    memory.
    """

    def body(carry, rng_i):
        cache, cnts, tok, lens = carry
        positions = lens[:, None]
        # Carry-path forward: the cache stays in place in the scan carry and
        # attention reads it layer-indexed — no per-layer xs→ys copy (the
        # copy cost dominated decode at ~24 ms/token on v5e; see
        # model_forward_carry's docstring); the kernels address the pool's
        # pages through the block ``table``.
        attend = _sa.make_decode_attend_select(
            cfg, lens, table, impl=impl, bblock=bblock, live=live) \
            if cfg.selects \
            else _attend(
                cfg, lambda t, w, kind: make_decode_attend_carry_paged(
                    lens, t, impl=impl, mesh=mesh, window=w, bblock=bblock,
                    of_window_kind=kind), table, wtable)
        with _moe.routed_rows(live) as routing, _sa.counting() as picked:
            logits, cache = model_forward_carry(
                params, cfg, tok[:, None], positions, cache, attend,
                _recur(cfg, _la.make_recur_decode, live))
        with jax.named_scope(parts.SAMPLE):
            step_logits = logits[:, 0, :]
            if penalties:
                # presence/frequency/repetition over the [B, V]
                # generated-token counts that ride the carry (updated per
                # sampled token, so a mid-horizon repeat is penalized
                # immediately, not at the next dispatch); repetition
                # additionally covers the prompt mask
                step_logits = apply_penalties(
                    step_logits, cnts, presence, frequency, repetition,
                    prompt_mask)
            # OpenAI logit_bias: additive on logits before every sampling
            # decision, then min_tokens stop suppression (mask wins: a +100
            # bias on eos must not resurrect a banned stop token). The ban
            # evaluates PER SUBSTEP (lens rides the carry), so it can expire
            # mid-horizon exactly when vLLM's would.
            step_logits = _apply_logit_bias(step_logits, bias_ids, bias_vals)
            step_logits = _mask_banned(step_logits, ban_ids, ban_until, lens)
            # Guided mask is computed for substep 0's state only: in mixed
            # batches the host emits just that substep for guided slots and
            # discards the rest (penalized guided slots force horizon 1 so
            # the per-substep count updates above never cover discarded
            # tokens — see _do_decode).
            step_logits = _apply_allow(step_logits, allow)
            # ctr = lens + 1 = the context length this draw extends TO:
            # distinct from the prefill draw's ctr (= prompt length) and
            # equal to what a preemption-resume prefill of the same position
            # would use — the seed contract's cross-resume reproducibility
            # hangs on this alignment (review r3).
            keys = per_slot_keys(seeds, lens + 1) if seeds is not None \
                else rng_i
            nxt = sample(step_logits, keys, temperature, top_k, top_p)
            if penalties:
                cnts = cnts.at[jnp.arange(cnts.shape[0]), nxt].add(1)
            if logprobs:
                nxt_out = (nxt, _logprob_topk(step_logits, nxt))
            else:
                nxt_out = nxt
        return (cache, cnts, nxt, lens + 1), (nxt_out, routing["stats"],
                                              picked["pages"])

    if counts is None:
        counts = jnp.zeros((tokens.shape[0], 1), jnp.int32)  # unused dummy
    rngs = jax.random.split(rng, n_steps)
    carry = (cache, counts, tokens, lengths)
    if steps is None:
        steps = n_steps

    def substep(i, state):
        carry, outs = state
        carry, ys = body(carry, rngs[i])
        return carry, jax.tree.map(
            lambda buf, y: jax.lax.dynamic_update_index_in_dim(buf, y, i, 0),
            outs, ys)

    with lora_context(lora_idx):
        # each substep's outputs land in their row of buffers sized for
        # n_steps (shapes only: the body is traced for the loop alone)
        outs = jax.tree.map(
            lambda y: jnp.zeros((n_steps,) + y.shape, y.dtype),
            jax.eval_shape(lambda c, r: body(c, r)[1], carry, rngs[0]))
        (cache, counts, tok, lens), (out, moe, picked) = jax.lax.fori_loop(
            0, steps, substep, (carry, outs))
    return cache, counts, out, tok, lens, _aux(moe, picked, steps)


@partial(jax.jit, static_argnums=(0,),
         static_argnames=("mesh", "impl", "logprobs", "chunk_logprobs",
                          "penalties", "bblock"),
         donate_argnums=(2, 3, 4), donate_argnames=("counts",))
def mixed_step(cfg: ModelConfig, params, cache, tokens, lengths, ptokens,
               pslot, pstart, plen, prep, prep_seen, pseed, ptemp, ptop_k,
               ptop_p, rng, temperature, top_k, top_p, *, table, mesh=None,
               impl: str = "auto", logprobs: bool = False,
               chunk_logprobs: bool = False, counts=None, presence=None,
               frequency=None, repetition=None, prompt_mask=None,
               penalties: bool = False, seeds=None,
               ban_ids=None, ban_until=None, bias_ids=None, bias_vals=None,
               allow=None, pallow=None, lora_idx=None, bblock: int = 1,
               live=None, wtable=None):
    """ONE ragged dispatch serving a mixed batch: a decode step for every
    active slot AND one prefill chunk of slot ``pslot`` — the program that
    lets the one-deep pipeline ride across prefill admissions instead of
    draining on every chunk edge (ISSUE 14 / ROADMAP open item 2; the
    variable-length-rows layout follows Ragged Paged Attention, arxiv
    2604.15464).

    Layout: the forward pass runs ONCE over a query-token-packed sequence
    ``[1, B + W]`` — B decode rows (token ``tokens[b]`` at position
    ``lengths[b]``), then the first W chunk rows of ``ptokens`` at positions
    ``pstart + j``. MLP/norm/projections are per-token, so packing changes
    nothing; attention goes through make_mixed_attend_carry_paged, whose
    per-row (write row, live-column limit, page-table row) metadata gives
    each packed row exactly the view the separate decode/chunk programs
    gave it — byte-identical streams either way (pinned by
    tests/test_decode_pipeline.py's ragged parity cases).

    TWO widths, one program: ``ptokens`` arrives padded to the chunk width
    C = ``ptokens.shape[1]`` whatever the chunk holds, and the program holds
    its layers (``body``: everything whose shape has the chunk's rows in
    it) twice — over W = C rows and over W = C // 2, the first half of
    ``ptokens`` — and runs the narrow body where ``plen`` fits it
    (``mixed_takes_narrow``; the engine's record states the rows that ran
    by the same predicate). The projections and the MLP run over every
    packed row whether it holds a token or not, so a prompt of a quarter of
    the chunk pays for half the rows, not all. Where the shapes admit no
    second width (``mixed_narrow_rows``: read from the shapes, no option)
    the program is the one body. What comes out of a body — the sampled
    rows' logits ``[B + 1, V]``, the pool, the routing summary, the page
    counts — has no W in its shape; the sampling tail below is one.

    ``pslot``'s own decode row is a dead passenger while it chunks, and so
    is every chunk row at or past ``plen``: a dead row's K/V is not written
    (the decode row's write row is -1, the chunk is written as the span
    [pstart, pstart + plen)), it attends nothing (limit 0), which costs the
    ragged kernel nothing, it is routed to no expert and advances no state
    — so the rows ``[C // 2, C)`` the narrow body leaves out are rows that
    changed nothing in the wide one. For ``pslot``
    the returned carry overrides its lanes with the chunk's sample
    (``tok_out[pslot] = chunk token``, ``lens_out[pslot] = pstart + plen``)
    so the device carry matches the host mirrors a final-chunk activation
    produces — the generation-stamped carry extended to cover
    prefill-admitted slots.

    Sampling matches the programs it replaces exactly: decode rows take the
    decode_steps transform order (penalties → bias → ban(lens) → allow →
    seeded key at lens + 1); the chunk's last valid row takes
    prefill_chunk_step's (host rep_seen → bias → ban at pstart + plen →
    allow → seeded key at pstart + plen). Only the FINAL chunk's sample
    survives on the host.

    Feature operands (ISSUE 16 — no feature de-pipelines the batch):
    ``allow`` [B, ceil(V/32)] uint32 masks the decode rows (guided slots'
    FSM bitsets, all-ones elsewhere); ``pallow`` [1, ceil(V/32)] masks the
    chunk row when the CHUNKING request itself is guided. Both are program
    variants (None = compiled out). ``lora_idx`` [B] per-slot adapter
    indices are packed in-program to per-TOKEN indices over the [1, B + W]
    layout (the chunk rows inherit ``lora_idx[pslot]``), selecting each
    row's A/B delta inside one shared program (models/layers._linear's
    per-token branch).

    Returns (cache, counts, out [1, B] (+logprobs), chunk token [1]
    (+chunk logprobs), tok_carry [B], lens_carry [B], moe). For an MoE model
    ``live`` [B] bool marks the slots that hold a request; dead passengers
    and the chunk's padding rows are routed to no expert, and ``moe`` is
    decode_steps' pair over the packed rows that carry a token.
    """
    B = tokens.shape[0]
    C = ptokens.shape[1]
    is_p = jnp.arange(B, dtype=jnp.int32) == pslot

    def body(W: int, cache):
        """The layers over ``B + W`` packed rows: the chunk's first ``W``
        (static). Returns (cache, logits [B + 1, V], routing stats, picked
        pages): nothing with ``W`` in its shape."""
        crows = pstart + jnp.arange(W, dtype=jnp.int32)
        # the chunk is padded to W rows: rows past the prompt are dead too
        is_pad = jnp.arange(W, dtype=jnp.int32) >= plen
        row_limits = jnp.concatenate(
            [jnp.where(is_p, jnp.int32(0), lengths + 1),
             jnp.where(is_pad, jnp.int32(0), crows + 1)])
        # which row of ``table`` (one a SLOT) each packed row reads: a
        # decode row its own slot's, every chunk row pslot's
        row_map = jnp.concatenate(
            [jnp.arange(B, dtype=jnp.int32),
             jnp.broadcast_to(pslot.astype(jnp.int32), (W,))])
        packed = jnp.concatenate([tokens[None], ptokens[:, :W]],
                                 axis=1)                       # [1, B+W]
        positions = jnp.concatenate(
            [jnp.where(is_p, jnp.int32(0), lengths)[None], crows[None]],
            axis=1)
        # K/V writes: one row a decode slot (pslot's own dropped), the chunk
        # as the span [pstart, pstart + plen) of pslot's page run
        if cfg.selects:
            attend = _sa.make_mixed_attend_select(
                cfg, jnp.where(is_p, jnp.int32(-1), lengths), pstart, plen,
                row_limits, table, row_map, impl=impl, bblock=bblock,
                live=live)
        else:
            attend = _attend(
                cfg, lambda t, w, kind: make_mixed_attend_carry_paged(
                    jnp.where(is_p, jnp.int32(-1), lengths), pstart, plen,
                    row_limits, t, row_map, impl=impl, mesh=mesh, window=w,
                    bblock=bblock, of_window_kind=kind), table, wtable)
        # Per-TOKEN adapter indices over the packed layout: decode row b
        # keeps its slot's adapter, every chunk row runs the chunking
        # slot's — one program serves any adapter mix (models/layers._linear
        # gathers factors per token when the index rank matches x's row
        # rank).
        packed_lora = None
        if lora_idx is not None:
            packed_lora = jnp.concatenate(
                [lora_idx, jnp.broadcast_to(lora_idx[pslot], (W,))])[None]
        packed_live = None
        if live is not None:
            packed_live = jnp.concatenate([live & ~is_p, ~is_pad])
        recur = None
        if cfg.recurrent:   # (not through _recur: its operands would be traced)
            recur = _la.make_recur_mixed(
                B, None if live is None else live & ~is_p, pslot, pstart,
                plen)
        with lora_context(packed_lora), \
                _moe.routed_rows(packed_live) as routing, \
                _sa.counting() as picked:
            # the head over the rows that are sampled: every decode row and
            # the chunk's last valid one — [B + 1, V], not [B + W, V]
            logits, cache = model_forward_carry(
                params, cfg, packed, positions, cache, attend, recur,
                head_rows=jnp.concatenate(
                    [jnp.arange(B, dtype=jnp.int32), (B + plen - 1)[None]]))
        return cache, logits, routing["stats"], picked["pages"]

    narrow = mixed_narrow_rows(cfg, B, C, cache["k"].shape[3], bblock,
                               table.shape[1], cache["k"].dtype)
    if narrow:
        # Both bodies in the ONE program, each inside a loop that runs once
        # or not at all: the operand the program already has picks. A loop,
        # not ``lax.cond``: the pool rides a loop's carry in place (as
        # through decode_steps' substeps), while under a conditional the
        # TPU compiler copied it into and out of one branch's layer loop
        # at EVERY layer (deviceless compile, PR 55: two pool-sized copies
        # a leaf a layer in the 8B's, OLMoE's and Falcon-H1's programs —
        # 16.7 GB asked of the 8B cell's 15.75).
        takes = mixed_takes_narrow(plen, narrow).astype(jnp.int32)
        state = (cache,) + jax.tree.map(
            lambda y: jnp.zeros(y.shape, y.dtype),
            jax.eval_shape(partial(body, C), cache)[1:])
        for rows, times in ((narrow, takes), (C, 1 - takes)):
            state = jax.lax.fori_loop(
                0, times, lambda _, st, rows=rows: body(rows, st[0]), state)
        cache, logits, moe, picked = state
    else:
        cache, logits, moe, picked = body(C, cache)
    with jax.named_scope(parts.SAMPLE):
        # -- decode rows: the decode_steps substep body, verbatim order ----
        dec_logits = logits[:B]
        if penalties:
            dec_logits = apply_penalties(dec_logits, counts, presence,
                                         frequency, repetition, prompt_mask)
        dec_logits = _apply_logit_bias(dec_logits, bias_ids, bias_vals)
        dec_logits = _mask_banned(dec_logits, ban_ids, ban_until, lengths)
        dec_logits = _apply_allow(dec_logits, allow)
        keys = per_slot_keys(seeds, lengths + 1) if seeds is not None else rng
        nxt = sample(dec_logits, keys, temperature, top_k, top_p)
        if penalties:
            # pslot's lane counts a garbage sample; _activate's count-row
            # reset/restore at the final chunk wipes it (same policy as its
            # stale-occupant rows)
            counts = counts.at[jnp.arange(counts.shape[0]), nxt].add(1)
        # -- chunk row: the prefill_chunk_step tail, verbatim order --------
        plast = logits[B:]                                        # [1, V]
        if prep is not None and prep_seen is not None:
            r = prep.astype(jnp.float32)
            lf = plast.astype(jnp.float32)
            plast = jnp.where(prep_seen[None],
                              jnp.where(lf > 0, lf / r, lf * r), lf)
        plast = _apply_logit_bias(plast, bias_ids[pslot][None],
                                  bias_vals[pslot][None])
        plast = _mask_banned(plast, ban_ids[pslot][None],
                             ban_until[pslot][None], (pstart + plen)[None])
        plast = _apply_allow(plast, pallow)
        pkeys = per_slot_keys(pseed[None], (pstart + plen)[None]) \
            if pseed is not None else rng
        ptok = sample(plast, pkeys, ptemp[None], ptop_k[None], ptop_p[None])
        # -- regenerated carry: pslot's lanes become the chunk frontier ----
        tok_out = jnp.where(is_p, ptok[0], nxt)
        lens_out = jnp.where(is_p, pstart + plen, lengths + 1)
        if counts is None:
            counts = jnp.zeros((B, 1), jnp.int32)  # unused dummy
        out = (nxt[None],
               tuple(a[None] for a in _logprob_topk(dec_logits, nxt))) \
            if logprobs else nxt[None]
        pout = (ptok, _logprob_topk(plast, ptok)) if chunk_logprobs else ptok
    return cache, counts, out, pout, tok_out, lens_out, _aux(moe, picked)


@partial(jax.jit, static_argnums=(0, 1), static_argnames=("impl", "mesh",
                                                          "bblock"),
         donate_argnums=(3,))
def spec_decode_step(cfg: ModelConfig, R: int, params, cache, tokens,
                     lengths, rng, temperature, top_k, top_p, *, table,
                     impl: str = "auto", seeds=None, mesh=None,
                     lora_idx=None, bblock: int = 1):
    """Speculative verify: R tokens per slot in ONE dispatch.

    tokens: [B, R] = [last accepted token, spec_k prompt-lookup drafts];
    returns (cache, out [B, R], accepted [B]) where out[b, :accepted[b]] are
    the emitted tokens (accepted draft prefix + one correction/bonus token
    from the target model). Greedy-lossless: a greedy slot's emitted tokens
    are exactly the plain-decode sequence — the verify pass computes the
    target model's argmax at every draft position and accepts only the
    matching prefix. Sampled slots (temperature > 0) accept nothing and
    sample one token from position 0, preserving their distribution.

    K/V rows for all R positions are written in place; rows past the
    accepted prefix are garbage BEYOND the slot's new length and get
    overwritten when those positions are next processed (the engine's
    standard surplus-write invariant — see decode_steps).
    """
    B = tokens.shape[0]
    positions = lengths[:, None] + jnp.arange(R, dtype=jnp.int32)[None, :]
    attend = make_spec_attend_carry_paged(lengths, table, impl=impl,
                                          mesh=mesh,
                                          window=cfg.attn_window,
                                          bblock=bblock)
    with lora_context(lora_idx):
        logits, cache = model_forward_carry(params, cfg, tokens, positions,
                                            cache, attend)
    with jax.named_scope(parts.SAMPLE):
        preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)      # [B, R]
        drafts = tokens[:, 1:]                                     # [B, R-1]
        match = (drafts == preds[:, :-1]).astype(jnp.int32)
        m = jnp.cumprod(match, axis=-1).sum(axis=-1)               # [B]
        greedy = temperature <= 0.0
        m = jnp.where(greedy, m, 0)
        # same ctr convention as decode_steps: this draw extends the context
        # to lengths + 1
        keys = per_slot_keys(seeds, lengths + 1) if seeds is not None else rng
        sampled0 = sample(logits[:, 0], keys, temperature, top_k, top_p)
        correction = jnp.where(greedy, preds[jnp.arange(B), m], sampled0)
        pos = jnp.arange(R - 1, dtype=jnp.int32)[None, :]
        out = jnp.where(pos < m[:, None], drafts, 0)
        out = jnp.concatenate([out, jnp.zeros((B, 1), jnp.int32)], axis=1)
        out = out.at[jnp.arange(B), m].set(correction)
    return cache, out, m + 1


# ---------------------------------------------------------------------------
# EnginePrograms — the per-engine compiled-program surface
# ---------------------------------------------------------------------------


class EnginePrograms:
    """Mixin holding the Engine's compiled-program surface: operand
    construction, program dispatch, bblock autotune, and warmup. ``Engine``
    (serving/engine.py) inherits this; the split keeps the scheduler
    host-side logic and the jit layer in separate files with zero behavior
    change. Methods here reach scheduler state (``self.sched``, slot arrays,
    page allocators) through the subclass."""

    # -- decode batch-block autotune ----------------------------------------

    # injectable for the deterministic-selection tests (fake timer)
    _bblock_timer = staticmethod(time.perf_counter)

    def _fit_bblock(self, req: int) -> int:
        """Largest divisor of the slot count not exceeding the request."""
        bb = max(1, min(int(req), self.num_slots))
        while self.num_slots % bb:
            bb -= 1
        return bb

    def _bblock_autotune_supported(self) -> bool:
        """The microbench dispatches the real paged kernel, so it needs the
        TPU: never under JAX_PLATFORMS=cpu (tier-1 must stay
        fast — interpret-mode timing is meaningless anyway). Single-device
        engines call the kernel directly (_bblock_bench_once); tp/dp meshes
        bench through the same shard_map wrapper the decode program uses
        (_bblock_bench_once_mesh), so the timing includes each chip's head/
        page slice and the dp table rebase — closing the ROADMAP gap where
        meshes pinned bb=1 until tuned explicitly."""
        return jax.default_backend() == "tpu"

    def _bblock_bench_once(self, bb: int) -> None:
        """One steady-state decode-attention dispatch at block size ``bb``:
        full-window lengths (every page live — the worst-case stream the
        served config must sustain) over a synthetic table cycling the
        pool's real pages. Blocks until the result is ready so the timer
        wraps device time, not dispatch issue."""
        from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention

        cfg = self.cfg
        ps = self.serving.page_size
        q = jnp.zeros((self.num_slots, 1, cfg.num_heads, cfg.pool_head_dim),
                      jnp.bfloat16 if self.serving.dtype == "bfloat16"
                      else jnp.float32)
        lengths = jnp.full((self.num_slots,), self.pages_per_slot * ps,
                           jnp.int32)
        total = self.cache["k"].shape[1]
        tab = (np.arange(self.num_slots * self.pages_per_slot,
                         dtype=np.int32).reshape(self.num_slots,
                                                 self.pages_per_slot)
               % max(1, total - 1)) + 1          # skip the scratch page
        kw = {}
        if self.kv_quant:
            kw = dict(pool_ks=self.cache["ks"], pool_vs=self.cache["vs"])
        out = pallas_attention.decode_attend_pallas_paged(
            q, self.cache["k"], self.cache["v"], lengths, jnp.int32(0),
            jnp.asarray(tab), bblock=bb, window=self.cfg.sliding_window,
            **kw)
        jax.block_until_ready(out)

    def _bblock_synthetic_table(self) -> np.ndarray:
        """Full-window synthetic block table with GLOBAL page ids: each
        slot's pages cycle inside its dp group's pool partition, skipping
        the group's scratch page (allocators hand out first_page=1), so the
        shard_map body's global→local rebase lands in range on every chip.
        dp=1 reduces to the single-pool case."""
        total = self.cache["k"].shape[1]
        dp = self.mesh.shape.get("dp", 1) if self.mesh is not None else 1
        gp = total // dp                      # pages per dp-group partition
        spg = self.num_slots // dp            # slots per group
        tab = np.empty((self.num_slots, self.pages_per_slot), np.int32)
        for s in range(self.num_slots):
            base = (s // spg) * gp
            tab[s] = base + (np.arange(self.pages_per_slot, dtype=np.int32)
                             % max(1, gp - 1)) + 1
        return tab

    def _bblock_bench_once_mesh(self, bb: int) -> None:
        """One steady-state decode-attention dispatch under the mesh: the
        same shard_map wrapper the decode program uses
        (make_decode_attend_carry_paged), so each chip runs the kernel on
        its head/page slice of the sharded pool and dp tables rebase —
        timing the path the served config actually dispatches. The pool
        rides through untouched (the returned copy is dropped; warmup
        re-dispatches on the real state later)."""
        cfg = self.cfg
        lengths = jnp.full((self.num_slots,),
                           self.pages_per_slot * self.serving.page_size,
                           jnp.int32)
        tab = jnp.asarray(self._bblock_synthetic_table())
        attend = make_decode_attend_carry_paged(
            lengths, tab, impl="pallas", mesh=self.mesh,
            window=cfg.sliding_window, bblock=bb)
        acc = jnp.bfloat16 if self.serving.dtype == "bfloat16" \
            else jnp.float32
        q = jnp.zeros((self.num_slots, 1, cfg.num_heads, cfg.pool_head_dim),
                      acc)
        kv = jnp.zeros((self.num_slots, 1, cfg.pool_kv_heads,
                        cfg.pool_head_dim), acc)
        ctx, _ = attend(q, kv, kv, (self.cache, jnp.int32(0)))
        jax.block_until_ready(ctx)

    def _bblock_cache_key(self) -> tuple:
        """Per-config winner key; meshes append their axis shape so a tp=8
        winner never leaks onto a dp=2 engine (or single-device, whose key
        stays the historical 3-tuple)."""
        key = (self.num_slots, self.serving.page_size,
               "int8" if self.kv_quant else "bf16")
        if self.mesh is not None:
            key += (tuple(sorted(self.mesh.shape.items())),)
        return key

    def _resolve_decode_bblock(self) -> int:
        env = os.environ.get("PALLAS_DECODE_BBLOCK", "")
        req = int(env) if env.strip() else int(self.serving.decode_bblock)
        if req > 0:
            return self._fit_bblock(req)     # explicit pin wins, no bench
        key = self._bblock_cache_key()
        if key in _BBLOCK_CACHE:
            return self._fit_bblock(_BBLOCK_CACHE[key])
        if not self._bblock_autotune_supported():
            return 1
        cands = [b for b in BBLOCK_CANDIDATES
                 if b <= self.num_slots and self.num_slots % b == 0]
        bench = self._bblock_bench_once if self.mesh is None \
            else self._bblock_bench_once_mesh
        choice = pick_decode_bblock(cands or [1], bench,
                                    timer=self._bblock_timer)
        _BBLOCK_CACHE[key] = choice
        return choice

    @staticmethod
    def _refuse_unsupported(cfg: ModelConfig, serving, mesh, lora) -> None:
        """What cannot be right yet for a model with recurrent layers, an
        expert share or selecting attention is refused at start-up, each
        with its reason."""
        if not (cfg.recurrent or cfg.expert_share or cfg.selects
                or cfg.windowed):
            return
        what = f"recurrent ({cfg.recurrent_kinds}) layers" if cfg.recurrent \
            else "an expert share" if cfg.expert_share \
            else "attention that selects its pages" if cfg.selects \
            else "window layers beside full ones"
        multi = mesh is not None or serving.mesh.num_devices > 1
        for bad, why in (
                (cfg.windowed and multi,
                 "--tp/--dp > 1: the window layers' page inventory has no "
                 "partition by dp group and its leaves no sharding rule"),
                (cfg.windowed and serving.spec_decode,
                 "speculative decoding: the verify program takes one table "
                 "a slot, and the window layers have their own"),
                (cfg.windowed and bool(lora),
                 "LoRA adapters: a prefix is salted by its adapter, and a "
                 "model that releases pages behind its window keeps no "
                 "prefix index"),
                (cfg.windowed and serving.kv_host_tier_bytes > 0,
                 "the host KV tier (--kv-host-tier-bytes > 0): a spilled "
                 "page would carry the full layers' K/V without the window "
                 "layers' pages, which are gone"),
                (cfg.windowed and serving.kv_dtype == "int8",
                 "int8 KV: the window layers' leaves have no scale leaves "
                 "and the one-table-row-a-slot ragged kernel reads a bf16 "
                 "pool"),
                (cfg.selects
                 and serving.page_size != cfg.sparse_block_size,
                 f"--page-size {serving.page_size}: its attention selects "
                 f"BLOCKS of {cfg.sparse_block_size} tokens, and a block is "
                 f"a page of the pool"),
                (cfg.selects and serving.kv_dtype == "int8",
                 "int8 KV: the selecting kernels read a bf16 pool (a key's "
                 "scale would have to enter the selector's sums)"),
                (cfg.selects and serving.spec_decode,
                 "speculative decoding: the verify kernel walks every page "
                 "of a row, not a selection"),
                (multi, "--tp/--dp > 1: no sharding rule says how the "
                 "per-slot state leaves or a share's expert stacks divide "
                 "over a mesh"),
                (cfg.recurrent and serving.spec_decode,
                 "speculative decoding: a rejected draft token has already "
                 "advanced the recurrent state, and no snapshot exists to "
                 "roll it back"),
                (cfg.recurrent and bool(lora),
                 f"LoRA adapters: no adapter layout names the "
                 f"{cfg.recurrent_kinds} layers' projections"),
                (cfg.recurrent and serving.kv_host_tier_bytes > 0,
                 "the host KV tier (--kv-host-tier-bytes > 0): a restored "
                 "page carries K/V without the recurrent state that goes "
                 "with it"),
                (cfg.recurrent and serving.kv_dtype == "int8",
                 "int8 KV: the recurrent state is float32 and the mixed "
                 "cache has not been compared with the reference"),
                (cfg.expert_share and cfg.moe_impl == "gshard",
                 "moe_impl 'gshard': its capacity dispatch has no rule for "
                 "a chosen expert that is held elsewhere")):
            if bad:
                raise ValueError(f"model {cfg.name} has {what} and cannot "
                                 f"be served with {why}")

    def _init_params_and_cache(self, mesh, lora):
        """Program-operand construction, moved verbatim from
        ``Engine.__init__``: dtype resolution, weight quantization, mesh
        build + parameter sharding, LoRA attach, draft-model wiring, and the
        paged KV pool allocation. Runs between the scheduler
        sizing above it and the host slot-state arrays below it."""
        cfg, params, serving = self.cfg, self.params, self.serving
        dtype = jnp.bfloat16 if serving.dtype == "bfloat16" else jnp.float32
        if serving.weights_dtype not in ("auto", "bf16", "int8"):
            # "int8" is the SHIPPED default (PERF.md: the weight stream is the
            # dominant bytes/token term at small batch); "bf16" (alias
            # "auto") is the explicit opt-out that keeps the load dtype.
            raise ValueError(f"weights_dtype={serving.weights_dtype!r}: "
                             f"expected 'int8' (default), 'bf16', or 'auto'")
        if serving.weights_dtype == "int8":
            # Weights-only int8 (models/quant.py): quantized on host/device
            # BEFORE the mesh sharding below, so each chip receives the
            # int8 shard (half the transfer and half the resident bytes).
            from aws_k8s_ansible_provisioner_tpu.models.quant import (
                quantize_params, weights_quantized)

            if weights_quantized(params):
                # Already-quantized tree (e.g. restored from an int8
                # checkpoint): re-quantizing would treat the int8 kernels as
                # values and overwrite the scale leaves — silent corruption,
                # not an error. Skip; sharding handles quantized trees.
                pass
            else:
                # host=True under a mesh: leaf-wise numpy quantization so no
                # single chip ever holds the full unquantized tree (the
                # jitted path would device_put it whole — the 8B-on-v5e-8
                # OOM the sharded loader exists to avoid)
                self.params = params = quantize_params(
                    params, cfg,
                    host=mesh is not None or serving.mesh.num_devices > 1)
        if serving.kv_dtype not in ("auto", "int8"):
            # An unrecognized value (e.g. "fp8", "INT8") must not silently
            # degrade to the unquantized cache — capacity would halve with no
            # error until an OOM much later.
            raise ValueError(f"kv_dtype={serving.kv_dtype!r}: expected "
                             f"'auto' or 'int8'")
        self.kv_quant = serving.kv_dtype == "int8"
        self._refuse_unsupported(cfg, serving, mesh, lora)

        # Multi-chip serving: a (dp, tp) mesh shards params (Megatron TP),
        # slots over dp, and kv heads over tp (parallel/sharding.py). The
        # comms backend is XLA collectives over ICI — GSPMD partitions the
        # matmuls, shard_map runs the Pallas kernel per-shard (SURVEY.md §2.3:
        # every parallelism capability is net-new on the TPU side).
        self.mesh = mesh if mesh is not None else self._build_mesh(serving)
        if self.mesh is not None:
            from aws_k8s_ansible_provisioner_tpu.parallel.sharding import (
                check_tp_divisibility, shard_params)

            tp = self.mesh.shape["tp"]
            dp = self.mesh.shape["dp"]
            if self.mesh.shape.get("sp", 1) > 1:
                # a page is a contiguous row run: sharding the sequence axis
                # would split pages across chips
                raise ValueError(
                    "sequence-parallel serving (sp > 1) is not supported: "
                    "the KV pool shards pages over dp and KV heads over tp "
                    "— serve long contexts across chips with --tp")
            check_tp_divisibility(cfg, tp, self.mesh.shape.get("ep", 1))
            if cfg.num_experts > 0 and cfg.moe_impl != "gshard":
                # Distributed MoE must use the GSPMD-partitionable dispatch
                # formulation; ragged_dot's data-dependent groups would make
                # the compiler all-gather every expert (ops/moe.py). This
                # trades the exact no-drop impl for capacity-limited dispatch
                # — say so, loudly, or a quality difference vs single-device
                # serving is undiagnosable.
                import logging

                logging.getLogger(__name__).warning(
                    "MoE under a mesh: switching moe_impl ragged -> gshard "
                    "(capacity_factor=%s; tokens past an expert's capacity "
                    "fall back to the residual stream)",
                    cfg.moe_capacity_factor)
                cfg = self.cfg = cfg.scaled(moe_impl="gshard")
            if self.num_slots % dp:
                raise ValueError(f"max_decode_slots={self.num_slots} must be "
                                 f"divisible by dp={dp}")
            self.params = params = shard_params(params, self.mesh, cfg)
        # Multi-LoRA (models/lora.py): adapters stack along a leading
        # adapter axis and attach beside their target kernels, AFTER
        # quantization (int8 kernels keep f32-loaded LoRA factors separate)
        # — one compiled program serves every adapter mix via the per-slot
        # index vector the dispatches carry.
        self.lora_names: List[str] = []
        if lora:
            if self.mesh is not None:
                raise ValueError("multi-LoRA under a mesh is not wired yet "
                                 "(adapter-axis pspecs)")
            from aws_k8s_ansible_provisioner_tpu.models import lora as _lora

            items = list(lora.items())
            loaded = [_lora.load_adapter(path) for _, path in items]
            stacked = _lora.stack_adapters(loaded, cfg.num_layers, dtype)
            self.params = params = _lora.attach(params, stacked)
            self.lora_names = [name for name, _ in items]
        # What each part of the model weighs in the tree as it is served
        # (models/parts.py; per chip): tpu_serve_param_bytes{part} and
        # tpu_serve_param_elements{part}, and the start-up log
        self.param_weights = parts.param_weights(params, cfg)
        _metrics.params_by_part.publish(self.param_weights)
        import logging

        logging.getLogger(__name__).info(
            "params: %.3f GiB a chip — %s",
            sum(b for b, _ in self.param_weights.values()) / 2**30,
            ", ".join(f"{part} {b / 2**30:.3f} GiB ({n / 1e6:.1f} M "
                      f"matmul elements)" if n else
                      f"{part} {b / 2**30:.3f} GiB"
                      for part, (b, n) in self.param_weights.items()))
        # Speculation needs no mesh gate: every tp shard executes the
        # identical token stream, so the data-dependent accept length is
        # shard-invariant, and under dp accept lengths are per-slot host
        # state exactly like plain decode's variable lengths (parity pinned
        # by tests/test_spec_decode.py and dryrun_multichip).
        # Alternation flag: after a spec dispatch that skipped ineligible
        # slots (logprobs/penalties/min_tokens — _slot_spec_ineligible), the
        # next dispatch takes the plain fused path so those slots advance
        # every other step instead of starving.
        self._spec_plain_due = False
        # Draft-model proposer (serving/draft.py): replaces prompt-lookup as
        # the proposal source when spec_method="draft". The draft runs
        # UNSHARDED (it is small by design); everything else about the spec
        # path (verify program, per-slot eligibility, mesh gating) is shared.
        self.draft = None
        if serving.spec_method not in ("prompt_lookup", "draft"):
            raise ValueError(f"spec_method={serving.spec_method!r}: expected "
                             f"'prompt_lookup' or 'draft'")
        if serving.spec_method == "draft" and serving.spec_decode:
            if self._draft_src is None:
                raise ValueError("spec_method='draft' requires draft="
                                 "(draft_cfg, draft_params)")
            from aws_k8s_ansible_provisioner_tpu.serving.draft import (
                DraftModel)

            dcfg, dparams = self._draft_src
            if dcfg.vocab_size < cfg.vocab_size:
                raise ValueError(
                    f"draft vocab ({dcfg.vocab_size}) must cover the target "
                    f"vocab ({cfg.vocab_size}) — drafts are target token ids")
            self.draft = DraftModel(dcfg, dparams, self.num_slots,
                                    self.max_len, dtype, serving.page_size)
        # Tier-2 host store handle (None = tier off). /healthz and the fit
        # ledger read it.
        self.host_tier = None
        # True paged KV: shared page pool + block tables. Composes with tp
        # (and ep) meshes — the pool shards only its KV-HEAD axis, so page
        # identity, tables, and the host allocator are shard-invariant
        # (parallel/sharding.pool_pspecs) — AND with dp meshes (VERDICT r3
        # next #6): the pool's PAGE axis shards over dp, giving each
        # dp group its own pool partition with a per-group host allocator
        # (slots are dp-sharded, so a slot's pages always live in its own
        # group's partition; prefix sharing is group-local).
        ps = serving.page_size
        # the Pallas row-write kernels touch 8-row (bf16) / 32-row (int8)
        # sub-blocks that must divide the page
        align = 32 if self.kv_quant else 8
        if ps % align:
            raise ValueError(f"page_size={ps} must be a multiple of "
                             f"{align} for the "
                             f"{'int8' if self.kv_quant else 'bf16'} "
                             f"paged kernels")
        self.pages_per_slot = -(-self.max_len // ps)
        # dp groups: slots split evenly over dp (divisibility enforced
        # above); each group owns one partition of the pool's page axis
        # and its own host allocator working in LOCAL page ids. The
        # device-side table holds GLOBAL ids (local + group * partition),
        # so the GSPMD paths address the full pool directly and the
        # shard_map kernels subtract their own partition base.
        self.dp_groups = (self.mesh.shape.get("dp", 1)
                          if self.mesh is not None else 1)
        self._slots_per_group = self.num_slots // self.dp_groups
        pool_pages = serving.kv_pool_pages \
            or self.num_slots * self.pages_per_slot
        if serving.kv_pool_pages and pool_pages % self.dp_groups:
            # an explicit pool size must split exactly — silently
            # dropping the remainder would skew the operator's capacity
            # math by up to dp-1 pages (review r4)
            raise ValueError(
                f"kv_pool_pages={pool_pages} must be divisible by the "
                f"dp group count ({self.dp_groups})")
        group_pages = pool_pages // self.dp_groups
        if group_pages < self.pages_per_slot:
            # a lone max-length request must always be able to grow to
            # the window IN ITS OWN GROUP, or preemption would spin on
            # itself
            raise ValueError(
                f"kv_pool_pages={pool_pages} over {self.dp_groups} dp "
                f"group(s) gives {group_pages}/group < pages for one "
                f"full window ({self.pages_per_slot})")
        # +1 per group: local physical page 0 is that group's SCRATCH
        # page — every idle slot's table points at its group's scratch,
        # so the decode programs' per-slot garbage row writes can never
        # land in a page another slot owns.
        self._group_pages = group_pages + 1     # pool partition size
        total_pages = self.dp_groups * self._group_pages
        # A list with window layers beside full ones: the window layers'
        # K/V live in leaves, a table a slot and an inventory of their own,
        # sized by what a slot can hold AT MOST (kv_pool.window_inventory)
        self._win_slot_pages, self.win_pages = kvp.window_inventory(
            cfg, self.num_slots, self.pages_per_slot, ps,
            max(1, serving.decode_horizon),
            serving.prefill_chunk or max(self.buckets))
        if self.mesh is not None:
            # born sharded (pages over dp, heads over tp): no device ever
            # holds the full pool. Building it whole and re-sharding with
            # device_put would peak one device's HBM at the FULL pool size —
            # defeating the capacity scaling the mesh exists to provide
            from jax.sharding import NamedSharding

            from aws_k8s_ansible_provisioner_tpu.parallel.sharding import (
                pool_pspecs)

            out_sh = {name: NamedSharding(self.mesh, spec)
                      for name, spec in
                      pool_pspecs(self.kv_quant).items()}
            self.cache = jax.jit(
                lambda: kvp.init_pool(cfg, total_pages, ps, dtype,
                                      quant=self.kv_quant),
                out_shardings=out_sh)()
        else:
            self.cache = kvp.init_pool(cfg, total_pages, ps, dtype,
                                       quant=self.kv_quant,
                                       win_pages=self.win_pages)
        pool_leaves = {n: a for n, a in self.cache.items()
                       if n not in kvp.WINDOW_LEAVES.values()}
        # Recurrent layers keep per-SLOT state beside the pool, in the same
        # pytree the step programs donate (ops/linear_attention.py)
        self.kda_state_bytes = _la.state_bytes(cfg, self.num_slots, dtype)
        self.selector_bytes = kvp.selector_bytes(cfg, total_pages, ps)
        if cfg.recurrent:
            self.cache.update(_la.init_state(cfg, self.num_slots, dtype))
        if cfg.recurrent or cfg.selects:
            import logging

            logging.getLogger(__name__).info(
                "cache: KV pool %.3f GiB (%d attending layers x %d pages; "
                "of it the selector's cache %.3f GiB) + recurrent state "
                "%.3f GiB (%d %s layers x %d slots)",
                kvp.pool_bytes(cfg, total_pages, ps, dtype, self.kv_quant)
                / 2**30, cfg.num_attn_layers, total_pages,
                self.selector_bytes / 2**30,
                self.kda_state_bytes / 2**30, cfg.num_recurrent_layers,
                cfg.recurrent_kinds or "recurrent", self.num_slots)
        if "conv_tail" in self.cache:
            tail = self.cache["conv_tail"]
            logging.getLogger(__name__).info(
                "cache: conv tails %d bytes (%d conv layers x %d slots x %d "
                "rows of %d, float32: %d bytes a slot and layer) beside the "
                "KV pool's %d", tail.nbytes, *tail.shape,
                tail.nbytes // (tail.shape[0] * tail.shape[1]),
                kvp.pool_bytes(cfg, total_pages, ps, dtype, self.kv_quant))
        if "ssm_state" in self.cache:
            state, tail = self.cache["ssm_state"], self.cache["ssm_conv"]
            logging.getLogger(__name__).info(
                "cache: SSM state %d bytes (%d layers x %d slots x %d heads "
                "x [%d, %d], float32: %d bytes a slot and layer) + conv "
                "tails %d bytes (%d rows of %d, float32) beside the KV "
                "pool's %d", state.nbytes, state.shape[0], *state.shape[2:],
                state.nbytes // (state.shape[0] * state.shape[2]),
                tail.nbytes, *tail.shape[2:],
                kvp.pool_bytes(cfg, total_pages, ps, dtype, self.kv_quant))
        if cfg.windowed:
            import logging

            logging.getLogger(__name__).info(
                "cache: KV pool %.3f GiB — the %d full layers' inventory "
                "%.3f GiB (%d pages: %d slots x %d) + the %d window layers' "
                "%.3f GiB (%d pages: %d a slot of window %d, the chunk on "
                "top); one inventory and table a slot would hold %.3f GiB",
                kvp.pool_bytes(cfg, total_pages, ps, dtype,
                               win_pages=self.win_pages) / 2**30,
                cfg.num_attn_layers,
                kvp.pool_bytes(cfg, total_pages, ps, dtype) / 2**30,
                total_pages, self.num_slots, self.pages_per_slot,
                cfg.num_window_layers,
                kvp.pool_bytes(cfg, 0, ps, dtype,
                               win_pages=self.win_pages) / 2**30,
                self.win_pages, self._win_slot_pages, cfg.sliding_window,
                kvp.pool_bytes(cfg, total_pages, ps, dtype) / 2**30
                * cfg.num_layers / max(1, cfg.num_attn_layers))
        self.allocators = [pkv.PagePool(self._group_pages, ps,
                                        first_page=1)
                           for _ in range(self.dp_groups)]
        # the window layers' inventory (page 0 its scratch page) and, a
        # slot, the pages it holds there: logical pages [_wfirst[slot],
        # _wfirst[slot] + len(_slot_wpages[slot])) — what lies below went
        # back, and its table entries read scratch
        self.win_allocator = pkv.PagePool(self.win_pages, ps, first_page=1) \
            if cfg.windowed else None
        self.wtable = np.zeros((self.num_slots, self.pages_per_slot),
                               np.int32) if cfg.windowed else None
        self._slot_wpages: List[List[int]] = [[] for _ in
                                              range(self.num_slots)]
        self._wfirst = np.zeros(self.num_slots, np.int64)
        self._win_unreleased = 0    # sum of the slots' contexts, in pages
        _metrics.window_pool.reset(
            self.win_pages - 1 if cfg.windowed else 0)
        # Tier-2 KV (ISSUE 20): ONE host-RAM store shared by every dp
        # group's allocator — chain-hash keys are group-agnostic, so a
        # prefix evicted from one group's partition can restore into any
        # group's fresh pages. Budget 0 leaves the tier off entirely:
        # no spill log, no host walk in lookup_prefix — the
        # byte-identity escape hatch.
        if serving.kv_host_tier_bytes > 0:
            self.host_tier = pkv.HostTier(serving.kv_host_tier_bytes)
            for a in self.allocators:
                a.host_tier = self.host_tier
        # host metadata for spill/restore accounting (never touches the
        # device): per-page payload bytes across all leaves, and each
        # leaf's expected per-page shape [L, Hkv, page, (D)] — the
        # fetch-time truncation check behind chaos kv_offload_error
        self._page_bytes = sum(
            cfg.num_attn_layers * int(np.prod(arr.shape[2:]))
            * arr.dtype.itemsize for arr in pool_leaves.values())
        self._page_shapes = {
            name: (cfg.num_attn_layers,) + tuple(arr.shape[2:])
            for name, arr in pool_leaves.items()}
        # slot -> scheduled-but-unsettled restore record (timing +
        # byte accounting; correctness rides XLA data dependencies)
        self._restore_pending: dict = {}
        # program -> device seconds A SUBSTEP the last such dispatch took
        # (_dispatch_close; a program without substeps is one): how long
        # the one in flight will take (Engine._await_arrival) and what a
        # decode substep buys (_short_horizon)
        self._dispatch_s: dict = {}
        # what a dispatch has to cover for the device to stay fed: the
        # host's seconds from a fetch's return to the next enqueue's end
        # (_note_host), and the stamps it is taken from
        self._host_s = 0.0
        self._t_fetched = 0.0       # the last fetch's return
        self._t_awaited = 0.0       # Engine._await_arrival's last return
        self._waited_s = 0.0        # ... and what it waited since that fetch
        # per-slot global id of its group's scratch page (group 0's is 0,
        # preserving the single-device layout)
        self._scratch = np.repeat(
            np.arange(self.dp_groups, dtype=np.int32)
            * self._group_pages, self._slots_per_group)
        self.table = np.broadcast_to(
            self._scratch[:, None],
            (self.num_slots, self.pages_per_slot)).copy()
        self._slot_pages: List[List[int]] = [[] for _ in
                                             range(self.num_slots)]
        # req id -> prompt+generated context for preemption resume.
        # tpulint: disable=R5 per-key happens-before — submit() installs a key BEFORE sched.submit publishes the id, the step thread touches it only after; dict ops are GIL-atomic
        self._resume_ctx: dict = {}
        # admission recency per slot: preemption victims are newest-first
        self._admit_seq = np.zeros(self.num_slots, np.int64)
        self._seq_counter = 0

    # -- scheduling ---------------------------------------------------------

    def _want_logprobs(self, reqs) -> bool:
        return any(r is not None and r.logprobs is not None for r in reqs)

    def _ban_set(self, req: Request) -> set:
        """Tokens suppressed for this request while min_tokens is unmet —
        exactly the set _emit would stop on."""
        base = set() if req.ignore_eos else set(self._eos_set)
        return base | set(req.stop_token_ids)

    def _fill_sampling_rows(self, req: Request, slot: int):
        """Populate the slot's min_tokens ban and logit_bias rows from the
        request. Called BEFORE the prefill dispatch (so the FIRST sampled
        token already honors both — filling only at _activate would let it
        escape suppression/bias) and again at _activate (idempotent; covers
        the preemption-resume path)."""
        self._op_dirty_sampling = True
        self.ban_ids[slot, :] = 2**31 - 1
        if req.min_tokens > 0:
            bs = sorted(self._ban_set(req))[:BAN_K]
            self.ban_ids[slot, :len(bs)] = bs
            self.ban_until[slot] = len(req.prompt_ids) + req.min_tokens
        else:
            self.ban_until[slot] = 0
        self.lora_idx[slot] = (self.lora_names.index(req.lora) + 1
                               if req.lora is not None else 0)
        self.bias_ids[slot, :] = 2**31 - 1
        self.bias_vals[slot, :] = 0.0
        n = len(req.logit_bias)
        self._bias_n[slot] = n
        if n:
            self.bias_ids[slot, :n] = [t for t, _ in req.logit_bias]
            self.bias_vals[slot, :n] = [v for _, v in req.logit_bias]

    @staticmethod
    def _fill_allow(aw: np.ndarray, i: int, req: Request) -> None:
        """Overwrite row ``i`` of an allow-words array with the request's
        grammar mask. Grammar words for a smaller tokenizer vocab pad with
        zero bits — out-of-tokenizer model rows are never sampleable under
        guidance."""
        words = req.guided.mask_words()
        aw[i, :] = 0
        aw[i, :len(words)] = words

    def _lora_vec(self):
        return jnp.asarray(self.lora_idx) if self.lora_names else None

    def _lora_salt(self, idx: int):
        """Prefix-cache identity component for a slot's adapter: KV rows
        computed under adapter A must never prefix-hit a request running
        adapter B or the base model — wq/wk/wv project differently per
        adapter (review r5; vLLM folds lora_int_id into its block hash for
        the same reason). None for the base keeps pre-LoRA hash chains
        byte-compatible."""
        return ("lora", int(idx)) if idx else None

    def _allow_row(self, req: Request):
        """[1, ceil(V/32)] guided allow-bitmask device array for one request,
        or None (no-variant) when the request is unguided.

        One-entry device cache keyed on the request's FSM fingerprint
        (serving/guided.py): a guided CHUNKING request's state never
        advances mid-walk, so every mixed dispatch of the walk reuses the
        same device-resident mask — zero rebuild, zero re-upload (the
        mask-upload-overlap term in PERF.md's mixed-feature cost model).
        The upload itself is ``jnp.asarray`` — async enqueue, no blocking
        read (this helper is on the tpulint R8 dispatch path)."""
        if req.guided is None:
            return None
        key = (req.id, req.guided.fingerprint())
        cached = self._allow_dev
        if cached is not None and cached[0] == key:
            return cached[1]
        row = np.zeros((1, (self.cfg.vocab_size + 31) // 32), np.uint32)
        self._fill_allow(row, 0, req)
        arr = jnp.asarray(row)
        self._allow_dev = (key, arr)
        return arr

    def _allow_words(self, gslots: List[int]):
        """[B, ceil(V/32)] allow-bitmask covering all slots (unguided rows
        all-ones), or None when no guided slot is active.

        Same one-entry device cache as _allow_row, keyed on every guided
        slot's (slot, FSM fingerprint): consecutive dispatches whose
        grammar states did not advance (e.g. decode steps interleaved
        around a neighbor's chunk walk) skip both the numpy rebuild and
        the re-upload."""
        if not gslots:
            return None
        key = tuple((s, self.slot_req[s].guided.fingerprint())
                    for s in gslots)
        cached = self._allow_batch_dev
        if cached is not None and cached[0] == key:
            return cached[1]
        aw = np.full((self.num_slots, (self.cfg.vocab_size + 31) // 32),
                     0xFFFFFFFF, np.uint32)
        for s in gslots:
            self._fill_allow(aw, s, self.slot_req[s])
        arr = jnp.asarray(aw)
        self._allow_batch_dev = (key, arr)
        return arr

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _state_kw(self, name: str, value) -> dict:
        """The slot operand a prefill program takes for a model with
        recurrent layers (whose per-slot state it builds); no operand at
        all for any other model."""
        if not self.cfg.recurrent:
            return {}
        return {name: jnp.asarray(value, jnp.int32)}

    def _win_kw(self, name: str, rows=None) -> dict:
        """The window layers' table operand of a step program (``rows``: the
        slots whose rows it takes, a slot or an array of them; None = every
        slot's, from the operand cache); no operand at all for a model
        without window layers beside full ones."""
        if not self.cfg.windowed:
            return {}
        if rows is None:
            return {name: self._decode_operands()["wtable"]}
        return {name: self._donatable(self.wtable[rows])}

    def _kda_rows(self, rows: int, slots: int = 0, span: int = 0) -> dict:
        """Dispatch-record fields of a model with recurrent layers:
        ``state_rows`` (rows that advance a state in this dispatch, per
        layer: horizon x active for a decode dispatch), ``state_slots``
        (slots whose state a decode or mixed dispatch reads and writes) and
        ``state_kind`` (KDA, Lightning, conv, SSM); a model with KDA layers
        carries the first two as ``kda_rows`` / ``kda_slots`` too, the names
        its readers know; one with state-space mixers ``ssm_slots`` (the
        live rows its decode update streams), and of a mixed step's chunk
        ``ssm_span_rows`` (``span``) in ``ssm_span_blocks`` blocks of the
        span form."""
        if not self.cfg.recurrent:
            return {}
        out = {"state_rows": int(rows), "state_slots": int(slots),
               "state_kind": self.cfg.recurrent_kinds}
        if "k" in self.cfg.layer_pattern:
            out.update(kda_rows=int(rows), kda_slots=int(slots))
        if "h" in self.cfg.layer_pattern:
            out.update(ssm_slots=int(slots), ssm_span_rows=int(span),
                       ssm_span_blocks=-(-int(span) // _la.LIN_BLOCK))
        return out

    def _attn_pages(self, horizon: int, carry_steps: int) -> dict:
        """Dispatch-record fields of a plain decode dispatch, per attending
        layer, summed over its substeps: ``attn_pages_live`` (pages the rows
        hold: ceil((len + 1) / page) a slot, the window's dead pages off)
        and ``attn_pages_walked`` (pages the kernel's blocks walk: block
        rows x [the block's first live page, its longest row's last]), the
        blocks cut from the mirror's lengths as the device cuts them — each
        dp shard's rows in one ascending order (ops/attention._length_order;
        where the device takes no order, blocks of one row or one block,
        the sum is the same in any order). Every slot counts: the kernel
        walks an idle slot's row too. Their ratio is the walk's fill: the
        share of the blocks' masked updates that has a page to fold.
        ``attn_pages_copied`` is what the kernel's copies FETCH: a row
        starts a copy at a page step of its block's walk only inside its
        own range (pallas_attention._paged_db_body, ``fetches``), so it
        equals live — it was walked while a row past its pages re-copied
        its last one. A list with window layers beside full ones: those
        three are the FULL layers' and ``win_pages_live`` /
        ``win_pages_walked`` / ``win_pages_copied`` the window layers'
        (per window layer), beside ``attn_layers_full`` /
        ``attn_layers_window``."""
        from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
            _resolve_bb)

        ps = self.serving.page_size
        dp = self.mesh.shape.get("dp", 1) if self.mesh is not None else 1
        bb = _resolve_bb(self.decode_bblock, self.num_slots // dp)
        # [substep, dp shard, row]: the live columns of each kernel row
        limits = np.sort(
            (self.lengths.astype(np.int64) + carry_steps + 1
             + np.arange(horizon)[:, None]).reshape(horizon, dp, -1), axis=-1)
        hi = np.minimum(-(-limits // ps), self.pages_per_slot)
        blocks = (horizon, -1, bb)

        def pages(window: int):
            lo = np.maximum(limits - window, 0) // ps if window > 0 \
                else np.zeros_like(hi)
            first = lo.reshape(blocks)[..., :1]
            last = hi.reshape(blocks)[..., -1:]
            # a row's copies: the steps of its block's walk inside its range
            copied = (np.minimum(hi.reshape(blocks), last)
                      - np.maximum(lo.reshape(blocks), first))
            return (int((hi - lo).sum()), int(bb * (last - first).sum()),
                    int(copied.sum()))

        live, walked, copied = pages(self.cfg.attn_window)
        out = {"attn_pages_live": live, "attn_pages_walked": walked,
               "attn_pages_copied": copied}
        if self.cfg.windowed:
            live, walked, copied = pages(self.cfg.sliding_window)
            out.update(win_pages_live=live, win_pages_walked=walked,
                       win_pages_copied=copied, **self._attn_layers())
        return out

    @property
    def _kv_dtype(self):
        """The pool's element type, as the kernels' shape rules read it."""
        return jnp.int8 if self.kv_quant else self.serving.dtype

    def _attn_layers(self) -> dict:
        """Dispatch-record fields of a list with window layers beside full
        ones: how many attending layers of each kind a forward pass runs,
        and what the window inventory holds as the dispatch leaves — its
        pages in use beside the pages the same slots would hold for those
        layers with nothing released (their contexts)."""
        if not self.cfg.windowed:
            return {}
        return {"attn_layers_full": self.cfg.num_attn_layers,
                "attn_layers_window": self.cfg.num_window_layers,
                "win_pages_held": int(self.win_allocator.pages_in_use),
                "win_pages_unreleased": int(self._win_unreleased)}

    def _chunk_page_steps(self, C: int, off: int, n: int) -> dict:
        """Dispatch-record fields of a mixed dispatch, per attending layer:
        ``chunk_page_steps`` — the page steps (one page fetched and folded
        into a flash state) the ragged kernel walks for the chunk's ``n``
        live rows at ``off`` of a ``C``-row chunk (the width of the body
        of ``mixed_step`` that runs them), its grid steps cut as
        the device cuts them: a tile of pallas_attention._tile_rows rows
        that holds chunk rows only is ONE walk from its lowest row's first
        page to its highest row's last, one that also holds decode rows
        walks block by block — beside ``chunk_page_steps_by8``, what blocks
        of ``decode_bblock`` rows walk for the same rows (every tile's
        cost before the tile widened). A selecting model's tiles are cut
        the same way (ragged_attend_pallas_paged_select; a chunk so long
        that the entry walks it in several calls — 8,192 rows — is cut a
        call at a time there and as one here). Decode
        rows that share a BLOCK with chunk rows (slots no multiple of the
        block) are left out of both. A list with window layers beside
        full ones: those two are a FULL layer's, ``win_chunk_page_steps``
        / ``win_chunk_page_steps_by8`` a window layer's."""
        from aws_k8s_ansible_provisioner_tpu.ops.pallas_attention import (
            _ragged_pad, _resolve_bb, _tile_rows)

        ps, B = self.serving.page_size, self.num_slots
        tp = self.mesh.shape.get("tp", 1) if self.mesh is not None else 1
        shape = (self.cfg.num_heads // tp, self.cfg.pool_head_dim, ps,
                 self._kv_dtype)
        # (the plain entry pads a row count that has no tile of its own;
        # the selecting one takes its rows as they are)
        N = B + C if self.cfg.selects \
            else _ragged_pad(B + C, self.decode_bblock, *shape)
        bb = _resolve_bb(self.decode_bblock, N)
        tile = _tile_rows(N, bb, *shape)
        limits = np.zeros(N, np.int64)
        limits[B:B + n] = off + 1 + np.arange(n)
        hi = np.minimum(-(-limits // ps), self.pages_per_slot) - 1

        def steps(window: int) -> tuple:
            lo = np.maximum(limits - window, 0) // ps if window > 0 \
                else np.zeros_like(hi)

            def walks(width: int):      # a run of ``width`` rows: one walk
                top = hi.reshape(-1, width).max(axis=1)
                low = np.where(limits > 0, lo, self.pages_per_slot) \
                    .reshape(-1, width).min(axis=1)
                return np.maximum(top - low + 1, 0)

            by8 = walks(bb)
            mixed = -(-B // tile)       # the tiles that hold decode rows
            return (int(walks(tile)[mixed:].sum()
                        + by8[:mixed * (tile // bb)].sum()), int(by8.sum()))

        wide, by8 = steps(self.cfg.attn_window)
        out = {"chunk_page_steps": wide, "chunk_page_steps_by8": by8}
        if self.cfg.windowed:
            wide, by8 = steps(self.cfg.sliding_window)
            out.update(win_chunk_page_steps=wide,
                       win_chunk_page_steps_by8=by8)
        return out

    def _live_rows(self, active):
        """[B] bool device mask of the decode rows that hold a request, for
        an MoE model's step programs (None for a dense model: no operand);
        re-uploaded only when the active set changed."""
        if self.cfg.num_experts <= 0 and not self.cfg.recurrent:
            return None
        key = tuple(active)
        oc = self._op_cache
        if oc.get("live_key") != key:
            mask = np.zeros(self.num_slots, bool)
            mask[list(active)] = True
            oc["live_key"], oc["live"] = key, jnp.asarray(mask)
        return oc["live"]

    # -- the dispatch record -------------------------------------------------

    def _dispatch_open(self, program: str, kind: str, active=(),
                       **given) -> dict:
        """Enqueue half of THE record of one device dispatch: what the
        program is given, exactly, from the host mirrors (no device read —
        tpulint R8 stands). ``program`` is the jitted function as the trace
        prints it, ``kind`` devmon's program kind, ``active`` the decode
        rows live, ``given`` the static key and the per-kind facts
        (horizon = the substeps the dispatch RUNS, with horizon_why =
        what chose that count for a decode dispatch: ``_decode_horizon``;
        chunk_rows = the chunk rows the program's layers run over (of a
        mixed step: the width of the body that runs), chunk_n, chunk_off,
        bucket, rows,
        prompt_tokens, padded_tokens = the rows a prefill-type program's
        layers run over, head_rows = the rows its head runs over: one a
        sampled row, every row in a prompt_logprobs variant,
        sample_rows = the rows it samples whose temperature is above zero,
        idle slots included as the program reads them: at 0 its sampler
        takes the argmax and skips the candidates (ops/sampling.sample),
        carry_steps = steps of an unfetched
        predecessor the device-side lengths are ahead of the mirrors by,
        write_pages = page windows of the pool that hold a row of a mixed
        step's chunk: what its span write changes, whatever chunk_rows is,
        chunk_page_steps / chunk_page_steps_by8 = ``_chunk_page_steps``).
        Closed by ``_dispatch_close`` on the blocking half. For an MoE
        model ``_decode_fetch`` adds, to the decode and mixed records,
        ``moe_rows`` ((token, expert) rows of live tokens per layer: k x
        (horizon x active + chunk_n); padding rows and idle slots are not
        routed), ``moe_experts_hit`` (experts with a live row, mean over
        layers and substeps) and ``moe_group_max`` (rows of the largest
        group), the last two from the program's own output. A plain
        decode dispatch also carries ``attn_pages_live``,
        ``attn_pages_walked`` and ``attn_pages_copied`` (``_attn_pages``)."""
        n = len(active)
        lens = self.lengths[list(active)] if n else None
        return {"seq": next(_DISPATCH_SEQ), "program": program, "kind": kind,
                "active": n,
                "ctx_tokens": int(lens.sum()) if n else 0,
                "ctx_max": int(lens.max()) if n else 0,
                "first_use": False, **given, "t_enqueue": time.monotonic()}

    @contextlib.contextmanager
    def _emit_phase(self):
        """An emit phase: the tokens a dispatch produced are recorded per
        request (Engine._emit) and, on the way out, handed to each stream
        as ONE queue item — so a handler thread is woken once a dispatch,
        not once a token, and no token is held past the phase that
        produced it (an exception inside it included)."""
        with _phase(PH_EMIT):
            try:
                yield
            finally:
                self._flush_streams()

    def _dispatch_close(self, rec: dict, t_ready: float, *, batch: int = 1,
                        tokens: int = 1, ctx_rows: float = 0.0,
                        steps: int = 1, guided_rows: int = 0,
                        tail: bool = True, emitted: int = 0,
                        puts: int = 0,
                        t_wait: Optional[float] = None) -> None:
        """Blocking half, and the ONE site that feeds the sinks: the
        device-busy counters, devmon's window, one ``dispatch`` event on
        the flight ring and — if the server's tracer has an exporter at
        this moment — one ``engine.dispatch`` span. The busy window opens
        at this dispatch's enqueue or the previous dispatch's completion,
        whichever is later, so overlapped (pipelined) dispatches never
        double-count device seconds."""
        device_s = max(0.0, t_ready - max(rec["t_enqueue"],
                                          self._busy_watermark))
        self._busy_watermark = t_ready
        if t_wait is not None and not rec["first_use"]:
            # what the next such dispatch is expected to take (``t_wait``:
            # when the host came to wait for this one). Still running then,
            # t_ready is when it FINISHED and device_s is its time; already
            # done (a host stall: another program's compile, say), it took
            # at most this. first_use: its own compile would be in it.
            key, per = rec["program"], device_s / steps
            if t_ready - t_wait > 0.1 * device_s:
                self._dispatch_s[key] = per
            elif key in self._dispatch_s:
                self._dispatch_s[key] = min(self._dispatch_s[key], per)
        self._t_fetched, self._waited_s = t_ready, 0.0
        rec["t_ready"] = t_ready
        rec["tail"] = tail
        rec["emitted"] = emitted
        rec["puts"] = puts
        self.metrics.device_busy_seconds.inc(device_s)
        if "moe_rows" in rec:
            m, prog = self.metrics, rec["program"]
            m.moe_routed_rows.inc(rec["moe_rows"], program=prog)
            m.moe_experts_hit.inc(rec["moe_experts_hit"] * steps,
                                  program=prog)
            m.moe_forward_passes.inc(steps, program=prog)
            m.moe_group_rows_max.set(rec["moe_group_max"])
            if "moe_rows_held" in rec:
                m.moe_rows_held.inc(rec["moe_rows_held"], program=prog)
        if "head_rows" in rec:
            self.metrics.head_rows.inc(rec["head_rows"],
                                       program=rec["program"])
        if "kda_rows" in rec:
            self.metrics.kda_rows.inc(rec["kda_rows"],
                                      program=rec["program"])
        if "state_rows" in rec:
            self.metrics.state_rows.inc(rec["state_rows"],
                                        kind=rec["state_kind"],
                                        program=rec["program"])
        if rec.get("ssm_span_rows"):
            self.metrics.ssm_span_rows.inc(rec["ssm_span_rows"])
        if "sparse_pages_live" in rec:
            self.metrics.sparse_pages.inc(rec["sparse_pages_live"],
                                          kind="live")
            self.metrics.sparse_pages.inc(rec["sparse_pages_selected"],
                                          kind="selected")
        if "attn_pages_live" in rec:
            self.metrics.decode_attn_pages.inc(rec["attn_pages_live"],
                                               kind="live")
            self.metrics.decode_attn_pages.inc(rec["attn_pages_walked"],
                                               kind="walked")
            self.metrics.decode_attn_pages.inc(rec["attn_pages_copied"],
                                               kind="copied")
        if "win_pages_live" in rec:
            self.metrics.window_attn_pages.inc(rec["win_pages_live"],
                                               kind="live")
            self.metrics.window_attn_pages.inc(rec["win_pages_walked"],
                                               kind="walked")
            self.metrics.window_attn_pages.inc(rec["win_pages_copied"],
                                               kind="copied")
        if "chunk_page_steps" in rec:
            full = rec.get("attn_layers_full", self.cfg.num_attn_layers)
            win = rec.get("attn_layers_window", 0)
            for path, sfx in (("tile", ""), ("by8", "_by8")):
                self.metrics.ragged_page_steps.inc(
                    full * rec["chunk_page_steps" + sfx]
                    + win * rec.get("win_chunk_page_steps" + sfx, 0),
                    path=path)
        if rec["program"] == "mixed_step":
            self.metrics.mixed_steps.inc(
                body="narrow" if rec["chunk_rows"] < self._chunk_size
                else "wide")
        if "horizon_why" in rec:
            self.metrics.decode_dispatches.inc(
                substeps="whole" if rec["horizon"] >= max(
                    1, self.serving.decode_horizon) else "short")
            self.metrics.decode_substeps.inc(rec["horizon"])
        if "sample_rows" in rec:
            self.metrics.sample_dispatches.inc(
                program=rec["program"],
                path="candidates" if rec["sample_rows"] else "greedy")
        _devmon.note(rec["kind"], device_s, batch=batch, tokens=tokens,
                     ctx_rows=ctx_rows, steps=steps, guided_rows=guided_rows)
        _flight.record("dispatch", None, **rec)
        src = self.tracer_source
        tracer = src() if src is not None else None
        exporter = tracer.exporter if tracer is not None else None
        if exporter is not None:
            sid = format(rec["seq"], "016x")
            span = _tracing.Span(
                PH_DISPATCH, _tracing.SpanContext(_SPAN_PREFIX + sid, sid),
                start_ns=_tracing.mono_ns(rec["t_enqueue"]), attributes=rec)
            span.end_ns = _tracing.mono_ns(t_ready)
            exporter.export(span, tracer.service_name)

    def _activate(self, req: Request, slot: int, token: int, lp=None,
                  ids: Optional[List[int]] = None, resumed: bool = False):
        """Shared post-prefill bookkeeping of a prefill whose sampled token
        is on the host: the slot joins the batch (``_join``) and, unless
        this is a resume, its first token goes out (``_first_token``), back
        to back. The device carry of a dispatch still in flight does not
        hold this slot's token and length, so the carry generation moves
        (the final chunk of a mixed walk that stays in flight calls the two
        halves apart, and does NOT move it: ``_advance_chunk_mixed``).

        ``ids`` overrides the cache-resident token sequence when it differs
        from the request prompt — a preemption resume re-prefills
        prompt + generated-so-far, so lengths and page indexing must track
        THAT sequence. A resume (``resumed``) is a pure CACHE REBUILD: the
        prefill-sampled token is DISCARDED (prefill applies no penalties and
        its draw position belongs to the already-emitted stream); the next
        decode dispatch produces the continuation with penalties and the
        seeded key it would have used without the preemption — bit-identical
        streams either way."""
        # an in-flight decode dispatch's device carry (token/length) no
        # longer describes the batch once this slot joins it
        self._carry_gen += 1
        self._join(req, slot, ids, resumed, token)
        if not resumed:
            self._first_token(req, slot, token, lp)

    @staticmethod
    def _penalised(req: Request) -> bool:
        return bool(req.presence_penalty or req.frequency_penalty
                    or (req.repetition_penalty
                        and req.repetition_penalty != 1.0))

    def _first_token(self, req: Request, slot: int, token: int, lp=None):
        """The half of an activation that needs the sampled token's VALUE:
        TTFT, the emit (stop check, ``last_token``'s mirror) and the
        stream's first item. Inside an emit phase."""
        now = time.monotonic()
        if not req.t_first_token:
            req.t_first_token = now
            self.metrics.ttft.observe(now - req.t_submit,
                                      trace_id=req.trace_id or None)
            _slo.get().observe_ttft(now - req.t_submit)
        # the first token is TTFT: an item of its own, put at once
        self._emit(slot, token, lp)
        self._put_pending(req)

    def _join(self, req: Request, slot: int,
              ids: Optional[List[int]] = None, resumed: bool = False,
              token: Optional[int] = None):
        """The half of an activation the host knows at ENQUEUE time: the
        slot holds the request from here on (``slot_req``, its length, its
        sampling rows — uploaded with the next dispatch's operands —, the
        scheduler's and the prefix index's view of it). ``token`` is the
        sampled first token, which only a penalised request's count row
        takes; a caller that has not fetched it yet passes None and never
        joins such a request (``_first_token_can_wait``)."""
        ids = list(req.prompt_ids) if ids is None else ids
        self._op_dirty_sampling = True
        now = time.monotonic()
        _flight.record("admit", req.id, slot=slot, resumed=resumed,
                       queue_wait_s=round(max(0.0, (req.t_prefill_start
                                                    or now) - req.t_submit),
                                          6))
        if not resumed:
            # a resume's context tokens were all counted at first admission
            self.metrics.prompt_tokens.inc(len(ids))
        self._index_prompt_pages(slot, ids)
        self.slot_req[slot] = req
        # Resume: decode's next dispatch RE-writes last_token's K/V at row
        # ``lengths`` before attending, so point it at the last real token's
        # own row (its recomputed K/V is identical) — lengths = len(ids)
        # would duplicate that row at len(ids) and shift every later write,
        # and the seeded draw counter (lens + 1) aligns with the
        # unpreempted stream exactly at len(ids) - 1.
        self.lengths[slot] = len(ids) - 1 if resumed else len(ids)
        self.temps[slot] = req.temperature
        self.top_ks[slot] = req.top_k
        self.top_ps[slot] = req.top_p
        self.seeds[slot] = req.eff_seed
        self._fill_sampling_rows(req, slot)
        self.pres_pens[slot] = req.presence_penalty
        self.freq_pens[slot] = req.frequency_penalty
        self.rep_pens[slot] = req.repetition_penalty or 1.0
        if req.repetition_penalty and req.repetition_penalty != 1.0:
            if self.prompt_mask is None:
                self.prompt_mask = jnp.zeros(
                    (self.num_slots, self.cfg.vocab_size), jnp.bool_)
            row = np.zeros(self.cfg.vocab_size, bool)
            row[np.asarray(req.prompt_ids, np.int64)] = True
            self.prompt_mask = _set_mask_row(self.prompt_mask,
                                             jnp.int32(slot),
                                             jnp.asarray(row))
        if self._penalised(req):
            # Only penalized occupants touch the counts array: a stale row
            # under a zero-penalty occupant is multiplied by zero, so
            # un-penalized prefills never pay this extra device dispatch.
            if self.counts is None:
                self.counts = jnp.zeros(
                    (self.num_slots, self.cfg.vocab_size), jnp.int32)
            if self.prompt_mask is None:
                # allocated WITH counts (not only for repetition requests):
                # the penalized decode program's signature always carries
                # the mask, so pres/freq-only traffic reuses the program
                # warmup compiled instead of compiling a mask-less variant
                self.prompt_mask = jnp.zeros(
                    (self.num_slots, self.cfg.vocab_size), jnp.bool_)
            if resumed:
                # restore the full pre-preemption penalty state (the
                # discarded prefill token contributes nothing)
                row = np.bincount(np.asarray(req.generated, np.int64),
                                  minlength=self.cfg.vocab_size)
                self.counts = _restore_count_row(
                    self.counts, jnp.int32(slot), jnp.asarray(row, jnp.int32))
            else:
                # zero the recycled slot's row, then count the first token
                self.counts = _reset_count_row(self.counts, jnp.int32(slot),
                                               jnp.int32(token))
        self.sched.note_prefill(slot, int(self.lengths[slot]))
        self.metrics.active_requests.set(len(self._active_slots()))
        if resumed:
            # rebuild complete; decode continues from the last REAL token
            self.last_token[slot] = ids[-1]
            if self.draft is not None:
                # resumes always arrive via the chunk walk (paged admit
                # forces it), which never rebuilds the draft cache; this is
                # the same stale mark _start_chunk applied, kept for the
                # invariant "resumed slot => stale" independent of path
                self.draft.mark_stale(slot)

    @staticmethod
    def _host_prompt_lp(req: Request, plp, row: int, n_prompt: int) -> None:
        """Format one row of a device (sel, vals, ids) prompt-logprob
        triple into req.prompt_logprob_data ([None, (own, [(id, lp) x k]),
        ...]) — ONE bulk transfer, pure numpy slicing after."""
        sel, vals, ids = (np.asarray(a) for a in plp)
        k = int(req.prompt_logprobs)
        data: List = [None]
        for t in range(1, n_prompt):
            pairs = [(int(ids[row, t - 1, j]), float(vals[row, t - 1, j]))
                     for j in range(k)]
            data.append((float(sel[row, t - 1]), pairs))
        req.prompt_logprob_data = data

    def _do_prefill(self, req: Request, slot: int):
        ids = req.prompt_ids
        bucket = self._bucket_for(len(ids))
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(ids)] = ids
        self._fill_sampling_rows(req, slot)
        if self.cfg.windowed:
            self._win_cover(slot, len(ids), len(ids))
        args = (jnp.asarray(tokens), jnp.int32(len(ids)), self._next_rng(),
                jnp.float32(req.temperature), jnp.int32(req.top_k),
                jnp.float32(req.top_p))
        kw = dict(
            logprobs=req.logprobs is not None,
            pages=jnp.asarray(self.table[slot]),
            seed=jnp.uint32(req.eff_seed),
            ban_ids=jnp.asarray(self.ban_ids[slot]),
            ban_until=jnp.int32(self.ban_until[slot]),
            bias_ids=jnp.asarray(self.bias_ids[slot]),
            bias_vals=jnp.asarray(self.bias_vals[slot]),
            rep=jnp.float32(req.repetition_penalty or 1.0),
            allow=self._allow_row(req),
            lora_idx=(jnp.asarray(self.lora_idx[slot:slot + 1])
                      if self.lora_names else None),
            prompt_logprobs=req.prompt_logprobs is not None,
            **self._state_kw("slot", slot), **self._win_kw("wpages", slot))
        drec = self._dispatch_open(
            "prefill_step", "prefill", bucket=bucket,
            prompt_tokens=len(ids), padded_tokens=bucket,
            head_rows=bucket if kw["prompt_logprobs"] else 1,
            sample_rows=int(req.temperature > 0),
            **self._kda_rows(len(ids)))
        with _Dispatching(drec):
            out = prefill_step(self.cfg, self.params, self.cache, *args,
                               **kw)
        items = list(out)
        self.cache, token = items[0], items[1]
        pos = 2
        lp = None
        with _phase(PH_FETCH):
            if req.logprobs is not None:
                lp = _host_lp(items[pos], 0, req.logprobs)
                pos += 1
            if req.prompt_logprobs is not None:
                self._host_prompt_lp(req, items[pos], 0, len(ids))
            token = int(token)  # device sync
        self._dispatch_close(drec, time.monotonic(), batch=1,
                             tokens=len(ids))
        if self.draft is not None:
            self.draft.prefill(self, tokens, np.asarray([len(ids)], np.int32),
                               np.asarray([slot], np.int32))
        with self._emit_phase():
            self._activate(req, slot, token, lp)

    def _do_prefill_batch(self, batch: List):
        """Prefill N waiting prompts in one dispatch (rows padded to a power
        of two, lengths to the largest member's bucket)."""
        n_bucket = 1
        while n_bucket < len(batch):
            n_bucket *= 2
        t_bucket = self._bucket_for(max(len(r.prompt_ids) for r, _ in batch))
        tokens = np.zeros((n_bucket, t_bucket), np.int32)
        true_lens = np.ones(n_bucket, np.int32)
        # padding rows carry slot index == num_slots and an all-OOB_PAGE
        # table: their cache writes drop
        slots = np.full(n_bucket, self.num_slots, np.int32)
        tb = np.full((n_bucket, self.pages_per_slot), kvp.OOB_PAGE, np.int32)
        wtb = tb.copy() if self.cfg.windowed else None
        temps = np.zeros(n_bucket, np.float32)
        top_ks = np.zeros(n_bucket, np.int32)
        top_ps = np.ones(n_bucket, np.float32)
        seeds = np.zeros(n_bucket, np.uint32)
        for i, (req, slot) in enumerate(batch):
            ids = req.prompt_ids
            tokens[i, :len(ids)] = ids
            true_lens[i] = len(ids)
            slots[i] = slot
            tb[i] = self.table[slot]
            if wtb is not None:
                self._win_cover(slot, len(ids), len(ids))
                wtb[i] = self.wtable[slot]
            temps[i] = req.temperature
            top_ks[i] = req.top_k
            top_ps[i] = req.top_p
            seeds[i] = req.eff_seed
        ban_ids = np.full((n_bucket, BAN_K), 2**31 - 1, np.int32)
        ban_until = np.zeros(n_bucket, np.int32)
        bias_ids = np.full((n_bucket, BIAS_K), 2**31 - 1, np.int32)
        bias_vals = np.zeros((n_bucket, BIAS_K), np.float32)
        reps = np.ones(n_bucket, np.float32)
        row_lora = np.zeros(n_bucket, np.int32)
        for i, (req, slot) in enumerate(batch):
            self._fill_sampling_rows(req, slot)
            ban_ids[i] = self.ban_ids[slot]
            ban_until[i] = self.ban_until[slot]
            bias_ids[i] = self.bias_ids[slot]
            bias_vals[i] = self.bias_vals[slot]
            reps[i] = req.repetition_penalty or 1.0
            row_lora[i] = self.lora_idx[slot]
        allow = None
        if any(req.guided is not None for req, _ in batch):
            aw = np.full((n_bucket, (self.cfg.vocab_size + 31) // 32),
                         0xFFFFFFFF, np.uint32)
            for i, (req, _) in enumerate(batch):
                if req.guided is not None:
                    self._fill_allow(aw, i, req)
            allow = jnp.asarray(aw)
        want_lp = self._want_logprobs([r for r, _ in batch])
        want_plp = any(r.prompt_logprobs is not None for r, _ in batch)
        args = (jnp.asarray(tokens), jnp.asarray(true_lens),
                self._next_rng(), jnp.asarray(temps),
                jnp.asarray(top_ks), jnp.asarray(top_ps))
        kw = dict(
            logprobs=want_lp, tables=jnp.asarray(tb),
            seeds=jnp.asarray(seeds),
            ban_ids=jnp.asarray(ban_ids),
            ban_until=jnp.asarray(ban_until),
            bias_ids=jnp.asarray(bias_ids),
            bias_vals=jnp.asarray(bias_vals),
            reps=jnp.asarray(reps), allow=allow,
            lora_idx=(jnp.asarray(row_lora) if self.lora_names
                      else None),
            prompt_logprobs=want_plp, **self._state_kw("slots", slots),
            **({} if wtb is None else {"wtables": jnp.asarray(wtb)}))
        n_prompt = int(true_lens[:len(batch)].sum())
        drec = self._dispatch_open(
            "prefill_batch_step", "prefill_batch", rows=n_bucket,
            bucket=t_bucket, prompt_tokens=n_prompt,
            padded_tokens=n_bucket * t_bucket,
            head_rows=n_bucket * t_bucket if want_plp else n_bucket,
            sample_rows=int((temps > 0).sum()),
            **self._kda_rows(n_prompt))
        with _Dispatching(drec):
            out = prefill_batch_step(self.cfg, self.params, self.cache,
                                     *args, **kw)
        items = list(out)
        self.cache, toks = items[0], items[1]
        pos = 2
        lp_t = None
        with _phase(PH_FETCH):
            if want_lp:
                lp_t = tuple(np.asarray(a) for a in items[pos])  # ONE transfer
                pos += 1
            plp_t = tuple(np.asarray(a) for a in items[pos]) \
                if want_plp else None                    # ONE bulk transfer
            toks = np.asarray(toks)  # device sync
        self._dispatch_close(drec, time.monotonic(), batch=len(batch),
                             tokens=int(true_lens.sum()))
        if self.draft is not None:
            self.draft.prefill(self, tokens, true_lens, slots)
        with self._emit_phase():
            for i, (req, slot) in enumerate(batch):
                lp = _host_lp(lp_t, i, req.logprobs) \
                    if req.logprobs is not None else None
                if req.prompt_logprobs is not None:
                    self._host_prompt_lp(req, plp_t, i, len(req.prompt_ids))
                self._activate(req, slot, int(toks[i]), lp)

    def _start_chunk(self, req: Request, slot: int, ids: List[int],
                     off: int, resumed: bool):
        """Begin chunked prefill of ``req`` into ``slot``.

        Reused prefix pages are already in the slot's table (hash-chain
        sharing, no copy); the walk starts at the reuse offset ``off``, over
        ``ids`` — which is prompt + generated for a preemption resume.
        """
        self._fill_sampling_rows(req, slot)   # before the first chunk dispatch
        # Route the WHOLE walk once, here: the ragged mixed program pays for
        # itself only when there are live decode rows to pack alongside (or
        # an in-flight dispatch to keep open) — an idle engine's chunk walk
        # uses the plain chunk program it already compiled, paying neither a
        # mixed_step compile nor packed-row arithmetic for zero decode rows.
        # Frozen at walk start: no admission/activation can happen mid-walk
        # (engine.step services _chunk before admissions), so the conditions
        # cannot flip under the walk — except draining, which both branches
        # tolerate.
        mixed = (self._ragged_on()
                 and (req.guided is None
                      or self.serving.ragged_features > 0)
                 # (a model whose attention selects has ONE chunk program
                 # that reads a long window: the ragged one; so has a list
                 # with window layers — the plain chunk program attends
                 # over a gather of the slot's WHOLE page run, 4.8 GB of
                 # logits for a 4,096-row chunk of a 9,216-token slot)
                 and (self._inflight is not None
                      or bool(self._active_slots()) or self.cfg.selects
                      or self.cfg.windowed))
        if not mixed:
            # chunking rewrites the slot's length out of band of any decode
            # carry (admission already drained the pipeline; belt-and-braces)
            self._carry_gen += 1
        # else: ragged mixed walk — the in-flight carry STAYS valid. The
        # chunking slot was inactive, so the in-flight dispatch's garbage
        # row for it lands in scratch (its old device-side table row), and
        # every mixed dispatch overrides the slot's carry lanes in-program
        # (mixed_step's is_p masking) — nothing the carry describes changed.
        if self.draft is not None:
            # the draft has no chunk walk; the slot serves the plain path
            self.draft.mark_stale(slot)
        # repetition_penalty seen-set over the WHOLE context the chunk walk
        # will have written (chunk dispatches only see their slice) — only
        # the final chunk's sample survives, and it must be penalized over
        # all of it (review r4: the first token escaped the penalty)
        rep_seen = np.zeros(self.cfg.vocab_size, bool)
        rep_seen[np.asarray(ids, np.int64)] = True
        # settle any scheduled host-tier restore before the first suffix
        # chunk dispatch — timing/byte accounting only; XLA data
        # dependencies already order the restore scatter ahead of every
        # program reading these pages
        self._settle_restore(slot)
        self.lengths[slot] = off
        # What the walk prefills. A resume ends with decode RE-processing
        # the last real token at its own row (_activate): rewriting a K/V
        # row is idempotent, advancing a recurrent state twice is not (nor
        # adding a key to the selector's run sums twice) — so a model with
        # recurrent or selecting layers rebuilds over all but that token.
        walk = ids[:-1] if resumed and (self.cfg.recurrent
                                        or self.cfg.selects) else ids
        self._chunk = {"req": req, "slot": slot, "off": off,
                       "C": self._chunk_size, "ids": ids, "walk": walk,
                       "resumed": resumed, "rep_seen": rep_seen,
                       "mixed": mixed}

    def _advance_chunk(self):
        """Dispatch the next chunk of the in-progress chunked prefill."""
        st = self._chunk
        req, slot = st["req"], st["slot"]
        if req.cancelled:
            # settle any in-flight mixed dispatch BEFORE releasing this
            # slot's pages: its deferred emits still reference the batch
            self._drain_decode_pipeline("chunk")
            self._chunk = None
            self._release_slot_pages(slot)
            self.sched.release(slot)
            req.finish_reason = "cancelled"
            self.metrics.mark_request("cancelled",
                                      time.monotonic() - req.t_submit)
            _flight.record("cancel_reap", req.id, phase="prefill_chunk")
            _flight.finish(req.id, "cancelled", ok=False)
            self._close_stream(req)
            return
        if st["mixed"]:
            self._advance_chunk_mixed(st)
            return
        if self._inflight is not None:
            # legacy walk with a dispatch in flight (ragged off, or the
            # walk was routed legacy at start): settle it before the sync
            # chunk dispatch rewrites slot state out from under its carry
            self._drain_decode_pipeline("chunk")
        C = st["C"]
        ids = st["walk"]
        off = st["off"]
        chunk = ids[off:off + C]
        _flight.record("prefill_chunk", req.id, off=off, n=len(chunk))
        tokens = np.zeros((1, C), np.int32)
        tokens[0, :len(chunk)] = chunk
        final_lp = (req.logprobs is not None and not st["resumed"]
                    and off + len(chunk) >= len(ids))
        lp_t = None
        if self.cfg.windowed:
            self._win_cover(slot, off, off + len(chunk))
        try:
            args = (jnp.asarray(tokens), jnp.int32(off),
                    jnp.int32(len(chunk)), self._next_rng(),
                    jnp.float32(req.temperature), jnp.int32(req.top_k),
                    jnp.float32(req.top_p))
            kw = dict(
                logprobs=final_lp,
                pages=jnp.asarray(self.table[slot]),
                seed=jnp.uint32(req.eff_seed),
                ban_ids=jnp.asarray(self.ban_ids[slot]),
                ban_until=jnp.int32(self.ban_until[slot]),
                bias_ids=jnp.asarray(self.bias_ids[slot]),
                bias_vals=jnp.asarray(self.bias_vals[slot]),
                rep=jnp.float32(req.repetition_penalty or 1.0),
                rep_seen=jnp.asarray(st["rep_seen"]),
                allow=self._allow_row(req),
                lora_idx=(jnp.asarray(self.lora_idx[slot:slot + 1])
                          if self.lora_names else None),
                **self._state_kw("slot", slot),
                **self._win_kw("wpages", slot))
            drec = self._dispatch_open(
                "prefill_chunk_step", "prefill_chunk", chunk_rows=C,
                chunk_n=len(chunk), chunk_off=off, padded_tokens=C,
                head_rows=1, sample_rows=int(req.temperature > 0),
                **self._kda_rows(len(chunk)))
            with _Dispatching(drec):
                out = prefill_chunk_step(self.cfg, self.params, self.cache,
                                         *args, **kw)
            if final_lp:
                self.cache, token, lp_t = out
            else:
                self.cache, token = out
        except Exception:
            self._chunk = None
            self._release_slot_pages(slot)
            self.sched.release(slot)
            req.finish_reason = "error"
            self.metrics.mark_request("error", 0.0)
            self._close_stream(req)
            raise
        # closed at enqueue: the walk reads the sampled token only after
        # the final chunk (at _activate, below)
        self._dispatch_close(drec, time.monotonic(), tokens=len(chunk))
        st["off"] = off + len(chunk)
        # Interleaved decode dispatches write a (garbage) k/v row for every
        # slot at its host length; keeping this slot's length at the chunk
        # frontier means that row is exactly where the NEXT chunk writes.
        self.lengths[slot] = st["off"]
        if st["off"] >= len(ids):
            self._chunk = None
            with _phase(PH_FETCH):
                lp = _host_lp(lp_t, 0, req.logprobs) \
                    if req.logprobs is not None and lp_t is not None else None
                token = int(token)  # device sync
            with self._emit_phase():
                self._activate(req, slot, token, lp,
                               ids=list(st["ids"]), resumed=st["resumed"])

    def _first_token_can_wait(self, st: dict) -> bool:
        """May the FINAL chunk of this mixed walk stay in flight, its slot
        joining the batch from the device carry (``mixed_step`` leaves the
        sampled first token and the post-prompt length in the slot's carry
        lanes) and its first token going out one dispatch later, at the
        fetch? Read off the request and the engine, no knob: not where the
        token's VALUE or the emitted state is needed before the next
        dispatch can be built — a resume (its sampled token is discarded
        and the carry's lane is wrong for it), penalties (the count row
        takes the token), a guided request (its FSM must see the token
        before the next mask), ``prompt_logprobs``, spec decode or a draft
        model (they read the host mirrors), a draining engine (which must
        reach "nothing in flight" with its last emit)."""
        req = st["req"]
        return not (st["resumed"] or req.guided is not None
                    or req.prompt_logprobs is not None
                    or self._penalised(req)
                    or self.serving.spec_decode or self.draft is not None
                    or self.draining)

    def _advance_chunk_mixed(self, st: dict) -> None:
        """One RAGGED mixed dispatch: this walk's next prefill chunk packed
        alongside the whole decode batch, served by a single program
        (``mixed_step``). The dispatch rides the one-deep pipeline exactly
        like a plain decode — the in-flight record it leaves behind IS a
        decode record (plus the chunk outputs), so the pipeline never
        drains on a chunk edge. The legacy path pays one drain per
        admission plus a serialized chunk dispatch per chunk; here both
        costs go to zero.

        The FINAL chunk stays in flight too (``_first_token_can_wait``):
        once its predecessor is fetched — never before, so no deferred emit
        of the slot's previous occupant can reach the new request — the
        slot JOINS the batch on the host (``_join``) WITHOUT moving the
        carry generation: this dispatch's device carry describes the batch
        with the slot in it. The next dispatch is enqueued behind it with
        the slot among its rows, and the first token is emitted where this
        one is fetched (``_decode_fetch``: the record's ``first``). A
        request that left the slot meanwhile (cancel, deadline, a
        preemption — which moves the generation and drains) has its unseen
        token discarded there. Where the token is needed at once the final
        dispatch settles synchronously as before — predecessor, then
        itself, then ``_activate``; nothing is discarded early, so the
        drain counter does NOT move. ``tpu_serve_activations_total{path}``
        counts each way.
        """
        req, slot = st["req"], st["slot"]
        C = st["C"]
        ids = st["walk"]
        off = st["off"]
        chunk = ids[off:off + C]
        final = off + len(chunk) >= len(ids)
        rides = final and self._first_token_can_wait(st)
        _flight.record("prefill_chunk", req.id, off=off, n=len(chunk),
                       mixed=True)
        prev = self._inflight
        if prev is not None and not self._carry_valid():
            # a slot activated/preempted under the in-flight dispatch —
            # same invalidation rule as _do_decode
            self._drain_decode_pipeline("prefill")
            prev = None
        # Page headroom for the decode rows' writes (the chunk slot's pages
        # were fully allocated at admission, and it is NOT in the active
        # set, so _ensure_pages never preempts it). The bool return (any
        # active slots left) is deliberately ignored: the chunk must
        # proceed even with zero active decode rows.
        grow = 1 + (prev["horizon"] if prev is not None else 0)
        with _phase(PH_ADMIT):      # pool bookkeeping, as at admission
            self._ensure_pages(grow)
        if prev is not None and not self._carry_valid():
            # _ensure_pages preempted under the in-flight dispatch
            self._drain_decode_pipeline("prefill")
            prev = None
        if (prev is not None
                and any(r is not None and r.guided is not None
                        for r in self.slot_req)):
            # A guided DECODE row rides this mixed dispatch and its allow
            # mask must reflect the post-emit FSM state: settle the
            # predecessor first (same rule as _do_decode's guided path —
            # carry retained, no drain counted). The steady-state
            # dispatch-then-fetch overlap below is kept for unguided
            # traffic, where no mask depends on the predecessor's emits.
            # The CHUNKING request's own pallow needs no settle: its FSM
            # never advances mid-walk (only the final chunk's token is
            # emitted, at activation).
            self._settle_inflight()
            prev = None
        try:
            # (the carry is valid after a settle too: prev is None, the
            # carry retained)
            rec = self._mixed_dispatch(st, chunk, *self._carry_in())
            if final:
                # how this admission's slot joins the batch, beside
                # chunk_n in the engine.dispatch record
                rec["drec"]["activation"] = \
                    "in_flight" if rides else "settled"
            st["off"] = off + len(chunk)
            self.lengths[slot] = st["off"]
            if rides or not final:
                # steady state: leave the mixed dispatch in flight, settle
                # its predecessor while the device runs this one
                self._inflight = rec
                self.metrics.pipeline_depth.set(1.0)
                if prev is not None:
                    self._decode_fetch(prev, tail=False)
            else:
                # settle in order — predecessor first, then this dispatch
                # (whose chunk token activates the slot below)
                self._pipe_carry = None
                if prev is not None:
                    self._inflight = None
                    self.metrics.pipeline_depth.set(0.0)
                    self._decode_fetch(prev, tail=False)
                self._decode_fetch(rec, tail=True)
        except Exception:
            # exactly-once release: clearing _chunk BEFORE the raise means
            # the engine's failover (_fail_all) sees no chunk in progress
            # and cannot release this slot a second time
            self._chunk = None
            self._release_slot_pages(slot)
            self.sched.release(slot)
            req.finish_reason = "error"
            self.metrics.mark_request("error", 0.0)
            self._close_stream(req)
            raise
        if not final:
            return
        self._chunk = None
        self.metrics.activations.inc(path=rec["drec"]["activation"])
        if rides:
            # from here the slot is an active one like any other (reaped,
            # preempted, failed as such); only its first token is still on
            # the device, and goes out at this record's fetch
            rec["first"] = (req, slot)
            self._join(req, slot, ids=list(st["ids"]))
            return
        with self._emit_phase():
            self._activate(req, slot, *self._chunk_sample(rec, req),
                           ids=list(st["ids"]), resumed=st["resumed"])

    @staticmethod
    def _chunk_sample(rec: dict, req: Request) -> tuple:
        """(token, logprobs or None) a FETCHED mixed record's chunk row
        sampled: the first token of the request whose walk it ended."""
        lp = _host_lp(rec["chunk_lp_t"], 0, req.logprobs) \
            if rec["chunk_lp"] else None
        return rec["chunk_token"], lp

    def _mixed_dispatch(self, st: dict, chunk, tok_in, len_in) -> dict:
        """Enqueue ONE ragged mixed dispatch (prefill chunk + decode batch)
        and return its in-flight record. Async half only — no blocking
        device reads here (tpulint R8); the transfer and emits happen in
        _decode_fetch, which also unpacks the chunk-row outputs. The
        program gets the chunk padded to ``C`` rows always and runs one of
        its two bodies (``mixed_step``); the record's ``chunk_rows``,
        ``padded_tokens``, page steps are those of the rows that RUN, by the
        program's own two functions (``mixed_narrow_rows``,
        ``mixed_takes_narrow``) — the benchmark's readers compute a step's
        need and fill from them."""
        req, slot, off = st["req"], st["slot"], st["off"]
        ids = st["walk"]
        active = [s for s in self._active_slots() if s != slot]
        # Feature operands (ISSUE 16): guided decode rows carry their FSM
        # allow-bitmask, a guided CHUNKING request carries its own over the
        # chunk row (constant across the walk — the one-entry device cache
        # in _allow_row makes re-dispatching it free). Both are async
        # uploads on the enqueue half (tpulint R8 covers this fn).
        gslots = [s for s in active
                  if self.slot_req[s] is not None
                  and self.slot_req[s].guided is not None]
        want_lp = self._want_logprobs(self.slot_req)
        want_pen = self.counts is not None and bool(
            self.pres_pens.any() or self.freq_pens.any()
            or (self.rep_pens != 1.0).any())
        chunk_lp = (req.logprobs is not None and not st["resumed"]
                    and off + len(chunk) >= len(ids))
        C = st["C"]
        narrow = mixed_narrow_rows(
            self.cfg, self.num_slots, C, self.serving.page_size,
            self.decode_bblock, self.pages_per_slot, self._kv_dtype)
        W = narrow if mixed_takes_narrow(len(chunk), narrow) else C
        tokens = np.zeros((1, C), np.int32)
        tokens[0, :len(chunk)] = chunk
        allow = self._allow_words(gslots)
        pallow = self._allow_row(req)
        if self.cfg.windowed:
            self._win_cover(slot, off, off + len(chunk))
        oc = self._decode_operands()
        args = (jnp.asarray(tokens), jnp.int32(slot), jnp.int32(off),
                jnp.int32(len(chunk)),
                jnp.float32(req.repetition_penalty or 1.0),
                jnp.asarray(st["rep_seen"]), jnp.uint32(req.eff_seed),
                jnp.float32(req.temperature), jnp.int32(req.top_k),
                jnp.float32(req.top_p), self._next_rng(),
                oc["temps"], oc["top_ks"], oc["top_ps"])
        prev = self._inflight
        ps = self.serving.page_size
        drec = self._dispatch_open(
            "mixed_step", "mixed_step", active, horizon=1,
            chunk_rows=W, chunk_n=len(chunk), chunk_off=off,
            write_pages=(off + len(chunk) - 1) // ps - off // ps + 1,
            padded_tokens=self.num_slots + W,
            head_rows=self.num_slots + 1,
            sample_rows=int((self.temps > 0).sum()
                            + (req.temperature > 0)),
            carry_steps=prev["horizon"] if prev is not None else 0,
            **self._kda_rows(len(active) + len(chunk), len(active),
                             span=len(chunk)),
            **self._attn_layers(),
            **self._chunk_page_steps(W, off, len(chunk)))
        self._book_bubble(drec["t_enqueue"])
        real_counts = self.counts
        with _Dispatching(drec):
            self.cache, new_counts, out, pout, tok, lens, moe = mixed_step(
                self.cfg, self.params, self.cache, tok_in, len_in, *args,
                mesh=self.mesh, impl=self.serving.attention_impl,
                logprobs=want_lp, chunk_logprobs=chunk_lp,
                counts=self.counts if want_pen else None,
                presence=oc["pres"] if want_pen else None,
                frequency=oc["freq"] if want_pen else None,
                repetition=oc["rep"] if want_pen else None,
                prompt_mask=self.prompt_mask if want_pen else None,
                penalties=want_pen,
                table=oc["table"],
                seeds=oc["seeds"],
                ban_ids=oc["ban_ids"],
                ban_until=oc["ban_until"],
                bias_ids=oc["bias_ids"],
                bias_vals=oc["bias_vals"],
                allow=allow,
                pallow=pallow,
                lora_idx=oc["lora"],
                bblock=self.decode_bblock,
                live=self._live_rows(active), **self._win_kw("wtable"))
        self._note_host(drec)
        self.counts = new_counts if want_pen else real_counts
        self._pipe_carry = (tok, lens, self._carry_gen)
        _metrics.pipeline.dispatches.inc()
        return {"mixed": True, "out": out, "pout": pout, "horizon": 1,
                "active": active, "gset": frozenset(gslots),
                "gslots": gslots,
                "want_lp": want_lp, "chunk_lp": chunk_lp,
                "want_pen": want_pen, "chunk_n": len(chunk), "drec": drec,
                "moe": moe}

    def _book_bubble(self, t_enqueue: float) -> None:
        """The device has sat idle since the previous fetch completed with
        nothing enqueued behind it; the gap until THIS enqueue is pure
        host-side bubble — the cost the one-deep pipeline exists to hide
        (and the sync path pays every dispatch)."""
        if self._last_ready > 0.0:
            self.metrics.decode_bubble_seconds.inc(
                max(0.0, t_enqueue - self._last_ready))
            self._last_ready = 0.0

    def _propose_drafts(self, active: List[int]):
        """Proposal source for the verify dispatch. With a draft model
        attached (spec_method="draft"), the DraftModel rolls out spec_k
        greedy tokens per up-to-date slot (serving/draft.py); otherwise
        prompt-lookup: match the context's trailing
        spec_ngram against its own history (numpy sliding-window compare,
        rightmost hit wins) and propose the following spec_k tokens. Returns
        [num_slots, spec_k] int32, or None when nothing matched anywhere
        (the step then falls back to plain fused decode)."""
        K = self.serving.spec_k
        if self.draft is not None:
            # sampled slots accept nothing (spec_decode_step preserves their
            # distribution by sampling position 0 only) — don't draft them
            eligible = [s for s in active
                        if self.slot_req[s] is not None
                        and self.slot_req[s].temperature <= 0.0]
            return self.draft.propose(self, eligible, K)
        n = self.serving.spec_ngram
        drafts = np.zeros((self.num_slots, K), np.int32)
        # {slot: true draft count} — drafts shorter than spec_k are
        # zero-padded for the verify dispatch, and the verify argmax can
        # "accept" a padding zero; the metrics below clamp to these counts
        # so the reported acceptance rate covers only real proposed tokens
        # (ADVICE r2).
        proposed: dict = {}
        for slot in active:
            req = self.slot_req[slot]
            # Only greedy slots can accept drafts (sampled slots always fall
            # back to one token); proposing for them would burn verify FLOPs.
            if req.temperature > 0.0:
                continue
            ctx = req.prompt_ids + req.generated
            if len(ctx) < n + 2:
                continue
            arr = np.asarray(ctx[-2048:], np.int32)
            tgt = arr[-n:]
            win = np.lib.stride_tricks.sliding_window_view(arr[:-1], n)
            hits = np.nonzero((win == tgt).all(axis=1))[0]
            if hits.size == 0:
                continue
            cont = arr[int(hits[-1]) + n:][:K]
            if cont.size == 0:
                continue
            drafts[slot, :cont.size] = cont
            proposed[slot] = int(cont.size)
        return (drafts, proposed) if proposed else None

    def _slot_spec_ineligible(self, slot: int) -> bool:
        """True when this slot's request needs a plain-path-only feature:
        logprobs (verify computes no logprob tensors), active presence/
        frequency penalties (verify sampling applies none), an active
        min_tokens ban (verify has no stop-suppression masking), or a
        logit_bias (verify argmax ignores it), or guided decoding (verify
        emits multiple tokens per dispatch; the grammar mask needs the host
        FSM between every token). Such slots
        are skipped by the verify dispatch and served by the alternating
        plain step — per-slot fallback, not batch-wide."""
        req = self.slot_req[slot]
        return (req.logprobs is not None
                or req.guided is not None
                or (self.counts is not None
                    and bool(self.pres_pens[slot] or self.freq_pens[slot]
                             or self.rep_pens[slot] != 1.0))
                or self.ban_until[slot] > self.lengths[slot]
                or self._bias_n[slot] > 0)

    def _do_spec_decode(self, active: List[int], drafts,
                        proposed: dict, skip=frozenset()) -> None:
        """One speculative verify dispatch: up to spec_k + 1 tokens per slot.

        ``skip`` slots participate in the dispatch (the batch shape is fixed
        and their surplus K/V row writes follow the standard rewrite
        invariant) but emit nothing — their tokens come from the next plain
        step, which applies the features the verify pass lacks."""
        R = self.serving.spec_k + 1
        tokens = np.concatenate([self.last_token[:, None], drafts], axis=1)
        args = (jnp.asarray(tokens), jnp.asarray(self.lengths),
                self._next_rng(), jnp.asarray(self.temps),
                jnp.asarray(self.top_ks), jnp.asarray(self.top_ps))
        kw = dict(
            impl=self.serving.attention_impl,
            table=jnp.asarray(self.table),
            seeds=jnp.asarray(self.seeds), mesh=self.mesh,
            lora_idx=self._lora_vec(), bblock=self.decode_bblock)
        drec = self._dispatch_open("spec_decode_step", "spec_decode", active,
                                   rows=R,
                                   sample_rows=int((self.temps > 0).sum()))
        t0 = drec["t_enqueue"]
        ctx_rows = float(np.mean(self.lengths[list(active)])) \
            if active else 0.0
        with _Dispatching(drec):
            self.cache, out, accepted = spec_decode_step(
                self.cfg, R, self.params, self.cache, *args, **kw)
        ch = _chaos.get()
        if ch.enabled:
            # an armed "ragged_feature_error" raises here, standing in for
            # a corrupted verify-row transfer: nothing below has emitted, so
            # the failover path discards the whole dispatch un-emitted and
            # releases every slot exactly once (engine._fail_all)
            ch.on_feature_path(self, kind="spec")
        with _phase(PH_FETCH):
            out = np.asarray(out)
            accepted = np.asarray(accepted)
        t_ready = time.monotonic()
        emitted = 0
        puts0 = self.metrics.stream_items.total()
        with self._emit_phase():
            for slot in active:
                if slot in skip:
                    continue
                acc = int(accepted[slot])
                if slot in proposed:  # acceptance rate over REAL proposals
                    # clamp both sides to the slot's true draft count: the verify
                    # pass can "accept" zero-padding past a short draft, which
                    # would otherwise inflate the acceptance rate (ADVICE r2)
                    n_drafted = proposed[slot]
                    self.metrics.spec_drafted_tokens.inc(n_drafted)
                    self.metrics.spec_accepted_tokens.inc(
                        min(max(acc - 1, 0), n_drafted))
                    d = self.metrics.spec_drafted_tokens.total()
                    if d > 0:
                        self.metrics.spec_acceptance_rate.set(
                            self.metrics.spec_accepted_tokens.total() / d)
                slot_emitted = 0
                for i in range(acc):
                    if self.slot_req[slot] is None:
                        break  # hit a stop condition mid-prefix
                    self.lengths[slot] += 1
                    self.sched.note_decode(slot, 1)
                    self._emit(slot, int(out[slot, i]))
                    emitted += 1
                    slot_emitted += 1
                if self.draft is not None and slot in proposed:
                    # newest token + accepted drafts are now true draft context
                    self.draft.note_emitted(slot, slot_emitted)
        self._dispatch_close(
            drec, t_ready, batch=len(active), tokens=R * len(active),
            ctx_rows=ctx_rows, emitted=emitted,
            puts=int(self.metrics.stream_items.total() - puts0))
        self._tok_times.append((t0, emitted))
        if len(self._tok_times) >= 2:
            span = time.monotonic() - self._tok_times[0][0]
            toks = sum(n for _, n in self._tok_times)
            if span > 0:
                self.metrics.tokens_per_second.set(toks / span)
        # The verify advanced lengths/last_token on the HOST (accept counts
        # are data-dependent); a carry retained across the preceding settle
        # no longer matches the mirrors but _carry_gen never moved — drop
        # it explicitly so the next dispatch re-uploads the synced mirrors
        # instead of feeding a stale device carry (_carry_valid would
        # otherwise say yes).
        self._pipe_carry = None

    def _pipeline_on(self) -> bool:
        """May a decode dispatch be left in flight after this step?

        Chunked prefill interleaves horizon-1 decodes against a half-built
        slot and a draining engine must hit "nothing in flight" the moment
        its last emit goes out — both always force sync. Spec decode used
        to as well (its proposer reads host mirrors); with
        ``ragged_features`` on, the spec branch instead SETTLES the
        in-flight dispatch (``_settle_inflight`` — carry retained, no drain
        counted) right before proposing, so plain dispatches between verify
        rounds keep the pipeline open.
        """
        return (self.serving.decode_pipeline > 0
                and (self.serving.ragged_features > 0
                     or not self.serving.spec_decode)
                and self._chunk is None
                and not self.draining)

    def _ragged_on(self) -> bool:
        """May chunked prefill ride the ragged mixed-batch program?

        Requires the pipeline itself (the whole point is keeping it open).
        Always gated off for multi-group meshes (the packed batch spans dp
        shards) and a draining engine. With ``ragged_features``
        (the default) the feature paths COMPOSE with the mixed program
        (ISSUE 16): guided slots ride as a per-row allow-mask operand, LoRA
        as a per-token adapter-index operand, and spec decode settles (not
        drains) around its verify dispatches. ``ragged_features=0``
        restores the PR-14 fallback: spec decode, LoRA, and any active
        guided slot de-pipeline to the sync floor (the byte-identity A/B
        arm in tests/test_decode_pipeline.py)."""
        feats = self.serving.ragged_features > 0
        if not (self.serving.ragged_attention > 0
                and self.serving.decode_pipeline > 0
                and (feats or not self.serving.spec_decode)
                and (feats or not self.lora_names)
                and not self.draining):
            return False
        if self.mesh is not None and self.mesh.shape.get("dp", 1) > 1:
            return False
        return feats or not any(r is not None and r.guided is not None
                                for r in self.slot_req)

    def _carry_valid(self) -> bool:
        """True while the device-resident token/length carry of the
        in-flight dispatch still describes the batch — no slot was
        activated from the host, preempted, or otherwise rewritten since
        it was enqueued (every such transition bumps ``_carry_gen``; a
        slot that joins from a mixed dispatch's own carry lanes is IN the
        carry and bumps nothing)."""
        return (self._pipe_carry is not None
                and self._pipe_carry[2] == self._carry_gen)

    def _carry_in(self) -> tuple:
        """The (token, length) operands of the next decode or mixed
        dispatch. While the carry is valid, dispatch N's final arrays feed
        dispatch N+1 directly (donated) — no host round-trip; still so
        after a settle (``_settle_inflight``'s contract). Else a fresh
        upload of the host mirrors. The length lanes of the slots that
        hold NO request are zeroed on the way, as their mirror is (one
        tiny program, enqueued like an operand upload; the mask is
        uploaded when the set of empty slots changes): the step programs
        run every slot, a lane left to itself grows a step a token and its
        row then walks ever more pages of scratch. The mirrors' upload
        after every activation used to reset them; with admissions joining
        from the carry the pipeline no longer closes, and under open
        traffic most slots are empty. (A slot mid-walk is one of them: the
        mixed program ignores its lane and sets it, ``is_p``.)"""
        if not self._carry_valid():
            return (self._donatable(self.last_token),
                    self._donatable(self.lengths))
        tok, lens = self._pipe_carry[:2]
        empty = tuple(r is None for r in self.slot_req)
        if any(empty):
            oc = self._op_cache
            if oc.get("empty_key") != empty:
                oc["empty_key"], oc["empty"] = empty, jnp.asarray(
                    np.asarray(empty))
            lens = _zero_lanes(lens, oc["empty"])
        return tok, lens

    def _drain_decode_pipeline(self, reason: str = "drain") -> None:
        """Fetch + emit the in-flight decode dispatch, if any.

        Every transition that reads or rewrites slot state out of band of
        the device carry must drain first: prefill admission (slot reuse
        would mis-route the deferred emits), chunk start, spec decode,
        drain/failover. The device carry is dropped with it; the next
        dispatch re-uploads token/length from the now-fresh host mirrors.

        ``reason`` feeds tpu_serve_pipeline_drains_total (prefill/chunk/
        spec/guided/drain/fail) — the production-visible count of how often
        the pipeline is forced shut, which the ragged mixed-batch path
        (ISSUE 14) exists to drive to ~zero under mixed traffic.
        """
        rec = self._inflight
        if rec is None:
            return
        _metrics.pipeline.drains.inc(reason=reason)
        self._inflight = None
        self._pipe_carry = None
        self.metrics.pipeline_depth.set(0.0)
        self._decode_fetch(rec, tail=True)

    def _settle_inflight(self) -> None:
        """Fetch + emit the in-flight dispatch WITHOUT counting a drain and
        WITHOUT dropping the device carry.

        The carry-generation handoff (ISSUE 16): a feature path that needs
        the host mirrors current (spec decode's proposer) or the emits
        applied (a guided slot's FSM must see token N before masking token
        N+1) settles the predecessor instead of draining it. Finishing a
        slot mid-fetch does NOT bump ``_carry_gen`` (the carry's surplus
        lanes for a finished slot are discarded on emit — see
        engine._finish), so ``_pipe_carry`` remains valid and the next
        dispatch feeds it straight back in, device-resident: no host
        re-upload, no ``tpu_serve_pipeline_drains_total`` increment. Only
        transitions that REWRITE slot state out of band of the carry (an
        activation from a token already on the host, a preemption, the
        spec verify's host advance) invalidate it; a slot that joins from
        the carry itself — the final chunk of a mixed walk left in flight
        — does not. Settling such a record emits that slot's first token
        with the rest (``_decode_fetch``).
        """
        rec = self._inflight
        if rec is None:
            return
        self._inflight = None
        self.metrics.pipeline_depth.set(0.0)
        self._decode_fetch(rec, tail=True)

    @staticmethod
    def _donatable(mirror: np.ndarray):
        """Device upload of a host mirror that is SAFE to pass in a donated
        argument position.

        ``jnp.asarray`` of an aligned numpy array is zero-copy on the CPU
        backend — the jax.Array is a *view of the engine's mirror buffer*.
        ``decode_steps`` donates its token/length carry, so XLA may alias
        that buffer for an output and write the final device-side lengths
        straight into ``self.lengths``: the mirror then advances once in
        place by the kernel and again (+1/token) by the emit loop, and the
        double-counted rows exhaust the cache window at half budget with a
        premature "length" finish. Copying first hands the device a buffer
        nothing else references, which donation may then consume freely.
        """
        return jnp.asarray(np.array(mirror))

    def _decode_operands(self):
        """Device-resident sampling/table operands for decode dispatches.

        Re-uploaded only when the host mirrors changed (dirty flags set on
        slot activate/finish/preempt and at every block-table write) —
        re-``jnp.asarray``-ing ~10 arrays per dispatch put serial host
        uploads on the critical path of every decode, visible at the
        89.5 ms-RTT class latencies of a network-attached chip.
        """
        oc = self._op_cache
        if self._op_dirty_sampling or "temps" not in oc:
            oc["temps"] = jnp.asarray(self.temps)
            oc["top_ks"] = jnp.asarray(self.top_ks)
            oc["top_ps"] = jnp.asarray(self.top_ps)
            oc["seeds"] = jnp.asarray(self.seeds)
            oc["ban_ids"] = jnp.asarray(self.ban_ids)
            oc["ban_until"] = jnp.asarray(self.ban_until)
            oc["bias_ids"] = jnp.asarray(self.bias_ids)
            oc["bias_vals"] = jnp.asarray(self.bias_vals)
            oc["pres"] = jnp.asarray(self.pres_pens)
            oc["freq"] = jnp.asarray(self.freq_pens)
            oc["rep"] = jnp.asarray(self.rep_pens)
            oc["lora"] = self._lora_vec()
            self._op_dirty_sampling = False
        if self._op_dirty_table or "table" not in oc:
            # COPIES (_donatable's reason): the mirrors are rewritten in
            # place — Engine._win_cover (a released page's row reads
            # scratch, then another slot's page), an admission that hands
            # a freed slot its pages — while a dispatch that took the table
            # may still be in flight, and on the CPU backend jnp.asarray of
            # a mirror is a VIEW of it: that dispatch's dead row for the
            # slot (length lane 0: _carry_in) would write into the new
            # occupant's first page and not into scratch
            oc["table"] = self._donatable(self.table)
            if self.cfg.windowed:
                oc["wtable"] = self._donatable(self.wtable)
            self._op_dirty_table = False
        return oc

    def _note_host(self, drec: dict) -> None:
        """An enqueue just ended: what the host took since the last fetch
        returned is what the dispatch in flight meanwhile had to cover for
        the device to stay fed. Its own work — the emits, the step, the
        operands and the enqueue; what ``_await_arrival`` chose to wait is
        no work — and, since that wait runs to ``AWAIT_SHARE`` of the
        running dispatch whenever a slot is free, the part that follows the
        wait within the share it leaves. ``_host_s`` is the turn-around a
        SHORT dispatch exists to cover, the dear one: the fetch before it
        finishes streams and the step after it admits a successor. So the
        admission of a walk's first chunk behind a short decode dispatch
        SETS it (the last value, not a mean: the batch and the prompts move
        with the traffic) and any other turn-around can only raise it
        until the next such admission. (An enqueue that compiled says
        nothing about the next.)"""
        now = time.monotonic()
        if self._t_fetched and not drec["first_use"]:
            after = now - self._t_awaited \
                if self._t_awaited >= self._t_fetched else 0.0
            took = max(now - self._t_fetched - self._waited_s,
                       after / (1.0 - AWAIT_SHARE))
            prev = self._inflight
            admits = drec["program"] == "mixed_step" and prev is not None \
                and prev["drec"].get("horizon_why", "whole") != "whole"
            self._host_s = took if admits else max(self._host_s, took)
        self._t_fetched = 0.0

    def _short_horizon(self) -> int:
        """The fewest substeps whose device time still covers the host's
        work for one dispatch, both as the engine last measured them
        (``_dispatch_s``, ``_host_s``); one while either is unknown."""
        step_s = self._dispatch_s.get("decode_steps", 0.0)
        if step_s <= 0.0 or self._host_s <= 0.0:
            return 1
        return max(1, math.ceil(self._host_s / step_s))

    def _decode_horizon(self, prev: Optional[dict], active: List[int],
                        waiting: bool) -> tuple:
        """(substeps, why) of the next fused decode dispatch. What is
        enqueued stands between an arrival and its admission: its mixed
        step can only run behind it. So a dispatch runs the WHOLE horizon
        (``decode_horizon``, the cap) only while no admission can follow
        it — every slot holds a stream whose budget reaches past what is
        in flight (``whole``) — and SHORT (``_short_horizon``) while one
        can: a request waits with a slot free (``waiting``), a slot is free
        now (``slot_free``), or a live stream's budget — ``max_tokens`` or
        the cache window — ends inside the dispatch still in flight
        ``prev``, so its slot is free by the time this one starts
        (``budget_ends``; a stream that ends at EOS is seen one fetch
        later, as a free slot)."""
        whole = max(1, self.serving.decode_horizon)
        why = "whole"
        if waiting:
            why = "waiting"
        elif len(active) < self.num_slots:
            why = "slot_free"
        elif prev is not None:
            steps, gset = prev["horizon"], prev["gset"]
            for slot in active:
                req = self.slot_req[slot]
                left = min(req.max_tokens - len(req.generated),
                           self.max_len - 1 - int(self.lengths[slot]))
                # (a guided slot emits one token a dispatch)
                if left <= (1 if slot in gset else steps):
                    why = "budget_ends"
                    break
        if why == "whole":
            return whole, why
        return min(whole, self._short_horizon()), why

    def _do_decode(self, max_horizon: Optional[int] = None,
                   fair_horizon: bool = False,
                   prefill_possible: Optional[bool] = None):
        ch = _chaos.get()
        if ch.enabled:
            # an armed "stalled_decode" wedges here (standing in for a hung
            # device dispatch) until the watchdog aborts it — see chaos.py
            ch.on_decode_step(self)
        self._prefill_streak = 0
        prev = self._inflight
        if prev is not None and not self._carry_valid():
            # Slot lifecycle changed under the in-flight dispatch (activate/
            # preempt): its device carry no longer describes the batch, and
            # the host mirrors are stale until its tokens land — fetch
            # FIRST, then dispatch from the refreshed mirrors.
            self._drain_decode_pipeline("prefill")
            prev = None
        active = self._active_slots()
        # How many substeps: ``_decode_horizon``'s rule, then the bounds of
        # the paths that force fewer — each a VALUE of the one program's
        # ``steps`` operand (``capped`` in the record).
        # (``prefill_possible``: Engine.step hands over what its admission
        # pass left waiting, read before the pop that found nothing to
        # admit — a caller that comes back between that pop and this point
        # is taken by the next step's walk behind this dispatch like any
        # arrival a moment later.)
        if prefill_possible is None:
            st = self.sched.stats()
            prefill_possible = (st.queue_depth > 0
                                and st.active_slots < st.num_slots)
        whole = max(1, self.serving.decode_horizon)
        # A fairness-forced decode (``fair_horizon``) takes the WHOLE
        # horizon even though a prefill is possible: that is the point —
        # one real decode dispatch per prefill_fairness prefills.
        horizon, why = (whole, "whole") if fair_horizon \
            else self._decode_horizon(prev, active, prefill_possible)
        # speculation runs only while nothing waits to prefill and the path
        # may fuse at all (prefill priority stands)
        may_spec = whole > 1 and why != "waiting" \
            and (max_horizon is None or max_horizon > 1)
        cap = whole if max_horizon is None else max_horizon
        # Draft-model speculation keeps plain-path horizons within one
        # catch-up dispatch (R = spec_k + 1 rows): a full fused horizon
        # would put the draft cache R+ tokens behind, needing multiple
        # teacher-forcing rounds to recover (serving/draft.py).
        if self.draft is not None and self.serving.spec_decode:
            cap = min(cap, self.serving.spec_k + 1)
        if horizon > cap:
            horizon, why = cap, "capped"
        # The device cannot allocate: every active slot's pages must
        # cover its whole write horizon (incl. the spec path's R rows)
        # BEFORE the dispatch. May preempt the newest requests when the
        # pool runs dry — recompute the active set afterwards.
        grow = max(horizon, (self.serving.spec_k + 1)
                   if self.serving.spec_decode else 1)
        if prev is not None:
            # the unfetched dispatch writes its own horizon of rows
            # before the one about to be enqueued
            grow += prev["horizon"]
        with _phase(PH_ADMIT):  # pool bookkeeping, as at admission
            grown = self._ensure_pages(grow)
        if not grown:
            return
        active = self._active_slots()
        if prev is not None and not self._carry_valid():
            # _ensure_pages preempted under the in-flight dispatch
            self._drain_decode_pipeline("prefill")
            prev = None
            active = self._active_slots()
        if not active:
            # cancel/deadline reaps emptied the batch since the last
            # dispatch; nothing to decode — just settle the pipeline
            self._drain_decode_pipeline()
            return
        # Speculative path: only when nothing is waiting (prefill priority
        # stands). Eligibility is PER SLOT: a logprobs, penalized, or
        # min_tokens-banned request is skipped by the verify dispatch (those
        # features live only in the plain path) WITHOUT disabling speculation
        # for its neighbors; the skipped slots advance on the alternating
        # plain step (_spec_plain_due), so one logprobs request costs the
        # batch one interleaved plain dispatch, not the whole spec win
        # (VERDICT r3 weak #4: the old global .any() gates gave a single
        # request a batch-wide blast radius). Falls back when no context
        # matched.
        if (self.serving.spec_decode and may_spec
                and not self._spec_plain_due):
            if prev is not None:
                # Carry-generation handoff (ISSUE 16): the proposer and the
                # length bound below read host mirrors, so the in-flight
                # dispatch is SETTLED first — its emits sync the mirrors,
                # the carry stays valid, and no drain is counted. The old
                # mandatory pre-spec drain is gone (with ragged_features=0,
                # _pipeline_on keeps spec traffic sync and prev is None).
                self._settle_inflight()
                prev = None
                active = self._active_slots()
                if not active:
                    return
            # the verify dispatch writes spec_k + 1 rows for EVERY slot,
            # so the bound stays global over the active set
            if (self.lengths[active].max(initial=0) + self.serving.spec_k
                    + 1 < self.max_len):
                skip = {s for s in active if self._slot_spec_ineligible(s)}
                proposal = self._propose_drafts([s for s in active
                                                 if s not in skip])
                if proposal is not None:
                    self._do_spec_decode(active, *proposal, skip=skip)
                    self._spec_plain_due = bool(skip)
                    return
        self._spec_plain_due = False
        # Guided decoding: the grammar mask is valid for ONE token (the host
        # FSM must see token N before masking token N+1), but capping the
        # whole batch at horizon 1 would collapse every unguided neighbor to
        # per-token dispatches (review r5: one response_format request would
        # cost the batch ~an order of magnitude at the measured 89.5 ms
        # dispatch RTT). Instead, MIXED batches keep the fused horizon and
        # guided slots emit only substep 0's token — their surplus substeps
        # sample against the (stale) mask and are discarded on the host,
        # with the surplus K/V rows following the standard rewrite
        # invariant. Pure-guided batches drop to horizon 1 for per-token
        # latency. Evaluated after the spec branch (a guided request rides
        # the _slot_spec_ineligible skip set, not an engine-wide disable)
        # and after _ensure_pages, whose preemption may have just cleared a
        # guided slot.
        gset = frozenset(
            s for s in active
            if self.slot_req[s] is not None
            and self.slot_req[s].guided is not None)
        feats = self.serving.ragged_features > 0
        if feats and gset and prev is not None:
            # Guided mask freshness: _decode_dispatch builds the allow rows
            # from each guided slot's host FSM, which only advances when the
            # predecessor's tokens are EMITTED — settle it first (fetch +
            # emit, carry retained, NO drain counted), then dispatch against
            # the post-advance grammar states. The mask upload itself is
            # async (jnp.asarray on the dispatch half — tpulint R8 allows
            # enqueue-side uploads; only blocking READS are banned), so the
            # per-row operand rides one step ahead of the device exactly
            # like the token carry.
            self._settle_inflight()
            prev = None
            active = self._active_slots()
            if not active:
                # the settle's emits finished every slot (EOS mid-stream)
                return
            gset = frozenset(
                s for s in active
                if self.slot_req[s] is not None
                and self.slot_req[s].guided is not None)
        if horizon > 1 and gset and not any(
                self.slot_req[s] is not None and s not in gset
                for s in active):
            horizon, why = 1, "capped"
        gslots = list(gset)
        want_lp = self._want_logprobs(self.slot_req)
        want_pen = self.counts is not None and bool(
            self.pres_pens.any() or self.freq_pens.any()
            or (self.rep_pens != 1.0).any())
        rec = self._decode_dispatch(horizon, why, active, gset, gslots,
                                    want_lp, want_pen, *self._carry_in())
        if self._pipeline_on() and (feats or not gset):
            # leave the new dispatch in flight: its fetch is deferred to
            # the next decode step (or a pipeline drain), so the entire
            # emit/SSE/scheduling gap between dispatches overlaps device
            # compute instead of idling the chip for ~an RTT
            self._inflight = rec
            self.metrics.pipeline_depth.set(1.0)
            if prev is not None:
                self._decode_fetch(prev, tail=False)
        else:
            # synchronous path (decode_pipeline=0, guided, chunk, spec,
            # draining): settle everything before returning, in order. prev
            # IS self._inflight — retire it before fetching, or the next
            # step would fetch-and-emit the same dispatch twice (the
            # double emit advances the length mirrors two rows per real
            # token and exhausts the cache window at half budget).
            self._pipe_carry = None
            if prev is not None:
                _metrics.pipeline.drains.inc(reason=(
                    "chunk" if self._chunk is not None
                    else "guided" if gset
                    else "spec" if self.serving.spec_decode
                    else "drain"))
                self._inflight = None
                self.metrics.pipeline_depth.set(0.0)
                self._decode_fetch(prev, tail=False)
            self._decode_fetch(rec, tail=True)

    def _decode_dispatch(self, horizon: int, why: str, active: List[int],
                         gset, gslots: List[int], want_lp: bool,
                         want_pen: bool, tok_in, len_in) -> dict:
        """Enqueue ONE fused decode dispatch of ``horizon`` substeps (the
        program's ``steps`` operand; ``why``: what chose the count) and
        return its in-flight record. JAX async dispatch: this returns as
        soon as the program is enqueued — no blocking device reads on this
        half (tpulint R8; they belong in _decode_fetch), so the host is free
        to emit the previous dispatch's tokens while the device runs this
        one."""
        oc = self._decode_operands()
        rng = self._next_rng()
        allow = self._allow_words(gslots)
        prev = self._inflight
        drec = self._dispatch_open(
            "decode_steps", "decode", active, horizon=horizon,
            horizon_why=why, sample_rows=int((self.temps > 0).sum()),
            carry_steps=prev["horizon"] if prev is not None else 0,
            **self._kda_rows(horizon * len(active), len(active)))
        if not self.cfg.selects:    # (its rows walk a selection: the
            # program counts it, sparse_pages_* at the fetch)
            drec.update(self._attn_pages(horizon, drec["carry_steps"]))
        self._book_bubble(drec["t_enqueue"])
        real_counts = self.counts
        with _Dispatching(drec):
            self.cache, new_counts, out, tok, lens, moe = decode_steps(
                self.cfg, max(1, self.serving.decode_horizon), self.params,
                self.cache, tok_in, len_in,
                rng, oc["temps"], oc["top_ks"], oc["top_ps"],
                steps=np.int32(horizon),
                mesh=self.mesh, impl=self.serving.attention_impl,
                logprobs=want_lp,
                counts=self.counts if want_pen else None,
                presence=oc["pres"] if want_pen else None,
                frequency=oc["freq"] if want_pen else None,
                repetition=oc["rep"] if want_pen else None,
                prompt_mask=self.prompt_mask if want_pen else None,
                penalties=want_pen,
                table=oc["table"],
                seeds=oc["seeds"],
                ban_ids=oc["ban_ids"],
                ban_until=oc["ban_until"],
                bias_ids=oc["bias_ids"],
                bias_vals=oc["bias_vals"],
                allow=allow,
                lora_idx=oc["lora"],
                bblock=self.decode_bblock,
                live=self._live_rows(active), **self._win_kw("wtable"))
        self._note_host(drec)
        # un-penalized dispatches return a dummy counts array — keep ours
        self.counts = new_counts if want_pen else real_counts
        self._pipe_carry = (tok, lens, self._carry_gen)
        _metrics.pipeline.dispatches.inc()
        return {"out": out, "horizon": horizon, "active": list(active),
                "gset": gset, "gslots": gslots, "want_lp": want_lp,
                "want_pen": want_pen, "drec": drec, "moe": moe}

    def _decode_fetch(self, rec: dict, tail: bool) -> None:
        """Blocking half of a decode dispatch: transfer the sampled tokens,
        update the host mirrors, emit. The ONLY place the decode path may
        block on program output (tpulint R8 sanctions exactly this helper).

        ``tail``: nothing is enqueued behind this dispatch, so the device
        goes idle when it completes — mark the completion time and let the
        next enqueue account the gap as host bubble. A non-tail fetch (the
        steady-state pipelined case) already has the next dispatch queued:
        no mark, no bubble.

        A slot that finished (EOS/deadline/cancel) after this dispatch was
        enqueued was still computed speculatively on the device; its
        surplus tokens are discarded here by the ``slot_req is None``
        guard, under the same rewrite invariant the guided/chunk surplus
        paths rely on. A mixed record whose final chunk stayed in flight
        carries ``first`` (request, slot): that request's first token is
        emitted here, after the decode rows'.
        """
        ch = _chaos.get()
        if ch.enabled:
            # an armed "pipeline_fetch_error" raises here, standing in for
            # a transfer/XLA failure surfacing at the deferred block point
            ch.on_pipeline_fetch(self)
            if rec.get("mixed"):
                # an armed "ragged_dispatch_error" targets only mixed
                # dispatches — the in-flight record is discarded and the
                # chunk walk's error path releases its slot exactly once
                ch.on_mixed_fetch(self)
            if rec.get("gslots"):
                # an armed "ragged_feature_error" targets dispatches whose
                # allow-mask operand was live (guided rows), standing in
                # for a corrupted mask upload: the record is discarded
                # UN-EMITTED (no token below ever reached a stream) and
                # the failover path releases pages/slots exactly once
                ch.on_feature_path(self, kind="guided")
        out = rec["out"]
        lp_t = None
        t_fetch = time.monotonic()
        with _phase(PH_FETCH):
            if rec["want_lp"]:
                out, lp_t = out      # ([h, B], ([h,B], [h,B,K], [h,B,K]))
                # ONE bulk transfer; per-token slicing below is pure numpy
                # (3 tiny device gathers per emitted token would round-trip
                # the network-attached chip thousands of times per dispatch)
                lp_t = tuple(np.asarray(a) for a in lp_t)
            out = np.asarray(out)  # [horizon, B] — blocks until complete
            if rec.get("mixed"):
                # chunk-row outputs ride the same record: the sampled token
                # of the chunk's last position (only meaningful on the final
                # chunk: the slot's first token, emitted below or handed to
                # _activate by _advance_chunk_mixed)
                pout = rec["pout"]
                if rec["chunk_lp"]:
                    ptok_arr, plp = pout
                    rec["chunk_token"] = int(np.asarray(ptok_arr)[0])
                    rec["chunk_lp_t"] = tuple(np.asarray(a) for a in plp)
                else:
                    rec["chunk_token"] = int(np.asarray(pout)[0])
            if rec.get("moe") is not None and self.cfg.selects:
                # a selecting model's page counts ride the same fetch (the
                # programs' last output, _aux): per selecting layer, the
                # (row, KV head) pairs' live and selected pages over the
                # record's substeps
                live_n, picked = (
                    np.asarray(rec["moe"]) / self.cfg.num_attn_layers)
                rec["drec"].update(
                    sparse_rows=rec["horizon"] * len(rec["active"])
                    + rec.get("chunk_n", 0),
                    sparse_pages_live=float(live_n),
                    sparse_pages_selected=float(picked))
            elif rec.get("moe") is not None:
                # routing counts of an MoE model ride the same fetch: the
                # program has ended, this is a copy of two floats
                moe = np.asarray(rec["moe"])
                hit, largest = moe[:2]
                k = self.cfg.num_experts_per_tok
                rec["drec"].update(
                    moe_rows=k * (rec["horizon"] * len(rec["active"])
                                  + rec.get("chunk_n", 0)),
                    moe_experts_hit=float(hit), moe_group_max=int(largest))
                if moe.shape[0] == 3:
                    # an expert share: the pairs that landed on an expert
                    # held here, of the moe_rows chosen (per layer)
                    rec["drec"]["moe_rows_held"] = float(moe[2])
        t_ready = time.monotonic()
        horizon = rec["horizon"]
        active = rec["active"]
        ctx_rows = float(np.mean(self.lengths[list(active)])) \
            if active else 0.0
        gset = rec["gset"]
        emitted = 0
        puts0 = self.metrics.stream_items.total()
        mixed = bool(rec.get("mixed"))
        try:
            with self._emit_phase():
                for s in range(horizon):
                    for slot in active:
                        if self.slot_req[slot] is None:
                            # finished earlier in this horizon — or after
                            # the dispatch was enqueued (pipelined surplus
                            # discard)
                            continue
                        if s > 0 and slot in gset:
                            # guided slots advance one grammar-checked
                            # token per dispatch; substeps past 0 are
                            # unconstrained surplus
                            continue
                        req = self.slot_req[slot]
                        lp = None
                        if req.logprobs is not None and lp_t is not None:
                            lp = _host_lp(tuple(a[s] for a in lp_t), slot,
                                          req.logprobs)
                        self.lengths[slot] += 1
                        self.sched.note_decode(slot, 1)
                        self._emit(slot, int(out[s, slot]), lp)
                        emitted += 1
            if rec["want_pen"] and rec["gslots"] and horizon > 1:
                # the fused dispatch incremented guided slots' device-side
                # penalty-count rows for EVERY substep, but only substep 0
                # was emitted — resync those rows from the authoritative
                # host stream (review r5: the first fix dropped the whole
                # batch to horizon 1 for one penalized guided request; this
                # one costs a single [V]-row scatter per guided slot instead)
                for slot in rec["gslots"]:
                    req = self.slot_req[slot]
                    if req is None or not (self.pres_pens[slot]
                                           or self.freq_pens[slot]
                                           or self.rep_pens[slot] != 1.0):
                        continue
                    row = np.bincount(np.asarray(req.generated, np.int64),
                                      minlength=self.cfg.vocab_size)
                    self.counts = _restore_count_row(
                        self.counts, jnp.int32(slot),
                        jnp.asarray(row, jnp.int32))
            if tail and any(r is not None for r in self.slot_req):
                self._last_ready = t_ready
        finally:
            self._dispatch_close(
                rec["drec"], t_ready,
                batch=len(active) + (1 if mixed else 0),
                tokens=horizon * len(active) + rec.get("chunk_n", 0),
                ctx_rows=ctx_rows, steps=horizon,
                guided_rows=len(rec["gslots"]), tail=tail, emitted=emitted,
                puts=int(self.metrics.stream_items.total() - puts0),
                t_wait=t_fetch)
        first = rec.get("first")
        if first is not None and self.slot_req[first[1]] is first[0]:
            # a final chunk that stayed in flight (_advance_chunk_mixed):
            # its slot joined the batch at the enqueue, its first token
            # goes out here, after the decode rows' as it always did. A
            # request that left the slot meanwhile (cancel, deadline,
            # preemption) fails the identity test — no later occupant can
            # have joined before this fetch — and the token is discarded.
            req, slot = first
            with self._emit_phase():
                self._first_token(req, slot, *self._chunk_sample(rec, req))
        self._tok_times.append((rec["drec"]["t_enqueue"], emitted))
        if len(self._tok_times) >= 2:
            span = time.monotonic() - self._tok_times[0][0]
            toks = sum(n for _, n in self._tok_times)
            if span > 0:
                self.metrics.tokens_per_second.set(toks / span)

    def warmup(self, scope: str = "full"):
        """Pre-compile programs so the first real request doesn't pay 20-40s
        of XLA compile time per program.

        scope="full" (serving): every variant — each prefill bucket, batched/
        chunked prefill, prefix cache, speculative, penalties, logprobs, the
        fused decode. ~20 programs, minutes of XLA time cold — fine at
        server startup (the readiness probe gates traffic) but NOT inside a
        bounded benchmark window.

        scope="bench": only the two programs the benchmark path executes —
        the full-width batched prefill and the fused-horizon decode (bench
        prompts sit below the prefix-cache min length, spec decode is off,
        and the fill loop admits batches until the queue drains, so no other
        program is ever dispatched). This is what keeps bench.py's warmup
        to two compiles instead of ~20.
        """
        t0 = time.monotonic()
        try:
            self._warmup(scope)
        finally:
            # Cold-start observability: with a warm persistent compilation
            # cache (or an AOT-populated one) this stays near zero; minutes
            # here mean every respawn re-pays XLA (serving/aot.py).
            self.metrics.compile_seconds.inc(time.monotonic() - t0)

    def load_aot_manifest(self, path: str) -> dict:
        """Adopt an AOT manifest (serving/aot.py) for THIS engine.

        The manifest carries no executables — binaries come from the
        persistent compilation cache the AOT run populated. Adoption checks
        the manifest was built for this exact program set (config
        fingerprint) and that its HBM ledger fits, then surfaces the ledger
        on ``tpu_serve_hbm_compiled_bytes`` and ``/healthz``. A mismatched
        or no-fit manifest raises: serving silently without the AOT
        guarantee is exactly the cold-start/OOM surprise the artifact
        exists to rule out.
        """
        import json

        from aws_k8s_ansible_provisioner_tpu.serving.aot import verify_manifest

        with open(path, encoding="utf-8") as f:
            manifest = json.load(f)
        verify_manifest(manifest)
        dp = self.mesh.shape.get("dp", 1) if self.mesh is not None else 1
        tp = self.mesh.shape.get("tp", 1) if self.mesh is not None else 1
        want = {
            "model": self.cfg.name,
            "num_slots": self.num_slots,
            "max_len": self.max_len,
            "page_size": self.serving.page_size,
            "buckets": list(self.buckets),
            "weights_dtype": self.serving.weights_dtype,
            "kv_dtype": self.serving.kv_dtype,
            "dp": dp, "tp": tp,
        }
        got = manifest["config"]
        bad = {k: (got.get(k), v) for k, v in want.items()
               if got.get(k) != v}
        if bad:
            raise ValueError(
                f"AOT manifest {path} was built for a different program "
                "set: " + "; ".join(
                    f"{k}: manifest={a!r} engine={b!r}"
                    for k, (a, b) in sorted(bad.items())))
        ledger = manifest["hbm_ledger"]
        if not ledger["fit"]:
            raise RuntimeError(
                f"AOT manifest {path} verdict is NO-FIT: "
                f"{ledger['total_bytes']} accounted bytes/chip vs "
                f"{ledger['capacity_bytes_per_chip']} capacity "
                f"(headroom {ledger['headroom_bytes']})")
        self.aot = {
            "path": path,
            "platform": manifest["platform"],
            "topology": manifest.get("topology", ""),
            "programs": len(manifest["programs"]),
            "total_compile_seconds": manifest["total_compile_seconds"],
            "hbm_total_bytes": ledger["total_bytes"],
            "hbm_headroom_bytes": ledger["headroom_bytes"],
            "fit": True,
        }
        self.metrics.hbm_compiled_bytes.set(float(ledger["total_bytes"]))
        return self.aot

    def _warmup(self, scope: str) -> None:
        # Runtime import: Request lives with the scheduler (engine.py), which
        # imports this module at load time — resolve the cycle at call time.
        from aws_k8s_ansible_provisioner_tpu.serving.engine import Request

        def drain():
            while (any(s is not None for s in self.slot_req) or self.pending
                   or self._chunk is not None):
                self.step()

        horizon = max(1, self.serving.decode_horizon)
        # the carry's empty-lane reset (_carry_in): first met with a slot
        # empty under an open pipeline, i.e. while streams are served
        _zero_lanes(self._donatable(self.lengths),
                    jnp.zeros(self.num_slots, bool))
        if scope == "bench":
            nb = min(self.serving.max_prefill_batch, self.num_slots)
            rs = [Request(prompt_ids=[0] * 4, max_tokens=1, ignore_eos=True)
                  for _ in range(max(1, nb))]
            for r in rs:
                self.submit(r)
            drain()
            self.cache, _, _, _, _, _ = decode_steps(
                self.cfg, horizon, self.params, self.cache,
                self._donatable(self.last_token),
                self._donatable(self.lengths),
                self._next_rng(), jnp.asarray(self.temps),
                jnp.asarray(self.top_ks), jnp.asarray(self.top_ps),
                steps=np.int32(horizon),
                mesh=self.mesh, impl=self.serving.attention_impl,
                table=jnp.asarray(self.table),
                seeds=jnp.asarray(self.seeds),
                ban_ids=jnp.asarray(self.ban_ids),
                ban_until=jnp.asarray(self.ban_until),
                bias_ids=jnp.asarray(self.bias_ids),
                bias_vals=jnp.asarray(self.bias_vals),
                lora_idx=self._lora_vec(),
                bblock=self.decode_bblock,
                live=self._live_rows(()), **self._win_kw("wtable"))
            return

        # Distinct token values per warmup request — identical prompts would
        # prefix-cache-match each other and warm the WRONG program.
        for i, b in enumerate(self.buckets):
            r = Request(prompt_ids=[(2 * i + 1) % (self.cfg.vocab_size - 1)]
                        * min(b, self.max_len - 2),
                        max_tokens=1, ignore_eos=True)
            self.submit(r)
            drain()
        # Batched-prefill program for the full batch width at the smallest
        # bucket (the burst-of-short-prompts case the batching exists for;
        # other (N, T) combos compile lazily on first use).
        nb = min(self.serving.max_prefill_batch, self.num_slots)
        if nb > 1:
            rs = [Request(prompt_ids=[0] * 4, max_tokens=1, ignore_eos=True)
                  for _ in range(nb)]
            for r in rs:
                self.submit(r)
            drain()
        # Chunk-prefill program (one program serves every chunk).
        if self.serving.prefill_chunk > 0 \
                and self.max_len - 2 > self.serving.prefill_chunk:
            r = Request(prompt_ids=[97 % (self.cfg.vocab_size - 1)]
                        * (self.serving.prefill_chunk + 1),
                        max_tokens=1, ignore_eos=True)
            self.submit(r)
            drain()
        # Prefix-reuse program (the suffix chunk walk from a reuse offset):
        # a seed prompt, then an extension of it, so the second shares the
        # seed's whole pages and takes the hit path. When the seed doesn't
        # fit the prompt limit, the program compiles lazily on the first
        # real hit instead.
        n_seed = self.serving.page_size \
            * max(1, self.serving.prefix_reuse_min_pages) + 1
        if self.serving.prefix_cache and n_seed + 8 <= self.prompt_limit:
            tok = 43 % (self.cfg.vocab_size - 1)
            seed = [tok] * n_seed
            self.submit(Request(prompt_ids=list(seed), max_tokens=1,
                                ignore_eos=True))
            drain()
            self.submit(Request(prompt_ids=list(seed) + [tok + 1] * 8,
                                max_tokens=1, ignore_eos=True))
            drain()
        # Speculative-verify program: a self-repeating prompt guarantees the
        # prompt-lookup proposer fires, compiling spec_decode_step.
        if self.serving.spec_decode:
            n = self.serving.spec_ngram
            pat = [11, 12, 13][:max(1, min(3, n))]
            r = Request(prompt_ids=(pat * (2 + (2 * n) // len(pat)))[:self.prompt_limit],
                        max_tokens=self.serving.spec_k + 2, ignore_eos=True)
            self.submit(r)
            drain()
        # compile the fused decode program too — ONE program whatever the
        # substeps a dispatch runs (the count is its ``steps`` operand) —
        # and its penalties variant ('penalties' is a static arg — a
        # distinct program): the first penalized request must not pay a
        # 20-40s XLA compile inside step(), freezing every in-flight stream
        # (and burning most of the /health stall budget).
        r = Request(prompt_ids=[0] * 4, max_tokens=horizon + 1,
                    ignore_eos=True)
        self.submit(r)
        drain()
        # Penalties variants compile against THROWAWAY buffers so warmup does
        # not permanently allocate the [num_slots, vocab] counts array (~78 MB
        # int32 at Qwen3 vocab x 128 slots) an engine whose clients never use
        # penalties would otherwise carry — self.counts stays None until the
        # first real penalized request (ADVICE r2). Both device calls donate
        # their counts input, so the scratch buffer is freed on return.
        cnts = jnp.zeros((self.num_slots, self.cfg.vocab_size), jnp.int32)
        cnts = _reset_count_row(cnts, jnp.int32(0), jnp.int32(0))
        mask = jnp.zeros((self.num_slots, self.cfg.vocab_size), jnp.bool_)
        self.cache, _, _, _, _, _ = decode_steps(
            self.cfg, horizon, self.params, self.cache,
            self._donatable(self.last_token), self._donatable(self.lengths),
            self._next_rng(), jnp.asarray(self.temps),
            jnp.asarray(self.top_ks), jnp.asarray(self.top_ps),
            steps=np.int32(horizon),
            mesh=self.mesh, impl=self.serving.attention_impl,
            counts=cnts, presence=jnp.asarray(self.pres_pens),
            frequency=jnp.asarray(self.freq_pens),
            repetition=jnp.asarray(self.rep_pens), prompt_mask=mask,
            penalties=True,
            table=jnp.asarray(self.table),
            seeds=jnp.asarray(self.seeds),
            ban_ids=jnp.asarray(self.ban_ids),
            ban_until=jnp.asarray(self.ban_until),
            bias_ids=jnp.asarray(self.bias_ids),
            bias_vals=jnp.asarray(self.bias_vals),
                    lora_idx=self._lora_vec(),
                    bblock=self.decode_bblock,
                    live=self._live_rows(()), **self._win_kw("wtable"))
        del cnts, mask
        # Logprobs program variants ('logprobs' is a static arg on every step
        # fn — distinct programs): one isolated request compiles the
        # single-prefill + fused-decode logprob programs, one burst compiles
        # the batched-prefill logprob program. Without these, the first
        # logprobs=N request pays the same all-streams XLA freeze the
        # penalties warmup exists to prevent (ADVICE r2, medium).
        self.submit(Request(prompt_ids=[3] * 4, max_tokens=max(2, horizon + 1),
                            ignore_eos=True, logprobs=0, prompt_logprobs=0))
        drain()
        if nb > 1:
            # one plp row in the burst also compiles the batched
            # prompt-logprob variant (echo+logprobs implies it — review r5)
            rs = [Request(prompt_ids=[5] * 4, max_tokens=1, ignore_eos=True,
                          logprobs=0, prompt_logprobs=0 if i == 0 else None)
                  for i in range(nb)]
            for r in rs:
                self.submit(r)
            drain()
