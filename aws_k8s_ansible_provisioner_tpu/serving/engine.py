"""Continuous-batching serving engine: the reference's vLLM replacement.

The reference delegates this entire component to the external vLLM container
(SURVEY.md §0 item 4, §2.2 row 1); here it is in-repo and TPU-native:

- **A small fixed set of compiled programs** drives everything:
  ``prefill_step`` (one program per prompt-length bucket),
  ``prefill_batch_step`` (N waiting prompts in one dispatch, N a power of
  two), ``prefill_chunk_step`` (one fixed-size chunk of a long prompt, decode
  interleaved between chunks), and ``decode_steps`` (one program over all
  slots, up to ``decode_horizon`` fused substeps: how many a dispatch runs
  is an operand, the whole horizon while no admission can follow it and a
  few substeps while one can). Static shapes throughout — XLA's compilation model
  is the design constraint (SURVEY.md §7 hard part #2: "continuous batching
  under XLA's static-shape constraint").
- **Prefill/decode interleaving** with prefill priority: TTFT p50 is the headline
  baseline metric (BASELINE.json), and a waiting prompt hurts TTFT more than one
  decode step hurts per-token latency.
- **Donated KV cache**: the multi-GB cache is donated to each step so XLA updates
  it in place in HBM — no per-token copies.
- **Per-slot sampling params as vectors**: any mix of greedy/temperature/top-p
  requests shares the single decode program.

The host-side scheduler (this file) is deliberately thin: slot bookkeeping,
stop conditions, and streaming queues; everything hot is inside jit. The jit
layer itself — the step functions, bblock autotune, operand construction,
and the warmup plan — lives in ``serving/programs.py`` (the compiled-program
registry, which ``serving/aot.py`` also compiles ahead-of-time); ``Engine``
inherits it as the ``EnginePrograms`` mixin.
"""

from __future__ import annotations

import collections
import itertools
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from aws_k8s_ansible_provisioner_tpu.config import ModelConfig, ServingConfig
from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp
from aws_k8s_ansible_provisioner_tpu.serving import capacity as _capacity
from aws_k8s_ansible_provisioner_tpu.serving import chaos as _chaos
from aws_k8s_ansible_provisioner_tpu.serving import devmon as _devmon
from aws_k8s_ansible_provisioner_tpu.serving import flightrec as _flight
from aws_k8s_ansible_provisioner_tpu.serving import metrics as _metrics
from aws_k8s_ansible_provisioner_tpu.serving import slo as _slo
from aws_k8s_ansible_provisioner_tpu.serving.metrics import EngineMetrics
from aws_k8s_ansible_provisioner_tpu.serving.programs import (  # noqa: F401
    AWAIT_SHARE,
    BAN_K,
    BBLOCK_CANDIDATES,
    BIAS_K,
    LOGPROB_K,
    PH_ADMIT,
    PH_FETCH,
    PH_IDLE,
    PH_OPERANDS,
    PH_REAP,
    _BBLOCK_CACHE,
    EnginePrograms,
    _Dispatching,
    _host_lp,
    _phase,
    decode_steps,
    pick_decode_bblock,
    prefill_batch_step,
    prefill_chunk_step,
    prefill_step,
    install_compile_listeners,
    spec_decode_step,
)

_REQUEST_IDS = itertools.count()


def _tree_bytes(tree) -> int:
    """HBM bytes of a pytree of device/host arrays, from shape/dtype
    metadata only — never a device transfer (safe on any thread)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        try:
            total += int(leaf.size) * int(np.dtype(leaf.dtype).itemsize)
        except (TypeError, ValueError, AttributeError):
            pass
    return total


class ContextLengthExceeded(ValueError):
    """Prompt does not fit the engine's context window.

    Raised by :meth:`Engine.submit` instead of silently truncating the prompt
    tail — the server maps this to the OpenAI ``400 context_length_exceeded``
    error the reference's vLLM engine returns for the same condition.
    """

    def __init__(self, n_prompt: int, limit: int, max_len: int):
        self.n_prompt, self.limit, self.max_len = n_prompt, limit, max_len
        super().__init__(
            f"This model's maximum prompt length is {limit} tokens "
            f"(context window {max_len}); your prompt has {n_prompt} tokens.")


class EngineOverloaded(RuntimeError):
    """Admission control shed this request (bounded queue / wait estimate).

    Raised by :meth:`Engine.submit` BEFORE the request enters the queue —
    nothing was generated, so the caller may safely retry elsewhere/later.
    The server maps this to ``429`` with a ``Retry-After`` header carrying
    :attr:`retry_after_s`; the router treats that 429 as a routable signal.
    """

    def __init__(self, reason: str, message: str, retry_after_s: float = 1.0):
        self.reason = reason
        self.retry_after_s = max(1.0, float(retry_after_s))
        super().__init__(message)


@dataclass
class Request:
    """One in-flight generation request."""

    prompt_ids: List[int]
    max_tokens: int = 256
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    # OpenAI presence/frequency penalties over the request's generated
    # tokens (0.0 = off; subtractive on logits — ops/sampling.apply_penalties)
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # vLLM/HF ``repetition_penalty`` (1.0 = off): multiplicative over every
    # token in the prompt OR generated so far — positive logits divide,
    # non-positive multiply (HF RepetitionPenaltyLogitsProcessor semantics).
    repetition_penalty: float = 1.0
    ignore_eos: bool = False
    stream: bool = False
    cancelled: bool = False
    # OpenAI ``logprobs``: None = off; an int N = return the chosen token's
    # logprob plus N top alternatives (N=0 is valid: chosen-only, the OpenAI
    # completions logprobs=0 semantics; capped at LOGPROB_K). Any non-None
    # value switches the slot's dispatches to the logprob program variants.
    logprobs: object = None
    # OpenAI ``seed``: deterministic sampling for this request — same seed +
    # same prompt + same sampling params => same token stream, independent of
    # batch composition (ops/sampling.per_slot_keys). None = a per-engine
    # derived seed (sampling still randomized across requests).
    seed: Optional[int] = None
    # resolved at submit(): seed, or the engine's derived default
    eff_seed: int = 0
    # vLLM ``stop_token_ids``: extra per-request stop tokens (the model's
    # eos set still applies unless ignore_eos).
    stop_token_ids: tuple = ()
    # vLLM ``min_tokens``: suppress ALL stop tokens (eos + stop_token_ids)
    # until this many tokens have been generated (budget still caps).
    min_tokens: int = 0
    # OpenAI ``logit_bias``: ((token_id, bias), ...) pairs added to the
    # logits before every sampling decision (greedy included — ±100 act as
    # force/ban, the documented semantics). Server normalizes the JSON map;
    # () = off. At most BIAS_K entries (submit() validates).
    logit_bias: tuple = ()
    # vLLM ``prompt_logprobs`` (also powers OpenAI legacy echo+logprobs):
    # None = off; int K = per-PROMPT-position logprob of the actual token
    # plus top-K alternatives (position 0 is None). Disables prefix-cache
    # reuse for the request (reused rows skip prefill, which is where these
    # are computed) and rejects prompts that need chunking.
    prompt_logprobs: object = None
    # Multi-LoRA (models/lora.py): name of an adapter registered at Engine
    # construction, or None = base model. Any mix of adapters rides one
    # continuous batch (per-slot index vector on every dispatch).
    lora: Optional[str] = None
    # OpenAI ``response_format`` (serving/guided.py): a TokenGrammar (or
    # GuidedState) constraining every sampled token to the grammar's allowed
    # set. submit() wraps a bare grammar in a fresh per-request GuidedState.
    # Guided slots force horizon-1 decode dispatches (the host FSM must see
    # token N before masking token N+1) and are spec-decode-ineligible.
    guided: object = None
    # End-to-end deadline, RELATIVE seconds from submission (server parses
    # the X-Request-Deadline-Ms header / deadline_ms body field into this).
    # None = the engine's default (serving.request_timeout_s). submit()
    # resolves it into the absolute ``t_deadline``; the engine enforces it
    # between dispatches — expiry cancels the request, releases its slot and
    # pages, and finishes it with finish_reason "timeout" (HTTP 408).
    deadline_s: Optional[float] = None
    # Mid-stream failover continuation (r8): token ids another replica
    # already generated (and relayed to the client) for this exact prompt +
    # sampling params + seed. submit() pre-populates ``generated`` with them
    # and registers a preemption-style resume, so the request re-prefills
    # prompt + resume as pure CACHE REBUILD and the next decode draw uses
    # the seeded key at position len(prompt) + len(resume) — by the
    # cross-resume reproducibility contract (decode_steps' ctr alignment),
    # the continuation is token-identical to the uninterrupted stream.
    # Only the NEW tokens reach out_queue. Paged engines only.
    resume_ids: tuple = ()
    # absolute time.monotonic() deadline, resolved at submit (0.0 = none)
    t_deadline: float = 0.0
    # root-span trace id the server bound to this request (empty = tracing
    # off) — feeds the OpenMetrics exemplars on the ttft/request-duration
    # histogram buckets so a burning bucket links straight to its trace
    trace_id: str = ""
    id: int = field(default_factory=lambda: next(_REQUEST_IDS))
    # Filled in by the engine:
    generated: List[int] = field(default_factory=list)
    # per generated token: (own logprob, [(token_id, logprob) x k])
    logprob_data: List[tuple] = field(default_factory=list)
    # per PROMPT position: None (position 0) or (own logprob,
    # [(token_id, logprob) x k]) — filled at activation when
    # prompt_logprobs is requested
    prompt_logprob_data: List = field(default_factory=list)
    # A stream's queue carries ITEMS: a list of the token ids one dispatch
    # produced for this request (the activation token is an item of its
    # own), then None when the request is done. ``pending`` holds the ids
    # the emit phase in progress has recorded and not yet put (engine
    # thread only; empty whenever the engine is outside an emit phase).
    out_queue: "queue.Queue" = field(default_factory=queue.Queue)
    pending: List[int] = field(default_factory=list)
    t_submit: float = 0.0
    # first admission out of the queue into a slot (set-if-unset, so a
    # preempt/requeue round-trip keeps the original queue-wait boundary) —
    # splits TTFT into queue-wait vs prefill for the tracing phase spans
    t_prefill_start: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    finish_reason: str = ""

    def wait(self, timeout: Optional[float] = None) -> List[int]:
        """Block until completion; returns generated token ids."""
        deadline = time.monotonic() + timeout if timeout else None
        while True:
            remaining = (deadline - time.monotonic()) if deadline else None
            if remaining is not None and remaining <= 0:
                raise TimeoutError(f"request {self.id} timed out")
            item = self.out_queue.get(timeout=remaining)
            if item is None:
                return self.generated


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class Engine(EnginePrograms):
    """Continuous-batching engine over a fixed set of decode slots."""

    # The paged pool is the only KV layout. Read-only, kept for two readers:
    # benchmark/benchlib/server_under_test.py (refuses a run whose engine
    # did not resolve paged) and server.py's /debug/state.
    paged = True

    # Single-writer contract (tpulint R5 / LockSan): these attributes are
    # mutated ONLY by the engine-step thread (run_forever -> step and its
    # helpers). Other threads may read them (GIL-atomic snapshots for
    # /health, /load and metrics) but never write. Attributes shared for
    # WRITING across threads (draining, _drain_deadline, _stall_abort,
    # _queued, ...) are NOT listed here — their writes go under self._lock.
    _R5_THREAD_OWNED = (
        "table", "lengths", "cache", "counts", "last_token",
        "slot_req", "temps", "pres_pens", "freq_pens", "rep_pens",
        "ban_until", "bias_ids", "bias_vals", "lora_idx", "_bias_n",
        "_slot_pages", "_chunk", "wtable", "_slot_wpages", "_wfirst",
        "_win_unreleased",
        "_chunk_yield", "_prefill_streak", "_admission_blocked_since",
        "_tok_times", "_admit_seq", "_seq_counter", "prompt_mask",
        "_inflight", "_pipe_carry", "_carry_gen", "_op_cache",
        "_op_dirty_sampling", "_op_dirty_table", "_last_ready",
        "_busy_watermark", "_allow_dev", "_allow_batch_dev",
        "_restore_pending", "_emit_streams", "_dispatch_s",
        "_host_s", "_t_fetched", "_t_awaited", "_waited_s",
    )

    def __init__(self, cfg: ModelConfig, params, serving: ServingConfig,
                 eos_token_id: Optional[int] = None, mesh=None, draft=None,
                 lora=None):
        self.cfg = cfg
        self.params = params
        self.serving = serving
        # Draft-model speculation (serving/draft.py; VERDICT r4 next #7):
        # ``draft`` is (draft_cfg, draft_params). Requires spec_decode with
        # spec_method="draft"; the DraftModel allocates its own small pool
        # after max_len resolves below.
        self._draft_src = draft
        self.eos_token_id = cfg.eos_token_id if eos_token_id is None \
            else eos_token_id
        # Any member stops generation (Llama-3 Instruct ships several eos
        # ids; chat turns end with <|eot_id|>, not the primary eos). A
        # constructor override (e.g. the tokenizer's eos) EXTENDS the config's
        # set — replacing it would evict <|end_of_text|> when the tokenizer
        # declares <|eot_id|>.
        self._eos_set = ({self.eos_token_id, cfg.eos_token_id}
                         | set(cfg.extra_eos_token_ids))
        self.num_slots = serving.max_decode_slots
        # Round the cache window up to a 256 multiple: the Pallas decode
        # kernel streams the cache in chunks that must divide the window, and
        # an awkward length (e.g. 509) would degrade its chunk size to the
        # largest divisor — potentially 1. A slightly larger cache is the
        # right trade.
        self.max_len = -(-serving.max_cache_len // 256) * 256 \
            if serving.max_cache_len > 256 else serving.max_cache_len
        # Never exceed the model's position range: RoPE models degrade
        # gracefully, but a learned position table (OPT) silently clamps its
        # gather past max_seq_len — same embedding for every later token.
        self.max_len = min(self.max_len, cfg.max_seq_len)
        self.buckets = tuple(b for b in serving.prefill_buckets
                             if b <= self.max_len)
        # Program-operand construction (quantize/shard/LoRA, paged pool)
        # lives with the compiled-program registry:
        # EnginePrograms._init_params_and_cache (serving/programs.py).
        self._init_params_and_cache(mesh, lora)

        self.metrics = EngineMetrics()
        self.metrics.kda_state_bytes.set(self.kda_state_bytes)
        if cfg.recurrent:
            self.metrics.recurrent_state_bytes.set(
                self.kda_state_bytes, kind=cfg.recurrent_kinds)
        if "conv_tail" in self.cache:
            self.metrics.conv_state_bytes.set(
                self.cache["conv_tail"].nbytes)
        if "ssm_state" in self.cache:
            self.metrics.ssm_state_bytes.set(
                self.cache["ssm_state"].nbytes + self.cache["ssm_conv"].nbytes)
        if cfg.selects:
            self.metrics.selector_cache_bytes.set(self.selector_bytes)
        # AOT manifest summary (serving/aot.py), installed by
        # load_aot_manifest; surfaced on /healthz and the hbm gauge.
        self.aot = None
        self._rng = jax.random.PRNGKey(0)
        # Derived sampling seeds for requests that don't set OpenAI `seed`.
        # Default (derived_seed=None): entropy from os.urandom, so engine
        # restarts and sibling replicas draw independently — the vLLM/OpenAI
        # nondeterministic default (ADVICE r3: Random(0) made every restart
        # replay the identical unseeded sample sequence). Harnesses that
        # need two engines to draw identically (dryrun parity, tests) pin an
        # int derived_seed.
        import os as _os
        import random as _random

        self._py_rng = _random.Random(
            int.from_bytes(_os.urandom(8), "little")
            if serving.derived_seed is None else int(serving.derived_seed))
        # Host-side slot state (numpy mirrors of the device vectors).
        self.lengths = np.zeros(self.num_slots, np.int32)
        self.last_token = np.zeros(self.num_slots, np.int32)
        self.temps = np.zeros(self.num_slots, np.float32)
        self.top_ks = np.zeros(self.num_slots, np.int32)
        self.top_ps = np.ones(self.num_slots, np.float32)
        self.seeds = np.zeros(self.num_slots, np.uint32)
        # min_tokens stop suppression: per-slot banned-token lists (padded
        # with an out-of-vocab id — the masking scatter drops them) active
        # while the slot's context length < ban_until (prompt + min_tokens)
        self.ban_ids = np.full((self.num_slots, BAN_K), 2**31 - 1, np.int32)
        self.ban_until = np.zeros(self.num_slots, np.int32)
        # OpenAI logit_bias: per-slot (ids, vals) rows, always-on scatter-add
        # in every sampling step (padding ids are out-of-vocab and drop) —
        # the same no-program-variant mechanism as the ban rows above.
        # _bias_n tracks which slots have live bias (spec eligibility).
        self.bias_ids = np.full((self.num_slots, BIAS_K), 2**31 - 1, np.int32)
        self.bias_vals = np.zeros((self.num_slots, BIAS_K), np.float32)
        self._bias_n = np.zeros(self.num_slots, np.int32)
        # per-slot LoRA adapter index (0 = base); rides every dispatch when
        # adapters are registered
        self.lora_idx = np.zeros(self.num_slots, np.int32)
        self.pres_pens = np.zeros(self.num_slots, np.float32)
        self.freq_pens = np.zeros(self.num_slots, np.float32)
        self.rep_pens = np.ones(self.num_slots, np.float32)
        # [num_slots, V] generated-token counts, allocated lazily on the
        # first penalized request (78 MB at Qwen3 vocab x 128 slots — only
        # paid when the feature is used); rides decode_steps' donated carry.
        self.counts = None
        # [num_slots, V] bool prompt-token presence, lazily allocated with
        # the first repetition_penalty request (repetition covers PROMPT
        # tokens too — counts track generated only). Stale rows under
        # rep == 1.0 slots are exact no-ops, like stale counts rows.
        self.prompt_mask = None
        self.slot_req: List[Optional[Request]] = [None] * self.num_slots
        # Admission queue + slot lifecycle live in the runtime core (native
        # C++ when built — see native/runtime; Python fallback otherwise).
        # The engine holds only the id -> Request map for queued requests.
        from aws_k8s_ansible_provisioner_tpu.runtime import make_scheduler

        self.sched = make_scheduler(self.num_slots, self.max_len,
                                    serving.page_size,
                                    max_queue=max(0,
                                                  serving.max_queue_depth))
        self._queued: dict = {}
        self._lock = threading.Lock()
        self._work_event = threading.Event()
        self._tok_times: Deque = collections.deque(maxlen=50)
        # streams the emit phase in progress recorded a token for, in the
        # order of their first token (_emit appends, _flush_streams empties)
        self._emit_streams: List[Request] = []
        # Chunked-prefill state: {"req", "slot", "off", "C"} while a prompt
        # (or a prefix-cache suffix) is being prefilled chunk-by-chunk; decode
        # steps interleave between chunks (self._chunk_yield alternates).
        self._chunk: Optional[dict] = None
        self._chunk_yield = False
        # Consecutive prefill dispatches since the last decode — the
        # prefill_fairness floor keys off this (step()).
        self._prefill_streak = 0
        # Batch-block size for the decode kernels (PALLAS_DECODE_BBLOCK
        # promoted to a first-class parameter): explicit config/env override,
        # else a one-shot deterministic startup microbench over
        # BBLOCK_CANDIDATES per (batch, page_size, kv_dtype) — TPU-only (the
        # guard keeps CPU tests and the tier-1 gate free of it). Reported on
        # /healthz and as the tpu_serve_decode_bblock gauge.
        self.decode_bblock = self._resolve_decode_bblock()
        self.metrics.decode_bblock.set(self.decode_bblock)
        # Robustness layer (r7): stall watchdog + paged-admission pressure
        # relief. STALL_AFTER_S becomes an instance knob (the class default
        # stays as documentation/back-compat); _stall_abort is the watchdog's
        # signal to a chaos-observable stalled step; _admission_blocked_since
        # tracks how long the queue head has been page-starved while a slot
        # sat free (the preempt-under-pressure trigger).
        if serving.watchdog_stall_s > 0:
            self.STALL_AFTER_S = float(serving.watchdog_stall_s)
        self._stall_abort = False
        self._admission_blocked_since = 0.0
        # Graceful drain (r8): while draining, submit() sheds everything with
        # the structured "draining" reason (503 at the HTTP layer — the
        # router re-routes it like a connect failure); in-flight requests run
        # to completion until _drain_deadline, past which _reap_expired
        # cancels stragglers through the existing deadline path.
        self.draining = False
        self._drain_deadline = 0.0
        # One-deep asynchronous decode pipeline (perf_opt r9): the engine
        # enqueues decode N+1 before fetching N's tokens, so the host
        # emit/SSE/scheduling gap overlaps device compute.
        # _inflight: the dispatched-but-unfetched decode record (see
        # EnginePrograms._decode_dispatch); _pipe_carry: its device-resident
        # (last_token, lengths, carry_gen) end state, consumed by the next
        # dispatch when _carry_gen still matches; _carry_gen bumps on every
        # slot-lifecycle transition that rewrites state out of band of the
        # carry (activate/preempt/chunk start).
        self._inflight: Optional[dict] = None
        self._pipe_carry = None
        self._carry_gen = 0
        # Device operand-upload cache (seeds/ban/bias/penalties/table...):
        # re-uploaded only when the dirty flags say the host mirrors
        # changed, instead of per dispatch (EnginePrograms._decode_operands)
        self._op_cache: dict = {}
        self._op_dirty_sampling = True
        self._op_dirty_table = True
        # Guided allow-mask device caches (ISSUE 16): one-entry
        # (key, device array) pairs keyed on FSM fingerprints, so a mask
        # whose grammar state did not advance between dispatches (a guided
        # chunk walk, decode steps around a neighbor's admission) is
        # re-dispatched without a rebuild or re-upload
        # (EnginePrograms._allow_row / _allow_words).
        self._allow_dev = None
        self._allow_batch_dev = None
        # Bubble accounting: _last_ready marks a fetch completing with
        # nothing enqueued behind it (device going idle); the next dispatch
        # books the gap on decode_bubble_seconds. _busy_watermark is the
        # device-time high-water mark so overlapped dispatches never
        # double-count device_busy_seconds.
        self._last_ready = 0.0
        self._busy_watermark = 0.0
        # () -> the server's Tracer (or None), read at every dispatch close:
        # engine.dispatch spans go to whatever exporter it holds THEN
        # (build_state wires it; None = no spans, one attribute read)
        self.tracer_source = None
        install_compile_listeners()
        # Device telemetry (serving/devmon.py): hand the monitor the
        # analytical cost model and the host-metadata HBM samplers. Pure
        # wiring — recording happens at the programs.py busy sites, and the
        # samplers never touch the device (sizes/dtypes are host metadata).
        self._install_devmon()
        self._install_capacity()

    def _install_devmon(self):
        mon = _devmon.get()
        params_bytes = _tree_bytes(self.params)
        mon.install_cost_model(_devmon.CostModel.from_config(
            self.cfg, kv_dtype=self.serving.kv_dtype,
            weight_bytes=params_bytes))
        # the pool alone: the per-slot recurrent state beside it is held
        # whole whatever the pages do, and ledgered as its own component
        cache_bytes = _tree_bytes(self.cache) - self.kda_state_bytes

        def _live() -> dict:
            comp = {"params": float(params_bytes)}
            if self.kda_state_bytes:
                comp["kda_state"] = float(self.kda_state_bytes)
            sts = [a.stats() for a in self.allocators]
            total = sum(s["pages_total"] for s in sts) or 1
            live = sum(s["pages_live"] for s in sts)
            comp["kv_pages"] = cache_bytes * (live / total)
            # evictable pages hold reusable prefixes but yield to the
            # allocator on demand — ledger them as their own component
            # so "pool full" and "pool full of reclaimable prefixes"
            # read differently (ISSUE 20 satellite)
            evict = sum(s["pages_evictable"] for s in sts)
            comp["kv_pages_evictable"] = cache_bytes * (evict / total)
            carry = self._pipe_carry
            if carry is not None:
                comp["sampler_carry"] = float(
                    _tree_bytes((carry[0], carry[1])))
            if self._op_cache:
                comp["operand_cache"] = float(
                    _tree_bytes(tuple(self._op_cache.values())))
            return comp

        def _compiled() -> float:
            aot = self.aot
            return float(aot["hbm_total_bytes"]) if aot else 0.0

        mon.install_hbm(_live, _compiled)

    def _install_capacity(self):
        """Hand the capacity estimator (serving/capacity.py) its engine
        closures: live queue depth for the Little's-law delay, and the
        throughput gauge as the ceiling fallback while devmon's decode
        window is still empty. Pure wiring — offered-load recording
        happens at the submit()/shed edges."""
        _capacity.get().install_engine(
            lambda: self.sched.stats().queue_depth,
            lambda: self.metrics.tokens_per_second.value())

    @staticmethod
    def _build_mesh(serving: ServingConfig):
        """Build the serving mesh from config (None for single-device).

        ``dp`` shards slots and the pool's pages, ``tp`` shards heads
        (Megatron) and the pool's KV heads, ``ep`` shards an MoE model's
        experts. A mesh with ``sp`` > 1 is refused
        (EnginePrograms._init_params_and_cache).
        """
        mc = serving.mesh
        if mc.num_devices <= 1:
            return None
        from aws_k8s_ansible_provisioner_tpu.parallel.mesh import make_mesh

        return make_mesh(mc)

    @property
    def pending(self):
        """Back-compat view of the scheduler queue (len / truthiness)."""
        with self._lock:
            return list(self._queued.values())

    # -- submission ---------------------------------------------------------

    @property
    def prompt_limit(self) -> int:
        """Longest prompt a slot can hold.

        Whole-prompt prefill is bound by the largest bucket; chunked prefill
        (serving.prefill_chunk > 0) lifts that to the cache window itself —
        any prompt that physically fits the slot is servable in chunks.
        """
        if self.serving.prefill_chunk > 0:
            return self.max_len - 2
        return min(self.buckets[-1], self.max_len - 2)

    def _should_chunk(self, req: Request) -> bool:
        if self.serving.prefill_chunk <= 0:
            return False
        n = len(req.prompt_ids)
        # Chunk when the prompt exceeds the chunk size OR the largest bucket:
        # with chunking enabled, prompt_limit is lifted past the buckets, so a
        # prompt in (buckets[-1], prefill_chunk] must take the chunked path
        # too — the whole-prompt path cannot represent it (review r2 #2).
        return n > self.serving.prefill_chunk or n > self.buckets[-1]

    @property
    def _chunk_size(self) -> int:
        """Chunk program width: the configured chunk, else the largest bucket
        (the prefix-cache suffix path needs a chunk program even when plain
        chunked prefill is disabled)."""
        if self.serving.prefill_chunk > 0:
            return self.serving.prefill_chunk
        return self.buckets[-1]

    # -- paged-KV lifecycle -------------------------------------------------
    # Slots map to dp groups contiguously (slot // slots_per_group); each
    # group's allocator works in LOCAL page ids (0 = its scratch page) and
    # the device table stores GLOBAL ids = local + group * _group_pages.
    # Single-device (dp_groups == 1) degenerates to the original layout.

    def _group(self, slot: int) -> int:
        return slot // self._slots_per_group

    def _alloc(self, slot: int):
        """The allocator owning this slot's dp group's pool partition."""
        return self.allocators[self._group(slot)]

    def _gbase(self, slot: int) -> int:
        """Global page id of this slot's group's partition base."""
        return self._group(slot) * self._group_pages

    def _paged_admit(self, req: Request, slot: int, isolated: bool):
        """Assign pages to an admitted request: page-level prefix reuse
        (hash-chain lookup, refcounted sharing — no row copies) + fresh
        allocation for the tail. Returns (ids, reuse_off, resumed), or None
        if the allocator cannot cover the tail right now (the caller
        requeues; the admission gate makes this rare — it means evictable
        pages vanished between the gate and here).

        ``isolated`` is the dispatch-economics gate: a prefix hit forces
        the serialized chunk path, so under a burst the
        batched prefill wins — unless the request would chunk anyway, or the
        match spans >= prefix_reuse_min_pages whole pages, where skipping
        the shared-prefix compute (and refcount-sharing the pages instead
        of writing duplicates) beats the batch slot (ROUTER_BENCH round 5:
        the isolation-only gate left affinity-routed conversation load at a
        ~12% hit rate because bursts never consulted the index).
        """
        if self.host_tier is not None:
            # land spill copies issued on earlier steps (the
            # copy_to_host_async has normally completed by now), releasing
            # their staging HBM before this admission allocates
            self.host_tier.flush_to_host()
        ctx = self._resume_ctx.get(req.id)
        resumed = ctx is not None
        ids = list(ctx) if resumed else list(req.prompt_ids)
        ps = self.serving.page_size
        allocator = self._alloc(slot)
        matched: List[int] = []
        n = 0
        host_keys: List[tuple] = []
        if self.cfg.recurrent:
            # restoring K/V pages without the recurrent state that goes
            # with them would be wrong: no lookup, every admission (a
            # preemption's resume too) prefills from token 0
            self.metrics.prefix_lookups_skipped.inc(reason="recurrent_state")
        elif self.cfg.windowed:
            # a hit could restore the full layers' pages but not the window
            # layers', which went back as the context passed them
            self.metrics.prefix_lookups_skipped.inc(reason="window_pages")
        elif self.serving.prefix_cache and req.prompt_logprobs is None:
            req_lidx = (self.lora_names.index(req.lora) + 1
                        if req.lora is not None else 0)
            matched, n, host_keys = allocator.lookup_prefix(
                ids, salt=self._lora_salt(req_lidx))
            # the final token must run through prefill to produce the first
            # sampled token — cap reuse one token short of the prompt
            while host_keys and n + len(host_keys) * ps > len(ids) - 1:
                host_keys.pop()
            while n > len(ids) - 1:
                matched.pop()
                n -= ps
            # host-restorable pages count toward the burst-economics gate:
            # a restore replaces the same prefill compute a resident share
            # does, at PCIe cost instead of zero
            if not (isolated or resumed or self._should_chunk(req)
                    or n + len(host_keys) * ps
                    >= ps * max(1, self.serving.prefix_reuse_min_pages)):
                matched, n, host_keys = [], 0, []
        restore = self._host_entries(allocator, ids, n, host_keys)
        for pid in matched:
            allocator.retain(pid)
        need = -(-len(ids) // ps) - len(matched)
        fresh = allocator.alloc(need) if need > 0 else []
        if fresh is None:
            allocator.release_all(matched)
            return None
        # gather any content this alloc just reclaimed BEFORE the restore
        # scatter (or the upcoming prefill) can overwrite those pages —
        # enqueue order is what makes the spill read pre-reclaim bytes
        self._spill_reclaimed(slot)
        self._resume_ctx.pop(req.id, None)
        pages = matched + list(fresh)
        self._slot_pages[slot] = pages
        self._op_dirty_table = True
        self.table[slot, :] = self._scratch[slot]
        self.table[slot, :len(pages)] = \
            np.asarray(pages, np.int32) + self._gbase(slot)
        self._seq_counter += 1
        self._admit_seq[slot] = self._seq_counter
        off = n
        if restore:
            # the restored span begins at the first fresh page: pages[p] for
            # p in [len(matched), len(matched)+len(restore)) — exactly the
            # logical positions the host chain extends
            self._schedule_restore(slot, fresh[:len(restore)], restore)
            off = n + len(restore) * ps
        if off > 0:
            self.metrics.prefix_cache_hits.inc()
            self.metrics.prefix_tokens_reused.inc(off)
        self.metrics.prefix_tier_hits.inc(
            tier="host" if restore else ("hbm" if n > 0 else "miss"))
        self._pages_gauges()
        return ids, off, resumed

    def _host_entries(self, allocator, ids: List[int], n: int,
                      host_keys: List[tuple]) -> List[dict]:
        """Fetch + verify the host-tier payloads extending a resident match.

        Walks ``host_keys`` in chain order, verifying each entry's tokens and
        per-leaf shapes against the pool's layout. The first failure —
        corrupted/truncated payload (chaos ``kv_offload_error``) or an entry
        that raced away — truncates the restorable extension there: the
        suffix re-prefills, tokens are never wrong (drop, not corrupt).
        """
        tier = allocator.host_tier
        if tier is None or not host_keys:
            return []
        ch = _chaos.get()
        if ch.enabled:
            ch.on_kv_restore(tier, host_keys)
        ps = self.serving.page_size
        entries: List[dict] = []
        p0 = n // ps
        for i, key in enumerate(host_keys):
            toks = tuple(ids[(p0 + i) * ps:(p0 + i + 1) * ps])
            data = tier.fetch(key, toks, self._page_shapes)
            if data is None:
                self.metrics.kv_restore_dropped.inc()
                break
            entries.append(data)
        return entries

    def _schedule_restore(self, slot: int, pids: List[int],
                          entries: List[dict]):
        """Enqueue the batched host->HBM restore of spilled pages into the
        slot's freshly allocated pages. Async-only (tpulint R8): stacks the
        payloads per leaf, device-puts them and scatters in place (donated
        pool, same per-page layout as write_prompts_paged_layer). XLA data
        dependencies order the scatter ahead of every later program reading
        these pages — nothing blocks here and no pipeline drains; timing and
        byte accounting settle in _settle_restore at chunk start."""
        gbase = self._gbase(slot)
        data = {name: jnp.stack([e[name] for e in entries], axis=1)
                for name in entries[0]}
        tokens = len(entries) * self.serving.page_size
        drec = self._dispatch_open("_restore_scatter", "kv_restore",
                                   prompt_tokens=tokens)
        with _Dispatching(drec):
            self.cache = kvp.restore_pages(
                self.cache, [int(p) + gbase for p in pids], data)
        nbytes = len(entries) * self._page_bytes
        self._alloc(slot).host_tier.note_restored(len(entries), nbytes)
        self._restore_pending[slot] = {
            "pages": len(entries), "tokens": tokens, "bytes": nbytes,
            "drec": drec}

    def _settle_restore(self, slot: int):
        """Settle a scheduled restore before the slot's first suffix chunk.
        The block is sanctioned (R8) — the wait IS the PCIe DMA this feature
        trades for the prefix re-prefill FLOPs, and devmon's kv_restore cost
        term needs the real wall time."""
        pend = self._restore_pending.pop(slot, None)
        if pend is None:
            return
        with _phase(PH_FETCH):
            jax.block_until_ready(self.cache["k"])
        self._dispatch_close(pend["drec"], time.monotonic(),
                             tokens=pend["tokens"])
        self.metrics.kv_restore_bytes.inc(pend["bytes"])
        if self.host_tier is not None:
            self.host_tier.flush_to_host()

    def _spill_reclaimed(self, slot: int):
        """Drain the slot's allocator reclaim log into the host tier:
        one batched device-side gather per burst, per-page slices handed to
        the tier with their PCIe copy started. Async-only (tpulint R8) —
        runs right after the allocation that reclaimed the pages, on the
        admission/growth path, and never blocks; the numpy conversion
        happens later in HostTier.flush_to_host at a sanctioned point."""
        allocator = self._alloc(slot)
        tier = allocator.host_tier
        log = allocator.evicted_log
        if tier is None or not log:
            return
        allocator.evicted_log = []
        gbase = self._gbase(slot)
        data = kvp.gather_pages(self.cache,
                                [pid + gbase for pid, _, _ in log])
        for i, (_, key, toks) in enumerate(log):
            entry = {name: arr[:, i] for name, arr in data.items()}
            for a in entry.values():
                start = getattr(a, "copy_to_host_async", None)
                if start is not None:
                    start()
            tier.put(key, toks, entry, self._page_bytes)
        self.metrics.kv_spill_bytes.inc(len(log) * self._page_bytes)

    def _index_prompt_pages(self, slot: int, ids: List[int],
                            n_valid: Optional[int] = None):
        """Register the slot's FULL pages over ``ids`` in the allocator's
        hash-chain index so later prompts (and preemption resumes) can share
        them. Partial tail pages are never indexed — their rows past the
        content are scratch garbage. ``n_valid`` caps indexing to pages whose
        rows are all WRITTEN: at preemption the last generated token's K/V
        row is still pending the next dispatch, so indexing past
        len(ids) - 1 would publish a page with one garbage row to every
        future prefix hit (review r3)."""
        if not self.serving.prefix_cache or self.cfg.recurrent \
                or self.cfg.windowed:
            # no lookup side -> indexing would be pure overhead, and
            # unindexed pages go straight back to the free list at release
            return
        ps = self.serving.page_size
        allocator = self._alloc(slot)
        pages = self._slot_pages[slot]
        n_valid = len(ids) if n_valid is None else n_valid
        key = self._lora_salt(self.lora_idx[slot])
        for p in range(min(n_valid // ps, len(pages))):
            key = allocator.index_page(
                pages[p], key, tuple(ids[p * ps:(p + 1) * ps]))

    def _release_slot_pages(self, slot: int):
        """Return a slot's pages to the allocator (indexed ones go to the
        evictable LRU, still prefix-matchable) and point its table at the
        scratch page — idle slots' garbage decode writes must never land in
        pages another request now owns."""
        self._alloc(slot).release_all(self._slot_pages[slot])
        self._slot_pages[slot] = []
        if self.cfg.windowed:
            self.win_allocator.release_all(self._slot_wpages[slot])
            self._win_unreleased -= int(self._wfirst[slot]) \
                + len(self._slot_wpages[slot])
            self._slot_wpages[slot] = []
            self._wfirst[slot] = 0
            self.wtable[slot, :] = 0
        # a restore scheduled for a slot torn down before its chunk started
        # (deadline/cancel between admission and dispatch) must not settle
        # against a later tenant's chunk
        self._restore_pending.pop(slot, None)
        self._op_dirty_table = True
        self.table[slot, :] = self._scratch[slot]
        self.lengths[slot] = 0
        self._pages_gauges()

    def _pages_gauges(self):
        sts = [a.stats() for a in self.allocators]
        self.metrics.kv_pages_total.set(sum(s["pages_total"] for s in sts))
        self.metrics.kv_pages_in_use.set(sum(s["pages_live"] for s in sts))
        self.metrics.kv_pages_evictable.set(
            sum(s["pages_evictable"] for s in sts))
        if self.cfg.windowed:
            _metrics.window_pool.in_use.set(self.win_allocator.pages_in_use)

    def _win_cover(self, slot: int, n: int, upto: int) -> None:
        """A list with window layers beside full ones: make the pages the
        slot holds in the WINDOW layers' inventory the logical pages that a
        query at position ``n`` or later can read or that rows below
        ``upto`` land in — [(n + 1 - window) // page, ceil(upto / page)).
        What lies below goes back to the inventory (its table entry reads
        the scratch page: the kernels start a row's walk at its window and
        never fetch it; an XLA gather masks it), what is missing above is
        allocated. Called before every dispatch that writes the slot's
        rows, with the host mirror's length: a dispatch still in flight was
        enqueued with the table it needs, and the device runs dispatches in
        order, so a page given back here is rewritten only after its last
        reader. The inventory holds every slot's bound
        (_init_params_and_cache), so the allocation cannot fail."""
        ps, wp = self.serving.page_size, _metrics.window_pool
        lo = max(0, n + 1 - self.cfg.sliding_window) // ps
        hi = min(-(-upto // ps), self.pages_per_slot)
        pages, first = self._slot_wpages[slot], int(self._wfirst[slot])
        was = first + len(pages) if pages else 0
        drop = min(max(0, lo - first), len(pages))
        if drop:
            self.win_allocator.release_all(pages[:drop])
            del pages[:drop]
            self.wtable[slot, first:first + drop] = 0
            wp.released.inc(drop)
        first = max(first + drop, lo) if pages else lo
        need = hi - (first + len(pages))
        if need > 0:
            got = self.win_allocator.alloc(need)
            if got is None:
                raise RuntimeError(
                    f"the window layers' inventory ({self.win_pages} pages) "
                    f"cannot give slot {slot} {need} more pages beside its "
                    f"{len(pages)}: it is sized for every slot's bound")
            self.wtable[slot, first + len(pages):hi] = got
            pages.extend(got)
        self._wfirst[slot] = first
        if not (drop or need > 0):
            return
        self._op_dirty_table = True
        # what the slots would hold with nothing released: their contexts
        self._win_unreleased += (first + len(pages) if pages else 0) - was
        in_use = self.win_allocator.pages_in_use
        if in_use > wp.in_use_peak.value():
            wp.in_use_peak.set(in_use)
            wp.unreleased_at_peak.set(self._win_unreleased)
        if len(pages) > wp.slot_peak.value():
            wp.slot_peak.set(len(pages))

    def _ensure_pages(self, new_rows: int) -> bool:
        """Grow every active slot's page run to cover rows
        [0, min(len + new_rows, window)) before a decode/spec dispatch — the
        device cannot allocate, and surplus mid-horizon writes must land in
        pages the slot OWNS (never scratch aliased with another slot's
        table). When the pool runs dry, preempt the newest-admitted request
        (vLLM-style recompute: pages freed, request resubmitted at the queue
        front) until allocation succeeds. Returns whether any slot is still
        active."""
        ps = self.serving.page_size
        # oldest first: under pressure the newest admissions yield their
        # pages (and their slots) to the oldest — FCFS fairness
        order = sorted(self._active_slots(), key=lambda s: self._admit_seq[s])
        for slot in order:
            if self.slot_req[slot] is None:   # preempted below this round
                continue
            rows = min(int(self.lengths[slot]) + new_rows,
                       self.pages_per_slot * ps)
            if self.cfg.windowed:
                self._win_cover(slot, int(self.lengths[slot]), rows)
            pages = self._slot_pages[slot]
            while len(pages) < -(-rows // ps):
                need = -(-rows // ps) - len(pages)
                got = self._alloc(slot).alloc(need)
                if got is not None:
                    # spill whatever this alloc reclaimed before the decode
                    # dispatch can write the pages (async gather only —
                    # this is the hot path)
                    self._spill_reclaimed(slot)
                    self._op_dirty_table = True
                    self.table[slot, len(pages):len(pages) + need] = \
                        np.asarray(got, np.int32) + self._gbase(slot)
                    pages.extend(got)
                    break
                # newest admission IN THIS SLOT'S GROUP yields — pages are
                # group-local, so preempting another group frees nothing for
                # this slot. When the victim is this slot itself (youngest in
                # its group and still starving), it gets requeued rather than
                # taking pages from older requests.
                victim = max((s for s in self._active_slots()
                              if self._group(s) == self._group(slot)),
                             default=None,
                             key=lambda s: self._admit_seq[s])
                if victim is None:
                    break
                self._preempt(victim)
                if victim == slot:
                    break
        self._pages_gauges()
        return bool(self._active_slots())

    def _preempt(self, slot: int, front: bool = True):
        """Reclaim a running request's pages; it resumes later by
        re-prefilling prompt + generated-so-far (the full pages of that
        context stay in the evictable index, so the resume usually hash-hits
        everything but the tail). The vLLM scheduler's RECOMPUTE preemption,
        paged-TPU edition. ``front=False`` (admission pressure relief)
        requeues at the BACK so the starved queue head admits first."""
        req = self.slot_req[slot]
        ids = req.prompt_ids + req.generated
        # make the resume a prefix hit — but only over fully-WRITTEN pages
        # (the last generated token's row is pending the next dispatch)
        self._index_prompt_pages(slot, ids, n_valid=len(ids) - 1)
        if req.generated:
            self._resume_ctx[req.id] = ids
        # else: its first token is still on the device (the final chunk of
        # its walk is in flight, programs._advance_chunk_mixed) and is
        # discarded at that fetch; nothing was emitted, so it comes back as
        # the FRESH admission it still is — the prefill samples the first
        # token again under the same (seed, position) key
        self.slot_req[slot] = None
        # the preempted slot's host state diverges from any in-flight
        # dispatch's device carry, and its sampling rows are rewritten
        self._carry_gen += 1
        self._op_dirty_sampling = True
        self.temps[slot] = 0.0
        self.pres_pens[slot] = 0.0
        self.freq_pens[slot] = 0.0
        self.rep_pens[slot] = 1.0
        self.ban_until[slot] = 0
        self.bias_ids[slot, :] = 2**31 - 1
        self.bias_vals[slot, :] = 0.0
        self.lora_idx[slot] = 0
        self._bias_n[slot] = 0
        self._release_slot_pages(slot)
        self.sched.release(slot)
        remaining = max(1, req.max_tokens - len(req.generated))
        with self._lock:
            self._queued[req.id] = req
        if front:
            self.sched.submit_front(req.id, len(ids), remaining)
        else:
            # bound-exempt: already-admitted work must never shed on requeue
            self.sched.requeue(req.id, len(ids), remaining)
        self.metrics.preemptions.inc()
        _flight.record("preempt", req.id, slot=slot,
                       n_generated=len(req.generated), front=front)
        self.metrics.active_requests.set(len(self._active_slots()))
        self.metrics.queue_depth.set(self.sched.stats().queue_depth)

    def submit(self, req: Request) -> Request:
        req.t_submit = time.monotonic()
        # Graceful drain (r8): a draining engine admits NOTHING — shed with
        # the structured "draining" reason before any other validation.
        # Nothing was generated, so the caller (router) may always re-route.
        if self.draining:
            self.metrics.requests_shed.inc(reason="draining")
            _slo.get().observe_admission(shed=True)
            _capacity.get().observe_submit(tokens=max(1, req.max_tokens),
                                           shed=True)
            _flight.record("shed", req.id, reason="draining")
            _flight.finish(req.id, "shed", ok=False)
            raise EngineOverloaded(
                "draining", "engine is draining; not admitting new requests",
                retry_after_s=max(1.0, self._drain_deadline
                                  - time.monotonic()))
        # A prompt that doesn't fit is an ERROR, not a truncation: serving the
        # tail of a too-long prompt silently answers a different question
        # (the reference's vLLM rejects with 400 context_length_exceeded).
        # max_tokens, by contrast, is a *budget* and clamps to what's left.
        if len(req.prompt_ids) > self.prompt_limit:
            raise ContextLengthExceeded(len(req.prompt_ids), self.prompt_limit,
                                        self.max_len)
        if req.resume_ids:
            # Failover continuation: rides the preemption-resume machinery
            # (_paged_admit consults _resume_ctx).
            if len(req.prompt_ids) + len(req.resume_ids) > self.max_len - 2:
                raise ContextLengthExceeded(
                    len(req.prompt_ids) + len(req.resume_ids),
                    self.max_len - 2, self.max_len)
            if req.prompt_logprobs is not None:
                raise ValueError("continuation cannot carry prompt_logprobs "
                                 "(computed at first prefill only)")
        if req.min_tokens > 0:
            n_ban = len(self._ban_set(req))
            if n_ban > BAN_K:
                raise ValueError(
                    f"min_tokens suppression supports at most {BAN_K} stop "
                    f"tokens (eos set + stop_token_ids = {n_ban})")
        if len(req.logit_bias) > BIAS_K:
            raise ValueError(f"logit_bias supports at most {BIAS_K} entries "
                             f"(got {len(req.logit_bias)})")
        if req.repetition_penalty is not None and req.repetition_penalty <= 0:
            # The where(out>0, out/r, out*r) kernels flip logit signs for
            # r <= 0 — silently nonsensical sampling for a direct engine
            # user the HTTP layer's (0, 10] check never sees.
            raise ValueError(f"repetition_penalty must be > 0 "
                             f"(got {req.repetition_penalty})")
        if req.guided is not None:
            from aws_k8s_ansible_provisioner_tpu.serving.guided import (
                GuidedState, TokenGrammar)

            if isinstance(req.guided, TokenGrammar):
                req.guided = GuidedState(req.guided)
            elif not isinstance(req.guided, GuidedState):
                raise ValueError("guided must be a TokenGrammar or "
                                 "GuidedState (serving/guided.py)")
            if req.guided.grammar.vocab_size > self.cfg.vocab_size:
                raise ValueError(
                    f"guided grammar vocab ({req.guided.grammar.vocab_size}) "
                    f"exceeds model vocab ({self.cfg.vocab_size})")
            if req.min_tokens > 0 and req.guided.grammar.exact:
                # an exact-match grammar's final accepting state allows ONLY
                # eos; the min_tokens device ban would mask that too,
                # leaving an all--inf logits row (review r5)
                raise ValueError(
                    "min_tokens cannot combine with exact-match guided "
                    "decoding (guided_regex / guided_choice)")
        if req.prompt_logprobs is not None:
            if not (0 <= int(req.prompt_logprobs) <= LOGPROB_K):
                raise ValueError(f"prompt_logprobs must be in "
                                 f"[0, {LOGPROB_K}]")
            if self._should_chunk(req):
                raise ValueError(
                    "prompt_logprobs is not supported for prompts that "
                    "need chunked prefill (fits-in-bucket prompts only)")
        if req.lora is not None and req.lora not in self.lora_names:
            raise ValueError(f"unknown LoRA adapter {req.lora!r} "
                             f"(registered: {self.lora_names})")
        budget = self.max_len - len(req.prompt_ids) - 1
        if req.max_tokens > budget:
            req.max_tokens = max(1, budget)
        # OpenAI `seed`: the request's own seed wins; otherwise a derived
        # per-engine seed keeps unseeded sampling randomized across requests
        # while identical submission orders stay reproducible.
        req.eff_seed = (int(req.seed) & 0xffffffff) if req.seed is not None \
            else self._py_rng.getrandbits(32)
        # End-to-end deadline: the client's (capped by the server default)
        # or the server default alone; request_timeout_s <= 0 means no cap
        # and no default. Resolved to an ABSOLUTE monotonic time here so
        # queue wait counts against it — a deadline covers the request, not
        # just its decode.
        cap = float(self.serving.request_timeout_s or 0)
        d = req.deadline_s
        if d is not None and d <= 0:
            raise ValueError(f"deadline must be > 0 seconds (got {d})")
        if d is None:
            d = cap if cap > 0 else None
        elif cap > 0:
            d = min(float(d), cap)
        req.t_deadline = (req.t_submit + d) if d else 0.0
        # Admission control (r7): shed over-limit work with a structured
        # overload error BEFORE it queues — bounded queue depth first, then
        # the estimated-wait gate. Nothing was generated, so shedding is
        # always retry-safe for the caller.
        st = self.sched.stats()
        mw = float(self.serving.admission_max_wait_s or 0)
        if mw > 0:
            est = self._estimated_wait_s(st)
            if est > mw:
                self.metrics.requests_shed.inc(reason="est_wait")
                _slo.get().observe_admission(shed=True)
                _capacity.get().observe_submit(
                    tokens=max(1, req.max_tokens), shed=True)
                _flight.record("shed", req.id, reason="est_wait",
                               est_wait_s=round(est, 3))
                _flight.finish(req.id, "shed", ok=False)
                raise EngineOverloaded(
                    "est_wait",
                    f"estimated queue wait {est:.1f}s exceeds the "
                    f"admission limit {mw:.1f}s", retry_after_s=est - mw + 1)
        ctx_len = len(req.prompt_ids)
        if req.resume_ids:
            # Continuation admission: pre-populate ``generated`` with the
            # already-relayed tokens and register a preemption-style resume —
            # _paged_admit sees the ctx and the chunk walk re-prefills
            # prompt + resume as a cache rebuild (_activate(resumed=True)
            # discards the prefill draw; the next decode draw's seeded key
            # lands at the exact position the dead replica would have used).
            # All of this is installed BEFORE sched.submit publishes the id:
            # the engine thread may admit the instant it does.
            req.generated = list(req.resume_ids)
            if req.guided is not None:
                # the FSM must stand where the dead replica's stood: past
                # every already-emitted token
                for t in req.resume_ids:
                    req.guided.advance(int(t))
            ctx = list(req.prompt_ids) + list(req.resume_ids)
            ctx_len = len(ctx)
            # tpulint: disable=R5 per-key happens-before — submit() installs a key BEFORE sched.submit publishes the id, the step thread touches it only after; dict ops are GIL-atomic
            self._resume_ctx[req.id] = ctx
        with self._lock:
            self._queued[req.id] = req
            # paged admission gates on the FULL context a resume re-prefills
            ok = self.sched.submit(req.id, ctx_len,
                                   max(1, req.max_tokens
                                       - len(req.resume_ids)))
            if not ok:
                # bounded queue (scheduler-enforced so the native core and
                # Python fallback shed identically under racing submitters)
                del self._queued[req.id]
            self.metrics.queue_depth.set(self.sched.stats().queue_depth)
        if not ok:
            if req.resume_ids:
                self._resume_ctx.pop(req.id, None)
            self.metrics.requests_shed.inc(reason="queue_full")
            _slo.get().observe_admission(shed=True)
            _capacity.get().observe_submit(tokens=max(1, req.max_tokens),
                                           shed=True)
            _flight.record("shed", req.id, reason="queue_full",
                           queue_depth=st.queue_depth)
            _flight.finish(req.id, "shed", ok=False)
            raise EngineOverloaded(
                "queue_full",
                f"engine queue is full ({st.queue_depth} waiting, "
                f"limit {self.serving.max_queue_depth})",
                retry_after_s=self._estimated_wait_s(st) or 1.0)
        _slo.get().observe_admission(shed=False)
        _capacity.get().observe_submit(tokens=max(1, req.max_tokens),
                                       shed=False)
        _flight.record("queue", req.id, n_prompt=len(req.prompt_ids),
                       max_tokens=req.max_tokens)
        if req.resume_ids:
            _flight.record("failover_resume", req.id,
                           n_resume=len(req.resume_ids))
        self._work_event.set()
        return req

    def _estimated_wait_s(self, st) -> float:
        """Coarse queue-wait estimate: queued requests x recent average
        tokens per finished request / recent decode throughput. 0.0 when
        there is no throughput history yet (cold engines never shed on an
        estimate)."""
        tps = self.metrics.tokens_per_second.value()
        if tps <= 0 or st.queue_depth <= 0:
            return 0.0
        done = max(1, st.finished_total)
        avg_tokens = self.metrics.generated_tokens.total() / done
        return st.queue_depth * max(1.0, avg_tokens) / tps

    def generate(self, prompt_ids: List[int], **kw) -> Request:
        req = Request(prompt_ids=list(prompt_ids), **kw)
        return self.submit(req)

    def cancel(self, req: Request):
        """Mark a request cancelled; its slot frees on the next engine step."""
        req.cancelled = True
        self.sched.cancel(req.id)
        self._work_event.set()

    # -- graceful drain (r8) -------------------------------------------------

    def begin_drain(self, timeout_s: Optional[float] = None) -> float:
        """Stop admitting (submit sheds with reason "draining") and give
        in-flight requests until ``timeout_s`` (default
        serving.drain_timeout_s) to finish; past that, _reap_expired cancels
        stragglers through the existing deadline path — slot/pages released
        exactly once, streams finish "timeout". Idempotent: a second call
        while draining keeps the FIRST deadline (preStop + SIGTERM both
        trigger it). Returns seconds until the drain deadline."""
        now = time.monotonic()
        # begin_drain races preStop vs SIGTERM (two server threads): the
        # check-then-set below must be atomic or the second caller could
        # replace the first deadline.
        with self._lock:
            if self.draining:
                return max(0.0, self._drain_deadline - now)
            t = float(self.serving.drain_timeout_s
                      if timeout_s is None else timeout_s)
            t = max(0.0, t)
            self.draining = True
            self._drain_deadline = now + t
        self.metrics.draining.set(1)
        _flight.record("drain", None, state="begin", timeout_s=t)
        self._work_event.set()
        return t

    def end_drain(self):
        """Cancel a drain: admissions resume (operator abort / rollback)."""
        with self._lock:
            self.draining = False
            self._drain_deadline = 0.0
        self.metrics.draining.set(0)
        _flight.record("drain", None, state="end")
        self._work_event.set()

    def _effective_deadline(self, req: Request) -> float:
        """The request's own deadline tightened by the drain deadline
        (0.0 = none): drain stragglers expire through the SAME path as any
        deadline — one cancel site, exactly-once accounting."""
        d = req.t_deadline or 0.0
        if self.draining and self._drain_deadline:
            d = min(d or self._drain_deadline, self._drain_deadline)
        return d

    def _reap_expired(self):
        """Cancel every request whose end-to-end deadline has passed:
        running slots finish with "timeout" (slot + pages released through
        the one _finish path — exactly-once), the in-flight chunk walk is
        torn down, and queued requests are notified immediately instead of
        waiting to surface through admission. The drain deadline
        (begin_drain) tightens every deadline through the same path."""
        now = time.monotonic()
        for slot, r in enumerate(self.slot_req):
            if r is not None and 0 < self._effective_deadline(r) <= now:
                r.finish_reason = "timeout"
                self.metrics.deadline_expired.inc()
                _flight.record("deadline_reap", r.id, slot=slot,
                               phase="decode")
                self._finish(slot)
        st = self._chunk
        if st is not None \
                and 0 < self._effective_deadline(st["req"]) <= now:
            self._chunk = None
            req, slot = st["req"], st["slot"]
            self._release_slot_pages(slot)
            self.sched.release(slot)
            req.finish_reason = "timeout"
            self.metrics.deadline_expired.inc()
            self.metrics.mark_request("timeout", now - req.t_submit)
            _flight.record("deadline_reap", req.id, slot=slot,
                           phase="prefill_chunk")
            _flight.finish(req.id, "timeout", ok=False)
            self._close_stream(req)
        expired = []
        with self._lock:
            for rid, r in list(self._queued.items()):
                if 0 < self._effective_deadline(r) <= now:
                    expired.append(r)
                    del self._queued[rid]
        for r in expired:
            # the scheduler entry drains later as a "cancelled" pop; the
            # client is answered NOW with the real reason
            self.sched.cancel(r.id)
            self._resume_ctx.pop(r.id, None)
            r.finish_reason = "timeout"
            self.metrics.deadline_expired.inc()
            self.metrics.mark_request("timeout", now - r.t_submit)
            _flight.record("deadline_reap", r.id, phase="queued")
            _flight.finish(r.id, "timeout", ok=False)
            self._close_stream(r)
        if expired:
            self.metrics.queue_depth.set(self.sched.stats().queue_depth)

    def _relieve_admission_pressure(self) -> bool:
        """Paged admission wedged on page starvation (queue head can't be
        placed although a slot is free): after admission_preempt_after_s,
        preempt the LOWEST-progress running request — least recompute lost,
        requeued at the BACK so the starved head takes the freed pages —
        instead of letting admission hang on requests that may hold their
        pages for minutes. Returns whether a victim was preempted."""
        wait = float(self.serving.admission_preempt_after_s or 0)
        st = self.sched.stats()
        active = self._active_slots()
        if (wait <= 0 or st.queue_depth == 0
                or st.active_slots >= st.num_slots or not active):
            self._admission_blocked_since = 0.0
            return False
        now = time.monotonic()
        if not self._admission_blocked_since:
            self._admission_blocked_since = now
            return False
        if now - self._admission_blocked_since < wait:
            return False
        victim = min(active, key=lambda s: (len(self.slot_req[s].generated),
                                            -self._admit_seq[s]))
        self.metrics.admission_preemptions.inc()
        self._preempt(victim, front=False)
        self._admission_blocked_since = now
        return True

    def _admit_round(self):
        """One admission pass of a step: (batch of (req, slot) that prefill
        together, the admission that starts a chunk walk or None, whether
        the pass LEFT a request waiting with a slot free — a page-starved
        head: the decode horizon's question, _do_decode). The last is read
        BEFORE the pop that found nothing to admit: a caller that comes
        back after that pop (each scheduler call releases the GIL to it)
        was not there for this pass, and the next step's takes it."""
        # Admission decisions come from the runtime core (FCFS; skips
        # cancelled-in-queue requests, surfacing them for client notification).
        # Bucket-fitting prompts batch into one dispatch; a chunk-needing
        # prompt ends the batch and starts the chunked path.
        batch: List = []
        chunk_next = None
        waiting = False
        while len(batch) < max(1, self.serving.max_prefill_batch):
            # Admission is gated by the allocators' headroom (free +
            # evictable pages) — capacity scales with ACTUAL lengths, the
            # vLLM on-demand-block behavior (VERDICT r2 missing #2). With dp
            # groups the gate is the BEST group's headroom (the scheduler
            # picks the slot, not the group): when it hands a slot from a
            # fuller group, _paged_admit fails and the requeue below retries
            # — the freed slot rotates to the back of the free deque, so
            # retries walk onto other groups' slots.
            st = self.sched.stats()
            action = self.sched.pop_admission(
                max(a.free_pages for a in self.allocators))
            if action is None:
                waiting = (st.queue_depth > 0
                           and st.active_slots < st.num_slots)
                break
            if action[0] == "cancelled":
                with self._lock:
                    cand = self._queued.pop(action[1], None)
                self._resume_ctx.pop(action[1], None)
                self.metrics.queue_depth.set(self.sched.stats().queue_depth)
                if cand is not None:
                    cand.finish_reason = "cancelled"
                    _flight.record("cancel_reap", cand.id, phase="queued")
                    _flight.finish(cand.id, "cancelled", ok=False)
                    self._close_stream(cand)
                continue
            _, rid, slot = action
            with self._lock:
                req = self._queued.pop(rid, None)
            self.metrics.queue_depth.set(self.sched.stats().queue_depth)
            if req is None:  # should not happen; free the slot defensively
                self.sched.release(slot)
                continue
            if not req.t_prefill_start:
                req.t_prefill_start = time.monotonic()
            isolated = (not batch
                        and self.sched.stats().queue_depth == 0)
            prep = self._paged_admit(req, slot, isolated)
            if prep is None:
                # evictable pages vanished between the admission gate
                # and allocation (another admit this round took them):
                # requeue at the front and stop admitting this step
                self.sched.release(slot)
                with self._lock:
                    self._queued[rid] = req
                ids_q = self._resume_ctx.get(rid, req.prompt_ids)
                self.sched.submit_front(
                    rid, len(ids_q),
                    max(1, req.max_tokens - len(req.generated)))
                waiting = True
                break
            ids, off, resumed = prep
            # prefix reuse and resumes walk the chunk program from the
            # reuse offset; fresh bucket-sized prompts join the batch.
            # With a dispatch in flight on the ragged path, EVERY
            # admission takes the chunk walk: the mixed program prefills
            # it without draining the pipeline, where a batch prefill
            # would activate slots under the in-flight carry.
            if (self._inflight is not None and req.prompt_logprobs is not None
                    and not (off > 0 or resumed or self._should_chunk(req))):
                # the chunk walk returns no prompt logprobs: a request that
                # asks for them settles the pipeline and prefills whole (it
                # lost them, one run in two under load: the echo+logprobs
                # test's flake PR 33 recorded)
                self._drain_decode_pipeline("prefill")
            if (off > 0 or resumed or self._should_chunk(req)
                    or (self._ragged_on()
                        and self._inflight is not None)):
                chunk_next = (req, slot, ids, off, resumed)
                break
            batch.append((req, slot))
        return batch, chunk_next, waiting

    def step(self) -> bool:
        """One scheduling step. Priority: advance a chunked prefill (with one
        decode step interleaved between chunks), else admit waiting prompts
        (batched into one dispatch), else decode. Returns whether any work was
        done."""
        ch = _chaos.get()
        if ch.enabled:
            ch.on_engine_step(self)
        with _phase(PH_REAP):
            # reap cancelled slots first so disconnected clients free
            # capacity
            for slot, r in enumerate(self.slot_req):
                if r is not None and r.cancelled:
                    r.finish_reason = "cancelled"
                    _flight.record("cancel_reap", r.id, slot=slot)
                    self._finish(slot)
            # then expired deadlines — every blocking wait in the pipeline
            # keys off the same t_deadline, so enforcement here (between
            # dispatches) is what turns a deadline into released capacity
            self._reap_expired()
        # A long prompt mid-chunking: alternate chunk and decode dispatches so
        # in-flight streams keep progressing during the prefill (the whole
        # point of chunking — VERDICT r1 missing #4).
        if self._chunk is not None:
            if self._chunk.get("mixed"):
                # Ragged mixed walk: every chunk dispatch IS a decode
                # dispatch for the whole batch (one program serves both),
                # so the chunk/decode alternation — and the horizon-1
                # garbage-row caveat it exists for — doesn't apply.
                self._advance_chunk()
                return True
            if self._chunk_yield and self._active_slots():
                self._chunk_yield = False
                # horizon must be 1 while chunking: the decode program writes
                # a k/v row for EVERY slot at its current length — for the
                # chunking slot that row is garbage at offset `off`, which the
                # next chunk overwrites only if the write stays within the
                # next chunk's span.
                self._do_decode(max_horizon=1)
                return True
            self._advance_chunk()
            self._chunk_yield = True
            return True
        # Prefill/decode fairness floor (VERDICT r3 weak #5): prefill
        # priority means decode runs only when nothing can be admitted, so a
        # sustained admission stream can hold in-flight streams at a token
        # trickle indefinitely. After prefill_fairness consecutive prefill
        # dispatches with decode work pending, force ONE full-horizon decode
        # dispatch before admitting more.
        fair = max(0, self.serving.prefill_fairness)
        if (fair and self._prefill_streak >= fair and self._active_slots()
                and self.sched.stats().queue_depth > 0):
            self._prefill_streak = 0
            self._do_decode(fair_horizon=True)
            return True
        # Pipelined decode: settle the in-flight dispatch (its deferred
        # emits, possible finishes) BEFORE admission can reuse a freed slot
        # or start a chunk — slot reuse under unfetched tokens would
        # mis-route the deferred emits to the new request. With the ragged
        # mixed path on, admission under an in-flight dispatch is forced
        # onto the chunk walk (below), which keeps the carry valid — so the
        # pipeline stays open across admissions, the walk's final chunk
        # included (the whole point of the ragged program). The invariant:
        # a slot JOINS the batch only after every dispatch that listed its
        # previous occupant has been fetched (_advance_chunk_mixed joins
        # after fetching the predecessor of the walk's final mixed
        # dispatch, which lists the slot nowhere among its decode rows), so
        # deferred emits for a freed slot are discarded by the
        # slot_req-is-None guard in _decode_fetch, never mis-routed.
        if (self._inflight is not None
                and self.sched.stats().queue_depth > 0
                and not self._ragged_on()):
            self._drain_decode_pipeline("prefill")
        self._await_arrival()
        self._t_awaited = time.monotonic()
        with _phase(PH_ADMIT):
            batch, chunk_next, waiting = self._admit_round()
        if batch or chunk_next is not None:
            self._admission_blocked_since = 0.0
        else:
            # nothing admitted although work waits: if a slot is free, the
            # head is page-starved — degrade by policy, don't wedge
            with _phase(PH_ADMIT):
                relieved = self._relieve_admission_pressure()
            if relieved:
                # The preemption IS this step's work: when the victim was the
                # only active slot, falling through would return False with
                # the queue non-empty, and every caller that treats a False
                # step as quiescence (run_forever's idle sleep, test drivers)
                # would strand the requeued request. The freed pages let the
                # NEXT step admit the starved head.
                return True
        if batch:
            self._prefill_streak += 1
            try:
                if len(batch) == 1:
                    self._do_prefill(*batch[0])
                else:
                    self._do_prefill_batch(batch)
            except Exception:
                # Slots were assigned by the scheduler but slot_req[slot] is
                # only set on success — release them (and their pages) and
                # notify the clients here, or the capacity leaks and the
                # waiters hang (run_forever's _fail_all can't see either).
                for req, slot in batch:
                    self._release_slot_pages(slot)
                    self.sched.release(slot)
                    req.finish_reason = "error"
                    self.metrics.mark_request("error", 0.0)
                    _flight.finish(req.id, "error", ok=False,
                                   phase="prefill_batch")
                    self._close_stream(req)
                if chunk_next is not None:
                    req, slot = chunk_next[:2]
                    self._release_slot_pages(slot)
                    self.sched.release(slot)
                    req.finish_reason = "error"
                    self.metrics.mark_request("error", 0.0)
                    _flight.finish(req.id, "error", ok=False,
                                   phase="prefill_batch")
                    self._close_stream(req)
                raise
            if chunk_next is not None:  # chunking starts next step
                with _phase(PH_ADMIT):
                    self._start_chunk(*chunk_next)
                self._chunk_yield = False
            return True
        if chunk_next is not None:
            with _phase(PH_ADMIT):
                self._start_chunk(*chunk_next)
            self._advance_chunk()
            self._chunk_yield = True
            return True
        if self._active_slots():
            self._do_decode(prefill_possible=waiting)
            return True
        if self._inflight is not None:
            # cancel/deadline reaps emptied the batch with a dispatch still
            # in flight: settle it (all its emits discard) so nothing stays
            # enqueued on the device across idle or drain periods
            self._drain_decode_pipeline()
            return True
        return False

    def _await_arrival(self) -> None:
        """Bind the next dispatch late. With a dispatch running on the
        device the next one only has to be enqueued before that one ends,
        and whatever is enqueued now stands between a request that arrives
        a moment later and its admission for a whole dispatch more. So
        with a slot free and nobody waiting, give an arrival the first half
        (``AWAIT_SHARE``) of the running dispatch's expected time (its
        program's last seconds a substep, ``_dispatch_s``, times the
        substeps it runs) to show up — the engine thread would otherwise
        spend that time blocked in the fetch — and keep the other half for
        building and enqueueing what comes next (a short dispatch is sized
        so that it is enough: ``_note_host``; what is waited here is booked
        in ``_waited_s``, no work of the host's). Who gains: a caller that
        asks again right after its answer (when a token woke its handler
        the emit loop took tens of ms and such a caller was queued by the
        time the engine looked; now the engine looks within a few ms), and
        any arrival in the first half of a dispatch. The half is a choice,
        not a swept optimum (PERF.md section 7). A running mixed dispatch
        whose final chunk rides it (its record's ``first``) is waited on
        like any other: the callers its predecessor's fetch just answered
        come back in these milliseconds and are admitted BEHIND it (each a
        mixed step that also advances every decode row) and not behind
        another decode dispatch; the first token it carries goes out at its
        fetch, which the other half of its time still precedes (PERF.md
        section 6, PR 44, has the host's wait at those fetches)."""
        rec = self._inflight
        if rec is None or self.draining:
            return
        st = self.sched.stats()
        if st.queue_depth > 0 or st.active_slots >= st.num_slots:
            return
        drec = rec["drec"]
        per = self._dispatch_s.get(drec["program"])
        if per is None:
            return
        until = max(drec["t_enqueue"], self._busy_watermark) \
            + AWAIT_SHARE * per * drec["horizon"]
        self._work_event.clear()        # submit() and cancel() set it
        if self.sched.stats().queue_depth > 0:
            return                      # arrived between the two reads
        t0 = time.monotonic()
        if until - t0 > 0.001:
            with _phase(PH_IDLE):
                self._work_event.wait(until - t0)
            self._waited_s += time.monotonic() - t0

    def _emit(self, slot: int, token: int, lp=None):
        """Record one generated token for a slot; handle stop conditions."""
        req = self.slot_req[slot]
        if req.guided is not None:
            # advance the grammar FSM past the emitted token; the NEXT
            # dispatch's mask comes from the new state. A rejection (only
            # possible when the vocab can't spell any continuation) flips
            # the state to dead = eos/ws-only, forcing a clean finish.
            req.guided.advance(token)
        req.generated.append(token)
        if req.logprobs is not None:
            # pad with None if a path couldn't supply logprobs (spec decode
            # is gated off for logprob requests, so this stays aligned)
            req.logprob_data.append(lp)
        self.last_token[slot] = token
        self.metrics.generated_tokens.inc()
        if req.stream:
            if not req.pending:
                self._emit_streams.append(req)
            req.pending.append(token)

        hit_eos = ((token in self._eos_set and not req.ignore_eos)
                   or token in req.stop_token_ids) \
            and len(req.generated) > req.min_tokens
        out_of_budget = (len(req.generated) >= req.max_tokens
                         or self.lengths[slot] + 1 >= self.max_len)
        if hit_eos or out_of_budget:
            req.finish_reason = "stop" if hit_eos else "length"
            self._finish(slot)

    def _put_pending(self, req: Request) -> None:
        """Hand a stream what the emit phase recorded for it, as ONE queue
        item (one wake-up of its handler thread)."""
        if not req.pending:
            return
        item, req.pending = req.pending, []
        req.out_queue.put(item)
        self.metrics.stream_items.inc()

    def _flush_streams(self) -> None:
        """Leave an emit phase: every stream it recorded a token for gets
        its item now — no token waits for a later dispatch or step (a
        request _finish closed meanwhile has nothing left to put)."""
        for req in self._emit_streams:
            self._put_pending(req)
        self._emit_streams.clear()

    def _close_stream(self, req: Request) -> None:
        """The ONE way a request's queue ends: what is pending, then the
        None sentinel."""
        self._put_pending(req)
        req.out_queue.put(None)

    def _finish(self, slot: int):
        req = self.slot_req[slot]
        req.t_done = time.monotonic()
        status = ("success" if req.finish_reason in ("stop", "length")
                  else req.finish_reason or "success")
        self.metrics.mark_request(status, req.t_done - req.t_submit,
                                  trace_id=req.trace_id or None)
        # Terminal flight event: OK finishes free the timeline; anomalous
        # ones (timeout/error/cancelled) snapshot it for /debug/flight and
        # the spool (drop-on-overflow — never blocks this thread).
        _flight.finish(req.id, reason=req.finish_reason or "stop",
                       ok=(status == "success"), slot=slot,
                       n_generated=len(req.generated))
        # Index the GENERATED pages too, so a follow-up turn whose prompt
        # contains this response prefix-hits past the original prompt
        # (ADVICE r3: only _activate indexed pages, so the generated
        # region always re-prefilled). Same pending-row cap as
        # preemption: the last emitted token's K/V row is written by the
        # NEXT dispatch, which never came — cap at len(ids) - 1.
        ids = req.prompt_ids + req.generated
        self._index_prompt_pages(slot, ids, n_valid=len(ids) - 1)
        self.slot_req[slot] = None
        # The pages are RELEASED below — indexed ones stay prefix-matchable
        # in the evictable LRU — and the zeroed table points idle slots'
        # garbage decode writes at the scratch page, so the length resets
        # to 0.
        # NOTE: a finish does NOT bump _carry_gen — an in-flight pipelined
        # dispatch keeps decoding the freed slot as discardable garbage
        # (scratch-table writes, emits skipped); only a REUSE (_activate)
        # invalidates the device carry. The cleared sampling rows do dirty
        # the operand cache for the next upload.
        self._op_dirty_sampling = True
        self.temps[slot] = 0.0
        self.pres_pens[slot] = 0.0
        self.freq_pens[slot] = 0.0
        self.rep_pens[slot] = 1.0
        self.ban_until[slot] = 0
        self.bias_ids[slot, :] = 2**31 - 1
        self.bias_vals[slot, :] = 0.0
        self._bias_n[slot] = 0
        self.lora_idx[slot] = 0
        self._release_slot_pages(slot)
        self.sched.release(slot)
        self.metrics.active_requests.set(len(self._active_slots()))
        self._close_stream(req)

    # -- loop ---------------------------------------------------------------

    def run_forever(self, stop: threading.Event):
        """Engine thread body: step until stopped, sleeping when idle.

        A step failure (XLA error, OOM) must not silently kill the loop: every
        in-flight and queued request is failed loudly (clients get their
        sentinel instead of hanging), the error is recorded for /health, and
        the loop keeps serving subsequent requests.
        """
        import logging

        log = logging.getLogger(__name__)
        wd = threading.Thread(target=self._watchdog_loop, args=(stop,),
                              daemon=True, name="engine-watchdog")
        wd.start()
        while not stop.is_set():
            self.last_step_start = time.monotonic()
            try:
                # host work no narrower phase claims (routing, building the
                # next dispatch's operands) reads as engine.operands
                with _phase(PH_OPERANDS):
                    did_work = self.step()
            # tpulint: disable=R3 fail-loud catch-all — _fail_all fails every in-flight request with its sentinel, /health records the error, loop keeps serving
            except Exception as e:
                log.exception("engine step failed; failing in-flight requests")
                self.last_error = f"{type(e).__name__}: {e}"
                self._fail_all(self.last_error)
                did_work = False
            self.last_step_start = 0.0
            with self._lock:
                self._stall_abort = False   # the aborted step has unwound
            if not did_work:
                with _phase(PH_IDLE):
                    self._work_event.wait(timeout=0.05)
                self._work_event.clear()

    def _watchdog_loop(self, stop: threading.Event):
        """Stall watchdog (r7): when a step executes past STALL_AFTER_S,
        arm the abort flag a host-observable stall (chaos-injected or any
        cooperative wait) checks — the step raises, run_forever fails the
        AFFECTED requests, and the process keeps serving. A truly wedged
        device call never sees the flag; for that class /healthz stays 503
        "stalled" until the K8s liveness restart (the pre-r7 behavior)."""
        while not stop.is_set():
            if self.stalled_for_s > 0:
                with self._lock:
                    armed = not self._stall_abort
                    self._stall_abort = True
                if armed:
                    self.metrics.watchdog_stalls.inc()
                    _flight.record("watchdog_stall", None,
                                   stalled_for_s=round(self.stalled_for_s, 3))
            stop.wait(min(1.0, max(0.05, self.STALL_AFTER_S / 4)))

    last_error: str = ""
    # monotonic timestamp of the step currently executing (0.0 = idle):
    # /health derives a "stalled" status from it — a wedged device dispatch
    # (hung runtime, driver fault) hangs INSIDE step() and would otherwise
    # look healthy forever, since run_forever never returns to record an
    # error (failure-detection beyond the reference's set -e, SURVEY.md §5).
    last_step_start: float = 0.0
    STALL_AFTER_S: float = 120.0

    @property
    def stalled_for_s(self) -> float:
        """Seconds the current step has been executing past the stall
        threshold (0.0 = healthy/idle)."""
        t0 = self.last_step_start
        if not t0:
            return 0.0
        dt = time.monotonic() - t0
        return dt if dt >= self.STALL_AFTER_S else 0.0

    def _fail_all(self, reason: str):
        _flight.record("fail_all", None, reason=reason)
        # Discard the in-flight pipelined decode outright: its requests are
        # failed below through the normal slot teardown (exactly-once page/
        # slot release via _finish), and fetching a dispatch that may BE the
        # failure (pipeline_fetch_error, transfer fault) would re-raise.
        if self._inflight is not None:
            _metrics.pipeline.drains.inc(reason="fail")
        self._inflight = None
        self._pipe_carry = None
        self.metrics.pipeline_depth.set(0.0)
        if self._chunk is not None:  # fail the half-prefilled request too
            st, self._chunk = self._chunk, None
            self._release_slot_pages(st["slot"])
            self.sched.release(st["slot"])
            st["req"].finish_reason = "error"
            self.metrics.mark_request("error", 0.0)
            _flight.finish(st["req"].id, "error", ok=False, detail=reason)
            self._close_stream(st["req"])
        self._resume_ctx.clear()   # queued resumes are failed below
        for slot, r in enumerate(self.slot_req):
            if r is not None:
                r.finish_reason = "error"
                self._finish(slot)
        with self._lock:
            queued, self._queued = self._queued, {}
        for r in queued.values():
            self.sched.cancel(r.id)
            r.finish_reason = "error"
            self.metrics.mark_request("error", 0.0)
            _flight.finish(r.id, "error", ok=False, detail=reason)
            self._close_stream(r)
        # Drain the scheduler's cancelled-in-queue notifications so its queue
        # empties (the Request objects were already notified above). A request
        # submitted AFTER the failure may interleave here and surface as an
        # admission: it is healthy work, not part of the failure — requeue it
        # for the next step and stop draining (everything behind it is new).
        while True:
            action = self.sched.pop_admission()
            if action is None:
                break
            if action[0] == "admit":
                _, rid, slot = action
                self.sched.release(slot)
                with self._lock:
                    r = self._queued.get(rid)
                if r is not None:
                    self.sched.submit(rid, len(r.prompt_ids), r.max_tokens)
                break
        self.metrics.queue_depth.set(self.sched.stats().queue_depth)
