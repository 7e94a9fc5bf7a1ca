"""Draft-model speculative decoding: a small LM proposes, the target verifies.

The vLLM draft-worker equivalent (SURVEY.md §2.2 row 1; VERDICT r4 next #7):
prompt-lookup speculation (engine.py `_propose_drafts`) only fires on
repetitive continuations, while a draft model proposes on EVERY step — the
standard small-model/large-model pairing (e.g. Qwen3-0.6B drafting for
Qwen3-8B). TPU-first economics: decode is HBM-bandwidth-bound, so a draft at
~1/10 the target's bytes adds ~10% bandwidth per round while the multi-query
verify answers all K drafts from ONE target cache stream — accepted drafts
are nearly free tokens.

No new jitted programs: the draft REUSES the engine's compiled step family —
``decode_steps`` (greedy, horizon=spec_k) for the autoregressive rollout and
``spec_decode_step`` (R=spec_k+1, argmax side only) for multi-token
catch-up after plain-path dispatches advanced the target past the draft.

Cache-coherence design (the part draft speculation usually gets wrong):

- ``lens[slot]`` counts rows of the draft cache holding TRUE context K/V —
  the next write position. Steady state is ``engine.lengths - lens == 1``
  (the newest emitted token's K/V rides the next draft dispatch, exactly
  like the target's own cache).
- A proposal dispatch feeds the newest emitted token (``engine.last_token``)
  at position ``lens`` and greedily rolls K tokens, writing K rows. The
  accepted prefix of those rows is ALREADY-correct context (greedy draft
  rows are the drafts' own K/V), so after the verify emits m drafts + 1
  correction the sync is just ``lens += emitted`` — no rollback copies.
- Rejected-draft rows and catch-up padding rows are garbage BEYOND ``lens``;
  every position is rewritten when its true token is processed before any
  query can attend it (the engine's standard surplus-write invariant,
  engine.py `decode_steps` docstring).
- Slots the draft cannot cheaply track (chunked prefills, preemption
  resumes) turn ``stale`` and simply stop proposing — per-slot degradation,
  never engine-wide (VERDICT r3 weak #4 precedent).

The engine caps plain-path horizons at spec_k + 1 while a draft is attached
so the catch-up gap always fits one R-wide dispatch.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp


class DraftModel:
    """Holds the draft network + its per-slot KV cache and sync state."""

    def __init__(self, cfg, params, num_slots: int, max_len: int, dtype,
                 page_size: int):
        self.cfg = cfg
        self.params = params
        # A pool of its own under a STATIC IDENTITY block table: slot i owns
        # pages [i * MP, (i + 1) * MP). No allocator, no sharing — the draft
        # is small, so every slot simply keeps a full window of pages.
        mp = -(-max_len // page_size)
        self.cache = kvp.init_pool(cfg, num_slots * mp, page_size, dtype)
        self.table = np.arange(num_slots * mp, dtype=np.int32).reshape(
            num_slots, mp)
        self.num_slots = num_slots
        self.max_len = max_len
        # rows of TRUE context K/V per slot (== next write position)
        self.lens = np.zeros(num_slots, np.int32)
        # chunked/resumed slots: cache can't be cheaply rebuilt -> no drafts
        self.stale = np.zeros(num_slots, bool)

    # -- admission sync ------------------------------------------------------

    def prefill(self, engine, tokens: np.ndarray, true_lens: np.ndarray,
                slots: np.ndarray) -> None:
        """Mirror a (batched) target prefill into the draft cache.

        Reuses the engine's already-built padded token arrays, so the draft
        costs ONE extra dispatch per admission batch. The sampled tokens are
        discarded — only the K/V writes matter."""
        from aws_k8s_ansible_provisioner_tpu.serving.engine import (
            prefill_batch_step)

        n = tokens.shape[0]
        # padding rows (slot == num_slots) carry all-OOB_PAGE tables: their
        # writes drop
        slots = np.asarray(slots)
        real = slots < self.num_slots
        tables = np.where(real[:, None],
                          self.table[np.where(real, slots, 0)],
                          kvp.OOB_PAGE).astype(np.int32)
        out = prefill_batch_step(
            self.cfg, self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(true_lens), engine._next_rng(),
            jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.int32),
            jnp.ones(n, jnp.float32), tables=jnp.asarray(tables))
        self.cache = out[0]
        for i in range(n):
            s = int(slots[i])
            if s < self.num_slots:
                self.lens[s] = int(true_lens[i])
                self.stale[s] = False

    def mark_stale(self, slot: int) -> None:
        self.stale[slot] = True

    # -- per-round proposal --------------------------------------------------

    def propose(self, engine, eligible: List[int],
                K: int) -> Optional[Tuple[np.ndarray, dict]]:
        """Return (drafts [num_slots, K], {slot: K}) or None.

        1. catch-up: slots whose gap to the target exceeds 1 (a plain-path
           dispatch advanced them) teacher-force the missed tokens through
           the draft via one R-wide argmax dispatch; they propose NEXT round.
        2. rollout: one fused greedy ``decode_steps`` over the whole slot
           axis proposes K tokens for every up-to-date slot.

        Carry-generation handoff contract (ISSUE 16): the engine reaches a
        spec round by SETTLING any in-flight pipelined dispatch (fetch +
        emit, no drain) rather than draining it, so by the time propose()
        reads the host mirrors (``engine.lengths``, ``engine.last_token``)
        they are exact — lazily synced, never stale. The assert makes a
        violated handoff fail loudly at the proposal site instead of as a
        silent off-by-one in the draft cache.
        """
        from aws_k8s_ansible_provisioner_tpu.serving.engine import (
            decode_steps, spec_decode_step)

        assert getattr(engine, "_inflight", None) is None, (
            "draft.propose() with a dispatch still in flight — the engine "
            "must settle the pipeline before a spec round (host mirrors "
            "would be stale)")

        R = K + 1
        gaps = {s: int(engine.lengths[s]) - int(self.lens[s])
                for s in eligible if not self.stale[s]}
        behind = [s for s, g in gaps.items() if 1 < g <= self.max_len]
        if behind:
            self._catch_up(engine, behind, R)
            gaps = {s: int(engine.lengths[s]) - int(self.lens[s])
                    for s in gaps}
        ready = [s for s, g in gaps.items()
                 if g == 1 and int(self.lens[s]) + K < self.max_len]
        if not ready:
            return None
        self.cache, _, out, _, _, _ = decode_steps(
            self.cfg, K, self.params, self.cache,
            jnp.asarray(engine.last_token), jnp.asarray(self.lens),
            engine._next_rng(),
            jnp.zeros(self.num_slots, jnp.float32),       # greedy rollout
            jnp.zeros(self.num_slots, jnp.int32),
            jnp.ones(self.num_slots, jnp.float32), table=jnp.asarray(self.table))
        out = np.asarray(out)                              # [K, B]
        drafts = np.zeros((self.num_slots, K), np.int32)
        proposed = {}
        for s in ready:
            drafts[s] = out[:, s]
            proposed[s] = K
        # non-ready rows wrote garbage K/V at THEIR lens..lens+K-1: future
        # positions, rewritten before any query attends them (surplus-write
        # invariant) — their lens stays put, so nothing is lost.
        return drafts, proposed

    def _catch_up(self, engine, slots: List[int], R: int) -> None:
        """Teacher-force up to R tokens of target-emitted context the draft
        missed. Uses the draft-model spec program purely for its multi-row
        K/V writes (argmax output discarded)."""
        from aws_k8s_ansible_provisioner_tpu.serving.engine import (
            spec_decode_step)

        tokens = np.zeros((self.num_slots, R), np.int32)
        adv = np.zeros(self.num_slots, np.int32)
        for s in slots:
            req = engine.slot_req[s]
            if req is None:
                continue
            ctx = req.prompt_ids + req.generated
            lo = int(self.lens[s])
            # leave the newest token for the proposal dispatch (gap -> 1)
            cu = ctx[lo:int(engine.lengths[s]) - 1][:R]
            if not cu:
                continue
            tokens[s, :len(cu)] = cu
            tokens[s, len(cu):] = cu[-1]                  # pad: surplus rows
            adv[s] = len(cu)
        if not adv.any():
            return
        out = spec_decode_step(
            self.cfg, R, self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(self.lens), engine._next_rng(),
            jnp.zeros(self.num_slots, jnp.float32),
            jnp.zeros(self.num_slots, jnp.int32),
            jnp.ones(self.num_slots, jnp.float32), table=jnp.asarray(self.table))
        self.cache = out[0]
        self.lens += adv

    # -- post-verify sync ----------------------------------------------------

    def note_emitted(self, slot: int, n: int) -> None:
        """After a verify emitted ``n`` tokens for a drafted slot: the first
        n of this round's rollout rows (newest token + accepted drafts) are
        now true context."""
        self.lens[slot] = min(self.lens[slot] + n, self.max_len)
