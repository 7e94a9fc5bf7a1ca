"""Token sampling: greedy / temperature / top-k / top-p, fully jittable.

Equivalent of the sampling parameters the reference's OpenAI API accepts and
forwards to vLLM (``llm-d-test.yaml:61-78`` exercises the endpoint with
``max_tokens``; vLLM handles temperature/top_p/top_k). TPU-first details:

- Per-request parameters are vectors ``[B]`` so one compiled program serves any
  mix of greedy and sampled requests in a continuous batch (no re-jit).
- top-k/top-p run on a static ``MAX_TOPK`` candidate set from ``lax.top_k``
  (sorting the full 152k vocab per step would dominate decode time on the VPU);
  requests wanting a larger k degrade to MAX_TOPK, which is standard practice.
- temperature == 0 selects greedy. The candidate path (the top-k sort, the
  nucleus, the draws) sits inside ONE ``lax.cond`` on "does any row draw": a
  scalar read from the ``temperature`` operand on the device, so an
  all-greedy batch pays the argmax alone and there is still one program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MAX_TOPK = 64


def per_slot_keys(seeds: jnp.ndarray, ctrs: jnp.ndarray) -> jax.Array:
    """[B] typed PRNG keys from per-slot (seed, position) pairs.

    ``fold_in(key(seed_b), ctr_b)`` makes each draw a pure function of the
    request's seed and its token position — NOT of batch composition, rng
    chain history, or scheduling order. That is what the OpenAI ``seed``
    parameter requires (same seed + same prompt => same sampled stream, even
    across restarts and preemption resumes) and what a per-batch key can
    never give. seeds: [B] uint32; ctrs: [B] int32.
    """
    return jax.vmap(lambda s, c: jax.random.fold_in(jax.random.key(s), c))(
        seeds, ctrs)


def apply_penalties(logits: jnp.ndarray, counts: jnp.ndarray,
                    presence: jnp.ndarray,
                    frequency: jnp.ndarray,
                    repetition: jnp.ndarray = None,
                    prompt_mask: jnp.ndarray = None) -> jnp.ndarray:
    """OpenAI presence/frequency penalties + vLLM/HF ``repetition_penalty``.

    logits: [B, V]; counts: [B, V] int (occurrences of each token in the
    slot's generated text so far); presence/frequency: [B]. Subtractive on
    raw logits before any sampling — the vLLM semantics (greedy decode is
    affected too). Zero penalties are exact no-ops.

    ``repetition`` [B] (1.0 = off) is MULTIPLICATIVE over every token seen
    in the PROMPT (``prompt_mask`` [B, V] bool) or generated so far — HF
    ``RepetitionPenaltyLogitsProcessor`` semantics: positive logits divide
    by the penalty, non-positive multiply. Applied before the subtractive
    penalties, matching vLLM's sampler order.
    """
    c = counts.astype(jnp.float32)
    out = logits.astype(jnp.float32)
    if repetition is not None:
        seen = c > 0
        if prompt_mask is not None:
            seen = seen | prompt_mask
        r = repetition[:, None].astype(jnp.float32)
        penalized = jnp.where(out > 0, out / r, out * r)
        out = jnp.where(seen, penalized, out)
    return (out
            - frequency[:, None] * c
            - presence[:, None] * (c > 0))


def apply_allow(logits: jnp.ndarray, allow: jnp.ndarray) -> jnp.ndarray:
    """Grammar allow-mask: keep only tokens whose bit is set per row.

    logits: [B, V]; allow: [B, ceil(V/32)] uint32 bitset (bit t of word
    t >> 5 = token t allowed). A row of all-ones words is an exact no-op, so
    unguided slots ride the same compiled program as guided ones — the mask
    is a per-row OPERAND, not a program variant. Applied after bias/ban and
    before sampling; masked logits go to -inf, which the token-id-keyed
    Gumbel in :func:`sample` tolerates without perturbing other tokens'
    draws (the byte-identity contract for guided streams).
    """
    V = logits.shape[-1]
    idx = jnp.arange(V, dtype=jnp.int32)
    bits = (allow[:, idx >> 5] >> (idx & 31).astype(jnp.uint32)) \
        & jnp.uint32(1)
    return jnp.where(bits.astype(bool), logits, -jnp.inf)


def sample(
    logits: jnp.ndarray,       # [B, V] float
    rng: jax.Array,            # one key for the batch, OR [B] per-slot keys
    temperature: jnp.ndarray,  # [B] float; 0 => greedy
    top_k: jnp.ndarray,        # [B] int; 0 => disabled (use all MAX_TOPK)
    top_p: jnp.ndarray,        # [B] float; 1.0 => disabled
) -> jnp.ndarray:
    """Return sampled token ids [B] (int32).

    ``rng`` may be a single key (legacy batch draw) or a [B] vector of typed
    keys from :func:`per_slot_keys` — the engine's seeded path, where each
    slot's draw is independent of the others' presence.
    """
    V = logits.shape[-1]
    # the argmax reads the float32 cast inside its own reduction; the
    # candidates cast again in their branch, so a greedy batch never writes
    # a float32 copy of the logits just to hand it to a branch not taken
    greedy = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    cap = min(MAX_TOPK, V)  # tiny test vocabularies can be smaller than the cap
    per_slot = jnp.ndim(rng) == 1 and jax.dtypes.issubdtype(
        rng.dtype, jax.dtypes.prng_key)

    def candidates():
        vals, idxs = jax.lax.top_k(logits.astype(jnp.float32), cap)  # desc
        k_ranks = jnp.arange(cap)[None, :]
        eff_k = jnp.where(top_k <= 0, cap, jnp.minimum(top_k, cap))
        vals = jnp.where(k_ranks < eff_k[:, None], vals, -jnp.inf)

        # top-p (nucleus) over the candidate set: keep the smallest prefix
        # whose probability mass reaches top_p; always keep the best candidate.
        safe_t = jnp.maximum(temperature, 1e-6)[:, None]
        probs = jax.nn.softmax(vals / safe_t, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_p[:, None]                # prefix mass before me
        keep = keep.at[:, 0].set(True)
        vals = jnp.where(keep, vals, -jnp.inf)

        scaled = vals / safe_t
        if per_slot:
            # Seeded path: TOKEN-ID-KEYED Gumbel-max over the candidate set.
            # The noise for token t is a pure function of (slot key, t), so
            # masking one token (min_tokens stop suppression, logit_bias -100,
            # grammar bans) never perturbs any other token's draw — a banned
            # stream diverges from its unbanned twin only at positions where
            # the banned token would have WON. jax.random.categorical's
            # slot-positional gumbel lacks this: one masked token shifts every
            # later candidate into a different slot and reshuffles the whole
            # draw (the engine-level min_tokens determinism contract in
            # test_engine). Cost: MAX_TOPK fold_in+uniform per slot.
            def slot_draw(key, row_scaled, row_ids):
                u = jax.vmap(lambda t: jax.random.uniform(
                    jax.random.fold_in(key, t), minval=1e-20))(row_ids)
                return jnp.argmax(row_scaled - jnp.log(-jnp.log(u)))

            draw = jax.vmap(slot_draw)(rng, scaled, idxs)       # per-slot
        else:
            draw = jax.random.categorical(rng, scaled, axis=-1)  # [B] in [0,K)
        sampled = jnp.take_along_axis(
            idxs, draw[:, None], axis=1)[:, 0].astype(jnp.int32)
        return jnp.where(temperature <= 0.0, greedy, sampled)

    # The sort over the vocabulary, the nucleus and the draws run only when a
    # row asks for a draw. Every slot's temperature is read, live or not: the
    # engine zeroes a slot's row where it gives the slot back (test_engine
    # pins that), so an idle slot never holds the gate open.
    return jax.lax.cond(jnp.any(temperature > 0.0), candidates,
                        lambda: greedy)
