"""Sampling op unit tests (greedy/temperature/top-k/top-p semantics)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aws_k8s_ansible_provisioner_tpu.ops.sampling import (MAX_TOPK,
                                                          per_slot_keys,
                                                          sample)


def _logits(rows):
    return jnp.asarray(np.array(rows, np.float32))


def test_greedy_at_zero_temperature():
    logits = _logits([[0.1, 5.0, 0.2, 0.3], [9.0, 1.0, 2.0, 3.0]])
    out = sample(logits, jax.random.PRNGKey(0),
                 jnp.zeros(2), jnp.zeros(2, jnp.int32), jnp.ones(2))
    assert out.tolist() == [1, 0]


def test_top_k_one_is_greedy_even_with_temperature():
    logits = _logits([[0.1, 5.0, 0.2, 0.3]])
    for seed in range(5):
        out = sample(logits, jax.random.PRNGKey(seed),
                     jnp.asarray([2.0]), jnp.asarray([1], jnp.int32),
                     jnp.ones(1))
        assert out.tolist() == [1]


def test_top_p_excludes_tail():
    # One dominant token (prob ~1 under softmax): nucleus p=0.5 keeps only it.
    logits = _logits([[20.0, 0.0, 0.0, 0.0]])
    for seed in range(10):
        out = sample(logits, jax.random.PRNGKey(seed),
                     jnp.asarray([1.0]), jnp.zeros(1, jnp.int32),
                     jnp.asarray([0.5]))
        assert out.tolist() == [0]


def test_sampled_tokens_respect_top_k_support():
    rng = np.random.default_rng(0)
    logits = _logits(rng.normal(size=(4, 100)))
    top3 = np.argsort(-np.asarray(logits), axis=-1)[:, :3]
    for seed in range(10):
        out = np.asarray(sample(logits, jax.random.PRNGKey(seed),
                                jnp.full(4, 1.5), jnp.full(4, 3, jnp.int32),
                                jnp.ones(4)))
        for b in range(4):
            assert out[b] in top3[b]


def test_mixed_batch_greedy_and_sampled():
    logits = _logits([[0.0, 10.0, 0.0], [3.0, 3.0, 3.0]])
    out = sample(logits, jax.random.PRNGKey(1),
                 jnp.asarray([0.0, 1.0]), jnp.zeros(2, jnp.int32),
                 jnp.ones(2))
    assert int(out[0]) == 1
    assert 0 <= int(out[1]) < 3


def test_large_vocab_uses_candidate_cap():
    rng = np.random.default_rng(1)
    logits = _logits(rng.normal(size=(1, 152064)))
    out = sample(logits, jax.random.PRNGKey(2), jnp.asarray([1.0]),
                 jnp.zeros(1, jnp.int32), jnp.asarray([0.99]))
    topk = set(np.argsort(-np.asarray(logits)[0])[:MAX_TOPK].tolist())
    assert int(out[0]) in topk


def _ungated(logits, rng, temperature, top_k, top_p):
    """``sample`` as it was before its candidates sat behind a conditional:
    every row's top-64, nucleus and draw, and a ``where`` over the argmax."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    cap = min(MAX_TOPK, logits.shape[-1])
    vals, idxs = jax.lax.top_k(logits, cap)
    eff_k = jnp.where(top_k <= 0, cap, jnp.minimum(top_k, cap))
    vals = jnp.where(jnp.arange(cap)[None, :] < eff_k[:, None], vals,
                     -jnp.inf)
    safe_t = jnp.maximum(temperature, 1e-6)[:, None]
    probs = jax.nn.softmax(vals / safe_t, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = ((cum - probs) < top_p[:, None]).at[:, 0].set(True)
    scaled = jnp.where(keep, vals, -jnp.inf) / safe_t
    if jnp.ndim(rng) == 1:
        def slot_draw(key, row_scaled, row_ids):
            u = jax.vmap(lambda t: jax.random.uniform(
                jax.random.fold_in(key, t), minval=1e-20))(row_ids)
            return jnp.argmax(row_scaled - jnp.log(-jnp.log(u)))

        draw = jax.vmap(slot_draw)(rng, scaled, idxs)
    else:
        draw = jax.random.categorical(rng, scaled, axis=-1)
    sampled = jnp.take_along_axis(idxs, draw[:, None], axis=1)[:, 0]
    return jnp.where(temperature <= 0.0, greedy, sampled.astype(jnp.int32))


def _equations(jaxpr, name, inside_cond=False):
    """(equation, is it inside a ``cond`` branch) for every ``name`` primitive
    of a jaxpr, sub-jaxprs walked."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn, inside_cond
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, name, inside_cond
                                  or eqn.primitive.name == "cond")


@pytest.mark.parametrize("keys", ["per-slot", "batch"])
@pytest.mark.parametrize("temps", [(0.0, 0.0, 0.0, 0.0), (0.0, 0.0, 0.9, 0.0),
                                   (0.7, 1.3, 0.9, 2.0)],
                         ids=["all-greedy", "one-draws", "all-draw"])
def test_gated_sampler_is_the_ungated_one_token_for_token(temps, keys):
    """The candidates behind ``lax.cond(any(temperature > 0))`` change no
    token: all greedy, one drawing row among greedy ones, all drawing, under
    per-slot keys and under one batch key, with a banned token's ``-inf`` and
    a ``+100`` bias among the logits. And the sort over the vocabulary is in
    the conditional's branch and nowhere else."""
    rng = np.random.default_rng(3)
    raw = rng.normal(size=(4, 300)).astype(np.float32) * 3.0
    raw[0, 17] = raw[2, 40] = -np.inf                  # min_tokens / grammar
    raw[1, 250] += 100.0                               # logit_bias +100
    raw[2, int(np.argmax(raw[2]))] = -np.inf           # the ban took the best
    logits = jnp.asarray(raw)
    temperature = jnp.asarray(temps, jnp.float32)
    top_k = jnp.asarray([0, 5, 40, 0], jnp.int32)
    top_p = jnp.asarray([1.0, 0.9, 0.95, 0.5], jnp.float32)
    for seed in range(6):
        key = jax.random.key(seed)
        if keys == "per-slot":
            key = per_slot_keys(jnp.arange(4, dtype=jnp.uint32) + seed,
                                jnp.asarray([9, 3, 11, 30], jnp.int32))
        got = jax.jit(sample)(logits, key, temperature, top_k, top_p)
        want = _ungated(logits, key, temperature, top_k, top_p)
        assert got.dtype == jnp.int32 and got.tolist() == want.tolist()
    assert all(int(g) == int(np.argmax(row))
               for t, g, row in zip(temps, np.asarray(got), raw) if t == 0.0)
    jaxpr = jax.make_jaxpr(sample)(logits, key, temperature, top_k,
                                   top_p).jaxpr
    sorts = list(_equations(jaxpr, "top_k"))
    assert len(sorts) == 1 and sorts[0][1]
    assert not list(_equations(jaxpr, "sort"))
    assert len(list(_equations(jaxpr, "cond"))) == 1
