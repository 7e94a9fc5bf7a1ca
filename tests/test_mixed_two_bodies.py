"""``mixed_step`` holds its layers TWICE inside one program (serving/programs.py):
over ``slots + C`` packed rows and over ``slots + C // 2``, and the chunk's
length — an operand the program already has — picks. A chunk row at or past
``plen`` is dead in the wide body (limit 0, no K/V write, routed to no
expert, no state advanced), so leaving rows ``[C // 2, C)`` out when ``plen
<= C // 2`` removes dead rows only.

(a) parity: the operands a real engine hands the program (captured at the
    SECOND chunk of a long admission beside a live stream: a non-zero
    start, a carried state), replayed at ``plen`` in {1, C // 2 - 1, C // 2}
    (the narrow body) and {C // 2 + 1, C} (the wide one) through the program
    as it is and through the single-body program the parent had (the width
    rule forced to "one") — every output equal: tokens, chunk token, carry
    lanes, every cache leaf (pool, recurrent state, tails, tally), the MoE
    summary, the picked pages. One small configuration a family, and the
    dense one under LoRA, penalties, ``logprobs`` and ``chunk_logprobs``.
(b) one compiled variant whatever the length.
(c) shapes that admit no second width: one body, and it serves.
(d) the dispatch record says the rows that RAN, and
    ``tpu_serve_mixed_steps_total{body}`` adds up to the mixed dispatches.
(the deviceless compiles of (e) are in tests/test_tpu_compile.py)
"""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aws_k8s_ansible_provisioner_tpu.config import (
    ServingConfig, tiny_falcon_h1, tiny_lfm2, tiny_olmoe,
    tiny_qwen3, tiny_sala, tiny_solar, tiny_trinity)
from aws_k8s_ansible_provisioner_tpu.models import layers as L
from aws_k8s_ansible_provisioner_tpu.models import lora
from aws_k8s_ansible_provisioner_tpu.serving import flightrec
from aws_k8s_ansible_provisioner_tpu.serving import programs as pg
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request

PS, C, SLOTS = 8, 32, 4
W = C // 2
FAMILIES = {"dense": tiny_qwen3, "moe-live": tiny_olmoe,
            "kda-hybrid": tiny_solar, "ssm-two-mixer": tiny_falcon_h1,
            "selecting": tiny_sala, "window-full-list": tiny_trinity,
            "conv-list": tiny_lfm2}
VARIANTS = ("lora", "penalties", "logprobs", "chunk_logprobs")
CASES = sorted(FAMILIES) + [f"dense+{v}" for v in VARIANTS]
NARROW, WIDE = (1, W - 1, W), (W + 1, C)
STATIC = ("mesh", "impl", "logprobs", "chunk_logprobs", "penalties", "bblock")
PLEN = 5            # where ``plen`` sits among the operands behind the cache
# Every family's outputs are BITWISE the single-body program's but one: the
# Lightning layers' span form sums a 16-row chunk in one block where the
# 32-row chunk has a second, dead one, and XLA's CPU code for the two shapes
# rounds float32 differently (2e-7 in the state, 7e-7 in the K/V behind it;
# the tokens, lengths and page counts are equal).
ROUNDED = {"selecting": 5e-6}


def _params(cfg):
    return L.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)


def _engine(cfg, params, **over):
    kw = dict(max_decode_slots=SLOTS, max_cache_len=256,
              prefill_buckets=(16, 32), dtype="float32", weights_dtype="bf16",
              prefix_cache=False, decode_horizon=2, page_size=PS,
              decode_pipeline=1, ragged_attention=1, attention_impl="xla",
              kv_host_tier_bytes=0, derived_seed=7, prefill_chunk=C)
    kw.update(over)
    return Engine(cfg, params, ServingConfig(**kw))


def _req(cfg, n, seed, max_tokens=4):
    ids = np.random.default_rng(seed).integers(2, cfg.vocab_size, n).tolist()
    return Request(prompt_ids=ids, max_tokens=max_tokens, ignore_eos=True)


def _drain(eng):
    for _ in range(10000):
        if not eng.step():
            return
    raise AssertionError("engine did not drain")


def _admissions(eng, cfg, lengths):
    """A live stream, then prompts of ``lengths`` admitted beside it."""
    eng.submit(_req(cfg, 20, 3, 60))
    for _ in range(3):
        eng.step()
    reqs = [eng.submit(_req(cfg, n, 10 + i)) for i, n in enumerate(lengths)]
    _drain(eng)
    return reqs


@contextlib.contextmanager
def _dispatch_records():
    seen, orig = [], flightrec.record

    def tap(*a, **rec):
        if a[0] == "dispatch":
            seen.append(dict(rec))
        return orig(*a, **rec)

    flightrec.record = tap
    try:
        yield seen
    finally:
        flightrec.record = orig


@functools.lru_cache(maxsize=None)
def _captured(family):
    """(cfg, params, (cache, operands, keywords)) of the second
    ``mixed_step`` dispatch of a 2 C + 5 token admission beside a live
    stream: C tokens from row C, the first chunk's K/V and state behind."""
    cfg = FAMILIES[family]()
    params = _params(cfg)
    eng = _engine(cfg, params)
    calls, real = [], pg.mixed_step

    def tap(cfg_, params_, cache, *args, **kw):
        calls.append(jax.tree.map(
            lambda x: np.array(x) if isinstance(x, jax.Array) else x,
            (cache, args, kw)))
        return real(cfg_, params_, cache, *args, **kw)

    pg.mixed_step = tap
    try:
        _admissions(eng, cfg, [2 * C + 5])
    finally:
        pg.mixed_step = real
    cache, args, kw = calls[1]
    assert int(args[PLEN]) == C and int(args[PLEN - 1]) == C
    assert args[2].shape == (1, C)
    return cfg, params, (cache, args, kw)


@contextlib.contextmanager
def _one_body():
    """The parent's program: the width rule answers "one width"."""
    real = pg.mixed_narrow_rows
    pg.mixed_narrow_rows = lambda *a: 0     # (unlike the real one, uncached)
    try:
        yield
    finally:
        pg.mixed_narrow_rows = real


def _program(counter):
    """``mixed_step`` freshly traced (jit's cache is keyed by the function)
    and without donation; ``counter`` takes a tick a forward pass TRACED."""
    real = pg.model_forward_carry

    def forward(*a, **k):
        counter.append(a[2].shape[1])       # the packed rows of this body
        return real(*a, **k)

    fn = jax.jit(lambda *a, **k: pg.mixed_step.__wrapped__(*a, **k),
                 static_argnums=(0,), static_argnames=STATIC)

    def run(cfg, *args, **kw):
        pg.model_forward_carry = forward
        try:
            return jax.tree.map(np.asarray, fn(cfg, *args, **kw))
        finally:
            pg.model_forward_carry = real

    return run


def _variant(cfg, params, kw, variant):
    """The dense program's other variants, as operands of the replay."""
    rng = np.random.default_rng(5)
    kw = dict(kw)
    if variant == "lora":
        r, H, stacked = 4, cfg.hidden_size, {}
        for target, dout in (("wq", cfg.q_size),
                             ("w_up", cfg.intermediate_size)):
            A = rng.standard_normal((cfg.num_layers, 3, H, r)) * 0.3
            Bm = rng.standard_normal((cfg.num_layers, 3, r, dout)) * 0.3
            A[:, 0] = Bm[:, 0] = 0
            stacked[target] = {"lora_A": jnp.asarray(A, jnp.float32),
                               "lora_B": jnp.asarray(Bm, jnp.float32)}
        params = lora.attach(params, stacked)
        kw["lora_idx"] = jnp.asarray([1, 2, 0, 1], jnp.int32)
    elif variant == "penalties":
        V = cfg.vocab_size
        kw.update(penalties=True,
                  counts=jnp.asarray(rng.integers(0, 3, (SLOTS, V)),
                                     jnp.int32),
                  presence=jnp.full(SLOTS, 0.5, jnp.float32),
                  frequency=jnp.full(SLOTS, 0.25, jnp.float32),
                  repetition=jnp.full(SLOTS, 1.3, jnp.float32),
                  prompt_mask=jnp.asarray(rng.random((SLOTS, V)) < 0.1))
    elif variant:
        kw[variant] = True
    return params, kw


@functools.lru_cache(maxsize=None)
def _replayed(case):
    """{plen: (outputs of the program as it is, of the one-body program)},
    and the rows each program's bodies were traced over."""
    family, _, variant = case.partition("+")
    cfg, params, (cache, args, kw) = _captured(family)
    params, kw = _variant(cfg, params, kw, variant)
    two, one = [], []
    ours, parents = _program(two), _program(one)
    out = {}
    for plen in NARROW + WIDE:
        a = args[:PLEN] + (np.int32(plen),) + args[PLEN + 1:]
        got = ours(cfg, params, cache, *a, **kw)
        with _one_body():
            want = parents(cfg, params, cache, *a, **kw)
        out[plen] = (got, want)
    return out, two, one


@pytest.mark.parametrize("plen", NARROW + WIDE)
@pytest.mark.parametrize("case", CASES)
def test_either_body_gives_what_the_single_body_program_gives(case, plen):
    out, two, one = _replayed(case)
    # ONE program each, the first holding both bodies (and a shapes-only
    # pass over the wide one, for its loops' carries)
    assert two == [SLOTS + C, SLOTS + W, SLOTS + C] and one == [SLOTS + C]
    got, want = out[plen]
    flat = lambda t: {jax.tree_util.keystr(k): v for k, v in
                      jax.tree_util.tree_leaves_with_path(t)}
    got, want = flat(got), flat(want)
    assert got.keys() == want.keys()
    for name in got:
        assert got[name].shape == want[name].shape, name
        if got[name].dtype.kind == "f" and case in ROUNDED:
            assert np.abs(got[name] - want[name]).max() < ROUNDED[case], name
        else:
            assert np.array_equal(got[name], want[name]), name


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_replayed_operands_reach_what_a_family_adds(family):
    """The parity above compares something: the chunk's rows change the
    pool (and the recurrent state, the MoE summary, the picked pages where
    the family has them) and a shorter chunk changes them differently."""
    cfg, _, (cache, _, _) = _captured(family)
    out, _, _ = _replayed(family)
    new_cache, aux = out[C][0][0], out[C][0][-1]
    assert not np.array_equal(new_cache["k"], cache["k"])
    assert not np.array_equal(new_cache["k"], out[1][0][0]["k"])
    state = sorted(set(new_cache) - {"k", "v", "wk", "wv"})
    assert bool(state) == bool(cfg.recurrent or cfg.selects), state
    assert ("wk" in new_cache) == cfg.windowed
    for name in state:
        if new_cache[name].size and name != "kc":
            assert not np.array_equal(new_cache[name], out[1][0][0][name]) \
                or not np.array_equal(new_cache[name], cache[name]), name
    assert (aux is not None) == (cfg.num_experts > 0 or cfg.selects)


def test_one_compiled_variant_whatever_the_length():
    cfg, params, (cache, args, kw) = _captured("dense")
    sizes = []
    for plen in NARROW + WIDE + NARROW:
        a = args[:PLEN] + (np.int32(plen),) + args[PLEN + 1:]
        # (the program donates its pool, tokens and lengths: fresh copies)
        pg.mixed_step(cfg, params, *jax.tree.map(jnp.array, (cache,) + a),
                      **kw)
        sizes.append(pg.mixed_step._cache_size())
    assert len(set(sizes)) == 1, sizes


# -- the width rule -----------------------------------------------------------


# (cell's model, slots, chunk, cache length, decode block) of the nine
# cells' servers (benchmark/configs/*.json) -> the narrow body's chunk rows
SERVED = [
    ("qwen3-0.6b", 32, 2048, 2048, 8, 1024),
    ("qwen3-8b", 16, 2048, 2048, 8, 1024),
    ("olmoe-1b-7b", 24, 2048, 2048, 8, 1024),
    ("solar-open2-250b-ep8", 64, 512, 2048, 8, 256),
    ("lfm2-8b-a1b", 128, 512, 2048, 8, 256),
    ("falcon-h1-34b-pp8", 64, 512, 2048, 8, 256),
    ("minicpm-sala-9b-pp4", 24, 4608, 32768, 8, 2304),
    ("trinity-mini-26b-pp4", 48, 4096, 9216, 8, 2048),
    # a chunk the selecting entry walks in several calls: one body
    ("minicpm-sala-9b-pp4", 24, 8192, 32768, 8, 0),
    # half a chunk is no whole number of pages / of the kernel's blocks
    ("qwen3-0.6b", 32, 2112, 4096, 8, 0),
    ("qwen3-0.6b", 32, 64, 2048, 8, 0),
    ("qwen3-0.6b", 30, 2048, 2048, 8, 1024),    # blocks of 2 at 2,078 rows
    ("qwen3-0.6b", 32, 128, 2048, 1, 64),
]


@pytest.mark.parametrize("cell,slots,chunk,max_len,bblock,want", SERVED,
                         ids=[f"{m}-s{s}-c{c}-bb{b}"
                              for m, s, c, _, b, _ in SERVED])
def test_the_width_rule_reads_the_shapes(cell, slots, chunk, max_len, bblock,
                                         want):
    # the rule reads a MODEL only where it selects: the selecting stage's
    # heads (32 query, 2 KV of 128), any other model's stand-in
    cfg = dataclasses.replace(tiny_sala(), num_heads=32, num_kv_heads=2,
                              head_dim=128) \
        if cell.startswith("minicpm-sala") else tiny_qwen3()
    got = pg.mixed_narrow_rows(cfg, slots, chunk, 64, bblock, max_len // 64,
                               jnp.bfloat16)
    assert got == want
    assert bool(pg.mixed_takes_narrow(max(want, 1), want)) == bool(want)
    assert not pg.mixed_takes_narrow(want + 1, want)


# -- (c), (d): the engine's side ---------------------------------------------


def _served(cfg, lengths, **over):
    eng = _engine(cfg, _params(cfg), **over)
    forwards, real = [], pg.model_forward_carry

    def forward(*a, **k):
        forwards.append(a[2].shape[1])
        return real(*a, **k)

    pg.model_forward_carry = forward
    jax.clear_caches()          # so that this engine's programs are traced
    try:
        with _dispatch_records() as seen:
            reqs = _admissions(eng, cfg, lengths)
    finally:
        pg.model_forward_carry = real
    assert all(len(r.generated) == 4 for r in reqs)
    return eng, [r for r in seen if r["program"] == "mixed_step"], forwards


def _bodies(eng):
    return {body: eng.metrics.mixed_steps.value(body=body)
            for body in ("narrow", "wide")}


def test_shapes_without_a_second_width_compile_one_body_and_serve():
    """A 24-row chunk over pages of 8: half of it is no whole page."""
    cfg = tiny_qwen3()
    eng, mixed, forwards = _served(cfg, [5, 12, 13, 24, 40], prefill_chunk=24)
    assert pg.mixed_narrow_rows(cfg, SLOTS, 24, PS, eng.decode_bblock,
                                eng.pages_per_slot, jnp.float32) == 0
    assert [r["chunk_n"] for r in mixed] == [5, 12, 13, 24, 24, 16]
    assert {r["chunk_rows"] for r in mixed} == {24}
    assert {r["padded_tokens"] for r in mixed} == {SLOTS + 24}
    assert SLOTS + 24 in forwards and SLOTS + 12 not in forwards
    assert _bodies(eng) == {"narrow": 0, "wide": len(mixed)}


@pytest.mark.parametrize("family", ["dense", "window-full-list",
                                    "ssm-two-mixer"])
def test_the_record_says_the_rows_that_ran(family):
    cfg = FAMILIES[family]()
    eng, mixed, forwards = _served(cfg, [1, W - 1, W, W + 1, C, C + 3])
    assert [r["chunk_n"] for r in mixed] == [1, W - 1, W, W + 1, C, C, 3]
    rows = [W, W, W, C, C, C, W]
    assert [r["chunk_rows"] for r in mixed] == rows
    assert [r["padded_tokens"] for r in mixed] == [SLOTS + w for w in rows]
    for r, w in zip(mixed, rows):
        want = eng._chunk_page_steps(w, r["chunk_off"], r["chunk_n"])
        assert want and {k: r[k] for k in want} == want
        if cfg.recurrent:
            assert r["state_rows"] == r["active"] + r["chunk_n"]
            assert r["ssm_span_rows"] == r["chunk_n"]
    # the narrow walk differs from the wide one's where the tile does
    narrow_steps = eng._chunk_page_steps(W, 0, W)
    assert narrow_steps["chunk_page_steps_by8"] > 0
    # ONE program held both bodies: the narrow one traced once
    assert forwards.count(SLOTS + W) == 1 and SLOTS + C in forwards
    assert _bodies(eng) == {"narrow": 4, "wide": 3}
    text = eng.metrics.registry.render()
    assert 'tpu_serve_mixed_steps_total{body="narrow"} 4' in text
    assert 'tpu_serve_mixed_steps_total{body="wide"} 3' in text
