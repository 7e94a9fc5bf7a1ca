"""The head runs over the rows that are sampled (models/layers.py
``head_rows``; serving/programs.py's four prefill-type step programs).

A prefill-type program samples one row a prompt — ``prefill_step`` and
``prefill_chunk_step`` 1, ``prefill_batch_step`` N, ``mixed_step`` every
decode row and the chunk's last valid row (B + 1) — and hands those rows to
``model_forward_carry``, which gathers the hidden state to them BEFORE the
final norm, the muP scale and the vocabulary matmul. All three are
row-wise, so what a request receives is what the every-row head gave it.

Each case runs one program twice on the same operands: as it is, and with
``model_forward_carry`` made to compute the every-row ``[B, T, V]`` logits
and gather the rows afterwards (what the programs did before). The sampled
tokens are equal and the logprob payloads agree to float32 rounding (the
matmul's row count changes the backend's blocking, not the mathematics).
The models are the ones tests/test_minicpm_sala.py pins or builds: tiny-qwen3
with its TIED head quantized to int8, tiny-olmoe and tiny-solar (untied
int8 heads; routed rows, recurrent state) and the tiny selecting hybrid
with muP's ``logit_scale``.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aws_k8s_ansible_provisioner_tpu.config import (
    ServingConfig, tiny_olmoe, tiny_qwen3, tiny_sala, tiny_solar)
from aws_k8s_ansible_provisioner_tpu.models import layers as L
from aws_k8s_ansible_provisioner_tpu.models import lora
from aws_k8s_ansible_provisioner_tpu.models.quant import quantize_params
from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp
from aws_k8s_ansible_provisioner_tpu.ops import linear_attention as la
from aws_k8s_ansible_provisioner_tpu.serving import flightrec
from aws_k8s_ansible_provisioner_tpu.serving import programs as pg
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request

PS, PPS, B, C, T = 8, 8, 3, 16, 32      # page, pages a slot, slots, chunk, bucket
MODELS = {"tiny-qwen3": tiny_qwen3, "tiny-olmoe": tiny_olmoe,
          "tiny-solar": tiny_solar, "tiny-sala": tiny_sala}
TOL = 2e-5


@pytest.fixture(scope="module", params=sorted(MODELS))
def model(request):
    cfg = MODELS[request.param]()
    params = quantize_params(
        L.init_params(cfg, jax.random.PRNGKey(1), jnp.float32), cfg)
    if cfg.tie_embeddings:
        assert "scale" in params["embed"]       # the tied head is int8
    if request.param == "tiny-sala":
        assert cfg.logit_scale != 1.0
    return cfg, params


def _cache(cfg):
    c = kvp.init_pool(cfg, B * PPS + 1, PS, jnp.float32)
    if cfg.recurrent:
        c.update(la.init_state(cfg, B, jnp.float32))
    return c


TABLE = jnp.asarray([[1 + s * PPS + p for p in range(PPS)] for s in range(B)],
                    jnp.int32)


def _ids(cfg, n, seed):
    return np.random.default_rng(seed).integers(2, cfg.vocab_size, n)


def _sampling(n=None):
    shape = () if n is None else (n,)
    return (jax.random.PRNGKey(0), jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape, jnp.int32), jnp.ones(shape, jnp.float32))


def _rows_kw(n=None):
    lead = () if n is None else (n,)
    return dict(bias_ids=jnp.full(lead + (pg.BIAS_K,), 2**31 - 1, jnp.int32),
                bias_vals=jnp.zeros(lead + (pg.BIAS_K,), jnp.float32),
                ban_ids=jnp.full(lead + (pg.BAN_K,), 2**31 - 1, jnp.int32),
                ban_until=jnp.zeros(lead, jnp.int32))


STATIC = ("mesh", "impl", "logprobs", "chunk_logprobs", "prompt_logprobs",
          "penalties", "bblock")


def _run(program, every_row, cfg, *args, **kw):
    """One call of a step program, freshly traced and without donation;
    ``every_row`` = the head over every row, the asked rows gathered after.
    Returns (outputs, the shape of the logits the forward handed back)."""
    real, shapes = pg.model_forward_carry, []

    def forward(*a, head_rows=None, **k):
        logits, cache = real(
            *a, head_rows=None if every_row else head_rows, **k)
        shapes.append(logits.shape)
        if every_row and head_rows is not None:
            logits = logits.reshape(-1, logits.shape[-1])[head_rows]
        return logits, cache

    # (a fresh function: jit's trace cache is keyed by the function)
    fn = jax.jit(lambda *a, **k: program.__wrapped__(*a, **k),
                 static_argnums=(0,),
                 static_argnames=[n for n in STATIC if n in kw])
    pg.model_forward_carry = forward
    try:
        out = fn(cfg, *args, **kw)
    finally:
        pg.model_forward_carry = real
    # (mixed_step traces its layers at two widths, PR 55: the widest — the
    # body these operands run — stands for the program)
    return jax.tree.map(np.asarray, out), max(set(shapes), key=np.prod)


def _prefill(cfg, params, every_row=False, **kw):
    n = 21
    toks = np.zeros((1, T), np.int32)
    toks[0, :n] = _ids(cfg, n, 0)
    state = {"slot": jnp.int32(0)} if cfg.recurrent else {}
    return _run(pg.prefill_step, every_row, cfg, params, _cache(cfg),
                jnp.asarray(toks), jnp.int32(n), *_sampling(),
                pages=TABLE[0], seed=jnp.uint32(1), rep=jnp.float32(1.0),
                logprobs=True, **state, **_rows_kw(), **kw)


def _prefill_batch(cfg, params, every_row=False, **kw):
    lens = [21, 9]
    toks = np.zeros((2, T), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = _ids(cfg, n, 10 + i)
    state = {"slots": jnp.asarray([0, 2], jnp.int32)} if cfg.recurrent else {}
    return _run(pg.prefill_batch_step, every_row, cfg, params, _cache(cfg),
                jnp.asarray(toks), jnp.asarray(lens, jnp.int32),
                *_sampling(2), tables=TABLE[jnp.asarray([0, 2])],
                seeds=jnp.ones(2, jnp.uint32), reps=jnp.ones(2, jnp.float32),
                logprobs=True, **state, **_rows_kw(2), **kw)


def _prefill_chunk(cfg, params, every_row=False):
    n = 11
    toks = np.zeros((1, C), np.int32)
    toks[0, :n] = _ids(cfg, n, 20)
    state = {"slot": jnp.int32(1)} if cfg.recurrent else {}
    return _run(pg.prefill_chunk_step, every_row, cfg, params, _cache(cfg),
                jnp.asarray(toks), jnp.int32(0), jnp.int32(n), *_sampling(),
                pages=TABLE[1], seed=jnp.uint32(1), rep=jnp.float32(1.0),
                rep_seen=jnp.zeros(cfg.vocab_size, jnp.bool_), logprobs=True,
                **state, **_rows_kw())


def _mixed(cfg, params, every_row=False, cache=None, lora_idx=None):
    """Slot 0 decodes at a prefilled context of 21, slot 2 idles, slot 1
    chunks 11 rows of 16."""
    if cache is None:
        cache = _prefill(cfg, params)[0][0]
    n = 11
    ptoks = np.zeros((1, C), np.int32)
    ptoks[0, :n] = _ids(cfg, n, 30)
    live = jnp.asarray([True, False, False]) \
        if cfg.num_experts > 0 or cfg.recurrent else None
    return _run(pg.mixed_step, every_row, cfg, params, cache,
                jnp.asarray([5, 0, 0], jnp.int32),
                jnp.asarray([21, 0, 0], jnp.int32), jnp.asarray(ptoks),
                jnp.int32(1), jnp.int32(0), jnp.int32(n), jnp.float32(1.0),
                jnp.zeros(cfg.vocab_size, jnp.bool_), jnp.uint32(1),
                jnp.float32(0.0), jnp.int32(0), jnp.float32(1.0),
                *_sampling(B), table=TABLE, impl="xla", logprobs=True,
                chunk_logprobs=True, seeds=jnp.ones(B, jnp.uint32),
                live=live, lora_idx=lora_idx, **_rows_kw(B))


# (program, driver, rows the head runs over, rows the layers run over,
#  where the tokens and the logprob payloads sit in the output)
PROGRAMS = {
    "prefill_step": (_prefill, 1, T, lambda o: (o[1], o[2])),
    "prefill_batch_step": (_prefill_batch, 2, 2 * T, lambda o: (o[1], o[2])),
    "prefill_chunk_step": (_prefill_chunk, 1, C, lambda o: (o[1], o[2])),
    "mixed_step": (_mixed, B + 1, B + C,
                   lambda o: ((o[2][0], o[3][0]), (o[2][1], o[3][1]))),
}


def _same(got, want):
    (tok, lps), (wtok, wlps) = got, want
    assert jax.tree.all(jax.tree.map(np.array_equal, tok, wtok))
    for a, b in zip(jax.tree.leaves(lps), jax.tree.leaves(wlps)):
        if a.dtype.kind == "i":         # the top-k ids
            assert np.array_equal(a, b)
        else:
            assert np.abs(a - b).max() < TOL


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_sampled_rows_are_the_every_row_heads(model, program):
    cfg, params = model
    drive, head, padded, read = PROGRAMS[program]
    out, shape = drive(cfg, params)
    want, every = drive(cfg, params, every_row=True)
    assert shape == (head, cfg.vocab_size)
    assert every[-1] == cfg.vocab_size and np.prod(every[:-1]) == padded
    _same(read(out), read(want))
    # and the pool, the state and the carry are the same arrays
    assert jax.tree.all(jax.tree.map(np.array_equal, out[0], want[0]))


@pytest.mark.parametrize("program", ["prefill_step", "prefill_batch_step"])
def test_prompt_logprobs_still_read_every_row(model, program):
    """The ``prompt_logprobs=True`` variants read every prompt row
    (``_prompt_logprobs(logits, tokens)``): their head stays every-row, the
    payload covers every position, and the sampled row is the same."""
    cfg, params = model
    drive, head, padded, read = PROGRAMS[program]
    out, shape = drive(cfg, params, prompt_logprobs=True)
    assert shape == (padded // T, T, cfg.vocab_size)
    sel, vals, ids = out[3]
    assert sel.shape == (padded // T, T - 1)
    assert vals.shape == ids.shape == sel.shape + (pg.LOGPROB_K,)
    assert np.isfinite(sel).all() and (sel <= 0).all()
    _same(read(out), read(drive(cfg, params)[0]))


def test_mixed_step_under_adapter_indices_is_its_every_row_form():
    """Adapters sit on q/k/v/o and the MLP (models/lora.py), never on the
    head, so the gathered head consults no per-token index: with
    ``lora_idx`` given (slot 0 on adapter 1, the chunking slot on adapter
    2) the program still equals its every-row form, and the adapters do
    move the answer."""
    cfg = tiny_qwen3()
    base = L.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    rng = np.random.default_rng(0)
    r, H = 4, cfg.hidden_size
    stacked = {}
    for target, dout in (("wq", cfg.q_size), ("w_up", cfg.intermediate_size)):
        A = rng.standard_normal((cfg.num_layers, 3, H, r)) * 0.3
        Bm = rng.standard_normal((cfg.num_layers, 3, r, dout)) * 0.3
        A[:, 0] = Bm[:, 0] = 0          # index 0: the base model
        stacked[target] = {"lora_A": jnp.asarray(A, jnp.float32),
                           "lora_B": jnp.asarray(Bm, jnp.float32)}
    params = lora.attach(base, stacked)
    assert "lora_A" not in params.get("lm_head", {}) \
        and "lora_A" not in params["embed"]
    cache = _prefill(cfg, base)[0][0]
    idx = jnp.asarray([1, 2, 0], jnp.int32)
    read = PROGRAMS["mixed_step"][3]
    out, shape = _mixed(cfg, params, cache=cache, lora_idx=idx)
    want, _ = _mixed(cfg, params, every_row=True, cache=cache, lora_idx=idx)
    assert shape == (B + 1, cfg.vocab_size)
    _same(read(out), read(want))
    plain, _ = _mixed(cfg, params, cache=cache,
                      lora_idx=jnp.zeros(B, jnp.int32))
    (_, lps), (_, plain_lps) = read(out), read(plain)
    assert np.abs(lps[0][0] - plain_lps[0][0]).max() > 1e-3     # decode row
    assert np.abs(lps[1][0] - plain_lps[1][0]).max() > 1e-3     # chunk row


# -- the counter: head_rows in the dispatch record and on /metrics ----------


def _serving(**over):
    base = dict(weights_dtype="bf16", model="tiny-qwen3", max_decode_slots=4,
                max_cache_len=128, page_size=32, prefill_buckets=(16, 32, 64),
                dtype="float32", prefix_cache=False, decode_horizon=4)
    base.update(over)
    return ServingConfig(**base)


def _req(n_prompt, max_tokens, start=3, **kw):
    return Request(prompt_ids=[start + (i % 100) for i in range(n_prompt)],
                   max_tokens=max_tokens, ignore_eos=True, **kw)


def _one(eng, **kw):
    eng.submit(_req(9, 3, **kw))


def _three(eng, **second):
    for i in range(3):
        eng.submit(_req(7 + i, 3, start=10 * i + 3,
                        **(second if i == 1 else {})))


def _long(eng):
    eng.submit(_req(40, 3))


def _under_decode(eng):
    eng.submit(_req(9, 24))
    for _ in range(3):
        eng.step()
    eng.submit(_req(20, 4, start=50))


# (id, serving overrides, traffic, program, head_rows, padded_tokens)
RECORDS = [
    ("prefill", {}, _one, "prefill_step", 1, 16),
    ("prefill-prompt-logprobs", {}, partial(_one, prompt_logprobs=0),
     "prefill_step", 16, 16),
    ("batched-prefill", {}, _three, "prefill_batch_step", 4, 4 * 16),
    ("batched-prefill-prompt-logprobs", {},
     partial(_three, prompt_logprobs=0), "prefill_batch_step", 4 * 16, 4 * 16),
    ("chunk", dict(prefill_chunk=16), _long, "prefill_chunk_step", 1, 16),
    # (a 20-token chunk runs the narrow body: half of the 64 rows, PR 55)
    ("mixed", dict(decode_pipeline=1, ragged_attention=1), _under_decode,
     "mixed_step", 4 + 1, 4 + 32),
]


@pytest.fixture(scope="module")
def engine_model():
    cfg = tiny_qwen3()
    return cfg, L.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


@pytest.mark.parametrize("serving_kw,traffic,program,head,padded",
                         [r[1:] for r in RECORDS], ids=[r[0] for r in RECORDS])
def test_the_dispatch_record_and_metrics_state_the_heads_rows(
        engine_model, serving_kw, traffic, program, head, padded):
    cfg, params = engine_model
    eng = Engine(cfg, params, _serving(**serving_kw))
    seen, orig = [], flightrec.record

    def tap(*a, **rec):
        if a[0] == "dispatch":
            seen.append(dict(rec))
        return orig(*a, **rec)

    flightrec.record = tap
    try:
        traffic(eng)
        for _ in range(10000):
            if not eng.step():
                break
    finally:
        flightrec.record = orig
    mine = [r for r in seen if r["program"] == program]
    assert mine
    for r in mine:
        assert (r["head_rows"], r["padded_tokens"]) == (head, padded)
    # the programs whose every row is used state no head_rows
    assert all("head_rows" not in r for r in seen
               if r["program"] in ("decode_steps", "spec_decode_step"))
    m = eng.metrics
    by_program = {}
    for r in seen:
        if "head_rows" in r:
            by_program[r["program"]] = by_program.get(r["program"], 0) \
                + r["head_rows"]
    assert m.head_rows.total() == sum(by_program.values())
    text = m.registry.render()
    for prog, n in by_program.items():
        line = f'tpu_serve_head_rows_total{{program="{prog}"}} '
        assert any(ln.startswith(line) and float(ln.split()[-1]) == n
                   for ln in text.splitlines()), (line, text)


def test_no_serving_option_steers_the_head():
    """One path for every configuration: what decides is what a program
    reads of its own logits."""
    assert not [f.name for f in dataclasses.fields(ServingConfig)
                if "head_" in f.name or "logit" in f.name]
