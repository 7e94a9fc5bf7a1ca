"""Prompt logprobs (vLLM ``prompt_logprobs`` + OpenAI legacy echo+logprobs).

Ground truth is a direct full-context ``log_softmax`` of the model: the
engine's prefill-computed per-position values must match it bit-close, on
both the single and batched prefill paths, with the prefix cache bypassed
(reused rows skip prefill — the request must force a full one).
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import (init_params,
                                                           model_forward)
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request

CFG = tiny_qwen3()
PARAMS = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
PROMPT = [5, 9, 2, 11, 7, 3, 13]


def _serving(**over):
    base = dict(max_decode_slots=4, max_cache_len=64, prefill_buckets=(16,),
                dtype="float32", decode_horizon=4)
    base.update(over)
    return ServingConfig(weights_dtype="bf16", **base)


def _reference_plp(prompt, k):
    tokens = jnp.asarray([prompt], jnp.int32)
    pos = jnp.arange(len(prompt), dtype=jnp.int32)[None]
    logits, _ = model_forward(PARAMS, CFG, tokens, pos)
    lp = jax.nn.log_softmax(logits[0].astype(jnp.float32), -1)
    out = [None]
    for t in range(1, len(prompt)):
        own = float(lp[t - 1, prompt[t]])
        vals, ids = jax.lax.top_k(lp[t - 1], k)
        out.append((own, list(zip(np.asarray(ids).tolist(),
                                  np.asarray(vals).tolist()))))
    return out


def _drain(eng):
    for _ in range(10000):
        if not eng.step():
            break


def _check(data, ref, k):
    assert data[0] is None and len(data) == len(ref)
    for got, want in zip(data[1:], ref[1:]):
        assert got[0] == pytest.approx(want[0], abs=1e-4)
        got_ids = [t for t, _ in got[1][:k]]
        want_ids = [t for t, _ in want[1][:k]]
        assert got_ids == want_ids


def test_single_prefill_matches_direct_log_softmax():
    eng = Engine(CFG, PARAMS, _serving(max_prefill_batch=1))
    req = eng.submit(Request(prompt_ids=list(PROMPT), max_tokens=2,
                             ignore_eos=True, prompt_logprobs=3))
    _drain(eng)
    _check(req.prompt_logprob_data, _reference_plp(PROMPT, 3), 3)


def test_batched_prefill_matches_and_mixes_with_plain():
    """A burst mixing plp and non-plp requests: the plp rows match the
    reference; plain rows carry no data."""
    eng = Engine(CFG, PARAMS, _serving())
    other = [4, 4, 8, 2]
    r1 = eng.submit(Request(prompt_ids=list(PROMPT), max_tokens=2,
                            ignore_eos=True, prompt_logprobs=2))
    r2 = eng.submit(Request(prompt_ids=list(other), max_tokens=2,
                            ignore_eos=True))
    _drain(eng)
    _check(r1.prompt_logprob_data, _reference_plp(PROMPT, 2), 2)
    assert r2.prompt_logprob_data == []


@pytest.mark.parametrize("n_pages", [1, 2])
def test_prefix_cache_bypassed_for_prompt_logprobs(n_pages):
    """With the shared prefix already resident (``n_pages`` whole pages of
    it indexed), a prompt_logprobs request must force a FULL prefill (reused
    rows skip the computation) and still match the reference; the same
    prompt WITHOUT prompt_logprobs does hit."""
    prompt = (PROMPT * 3)[:8 * n_pages + 3]
    eng = Engine(CFG, PARAMS, _serving(prefix_cache=True, page_size=8,
                                       max_cache_len=64,
                                       prefill_buckets=(16, 32),
                                       prefix_reuse_min_pages=1,
                                       max_prefill_batch=1))
    eng.submit(Request(prompt_ids=list(prompt), max_tokens=2,
                       ignore_eos=True))
    _drain(eng)
    hits0 = eng.metrics.prefix_cache_hits.total()
    req = eng.submit(Request(prompt_ids=list(prompt), max_tokens=2,
                             ignore_eos=True, prompt_logprobs=2))
    _drain(eng)
    assert eng.metrics.prefix_cache_hits.total() == hits0
    _check(req.prompt_logprob_data, _reference_plp(prompt, 2), 2)
    eng.submit(Request(prompt_ids=list(prompt), max_tokens=2,
                       ignore_eos=True))
    _drain(eng)
    assert eng.metrics.prefix_cache_hits.total() == hits0 + 1


def test_chunked_prompt_rejected():
    eng = Engine(CFG, PARAMS, _serving(prefill_chunk=8, max_cache_len=64,
                                       prefill_buckets=(16,)))
    with pytest.raises(ValueError, match="chunk"):
        eng.submit(Request(prompt_ids=list(range(2, 32)), prompt_logprobs=1))


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def server():
    from aws_k8s_ansible_provisioner_tpu.serving.server import (build_state,
                                                                serve)
    from aws_k8s_ansible_provisioner_tpu.utils.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    cfg = tiny_qwen3(vocab_size=tok.vocab_size, eos_token_id=tok.eos_token_id)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    serving = ServingConfig(weights_dtype="bf16", model="plp-model", max_decode_slots=4,
                            max_cache_len=128, prefill_buckets=(16, 32),
                            dtype="float32")
    state = build_state(serving, model_cfg=cfg, params=params, tokenizer=tok)
    ready, stop = threading.Event(), threading.Event()
    threading.Thread(target=serve,
                     args=(state, "127.0.0.1", 18429, ready, stop),
                     daemon=True).start()
    assert ready.wait(30)
    yield "http://127.0.0.1:18429"
    stop.set()


def _post(url, payload):
    req = urllib.request.Request(url, data=json.dumps(payload).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def test_http_prompt_logprobs_field(server):
    resp = _post(server + "/v1/completions", {
        "model": "plp-model", "prompt": "hello", "max_tokens": 3,
        "prompt_logprobs": 2, "ignore_eos": True})
    pl = resp["choices"][0]["prompt_logprobs"]
    assert pl[0] is None
    assert len(pl) == 5                       # "hello" = 5 byte tokens
    for entry in pl[1:]:
        assert isinstance(entry, dict) and len(entry) >= 1
        assert all(isinstance(v, float) for v in entry.values())


def test_http_echo_logprobs_covers_prompt(server):
    resp = _post(server + "/v1/completions", {
        "model": "plp-model", "prompt": "hi!", "max_tokens": 2,
        "echo": True, "logprobs": 2, "ignore_eos": True})
    ch = resp["choices"][0]
    assert ch["text"].startswith("hi!")
    lp = ch["logprobs"]
    assert len(lp["tokens"]) == 3 + 2         # prompt + generated
    assert lp["token_logprobs"][0] is None    # position 0 unscored
    assert all(isinstance(v, float) for v in lp["token_logprobs"][1:])
    assert lp["text_offset"][:3] == [0, 1, 2]
    # generated offsets continue past the echoed prompt
    assert lp["text_offset"][3] == 3


def test_http_prompt_logprobs_stream_rejected(server):
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server + "/v1/completions", {
            "model": "plp-model", "prompt": "x", "stream": True,
            "prompt_logprobs": 1})
    assert e.value.code == 400
