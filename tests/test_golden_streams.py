"""Golden decode-STREAM parity vs the real HF torch classes (VERDICT r4 #4).

`tests/test_model_parity.py` pins one-step logits; generation bugs can hide
past that (cache write/position drift, sliding-window boundary handling,
router tie-breaking only bite over MULTI-step decode). These tests pin the
full greedy token stream of our serving ENGINE against
``HF model.generate(do_sample=False)`` for every family, plus the two cases
the verdict singles out: a sliding-window model generating far past its
window, and MoE routing with EXACT router-logit ties. Chat-template renders
are pinned against HF ``apply_chat_template`` over the SAME shipped Jinja
sources (templates/*.yaml, the ConfigMaps production mounts).

Like the one-step suite this builds tiny random instances of the real HF
classes in-process (zero egress) — stronger than committed token fixtures,
because the HF side is re-derived from torch on every run instead of
trusted from a file.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from aws_k8s_ansible_provisioner_tpu.config import (ServingConfig, tiny_gemma,
                                                    tiny_llama, tiny_mistral,
                                                    tiny_opt, tiny_phi,
                                                    tiny_qwen3,
                                                    tiny_qwen3_moe)
from aws_k8s_ansible_provisioner_tpu.models import convert_state_dict
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request

from test_model_parity import (_hf_gemma, _hf_llama, _hf_mistral, _hf_opt,
                               _hf_phi, _hf_qwen3)
from test_moe import _hf_qwen3_moe

N_NEW = 24


def _hf_greedy(model, prompt, n_new):
    import torch

    with torch.no_grad():
        out = model.generate(torch.tensor([prompt]), max_new_tokens=n_new,
                             do_sample=False, num_beams=1,
                             pad_token_id=0, use_cache=True,
                             # the engine side runs ignore_eos=True; an eos
                             # mid-stream must not truncate the golden ref
                             eos_token_id=None)
    return out[0, len(prompt):].tolist()


def _engine_greedy(cfg, params, prompt, n_new, **serving_over):
    base = dict(max_decode_slots=2, max_cache_len=128, prefill_buckets=(16,),
                dtype="float32", prefix_cache=False, decode_horizon=4)
    base.update(serving_over)
    eng = Engine(cfg, params, ServingConfig(weights_dtype="bf16", **base))
    req = eng.submit(Request(prompt_ids=list(prompt), max_tokens=n_new,
                             ignore_eos=True))
    for _ in range(10000):
        if not eng.step():
            break
    return req.generated


@pytest.mark.parametrize("family", ["qwen3", "phi", "opt", "llama", "gemma",
                                    "mistral"])
def test_greedy_stream_matches_hf_generate(family):
    builders = {"qwen3": (tiny_qwen3, _hf_qwen3),
                "phi": (tiny_phi, _hf_phi),
                "opt": (tiny_opt, _hf_opt),
                "llama": (tiny_llama, _hf_llama),
                "gemma": (tiny_gemma, _hf_gemma),
                "mistral": (tiny_mistral, _hf_mistral)}
    mk_cfg, mk_model = builders[family]
    cfg = mk_cfg()
    model = mk_model(cfg)
    params = convert_state_dict(cfg, dict(model.state_dict()),
                                dtype=jnp.float32)
    rng = np.random.default_rng(3)
    prompt = rng.integers(2, cfg.vocab_size, 11).tolist()
    ref = _hf_greedy(model, prompt, N_NEW)
    got = _engine_greedy(cfg, params, prompt, N_NEW)
    assert got == ref, f"{family} greedy stream diverged from HF generate"


def test_sliding_window_stream_crosses_boundary():
    """Mistral with window 8 generating 3x past it: every decode step beyond
    token 8 attends a PARTIAL window whose start slides — any off-by-one in
    the window mask or cache ring shows up as a divergent token."""
    cfg = tiny_mistral()
    assert 0 < cfg.sliding_window < 12, "test needs a tiny window"
    model = _hf_mistral(cfg)
    params = convert_state_dict(cfg, dict(model.state_dict()),
                                dtype=jnp.float32)
    rng = np.random.default_rng(5)
    prompt = rng.integers(2, cfg.vocab_size, cfg.sliding_window + 3).tolist()
    n_new = 3 * cfg.sliding_window
    ref = _hf_greedy(model, prompt, n_new)
    for impl in ("xla", "pallas"):
        got = _engine_greedy(cfg, params, prompt, n_new,
                             attention_impl=impl)
        assert got == ref, f"window-crossing stream diverged ({impl})"


def test_moe_stream_matches_hf_with_router_ties():
    """MoE greedy stream parity — with EXACT router ties engineered: two
    experts share identical gate rows, so top-k must tie-break identically
    (lowest expert index) in torch and our jax router for streams to
    match."""
    import torch

    cfg = tiny_qwen3_moe()
    model = _hf_qwen3_moe(cfg)
    with torch.no_grad():
        for layer in model.model.layers:
            gate = layer.mlp.gate.weight          # [n_experts, hidden]
            gate[1].copy_(gate[0])                # experts 0 and 1 tie exactly
    params = convert_state_dict(cfg, dict(model.state_dict()),
                                dtype=jnp.float32)
    rng = np.random.default_rng(7)
    prompt = rng.integers(2, cfg.vocab_size, 9).tolist()
    ref = _hf_greedy(model, prompt, N_NEW)
    got = _engine_greedy(cfg, params, prompt, N_NEW)
    assert got == ref, "MoE stream diverged (router tie-breaking?)"


# ---------------------------------------------------------------------------
# Feature paths on the ragged pipeline vs HF (ISSUE 16): spec decode's
# accept/reject rule and the per-row LoRA operand must both be invisible in
# the greedy stream — pinned against torch, not against our own sync engine.
# ---------------------------------------------------------------------------

_RAGGED_FEATS = dict(page_size=32, decode_pipeline=1, ragged_attention=1,
                     ragged_features=1)


def test_spec_stream_on_ragged_pipeline_matches_hf_generate():
    """Spec decode is lossless for greedy decoding — and stays lossless now
    that verify rides the ragged pipeline (carry-generation handoff instead
    of a pre-spec drain). A repetitive prompt makes the n-gram drafter
    actually propose, so acceptance arithmetic is really exercised."""
    cfg = tiny_qwen3()
    model = _hf_qwen3(cfg)
    params = convert_state_dict(cfg, dict(model.state_dict()),
                                dtype=jnp.float32)
    prompt = [5, 9, 2, 11] * 5
    ref = _hf_greedy(model, prompt, N_NEW)
    eng = Engine(cfg, params, ServingConfig(
        weights_dtype="bf16", max_decode_slots=2, max_cache_len=128,
        prefill_buckets=(32,), dtype="float32", prefix_cache=False,
        decode_horizon=4, spec_decode=True, spec_k=4, spec_ngram=3,
        **_RAGGED_FEATS))
    req = eng.submit(Request(prompt_ids=list(prompt), max_tokens=N_NEW,
                             ignore_eos=True))
    for _ in range(10000):
        if not eng.step():
            break
    assert req.generated == ref, "spec-on-pipeline stream diverged from HF"
    assert eng.metrics.spec_drafted_tokens.total() > 0, \
        "drafter never proposed (test is vacuous)"


@pytest.mark.parametrize("n_prompt", [13, 45])
def test_stream_admitted_under_a_live_batch_matches_hf_generate(n_prompt):
    """A request admitted beside a live stream walks ``mixed_step`` (one
    chunk of 32, or two) and its FINAL chunk stays in flight: the slot
    joins the batch from the device carry and the first token goes out a
    dispatch later. The greedy stream is torch's, first token included —
    and so is the neighbour's, which decoded through every one of those
    dispatches."""
    cfg = tiny_qwen3()
    model = _hf_qwen3(cfg)
    params = convert_state_dict(cfg, dict(model.state_dict()),
                                dtype=jnp.float32)
    rng = np.random.default_rng(17)
    live_prompt = rng.integers(2, cfg.vocab_size, 9).tolist()
    prompt = rng.integers(2, cfg.vocab_size, n_prompt).tolist()
    eng = Engine(cfg, params, ServingConfig(
        weights_dtype="bf16", max_decode_slots=2, max_cache_len=128,
        prefill_buckets=(16, 32), dtype="float32", prefix_cache=False,
        decode_horizon=4, prefill_chunk=32, **_RAGGED_FEATS))
    live = eng.submit(Request(prompt_ids=list(live_prompt), max_tokens=60,
                              ignore_eos=True))
    for _ in range(5):
        eng.step()
    assert eng._inflight is not None    # or nothing rides mixed_step
    late = eng.submit(Request(prompt_ids=list(prompt), max_tokens=N_NEW,
                              ignore_eos=True))
    for _ in range(10000):
        if not eng.step():
            break
    assert eng.metrics.activations.value(path="in_flight") == 1
    assert late.generated == _hf_greedy(model, prompt, N_NEW), \
        "stream admitted through an in-flight final chunk diverged from HF"
    assert live.generated == _hf_greedy(model, live_prompt, 60)


def test_zero_b_lora_stream_on_ragged_pipeline_matches_hf_generate(tmp_path):
    """A zero-B adapter is algebraically a no-op: the tuned row — packed
    into the mixed dispatch via the per-row adapter-index operand, beside a
    base-weight neighbor — must reproduce the BASE model's HF greedy stream
    exactly. Catches adapter-delta leakage across packed rows."""
    from test_lora import _write_adapter

    cfg = tiny_qwen3()
    model = _hf_qwen3(cfg)
    params = convert_state_dict(cfg, dict(model.state_dict()),
                                dtype=jnp.float32)
    path = _write_adapter(tmp_path, "zero", cfg, zero_b=True)
    rng = np.random.default_rng(11)
    prompt = rng.integers(2, cfg.vocab_size, 13).tolist()
    ref = _hf_greedy(model, prompt, N_NEW)
    eng = Engine(cfg, params, ServingConfig(
        weights_dtype="bf16", max_decode_slots=2, max_cache_len=128,
        prefill_buckets=(16, 32), dtype="float32", prefix_cache=False,
        decode_horizon=4, **_RAGGED_FEATS), lora={"zero": path})
    tuned = eng.submit(Request(prompt_ids=list(prompt), max_tokens=N_NEW,
                               ignore_eos=True, lora="zero"))
    base = eng.submit(Request(prompt_ids=list(prompt), max_tokens=N_NEW,
                              ignore_eos=True))
    for _ in range(10000):
        if not eng.step():
            break
    assert tuned.generated == ref, "zero-B adapter bent the greedy stream"
    assert base.generated == ref, "base neighbor perturbed by adapter row"


# ---------------------------------------------------------------------------
# Chat-template renders vs HF apply_chat_template (same shipped Jinja)
# ---------------------------------------------------------------------------

MSGS = [
    {"role": "system", "content": "Be terse."},
    {"role": "user", "content": "hi"},
    {"role": "assistant", "content": "yo"},
    {"role": "user", "content": "bye?"},
]


def _configmap_template(path):
    import yaml

    with open(path) as fh:
        doc = yaml.safe_load(fh)
    [(_, tpl)] = doc["data"].items()
    return tpl


def _hf_render(template, messages, add_generation_prompt):
    """Render through HF's own chat-template engine (the vLLM-side behavior
    our ChatTemplater replaces)."""
    from tokenizers import Tokenizer, models
    from transformers import PreTrainedTokenizerFast

    tok = PreTrainedTokenizerFast(tokenizer_object=Tokenizer(models.BPE()),
                                  chat_template=template)
    return tok.apply_chat_template(messages, tokenize=False,
                                   add_generation_prompt=add_generation_prompt)


@pytest.mark.parametrize("name", ["qwen", "phi", "opt", "llama", "gemma"])
@pytest.mark.parametrize("gen", [True, False])
def test_shipped_templates_match_hf_apply_chat_template(name, gen):
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "templates",
                        f"{name}-chat-template.yaml")
    tpl = _configmap_template(path)
    from aws_k8s_ansible_provisioner_tpu.serving.chat_template import (
        ChatTemplater)

    import jinja2

    env = jinja2.Environment(keep_trailing_newline=True)
    msgs = MSGS
    if name in ("llama", "gemma"):
        # these shipped templates fold no system turn; drop it for both sides
        msgs = MSGS[1:]
    ours = env.from_string(tpl).render(messages=msgs,
                                       add_generation_prompt=gen)
    theirs = _hf_render(tpl, msgs, gen)
    assert ours == theirs, f"{name} template renders differently under HF"


def test_templater_file_render_matches_hf(tmp_path):
    """End-to-end: ChatTemplater loading the shipped qwen template file must
    byte-match HF's rendering of the same source."""
    import os

    tpl = _configmap_template(
        os.path.join(os.path.dirname(__file__), "..", "templates",
                     "qwen-chat-template.yaml"))
    f = tmp_path / "t.jinja"
    f.write_text(tpl)
    from aws_k8s_ansible_provisioner_tpu.serving.chat_template import (
        ChatTemplater)

    t = ChatTemplater("Qwen/Qwen3-0.6B", template_path=str(f))
    assert t.render(MSGS, add_generation_prompt=True) == \
        _hf_render(tpl, MSGS, True)
