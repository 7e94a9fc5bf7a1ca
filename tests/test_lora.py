"""Multi-LoRA serving (models/lora.py; vLLM --enable-lora parity).

The properties that matter: (1) the batched per-slot gather applies each
slot's OWN adapter — a mixed batch reproduces every request's solo stream;
(2) math parity — an adapter stream equals the base model with W + A·B·s
pre-merged into its weights; (3) the peft checkpoint format round-trips
(written BY peft itself, loaded by our loader, streams matched against the
peft-wrapped torch model); (4) the HTTP surface serves adapters as model
ids.
"""

import json
import threading
import urllib.request

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models import convert_state_dict
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.models.lora import (TARGET_MAP,
                                                         load_adapter)
from aws_k8s_ansible_provisioner_tpu.serving.engine import Engine, Request

CFG = tiny_qwen3()


def _write_adapter(tmp_path, name, cfg, rank=4, alpha=8, seed=0,
                   targets=("q_proj", "v_proj", "up_proj"), zero_b=False):
    """Write a peft-format adapter dir by hand (safetensors + config)."""
    from safetensors import numpy as st_np

    rng = np.random.default_rng(seed)
    d = tmp_path / name
    d.mkdir()
    (d / "adapter_config.json").write_text(json.dumps({
        "peft_type": "LORA", "r": rank, "lora_alpha": alpha,
        "target_modules": list(targets),
    }))
    dims = {"q_proj": (cfg.q_size, cfg.hidden_size),
            "k_proj": (cfg.kv_size, cfg.hidden_size),
            "v_proj": (cfg.kv_size, cfg.hidden_size),
            "o_proj": (cfg.hidden_size, cfg.q_size),
            "gate_proj": (cfg.intermediate_size, cfg.hidden_size),
            "up_proj": (cfg.intermediate_size, cfg.hidden_size),
            "down_proj": (cfg.hidden_size, cfg.intermediate_size)}
    tensors = {}
    for layer in range(cfg.num_layers):
        for t in targets:
            dout, din = dims[t]
            mod = "self_attn" if t.endswith(("q_proj", "k_proj", "v_proj",
                                             "o_proj")) else "mlp"
            base = (f"base_model.model.model.layers.{layer}.{mod}.{t}")
            tensors[f"{base}.lora_A.weight"] = \
                (0.3 * rng.standard_normal((rank, din))).astype(np.float32)
            b = np.zeros((dout, rank), np.float32) if zero_b else \
                (0.3 * rng.standard_normal((dout, rank))).astype(np.float32)
            tensors[f"{base}.lora_B.weight"] = b
    st_np.save_file(tensors, str(d / "adapter_model.safetensors"))
    return str(d)


def _serving(**over):
    base = dict(max_decode_slots=4, max_cache_len=64, prefill_buckets=(16,),
                dtype="float32", prefix_cache=False, decode_horizon=4)
    base.update(over)
    return ServingConfig(weights_dtype="bf16", **base)


def _stream(eng, prompt, n=16, **kw):
    req = eng.submit(Request(prompt_ids=list(prompt), max_tokens=n,
                             ignore_eos=True, **kw))
    for _ in range(10000):
        if not eng.step():
            break
    return req.generated


PROMPT = [5, 9, 2, 11, 7]


def test_zero_b_adapter_equals_base(tmp_path):
    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    path = _write_adapter(tmp_path, "zero", CFG, zero_b=True)
    eng = Engine(CFG, params, _serving(), lora={"zero": path})
    base = _stream(eng, PROMPT)
    adapted = _stream(eng, PROMPT, lora="zero")
    assert adapted == base


def test_adapter_equals_merged_weights(tmp_path):
    """x@W + (x@A)@B·s must produce the same stream as pre-merging
    W + A@B·s into the base weights — the LoRA math ground truth."""
    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    path = _write_adapter(tmp_path, "ad", CFG, seed=3)
    ad = load_adapter(path)

    merged = jax.tree.map(lambda x: x, params)
    layers = dict(merged["layers"])
    for target, (A, B) in ad["targets"].items():
        sub = dict(layers[target])
        sub["kernel"] = sub["kernel"] + jnp.einsum(
            "lir,lro->lio", jnp.asarray(A), jnp.asarray(B))
        layers[target] = sub
    merged["layers"] = layers

    eng_l = Engine(CFG, params, _serving(), lora={"ad": path})
    eng_m = Engine(CFG, merged, _serving())
    got = _stream(eng_l, PROMPT, lora="ad")
    ref = _stream(eng_m, PROMPT)
    assert got == ref


def test_mixed_batch_each_slot_own_adapter(tmp_path):
    """Three slots — base, adapter A, adapter B — in ONE continuous batch
    must each reproduce their solo streams (the per-slot gather is the
    whole point of multi-LoRA)."""
    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    pa = _write_adapter(tmp_path, "a", CFG, seed=1)
    pb = _write_adapter(tmp_path, "b", CFG, seed=2,
                        targets=("q_proj", "o_proj", "down_proj"), rank=2)
    lora = {"a": pa, "b": pb}
    solo = {}
    for name in (None, "a", "b"):
        eng = Engine(CFG, params, _serving(), lora=lora)
        solo[name] = _stream(eng, PROMPT, lora=name)
    assert solo["a"] != solo[None] and solo["b"] != solo[None]

    eng = Engine(CFG, params, _serving(), lora=lora)
    reqs = [eng.submit(Request(prompt_ids=list(PROMPT), max_tokens=16,
                               ignore_eos=True, lora=name))
            for name in (None, "a", "b")]
    for _ in range(10000):
        if not eng.step():
            break
    assert reqs[0].generated == solo[None]
    assert reqs[1].generated == solo["a"]
    assert reqs[2].generated == solo["b"]


def test_unknown_adapter_rejected(tmp_path):
    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    eng = Engine(CFG, params, _serving())
    with pytest.raises(ValueError, match="unknown LoRA"):
        eng.submit(Request(prompt_ids=PROMPT, lora="nope"))


def test_peft_written_adapter_hf_stream_parity(tmp_path):
    """peft writes the adapter; our loader + engine must match the
    peft-wrapped torch model's greedy stream token for token."""
    import torch
    from peft import LoraConfig, get_peft_model

    from test_model_parity import _hf_qwen3

    model = _hf_qwen3(CFG)
    # convert the BASE weights before wrapping: get_peft_model mutates the
    # module in place, renaming every targeted weight to *.base_layer.*
    params = convert_state_dict(CFG, dict(model.state_dict()),
                                dtype=jnp.float32)
    lcfg = LoraConfig(r=4, lora_alpha=16, lora_dropout=0.0,
                      target_modules=["q_proj", "k_proj", "v_proj", "o_proj",
                                      "gate_proj", "up_proj", "down_proj"],
                      init_lora_weights=False)   # random A AND B
    torch.manual_seed(7)
    pm = get_peft_model(model, lcfg)
    pm.save_pretrained(str(tmp_path / "peft_ad"))
    eng = Engine(CFG, params, _serving(),
                 lora={"tuned": str(tmp_path / "peft_ad" / "default")
                       if (tmp_path / "peft_ad" / "default").exists()
                       else str(tmp_path / "peft_ad")})
    got = _stream(eng, PROMPT, n=20, lora="tuned")

    with torch.no_grad():
        out = pm(torch.tensor([PROMPT + got[:-1]])).logits
    # teacher-forced argmax of the peft model over our stream: every step's
    # argmax must equal the token we generated
    preds = out[0, len(PROMPT) - 1:].argmax(-1).tolist()
    assert got == preds, "peft-adapter stream diverged from torch"


def test_http_serves_adapters_as_models(tmp_path):
    from aws_k8s_ansible_provisioner_tpu.serving.server import (build_state,
                                                                serve)
    from aws_k8s_ansible_provisioner_tpu.utils.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    cfg = tiny_qwen3(vocab_size=tok.vocab_size, eos_token_id=tok.eos_token_id)
    path = _write_adapter(tmp_path, "styl", cfg, seed=5)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    serving = ServingConfig(weights_dtype="bf16", model="base-model", max_decode_slots=2,
                            max_cache_len=64, prefill_buckets=(16,),
                            dtype="float32",
                            lora_adapters=(f"styl={path}",))
    state = build_state(serving, model_cfg=cfg, params=params, tokenizer=tok)
    ready, stop = threading.Event(), threading.Event()
    threading.Thread(target=serve,
                     args=(state, "127.0.0.1", 18425, ready, stop),
                     daemon=True).start()
    assert ready.wait(30)
    with urllib.request.urlopen("http://127.0.0.1:18425/v1/models",
                                timeout=30) as r:
        ids = [m["id"] for m in json.loads(r.read())["data"]]
    assert ids == ["base-model", "styl"]
    body = json.dumps({"model": "styl", "prompt": "hi", "max_tokens": 4,
                       "ignore_eos": True}).encode()
    req = urllib.request.Request("http://127.0.0.1:18425/v1/completions",
                                 data=body,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        resp = json.loads(r.read())
    assert resp["model"] == "styl"
    assert resp["usage"]["completion_tokens"] == 4
    stop.set()


@pytest.mark.parametrize("page_size", [8, 16])
def test_prefix_cache_never_crosses_adapters(tmp_path, page_size):
    """KV rows projected under adapter A must never prefix-hit a request on
    adapter B or the base (review r5: token-only cache keys served A's
    wq/wk/wv projections to B). Same shared prompt, different adapters —
    streams must equal their cache-cold solo runs, and same-adapter reuse
    must still hit."""
    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    pa = _write_adapter(tmp_path, "a", CFG, seed=1)
    pb = _write_adapter(tmp_path, "b", CFG, seed=2)
    lora = {"a": pa, "b": pb}
    shared = list(range(2, 2 + 40))        # >= 2 pages at either page size

    def serving():
        return _serving(prefix_cache=True, page_size=page_size,
                        max_cache_len=128, prefill_buckets=(16, 64),
                        prefix_reuse_min_pages=1)

    solo = {}
    for name in ("a", "b", None):
        eng = Engine(CFG, params, serving(), lora=lora)
        solo[name] = _stream(eng, shared, lora=name)

    eng = Engine(CFG, params, serving(), lora=lora)
    first = _stream(eng, shared, lora="a")           # seeds the cache
    assert first == solo["a"]
    hits0 = eng.metrics.prefix_cache_hits.total()
    cross = _stream(eng, shared, lora="b")           # must NOT reuse a's rows
    assert cross == solo["b"], "adapter b reused adapter a's KV"
    base = _stream(eng, shared, lora=None)
    assert base == solo[None], "base reused an adapter's KV"
    again = _stream(eng, shared, lora="a")           # same-adapter: may reuse
    assert again == solo["a"]
    assert eng.metrics.prefix_cache_hits.total() > hits0, \
        "same-adapter reuse should still prefix-hit"


def test_spec_decode_verifies_with_adapter(tmp_path):
    """The spec verify dispatch carries the slot's adapter index: a
    repetitive greedy prompt under prompt-lookup speculation must emit the
    adapter's exact plain-decode stream (a base-model verify would accept
    different tokens), with drafts actually proposed."""
    import dataclasses

    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    path = _write_adapter(tmp_path, "ad", CFG, seed=4)
    pat = [5, 6, 7]
    prompt = pat * 5
    base_cfg = _serving()
    plain = Engine(CFG, params, base_cfg, lora={"ad": path})
    ref = _stream(plain, prompt, n=20, lora="ad")

    spec_cfg = dataclasses.replace(base_cfg, spec_decode=True, spec_k=4,
                                   spec_ngram=3)
    eng = Engine(CFG, params, spec_cfg, lora={"ad": path})
    got = _stream(eng, prompt, n=20, lora="ad")
    assert got == ref, "spec verify diverged under the adapter"
    assert eng.metrics.spec_drafted_tokens.total() > 0
