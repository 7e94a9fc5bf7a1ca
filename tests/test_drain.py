"""Replica lifecycle: graceful drain (ISSUE r8).

Three layers, matching how production exercises them:

1. The DRAIN STATE MACHINE itself, unit-level against a bare Engine (no
   sockets): a draining engine sheds new submits with the structured
   "draining" reason, finishes active requests, and past drain_timeout_s
   cancels stragglers through the EXISTING deadline path — slot accounting
   (SchedulerStats) proves exactly-once release.
2. The HTTP surface: /admin/drain + /admin/undrain flip /readyz, /healthz
   and /load, and completions shed 503 + X-TPU-Draining (the marker the
   router re-routes on without dead-marking).
3. The PROCESS contract (the chaos-test acceptance gate): SIGTERM to a real
   serving subprocess under an active stream exits 0 within
   drain_timeout_s with the stream finished — zero dropped in-flight
   requests.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.serving.engine import (
    Engine, EngineOverloaded, Request)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_qwen3()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **over):
    base = dict(weights_dtype="bf16", max_decode_slots=4, max_cache_len=64,
                prefill_buckets=(8, 16, 32), dtype="float32",
                drain_timeout_s=30.0)
    base.update(over)
    return Engine(cfg, params, ServingConfig(**base))


def _run(engine, max_steps=10000):
    for _ in range(max_steps):
        if not engine.step():
            break


# ---------------------------------------------------------------------------
# 1. drain state machine (no sockets)
# ---------------------------------------------------------------------------


def test_draining_engine_sheds_new_submits(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    shed0 = eng.metrics.requests_shed.total()
    t = eng.begin_drain()
    assert t == pytest.approx(30.0, abs=1.0)
    with pytest.raises(EngineOverloaded) as ei:
        eng.submit(Request(prompt_ids=[1, 2, 3], max_tokens=4))
    assert ei.value.reason == "draining"
    assert ei.value.retry_after_s >= 1.0
    assert eng.metrics.requests_shed.total() == shed0 + 1
    # undrain: admissions resume
    eng.end_drain()
    req = eng.submit(Request(prompt_ids=[1, 2, 3], max_tokens=4,
                             ignore_eos=True))
    _run(eng)
    assert req.finish_reason == "length"


def test_drain_finishes_active_requests(setup):
    """In-flight work runs to completion during a drain; the engine
    quiesces with clean slot accounting."""
    cfg, params = setup
    eng = _engine(cfg, params)
    reqs = [eng.submit(Request(prompt_ids=[2 + i, 5, 9], max_tokens=6,
                               ignore_eos=True)) for i in range(3)]
    eng.step()                      # admit (batched prefill)
    eng.begin_drain()               # drain with 3 active generations
    _run(eng)
    for r in reqs:
        assert r.finish_reason == "length"
        assert len(r.generated) == 6
    st = eng.sched.stats()
    assert st.active_slots == 0 and st.queue_depth == 0
    assert eng.draining             # still draining (no auto-undrain)


def test_drain_timeout_cancels_stragglers_exactly_once(setup):
    """Past drain_timeout_s the deadline reaper cancels stragglers: finish
    "timeout", deadline_expired counted once each, slots/pages released
    exactly once (SchedulerStats), queued requests answered too."""
    cfg, params = setup
    eng = _engine(cfg, params, max_decode_slots=2)
    active = [eng.submit(Request(prompt_ids=[3, 1, 4], max_tokens=40,
                                 ignore_eos=True)) for _ in range(2)]
    eng.step()                      # both admitted
    queued = eng.submit(Request(prompt_ids=[2, 7], max_tokens=40,
                                ignore_eos=True))
    d0 = eng.metrics.deadline_expired.total()
    eng.begin_drain(timeout_s=0.05)
    time.sleep(0.08)                # let the drain deadline pass
    _run(eng)
    for r in active:
        assert r.finish_reason == "timeout"
        assert 0 < len(r.generated) < 40     # it ran, then was cancelled
    assert queued.finish_reason == "timeout"
    assert eng.metrics.deadline_expired.total() == d0 + 3
    st = eng.sched.stats()
    assert st.active_slots == 0 and st.queue_depth == 0
    # exactly-once: every slot free again, a second reap pass is a no-op
    eng._reap_expired()
    assert eng.metrics.deadline_expired.total() == d0 + 3
    assert all(not p for p in eng._slot_pages)


def test_drain_deadline_tightens_not_loosens(setup):
    """A request whose own deadline is EARLIER than the drain deadline
    keeps it (drain never extends anyone's budget)."""
    cfg, params = setup
    eng = _engine(cfg, params)
    r = Request(prompt_ids=[1, 2], max_tokens=4, deadline_s=1.0)
    eng.submit(r)
    eng.begin_drain(timeout_s=500.0)
    assert eng._effective_deadline(r) == pytest.approx(r.t_deadline)
    r2 = Request(prompt_ids=[1], max_tokens=4)
    r2.t_deadline = 0.0             # no own deadline -> drain deadline rules
    assert eng._effective_deadline(r2) == pytest.approx(eng._drain_deadline)


# ---------------------------------------------------------------------------
# 2. HTTP surface
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def server():
    from aws_k8s_ansible_provisioner_tpu.serving.server import (
        build_state, serve)
    from aws_k8s_ansible_provisioner_tpu.utils.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    cfg = tiny_qwen3(vocab_size=tok.vocab_size,
                     eos_token_id=tok.eos_token_id)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    serving = ServingConfig(weights_dtype="bf16", model="tiny-qwen3",
                            max_decode_slots=4, max_cache_len=128,
                            prefill_buckets=(16, 32, 64), dtype="float32")
    state = build_state(serving, model_cfg=cfg, params=params, tokenizer=tok)
    ready, stop = threading.Event(), threading.Event()
    port = 18460
    t = threading.Thread(target=serve,
                         args=(state, "127.0.0.1", port, ready, stop),
                         daemon=True)
    t.start()
    assert ready.wait(30)
    yield f"http://127.0.0.1:{port}", state
    stop.set()


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _post(url, payload, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def test_admin_drain_flips_readiness_and_sheds(server):
    url, state = server
    assert _get(url + "/readyz")[0] == 200
    # exit:false = rotation-removal drain (keeps the test server alive)
    code, body, _ = _post(url + "/admin/drain", {"exit": False})
    assert code == 200 and body["status"] == "draining"
    try:
        code, body, hdrs = _get(url + "/readyz")
        assert code == 503 and hdrs.get("X-TPU-Draining") == "1"
        code, body, _ = _get(url + "/healthz")
        assert code == 200 and body["status"] == "draining"
        assert body["draining"] is True
        code, body, _ = _get(url + "/load")
        assert code == 200 and body["draining"] is True
        # new completions shed 503 with the router's re-route marker
        code, body, hdrs = _post(url + "/v1/completions",
                                 {"model": "tiny-qwen3", "prompt": "x",
                                  "max_tokens": 4})
        assert code == 503
        assert hdrs.get("X-TPU-Draining") == "1"
        assert body["error"]["code"] == "draining"
        assert "Retry-After" in hdrs
    finally:
        code, body, _ = _post(url + "/admin/undrain", {})
        assert code == 200
    assert _get(url + "/readyz")[0] == 200
    code, body, _ = _post(url + "/v1/completions",
                          {"model": "tiny-qwen3", "prompt": "y",
                           "max_tokens": 4})
    assert code == 200


# ---------------------------------------------------------------------------
# 3. SIGTERM process contract (the chaos acceptance gate)
# ---------------------------------------------------------------------------


def test_sigterm_drains_and_exits_zero_with_streams_intact():
    """SIGTERM under an active stream: the stream finishes ([DONE] seen,
    full token budget), new work sheds 503 draining, and the process exits
    0 within drain_timeout_s — zero dropped in-flight requests."""
    port = 18461
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "aws_k8s_ansible_provisioner_tpu.serving.server",
         "--model", "tiny-qwen3", "--platform", "cpu", "--no-warmup",
         "--max-decode-slots", "4", "--max-cache-len", "256",
         "--port", str(port), "--drain-timeout", "30"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz", timeout=2) as r:
                    if r.status == 200:
                        break
            except OSError:
                time.sleep(0.5)
        else:
            pytest.fail("server subprocess never became healthy")

        result = {}
        first_chunk = threading.Event()
        budget = 110     # near the tiny model's max_len=128 window: long
                         # enough that SIGTERM (sent at the FIRST chunk,
                         # not after a fixed sleep) lands mid-decode even
                         # on a fast idle machine — the old fixed 1s sleep
                         # raced a sub-second stream: the drain exited
                         # before the 503 probe, which then saw an RST

        def client():
            body = json.dumps({"model": "tiny-qwen3", "prompt": "drain me",
                               "max_tokens": budget, "stream": True,
                               "ignore_eos": True}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/completions", data=body,
                headers={"Content-Type": "application/json"})
            chunks = []
            with urllib.request.urlopen(req, timeout=120) as r:
                for line in r:
                    chunks.append(line.decode())
                    first_chunk.set()
            result["raw"] = "".join(chunks)

        t = threading.Thread(target=client, daemon=True)
        t.start()
        # synchronize on the stream ACTUALLY decoding, then signal at once
        assert first_chunk.wait(60), "stream produced no output"
        proc.send_signal(signal.SIGTERM)
        time.sleep(0.05)             # let the handler arm the drain flag
        # a NEW request during the drain is shed with the routable 503
        code, _, hdrs = _post(f"http://127.0.0.1:{port}/v1/completions",
                              {"model": "tiny-qwen3", "prompt": "new",
                               "max_tokens": 4}, timeout=10)
        assert code == 503 and hdrs.get("X-TPU-Draining") == "1"
        t.join(timeout=90)
        assert not t.is_alive(), "in-flight stream never finished"
        assert "data: [DONE]" in result["raw"]
        # the stream ran to its FULL budget — nothing was cut by the drain
        fins = [json.loads(ln[6:]) for ln in result["raw"].splitlines()
                if ln.startswith("data: ") and ln != "data: [DONE]"]
        finish = [c.get("finish_reason") for o in fins
                  for c in o.get("choices", []) if c.get("finish_reason")]
        assert finish == ["length"]
        n_ids = sum(len(c.get("token_ids") or []) for o in fins
                    for c in o.get("choices", []))
        assert n_ids == budget
        rc = proc.wait(timeout=40)
        assert rc == 0, f"exit code {rc}"
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
