"""LockSan (serving/locksan.py) tests.

The inversion tests are DETERMINISTIC: a lock-order cycle is a property of
the acquisition-order graph, not of thread timing, so a single thread that
performs A->B then B->A is enough to close the cycle — no racing, no
sleeps, no flakes. The shared-write tests use two real threads but join
them before asserting, so both writes have definitely happened.

The seeded-parity test is satellite (f) of the tpulint ISSUE: the
sanitizer must be a pure observer — byte-identical seeded streamed and
unary responses with the sanitizer on vs off.
"""

import json
import threading
import urllib.request

import pytest

from aws_k8s_ansible_provisioner_tpu.serving import locksan

pytestmark = pytest.mark.locksan_smoke

MODEL_NAME = "tiny-qwen3"


@pytest.fixture()
def san():
    """locksan installed for the test, prior state restored after."""
    was = locksan.installed()
    locksan.install()
    locksan.reset()
    yield locksan
    locksan.reset()
    if not was:
        locksan.uninstall()


# ---------------------------------------------------------------------------
# lock-order inversion
# ---------------------------------------------------------------------------


def test_two_lock_inversion_caught_deterministically(san):
    a = san.tracked_lock(site="synthetic.py:1")
    b = san.tracked_lock(site="synthetic.py:2")
    with a:
        with b:
            pass
    assert san.violations() == []       # one order alone is fine
    with b:
        with a:                          # closes the cycle
            pass
    vs = san.violations()
    assert len(vs) == 1
    assert vs[0]["kind"] == "lock-order-inversion"
    assert "synthetic.py:1" in vs[0]["detail"]
    assert "synthetic.py:2" in vs[0]["detail"]


def test_inversion_report_is_reproducible(san):
    """Same program -> same report, run twice."""

    def provoke():
        a = san.tracked_lock(site="repro.py:1")
        b = san.tracked_lock(site="repro.py:2")
        with a, b:
            pass
        with b, a:
            pass
        out = san.report()
        san.reset()
        return out

    assert provoke() == provoke()


def test_inversion_across_threads(san):
    """The graph is global: thread 1 establishes A->B, thread 2's B->A
    closes the cycle. Handshake events order the two acquisitions, so the
    detection is still deterministic."""
    a = san.tracked_lock(site="xthread.py:1")
    b = san.tracked_lock(site="xthread.py:2")
    first_done = threading.Event()

    def t1():
        with a:
            with b:
                pass
        first_done.set()

    def t2():
        first_done.wait(10)
        with b:
            with a:
                pass

    ts = [threading.Thread(target=t1), threading.Thread(target=t2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    vs = san.violations()
    assert len(vs) == 1 and vs[0]["kind"] == "lock-order-inversion"


def test_consistent_order_and_rlock_reentry_are_clean(san):
    a = san.tracked_lock(site="clean.py:1")
    b = san.tracked_lock(site="clean.py:2")
    r = san.tracked_lock(reentrant=True, site="clean.py:3")
    for _ in range(3):
        with a, b:                       # always the same order
            pass
    with r:
        with r:                          # re-entry is not an ordering
            with a:
                pass
    assert san.violations() == []


def test_three_lock_cycle_caught(san):
    """A->B, B->C, C->A: no PAIR inverts, the cycle only exists globally."""
    a = san.tracked_lock(site="tri.py:1")
    b = san.tracked_lock(site="tri.py:2")
    c = san.tracked_lock(site="tri.py:3")
    with a, b:
        pass
    with b, c:
        pass
    assert san.violations() == []
    with c, a:
        pass
    vs = san.violations()
    assert len(vs) == 1 and vs[0]["kind"] == "lock-order-inversion"


# ---------------------------------------------------------------------------
# serving/ construction sites get wrapped locks; stdlib does not
# ---------------------------------------------------------------------------


def test_serving_lock_sites_are_wrapped_stdlib_is_not(san):
    import queue

    from aws_k8s_ansible_provisioner_tpu.serving.metrics import Counter

    m = Counter("tpu_serve_locksan_probe", "probe")   # serving/metrics.py
    assert isinstance(m._lock, locksan._SanLock)
    assert "serving/metrics.py" in m._lock.site
    q = queue.Queue()                                  # stdlib caller
    assert not isinstance(q.mutex, locksan._SanLock)
    ev = threading.Event()                             # threading.py caller
    assert not isinstance(getattr(ev._cond, "_lock", None), locksan._SanLock)


# ---------------------------------------------------------------------------
# watched attributes (dynamic R5)
# ---------------------------------------------------------------------------


class _Shared:
    _R5_THREAD_OWNED = ()

    def __init__(self):
        self.counter = 0


def _lock_for(obj, san):
    obj._lock = san.tracked_lock(site="watch.py:1")


def test_unguarded_write_from_two_threads_flagged(san):
    undo = san.watch_attrs(_Shared, attrs=("counter",))
    try:
        obj = _Shared()
        _lock_for(obj, san)
        t = threading.Thread(target=lambda: setattr(obj, "counter", 2))
        t.start()
        t.join(10)
        obj.counter = 3                  # second distinct unguarded writer
        vs = san.violations()
        assert len(vs) == 1
        assert vs[0]["kind"] == "unguarded-shared-write"
        assert "counter" in vs[0]["detail"]
    finally:
        undo()


def test_guarded_writes_from_two_threads_are_clean(san):
    undo = san.watch_attrs(_Shared, attrs=("counter",))
    try:
        obj = _Shared()
        _lock_for(obj, san)

        def write():
            with obj._lock:
                obj.counter += 1

        t = threading.Thread(target=write)
        t.start()
        t.join(10)
        write()
        assert obj.counter == 2          # descriptor stores values normally
        assert san.violations() == []
    finally:
        undo()


def test_single_thread_unguarded_writes_are_clean(san):
    """One writer thread is the single-writer contract — not a violation."""
    undo = san.watch_attrs(_Shared, attrs=("counter",))
    try:
        obj = _Shared()
        _lock_for(obj, san)
        for i in range(5):
            obj.counter = i
        assert san.violations() == []
    finally:
        undo()


# ---------------------------------------------------------------------------
# satellite (f): sanitizer is a pure observer — byte-identical seeded
# responses with LockSan on vs off
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def parity_server():
    import jax
    import jax.numpy as jnp

    from aws_k8s_ansible_provisioner_tpu.config import (
        ServingConfig, tiny_qwen3)
    from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
    from aws_k8s_ansible_provisioner_tpu.serving.server import (
        build_state, serve)
    from aws_k8s_ansible_provisioner_tpu.utils.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    cfg = tiny_qwen3(vocab_size=tok.vocab_size, eos_token_id=tok.eos_token_id)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    serving = ServingConfig(weights_dtype="bf16", model=MODEL_NAME,
                            max_decode_slots=4, max_cache_len=128,
                            prefill_buckets=(16, 32, 64), dtype="float32")
    state = build_state(serving, model_cfg=cfg, params=params, tokenizer=tok)
    ready, stop = threading.Event(), threading.Event()
    t = threading.Thread(target=serve,
                         args=(state, "127.0.0.1", 18310, ready, stop),
                         daemon=True)
    t.start()
    assert ready.wait(30)
    yield "http://127.0.0.1:18310"
    stop.set()


def _post_raw(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return r.read()


def _scrub(obj: dict) -> dict:
    obj.pop("id", None)
    obj.pop("created", None)
    if isinstance(obj.get("usage"), dict):      # per-request trace identity
        obj["usage"].pop("trace_id", None)
        obj["usage"].pop("span_id", None)
    return obj


def _strip_volatile(raw: bytes, stream: bool) -> bytes:
    """Response bytes minus the per-request id, wall-clock created stamp and
    trace/span ids (all differ across ANY two requests, sanitizer or not)."""
    if not stream:
        return json.dumps(_scrub(json.loads(raw)), sort_keys=True).encode()
    # ... and minus the GROUPING of the tokens into events: an event
    # carries what one dispatch gave the stream, and with a slot free a
    # dispatch runs a measured few substeps (EnginePrograms._decode_horizon)
    # — the text, the ids in order and the finish are what must not move
    text, ids, other = "", [], []
    for line in raw.split(b"\n"):
        if line.startswith(b"data: ") and line != b"data: [DONE]":
            obj = _scrub(json.loads(line[len(b"data: "):]))
            for ch in obj.get("choices", []):
                text += ch.pop("text", "")
                ids += ch.pop("token_ids", None) or []
            if obj not in other:
                other.append(obj)
        elif line and line not in other:
            other.append(line.decode())
    return json.dumps([text, ids, other], sort_keys=True).encode()


def test_seeded_responses_byte_identical_with_locksan_on_vs_off(
        parity_server):
    payload = {"model": MODEL_NAME, "prompt": "locksan parity", "seed": 777,
               "temperature": 0.8, "max_tokens": 12, "ignore_eos": True}
    was = locksan.installed()
    try:
        locksan.install()
        on_unary = _strip_volatile(
            _post_raw(parity_server + "/v1/completions", payload), False)
        on_stream = _strip_volatile(
            _post_raw(parity_server + "/v1/completions",
                      {**payload, "stream": True}), True)
        assert locksan.violations() == []
        locksan.uninstall()
        off_unary = _strip_volatile(
            _post_raw(parity_server + "/v1/completions", payload), False)
        off_stream = _strip_volatile(
            _post_raw(parity_server + "/v1/completions",
                      {**payload, "stream": True}), True)
    finally:
        locksan.uninstall()
        if was:
            locksan.install()
    assert on_unary == off_unary
    assert on_stream == off_stream
    assert b'"text"' in on_unary and b"data: [DONE]" in on_stream
