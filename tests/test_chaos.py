"""Fault-injection suite (serving/chaos.py): every injected fault must
produce its DOCUMENTED degradation behavior — correct status code, slot/page
release verified via SchedulerStats, a metrics increment — with zero process
crashes. The faults and their contracts are tabled in chaos.py's docstring
and README.md's "Failure modes and degradation behavior" section.

Chaos state is process-global, so tests that arm the controller use
function-scoped engines/servers (torn down before the next test) and
``_chaos.reset()`` around themselves — no background stepper may consume
another test's firings.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from aws_k8s_ansible_provisioner_tpu.config import ServingConfig, tiny_qwen3
from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
from aws_k8s_ansible_provisioner_tpu.serving import chaos as _chaos
from aws_k8s_ansible_provisioner_tpu.serving.engine import (
    Engine, EngineOverloaded, Request)
from aws_k8s_ansible_provisioner_tpu.serving.server import build_state, serve
from aws_k8s_ansible_provisioner_tpu.utils.tokenizer import ByteTokenizer

MODEL = "tiny-qwen3"
_PORTS = iter(range(18300, 18400))


@pytest.fixture(autouse=True)
def fresh_chaos():
    _chaos.reset()
    yield
    _chaos.reset()


def _mk_engine(**over):
    tok = ByteTokenizer()
    cfg = tiny_qwen3(vocab_size=tok.vocab_size, eos_token_id=tok.eos_token_id)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    base = dict(weights_dtype="bf16", model=MODEL, max_decode_slots=2,
                max_cache_len=128, page_size=32,
                prefill_buckets=(16, 32, 64, 128), dtype="float32",
                derived_seed=0)
    base.update(over)
    return Engine(cfg, params, ServingConfig(**base)), tok


def _drain(eng, reqs, limit_s=120.0):
    t0 = time.monotonic()
    while any(not r.finish_reason for r in reqs):
        eng.step()
        assert time.monotonic() - t0 < limit_s, "engine failed to drain"


@pytest.fixture()
def http_server(request):
    """Function-scoped HTTP server factory; every server (and its engine
    thread) stops at teardown so no background stepper leaks into the next
    test's chaos state."""
    stops = []

    def make(**over):
        tok = ByteTokenizer()
        cfg = tiny_qwen3(vocab_size=tok.vocab_size,
                         eos_token_id=tok.eos_token_id)
        params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        base = dict(weights_dtype="bf16", model=MODEL, max_decode_slots=2,
                    max_cache_len=128, page_size=32,
                    prefill_buckets=(16, 32, 64, 128), dtype="float32",
                    derived_seed=0)
        base.update(over)
        state = build_state(ServingConfig(**base), model_cfg=cfg,
                            params=params, tokenizer=tok)
        port = next(_PORTS)
        ready, stop = threading.Event(), threading.Event()
        threading.Thread(target=serve,
                         args=(state, "127.0.0.1", port, ready, stop),
                         daemon=True).start()
        assert ready.wait(10)
        stops.append(stop)
        return state, port

    yield make
    for s in stops:
        s.set()
    time.sleep(0.1)   # let engine threads observe the stop


def _post(port, payload, path="/v1/completions", headers=None, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps({"model": MODEL, **payload}).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return r.status, json.loads(r.read())


def _settled(eng, timeout_s=30.0):
    """Wait for the engine to fully quiesce; returns SchedulerStats."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = eng.sched.stats()
        if st.active_slots == 0 and st.queue_depth == 0 \
                and not eng.pending and eng._chunk is None:
            return st
        time.sleep(0.05)
    raise AssertionError(f"engine never settled: {eng.sched.stats()}")


def _assert_released(eng, n_terminal=None):
    """Slot/page release accounting over everything submitted so far.

    ``n_terminal`` asserts the exactly-once equation finished + cancelled ==
    terminal notifications; pass it only when no preemption/requeue happened
    (each of those releases-and-readmits the same request, which the
    scheduler's totals count again by design)."""
    st = _settled(eng)
    assert st.active_slots == 0, st
    for a in eng.allocators:
        assert a.stats()["pages_live"] == 0, a.stats()
    if n_terminal is not None:
        assert st.finished_total + st.cancelled_total == n_terminal, st
    return st


# ---------------------------------------------------------------------------
# Controller determinism
# ---------------------------------------------------------------------------


def test_controller_counting_is_deterministic():
    c = _chaos.ChaosController()
    c.inject("page_exhaustion", after=2, times=2, allocs=3)
    fires = [c.fire("page_exhaustion") is not None for _ in range(6)]
    assert fires == [False, False, True, True, False, False]
    assert c.stats()["page_exhaustion"] == {"triggers": 6, "fired": 2}
    assert c.fire("stalled_decode") is None          # unarmed never fires
    with pytest.raises(ValueError):
        c.inject("not_a_fault")


def test_controller_env_parsing(monkeypatch):
    monkeypatch.setenv("TPU_SERVE_CHAOS",
                       "stalled_decode:duration_s=2,"
                       "page_exhaustion:times=3:allocs=2")
    c = _chaos.reset()
    assert c.active("stalled_decode") == {"duration_s": 2}
    assert c.active("page_exhaustion") == {"allocs": 2}
    assert c.fire("page_exhaustion") == {"allocs": 2}
    monkeypatch.delenv("TPU_SERVE_CHAOS")
    assert not _chaos.reset().enabled


# ---------------------------------------------------------------------------
# Deadline expiry (engine-native fault: no injection needed)
# ---------------------------------------------------------------------------


def test_deadline_expiry_http_408(http_server):
    """A request whose deadline passes answers 408 deadline_exceeded, the
    slot/pages release, and the deadline metric increments."""
    state, port = http_server()
    # ~1 ms deadline: guaranteed to expire before a 100-token budget can
    # complete (the engine reaps at every step start), warm jit cache or not
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(port, {"prompt": "never finishes", "max_tokens": 100,
                     "ignore_eos": True, "deadline_ms": 1})
    assert ei.value.code == 408
    body = json.loads(ei.value.read())
    assert body["error"]["code"] == "deadline_exceeded"
    assert body["error"]["type"] == "timeout"
    eng = state.engine
    _assert_released(eng, 1)
    assert eng.metrics.deadline_expired.total() >= 1
    _, health = _get(port, "/healthz")
    assert health["deadline_expired_total"] >= 1
    # the engine is fine: an undeadlined request completes normally
    code, ok = _post(port, {"prompt": "hello", "max_tokens": 4})
    assert code == 200
    assert ok["choices"][0]["finish_reason"] in ("stop", "length")


def test_deadline_header_equivalent_to_body_field(http_server):
    _, port = http_server()
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(port, {"prompt": "header deadline", "max_tokens": 100,
                     "ignore_eos": True},
              headers={"X-Request-Deadline-Ms": "1"})
    assert ei.value.code == 408
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(port, {"prompt": "x", "deadline_ms": -5})
    assert ei.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(port, {"prompt": "x", "deadline_ms": "soon"})
    assert ei.value.code == 400


def test_deadline_expiry_racing_final_token_releases_exactly_once():
    """Satellite: deadline expiry racing request completion must release
    the slot exactly once — across a spread of deadlines that straddle the
    typical completion time, total accounting stays exact."""
    eng, tok = _mk_engine()
    stop = threading.Event()
    threading.Thread(target=eng.run_forever, args=(stop,),
                     daemon=True).start()
    try:
        reqs = []
        for i in range(8):
            reqs.append(eng.generate(tok.encode(f"race {i}"), max_tokens=2,
                                     ignore_eos=True,
                                     deadline_s=0.001 * (i + 1) * 5))
        for r in reqs:
            r.wait(timeout=60)
        for r in reqs:
            assert r.finish_reason in ("stop", "length", "timeout"), \
                r.finish_reason
        _assert_released(eng, 8)
    finally:
        stop.set()


def test_queued_deadline_expiry_notifies_without_admission():
    """An already-expired queued request is answered with "timeout" on the
    next step, never admitted, and the queue drains."""
    eng, tok = _mk_engine()
    r = eng.generate(tok.encode("expired in queue"), max_tokens=4,
                     deadline_s=0.001)
    time.sleep(0.01)
    eng.step()
    assert r.finish_reason == "timeout"
    assert r.out_queue.get(timeout=1) is None
    st = _settled(eng)
    assert st.admitted_total == 0
    assert eng.metrics.deadline_expired.total() == 1


# ---------------------------------------------------------------------------
# Admission control / load shedding
# ---------------------------------------------------------------------------


def test_queue_bound_sheds_with_structured_error():
    eng, tok = _mk_engine(max_decode_slots=1, max_queue_depth=1)
    r1 = eng.generate(tok.encode("first"), max_tokens=2)     # queued
    with pytest.raises(EngineOverloaded) as ei:
        eng.generate(tok.encode("second"), max_tokens=2)     # over bound
    assert ei.value.reason == "queue_full"
    assert ei.value.retry_after_s >= 1.0
    assert eng.metrics.requests_shed.total() == 1
    _drain(eng, [r1])
    _assert_released(eng, 1)     # the shed request never entered accounting


def test_estimated_wait_shed():
    eng, tok = _mk_engine(max_decode_slots=1, admission_max_wait_s=0.5)
    # forge throughput history: 1 token/s, 10 tokens generated so far
    eng.metrics.tokens_per_second.set(1.0)
    eng.metrics.generated_tokens.inc(10)
    r1 = eng.generate(tok.encode("fills the queue"), max_tokens=2)
    with pytest.raises(EngineOverloaded) as ei:
        eng.generate(tok.encode("sheds"), max_tokens=2)
    assert ei.value.reason == "est_wait"
    assert eng.metrics.requests_shed.total() == 1
    _drain(eng, [r1])


def test_http_429_with_retry_after(http_server):
    """HTTP surface of load shedding: 429 + Retry-After + shed counters on
    /healthz."""
    # horizon-1 dispatches keep the hog stream busy for its whole budget —
    # the queue must still be full when the shed POST lands (the pipelined
    # decode path finishes a horizon-8 stream fast enough to race it)
    state, port = http_server(max_decode_slots=1, max_queue_depth=1,
                              decode_horizon=1)
    eng = state.engine
    done = {}

    def hog():
        try:
            done["hog"] = _post(port, {"prompt": "hog", "max_tokens": 120,
                                       "ignore_eos": True})
        except Exception as e:       # noqa: BLE001 — recorded for the assert
            done["hog"] = e

    t = threading.Thread(target=hog, daemon=True)
    t.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not eng._active_slots():
        time.sleep(0.02)
    assert eng._active_slots(), "hog request never activated"
    queued = eng.generate([65, 66, 67], max_tokens=4)    # fills the queue
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(port, {"prompt": "shed me", "max_tokens": 4})
    assert ei.value.code == 429
    assert ei.value.headers.get("Retry-After") is not None
    body = json.loads(ei.value.read())
    assert body["error"]["type"] == "overloaded_error"
    assert body["error"]["code"].startswith("engine_overloaded")
    _, health = _get(port, "/healthz")
    assert health["shed_total"] >= 1
    assert health["max_queue_depth"] == 1
    eng.cancel(queued)
    t.join(timeout=60)
    assert isinstance(done.get("hog"), tuple) and done["hog"][0] == 200


# ---------------------------------------------------------------------------
# Stalled decode step → watchdog fails requests, not the process
# ---------------------------------------------------------------------------


def test_stalled_decode_watchdog_fails_requests_not_process():
    eng, tok = _mk_engine(watchdog_stall_s=0.2)
    _chaos.get().inject("stalled_decode", times=1, duration_s=30.0)
    stop = threading.Event()
    threading.Thread(target=eng.run_forever, args=(stop,),
                     daemon=True).start()
    try:
        r = eng.generate(tok.encode("will stall"), max_tokens=8,
                         ignore_eos=True)
        ids = r.wait(timeout=30)
        # the stall struck mid-generation: the watchdog aborted the step and
        # the request failed loudly instead of hanging for duration_s
        assert r.finish_reason == "error"
        assert len(ids) < 8
        assert "InjectedStall" in eng.last_error
        assert eng.metrics.watchdog_stalls.total() == 1
        # the PROCESS survived: the engine loop keeps serving
        r2 = eng.generate(tok.encode("after the stall"), max_tokens=4)
        r2.wait(timeout=60)
        assert r2.finish_reason in ("stop", "length")
        # no exact count: a submit racing _fail_all's admission-drain is
        # released-and-requeued by design, recounting in the totals
        _assert_released(eng)
    finally:
        stop.set()


def test_stall_visible_on_health_fields():
    """The stall threshold is config-driven (watchdog_stall_s), not the old
    hardcoded class constant."""
    eng, _ = _mk_engine(watchdog_stall_s=0.25)
    assert eng.STALL_AFTER_S == 0.25
    eng.last_step_start = time.monotonic() - 1.0
    assert eng.stalled_for_s > 0.0


# ---------------------------------------------------------------------------
# Page-pool exhaustion → requeue / preempt instead of wedging
# ---------------------------------------------------------------------------


def test_page_exhaustion_at_admission_requeues_and_heals():
    eng, tok = _mk_engine()
    _chaos.get().inject("page_exhaustion", times=1, allocs=1)
    r = eng.generate(tok.encode("alloc fails once"), max_tokens=3)
    eng.step()           # chaos arms the allocator; admission requeues
    assert not eng._active_slots()
    assert eng.sched.stats().queue_depth == 1
    _drain(eng, [r])     # next steps admit and finish normally
    assert r.finish_reason in ("stop", "length")
    _assert_released(eng)     # requeue re-counts; structural release only


def test_page_exhaustion_mid_decode_preempts_not_crashes():
    """The pool runs dry while a slot grows mid-decode: the engine preempts
    (vLLM recompute), resumes, and completes — zero crashes, pages exact."""
    eng, tok = _mk_engine()
    r = eng.generate(tok.encode("grow across pages"), max_tokens=40,
                     ignore_eos=True)
    eng.step()                               # admit + prefill
    assert eng._active_slots()
    # force the next growth allocation to fail (the direct allocator hook
    # chaos's on_engine_step uses; driven directly so no other step
    # consumes the firing)
    for a in eng.allocators:
        a.fail_next_allocs = 1
    _drain(eng, [r])
    assert r.finish_reason in ("stop", "length")
    assert eng.metrics.preemptions.total() >= 1
    # bit-exact stream despite the preemption: the same engine config
    # replays the identical request without faults
    eng2, tok2 = _mk_engine()
    r2 = eng2.generate(tok2.encode("grow across pages"), max_tokens=40,
                       ignore_eos=True)
    _drain(eng2, [r2])
    assert r2.generated == r.generated, \
        "preemption-resume changed the token stream"
    _assert_released(eng)


def test_admission_pressure_preempts_lowest_progress():
    """Tentpole (3): a page-starved queue head with a FREE slot preempts the
    lowest-progress running request (requeued at the back) instead of
    wedging until the hog finishes."""
    eng, tok = _mk_engine(kv_pool_pages=4, max_cache_len=128, page_size=32,
                          admission_preempt_after_s=0.005)
    # prompt fills the whole 4-page pool; budget keeps it running a while
    hog = eng.generate([65] * 120, max_tokens=7, ignore_eos=True)
    while not eng._active_slots():
        eng.step()
    small = eng.generate(tok.encode("let me in"), max_tokens=2)
    eng.step()                 # blocked admission: pressure timer starts
    assert not [s for s in eng._active_slots()
                if eng.slot_req[s] is small], "small admitted impossibly"
    time.sleep(0.02)
    eng.step()                 # timer elapsed: hog preempted, requeued BACK
    assert eng.metrics.admission_preemptions.total() == 1
    assert eng.metrics.preemptions.total() == 1
    _drain(eng, [hog, small])
    assert small.finish_reason in ("stop", "length")
    assert hog.finish_reason in ("stop", "length")
    assert len(hog.generated) == 7          # resumed, nothing lost
    _assert_released(eng)


def test_pressure_preempting_the_only_active_slot_is_still_work():
    """Regression (r8, the order-dependent test_engine_mesh wedge): when
    pressure relief preempts the SOLE active request, that step must return
    True — it returned False with the queue non-empty, so every driver that
    treats a False step as quiescence (run_forever's idle sleep, the test
    suites' ``if not eng.step(): break`` loops) stranded the requeued
    victim. Deterministic replay of what full-suite CPU contention did to
    the mesh test: steps slower than admission_preempt_after_s."""
    eng, tok = _mk_engine(kv_pool_pages=4, max_cache_len=128, page_size=32,
                          admission_preempt_after_s=0.005)
    hog = eng.generate([65] * 120, max_tokens=7, ignore_eos=True)
    while not eng._active_slots():
        eng.step()
    blocked = eng.generate(tok.encode("starved head"), max_tokens=2)
    eng.step()                  # blocked admission: pressure timer starts
    time.sleep(0.02)
    assert eng.step() is True, \
        "the step that preempted the only active slot reported no work"
    assert eng.metrics.admission_preemptions.total() == 1
    assert not eng._active_slots()      # victim gone — queue must revive it
    for _ in range(10000):              # the drivers' quiescence loop
        if not eng.step():
            break
    assert blocked.finish_reason in ("stop", "length")
    assert hog.finish_reason in ("stop", "length")
    assert len(hog.generated) == 7
    _assert_released(eng)


# ---------------------------------------------------------------------------
# Client-side faults: mid-stream disconnect, slow client
# ---------------------------------------------------------------------------


def test_mid_stream_disconnect_releases_slot_exactly_once(http_server):
    """Satellite: broken pipe mid-stream cancels the engine request; the
    slot and pages release exactly once (SchedulerStats accounting)."""
    state, port = http_server()
    eng = state.engine
    got = _chaos.stream_then_disconnect(
        "127.0.0.1", port,
        {"model": MODEL, "prompt": "disconnect me", "max_tokens": 100,
         "ignore_eos": True},
        after_bytes=120)
    assert got, "no stream bytes before the disconnect"
    st = _settled(eng)
    assert st.cancelled_total == 1 and st.finished_total == 0, st
    assert st.admitted_total == 1
    for a in eng.allocators:
        assert a.stats()["pages_live"] == 0
    # the engine keeps serving afterwards
    code, body = _post(port, {"prompt": "still alive?", "max_tokens": 4})
    assert code == 200
    _assert_released(eng, 2)


def test_many_disconnects_no_leak(http_server):
    """Repeated hard disconnects must not leak slots or pages."""
    state, port = http_server()
    eng = state.engine
    for i in range(4):
        _chaos.stream_then_disconnect(
            "127.0.0.1", port,
            {"model": MODEL, "prompt": f"drop {i}", "max_tokens": 100,
             "ignore_eos": True},
            after_bytes=80)
        _settled(eng)
    st = _settled(eng)
    assert st.finished_total + st.cancelled_total == 4
    for a in eng.allocators:
        assert a.stats()["pages_live"] == 0


def test_slow_client_does_not_starve_siblings(http_server):
    """A slow-reading stream consumer backpressures only its own handler
    thread: sibling requests complete at full speed while it drips."""
    state, port = http_server(max_decode_slots=4)
    result = {}

    def slow():
        result["slow"] = _chaos.slow_client_stream(
            "127.0.0.1", port,
            {"model": MODEL, "prompt": "drip feed", "max_tokens": 30,
             "ignore_eos": True},
            read_delay_s=0.05, read_size=48, timeout=120)

    t = threading.Thread(target=slow, daemon=True)
    t.start()
    time.sleep(0.2)          # slow stream underway
    t0 = time.monotonic()
    for i in range(3):
        code, body = _post(port, {"prompt": f"fast {i}", "max_tokens": 4})
        assert code == 200
    fast_elapsed = time.monotonic() - t0
    assert t.is_alive() or b"data: [DONE]" in result.get("slow", b""), \
        "slow client finished before the fast ones even ran"
    t.join(timeout=120)
    assert b"data: [DONE]" in result["slow"], "slow stream never completed"
    # 3 tiny completions must not have been serialized behind the slow
    # consumer's multi-second read schedule
    assert fast_elapsed < 20.0
    _assert_released(state.engine, 4)


# ---------------------------------------------------------------------------
# Router: injected connect refusal + 429 as a routable signal
# ---------------------------------------------------------------------------


from http.server import (  # noqa: E402
    BaseHTTPRequestHandler, ThreadingHTTPServer)

from aws_k8s_ansible_provisioner_tpu.serving.router import (  # noqa: E402
    BackendPool, RouterHandler, RouterMetrics)


class _FakeBackend(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    status = 200
    retry_after = None

    def log_message(self, *a):
        pass

    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(n)
        body = json.dumps({"port": self.server.server_port,
                           "deadline_hdr":
                               self.headers.get("X-Request-Deadline-Ms"),
                           "status": self.status}).encode()
        self.send_response(self.status)
        self.send_header("Content-Type", "application/json")
        if self.status == 429 and self.retry_after:
            self.send_header("Retry-After", self.retry_after)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def _fake_backend(status=200, retry_after=None):
    handler = type("H", (_FakeBackend,),
                   {"status": status, "retry_after": retry_after})
    srv = ThreadingHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def _router_for(pool):
    old = RouterHandler.pool, RouterHandler.metrics
    RouterHandler.pool = pool
    RouterHandler.metrics = RouterMetrics()
    srv = ThreadingHTTPServer(("127.0.0.1", 0), RouterHandler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, old


def _router_post(port, payload, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, json.loads(r.read()), dict(r.headers)


def test_injected_connect_refusal_fails_over():
    """connect_refused chaos: the refused replica is dead-marked and the
    request fails over and serves — POST included (nothing was sent)."""
    b1, b2 = _fake_backend(), _fake_backend()
    addrs = [f"127.0.0.1:{b.server_port}" for b in (b1, b2)]

    class FixedOrder(BackendPool):
        def pick(self, affinity_key=None):
            return list(addrs)

    _chaos.get().inject("connect_refused", times=1,
                        addr_prefix=addrs[0])
    router, old = _router_for(FixedOrder(",".join(addrs)))
    try:
        code, body, _ = _router_post(router.server_port, {"prompt": "x"})
        assert code == 200
        assert body["port"] == b2.server_port      # served by the survivor
        m = RouterHandler.metrics
        assert m.failovers.total() == 1
        assert m.dead_marks.total() == 1
        assert addrs[0] in RouterHandler.pool.cooling()
    finally:
        router.shutdown()
        for b in (b1, b2):
            b.shutdown()
        RouterHandler.pool, RouterHandler.metrics = old


def test_router_retries_429_on_next_replica():
    shedder = _fake_backend(status=429, retry_after="7")
    server = _fake_backend(status=200)
    addrs = [f"127.0.0.1:{shedder.server_port}",
             f"127.0.0.1:{server.server_port}"]

    class ShedderFirst(BackendPool):
        def pick(self, affinity_key=None):
            return list(addrs)

    router, old = _router_for(ShedderFirst(",".join(addrs)))
    try:
        code, body, _ = _router_post(router.server_port, {"prompt": "x"})
        assert code == 200
        assert body["port"] == server.server_port
        m = RouterHandler.metrics
        assert m.retries_429.total() == 1
        # shedding is NOT death: the full replica stays in rotation
        assert m.dead_marks.total() == 0
        assert addrs[0] not in RouterHandler.pool.cooling()
    finally:
        router.shutdown()
        for b in (shedder, server):
            b.shutdown()
        RouterHandler.pool, RouterHandler.metrics = old


def test_router_relays_429_when_all_replicas_shed():
    b1 = _fake_backend(status=429, retry_after="3")
    b2 = _fake_backend(status=429, retry_after="9")
    addrs = [f"127.0.0.1:{b.server_port}" for b in (b1, b2)]

    class Both(BackendPool):
        def pick(self, affinity_key=None):
            return list(addrs)

    router, old = _router_for(Both(",".join(addrs)))
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _router_post(router.server_port, {"prompt": "x"})
        assert ei.value.code == 429
        assert ei.value.headers.get("Retry-After") in ("3", "9")
    finally:
        router.shutdown()
        for b in (b1, b2):
            b.shutdown()
        RouterHandler.pool, RouterHandler.metrics = old


def test_router_forwards_deadline_header():
    """The backend sees the REMAINING deadline budget: since r8 the router
    subtracts its own elapsed wall-clock before every dispatch (verbatim
    forwarding let a retry chain hand each hop a fresh deadline), so the
    first hop sees at most the declared value and strictly more than
    nothing."""
    b = _fake_backend()
    router, old = _router_for(BackendPool(f"127.0.0.1:{b.server_port}"))
    try:
        code, body, _ = _router_post(
            router.server_port, {"prompt": "x"},
            headers={"X-Request-Deadline-Ms": "5000"})
        assert code == 200
        fwd = int(body["deadline_hdr"])
        assert 0 < fwd <= 5000
        assert fwd > 4000    # one healthy hop burns ~ms, not seconds
    finally:
        router.shutdown()
        b.shutdown()
        RouterHandler.pool, RouterHandler.metrics = old
