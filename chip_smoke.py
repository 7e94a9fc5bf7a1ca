"""Chip smoke: the served path, end to end, on the TPU JAX finds.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the sharded path (--tp 4) against the
                                      # same weights on one device; four chips
    python chip_smoke.py --config benchmark/configs/olmoe-1b-7b-int8.json
                                      # another model, as its benchmark cell
                                      # serves it (one chip)

One process holds the chip(s): the server is built by its own entry points
(``serving.server.build_parser`` -> ``serving_config_from_args`` ->
``build_state`` -> ``warmup`` -> ``serve``, the sequence ``main()`` runs) on a
worker thread, and the HTTP client runs beside it. Flags are the README
command's minus ``--checkpoint-dir``: Qwen/Qwen3-0.6B at full width and
depth, random weights from the server's own seeded no-checkpoint path, byte
tokenizer, paged pool, autotuned decode block, pipeline, ragged dispatch,
full warm-up. Nothing here sets ``jax_platforms`` or passes ``--platform``.

It fails (non-zero, no result line) unless ``jax.devices()[0].platform`` is
"tpu". Every phase raises on failure; nothing is caught and carried past.
The last stdout line of a passing run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Tolerances (stated once, used below):
- KERNEL_TOL: compiled Pallas kernels vs the plain jax.numpy references, on
  bf16 outputs of O(1) values (bf16 spacing there is 2**-8 ~ 4e-3).
- NEAR_MAX_NATS: end-to-end, per generated position, how far the served
  token's logprob under the teacher-forced reference may sit below the
  reference's own maximum. Weights are random, so the top two logits are
  often a few hundredths of a nat apart and bf16 matmuls at another batch
  shape legitimately flip the argmax; a broken cache or kernel instead
  lands ~3 nats down (logit sigma ~0.6 over a 152k vocabulary). Raw token
  equality would be brittle; this is not.
- LOGPROB_NATS: served chosen-token logprob vs the reference's logprob of
  that same token. With --chips 4 the served side is the --tp 4 engine and
  the reference holds the whole model on device 0.

``--config <file>`` serves the model of a benchmark configuration file with
that file's server flags instead (one chip). A model whose bf16 tree no chip
holds (OLMoE: 13.8 GB) cannot start from the server's own seeded init, so its
weights come from the file's seeded maker, in int8, through
``build_state(params=...)``, and the numerics phase compares with the file's
plain float32 reference (benchmark/reference/), which shares no code with
the program — for an MoE model it routes on its own activations. Everything
else is the same run: the same requests, a new 700-token prompt admitted
under a live stream (``mixed_step``'s expert path), kernel parity at the
model's own head shape. A configuration without a ``registry_name`` (a cut
of a published model, e.g. the Solar-Open2 hybrid's expert share) is served
from its own ``model_config`` fields; for it ``m700`` also passes the KDA
chunk rows and the state hand-over at the chunk's end through
``mixed_step`` against the reference.

``--rehearse`` is the builder's CPU rehearsal of this same script (tiny
model, XLA attention, interpret-mode kernel parity at small shapes, no
persistent cache); it never prints an "ok" line.
"""

from __future__ import annotations

import argparse
import http.client
import json
import logging
import os
import socket
import subprocess
import sys
import threading
import time

KERNEL_TOL = 2e-2
NEAR_MAX_NATS = 0.35
LOGPROB_NATS = 0.25

HERE = os.path.dirname(os.path.abspath(__file__))


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# HTTP client (stdlib; talks to the in-process server over loopback)
# ---------------------------------------------------------------------------


def http_json(port: int, method: str, path: str, body=None, timeout=900.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = json.dumps(body).encode() if body is not None else None
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        return resp.status, raw
    finally:
        conn.close()


def http_stream(port: int, path: str, body: dict, on_first=None,
                timeout=900.0) -> dict:
    """POST a stream=true request; returns {"status", "token_ids",
    "logprobs", "done"} gathered from the SSE chunks."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    out = {"status": None, "token_ids": [], "logprobs": [], "done": False}
    try:
        conn.request("POST", path, body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        out["status"] = resp.status
        if resp.status != 200:
            out["error"] = resp.read()[:400]
            return out
        first = True
        for raw in resp:
            line = raw.strip()
            if not line.startswith(b"data:"):
                continue
            payload = line[5:].strip()
            if payload == b"[DONE]":
                out["done"] = True      # keep reading to the chunked end:
                continue                # closing on unread bytes is a reset
            for ch in json.loads(payload).get("choices", []):
                ids = ch.get("token_ids") or []
                out["token_ids"] += ids
                lp = ch.get("logprobs")
                if lp and "token_logprobs" in lp:
                    out["logprobs"] += lp["token_logprobs"]
                if ids and first and on_first is not None:
                    first = False
                    on_first()
        return out
    finally:
        conn.close()


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {"name{labels}": value}."""
    vals = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, _, val = line.rpartition(" ")
            try:
                vals[key] = float(val)
            except ValueError:
                pass
    return vals


def dispatched(port: int) -> set:
    """Program kinds with device time in devmon's window (/debug/roofline).
    The window is 60 s, so callers ask right after the traffic they mean."""
    status, raw = http_json(port, "GET", "/debug/roofline")
    check(status == 200, f"/debug/roofline -> {status}")
    return {k for k, p in json.loads(raw)["programs"].items()
            if p["device_seconds"] > 0}


def prompt_of(n: int, salt: int) -> str:
    """n ASCII bytes = n byte-tokenizer tokens; distinct per salt so no two
    prompts share a page-long prefix by accident."""
    words = ["tpu", "page", "ragged", "decode", "prefill", "kernel", "slot",
             "block", "cache", "chip", "mesh", "shard"]
    s, i = f"[{salt}] ", salt
    while len(s) < n:
        s += words[i % len(words)] + " "
        i += 3 + salt
    return s[:n]


# ---------------------------------------------------------------------------
# The server under test
# ---------------------------------------------------------------------------


def build_native_scheduler() -> None:
    """The native scheduler is the intended one (runtime/__init__.py: the
    C++ core is authoritative, Python the fallback), and make_scheduler picks
    up whatever libtpu_serve_runtime.so it finds. Rebuild it from the
    committed sources every time, so the smoke behaves the same on a
    checkout (no native/build/) and on a disk copy (a stale one)."""
    subprocess.run(["make", "-B", "-C", os.path.join(HERE, "native"),
                    "runtime"], check=True, stdout=subprocess.DEVNULL)


class Server:
    """build_state -> warmup -> serve on a worker thread, as main() does."""

    def __init__(self, flags, params=None):
        from aws_k8s_ansible_provisioner_tpu.serving import server

        self.port = _free_port()
        argv = list(flags) + ["--host", "127.0.0.1", "--port", str(self.port)]
        args = server.build_parser().parse_args(argv)
        t0 = time.monotonic()
        self.state = server.build_state(server.serving_config_from_args(args),
                                        params=params)
        self.build_s = time.monotonic() - t0
        self.engine = self.state.engine
        t0 = time.monotonic()
        self.engine.warmup()
        self.warmup_s = time.monotonic() - t0
        ready = threading.Event()
        # daemon: a failed phase must end the process, not leave it serving
        self._thread = threading.Thread(
            target=server.serve, name="serve", daemon=True,
            args=(self.state, "127.0.0.1", self.port, ready))
        self._thread.start()
        check(ready.wait(60), "server did not come up")

    def drain(self) -> None:
        """End the run through the server's own drain (POST /admin/drain ->
        begin_drain -> stop once idle), then release the device buffers."""
        status, _ = http_json(self.port, "POST", "/admin/drain", {})
        check(status == 200, f"/admin/drain -> {status}")
        self._thread.join(120)
        check(not self._thread.is_alive(), "server did not stop after drain")
        self.engine.cache = None
        self.engine.params = None
        self.engine = self.state = None


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# Phase: requests
# ---------------------------------------------------------------------------

# (name, prompt tokens, max_tokens, streamed-with-logprobs?) — lengths on both
# sides of one page (64) and of the smallest bucket (32); the streamed ones
# carry token ids + chosen logprobs for the numerics checks.
WAVE = [("c12", 12, 32, False), ("c70", 70, 48, True),
        ("c300", 300, 64, False), ("c1100", 1100, 40, False),
        ("c20", 20, 32, False), ("c25", 25, 32, False),
        ("c9", 9, 32, False), ("c30", 30, 32, True)]


def run_requests(srv: Server, model: str) -> dict:
    """The whole request phase against one server. Returns what the
    numerics phase compares: {name: {"prompt", "token_ids", "logprobs"}} for
    the streamed requests."""
    port = srv.port
    status, raw = http_json(port, "GET", "/v1/models")
    check(status == 200, f"/v1/models -> {status}")
    ids = [m["id"] for m in json.loads(raw)["data"]]
    check(model in ids, f"/v1/models lists {ids}, not {model}")

    status, raw = http_json(port, "GET", "/metrics")
    check(status == 200, f"/metrics -> {status}")
    before = parse_metrics(raw.decode())

    expected = 0
    streams: dict = {}
    errors: list = []
    gate = threading.Barrier(len(WAVE))

    def one(i, name, n_prompt, n_gen, streamed):
        try:
            body = {"model": model, "prompt": prompt_of(n_prompt, i + 1),
                    "max_tokens": n_gen, "temperature": 0.0,
                    "ignore_eos": True}
            gate.wait(60)
            if streamed:
                r = http_stream(port, "/v1/completions",
                                dict(body, stream=True, logprobs=0))
                check(r["status"] == 200 and r["done"],
                      f"{name}: stream status {r['status']} done {r['done']}")
                check(len(r["token_ids"]) == n_gen,
                      f"{name}: {len(r['token_ids'])} tokens, want {n_gen}")
                check(len(r["logprobs"]) == n_gen
                      and all(isinstance(x, float) for x in r["logprobs"]),
                      f"{name}: logprobs {r['logprobs'][:4]}...")
                streams[name] = {"prompt": body["prompt"],
                                 "token_ids": r["token_ids"],
                                 "logprobs": r["logprobs"]}
            else:
                status, raw = http_json(port, "POST", "/v1/completions",
                                        body)
                check(status == 200, f"{name}: status {status} {raw[:300]}")
                usage = json.loads(raw)["usage"]
                check(usage["completion_tokens"] == n_gen
                      and usage["prompt_tokens"] == n_prompt,
                      f"{name}: usage {usage}, want {n_prompt}+{n_gen}")
        except BaseException as e:      # re-raised on the main thread below
            errors.append(e)
            gate.abort()

    threads = [threading.Thread(target=one, args=(i,) + w)
               for i, w in enumerate(WAVE)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    expected += sum(w[2] for w in WAVE)
    ran = dispatched(port)
    say(f"requests: {len(WAVE)} concurrent /v1/completions ok "
        f"(prompts {[w[1] for w in WAVE]}, {expected} tokens)")

    # one streamed chat completion, into an IDLE engine: the wave's clients
    # have their last tokens, the engine may still hold its last (surplus)
    # decode dispatch in flight for a step, and an admission under it would
    # walk the mixed program instead of ``prefill_step``
    deadline = time.monotonic() + 10
    while srv.engine._inflight is not None and time.monotonic() < deadline:
        time.sleep(0.01)
    r = http_stream(port, "/v1/chat/completions", {
        "model": model, "stream": True, "max_tokens": 32,
        "temperature": 0.0, "ignore_eos": True,
        "messages": [{"role": "user", "content": "Say something long."}]})
    check(r["status"] == 200 and r["done"] and len(r["token_ids"]) == 32,
          f"chat stream: status {r['status']} done {r['done']} "
          f"tokens {len(r['token_ids'])}")
    expected += 32
    # whether the wave's first arrival was admitted alone (``prefill_step``)
    # or batched with the rest is a race; this one met an idle engine
    ran |= dispatched(port)
    say("requests: streamed /v1/chat/completions ok (32 tokens)")

    # Two admissions WHILE another stream decodes, so they ride the ragged
    # mixed program. First a NEW 700-token prompt: no prefix to reuse, so
    # all of it is one padded chunk (packed K/V writes, the ragged kernel's
    # sharing blocks and dead rows); streamed with logprobs for the numerics
    # phase, which no idle-engine prefill reaches. Then the long prompt
    # again: its 17 full pages are a prefix-cache hit, the suffix is a chunk.
    bg: dict = {}
    tokens_total = "tpu_serve_generated_tokens_total"

    def generated() -> float:
        status, raw = http_json(port, "GET", "/metrics")
        check(status == 200, f"/metrics -> {status}")
        return parse_metrics(raw.decode()).get(tokens_total, 0.0)

    def background():
        t0 = time.monotonic()
        bg.update(http_stream(
            port, "/v1/completions",
            {"model": model, "prompt": prompt_of(40, 99), "stream": True,
             "max_tokens": 1900, "temperature": 0.0, "ignore_eos": True}))
        bg["t_done"] = time.monotonic()
        bg["seconds"] = bg["t_done"] - t0

    base = generated()
    t = threading.Thread(target=background)
    t.start()
    # the stream's own first chunk says nothing: a random-weight stream may
    # hold its text to the end (server.py::_stream_completions), so wait for
    # the engine's token counter instead
    deadline = time.monotonic() + 600
    while generated() < base + 16:
        check(time.monotonic() < deadline and t.is_alive(),
              "the background stream never got to decoding")
        time.sleep(0.05)
    body = {"model": model, "prompt": prompt_of(700, 55), "max_tokens": 24,
            "temperature": 0.0, "ignore_eos": True}
    t0 = time.monotonic()
    r = http_stream(port, "/v1/completions",
                    dict(body, stream=True, logprobs=0))
    t_mixed = time.monotonic()
    cfg = srv.engine.cfg
    if cfg.selects or cfg.windowed:
        # a prompt PAST the dense length, several chunks of mixed_step under
        # the live stream: every later chunk's rows select their pages, the
        # Lightning state is handed over between chunks, and the 24 generated
        # tokens read 64 selected pages each. A list with window layers:
        # three windows and a bit (two chunks at the served chunk), so that
        # pages of the window layers are released between its chunks and
        # AGAIN among its 24 generated tokens, which also cross a page
        n_long = cfg.sparse_dense_len + cfg.sparse_dense_len // 8 + 37 \
            if cfg.selects \
            else 3 * cfg.sliding_window + cfg.sliding_window // 8 + 53
        rl = http_stream(port, "/v1/completions", {
            "model": model, "prompt": prompt_of(n_long, 77), "stream": True,
            "max_tokens": 24, "temperature": 0.0, "ignore_eos": True,
            "logprobs": 0})
        check(rl["status"] == 200 and rl["done"]
              and len(rl["token_ids"]) == 24 and len(rl["logprobs"]) == 24,
              f"mlong: status {rl['status']} tokens {len(rl['token_ids'])}")
        check(not bg.get("t_done"),
              "the background stream ended before the long prompt did: it "
              "was not admitted under a live batch")
        streams["mlong"] = {"prompt": prompt_of(n_long, 77),
                            "token_ids": rl["token_ids"],
                            "logprobs": rl["logprobs"]}
        expected += 24
        say(f"requests: a {n_long}-token prompt past the "
            + (f"dense length ({cfg.sparse_dense_len})" if cfg.selects
               else f"window ({cfg.sliding_window}) three times over")
            + f" beside the live stream ok "
            f"({time.monotonic() - t_mixed:.2f}s)")
    # the one request of this script that DRAWS: until here every sampler
    # call took the all-greedy side of its gate (ops/sampling.sample); this
    # one, admitted under the live greedy stream, opens it for the batch
    path = "tpu_serve_sample_dispatches_total"

    def sampler_paths() -> dict:
        """Dispatches by the side of the gate they took, over programs."""
        status, raw = http_json(port, "GET", "/metrics")
        check(status == 200, f"/metrics -> {status}")
        return {side: sum(v for k, v in parse_metrics(raw.decode()).items()
                          if k.startswith(path) and f'path="{side}"' in k)
                for side in ("greedy", "candidates")}

    took = sampler_paths()
    check(took["greedy"] > 0 and took["candidates"] == 0,
          f"{path}: greedy requests alone, yet it reads {took}")
    drawn = {"model": model, "prompt": prompt_of(90, 31), "stream": True,
             "max_tokens": 16, "temperature": 0.8, "top_p": 0.9, "seed": 7,
             "ignore_eos": True}
    rs = http_stream(port, "/v1/completions", drawn)
    check(rs["status"] == 200 and rs["done"] and len(rs["token_ids"]) == 16,
          f"seeded draw: status {rs['status']} tokens {len(rs['token_ids'])}")
    check(not bg.get("t_done"),
          "the background stream ended before the seeded draw did: it was "
          "not admitted under a live batch")
    status, raw = http_json(
        port, "POST", "/v1/completions",
        {"model": model, "prompt": prompt_of(1100, 4), "max_tokens": 32,
         "temperature": 0.0, "ignore_eos": True})
    t.join()
    check(status == 200
          and json.loads(raw)["usage"]["completion_tokens"] == 32,
          f"repeated long prompt: {status} {raw[:300]}")
    check(r["status"] == 200 and r["done"] and len(r["token_ids"]) == 24
          and len(r["logprobs"]) == 24,
          f"m700: status {r['status']} tokens {len(r['token_ids'])}")
    check(bg.get("status") == 200 and bg["done"]
          and len(bg["token_ids"]) == 1900,
          f"background stream: {bg.get('status')} "
          f"{len(bg.get('token_ids', []))} tokens")
    check(bg["t_done"] > t_mixed,
          f"the background stream ({bg['seconds']:.2f}s) ended before the "
          f"700-token request did ({t_mixed - t0:.2f}s): it was not "
          f"admitted under a live batch")
    streams["m700"] = {"prompt": body["prompt"], "token_ids": r["token_ids"],
                       "logprobs": r["logprobs"]}
    expected += 32 + 24 + 1900
    ran |= dispatched(port)
    say(f"requests: a new 700-token prompt ({t_mixed - t0:.2f}s) and the "
        f"repeated 1100-token prompt beside a live stream of "
        f"{bg['seconds']:.2f}s ok")
    # ... and served alone it draws the same stream: the seed contract
    # (a draw is a function of seed and position, not of the batch) through
    # the candidates' branch, on the chip. A recurrent model reads no prefix
    # hit; any other restores the prompt's full page and prefills the rest.
    ra = http_stream(port, "/v1/completions", drawn)
    check(ra["status"] == 200 and ra["token_ids"] == rs["token_ids"],
          f"seeded draw: alone {ra['token_ids']}, beside the live stream "
          f"{rs['token_ids']}")
    drew = sampler_paths()["candidates"]
    check(drew >= 4, f"{path}: two seeded draws of 16 tokens, "
                     f"{drew} dispatches on the candidates' path")
    expected += 16 + 16
    say(f"requests: a seeded draw (temperature 0.8, top_p 0.9) beside the "
        f"live stream and alone: the same 16 tokens; {int(drew)} dispatches "
        f"ran the sampler's candidates")

    status, raw = http_json(port, "GET", "/metrics")
    check(status == 200, f"/metrics -> {status}")
    after = parse_metrics(raw.decode())

    def delta(key):
        return after.get(key, 0.0) - before.get(key, 0.0)

    got = delta("tpu_serve_generated_tokens_total")
    check(got == expected,
          f"generated-token counter moved {got}, requests asked {expected}")
    for bad in ("error", "timeout"):
        key = f'tpu_serve_request_total{{status="{bad}"}}'
        check(delta(key) == 0, f"{key} moved by {delta(key)}")
    hits = delta("tpu_serve_prefix_cache_hits_total")
    if srv.engine.cfg.recurrent or srv.engine.cfg.windowed:
        # K/V pages restored without the recurrent state that goes with
        # them would be wrong: such a model is never handed a prefix hit
        # (nor is one whose window layers' pages went back)
        skipped = sum(v for k, v in after.items() if k.startswith(
            "tpu_serve_prefix_lookups_skipped_total"))
        check(hits == 0 and skipped >= 1,
              f"a model with recurrent layers or released window pages read "
              f"{hits} prefix hit(s); lookups skipped {skipped}")
    else:
        check(hits >= 1,
              "the repeated long prompt did not hit the prefix cache")
    if srv.engine.cfg.windowed:
        peak = after.get("tpu_serve_kv_window_pages_slot_peak", 0.0)
        eng = srv.engine
        bound = -(-(eng.cfg.sliding_window + eng._chunk_size)
                  // eng.serving.page_size) + 1
        check(0 < peak <= bound
              and after.get("tpu_serve_kv_window_pages_released_total", 0) > 0,
              f"a slot held {peak} pages of the window layers (bound: window "
              f"+ chunk + a page = {bound})")
        say(f"window inventory: {int(after['tpu_serve_kv_window_pages_total'])}"
            f" pages; a slot held at most {int(peak)} (bound {bound}); "
            f"{int(after['tpu_serve_kv_window_pages_released_total'])} pages "
            f"released; at its fullest "
            f"{int(after['tpu_serve_kv_window_pages_in_use_peak'])} pages "
            f"held where nothing released would hold "
            f"{int(after['tpu_serve_kv_window_pages_unreleased_at_peak'])}")

    status, raw = http_json(port, "GET", "/healthz")
    check(status == 200, f"/healthz -> {status}")
    hz = json.loads(raw)
    check(hz["status"] == "ok" and not hz["last_error"],
          f"/healthz status {hz['status']} last_error {hz['last_error']}")
    ran |= dispatched(port)      # the admissions beside a live stream
    for kind in ("prefill", "prefill_batch", "decode", "mixed_step"):
        check(kind in ran, f"program kind {kind!r} never dispatched "
                           f"(dispatched: {sorted(ran)})")
    rode, settled = (delta(f'tpu_serve_activations_total{{path="{p}"}}')
                     for p in ("in_flight", "settled"))
    check(rode >= 1 and settled == 0,
          f"the admissions beside the live stream ended {rode} walks with "
          f"the final chunk in flight and settled {settled}: none of them "
          f"is resumed, penalised or guided")
    say(f"requests: {int(rode)} admissions joined the batch from the final "
        f"chunk's device carry, their first token at its fetch; 0 settled")
    tile, by8 = (delta(f'tpu_serve_ragged_page_steps_total{{path="{p}"}}')
                 for p in ("tile", "by8"))
    check(by8 >= tile > 0, f"the admitted chunks walked {tile} page steps "
                           f"as tiles, {by8} as blocks of 8 rows")
    say(f"requests: the admitted chunks' rows walked {int(tile)} page steps "
        f"as the ragged kernel's tiles are cut; blocks of 8 rows would "
        f"have walked {int(by8)}")
    narrow, wide = (delta(f'tpu_serve_mixed_steps_total{{body="{b}"}}')
                    for b in ("narrow", "wide"))
    check(narrow + wide >= 1, "no mixed_step dispatch was counted by body")
    say(f"requests: mixed_step ran its layers over half a chunk's rows "
        f"{int(narrow)} times (the chunks that fit them) and over the whole "
        f"chunk's {int(wide)}")
    say(f"requests: /metrics tokens +{int(got)}, prefix hits +{int(hits)}, "
        f"0 error/timeout; programs dispatched: {sorted(ran)}")
    return streams


# ---------------------------------------------------------------------------
# Phase: end-to-end numerics against teacher-forced model_forward
# ---------------------------------------------------------------------------


def bench_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module (the benchmark's seeded weight
    makers and plain references), found as the benchmark finds them."""
    sys.path.insert(0, os.path.join(HERE, "benchmark"))
    from benchlib import files

    return files.load_module(kind, name)


def reference_logprobs(cfg, params, tokenizer, prompt: str, token_ids,
                       plain=None):
    """Teacher-forced float32 log-softmax of the plain model (``model_forward``
    with its default XLA causal attention, no cache, no kernel) over
    prompt + served tokens: returns (logprob of each served token, the
    reference's own max logprob) per generated position. With ``plain`` (a
    benchmark/reference/ module) that independent reference is asked
    instead of the program's own model code."""
    import jax
    import jax.numpy as jnp

    from aws_k8s_ansible_provisioner_tpu.models.layers import model_forward

    ids = tokenizer.encode(prompt) + [int(t) for t in token_ids]
    n_prompt = len(ids) - len(token_ids)
    if plain is not None:
        import dataclasses

        import numpy as np

        rows = plain.logprobs(dataclasses.asdict(cfg), params, ids,
                              len(token_ids))
        return (rows[np.arange(len(token_ids)), np.asarray(token_ids)],
                rows.max(axis=-1))
    T = -(-len(ids) // 64) * 64
    toks = jnp.asarray([ids + [0] * (T - len(ids))], jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)[None]

    @jax.jit
    def fwd(params, toks, pos):
        logits, _ = model_forward(params, cfg, toks, pos)
        return jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)

    lp = fwd(params, toks, pos)
    # position p's logits predict token p+1
    rows = lp[n_prompt - 1:len(ids) - 1]
    served = rows[jnp.arange(len(token_ids)), jnp.asarray(token_ids)]
    return jax.device_get(served), jax.device_get(rows.max(axis=-1))


def single_device_params(cfg, serving):
    """The server's own no-checkpoint weights as ONE device holds them
    (build_state's seeded init, then the engine's single-device int8
    quantization) — what the tp=4 engine's answers are compared with."""
    import jax
    import jax.numpy as jnp

    from aws_k8s_ansible_provisioner_tpu.models.layers import init_params
    from aws_k8s_ansible_provisioner_tpu.models.quant import quantize_params

    dtype = jnp.bfloat16 if serving.dtype == "bfloat16" else jnp.float32
    params = init_params(cfg, jax.random.PRNGKey(0), dtype)
    check(serving.weights_dtype == "int8", "default weights are int8")
    return quantize_params(params, cfg)


def check_numerics(name: str, stream: dict, cfg, params, tokenizer,
                   plain=None) -> None:
    import numpy as np

    served_ref, ref_max = reference_logprobs(
        cfg, params, tokenizer, stream["prompt"], stream["token_ids"], plain)
    gap = float(np.max(ref_max - served_ref))
    agree = float(np.max(np.abs(np.asarray(stream["logprobs"])
                                - served_ref)))
    exact = int(np.sum(served_ref == ref_max))
    say(f"numerics[{name}]: {len(served_ref)} positions; served token below "
        f"reference max by <= {gap:.4f} nats (tol {NEAR_MAX_NATS}); served "
        f"vs reference logprob differ <= {agree:.4f} nats (tol "
        f"{LOGPROB_NATS}); reference argmax == served at {exact} positions")
    check(np.all(np.isfinite(served_ref)), "non-finite reference logprobs")
    check(gap <= NEAR_MAX_NATS,
          f"{name}: a served token sits {gap:.3f} nats below the reference "
          f"maximum")
    check(agree <= LOGPROB_NATS,
          f"{name}: served logprobs differ from the reference by "
          f"{agree:.3f} nats")


# ---------------------------------------------------------------------------
# Phase: on-chip kernel parity (compiled Pallas vs plain jax.numpy)
# ---------------------------------------------------------------------------


def kernel_parity(cfg, slots: int, window: int, page: int, bblocks,
                  interpret: bool, parent=None) -> None:
    """decode / ragged / write kernels at the served widths against the
    repo's jax.numpy references (ops/attention.decode_attend over
    kv_pool.gather_layer_dense; kv_pool.write_token_layer_paged), on
    seeded inputs, bf16 and int8 pools. Depth is cut to 2 layers — a layer
    is an index into the pool here — everything else is the server's.
    ``parent``: another checkout's ``ops.pallas_attention`` (``--parent``),
    whose outputs the copy-skipping kernels' must equal bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa
    from aws_k8s_ansible_provisioner_tpu.ops.attention import (
        decode_attend, make_decode_attend_carry_paged,
        make_mixed_attend_carry_paged)
    from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp

    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    B, MP, L = slots, window // page, 2
    P = B * MP + 1
    layer = jnp.int32(1)
    rng = np.random.default_rng(21)
    # a permuted table (page 0 = scratch stays out), ragged lengths mixing a
    # full window, page edges, one token, and mid-page
    table = jnp.asarray((rng.permutation(B * MP) + 1)
                        .reshape(B, MP).astype(np.int32))
    base = [window, 1, page, page + 1, 2 * page - 1, 300, 1100, 7]
    lengths = jnp.asarray([base[i % len(base)] for i in range(B)], jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(21), 8)
    q = jax.random.normal(keys[0], (B, 1, Hq, D), jnp.bfloat16)
    shape = (L, P, Hkv, page, D)

    def close(name, got, want):
        got = np.asarray(jnp.asarray(got, jnp.float32))
        want = np.asarray(jnp.asarray(want, jnp.float32))
        check(np.all(np.isfinite(got)), f"{name}: non-finite output")
        err = float(np.max(np.abs(got - want)
                           / (KERNEL_TOL + KERNEL_TOL * np.abs(want))))
        check(err <= 1.0, f"{name}: off by {err:.2f}x the tolerance "
                          f"(atol=rtol={KERNEL_TOL})")
        return float(np.max(np.abs(got - want)))

    for quant in (False, True):
        tag = "int8" if quant else "bf16"
        kf = jax.random.normal(keys[1], shape, jnp.bfloat16)
        vf = jax.random.normal(keys[2], shape, jnp.bfloat16)
        if quant:
            # the engine's int8 pool layout: scale leaves lane-padded
            pad = [(0, 0)] * 3 + [(0, kvp.scale_lanes(page) - page)]
            k8, ks = kvp.quantize_rows(kf)
            v8, vs = kvp.quantize_rows(vf)
            pool = {"k": k8, "v": v8,
                    "ks": jnp.pad(ks, pad), "vs": jnp.pad(vs, pad)}
            del k8, v8, ks, vs
            skw = dict(pool_ks=pool["ks"], pool_vs=pool["vs"])
        else:
            pool = {"k": kf, "v": vf}
            skw = {}
        del kf, vf

        def dense_view(tab, of=None):
            d = kvp.gather_layer_dense(of or pool, layer, tab)
            if quant:
                return (kvp.dequantize(d["k"], d["ks"]),
                        kvp.dequantize(d["v"], d["vs"]))
            return d["k"], d["v"]

        with jax.default_matmul_precision("highest"):
            ck, cv = dense_view(table)
            ref = decode_attend(q, ck, cv, lengths)
        # ragged rows, as mixed_step packs them: every decode row (slot 3's
        # is dead: it is the one chunking), then a two-page chunk of slot 3
        # at positions 300.. of which 40 rows are prompt (limit = position
        # + 1) and the rest padding (limit 0: nothing fetched, output zero)
        C = 2 * page
        crow = 300 + jnp.arange(C, dtype=jnp.int32)
        limits = jnp.concatenate(
            [jnp.where(jnp.arange(B) == 3, 0, lengths),
             jnp.where(jnp.arange(C) < 40, crow + 1, 0)])
        rmap = slot_rows(B, C, 3)
        q3 = jax.random.normal(keys[3], (B + C, Hq, D), jnp.bfloat16)
        with jax.default_matmul_precision("highest"):
            ck, cv = dense_view(table[rmap])
            rref = decode_attend(q3[:, None], ck, cv, limits)[:, 0]
        rref = jnp.where((limits > 0)[:, None, None], rref, 0)
        del ck, cv
        for bb in bblocks:
            out = pa.decode_attend_pallas_paged(
                q, pool["k"], pool["v"], lengths, layer, table,
                interpret=interpret, bblock=bb, **skw)
            e1 = close(f"decode_attend_pallas_paged {tag} bb={bb}", out, ref)
            out = pa.ragged_attend_pallas_paged(
                q3, pool["k"], pool["v"], limits, layer, table, rmap,
                interpret=interpret, bblock=bb, **skw)
            e2 = close(f"ragged_attend_pallas_paged {tag} bb={bb}", out,
                       rref)
            check(not np.asarray(out, np.float32)[np.asarray(limits) == 0]
                  .any(), f"ragged {tag} bb={bb}: a dead row is not zero")
            say(f"parity: decode/ragged paged {tag} bb={bb}: max abs err "
                f"{e1:.2e} / {e2:.2e} (tol {KERNEL_TOL})")
        # the served mixed step's WIDTH: every decode row, then a chunk as
        # wide as the window — slot 3's prompt of 640 tokens from row 0 —
        # so the grid steps are the wide tiles of pallas_attention
        # ._tile_rows: the first straddles decode and chunk rows (its
        # blocks run one by one), the chunk's first rows read ONE page,
        # the tile that holds row 640 is part dead and 1,400 rows of
        # tiles are dead. The chunk's reference a block of rows at a time
        # against slot 3's gathered view (2,048 rows at once: 8.6 GB)
        bb = max(bblocks)
        wide_n = jnp.arange(window, dtype=jnp.int32)
        wlimits = jnp.concatenate(
            [jnp.where(jnp.arange(B) == 3, 0, lengths),
             jnp.where(wide_n < 640, wide_n + 1, 0)])
        qw = jax.random.normal(keys[3], (B + window, Hq, D), jnp.bfloat16)
        out = pa.ragged_attend_pallas_paged(
            qw, pool["k"], pool["v"], wlimits, layer, table,
            slot_rows(B, window, 3), interpret=interpret, bblock=bb, **skw)
        with jax.default_matmul_precision("highest"):
            ck, cv = dense_view(table)
            want = [decode_attend(qw[:B, None], ck, cv, wlimits[:B])[:, 0]]
            ck, cv = dense_view(table[3][None])
            want += [decode_attend(qw[None, B + s0:B + s0 + 512], ck, cv,
                                   jnp.asarray([s0 + 1]))[0]
                     for s0 in range(0, window, 512)]
        want = jnp.where((wlimits > 0)[:, None, None],
                         jnp.concatenate(want), 0)
        tile = pa._tile_rows(B + window, pa._resolve_bb(bb, B + window),
                             Hq, D, page, pool["k"].dtype)
        e4 = close(f"ragged_attend_pallas_paged {tag} bb={bb}, tiles of "
                   f"{tile} rows", out, want)
        check(not np.asarray(out, np.float32)[np.asarray(wlimits) == 0]
              .any(), f"ragged {tag}, tiles of {tile}: a dead row is not "
                      f"zero")
        say(f"parity: ragged paged {tag} at the served width, {B}+{window} "
            f"rows in tiles of {tile} (one straddling, one part dead, "
            f"one-page rows): max abs err {e4:.2e} (tol {KERNEL_TOL})")
        del ck, cv, want, qw
        # the decode program's call: make_decode_attend_carry_paged writes
        # each slot's row, then hands the kernel the rows IN ORDER OF LENGTH
        # (every slot-order block of 8 here holds a one-page row beside a
        # full window) and un-permutes the context — compiled, against the
        # reference and BITWISE against the slot-order call on the same pool
        knew, vnew = (jax.random.normal(k, (B, 1, Hkv, D), jnp.bfloat16)
                      for k in keys[6:8])
        attend = make_decode_attend_carry_paged(lengths - 1, table,
                                                impl="pallas", bblock=bb)
        out, (wrote, _) = jax.jit(attend)(q, knew, vnew, (pool, layer))
        wkw = dict(pool_ks=wrote["ks"], pool_vs=wrote["vs"]) if quant else {}
        slot_order = pa.decode_attend_pallas_paged(
            q, wrote["k"], wrote["v"], lengths, layer, table,
            interpret=interpret, bblock=bb, **wkw)
        check(bool(jnp.array_equal(out, slot_order)),
              f"decode rows in order of length {tag} bb={bb}: the context "
              f"is not bitwise the slot-order call's")
        with jax.default_matmul_precision("highest"):
            e3 = close(f"decode rows in order of length {tag} bb={bb}", out,
                       decode_attend(q, *dense_view(table, wrote), lengths))
        say(f"parity: decode rows in order of length {tag}, {B} slots x "
            f"bb={bb}, {Hkv} KV heads: bitwise the slot-order call, max abs "
            f"err {e3:.2e} (tol {KERNEL_TOL})")
        del wrote, wkw, slot_order
        copy_skip_parity(pa, parent, pool, skw, table, layer, page, window,
                         (q, q3), limits, rmap, bblocks, tag, interpret)
        if not interpret:
            ragged_call_time(pa, pool, skw, table, lengths, layer, Hq, D,
                             window, max(bblocks), tag)

        # write kernels: one new row per slot at each slot's length (the
        # full-window slot's row is out of range and must DROP)
        new = jax.random.normal(keys[4], (B, Hkv, D), jnp.bfloat16)
        want = kvp.write_token_layer_paged(
            pool, layer, lengths, table, new[:, None], new[:, None], page)
        if quant:
            gk, gks = pa.cache_write_row_quant_paged(
                pool["k"], pool["ks"], new, lengths, table, layer,
                interpret=interpret)
            check(bool(jnp.array_equal(gk, want["k"])),
                  "cache_write_row_quant_paged: int8 rows differ")
            close("cache_write_row_quant_paged scales", gks, want["ks"])
        else:
            gk = pa.cache_write_row_paged(pool["k"], new, lengths, table,
                                          layer, interpret=interpret)
            check(bool(jnp.array_equal(gk, want["k"])),
                  "cache_write_row_paged: rows differ from the scatter")
        # mixed_step's writes as its attend makes them: the decode rows by
        # the row kernel (slot 3, the one chunking, dropped), the chunk as
        # ONE span of slot 3's page run — 40 rows from row 300, 44 rows into
        # a page and across its edge — its padding rows unwritten
        prow = jnp.concatenate(
            [jnp.where(jnp.arange(B) == 3, -1, lengths),
             jnp.where(jnp.arange(C) < 40, crow, -1)])
        pnew = jax.random.normal(keys[5], (B + C, Hkv, D), jnp.bfloat16)
        want = kvp.write_token_layer_paged(
            pool, layer, prow, table[rmap], pnew[:, None], pnew[:, None],
            page)
        attend = make_mixed_attend_carry_paged(
            prow[:B], jnp.int32(300), jnp.int32(40), limits, table, rmap,
            impl="pallas", bblock=max(bblocks))
        _, (got, _) = jax.jit(attend)(q3[None], pnew[None], pnew[None],
                                      (pool, layer))
        for name in want:
            if name in ("ks", "vs"):
                close(f"mixed_step writes {tag}: {name}", got[name],
                      want[name])
                continue
            # int8: the compiled quantizer may round one step from the
            # eager one (kv_pool.quantize_rows); bf16 rows are copies
            diff = np.abs(np.asarray(got[name], np.float32)
                          - np.asarray(want[name], np.float32))
            off = int((diff > 0).sum())
            check(diff.max() <= (1 if quant else 0) and off <= 8,
                  f"mixed_step writes {tag}: pool leaf {name} differs from "
                  f"the row-by-row scatter at {off} elements (max "
                  f"{diff.max()})")
        say(f"parity: paged writes {tag}: equal to the jnp scatter, one row "
            f"a slot (kernel) and mixed_step's rows (kernel + chunk span)")
        if not interpret:
            chunk_write_time(kvp, pool, table, layer, page, window, tag)
        del got
        del pool, want, gk


def slot_rows(slots: int, chunk: int, pslot: int):
    """mixed_step's row map: packed row -> the row of the table (one a
    slot) it reads — every decode row its own slot's, the ``chunk`` rows
    slot ``pslot``'s."""
    import jax.numpy as jnp

    return jnp.concatenate([jnp.arange(slots, dtype=jnp.int32),
                            jnp.full((chunk,), pslot, jnp.int32)])


def ragged_tables(mod, table, rmap) -> tuple:
    """The table operands of ``mod.ragged_attend_pallas_paged``: ``(table,
    row_map)``; for the kernels of a checkout from before PR 46
    (``--parent``) the table row a packed row they took."""
    import inspect

    if "row_map" in inspect.signature(
            mod.ragged_attend_pallas_paged).parameters:
        return table, rmap
    return (table[rmap],)


def load_kernels(root: str):
    """``ops/pallas_attention.py`` of the checkout at ``root`` as a module
    of its own, beside this checkout's."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "parent_pallas_attention", os.path.join(
            root, "aws_k8s_ansible_provisioner_tpu", "ops",
            "pallas_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def poison_vmem(interpret: bool) -> None:
    """A Pallas call that leaves NaN in 12 of the 16 MiB of VMEM a kernel
    gets (every 16 bits 0x7FC0: NaN read as bf16 or as float32), so that
    the call after it does not pass by what its page buffers happened to
    hold."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, step = 12 * 1024 * 1024 // (128 * 2), 1024

    def kernel(o_ref, scratch):
        def fill(i, carry):
            scratch[pl.ds(pl.multiple_of(i * step, step), step)] = \
                jnp.full((step, 128), jnp.nan, scratch.dtype)
            return carry

        jax.lax.fori_loop(0, rows // step, fill, 0)
        o_ref[:] = scratch[rows - 16:]

    out = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((16, 128), jnp.bfloat16),
        scratch_shapes=[pltpu.VMEM((rows, 128), jnp.bfloat16)],
        interpret=interpret)()
    check(np.isnan(np.asarray(out, np.float32)).all(),
          "the poisoning call left no NaN")


def copy_skip_parity(pa, parent, pool, skw, table, layer, page, window,
                     queries, limits, rmap, bblocks, tag, interpret) -> None:
    """A row past its own pages starts no copy (PR 45): the decode entry and
    the ragged entry's decode-row tile, each call made right after
    :func:`poison_vmem`, BITWISE against the same rows in blocks of ONE (a
    row alone walks its own range: the copies the parent made) and, where
    ``parent`` is given, against the parent's kernel in the same blocks.
    Every block of 8 holds a full window beside a one-page row, a row that
    ends on a page edge and a DEAD row; under the window (4 pages) the long
    rows' first live page lies above their block's."""
    import jax.numpy as jnp
    import numpy as np

    q, q3 = queries
    B = table.shape[0]
    base = [window, 1, page, 0, 2 * page - 1, 300, 1100, 7]
    lens = jnp.asarray([min(base[i % len(base)], window) for i in range(B)],
                       jnp.int32)
    rlim = jnp.concatenate([jnp.where(limits[:B] > 0, lens, 0), limits[B:]])

    def same(name, got, want, rows=slice(None)):
        got, want = (np.asarray(a, np.float32)[rows] for a in (got, want))
        check(np.isfinite(got).all(), f"{name}: non-finite output (a page "
                                      f"buffer nothing filled was read)")
        check(np.array_equal(got, want),
              f"{name}: {int((got != want).sum())} elements differ, max "
              f"{np.abs(got - want).max():.3e}")

    for win in (0, 4 * page):
        def decode(mod, bb):
            return mod.decode_attend_pallas_paged(
                q, pool["k"], pool["v"], lens, layer, table,
                interpret=interpret, window=win, bblock=bb, **skw)

        def ragged(mod, bb):
            return mod.ragged_attend_pallas_paged(
                q3, pool["k"], pool["v"], rlim, layer,
                *ragged_tables(mod, table, rmap), interpret=interpret,
                window=win, bblock=bb, **skw)

        alone = decode(pa, 1), ragged(pa, 1)
        for bb in (b for b in bblocks if b > 1):
            name = f"copy skip {tag} bb={bb} window={win}"
            poison_vmem(interpret)
            out = decode(pa, bb)
            same(f"{name}: decode entry against blocks of one row", out,
                 alone[0])
            check(not np.asarray(out, np.float32)[np.asarray(lens) == 0]
                  .any(), f"{name}: a dead row of the decode entry is not "
                          f"zero")
            poison_vmem(interpret)
            rout = ragged(pa, bb)
            # (the chunk rows of a sharing block take another arithmetic
            # than rows alone: the decode rows only)
            same(f"{name}: ragged entry's decode rows against blocks of one "
                 f"row", rout, alone[1], slice(0, B))
            if parent is not None:
                same(f"{name}: decode entry against the parent's kernel",
                     out, decode(parent, bb))
                same(f"{name}: ragged entry against the parent's kernel",
                     rout, ragged(parent, bb))
            say(f"parity: {name}: decode entry and the ragged entry's decode "
                f"rows after a NaN-filled VMEM, bitwise blocks of one row"
                + ("" if parent is None else " and the parent's kernel")
                + f" ({int((np.asarray(lens) == 0).sum())} dead rows zero)")


def chunk_write_time(kvp, pool, table, layer, page, chunk, tag) -> None:
    """Device time of ONE layer's chunk write (K and V) at the served mixed
    step's shape: a ``chunk``-row chunk of slot 3 from row 0 or 1, 640 rows
    of it a prompt. 64 writes in one program, so the host's dispatch
    (0.7 ms a call, ten times the write) is not what is timed."""
    import jax
    import jax.numpy as jnp

    Hkv, D = pool["k"].shape[2], pool["k"].shape[4]
    new = jax.random.normal(jax.random.PRNGKey(6), (1, chunk, Hkv, D),
                            jnp.bfloat16)
    reps = 64

    def body(i, pool):
        # the start moves, so nothing is hoisted out of the loop
        return kvp.write_chunk_paged_layer(pool, layer, table[3], i % 2, new,
                                           new, page, n_valid=640)

    write = jax.jit(lambda pool: jax.lax.fori_loop(0, reps, body, pool),
                    donate_argnums=(0,))
    pool = jax.block_until_ready(write(dict(pool)))
    t0, n = time.monotonic(), 5
    for _ in range(n):
        pool = write(pool)
    jax.block_until_ready(pool)
    say(f"chunk write {tag}, {chunk}-row chunk, 640 live, K and V of one "
        f"layer: {(time.monotonic() - t0) / (n * reps) * 1e3:.3f} ms")


def call_ms(call, n: int) -> float:
    """Milliseconds a call of ``call`` takes on the device: ``n`` of them
    enqueued back to back after one that compiled it."""
    call().block_until_ready()
    t0 = time.monotonic()
    for _ in range(n):
        out = call()
    out.block_until_ready()
    return (time.monotonic() - t0) / n * 1e3


def ragged_call_time(pa, pool, skw, table, lengths, layer, Hq, D, chunk,
                     bb, tag) -> None:
    """Device time of ONE ragged call at the served mixed step's shape:
    every slot's decode row plus a ``chunk``-row chunk of slot 3 holding a
    640-token prompt (the closed cells' mean) — with every chunk row given a
    causal limit, as mixed_step did before PR 25, and with the 1,408 padding
    rows dead, as it does now."""
    import jax
    import jax.numpy as jnp

    B = table.shape[0]
    j = jnp.arange(chunk, dtype=jnp.int32)
    tables = ragged_tables(pa, table, slot_rows(B, chunk, 3))
    q = jax.random.normal(jax.random.PRNGKey(5), (B + chunk, Hq, D),
                          jnp.bfloat16)
    decode = jnp.where(jnp.arange(B) == 3, 0, lengths)
    for name, climit in (("every chunk row live", j + 1),
                         ("padding rows dead", jnp.where(j < 640, j + 1, 0))):
        limits = jnp.concatenate([decode, climit])

        def call():
            return pa.ragged_attend_pallas_paged(
                q, pool["k"], pool["v"], limits, layer, *tables, bblock=bb,
                **skw)

        say(f"ragged call {tag} bb={bb}, {B}+{chunk} rows, 640-token "
            f"chunk, {name}: {call_ms(call, 20):.3f} ms")


# ---------------------------------------------------------------------------
# Phase (--chips 4): shards and the compiled decode program
# ---------------------------------------------------------------------------


def check_routing_counts(port: int, cfg) -> None:
    """An MoE model's /metrics say what its expert layers were given."""
    status, raw = http_json(port, "GET", "/metrics")
    check(status == 200, f"/metrics -> {status}")
    m = parse_metrics(raw.decode())
    tot = {k: sum(v for name, v in m.items() if name.startswith(k))
           for k in ("tpu_serve_moe_routed_rows_total",
                     "tpu_serve_moe_experts_hit_total",
                     "tpu_serve_moe_forward_passes_total")}
    passes = tot["tpu_serve_moe_forward_passes_total"]
    check(passes > 0, f"no MoE forward pass was counted: {tot}")
    hit = tot["tpu_serve_moe_experts_hit_total"] / passes
    rows = tot["tpu_serve_moe_routed_rows_total"] / passes
    # an expert share counts the experts HELD here: one live row may hit none
    check((0 if cfg.expert_share else 1) <= hit <= cfg.num_experts,
          f"experts hit a pass: {hit}")
    say(f"routing: {int(passes)} forward passes of decode and mixed "
        f"dispatches; {rows:.1f} routed rows and {hit:.1f} of "
        f"{cfg.num_experts} experts hit a layer, mean; largest group last "
        f"{m.get('tpu_serve_moe_group_rows_max')}")


def expert_forms_parity(cfg, rows: int) -> None:
    """The two forms of the expert FFN (ops/moe.py: every expert over every
    row, and rows sorted into XLA's grouped matmul) on one layer of seeded
    int8 stacks at the served widths: the same sum by two routes, idle rows
    included. The served programs use the first for decode and short
    prefills, the second for the mixed step and long prefills."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aws_k8s_ansible_provisioner_tpu.ops import moe

    E, H, inter = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
    ks = jax.random.split(jax.random.PRNGKey(31), 5)

    def stack(key, din, dout, std):
        q = jax.random.randint(key, (E, din, dout), -127, 128, jnp.int8)
        return {"kernel": q,
                "scale": jnp.full((E, dout), std / 73.6, jnp.float32)}

    R = cfg.router_width     # an expert share routes over more than E
    p = {"router": {"kernel": (jax.random.normal(ks[0], (H, R))
                               * 2.0 / H ** 0.5).astype(jnp.bfloat16),
                    "bias": jnp.zeros((R,), jnp.float32)},
         "w_gate": stack(ks[1], H, inter, 0.9 / H ** 0.5),
         "w_up": stack(ks[2], H, inter, 0.9 / H ** 0.5),
         "w_down": stack(ks[3], inter, H, 0.9 / inter ** 0.5)}
    x = jax.random.normal(ks[4], (rows, H), jnp.bfloat16)
    live = jnp.arange(rows) % 5 != 3
    want, gs = jax.jit(lambda x: moe._sorted_groups(cfg, x, p, live))(x)
    got, gk = jax.jit(lambda x: moe._every_expert(cfg, x, p, live))(x)
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    check(np.array_equal(np.asarray(gs), np.asarray(gk)),
          "the two forms count different groups")
    check(np.all(np.isfinite(got)) and np.all(np.isfinite(want)),
          "expert FFN: non-finite output")
    dead = np.asarray(~live)
    check(not got[dead].any() and not want[dead].any(),
          "an idle row got an expert's output")
    err = float(np.max(np.abs(got - want)
                       / (KERNEL_TOL + KERNEL_TOL * np.abs(want))))
    check(err <= 1.0, f"expert FFN: the two forms are {err:.2f}x the "
                      f"tolerance apart (atol=rtol={KERNEL_TOL})")
    say(f"expert FFN parity [{rows} rows, {E} experts, int8 stacks]: every-"
        f"expert vs sorted max |diff| "
        f"{float(np.max(np.abs(got - want))):.4f} on outputs of max "
        f"|{float(np.abs(want).max()):.2f}|; experts hit "
        f"{int((np.asarray(gs) > 0).sum())}")


def sala_kernel_parity(cfg, slots: int, window: int, page: int, bb: int,
                       chunk: int, interpret: bool, parent=None) -> None:
    """The kernels a model with selecting attention and Lightning layers
    adds, at the served widths (``groups`` 16, 512-page tables at the 32k
    window) against jax.numpy: the decode kernel over LISTS of selected
    pages, the ragged kernel under BITMASKS with one table row a slot —
    every slot's decode row and a ``chunk``-row chunk whose contexts
    straddle the dense length, the served mixed step's rows: the wide
    tiles of ``pallas_attention._tile_rows`` with the selection as a lane
    mask, the decode rows' tile block by block —, the selector's row add,
    and the Lightning decode update — on seeded inputs and seeded (random)
    selections that differ per KV head. On the chip the ragged call is
    also TIMED, a first chunk (every page chosen) and the straddling one;
    with ``parent`` (``--parent``: that checkout's kernels) in the order
    parent, change, change, parent on the same inputs, the outputs
    compared."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp
    from aws_k8s_ansible_provisioner_tpu.ops import linear_attention as la
    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa
    from aws_k8s_ansible_provisioner_tpu.ops import sparse_attention as sa

    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    B, MP, L = slots, window // page, 2
    P = B * MP + 1
    layer = jnp.int32(1)
    rng = np.random.default_rng(34)
    table = jnp.asarray((rng.permutation(B * MP) + 1)
                        .reshape(B, MP).astype(np.int32))
    dense_len, K = cfg.sparse_dense_len, cfg.sparse_select_width
    base = [window, dense_len + 1, page + 1, 2 * dense_len - 7, 0, 300,
            dense_len + 3 * page, 7]
    lengths = np.asarray([min(base[i % len(base)], window) for i in range(B)],
                         np.int32)
    keys = jax.random.split(jax.random.PRNGKey(34), 8)
    pool = {n: jax.random.normal(k, (L, P, Hkv, page, D), jnp.bfloat16)
            for n, k in (("k", keys[1]), ("v", keys[2]))}

    def close(name, got, want, tol=KERNEL_TOL):
        got = np.asarray(jnp.asarray(got, jnp.float32))
        want = np.asarray(jnp.asarray(want, jnp.float32))
        check(np.all(np.isfinite(got)), f"{name}: non-finite output")
        err = float(np.max(np.abs(got - want) / (tol + tol * np.abs(want))))
        check(err <= 1.0, f"{name}: off by {err:.2f}x the tolerance "
                          f"(atol=rtol={tol})")
        return float(np.max(np.abs(got - want)))

    def random_selection(lims):
        """[R, Hkv, MP] bool: the last page, page 0 and a random subset of
        the rest, at most topk pages, another set a KV head."""
        sel = np.zeros((len(lims), Hkv, MP), bool)
        for r, n in enumerate(lims):
            live = -(-int(n) // page)
            for h in range(Hkv):
                if not live:
                    continue
                pick = rng.permutation(live)[:max(1, cfg.sparse_topk - 2)]
                sel[r, h, pick] = True
                sel[r, h, [0, live - 1]] = True
                if n < dense_len:
                    sel[r, h, :live] = True
        return jnp.asarray(sel)

    dense = kvp.gather_layer_dense(pool, layer, table)     # [B, Hkv, S, D]
    # -- decode: lists ------------------------------------------------------
    q = jax.random.normal(keys[0], (B, 1, Hq, D), jnp.bfloat16)
    sel = random_selection(lengths)
    pages, cnt = sa.as_list(cfg, sel)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda q1, k1, v1, l1, s1: sa._attend_rows(
            q1, k1, v1, l1[None], s1[None], page))(
                q, dense["k"], dense["v"], jnp.asarray(lengths), sel)
    worst = 0.0
    for b in sorted({1, bb}):
        got = pa.decode_attend_pallas_paged_select(
            q, pool["k"], pool["v"], jnp.asarray(lengths), layer, table,
            pages, cnt, interpret=interpret, bblock=b)
        worst = max(worst, close(f"decode over selected pages, bblock {b}",
                                 got, want))
        check(not np.asarray(got, np.float32)[lengths == 0].any(),
              "a dead row's output is not zero")
    differ = int((np.asarray(sel)[:, 0] != np.asarray(sel)[:, 1])
                 .any(axis=-1).sum())
    say(f"kernel parity [decode, selected pages, groups {Hq // Hkv}, "
        f"{MP}-page tables, lists of {K}]: max |diff| {worst:.4f}; "
        f"{differ} of {B} rows read other pages a KV head")
    # -- ragged: bitmasks, one table row a slot -------------------------------
    C, pslot = chunk, 3
    off = max(dense_len - 3 * C // 8, 0) + page // 2 \
        if dense_len + C <= window else page
    dec = np.where(np.arange(B) == pslot, 0, lengths)
    lims = np.concatenate([dec, off + 1 + np.arange(C)]).astype(np.int32)
    lims[B + C - 5:] = 0                           # the chunk's padding
    row_map = slot_rows(B, C, pslot)
    q3 = jax.random.normal(keys[3], (B + C, Hq, D), jnp.bfloat16)
    sel = random_selection(lims)
    with jax.default_matmul_precision("highest"):
        want = jax.lax.map(
            lambda a: sa._attend_rows(a[0][None], dense["k"][a[3]],
                                      dense["v"][a[3]], a[1][None],
                                      a[2][None], page)[0],
            (q3, jnp.asarray(lims), sel, row_map))
    got = pa.ragged_attend_pallas_paged_select(
        q3, pool["k"], pool["v"], jnp.asarray(lims), layer, table, row_map,
        sa.as_bits(sel), interpret=interpret, bblock=bb)
    worst = close(f"ragged under page masks, bblock {bb}", got, want)
    tile = pa._tile_rows(B + C, pa._resolve_bb(bb, B + C), Hq, D, page,
                         pool["k"].dtype)
    say(f"kernel parity [ragged, page masks, {B} decode rows + a {C}-row "
        f"chunk at {off}, one table row a slot, tiles of {tile} rows]: max "
        f"|diff| {worst:.4f}")
    del dense
    if not interpret:
        first = np.concatenate([dec, 1 + np.arange(C)]).astype(np.int32)
        for name, lm in (("a first chunk", first),
                         (f"a chunk at {off}", lims)):
            bits = sa.as_bits(sel if lm is lims else random_selection(lm))
            outs = []
            for tag, mod in [("parent", parent), ("change", pa),
                             ("change", pa), ("parent", parent)] \
                    if parent else [("change", pa)]:
                def call():
                    return mod.ragged_attend_pallas_paged_select(
                        q3, pool["k"], pool["v"], jnp.asarray(lm), layer,
                        table, row_map, bits, bblock=bb)

                say(f"ragged call under page masks [{tag}] bb={bb}, "
                    f"{B}+{C} rows, {name}: {call_ms(call, 10):.3f} ms")
                outs.append(call())
            if parent:
                close(f"ragged under page masks, {name}: change against "
                      f"parent", outs[1], outs[0])
                check(bool((outs[1] == outs[2]).all()), "two calls differ")
    # -- the selector's row add ----------------------------------------------
    runs = page // cfg.sparse_kernel_stride
    kc = jax.random.normal(keys[4], (L, P, Hkv, runs, D), jnp.float32)
    knew = jax.random.normal(keys[5], (B, Hkv, D), jnp.bfloat16)
    rows = jnp.asarray(np.where(np.arange(B) == pslot, -1,
                                np.minimum(lengths, window - 1)), jnp.int32)
    want = sa.add_rows(cfg, kc, layer, rows, table, knew, "xla")
    got = pa.selector_add_row_paged(kc + 0, knew, rows, table, layer,
                                    stride=cfg.sparse_kernel_stride,
                                    interpret=interpret)
    worst = close("selector row add", got, want, 1e-5)
    say(f"kernel parity [selector row add, {runs} runs a page]: max |diff| "
        f"{worst:.6f}")
    # -- the Lightning decode update ------------------------------------------
    H, d = cfg.lightning_num_heads, cfg.lightning_head_dim
    nl = cfg.layer_pattern.count("l")
    st = jax.random.normal(keys[6], (nl, 1, B, H, d, d), jnp.float32)
    ks = jax.random.split(keys[7], 3)
    ql, kl, vl = (jax.random.normal(k, (B, H, d), jnp.float32) for k in ks)
    live = jnp.asarray(lengths > 0)
    from aws_k8s_ansible_provisioner_tpu.models.layers import lightning_slopes

    g, beta = la._lin_decay(lightning_slopes(H), live)
    want_o, want_s = la.lightning_step(st[nl - 1, 0], ql, kl, vl, g, beta)
    got_o, got_s = la.kda_decode_update(
        st + 0, jnp.int32(nl - 1), 0, ql, kl, vl,
        jnp.broadcast_to(g[..., None], ql.shape), beta, interpret=interpret,
        delta_rule=False)
    worst = max(close("Lightning decode update: output", got_o, want_o, 1e-3),
                close("Lightning decode update: state", got_s[nl - 1, 0],
                      want_s, 1e-3))
    check(bool((got_s[0] == st[0]).all()) if nl > 1 else True,
          "the Lightning update touched another layer's state")
    say(f"kernel parity [Lightning decode update, {H} heads of {d}, in "
        f"place]: max |diff| {worst:.5f}")


def handed_selection_program(cfg, T: int):
    """The program's own stateless forward over one T-token sequence
    (bfloat16, the served tree, ``lightning_span`` from zero, the selecting
    attention dense under its selection), unrolled layer by layer: each
    selecting layer returns the blocks it chose and, where ``use`` is set,
    reads the ``handed`` ones [selecting layers, T, Hkv, blocks] instead.
    Jitted: (tree, tokens [T], handed, use) -> (logprob rows [T - 1, V]
    float32, the choices made)."""
    import jax
    import jax.numpy as jnp

    from aws_k8s_ansible_provisioner_tpu.models import layers as L
    from aws_k8s_ansible_provisioner_tpu.ops import linear_attention as la
    from aws_k8s_ansible_provisioner_tpu.ops import sparse_attention as sa

    def program(tree, toks, handed, use):
        pos = jnp.arange(T, dtype=jnp.int32)[None]
        x, cos, sin = L._embed_inputs(tree, cfg, toks[None], pos)
        picked, seen = [], {"attn": 0, "lightning": 0}
        for kind in cfg.layer_pattern:
            stack = "lightning" if kind == "l" else "attn"
            lp = jax.tree.map(lambda a, i=seen[stack]: a[i],
                              tree["layers"][stack])
            seen[stack] += 1
            if kind == "l":
                x, _ = L.lightning_block(cfg, lp, x, cos, sin,
                                         la.recur_from_zero, (None, 0, 0))
                continue
            own = sa.make_stateless_attend_select(cfg)
            took = sa.make_stateless_attend_select(
                cfg, handed=handed[len(picked)][None])

            def attend(q, k, v, cl, own=own, took=took):
                ctx_own, sel = own(q, k, v, None)
                ctx = jnp.where(use, took(q, k, v, None)[0], ctx_own)
                return ctx, sel[0]

            x, sel = L.decoder_block(cfg, lp, x, cos, sin, attend, None)
            picked.append(sel)
        logits = L._final_logits(tree, cfg, x)[0].astype(jnp.float32)
        return jax.nn.log_softmax(logits, axis=-1)[:-1], jnp.stack(picked)

    return jax.jit(program)


def check_selection_cause(cfg, params, plain, T: int,
                          strict: bool = True) -> None:
    """Where a selecting model's distance from its reference comes from,
    directly, at the served size, on the served int8 tree.

    The reference selects on its own float32 activations; the program on
    bfloat16 ones. Where the 64th and 65th block scores of a token's KV head
    are nearer than that noise the two read different blocks. So this phase
    (1) COUNTS the (token, layer, KV head) triples past the dense length
    whose selected sets differ, and how many blocks differ; (2) hands each
    side the OTHER's choices and measures what is left — everything but
    selection ties; (3) under handed selection, takes the LEARNED blocks
    away (the forced ones only: the first block and the local window) and
    separately hands every token its neighbour KV head's set: each has to
    move the logprobs, else the comparison could not tell a selector that
    chooses from one that does not.

    Distances are over every position of one ``T``-token sequence past the
    dense length: |program - reference| of the reference's most likely
    token, the worst position and the median over all windows of 16
    consecutive positions of the window's worst (16 positions are what one
    comparison of the benchmark sees)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    mc = dataclasses.asdict(cfg)
    program = handed_selection_program(cfg, T)
    ids = np.random.default_rng(20260929).integers(32, 127, T)
    toks = jnp.asarray(ids, jnp.int32)
    ns, Hkv = cfg.num_attn_layers, cfg.num_kv_heads
    NB = -(-T // cfg.sparse_block_size)
    none = jnp.zeros((ns, T, Hkv, NB), bool)
    at = np.arange(T - 1)
    past = np.arange(T) >= cfg.sparse_dense_len        # rows that select

    def reference(selection=None):
        with jax.default_matmul_precision("highest"):
            lg, sel = plain.forward(mc, params, list(ids), T - 1,
                                    selection=selection)
            return (np.asarray(jax.nn.log_softmax(lg, axis=-1)),
                    np.asarray(sel))

    def served(handed=None):
        lp, sel = program(params, toks, none if handed is None
                          else jnp.asarray(handed), handed is not None)
        return np.asarray(lp), np.asarray(sel)

    def apart(a, b, tok):
        d = np.abs(a[at, tok] - b[at, tok])[cfg.sparse_dense_len:]
        if len(d) < 16:
            d = np.abs(a[at, tok] - b[at, tok])
        worst16 = [d[i:i + 16].max() for i in range(0, len(d) - 15)]
        return float(d.max()), float(np.median(worst16))

    t0 = time.monotonic()
    ref_lp, ref_sel = reference()
    tok = ref_lp.argmax(axis=-1)
    own_lp, own_sel = served()
    diff = (ref_sel != own_sel)[:, past]               # [ns, rows, Hkv, NB]
    triples = diff.any(axis=-1)
    by_layer = " ".join(f"{100 * x:.1f}" for x in triples.mean(axis=(1, 2)))
    say(f"selection[{T} tokens, {int(past.sum())} past the dense length, "
        f"{ns} selecting layers x {Hkv} KV heads]: the reference's and the "
        f"program's selected sets differ in {100 * triples.mean():.2f} % of "
        f"(token, layer, KV head) triples (by layer: {by_layer}); "
        f"{diff.sum() / 2 / max(1, triples.sum()):.2f} blocks swapped where "
        f"they differ, of {int(ref_sel[:, past].sum(-1).mean())} read")
    d_own = apart(own_lp, ref_lp, tok)
    d_handed = apart(served(ref_sel)[0], ref_lp, tok)
    d_back = apart(own_lp, reference(own_sel)[0], tok)
    say(f"selection cause: program vs reference on their own selections "
        f"{d_own[0]:.4f} / {d_own[1]:.4f} nats (worst position / median of "
        f"the worst of 16); the program handed the reference's "
        f"{d_handed[0]:.4f} / {d_handed[1]:.4f}; the reference handed the "
        f"program's {d_back[0]:.4f} / {d_back[1]:.4f} "
        f"({time.monotonic() - t0:.1f}s)")
    # what a flip costs, and whether the comparison sees the selector at all
    bs = cfg.sparse_block_size
    own_blk = (np.arange(T) // bs)[:, None]
    blk = np.arange(NB)[None, :]
    forced = ((blk < cfg.sparse_init_blocks)
              | (blk > own_blk - cfg.sparse_window_size // bs)) \
        & (blk <= own_blk)
    only_forced = np.where(past[None, :, None, None],
                           ref_sel & forced[None, :, None, :], ref_sel)
    swapped = ref_sel[:, :, ::-1]                      # the other head's set
    d_forced = apart(served(only_forced)[0], ref_lp, tok)
    d_swapped = apart(served(swapped)[0], ref_lp, tok)
    say(f"selection cause: the program handed ONLY the forced blocks "
        f"{d_forced[0]:.4f} / {d_forced[1]:.4f} nats; handed the OTHER KV "
        f"head's blocks {d_swapped[0]:.4f} / {d_swapped[1]:.4f}")
    if strict:
        check(d_handed[0] <= LOGPROB_NATS and d_back[0] <= LOGPROB_NATS,
              "with the selection handed over the program and the reference "
              "are still apart: the distance is not selection ties")
        check(d_forced[0] > d_handed[0] and d_swapped[0] > d_handed[0],
              "taking the learned blocks away (or reading the other KV "
              "head's) moves nothing: the comparison cannot see the "
              "selector")


def _handed_route(own, handed, use, picked: list):
    """``ops.moe.route`` wrapped for one trace of an unrolled forward pass:
    records each routed layer's own choices in ``picked`` and, where ``use``
    is set, takes ``handed[layer]`` instead — weighted by the program's own
    sigmoid scores of them, renormalised, times the model's route scale."""
    import jax
    import jax.numpy as jnp

    def route(c, x, kernel, bias=None):
        w, idx = own(c, x, kernel, bias)
        theirs = handed[len(picked)]
        picked.append(idx)
        s = jax.nn.sigmoid(x.astype(jnp.float32) @ kernel.astype(jnp.float32))
        wt = jnp.take_along_axis(s, theirs, axis=-1)
        wt = (wt / (wt.sum(axis=-1, keepdims=True) + c.route_norm_eps)
              * c.route_scale).astype(w.dtype)
        return jnp.where(use, wt, w), jnp.where(use, theirs, idx)

    return route


def handed_routing_program(cfg, T: int):
    """The program's own stateless forward over one T-token sequence
    (bfloat16, the served tree, ``kda_span`` from zero, the expert form the
    shape picks), unrolled layer by layer with ``ops.moe.route`` wrapped: it
    records each layer's choices and, where ``use`` is set, takes the
    ``handed`` ones [layers, T, k] instead (weighted by the program's own
    scores of them). Jitted: (tree, tokens [T], handed, use) -> (logprob
    rows [T - 1, V] float32, the choices made [layers, T, k])."""
    import jax
    import jax.numpy as jnp

    from aws_k8s_ansible_provisioner_tpu.models import layers as L
    from aws_k8s_ansible_provisioner_tpu.ops import linear_attention as la
    from aws_k8s_ansible_provisioner_tpu.ops import moe

    def program(tree, toks, handed, use):
        picked, own = [], moe.route
        moe.route = _handed_route(own, handed, use, picked)
        try:
            pos = jnp.arange(T, dtype=jnp.int32)[None]
            x, cos, sin = L._embed_inputs(tree, cfg, toks[None], pos)
            attend = L.make_default_attend(cfg)
            for period in range(cfg.num_periods):
                j = 0
                for kind in cfg.layer_pattern:
                    if kind == "g":
                        lp = jax.tree.map(lambda a: a[period],
                                          tree["layers"]["gqa"])
                        x, _ = L.decoder_block(
                            cfg, lp, x, cos, sin,
                            lambda q, kk, v, cl: (attend(q, kk, v, None)[0],
                                                  cl), None)
                    else:
                        lp = jax.tree.map(lambda a, j=j: a[period, j],
                                          tree["layers"]["kda"])
                        x, _ = L.kda_block(cfg, lp, x, la.recur_from_zero,
                                           (None, 0, 0))
                        j += 1
            logits = L._final_logits(tree, cfg, x)[0].astype(jnp.float32)
        finally:
            moe.route = own
        return jax.nn.log_softmax(logits, axis=-1)[:-1], jnp.stack(picked)

    return jax.jit(program)


def check_routing_cause(cfg, params, plain, rows: int,
                        strict: bool = True) -> None:
    """Where an expert share's distance from its reference comes from, and
    whether the comparison can see ONE expert — directly, at the served
    size, on the served int8 tree.

    The reference routes on its own float32 activations; the program on
    bfloat16 ones. Where a token's 8th and 9th scores are nearer than that
    noise the two pick different experts, and with a renormalised sigmoid
    router (eight nearly equal weights) each such flip swaps a whole eighth
    of the routed sum. So this phase (1) COUNTS the token-layers whose chosen
    sets differ, and those where the difference touches a held expert; (2)
    hands each side the OTHER's choices and measures what is left — with the
    held experts' down projections at ``GAIN`` times the benchmark maker's
    (their scales alone are multiplied: the big leaves are shared), where
    routing on their own the two sit far apart; (3) under handed routing,
    DROPS one held expert (its down scale zeroed in every layer) and hands
    the program a WRONG one (a held id shifted by one): each has to move the
    logprobs past LOGPROB_NATS, which on its own routing no gain lets the
    comparison tell from the flips (a flip IS a wrong expert in one
    token-layer).

    The program side is :func:`handed_routing_program`. Distances are over every position of one ``rows``-token sequence:
    |program - reference| of the reference's most likely token, the worst
    position and the median over all windows of 16 consecutive positions of
    the window's worst (16 positions are what one comparison of the
    benchmark sees)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    GAIN, T = 6.0, rows
    mc = dataclasses.asdict(cfg)
    k, E, off = cfg.num_experts_per_tok, cfg.num_experts, cfg.expert_offset
    n_layers = cfg.num_layers

    def down_scaled(tree, factor, expert=None):
        """``tree`` with the held experts' down scales times ``factor``
        (``expert``: that held expert's alone)."""
        out = dict(tree, layers={kind: dict(sub) for kind, sub
                                 in tree["layers"].items()})
        for sub in out["layers"].values():
            sc = sub["w_down"]["scale"]                  # [..., E, H]
            f = factor if expert is None else jnp.where(
                jnp.arange(E)[:, None] == expert, factor, 1.0)
            sub["w_down"] = dict(sub["w_down"], scale=sc * f)
        return out

    program = handed_routing_program(cfg, T)
    ids = np.random.default_rng(20260928).integers(32, 127, T)
    toks = jnp.asarray(ids, jnp.int32)
    none = jnp.zeros((n_layers, T, k), jnp.int32)
    hot = down_scaled(params, GAIN)
    at = np.arange(T - 1)

    def reference(tree, routing=None):
        with jax.default_matmul_precision("highest"):
            lg, idx = plain.forward(mc, tree, list(ids), T - 1,
                                    routing=routing)
            return (np.asarray(jax.nn.log_softmax(lg, axis=-1)),
                    np.asarray(idx))

    def served(tree, handed=None):
        lp, idx = program(tree, toks, none if handed is None
                          else jnp.asarray(handed), handed is not None)
        return np.asarray(lp), np.asarray(idx)

    def apart(a, b, tok):
        d = np.abs(a[at, tok] - b[at, tok])
        worst16 = [d[i:i + 16].max() for i in range(0, len(d) - 15)]
        return float(d.max()), float(np.median(worst16))

    def held(idx):      # per token-layer, which held experts were chosen
        hit = np.zeros(idx.shape[:2] + (E + 1,), bool)
        loc = np.where((idx >= off) & (idx < off + E), idx - off, E)
        np.put_along_axis(hit, loc, True, axis=-1)
        return hit[..., :E]

    def flips(a, b):
        """% of token-layers whose chosen SET differs, whose HELD set
        differs, and the first by layer (layer 0 reads the same embedding
        rows on both sides: what bfloat16 alone does to a top-k)."""
        other = (np.sort(a, axis=-1) != np.sort(b, axis=-1)).any(axis=-1)
        other_held = (held(a) != held(b)).any(axis=-1)
        return (100 * other.mean(), 100 * other_held.mean(),
                " ".join(f"{100 * x:.0f}" for x in other.mean(axis=1)))

    t0 = time.monotonic()
    ref_lp, ref_idx = reference(hot)
    tok = ref_lp.argmax(axis=-1)
    own_lp, own_idx = served(hot)
    on_held = float(held(ref_idx).sum()) / ref_idx.size
    d_own = apart(own_lp, ref_lp, tok)
    d_handed = apart(served(hot, ref_idx)[0], ref_lp, tok)
    d_back = apart(own_lp, reference(hot, own_idx)[0], tok)
    base_lp, base_idx = served(params)
    base_ref_lp, base_ref_idx = reference(params)
    d_base = apart(base_lp, base_ref_lp, base_ref_lp.argmax(axis=-1))
    f_hot, f_base = flips(ref_idx, own_idx), flips(base_ref_idx, base_idx)
    # the held expert the reference chose most often, dropped; and the
    # program handed its neighbour instead
    e = int(np.bincount((ref_idx - off)[(ref_idx >= off)
                                        & (ref_idx < off + E)],
                        minlength=E).argmax())
    d_drop = apart(served(down_scaled(hot, 0.0, e), ref_idx)[0], ref_lp, tok)
    wrong = np.where(ref_idx == off + e, off + (e + 1) % E, ref_idx)
    d_wrong = apart(served(hot, wrong)[0], ref_lp, tok)
    uses = float((ref_idx == off + e).any(axis=-1).mean())
    say(f"routing cause [{T} positions x {n_layers} layers; distances are "
        f"(worst position, median worst-of-16) in nats; "
        f"{100 * on_held:.1f} % of the pairs chosen land on a held expert]: "
        f"AT THE BENCHMARK'S GAIN {f_base[0]:.1f} % of token-layers choose "
        f"another set than the reference ({f_base[1]:.1f} % another HELD "
        f"set; by layer {f_base[2]}), each on its own routing "
        f"{d_base[0]:.3f} / {d_base[1]:.3f}; HELD DOWN PROJECTIONS x "
        f"{GAIN:g}: {f_hot[0]:.1f} % ({f_hot[1]:.1f} % held; by layer "
        f"{f_hot[2]}), own routing {d_own[0]:.3f} / {d_own[1]:.3f}; the "
        f"program handed the reference's choices {d_handed[0]:.3f} / "
        f"{d_handed[1]:.3f}; the reference handed the program's "
        f"{d_back[0]:.3f} / {d_back[1]:.3f}; handed routing with held expert "
        f"{e} DROPPED (chosen in {100 * uses:.1f} % of token-layers) "
        f"{d_drop[0]:.3f} / {d_drop[1]:.3f}, with its neighbour computed in "
        f"its place {d_wrong[0]:.3f} / {d_wrong[1]:.3f}; "
        f"{time.monotonic() - t0:.0f}s")
    if not strict:      # a tiny model's sizes say nothing about these
        return
    check(d_handed[0] <= LOGPROB_NATS and d_back[0] <= LOGPROB_NATS,
          f"with the routing handed over the program and the reference still "
          f"differ by {max(d_handed[0], d_back[0]):.3f} nats: the expert "
          f"path itself is off, not the ties")
    check(d_drop[1] > LOGPROB_NATS and d_wrong[1] > LOGPROB_NATS,
          f"one dropped ({d_drop[1]:.3f}) or wrong ({d_wrong[1]:.3f}) held "
          f"expert stays inside {LOGPROB_NATS} nats under handed routing")


def check_lower_precision(name: str, stream: dict, cfg, params, tokenizer,
                          plain) -> dict:
    """The controls: the reference computed one precision BELOW what the
    configuration states (benchmark/reference/solar_open2.py, ``lower``),
    held against the served stream by the same two limits, has to come out
    NOT correct — else the limits would pass a server computing in that
    type. Returns {control: refused}."""
    refused = check_controls(
        name, stream, cfg, params, tokenizer, plain,
        {f"lower={lower!r}": dict(lower=lower) for lower in ("state", "act")})
    return {label.split("'")[1]: no for label, no in refused.items()}


def check_controls(name: str, stream: dict, cfg, params, tokenizer, plain,
                   controls: dict) -> dict:
    """check_lower_precision for any instrument of a reference: each of
    ``controls`` ({label: keyword arguments of ``plain.logprobs``} — a
    mechanism left out, a precision lowered), held against the served
    stream by the same two limits, has to come out NOT correct. Returns
    {label: refused}."""
    import dataclasses

    import numpy as np

    refused = {}
    ids = tokenizer.encode(stream["prompt"]) \
        + [int(t) for t in stream["token_ids"]]
    n = len(stream["token_ids"])
    at, tok = np.arange(n), np.asarray(stream["token_ids"])
    for label, kw in controls.items():
        rows = plain.logprobs(dataclasses.asdict(cfg), params, ids, n, **kw)
        gap = float(np.max(rows.max(axis=-1) - rows[at, tok]))
        agree = float(np.max(np.abs(np.asarray(stream["logprobs"])
                                    - rows[at, tok])))
        refused[label] = gap > NEAR_MAX_NATS or agree > LOGPROB_NATS
        say(f"control[{name}, reference with {label}]: served token below "
            f"its maximum by <= {gap:.4f} nats (tol {NEAR_MAX_NATS}); served "
            f"vs that reference differ <= {agree:.4f} nats (tol "
            f"{LOGPROB_NATS}): "
            f"{'NOT correct' if refused[label] else 'correct'}")
    return refused


def handed_routing_list_program(cfg, T: int):
    """handed_routing_program for a model whose layers are a LIST with the
    FFN by layer (window and full attention, or gated short convolutions
    and attention; dense then routed FFNs): the
    program's own stateless forward over one T-token sequence (bfloat16,
    the served tree), unrolled layer by layer with ``ops.moe.route``
    wrapped. Jitted: (tree, tokens [T], handed [routed layers, T, k], use)
    -> (logprob rows [T - 1, V] float32, the choices made)."""
    import jax
    import jax.numpy as jnp

    from aws_k8s_ansible_provisioner_tpu.models import layers as L
    from aws_k8s_ansible_provisioner_tpu.ops import linear_attention as la
    from aws_k8s_ansible_provisioner_tpu.ops import moe

    nd = cfg.num_dense_layers

    def program(tree, toks, handed, use):
        picked, own = [], moe.route
        moe.route = _handed_route(own, handed, use, picked)
        try:
            pos = jnp.arange(T, dtype=jnp.int32)[None]
            x, cos, sin = L._embed_inputs(tree, cfg, toks[None], pos)
            attend = L.make_default_attend(cfg)

            def stateless(fn):
                return lambda q, kk, v, cl: (fn(q, kk, v, None)[0], cl)

            seen = {"conv": 0, "attn": 0}
            for i, kind in enumerate(cfg.layer_pattern):
                own_stack = "conv" if kind == "c" else "attn"
                at = seen[own_stack]
                seen[own_stack] += 1
                lp = jax.tree.map(lambda a: a[at], tree["layers"][own_stack])
                stack, j = ("ffn_moe", i - nd) if i >= nd \
                    else ("ffn_dense", i)
                fp = jax.tree.map(lambda a: a[j], tree["layers"][stack])
                if kind == "c":
                    x, _ = L.conv_block(cfg, lp, x, la.recur_from_zero,
                                        ({}, at), ffn=fp)
                    continue
                x, _ = L.decoder_block(
                    cfg, lp, x, cos, sin,
                    stateless(attend.window if kind == "w" else attend),
                    None, ffn=fp, rope=True if kind == "w" else None)
            logits = L._final_logits(tree, cfg, x)[0].astype(jnp.float32)
        finally:
            moe.route = own
        return jax.nn.log_softmax(logits, axis=-1)[:-1], jnp.stack(picked)

    return jax.jit(program)


def check_list_routing_cause(cfg, params, plain, rows: int,
                             strict: bool = True,
                             scale_control: bool = True,
                             outside: bool = True) -> None:
    """check_routing_cause for the Trinity list, whose routed branches the
    maker keeps at the other branches' gain: the reference routes on
    float32 activations and the program on bfloat16 ones, so a token's
    8th and 9th of 128 scores + bias flip between them in 7 % of
    token-layers at the first routed layer and 21 % at the last (my chip
    run, PR 39) — and because the maker's router is top-heavy (its
    docstring) a flip swaps 2-4 % of a routed sum, not an eighth. Here the
    routing is HANDED over both ways: what is left has to be inside the
    limit on either routing, and under handed routing the reference with
    ``route_scale`` 1, the program handed a WRONG expert (an id shifted by
    one) and one expert DROPPED (its down scale zeroed in every layer) have
    each to be outside it (``scale_control`` False: a model whose router
    has no scale — LFM2 — holds the wrong and the dropped expert alone;
    ``outside`` False: those two are shown and not required, for a list
    whose routed branches are smaller than its others).
    Distances as check_routing_cause's."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    T = rows
    mc = dataclasses.asdict(cfg)
    k, E, nd = cfg.num_experts_per_tok, cfg.num_experts, cfg.num_dense_layers
    n_moe = cfg.num_layers - nd

    def without(tree, drop):
        lay = dict(tree["layers"])
        ffn = dict(lay["ffn_moe"])
        sc = ffn["w_down"]["scale"]                      # [n_moe, E, H]
        ffn["w_down"] = dict(ffn["w_down"], scale=sc * jnp.where(
            jnp.arange(E)[:, None] == drop, 0.0, 1.0))
        lay["ffn_moe"] = ffn
        return dict(tree, layers=lay)

    program = handed_routing_list_program(cfg, T)
    ids = np.random.default_rng(20260930).integers(32, 127, T)
    toks = jnp.asarray(ids, jnp.int32)
    none = jnp.zeros((n_moe, T, k), jnp.int32)
    at = np.arange(T - 1)

    def reference(tree, **kw):
        with jax.default_matmul_precision("highest"):
            lg, idx = plain.forward(mc, tree, list(ids), T - 1, **kw)
            return (np.asarray(jax.nn.log_softmax(lg, axis=-1)),
                    np.asarray(idx))

    def served(tree, handed=None):
        lp, idx = program(tree, toks, none if handed is None
                          else jnp.asarray(handed), handed is not None)
        return np.asarray(lp), np.asarray(idx)

    def apart(a, b, tok):
        d = np.abs(a[at, tok] - b[at, tok])
        worst16 = [d[i:i + 16].max() for i in range(0, len(d) - 15)]
        return float(d.max()), float(np.median(worst16))

    t0 = time.monotonic()
    hot = params
    ref_lp, ref_idx = reference(hot)
    tok = ref_lp.argmax(-1)
    own_lp, own_idx = served(hot)
    flips = (np.sort(own_idx, -1) != np.sort(ref_idx, -1)).any(-1).mean(1)
    d_own = apart(own_lp, ref_lp, tok)
    d_handed = apart(served(hot, ref_idx)[0], ref_lp, tok)
    d_back = apart(own_lp, reference(hot, routing=own_idx)[0], tok)
    say(f"routing cause ({T} tokens): the "
        f"chosen sets differ in "
        f"{' '.join(f'{100 * x:.0f}' for x in flips)} % of tokens by routed "
        f"layer; on their own routing {d_own[0]:.4f} / {d_own[1]:.4f} nats "
        f"(worst position / median of the worst of 16); the program handed "
        f"the reference's {d_handed[0]:.4f} / {d_handed[1]:.4f}; the "
        f"reference handed the program's {d_back[0]:.4f} / {d_back[1]:.4f} "
        f"({time.monotonic() - t0:.1f}s)")
    served_handed = served(hot, ref_idx)[0]
    d_scale = apart(reference(hot, routing=ref_idx,
                              wrong="route_scale_1")[0], served_handed, tok) \
        if scale_control else (float("inf"), float("inf"))
    busiest = int(np.bincount(ref_idx.reshape(-1), minlength=E).argmax())
    wrong_idx = np.where(ref_idx == busiest, (busiest + 1) % E, ref_idx)
    d_wrong = apart(served(hot, wrong_idx)[0], ref_lp, tok)
    d_drop = apart(served(without(params, busiest), ref_idx)[0],
                   ref_lp, tok)
    share = 100 * (ref_idx == busiest).any(-1).mean()
    say(f"routing cause, under HANDED routing: the reference with "
        f"route_scale 1 {d_scale[0]:.4f} / {d_scale[1]:.4f} nats from the "
        f"program; expert {busiest} (chosen in {share:.1f} % of "
        f"token-layers) computed as its neighbour {d_wrong[0]:.4f} / "
        f"{d_wrong[1]:.4f}; dropped {d_drop[0]:.4f} / {d_drop[1]:.4f}")
    if strict:
        check(max(d_own[0], d_handed[0], d_back[0]) <= LOGPROB_NATS,
              "the program and the reference are apart on their own or on "
              "handed routing: the routed branches are at full gain and "
              "have to be inside the limit either way")
        check(not outside
              or min(d_scale[1], d_wrong[0], d_drop[0]) > LOGPROB_NATS,
              "under handed routing route_scale 1, a wrong expert or a "
              "dropped one stays inside the limit: nothing guards them")


def window_kernel_parity(cfg, slots: int, window: int, page: int, bb: int,
                         chunk: int, interpret: bool) -> None:
    """The entry points of a list with window AND full layers at the served
    widths against the jax.numpy reference (ops/attention.decode_attend
    over kv_pool.gather_layer_dense): the decode kernel under its window
    name, and the ragged kernel under both of its names (full, and under
    the window), on seeded inputs with the pages BELOW each row's window
    released — their table entries read the scratch page, which holds
    garbage no row may see."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aws_k8s_ansible_provisioner_tpu.ops import kv_pool as kvp
    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa
    from aws_k8s_ansible_provisioner_tpu.ops.attention import decode_attend

    Hq, Hkv, D, W = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                     cfg.sliding_window)
    B, MP = slots, window // page
    rng = np.random.default_rng(39)
    keys = jax.random.split(jax.random.PRNGKey(39), 4)
    shape = (2, B * MP + 1, Hkv, page, D)
    pool = {n: jax.random.normal(kk, shape, jnp.bfloat16)
            for n, kk in zip("kv", keys)}
    layer = jnp.int32(1)
    base = [window, 1, page, W + 1, W + page - 1, 3 * W + 77, 2 * W, 7]
    lens = np.asarray([min(base[i % len(base)], window) for i in range(B)])
    full = (rng.permutation(B * MP) + 1).reshape(B, MP).astype(np.int32)
    # the window layers' table: what lies below a row's window went back
    first = np.maximum(lens - W, 0) // page
    released = np.where(np.arange(MP)[None] < first[:, None], 0, full)

    def close(name, got, want):
        got = np.asarray(jnp.asarray(got, jnp.float32))
        want = np.asarray(jnp.asarray(want, jnp.float32))
        err = float(np.max(np.abs(got - want)
                           / (KERNEL_TOL + KERNEL_TOL * np.abs(want))))
        check(np.all(np.isfinite(got)) and err <= 1.0,
              f"{name}: off by {err:.2f}x the tolerance")
        return float(np.max(np.abs(got - want)))

    def want_of(q, limits, tab, w):
        dense = kvp.gather_layer_dense(pool, layer, jnp.asarray(tab))
        return decode_attend(q, dense["k"], dense["v"], jnp.asarray(limits),
                             window=w)

    def want_of_chunk(qc, first_limit, pages, w, block=512):
        """Consecutive rows of ONE slot (row r sees first_limit + r
        columns), a block of rows at a time against the slot's gathered
        view: 4,096 rows at once are 4.8 GB of logits."""
        dense = kvp.gather_layer_dense(pool, layer, jnp.asarray(pages[None]))
        out = [decode_attend(qc[None, s:s + block], dense["k"], dense["v"],
                             jnp.asarray([first_limit + s]), window=w)[0]
               for s in range(0, qc.shape[0], block)]
        return jnp.concatenate(out)

    q = jax.random.normal(keys[2], (B, 1, Hq, D), jnp.bfloat16)
    got = pa.decode_attend_pallas_paged_window(
        q, pool["k"], pool["v"], jnp.asarray(lens, jnp.int32), layer,
        jnp.asarray(released), interpret=interpret, window=W, bblock=bb)
    d_dec = close("decode kernel under the window, pages released", got,
                  want_of(q, lens, full, W))
    # the mixed layout: every slot's decode row, then a chunk of slot 5
    # whose earlier chunks' pages below ITS first row's window went back
    pslot, C = 5 % B, chunk
    off = min(2 * W + 19, window - C)
    crows = off + 1 + np.arange(C)
    crows[-(C // 5):] = 0                           # the chunk's padding
    limits = np.concatenate([np.where(np.arange(B) == pslot, 0, lens),
                             crows]).astype(np.int32)
    row_map = slot_rows(B, C, pslot)
    wtab = released.copy()
    wtab[pslot] = np.where(np.arange(MP) < max(off + 1 - W, 0) // page, 0,
                           full[pslot])
    qn = jax.random.normal(keys[3], (B + C, Hq, D), jnp.bfloat16)
    out = {}
    kinds = (("full", pa.ragged_attend_pallas_paged, full, 0),
             ("window", pa.ragged_attend_pallas_paged_window, wtab, W))
    for name, fn, tab, w in kinds:
        got = fn(qn, pool["k"], pool["v"], jnp.asarray(limits), layer,
                 jnp.asarray(tab), row_map, interpret=interpret, window=w,
                 bblock=bb)
        want = jnp.concatenate([
            want_of(qn[:B, None], limits[:B], full, w)[:, 0],
            want_of_chunk(qn[B:], off + 1, full[pslot], w)])
        want = jnp.where((limits > 0)[:, None, None], want, 0)
        out[name] = close(f"ragged kernel, {name}", got, want)
    if not interpret:
        # ONE call of each kind at the served mixed step's shape: a first
        # chunk (every row live from row 0) and a second one (from row
        # ``chunk``, three fifths of it live) beside the decode rows
        for name, fn, tab, w in kinds:
            for what, first, n_live in (("first chunk", 0, C),
                                        ("second chunk", C, 3 * C // 5)):
                if first + C > window:
                    continue
                lim = np.concatenate(
                    [np.where(np.arange(B) == pslot, 0, lens),
                     np.where(np.arange(C) < n_live,
                              first + 1 + np.arange(C), 0)]).astype(np.int32)
                args = (qn, pool["k"], pool["v"], jnp.asarray(lim), layer,
                        jnp.asarray(full if first == 0 else tab), row_map)
                ms = call_ms(lambda: fn(*args, window=w, bblock=bb), 10)
                say(f"ragged call, {name}, {B}+{C} rows, {what} "
                    f"({n_live} live from row {first}): {ms:.3f} ms")
    say(f"kernel parity (window and full kinds, Hq {Hq} Hkv {Hkv}, {B} slots "
        f"+ a {C}-row chunk, window {W} of {window}, bblock {bb}): decode "
        f"under the window max |diff| {d_dec:.4f}; ragged full "
        f"{out['full']:.4f}, under the window {out['window']:.4f}")


def check_shards(engine, n: int) -> None:
    import jax

    def per_device(tree):
        by_dev: dict = {}
        for leaf in jax.tree.leaves(tree):
            for sh in leaf.addressable_shards:
                by_dev[sh.device.id] = by_dev.get(sh.device.id, 0) \
                    + sh.data.nbytes
        return by_dev

    for name, tree in (("params", engine.params), ("kv pool", engine.cache)):
        by_dev = per_device(tree)
        whole = sum(leaf.nbytes for leaf in jax.tree.leaves(tree))
        say(f"shards[{name}]: whole {whole / 2**20:.1f} MiB; per device "
            + ", ".join(f"d{d}: {b / 2**20:.1f}"
                        for d, b in sorted(by_dev.items())))
        check(len(by_dev) == n,
              f"{name} lives on {len(by_dev)} device(s), want {n}")
        # a quarter each, with room for the replicated leaves (norms,
        # scales) every device holds whole
        check(max(by_dev.values()) <= 0.35 * whole * 4 / n,
              f"{name}: a device holds {max(by_dev.values())} of {whole} "
              f"bytes — not sharded {n} ways")
    stats = {d.id: d.memory_stats() for d in jax.devices()[:n]}
    if not all(stats.values()):      # the CPU backend reports none
        say("shards: memory_stats not reported by this backend")
        return
    used = {d: st["bytes_in_use"] for d, st in stats.items()}
    say("shards: memory_stats bytes_in_use per device: "
        + ", ".join(f"d{d}: {b / 2**20:.1f} MiB" for d, b in used.items()))
    check(min(used.values()) >= 0.5 * max(used.values()),
          f"device memory is lopsided: {used}")


def check_decode_program(engine) -> None:
    """The decode program the engine dispatches, lowered from the engine's
    own enumeration (serving/aot.py), compiled for its mesh: the Pallas
    kernels are in it, under shard_map, and decode attention needs no
    collective (ops/attention.make_decode_attend_carry: none for dp/tp) —
    the only ones are GSPMD's for the tensor-parallel matmuls."""
    import re

    from aws_k8s_ansible_provisioner_tpu.serving import aot

    plan = aot.ProgramPlan(engine.cfg, engine.serving,
                           tp=engine.mesh.shape["tp"])
    params, cache = aot._abstract_state(plan, engine.mesh)
    name, fn, args, kwargs = next(
        p for p in aot.enumerate_programs(plan, engine.mesh, params, cache,
                                          bblock=engine.decode_bblock)
        if p[0].startswith("decode_fused_h"))
    lowered = fn.lower(*args, **kwargs)
    check("shard_map" in lowered.as_text(debug_info=True),
          f"{name}: no shard_map in the lowered program")
    text = lowered.compile().as_text()
    n_kernels = text.count("tpu_custom_call")
    coll = [ln for ln in text.splitlines() if re.search(
        r"= \S+ (all-reduce|all-gather|all-to-all|collective-permute|"
        r"reduce-scatter)(-start)?\(", ln)]
    in_attn = [ln for ln in coll if "shard_map" in ln or "pallas" in ln]
    kinds = sorted({re.search(r"(all-reduce|all-gather|all-to-all|"
                              r"collective-permute|reduce-scatter)",
                              ln).group(1) for ln in coll})
    say(f"program[{name}]: {n_kernels} tpu_custom_call, {len(coll)} "
        f"collectives {kinds}, {len(in_attn)} inside the attention "
        f"shard_map")
    check(n_kernels >= 3, f"{name}: {n_kernels} Pallas kernels compiled in, "
                          f"want the two row writes and the attend")
    check("all-reduce" in kinds, f"{name}: tensor-parallel matmuls need an "
                                 f"all-reduce; found {kinds}")
    check(not in_attn, f"{name}: a collective inside decode attention: "
                       f"{[ln[:200] for ln in in_attn[:1]]}")


# ---------------------------------------------------------------------------
# Main
# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--config", default="",
                    help="a benchmark configuration file: serve its model "
                         "with its flags, seeded weights and reference")
    ap.add_argument("--rehearse", action="store_true",
                    help="builder's CPU rehearsal: tiny model, no TPU "
                         "required, never prints an ok line")
    ap.add_argument("--parent", default="",
                    help="a checkout of the parent commit: kernel_parity "
                         "also holds the paged kernels bitwise to its "
                         "ops/pallas_attention.py")
    opts = ap.parse_args()
    # the server's own log lines (scheduler pick, compile cache, devmon's
    # device kind and peaks, drain) go to stderr, as under main()
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not opts.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this smoke runs on the chip or not at all", file=sys.stderr)
        return 2
    check(device["count"] >= opts.chips,
          f"--chips {opts.chips} needs {opts.chips} devices, JAX sees "
          f"{device['count']}")
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    say(f"device: {dev.device_kind} x{device['count']} ({dev.platform}); "
        f"jax {jax.__version__}, libtpu {libtpu_version}")

    from aws_k8s_ansible_provisioner_tpu.ops import pallas_attention as pa
    from aws_k8s_ansible_provisioner_tpu.ops.attention import resolve_impl
    from aws_k8s_ansible_provisioner_tpu.utils.compile_cache import (
        enable_compile_cache)

    # Compile cache: the one placement rule (utils/compile_cache.py), and a
    # listener on JAX's own hit/miss events. The CPU rehearsal leaves it off:
    # serializing interpret-mode Pallas executables has segfaulted
    # (tests/conftest.py).
    cache = {"hits": 0, "misses": 0}
    if not opts.rehearse:
        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                cache["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                cache["misses"] += 1

        jax.monitoring.register_event_listener(on_event)
        cache_dir = enable_compile_cache()
        say(f"compile cache: {cache_dir} (JAX_COMPILATION_CACHE_DIR "
            f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
            f"), {len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
            f" entries at start")

    # every Pallas call the process traces, with its interpret flag
    kernel_calls: list = []
    real_pallas_call = pa.pl.pallas_call

    def recording_pallas_call(kernel, *a, **kw):
        kernel_calls.append((getattr(kernel, "func", kernel).__name__,
                             bool(kw.get("interpret", False))))
        return real_pallas_call(kernel, *a, **kw)

    pa.pl.pallas_call = recording_pallas_call

    build_native_scheduler()

    from aws_k8s_ansible_provisioner_tpu import config as _config

    model, params, plain, cfg_file = "Qwen/Qwen3-0.6B", None, None, None
    if opts.config:
        check(opts.chips == 1, "--config is a one-chip run")
        with open(opts.config, encoding="utf-8") as f:
            cfg_file = json.load(f)
        model = cfg_file.get("registry_name")
        if model is None:
            # a configuration the program registers no preset of (a cut of
            # a published model): its own ModelConfig fields, under its name
            mc = _config.ModelConfig(**cfg_file["model_config"])
            model = mc.name
            _config.MODEL_REGISTRY[model] = mc
    rehearse_flags: dict = {}
    if opts.rehearse:
        # the same flags, window and traffic on a model the CPU can serve
        tiny = dict(vocab_size=512, hidden_size=128, max_seq_len=4096,
                    eos_token_id=258)
        if cfg_file is None:
            model = "rehearse-qwen3"
            _config.MODEL_REGISTRY[model] = _config.tiny_qwen3(
                name=model, intermediate_size=256, num_heads=8,
                num_kv_heads=4, head_dim=32, **tiny)
        elif _config.MODEL_REGISTRY[model].selects:
            # the list hybrid: selecting attention + Lightning layers; the
            # window and the chunk cut to what the CPU's dense fallback holds
            model = "rehearse-sala"
            _config.MODEL_REGISTRY[model] = _config.tiny_sala(
                name=model, intermediate_size=256, num_heads=4,
                num_kv_heads=2, head_dim=32, lightning_num_heads=4,
                lightning_head_dim=32, sparse_block_size=64,
                sparse_kernel_size=32, sparse_kernel_stride=16,
                sparse_topk=4, sparse_window_size=128, sparse_dense_len=256,
                dim_model_base=32, **tiny)
            rehearse_flags = {"--max-cache-len": "4096",
                              "--prefill-chunk": "128",
                              "--prefill-buckets": "64,128,2048",
                              "--max-decode-slots": "8"}
        elif _config.MODEL_REGISTRY[model].windowed:
            # the list with window layers beside full ones, dense then
            # routed FFNs; the window, the chunk and the cache cut
            model = "rehearse-trinity"
            _config.MODEL_REGISTRY[model] = _config.tiny_trinity(
                name=model, intermediate_size=256, moe_intermediate_size=64,
                num_heads=4, num_kv_heads=2, head_dim=32,
                sliding_window=128, **tiny)
            rehearse_flags = {"--max-cache-len": "4096",
                              "--prefill-chunk": "256",
                              "--prefill-buckets": "64,128,2048",
                              "--max-decode-slots": "8"}
        elif "c" in _config.MODEL_REGISTRY[model].layer_pattern:
            # the list of gated short convolutions and GQA layers of
            # 64-wide heads (two a pool row), dense then routed FFNs
            model = "rehearse-lfm2"
            _config.MODEL_REGISTRY[model] = _config.tiny_lfm2(
                name=model, intermediate_size=256, moe_intermediate_size=64,
                num_heads=4, num_kv_heads=2, head_dim=64, **tiny)
            # (128 slots of a tiny model are slow on the CPU: the long
            # background stream would outlast its client)
            rehearse_flags = {"--max-decode-slots": "8"}
        elif "h" in _config.MODEL_REGISTRY[model].layer_pattern:
            # the list of blocks with two mixers: a state-space mixer beside
            # GQA attention at a query group of 5
            model = "rehearse-falcon-h1"
            _config.MODEL_REGISTRY[model] = _config.tiny_falcon_h1(
                name=model, intermediate_size=256, head_dim=32,
                ssm_head_dim=32, **tiny)
            rehearse_flags = {"--max-decode-slots": "8"}
        elif _config.MODEL_REGISTRY[model].layer_pattern:
            # the hybrid: gated NoPE GQA + KDA layers, an expert share
            model = "rehearse-solar"
            _config.MODEL_REGISTRY[model] = _config.tiny_solar(
                name=model, intermediate_size=64, moe_intermediate_size=64,
                num_heads=4, num_kv_heads=2, head_dim=32, kda_num_heads=4,
                kda_head_dim=32, kda_low_rank=32, **tiny)
        else:
            check(_config.MODEL_REGISTRY[model].num_experts > 0,
                  "the rehearsal's other model is the OLMoE-shaped one")
            model = "rehearse-olmoe"
            _config.MODEL_REGISTRY[model] = _config.tiny_olmoe(
                name=model, intermediate_size=64, moe_intermediate_size=64,
                num_heads=4, num_kv_heads=4, head_dim=32, **tiny)
    flags = ["--model", model]
    if cfg_file is not None:
        flags = list(cfg_file["server_flags"])
        flags[flags.index("--model") + 1] = model
        for flag, value in rehearse_flags.items():
            flags[flags.index(flag) + 1] = value
        import dataclasses

        t0 = time.monotonic()
        # (the rehearsal's tiny list routes top-2 of 8: a routing flip
        # between the float32 reference and the bfloat16 program swaps a
        # third of a routed sum there, so its routed branches stay small;
        # the chip run serves the maker's own, as large as the others)
        small = {"moe_gain": 0.15} if opts.rehearse \
            and _config.MODEL_REGISTRY[model].windowed else {}
        params = bench_module("weight_makers", cfg_file["weights_maker"]).make(
            dataclasses.asdict(_config.MODEL_REGISTRY[model]),
            int(cfg_file["weights_seed"]), cfg_file["weights_dtype"] == "int8",
            **small)
        jax.block_until_ready(params)
        plain = bench_module("reference", cfg_file["reference"])
        say(f"weights: {cfg_file['weights_maker']} "
            f"{cfg_file['weights_dtype']} made on the device in "
            f"{time.monotonic() - t0:.1f}s; reference "
            f"benchmark/reference/{cfg_file['reference']}.py")
    if opts.chips == 4:
        flags += ["--tp", "4"]

    srv = Server(flags, params)
    eng = srv.engine
    impl = resolve_impl(eng.serving.attention_impl)
    say(f"server: flags {flags}; scheduler {type(eng.sched).__name__}; "
        f"attention impl {impl}; kv pool page {eng.serving.page_size} "
        f"dtype {'int8' if eng.kv_quant else eng.serving.dtype}; weights "
        f"{eng.serving.weights_dtype}; slots {eng.num_slots} window "
        f"{eng.max_len}; decode bblock {eng.decode_bblock} "
        f"({'autotuned' if eng.serving.decode_bblock == 0 else 'pinned'}); "
        f"pipeline {eng.serving.decode_pipeline} ragged "
        f"{eng.serving.ragged_attention}")
    say(f"server: build {srv.build_s:.1f}s, warm-up {srv.warmup_s:.1f}s; "
        f"compile cache hits {cache['hits']} misses {cache['misses']}")
    check(type(eng.sched).__name__ == "NativeScheduler",
          "the native scheduler was built from source but not loaded")
    if not opts.rehearse:
        check(impl == "pallas", f"attention impl resolved to {impl!r}")
        check(eng.serving.decode_bblock == 0 or cfg_file is not None,
              "bblock was not autotuned")

    cfg, tokenizer = eng.cfg, srv.state.tokenizer
    got = run_requests(srv, model)
    peak = dev.memory_stats() or {} if dev.platform == "tpu" else {}
    say(f"memory: peak_bytes_in_use {peak.get('peak_bytes_in_use')} "
        f"bytes_limit {peak.get('bytes_limit')} after the requests")

    bb, serving = eng.decode_bblock, eng.serving
    slots, window, page = eng.num_slots, eng.max_len, serving.page_size
    if opts.chips == 4:
        check_shards(eng, 4)
        if not opts.rehearse:
            check_decode_program(eng)
        srv.drain()
        # what the tp=4 answers are compared with: the same weights whole on
        # device 0, teacher-forced through the plain model over the tokens
        # the sharded server produced
        params = single_device_params(cfg, serving)
        for name in ("c70", "c30", "m700"):
            check_numerics(f"{name}, tp=4 vs one device", got[name], cfg,
                           params, tokenizer)
    else:
        for name in ("c70", "c30", "m700") + (
                ("mlong",) if cfg.selects or cfg.windowed else ()):
            # m700 (and mlong, several chunks): through mixed_step
            check_numerics(name, got[name], cfg, eng.params, tokenizer, plain)
        if cfg.selects:
            refused = check_lower_precision("mlong", got["mlong"], cfg,
                                            eng.params, tokenizer, plain)
            check(opts.rehearse or refused["act"],
                  "the comparison passes a reference computed in float8")
            check_selection_cause(
                cfg, eng.params, plain,
                cfg.sparse_dense_len + (64 if opts.rehearse else 1024),
                strict=not opts.rehearse)
        if cfg.windowed:
            # the reference's own list; its CONTROLS_REPORTED are shown only
            refused = check_controls(
                "mlong", got["mlong"], cfg, eng.params, tokenizer, plain,
                {**plain.CONTROLS, **plain.CONTROLS_REPORTED})
            check(opts.rehearse or all(refused[c] for c in plain.CONTROLS),
                  f"the comparison passes a reference without a mechanism: "
                  f"{refused}")
            check_list_routing_cause(cfg, eng.params, plain,
                                     96 if opts.rehearse else 512,
                                     strict=not opts.rehearse)
        if set(cfg.layer_pattern) & set("ch"):
            # m700: two chunks of mixed_step, the second from a carried
            # tail (and, for a state-space mixer, a carried state); every
            # control of the reference is shown, and the ones the limits
            # are known to see at that length (PERF.md section 6, PRs 42
            # and 48) have to be refused
            refused = check_controls(
                "m700", got["m700"], cfg, eng.params, tokenizer, plain,
                {**plain.CONTROLS, **plain.CONTROLS_REPORTED})
            check(opts.rehearse or all(refused[c]
                                       for c in plain.CONTROLS_SEEN_LONG),
                  f"the comparison passes a reference without a mechanism: "
                  f"{refused}")
        if "c" in cfg.layer_pattern:
            check_list_routing_cause(cfg, eng.params, plain,
                                     96 if opts.rehearse else 512,
                                     strict=not opts.rehearse,
                                     scale_control=False, outside=False)
        if cfg.num_experts > 0:
            check_routing_counts(srv.port, cfg)
        if cfg.expert_share:
            refused = check_lower_precision("m700", got["m700"], cfg,
                                            eng.params, tokenizer, plain)
            check(opts.rehearse or refused["act"],
                  "the comparison passes a reference computed in float8")
            check_routing_cause(cfg, eng.params, plain,
                                64 if opts.rehearse else 256,
                                strict=not opts.rehearse)
        srv.drain()
        params = None
        # beside the served model's head shape: multi-head attention
        # (groups = 1, 16 KV heads: OLMoE's), which gives a decode block of 8
        # slots 8 query rows a KV head where the 0.6B gives 16
        mha = _config.MODEL_REGISTRY["allenai/OLMoE-1B-7B-0125-Instruct"]
        parent = load_kernels(opts.parent) if opts.parent else None
        if cfg.windowed:
            # (both kinds' kernels under their own names, the window
            # layers' over a table with released pages; the default run
            # has the plain parity)
            if opts.rehearse:
                window_kernel_parity(cfg, 8, 1024, page, 4, 64,
                                     interpret=True)
            else:
                window_kernel_parity(cfg, slots, window, page, bb,
                                     eng._chunk_size, interpret=False)
        elif cfg.selects:
            # (the selecting entries; the default run has the plain
            # kernels' parity)
            if opts.rehearse:
                sala_kernel_parity(cfg.scaled(
                    lightning_num_heads=8, lightning_head_dim=128), 8,
                    16 * page, page, 4, 64, interpret=True)
            else:
                sala_kernel_parity(cfg, slots, window, page, bb,
                                   eng._chunk_size, interpret=False,
                                   parent=parent)
        elif opts.rehearse:
            # interpret mode is slow: same code path at a small shape
            kernel_parity(cfg, 8, 256, 32, sorted({1, 4}), interpret=True,
                          parent=parent)
            if cfg_file is None:
                kernel_parity(mha.scaled(num_heads=4, num_kv_heads=4,
                                         head_dim=32), 8, 256, 32, [4],
                              interpret=True, parent=parent)
        else:
            # (at the geometry the kernels see: narrow heads lie
            # cfg.kv_lane_pack a pool row)
            kernel_parity(cfg.scaled(
                head_dim=cfg.pool_head_dim, num_kv_heads=cfg.pool_kv_heads),
                slots, window, page, sorted({1, bb}), interpret=False,
                parent=parent)
            if cfg_file is None:
                kernel_parity(mha, 24, window, page, [1, 8], interpret=False,
                              parent=parent)
        if cfg.num_experts > 0:
            expert_forms_parity(cfg, slots)

    names = sorted({n for n, _ in kernel_calls})
    say(f"kernels: {len(kernel_calls)} pallas_call traces "
        f"({', '.join(names)}); interpret=True in "
        f"{sum(1 for _, i in kernel_calls if i)}")
    say(f"compile cache: hits {cache['hits']} misses {cache['misses']} over "
        f"the run")
    if opts.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}))
        return 0
    check(kernel_calls and not any(i for _, i in kernel_calls),
          "a Pallas kernel was traced with interpret=True (or none ran)")
    if opts.chips == 4:
        device["count"] = 4     # the count this run used
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
