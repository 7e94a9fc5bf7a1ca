"""Warms what the cell's traffic can dispatch, through the HTTP path.

With the ragged mixed program on (the default), an admission while a decode
dispatch is in flight rides ``mixed_step`` (chunk = the largest bucket);
admissions into an engine with nothing in flight take ``prefill_step`` (one
prompt) or ``prefill_batch_step`` (2..4 queued prompts, rows padded to 2 or
4, lengths to the largest member's bucket). So the reachable shapes are: one
``prefill_step`` per bucket the mix's prompt lengths reach, two
``prefill_batch_step`` per bucket, ``decode_steps`` at the fused horizon and
``mixed_step``. Single prompts are sent one at a time into an idle engine;
batch shapes use one HTTP request with ``n`` = 2 / 4 (its choices are
submitted back to back from one handler thread, so they queue together);
the mixed program gets a prompt arriving while another stream decodes.

The jitted programs' own compile-cache sizes (``fn._cache_size()``) say when
every expected shape exists; a burst that raced is repeated.
"""

from __future__ import annotations

import threading
import time

from benchlib import client as cl
from benchlib.trafficgen import prompt_text


def _sizes():
    from aws_k8s_ansible_provisioner_tpu.serving import programs as pg

    return {name: getattr(pg, name)._cache_size()
            for name in ("prefill_step", "prefill_batch_step",
                         "prefill_chunk_step", "decode_steps", "mixed_step")}


def buckets_hit(engine, traffic: dict) -> list:
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    if traffic["prompt_len"]["dist"] == "fixed":
        lo = hi = traffic["prompt_len"]["value"]
    bs = sorted(engine.buckets)
    first = next(b for b in bs if b >= lo)
    last = next(b for b in bs if b >= hi)
    return [b for b in bs if first <= b <= last]


def warm(srv, traffic: dict, seed: int, say) -> dict:
    port, model, eng = srv.port, srv.served_model, srv.engine
    hi = traffic["prompt_len"]["max"] \
        if traffic["prompt_len"]["dist"] != "fixed" \
        else traffic["prompt_len"]["value"]
    buckets = buckets_hit(eng, traffic)
    salt = [20_000_000]

    def prompt(n):
        salt[0] += 1
        return prompt_text(seed, salt[0], n)

    def post(n_prompt, max_tokens, n=1):
        body = {"model": model, "prompt": prompt(n_prompt),
                "max_tokens": max_tokens, "temperature": 0.0,
                "ignore_eos": True, "n": n}
        status, raw = cl.http_json(port, "POST", "/v1/completions", body)
        if status != 200:
            raise SystemExit(f"warm-up request failed: {status} {raw[:300]}")

    t0 = time.monotonic()
    before = _sizes()
    for b in buckets:                     # prefill_step per bucket + decode
        post(min(b, hi), 9)
        srv.wait_idle()
    t_single = time.monotonic()
    for b in buckets:                     # prefill_batch_step, rows 2 and 4
        for n in (2, 4):
            for _ in range(4):
                was = _sizes()["prefill_batch_step"]
                post(min(b, hi), 1, n=n)
                srv.wait_idle()
                if _sizes()["prefill_batch_step"] > was:
                    break
    t_batch = time.monotonic()
    bg_tokens = min(1024, int(eng.max_len) - 128)
    # mixed_step: a prompt arriving while another stream decodes. The
    # background stream is long and is cut off once the prompt is served.
    for _ in range(3):
        was = _sizes()["mixed_step"]
        done = threading.Event()
        bg = cl.Result(measured=False)

        def background():
            cl.stream_completion(
                port, cl.completion_body(model, prompt(40), bg_tokens,
                                         **traffic.get("request_extra", {})),
                bg,
                stop=done.is_set)

        t = threading.Thread(target=background, daemon=True)
        t.start()
        end = time.monotonic() + 300
        while bg.t_first is None and time.monotonic() < end \
                and t.is_alive():
            time.sleep(0.002)
        post(min(buckets[-1], hi), 9)
        done.set()
        t.join(300)
        srv.wait_idle()
        if _sizes()["mixed_step"] > was or was > 0:
            break
    after = _sizes()
    want_batch = 2 * len(buckets)
    say(f"warm-up: buckets {buckets}; program variants compiled "
        f"{ {k: after[k] - before[k] for k in after} } in "
        f"{time.monotonic() - t0:.1f}s = singles {t_single - t0:.1f} + "
        f"batches {t_batch - t_single:.1f} + mixed "
        f"{time.monotonic() - t_batch:.1f} (prefill_batch_step wanted "
        f"{want_batch})")
    return {"buckets": buckets, "variants": after,
            "complete": after["prefill_batch_step"] - before[
                "prefill_batch_step"] >= want_batch
            and after["mixed_step"] > 0}
