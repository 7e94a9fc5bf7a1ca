"""Stdlib HTTP/SSE client for the in-process server (a copy of
chip_smoke.py's client, made cheap: it shares the process, and so the GIL,
with the server it measures, so a streamed chunk is scanned for its token
ids and parsed as JSON only when asked)."""

from __future__ import annotations

import http.client
import json
import time
from dataclasses import dataclass, field
from typing import List, Optional

_KEY = b'"token_ids": ['


@dataclass
class Result:
    idx: int = -1
    measured: bool = False
    due_t: Optional[float] = None      # open loop: when it was due
    send_t: float = 0.0
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    n_out: int = 0
    want_out: int = 0
    status: Optional[int] = None
    done: bool = False                 # saw [DONE]
    aborted: bool = False              # closed by the benchmark, not a failure
    error: str = ""
    chunks: List[tuple] = field(default_factory=list)   # (t, n tokens)
    token_ids: List[int] = field(default_factory=list)  # parse=True only
    logprobs: List[float] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return (self.status == 200 and self.done
                and self.n_out == self.want_out and not self.error)


def count_token_ids(line: bytes) -> int:
    i = line.find(_KEY)
    if i < 0:
        return 0
    j = line.find(b"]", i)
    body = line[i + len(_KEY):j]
    return body.count(b",") + 1 if body.strip() else 0


def http_json(port: int, method: str, path: str, body=None, timeout=600.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = json.dumps(body).encode() if body is not None else None
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def completion_body(model: str, prompt: str, max_tokens: int, **extra) -> dict:
    """Greedy, exact length, streamed: what every measured request sends."""
    body = {"model": model, "prompt": prompt, "max_tokens": int(max_tokens),
            "temperature": 0.0, "ignore_eos": True, "stream": True}
    body.update(extra)
    return body


def stream_completion(port: int, body: dict, res: Result, stop=None,
                      deadline: Optional[float] = None, parse: bool = False,
                      timeout: float = 120.0) -> Result:
    """POST /v1/completions (stream) and fill ``res``. ``stop()`` true ends an
    UNMEASURED request early (aborted, not failed); ``deadline`` (monotonic)
    fails a request that is still running then."""
    res.want_out = int(body["max_tokens"])
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        res.send_t = time.monotonic()
        conn.request("POST", "/v1/completions", body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        res.status = resp.status
        if resp.status != 200:
            res.error = resp.read()[:300].decode("utf-8", "replace")
            return res
        for raw in resp:
            if not raw.startswith(b"data:"):
                continue
            now = time.monotonic()
            if raw.startswith(b"data: [DONE]"):
                res.done = True
                continue               # read on to the chunked end
            n = count_token_ids(raw)
            if n:
                if res.t_first is None:
                    res.t_first = now
                res.t_last = now
                res.n_out += n
                res.chunks.append((now, n))
                if parse:
                    for ch in json.loads(raw[5:])["choices"]:
                        res.token_ids += ch.get("token_ids") or []
                        lp = ch.get("logprobs")
                        if lp and "token_logprobs" in lp:
                            res.logprobs += lp["token_logprobs"]
            if stop is not None and not res.measured and stop():
                res.aborted = True
                return res
            if deadline is not None and now > deadline:
                res.error = "not finished at the deadline"
                return res
        return res
    except (OSError, http.client.HTTPException) as e:
        res.error = f"{type(e).__name__}: {e}"
        return res
    finally:
        conn.close()
