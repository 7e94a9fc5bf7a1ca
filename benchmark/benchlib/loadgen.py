"""Drives a Plan against the server: ramp, measured window, tail.

Traffic starts ``ramp_s`` before the window opens (counted as set-up) and goes
on after it closes until every request that was due inside the window has
finished (or the mix's ``grace_s`` passed), so the requests measured last still run
under the cell's load. A request is MEASURED if it was due (open loop) or
sent (closed loop) inside [t0, t1). One process, few threads: a closed loop
has one thread per client, an open loop one per request in flight.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, List

from benchlib import client as cl
from benchlib.trafficgen import Plan



class Run:
    def __init__(self, plan: Plan, port: int, model: str, seconds: float):
        self.plan, self.port, self.model = plan, port, model
        self.seconds = float(seconds)
        self.results: List[cl.Result] = []
        self.t_start = self.t0 = self.t1 = 0.0
        self.late_s: List[float] = []       # open loop: send - due
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._next = 0
        self.exhausted = False

    # -- helpers -------------------------------------------------------------

    def _measured(self, t: float) -> bool:
        return self.t0 <= t < self.t1

    def _one(self, pr, due_t=None) -> cl.Result:
        res = cl.Result(idx=pr.idx, due_t=due_t)
        now = time.monotonic()
        res.measured = self._measured(due_t if due_t is not None else now)
        with self._lock:
            self.results.append(res)
        body = cl.completion_body(self.model, pr.prompt, pr.max_tokens,
                                  **self.plan.body_extra)
        cl.stream_completion(self.port, body, res, stop=self._stop.is_set,
                             deadline=self.t1 + self.plan.grace_s)
        return res

    def _closed_client(self):
        reqs = self.plan.requests
        while not self._stop.is_set():
            with self._lock:
                i = self._next
                self._next += 1
            if i >= len(reqs):
                self.exhausted = True
                return
            # measured-ness is fixed at send: _one reads the clock itself
            self._one(reqs[i])

    def _open_dispatcher(self, threads: list):
        for pr in self.plan.requests:
            due_t = self.t_start + pr.due_s
            while True:
                dt = due_t - time.monotonic()
                if dt <= 0 or self._stop.is_set():
                    break
                time.sleep(min(dt, 0.05))
            if self._stop.is_set():
                return
            self.late_s.append(time.monotonic() - due_t)
            t = threading.Thread(target=self._one, args=(pr, due_t),
                                 daemon=True)
            t.start()
            threads.append(t)
        self.exhausted = True

    def _measured_all_done(self) -> bool:
        with self._lock:
            return all((r.done or r.error or r.status not in (None, 200))
                       for r in self.results if r.measured)

    # -- the run -------------------------------------------------------------

    def run(self, on_window_open: Callable = None,
            during_window: Callable = None,
            on_window_close: Callable = None) -> None:
        """Blocks until the tail is over. ``on_window_open()`` is called at
        t0 and ``during_window(run)`` right after, on the caller's thread
        (the traced run starts and stops its trace and sampler there); it
        must return before t1."""
        plan = self.plan
        self.t_start = time.monotonic()
        self.t0 = self.t_start + plan.ramp_s
        self.t1 = self.t0 + self.seconds
        threads: list = []
        if plan.loop == "closed":
            for _ in range(plan.clients):
                t = threading.Thread(target=self._closed_client, daemon=True)
                t.start()
                threads.append(t)
        else:
            d = threading.Thread(target=self._open_dispatcher,
                                 args=(threads,), daemon=True)
            d.start()
            threads.append(d)
        time.sleep(max(0.0, self.t0 - time.monotonic()))
        if on_window_open is not None:
            on_window_open()
        if during_window is not None:
            during_window(self)
        time.sleep(max(0.0, self.t1 - time.monotonic()))
        if on_window_close is not None:
            on_window_close()
        while time.monotonic() < self.t1 + plan.grace_s:
            if self._measured_all_done():
                break
            time.sleep(0.05)
        self._stop.set()
        end = time.monotonic() + 30.0
        for t in list(threads):
            t.join(max(0.1, end - time.monotonic()))
