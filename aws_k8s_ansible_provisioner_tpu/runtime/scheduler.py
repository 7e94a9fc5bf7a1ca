"""Slot scheduler: ctypes binding to the native runtime core + Python fallback.

Both implementations expose the same five-call surface the engine drives:

    submit(req_id, prompt_len, max_tokens)  -> bool (prompt can ever fit)
    cancel(req_id)                          -> 0 unknown | 1 dequeued | 2 running
    pop_admission()                         -> ("admit", req_id, slot)
                                             | ("cancelled", req_id)
                                             | None
    note_prefill(slot, length) / note_decode(slot, n)
    next_cancelled_slot()                   -> slot | None
    release(slot)                           -> req_id | None
    stats()                                 -> SchedulerStats

``NativeScheduler`` wraps ``native/build/libtpu_serve_runtime.so`` (built by
``make -C native runtime``; C ABI in native/runtime/runtime.h — ctypes because
the image has no pybind11). ``PyScheduler`` mirrors it exactly; the parity
tests in tests/test_runtime.py run the same scenario against both.
"""

from __future__ import annotations

import collections
import ctypes
import logging
import os
import threading
from dataclasses import dataclass
from typing import Optional, Tuple

log = logging.getLogger(__name__)

_LIB_PATHS = tuple(p for p in (
    # Container image sets TPU_SERVE_NATIVE_DIR (the package is pip-installed
    # there, so the repo-relative path below doesn't exist in the image).
    os.path.join(os.environ.get("TPU_SERVE_NATIVE_DIR", ""),
                 "libtpu_serve_runtime.so")
    if os.environ.get("TPU_SERVE_NATIVE_DIR") else "",
    os.path.join(os.path.dirname(__file__), "..", "..", "native", "build",
                 "libtpu_serve_runtime.so"),
    "/usr/local/lib/libtpu_serve_runtime.so",
) if p)


@dataclass
class SchedulerStats:
    num_slots: int
    active_slots: int
    queue_depth: int
    pages_total: int
    pages_in_use: int
    admitted_total: int
    finished_total: int
    cancelled_total: int


class _CStats(ctypes.Structure):
    _fields_ = [
        ("num_slots", ctypes.c_int32),
        ("active_slots", ctypes.c_int32),
        ("queue_depth", ctypes.c_int32),
        ("pages_total", ctypes.c_int64),
        ("pages_in_use", ctypes.c_int64),
        ("admitted_total", ctypes.c_int64),
        ("finished_total", ctypes.c_int64),
        ("cancelled_total", ctypes.c_int64),
    ]


def _load_lib() -> Optional[ctypes.CDLL]:
    for path in _LIB_PATHS:
        if os.path.exists(path):
            lib = ctypes.CDLL(os.path.abspath(path))
            _lib_cache["path"] = os.path.abspath(path)
            lib.ts_create.restype = ctypes.c_void_p
            lib.ts_create.argtypes = [ctypes.c_int32] * 3
            lib.ts_destroy.argtypes = [ctypes.c_void_p]
            lib.ts_submit.restype = ctypes.c_int32
            lib.ts_submit.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                      ctypes.c_int32, ctypes.c_int32]
            if not hasattr(lib, "ts_submit_front"):
                return None   # stale pre-paged build: rebuild native/
            lib.ts_submit_front.restype = ctypes.c_int32
            lib.ts_submit_front.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                            ctypes.c_int32, ctypes.c_int32]
            lib.ts_cancel.restype = ctypes.c_int32
            lib.ts_cancel.argtypes = [ctypes.c_void_p, ctypes.c_int64]
            lib.ts_pop_admission.restype = ctypes.c_int32
            lib.ts_pop_admission.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int32)]
            lib.ts_pop_admission_paged.restype = ctypes.c_int32
            lib.ts_pop_admission_paged.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int32)]
            lib.ts_note_prefill.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                            ctypes.c_int32]
            lib.ts_note_decode.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                           ctypes.c_int32]
            lib.ts_release.restype = ctypes.c_int64
            lib.ts_release.argtypes = [ctypes.c_void_p, ctypes.c_int32]
            lib.ts_next_cancelled_slot.restype = ctypes.c_int32
            lib.ts_next_cancelled_slot.argtypes = [ctypes.c_void_p]
            lib.ts_get_stats.argtypes = [ctypes.c_void_p,
                                         ctypes.POINTER(_CStats)]
            return lib
    return None


_lib_cache: dict = {}


def native_available() -> bool:
    if "lib" not in _lib_cache:
        _lib_cache["lib"] = _load_lib()
    return _lib_cache["lib"] is not None


class NativeScheduler:
    """ctypes wrapper over the C++ runtime core.

    ``max_queue`` bounds the admission queue (0 = unbounded): the bound is
    enforced HERE in the shim — the C ABI predates it, and admission control
    is a host-side policy, not slot bookkeeping. ``submit`` returns False at
    the bound; ``submit_front`` (preemption resume) is exempt, because a
    resume returns capacity the queue already accounted for.
    """

    def __init__(self, num_slots: int, max_len: int, page_size: int,
                 max_queue: int = 0):
        if not native_available():
            raise RuntimeError("libtpu_serve_runtime.so not built "
                               "(run: make -C native runtime)")
        self.max_queue = int(max_queue)
        self._lib = _lib_cache["lib"]
        self._rt = self._lib.ts_create(num_slots, max_len, page_size)
        if not self._rt:
            raise ValueError("invalid scheduler geometry")

    def __del__(self):
        rt = getattr(self, "_rt", None)
        if rt:
            self._lib.ts_destroy(rt)
            self._rt = None

    def submit(self, req_id: int, prompt_len: int, max_tokens: int) -> bool:
        if self.max_queue and self.stats().queue_depth >= self.max_queue:
            return False
        return self._lib.ts_submit(self._rt, req_id, prompt_len,
                                   max_tokens) == 0

    def submit_front(self, req_id: int, prompt_len: int,
                     max_tokens: int) -> bool:
        return self._lib.ts_submit_front(self._rt, req_id, prompt_len,
                                         max_tokens) == 0

    def requeue(self, req_id: int, prompt_len: int, max_tokens: int) -> bool:
        """Back-of-queue submit EXEMPT from the max_queue bound (preemption
        requeue of already-admitted work must never shed)."""
        return self._lib.ts_submit(self._rt, req_id, prompt_len,
                                   max_tokens) == 0

    def cancel(self, req_id: int) -> int:
        return self._lib.ts_cancel(self._rt, req_id)

    def pop_admission(self, free_pages: Optional[int] = None) -> Optional[Tuple]:
        """``free_pages`` gates the head request by its worst-case page need
        (paged-KV admission); None = dense admission (slots only)."""
        rid = ctypes.c_int64(-1)
        slot = ctypes.c_int32(-1)
        cid = ctypes.c_int64(-1)
        ncan = ctypes.c_int32(0)
        if free_pages is None:
            got = self._lib.ts_pop_admission(
                self._rt, ctypes.byref(rid), ctypes.byref(slot),
                ctypes.byref(cid), ctypes.byref(ncan))
        else:
            got = self._lib.ts_pop_admission_paged(
                self._rt, free_pages, ctypes.byref(rid), ctypes.byref(slot),
                ctypes.byref(cid), ctypes.byref(ncan))
        if ncan.value:
            return ("cancelled", cid.value)
        if got:
            return ("admit", rid.value, slot.value)
        return None

    def note_prefill(self, slot: int, length: int):
        self._lib.ts_note_prefill(self._rt, slot, length)

    def note_decode(self, slot: int, n: int = 1):
        self._lib.ts_note_decode(self._rt, slot, n)

    def next_cancelled_slot(self) -> Optional[int]:
        s = self._lib.ts_next_cancelled_slot(self._rt)
        return None if s < 0 else s

    def release(self, slot: int) -> Optional[int]:
        rid = self._lib.ts_release(self._rt, slot)
        return None if rid < 0 else rid

    def stats(self) -> SchedulerStats:
        c = _CStats()
        self._lib.ts_get_stats(self._rt, ctypes.byref(c))
        return SchedulerStats(**{f: getattr(c, f) for f, _ in c._fields_})


class PyScheduler:
    """Pure-Python mirror of the native core (identical semantics).

    ``max_queue`` bounds the admission queue (0 = unbounded) with the same
    contract as NativeScheduler's shim-level bound: ``submit`` returns False
    at the bound, ``submit_front`` (preemption resume) is exempt.
    """

    def __init__(self, num_slots: int, max_len: int, page_size: int,
                 max_queue: int = 0):
        if num_slots <= 0 or max_len <= 0 or page_size <= 0:
            raise ValueError("invalid scheduler geometry")
        self.num_slots = num_slots
        self.max_len = max_len
        self.page_size = page_size
        self.max_queue = int(max_queue)
        self._lock = threading.Lock()
        self._queue: collections.deque = collections.deque()
        self._cancelled_pending: set = set()
        self._slot_req = [-1] * num_slots
        self._slot_len = [0] * num_slots
        self._slot_cancelled = [False] * num_slots
        # Least-recently-released free slots (admit from front, release to
        # back): a freed slot is reused LAST, maximizing how long its K/V
        # rows stay available to the engine's prefix cache. Mirrors the
        # native core's free_slots deque.
        self._free: collections.deque = collections.deque(range(num_slots))
        self._admitted = 0
        self._finished = 0
        self._cancelled = 0

    def submit(self, req_id: int, prompt_len: int, max_tokens: int) -> bool:
        if prompt_len < 0 or prompt_len + 1 > self.max_len:
            return False
        with self._lock:
            if self.max_queue and len(self._queue) >= self.max_queue:
                return False
            self._queue.append((req_id, prompt_len, max_tokens))
        return True

    def submit_front(self, req_id: int, prompt_len: int,
                     max_tokens: int) -> bool:
        """Front-of-queue submit: paged-KV preemption resume (see runtime.h)."""
        if prompt_len < 0 or prompt_len + 1 > self.max_len:
            return False
        with self._lock:
            self._queue.appendleft((req_id, prompt_len, max_tokens))
        return True

    def requeue(self, req_id: int, prompt_len: int, max_tokens: int) -> bool:
        """Back-of-queue submit EXEMPT from the max_queue bound (preemption
        requeue of already-admitted work must never shed)."""
        if prompt_len < 0 or prompt_len + 1 > self.max_len:
            return False
        with self._lock:
            self._queue.append((req_id, prompt_len, max_tokens))
        return True

    def cancel(self, req_id: int) -> int:
        with self._lock:
            if any(r == req_id for r, _, _ in self._queue):
                self._cancelled_pending.add(req_id)
                return 1
            for s, r in enumerate(self._slot_req):
                if r == req_id:
                    self._slot_cancelled[s] = True
                    return 2
        return 0

    def pop_admission(self, free_pages: Optional[int] = None) -> Optional[Tuple]:
        """``free_pages`` gates the head request by its worst-case page need
        ceil((prompt_len + 1) / page_size) — paged-KV admission; None = dense
        (slots-only). Head-of-line blocking is deliberate: FCFS fairness, the
        vLLM scheduler's behavior."""
        with self._lock:
            free = self._free[0] if self._free else None
            while self._queue:
                rid, plen, mtok = self._queue[0]
                if rid in self._cancelled_pending:
                    self._queue.popleft()
                    self._cancelled_pending.discard(rid)
                    self._cancelled += 1
                    return ("cancelled", rid)
                if free is None:
                    return None
                if free_pages is not None:
                    needed = -(-(plen + 1) // self.page_size)
                    if needed > free_pages:
                        return None
                self._queue.popleft()
                self._free.popleft()
                self._slot_req[free] = rid
                self._slot_len[free] = 0
                self._slot_cancelled[free] = False
                self._admitted += 1
                return ("admit", rid, free)
        return None

    def note_prefill(self, slot: int, length: int):
        with self._lock:
            if 0 <= slot < self.num_slots:
                self._slot_len[slot] = length

    def note_decode(self, slot: int, n: int = 1):
        with self._lock:
            if 0 <= slot < self.num_slots:
                self._slot_len[slot] = min(self._slot_len[slot] + n,
                                           self.max_len)

    def next_cancelled_slot(self) -> Optional[int]:
        with self._lock:
            for s, r in enumerate(self._slot_req):
                if r >= 0 and self._slot_cancelled[s]:
                    return s
        return None

    def release(self, slot: int) -> Optional[int]:
        with self._lock:
            if not (0 <= slot < self.num_slots) or self._slot_req[slot] < 0:
                return None
            rid = self._slot_req[slot]
            self._slot_req[slot] = -1
            self._slot_len[slot] = 0
            self._free.append(slot)
            if self._slot_cancelled[slot]:
                self._cancelled += 1
            else:
                self._finished += 1
            self._slot_cancelled[slot] = False
            return rid

    def stats(self) -> SchedulerStats:
        with self._lock:
            pps = -(-self.max_len // self.page_size)
            in_use = sum(-(-l // self.page_size)
                         for s, l in enumerate(self._slot_len)
                         if self._slot_req[s] >= 0)
            return SchedulerStats(
                num_slots=self.num_slots,
                active_slots=sum(1 for r in self._slot_req if r >= 0),
                queue_depth=len(self._queue),
                pages_total=pps * self.num_slots,
                pages_in_use=in_use,
                admitted_total=self._admitted,
                finished_total=self._finished,
                cancelled_total=self._cancelled,
            )


def make_scheduler(num_slots: int, max_len: int, page_size: int,
                   max_queue: int = 0):
    """Native core when built, Python fallback otherwise.

    TPU_SERVE_NATIVE_RUNTIME=0 forces the fallback (A/B and CI without g++).
    ``max_queue`` bounds the admission queue (0 = unbounded) — the engine's
    load-shedding gate; see NativeScheduler/PyScheduler.
    """
    want_native = os.environ.get("TPU_SERVE_NATIVE_RUNTIME", "1") != "0"
    if want_native and native_available():
        log.info("scheduler: native (%s)", _lib_cache.get("path"))
        return NativeScheduler(num_slots, max_len, page_size,
                               max_queue=max_queue)
    log.info("scheduler: python (%s)",
             "TPU_SERVE_NATIVE_RUNTIME=0" if not want_native
             else "no libtpu_serve_runtime.so built — make -C native runtime")
    return PyScheduler(num_slots, max_len, page_size, max_queue=max_queue)
