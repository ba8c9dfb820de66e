"""An open-loop load generator: requests leave on a schedule drawn from
the seed before the window, whether or not earlier ones were answered.

Latency runs from the instant a request was DUE, not from when it was
sent or admitted, so a stall (in the server's admission or in this
generator) is charged to every request behind it; how late the generator
itself ran is recorded, so that a starved generator is not read as a fast
server.  One sender thread, which never waits for an answer.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional

import numpy as np


def poisson_schedule(seed: int, rate_per_s: float, seconds: float
                     ) -> np.ndarray:
    """Due times in [0, seconds), seconds from the start of the window:
    a Poisson process of ``rate_per_s``, a pure function of ``seed``."""
    rng = np.random.default_rng(seed)
    n = int(rate_per_s * seconds * 1.5) + 64
    due = np.cumsum(rng.exponential(1.0 / rate_per_s, n))
    while due[-1] < seconds:                       # far tail of the draw
        due = np.concatenate([due, due[-1] + np.cumsum(
            rng.exponential(1.0 / rate_per_s, n))])
    return due[due < seconds]


class Record:
    """One request: when it was due, sent and done (seconds from the start
    of the window), and how it ended."""
    __slots__ = ("index", "due", "sent", "done", "outputs", "error")

    def __init__(self, index: int, due: float):
        self.index = index
        self.due = due
        self.sent: Optional[float] = None
        self.done: Optional[float] = None
        self.outputs = None
        self.error: Optional[str] = None       # exception class name

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.done is None else self.done - self.due

    @property
    def late_s(self) -> Optional[float]:
        return None if self.sent is None else self.sent - self.due


def run(schedule, submit: Callable[[int], object],
        drain_timeout_s: float = 30.0) -> List[Record]:
    """Send request i at ``schedule[i]``: ``submit(i)`` returns a handle
    with ``add_done_callback(cb)`` whose callback argument carries
    ``outputs`` and ``error`` (``serving.PendingResponse``), or raises a
    rejection.  Returns every record once all were answered (or
    ``drain_timeout_s`` after the last was sent)."""
    records = [Record(i, float(d)) for i, d in enumerate(schedule)]
    all_done = threading.Event()
    left = [len(records)]
    lock = threading.Lock()
    t0 = time.perf_counter()

    def finish(rec: Record, outputs, error):
        rec.done = time.perf_counter() - t0
        rec.outputs = outputs
        rec.error = None if error is None else type(error).__name__
        with lock:
            left[0] -= 1
            if left[0] == 0:
                all_done.set()

    def sender():
        for rec in records:
            wait = t0 + rec.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            rec.sent = time.perf_counter() - t0
            try:
                handle = submit(rec.index)
            except Exception as e:     # noqa: BLE001 — a rejection is a result
                finish(rec, None, e)
                continue
            handle.add_done_callback(
                lambda h, rec=rec: finish(rec, h.outputs, h.error))

    thread = threading.Thread(target=sender, name="cb-open-loop-sender",
                              daemon=True)
    thread.start()
    thread.join()
    if records:
        all_done.wait(drain_timeout_s)
    return records
