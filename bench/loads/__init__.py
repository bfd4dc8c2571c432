"""Traffic drivers, one module per kind, found by the ``kind`` of a
traffic file: ``bench/loads/<kind>.py`` with ``drive`` and
``dispatch_rows``.

Every time a run reads is taken here, on ``time.perf_counter``: when a
request was due, when it was submitted, and when its answer came. No time
comes from the program (its handles' ``latency_s`` is not read), so a
change to where the program stamps its own clocks moves no metric.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Optional

import numpy as np

#: longest the collector blocks on the oldest outstanding answer before it
#: looks at the others (answers that come out of order are stamped at most
#: this late)
POLL_S = 0.005


class Request:
    """One request: when it was due, what it asked, what came back.

    ``status`` is "pending", "ok", "rejected" (refused at admission),
    "error" (the dispatch raised) or "unanswered" (no answer within the
    wait)."""

    __slots__ = ("scheduled", "rows", "submitted", "resolved", "d", "ids",
                 "status", "handle", "answered")

    def __init__(self, scheduled: float, rows: np.ndarray):
        self.scheduled = scheduled
        self.rows = rows
        self.submitted: Optional[float] = None
        self.resolved: Optional[float] = None
        self.d = self.ids = self.handle = None
        self.status = "pending"
        self.answered = threading.Event()

    def submit(self, submit, queries: np.ndarray, n_neighbors: int) -> None:
        self.submitted = time.perf_counter()
        try:
            self.handle = submit(queries, n_neighbors)
        except RuntimeError:  # the frontend refuses when its queue is full
            self.status = "rejected"
            self.answered.set()

    def take(self, now: float) -> None:
        """Take the answer of a handle that is done, stamped ``now``."""
        try:
            d, ids = self.handle.result(timeout=0)
        except Exception:  # noqa: BLE001 - a failed dispatch is a result
            self.status = "error"
        else:
            self.d, self.ids = np.array(d), np.array(ids)
            self.status = "ok"
        self.resolved = now
        self.handle = None
        self.answered.set()

    def give_up(self) -> None:
        self.status = "unanswered"
        self.handle = None
        self.answered.set()


class Collector:
    """Stamps each answer on the benchmark's clock as it comes.

    One thread blocks on the oldest outstanding request's handle. When that
    answer comes (or after ``POLL_S``), it reads the clock once and takes
    every outstanding answer that has come by then with that one stamp, so
    the answers of one dispatch share a stamp. Requests are ``add``-ed once
    submitted; ``finish`` waits until each is answered or ``deadline``
    passes, and gives up on the rest."""

    def __init__(self):
        self._pending = collections.deque()
        self._cv = threading.Condition()
        self._closing = False
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-collector")
        self._thread.start()

    def add(self, req: Request) -> None:
        if req.handle is None:  # refused at admission: nothing to wait for
            return
        with self._cv:
            self._pending.append(req)
            self._cv.notify()

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._closing:
                    self._cv.wait()
                if not self._pending:
                    return
                oldest = self._pending[0].handle
            try:
                oldest.result(timeout=POLL_S)
            except Exception:  # noqa: BLE001 - not yet, or failed: see below
                pass
            now = time.perf_counter()
            with self._cv:
                waiting = collections.deque()
                for r in self._pending:
                    if r.handle is not None and r.handle.done():
                        r.take(now)
                    elif r.handle is not None:
                        waiting.append(r)
                self._pending = waiting

    def finish(self, deadline: float) -> None:
        """Wait for every outstanding answer until ``deadline`` (on
        ``time.perf_counter``); the ones still missing then are given up."""
        with self._cv:
            self._closing = True
            self._cv.notify()
            pending = list(self._pending)
        for r in pending:
            r.answered.wait(max(deadline - time.perf_counter(), 0.0))
        with self._cv:
            for r in self._pending:
                r.give_up()
            self._pending.clear()
            self._cv.notify()
        self._thread.join()
