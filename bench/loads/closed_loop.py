"""Closed loop: a fixed number of clients, each sending its next request
only once the previous one is answered (batch jobs streaming a query set).

Traffic parameters (``bench/traffic/<name>.json``):
  clients           concurrent clients, one thread each
  rows_per_request  query rows in each request
  n_neighbors       neighbours asked for
  pool              size of the query pool that requests draw rows from

Every request has the same size. The requests are one fixed sequence, the
same in every run: the pool's rows in an order drawn with ``ROWS_SEED``,
cut into requests and taken in turn by whichever client sends next. The
run's seed reorders the rows inside each request. So every run asks the
same queries in the same order of requests, and the numbers it reads do
not move with which rows a seed happened to draw. A client stops sending
at the window's end; what it has in flight then is still answered and
checked, and counts in ``qps`` by the share of its service inside the
window.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from bench.loads import Request

#: longest a client waits for one answer before it gives up
ANSWER_TIMEOUT_S = 120.0
#: seed of the one order of pool rows every run sends
ROWS_SEED = 20230224


def request_rows(traffic: dict, seed: int, i: int) -> np.ndarray:
    """Pool rows of the run's ``i``-th request (cycling over the pool)."""
    r, pool = traffic["rows_per_request"], traffic["pool"]
    order = np.random.default_rng(ROWS_SEED).permutation(pool)
    rows = np.take(order, np.arange(i * r, (i + 1) * r), mode="wrap")
    return np.random.default_rng([seed, i]).permutation(rows)


def dispatch_rows(traffic: dict, max_batch: int):
    """Row counts a dispatch can carry when every client's request is
    pending at once and the frontend cuts them into ``max_batch`` chunks."""
    r = traffic["rows_per_request"]
    sizes = set()
    for m in range(1, traffic["clients"] + 1):
        full, rest = divmod(m * r, max_batch)
        sizes.update(([max_batch] if full else []) + ([rest] if rest else []))
    return sorted(sizes)


def drive(submit, pool: np.ndarray, traffic: dict, seed: int,
          seconds: float, collector):
    """Run the clients for ``seconds``, each request handed to
    ``collector``; returns (t0, requests) once every client has stopped."""
    n_neighbors = traffic["n_neighbors"]
    t0 = time.perf_counter()
    t_end = t0 + seconds
    requests, lock = [], threading.Lock()

    def client() -> None:
        while time.perf_counter() < t_end:
            with lock:
                rows = request_rows(traffic, seed, len(requests))
                req = Request(scheduled=time.perf_counter(), rows=rows)
                requests.append(req)
            req.submit(submit, pool[rows], n_neighbors)
            collector.add(req)
            if not req.answered.wait(ANSWER_TIMEOUT_S):
                return

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(traffic["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return t0, requests
