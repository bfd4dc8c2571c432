"""Open loop: requests arrive on a schedule, whether or not earlier ones
have been answered (independent users of a search service).

Adapted from the program's ``serving/loadgen.py::run_open_loop``. Arrivals
are submitted from the calling thread at their scheduled times while the
server's own ticker thread serves them, so a slow dispatch never holds the
generator back. Latency is counted from the scheduled arrival, which
charges a stall to every request it delays (no coordinated omission).

Traffic parameters (``bench/traffic/<name>.json``):
  rate_qps          mean arrival rate, requests per second
  rows_per_request  query rows in each request
  n_neighbors       neighbours asked for
  pool              size of the query pool that requests draw rows from

The gaps between arrivals are one fixed set (an exponential draw made with
``GAP_SEED``, scaled to fill the window), and the requests' pool rows one
fixed uniform draw (``ROWS_SEED``); the run's seed draws the order of
each. So every seed offers the same requests with the same spacings, in
another order, with a Poisson process's burstiness, and the numbers a run
reads do not move with which rows a seed happened to draw.
"""
from __future__ import annotations

import time

import numpy as np

from bench.loads import Request

#: seed of the one set of inter-arrival gaps every run reorders
GAP_SEED = 20230222
#: seed of the one draw of pool rows every run reorders
ROWS_SEED = 20230223


def schedule(traffic: dict, seed: int, seconds: float):
    """(arrival offsets (n,) in seconds, pool rows (n, rows_per_request))."""
    n = max(1, int(round(traffic["rate_qps"] * seconds)))
    gaps = np.random.default_rng(GAP_SEED).exponential(1.0, n + 1)
    gaps *= seconds / gaps.sum()
    rows = np.random.default_rng(ROWS_SEED).integers(
        0, traffic["pool"], (n, traffic["rows_per_request"]))
    rng = np.random.default_rng(seed)
    times = np.cumsum(rng.permutation(gaps[:n]))  # the last gap is the tail
    return times, rows[rng.permutation(n)]


def dispatch_rows(traffic: dict, max_batch: int):
    """Row counts a dispatch can carry: any count up to ``max_batch``."""
    return range(1, max_batch + 1)


def drive(submit, pool: np.ndarray, traffic: dict, seed: int,
          seconds: float, collector):
    """Submit every arrival of the window on time, each handed to
    ``collector``; returns (t0, requests) once the last is submitted.

    ``submit(rows, n_neighbors)`` returns a handle or raises when the
    server refuses the request. ``t0`` is the window's start on
    ``time.perf_counter``; each request's ``scheduled`` is absolute."""
    times, rows = schedule(traffic, seed, seconds)
    n_neighbors = traffic["n_neighbors"]
    t0 = time.perf_counter()
    requests = []
    for t, r in zip(times, rows):
        req = Request(scheduled=t0 + float(t), rows=r)
        wait = req.scheduled - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        req.submit(submit, pool[r], n_neighbors)
        collector.add(req)
        requests.append(req)
    return t0, requests
