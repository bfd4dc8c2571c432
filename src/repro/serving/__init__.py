"""Serving frontend: micro-batching scheduler + projection/result cache.

The paper's deployment story is cheap online queries — a query needs only
its k reference distances to be projected and scored — so serving cost is
dominated by how efficiently query traffic is fed to the fused top-k /
IVF-probe kernels. This package sits between callers and the index:

  * ``scheduler.MicroBatchScheduler`` coalesces concurrent ``submit()``
    calls into one kernel dispatch per tick, pads each dispatch to a
    power-of-two query bucket and a fixed ``n_neighbors`` menu (so the jit
    cache holds a handful of entries instead of one per caller shape), and
    splits oversized coalesced batches at ``max_batch``.
  * ``cache.LRUCache`` is the projection/result cache, keyed on the
    query's canonical f32 bytes plus (mode, width, nprobe, rerank,
    index generation) — churn bumps the generation and silently
    invalidates every stale entry.
  * ``stats.FrontendStats`` carries the SLO instrumentation: p50/p95/p99
    latency, batch occupancy, cache hit rate, dispatch-shape (compile)
    count, reject-on-full backpressure counters, queue wait, and replica
    hot-swap accounting.
  * ``tracing.span`` names each step of the serving path in the
    profiler's trace (``zen.submit`` ... ``zen.resolve``) and counts
    compiles by the step that caused them.
  * ``loadgen.run_open_loop`` measures all of it under *offered* load:
    Poisson arrivals at a configured QPS (open-loop — no coordinated
    omission), latency-vs-offered-load curves, p99 under overload with
    the backpressure shedding, single servers or replica fleets
    (``launch.replicate``) round-robin.

``launch.serve.ZenServer(frontend=True)`` wires the three together; the
scheduler takes an injectable clock/ticker so tests drive it step by step
with no real threads sleeping (``tests/test_frontend.py``).
"""
from .cache import LRUCache, query_fingerprint
from .loadgen import OpenLoopReport, poisson_arrivals, run_open_loop
from .scheduler import (
    DEFAULT_NEIGHBOR_MENU,
    FrontendOverloadError,
    MicroBatchScheduler,
    QueryHandle,
    bucket_neighbors,
    bucket_q,
)
from .stats import FrontendStats

__all__ = [
    "DEFAULT_NEIGHBOR_MENU",
    "FrontendOverloadError",
    "FrontendStats",
    "LRUCache",
    "MicroBatchScheduler",
    "OpenLoopReport",
    "QueryHandle",
    "bucket_neighbors",
    "bucket_q",
    "poisson_arrivals",
    "query_fingerprint",
    "run_open_loop",
]
