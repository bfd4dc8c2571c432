"""Spans, queue wait and compile counts of the serving path
(``repro.serving.tracing``), read back as a profiler trace on the CPU."""
import glob
import os
import threading

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.data import synthetic as syn
from repro.launch import serve
from repro.launch.serve import ZenServer, build_index
from repro.serving import tracing

# sizes no other test module builds, so the compile counts start fresh
N, DIM, K = 424, 40, 8


@pytest.fixture(scope="module", autouse=True)
def _x32():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", prev)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


@pytest.fixture(scope="module")
def corpus():
    return syn.manifold_space(jax.random.PRNGKey(3), N, DIM, 6)


@pytest.fixture(scope="module")
def queries():
    return np.asarray(
        syn.manifold_space(jax.random.PRNGKey(4), 16, DIM, 6), np.float32)


@pytest.fixture(scope="module")
def index(corpus):
    return build_index(corpus, K, index="flat")


def _zen_events(trace_dir):
    """[(line, name, start, end, args)] of every ``zen.*`` host event."""
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name.startswith("zen."):
                    start = int(e.start_ns)
                    out.append((i, name, start, start + int(e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda ev: ev[2])


def test_dispatch_span_holds_steps_and_names_its_requests(
        index, queries, tmp_path):
    server = ZenServer(index, rerank_factor=2, frontend=True,
                       clock=FakeClock())
    sched = server.frontend
    server.query(queries[:2], 5, direct=True)  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        handles = [sched.submit(queries[i], 5) for i in range(3)]
        sched.tick()
        sched.tick()  # nothing pending: no zen.tick span
    finally:
        jax.profiler.stop_trace()
    for h in handles:
        assert h.done()
    events = _zen_events(str(tmp_path))

    submits = [ev for ev in events if ev[1] == tracing.SUBMIT]
    assert [ev[4]["request"] for ev in submits] == [
        h.request_id for h in handles]
    assert all(ev[4]["rows"] == 1 for ev in submits)
    assert len([ev for ev in events if ev[1] == tracing.TICK]) == 1

    (disp,) = [ev for ev in events if ev[1] == tracing.DISPATCH]
    line, _, start, end, args = disp
    served = [int(v) for v in args["requests"].strip("[]").split(",")]
    assert served == [h.request_id for h in handles]
    assert (args["rows"], args["bucket"]) == (3, 4)
    inside = [ev[1] for ev in events
              if ev[0] == line and start <= ev[2] and ev[3] <= end
              and ev is not disp]
    steps = [tracing.PROJECT, tracing.SEARCH, tracing.MAP_IDS,
             tracing.RERANK, tracing.FETCH, tracing.RESOLVE]
    assert [n for n in inside if n in steps] == steps
    (search,) = [ev for ev in events if ev[1] == tracing.SEARCH]
    assert search[4]["index"] == "flat"


def test_direct_query_has_steps_but_no_dispatch(index, queries, tmp_path):
    server = ZenServer(index, frontend=True, clock=FakeClock())
    server.query(queries[:2], 5, direct=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        server.query(queries[:2], 5, direct=True)
    finally:
        jax.profiler.stop_trace()
    names = [ev[1] for ev in _zen_events(str(tmp_path))]
    assert tracing.PROJECT in names and tracing.SEARCH in names
    assert tracing.DISPATCH not in names and tracing.SUBMIT not in names


def test_queue_wait_counts_each_row_to_its_dispatch(index, queries):
    clock = FakeClock()
    server = ZenServer(index, frontend=True, clock=clock)
    sched = server.frontend
    sched.submit(queries[0], 5)
    clock.t = 0.1
    sched.submit(queries[1], 5)
    clock.t = 0.25
    sched.tick()
    assert sched.stats.queue_wait_s == 0.4
    assert sched.stats.dispatched_rows == 2
    assert server.stats()["frontend"]["queue_wait_ms_mean"] == 200.0


def test_new_q_bucket_compiles_under_search(index, queries):
    server = ZenServer(index)
    server.query(queries[:2], 5, direct=True)
    before = server.stats()["compiles"]
    server.query(queries[:16], 5, direct=True)  # Q bucket 16: new shape
    after = server.stats()["compiles"]
    assert after.get(tracing.SEARCH, 0) > before.get(tracing.SEARCH, 0)
    server.query(queries[:16], 5, direct=True)
    assert server.stats()["compiles"] == after


def test_open_spans_are_per_thread():
    seen = []
    with tracing.span(tracing.DISPATCH):
        with tracing.span(tracing.FETCH):
            t = threading.Thread(target=lambda: seen.append(
                tracing.current()))
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
            assert tracing.current() == tracing.FETCH
        assert tracing.current() == tracing.DISPATCH
    assert tracing.current() == tracing.NO_SPAN
    assert seen == [tracing.NO_SPAN]


def test_server_latency_window_is_bounded(index, queries, monkeypatch):
    monkeypatch.setattr(serve, "LATENCY_WINDOW", 3)
    server = ZenServer(index)
    for _ in range(5):
        server.query(queries[:2], 5)
    assert len(server._latency_s) == 3
    out = server.stats()
    assert out["queries"] == 10 and out["batches"] == 5
    assert 0.0 < out["p50_ms"] <= out["p99_ms"]
