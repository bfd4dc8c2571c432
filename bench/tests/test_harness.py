"""CPU rehearsal of a whole run of each cell at a tiny size, of the traffic
drivers, and of the refusal to run without a TPU."""
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import threading
import types

from bench import readers, run, spec, sweep
from bench.loads import Collector, Request, closed_loop, open_loop
from bench.tests import tiny

SEED = 2 ** 33 + 5  # wider than 32 bits, as the driver's seeds are


@pytest.mark.parametrize("name", sorted(tiny.CELLS))
@pytest.mark.parametrize("traced", [False, True])
def test_run_reports_cell_metrics(name, traced):
    cell = tiny.cell(name)
    result, lines = run.run(cell, SEED, 1.5, traced, jax.devices(),
                            t_start=time.perf_counter())
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    expected = cell.per_layer if traced else cell.end_to_end
    names = {m["name"] for m in expected}
    # on the CPU no device plane is traced: the trace's readers find
    # nothing to read and their metrics are left out
    assert set(result["metrics"]) <= names
    if not traced:
        assert set(result["metrics"]) == names
    assert "build_index_s" in result["metrics"] or not traced
    assert any("compiles_in_window=0" in line for line in lines)
    json.dumps(result)


def test_same_seed_same_inputs():
    cell = tiny.cell("deep1b-ivf.online")
    a = open_loop.schedule(cell.traffic, SEED, 3.0)
    b = open_loop.schedule(cell.traffic, SEED, 3.0)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_every_seed_offers_the_same_gaps():
    traffic = {"rate_qps": 50.0, "rows_per_request": 1, "pool": 100}
    t1, _ = open_loop.schedule(traffic, 1, 10.0)
    t2, _ = open_loop.schedule(traffic, 2 ** 40, 10.0)
    assert len(t1) == len(t2) == 500
    assert not np.array_equal(t1, t2)
    gaps = [np.sort(np.diff(np.concatenate([[0.0], t]))) for t in (t1, t2)]
    assert np.allclose(gaps[0], gaps[1])
    assert t1[-1] < 10.0 and t2[-1] < 10.0


def test_every_seed_asks_the_same_rows():
    open_ = {"rate_qps": 50.0, "rows_per_request": 1, "pool": 100}
    a = open_loop.schedule(open_, 1, 10.0)[1]
    b = open_loop.schedule(open_, 2 ** 40, 10.0)[1]
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a, axis=None), np.sort(b, axis=None))
    closed = {"rows_per_request": 64, "pool": 1024}
    for i in (0, 7, 15, 16):  # 16 wraps round the pool
        a = closed_loop.request_rows(closed, 1, i)
        b = closed_loop.request_rows(closed, 2 ** 40, i)
        assert not np.array_equal(a, b)
        assert np.array_equal(np.sort(a), np.sort(b))
    assert np.array_equal(
        np.sort(closed_loop.request_rows(closed, 3, 16)),
        np.sort(closed_loop.request_rows(closed, 3, 0)))


def test_dispatch_rows():
    assert list(open_loop.dispatch_rows({}, 64)) == list(range(1, 65))
    two = {"clients": 2, "rows_per_request": 64}
    assert closed_loop.dispatch_rows(two, 64) == [64]
    odd = {"clients": 3, "rows_per_request": 24}
    assert closed_loop.dispatch_rows(odd, 64) == [8, 24, 48, 64]


def test_every_cell_names_files_that_exist():
    bm = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"])
        spec.module("loads", cell.traffic["kind"])
        spec.module("references", cell.config["reference"])
        spec.module("generators", cell.config["data"]["generator"])
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.reader(m["name"]))
        assert cell.end_to_end and cell.per_layer


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "bench", "run.py"),
         "--workload", "deep1b-flat.offline", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=spec.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs 1 TPU" in out.stderr


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        spec.peaks("cpu")
    assert spec.peaks("TPU v5 lite")["bytes_per_s"] == 819e9


class _Handle:
    """A program's handle whose own clock reads far off."""

    latency_s = 1e6

    def __init__(self):
        self.event = threading.Event()

    def done(self):
        return self.event.is_set()

    def result(self, timeout=None):
        if not self.event.wait(timeout):
            raise TimeoutError
        return np.zeros((1, 10), np.float32), np.zeros((1, 10), np.int32)


def test_answers_are_stamped_on_the_benchmarks_clock():
    collector = Collector()
    reqs = [Request(time.perf_counter(), np.array([i])) for i in range(3)]
    handles = [_Handle() for _ in reqs]
    for req, h in zip(reqs, handles):
        req.submit(lambda q, n, h=h: h, None, 10)
        collector.add(req)
    time.sleep(0.05)
    t = time.perf_counter()
    handles[2].event.set()  # out of order: the oldest is still due
    handles[0].event.set()
    collector.finish(time.perf_counter() + 0.2)
    assert [r.status for r in reqs] == ["ok", "unanswered", "ok"]
    for r in (reqs[0], reqs[2]):
        assert t <= r.resolved < t + 0.02
        assert r.answered.is_set()


def _served(submitted, resolved, rows=64):
    r = Request(submitted, np.zeros(rows, np.int64))
    r.submitted, r.resolved, r.status = submitted, resolved, "ok"
    return r


def test_window_rate_counts_the_share_in_flight_at_the_close():
    # two closed-loop clients, one 64-row dispatch a second, window [0, 10]
    reqs = [_served(max(i - 2.0, 0.0), float(i)) for i in range(1, 13)]
    for close, expect in ((10.0, 640.0), (10.25, 656.0), (10.75, 688.0)):
        ctx = types.SimpleNamespace(requests=reqs, t0=0.0, t1=close,
                                    seconds=10.0)
        assert readers.window_rate(ctx) == pytest.approx(expect / 10.0)
    # a stall at the close lowers the rate: the last answer takes 3 s
    reqs[9].resolved = 12.0
    ctx = types.SimpleNamespace(requests=reqs[:10], t0=0.0, t1=10.0,
                                seconds=10.0)
    assert readers.window_rate(ctx) == pytest.approx(
        (9 * 64 + 64 / 3) / 10.0)


def test_metric_split_by_cell_shares_a_reader():
    assert spec.reader("outside_kernel.ms_per_query.online") is not None
    assert (spec.reader("device.idle.offline").__module__
            == spec.reader("device.idle").__module__)
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric.online")


def _window(latencies, failed=0):
    reqs = [_served(float(i), float(i) + lat, rows=1)
            for i, lat in enumerate(latencies)]
    for r in reqs[:failed]:
        r.status = "unanswered"
    return types.SimpleNamespace(
        requests=reqs, drained=float(len(reqs) + 10), t1=float(len(reqs)),
        frontend={"dispatched_rows": 1, "padded_rows": 2, "dispatches": 1},
        compiles={})


def test_sweep_rule_finds_a_growing_backlog():
    steady = sweep.summary(_window([1.0] * 30), 10.0, 30.0)
    growing = sweep.summary(_window([1.0] * 20 + [1.3] * 10), 20.0, 30.0)
    failing = sweep.summary(_window([1.0] * 30, failed=1), 30.0, 30.0)
    assert steady["sustained"]
    assert not growing["sustained"] and not failing["sustained"]
    assert sweep.knee([steady, growing, failing]) == 10.0
    assert sweep.knee([growing]) is None
