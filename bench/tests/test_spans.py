"""The readers of the program's own spans: host time per dispatch and the
device's idle time between dispatches, on a hand-made trace known to the
ns, on a trace recorded on a TPU, and through a traced run at CPU size."""
import json
import os
import time

import jax
import pytest

from bench import run, spans, spec, trace
from bench.tests import tiny

NEW = ("frontend.queue_wait_ms.online",
       "served_path.host_ms_per_dispatch.online",
       "served_path.host_ms_per_dispatch.offline",
       "device.idle_between_dispatches.online",
       "device.idle_between_dispatches.offline")


def _hand_made():
    # window 0-1000 ns; one device busy 100-300 and 500-800. Dispatch A
    # 50-400 waits for the device in 150-380; dispatch B 450-900 (its
    # arguments kept in its name) waits in 520-850. Idle 0-100, 300-500,
    # 800-1000: outside the dispatches 0-50, 400-450, 900-1000.
    ops = [("nsimplex_ivf_probe.1", 100, 300), ("copy", 500, 800)]
    host = [("bench.window", 0, 1000),
            ("zen.dispatch", 50, 400), ("zen.project", 60, 100),
            ("zen.fetch", 150, 380),
            ("zen.dispatch#dispatch=1,rows=3#", 450, 900),
            ("zen.fetch", 520, 850),
            ("zen.submit", 420, 430)]
    return trace.from_events({0: ops}, host)


def test_host_time_and_idle_split_known_to_the_ns():
    t = _hand_made()
    # A: 350 - 230 = 120 ns; B: 450 - 330 = 120 ns
    assert spans.host_ms_per_dispatch(t) == pytest.approx(120e-6)
    # 50 + 50 + 100 ns of 1000
    assert spans.idle_between_dispatches_percent(t) == pytest.approx(20.0)
    idle = 100.0 * (1 - t.busy_s / t.window_s)
    assert idle == pytest.approx(50.0)
    assert spans.idle_between_dispatches_percent(t) <= idle


def test_dispatch_cut_by_the_window_counts_for_idle_only():
    ops = [("a", 100, 200)]
    host = [("bench.window", 0, 1000), ("zen.dispatch", 50, 300),
            ("zen.fetch", 90, 210), ("zen.dispatch", 900, 1200)]
    t = trace.from_events({0: ops}, host)
    assert spans.host_ms_per_dispatch(t) == pytest.approx(130e-6)
    # idle 0-100 and 200-1000; outside dispatches 0-50 and 300-900
    assert spans.idle_between_dispatches_percent(t) == pytest.approx(65.0)


def test_no_spans_nothing_to_read():
    t = trace.from_events({0: [("a", 100, 200)]},
                          [("bench.window", 0, 1000),
                           ("bench.query_block", 50, 300)])
    assert spans.host_ms_per_dispatch(t) is None
    assert spans.idle_between_dispatches_percent(t) is None
    no_ops = trace.from_events({0: []}, [("bench.window", 0, 1000),
                                         ("zen.dispatch", 50, 300)])
    assert spans.idle_between_dispatches_percent(no_ops) is None


def _sweep_union(intervals):
    """Union length by a sweep over sorted endpoints (independent of
    trace.merge)."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def test_recorded_tpu_trace_with_program_spans():
    """Three IVF dispatches of the online cell recorded on a TPU v5 lite,
    with the program's spans: the idle split and the host time agree with
    a plain computation over the recorded events, and every dispatch holds
    its steps in order."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tpu_ivf_spans.json")) as f:
        rec = json.load(f)
    ops = [tuple(ev) for ev in rec["devices"]["0"]]
    host = [tuple(ev[:3]) for ev in rec["host"]]
    t = trace.from_events({0: ops}, host)
    disp = sorted((s, e) for n, s, e in host if n == "zen.dispatch")
    fetch = [(s, e) for n, s, e in host if n == "zen.fetch"]
    assert len(disp) == 3
    own = [(e - s) - sum(min(e, fe) - max(s, fs) for fs, fe in fetch
                         if fs < e and fe > s) for s, e in disp]
    assert spans.host_ms_per_dispatch(t) == pytest.approx(
        sum(own) / 3 / 1e6)
    window = t.end - t.start
    busy = _sweep_union([(s, e) for _, s, e, _ in ops])
    covered = _sweep_union([(s, e) for _, s, e, _ in ops] + disp)
    between = 100.0 * (window - covered) / window
    assert spans.idle_between_dispatches_percent(t) == pytest.approx(between)
    assert between <= 100.0 * (window - busy) / window
    # one id links a request to its dispatch: the rows a dispatch serves
    # were submitted while the dispatch before it ran
    args = sorted((ev for ev in rec["host"] if ev[0] == "zen.dispatch"),
                  key=lambda ev: ev[1])
    submits = {ev[4]["request"]: ev[1] for ev in rec["host"]
               if ev[0] == "zen.submit"}
    for before, ev in zip(args, args[1:]):
        served = [int(v) for v in ev[4]["requests"].strip("[]").split(",")]
        assert len(served) == ev[4]["rows"]
        assert all(before[1] < submits[r] < ev[1] for r in served)
    for s, e in disp:
        steps =[ev[0] for ev in sorted(rec["host"], key=lambda ev: ev[1])
                 if s <= ev[1] and ev[2] <= e and ev[0] in (
                     "zen.project", "zen.search", "zen.rerank",
                     "zen.fetch", "zen.resolve")]
        assert steps == ["zen.project", "zen.search", "zen.rerank",
                         "zen.fetch", "zen.resolve"]


def test_new_metrics_name_their_readers():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        assert callable(spec.reader(name))
        assert entries[name]["workloads"]


@pytest.mark.parametrize("name", sorted(tiny.CELLS))
def test_traced_run_reads_the_program_spans(name):
    """A traced run at CPU size: the host spans and the queue-wait counter
    are read; the CPU traces no device plane, so the idle split is left
    out."""
    bm = tiny.benchmark()
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bm["per_layer"] += [m for m in json.load(f)["per_layer"]
                            if m["name"] in NEW]
    cell = spec.load_cell(name, bm, traffic_dir=tiny.FIXTURES)
    result, _ = run.run(cell, 2 ** 33 + 7, 1.5, True, jax.devices(),
                        t_start=time.perf_counter())
    assert result["correct"], result["checks"]
    got = result["metrics"]
    host = [m for m in got if m.startswith("served_path.host_ms")]
    assert len(host) == 1 and got[host[0]]["value"] > 0
    assert not any(m.startswith("device.idle_between") for m in got)
    if name == "deep1b-ivf.online":
        assert got["frontend.queue_wait_ms.online"]["value"] >= 0
