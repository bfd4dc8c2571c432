"""Trace reduction: busy/idle union, kernel time, outside-kernel time and
idle gaps named by host spans."""
import pytest

from bench import trace


def _trace():
    # one device: a kernel, an overlapping op, an idle gap while the host
    # is in a dispatch span, and a gap with no host span; ns
    ops = [("fusion.1", 100, 200), ("nsimplex_ivf_probe", 150, 400),
           ("copy.2", 600, 700)]
    host = [("bench.window", 0, 1000), ("bench.query_block", 380, 590),
            ("PjitFunction(f)", 450, 500)]
    return trace.from_events({0: ops}, host)


def test_merge_and_length():
    assert trace.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert trace.length([(1, 4), (5, 8)]) == 6


def test_busy_idle_and_kernel_time():
    t = _trace()
    assert t.window_s == pytest.approx(1000e-9)
    assert t.busy_s == pytest.approx(400e-9)  # [100,400] + [600,700]
    assert t.op_time("nsimplex_") == pytest.approx(250e-9)
    assert t.op_time("nsimplex_zen_topk") == 0.0
    assert t.top_ops(2) == [["nsimplex_ivf_probe", 250e-9],
                            ["fusion.1", 100e-9]]


def test_gaps_named_by_host():
    t = _trace()
    assert t.gaps(0) == [(0, 100), (400, 600), (700, 1000)]
    gaps = t.idle_gaps(3)
    assert [g[1] for g in gaps] == pytest.approx([300e-9, 200e-9, 100e-9])
    # the dispatch span covers the (400, 600) gap; the window span never
    # names a gap
    assert gaps[1][0] == "bench.query_block"
    assert gaps[0][0] == "no host event"


def test_ops_clipped_to_window():
    t = trace.from_events({0: [("a", 0, 50), ("b", 90, 130)]},
                          [("bench.window", 100, 200)])
    assert t.busy_s == pytest.approx(30e-9)


def test_load_reads_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.query_block"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = trace.load(str(tmp_path), [0])
    names = {n for n, _, _ in t.host}
    assert {"bench.query_block", trace.WINDOW_SPAN} <= names
    span = [e - s for n, s, e in t.host if n == trace.WINDOW_SPAN][0]
    assert t.window_s == pytest.approx(span / 1e9)


def test_tpu_op_named_by_its_instruction():
    class Ev:
        name = ("%nsimplex_zen_topk.1 = (f32[64,128]) custom-call(f32[64,16]"
                " %copy), custom_call_target=\"tpu_custom_call\"")
        start_ns, duration_ns = 10.0, 90.0

    name, start, end, label = trace._op(Ev)
    assert (name, start, end) == ("nsimplex_zen_topk.1", 10, 100)
    assert label.startswith("%nsimplex_zen_topk.1 = ")
    t = trace.from_events({0: [(name, start, end, label)]}, [])
    assert t.op_time("nsimplex_zen_topk") == pytest.approx(90e-9)
    assert t.top_ops(1) == [[label, 90e-9]]


def test_recorded_tpu_trace():
    """Three flat-scan dispatches recorded on a TPU v5 lite: the kernel
    holds most of the busy time, and busy time is the union of the ops."""
    import json
    import os

    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tpu_flat_3_dispatches.json")) as f:
        rec = json.load(f)
    ops = [tuple(ev) for ev in rec["devices"]["0"]]
    t = trace.from_events({0: ops}, [tuple(ev) for ev in rec["host"]])
    # union by a sweep over sorted endpoints, independent of trace.merge
    busy, end = 0, -1
    for _, s, e, _ in sorted(ops, key=lambda ev: ev[1]):
        if e > end:
            busy += e - max(s, end)
            end = e
    assert t.busy_s == pytest.approx(busy / 1e9)
    kernel = t.op_time("nsimplex_zen_topk")
    assert 0.9 * t.busy_s < kernel <= t.busy_s
    assert t.top_ops(1)[0][0].startswith("%nsimplex_zen_topk")
    assert len(t.gaps(0)) > 3
    assert all(label for label, _ in t.idle_gaps(5))
