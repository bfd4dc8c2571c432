#!/usr/bin/env python3
"""One run of one benchmark cell, on the chips of the machine it runs on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run is one process. It makes the cell's data from ``--seed`` on the
device, builds the index through the program's ``build_index``, starts
``ZenServer(frontend=True)`` with its own ticker thread, warms the shapes
the cell's traffic dispatches, drives the traffic for ``--seconds``, waits
for every answer, frees the program's state and compares every answer with
the plain reference. Its last line on stdout is one JSON object: the
cell's end-to-end metrics (``--trace 0``) or its per-layer metrics read
from a profiler trace of the window (``--trace 1``); the compared numbers,
each beside its limit, come last there and as the last lines on stderr.

Everything a cell names is found by name (``bench/spec.py``). Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits with code 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import checks, spec  # noqa: E402
from bench.loads import Collector  # noqa: E402

#: how long after the window closes the run waits for outstanding answers
DRAIN_S = 60.0


def seed_key(seed: int):
    """A PRNG key that keeps all 64 bits of ``seed``."""
    import jax

    seed %= 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed // 2 ** 32)


class CompileCounter:
    """Counts JAX lowerings and backend compiles while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.on = False
        self.count = {e.rsplit("/", 1)[1]: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.count[event.rsplit("/", 1)[1]] += 1


def _frontend_counts(frontend) -> dict:
    s = frontend.stats
    return {"dispatches": s.dispatches, "dispatched_rows": s.dispatched_rows,
            "padded_rows": s.padded_rows, "rejected": s.rejected,
            "failures": s.failures}


def _record_dispatches(server, log: list):
    """Wrap ``server._query_block`` in a host span and log each dispatch:
    (start, end, padded rows, real rows of the previous dispatch filled in
    from the frontend's counters when the next one starts)."""
    import jax

    inner = server._query_block
    stats = server.frontend.stats

    def query_block(queries, width, n_bucket, index=None):
        if log:
            log[-1]["real"] = stats.dispatched_rows - log[-1]["before"]
        entry = {"rows": np.array(queries), "width": width,
                 "before": stats.dispatched_rows,
                 "start": time.perf_counter()}
        with jax.profiler.TraceAnnotation("bench.query_block"):
            out = inner(queries, width, n_bucket, index=index)
        entry["end"] = time.perf_counter()
        log.append(entry)
        return out

    server._query_block = query_block

    def close():
        if log:
            log[-1]["real"] = stats.dispatched_rows - log[-1]["before"]
        server._query_block = inner

    return close


def setup(cell: spec.Cell, seed: int):
    """Data, index and a started server for ``cell``, its shapes warmed.

    The corpus, the query pool and the build's key come from the
    configuration's ``data_seed``, not from ``seed``: a fresh corpus and
    fresh random pivots move recall by 0.67-0.98 from seed to seed, so the
    run's seed draws only the traffic (which pool rows, in what order).

    Returns a namespace: ``corpus``, ``pool`` (host copy of the query
    pool), ``index``, ``server``, ``load`` (the traffic's driver module),
    the seconds of each step, ``buckets`` (the Q buckets warmed) and
    ``lines`` (log lines)."""
    import jax

    from repro.launch.serve import ZenServer, build_index
    from repro.serving import bucket_q

    cfg, traffic = cell.config, cell.traffic
    key = seed_key(cfg["data_seed"])
    load = spec.module("loads", traffic["kind"])

    t = time.perf_counter()
    gen = spec.module("generators", cfg["data"]["generator"])
    params = {k: v for k, v in cfg["data"].items()
              if k not in ("generator", "note")}
    corpus, pool = gen.make(jax.random.fold_in(key, 0), rows=cfg["rows"],
                            pool=traffic["pool"], dim=cfg["dim"], **params)
    pool_np = np.asarray(pool)
    data_s = time.perf_counter() - t

    t = time.perf_counter()
    index = build_index(corpus, cfg["k"], key=jax.random.fold_in(key, 1),
                        **cfg["build"])
    jax.block_until_ready([index.coords, index.ivf])
    build_s = time.perf_counter() - t

    t = time.perf_counter()
    server = ZenServer(index, frontend=True, **cfg["server"])
    front = server.frontend
    buckets = sorted({bucket_q(n, front.max_batch)
                      for n in load.dispatch_rows(traffic, front.max_batch)})
    for b in buckets:
        d, ids = server.query(pool_np[:b], traffic["n_neighbors"],
                              direct=True)
        np.asarray(d), np.asarray(ids)
    front.start()
    warm_s = time.perf_counter() - t
    return types.SimpleNamespace(
        corpus=corpus, pool=pool_np, index=index, server=server, load=load,
        data_s=data_s, build_s=build_s, warm_s=warm_s, buckets=buckets,
        lines=[f"setup data_s={data_s:.3f} build_index_s={build_s:.3f} "
               f"warm_s={warm_s:.3f} buckets={buckets}"])


def window(sut, traffic: dict, seed: int, seconds: float,
           counter: "CompileCounter"):
    """Drive ``traffic`` for ``seconds`` and wait for every answer.

    Returns a namespace: ``t0`` (window start), ``t1`` (its end),
    ``requests``, ``drained`` (when the wait ended), ``frontend`` (counter
    deltas over the window) and ``compiles`` (lowerings and compiles
    inside it)."""
    import jax

    front = sut.server.frontend
    before = _frontend_counts(front)
    counts = dict(counter.count)
    counter.on = True
    collector = Collector()
    with jax.profiler.TraceAnnotation("bench.window"):
        t0, requests = sut.load.drive(front.submit, sut.pool, traffic, seed,
                                      seconds, collector)
        t1 = t0 + seconds
        collector.finish(t1 + DRAIN_S)
        drained = time.perf_counter()
    counter.on = False
    after = _frontend_counts(front)
    return types.SimpleNamespace(
        t0=t0, t1=t1, requests=requests, drained=drained,
        frontend={k: after[k] - before[k] for k in after},
        compiles={k: counter.count[k] - counts[k] for k in counts})


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        devices, t_start: float = T_START) -> tuple:
    """Run ``cell`` once; returns (result dict, log lines)."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    sut = setup(cell, seed)
    lines = sut.lines
    server, index = sut.server, sut.index
    counter = CompileCounter()
    dispatches, trace_dir = [], None
    if trace:
        close_log = _record_dispatches(server, dispatches)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    w = window(sut, traffic, seed, seconds, counter)
    if trace:
        jax.profiler.stop_trace()
        close_log()
        del close_log  # it holds the server; the reference needs the memory
    server.frontend.stop()
    requests, t0 = w.requests, w.t0
    setup_s = t0 - t_start
    lines.append(_load_line(requests, t0, seconds, traffic, w.drained,
                            w.frontend, w.compiles))

    used = devices[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    ctx = types.SimpleNamespace(
        cell=cell, requests=requests, t0=t0, t1=w.t1, seconds=seconds,
        drained=w.drained, setup_s=setup_s, build_s=sut.build_s,
        frontend=w.frontend,
        dispatches=dispatches, server=server, index=index, trace=None,
        device_kind=used[0].device_kind, numbers=None)
    metrics, result_extra = {}, {}
    if trace:
        from bench import trace as trace_lib

        tr = trace_lib.load(trace_dir, [d.id for d in used])
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.trace = tr
        metrics = _read(cell.per_layer, ctx)
        result_extra["breakdown"] = {"device_ops": tr.top_ops(10),
                                     "idle_gaps": tr.idle_gaps(10)}
        lines.append(f"trace window_s={tr.window_s:.6f} "
                     f"busy_s={tr.busy_s:.6f} device_ops={tr.n_ops}")
    ctx.server = ctx.index = server = index = sut.server = sut.index = None
    gc.collect()

    t = time.perf_counter()
    reference = spec.module("references", cfg["reference"])
    rows, d, ids = checks.served(requests)
    ctx.numbers = checks.readings(reference, sut.pool, sut.corpus, rows, d,
                                  ids, traffic["n_neighbors"])
    correct, compared = checks.judge(ctx.numbers, cfg["correct"], requests)
    lines.append(f"reference answers={rows.size} "
                 f"seconds={time.perf_counter() - t:.3f}")
    if not trace:
        metrics = _read(cell.end_to_end, ctx)

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    if trace:
        device.update(busy_s=ctx.trace.busy_s, window_s=ctx.trace.window_s)
    result = {"correct": correct, "attempted": len(requests),
              "failed": sum(r.status != "ok" for r in requests),
              "metrics": metrics, "device": device, **result_extra,
              "checks": compared}
    return result, lines


def _read(entries, ctx) -> dict:
    out = {}
    for m in entries:
        value = spec.reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _load_line(requests, t0, seconds, traffic, drained, stats,
               compiles) -> str:
    late = [r.submitted - r.scheduled for r in requests
            if r.submitted is not None]
    done = [r for r in requests if r.status == "ok"]
    in_window = sum(len(r.rows) for r in done if r.resolved <= t0 + seconds)
    offered = traffic.get("rate_qps")
    parts = [
        f"load requests={len(requests)}",
        f"answered={len(done)}",
        f"rows_answered_in_window={in_window}",
        f"achieved_rows_per_s={in_window / seconds:.4f}",
        f"submitted_per_s={len(late) / seconds:.4f}",
        f"offered_per_s={offered}",
        f"generator_late_p50_ms={np.percentile(late, 50) * 1e3:.4f}"
        if late else "generator_late_p50_ms=nan",
        f"generator_late_max_ms={max(late) * 1e3:.4f}"
        if late else "generator_late_max_ms=nan",
        f"drain_s={drained - t0 - seconds:.4f}",
        f"compiles_in_window={sum(compiles.values())}",
        " ".join(f"{k}={v}" for k, v in compiles.items()),
        " ".join(f"frontend_{k}={v}" for k, v in stats.items()),
    ]
    return " ".join(parts)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    cell = spec.load_cell(args.workload)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"bench workload={args.workload} seed={args.seed} "
          f"seconds={args.seconds} trace={args.trace} cache_dir={cache_dir} "
          f"device_kind={devices[0].device_kind!r} devices={len(devices)}",
          flush=True)
    result, lines = run(cell, args.seed, args.seconds, bool(args.trace),
                        devices)
    for line in lines:
        print(line, flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(f"check correct={result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
