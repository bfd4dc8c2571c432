#!/usr/bin/env python3
"""Sweep of offered rates for an open-loop cell, to find its knee.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 85 90 95

One process builds the cell's system once, then drives the cell's traffic
at each rate in turn for ``--seconds`` (the queue drains between rates).
For each rate it prints the offered rate, the latency percentiles from the
scheduled arrival, the median latency of each third of the arrivals, the
requests still outstanding at the window's close, how long the drain took,
the frontend's occupancy and how late the generator ran.

The rule: a rate is sustained when no request fails and the median latency
of the window's last third of arrivals is at most ``GROWTH`` times that of
its middle third. (The first third is left out, as the queue starts
empty.) A backlog that grows by r rows a second lengthens the last third's
latencies over the middle third's by the service of r x seconds / 3 rows.
The sweep runs the rates in the order given, upward, and stops after two
rates in a row are not sustained; the knee is the highest rate sustained. A cell's rate is fixed
at a share of the knee in its traffic file. The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import readers, run, spec  # noqa: E402


#: the last third's median latency may be at most this times the middle's
GROWTH = 1.2


def summary(w, rate: float, seconds: float) -> dict:
    reqs = w.requests  # in the order of their arrivals
    lat = readers.latencies_ms(w)
    third = max(len(lat) // 3, 1)
    p50 = [float(np.percentile(part, 50)) for part in
           (lat[:third], lat[third:-third] if len(lat) > 2 * third
            else lat, lat[-third:])]
    late = np.array([r.submitted - r.scheduled for r in reqs]) * 1e3
    failed = sum(r.status != "ok" for r in reqs)
    answers = [r.resolved for r in reqs if r.status == "ok"]
    f = w.frontend
    return {
        "offered_qps": rate, "requests": len(reqs), "failed": failed,
        "p50_ms": float(np.percentile(lat, 50)),
        "p99_ms": float(np.percentile(lat, 99)),
        "p50_thirds_ms": p50,
        "outstanding_at_close": sum(
            r.status != "ok" or r.resolved > w.t1 for r in reqs
            if r.submitted is not None and r.submitted <= w.t1),
        "drain_s": (max(answers) - w.t1) if answers else None,
        "occupancy": f["dispatched_rows"] / max(f["padded_rows"], 1),
        "dispatches": f["dispatches"],
        "rows_per_dispatch": f["dispatched_rows"] / max(f["dispatches"], 1),
        "generator_late_max_ms": float(late.max()),
        "compiles": w.compiles,
        "sustained": failed == 0 and p50[2] <= GROWTH * p50[1],
    }


def sweep(sut, cell, rates, seed: int, seconds: float, counter) -> list:
    """Drive each rate in turn; stop after two in a row not sustained."""
    rows, missed = [], 0
    for i, rate in enumerate(rates):
        traffic = dict(cell.traffic, rate_qps=rate)
        w = run.window(sut, traffic, seed + i, seconds, counter)
        rows.append(summary(w, rate, seconds))
        print(json.dumps(rows[-1]), flush=True)
        missed = 0 if rows[-1]["sustained"] else missed + 1
        if missed == 2:
            break
    return rows


def knee(rows: list):
    """The highest rate sustained, or None."""
    rates = [r["offered_qps"] for r in rows if r["sustained"]]
    return max(rates) if rates else None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if cell.traffic["kind"] != "open_loop":
        print("sweep: needs an open-loop cell", file=sys.stderr)
        return 2
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    sut = run.setup(cell, args.seed)
    print("\n".join(sut.lines), flush=True)
    counter = run.CompileCounter()
    rows = sweep(sut, cell, args.rates, args.seed, args.seconds, counter)
    print(json.dumps({"knee_qps": knee(rows)}), flush=True)
    sut.server.frontend.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
