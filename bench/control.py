#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the plain
reference put in the program's place, one precision step lower.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed it makes the cell's data as a run does, takes the query rows
that a run of the cell would ask (the open loop's schedule; for a closed
loop ``--requests`` per client), answers them with the reference's scan in
three bf16 passes (``control=True``, a TPU's ``Precision.HIGH``) instead of
the program, and compares those answers as a run compares the program's.
It must come out not correct. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import checks, spec  # noqa: E402


def request_rows(cell: spec.Cell, seed: int, seconds: float,
                 per_client: int) -> np.ndarray:
    """Pool rows of the requests a run of ``cell`` would send."""
    traffic = cell.traffic
    if traffic["kind"] == "open_loop":
        load = spec.module("loads", "open_loop")
        return load.schedule(traffic, seed, seconds)[1].reshape(-1)
    load = spec.module("loads", "closed_loop")
    return np.concatenate([load.request_rows(traffic, seed, i)
                           for i in range(per_client * traffic["clients"])])


def readings(cell: spec.Cell, seed: int, seconds: float,
             per_client: int) -> dict:
    """The control's compared numbers for one seed, and ``correct``."""
    import jax

    from bench.run import seed_key

    cfg, traffic = cell.config, cell.traffic
    gen = spec.module("generators", cfg["data"]["generator"])
    params = {k: v for k, v in cfg["data"].items()
              if k not in ("generator", "note")}
    key = jax.random.fold_in(seed_key(cfg["data_seed"]), 0)
    corpus, pool = gen.make(key, rows=cfg["rows"], pool=traffic["pool"],
                            dim=cfg["dim"], **params)
    pool_np = np.asarray(pool)
    reference = spec.module("references", cfg["reference"])
    rows = request_rows(cell, seed, seconds, per_client)
    n = traffic["n_neighbors"]
    d2, ids = reference.search(pool_np[rows], corpus, n, control=True)
    numbers = checks.readings(reference, pool_np, corpus, rows,
                              np.sqrt(np.maximum(d2, 0.0)), ids, n)
    correct, compared = checks.judge(numbers, cfg["correct"], [])
    return {"seed": seed, "answers": int(rows.size), "correct": correct,
            "checks": compared}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--requests", type=int, default=60,
                   help="requests per client of a closed loop")
    args = p.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.seconds, args.requests)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
