#!/usr/bin/env python3
"""Smoke run of nSimplex-Zen retrieval serving on a TPU.

Drives the normal serving path, ``build_index`` -> ``ZenServer.query``, once
per phase on one chip's share of a Deep1B-shaped corpus: 96-d float32 vectors
under L2, 10^7 rows (big-ann-benchmarks NeurIPS'21, arXiv:2205.03763: a
10^9-row corpus over 64 chips is ~1.56e7 rows per chip). Vectors come from
``--seed`` on the device; queries are held-out rows of the same manifold.

Phases (one chip, the default):
  (a) ivf_f32   build_index(index="ivf", storage="float32") served by
                ZenServer(rerank_factor=4, frontend=True);
  (b) ivf_int8  the same with int8 tiles;
  (c) flat      the flat streaming index (the ``zen_topk`` kernel);
  (d) stage     the tiered store's host -> device tile upload
                (``tile_stage.stage_blocks``: pinned host memory, Pallas DMA)
                round-trips f32, int8 and id tiles bit for bit.
With ``--chips 4`` the script runs only the 4-way sharded IVF over
``Mesh(jax.devices()[:4], ("shard",))`` and the one-chip IVF it must equal.

Checks, all of which must hold for ``"ok": true``:
  * the device is a TPU;
  * every phase ran;
  * each kernel's ids match the jnp scan reference run on the same chip
    (``ivf_probe_scan`` / ``zen_topk_scan``, called directly as a check),
    a differing slot allowed only where its distance ties the reference's
    within ``PARITY_RTOL``. Both sides compute the estimator's matmul at
    ``Precision.HIGHEST`` (the kernels set it in-kernel, the scans in jnp);
  * recall@10 of (a) and (c) against a blocked exact f32 scan of the raw
    vectors at ``Precision.HIGHEST`` is at least ``RECALL_FLOOR``;
  * with ``--chips 4``: the sharded ids equal the one-chip ids, and the
    tiles sit on four devices.

Every result line goes to stdout before the last, which is one JSON object:
``{"ok": ..., "device": {"platform", "kind", "count"}}``. Without a TPU the
script prints no result and exits non-zero.

    python chip_smoke.py                  # phases (a)-(c), one chip
    python chip_smoke.py --chips 4        # sharded IVF vs one-chip IVF
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.data import synthetic as syn  # noqa: E402
from repro.kernels import ivf_probe as ivf_k  # noqa: E402
from repro.kernels import tile_stage  # noqa: E402
from repro.kernels import zen_topk as zt  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import ZenServer, build_index  # noqa: E402
from repro.serving import bucket_neighbors  # noqa: E402

N_ROWS = 10_000_000     # one chip's share of a 10^9-row corpus
DIM = 96                # Deep1B vector width
K = 16                  # nSimplex projection width
NEIGHBORS = 10          # recall@10
RERANK = 4              # ZenServer(rerank_factor=4)
BATCH = 64              # queries per dispatch
NPROBE = 32             # clusters probed per query
MODE = "zen"
#: per-phase recall@10 floors against the exact scan (see CHANGES.md)
RECALL_FLOOR = {"ivf_f32": 0.5, "flat": 0.5}
#: relative distance tolerance within which kernel and reference may order
#: tied candidates differently
PARITY_RTOL = 1e-4


def log(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def make_data(seed: int, n: int, n_queries: int, dim: int):
    """(corpus (n, dim), queries (n_queries, dim)) f32 from one manifold.

    Made in one jitted call on the default device; queries are extra rows
    of the same draw, never part of the corpus."""
    def gen(key):
        x = syn.manifold_space(key, n + n_queries, dim, dim // 8)
        return x[:n], x[n:]

    return jax.jit(gen)(jax.random.PRNGKey(seed))


@functools.partial(jax.jit, static_argnames=("n_neighbors", "block"))
def exact_topk(queries, corpus, n_neighbors: int, block: int = 65536):
    """Ids (Q, n) of the exact L2 nearest rows: a blocked f32 scan with every
    matmul at ``Precision.HIGHEST``, independent of the code under test."""
    n = corpus.shape[0]
    block = min(block, n)
    qn = jnp.sum(queries * queries, axis=1, keepdims=True)

    def body(i, carry):
        best_d, best_i = carry
        start = jnp.minimum(i * block, n - block)  # clamp the tail block
        blk = jax.lax.dynamic_slice_in_dim(corpus, start, block, axis=0)
        d2 = qn + jnp.sum(blk * blk, axis=1)[None, :] - 2.0 * jnp.matmul(
            queries, blk.T, precision=jax.lax.Precision.HIGHEST)
        ids = start + jnp.arange(block, dtype=jnp.int32)
        d2 = jnp.where(ids[None, :] >= i * block, d2, jnp.inf)  # revisits
        cat_d = jnp.concatenate([best_d, d2], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, d2.shape)], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, n_neighbors)
        return -neg, jnp.take_along_axis(cat_i, pos, axis=1)

    q = queries.shape[0]
    init = (jnp.full((q, n_neighbors), jnp.inf, jnp.float32),
            jnp.full((q, n_neighbors), -1, jnp.int32))
    return jax.lax.fori_loop(0, -(-n // block), body, init)[1]


def recall(served: np.ndarray, truth: np.ndarray) -> float:
    hits = [len(set(s[:NEIGHBORS]) & set(t[:NEIGHBORS]))
            for s, t in zip(served, truth)]
    return float(np.mean(hits)) / NEIGHBORS


def parity(kernel, reference) -> dict:
    """Compare a kernel's (d, ids) with the scan reference's, slot by slot.

    Distances must agree everywhere within ``PARITY_RTOL``. A slot whose id
    differs passes only as a tie: the kernel's id sits elsewhere in the
    reference row at the same distance, or (past the reference's last slot)
    ties the reference's last distance."""
    kd, ki = (np.asarray(a) for a in kernel)
    rd, ri = (np.asarray(a) for a in reference)

    def tied(a, b):
        return (np.isinf(a) & np.isinf(b)) | (
            np.abs(a - b) <= PARITY_RTOL * (1.0 + np.abs(b)))

    bad = int(np.sum(~tied(kd, rd)))
    differing = np.argwhere(ki != ri)
    for r, j in differing:
        pos = np.flatnonzero(ri[r] == ki[r, j])
        other = rd[r, pos[0]] if pos.size else rd[r, -1]
        bad += int(not tied(kd[r, j], other))
    return {"slots": int(ki.size), "ids_differing": int(len(differing)),
            "beyond_tolerance": bad, "ok": bad == 0}


def _block(index) -> None:
    arrays = [index.coords] + ([index.ivf.tile_coords, index.ivf.tile_ids]
                               if index.ivf is not None else [])
    jax.block_until_ready([a for a in arrays if a is not None])


def _memory(devices) -> str:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(f"{stats.get('bytes_in_use', 0) / 2**30:.2f}/"
                   f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f}GiB")
    return ",".join(out)


def _serve(server, queries, batch: int):
    """Serve ``queries`` in ``batch``-row calls; (ids, seconds per call)."""
    ids, secs = [], []
    for b in range(0, queries.shape[0], batch):
        t = time.perf_counter()
        _, got = server.query(queries[b:b + batch], NEIGHBORS)
        ids.append(np.asarray(got))
        secs.append(time.perf_counter() - t)
    return np.concatenate(ids), secs


def run_serving_phase(name, corpus, queries, truth, *, key, build_kw,
                      nprobe=NPROBE, batch=BATCH, interpret=False) -> dict:
    """Build one index, serve every query batch, check parity and recall.

    ``interpret`` runs the Pallas kernels in interpret mode (CPU rehearsal);
    on a TPU it stays False and every kernel is the compiled one."""
    t0 = time.perf_counter()
    index = build_index(corpus, K, key=key, **build_kw)
    _block(index)
    build_s = time.perf_counter() - t0
    server = ZenServer(index, rerank_factor=RERANK, frontend=True,
                       nprobe=nprobe, force_kernel=interpret)
    served, secs = _serve(server, queries, batch)

    fetch = bucket_neighbors(NEIGHBORS * RERANK)
    qp = index.transform.transform(queries[:batch])
    if index.ivf is not None:
        ivf = index.ivf
        probes = ivf.probe_clusters(qp, nprobe, MODE)
        args = (qp, ivf.tile_coords, ivf.tile_ids, probes, fetch, MODE)
        kw = dict(tiles_per_cluster=ivf.tiles_per_cluster,
                  tile_scales=ivf.tile_scales)
        check = parity(ivf_k.ivf_probe(*args, interpret=interpret, **kw),
                       ivf_k.ivf_probe_scan(*args, **kw))
        shape = (f"clusters={ivf.n_clusters} "
                 f"tiles_per_cluster={ivf.tiles_per_cluster}")
    else:
        args = (qp, index.coords, fetch, MODE)
        kw = dict(scales=index.coord_scales)
        check = parity(zt.zen_topk(*args, interpret=interpret, **kw),
                       zt.zen_topk_scan(*args, **kw))
        shape = f"rows={index.coords.shape[0]}"
    out = dict(phase=name, build_s=build_s, first_query_s=secs[0],
               steady_query_ms=[s * 1e3 for s in secs[1:]],
               recall=recall(served, truth), parity=check, shape=shape)
    del server, index
    gc.collect()
    return out


def run_stage_phase(seed: int, *, n_tiles: int = 4096,
                    force_kernel: bool = False) -> dict:
    """Stage f32, int8 and id tiles host -> device; all must come back exact.

    ``n_tiles`` 128-row tiles of width ``K`` per dtype, drawn from ``seed``
    on the host (the tiered store's pool is host data)."""
    rng = np.random.default_rng(seed)
    bufs = {
        "f32": rng.standard_normal((n_tiles, 128, K), np.float32),
        "int8": rng.integers(-127, 128, (n_tiles, 128, K), np.int8),
        "ids": rng.integers(-1, 2**31 - 1, (n_tiles, 128), np.int32),
    }
    out = {}
    for name, vals in bufs.items():
        t = time.perf_counter()
        got = np.asarray(tile_stage.stage_blocks(
            vals, force_kernel=force_kernel))
        out[f"{name}_s"] = time.perf_counter() - t
        out[f"{name}_exact"] = bool(got.dtype == vals.dtype
                                    and np.array_equal(got, vals))
    out["ok"] = all(v for k, v in out.items() if k.endswith("_exact"))
    return dict(phase="stage", n_tiles=n_tiles, **out)


def run_sharded_phase(corpus, queries, *, key, devices, nprobe=NPROBE,
                      batch=BATCH, interpret=False) -> dict:
    """4-way sharded IVF vs the one-chip IVF on the same corpus and nprobe.

    Both serve through ``ZenServer``; their ids must be identical, before
    the re-rank (the probe's candidates) and after it (the served rows)."""
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(devices[:4]), ("shard",))
    t0 = time.perf_counter()
    single = build_index(corpus, K, key=key, index="ivf")
    _block(single)
    single_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    sharded = build_index(corpus, K, key=key, index="ivf", mesh=mesh)
    _block(sharded)
    sharded_s = time.perf_counter() - t0
    placed = sorted({str(s.device) for s in
                     sharded.ivf.tile_coords.addressable_shards})
    servers = [ZenServer(ix, rerank_factor=RERANK, frontend=True,
                         nprobe=nprobe, force_kernel=interpret)
               for ix in (single, sharded)]
    (ids1, secs1), (ids4, secs4) = (_serve(s, queries, batch)
                                    for s in servers)
    fetch = bucket_neighbors(NEIGHBORS * RERANK)
    qp = single.transform.transform(queries[:batch])
    cand1, cand4 = (np.asarray(ix.ivf.search(
        qp, fetch, nprobe, MODE, force_kernel=interpret)[1])
        for ix in (single, sharded))
    return dict(phase="sharded_ivf", build_single_s=single_s,
                build_sharded_s=sharded_s, tile_devices=len(placed),
                first_query_s=[secs1[0], secs4[0]],
                steady_query_ms_single=[s * 1e3 for s in secs1[1:]],
                steady_query_ms_sharded=[s * 1e3 for s in secs4[1:]],
                served_ids_equal=bool(np.array_equal(ids1, ids4)),
                candidate_ids_equal=bool(np.array_equal(cand1, cand4)),
                memory=_memory(devices[:4]))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--n", type=int, default=N_ROWS,
                   help="corpus rows (default: one chip's Deep1B share)")
    p.add_argument("--batches", type=int, default=5,
                   help="64-query batches served per phase")
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = p.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 1
    log(cache_dir=enable_compile_cache())
    dev = devices[0]
    log(platform=dev.platform, device_kind=repr(dev.device_kind),
        devices=len(devices), jax=jax.__version__, n=args.n, dim=DIM, k=K,
        nprobe=NPROBE, batch=BATCH, batches=args.batches, seed=args.seed)

    key = jax.random.PRNGKey(args.seed)
    t0 = time.perf_counter()
    corpus, queries = make_data(args.seed, args.n, BATCH * args.batches, DIM)
    jax.block_until_ready(corpus)
    log(phase="data", seconds=time.perf_counter() - t0,
        memory=_memory(devices[:1]))

    ok = True
    if args.chips == 4:
        r = run_sharded_phase(corpus, queries, key=key, devices=devices)
        ok = (r["served_ids_equal"] and r["candidate_ids_equal"]
              and r["tile_devices"] == 4)
        log(**r, ok=ok)
    else:
        t0 = time.perf_counter()
        truth = np.asarray(exact_topk(queries, corpus, NEIGHBORS))
        log(phase="exact_reference", seconds=time.perf_counter() - t0)
        phases = [("ivf_f32", dict(index="ivf", storage="float32")),
                  ("ivf_int8", dict(index="ivf", storage="int8")),
                  ("flat", dict(index="flat"))]
        for name, build_kw in phases:
            r = run_serving_phase(name, corpus, queries, truth, key=key,
                                  build_kw=build_kw)
            floor = RECALL_FLOOR.get(name, 0.0)
            phase_ok = r["parity"]["ok"] and r["recall"] >= floor
            ok = ok and phase_ok
            log(**r, recall_floor=floor, memory=_memory(devices[:1]),
                ok=phase_ok)
        r = run_stage_phase(args.seed)
        ok = ok and r["ok"]
        log(**r)
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
