"""Arithmetic shared by the metric readers in ``bench/metrics``.

Each reader is ``read(ctx) -> float | None``; it returns None where the run
has nothing for it to read, and the harness then leaves the metric out.
``ctx`` holds the run's requests and window, the frontend's counter deltas
over the window, the build time, and in a traced run the reduced trace,
the dispatches of the window (padded rows, real rows, fetch width), the
program's index and server (alive until the readers are done) and the
device's kind, whose peaks are in ``bench/peaks.json``.

Roofline counts are the work the algorithm needs, the same whatever
implements it (so a share cannot pass 100% by a better schedule):

  estimator  2k + 4 flops per (query, index row) pair: a k-wide dot
             product, the norms' sum, a square root
  flat scan  the stored (N, k) coordinates read once per dispatch, plus the
             dispatch's real query rows and outputs
  IVF probe  the member rows of each distinct probed cluster read once per
             dispatch (ids included), the pairs of each real query with
             the members of its own probed clusters; the probe lists come
             from ``IVFZenIndex.probe_clusters`` after the window
"""
from __future__ import annotations

import numpy as np

from bench import spec

#: prefix of every kernel the program names (``pallas_call(name=...)``)
KERNEL_PREFIX = "nsimplex_"


def est_flops(k: int) -> int:
    return 2 * k + 4


def latencies_ms(ctx) -> np.ndarray:
    """Latency of every request, from its scheduled arrival to its answer;
    a request that failed counts as answered when the wait ended."""
    return np.array([((r.resolved if r.status == "ok" else ctx.drained)
                      - r.scheduled) * 1e3 for r in ctx.requests])


def percentile_ms(ctx, q: float):
    lat = latencies_ms(ctx)
    return float(np.percentile(lat, q)) if lat.size else None


def window_rate(ctx):
    """Query rows served per second of the whole window.

    An answer that came within the window counts whole. One still in
    flight at the window's close counts the share of its service that fell
    inside: the chip serves one dispatch at a time, so an answer's service
    runs from the answer before it (or its own submit, if later) to its
    own answer. So a stall at the window's end lowers the rate, and the
    close falling just before or after a whole answer does not make it
    jump by one answer's rows."""
    done = [r for r in ctx.requests if r.status == "ok"]
    if not done:
        return None
    stamps = sorted({r.resolved for r in done})
    before = dict(zip(stamps, [ctx.t0] + stamps[:-1]))
    rows = 0.0
    for r in done:
        start = max(r.submitted, before[r.resolved], ctx.t0)
        if r.resolved <= ctx.t1:
            rows += len(r.rows)
        elif start < ctx.t1:
            rows += len(r.rows) * (ctx.t1 - start) / (r.resolved - start)
    return rows / ctx.seconds


def traced_queries(ctx) -> int:
    return sum(d["real"] for d in ctx.dispatches)


def _outputs_bytes(disp) -> int:
    return disp["real"] * disp["width"] * 8  # f32 distance + int32 id


def flat_work(ctx):
    """(flops, bytes) of the window's flat-scan dispatches, or None."""
    index = ctx.index
    if index is None or index.ivf is not None or not ctx.dispatches:
        return None
    n, k = index.coords.shape
    item = index.coords.dtype.itemsize
    flops = nbytes = 0
    for disp in ctx.dispatches:
        real = disp["real"]
        flops += real * n * est_flops(k)
        nbytes += n * k * item + real * k * 4 + _outputs_bytes(disp)
    return flops, nbytes


def ivf_work(ctx):
    """(flops, bytes) of the window's IVF-probe dispatches, or None."""
    import jax.numpy as jnp

    index = ctx.index
    if index is None or index.ivf is None or not ctx.dispatches:
        return None
    ivf = index.ivf
    k = ivf.dim
    members = np.asarray(ivf.tile_ids >= 0).reshape(
        ivf.n_clusters, -1).sum(axis=1)
    row_bytes = k * ivf.tile_coords.dtype.itemsize + 4
    flops = nbytes = 0
    for disp in ctx.dispatches:
        real = disp["real"]
        if real == 0:
            continue
        # the padded block keeps to the shapes the window compiled
        qp = index.transform.transform(jnp.asarray(disp["rows"]))
        probes = np.asarray(ivf.probe_clusters(
            qp, ctx.server.nprobe, ctx.server.mode))[:real]
        flops += int(members[probes].sum()) * est_flops(k)
        nbytes += (int(members[np.unique(probes)].sum()) * row_bytes
                   + real * k * 4 + _outputs_bytes(disp))
    return flops, nbytes


def roofline_share(ctx, kernel: str, work):
    """Percent: the least time the chip could take for ``work(ctx)``
    (larger of flops over peak flops and bytes over peak bandwidth) over
    the kernel's summed device time in the trace."""
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_time(kernel)
    counted = work(ctx)
    if seconds <= 0 or counted is None:
        return None
    flops, nbytes = counted
    peaks = spec.peaks(ctx.device_kind)
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
    return 100.0 * least / seconds


def outside_kernel_ms_per_query(ctx):
    """Device busy time outside every ``nsimplex_*`` kernel, per query the
    traced window answered, in ms."""
    if ctx.trace is None or ctx.trace.n_ops == 0:
        return None
    n = traced_queries(ctx)
    if n == 0:
        return None
    outside = ctx.trace.busy_s - ctx.trace.op_time(KERNEL_PREFIX)
    return 1e3 * outside / n


def idle_percent(ctx):
    if ctx.trace is None or ctx.trace.n_ops == 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)


def occupancy_percent(ctx):
    f = ctx.frontend
    if not f["padded_rows"]:
        return None
    return 100.0 * f["dispatched_rows"] / f["padded_rows"]
