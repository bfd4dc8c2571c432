"""Batched Lloyd's k-means over apex coordinates — the IVF coarse quantizer.

The whole fit is jit-compiled and bounded-memory: the assignment pass walks
the (N, k) coordinate matrix in fixed-size row chunks (one (chunk, C) distance
block live at a time, same clamped-tail dynamic-slice pattern as
``kernels.zen_topk.zen_topk_scan``), and the update pass is two segment-sums.

Seeding is k-means++-style D² sampling (first centroid uniform, then each next
centroid drawn with probability proportional to the squared distance to the
nearest already-chosen centroid), the same spread-the-references intuition as
``core.projection.select_references``' redraw loop but with a deterministic
key. Empty clusters are reseeded each iteration to the points currently
farthest from their assigned centroid, so the quantizer cannot silently
collapse onto fewer than ``n_clusters`` cells on degenerate data.

Clustering runs in the *reduced* space under plain Euclidean distance: apex
coordinates live in R^k and the Zen/Lwb/Upb estimators of paper §4.1 are all
monotone in the base-coordinate L2, so Euclidean cells are the right coarse
partition for every estimator mode.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def _sq_dist(blk: Array, centroids: Array) -> Array:
    """Squared Euclidean distances (rows, C) between blk and centroids, f32."""
    bn = jnp.sum(blk * blk, axis=1, keepdims=True)
    cn = jnp.sum(centroids * centroids, axis=1)
    dot = jnp.matmul(blk, centroids.T, preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(bn + cn[None, :] - 2.0 * dot, 0.0)


def _assign_pass(
    coords: Array, centroids: Array, chunk: int
) -> Tuple[Array, Array]:
    """(assignments (N,), squared distance to own centroid (N,)) — chunked.

    One (chunk, C) block lives at a time; the tail chunk is clamped back like
    the streaming top-k scan, which merely recomputes (identically) a few
    already-visited rows.
    """
    n = coords.shape[0]
    chunk = min(chunk, n)
    n_chunks = -(-n // chunk)  # ceil

    def body(i, carry):
        assign, d2own = carry
        start = jnp.minimum(i * chunk, n - chunk)  # clamp the tail chunk
        blk = jax.lax.dynamic_slice_in_dim(coords, start, chunk, axis=0)
        d2 = _sq_dist(blk, centroids)  # (chunk, C)
        a = jnp.argmin(d2, axis=1).astype(jnp.int32)
        m = jnp.min(d2, axis=1)
        assign = jax.lax.dynamic_update_slice_in_dim(assign, a, start, 0)
        d2own = jax.lax.dynamic_update_slice_in_dim(d2own, m, start, 0)
        return assign, d2own

    init = (
        jnp.zeros((n,), jnp.int32),
        jnp.zeros((n,), jnp.float32),
    )
    return jax.lax.fori_loop(0, n_chunks, body, init)


def _seed_plus_plus(coords: Array, n_clusters: int, key: Array) -> Array:
    """k-means++ D² seeding: one (N,)-sized single-centroid distance pass per
    draw — O(N) live state, never an (N, C) temp."""
    n = coords.shape[0]
    first = jax.random.randint(jax.random.fold_in(key, 0), (), 0, n)
    cents = jnp.zeros((n_clusters, coords.shape[1]), jnp.float32)
    cents = cents.at[0].set(coords[first].astype(jnp.float32))

    def min_d2_to(c):
        # (N,) squared distance to a single centroid — no (N, C) temp
        diff = coords.astype(jnp.float32) - c[None, :]
        return jnp.sum(diff * diff, axis=1)

    def body(i, carry):
        cents, min_d2 = carry
        # degenerate data (all residual mass zero) degrades to uniform draws
        logits = jnp.log(jnp.maximum(min_d2, 1e-30))
        idx = jax.random.categorical(jax.random.fold_in(key, i), logits)
        c = coords[idx].astype(jnp.float32)
        cents = cents.at[i].set(c)
        return cents, jnp.minimum(min_d2, min_d2_to(c))

    cents, _ = jax.lax.fori_loop(
        1, n_clusters, body, (cents, min_d2_to(cents[0]))
    )
    return cents


@functools.partial(
    jax.jit, static_argnames=("n_clusters", "n_iters", "chunk")
)
def kmeans_fit(
    coords: Array,
    n_clusters: int,
    *,
    key: Array,
    n_iters: int = 15,
    chunk: int = 16384,
) -> Tuple[Array, Array]:
    """Fit ``n_clusters`` centroids to (N, k) coordinates with Lloyd's method.

    Returns ``(centroids (C, k) f32, inertia ())`` where inertia is the mean
    squared distance of every point to its nearest centroid at the final
    assignment pass — a fixed point of the iteration leaves it unchanged.
    Requires ``n_clusters <= N``.
    """
    n, kdim = coords.shape
    assert 0 < n_clusters <= n, (n_clusters, n)
    coords32 = coords.astype(jnp.float32)
    cents = _seed_plus_plus(coords32, n_clusters, key)

    def step(cents, _):
        assign, d2own = _assign_pass(coords32, cents, chunk)
        counts = jax.ops.segment_sum(
            jnp.ones((n,), jnp.float32), assign, n_clusters
        )
        sums = jax.ops.segment_sum(coords32, assign, n_clusters)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # empty-cluster reseeding: hand the i-th empty cluster the i-th
        # farthest point from its current centroid (all static shapes)
        empty = counts == 0.0
        far_d2, far_ids = jax.lax.top_k(d2own, min(n_clusters, n))
        rank = jnp.clip(jnp.cumsum(empty) - 1, 0, far_ids.shape[0] - 1)
        reseed = coords32[far_ids[rank]]
        new = jnp.where(empty[:, None], reseed, new)
        return new, jnp.sum(d2own) / n

    cents, inertias = jax.lax.scan(step, cents, None, length=n_iters)
    return cents, inertias[-1]


@functools.partial(jax.jit, static_argnames=("chunk",))
def kmeans_assign(
    coords: Array, centroids: Array, *, chunk: int = 16384
) -> Array:
    """Nearest-centroid assignment (N,) int32 — the IVF out-of-sample step."""
    assign, _ = _assign_pass(
        coords.astype(jnp.float32), centroids.astype(jnp.float32), chunk
    )
    return assign
