"""Zen / Lwb / Upb estimators over nSimplex-projected coordinates (paper §4.1).

For projected points x, y in R^k (last coordinate = altitude):

  base_dist(x,y) = sum_{i<k} (x_i - y_i)^2
  Lwb(x,y) = sqrt(base_dist + (x_k - y_k)^2)      # = l2, lower bound of d
  Upb(x,y) = sqrt(base_dist + (x_k + y_k)^2)      # upper bound of d
  Zen(x,y) = sqrt(base_dist + x_k^2 + y_k^2)      # zenith estimator

All three share one matmul:  with full squared norms nx = ||x||^2 (altitude
included) and the dot product restricted to the first k-1 coordinates
p = x[:k-1] . y[:k-1]:

  Zen^2 = nx + ny - 2 p
  Lwb^2 = Zen^2 - 2 x_k y_k
  Upb^2 = Zen^2 + 2 x_k y_k

so the pairwise estimator matrix is one (masked-last-column) matmul plus a
rank-1 correction — the shape the Pallas ``zen`` kernel implements on TPU.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops

Array = jax.Array

MODES = ("zen", "lwb", "upb")


def _acc(x: Array) -> jnp.dtype:
    return jnp.promote_types(x.dtype, jnp.float32)


def estimate_pdist(X: Array, Y: Array, mode: str = "zen") -> Array:
    """Pairwise estimator matrix (N, M) between projected sets X (N,k), Y (M,k)."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    acc = _acc(X)
    Xa, Ya = X.astype(acc), Y.astype(acc)
    nx = jnp.sum(Xa * Xa, axis=-1)
    ny = jnp.sum(Ya * Ya, axis=-1)
    p = jnp.matmul(Xa[:, :-1], Ya[:, :-1].T, preferred_element_type=acc,
                   precision=jax.lax.Precision.HIGHEST)
    z2 = nx[:, None] + ny[None, :] - 2.0 * p
    if mode != "zen":
        cross = jnp.outer(Xa[:, -1], Ya[:, -1])
        z2 = z2 - 2.0 * cross if mode == "lwb" else z2 + 2.0 * cross
    return jnp.sqrt(jnp.maximum(z2, 0.0))


def zen_pdist(X: Array, Y: Array) -> Array:
    return estimate_pdist(X, Y, "zen")


def lwb_pdist(X: Array, Y: Array) -> Array:
    return estimate_pdist(X, Y, "lwb")


def upb_pdist(X: Array, Y: Array) -> Array:
    return estimate_pdist(X, Y, "upb")


def estimate_triple(X: Array, Y: Array) -> Tuple[Array, Array, Array]:
    """(lwb, zen, upb) evaluated as a triple sharing one matmul (paper §4.1)."""
    acc = _acc(X)
    Xa, Ya = X.astype(acc), Y.astype(acc)
    nx = jnp.sum(Xa * Xa, axis=-1)
    ny = jnp.sum(Ya * Ya, axis=-1)
    p = jnp.matmul(Xa[:, :-1], Ya[:, :-1].T, preferred_element_type=acc,
                   precision=jax.lax.Precision.HIGHEST)
    z2 = nx[:, None] + ny[None, :] - 2.0 * p
    cross = 2.0 * jnp.outer(Xa[:, -1], Ya[:, -1])
    sq = lambda a: jnp.sqrt(jnp.maximum(a, 0.0))
    return sq(z2 - cross), sq(z2), sq(z2 + cross)


@partial(jax.jit, static_argnames=("n_neighbors", "mode"))
def _dense_topk(
    queries: Array, index: Array, n_neighbors: int, mode: str
) -> Tuple[Array, Array]:
    """Reference dense path: full (Q, N) estimator matrix + lax.top_k."""
    d = estimate_pdist(queries, index, mode)
    neg, ids = jax.lax.top_k(-d, n_neighbors)
    return -neg, ids


def knn_search(
    queries: Array,
    index: Array,
    n_neighbors: int = 10,
    mode: str = "zen",
    chunk: int = 0,
    *,
    scales: Array = None,
    stream: bool = None,
    force_kernel: bool = False,
) -> Tuple[Array, Array]:
    """Top-k nearest neighbours of ``queries`` in ``index`` under an estimator.

    Args:
      queries: (Q, k) projected queries.
      index:   (N, k) projected index, stored f32, bf16 or int8
               (``kernels.quantize``).
      chunk:   if > 0, stream the index in blocks of this many rows (bounded
               memory: keeps a running top-k instead of the full (Q, N) matrix).
      scales:  (N, 1) f32 per-row symmetric scales when ``index`` is int8;
               the streaming paths fuse the dequant into the estimator, the
               dense path reconstructs the f32 index once.
      stream:  force the streaming path on (True) or off (False); by default
               it is chosen automatically — always on TPU (fused Pallas
               kernel), and on other backends whenever ``chunk`` is set and
               the index is larger than one chunk.
      force_kernel: run the Pallas kernel in interpret mode off-TPU
               (tests / parity checks).

    Returns:
      (distances, indices), each (Q, n_neighbors), ascending distance.

    The streaming path dispatches through ``kernels.ops.zen_topk``: the fused
    Pallas kernel on TPU, a lax.scan with identical merge semantics elsewhere.
    Peak per-query memory is one index tile — flat in N — versus the dense
    path's O(N).
    """
    n_neighbors = min(n_neighbors, index.shape[0])
    use_stream = stream
    if use_stream is None:  # auto: always stream on TPU, else when chunked
        use_stream = (
            bool(chunk) and index.shape[0] > chunk
        ) or jax.default_backend() == "tpu"
    if use_stream or force_kernel:
        return kernel_ops.zen_topk(
            queries,
            index,
            n_neighbors,
            mode,
            scales=scales,
            force_kernel=force_kernel,
            chunk=chunk or 4096,
        )
    if scales is not None:  # dense reference path: dequantise once
        index = index.astype(jnp.float32) * scales.astype(jnp.float32)
    return _dense_topk(queries, index, n_neighbors, mode)
