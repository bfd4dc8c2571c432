"""nSimplex base-simplex construction and apex projection.

Two implementations live here:

1. **TPU-native (the framework path)** — the paper's inductive algorithms
   re-expressed as dense linear algebra (DESIGN.md §2):

   * base simplex  = Cholesky factor of the reference Gram matrix,
   * apex addition = batched lower-triangular solve + altitude.

   Both are jit-friendly, batched, and MXU-shaped.

2. **Paper-faithful oracle** (``nsimplex_build_reference`` /
   ``apex_addition_reference``) — Algorithms 1 and 2 of the paper, verbatim
   sequential numpy. Used as the correctness oracle in tests and as the
   paper-faithful baseline in benchmarks.

Conventions match the paper: the base simplex of ``k`` references lives in
R^(k-1) as a lower-triangular matrix ``Sigma`` of shape (k, k-1) whose first row
is the origin; an apex has ``k`` coordinates, the last one being its altitude
(non-negative) above the base hyperplane.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp

Array = jax.Array


class BaseSimplex(NamedTuple):
    """Base simplex over k reference objects.

    Attributes:
      chol:   (k-1, k-1) lower-triangular Cholesky factor L; row i are the
              coordinates of vertex i+1 (vertex 0 is the origin).
      diag_g: (k-1,) squared norms of vertices 1..k-1  (= diag of the Gram
              matrix), cached for the apex solve.
      d0:     (k,) distances from reference 0 to every reference (d0[0] = 0).
    """

    chol: Array
    diag_g: Array
    d0: Array

    @property
    def k(self) -> int:
        return self.chol.shape[0] + 1

    def vertices(self) -> Array:
        """(k, k-1) vertex coordinate matrix (paper's lower-triangular Sigma)."""
        return jnp.concatenate(
            [jnp.zeros((1, self.chol.shape[0]), self.chol.dtype), self.chol], axis=0
        )


def gram_from_distances(D: Array) -> Array:
    """Gram matrix of vertices 1..k-1 with vertex 0 at the origin.

    G_ij = <v_i, v_j> = (d(r0,ri)^2 + d(r0,rj)^2 - d(ri,rj)^2) / 2.
    """
    d0 = D[0, 1:]
    D2 = D[1:, 1:] ** 2
    return 0.5 * (d0[:, None] ** 2 + d0[None, :] ** 2 - D2)


def build_base_simplex(D: Array, *, jitter: float = 0.0) -> BaseSimplex:
    """Construct the base simplex from the (k, k) reference distance matrix.

    The Cholesky factor of the Gram matrix *is* the paper's inductively built
    vertex matrix (rows 1..k-1); equality is asserted against the faithful
    oracle in tests. ``jitter`` (relative to mean diagonal) regularises nearly
    degenerate reference sets.
    """
    D = jnp.asarray(D)
    acc = jnp.promote_types(D.dtype, jnp.float32)
    D = D.astype(acc)
    G = gram_from_distances(D)
    if jitter:
        G = G + jitter * jnp.mean(jnp.diag(G)) * jnp.eye(G.shape[0], dtype=acc)
    L = jnp.linalg.cholesky(G)
    return BaseSimplex(chol=L, diag_g=jnp.diag(G), d0=D[0, :])


def simplex_is_degenerate(base: BaseSimplex, *, rtol: float = 1e-5) -> Array:
    """True if the reference set spans fewer than k-1 dimensions (paper §7.2).

    Detected from the Cholesky diagonal: a (near-)zero altitude at row i means
    reference i lies (almost) in the span of references 0..i-1.
    """
    d = jnp.diag(base.chol)
    scale = jnp.sqrt(jnp.maximum(jnp.max(base.diag_g), 1e-30))
    return jnp.logical_or(jnp.any(~jnp.isfinite(d)), jnp.any(d < rtol * scale))


def apex_project(base: BaseSimplex, dists: Array) -> Array:
    """Project a batch of objects into R^k from their reference distances.

    Args:
      base:  the fitted base simplex over k references.
      dists: (N, k) distances d(u_n, r_i) in the original space.

    Returns:
      (N, k) apex coordinates; the last column is the altitude (>= 0).

    The solve is the batched TPU-native equivalent of the paper's per-object
    ApexAddition loop:  L x = b with
      b_i = (d(u,r0)^2 + ||v_i||^2 - d(u,ri)^2) / 2 ,
    then altitude = sqrt(max(d(u,r0)^2 - ||x||^2, 0)).
    """
    acc = jnp.promote_types(dists.dtype, jnp.float32)
    dists = jnp.asarray(dists).astype(acc)
    if dists.ndim == 1:
        dists = dists[None, :]
    delta0_sq = dists[:, 0] ** 2  # (N,)
    b = 0.5 * (delta0_sq[:, None] + base.diag_g[None, :] - dists[:, 1:] ** 2)
    # (k-1, N) triangular solve: one MXU-friendly op for the whole batch.
    x = jax.scipy.linalg.solve_triangular(
        base.chol.astype(acc), b.T, lower=True
    ).T  # (N, k-1)
    alt_sq = delta0_sq - jnp.sum(x * x, axis=-1)
    altitude = jnp.sqrt(jnp.maximum(alt_sq, 0.0))
    return jnp.concatenate([x, altitude[:, None]], axis=-1)


# ---------------------------------------------------------------------------
# Paper-faithful oracles (Algorithms 1 and 2, sequential; numpy float64)
# ---------------------------------------------------------------------------


def nsimplex_build_reference(D: np.ndarray) -> np.ndarray:
    """Algorithm 1 (nSimplexBuild), verbatim inductive construction.

    Args:
      D: (n+1, n+1) distance matrix among the reference points.

    Returns:
      Sigma: (n+1, n) lower-triangular vertex coordinate matrix.
    """
    D = np.asarray(D, dtype=np.float64)
    n_plus_1 = D.shape[0]
    n = n_plus_1 - 1
    if n == 1:
        return np.array([[0.0], [D[0, 1]]])
    sigma_base = nsimplex_build_reference(D[:n, :n])  # (n, n-1)
    distances = D[:n, n]
    apex = apex_addition_reference(sigma_base, distances)  # (n,)
    sigma = np.zeros((n_plus_1, n))
    sigma[:n, : n - 1] = sigma_base
    sigma[n, :] = apex
    return sigma


def apex_addition_reference(sigma_base: np.ndarray, distances: np.ndarray) -> np.ndarray:
    """Algorithm 2 (ApexAddition), verbatim sequential loop.

    Args:
      sigma_base: (n, n-1) base simplex vertex matrix.
      distances:  (n,) distances from the unknown apex to each base vertex.

    Returns:
      (n,) apex coordinates; last component is the (non-negative) altitude.
    """
    sigma_base = np.asarray(sigma_base, dtype=np.float64)
    distances = np.asarray(distances, dtype=np.float64)
    n = sigma_base.shape[0]
    out = np.zeros(n)
    out[0] = distances[0]
    for i in range(1, n):  # paper's i = 2..n (1-indexed)
        base_row = np.zeros(n)
        base_row[: n - 1] = sigma_base[i]
        l = np.linalg.norm(base_row - out)
        delta = distances[i]
        x = sigma_base[i, i - 1]
        y = out[i - 1]
        out[i - 1] = y - (delta**2 - l**2) / (2.0 * x)
        out[i] = np.sqrt(max(y**2 - out[i - 1] ** 2, 0.0))
    return out


def apex_project_reference(D_refs: np.ndarray, dists: np.ndarray) -> np.ndarray:
    """Project a batch with the paper-faithful per-object loop (oracle)."""
    D_refs = np.asarray(D_refs, dtype=np.float64)
    k = D_refs.shape[0]
    sigma = nsimplex_build_reference(D_refs)  # (k, k-1)
    dists = np.atleast_2d(np.asarray(dists, dtype=np.float64))
    out = np.zeros((dists.shape[0], k))
    for idx in range(dists.shape[0]):
        out[idx] = apex_addition_reference(sigma, dists[idx])
    return out


def verify_base_simplex(D: Array, base: BaseSimplex, *, atol: float = 1e-4) -> Tuple[bool, float]:
    """Check that pairwise vertex distances reproduce the reference distances."""
    V = base.vertices()
    d2 = (
        jnp.sum(V**2, -1)[:, None]
        + jnp.sum(V**2, -1)[None, :]
        - 2 * jnp.matmul(V, V.T, precision=jax.lax.Precision.HIGHEST)
    )
    # self-distances are definitionally zero; the matrix-op form leaves
    # O(eps*||v||^2) roundoff there which sqrt would inflate to O(sqrt(eps))
    d2 = d2 * (1.0 - jnp.eye(d2.shape[0], dtype=d2.dtype))
    got = jnp.sqrt(jnp.maximum(d2, 0.0))
    err = float(jnp.max(jnp.abs(got - jnp.asarray(D, got.dtype))))
    return err <= atol, err
