"""Distance metrics over Hilbert-embeddable spaces (paper Appendix A).

Every metric is exposed in two forms:
  * ``<name>_pdist(X, Y) -> (N, M)`` pairwise distance matrix, jit/vmap friendly,
  * via the registry ``get_metric(name)`` returning a ``Metric`` record with the
    pairwise function, pre-normalisation and Hilbert-embeddability flag.

All pairwise computations accumulate in float32 (or float64 if enabled) even for
bf16 inputs; matmul-shaped paths use ``preferred_element_type`` and
``Precision.HIGHEST`` — a TPU's default single bf16 pass would swamp the
small differences of large norms that every expanded distance subtracts.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

Array = jax.Array

_EPS = 1e-12


def _acc_dtype(x: Array) -> jnp.dtype:
    return jnp.promote_types(x.dtype, jnp.float32)


def sqeuclidean_pdist(X: Array, Y: Array) -> Array:
    """Pairwise squared Euclidean distances, matmul-shaped for the MXU."""
    acc = _acc_dtype(X)
    x2 = jnp.sum(X.astype(acc) ** 2, axis=-1)
    y2 = jnp.sum(Y.astype(acc) ** 2, axis=-1)
    xy = jnp.matmul(X, Y.T, preferred_element_type=acc,
                    precision=jax.lax.Precision.HIGHEST)
    d2 = x2[:, None] + y2[None, :] - 2.0 * xy
    if Y is X:
        # self-distances are definitionally zero; the matmul form leaves
        # O(eps*||x||^2) roundoff there, which sqrt inflates to O(sqrt(eps))
        d2 = d2 * (1.0 - jnp.eye(d2.shape[0], dtype=d2.dtype))
    return jnp.maximum(d2, 0.0)


def euclidean_pdist(X: Array, Y: Array) -> Array:
    return jnp.sqrt(sqeuclidean_pdist(X, Y))


def l2_normalize(X: Array, eps: float = _EPS) -> Array:
    n = jnp.linalg.norm(X, axis=-1, keepdims=True)
    return X / jnp.maximum(n, eps)


def l1_normalize(X: Array, eps: float = _EPS) -> Array:
    """Project onto the probability simplex (for JSD / triangular)."""
    Xp = jnp.maximum(X, 0.0)
    s = jnp.sum(Xp, axis=-1, keepdims=True)
    return Xp / jnp.maximum(s, eps)


def cosine_pdist(X: Array, Y: Array) -> Array:
    """Paper Eq. (11): Euclidean distance over L2-normalised vectors."""
    Xn = l2_normalize(X)
    Yn = Xn if Y is X else l2_normalize(Y)
    return euclidean_pdist(Xn, Yn)


def _h(x: Array) -> Array:
    """h(x) = -x log2(x), with 0 log 0 := 0 (paper Eq. 14)."""
    safe = jnp.where(x > 0, x, 1.0)
    return jnp.where(x > 0, -x * jnp.log2(safe), 0.0)


def jsd_pdist(X: Array, Y: Array, *, assume_normalized: bool = False) -> Array:
    """Jensen-Shannon distance (paper Eqs. 12-14). Inputs are l1-normalised
    probability vectors; set ``assume_normalized=False`` to normalise here.

    K(v, w) = 1 - 0.5 * sum_i [h(v_i) + h(w_i) - h(v_i + w_i)];  D = sqrt(K).
    The cross term sum_i h(v_i + w_i) is the O(N*M*m) hot loop (see kernels/jsd).
    """
    if not assume_normalized:
        X, Y = l1_normalize(X), l1_normalize(Y)
    acc = _acc_dtype(X)
    X = X.astype(acc)
    Y = Y.astype(acc)
    hx = jnp.sum(_h(X), axis=-1)  # (N,)
    hy = jnp.sum(_h(Y), axis=-1)  # (M,)
    # cross[i, j] = sum_k h(x_ik + y_jk); O(N*M*m) elementwise.
    cross = jnp.sum(_h(X[:, None, :] + Y[None, :, :]), axis=-1)
    K = 1.0 - 0.5 * (hx[:, None] + hy[None, :] - cross)
    return jnp.sqrt(jnp.maximum(K, 0.0))


def triangular_pdist(X: Array, Y: Array, *, assume_normalized: bool = False) -> Array:
    """Triangular distance (paper Eq. 15), cheap JSD estimator; 0/0 := 0."""
    if not assume_normalized:
        X, Y = l1_normalize(X), l1_normalize(Y)
    acc = _acc_dtype(X)
    num = (X[:, None, :].astype(acc) - Y[None, :, :].astype(acc)) ** 2
    den = X[:, None, :].astype(acc) + Y[None, :, :].astype(acc)
    frac = jnp.where(den > 0, num / jnp.maximum(den, _EPS), 0.0)
    return jnp.sqrt(0.5 * jnp.sum(frac, axis=-1))


def qform_pdist(X: Array, Y: Array, M: Array) -> Array:
    """Quadratic-form distance (paper Eq. 16) with PSD matrix ``M``.

    D(v,w)^2 = v'Mv + w'Mw - 2 v'Mw : three matmuls, no N*M*m intermediate.
    """
    acc = _acc_dtype(X)
    XM = jnp.matmul(X, M, preferred_element_type=acc,
                    precision=jax.lax.Precision.HIGHEST)
    YM = XM if Y is X else jnp.matmul(
        Y, M, preferred_element_type=acc, precision=jax.lax.Precision.HIGHEST)
    xmx = jnp.sum(XM * X, axis=-1)
    ymy = xmx if Y is X else jnp.sum(YM * Y, axis=-1)
    xmy = jnp.matmul(XM, Y.T, preferred_element_type=acc,
                     precision=jax.lax.Precision.HIGHEST)
    d2 = xmx[:, None] + ymy[None, :] - 2.0 * xmy
    if Y is X:  # exact-zero self distances (cf. sqeuclidean_pdist)
        d2 = d2 * (1.0 - jnp.eye(d2.shape[0], dtype=d2.dtype))
    return jnp.sqrt(jnp.maximum(d2, 0.0))


@dataclasses.dataclass(frozen=True)
class _DefaultQformMatrix:
    """Deterministic PSD matrix for the registry ``qform`` metric.

    The registry needs a parameter-free pairwise function, so the form
    matrix is fixed per input dimension: the Kac-Murdock-Szego correlation
    matrix ``M[i, j] = rho^|i - j|`` — strictly positive definite for
    ``|rho| < 1``, so the distance is a true Hilbert-embeddable metric
    (it is the Euclidean distance of the ``chol(M)``-transformed vectors).
    Neighbouring axes correlate, which is the textbook quadratic-form use
    case (e.g. colour-histogram bins). Callers with a domain matrix should
    use :func:`qform_pdist` directly.
    """

    rho: float = 0.5

    def __call__(self, m: int) -> Array:
        idx = jnp.arange(m)
        return self.rho ** jnp.abs(idx[:, None] - idx[None, :])


default_qform_matrix = _DefaultQformMatrix()


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    pdist: Callable[[Array, Array], Array]
    normalize: Optional[Callable[[Array], Array]]
    hilbert_embeddable: bool
    has_coordinates: bool  # False => only distance-based DR (nSimplex / LMDS) applies


def _make_registry() -> dict:
    return {
        "euclidean": Metric("euclidean", euclidean_pdist, None, True, True),
        "sqeuclidean": Metric("sqeuclidean", sqeuclidean_pdist, None, False, True),
        "cosine": Metric(
            "cosine",
            lambda X, Y: euclidean_pdist(X, Y),  # callers pre-normalise
            l2_normalize,
            True,
            True,
        ),
        "jsd": Metric(
            "jsd",
            lambda X, Y: jsd_pdist(X, Y, assume_normalized=True),
            l1_normalize,
            True,
            False,
        ),
        "triangular": Metric(
            "triangular",
            lambda X, Y: triangular_pdist(X, Y, assume_normalized=True),
            l1_normalize,
            True,
            False,
        ),
        "qform": Metric(
            "qform",
            lambda X, Y: qform_pdist(
                X, Y, default_qform_matrix(X.shape[-1])),
            None,
            True,
            True,
        ),
    }


_REGISTRY = _make_registry()


def get_metric(name: str) -> Metric:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown metric {name!r}; available: {sorted(_REGISTRY)}"
        ) from None


def pairwise(name: str, X: Array, Y: Array) -> Array:
    """Normalise (if the metric requires it) and compute the pairwise matrix."""
    m = get_metric(name)
    if m.normalize is not None:
        Xn = m.normalize(X)
        Y = Xn if Y is X else m.normalize(Y)  # keep the self-pdist identity
        X = Xn
    return m.pdist(X, Y)


def self_pairwise(name: str, X: Array) -> Array:
    return pairwise(name, X, X)
