"""Per-cluster-residual product quantizer — the "pq" storage mode.

The paper's compression claim (apex coordinates carry little information per
axis at low target dimension) caps out at 4x under scalar int8; product
quantisation is the next rung. Each IVF member stores, instead of its k
float32 apex coordinates, M uint8 codes: the member's *residual* against its
coarse centroid is split into M contiguous subspaces of ``ds = ceil(k / M)``
dims and each sub-vector is snapped to the nearest entry of a 256-entry
per-subspace codebook, trained by the same ``index.kmeans`` Lloyd's loop as
the coarse quantizer. 4 bytes instead of 64 at (k=16, M=4) — 16x — with the
codebooks (M, 256, ds) f32 a fixed few-KB overhead.

Residuals are taken against the *globally assigned* centroid (same invariant
as ``quantize.cluster_scales``): the stored codes depend only on the global
k-means assignment, never on tile packing or shard count, which is what
keeps PQ snapshots bit-identical across device counts.

Scoring is asymmetric-distance computation (ADC, Jégou et al.): queries stay
f32, and for every (query, probed cluster) pair a ``(M, 256)`` lookup table
of per-subspace squared distances

  lut[m, j] = || (q - c)_m  -  codebook[m, j] ||^2

is built once at query time (:func:`build_luts`), so that the Zen squared
distance to a member decoding to ``x_hat = c + decode(code)`` is an M-term
table gather:

  z2(q, x_hat) = sum_m lut[m, code[m]]

The Lwb/Upb altitude cross-term ``-+ 2 q_alt x_hat_alt`` is *folded into the
table* of the subspace holding the altitude column (``x_hat_alt`` is affine
in the codeword), so the probe kernels are estimator-mode-agnostic: one
LUT-gather body (``kernels.scoring.lut_estimate_tile`` / ``_rows``) serves
all three modes, and a PQ probe is bit-for-bit the plain estimator evaluated
on the decoded coordinates.

Width padding: when ``M`` does not divide k the subspace view is zero-padded
to ``M * ds`` columns. Padded residual columns are exactly zero, Lloyd
centroids over them stay exactly zero (means and reseeds of zeros), so the
padding contributes exactly 0.0 to every table entry — no epsilon drift
between the padded and unpadded formulations.

Everything but :func:`build_luts` is host-side numpy on the control plane
(build / upsert / compact / snapshot load); ``build_luts`` is jit-traceable
and runs on the query path.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

Array = jax.Array

#: codebook entries per subspace — one uint8 code addresses exactly this
PQ_ENTRIES = 256

#: target subspace width used by :func:`default_m` (4 dims per code byte)
_TARGET_DS = 4


def default_m(kdim: int) -> int:
    """The default subspace count for k-dim coordinates: ~4 dims per code.

    ``max(1, kdim // 4)`` — e.g. k=16 -> M=4 (16x vs f32), k=8 -> M=2.
    """
    return max(1, kdim // _TARGET_DS)


def subspace_dims(kdim: int, m: int) -> int:
    """ds = ceil(k / M), the per-subspace width (columns padded to M*ds)."""
    if not 1 <= m <= kdim:
        raise ValueError(f"pq_m must be in [1, k={kdim}], got {m}")
    return -(-kdim // m)


def split_subspaces(x: np.ndarray, m: int) -> np.ndarray:
    """(n, k) f32 -> (n, M, ds) f32 subspace view, zero-padded to M*ds."""
    x = np.asarray(x, np.float32)
    n, kdim = x.shape
    ds = subspace_dims(kdim, m)
    pad = m * ds - kdim
    if pad:
        x = np.concatenate([x, np.zeros((n, pad), np.float32)], axis=1)
    return x.reshape(n, m, ds)


def train_codebooks(
    residuals: np.ndarray,
    m: int,
    *,
    key: Optional[Array] = None,
    n_iters: int = 15,
) -> np.ndarray:
    """Fit (M, 256, ds) f32 codebooks on (n, k) residuals via Lloyd's loop.

    Each subspace trains independently with ``index.kmeans.kmeans_fit``
    (k-means++ D^2 seeding, empty-cluster reseeding) under a per-subspace
    fold of ``key`` — fully deterministic for a fixed key. When the corpus
    holds fewer than 256 rows the trailing codebook entries repeat entry 0:
    an exact-duplicate entry can never win an ``argmin`` tie (first
    occurrence wins), so codes stay dense in the trained range.
    """
    # deferred: index.kmeans sits above kernels in the import order and
    # importing it at module scope would cycle through repro.index.__init__
    from repro.index.kmeans import kmeans_fit

    key = key if key is not None else jax.random.PRNGKey(0)
    sub = split_subspaces(residuals, m)  # (n, M, ds)
    n, _, ds = sub.shape
    if n == 0:
        return np.zeros((m, PQ_ENTRIES, ds), np.float32)
    entries = min(PQ_ENTRIES, n)
    books = np.zeros((m, PQ_ENTRIES, ds), np.float32)
    for i in range(m):
        cents, _ = kmeans_fit(
            jnp.asarray(sub[:, i, :]), entries,
            key=jax.random.fold_in(key, i), n_iters=n_iters)
        books[i, :entries] = np.asarray(cents, np.float32)
        if entries < PQ_ENTRIES:
            books[i, entries:] = books[i, 0]
    return books


def encode(residuals: np.ndarray, codebooks: np.ndarray) -> np.ndarray:
    """(n, k) f32 residuals -> (n, M) uint8 nearest-entry codes."""
    from repro.index.kmeans import kmeans_assign

    m, entries, _ = codebooks.shape
    assert entries == PQ_ENTRIES, codebooks.shape
    sub = split_subspaces(residuals, m)  # (n, M, ds)
    n = sub.shape[0]
    codes = np.zeros((n, m), np.uint8)
    if n == 0:
        return codes
    for i in range(m):
        a = kmeans_assign(jnp.asarray(sub[:, i, :]),
                          jnp.asarray(codebooks[i]))
        codes[:, i] = np.asarray(a, np.int64).astype(np.uint8)
    return codes


def decode(codes: np.ndarray, codebooks: np.ndarray, kdim: int) -> np.ndarray:
    """(n, M) uint8 codes -> (n, k) f32 reconstructed residuals."""
    codes = np.asarray(codes)
    m, _, ds = codebooks.shape
    assert codes.ndim == 2 and codes.shape[1] == m, codes.shape
    gathered = np.asarray(codebooks, np.float32)[
        np.arange(m)[None, :], codes.astype(np.int64)]  # (n, M, ds)
    return gathered.reshape(codes.shape[0], m * ds)[:, :kdim]


def code_bytes(n: int, m: int) -> int:
    """Resident bytes of n members' codes (the compression numerator)."""
    return n * m


def build_luts(
    queries: Array,
    centroids: Array,
    codebooks: Array,
    probes: Array,
    mode: int,
) -> Array:
    """Per-(query, probed cluster) ADC tables — (Q, P, M, 256) f32.

    Args:
      queries:   (Q, k) f32 apex query coordinates.
      centroids: (C, k) f32 coarse centroids (the residual anchors).
      codebooks: (M, 256, ds) f32 subspace codebooks.
      probes:    (Q, P) int32 probed cluster ids.
      mode:      static estimator id (``scoring.MODE_IDS``); for lwb/upb the
                 altitude cross-term is folded into the table of the
                 subspace owning the altitude column, making the downstream
                 gather mode-agnostic.

    ``sum_m lut[q, p, m, code[m]]`` equals the mode's squared estimator
    distance between query q and a member of cluster ``probes[q, p]``
    decoding to ``centroid + decode(code)``. Tables stay resident (VMEM on
    TPU) while the uint8 code tiles stream through the probe kernel.
    """
    q_n, kdim = queries.shape
    m, entries, ds = codebooks.shape
    kp = m * ds
    qp = jnp.pad(queries.astype(jnp.float32), ((0, 0), (0, kp - kdim)))
    cp = jnp.pad(centroids.astype(jnp.float32), ((0, 0), (0, kp - kdim)))
    cb = codebooks.astype(jnp.float32)
    r = qp[:, None, :] - cp[probes]                  # (Q, P, kp) residual
    r = r.reshape(q_n, probes.shape[1], m, ds)       # (Q, P, M, ds)
    rn = jnp.sum(r * r, axis=-1)                     # (Q, P, M)
    cn = jnp.sum(cb * cb, axis=-1)                   # (M, E)
    dot = jnp.einsum("qpmd,med->qpme", r, cb,
                     preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)
    lut = rn[..., None] + cn[None, None] - 2.0 * dot  # (Q, P, M, E)
    # the base table is the plain squared Euclidean ||q - x_hat||^2, which
    # IS the Lwb estimator (paper §4.1: lwb^2 = sum_i<alt (q_i - x_i)^2 +
    # (q_alt - x_alt)^2); Zen replaces the altitude term by q_alt^2 +
    # x_alt^2 (+ 2 q_alt x_alt on top of lwb^2) and Upb by (q_alt +
    # x_alt)^2 (+ 4 q_alt x_alt). Fold the correction into the table of
    # the subspace owning the altitude column: x_alt = centroid_alt +
    # codebook[ma, j, da] is affine in the codeword.
    if mode != 1:
        ma, da = (kdim - 1) // ds, (kdim - 1) % ds
        qa = queries[:, -1].astype(jnp.float32)      # (Q,)
        ca = centroids[:, -1].astype(jnp.float32)[probes]  # (Q, P)
        cba = cb[ma, :, da]                          # (E,)
        cross = qa[:, None, None] * (ca[..., None] + cba[None, None])
        mult = 2.0 if mode == 0 else 4.0
        lut = lut.at[:, :, ma, :].add(mult * cross)
    return lut
