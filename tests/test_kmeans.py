"""Batched Lloyd's k-means (the IVF coarse quantizer): recovery on separated
blobs, empty-cluster reseeding, fixed-point behaviour on degenerate data, and
chunked-assignment invariance. All CPU."""
import numpy as np

import jax
import jax.numpy as jnp

from repro.index.kmeans import kmeans_assign, kmeans_fit


def _blobs(seed, n_per, n_blobs, dim, scale=20.0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_blobs, dim)) * scale
    X = np.concatenate([c + rng.normal(size=(n_per, dim)) for c in centers])
    return jnp.asarray(X, jnp.float32)


def test_recovers_separated_blobs():
    X = _blobs(0, 200, 8, 6)
    # k-means++ seeding may still put two seeds in one blob, and Lloyd's
    # then settles in a local minimum for that draw; the quantizer's
    # contract is recovery by the best of a few restarts (lowest inertia),
    # which holds whatever any single draw of the installed PRNG does
    fits = [kmeans_fit(X, 8, key=jax.random.PRNGKey(s), n_iters=20)
            for s in range(4)]
    cents, inertia = min(fits, key=lambda f: float(f[1]))
    assign = np.asarray(kmeans_assign(X, cents))
    counts = np.bincount(assign, minlength=8)
    # every blob found: all clusters populated with exactly one blob each
    assert (counts == 200).all(), counts
    # within-blob variance only: mean squared distance ~ dim
    assert float(inertia) < 3 * 6, float(inertia)


def test_empty_cluster_reseeding_uses_all_clusters():
    # two tight far-apart blobs but 8 clusters: naive Lloyd's would park most
    # centroids empty next to one blob; reseeding must keep all 8 in use
    X = _blobs(1, 100, 2, 4, scale=100.0)
    cents, _ = kmeans_fit(X, 8, key=jax.random.PRNGKey(1), n_iters=15)
    assign = np.asarray(kmeans_assign(X, cents))
    assert jnp.isfinite(cents).all()
    counts = np.bincount(assign, minlength=8)
    assert (counts > 0).all(), counts


def test_degenerate_identical_points_fixed_point():
    # all points identical and fewer distinct values than clusters: the fit
    # must stay finite, reach inertia 0, and be a fixed point of iteration
    X = jnp.ones((50, 4), jnp.float32)
    c_short, i_short = kmeans_fit(X, 16, key=jax.random.PRNGKey(2), n_iters=2)
    c_long, i_long = kmeans_fit(X, 16, key=jax.random.PRNGKey(2), n_iters=12)
    assert jnp.isfinite(c_short).all() and jnp.isfinite(c_long).all()
    assert float(i_short) == 0.0 and float(i_long) == 0.0
    np.testing.assert_allclose(np.asarray(c_short), np.asarray(c_long))
    a = np.asarray(kmeans_assign(X, c_long))
    assert a.min() >= 0 and a.max() < 16


def test_n_clusters_equals_n_gives_distinct_cells():
    X = _blobs(3, 2, 8, 5)  # 16 points
    cents, inertia = kmeans_fit(X, 16, key=jax.random.PRNGKey(3), n_iters=10)
    assign = np.asarray(kmeans_assign(X, cents))
    assert len(set(assign.tolist())) == 16
    assert float(inertia) < 1e-3  # f32 roundoff only: every point is its own cell


def test_assignment_chunking_invariance():
    X = _blobs(4, 37, 5, 7)  # 185 rows, deliberately ragged vs chunk
    cents, _ = kmeans_fit(X, 5, key=jax.random.PRNGKey(4), n_iters=10)
    a_full = np.asarray(kmeans_assign(X, cents, chunk=10_000))
    a_small = np.asarray(kmeans_assign(X, cents, chunk=13))
    assert (a_full == a_small).all()


def test_fit_deterministic_in_key():
    X = _blobs(5, 50, 4, 6)
    c1, _ = kmeans_fit(X, 4, key=jax.random.PRNGKey(9), n_iters=8)
    c2, _ = kmeans_fit(X, 4, key=jax.random.PRNGKey(9), n_iters=8)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2))
