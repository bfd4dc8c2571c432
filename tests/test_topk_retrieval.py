"""Streaming fused top-k retrieval: kernel/scan/dense parity (including
chunk-boundary and padded-tail shapes), estimator bound ordering, apex
projection parity with the paper oracle, sharded search, and the
bounded-memory guarantee. All paths run on CPU (interpret=True for Pallas)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import metrics as M
from repro.core import simplex as S
from repro.core import zen as Z
from repro.core.projection import NSimplexTransform
from repro.kernels import ops
from repro.kernels import zen_topk as zt


def _projected(seed, n, m, k):
    """Real apex coordinates: fit on random refs, project random objects."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, m))
    refs = rng.normal(size=(k, m))
    tr = NSimplexTransform(k=k).fit(jnp.asarray(refs, jnp.float32))
    return tr, jnp.asarray(tr.transform(jnp.asarray(X, jnp.float32)), jnp.float32)


def _rand_coords(seed, n, k):
    """Synthetic projected coords (non-negative altitude column)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, k)).astype(np.float32)
    X[:, -1] = np.abs(X[:, -1])
    return jnp.asarray(X)


# -- in-kernel merge == lax.top_k merge ----------------------------------------


def _merge_tile(tiles, rng, step, want_d, r, n_inf):
    """One (rows, r) f32 tile of distances for the merge cases; ``want_d``
    is the reference (rows, k) state the tile merges into."""
    rows, k = want_d.shape
    if tiles is True:    # heavy distance ties: lowest position wins
        d = rng.integers(0, 6, (rows, r)).astype(np.float32)
    elif tiles == "settled" and step:  # no candidate beats any k-th best
        d = 1.0 + rng.random((rows, r)).astype(np.float32)
    elif tiles == "one" and step:      # a single entrant in the block
        d = 1.0 + rng.random((rows, r)).astype(np.float32)
        d[2, 5] = np.float32(want_d[2, k - 1]) - 0.25
    elif tiles == "kth" and step:      # many ties at each row's k-th best
        d = want_d[:, rng.integers(0, k, r)]
        d[:, ::3] = want_d[:, k - 1:k]
    elif tiles == "inf":               # every candidate masked
        d = np.full((rows, r), np.inf, np.float32)
    elif tiles == "partial" and not step:  # the state fills part-way first
        d = np.full((rows, r), np.inf, np.float32)
        d[:, :k // 3] = rng.random((rows, k // 3))
    elif tiles == "mixed" and step:    # row m has m * k / 8 entrants
        d = 1.0 + rng.random((rows, r)).astype(np.float32)
        for m in range(rows):
            d[m, :m * k // 8] = rng.random(m * k // 8) * want_d[m, k - 1]
    else:                # uniform distances
        d = rng.random((rows, r)).astype(np.float32)
    d = d.astype(np.float32)
    if n_inf:
        d[:, r - n_inf:] = np.inf  # masked tail rows keep their ids
    return d


@pytest.mark.parametrize("k,r,tiles,n_inf", [
    (10, 256, False, 0),     # plain
    (16, 128, True, 0),      # heavy distance ties: lowest position wins
    (64, 128, True, 100),    # fewer finite candidates than k: (+inf, -1) fill
    (128, 256, False, 0),    # k == state width
    (64, 512, "settled", 0),  # a tile with no entrant: no round
    (64, 128, "one", 0),     # a tile with exactly one entrant: one round
    (16, 256, "kth", 0),     # ties exactly at the k-th distance stay out
    (10, 128, "inf", 0),     # all-+inf tiles into an empty state
    (64, 256, "partial", 0),  # a partly filled state takes every finite
    (64, 512, "mixed", 0),   # rows of one block with different counts
])
def test_merge_rounds_equals_top_k_merge(k, r, tiles, n_inf):
    from repro.kernels.scoring import merge_topk, merge_topk_rounds

    rng = np.random.default_rng(k + r)
    rows, w = 5, 128
    best_d, best_i = (jnp.full((rows, w), jnp.inf, jnp.float32),
                      jnp.full((rows, w), -1, jnp.int32))
    want_d, want_i = best_d[:, :k], best_i[:, :k]
    for step in range(3):
        d = _merge_tile(tiles, rng, step, np.asarray(want_d), r, n_inf)
        entrants = (d < np.asarray(want_d)[:, k - 1:k]).sum(axis=1)
        ids = (step * r + np.arange(r, dtype=np.int32))[None, :]
        best_d, best_i, rounds = merge_topk_rounds(
            best_d, best_i, jnp.asarray(d), jnp.asarray(ids), k)
        want_d, want_i = merge_topk(
            want_d, want_i, jnp.asarray(d), jnp.asarray(ids), k)
        np.testing.assert_array_equal(np.asarray(best_d[:, :k]), want_d)
        np.testing.assert_array_equal(np.asarray(best_i[:, :k]), want_i)
        assert bool(jnp.all(jnp.isinf(best_d[:, k:])))
        assert bool(jnp.all(best_i[:, k:] == -1))
        assert int(rounds) == min(k, entrants.max()), (step, entrants)
    if tiles == "settled":
        assert entrants.max() == 0
    if tiles == "one":
        assert entrants.max() == 1 and int(rounds) == 1
    if tiles == "mixed":
        assert len(set(entrants.tolist())) == rows and int(rounds) < k


def test_kernel_rounds_follow_entrants():
    """The flat kernel merges only entrants: a first tile holding every
    query's nearest rows takes min(k, tile) rounds and the far tiles after
    it none, with results equal to the scan fallback."""
    rng = np.random.default_rng(3)
    near = rng.normal(size=(128, 12)).astype(np.float32)
    far = 50.0 + rng.normal(size=(4 * 128 + 44, 12)).astype(np.float32)
    X = np.concatenate([near, far])
    X[:, -1] = np.abs(X[:, -1])
    Q = jnp.asarray(near[:9] + 0.01 * rng.normal(size=(9, 12)), jnp.float32)
    X = jnp.asarray(X)
    d, i, rounds = zt.zen_topk(Q, X, 10, "zen", block_q=8, block_n=128,
                               interpret=True, return_rounds=True)
    want_d, want_i = zt.zen_topk_scan(Q, X, 10, "zen", chunk=128)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(d), np.asarray(want_d),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(rounds), np.full(9, 10))
    assert rounds.dtype == jnp.int32


# -- kernel vs dense parity ----------------------------------------------------

SHAPES = [
    # (Q, N, k, n_neighbors, block_n): aligned, chunk-boundary, padded tail,
    # single-block, k=1 and k=N corner cases
    (8, 512, 16, 10, 128),    # N a multiple of the tile
    (5, 300, 17, 10, 128),    # padded tail (300 = 2*128 + 44)
    (3, 129, 8, 5, 128),      # one-row tail
    (9, 100, 12, 7, 128),     # N smaller than one tile
    (2, 257, 6, 1, 128),      # n_neighbors = 1
    (4, 96, 9, 96, 128),      # n_neighbors = N (full ranking)
]


@pytest.mark.parametrize("q,n,k,nn,bn", SHAPES)
@pytest.mark.parametrize("mode", ["zen", "lwb", "upb"])
def test_streaming_kernel_matches_dense(q, n, k, nn, bn, mode):
    rng = np.random.default_rng(q * 7 + n)
    Q = _rand_coords(q * 7 + n, q, k)
    X = _rand_coords(q * 7 + n + 1, n, k)
    want_d, want_i = Z._dense_topk(Q, X, nn, mode)
    got_d, got_i = zt.zen_topk(Q, X, nn, mode, block_n=bn, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(want_d), rtol=1e-5, atol=1e-5
    )
    assert (np.asarray(got_i) == np.asarray(want_i)).all()


@pytest.mark.parametrize("q,n,k,nn,bn", SHAPES)
def test_streaming_scan_matches_dense(q, n, k, nn, bn):
    Q = _rand_coords(q + n, q, k)
    X = _rand_coords(q + n + 1, n, k)
    want_d, want_i = Z._dense_topk(Q, X, nn, "zen")
    got_d, got_i = zt.zen_topk_scan(Q, X, nn, "zen", chunk=bn)
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(want_d), rtol=1e-5, atol=1e-5
    )
    assert (np.asarray(got_i) == np.asarray(want_i)).all()


def test_kernel_custom_query_blocks():
    Q = _rand_coords(0, 37, 11)  # ragged query count vs block_q
    X = _rand_coords(1, 400, 11)
    want_d, want_i = Z._dense_topk(Q, X, 9, "zen")
    got_d, got_i = zt.zen_topk(
        Q, X, 9, "zen", block_q=16, block_n=128, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(want_d), rtol=1e-5, atol=1e-5
    )
    assert (np.asarray(got_i) == np.asarray(want_i)).all()


def test_knn_search_dispatch_modes_agree():
    tr, Xp = _projected(3, 400, 64, 12)
    Qp = Xp[:11]
    dense = Z.knn_search(Qp, Xp, n_neighbors=8)
    streamed = Z.knn_search(Qp, Xp, n_neighbors=8, chunk=128)
    kernel = Z.knn_search(Qp, Xp, n_neighbors=8, force_kernel=True)
    for got_d, got_i in (streamed, kernel):
        np.testing.assert_allclose(
            np.asarray(got_d), np.asarray(dense[0]), rtol=1e-5, atol=1e-5
        )
        assert (np.asarray(got_i) == np.asarray(dense[1])).all()


def test_ops_dispatch_cpu_scan_vs_interpret_kernel():
    Q = _rand_coords(5, 6, 10)
    X = _rand_coords(6, 350, 10)
    a = ops.zen_topk(Q, X, 12)                      # scan fallback on CPU
    b = ops.zen_topk(Q, X, 12, force_kernel=True)   # interpret-mode kernel
    np.testing.assert_allclose(
        np.asarray(a[0]), np.asarray(b[0]), rtol=1e-5, atol=1e-5
    )
    assert (np.asarray(a[1]) == np.asarray(b[1])).all()


# -- estimator bound ordering (paper Lemma C.2 over the streaming path) --------


def test_streaming_bound_ordering_on_projected_batch():
    """Full streaming ranking per mode, rebuilt as matrices: Lwb <= Zen <= Upb."""
    tr, Xp = _projected(11, 160, 48, 10)
    Qp = Xp[:13]
    n = Xp.shape[0]
    mats = {}
    for mode in ("lwb", "zen", "upb"):
        d, ids = zt.zen_topk(Qp, Xp, n, mode, block_n=128, interpret=True)
        mat = np.zeros((Qp.shape[0], n), np.float32)
        np.put_along_axis(mat, np.asarray(ids), np.asarray(d), axis=1)
        mats[mode] = mat
    tol = 1e-5
    assert (mats["lwb"] <= mats["zen"] + tol).all()
    assert (mats["zen"] <= mats["upb"] + tol).all()
    # and the true distance is bracketed (projection preserves ref distances)
    np.testing.assert_allclose(
        mats["zen"], np.asarray(Z.zen_pdist(Qp, Xp)), rtol=1e-4, atol=1e-4
    )


# -- apex projection parity with the paper-faithful oracle ---------------------


def test_apex_projection_parity_feeds_streaming_search():
    rng = np.random.default_rng(21)
    X = rng.normal(size=(50, 40))
    refs = rng.normal(size=(9, 40))
    D_refs = np.linalg.norm(refs[:, None] - refs[None, :], axis=-1)
    dists = np.linalg.norm(X[:, None] - refs[None, :], axis=-1)
    apex_oracle = S.apex_project_reference(D_refs, dists)

    tr = NSimplexTransform(k=9).fit(jnp.asarray(refs))
    Xp = np.asarray(tr.transform(jnp.asarray(X)))
    np.testing.assert_allclose(Xp, apex_oracle, atol=1e-4)

    # the oracle coordinates drive the streaming kernel to the same neighbours
    Qf = jnp.asarray(Xp[:5], jnp.float32)
    Xf = jnp.asarray(apex_oracle, jnp.float32)
    got_d, got_i = zt.zen_topk(Qf, Xf, 6, "zen", interpret=True)
    want_d, want_i = Z._dense_topk(Qf, Xf, 6, "zen")
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(want_d), rtol=1e-5, atol=1e-5
    )
    assert (np.asarray(got_i) == np.asarray(want_i)).all()


# -- sharded search ------------------------------------------------------------


def test_sharded_search_single_device_mesh():
    from jax.sharding import Mesh

    from repro.distributed.retrieval import sharded_knn_search

    mesh = Mesh(np.array(jax.devices()[:1]), ("shard",))
    Q = _rand_coords(30, 7, 14)
    X = _rand_coords(31, 500, 14)
    want_d, want_i = Z._dense_topk(Q, X, 10, "zen")
    got_d, got_i = sharded_knn_search(Q, X, 10, "zen", mesh=mesh)
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(want_d), rtol=1e-5, atol=1e-5
    )
    assert (np.asarray(got_i) == np.asarray(want_i)).all()


_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import zen as Z
    from repro.distributed.retrieval import sharded_knn_search

    mesh = Mesh(np.array(jax.devices()[:4]), ("shard",))
    rng = np.random.default_rng(2)
    for n, shift in [(1000, 0.0), (1001, 0.0), (37, 0.0),
                     # pad rows sit at the origin: with the corpus far from it
                     # and queries near it, padding would win every local
                     # top-k slot unless masked/compensated correctly
                     (5, 100.0), (1001, 100.0)]:
        Q = jnp.asarray(rng.normal(size=(6, 12)), jnp.float32)
        X = jnp.asarray(shift + rng.normal(size=(n, 12)), jnp.float32)
        want_d, want_i = Z._dense_topk(Q, X, min(10, n), "zen")
        got_d, got_i = sharded_knn_search(Q, X, 10, "zen", mesh=mesh)
        assert np.allclose(np.asarray(got_d), np.asarray(want_d), atol=1e-4), n
        assert (np.asarray(got_i) == np.asarray(want_i)).all(), (n, shift)
    print("SHARDED_OK")
""")


def test_sharded_search_multi_device_merge():
    env = dict(
        os.environ,
        PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"),
    )
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SHARDED_OK" in r.stdout


# -- serving end-to-end over the kernel path -----------------------------------


def test_zen_server_force_kernel_matches_default():
    from repro.data import synthetic as syn
    from repro.launch.serve import ZenServer, build_index

    key = jax.random.PRNGKey(5)
    corpus = syn.uniform_space(key, 2000, 64)
    index = build_index(corpus, 8)
    q = syn.uniform_space(jax.random.fold_in(key, 1), 5, 64)
    d0, i0 = ZenServer(index, chunk=256).query(q, 5)
    d1, i1 = ZenServer(index, chunk=256, force_kernel=True).query(q, 5)
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-5,
                               atol=1e-5)
    assert (np.asarray(i0) == np.asarray(i1)).all()


# -- the memory bound itself ---------------------------------------------------


def test_streaming_memory_flat_in_index_size():
    """XLA temp allocation: dense grows ~linearly with N, streaming stays flat."""
    kdim, nn, chunk, q = 16, 10, 1024, 8

    def temp_bytes(fn, n):
        Q = jax.ShapeDtypeStruct((q, kdim), jnp.float32)
        X = jax.ShapeDtypeStruct((n, kdim), jnp.float32)
        mem = jax.jit(fn).lower(Q, X).compile().memory_analysis()
        return mem.temp_size_in_bytes

    dense = lambda Q, X: Z._dense_topk(Q, X, nn, "zen")
    stream = lambda Q, X: zt.zen_topk_scan(Q, X, nn, "zen", chunk=chunk)

    n_small, n_big = 16 * 1024, 128 * 1024
    dense_growth = temp_bytes(dense, n_big) / max(temp_bytes(dense, n_small), 1)
    stream_small = temp_bytes(stream, n_small)
    stream_big = temp_bytes(stream, n_big)
    assert dense_growth > 4, dense_growth  # ~8x for 8x the rows
    assert stream_big <= 2 * max(stream_small, 1), (stream_small, stream_big)
    # and the streaming path's live state is tile-sized, not index-sized
    assert stream_big < q * n_big * 4, stream_big
