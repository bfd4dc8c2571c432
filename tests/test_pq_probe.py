"""PQ probe parity: the fused-LUT path against its fallbacks and oracle.

Three implementations of the product-quantised probe must agree on the
same code tiles: the Pallas kernel (interpret mode on CPU), the fori_loop
LUT-gather scan, and the dense oracle — the plain estimator evaluated on
the *decoded* member coordinates (``centroid + decode(code)``). The ADC
tables fold the Zen/Lwb/Upb altitude terms, so agreement across all three
modes pins the mode-folding algebra, not just the gather. Mirrors
``test_ivf_index.py``: padded tails, single cluster, multi-tile clusters,
``nprobe = n_clusters`` exactness, plus the non-Euclidean (jsd/qform)
serving path through exact re-rank. All CPU.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import zen as Z
from repro.core.quality import recall_at_k
from repro.index import IVFZenIndex
from repro.kernels import ivf_probe as ip
from repro.kernels import ops
from repro.kernels import pq as pq_lib
from repro.kernels import scoring

MODES = ["zen", "lwb", "upb"]


def _coords(seed, n, k):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, k)).astype(np.float32)
    X[:, -1] = np.abs(X[:, -1])
    return jnp.asarray(X)


def _queries(seed, X, q, noise=0.05):
    rng = np.random.default_rng(seed)
    Q = np.asarray(X[:q]) + noise * rng.normal(size=(q, X.shape[1]))
    return jnp.asarray(Q.astype(np.float32))


def _decoded_corpus(idx, n):
    """(n, k) f32 coordinates the PQ index *actually* stores — each member
    decoded against its centroid — the oracle the LUT path must match."""
    tiles = idx._host_tiles_f32().reshape(-1, np.asarray(idx.centroids).shape[1])
    ids = np.asarray(idx.tile_ids).ravel()
    out = np.zeros((n, tiles.shape[1]), np.float32)
    out[ids[ids >= 0]] = tiles[ids >= 0]
    return out


# -- kernel vs scan vs dense oracle -------------------------------------------

PQ_PARITY_CASES = [
    # (n, k, n_clusters, nprobe): padded tiles, single cluster, T >= 2,
    # ragged k (k=18 -> M=4, ds=5: padded subspace columns in play)
    (600, 12, 8, 3),
    (513, 8, 1, 1),       # single cluster edge
    (900, 8, 4, 2),       # clusters > tile_rows: T >= 2
    (200, 18, 12, 12),    # ragged k + all clusters probed
]


@pytest.mark.parametrize("n,k,c,nprobe", PQ_PARITY_CASES)
@pytest.mark.parametrize("mode", MODES)
def test_pq_probe_kernel_matches_scan(n, k, c, nprobe, mode):
    """Interpret-mode kernel and fori_loop scan gather the same tables over
    the same code tiles: identical ids, near-bit distances."""
    X = _coords(n * 5 + k, n, k)
    Q = _queries(n * 5, X, 6)
    idx = IVFZenIndex.build(X, c, key=jax.random.PRNGKey(5), storage="pq")
    probes = idx.probe_clusters(Q, nprobe, mode)
    luts = pq_lib.build_luts(Q, idx.centroids, idx.codebooks, probes,
                             scoring.MODE_IDS[mode])
    scan_d, scan_i = ip.ivf_probe_pq_scan(
        idx.tile_coords, idx.tile_ids, probes, luts, 9,
        tiles_per_cluster=idx.tiles_per_cluster)
    kern_d, kern_i, rounds = ip.ivf_probe_pq(
        idx.tile_coords, idx.tile_ids, probes, luts, 9,
        tiles_per_cluster=idx.tiles_per_cluster, interpret=True,
        return_rounds=True)
    assert (np.asarray(kern_i) == np.asarray(scan_i)).all()
    np.testing.assert_allclose(np.asarray(kern_d), np.asarray(scan_d),
                               rtol=1e-5, atol=1e-5)
    # every filled slot entered in some round; no step runs more than 9
    rounds = np.asarray(rounds)
    assert (rounds >= np.isfinite(np.asarray(kern_d)).sum(axis=1)).all()
    assert (rounds <= 9 * nprobe * idx.tiles_per_cluster).all()


@pytest.mark.parametrize("mode", MODES)
def test_pq_full_probe_matches_dense_on_decoded(mode):
    """nprobe = n_clusters scans everything: the LUT path must equal the
    flat estimator search over the decoded coordinates — same distances,
    and ids agreeing wherever the decoded points are distinct (members
    sharing all M codes in one cluster decode identically; such genuine
    ties may legally reorder)."""
    n, k, c, nn = 700, 12, 10, 10
    X = _coords(7, n, k)
    Q = _queries(8, X, 7)
    idx = IVFZenIndex.build(X, c, key=jax.random.PRNGKey(2), storage="pq")
    Xhat = jnp.asarray(_decoded_corpus(idx, n))
    want_d, want_i = Z.knn_search(Q, Xhat, nn, mode)
    got_d, got_i = idx.search(Q, nn, nprobe=idx.n_clusters, mode=mode)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                               rtol=1e-4, atol=1e-4)
    # each returned id must realise its reported distance on the decoded
    # corpus (exactness up to ties), and the id sets must coincide
    dense = np.asarray(Z.estimate_pdist(Q, Xhat, mode))
    np.testing.assert_allclose(
        np.take_along_axis(dense, np.asarray(got_i), 1),
        np.asarray(got_d), rtol=1e-4, atol=1e-4)
    for qi in range(Q.shape[0]):
        assert set(np.asarray(got_i)[qi].tolist()) == \
            set(np.asarray(want_i)[qi].tolist())


@pytest.mark.parametrize("mode", MODES)
def test_build_luts_match_dense_estimator(mode):
    """sum_m lut[q, p, m, code[m]] == the mode's squared estimator distance
    to ``centroid + decode(code)`` — the table algebra itself, checked
    against arbitrary (not trained) codebooks and random codes."""
    rng = np.random.default_rng(9)
    q_n, c_n, k, m = 5, 6, 10, 3
    ds = pq_lib.subspace_dims(k, m)
    Qv = _coords(10, q_n, k)
    cents = _coords(11, c_n, k)
    books = rng.normal(size=(m, pq_lib.PQ_ENTRIES, ds)).astype(np.float32)
    pad = m * ds - k
    if pad:  # padded columns must stay zero, as trained books do
        books[-1, :, ds - pad:] = 0.0
    codes = rng.integers(0, 256, size=(c_n, 4, m)).astype(np.uint8)
    probes = jnp.asarray(np.stack([rng.permutation(c_n)[:4]
                                   for _ in range(q_n)]), jnp.int32)
    luts = pq_lib.build_luts(Qv, cents, jnp.asarray(books), probes,
                             scoring.MODE_IDS[mode])
    luts = np.asarray(luts)
    for qi in range(q_n):
        for pi in range(4):
            c = int(np.asarray(probes)[qi, pi])
            xhat = np.asarray(cents)[c] + pq_lib.decode(codes[c], books, k)
            want = np.asarray(Z.estimate_pdist(
                Qv[qi:qi + 1], jnp.asarray(xhat), mode))[0] ** 2
            got = np.take_along_axis(
                luts[qi, pi].T, codes[c].astype(np.int64), 0).sum(1)
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_pq_search_force_kernel_matches_scan():
    X = _coords(80, 700, 9)
    idx = IVFZenIndex.build(X, 12, key=jax.random.PRNGKey(9), storage="pq")
    Q = _queries(81, X, 5)
    d0, i0 = idx.search(Q, 7, nprobe=5)
    d1, i1 = idx.search(Q, 7, nprobe=5, force_kernel=True)
    assert (np.asarray(i0) == np.asarray(i1)).all()
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1),
                               rtol=1e-5, atol=1e-5)


def test_pq_ops_dispatch_matches():
    X = _coords(70, 500, 11)
    idx = IVFZenIndex.build(X, 10, key=jax.random.PRNGKey(8), storage="pq")
    Q = _queries(71, X, 6)
    probes = idx.probe_clusters(Q, 4)
    luts = pq_lib.build_luts(Q, idx.centroids, idx.codebooks, probes,
                             scoring.MODE_IDS["zen"])
    a = ops.ivf_probe_pq(idx.tile_coords, idx.tile_ids, probes, luts, 8,
                         tiles_per_cluster=idx.tiles_per_cluster)
    b = ops.ivf_probe_pq(idx.tile_coords, idx.tile_ids, probes, luts, 8,
                         tiles_per_cluster=idx.tiles_per_cluster,
                         force_kernel=True)
    assert (np.asarray(a[1]) == np.asarray(b[1])).all()
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                               rtol=1e-5, atol=1e-5)


def test_pq_probe_returns_padding_when_pool_too_small():
    X = _coords(60, 64, 6)
    idx = IVFZenIndex.build(X, 64, key=jax.random.PRNGKey(7), storage="pq",
                            pq_m=1)
    Q = _queries(61, X, 4)
    d, ids = idx.search(Q, 10, nprobe=1)
    d, ids = np.asarray(d), np.asarray(ids)
    assert (ids[:, 0] >= 0).all()
    assert (ids[:, 1:] == -1).all() and np.isinf(d[:, 1:]).all()
    assert ids.max() < 64


def test_pq_recall_close_to_f32():
    """The BENCH acceptance protocol in miniature: a 4x PQ candidate pool,
    exactly re-ranked, lands within 0.05 recall@10 of the f32 probe at the
    same nprobe (both indexes share the coarse quantizer key, so only the
    member storage differs)."""
    X = _coords(90, 4096, 16)
    Q = _queries(91, X, 16)
    truth = np.asarray(Z.knn_search(Q, X, 10, "zen")[1])
    f32 = IVFZenIndex.build(X, 32, key=jax.random.PRNGKey(0))
    pq = IVFZenIndex.build(X, 32, key=jax.random.PRNGKey(0), storage="pq")
    dense = np.asarray(Z.estimate_pdist(Q, X, "zen"))
    for nprobe in (8, 16):
        rec_f32 = recall_at_k(truth, np.asarray(
            f32.search(Q, 10, nprobe=nprobe)[1]))
        cand = np.asarray(pq.search(Q, 40, nprobe=nprobe)[1])
        cd = np.where(cand >= 0,
                      np.take_along_axis(dense, np.maximum(cand, 0), 1),
                      np.inf)
        picked = np.take_along_axis(
            cand, np.argsort(cd, axis=1, kind="stable"), 1)[:, :10]
        rec_pq = recall_at_k(truth, picked)
        assert rec_pq >= rec_f32 - 0.05, (nprobe, rec_pq, rec_f32)


# -- non-Euclidean metrics through serving (rerank pool from PQ probe) --------


@pytest.mark.parametrize("metric", ["jsd", "qform"])
def test_pq_noneuclid_serving_rerank(metric):
    """storage="pq" composes with jsd/qform end to end: the PQ probe feeds
    the candidate pool, the exact metric re-ranks — recall must track the
    f32 pipeline within the acceptance bar."""
    from repro.data import synthetic as syn
    from repro.launch.serve import ZenServer, build_index

    key = jax.random.PRNGKey(13)
    if metric == "jsd":
        corpus = syn.probability_space(key, 2000, 48, 8)
        q = syn.probability_space(jax.random.fold_in(key, 1), 32, 48, 8)
    else:
        corpus = syn.manifold_space(key, 2000, 48, 8)
        q = syn.manifold_space(jax.random.fold_in(key, 1), 32, 48, 8)
    kw = dict(metric=metric, index="ivf", n_clusters=24,
              key=jax.random.PRNGKey(3))
    pq_index = build_index(corpus, 12, storage="pq", **kw)
    assert pq_index.ivf.codebooks is not None
    f32_index = build_index(corpus, 12, **kw)
    d_pq, i_pq = ZenServer(pq_index, nprobe=8, rerank_factor=4).query(q, 10)
    d_f, i_f = ZenServer(f32_index, nprobe=8, rerank_factor=4).query(q, 10)
    assert (np.asarray(i_pq) >= 0).all()
    assert bool(jnp.isfinite(d_pq).all())
    rec = recall_at_k(np.asarray(i_f), np.asarray(i_pq))
    assert rec >= 1.0 - 0.05, (metric, rec)
