"""Clustered (IVF) Zen index: packed-layout invariants, exactness at
nprobe = n_clusters against the flat search, recall monotonicity in nprobe,
Pallas-kernel vs scan-fallback parity (padded-tile and single-cluster edge
shapes), sharded probes, serving integration, n_neighbors clamping, and the
flat-in-N memory bound of the probe. All CPU (interpret=True for Pallas)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import zen as Z
from repro.core.quality import recall_at_k
from repro.index import IVFZenIndex
from repro.kernels import ivf_probe as ip
from repro.kernels import ops


def _coords(seed, n, k):
    """Synthetic projected coords (non-negative altitude column)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, k)).astype(np.float32)
    X[:, -1] = np.abs(X[:, -1])
    return jnp.asarray(X)


def _queries(seed, X, q, noise=0.05):
    rng = np.random.default_rng(seed)
    Q = np.asarray(X[:q]) + noise * rng.normal(size=(q, X.shape[1]))
    return jnp.asarray(Q.astype(np.float32))


# -- packed layout invariants --------------------------------------------------


def test_build_packs_every_row_exactly_once():
    X = _coords(0, 777, 9)  # ragged vs tile_rows=128
    idx = IVFZenIndex.build(X, 12, key=jax.random.PRNGKey(0))
    ids = np.asarray(idx.tile_ids).ravel()
    valid = ids[ids >= 0]
    assert sorted(valid.tolist()) == list(range(777))  # each row once
    assert idx.tile_coords.shape == (
        12 * idx.tiles_per_cluster, idx.tile_rows, 9
    )
    # packed coordinates match the source rows; padding slots are zero
    packed = np.asarray(idx.tile_coords).reshape(-1, 9)
    flat_ids = np.asarray(idx.tile_ids).ravel()
    np.testing.assert_array_equal(
        packed[flat_ids >= 0], np.asarray(X)[flat_ids[flat_ids >= 0]]
    )
    assert (packed[flat_ids < 0] == 0).all()


def test_build_members_assigned_to_their_cluster():
    X = _coords(1, 400, 7)
    idx = IVFZenIndex.build(X, 8, key=jax.random.PRNGKey(1))
    cents = np.asarray(idx.centroids)
    T, tr = idx.tiles_per_cluster, idx.tile_rows
    ids = np.asarray(idx.tile_ids).reshape(8, T * tr)
    for c in range(8):
        members = ids[c][ids[c] >= 0]
        if members.size == 0:
            continue
        d2 = ((np.asarray(X)[members][:, None, :] - cents[None]) ** 2).sum(-1)
        assert (d2.argmin(1) == c).all()


# -- exactness at nprobe = n_clusters ------------------------------------------

EXACT_SHAPES = [
    # (n, k, n_clusters, n_neighbors): padded tails, single cluster, big k,
    # n_neighbors exceeding the smallest cluster
    (700, 12, 10, 10),
    (513, 8, 1, 5),      # single cluster: pure padded-tile scan
    (300, 17, 50, 25),   # n_neighbors > typical cluster size
    (64, 6, 64, 3),      # one point per cluster
    (129, 9, 4, 1),
]


@pytest.mark.parametrize("n,k,c,nn", EXACT_SHAPES)
@pytest.mark.parametrize("mode", ["zen", "lwb", "upb"])
def test_full_probe_matches_flat_search(n, k, c, nn, mode):
    X = _coords(n + k, n, k)
    Q = _queries(n, X, 7)
    idx = IVFZenIndex.build(X, c, key=jax.random.PRNGKey(2))
    want_d, want_i = Z.knn_search(Q, X, nn, mode)
    got_d, got_i = idx.search(Q, nn, nprobe=idx.n_clusters, mode=mode)
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(want_d), rtol=1e-4, atol=1e-4
    )
    assert (np.asarray(got_i) == np.asarray(want_i)).all()


def test_full_probe_matches_flat_on_projected_coords():
    from repro.core.projection import NSimplexTransform

    rng = np.random.default_rng(11)
    refs = rng.normal(size=(10, 48))
    tr = NSimplexTransform(k=10).fit(jnp.asarray(refs, jnp.float32))
    X = jnp.asarray(
        tr.transform(jnp.asarray(rng.normal(size=(500, 48)), jnp.float32)),
        jnp.float32,
    )
    Q = X[:9]
    idx = IVFZenIndex.build(X, 16, key=jax.random.PRNGKey(3))
    want_d, want_i = Z.knn_search(Q, X, 8, "zen")
    got_d, got_i = idx.search(Q, 8, nprobe=16)
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(want_d), rtol=1e-4, atol=1e-4
    )
    assert (np.asarray(got_i) == np.asarray(want_i)).all()


# -- recall monotonicity in nprobe ---------------------------------------------


def test_recall_monotone_in_nprobe():
    X = _coords(21, 3000, 10)
    Q = _queries(22, X, 16)
    idx = IVFZenIndex.build(X, 32, key=jax.random.PRNGKey(4))
    flat_ids = np.asarray(Z.knn_search(Q, X, 10, "zen")[1])
    last = -1.0
    for nprobe in (1, 2, 4, 8, 16, 32):
        _, ids = idx.search(Q, 10, nprobe=nprobe)
        rec = recall_at_k(flat_ids, np.asarray(ids))
        assert rec >= last - 1e-9, (nprobe, rec, last)
        last = rec
    assert last == 1.0  # nprobe = n_clusters is exact


# -- kernel vs fallback parity -------------------------------------------------

PARITY_CASES = [
    # (n, k, n_clusters, nprobe): padded tiles, single cluster (nprobe=1=C),
    # multi-tile clusters (T > 1), ragged k
    (600, 12, 8, 3),
    (513, 8, 1, 1),       # single cluster edge
    (900, 5, 4, 2),       # clusters > tile_rows: T >= 2
    (150, 18, 30, 30),    # tiny clusters, all probed
]


@pytest.mark.parametrize("n,k,c,nprobe", PARITY_CASES)
@pytest.mark.parametrize("mode", ["zen", "lwb", "upb"])
def test_probe_kernel_matches_scan(n, k, c, nprobe, mode):
    X = _coords(n * 3 + k, n, k)
    Q = _queries(n * 3, X, 6)
    idx = IVFZenIndex.build(X, c, key=jax.random.PRNGKey(5))
    probes = idx.probe_clusters(Q, nprobe, mode)
    scan_d, scan_i = ip.ivf_probe_scan(
        Q, idx.tile_coords, idx.tile_ids, probes, 9, mode,
        tiles_per_cluster=idx.tiles_per_cluster,
    )
    kern_d, kern_i = ip.ivf_probe(
        Q, idx.tile_coords, idx.tile_ids, probes, 9, mode,
        tiles_per_cluster=idx.tiles_per_cluster, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(kern_d), np.asarray(scan_d), rtol=1e-5, atol=1e-5
    )
    assert (np.asarray(kern_i) == np.asarray(scan_i)).all()


def test_probe_kernel_rounds_skip_padding_tiles():
    """An all-padding tile runs no merge round: each query probes its own
    near cluster (30 members, then an all-padding second tile) and a far
    one. With 9 neighbours the far members cannot enter (9 rounds); with
    40 the state is not full, so all 60 members enter (60 rounds). Both
    equal the scan."""
    rng = np.random.default_rng(11)
    c, t, rows, k = 6, 2, 128, 12
    coords = np.zeros((c * t, rows, k), np.float32)
    ids = np.full((c * t, rows), -1, np.int32)
    for cl in range(c):
        coords[cl * t, :30] = cl * 40.0 + rng.normal(size=(30, k))
        ids[cl * t, :30] = cl * 30 + np.arange(30)
    coords[..., -1] = np.abs(coords[..., -1])
    Q = jnp.asarray(coords[::t, 0] + 0.01 * rng.normal(size=(c, k)),
                    jnp.float32)
    probes = jnp.asarray(
        np.stack([np.arange(c), (np.arange(c) + 3) % c], axis=1), jnp.int32)
    args = (Q, jnp.asarray(coords), jnp.asarray(ids), probes)
    for n_neighbors, want_rounds in ((9, 9), (40, 60)):
        d, i, rounds = ip.ivf_probe(
            *args, n_neighbors, "zen", tiles_per_cluster=t, interpret=True,
            return_rounds=True)
        want_d, want_i = ip.ivf_probe_scan(
            *args, n_neighbors, "zen", tiles_per_cluster=t)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i))
        np.testing.assert_allclose(np.asarray(d), np.asarray(want_d),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(rounds), np.full(c, want_rounds))


def test_probe_multi_tile_cluster_layout():
    # force T > 1 and verify against brute force over the probed clusters
    X = _coords(40, 1000, 6)
    idx = IVFZenIndex.build(X, 3, key=jax.random.PRNGKey(6))
    assert idx.tiles_per_cluster >= 2  # ~333 rows per cluster vs 128-row tiles
    Q = _queries(41, X, 5)
    probes = idx.probe_clusters(Q, 2)
    got_d, got_i = ops.ivf_probe(
        Q, idx.tile_coords, idx.tile_ids, probes, 12, "zen",
        tiles_per_cluster=idx.tiles_per_cluster,
    )
    # oracle: dense distances restricted to each query's probed clusters
    T, tr = idx.tiles_per_cluster, idx.tile_rows
    ids_by_cluster = np.asarray(idx.tile_ids).reshape(idx.n_clusters, T * tr)
    dense = np.asarray(Z.estimate_pdist(Q, X, "zen"))
    for qi in range(5):
        member = np.concatenate(
            [ids_by_cluster[c][ids_by_cluster[c] >= 0]
             for c in np.asarray(probes)[qi]]
        )
        want = member[np.argsort(dense[qi][member], kind="stable")][:12]
        got = np.asarray(got_i)[qi]
        assert set(got.tolist()) == set(want.tolist())


def test_probe_returns_padding_when_pool_too_small():
    # nprobe=1 on a tiny cluster: unfillable slots must be (+inf, -1)
    X = _coords(60, 64, 6)
    idx = IVFZenIndex.build(X, 64, key=jax.random.PRNGKey(7))  # 1 row/cluster
    Q = _queries(61, X, 4)
    d, ids = idx.search(Q, 10, nprobe=1)
    d, ids = np.asarray(d), np.asarray(ids)
    assert (ids[:, 0] >= 0).all()  # the probed cluster's row is returned
    assert (ids[:, 1:] == -1).all() and np.isinf(d[:, 1:]).all()
    # and valid ids are never padding rows
    assert ids.max() < 64


# -- ops dispatch --------------------------------------------------------------


def test_ops_dispatch_scan_vs_interpret_kernel():
    X = _coords(70, 500, 11)
    idx = IVFZenIndex.build(X, 10, key=jax.random.PRNGKey(8))
    Q = _queries(71, X, 6)
    probes = idx.probe_clusters(Q, 4)
    a = ops.ivf_probe(Q, idx.tile_coords, idx.tile_ids, probes, 8,
                      tiles_per_cluster=idx.tiles_per_cluster)
    b = ops.ivf_probe(Q, idx.tile_coords, idx.tile_ids, probes, 8,
                      tiles_per_cluster=idx.tiles_per_cluster,
                      force_kernel=True)
    np.testing.assert_allclose(
        np.asarray(a[0]), np.asarray(b[0]), rtol=1e-5, atol=1e-5
    )
    assert (np.asarray(a[1]) == np.asarray(b[1])).all()


def test_ivf_search_force_kernel_matches_scan():
    X = _coords(80, 700, 9)
    idx = IVFZenIndex.build(X, 12, key=jax.random.PRNGKey(9))
    Q = _queries(81, X, 5)
    d0, i0 = idx.search(Q, 7, nprobe=5)
    d1, i1 = idx.search(Q, 7, nprobe=5, force_kernel=True)
    np.testing.assert_allclose(
        np.asarray(d0), np.asarray(d1), rtol=1e-5, atol=1e-5
    )
    assert (np.asarray(i0) == np.asarray(i1)).all()


# -- n_neighbors clamping (regression: nn > N / > cluster pool) ----------------


def test_knn_search_clamps_n_neighbors_regression():
    X = _coords(90, 7, 5)
    Q = X[:2]
    for kw in (dict(), dict(chunk=4), dict(force_kernel=True),
               dict(stream=True)):
        d, ids = Z.knn_search(Q, X, n_neighbors=20, **kw)
        assert d.shape == (2, 7) and ids.shape == (2, 7)
        ids = np.asarray(ids)
        assert (ids >= 0).all() and (ids < 7).all()
        assert sorted(ids[0].tolist()) == list(range(7))  # valid ids only


def test_kernel_level_topk_clamps_n_neighbors_regression():
    from repro.kernels import zen_topk as zt

    X = _coords(91, 9, 6)
    Q = X[:3]
    for fn in (lambda: zt.zen_topk_scan(Q, X, 25, "zen", chunk=4),
               lambda: zt.zen_topk(Q, X, 25, "zen", interpret=True),
               lambda: ops.zen_topk(Q, X, 25)):
        d, ids = fn()
        assert d.shape == (3, 9) and ids.shape == (3, 9)
        assert (np.asarray(ids) >= 0).all()


def test_sharded_knn_search_clamps_n_neighbors_regression():
    from jax.sharding import Mesh

    from repro.distributed.retrieval import sharded_knn_search

    mesh = Mesh(np.array(jax.devices()[:1]), ("shard",))
    X = _coords(92, 11, 6)
    Q = X[:2]
    d, ids = sharded_knn_search(Q, X, 30, mesh=mesh)
    assert ids.shape == (2, 11)
    assert (np.asarray(ids) >= 0).all() and (np.asarray(ids) < 11).all()
    # with pre-padded rows: clamp to n_valid, padded rows never returned
    Xp = jnp.pad(X, ((0, 5), (0, 0)))
    d, ids = sharded_knn_search(Q, Xp, 30, mesh=mesh, n_valid=11)
    assert ids.shape == (2, 11)
    assert (np.asarray(ids) < 11).all()


def test_ivf_search_clamps_n_neighbors():
    X = _coords(93, 40, 5)
    idx = IVFZenIndex.build(X, 5, key=jax.random.PRNGKey(10))
    Q = X[:2]
    d, ids = idx.search(Q, 99, nprobe=5)
    assert ids.shape == (2, 40)
    assert sorted(np.asarray(ids)[0].tolist()) == list(range(40))


# -- sharded IVF ---------------------------------------------------------------


def test_sharded_ivf_single_device_exact():
    from jax.sharding import Mesh

    from repro.index import ShardedIVFZenIndex

    mesh = Mesh(np.array(jax.devices()[:1]), ("shard",))
    X = _coords(100, 800, 10)
    Q = _queries(101, X, 6)
    sidx = ShardedIVFZenIndex.build(X, 12, mesh=mesh,
                                    key=jax.random.PRNGKey(11))
    want_d, want_i = Z.knn_search(Q, X, 9, "zen")
    got_d, got_i = sidx.search(Q, 9, nprobe=sidx.n_clusters)
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(want_d), rtol=1e-4, atol=1e-4
    )
    assert (np.asarray(got_i) == np.asarray(want_i)).all()


_SHARDED_IVF_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import zen as Z
    from repro.index import ShardedIVFZenIndex

    mesh = Mesh(np.array(jax.devices()[:4]), ("shard",))
    rng = np.random.default_rng(2)
    for n in [1000, 1001, 37]:  # ragged shard splits + n < shards * tile
        X = rng.normal(size=(n, 12)).astype(np.float32)
        X[:, -1] = np.abs(X[:, -1])
        X = jnp.asarray(X)
        Q = jnp.asarray(rng.normal(size=(6, 12)), jnp.float32)
        C = min(16, n)
        sidx = ShardedIVFZenIndex.build(X, C, mesh=mesh,
                                        key=jax.random.PRNGKey(0))
        want_d, want_i = Z.knn_search(Q, X, min(10, n), "zen")
        got_d, got_i = sidx.search(Q, 10, nprobe=sidx.n_clusters)
        assert np.allclose(np.asarray(got_d), np.asarray(want_d),
                           atol=1e-4), n
        assert (np.asarray(got_i) == np.asarray(want_i)).all(), n
        # partial probes still return only valid (or -1 padding) ids
        _, ids = sidx.search(Q, 10, nprobe=2)
        ids = np.asarray(ids)
        assert ((ids >= -1) & (ids < n)).all(), n
    print("SHARDED_IVF_OK")
""")


def test_sharded_ivf_multi_device_merge():
    env = dict(
        os.environ,
        PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"),
    )
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_IVF_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SHARDED_IVF_OK" in r.stdout


# -- serving integration -------------------------------------------------------


def test_zen_server_ivf_full_probe_matches_flat():
    from repro.data import synthetic as syn
    from repro.launch.serve import ZenIndex, ZenServer, build_index

    key = jax.random.PRNGKey(5)
    corpus = syn.uniform_space(key, 2000, 64)
    ivf_index = build_index(corpus, 8, index="ivf", n_clusters=24)
    assert ivf_index.ivf is not None
    flat_index = ZenIndex(transform=ivf_index.transform,
                          coords=ivf_index.coords, corpus=ivf_index.corpus)
    q = syn.uniform_space(jax.random.fold_in(key, 1), 5, 64)
    d0, i0 = ZenServer(flat_index, chunk=256).query(q, 5)
    d1, i1 = ZenServer(ivf_index, nprobe=24).query(q, 5)
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-4,
                               atol=1e-4)
    assert (np.asarray(i0) == np.asarray(i1)).all()
    # rerank over the IVF candidate pool returns valid ids
    d2, i2 = ZenServer(ivf_index, nprobe=6, rerank_factor=4).query(q, 5)
    assert (np.asarray(i2) >= 0).all() and (np.asarray(i2) < 2000).all()


def test_build_index_rejects_unknown_mode():
    from repro.data import synthetic as syn
    from repro.launch.serve import build_index

    corpus = syn.uniform_space(jax.random.PRNGKey(0), 200, 16)
    with pytest.raises(ValueError):
        build_index(corpus, 4, index="hnsw")


# -- the memory bound ----------------------------------------------------------


def test_probe_memory_flat_in_index_size():
    """XLA temp allocation of the probe scan: fixed tile geometry, 8x the
    index rows -> flat working set (the clustered analogue of
    test_topk_retrieval.py::test_streaming_memory_flat_in_index_size)."""
    q, kdim, nn, nprobe, tile_rows, T = 8, 16, 10, 8, 128, 2

    def temp_bytes(n_rows):
        n_c = n_rows // (T * tile_rows)
        shapes = (
            jax.ShapeDtypeStruct((q, kdim), jnp.float32),
            jax.ShapeDtypeStruct((n_c * T, tile_rows, kdim), jnp.float32),
            jax.ShapeDtypeStruct((n_c * T, tile_rows), jnp.int32),
            jax.ShapeDtypeStruct((q, nprobe), jnp.int32),
        )
        fn = lambda Q_, TC, TI, PR: ip.ivf_probe_scan(
            Q_, TC, TI, PR, nn, "zen", tiles_per_cluster=T
        )
        mem = jax.jit(fn).lower(*shapes).compile().memory_analysis()
        return mem.temp_size_in_bytes

    small, big = temp_bytes(16 * 1024), temp_bytes(128 * 1024)
    assert big <= 2 * max(small, 1), (small, big)
    assert big < q * 128 * 1024 * 4  # tile-sized, not index-sized


# -- tiered (host-offloaded) tile store ---------------------------------------


def _tiered_fixture(seed=17, n=1500, k=8, c=16, storage="float32"):
    from repro.index.ivf import TieredIVFZenIndex

    X = _coords(seed, n, k)
    idx = IVFZenIndex.build(X, c, key=jax.random.PRNGKey(seed),
                            storage=storage)
    tiered = TieredIVFZenIndex.from_index(idx, hot_clusters=3,
                                          prefetch_cols=2)
    return X, idx, tiered


@pytest.mark.parametrize("storage", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("nprobe", [1, 4, 16])
def test_tiered_search_matches_resident(storage, nprobe):
    """Hot-pass + streamed-cold-chunk search returns exactly the resident
    index's results at every nprobe: same kernel over the same tiles, only
    partitioned into device-resident and staged passes."""
    X, idx, tiered = _tiered_fixture(storage=storage)
    Q = _queries(1, X, 12)
    want_d, want_i = idx.search(Q, n_neighbors=10, nprobe=nprobe)
    got_d, got_i = tiered.search(Q, n_neighbors=10, nprobe=nprobe)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                               rtol=1e-5, atol=1e-5)


def test_tiered_all_hot_and_all_cold_extremes():
    from repro.index.ivf import TieredIVFZenIndex

    X = _coords(18, 900, 8)
    idx = IVFZenIndex.build(X, 12, key=jax.random.PRNGKey(18))
    Q = _queries(2, X, 8)
    want = idx.search(Q, n_neighbors=10, nprobe=12)
    for hot in (0, 12):  # pure streaming vs fully resident
        t = TieredIVFZenIndex.from_index(idx, hot_clusters=hot)
        got = t.search(Q, n_neighbors=10, nprobe=12)
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1])), hot
        st = t.stats()
        if hot == 0:
            assert st["cold_uploads"] > 0 and st["hot_hits"] == 0
        else:
            assert st["cold_uploads"] == 0 and st["hot_hits"] > 0
        # the analytic provisioning bound dominates the observed mark
        assert t.provisioned_device_bytes(Q.shape[0]) >= st["device_bytes"]


def test_tiered_stage_kernel_interpret_parity():
    """The Pallas double-buffered DMA staging path (interpret mode on CPU)
    produces the same device blocks — and therefore the same search
    results — as the device_put fallback."""
    from repro.index.ivf import TieredIVFZenIndex

    X = _coords(19, 800, 8)
    idx = IVFZenIndex.build(X, 10, key=jax.random.PRNGKey(19))
    Q = _queries(3, X, 6)
    plain = TieredIVFZenIndex.from_index(idx, hot_clusters=2)
    forced = TieredIVFZenIndex.from_index(idx, hot_clusters=2,
                                          force_stage_kernel=True)
    d0, i0 = plain.search(Q, n_neighbors=8, nprobe=10)
    d1, i1 = forced.search(Q, n_neighbors=8, nprobe=10)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


def test_dma_copy_blocks_roundtrip_dtypes():
    from repro.kernels import tile_stage

    rng = np.random.default_rng(20)
    for dtype in (np.float32, np.int32, np.int8):
        src = rng.normal(size=(5, 4, 8)).astype(dtype)
        chunks = tile_stage.to_chunks(src)
        moved = tile_stage.dma_copy_chunks(jnp.asarray(chunks),
                                           interpret=True)
        np.testing.assert_array_equal(np.asarray(moved), chunks)
        out = tile_stage.from_chunks(moved, src.shape, src.dtype)
        np.testing.assert_array_equal(np.asarray(out), src)


def test_tiered_tile_pool_snapshot_mmap_roundtrip(tmp_path):
    """save() persists the packed pool; load(mmap=True) serves straight
    off the snapshot (cold tiles stay on disk) with identical results."""
    from repro.index.ivf import TieredIVFZenIndex

    for storage in ("float32", "int8"):
        X, idx, tiered = _tiered_fixture(seed=21, storage=storage)
        Q = _queries(4, X, 8)
        want = tiered.search(Q, n_neighbors=10, nprobe=16)
        path = str(tmp_path / f"pool-{storage}")
        tiered.save(path)
        back = TieredIVFZenIndex.load(path, mmap=True, hot_clusters=3)
        assert isinstance(back.host_coords, np.memmap)
        got = back.search(Q, n_neighbors=10, nprobe=16)
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        assert back.size == tiered.size and back.storage == storage


def test_tiered_refresh_hot_follows_traffic():
    """refresh_hot() re-picks the device-resident set from observed probe
    traffic; results stay identical (residency is a placement decision)."""
    X, idx, tiered = _tiered_fixture(seed=22)
    Q = _queries(5, X, 16)
    want = tiered.search(Q, n_neighbors=10, nprobe=4)
    before = tiered.stats()["cold_uploads"]
    tiered.refresh_hot()
    got = tiered.search(Q, n_neighbors=10, nprobe=4)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    # the re-picked hot set covers this query mix at least as well
    assert tiered.stats()["cold_uploads"] - before <= before


def test_tiered_dead_shard_masks_members():
    from repro.index.ivf import TieredIVFZenIndex

    X = _coords(23, 1200, 8)
    idx = IVFZenIndex.build(X, 16, key=jax.random.PRNGKey(23))
    tiered = TieredIVFZenIndex.from_index(idx, hot_clusters=4, n_shards=4)
    Q = _queries(6, X, 12)
    tiered.set_dead_shards([1])
    d, ids = tiered.search(Q, n_neighbors=10, nprobe=16)
    dead_clusters = np.flatnonzero(tiered.shard_of_cluster() == 1)
    dead_members = set(np.asarray(
        idx.tile_ids).reshape(16, -1)[dead_clusters].ravel().tolist()) - {-1}
    assert not (set(np.asarray(ids).ravel().tolist()) & dead_members)
    assert tiered.stats()["masked_clusters"] == 4
    tiered.set_dead_shards([])  # recovery restores exactness
    _, ids2 = tiered.search(Q, n_neighbors=10, nprobe=16)
    want = idx.search(Q, n_neighbors=10, nprobe=16)
    np.testing.assert_array_equal(np.asarray(ids2), np.asarray(want[1]))
