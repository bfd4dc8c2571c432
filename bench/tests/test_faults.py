"""A whole run at a tiny size, with the timed path broken underneath:
``correct`` has to come out false for each fault a serving cell can have."""
import jax
import jax.numpy as jnp
import pytest

import repro.index
import repro.index.ivf as ivf_mod
from repro.launch import serve
from bench import run
from bench.tests import tiny


def _run(name):
    import time

    jax.clear_caches()
    try:
        result, _ = run.run(tiny.cell(name), 11, 1.0, False, jax.devices(),
                            t_start=time.perf_counter())
    finally:
        jax.clear_caches()
    return result


def _altered_answer(monkeypatch):
    """The re-rank returns another row's id in its first slot."""
    inner = repro.index.exact_rerank

    def rerank(queries, corpus, cand_ids, n, **kw):
        d, ids = inner(queries, corpus, cand_ids, n, **kw)
        return d, ids.at[:, 0].set((ids[:, 0] + 1) % corpus.shape[0])

    monkeypatch.setattr(repro.index, "exact_rerank", rerank)


def _half_batch(monkeypatch):
    """A dispatch serves the first half of its rows and hands the second
    half the first half's answers."""
    inner = serve.ZenServer._query_block

    def query_block(self, queries, width, n_bucket, index=None):
        d, ids = inner(self, queries, width, n_bucket, index=index)
        h = max(d.shape[0] // 2, 1)
        return (jnp.concatenate([d[:h], d[:d.shape[0] - h]]),
                jnp.concatenate([ids[:h], ids[:ids.shape[0] - h]]))

    monkeypatch.setattr(serve.ZenServer, "_query_block", query_block)


def _wrong_clusters(monkeypatch):
    """Centroid ranking probes the farthest clusters instead of the
    nearest."""
    def probe(queries, centroids, nprobe, mode):
        cd = ivf_mod.zen_lib.estimate_pdist(queries, centroids, mode)
        return jax.lax.top_k(cd, nprobe)[1].astype(jnp.int32)

    monkeypatch.setattr(ivf_mod, "_probe_clusters", probe)


def _wrong_scan(monkeypatch):
    """The flat scan answers each query with the next query's rows."""
    inner = serve.zen_lib.knn_search

    def knn(queries, *a, **kw):
        return inner(jnp.roll(queries, 1, axis=0), *a, **kw)

    monkeypatch.setattr(serve.zen_lib, "knn_search", knn)


def _half_index(monkeypatch):
    """Half of the index is left out: every candidate the probe or scan
    finds in the upper half of the corpus is lost (replaced by a row of
    the lower half) before the exact re-rank."""
    inner = serve.ZenServer._rerank

    def rerank(self, queries, cand_ids, n_neighbors, index):
        half = index.corpus.shape[0] // 2
        cand_ids = jnp.where(cand_ids >= half, cand_ids - half, cand_ids)
        return inner(self, queries, cand_ids, n_neighbors, index)

    monkeypatch.setattr(serve.ZenServer, "_rerank", rerank)


@pytest.mark.parametrize("name", sorted(tiny.CELLS))
@pytest.mark.parametrize("fault", [_altered_answer, _half_batch])
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    assert not _run(name)["correct"]


@pytest.mark.parametrize("name,fault", [
    ("deep1b-ivf.online", _wrong_clusters),
    ("deep1b-flat.offline", _wrong_scan),
    ("deep1b-ivf.online", _half_index),
    ("deep1b-flat.offline", _half_index)])
def test_kernel_layer_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    result = _run(name)
    assert not result["correct"]
    assert result["checks"]["recall_at_10"]["value"] < \
        result["checks"]["recall_at_10"]["limit"]
