"""Regenerate the committed golden-parity corpus (tests/golden/).

The golden file pins the serving stack's *exact* numerical output across
PRs: a fixed-seed corpus + query set and the expected top-k ids/distances
of every major retrieval configuration — flat f32, IVF probed at
``nprobe = n_clusters`` (exact), int8 and product-quantised (pq) storage,
exact re-rank, the non-Euclidean jsd/qform paths, the chosen pivot
ids of every ``core.pivots`` strategy, plus a baseline-reducer block
(pca/rp/lmds coordinates and the zen-vs-pca recall ordering at low k). ``tests/test_golden_parity.py``
replays
each configuration against the stored corpus and requires bit-identical
results; it also re-runs :func:`build_golden` and requires the regenerated
arrays to match the committed file bit-for-bit, so the synthetic-data
pipeline is pinned too.

Regenerate (only when an intentional numerical change lands — commit the
diff together with the change that justifies it):

    PYTHONPATH=src python tools/make_golden.py
"""
from __future__ import annotations

import contextlib
import os
from typing import Dict

import numpy as np

import jax

from repro.data import synthetic as syn
from repro.launch.serve import ZenServer, build_index


@contextlib.contextmanager
def _force_x32():
    """Pin the golden computations to f32 and to one PRNG stream.

    Some test modules enable ``jax_enable_x64`` globally at import time;
    the golden bits are defined as the serving stack's *default* (x32)
    numerics, so both generation and replay run under this guard. The
    guard also fixes ``jax_threefry_partitionable`` to False, the stream
    the committed pivots, codebooks and corpora were drawn from: JAX 0.5
    changed that flag's default, which redraws every random choice and
    so every neighbour id, while the serving numerics are unchanged.
    """
    prev = (jax.config.jax_enable_x64, jax.config.jax_threefry_partitionable)
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_threefry_partitionable", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", prev[0])
        jax.config.update("jax_threefry_partitionable", prev[1])

#: golden geometry — small enough to commit, big enough that top-k is
#: non-trivial (multiple IVF clusters, real neighbour structure)
N, DIM, K, Q, NN = 512, 32, 8, 16, 10
N_CLUSTERS = 16

GOLDEN_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tests", "golden", "serving_golden.npz")

#: the pinned configurations: name -> (corpus space, build/server kwargs)
CASES = {
    "flat_zen": dict(space="euclid", metric="euclidean", index="flat"),
    "flat_lwb": dict(space="euclid", metric="euclidean", index="flat",
                     mode="lwb"),
    "ivf_exact": dict(space="euclid", metric="euclidean", index="ivf",
                      nprobe=N_CLUSTERS),
    "ivf_probe4": dict(space="euclid", metric="euclidean", index="ivf",
                       nprobe=4),
    "flat_int8": dict(space="euclid", metric="euclidean", index="flat",
                      storage="int8"),
    "ivf_int8": dict(space="euclid", metric="euclidean", index="ivf",
                     storage="int8", nprobe=N_CLUSTERS),
    "flat_rerank": dict(space="euclid", metric="euclidean", index="flat",
                        rerank_factor=4),
    "flat_jsd": dict(space="jsd", metric="jsd", index="flat",
                     rerank_factor=4),
    "ivf_qform": dict(space="euclid", metric="qform", index="ivf",
                      nprobe=N_CLUSTERS, rerank_factor=4),
    # product-quantised storage: codes + codebooks + fused LUT probe.
    # pq_m pinned (not left to the default) so the golden stays meaningful
    # if the default subspace heuristic ever changes.
    "ivf_pq": dict(space="euclid", metric="euclidean", index="ivf",
                   storage="pq", pq_m=2, nprobe=N_CLUSTERS),
    "ivf_pq_rerank": dict(space="euclid", metric="euclidean", index="ivf",
                          storage="pq", pq_m=2, nprobe=4, rerank_factor=4),
    # replica-served (repro.launch.replicate): the leader publishes,
    # churns (3 deletes + 3 upserts), republishes; the pinned bits are
    # what a hot-swapped **mmap'd replica** serves at the published
    # generation — with leader parity asserted in-case, this pins the
    # whole publish -> hot-swap -> serve path, not just the maths.
    "ivf_replica_served": dict(space="euclid", metric="euclidean",
                               index="ivf", nprobe=N_CLUSTERS,
                               replica=True),
}

#: pivot-selection golden: chosen pivot row ids per strategy over the
#: euclid corpus — pins ``core.pivots`` end to end (witness subsample,
#: distance matrix, greedy/stochastic selection)
PIVOT_KEY_SEED = 7

#: baseline-reducer golden: reduced query coordinates of the coordinate
#: baselines (pca / rp / lmds) at a paper-regime k, plus the per-query
#: recall@10 of zen and pca on an isotropic gaussian corpus — the regime
#: where the paper's ordering claim (zen above pca at low k) holds, pinned
#: so a baseline refactor can neither shift the coordinates nor silently
#: flip the ordering.
BASELINE_K = 4
BASELINE_NN = 10
BASELINE_KEY_SEED = 19


def pivot_golden(arrays: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    from repro.core.pivots import PIVOT_STRATEGIES, pivot_ids

    with _force_x32():
        corpus = jax.numpy.asarray(arrays["corpus_euclid"])
        return {
            f"pivots_{strategy}_ids": np.asarray(
                pivot_ids(corpus, K, jax.random.PRNGKey(PIVOT_KEY_SEED),
                          strategy=strategy), np.int32)
            for strategy in PIVOT_STRATEGIES
        }


def baseline_golden(arrays: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    from repro.core import make_reducer
    from repro.core import metrics as metrics_lib

    with _force_x32():
        corpus = jax.numpy.asarray(arrays["corpus_gauss"])
        queries = jax.numpy.asarray(arrays["queries_gauss"])
        truth = np.argsort(np.asarray(
            metrics_lib.euclidean_pdist(queries, corpus)), 1)[:, :BASELINE_NN]
        key = jax.random.PRNGKey(BASELINE_KEY_SEED)
        out: Dict[str, np.ndarray] = {}
        for i, name in enumerate(("zen", "pca", "rp", "lmds")):
            r = make_reducer(name, BASELINE_K).fit(
                corpus, key=jax.random.fold_in(key, i))
            Xr, Qr = r.transform(corpus), r.transform(queries)
            if name != "zen":  # zen coords are covered by the serving cases
                out[f"baseline_{name}_coords"] = np.asarray(Qr, np.float32)
            pred = np.argsort(np.asarray(r.pdist(Qr, Xr)), 1)[:, :BASELINE_NN]
            out[f"baseline_recall_{name}"] = np.asarray(
                [len(set(truth[q]) & set(pred[q])) / BASELINE_NN
                 for q in range(truth.shape[0])], np.float32)
        if (out["baseline_recall_zen"].mean()
                < out["baseline_recall_pca"].mean()):
            raise AssertionError(
                "baseline golden would pin zen below pca on the isotropic "
                "corpus — the paper's low-k ordering claim is violated")
        return out


def _spaces() -> Dict[str, np.ndarray]:
    """Fixed-seed corpus/query pairs per metric domain."""
    with _force_x32():
        return _spaces_x32()


def _spaces_x32() -> Dict[str, np.ndarray]:
    key = jax.random.PRNGKey(1234)
    return {
        "corpus_euclid": np.asarray(
            syn.manifold_space(key, N, DIM, DIM // 4), np.float32),
        "queries_euclid": np.asarray(
            syn.manifold_space(jax.random.fold_in(key, 1), Q, DIM, DIM // 4),
            np.float32),
        # probability vectors: the jsd metric's natural domain
        "corpus_jsd": np.asarray(
            syn.probability_space(jax.random.fold_in(key, 2), N, DIM,
                                  DIM // 4), np.float32),
        "queries_jsd": np.asarray(
            syn.probability_space(jax.random.fold_in(key, 3), Q, DIM,
                                  DIM // 4), np.float32),
        # isotropic full-rank gaussians: the baseline-reducer golden's
        # domain (zen's favourable regime — no low-rank structure for
        # PCA to exploit)
        "corpus_gauss": np.asarray(
            syn.gaussian_space(jax.random.fold_in(key, 4), N, DIM),
            np.float32),
        "queries_gauss": np.asarray(
            syn.gaussian_space(jax.random.fold_in(key, 5), Q, DIM),
            np.float32),
    }


def run_case(name: str, arrays: Dict[str, np.ndarray]):
    """(distances, ids) of one pinned configuration over the stored data."""
    with _force_x32():
        return _run_case_x32(name, arrays)


def _run_case_x32(name: str, arrays: Dict[str, np.ndarray]):
    cfg = dict(CASES[name])
    space = cfg.pop("space")
    replica = cfg.pop("replica", False)
    corpus = np.asarray(arrays[f"corpus_{space}"])
    queries = np.asarray(arrays[f"queries_{space}"])
    build_kw = dict(
        metric=cfg.pop("metric"), index=cfg.pop("index"),
        storage=cfg.pop("storage", "float32"),
        pq_m=cfg.pop("pq_m", None),
        key=jax.random.PRNGKey(7),
    )
    if build_kw["index"] == "ivf":
        build_kw["n_clusters"] = N_CLUSTERS
    index = build_index(jax.numpy.asarray(corpus), K, **build_kw)
    server = ZenServer(index, **cfg)
    if replica:
        return _replica_serve_x32(server, queries)
    d, ids = server.query(jax.numpy.asarray(queries), NN)
    return np.asarray(d, np.float32), np.asarray(ids, np.int32)


def _replica_serve_x32(server: ZenServer, queries: np.ndarray):
    """Leader publish -> churn -> republish -> replica mmap hot-swap -> query.

    The returned bits come from the *replica*; leader parity is asserted
    here so a regenerated golden can never silently pin a divergence
    between the two serving paths.
    """
    import tempfile

    from repro.launch.replicate import IndexLeader, QueryReplica

    with tempfile.TemporaryDirectory(prefix="zen-golden-replica-") as root:
        leader = IndexLeader(server, root, keep=4)
        leader.publish()
        rep = QueryReplica(root, mmap=True)
        assert rep.poll() and rep.generation == 0
        leader.delete([3, 4, 5])                       # generation 1
        fresh = np.asarray(
            syn.manifold_space(jax.random.PRNGKey(4242), 3, DIM, DIM // 4),
            np.float32)
        leader.upsert([N + 1, N + 2, N + 3], fresh)    # generation 2
        leader.publish()
        assert rep.poll() and rep.generation == leader.generation == 2
        d, ids = rep.query(queries, NN)
        d_leader, ids_leader = server.query(queries, NN, direct=True)
        if not (np.array_equal(np.asarray(d), np.asarray(d_leader))
                and np.array_equal(np.asarray(ids), np.asarray(ids_leader))):
            raise AssertionError(
                "replica-served golden diverged from the leader")
        return np.asarray(d, np.float32), np.asarray(ids, np.int32)


def build_golden() -> Dict[str, np.ndarray]:
    """All golden arrays: the corpora plus every case's expected output."""
    arrays = _spaces()
    for name in CASES:
        d, ids = run_case(name, arrays)
        arrays[f"{name}_d"] = d
        arrays[f"{name}_ids"] = ids
    arrays.update(pivot_golden(arrays))
    arrays.update(baseline_golden(arrays))
    return arrays


def main() -> None:
    arrays = build_golden()
    os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
    np.savez(GOLDEN_PATH, **arrays)
    size = os.path.getsize(GOLDEN_PATH)
    print(f"wrote {GOLDEN_PATH} ({size / 1024:.1f} KiB, "
          f"{len(arrays)} arrays, {len(CASES)} cases)")


if __name__ == "__main__":
    main()
