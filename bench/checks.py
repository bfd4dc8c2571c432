"""The comparison that decides ``correct``.

Every answer served in a run is compared with the configuration's plain
reference, once the program's state is freed. Two numbers are compared,
each against a limit from the configuration file (``"correct"``):

``dist_gap`` (at most its limit)
    The widest gap, over every served (query, neighbour) slot, between the
    squared distance the server returned and the exact squared distance of
    that query to that id, as a share of |q|^2 + |x|^2 (the size of the
    terms a matmul-form distance cancels). The served distances come from
    the exact re-rank, so this holds the re-rank's arithmetic, the id
    mapping and the routing of each coalesced row back to its own caller
    to the reference. An empty slot (id -1) reads infinity.
``recall_at_10`` (at least its limit)
    Mean share of each answer's ids that are among the query's exact ten
    nearest rows. The approximate layers (projection, centroid ranking, the
    probe or scan kernel) lose some neighbours by design; the limit is the
    recall that the configuration states for the deployment.

Besides, an answer that never came, or a dispatch that raised, is not
correct; a request refused at admission is counted as failed.
"""
from __future__ import annotations

import numpy as np


def served(requests):
    """(pool rows (A,), distances (A, n), ids (A, n)) of answered rows."""
    ok = [r for r in requests if r.status == "ok"]
    if not ok:
        return (np.zeros((0,), np.int64), np.zeros((0, 1), np.float32),
                np.zeros((0, 1), np.int32))
    return (np.concatenate([r.rows for r in ok]),
            np.concatenate([r.d for r in ok]),
            np.concatenate([r.ids for r in ok]))


def readings(reference, pool: np.ndarray, corpus, rows, d, ids,
             n_neighbors: int = 10) -> dict:
    """The compared numbers for answers (``rows`` into ``pool``, ``d``,
    ``ids``), against ``reference`` on ``corpus``."""
    if rows.size == 0:
        return {"dist_gap": float("inf"), "recall_at_10": 0.0}
    uniq, inv = np.unique(rows, return_inverse=True)
    _, truth = reference.search(pool[uniq], corpus, n_neighbors)
    truth = truth[inv]
    k = min(n_neighbors, ids.shape[1])
    hits = (ids[:, :k, None] == truth[:, None, :]).any(-1).sum(1)
    d2_ref, scale = reference.sq_dist(pool[rows], np.asarray(corpus), ids)
    gap = np.abs(np.square(d.astype(np.float64)) - d2_ref) / scale
    gap = np.where((ids >= 0) & np.isfinite(d), gap, np.inf)
    return {"dist_gap": float(gap.max()),
            "recall_at_10": float(hits.mean() / n_neighbors)}


def judge(numbers: dict, limits: dict, requests) -> tuple:
    """(correct, checks): each number beside its limit, in print order."""
    counts = {s: sum(r.status == s for r in requests)
              for s in ("error", "unanswered")}
    checks = {
        "errors": {"value": counts["error"], "limit": 0},
        "unanswered": {"value": counts["unanswered"], "limit": 0},
        "dist_gap": {"value": numbers["dist_gap"],
                     "limit": limits["dist_gap_max"]},
        "recall_at_10": {"value": numbers["recall_at_10"],
                         "limit": limits["recall_at_10_min"]},
    }
    correct = (counts["error"] == 0 and counts["unanswered"] == 0
               and numbers["dist_gap"] <= limits["dist_gap_max"]
               and numbers["recall_at_10"] >= limits["recall_at_10_min"])
    return bool(correct), checks
