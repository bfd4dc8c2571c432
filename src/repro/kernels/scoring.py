"""Shared Zen/Lwb/Upb scoring + running-top-k helpers for streaming kernels.

Both streaming retrieval kernels — the brute-force ``zen_topk`` walk over the
whole index and the clustered ``ivf_probe`` walk over probed inverted-list
tiles — fuse the same two inner loops:

  1. estimator distances between a query block and one index tile
     (masked-last-column matmul + rank-1 altitude correction, paper §4.1);
  2. a merge of that tile's distances into a running per-query best-k,
     kept in VMEM scratch on TPU.

This module is that shared inner loop, factored out so the two kernels (and
their jnp scan fallbacks) cannot drift apart numerically. ``estimate_tile``
operates on transposed (k, rows) tiles as seen inside a Pallas kernel body;
``estimate_rows`` is the batched-gather variant used by the IVF scan fallback
where every query gathers its *own* (rows, k) tile. The scans merge with
``merge_topk`` (concat + ``lax.top_k``); Mosaic cannot lower ``top_k``, so
the kernels merge with ``merge_topk_rounds``, which selects exactly the same
entries in the same order. Its cost follows the tile's *entrants*, the
candidates that beat their row's current k-th best: one compare and one
count per tile, then one insertion round per entrant of the busiest row
(at most k), and none once the running best has settled.

Both accept an optional ``scale`` for quantised index tiles
(``kernels.quantize``): the tile is multiplied by its symmetric int8 scale
in-register, immediately after the cast to f32 — the dequantised tile never
exists outside the kernel body, so VMEM/DMA traffic stays at the storage
width while every norm/matmul keeps accumulating in float32. bf16 tiles need
no scale at all: the existing ``astype(float32)`` is their dequantisation.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

#: estimator name -> static integer id used inside kernel bodies
MODE_IDS = {"zen": 0, "lwb": 1, "upb": 2}


def estimate_tile(
    q: Array, xt: Array, *, mode: int, scale: Optional[Array] = None,
) -> Array:
    """Fused estimator distances for one (bq, k) x (k, bn) tile, f32.

    The index tile arrives *transposed* — coordinates on sublanes, rows on
    lanes — so a k=16 tile is lane-dense and the estimator is one plain
    (bq, k) @ (k, bn) matmul. ``mode`` is the static id from
    :data:`MODE_IDS`. ``scale`` (scalar or (1, bn), broadcastable over
    ``xt``) dequantises an int8 tile on the fly; ``xt`` must already be cast
    to f32 by the caller in that case. The matmul runs at
    ``Precision.HIGHEST``: the estimator subtracts two large norms, so a
    single bf16 pass would swamp near-neighbour distances.
    """
    if scale is not None:
        xt = xt * scale
    k = q.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    keep = (col < k - 1).astype(jnp.float32)  # drop the altitude column
    nq = jnp.sum(q * q, axis=1, keepdims=True)  # (bq, 1) full norms
    nx = jnp.sum(xt * xt, axis=0, keepdims=True)  # (1, bn)
    dot = jax.lax.dot_general(
        q * keep,
        xt,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # altitude zeroed on one side only — enough to drop it
    z2 = nq + nx - 2.0 * dot
    if mode != 0:
        qa = jnp.sum(q * (1.0 - keep), axis=1, keepdims=True)  # (bq, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)
        xa = jnp.sum(xt * (row == k - 1).astype(jnp.float32), axis=0,
                     keepdims=True)  # (1, bn)
        cross = 2.0 * qa * xa
        z2 = z2 - cross if mode == 1 else z2 + cross
    return jnp.sqrt(jnp.maximum(z2, 0.0))


def estimate_rows(
    q: Array, blk: Array, *, mode: int, scale: Optional[Array] = None
) -> Array:
    """Estimator distances between queries (Q, k) and per-query row tiles
    (Q, R, k) — the gathered-inverted-list shape of the IVF scan fallback.

    Unpadded widths (no lane masking); returns (Q, R) in the accumulation
    dtype of ``q``. ``scale`` (broadcastable over ``blk``, e.g. the
    (Q, 1, 1) per-cluster scales of the gathered tiles) dequantises int8
    tiles in place; ``blk`` must already be in the dtype of ``q`` then.
    """
    if scale is not None:
        blk = blk * scale
    qn = jnp.sum(q * q, axis=1, keepdims=True)  # (Q, 1)
    xn = jnp.sum(blk * blk, axis=-1)  # (Q, R)
    dot = jnp.einsum(
        "qk,qrk->qr", q[:, :-1], blk[..., :-1],
        preferred_element_type=q.dtype,
        precision=jax.lax.Precision.HIGHEST,
    )
    z2 = qn + xn - 2.0 * dot
    if mode != 0:
        cross = 2.0 * q[:, -1:] * blk[..., -1]
        z2 = z2 - cross if mode == 1 else z2 + cross
    return jnp.sqrt(jnp.maximum(z2, 0.0))


def lut_estimate_tile(lut: Array, codes_t: Array) -> Array:
    """LUT-gather estimator over one PQ code tile, as seen in a kernel body.

    Args:
      lut:     (M, E) f32 per-(query, cluster) ADC table (``kernels.pq
               .build_luts``); ``sum_m lut[m, code[m]]`` is the squared
               estimator distance (mode folding already applied).
      codes_t: (M, rows) int32 codes of one tile, transposed (rows on
               lanes).

    Returns (1, rows) f32 distances. The gather is expressed per subspace
    as a one-hot contraction — a (E, rows) ``code == iota`` mask multiplied
    by that subspace's (1, E) table row — which lowers to an MXU matmul on
    TPU (Pallas has no native vector gather from VMEM) and is exact: each
    row's result is the f32 sum of exactly M table entries, the rest
    multiply by 0.
    """
    m, rows = codes_t.shape
    e = lut.shape[1]
    entry = jax.lax.broadcasted_iota(jnp.int32, (e, rows), 0)
    z2 = jnp.zeros((1, rows), jnp.float32)
    for s in range(m):  # M is small and static
        hot = (codes_t[s:s + 1, :] == entry).astype(jnp.float32)  # (E, rows)
        z2 = z2 + jax.lax.dot_general(
            lut[s:s + 1, :], hot,
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    return jnp.sqrt(jnp.maximum(z2, 0.0))


def lut_estimate_rows(luts: Array, codes: Array) -> Array:
    """Batched LUT-gather for the PQ scan fallback: per-query code blocks.

    Args:
      luts:  (Q, M, E) f32 ADC tables of the probed cluster per query.
      codes: (Q, R, M) integer codes of the gathered tiles.

    Returns (Q, R) f32 distances — a plain ``take_along_axis`` gather, the
    jnp mirror of :func:`lut_estimate_tile`'s one-hot contraction.
    """
    idx = codes.astype(jnp.int32).transpose(0, 2, 1)    # (Q, M, R)
    g = jnp.take_along_axis(luts.astype(jnp.float32), idx, axis=2)
    z2 = jnp.sum(g, axis=1)                             # (Q, R)
    return jnp.sqrt(jnp.maximum(z2, 0.0))


def mask_invalid(d: Array, ids: Array) -> Array:
    """+inf out candidate slots whose id is negative.

    One predicate covers every kind of dead slot in the retrieval layouts —
    never-used tile padding, shard padding, *and* tombstoned (deleted) rows —
    because all of them are encoded as id ``-1``. Keeping the mask here means
    the Pallas kernels, the scan fallbacks, and the host-side id remapping in
    serving all agree on what "not a real candidate" means. Broadcasts:
    ``d`` (Q, r) against ``ids`` (Q, r) or (1, r).
    """
    return jnp.where(ids >= 0, d, jnp.inf)


def merge_topk(
    best_d: Array, best_i: Array, d: Array, ids: Array, k: int
) -> Tuple[Array, Array]:
    """Merge tile distances into the running best-k: concat + ``lax.top_k``.

    ``best_d``/``best_i`` are (Q, w) running state, ``d``/``ids`` the new
    (Q, r) candidates (``ids`` may be (1, r) and is broadcast). Returns the
    new (Q, k) state, ascending by distance.
    """
    cat_d = jnp.concatenate([best_d, d], axis=1)
    cat_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, d.shape)], axis=1)
    neg, pos = jax.lax.top_k(-cat_d, k)
    return -neg, jnp.take_along_axis(cat_i, pos, axis=1)


def merge_topk_rounds(
    best_d: Array, best_i: Array, d: Array, ids: Array, k: int
) -> Tuple[Array, Array, Array]:
    """Kernel-body twin of :func:`merge_topk` whose work follows the tile's
    entrants, built from lane reductions (Mosaic has no ``top_k``).

    A candidate is an *entrant* if its distance is strictly below its row's
    current k-th best: the state lanes precede the tile lanes in
    :func:`merge_topk`'s concatenation, so a tie at the k-th distance keeps
    the state entry, and a masked (+inf) candidate never enters. One compare
    and one lane sum count each row's entrants; the merge then runs
    ``c = min(k, most entrants of any row)`` rounds, none when no row has
    one. Round ``r`` takes each row's ``r``-th smallest entrant in
    (distance, lane) order and inserts it into the sorted state at
    ``count(state <= v)``, after every equal entry, shifting the later
    lanes by one. Only a row's k smallest entrants can survive, so
    ``c`` rounds suffice. The selection equals ``lax.top_k`` on the negated
    concatenation exactly: ascending, ties to the lowest position, and
    unfilled slots keep the (+inf, -1) state lanes.

    ``best_d``/``best_i`` are (Q, w) running state, ``w >= k``, ascending,
    lanes ``>= k`` (+inf, -1); ``d``/``ids`` the new (Q, r) candidates
    (``ids`` may be (1, r)). Returns the new (Q, w) state in the same form
    and ``c``, the int32 number of rounds run.
    """
    pos = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, best_d.shape, 1)
    kth = jnp.min(jnp.where(lane == k - 1, best_d, jnp.inf), axis=1,
                  keepdims=True)  # a lane slice at k-1 is off the tiling
    entrant = d < kth
    counts = jnp.sum(entrant, axis=1, keepdims=True, dtype=jnp.int32)
    rounds = jnp.minimum(jnp.max(counts), k)
    past_end = jnp.int32(d.shape[1])
    no_id = jnp.int32(jnp.iinfo(jnp.int32).min)
    rows = best_d.shape[0]

    def insert(r, carry):
        prev_d, prev_p, out_d, out_i = carry
        after = entrant & ((d > prev_d) | ((d == prev_d) & (pos > prev_p)))
        v = jnp.min(jnp.where(after, d, jnp.inf), axis=1, keepdims=True)
        p = jnp.min(jnp.where(after & (d == v), pos, past_end),
                    axis=1, keepdims=True)
        # exactly one lane sits at position p; a row out of entrants has
        # v = +inf, which lands past every lane and changes nothing
        i = jnp.max(jnp.where(pos == p, ids, no_id), axis=1, keepdims=True)
        at = jnp.sum(out_d <= v, axis=1, keepdims=True, dtype=jnp.int32)
        out_d = jnp.where(lane < at, out_d,
                          jnp.where(lane == at, v, jnp.roll(out_d, 1, 1)))
        out_i = jnp.where(lane < at, out_i,
                          jnp.where(lane == at, i, jnp.roll(out_i, 1, 1)))
        return (v, p, jnp.where(lane < k, out_d, jnp.inf),
                jnp.where(lane < k, out_i, -1))

    init = (
        jnp.full((rows, 1), -jnp.inf, jnp.float32),
        jnp.full((rows, 1), -1, jnp.int32),
        best_d,
        best_i,
    )
    # int32 bounds keep the round counter int32 when x64 is enabled
    _, _, out_d, out_i = jax.lax.fori_loop(
        jnp.int32(0), rounds, insert, init)
    return out_d, out_i, rounds
