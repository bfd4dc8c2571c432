"""Pallas TPU kernel: fused IVF probe — gather probed cluster tiles, score,
keep a running top-k.

The clustered index (``repro.index.ivf``) stores each cluster's members in a
fixed number ``T`` of fixed-size row tiles:

  tile_coords : (C*T, tile_rows, k)   member apex coordinates
  tile_ids    : (C*T, tile_rows)      global row ids, -1 = padding

so the tiles of cluster ``c`` are the blocks ``c*T .. c*T+T-1`` and every
shape is static under jit regardless of the (data-dependent) cluster sizes.

Given per-query probe lists ``probes`` (Q, P) of cluster ids, the kernel runs
on a (Q, P*T) grid with ``probes`` as a *scalar-prefetch* operand: the block
index maps read ``probes[i, j // T] * T + j % T`` to DMA exactly the probed
tiles from HBM — un-probed clusters are never touched, which is what makes
the probe sublinear in index size. Each grid step fuses the Zen/Lwb/Upb
estimator over one tile (``kernels.scoring.estimate_tile`` — shared with the
brute-force ``zen_topk`` kernel) with the entrant merge
(``kernels.scoring.merge_topk_rounds``) into VMEM scratch; dead rows
(id == -1: tile padding *and* tombstoned deletes — the mutable-index path
reuses the same encoding, ``kernels.scoring.mask_invalid``) are masked to
+inf before the merge. Peak per-query state is O(kw + tile_rows),
independent of both index size and cluster-size skew. The merge's cost
follows the tile's entrants, the candidates that beat the query's current
k-th best: an all-padding tile, or one whose rows are all farther than the
running best, costs one compare and one count and no insertion round. A
(Q, 1, 1) output counts each query's rounds over the grid
(``return_rounds=True`` returns it).

Mosaic accepts a block only when its last two dims divide by (8, 128) or
equal the array's, so every per-query operand is a (n, 1, X) view with a
squeezed leading block dim, and the tiles are read as (C*T, k, tile_rows):
rows on lanes, k on sublanes — a layout bitcast of the stored tiles on TPU,
with no lane padding of a narrow k.

``ivf_probe_scan`` is the schedule-equivalent jnp fallback for CPU/GPU: a
``fori_loop`` over the same (probe, tile) steps, gathering one
(Q, tile_rows, k) block per step — the same flat memory bound.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .scoring import (
    MODE_IDS, estimate_rows, estimate_tile, lut_estimate_rows,
    lut_estimate_tile, mask_invalid, merge_topk, merge_topk_rounds,
)

Array = jax.Array


def _probe_kernel(
    probes_ref,  # scalar-prefetch (Q, P) — also consumed by the index maps
    q_ref,       # (1, k)
    x_ref,       # (k, tile_rows) — the probed tile, rows on lanes
    id_ref,      # (1, tile_rows)
    *rest,       # [s_ref (1, 1)] od_ref oi_ref or_ref + scratch bd_ref bi_ref
    n_steps: int,
    n_keep: int,
    mode: int,
    has_scale: bool,
):
    del probes_ref  # only the index maps need it
    if has_scale:  # the probed cluster's dequant scale rides along
        s_ref, od_ref, oi_ref, or_ref, bd_ref, bi_ref = rest
    else:
        od_ref, oi_ref, or_ref, bd_ref, bi_ref = rest
        s_ref = None
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        bd_ref[...] = jnp.full_like(bd_ref, jnp.inf)
        bi_ref[...] = jnp.full_like(bi_ref, -1)
        or_ref[...] = jnp.zeros_like(or_ref)

    q = q_ref[...].astype(jnp.float32)          # (1, k)
    xt = x_ref[...].astype(jnp.float32)         # (k, tile_rows)
    ids = id_ref[...]                           # (1, tile_rows)
    scale = s_ref[...] if has_scale else None   # (1, 1)
    d = estimate_tile(q, xt, mode=mode, scale=scale)  # (1, tile_rows)
    d = mask_invalid(d, ids)                    # padding + tombstones

    bd_ref[...], bi_ref[...], rounds = merge_topk_rounds(
        bd_ref[...], bi_ref[...], d, ids, n_keep)
    or_ref[...] += rounds

    @pl.when(j == n_steps - 1)
    def _done():
        od_ref[...] = bd_ref[...]
        oi_ref[...] = bi_ref[...]


def _row_spec(width: int) -> pl.BlockSpec:
    """One query's (1, width) row of a (Q, 1, width) view, per grid row."""
    return pl.BlockSpec((None, 1, width), lambda i, j, pref: (i, 0, 0))


def _probe_outputs(q: int, kw: int):
    """(out_specs, scratch_shapes, out_shape) of a per-query probe: the
    (Q, 1, kw) result views and the (Q, 1, 1) merge-round counts keep every
    block's last two dims equal to the array's."""
    return (
        [_row_spec(kw), _row_spec(kw), _row_spec(1)],
        [pltpu.VMEM((1, kw), jnp.float32), pltpu.VMEM((1, kw), jnp.int32)],
        [jax.ShapeDtypeStruct((q, 1, kw), jnp.float32),
         jax.ShapeDtypeStruct((q, 1, kw), jnp.int32),
         jax.ShapeDtypeStruct((q, 1, 1), jnp.int32)],
    )


def _probe_results(out_d, out_i, rounds, n_neighbors: int,
                   return_rounds: bool):
    """A probe's (Q, n_neighbors) results, and its (Q,) rounds if asked."""
    out = out_d[:, 0, :n_neighbors], out_i[:, 0, :n_neighbors]
    return (*out, rounds[:, 0, 0]) if return_rounds else out


@functools.partial(
    jax.jit,
    static_argnames=("n_neighbors", "mode", "tiles_per_cluster", "interpret",
                     "return_rounds"),
)
def ivf_probe(
    queries: Array,
    tile_coords: Array,
    tile_ids: Array,
    probes: Array,
    n_neighbors: int = 10,
    mode: str = "zen",
    *,
    tiles_per_cluster: int,
    tile_scales: Optional[Array] = None,
    interpret: bool = False,
    return_rounds: bool = False,
) -> Tuple[Array, ...]:
    """Clustered top-k probe: score only the tiles of the probed clusters.

    Args:
      queries:     (Q, k) projected queries.
      tile_coords: (C*T, tile_rows, k) packed cluster tiles — stored f32,
                   bf16 or int8 (``kernels.quantize``). The kernel reads
                   them as (C*T, k, tile_rows), rows on lanes.
      tile_ids:    (C*T, tile_rows) int32 global row ids, -1 = padding.
      probes:      (Q, P) int32 cluster ids to visit per query.
      tiles_per_cluster: T — tiles per cluster in the packed layout.
      tile_scales: (C, 1) f32 per-cluster symmetric scales when
                   ``tile_coords`` is int8; the probed cluster's scale is
                   DMA'd through the same prefetched index map as its tiles
                   and the dequant fuses into the estimator.

    Returns (distances f32, indices int32), each (Q, n_neighbors), rows
    ascending by distance; slots beyond the number of valid candidates in the
    probed clusters come back as (+inf, -1). ``return_rounds`` adds a third
    element: the (Q,) int32 merge rounds run for each query over its probe
    (the kernel counts them either way).
    """
    q, kdim = queries.shape
    ct, tile_rows, kdim2 = tile_coords.shape
    assert kdim == kdim2, (queries.shape, tile_coords.shape)
    assert tile_ids.shape == (ct, tile_rows), tile_ids.shape
    assert probes.shape[0] == q, (probes.shape, queries.shape)
    assert ct % tiles_per_cluster == 0, (ct, tiles_per_cluster)
    T = tiles_per_cluster
    n_steps = probes.shape[1] * T
    kw = _rup(n_neighbors, 128)  # scratch lane width

    def tile(i, j, pref):
        return (pref[i, j // T] * T + j % T, 0, 0)

    in_specs = [
        _row_spec(kdim),
        pl.BlockSpec((None, kdim, tile_rows), tile),
        pl.BlockSpec((None, 1, tile_rows), tile),
    ]
    operands = [
        queries.reshape(q, 1, kdim),
        jnp.swapaxes(tile_coords, 1, 2),
        tile_ids.reshape(ct, 1, tile_rows),
    ]
    if tile_scales is not None:
        assert tile_scales.shape == (ct // T, 1), (tile_scales.shape, ct, T)
        # the probed *cluster* id indexes the scales directly
        in_specs.append(pl.BlockSpec(
            (None, 1, 1), lambda i, j, pref: (pref[i, j // T], 0, 0)))
        operands.append(tile_scales.astype(jnp.float32).reshape(ct // T, 1, 1))

    out_specs, scratch, out_shape = _probe_outputs(q, kw)
    outs = pl.pallas_call(
        functools.partial(
            _probe_kernel, n_steps=n_steps, n_keep=n_neighbors,
            mode=MODE_IDS[mode], has_scale=tile_scales is not None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(q, n_steps), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="nsimplex_ivf_probe",
    )(probes.astype(jnp.int32), *operands)
    return _probe_results(*outs, n_neighbors, return_rounds)


@functools.partial(
    jax.jit, static_argnames=("n_neighbors", "mode", "tiles_per_cluster")
)
def ivf_probe_scan(
    queries: Array,
    tile_coords: Array,
    tile_ids: Array,
    probes: Array,
    n_neighbors: int = 10,
    mode: str = "zen",
    *,
    tiles_per_cluster: int,
    tile_scales: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Bounded-memory jnp fallback: fori_loop over (probe, tile) steps.

    Each step gathers one (Q, tile_rows, k) block of the probed clusters'
    tiles and merges into the running (Q, n_neighbors) best — peak temp
    memory is one tile per query, flat in index size and in cluster count.
    ``tile_scales`` (C, 1) dequantises int8 tiles one gathered block at a
    time (same contract as :func:`ivf_probe`).
    """
    q, kdim = queries.shape
    ct, tile_rows, _ = tile_coords.shape
    T = tiles_per_cluster
    assert ct % T == 0, (ct, T)
    n_steps = probes.shape[1] * T
    acc = jnp.promote_types(queries.dtype, jnp.float32)
    queries = queries.astype(acc)
    mode_i = MODE_IDS[mode]

    def body(j, carry):
        best_d, best_i = carry
        p, t = j // T, j % T
        c = jax.lax.dynamic_slice_in_dim(probes, p, 1, axis=1)[:, 0]
        b = c.astype(jnp.int32) * T + t             # (Q,) tile block ids
        blk = tile_coords[b].astype(acc)            # (Q, tile_rows, k)
        ids = tile_ids[b]                           # (Q, tile_rows)
        scale = None
        if tile_scales is not None:  # per-query probed-cluster scales
            scale = tile_scales[c.astype(jnp.int32)].astype(acc)[:, :, None]
        d = estimate_rows(queries, blk, mode=mode_i, scale=scale)
        d = mask_invalid(d, ids)                    # padding + tombstones
        return merge_topk(best_d, best_i, d, ids, n_neighbors)

    init = (
        jnp.full((q, n_neighbors), jnp.inf, acc),
        jnp.full((q, n_neighbors), -1, jnp.int32),
    )
    best_d, best_i = jax.lax.fori_loop(0, n_steps, body, init)
    return best_d.astype(jnp.float32), best_i


def _rup(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


# -- product-quantised probe ---------------------------------------------------
#
# Same schedule as the scalar probe above — (Q, P*T) grid, scalar-prefetched
# probe list, running top-k in VMEM scratch — but the streamed operand is the
# (C*T, tile_rows, M) uint8 *code* tiles (16-32x less DMA than f32 coords)
# and the estimator is an asymmetric-distance LUT gather: the per-(query,
# probed-cluster) (M, 256) tables built once by ``kernels.pq.build_luts``
# stay VMEM-resident per grid step while codes stream past. All estimator
# mode handling lives in the table construction, so the kernel body is
# mode-agnostic.


def _probe_pq_kernel(
    probes_ref,  # scalar-prefetch (Q, P)
    lut_ref,     # (M, E) — this (query, probe column)'s ADC table
    x_ref,       # (M, tile_rows) int32 — the probed code tile, transposed
    id_ref,      # (1, tile_rows)
    od_ref,
    oi_ref,
    or_ref,      # (1, 1) int32 merge rounds
    bd_ref,      # scratch (1, kw) f32
    bi_ref,      # scratch (1, kw) int32
    *,
    n_steps: int,
    n_keep: int,
):
    del probes_ref  # only the index maps need it
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        bd_ref[...] = jnp.full_like(bd_ref, jnp.inf)
        bi_ref[...] = jnp.full_like(bi_ref, -1)
        or_ref[...] = jnp.zeros_like(or_ref)

    ids = id_ref[...]                            # (1, tile_rows)
    d = lut_estimate_tile(lut_ref[...], x_ref[...])  # (1, tile_rows)
    d = mask_invalid(d, ids)                     # padding + tombstones

    bd_ref[...], bi_ref[...], rounds = merge_topk_rounds(
        bd_ref[...], bi_ref[...], d, ids, n_keep)
    or_ref[...] += rounds

    @pl.when(j == n_steps - 1)
    def _done():
        od_ref[...] = bd_ref[...]
        oi_ref[...] = bi_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("n_neighbors", "tiles_per_cluster", "interpret",
                     "return_rounds"),
)
def ivf_probe_pq(
    tile_codes: Array,
    tile_ids: Array,
    probes: Array,
    luts: Array,
    n_neighbors: int = 10,
    *,
    tiles_per_cluster: int,
    interpret: bool = False,
    return_rounds: bool = False,
) -> Tuple[Array, ...]:
    """Clustered top-k probe over PQ code tiles with fused LUT scoring.

    Args:
      tile_codes: (C*T, tile_rows, M) uint8 packed member codes
                  (``kernels.pq``); cluster ``c`` owns blocks
                  ``c*T .. c*T+T-1`` exactly like the scalar layout. The
                  kernel reads them widened to int32 and transposed,
                  (C*T, M, tile_rows): Mosaic has no uint8 vector compare.
      tile_ids:   (C*T, tile_rows) int32 global row ids, -1 = padding.
      probes:     (Q, P) int32 cluster ids to visit per query.
      luts:       (Q, P, M, E) f32 ADC tables (``pq.build_luts``) — the
                  table of probe column ``p`` rides to the grid step through
                  a plain block index map (no prefetch: ``p = j // T`` is
                  grid-computable) and stays in VMEM for that cluster's T
                  tiles.
      tiles_per_cluster: T.

    Returns (distances f32, indices int32), each (Q, n_neighbors),
    ascending; unfilled slots are (+inf, -1). Distances equal the estimator
    on the *decoded* member coordinates — the mode folding happened in the
    tables. ``return_rounds`` adds the (Q,) merge rounds, as in
    :func:`ivf_probe`.
    """
    ct, tile_rows, m = tile_codes.shape
    q, n_probe = probes.shape
    assert ct % tiles_per_cluster == 0, (ct, tiles_per_cluster)
    assert luts.shape[:2] == (q, n_probe), (luts.shape, probes.shape)
    assert luts.shape[2] == m, (luts.shape, tile_codes.shape)
    assert tile_ids.shape == (ct, tile_rows), tile_ids.shape
    T = tiles_per_cluster
    n_steps = n_probe * T
    e = luts.shape[3]
    kw = _rup(n_neighbors, 128)
    # (Q, P, M, E) -> (Q*P, M, E): 3D blocks with a grid-computed leading
    # index keep the block maps rank-uniform for Mosaic
    luts3 = luts.astype(jnp.float32).reshape(q * n_probe, m, e)

    def tile(i, j, pref):
        return (pref[i, j // T] * T + j % T, 0, 0)

    out_specs, scratch, out_shape = _probe_outputs(q, kw)
    outs = pl.pallas_call(
        functools.partial(_probe_pq_kernel, n_steps=n_steps,
                          n_keep=n_neighbors),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(q, n_steps),
            in_specs=[
                pl.BlockSpec((None, m, e),
                             lambda i, j, pref: (i * n_probe + j // T, 0, 0)),
                pl.BlockSpec((None, m, tile_rows), tile),
                pl.BlockSpec((None, 1, tile_rows), tile),
            ],
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="nsimplex_ivf_probe_pq",
    )(probes.astype(jnp.int32), luts3,
      jnp.swapaxes(tile_codes, 1, 2).astype(jnp.int32),
      tile_ids.reshape(ct, 1, tile_rows))
    return _probe_results(*outs, n_neighbors, return_rounds)


@functools.partial(
    jax.jit, static_argnames=("n_neighbors", "tiles_per_cluster")
)
def ivf_probe_pq_scan(
    tile_codes: Array,
    tile_ids: Array,
    probes: Array,
    luts: Array,
    n_neighbors: int = 10,
    *,
    tiles_per_cluster: int,
) -> Tuple[Array, Array]:
    """Bounded-memory jnp fallback for the PQ probe: fori_loop over
    (probe, tile) steps, gathering one (Q, tile_rows, M) code block and its
    (Q, M, E) tables per step (same contract as :func:`ivf_probe_pq`)."""
    q = probes.shape[0]
    ct, tile_rows, _ = tile_codes.shape
    T = tiles_per_cluster
    assert ct % T == 0, (ct, T)
    n_steps = probes.shape[1] * T
    luts = luts.astype(jnp.float32)

    def body(j, carry):
        best_d, best_i = carry
        p, t = j // T, j % T
        c = jax.lax.dynamic_slice_in_dim(probes, p, 1, axis=1)[:, 0]
        b = c.astype(jnp.int32) * T + t              # (Q,) tile block ids
        blk = tile_codes[b]                          # (Q, tile_rows, M)
        ids = tile_ids[b]                            # (Q, tile_rows)
        lut_p = jax.lax.dynamic_slice_in_dim(
            luts, p, 1, axis=1)[:, 0]                # (Q, M, E)
        d = lut_estimate_rows(lut_p, blk)
        d = mask_invalid(d, ids)
        return merge_topk(best_d, best_i, d, ids, n_neighbors)

    init = (
        jnp.full((q, n_neighbors), jnp.inf, jnp.float32),
        jnp.full((q, n_neighbors), -1, jnp.int32),
    )
    best_d, best_i = jax.lax.fori_loop(0, n_steps, body, init)
    return best_d, best_i
