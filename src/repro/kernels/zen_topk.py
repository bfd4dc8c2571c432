"""Pallas TPU kernel: streaming fused Zen/Lwb/Upb top-k retrieval.

The serving hot path (paper §6) is "find the n nearest index rows to each
query under an estimator". The dense formulation materialises the full (Q, N)
estimator matrix and runs ``lax.top_k`` over it, so per-query memory grows
linearly with index size N. This kernel never materialises that matrix: the
grid is (Q/bq, N/bn) with ``dimension_semantics=("parallel", "arbitrary")`` —
each query block walks the index tiles sequentially, fusing the estimator
(same masked-matmul + rank-1 altitude correction as ``kernels/zen.py``) with a
running top-k held in VMEM scratch:

  best_d, best_i : (bq, kw) scratch, kw = n_neighbors rounded up to a lane
  per tile:        d = estimator(q_block, x_tile)          (bq, bn)
                   entrants = d < each row's n_neighbors-th best
                   merge = one sorted insertion per entrant of the block's
                           busiest row, at most n_neighbors rounds and none
                           for a tile without entrants  (== lax.top_k of
                           concat([best, d], axis=1))

Peak per-query state is therefore O(kw + bn) — one tile — independent of N.
Index row ids are derived in-register from the tile position (``j*bn + iota``)
so no id tensor is streamed either. Padded tail rows (N not a multiple of bn)
are masked to +inf before the merge; padded scratch lanes (kw > n_neighbors)
start at +inf and can never win.

The merge's cost follows the entrants, not ``n_neighbors``: once the
running best has settled, most tiles cost one compare and one count. A
small (bq, 1) output counts, per query row, the rounds run over the grid
(``return_rounds=True`` returns it).

``zen_topk_scan`` is the schedule-equivalent jnp fallback for CPU/GPU: a
``lax.scan`` over index chunks with a concat + top_k merge — XLA keeps
only one chunk of distances live, giving the same O(chunk) memory bound.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .scoring import MODE_IDS as _MODE
from .scoring import estimate_tile as _estimate_tile
from .scoring import merge_topk as _merge_topk
from .scoring import merge_topk_rounds as _merge_topk_rounds

Array = jax.Array


def _topk_kernel(
    q_ref,
    x_ref,
    *rest,
    n_index: int,
    n_index_blocks: int,
    n_keep: int,
    mode: int,
    has_scale: bool,
):
    # with quantised storage a (1, bn) per-row scale block rides along
    if has_scale:
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

    q = q_ref[...].astype(jnp.float32)  # (bq, k)
    xt = x_ref[...].astype(jnp.float32)  # (k, bn): rows on lanes
    scale = s_ref[...] if has_scale else None  # (1, bn) dequant factors
    d = _estimate_tile(q, xt, mode=mode, scale=scale)  # (bq, bn)

    bn = xt.shape[1]
    ids = j * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    d = jnp.where(ids < n_index, d, jnp.inf)  # mask padded tail rows

    bd_ref[...], bi_ref[...], rounds = _merge_topk_rounds(
        bd_ref[...], bi_ref[...], d, ids, n_keep
    )
    or_ref[...] += rounds

    @pl.when(j == n_index_blocks - 1)
    def _done():
        od_ref[...] = bd_ref[...]
        oi_ref[...] = bi_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("n_neighbors", "mode", "block_q", "block_n", "interpret",
                     "return_rounds"),
)
def zen_topk(
    queries: Array,
    index: Array,
    n_neighbors: int = 10,
    mode: str = "zen",
    *,
    scales: Optional[Array] = None,
    block_q: int = 256,
    block_n: int = 512,
    interpret: bool = False,
    return_rounds: bool = False,
) -> Tuple[Array, ...]:
    """Streaming top-k under an estimator: (Q, k) x (N, k) -> (Q, n), (Q, n).

    ``index`` may be stored quantised (bf16: just pass the narrow array;
    int8: also pass the (N, 1) per-row ``scales``) — the tile is dequantised
    in-register right after the VMEM load, so the f32 index never exists and
    DMA traffic stays at the storage width. The kernel streams the index
    transposed, (k, N) with rows on lanes, so a narrow k is not lane-padded.

    Returns (distances f32, indices int32), each (Q, n_neighbors), rows
    sorted ascending by distance. Never materialises a (Q, N) matrix.
    ``return_rounds`` adds a third element: the (Q,) int32 merge rounds run
    for each query over the whole scan (the kernel counts them either way).
    """
    q, kdim = queries.shape
    n, kdim2 = index.shape
    assert kdim == kdim2, (queries.shape, index.shape)
    assert n_neighbors > 0, n_neighbors
    n_neighbors = min(n_neighbors, n)  # clamp: only valid rows are returned
    bq = min(block_q, _rup(q, 8))
    bn = min(block_n, _rup(n, 128))
    kw = _rup(n_neighbors, 128)  # scratch lane width
    Qp, Np = _rup(q, bq), _rup(n, bn)
    Qpad = jnp.pad(queries, ((0, Qp - q), (0, 0)))
    Xt = jnp.pad(index.T, ((0, 0), (0, Np - n)))
    n_index_blocks = Np // bn

    in_specs = [
        pl.BlockSpec((bq, kdim), lambda i, j: (i, 0)),
        pl.BlockSpec((kdim, bn), lambda i, j: (0, j)),
    ]
    operands = [Qpad, Xt]
    if scales is not None:
        assert scales.shape == (n, 1), (scales.shape, n)
        # padded rows get scale 0: they dequantise to the origin and are
        # masked by the id bound below anyway
        operands.append(jnp.pad(scales.astype(jnp.float32).T,
                                ((0, 0), (0, Np - n))))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))

    out_d, out_i, rounds = pl.pallas_call(
        functools.partial(
            _topk_kernel,
            n_index=n,
            n_index_blocks=n_index_blocks,
            n_keep=n_neighbors,
            mode=_MODE[mode],
            has_scale=scales is not None,
        ),
        grid=(Qp // bq, n_index_blocks),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, kw), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, kw), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, kw), jnp.float32),
            jax.ShapeDtypeStruct((Qp, kw), jnp.int32),
            jax.ShapeDtypeStruct((Qp, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, kw), jnp.float32),
            pltpu.VMEM((bq, kw), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="nsimplex_zen_topk",
    )(*operands)
    out = out_d[:q, :n_neighbors], out_i[:q, :n_neighbors]
    return (*out, rounds[:q, 0]) if return_rounds else out


@functools.partial(
    jax.jit, static_argnames=("n_neighbors", "mode", "chunk")
)
def zen_topk_scan(
    queries: Array,
    index: Array,
    n_neighbors: int = 10,
    mode: str = "zen",
    *,
    scales: Optional[Array] = None,
    chunk: int = 4096,
) -> Tuple[Array, Array]:
    """Bounded-memory jnp fallback: fori_loop of dynamic index slices.

    Peak live distance state is one (Q, chunk) block + the (Q, n_neighbors)
    running best — flat in index size, matching the kernel's memory bound.
    The index is sliced in place (no padded copy): the final chunk is clamped
    back to ``n - chunk`` and its already-visited rows masked out, so no
    O(N) temporary is ever allocated. ``scales`` (N, 1) dequantises an int8
    index chunk-by-chunk (same contract as :func:`zen_topk`).
    """
    q, kdim = queries.shape
    n = index.shape[0]
    assert n_neighbors > 0, n_neighbors
    n_neighbors = min(n_neighbors, n)  # clamp: only valid rows are returned
    chunk = min(chunk, n)
    acc = jnp.promote_types(queries.dtype, jnp.float32)
    queries = queries.astype(acc)
    n_chunks = -(-n // chunk)  # ceil

    mode_i = _MODE[mode]
    qn = jnp.sum(queries * queries, axis=1, keepdims=True)  # (Q, 1)
    qa = queries[:, -1:]  # (Q, 1) altitudes

    def body(i, carry):
        best_d, best_i = carry
        start = jnp.minimum(i * chunk, n - chunk)  # clamp the tail chunk
        blk = jax.lax.dynamic_slice_in_dim(index, start, chunk, axis=0)
        blk = blk.astype(acc)
        if scales is not None:  # dequantise one chunk at a time
            blk = blk * jax.lax.dynamic_slice_in_dim(
                scales, start, chunk, axis=0).astype(acc)
        xn = jnp.sum(blk * blk, axis=1)  # (chunk,)
        dot = jnp.matmul(
            queries[:, :-1], blk[:, :-1].T, preferred_element_type=acc,
            precision=jax.lax.Precision.HIGHEST,
        )
        z2 = qn + xn[None, :] - 2.0 * dot
        if mode_i != 0:
            cross = 2.0 * qa * blk[:, -1][None, :]
            z2 = z2 - cross if mode_i == 1 else z2 + cross
        d = jnp.sqrt(jnp.maximum(z2, 0.0))
        ids = (start + jnp.arange(chunk, dtype=jnp.int32)).astype(jnp.int32)
        # a clamped tail revisits rows of the previous chunk: mask them out
        d = jnp.where(ids[None, :] >= i * chunk, d, jnp.inf)
        return _merge_topk(best_d, best_i, d, ids[None, :], n_neighbors)

    init = (
        jnp.full((q, n_neighbors), jnp.inf, acc),
        jnp.full((q, n_neighbors), -1, jnp.int32),
    )
    best_d, best_i = jax.lax.fori_loop(0, n_chunks, body, init)
    return best_d, best_i


def _rup(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
