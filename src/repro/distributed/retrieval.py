"""Sharded nSimplex-Zen retrieval: per-shard streaming (or clustered IVF)
top-k + on-mesh ring merge.

For indexes too large for one device, the reduced (N, k) coordinate matrix is
row-sharded over a mesh axis. Each device runs the streaming fused top-k
(``kernels.ops.zen_topk``) over its local shard — never materialising a
(Q, N_shard) matrix — and emits its best-k candidates with *global* row ids
(local id + shard offset, derived from ``lax.axis_index`` inside shard_map).

The per-shard candidate lists are merged *inside* shard_map with a ring of
``lax.ppermute`` hops: every device forwards the candidate buffer it received
on the previous hop to its ring successor and folds the incoming candidates
into its running top-k, so after ``size(axis) - 1`` hops each device has seen
every shard's candidates. Merge traffic is O(Q·k) per hop — no
O(n_shards · k) host gather, and no host round-trip at all. The fold selects
by the lexicographic key ``(distance, global id)``, so every device converges
to the *same* replicated result regardless of the order candidates arrived
in, and equal-distance ties break toward the lower global id exactly like the
single-device dense/streaming paths.

``sharded_ivf_probe`` runs the clustered variant under the same scaffolding:
each device probes its local slice of the packed inverted-list tiles
(``kernels.ops.ivf_probe``) with a replicated per-query probe list; tile ids
are already global and padding rows are masked inside the probe
(id == -1 -> +inf), so the merge needs no padding compensation.

Both entry points accept an optional per-shard ``alive`` mask (degraded-shard
serving, see ``distributed.fault``): a dead shard's candidates are forced to
(+inf, -1) before the ring, so queries keep answering from the surviving
shards with reduced recall instead of raising.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kernel_ops

Array = jax.Array


def _lex_topk(d: Array, ids: Array, k: int) -> Tuple[Array, Array]:
    """Smallest-k columns of (Q, w) candidates by the (distance, id) key.

    The id tie-break makes the selection canonical: any permutation of the
    candidate columns yields the same output, which is what lets every ring
    participant converge to an identical replicated top-k.
    """
    order = jnp.lexsort((ids, d), axis=-1)[..., :k]
    return (jnp.take_along_axis(d, order, axis=-1),
            jnp.take_along_axis(ids, order, axis=-1))


def _ring_merge(
    d: Array, ids: Array, n_neighbors: int, mesh, axis_names: Tuple[str, ...]
) -> Tuple[Array, Array]:
    """Merge per-shard (Q, k) candidates into a replicated global top-k.

    Runs inside shard_map. Along each sharded mesh axis in turn, every
    device forwards the buffer it received on the previous hop to its ring
    successor (so the *original* per-shard candidate sets circulate, O(Q·k)
    per hop) and folds the incoming buffer into its running best. For a
    multi-axis sharding the rings compose: the first axis' ring leaves every
    device of an axis group holding the group's merged top-k, which the next
    axis' ring then circulates.
    """
    best_d, best_i = _lex_topk(d, ids, n_neighbors)
    for a in axis_names:
        size = mesh.shape[a]
        if size == 1:
            continue
        perm = [(i, (i + 1) % size) for i in range(size)]
        recv_d, recv_i = best_d, best_i
        for _ in range(size - 1):
            recv_d = jax.lax.ppermute(recv_d, a, perm)
            recv_i = jax.lax.ppermute(recv_i, a, perm)
            best_d, best_i = _lex_topk(
                jnp.concatenate([best_d, recv_d], axis=1),
                jnp.concatenate([best_i, recv_i], axis=1),
                n_neighbors,
            )
    return best_d, best_i


def _apply_alive_mask(d: Array, ids: Array, alive_local) -> Tuple[Array, Array]:
    """Force a dead shard's local candidates to (+inf, -1) before the ring."""
    ok = alive_local[0]
    return (jnp.where(ok, d, jnp.inf),
            jnp.where(ok, ids, jnp.int32(-1)))


def sharded_knn_search(
    queries: Array,
    index: Array,
    n_neighbors: int = 10,
    mode: str = "zen",
    *,
    mesh,
    axis: Optional[Union[str, Tuple[str, ...]]] = None,
    chunk: int = 4096,
    force_kernel: bool = False,
    n_valid: Optional[int] = None,
    scales: Optional[Array] = None,
    alive: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Top-k of ``queries`` in a row-sharded ``index`` over ``mesh``.

    Args:
      queries: (Q, k) projected queries, replicated to every device.
      index:   (N, k) projected index, row-sharded over ``axis``; stored
               f32, bf16 or int8 (``kernels.quantize``).
      mesh:    the device mesh.
      axis:    mesh axis name (or tuple of names) the rows are sharded over;
               defaults to all mesh axes.
      chunk:   streaming chunk for the per-shard scan fallback off-TPU.
      force_kernel: run the Pallas kernel in interpret mode off-TPU.
      n_valid: number of real index rows when ``index`` was pre-padded to a
               shard-divisible length (e.g. by ``build_index``); trailing
               rows are treated as padding. Defaults to all rows.
      scales:  (N, 1) f32 per-row dequant scales when ``index`` is int8,
               sharded like the index rows; each shard dequantises its own
               tiles inside the streaming kernel.
      alive:   (n_shards,) bool, linearised in ``axis`` order; a False shard
               contributes nothing (degraded serving). Defaults to all-alive.

    Returns:
      (distances, indices), each (Q, n_neighbors), ascending distance, with
      indices referring to rows of the *global* index.
    """
    axis_names = resolve_axis_names(mesh, axis)
    n_shards = math.prod(mesh.shape[a] for a in axis_names)

    n = index.shape[0] if n_valid is None else n_valid
    n_neighbors = min(n_neighbors, n)
    if index.shape[0] % n_shards:
        shard_rows = -(-index.shape[0] // n_shards)  # ceil
        pad = shard_rows * n_shards - index.shape[0]
        index = jnp.pad(
            index, ((0, pad), (0, 0))
        )  # zero rows, never returned (see k_fetch below)
        if scales is not None:
            scales = jnp.pad(scales, ((0, pad), (0, 0)))
    else:  # pre-padded (or evenly divisible) index: no O(N) copy per call
        shard_rows = index.shape[0] // n_shards
    # Padding rows sit at the estimator distance of the origin, so they can
    # win local top-k slots from real candidates before the global-id mask
    # runs. All padding lives in the trailing shard(s): fetching that many
    # extra local candidates guarantees the true top-k survives the merge.
    n_pad = shard_rows * n_shards - n
    k_fetch = min(shard_rows, n_neighbors + min(n_pad, shard_rows))
    return _sharded_topk(
        queries, index, scales, alive, n=n, shard_rows=shard_rows,
        k_fetch=k_fetch, n_neighbors=n_neighbors, mode=mode, mesh=mesh,
        axis_names=axis_names, chunk=chunk, force_kernel=force_kernel,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n", "shard_rows", "k_fetch", "n_neighbors", "mode", "mesh",
        "axis_names", "chunk", "force_kernel",
    ),
)
def _sharded_topk(
    queries: Array,
    index: Array,
    scales: Optional[Array],
    alive: Optional[Array],
    *,
    n: int,
    shard_rows: int,
    k_fetch: int,
    n_neighbors: int,
    mode: str,
    mesh,
    axis_names: Tuple[str, ...],
    chunk: int,
    force_kernel: bool,
) -> Tuple[Array, Array]:
    shard_axes = axis_names if len(axis_names) > 1 else axis_names[0]

    def local_topk(q, x, *rest):
        # x: (shard_rows, kdim) — this device's shard
        rest = list(rest)
        s = rest.pop(0) if scales is not None else None
        al = rest.pop(0) if alive is not None else None
        off = jnp.int32(0)
        for a in axis_names:  # linearised shard position on the (sub)mesh
            off = off * mesh.shape[a] + jax.lax.axis_index(a)
        d, ids = kernel_ops.zen_topk(
            q, x, k_fetch, mode, scales=s,
            force_kernel=force_kernel, chunk=chunk
        )
        gids = ids + off * shard_rows
        pad = gids >= n  # padded tail rows never reach the merge
        d = jnp.where(pad, jnp.inf, d)
        gids = jnp.where(pad, jnp.int32(-1), gids)
        if al is not None:
            d, gids = _apply_alive_mask(d, gids, al)
        if k_fetch < n_neighbors:  # tiny shard: widen to the merge width
            fill = n_neighbors - k_fetch
            d = jnp.pad(d, ((0, 0), (0, fill)), constant_values=jnp.inf)
            gids = jnp.pad(gids, ((0, 0), (0, fill)), constant_values=-1)
        return _ring_merge(d, gids, n_neighbors, mesh, axis_names)

    in_specs = [P(), P(shard_axes, None)]
    operands = [queries, index]
    if scales is not None:
        in_specs.append(P(shard_axes, None))
        operands.append(scales)
    if alive is not None:
        in_specs.append(P(shard_axes))
        operands.append(alive)
    # the ring leaves every device holding the same merged top-k, so the
    # outputs are replicated (check_vma can't prove it through ppermute)
    return jax.shard_map(
        local_topk,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), P()),
        check_vma=False,
    )(*operands)


def resolve_axis_names(
    mesh, axis: Optional[Union[str, Tuple[str, ...]]]
) -> Tuple[str, ...]:
    """Normalise an ``axis`` argument: None -> all mesh axes, str -> 1-tuple."""
    if axis is None:
        return tuple(mesh.axis_names)
    if isinstance(axis, str):
        return (axis,)
    return tuple(axis)


def host_rows(x: Array, n_valid: Optional[int] = None):
    """Gather a (possibly row-sharded) array to one host copy.

    Used by the index checkpointing path (``ZenServer.save``): snapshots
    store canonical unsharded rows so the device count becomes a load-time
    choice. ``n_valid`` strips the trailing shard-padding rows that
    ``shard_rows`` appended.
    """
    import numpy as np

    out = np.asarray(jax.device_get(x))
    return out if n_valid is None else out[:n_valid]


def shard_rows(
    x: Array,
    *,
    mesh,
    axis: Optional[Union[str, Tuple[str, ...]]] = None,
) -> Tuple[Array, int]:
    """Row-shard ``x`` over ``mesh``, zero-padding to a divisible row count.

    The per-shard-save / reshard-on-load counterpart of :func:`host_rows`:
    pads (N, ...) with zero rows to a multiple of the shard count and
    device_puts it with ``NamedSharding(mesh, P(axes, None, ...))``. Returns
    ``(sharded array, n_valid)`` where ``n_valid`` is the original N —
    pass it back to :func:`sharded_knn_search` so padded rows are masked.
    """
    from jax.sharding import NamedSharding

    axis_names = resolve_axis_names(mesh, axis)
    n_shards = math.prod(mesh.shape[a] for a in axis_names)
    n_valid = x.shape[0]
    pad = (-n_valid) % n_shards
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    rows = axis_names if len(axis_names) > 1 else axis_names[0]
    spec = P(rows, *([None] * (x.ndim - 1)))
    return jax.device_put(x, NamedSharding(mesh, spec)), n_valid


def sharded_ivf_probe(
    queries: Array,
    tile_coords: Array,
    tile_ids: Array,
    probes: Array,
    n_neighbors: int = 10,
    mode: str = "zen",
    *,
    mesh,
    axis: Optional[Union[str, Tuple[str, ...]]] = None,
    tiles_per_cluster: int,
    tile_scales: Optional[Array] = None,
    force_kernel: bool = False,
    alive: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Clustered top-k of ``queries`` in mesh-sharded inverted-list tiles.

    Args:
      queries:     (Q, k) projected queries, replicated to every device.
      tile_coords: (S*C*T, tile_rows, k) packed tiles, row-sharded over
                   ``axis`` — each device holds its own shard's (C*T, ...)
                   inverted lists (see ``index.ivf.ShardedIVFZenIndex``);
                   stored f32, bf16 or int8.
      tile_ids:    (S*C*T, tile_rows) int32 *global* row ids, -1 = padding.
      probes:      (Q, nprobe) int32 cluster ids, replicated (one global
                   coarse quantizer).
      tiles_per_cluster: T of the packed layout.
      tile_scales: (C, 1) f32 per-cluster int8 dequant scales, replicated
                   (the scales follow the *global* assignment, like the
                   centroids — every shard sees the same values).
      alive:       (n_shards,) bool, linearised in ``axis`` order; a False
                   shard's tiles are dropped from the merge (degraded
                   serving). Defaults to all-alive.

    Returns (distances, indices), each (Q, n_neighbors), ascending, with
    global indices; slots the probed clusters cannot fill are (+inf, -1).
    """
    axis_names = resolve_axis_names(mesh, axis)
    return _sharded_ivf_topk(
        queries, tile_coords, tile_ids, probes, tile_scales, alive,
        n_neighbors=n_neighbors, mode=mode, mesh=mesh,
        axis_names=axis_names, tiles_per_cluster=tiles_per_cluster,
        force_kernel=force_kernel,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_neighbors", "mode", "mesh", "axis_names", "tiles_per_cluster",
        "force_kernel",
    ),
)
def _sharded_ivf_topk(
    queries: Array,
    tile_coords: Array,
    tile_ids: Array,
    probes: Array,
    tile_scales: Optional[Array],
    alive: Optional[Array],
    *,
    n_neighbors: int,
    mode: str,
    mesh,
    axis_names: Tuple[str, ...],
    tiles_per_cluster: int,
    force_kernel: bool,
) -> Tuple[Array, Array]:
    shard_axes = axis_names if len(axis_names) > 1 else axis_names[0]

    def local_probe(q, tc, ti, pr, *rest):
        # tc: (C*T, tile_rows, k) — this device's inverted lists, global ids
        rest = list(rest)
        ts = rest.pop(0) if tile_scales is not None else None
        al = rest.pop(0) if alive is not None else None
        d, gids = kernel_ops.ivf_probe(
            q, tc, ti, pr, n_neighbors, mode,
            tiles_per_cluster=tiles_per_cluster,
            tile_scales=ts, force_kernel=force_kernel,
        )
        if al is not None:
            d, gids = _apply_alive_mask(d, gids, al)
        # local padding already carries (+inf, -1): no compensation needed
        return _ring_merge(d, gids, n_neighbors, mesh, axis_names)

    in_specs = [P(), P(shard_axes, None, None), P(shard_axes, None), P()]
    operands = [queries, tile_coords, tile_ids, probes]
    if tile_scales is not None:
        in_specs.append(P())  # replicated, like the probes
        operands.append(tile_scales)
    if alive is not None:
        in_specs.append(P(shard_axes))
        operands.append(alive)
    return jax.shard_map(
        local_probe,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), P()),
        check_vma=False,
    )(*operands)
