"""nSimplex-Zen retrieval serving — the paper's technique as a production
feature (DESIGN.md §3).

Offline:  ``build_index`` fits the transform on a witness sample, projects the
          corpus to (N, k) apex coordinates (one pdist + one triangular solve,
          both kernel paths), and optionally row-shards the reduced index over
          a mesh.
Online:   ``ZenServer.query`` projects a query batch (k reference distances)
          and scores it with the *streaming fused top-k* path
          (``kernels.ops.zen_topk``): the estimator and a running top-k are
          fused over index tiles, so per-query peak memory is one tile —
          O(chunk + n_neighbors), flat in index size — instead of the dense
          (Q, N) estimator matrix. Sharded indexes run the same streaming
          search per device shard (``distributed.sharded_knn_search``) and
          merge the (Q, n_shards * k) candidate pool host-side. An optional
          exact re-rank of the candidate pool with true distances follows
          (paper [50]'s deployment pattern).

``build_index(..., index="ivf")`` swaps the flat scan for the *clustered* IVF
path (``repro.index``): a k-means coarse quantizer over the apex coordinates
plus padded inverted-list tiles, so each query scores only its ``nprobe``
nearest clusters — sublinear in N — at a recall knob the server exposes as
``ZenServer(nprobe=...)``. ``nprobe = n_clusters`` recovers the flat result.

Mutable corpus + persistence
----------------------------
The corpus is not frozen at build time. ``ZenServer.upsert(ids, vectors)``
projects new objects with the *already-fitted* transform (the paper's core
property: projection needs only distances to the k references, so it extends
to unseen data indefinitely) and inserts them into the live index;
``ZenServer.delete(ids)`` tombstones rows. Flat indexes tombstone by
rewriting the row's external id to ``-1`` and its coordinates to a far
sentinel (the row can never win a top-k slot); IVF indexes tombstone through
the inverted-list id padding (``repro.index.ivf``). ``maybe_compact`` checks
the churn thresholds and repacks when crossed. ``ZenServer.save``/``load``
persist the whole serving state — transform, coordinates/inverted lists, id
map, corpus — as a versioned snapshot (``repro.checkpoint.index_io``) that
restores bit-identically, including onto a different device count.

Serving frontend
----------------
``ZenServer(frontend=True)`` (CLI: ``--frontend [--max-batch N --cache
ROWS]``) attaches the ``repro.serving`` micro-batching scheduler: many
small concurrent callers coalesce into one shape-bucketed kernel dispatch
per tick, with an LRU result cache invalidated by the index ``generation``
counter and reject-on-full backpressure. Even without the frontend, every
query dispatches at bucketed shapes (power-of-two Q, fixed ``n_neighbors``
menu) so the jit cache stays a handful of entries — and so scheduled,
cached and direct responses are bit-identical (``tests/test_frontend.py``).

CLI:  PYTHONPATH=src python -m repro.launch.serve --n 20000 --dim \
      256 --k 16 --queries 64 [--index ivf --nprobe 8] \
      [--checkpoint /tmp/zen.ckpt] [--frontend --cache 1024]
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import time
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint import index_io
from repro.core import metrics as metrics_lib
from repro.core import zen as zen_lib
from repro.core import pivots as pivots_lib
from repro.core.projection import NSimplexTransform
from repro.core.simplex import BaseSimplex
from repro.distributed import retrieval as retrieval_lib
from repro.kernels import quantize as quant
from repro.kernels.scoring import mask_invalid
from repro.serving import (
    DEFAULT_NEIGHBOR_MENU, MicroBatchScheduler, bucket_neighbors, bucket_q,
)
from repro.serving import tracing
from repro.serving.stats import LATENCY_WINDOW

Array = jax.Array

#: snapshot kind tag for full serving state (transform + index + corpus)
SERVER_SNAPSHOT_KIND = "zen-server"
#: coordinate sentinel written into tombstoned flat rows — far enough that a
#: dead row can never win a top-k slot, small enough that f32 squared norms
#: stay finite (1e15^2 * k << f32 max)
_DEAD_COORD = 1.0e15
#: flat capacity growth quantum — amortises jit recompiles of the search
_GROW_ROWS = 4096


@dataclasses.dataclass
class ZenIndex:
    """Serving-side index state: fitted transform + searchable coordinates.

    Attributes:
      transform: fitted ``NSimplexTransform`` (projects corpus and queries).
      coords:    (cap, k) apex coordinates (possibly row-sharded). For a
                 mutable flat index, rows beyond the live set (tombstones,
                 growth slack) hold a far sentinel and never win a search.
                 ``None`` for IVF indexes restored from a checkpoint (the
                 inverted lists are the source of truth).
      corpus:    original vectors for exact re-ranking, indexed by external
                 id (row ``i`` holds the vector of id ``i``); optional.
      mesh:      device mesh when the index is row-sharded.
      n_valid:   number of live rows; ``None`` means every row of ``coords``
                 is live (immutable fast path).
      ivf:       ``IVFZenIndex`` / ``ShardedIVFZenIndex`` when built with
                 ``index="ivf"``.
      row_ids:   (cap,) int32 external id per flat row, ``-1`` for dead rows;
                 ``None`` while the flat index is untouched (ids == row
                 positions). Materialised by the first upsert/delete.
      n_deleted: flat tombstones accumulated since the last build/compact —
                 drives ``needs_compact`` (growth slack is *not* counted:
                 compacting it away would defeat the grow-in-quanta
                 recompile amortisation).
      storage:   resident dtype of the flat ``coords``, one of
                 ``kernels.quantize.SCALAR_STORAGE_DTYPES`` (the IVF path
                 additionally takes "pq"); the search kernels dequantise in
                 register, accumulation stays f32.
      coord_scales: (cap, 1) f32 per-row symmetric int8 scales, or ``None``
                 for f32/bf16 storage. Per *row* — a scale rides with its
                 row through mutation, compaction and resharding, so
                 untouched rows are never requantised, and the far-sentinel
                 dead rows get their own (huge) scale without poisoning
                 live neighbours.
      generation: monotonic churn counter — every upsert/delete/compact
                 that changes the searchable state bumps it, and the
                 serving frontend's result cache keys on it, so cached
                 responses can never outlive the index state that produced
                 them (``repro.serving.cache``).
    """

    transform: NSimplexTransform
    coords: Optional[Array]  # (cap, k) apex coordinates (possibly sharded)
    corpus: Optional[Array]  # original vectors for re-ranking (optional)
    mesh: Optional[object] = None  # device mesh when coords are row-sharded
    n_valid: Optional[int] = None  # live rows when coords hold dead slots
    ivf: Optional[object] = None   # IVFZenIndex / ShardedIVFZenIndex
    row_ids: Optional[Array] = None  # (cap,) int32 external ids, -1 = dead
    n_deleted: int = 0  # flat tombstones since the last build/compact
    storage: str = "float32"  # resident dtype of the flat coords
    coord_scales: Optional[Array] = None  # (cap, 1) int8 dequant scales
    generation: int = 0  # churn counter; invalidates frontend cache entries

    @property
    def size(self) -> int:
        """Number of live (searchable) rows."""
        if self.ivf is not None:
            return self.ivf.size
        if self.n_valid is not None:
            return self.n_valid
        return self.coords.shape[0]

    # -- storage helpers (flat path) ----------------------------------------
    def _host_coord_state(self):
        """Host copies of the raw coord values (+ per-row scales or None)."""
        vals = np.asarray(self.coords).copy()
        scl = (None if self.coord_scales is None
               else np.asarray(self.coord_scales, np.float32).copy())
        return vals, scl

    @staticmethod
    def _write_rows(vals, scl, where, new_f32):
        """Write f32 rows into the raw storage arrays at ``where``.

        int8 rows are quantised with their own fresh per-row scales;
        f32/bf16 rows are plain (casting) assignments. Only the written
        rows change — every other row keeps its exact stored bytes.
        """
        if scl is None:
            vals[where] = new_f32
        else:
            v, s = quant.encode_rows(new_f32, "int8")
            vals[where] = v
            scl[where] = s

    @staticmethod
    def _kill_rows(vals, scl, where):
        """Stamp the far-sentinel dead-row pattern at ``where``."""
        if scl is None:
            vals[where] = _DEAD_COORD
        else:  # 127 * (sentinel / 127) dequantises to the exact sentinel
            vals[where] = np.int8(127)
            scl[where] = np.float32(_DEAD_COORD / 127.0)

    # -- mutation (control plane; returns a new ZenIndex) -------------------
    def delete(self, ids: Sequence[int]) -> "ZenIndex":
        """Tombstone the given external ids; unknown ids are ignored."""
        self._check_not_sharded()
        if self.ivf is not None:
            self._check_not_tiered()
            new_ivf = self.ivf.delete(ids)
            if new_ivf is self.ivf:  # nothing removed: state unchanged
                return self
            return dataclasses.replace(self, ivf=new_ivf,
                                       generation=self.generation + 1)
        self._check_mutable()
        row_ids = self._host_row_ids()
        coords, scl = self._host_coord_state()
        mask = (row_ids >= 0) & np.isin(row_ids, np.asarray(ids, np.int64))
        if not mask.any():
            return self
        row_ids[mask] = -1
        self._kill_rows(coords, scl, mask)
        return dataclasses.replace(
            self,
            coords=jnp.asarray(coords),
            row_ids=jnp.asarray(row_ids.astype(np.int32)),
            n_valid=self.size - int(mask.sum()),
            n_deleted=self.n_deleted + int(mask.sum()),
            coord_scales=None if scl is None else jnp.asarray(scl),
            generation=self.generation + 1,
        )

    def upsert(self, ids: Sequence[int], coords_new: Array) -> "ZenIndex":
        """Insert (or replace) projected rows keyed by external id.

        Args:
          ids:        (B,) non-negative external ids; existing ids are
                      replaced in place, duplicate ids in the batch keep the
                      last occurrence.
          coords_new: (B, k) apex coordinates of the new rows.

        New rows reuse tombstoned slots first; when the capacity is
        exhausted the flat array grows by multiples of ``_GROW_ROWS``
        (growth slack rows are dead until used, so searches between growths
        compile once).
        """
        self._check_not_sharded()
        if self.ivf is not None:
            self._check_not_tiered()
            new_ivf = self.ivf.upsert(ids, coords_new)
            if new_ivf is self.ivf:  # empty batch: state unchanged
                return self
            return dataclasses.replace(self, ivf=new_ivf,
                                       generation=self.generation + 1)
        self._check_mutable()
        from repro.index.ivf import _check_ids, _dedupe_last_wins

        ids_np = np.asarray(ids, np.int64).ravel()
        _check_ids(ids_np)
        if ids_np.size == 0:
            return self
        new = np.asarray(coords_new, np.float32).reshape(ids_np.size, -1)
        ids_np, new = _dedupe_last_wins(ids_np, new)

        row_ids = self._host_row_ids()
        coords, scl = self._host_coord_state()
        # replace rows whose external id already exists
        sorter = np.argsort(row_ids, kind="stable")
        pos = np.searchsorted(row_ids, ids_np, sorter=sorter)
        pos = np.clip(pos, 0, row_ids.size - 1)
        hit = row_ids[sorter[pos]] == ids_np
        self._write_rows(coords, scl, sorter[pos[hit]], new[hit])
        ids_np, new = ids_np[~hit], new[~hit]
        n_live = self.size + int(ids_np.size)
        reclaimed = 0
        if ids_np.size:
            free = np.flatnonzero(row_ids < 0)[: ids_np.size]
            reclaimed = int(free.size)  # dead slots this batch refills
            if free.size < ids_np.size:  # grow capacity in fixed quanta
                deficit = int(ids_np.size - free.size)
                grow = -(-deficit // _GROW_ROWS) * _GROW_ROWS
                cap = row_ids.size
                row_ids = np.concatenate(
                    [row_ids, np.full(grow, -1, np.int64)])
                dead = np.empty((grow, coords.shape[1]), coords.dtype)
                coords = np.concatenate([coords, dead])
                if scl is not None:
                    scl = np.concatenate(
                        [scl, np.empty((grow, 1), np.float32)])
                self._kill_rows(coords, scl, slice(cap, cap + grow))
                free = np.concatenate([free, cap + np.arange(deficit)])
            row_ids[free] = ids_np
            self._write_rows(coords, scl, free, new)
        return dataclasses.replace(
            self,
            coords=jnp.asarray(coords),
            row_ids=jnp.asarray(row_ids.astype(np.int32)),
            n_valid=n_live,
            n_deleted=max(0, self.n_deleted - reclaimed),
            coord_scales=None if scl is None else jnp.asarray(scl),
            generation=self.generation + 1,
        )

    def compact(self, **kw) -> "ZenIndex":
        """Repack the live rows, dropping tombstones and growth slack.

        For IVF indexes this forwards to ``IVFZenIndex.compact`` (pass
        ``recluster=True`` to refit the quantizer); for flat indexes it
        rewrites ``coords``/``row_ids`` to the live rows only.
        """
        self._check_not_sharded()
        if self.ivf is not None:
            self._check_not_tiered()
            new_ivf = self.ivf.compact(**kw)
            if new_ivf is self.ivf:  # nothing to reclaim: state unchanged
                return self
            return dataclasses.replace(self, ivf=new_ivf,
                                       generation=self.generation + 1)
        self._check_mutable()
        if self.row_ids is None:
            return self
        row_ids = self._host_row_ids()
        live = row_ids >= 0
        return dataclasses.replace(
            self,
            # per-row scales ride with their rows: slicing is the whole
            # repack, no dequantise/requantise cycle
            coords=jnp.asarray(np.asarray(self.coords)[live]),
            row_ids=jnp.asarray(row_ids[live].astype(np.int32)),
            n_valid=int(live.sum()),
            n_deleted=0,
            coord_scales=(None if self.coord_scales is None else
                          jnp.asarray(np.asarray(self.coord_scales)[live])),
            generation=self.generation + 1,
        )

    def needs_compact(self, **kw) -> bool:
        """True when churn degraded the layout enough to repack.

        Flat indexes compare *tombstones* (deletes since the last
        build/compact) against the once-live rows — the same
        ``max_tombstone_ratio`` knob as ``IVFZenIndex.needs_compact``.
        Growth slack from upserts is deliberately not counted: it is what
        amortises search recompiles between capacity growths.
        """
        if self.mesh is not None:
            return False  # sharded indexes are immutable: nothing to compact
        if self.ivf is not None:
            if self._is_tiered():
                return False  # serve-only: no churn to compact away
            return self.ivf.needs_compact(**kw)
        max_ratio = kw.get("max_tombstone_ratio", 0.2)
        return (self.n_deleted / max(self.size + self.n_deleted, 1)
                > max_ratio)

    def _host_row_ids(self) -> np.ndarray:
        if self.row_ids is None:
            return np.arange(self.coords.shape[0], dtype=np.int64)
        return np.asarray(self.row_ids).astype(np.int64).copy()

    def _check_not_sharded(self):
        if self.mesh is not None:
            raise NotImplementedError(
                "mutating a mesh-sharded index in place is not supported: "
                "churn the single-host index, save(), and reload onto the "
                "mesh (resharding happens at load)"
            )

    def _check_mutable(self):
        self._check_not_sharded()
        if self.coords is None:
            raise ValueError("index has no flat coordinates to mutate")

    def _is_tiered(self) -> bool:
        from repro.index.ivf import TieredIVFZenIndex

        return isinstance(self.ivf, TieredIVFZenIndex)

    def _check_not_tiered(self):
        if self._is_tiered():
            raise NotImplementedError(
                "a tiered (host-offloaded) index is serve-only: churn the "
                "resident index and re-offload (build_index(..., "
                "offload=True) or TieredIVFZenIndex.from_index)"
            )


def build_index(
    corpus: Array,
    k: int,
    *,
    metric: str = "euclidean",
    key: Optional[jax.Array] = None,
    mesh=None,
    keep_corpus: bool = True,
    index: str = "flat",
    n_clusters: Optional[int] = None,
    tile_rows: int = 128,
    kmeans_iters: int = 15,
    storage: str = "float32",
    pq_m: Optional[int] = None,
    pivots: str = "random",
    offload: bool = False,
    hot_clusters: Optional[int] = None,
    offload_shards: int = 1,
    prefetch_cols: int = 2,
) -> ZenIndex:
    """Fit on the corpus (witness = corpus sample) and project every row.

    ``index="flat"`` keeps the (N, k) coordinates for the streaming scan;
    ``index="ivf"`` additionally fits a k-means coarse quantizer
    (``n_clusters`` defaults to ~4*sqrt(N)) and packs the inverted-list
    tiles so the server probes only a few clusters per query. With a
    ``mesh``, both variants shard rows (flat coordinates or inverted lists)
    over all mesh axes.

    ``storage`` picks the resident dtype of the searchable coordinates, one
    of ``kernels.quantize.STORAGE_DTYPES`` — "bfloat16" (half the bytes,
    plain cast), "int8" (quarter, symmetric scales: per row for the flat
    layout, per cluster for IVF tiles), or "pq" (IVF only: each member
    stores ``pq_m`` uint8 product-quantiser code bytes, ``kernels.pq``).
    The projection, quantizer fit and query math all stay f32; only what
    the probe kernels stream gets narrower.

    ``pivots`` picks the base-simplex selection strategy
    (``core.pivots.PIVOT_STRATEGIES``): the paper's "random" redraw loop by
    default, or a principled alternative ("kmeanspp", "farthest_first",
    "maxvol") — one fit-time knob that lifts estimator quality for every
    later query.

    ``offload=True`` (IVF only) drops the packed inverted-list tiles to a
    host-resident pool after the build (``index.ivf.TieredIVFZenIndex``):
    only the centroids, scales and the ``hot_clusters`` highest-traffic
    clusters stay device-resident, cold probes stream up in
    ``prefetch_cols``-wide double-buffered chunks, and the clusters are
    partitioned over ``offload_shards`` logical shards for degraded serving
    (``ZenServer.enable_fault_tolerance``). The offloaded index is
    serve-only: upsert/delete/compact raise.
    """
    if index not in ("flat", "ivf"):
        raise ValueError(f"index must be 'flat' or 'ivf', got {index!r}")
    if offload and index != "ivf":
        raise ValueError("offload=True requires index='ivf' (the tiered "
                         "tile store offloads inverted-list tiles)")
    if offload and mesh is not None:
        raise ValueError(
            "offload=True and mesh are mutually exclusive: the tiered "
            "store already splits device/host residency on one host; "
            "degraded serving over its logical shards replaces mesh "
            "sharding (offload_shards=...)")
    quant.check_storage(storage)
    if storage == "pq" and index != "ivf":
        raise ValueError(
            "storage='pq' is IVF-only (codes are per-cluster residuals); "
            "the flat layout takes "
            + "/".join(quant.SCALAR_STORAGE_DTYPES))
    if storage == "pq" and mesh is not None:
        raise NotImplementedError(
            "storage='pq' is single-host for now; drop the mesh or pick "
            "one of " + "/".join(quant.SCALAR_STORAGE_DTYPES))
    key = key if key is not None else jax.random.PRNGKey(0)
    tr = pivots_lib.select_references(
        corpus, k, key, metric=metric, strategy=pivots)
    coords = tr.transform(corpus)
    n = coords.shape[0]
    ivf = None
    coord_scales = None
    if index == "ivf":
        from repro.index import IVFZenIndex, ShardedIVFZenIndex

        n_clusters = n_clusters or max(1, min(n, int(round(4 * n ** 0.5))))
        builder = (
            functools.partial(ShardedIVFZenIndex.build, mesh=mesh)
            if mesh is not None else IVFZenIndex.build
        )
        if mesh is None:
            builder = functools.partial(builder, pq_m=pq_m)
        ivf = builder(
            coords, n_clusters, tile_rows=tile_rows, n_iters=kmeans_iters,
            key=jax.random.fold_in(key, 7), storage=storage,
        )
        if offload:
            from repro.index.ivf import TieredIVFZenIndex

            ivf = TieredIVFZenIndex.from_index(
                ivf, hot_clusters=hot_clusters,
                n_shards=offload_shards, prefetch_cols=prefetch_cols)
    elif storage != "float32":
        values, scales = quant.encode_rows(
            np.asarray(coords, np.float32), storage)
        coords = jnp.asarray(values)
        coord_scales = None if scales is None else jnp.asarray(scales)
    n_valid = None
    if mesh is not None and ivf is None:
        # pad once to a shard-divisible row count so every query batch skips
        # the O(N) re-pad; the search masks rows >= n_valid
        coords, n_valid = retrieval_lib.shard_rows(coords, mesh=mesh)
        if coord_scales is not None:
            coord_scales, _ = retrieval_lib.shard_rows(coord_scales,
                                                       mesh=mesh)
    return ZenIndex(transform=tr, coords=coords,
                    corpus=corpus if keep_corpus else None, mesh=mesh,
                    n_valid=n_valid, ivf=ivf, storage=storage,
                    coord_scales=coord_scales)


def load_index_snapshot(
    directory: str,
    *,
    mesh=None,
    mmap: bool = False,
    pool: Optional[str] = None,
    pool_kw: Optional[dict] = None,
) -> Tuple[ZenIndex, dict]:
    """Load a :meth:`ZenServer.save` snapshot into a ``ZenIndex``.

    The index-owner / query-plane split of the replicated serving tier
    (``repro.launch.replicate``) hinges on this function being independent
    of any server object: a replica loads the published snapshot into a
    fresh ``ZenIndex`` and swaps it under its long-lived ``ZenServer``
    without touching the leader's state.

    Args:
      directory: snapshot directory (``SERVER_SNAPSHOT_KIND``).
      mesh:      optional device mesh to reshard onto (flat coordinates are
                 re-padded/re-sharded, IVF inverted lists re-packed).
      mmap:      memory-map the snapshot arrays read-only instead of
                 materialising host copies. Device-resident layouts still
                 copy onto the device, but the host never holds a second
                 materialised copy — and for the tiered ``pool`` path the
                 cold tiles are *served* straight off the mapped files.
      pool:      optional ``TILE_POOL_SNAPSHOT_KIND`` snapshot directory
                 (published next to the server snapshot by
                 ``replicate.IndexLeader``): the IVF tier is opened as a
                 serve-only ``TieredIVFZenIndex`` over that pool
                 (``load(mmap=...)``) instead of re-packing resident tiles
                 — the billion-row replica shape. IVF snapshots only.
      pool_kw:   extra ``TieredIVFZenIndex.load`` options (``hot_clusters``,
                 ``hot_fraction``, ``prefetch_cols``, ``n_shards``, ...).

    Returns ``(index, server_kw)``: the restored index (its ``generation``
    is the *published* one, not a fresh counter — frontend cache keys
    depend on it) and the saved server construction kwargs.

    Raises ``checkpoint.CheckpointFormatError`` for snapshots written by an
    incompatible format version or of a different kind.
    """
    arrays, meta = index_io.load_state(
        directory, expect_kind=SERVER_SNAPSHOT_KIND, mmap=mmap)
    base = BaseSimplex(
        chol=jnp.asarray(arrays["base_chol"]),
        diag_g=jnp.asarray(arrays["base_diag_g"]),
        d0=jnp.asarray(arrays["base_d0"]),
    )
    tr = NSimplexTransform(
        k=int(meta["k"]), metric=meta["metric"],
        jitter=float(meta["jitter"]), refs=jnp.asarray(arrays["refs"]),
        base=base,
    )
    corpus = (jnp.asarray(arrays["corpus"])
              if "corpus" in arrays else None)
    generation = int(meta.get("generation", 0))
    if pool is not None and meta["index"] != "ivf":
        raise ValueError(
            "pool=... serves the IVF tier from a tile-pool snapshot; this "
            "snapshot holds a flat index")
    if pool is not None and mesh is not None:
        raise ValueError("pool=... and mesh are mutually exclusive (the "
                         "tiered store is single-host)")
    if meta["index"] == "ivf":
        from repro.index import IVFZenIndex, ShardedIVFZenIndex

        storage = meta.get("storage", "float32")
        if pool is not None:
            from repro.index.ivf import TieredIVFZenIndex

            ivf = TieredIVFZenIndex.load(pool, mmap=mmap,
                                         **dict(pool_kw or {}))
            # the server snapshot's wrapper generation is authoritative —
            # a pool republished out of band must not fork the key space
            ivf.generation = generation
        else:
            members = (arrays["ivf_member_coords"],
                       arrays["ivf_member_ids"].astype(np.int64),
                       arrays["ivf_member_assign"].astype(np.int64))
            scales = arrays.get("ivf_cluster_scales")
            if mesh is not None:
                ivf = ShardedIVFZenIndex._from_members(
                    *members, jnp.asarray(arrays["ivf_centroids"]),
                    int(meta["n_clusters"]), int(meta["tile_rows"]),
                    mesh=mesh, storage=storage, scales=scales)
            else:
                coords_m, mids, massign = members
                ivf = IVFZenIndex.from_members(
                    coords_m, mids, massign,
                    jnp.asarray(arrays["ivf_centroids"]),
                    int(meta["n_clusters"]), int(meta["tile_rows"]),
                    storage=storage, scales=scales,
                    codebooks=arrays.get("ivf_pq_codebooks"),
                    generation=generation)
        index = ZenIndex(transform=tr, coords=None, corpus=corpus,
                         mesh=mesh, ivf=ivf, storage=storage,
                         generation=generation)
    else:
        coords = jnp.asarray(arrays["coords"])
        row_ids = jnp.asarray(arrays["row_ids"].astype(np.int32))
        storage = meta.get("storage", "float32")
        coord_scales = (jnp.asarray(arrays["coord_scales"])
                        if "coord_scales" in arrays else None)
        n_valid = None
        if mesh is not None:
            coords, n_valid = retrieval_lib.shard_rows(coords, mesh=mesh)
            pad = coords.shape[0] - row_ids.shape[0]
            if pad:  # shard-padding positions map to the dead id
                row_ids = jnp.concatenate(
                    [row_ids, jnp.full((pad,), -1, jnp.int32)])
            if coord_scales is not None:
                coord_scales, _ = retrieval_lib.shard_rows(
                    coord_scales, mesh=mesh)
        index = ZenIndex(transform=tr, coords=coords, corpus=corpus,
                         mesh=mesh, n_valid=n_valid, row_ids=row_ids,
                         storage=storage, coord_scales=coord_scales,
                         generation=generation)
    return index, dict(meta.get("server", {}))


class ZenServer:
    """Batched k-NN serving over a reduced index.

    The search path never materialises a (Q, N) estimator matrix: single-host
    indexes stream through ``core.zen.knn_search`` (fused Pallas kernel on
    TPU, bounded-memory scan elsewhere) once the index exceeds ``chunk`` rows;
    mesh-sharded indexes run the streaming search per shard and merge the
    per-shard candidates host-side. IVF-built indexes probe only the
    ``nprobe`` nearest clusters per query (``repro.index``) — sublinear in
    index size, with ``nprobe`` as the recall/latency knob.

    Shape-bucketed dispatch
    -----------------------
    Every query — frontend-scheduled or direct — is served at *bucketed*
    shapes: the row count is padded to a power-of-two Q bucket (floor 2)
    and ``n_neighbors`` is rounded up to the fixed width menu
    (``repro.serving.DEFAULT_NEIGHBOR_MENU``), then sliced back. The jit
    cache therefore holds one entry per (Q bucket, width) pair instead of
    one per caller shape, and — because results are row-wise bit-identical
    across bucketed batch shapes — a coalesced, padded, or cached response
    is bit-identical to the same query served alone.

    Frontend
    --------
    ``frontend=True`` attaches a ``repro.serving.MicroBatchScheduler``:
    ``query`` becomes a thin client that submits rows to the scheduler
    (coalescing across concurrent callers, LRU result caching with
    generation-based invalidation, reject-on-full backpressure) and blocks
    for its answer; ``query(..., direct=True)`` is the escape hatch that
    bypasses the scheduler on the old synchronous path.
    """

    def __init__(self, index: ZenIndex, *, mode: str = "zen",
                 rerank_factor: int = 0, chunk: int = 8192,
                 nprobe: int = 8, force_kernel: bool = False,
                 frontend: bool = False, max_batch: int = 64,
                 cache_size: int = 0, queue_limit: int = 4096,
                 tick_interval: float = 0.002,
                 neighbor_menu: Sequence[int] = DEFAULT_NEIGHBOR_MENU,
                 clock=None):
        self.index = index
        self.mode = mode
        self.rerank_factor = rerank_factor
        self.chunk = chunk
        self.nprobe = nprobe
        self.force_kernel = force_kernel
        self.neighbor_menu = tuple(neighbor_menu)
        self.max_batch = max_batch
        self.cache_size = cache_size
        self._stats = {"queries": 0, "batches": 0,
                       "upserts": 0, "deletes": 0}
        # seconds per query() call, newest LATENCY_WINDOW on a monotonic clock
        self._latency_s = collections.deque(maxlen=LATENCY_WINDOW)
        tracing.count_compiles()
        # fault tolerance (enable_fault_tolerance): liveness registry,
        # preemption guard, and the degraded state they currently imply
        self.heartbeats = None
        self.preemption = None
        self._snapshot_dir: Optional[str] = None
        self._ft_shards: Tuple[str, ...] = ()
        self._degraded: Tuple[int, ...] = ()
        self._alive_mask: Optional[Array] = None
        self.frontend: Optional[MicroBatchScheduler] = None
        if frontend:
            kw = {"clock": clock} if clock is not None else {}
            self.frontend = MicroBatchScheduler(
                self, max_batch=max_batch, cache_size=cache_size,
                queue_limit=queue_limit, tick_interval=tick_interval,
                neighbor_menu=self.neighbor_menu, **kw)

    # -- bucketed dispatch core ----------------------------------------------
    def _query_geometry(self, n_neighbors: int) -> Tuple[int, int]:
        """(n_bucket, fetch width) a request dispatches at.

        ``n_bucket`` is the menu-rounded output width; the fetch width is
        the menu-rounded candidate-pool width (``n_neighbors *
        rerank_factor`` when re-ranking). Shared with the scheduler so
        direct and coalesced dispatches — and their cache keys — agree.
        """
        n_bucket = bucket_neighbors(n_neighbors, self.neighbor_menu)
        width = bucket_neighbors(
            n_neighbors * max(self.rerank_factor, 1), self.neighbor_menu)
        return n_bucket, max(width, n_bucket)

    def _query_block(self, queries: Array, width: int, n_bucket: int,
                     index: Optional[ZenIndex] = None
                     ) -> Tuple[Array, Array]:
        """Serve one already-padded block at bucketed shapes.

        Args:
          queries:  (Qp, m) raw query rows, ``Qp`` a power-of-two bucket
                    (padding rows are copies of real rows; their results
                    are sliced off by the caller, never observed).
          width:    bucketed candidate fetch width.
          n_bucket: bucketed output width (<= ``width``).
          index:    the ``ZenIndex`` snapshot to serve from (defaults to
                    the current ``self.index``). The whole block is served
                    from this one snapshot — ``self.index`` is read exactly
                    once — so concurrent churn swapping the live index can
                    never mix two index states within one query (the
                    scheduler passes the snapshot it keyed its cache
                    entries on).

        Returns (distances, ids), each (Qp, n_bucket) — project, search,
        optional exact re-rank, external-id mapping, and the (+inf, -1)
        fill for slots the index cannot serve. Both the direct path and
        the frontend scheduler dispatch through here, which is what makes
        their results (and cache entries) interchangeable bit-for-bit.
        """
        index = index if index is not None else self.index
        queries = jnp.asarray(queries)
        if index.size == 0:  # fully-deleted index: all slots unfilled
            return (jnp.full((queries.shape[0], n_bucket), jnp.inf,
                             jnp.float32),
                    jnp.full((queries.shape[0], n_bucket), -1, jnp.int32))
        with tracing.span(tracing.PROJECT):
            qp = index.transform.transform(queries)
        n_fetch = min(width, index.size)
        if index.ivf is not None:
            # mesh-sharded IVF takes the device-resident alive mask; the
            # tiered store is instead masked up front (set_dead_shards)
            kw = ({"alive": self._alive_mask}
                  if self._alive_mask is not None and index.mesh is not None
                  else {})
            with tracing.span(tracing.SEARCH, index="ivf"):
                d, ids = index.ivf.search(
                    qp, n_neighbors=n_fetch,
                    nprobe=self.nprobe, mode=self.mode,
                    force_kernel=self.force_kernel, **kw,
                )
        elif index.mesh is not None:
            with tracing.span(tracing.SEARCH, index="sharded"):
                d, ids = retrieval_lib.sharded_knn_search(
                    qp, index.coords,
                    n_neighbors=n_fetch, mode=self.mode,
                    mesh=index.mesh, chunk=self.chunk,
                    force_kernel=self.force_kernel, n_valid=index.n_valid,
                    scales=index.coord_scales, alive=self._alive_mask,
                )
        else:
            with tracing.span(tracing.SEARCH, index="flat"):
                d, ids = zen_lib.knn_search(
                    qp, index.coords,
                    n_neighbors=n_fetch, mode=self.mode,
                    chunk=self.chunk if index.coords.shape[0] > self.chunk
                    else 0,
                    scales=index.coord_scales,
                    force_kernel=self.force_kernel,
                )
        if index.ivf is None:  # IVF search returns external ids itself
            with tracing.span(tracing.MAP_IDS):
                d, ids = self._map_row_ids(d, ids, index)
        if self.rerank_factor and index.corpus is not None:
            with tracing.span(tracing.RERANK):
                d, ids = self._rerank(queries, ids, n_bucket, index)
        else:
            d, ids = d[:, :n_bucket], ids[:, :n_bucket]
        if d.shape[1] < n_bucket:
            # fewer live rows than the bucket width: pad to the full bucket
            pad = n_bucket - d.shape[1]
            with tracing.span(tracing.MAP_IDS):
                d = jnp.pad(d, ((0, 0), (0, pad)), constant_values=jnp.inf)
                ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
        return d, ids

    def query(self, queries: Array, n_neighbors: int = 10, *,
              direct: bool = False) -> Tuple[Array, Array]:
        """Serve one batch: (Q, m) raw queries -> (distances, ids).

        Args:
          queries:     (Q, m) raw (un-projected) query vectors.
          n_neighbors: neighbours to return per query.
          direct:      bypass the frontend scheduler (when one is attached)
                       and serve synchronously on the calling thread — the
                       unbatched escape hatch. Results are bit-identical
                       either way.

        Returns (distances, ids), each (Q, n_neighbors), ascending distance.
        Ids are *external* ids (stable across churn and checkpoint reload);
        slots the index cannot fill come back as (+inf, -1).
        """
        t0 = time.perf_counter()
        self.on_tick()  # refresh shard liveness / pending preemption save
        queries = jnp.asarray(queries)
        n_rows = int(queries.shape[0])
        if (self.frontend is not None and not direct
                and n_rows <= self.frontend.queue_limit):
            # batches beyond queue_limit fall through to the direct path:
            # they are already far past any coalescing benefit, and a
            # permanent reject-on-full for them would masquerade as
            # transient overload
            handle = self.frontend.submit(queries, n_neighbors)
            if not self.frontend.running:
                # no ticker thread: drive the scheduler inline so the
                # single-threaded caller still gets coalescing + caching
                self.frontend.flush()
            d_np, ids_np = handle.result()
            d, ids = jnp.asarray(d_np), jnp.asarray(ids_np)
        elif n_rows == 0:
            d = jnp.full((0, n_neighbors), jnp.inf, jnp.float32)
            ids = jnp.full((0, n_neighbors), -1, jnp.int32)
        else:
            n_bucket, width = self._query_geometry(n_neighbors)
            if n_rows <= self.max_batch:
                qp_rows = bucket_q(n_rows)
            else:
                # beyond max_batch, power-of-two padding would waste up to
                # ~2x scan compute; round up to a max_batch multiple
                # instead (waste < max_batch rows, shapes still bucketed)
                qp_rows = -(-n_rows // self.max_batch) * self.max_batch
            if qp_rows > n_rows:  # pad with copies of a real row
                queries = jnp.concatenate([
                    queries,
                    jnp.broadcast_to(queries[:1],
                                     (qp_rows - n_rows, queries.shape[1])),
                ])
            d, ids = self._query_block(queries, width, n_bucket)
            d, ids = d[:n_rows, :n_neighbors], ids[:n_rows, :n_neighbors]
        self._stats["queries"] += n_rows
        self._stats["batches"] += 1
        self._latency_s.append(time.perf_counter() - t0)
        return d, ids

    def _map_row_ids(self, d: Array, ids: Array, index: ZenIndex
                     ) -> Tuple[Array, Array]:
        """Map flat row positions to external ids (churned/reloaded index).

        With ``row_ids`` unset the two id spaces coincide and this is a
        no-op. Tombstoned rows cannot win a slot (their coordinates are a
        far sentinel), but any dead id that sneaks into an under-filled
        result is masked to (+inf, -1) — the same contract as the IVF path.
        """
        if index.row_ids is None:
            return d, ids
        ext = jnp.take(index.row_ids, jnp.maximum(ids, 0), axis=0)
        ext = jnp.where(ids >= 0, ext, -1)
        return mask_invalid(d, ext), ext

    # -- mutable corpus lifecycle -------------------------------------------
    def upsert(self, ids: Sequence[int], vectors: Array) -> None:
        """Project and insert (or replace) raw vectors under external ids.

        The fitted transform projects the (B, m) batch — no refit, the
        paper's out-of-sample property — and the index absorbs the rows
        (``ZenIndex.upsert``). When the server keeps a re-rank corpus it is
        grown/overwritten at the same ids so exact re-ranking stays
        consistent with the reduced index.
        """
        ids_np = np.asarray(ids, np.int64).ravel()
        vectors = jnp.asarray(vectors)
        qp = self.index.transform.transform(vectors)
        new_index = self.index.upsert(ids_np, qp)
        corpus = self.index.corpus
        if corpus is not None:
            host = np.asarray(corpus)
            hi = int(ids_np.max()) + 1 if ids_np.size else 0
            if hi > host.shape[0]:
                # the re-rank corpus is indexed *densely* by external id;
                # refuse growth a sparse huge id would turn into a silent
                # multi-GB allocation (use dense-ish ids, or
                # keep_corpus=False / rerank_factor=0 for sparse id spaces)
                limit = max(2 * host.shape[0], host.shape[0] + 1_000_000)
                if hi > limit:
                    raise ValueError(
                        f"upsert id {hi - 1} would grow the dense re-rank "
                        f"corpus from {host.shape[0]} to {hi} rows; ids "
                        "index the corpus by position — use dense ids or "
                        "drop the corpus (keep_corpus=False)"
                    )
                host = np.concatenate([
                    host,
                    np.zeros((hi - host.shape[0], host.shape[1]), host.dtype),
                ])
            else:
                host = host.copy()
            host[ids_np] = np.asarray(vectors, host.dtype)
            new_index = dataclasses.replace(
                new_index, corpus=jnp.asarray(host))
        self.index = new_index
        self._stats["upserts"] += int(ids_np.size)

    def delete(self, ids: Sequence[int]) -> None:
        """Tombstone external ids (flat and IVF; unknown ids are ignored)."""
        before = self.index.size
        self.index = self.index.delete(ids)
        self._stats["deletes"] += before - self.index.size

    def compact(self, **kw) -> None:
        """Repack the index now (see ``ZenIndex.compact``)."""
        self.index = self.index.compact(**kw)

    def maybe_compact(self, **thresholds) -> bool:
        """Compact iff churn crossed the thresholds; True when it ran.

        When the ``max_imbalance`` threshold is what tripped (IVF only),
        the compaction refits the quantizer (``recluster=True``) — a plain
        repack keeps the same assignments and cannot reduce imbalance, so
        it would trigger again on every call.
        """
        if not self.index.needs_compact(**thresholds):
            return False
        mi = thresholds.get("max_imbalance")
        if (mi is not None and self.index.ivf is not None
                and self.index.ivf.imbalance > mi):
            self.compact(recluster=True)
        else:
            self.compact()
        return True

    # -- fault tolerance ------------------------------------------------------
    def _default_shard_count(self) -> int:
        """Logical shard count implied by the index layout."""
        ivf = self.index.ivf
        if ivf is not None and hasattr(ivf, "set_dead_shards"):
            return int(ivf.n_shards)  # tiered: static cluster partition
        if self.index.mesh is not None:
            return int(self.index.mesh.devices.size)
        return 1

    def enable_fault_tolerance(self, shards=None, *,
                               deadline_s: float = 60.0, clock=None,
                               snapshot_dir: Optional[str] = None,
                               install_signal: bool = False):
        """Attach liveness + preemption handling (``distributed.fault``).

        Args:
          shards:      logical shard names expected to heartbeat — an int
                       (count; names become ``shard0..shardN-1``) or a
                       sequence of names. Defaults to the index's own shard
                       structure: ``n_shards`` for a tiered IVF index, the
                       mesh device count for a sharded one, else 1.
          deadline_s:  silence longer than this marks a shard dead.
          clock:       monotonic time source (tests inject a fake).
          snapshot_dir: when set, a platform preemption notice
                       (SIGTERM / ``preemption.request()``) triggers a full
                       server snapshot here at the next tick boundary.
          install_signal: install the real SIGTERM handler (off by default:
                       tests and embedded servers trigger manually).

        After this, each shard's supervisor calls :meth:`heartbeat`
        periodically; every query (and every frontend tick) refreshes the
        death verdicts via :meth:`on_tick`. A dead shard's data is masked
        out of the search — queries keep answering from the survivors with
        reduced recall instead of raising — and ``stats()`` reports the
        outage under ``"degraded_shards"``. Returns the registry.
        """
        from repro.distributed.fault import HeartbeatRegistry, PreemptionGuard

        if shards is None:
            shards = self._default_shard_count()
        if isinstance(shards, int):
            shards = [f"shard{i}" for i in range(shards)]
        self._ft_shards = tuple(str(s) for s in shards)
        kw = {"now": clock} if clock is not None else {}
        self.heartbeats = HeartbeatRegistry(deadline_s=deadline_s, **kw)
        for name in self._ft_shards:
            self.heartbeats.register(name)
        self.preemption = PreemptionGuard(install_signal=install_signal)
        self._snapshot_dir = snapshot_dir
        self._degraded = ()
        self._alive_mask = None
        return self.heartbeats

    def heartbeat(self, shard) -> None:
        """Record a liveness beat for ``shard`` (index or name)."""
        if self.heartbeats is None:
            raise RuntimeError("call enable_fault_tolerance() first")
        name = (self._ft_shards[shard] if isinstance(shard, int)
                else str(shard))
        self.heartbeats.beat(name)

    def on_tick(self) -> None:
        """Refresh liveness verdicts + run any pending preemption save.

        Called on every query and every frontend scheduler tick; a no-op
        until :meth:`enable_fault_tolerance`. Masking is applied only when
        the verdict *changes*, so steady state costs one clock read.
        """
        reg = self.heartbeats
        if reg is not None:
            dead_names = set(reg.dead_hosts())
            dead = tuple(i for i, n in enumerate(self._ft_shards)
                         if n in dead_names)
            if dead != self._degraded:
                self._degraded = dead
                ivf = self.index.ivf
                if ivf is not None and hasattr(ivf, "set_dead_shards"):
                    ivf.set_dead_shards(dead)
                elif self.index.mesh is not None:
                    alive = np.ones(len(self._ft_shards), bool)
                    alive[list(dead)] = False
                    self._alive_mask = (None if alive.all()
                                        else jnp.asarray(alive))
                # flat single-host index: nothing to mask — the registry
                # still tracks external replicas and stats() reports them
        guard = self.preemption
        if (guard is not None and guard.should_save()
                and self._snapshot_dir is not None):
            self.save(self._snapshot_dir)
            guard.clear()

    def _rerank(self, queries: Array, cand_ids: Array, n_neighbors: int,
                index: ZenIndex) -> Tuple[Array, Array]:
        """Exact re-rank of the Zen candidate pool with true distances."""
        from repro.index import exact_rerank

        return exact_rerank(
            queries, index.corpus, cand_ids, n_neighbors,
            metric=index.transform.metric,
        )

    def stats(self) -> dict:
        """Serving counters: query/batch totals, latency percentiles, churn.

        ``p50_ms``/``p99_ms`` are over the newest ``LATENCY_WINDOW``
        ``query()`` calls. ``compiles`` counts the executables JAX built in
        this process by the serving step that built them
        (``repro.serving.tracing``: ``zen.project``, ``zen.search``, ...;
        ``"none"`` outside every step): which step recompiled. The
        frontend's ``compile_count`` beside it counts distinct dispatch
        shapes, an upper bound on the query path's compiles.

        With a frontend attached, a ``"frontend"`` sub-dict adds the SLO
        instrumentation (p50/p95/p99 request latency, batch occupancy,
        queue wait, cache hit rate, compile count, backpressure counters)
        and a ``"cache"`` sub-dict the LRU state (``repro.serving.stats``).
        """
        lat = np.asarray(self._latency_s or [0.0])
        out = {
            "queries": self._stats["queries"],
            "batches": self._stats["batches"],
            "upserts": self._stats["upserts"],
            "deletes": self._stats["deletes"],
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "compiles": tracing.compiles(),
        }
        if self.heartbeats is not None:
            out["degraded_shards"] = [self._ft_shards[i]
                                      for i in self._degraded]
        ivf = self.index.ivf
        if ivf is not None and hasattr(ivf, "set_dead_shards"):
            out["tier"] = ivf.stats()  # hot/cold traffic + memory split
        if self.frontend is not None:
            out["frontend"] = self.frontend.stats.snapshot()
            out["cache"] = self.frontend.cache.info()
        return out

    # -- persistence ---------------------------------------------------------
    def save(self, directory: str) -> str:
        """Persist the full serving state as one versioned atomic snapshot.

        Everything needed to answer queries identically after a restart is
        written: the fitted transform (references + base simplex), the flat
        coordinates + external-id map *or* the IVF members + quantizer, and
        the re-rank corpus if kept. The snapshot is canonical host data —
        a server saved from a sharded mesh reloads onto any device count
        (``load(mesh=...)`` re-shards).
        """
        index = self.index
        tr = index.transform
        if tr.refs is None:
            raise ValueError(
                "distance-only transforms hold no reference coordinates and "
                "cannot serve raw-vector queries after reload; checkpointing "
                "them is unsupported"
            )
        arrays = {
            "refs": np.asarray(tr.refs, np.float32),
            "base_chol": np.asarray(tr.base.chol, np.float32),
            "base_diag_g": np.asarray(tr.base.diag_g, np.float32),
            "base_d0": np.asarray(tr.base.d0, np.float32),
        }
        meta = {
            "k": tr.k,
            "metric": tr.metric,
            "jitter": tr.jitter,
            "index": "ivf" if index.ivf is not None else "flat",
            "server": {
                "mode": self.mode,
                "rerank_factor": self.rerank_factor,
                "chunk": self.chunk,
                "nprobe": self.nprobe,
                "frontend": self.frontend is not None,
                "max_batch": self.max_batch,
                "cache_size": self.cache_size,
            },
        }
        if index.ivf is not None:
            from repro.index.ivf import snapshot_payload

            ivf_arrays, ivf_meta = snapshot_payload(index.ivf)
            arrays.update({f"ivf_{k}": v for k, v in ivf_arrays.items()})
            meta.update(ivf_meta)
        else:
            # raw storage-dtype rows + their per-row scales: the quantised
            # bytes round-trip untouched, any device count
            coords = retrieval_lib.host_rows(index.coords, index.n_valid) \
                if index.mesh is not None else np.asarray(index.coords)
            row_ids = index._host_row_ids()[: coords.shape[0]]
            live = row_ids >= 0
            arrays.update(
                coords=coords[live],
                row_ids=row_ids[live].astype(np.int32),
            )
            if index.coord_scales is not None:
                scales = retrieval_lib.host_rows(
                    index.coord_scales, index.n_valid) \
                    if index.mesh is not None \
                    else np.asarray(index.coord_scales)
                arrays["coord_scales"] = scales[live].astype(np.float32)
            meta["storage"] = index.storage
        # the *wrapper* churn counter is the published generation (set after
        # the ivf meta merge on purpose: the inner IVF keeps its own counter,
        # but cache keys — and therefore replica coherence — ride on this
        # one). Restored servers must not restart it from 0: a replica that
        # did would collide pre- and post-swap cache keys (launch.replicate).
        meta["generation"] = int(index.generation)
        if index.corpus is not None:
            arrays["corpus"] = np.asarray(index.corpus)
        return index_io.save_state(
            directory, arrays, meta, kind=SERVER_SNAPSHOT_KIND)

    @classmethod
    def load(cls, directory: str, *, mesh=None, mmap: bool = False,
             pool: Optional[str] = None, **server_kw) -> "ZenServer":
        """Restore a server from :meth:`save` — bit-identical search results.

        Args:
          directory: snapshot directory.
          mesh:      optional device mesh to reshard onto; may have a
                     different device count than the saving process (flat
                     coordinates are re-padded and re-sharded, IVF inverted
                     lists re-packed per shard).
          mmap:      memory-map the snapshot arrays read-only instead of
                     materialising host copies (see
                     :func:`load_index_snapshot`).
          pool:      optional tile-pool snapshot directory to serve the IVF
                     tier from (mmap'd tiered store; see
                     :func:`load_index_snapshot`).
          server_kw: overrides for the saved server config (``mode``,
                     ``rerank_factor``, ``chunk``, ``nprobe``,
                     ``force_kernel``).

        Raises ``checkpoint.CheckpointFormatError`` for snapshots written by
        an incompatible format version or of a different kind.
        """
        index, saved_kw = load_index_snapshot(
            directory, mesh=mesh, mmap=mmap, pool=pool)
        kw = dict(saved_kw)
        kw.update(server_kw)
        return cls(index, **kw)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--n", type=int, default=20000)
    p.add_argument("--dim", type=int, default=256)
    p.add_argument("--k", type=int, default=16)
    p.add_argument("--queries", type=int, default=64)
    p.add_argument("--batches", type=int, default=4)
    p.add_argument("--neighbors", type=int, default=10)
    p.add_argument("--metric", default="euclidean")
    p.add_argument("--rerank", type=int, default=4)
    p.add_argument("--index", default="flat", choices=["flat", "ivf"])
    p.add_argument("--clusters", type=int, default=0,
                   help="IVF cluster count (0 = ~4*sqrt(N))")
    p.add_argument("--nprobe", type=int, default=8)
    p.add_argument("--storage", default="float32",
                   choices=list(quant.STORAGE_DTYPES),
                   help=quant.storage_help())
    p.add_argument("--pq-m", type=int, default=0,
                   help="PQ subspace count M (storage=pq; 0 = ~k/4)")
    p.add_argument("--pivots", default="random",
                   choices=list(pivots_lib.PIVOT_STRATEGIES),
                   help="base-simplex (reference) selection strategy "
                        "(core.pivots; random = the paper's redraw loop)")
    p.add_argument("--offload", action="store_true",
                   help="host-offload the IVF tile pool (tiered store): "
                        "only centroids + a hot cluster set stay device-"
                        "resident, cold probes stream up double-buffered")
    p.add_argument("--hot-clusters", type=int, default=0,
                   help="device-resident hot set size (0 = 10%% of C)")
    p.add_argument("--offload-shards", type=int, default=1,
                   help="logical shards for degraded serving (tiered)")
    p.add_argument("--checkpoint", default=None, metavar="DIR",
                   help="restore the server from DIR if a snapshot exists "
                        "there, else build and save one (versioned, atomic)")
    p.add_argument("--frontend", action="store_true",
                   help="serve through the micro-batching frontend "
                        "(coalesced, shape-bucketed dispatches + result "
                        "cache; repro.serving)")
    p.add_argument("--max-batch", type=int, default=64,
                   help="largest coalesced dispatch (frontend mode)")
    p.add_argument("--cache", type=int, default=0, metavar="ROWS",
                   help="LRU result-cache capacity in rows (frontend mode; "
                        "0 disables)")
    args = p.parse_args()

    import os

    from repro.data import synthetic as syn
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    key = jax.random.PRNGKey(0)
    corpus = syn.manifold_space(key, args.n, args.dim, args.dim // 8)
    frontend_kw = dict(frontend=args.frontend, max_batch=args.max_batch,
                       cache_size=args.cache)
    if args.checkpoint and os.path.exists(
            os.path.join(args.checkpoint, "manifest.json")):
        server = ZenServer.load(args.checkpoint,
                                rerank_factor=args.rerank,
                                nprobe=args.nprobe, **frontend_kw)
        index = server.index
        ref_dim = int(index.transform.refs.shape[1])
        if ref_dim != args.dim:
            raise SystemExit(
                f"checkpoint {args.checkpoint} serves {ref_dim}-d vectors "
                f"but --dim is {args.dim}; pass --dim {ref_dim}")
        print(f"restored server from {args.checkpoint}")
    else:
        index = build_index(corpus, args.k, metric=args.metric,
                            index=args.index,
                            n_clusters=args.clusters or None,
                            storage=args.storage,
                            pq_m=args.pq_m or None,
                            pivots=args.pivots,
                            offload=args.offload,
                            hot_clusters=args.hot_clusters or None,
                            offload_shards=args.offload_shards)
        server = ZenServer(index, rerank_factor=args.rerank,
                           nprobe=args.nprobe, **frontend_kw)
        if args.checkpoint:
            print(f"saved snapshot to {server.save(args.checkpoint)}")
    print(f"index: {index.size} x {args.k} (from dim {args.dim}, "
          f"storage={index.storage})"
          + (f"; ivf: {index.ivf.n_clusters} clusters, nprobe={args.nprobe}"
             if index.ivf is not None else ""))

    qkey = jax.random.fold_in(key, 1)
    recalls = []
    for b in range(args.batches):
        q = syn.manifold_space(jax.random.fold_in(qkey, b), args.queries,
                               args.dim, args.dim // 8)
        d, ids = server.query(q, args.neighbors)
        true_d = metrics_lib.pairwise(args.metric, q, corpus)
        _, true_ids = jax.lax.top_k(-true_d, args.neighbors)
        hit = np.mean([
            len(set(np.asarray(ids)[i]) & set(np.asarray(true_ids)[i]))
            / args.neighbors
            for i in range(args.queries)
        ])
        recalls.append(hit)
    print(f"recall@{args.neighbors}: {np.mean(recalls):.3f}")
    print("latency:", server.stats())


if __name__ == "__main__":
    main()
