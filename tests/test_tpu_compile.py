"""The serving kernels compile for a described TPU v5e chip at real widths.

Interpret mode cannot show what Mosaic refuses: unaligned blocks, slices off
the lane tiling, primitives with no TPU lowering, too much VMEM. These tests
compile each kernel on the serving path for one chip of a ``v5e:2x2``
topology that is described, not attached, at the shapes ``chip_smoke.py``
serves: a 10^7-row, k=16 index of a 96-d corpus, 64-query batches, a
64-wide candidate fetch, and ``build_index``'s IVF layout for that corpus
(C = 4 sqrt(N) clusters of 128-row tiles).

The topology is described inside a fixture, never at import time, so that
pytest-xdist workers all collect the same tests (only the worker that runs
this file loads the TPU compiler).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ivf_probe as ivf_k
from repro.kernels import tile_stage
from repro.kernels import zen_topk as zt

N_ROWS = 10_000_000
K = 16                      # projection width
Q = 64                      # queries per dispatch
FETCH = 64                  # bucketed candidate width (10 x rerank 4 -> 64)
TILE_ROWS = 128
N_CLUSTERS = int(round(4 * N_ROWS ** 0.5))
TILES_PER_CLUSTER = 12      # the served layout: C*T = 151,788 tiles
NPROBE = 8
PQ_M = 4                    # kernels.pq.default_m(16)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the serving program runs at JAX's default 32-bit types; some test
    # modules turn x64 on when imported, and Mosaic lowers no 64-bit index
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield topo
    jax.config.update("jax_enable_x64", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("storage", ["float32", "bfloat16", "int8"])
def test_zen_topk_compiles(one_chip, storage):
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
             "int8": jnp.int8}[storage]
    q = _spec((Q, K), jnp.float32, one_chip)
    x = _spec((N_ROWS, K), dtype, one_chip)
    if storage == "int8":
        s = _spec((N_ROWS, 1), jnp.float32, one_chip)
        fn = lambda q, x, s: zt.zen_topk(q, x, FETCH, "zen", scales=s)
        compiled = _compile(fn, q, x, s)
    else:
        compiled = _compile(lambda q, x: zt.zen_topk(q, x, FETCH, "zen"),
                            q, x)
    mem = compiled.memory_analysis()
    # the transposed stream never lane-pads the index: no temp close to
    # the 5 GB a (N, 128) f32 copy would take
    assert mem.temp_size_in_bytes < 2 * N_ROWS * K * 4


@pytest.mark.parametrize("storage", ["float32", "int8"])
@pytest.mark.parametrize("mode", ["zen", "lwb"])
def test_ivf_probe_compiles(one_chip, storage, mode):
    ct = N_CLUSTERS * TILES_PER_CLUSTER
    dtype = jnp.float32 if storage == "float32" else jnp.int8
    q = _spec((Q, K), jnp.float32, one_chip)
    tc = _spec((ct, TILE_ROWS, K), dtype, one_chip)
    ti = _spec((ct, TILE_ROWS), jnp.int32, one_chip)
    pr = _spec((Q, NPROBE), jnp.int32, one_chip)
    kw = dict(tiles_per_cluster=TILES_PER_CLUSTER)
    if storage == "int8":
        sc = _spec((N_CLUSTERS, 1), jnp.float32, one_chip)
        fn = lambda q, tc, ti, pr, sc: ivf_k.ivf_probe(
            q, tc, ti, pr, FETCH, mode, tile_scales=sc, **kw)
        compiled = _compile(fn, q, tc, ti, pr, sc)
    else:
        fn = lambda q, tc, ti, pr: ivf_k.ivf_probe(
            q, tc, ti, pr, FETCH, mode, **kw)
        compiled = _compile(fn, q, tc, ti, pr)
    mem = compiled.memory_analysis()
    tile_bytes = ct * TILE_ROWS * K * jnp.dtype(dtype).itemsize
    assert mem.temp_size_in_bytes < tile_bytes  # no per-call lane padding


def test_ivf_probe_pq_compiles(one_chip):
    ct = N_CLUSTERS * TILES_PER_CLUSTER
    codes = _spec((ct, TILE_ROWS, PQ_M), jnp.uint8, one_chip)
    ti = _spec((ct, TILE_ROWS), jnp.int32, one_chip)
    pr = _spec((Q, NPROBE), jnp.int32, one_chip)
    luts = _spec((Q, NPROBE, PQ_M, 256), jnp.float32, one_chip)
    _compile(lambda c, t, p, l: ivf_k.ivf_probe_pq(
        c, t, p, l, FETCH, tiles_per_cluster=TILES_PER_CLUSTER),
        codes, ti, pr, luts)


@pytest.mark.parametrize("what,dtype", [
    ("coords", np.float32), ("coords", np.int8), ("ids", np.int32)])
def test_tile_stage_compiles_from_pinned_host(topo, one_chip, what, dtype):
    pinned = SingleDeviceSharding(topo.devices[0], memory_kind="pinned_host")
    blocks = NPROBE * TILES_PER_CLUSTER * Q  # one prefetch chunk of tiles
    shape = ((blocks, TILE_ROWS, K) if what == "coords"
             else (blocks, TILE_ROWS))
    chunks = tile_stage.to_chunks(np.zeros(shape, dtype))
    # two programs, as stage_blocks runs them: the DMA lands in HBM, then
    # a device-side bitcast restores dtype and shape
    copy = _compile(tile_stage.dma_copy_chunks,
                    _spec(chunks.shape, jnp.int32, pinned))
    assert copy.output_shardings.memory_kind == "device"
    restore = jax.jit(lambda c: tile_stage.from_chunks(c, shape, dtype))
    restore.lower(_spec(chunks.shape, jnp.int32, one_chip)).compile()

