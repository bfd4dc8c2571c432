"""Host -> device staging of cold inverted-list tile buffers.

The tiered tile store (``index.ivf.TieredIVFZenIndex``) keeps most packed
tiles in a host-resident pool and uploads only the buffers a probe batch
needs. :func:`stage_blocks` is the single upload primitive:

* **TPU** — the buffer's bytes are placed in ``pinned_host`` memory
  (:func:`pinned_host_sharding`) as (8, 1024)-word chunks, and
  :func:`dma_copy_chunks` streams them to HBM chunk by chunk with
  ``pltpu.make_async_copy`` DMAs: while chunk ``i`` is waited on, the copy
  for chunk ``i+1`` is already in flight. A bitcast on the device restores
  the buffer's dtype and shape.
* **CPU / GPU** — ``jax.device_put``, which is itself asynchronous: the
  store issues the put for the *next* probe chunk before scoring the
  current one, giving the same overlap without a kernel.

Both paths return an ordinary committed device array; callers never branch
on backend.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def pinned_host_sharding(device=None) -> Optional[jax.sharding.Sharding]:
    """Sharding that pins a host buffer for async DMA upload.

    Memory kinds are a backend capability, not an API constant, so this
    asks the device. A TPU without a ``pinned_host`` space is an error —
    the staging kernel cannot run there, and silently uploading with a
    plain ``device_put`` would hide it. Other backends return None, and
    their callers use an ordinary ``device_put``.
    """
    device = device if device is not None else jax.devices()[0]
    if "pinned_host" in {m.kind for m in device.addressable_memories()}:
        return jax.sharding.SingleDeviceSharding(
            device, memory_kind="pinned_host")
    if device.platform == "tpu":
        raise RuntimeError(
            f"{device.device_kind} exposes no pinned_host memory space; the "
            "tiered tile store cannot stage tiles on it")
    return None


#: the DMA engine moves host memory in whole (8, 128) tiles of 32-bit words,
#: so a staged buffer travels as (G, 8, 1024) chunks of int32 words
_CHUNK = (8, 1024)
_WORDS_PER_CHUNK = _CHUNK[0] * _CHUNK[1]


def _copy_kernel(src_ref, out_ref, sem_ref):
    """Host -> HBM chunk copy with the next chunk's DMA always in flight."""
    i = pl.program_id(0)
    n = pl.num_programs(0)

    def dma(c):
        return pltpu.make_async_copy(
            src_ref.at[c], out_ref.at[c], sem_ref.at[c % 2])

    @pl.when(i == 0)
    def _start_first():
        dma(i).start()

    @pl.when(i + 1 < n)
    def _prefetch_next():
        dma(i + 1).start()

    dma(i).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def dma_copy_chunks(src: Array, *, interpret: bool = False) -> Array:
    """Copy a (G, 8, 1024) int32 chunk array from pinned host memory to HBM.

    Each chunk is one manual async DMA, and chunk ``i+1``'s copy is started
    before chunk ``i``'s is waited on, so the transfer is pipelined. Grid is
    serial ("arbitrary"): the two semaphores alternate between steps. Host
    memory cannot be DMA'd into VMEM, so the copy lands in HBM directly.
    """
    assert src.shape[1:] == _CHUNK and src.dtype == jnp.int32, (
        src.shape, src.dtype)
    return pl.pallas_call(
        _copy_kernel,
        grid=(src.shape[0],),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HOST)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(src.shape, src.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="nsimplex_tile_stage",
    )(src)


def to_chunks(host_vals: np.ndarray) -> np.ndarray:
    """The bytes of a host buffer as zero-padded (G, 8, 1024) int32 chunks."""
    raw = np.ascontiguousarray(host_vals).reshape(-1).view(np.uint8)
    pad = (-raw.size) % (4 * _WORDS_PER_CHUNK)
    if pad:
        raw = np.concatenate([raw, np.zeros(pad, np.uint8)])
    return raw.view(np.int32).reshape((-1,) + _CHUNK)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def from_chunks(chunks: Array, shape: tuple, dtype) -> Array:
    """Inverse of :func:`to_chunks` on the device: bitcast and trim."""
    # a narrower dtype gains a trailing axis of 4 // itemsize values
    vals = jax.lax.bitcast_convert_type(chunks.reshape(-1), dtype)
    return vals.reshape(-1)[: math.prod(shape)].reshape(shape)


def stage_blocks(host_vals: np.ndarray, *, force_kernel: bool = False) -> Array:
    """Upload one packed block buffer; returns immediately (async transfer).

    Args:
      host_vals: (B, ...) numpy (or memmap) buffer of tile blocks.
      force_kernel: run the Pallas DMA path in interpret mode off-TPU
                    (parity testing). On TPU the compiled kernel always
                    runs.
    """
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or force_kernel):
        return jax.device_put(jnp.asarray(host_vals))
    chunks = to_chunks(host_vals)
    pinned = pinned_host_sharding()
    if pinned is not None:
        staged = jax.device_put(chunks, pinned)
    else:  # interpret-mode parity off-TPU: no pinned space to start from
        staged = jnp.asarray(chunks)
    out = dma_copy_chunks(staged, interpret=not on_tpu)
    return from_chunks(out, tuple(host_vals.shape), np.dtype(host_vals.dtype))
