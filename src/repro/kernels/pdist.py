"""Pallas TPU kernel: blocked pairwise squared-Euclidean distance matrix.

out[i, j] = ||X[i]||^2 + ||Y[j]||^2 - 2 <X[i], Y[j]>

This is the nSimplex transform's hot loop (N objects x K references over m
original dimensions) and the first stage of every metric-space query. The
kernel is matmul-shaped: grid (N/bn, K/bk, m/bm); each (i, j) tile accumulates
partial norms and the -2xy dot product over m-chunks in a float32 VMEM scratch
accumulator, so the MXU runs the dot while the VPU fuses the norm terms.
Feature-dim padding with zeros is exact (zeros change neither norms nor dots).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array


def _pdist_kernel(x_ref, y_ref, o_ref, acc_ref, *, n_m_blocks: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)  # (bn, bm)
    y = y_ref[...].astype(jnp.float32)  # (bk, bm)
    # partial squared norms for this m-chunk
    xn = jnp.sum(x * x, axis=1, keepdims=True)  # (bn, 1)
    yn = jnp.sum(y * y, axis=1, keepdims=True)  # (bk, 1)
    dot = jax.lax.dot_general(
        x,
        y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bn, bk)
    acc_ref[...] += xn + yn.T - 2.0 * dot

    @pl.when(pl.program_id(2) == n_m_blocks - 1)
    def _done():
        o_ref[...] = jnp.maximum(acc_ref[...], 0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_k", "block_m", "interpret")
)
def pdist_sq(
    X: Array,
    Y: Array,
    *,
    block_n: int = 128,
    block_k: int = 128,
    block_m: int = 512,
    interpret: bool = False,
) -> Array:
    """(N, m) x (K, m) -> (N, K) squared Euclidean distances, f32.

    Shapes need not be padded by the caller; padding happens here.
    """
    n, m = X.shape
    k, m2 = Y.shape
    assert m == m2, (X.shape, Y.shape)
    bn, bk, bm = min(block_n, _rup(n, 8)), min(block_k, _rup(k, 128)), min(
        block_m, _rup(m, 128)
    )
    Np, Kp, Mp = _rup(n, bn), _rup(k, bk), _rup(m, bm)
    Xp = jnp.pad(X, ((0, Np - n), (0, Mp - m)))
    Yp = jnp.pad(Y, ((0, Kp - k), (0, Mp - m)))
    n_m_blocks = Mp // bm

    out = pl.pallas_call(
        functools.partial(_pdist_kernel, n_m_blocks=n_m_blocks),
        grid=(Np // bn, Kp // bk, n_m_blocks),
        in_specs=[
            pl.BlockSpec((bn, bm), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bm), lambda i, j, l: (j, l)),
        ],
        out_specs=pl.BlockSpec((bn, bk), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Np, Kp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="nsimplex_pdist",
    )(Xp, Yp)
    return out[:n, :k]


def _rup(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
