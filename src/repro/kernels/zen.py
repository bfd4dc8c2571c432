"""Pallas TPU kernel: fused Zen / Lwb / Upb estimator matrix (paper §4.1).

For projected points X (N, k), Y (M, k), last coordinate = altitude:

  Zen^2 = ||x||^2 + ||y||^2 - 2 <x[:k-1], y[:k-1]>
  Lwb^2 = Zen^2 - 2 x_{k-1} y_{k-1}
  Upb^2 = Zen^2 + 2 x_{k-1} y_{k-1}

One kernel computes any of the three: the dot product masks the altitude
column in-register (iota mask against the static true width), the altitude
cross term is an MXU-free rank-1 update. k is small (<= a few hundred), so the
whole feature dimension is one block; the grid tiles (N, M) only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array

_MODE = {"zen": 0, "lwb": 1, "upb": 2}


def _zen_kernel(x_ref, y_ref, o_ref, *, true_k: int, mode: int):
    x = x_ref[...].astype(jnp.float32)  # (bn, kp)
    y = y_ref[...].astype(jnp.float32)  # (bm, kp)
    kp = x.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, kp), 1)
    keep = (col < true_k - 1).astype(jnp.float32)  # mask altitude + padding
    valid = (col < true_k).astype(jnp.float32)  # mask padding only
    xv = x * valid
    yv = y * valid
    nx = jnp.sum(xv * xv, axis=1, keepdims=True)  # (bn, 1) full norms
    ny = jnp.sum(yv * yv, axis=1, keepdims=True)  # (bm, 1)
    dot = jax.lax.dot_general(
        xv * keep,
        yv,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # altitude column zeroed on one side only — enough to drop it from <.,.>
    z2 = nx + ny.T - 2.0 * dot
    if mode != 0:
        is_alt = (col == true_k - 1).astype(jnp.float32)
        xa = jnp.sum(xv * is_alt, axis=1, keepdims=True)  # (bn, 1)
        ya = jnp.sum(yv * is_alt, axis=1, keepdims=True)  # (bm, 1)
        cross = 2.0 * xa * ya.T
        z2 = z2 - cross if mode == 1 else z2 + cross
    o_ref[...] = jnp.sqrt(jnp.maximum(z2, 0.0)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("mode", "block_n", "block_m", "interpret")
)
def zen_estimate(
    X: Array,
    Y: Array,
    mode: str = "zen",
    *,
    block_n: int = 256,
    block_m: int = 256,
    interpret: bool = False,
) -> Array:
    """(N, k) x (M, k) -> (N, M) estimator distances, f32."""
    n, k = X.shape
    m, k2 = Y.shape
    assert k == k2, (X.shape, Y.shape)
    bn, bm = min(block_n, _rup(n, 8)), min(block_m, _rup(m, 128))
    Np, Mp, Kp = _rup(n, bn), _rup(m, bm), _rup(k, 128)
    Xp = jnp.pad(X, ((0, Np - n), (0, Kp - k)))
    Yp = jnp.pad(Y, ((0, Mp - m), (0, Kp - k)))

    out = pl.pallas_call(
        functools.partial(_zen_kernel, true_k=k, mode=_MODE[mode]),
        grid=(Np // bn, Mp // bm),
        in_specs=[
            pl.BlockSpec((bn, Kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, Kp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Np, Mp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name="nsimplex_zen",
    )(Xp, Yp)
    return out[:n, :m]


def _rup(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
