"""Rows on a low-dimensional nonlinear manifold in R^dim, made on the device.

The benchmark's own copy of the program's ``data.synthetic.manifold_space``
(a GloVe/CNN-feature stand-in), so that the data a cell measures cannot
change under a later change to the program. Both matmuls run at
``Precision.HIGHEST``, so the rows are the same on a TPU as on a CPU up to
f32 rounding.

The corpus and the query pool are two draws from one manifold: the same
two layer weights, independent latent points and noise. Pool rows are
never corpus rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=(
    "rows", "pool", "dim", "intrinsic", "noise"))
def make(key, *, rows: int, pool: int, dim: int, intrinsic: int,
         noise: float):
    """(corpus (rows, dim), pool (pool, dim)), float32, in one jitted call."""
    k_latent, k_w1, k_w2, k_noise = jax.random.split(key, 4)
    w1 = jax.random.normal(k_w1, (intrinsic, 2 * intrinsic)) / np.sqrt(
        intrinsic)
    w2 = jax.random.normal(k_w2, (2 * intrinsic, dim)) / np.sqrt(
        2 * intrinsic)

    def draw(part: int, n: int):
        z = jax.random.normal(jax.random.fold_in(k_latent, part),
                              (n, intrinsic))
        x = jnp.matmul(jnp.tanh(jnp.matmul(z, w1, precision=HIGHEST)), w2,
                       precision=HIGHEST)
        return x + noise * jax.random.normal(
            jax.random.fold_in(k_noise, part), (n, dim))

    return draw(0, rows), draw(1, pool)
