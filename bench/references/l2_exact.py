"""Plain reference for Euclidean (L2) search: an exact blocked scan.

It imports nothing of the program. ``search`` returns the exact nearest
corpus rows of each query by squared L2 distance, computed in float32 with
every matmul at ``Precision.HIGHEST``. ``search(..., control=True)`` is the
same scan one precision step lower: each matmul is split into three bf16
products (hi*hi + hi*lo + lo*hi, f32 accumulation), which is what a TPU's
``Precision.HIGH`` computes, written out so that a CPU computes it too.
``sq_dist`` gives the squared distance of given (query, row) pairs by the
direct form, sum((q - x)^2), in float64 on the host, with no matmul and no
cancellation of norms.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: query rows per compiled scan call (the last block is padded)
QUERY_BLOCK = 512
#: corpus rows per step of the scan
ROW_BLOCK = 65536


def _split_bf16(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _dot(q, blk, control: bool):
    """(Q, n) = q @ blk.T in f32: exact, or in three bf16 passes."""
    if not control:
        return jnp.matmul(q, blk.T, precision=jax.lax.Precision.HIGHEST)
    (qh, ql), (bh, bl) = _split_bf16(q), _split_bf16(blk)

    def mm(a, b):
        return jnp.matmul(a, b.T, preferred_element_type=jnp.float32)

    return mm(qh, bh) + (mm(qh, bl) + mm(ql, bh))


@functools.partial(jax.jit, static_argnames=("n_neighbors", "control"))
def _scan(queries, corpus, n_neighbors: int, control: bool):
    n = corpus.shape[0]
    block = min(ROW_BLOCK, n)
    qn = jnp.sum(queries * queries, axis=1, keepdims=True)

    def body(i, carry):
        best_d, best_i = carry
        start = jnp.minimum(i * block, n - block)  # clamp the tail block
        blk = jax.lax.dynamic_slice_in_dim(corpus, start, block, axis=0)
        d2 = qn + jnp.sum(blk * blk, axis=1)[None, :] - 2.0 * _dot(
            queries, blk, control)
        ids = start + jnp.arange(block, dtype=jnp.int32)
        d2 = jnp.where(ids[None, :] >= i * block, d2, jnp.inf)  # revisits
        cat_d = jnp.concatenate([best_d, d2], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, d2.shape)], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, n_neighbors)
        return -neg, jnp.take_along_axis(cat_i, pos, axis=1)

    q = queries.shape[0]
    init = (jnp.full((q, n_neighbors), jnp.inf, jnp.float32),
            jnp.full((q, n_neighbors), -1, jnp.int32))
    return jax.lax.fori_loop(0, -(-n // block), body, init)


def search(queries, corpus, n_neighbors: int, *, control: bool = False):
    """(squared distances, ids), each (Q, n_neighbors) numpy, ascending."""
    queries = np.asarray(queries, np.float32)
    d_out, i_out = [], []
    for lo in range(0, queries.shape[0], QUERY_BLOCK):
        blk = queries[lo:lo + QUERY_BLOCK]
        pad = QUERY_BLOCK - blk.shape[0]
        blk = np.concatenate([blk, np.repeat(blk[:1], pad, axis=0)])
        d, i = _scan(jnp.asarray(blk), corpus, n_neighbors, control)
        d_out.append(np.asarray(d)[:QUERY_BLOCK - pad])
        i_out.append(np.asarray(i)[:QUERY_BLOCK - pad])
    return np.concatenate(d_out), np.concatenate(i_out)


def sq_dist(queries, corpus_host, ids):
    """(d2, scale) for each (query row, id) pair of ``ids`` (Q, n), on the
    host in float64: ``d2`` is the exact squared distance by the direct
    form; ``scale`` is |q|^2 + |x|^2, the size of the terms that a
    matmul-form distance cancels. ``corpus_host`` is a host copy of the
    corpus (a row gather on the device relays the whole corpus out)."""
    q = np.asarray(queries, np.float64)[:, None, :]
    x = np.asarray(corpus_host)[np.maximum(ids, 0)].astype(np.float64)
    return (np.sum(np.square(q - x), axis=-1),
            np.sum(q * q, axis=-1) + np.sum(x * x, axis=-1))
