"""k-means Pallas TPU kernels (the paper-core compute at fleet scale:
grouping 10^5+ node profiles, repro.core.clustering).

Two entry points:

* ``kmeans_assign`` — assignment only: grid over point blocks; the full
  centroid matrix (k <= 64, f <= 128) lives in VMEM; distances via one MXU
  matmul per block (||x-c||^2 = ||x||^2 - 2 x.c + ||c||^2) and an argmin
  over lanes.
* ``kmeans_lloyd_step`` — one *fused* Lloyd iteration: the same distance
  block additionally feeds an in-kernel accumulation of per-cluster sums
  and counts (block-local one-hot contraction on the MXU, accumulated
  across the sequential TPU grid into revisited output blocks).  The caller
  gets labels, sums, counts and min-distances from a single pass over the
  points, so the (n, k) one-hot never exists in HBM and the update step
  needs no second matmul over the full point set.

Any point count works: the wrappers pad the rows to a multiple of the
block, and the Lloyd kernel masks the padded rows out of the sums and
counts; labels and distances come back for the real rows only.

TARGET: TPU.  Validated via interpret=True vs ref.kmeans_assign /
ref.kmeans_lloyd_step in tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _assign_kernel(x_ref, c_ref, lab_ref, dist_ref):
    x = x_ref[...].astype(jnp.float32)               # (block_n, f)
    c = c_ref[...].astype(jnp.float32)               # (k, f)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)[None, :]
    d = x2 + c2 - 2.0 * jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    lab_ref[...] = jnp.argmin(d, axis=1).astype(jnp.int32)
    dist_ref[...] = jnp.min(d, axis=1)


def _pad_rows(x, block_n: int):
    """(x padded with zero rows to a multiple of the block, block size)."""
    n = x.shape[0]
    block_n = min(block_n, n)
    pad = -n % block_n
    return jnp.pad(x, ((0, pad), (0, 0))), block_n


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign(x, c, *, block_n: int = 1024, interpret: bool = False):
    """x: (N, f); c: (k, f) -> (labels (N,) int32, sq-dists (N,) f32)."""
    N = x.shape[0]
    xp, block_n = _pad_rows(x, block_n)
    Np, f = xp.shape
    k = c.shape[0]
    lab, dist = pl.pallas_call(
        _assign_kernel,
        grid=(Np // block_n,),
        in_specs=[pl.BlockSpec((block_n, f), lambda i: (i, 0)),
                  pl.BlockSpec((k, f), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((block_n,), lambda i: (i,)),
                   pl.BlockSpec((block_n,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((Np,), jnp.int32),
                   jax.ShapeDtypeStruct((Np,), jnp.float32)],
        interpret=interpret,
    )(xp, c)
    return lab[:N], dist[:N]


def _lloyd_kernel(x_ref, c_ref, lab_ref, dist_ref, sums_ref, cnt_ref, *,
                  n_valid: int):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)               # (block_n, f)
    c = c_ref[...].astype(jnp.float32)               # (k, f)
    k = c.shape[0]
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)[None, :]
    d = x2 + c2 - 2.0 * jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    lab = jnp.argmin(d, axis=1).astype(jnp.int32)
    lab_ref[...] = lab
    dist_ref[...] = jnp.min(d, axis=1)
    # block-local one-hot lives only in VMEM; contraction over the block
    # dimension yields this block's per-cluster sums/counts on the MXU
    onehot = lab[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    block_n = x.shape[0]
    if n_valid % block_n:
        # padded rows (global index >= n_valid) join no cluster
        row = i * block_n + jax.lax.broadcasted_iota(jnp.int32, (block_n, 1), 0)
        onehot = onehot & (row < n_valid)
    onehot = onehot.astype(jnp.float32)              # (block_n, k)
    block_sums = jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (k, f)
    block_cnt = jnp.sum(onehot, axis=0)              # (k,)

    # sequential-grid accumulation into the revisited (k, f)/(k,) outputs
    @pl.when(i == 0)
    def _init():
        sums_ref[...] = block_sums
        cnt_ref[...] = block_cnt

    @pl.when(i > 0)
    def _accum():
        sums_ref[...] += block_sums
        cnt_ref[...] += block_cnt


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_lloyd_step(x, c, *, block_n: int = 1024, interpret: bool = False):
    """One fused Lloyd step.  x: (N, f); c: (k, f).

    Returns (labels (N,) int32, sq-dists (N,) f32, sums (k, f) f32,
    counts (k,) f32) — everything the update `c' = sums / counts` and the
    inertia `sum(sq-dists)` need, from a single pass over the points.
    """
    N = x.shape[0]
    xp, block_n = _pad_rows(x, block_n)
    Np, f = xp.shape
    k = c.shape[0]
    lab, dist, sums, cnt = pl.pallas_call(
        functools.partial(_lloyd_kernel, n_valid=N),
        grid=(Np // block_n,),
        in_specs=[pl.BlockSpec((block_n, f), lambda i: (i, 0)),
                  pl.BlockSpec((k, f), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((block_n,), lambda i: (i,)),
                   pl.BlockSpec((block_n,), lambda i: (i,)),
                   pl.BlockSpec((k, f), lambda i: (0, 0)),
                   pl.BlockSpec((k,), lambda i: (0,))],
        out_shape=[jax.ShapeDtypeStruct((Np,), jnp.int32),
                   jax.ShapeDtypeStruct((Np,), jnp.float32),
                   jax.ShapeDtypeStruct((k, f), jnp.float32),
                   jax.ShapeDtypeStruct((k,), jnp.float32)],
        interpret=interpret,
    )(xp, c)
    return lab[:N], dist[:N], sums, cnt
