"""Plain reference of phase 1's node grouping (paper Sec. IV-B).

k-means++ seeding (D^2 sampling), 32 Lloyd steps, the best of ``restarts``
runs by inertia for every k in [2, k_max], and the k whose mean silhouette
is largest.  Inputs are scaled by their relative spread around the mean.
Above ``sample`` points the silhouette is taken on a sample of them.  The
random draws follow the documented key schedule of the grouping (key 0;
``fold_in(fold_in(key, k), restart)`` per run; ``fold_in(key, 0x5117)``
for the silhouette sample), so the same inputs give the same groups.

Straightforward ``jax.numpy`` at the highest matmul precision and in the
given dtype, with no kernel and no blocking: dense distances to the
centers, segment sums for the update, a dense distance matrix over the
silhouette sample.  Imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.roofline import LLOYD_STEPS

HI = jax.lax.Precision.HIGHEST


def _sq_dist(X, C):
    x2 = jnp.sum(X * X, axis=1)[:, None]
    c2 = jnp.sum(C * C, axis=1)[None, :]
    return jnp.maximum(x2 + c2 - 2 * jnp.matmul(X, C.T, precision=HI), 0)


def scale(X, dtype):
    X = jnp.asarray(np.asarray(X, np.float32)).astype(dtype)
    mu = jnp.mean(X, axis=0)
    return (X - mu) / jnp.where(jnp.abs(mu) > 1e-12, mu, 1)


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def kmeans(X, k: int, key, iters: int = LLOYD_STEPS):
    """(labels of the last assignment, centers, inertia) of one run."""
    n, f = X.shape
    key, sub = jax.random.split(key)
    C = jnp.zeros((k, f), X.dtype).at[0].set(X[jax.random.randint(sub, (), 0, n)])
    for m in range(1, k):
        d2 = jnp.min(_sq_dist(X, C[:m]), axis=1)
        key, sub = jax.random.split(key)
        logits = jnp.log(jnp.maximum(d2.astype(jnp.float32), 1e-30))
        C = C.at[m].set(X[jax.random.categorical(sub, logits)])

    def lloyd(_, carry):
        C, _ = carry
        labels = jnp.argmin(_sq_dist(X, C), axis=1).astype(jnp.int32)
        counts = jax.ops.segment_sum(jnp.ones(n, X.dtype), labels, k)
        sums = jax.ops.segment_sum(X, labels, k)
        return jnp.where(counts[:, None] > 0,
                         sums / jnp.maximum(counts, 1)[:, None], C), labels

    C, labels = jax.lax.fori_loop(0, iters, lloyd,
                                  (C, jnp.zeros(n, jnp.int32)))
    inertia = jnp.sum(jnp.min(_sq_dist(X, C), axis=1))
    return labels, C, inertia


@functools.partial(jax.jit, static_argnames=("k",))
def silhouette(X, labels, k: int):
    """Mean silhouette; a point alone in its group scores 0."""
    n = X.shape[0]
    d = jnp.sqrt(_sq_dist(X, X))
    onehot = jax.nn.one_hot(labels, k, dtype=X.dtype)
    counts = jnp.sum(onehot, axis=0)
    sums = jnp.matmul(d, onehot, precision=HI)
    own = counts[labels]
    a = jnp.where(own > 1, sums[jnp.arange(n), labels] / jnp.maximum(own - 1, 1), 0)
    other = jnp.where((jnp.arange(k)[None, :] == labels[:, None])
                      | (counts[None, :] == 0), jnp.inf,
                      sums / jnp.maximum(counts[None, :], 1))
    b = jnp.min(other, axis=1)
    s = jnp.where(own > 1, (b - a) / jnp.maximum(jnp.maximum(a, b), 1e-30), 0)
    return jnp.mean(s.astype(jnp.float32))


def sample_index(n: int, sample: int):
    """Indices of the silhouette sample, or None where all points count."""
    if n <= sample:
        return None
    key = jax.random.key(0)
    return np.asarray(jax.random.permutation(jax.random.fold_in(key, 0x5117),
                                             n)[:sample])


def choose_k(X_raw, k_max: int = 6, restarts: int = 4, sample: int = 4096,
             dtype=jnp.float32) -> dict:
    """Groups of the raw profiles ``X_raw``: k, labels, silhouette per k."""
    X = scale(X_raw, dtype)
    n = X.shape[0]
    key = jax.random.key(0)
    idx = sample_index(n, sample)
    best, per_k = None, {}
    for k in range(2, min(k_max, n - 1) + 1):
        run = None
        for r in range(restarts):
            lab, _, inertia = kmeans(X, k, jax.random.fold_in(
                jax.random.fold_in(key, k), r))
            if run is None or float(inertia) < run[1]:
                run = (lab, float(inertia))
        lab = run[0]
        score = float(silhouette(X if idx is None else X[idx],
                                 lab if idx is None else lab[idx], k))
        per_k[k] = score
        if best is None or score > best["silhouette"]:
            best = {"k": k, "labels": np.asarray(lab), "silhouette": score}
    best["per_k"] = per_k
    return best


def silhouette_of(X_raw, labels, k: int, sample: int = 4096,
                  dtype=jnp.float32) -> float:
    """The silhouette that ``choose_k`` would report for given labels."""
    X = scale(X_raw, dtype)
    idx = sample_index(X.shape[0], sample)
    labels = jnp.asarray(np.asarray(labels, np.int32))
    if idx is not None:
        X, labels = X[idx], labels[idx]
    return float(silhouette(X, labels, k))
