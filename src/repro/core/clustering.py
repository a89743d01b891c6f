"""k-means++ clustering with silhouette-based model selection (paper §IV-B).

Pure JAX, jit-able, deterministic in the PRNG key.  This is the fleet-scale
path: on 15-node clusters it is instant, and the same code groups 10^5
profiles:

  * the Lloyd update uses a segment-sum (or, on TPU and at any point
    count, the fused ``repro.kernels.kmeans.kmeans_lloyd_step`` Pallas
    kernel that emits labels and per-cluster sums/counts in one pass)
    instead of the seed's (n, k) one-hot matmul;
  * ``silhouette_blocked`` streams row blocks so the dense (n, n) distance
    matrix never exists; ``choose_k`` scores large inputs on a
    deterministic subsample through that blocked path.

``choose_k`` sweeps k and picks the silhouette maximiser, exactly the
paper's control-function formulation; results on paper-sized inputs are
unchanged from the seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing


def standardize(X, mode: str = "relative"):
    """Feature scaling before clustering.

    mode="relative" (default): (x - mean)/mean — features are compared by
    *relative* spread, so benchmark noise on features that are identical
    across the cluster (e.g. I/O on the paper's shared-PD clusters, Table IV)
    stays near zero instead of being amplified to unit variance the way a
    z-score would.  mode="zscore" for well-separated features.
    """
    X = jnp.asarray(X, jnp.float32)
    mu = jnp.mean(X, axis=0)
    if mode == "relative":
        return (X - mu) / jnp.where(jnp.abs(mu) > 1e-12, mu, 1.0)
    sd = jnp.std(X, axis=0)
    return jnp.where(sd > 1e-12, (X - mu) / jnp.where(sd > 1e-12, sd, 1.0), 0.0)


def _pairwise_sq(X, C):
    x2 = jnp.sum(X * X, axis=1)[:, None]
    c2 = jnp.sum(C * C, axis=1)[None, :]
    return jnp.maximum(x2 + c2 - 2.0 * X @ C.T, 0.0)


def uses_lloyd_kernel(X) -> bool:
    """Whether ``kmeans_pp`` runs its Lloyd steps through the fused Pallas
    kernel: yes where the points live on a TPU, at any point count.  The
    platform is read from the array itself, so a call under
    ``jax.default_device(<cpu device>)`` on a TPU host takes the CPU path."""
    X = jnp.asarray(X)
    return all(d.platform == "tpu" for d in X.devices())


def kmeans_pp(X, k: int, key, iters: int = 32, use_kernel: bool | None = None):
    """Returns (labels (n,), centers (k,f), inertia scalar).

    ``use_kernel=None`` takes the fused Pallas Lloyd step on TPU (see
    ``uses_lloyd_kernel``); the portable path computes the update with
    segment-sums, so neither path materializes the (n, k) one-hot matmul
    of the seed implementation.
    """
    X = jnp.asarray(X)
    if use_kernel is None:
        use_kernel = uses_lloyd_kernel(X)
    return _kmeans_pp(X, k, key, iters, bool(use_kernel))


@functools.partial(jax.jit, static_argnames=("k", "iters", "use_kernel"))
def _kmeans_pp(X, k: int, key, iters: int, use_kernel: bool):
    n, f = X.shape

    def init_step(carry, _):
        C, m, key = carry            # C: (k,f) with m centers filled
        d2 = _pairwise_sq(X, C)      # (n,k)
        live = jnp.arange(k) < m
        d2min = jnp.min(jnp.where(live[None, :], d2, jnp.inf), axis=1)
        key, sub = jax.random.split(key)
        # k-means++ D^2 sampling
        logits = jnp.log(jnp.maximum(d2min, 1e-30))
        idx = jax.random.categorical(sub, logits)
        C = C.at[m].set(X[idx])
        return (C, m + 1, key), None

    key, sub = jax.random.split(key)
    first = X[jax.random.randint(sub, (), 0, n)]
    C0 = jnp.zeros((k, f), X.dtype).at[0].set(first)
    (C, _, key), _ = jax.lax.scan(init_step, (C0, 1, key), None, length=k - 1)

    def lloyd(carry, _):
        C, _ = carry
        if use_kernel:
            from repro.kernels.ops import kmeans_lloyd_step
            lab, _d, sums, counts = kmeans_lloyd_step(X, C)
            sums = sums.astype(X.dtype)
            counts = counts.astype(X.dtype)
        else:
            d2 = _pairwise_sq(X, C)
            lab = jnp.argmin(d2, axis=1)
            counts = jax.ops.segment_sum(jnp.ones((n,), X.dtype), lab,
                                         num_segments=k)     # (k,)
            sums = jax.ops.segment_sum(X, lab, num_segments=k)  # (k,f)
        newC = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1)[:, None], C)
        return (newC, lab.astype(jnp.int32)), None

    (C, labels), _ = jax.lax.scan(lloyd, (C, jnp.zeros((n,), jnp.int32)), None,
                                  length=iters)
    inertia = jnp.sum(jnp.min(_pairwise_sq(X, C), axis=1))
    return labels, C, inertia


@functools.partial(jax.jit, static_argnames=("k",))
def silhouette(X, labels, k: int):
    """Mean silhouette coefficient.  Singleton clusters get s=0 (Rousseeuw)."""
    n = X.shape[0]
    d = jnp.sqrt(_pairwise_sq(X, X))                        # (n,n)
    onehot = jax.nn.one_hot(labels, k, dtype=X.dtype)       # (n,k)
    counts = jnp.sum(onehot, axis=0)                        # (k,)
    # mean distance from each point to each cluster
    sums = d @ onehot                                       # (n,k)
    own = counts[labels]                                    # (n,)
    a = jnp.where(own > 1, sums[jnp.arange(n), labels] / jnp.maximum(own - 1, 1), 0.0)
    other = sums / jnp.maximum(counts[None, :], 1)
    other = jnp.where((jnp.arange(k)[None, :] == labels[:, None]) |
                      (counts[None, :] == 0), jnp.inf, other)
    b = jnp.min(other, axis=1)
    s = jnp.where(own > 1, (b - a) / jnp.maximum(jnp.maximum(a, b), 1e-30), 0.0)
    return jnp.mean(s)


@functools.partial(jax.jit, static_argnames=("k", "block"))
def silhouette_blocked(X, labels, k: int, block: int = 1024):
    """Mean silhouette without ever forming the (n, n) distance matrix.

    Streams row blocks: peak memory is (block, n) per step.  Same formula
    as ``silhouette`` (singletons get s=0), so results agree to float
    tolerance; use this above a few thousand points.
    """
    n, f = X.shape
    nb = -(-n // block)
    pad = nb * block - n
    Xp = jnp.pad(X, ((0, pad), (0, 0)))
    labp = jnp.pad(labels, (0, pad), constant_values=-1)
    onehot = jax.nn.one_hot(labels, k, dtype=X.dtype)       # (n,k) — k is tiny
    counts = jnp.sum(onehot, axis=0)                        # (k,)

    def body(acc, inp):
        xb, lb = inp                                        # (block,f), (block,)
        d = jnp.sqrt(_pairwise_sq(xb, X))                   # (block, n)
        sums = d @ onehot                                   # (block, k)
        valid = lb >= 0
        lbc = jnp.maximum(lb, 0)
        own = counts[lbc]
        a = jnp.where(own > 1,
                      sums[jnp.arange(xb.shape[0]), lbc] / jnp.maximum(own - 1, 1),
                      0.0)
        other = sums / jnp.maximum(counts[None, :], 1)
        other = jnp.where((jnp.arange(k)[None, :] == lbc[:, None]) |
                          (counts[None, :] == 0), jnp.inf, other)
        b = jnp.min(other, axis=1)
        s = jnp.where(own > 1, (b - a) / jnp.maximum(jnp.maximum(a, b), 1e-30), 0.0)
        return acc + jnp.sum(jnp.where(valid, s, 0.0)), None

    total, _ = jax.lax.scan(
        body, jnp.float32(0.0),
        (Xp.reshape(nb, block, f), labp.reshape(nb, block)))
    return total / n


def choose_k(X, k_max: int = 6, key=None, restarts: int = 4,
             silhouette_sample: int = 4096, silhouette_block: int = 1024):
    """Sweep k in [2, k_max], pick max silhouette (paper's control function).
    Returns dict(k, labels (np), centers, silhouette, per_k scores, timings).

    Paper-sized inputs (n <= silhouette_sample) keep the seed's dense
    scoring path bit-for-bit.  Above that, scores come from a
    deterministic subsample evaluated through ``silhouette_blocked``, so a
    10^5-profile sweep completes without an (n, n) — or even
    (sample, sample) — distance matrix.

    ``timings`` is the call's ``tracing.Record``: the seconds of the spans
    ``grouping.standardize`` (upload and scaling of ``X``),
    ``grouping.kmeans`` (one per k and restart, its inertia read
    included), ``grouping.silhouette`` (one per k) and ``grouping.sync``
    (each blocking device-to-host read) as ``standardize_s``, ``kmeans_s``,
    ``silhouette_s`` and ``sync_s``; ``kmeans_runs`` and ``host_syncs``
    count the k-means runs and the blocking reads.
    """
    rec = tracing.Record()
    sync = functools.partial(rec.span, "grouping.sync", count="host_syncs")
    with rec.span("grouping.standardize"):
        X = standardize(X)
    n = X.shape[0]
    if n < 3:
        # degenerate profile sets (the k sweep needs 2 <= k <= n-1): a
        # single node is its own group; two nodes get one group each —
        # silhouette is undefined either way, reported as 0.0
        labels = np.arange(n, dtype=np.int32)
        with sync():
            centers = np.asarray(X, np.float64)
        return {"k": max(n, 1), "labels": labels, "centers": centers,
                "silhouette": 0.0, "per_k": {}, "timings": rec.as_dict()}
    key = key if key is not None else jax.random.key(0)
    sample_idx = None
    if n > silhouette_sample:
        perm = jax.random.permutation(jax.random.fold_in(key, 0x5117), n)
        sample_idx = perm[:silhouette_sample]
    best = None
    per_k = {}
    for k in range(2, min(k_max, n - 1) + 1):
        best_k = None
        for r in range(restarts):
            sub = jax.random.fold_in(jax.random.fold_in(key, k), r)
            with rec.span("grouping.kmeans", count="kmeans_runs"):
                labels, C, inertia = kmeans_pp(X, k, sub)
                with sync():
                    inertia = float(inertia)
            if best_k is None or inertia < best_k[2]:
                best_k = (labels, C, inertia)
        labels, C, _ = best_k
        with rec.span("grouping.silhouette"):
            if sample_idx is None:
                score = silhouette(X, labels, k)
            else:
                score = silhouette_blocked(
                    X[sample_idx], labels[sample_idx], k,
                    block=silhouette_block)
            with sync():
                score = float(score)
        per_k[k] = score
        if best is None or score > best["silhouette"]:
            with sync():
                labels = np.asarray(labels)
            with sync():
                C = np.asarray(C)
            best = {"k": k, "labels": labels, "centers": C,
                    "silhouette": score}
    best["per_k"] = per_k
    best["timings"] = rec.as_dict()
    return best
