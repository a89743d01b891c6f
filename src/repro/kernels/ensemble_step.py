"""Fused building blocks of the batched ensemble simulator's scan step.

These are the hot inner expressions of ``repro.workflow.ensemble`` — the
per-event node-rate / time-left / advance math, the masked first-min
argmin reductions and the one-hot reads and writes of per-replica panels —
kept here so they can be unit-tested against their
numpy twins in ``engine.py`` / ``allocation.py`` and reused by future
fleet-scale consumers (ROADMAP items 2/5 want exactly these primitives).

Everything is plain ``jax.numpy``: on this CPU-only container a Pallas
lowering would force interpret mode (slower than XLA:CPU's fused
elementwise loops), and the shapes involved — [R, N] node panels and
[R, T] task panels — are bandwidth-, not compute-, bound.  Bit-for-bit
equivalence with the numpy engine is part of the contract on XLA:CPU:
every expression mirrors its engine twin operand-for-operand (same
multiply / divide nesting), so under ``jax.enable_x64(True)`` the scan's
f64 results are identical to the sequential engine's.

On a TPU, f64 is emulated in pairs of f32 and is not IEEE binary64: on a
TPU v5 lite a jitted f64 add, multiply and divide each differ from numpy
in most lanes (largest relative errors about 7e-15, 1.5e-14 and 5e-14 on
uniform random operands).  The scan built from these expressions still
made every decision of the oracle (node assignment, finish order) at
256 nodes x 2,000 instances x 64 replicas for fair and sjfn, with start
and end times within 1.1e-13 relative (``ensemble.TPU_TIME_RTOL``).

All helpers are batched over a leading replica axis R and are intended to
be called from inside an already-jitted ``lax.scan`` step (they are not
individually jitted here).
"""
from __future__ import annotations

import jax.numpy as jnp

# Large sentinel for int32 "not a candidate" keys.  Room is left above it
# (2**30 < 2**31 - 1) so masked keys can never collide with real ones and
# an argmin over an all-masked row still returns a safely readable index.
INT_SENTINEL = jnp.int32(1 << 30)


def node_rates(free_cores, mem_denom, cpu_base, mem_base,
               cores, smt_penalty):
    """Per-node (cpu, mem) service rates, batched: all inputs [R, N] or [N].

    Mirrors ``Engine._node_rates`` operand-for-operand:

        occ  = 1 - free_cores / cores
        smt  = 1 - smt_penalty * max(0, occ - 0.5) / 0.5
        cpu  = (cpu_speed * slow) * smt
        mem  = ((mem_static * slow) * bw_scale) / mem_denom

    ``cpu_base = cpu_speed * slow`` and ``mem_base = (mem_static * slow) *
    bw_scale`` are hoisted by the caller (static while ``slow`` is the
    constant 1.0 — the ensemble does not support straggler injection), so
    the per-step work is exactly the engine's stale-node recompute.

    ``mem_denom`` is the engine's ``min(1 + beta * max(0, n_running - 1),
    cap)`` gathered from a *host-precomputed* table indexed by the node's
    running count.  It must not be computed inline with jnp: XLA:CPU
    contracts ``1.0 + beta * k`` into an FMA whose single rounding differs
    from numpy's two-rounding result for some k, silently breaking the
    bit-for-bit contract.  (The remaining expressions here are
    contraction-safe: divisions and subtractions cannot be fused into
    FMAs, and ``cpu_base * smt`` is a lone multiply.)
    """
    occ = 1.0 - free_cores / cores
    smt = 1.0 - smt_penalty * jnp.maximum(0.0, occ - 0.5) / 0.5
    cpu = cpu_base * smt
    mem = mem_base / mem_denom
    return cpu, mem


def time_left(rem_cpu, rem_mem, rem_io, cpu, mem, io_eff):
    """Time-to-finish per slot: rem [R, N, C], rates [R, N] broadcast.

    ``io_eff`` is the node's ``io_seq / io_denom`` (the engine divides the
    per-slot gathered ``io_seq`` by the scalar cluster denominator; with
    node-major slots the division happens per node — same float op).
    Dead slots have zeroed remaining work and yield 0.0, exactly like the
    engine's kept-dense slot range; callers mask them out of the argmin.
    """
    return (rem_cpu / cpu[:, :, None] + rem_mem / mem[:, :, None]
            + rem_io / io_eff[:, :, None])


def advance(rem_cpu, rem_mem, rem_io, tl, dt):
    """One engine ``_advance_full``: rem *= (1 - min(dt/tl, 1)) over every
    slot (active or dead).  ``dt`` is [R] (broadcast over slots); a dt of
    zero is the engine's early-return — callers wrap with
    ``jnp.where(dt > 0, advanced, rem)`` to reproduce it bit-for-bit.
    Dead slots: rem == 0 and tl == 0, so dt/0 == +inf saturates frac to 1
    and 0 * 0 stays 0 (dt > 0 lanes never see 0/0)."""
    frac = jnp.minimum(dt[:, None, None] / tl, 1.0)
    scale = (1.0 - frac)
    return rem_cpu * scale, rem_mem * scale, rem_io * scale


def first_min_by_order(values, order, active):
    """(min value, index of the *first started* slot achieving it).

    The engine's next-event pick is ``argmin`` over the dense slot array,
    whose order is start order (append-ordered, compaction-stable) — so
    among tied minima the earliest-started slot wins.  Here slots live in
    node-major layout, so the tie-break is made explicit: among slots whose
    time-left equals the masked minimum, take the smallest start ordinal.

    values, order, active: [R, S] (order int32, unique per active slot).
    Returns (m [R] f64, idx [R] int32 — flat slot index).
    """
    masked = jnp.where(active, values, jnp.inf)
    m = jnp.min(masked, axis=1)
    tie = jnp.where(active & (masked == m[:, None]), order, INT_SENTINEL)
    return m, jnp.argmin(tie, axis=1).astype(jnp.int32)


def blocked_argmin_i32(key, block: int):
    """First-min argmin over int32 keys [R, T], T a multiple of ``block``.

    A flat ``jnp.argmin`` over a wide int row is a scalar loop on XLA:CPU;
    reshaping to [R, T//block, block] and reducing block minima first is
    ~2.5x faster at the bench's T = 2048 and returns the identical first
    minimum (the first block holding the global min, then the first slot
    inside it).  Keys use INT_SENTINEL for "not a candidate"; callers
    check ``key[argmin] < INT_SENTINEL`` for emptiness.
    """
    R, T = key.shape
    k3 = key.reshape(R, T // block, block)
    bmin = jnp.min(k3, axis=2)
    b = jnp.argmin(bmin, axis=1)
    rows = jnp.take_along_axis(k3, b[:, None, None], axis=1)[:, 0, :]
    within = jnp.argmin(rows, axis=1)
    return (b * block + within).astype(jnp.int32)


def rank_of_names(rank, onehot):
    """``rank[r, name]`` looked up by a one-hot select-and-sum, no gather.

    rank: [R, K] int32, a rank per replica and name.  onehot: bool
    [R or 1, ..., K], True at each entry's name; its leading axis is the
    replica axis, of size 1 for names shared by every replica.  Returns
    [R, ...] int32: [1, TT, K] gives [R, TT], [R, K] gives [R], [R, D, K]
    gives [R, D].  Exact: each sum has one non-zero term.

    A batched gather from ``rank`` runs element by element on a TPU (683 us
    for [256, 320] on a v5 lite); the [R, TT, K] select fuses into its
    reduction and is never materialised.
    """
    R, K = rank.shape
    r = rank.reshape(R, *(1,) * (onehot.ndim - 2), K)
    return jnp.sum(jnp.where(onehot, r, 0), axis=-1, dtype=jnp.int32)


def _hot(a, idx):
    """The bool mask True where the k axes of ``a`` after the first equal
    ``idx``, shaped [R, a.shape[1], ..., a.shape[k], 1, ...] to broadcast
    against ``a``; and k.  ``a``'s first axis is the replica axis, or of
    size 1 for a panel that every replica shares."""
    idx = idx if isinstance(idx, tuple) else (idx,)
    hot = True
    for ax, i in enumerate(idx):
        iota = jnp.arange(a.shape[1 + ax], dtype=i.dtype).reshape(
            (1,) * (1 + ax) + (-1,) + (1,) * (a.ndim - 2 - ax))
        hot = hot & (iota == i.reshape((-1,) + (1,) * (a.ndim - 1)))
    return hot, len(idx)


def take_one_hot(a, idx):
    """``a[r, idx[r]]`` for every replica r by a one-hot select and a
    reduction, no gather.

    a: [R, W, ...], or [1, W, ...] for a panel every replica shares; idx:
    an [R] int array, or a tuple of k of them that index the k axes after
    the first.  Returns [R, ...] (the axes not indexed).  Exact, each
    reduction having one selected term: integers by ``sum``, floats by
    ``max`` over ``-inf`` elsewhere (an emulated f64 add is not IEEE on a
    TPU; a max is), so ``-0.0`` and ``inf`` come back as stored.

    A batched point gather runs element by element on a TPU; the select
    fuses into its reduction and streams the panel once.
    """
    hot, k = _hot(a, idx)
    axes = tuple(range(1, 1 + k))
    if jnp.issubdtype(a.dtype, jnp.integer):
        return jnp.sum(jnp.where(hot, a, 0), axis=axes, dtype=a.dtype)
    return jnp.max(jnp.where(hot, a, -jnp.inf), axis=axes)


def put_one_hot(a, idx, gate, value):
    """``a`` with ``a[r, idx[r]] = value[r]`` where ``gate[r]``, by a masked
    select, no scatter: a gated-off replica's row is left as it is.

    a, idx as in :func:`take_one_hot`; gate: [R] bool; value: a scalar, an
    [R, ...] array of the shape the index leaves (one value per replica),
    or an array of ``a``'s own shape (``a - x`` writes ``a[r, idx] - x``).
    The value is cast to ``a``'s dtype, as a scatter casts it.
    """
    hot, k = _hot(a, idx)
    hot = hot & gate.reshape((-1,) + (1,) * (a.ndim - 1))
    v = jnp.asarray(value, a.dtype)
    if 0 < v.ndim < a.ndim:
        v = v.reshape(v.shape[:1] + (1,) * k + v.shape[1:])
    return jnp.where(hot, v, a)


def node_load(free_cores, free_mem, cores, mem_gb):
    """``allocation.node_loads`` batched: 0.5 * ((1 - free_cores/cores)
    + (1 - free_mem/mem)) — operand-for-operand, so masked argmins over it
    are bit-for-bit the engine's lexsort pick under ordered tie keys."""
    return 0.5 * ((1.0 - free_cores / cores) + (1.0 - free_mem / mem_gb))
