"""JAX-native batched ensemble simulator (ROADMAP open item 1).

Lowers a *fixed-topology* engine run — dense task slots, time-left /
advance math, masked-argmin next-event selection, dependency-counter
ready promotion, and the fair / sjfn / fillnodes / roundrobin placement
rules as masked argmins — into a single ``lax.scan`` step function,
batched over a leading replica axis so hundreds of Monte-Carlo replicas
(same DAG + cluster, different per-replica work jitter) execute as ONE
jitted XLA program.  ``benchmarks/ensemble_bench.py`` measures the
resulting replicas/sec against the sequential numpy engine.

Equivalence contract
--------------------
The numpy ``Engine`` stays the oracle.  On XLA:CPU, on the same pre-drawn
jitter arrays, the jitted scan reproduces its makespans and assignment
traces **bit-for-bit** (``tests/test_ensemble.py`` pins this).  On a TPU,
whose f64 is emulated and not IEEE binary64, the scan is
**decision-exact** -- node assignment and finish order equal the
oracle's on every replica -- and its start/end times and makespans agree
within the relative tolerance ``TPU_TIME_RTOL`` (``compare_traces``
measures both; ``chip_smoke.py`` holds the chip to them).  Either way,
modulo one documented RNG-stream mapping:

* **Tie-break stream.**  ``fair`` and ``sjfn`` break equal-score node
  ties with a draw from the scheduler's own RNG; the batched path uses
  the deterministic first-min (lowest node index).  ``oracle_ensemble``
  therefore runs the engine with :class:`OrderedTies` substituted for
  the scheduler RNG — a strictly increasing fake stream under which the
  engine's ``lexsort((ties, ...))`` also picks the lowest-index
  candidate.  This is the *only* behavioural difference from a stock
  engine run, and it only fires on exact float load/speed ties.
* **Usage-noise stream.**  The engine draws 3 normals per finish
  (``EngineConfig.usage_noise``) for the monitor's usage columns.  None
  of the supported schedulers read usage features, so the draws cannot
  influence makespans or assignment traces; the scan skips them (and
  ``EngineConfig.seed``, which feeds only that stream, is ignored).
* **Replica seeds.**  Replica ``r`` instantiates every submission with
  ``seed + r * seed_stride`` — one vectorized lognormal draw per
  (replica, submission) reproduces the engine's sequential per-instance
  scalar draws bit-for-bit.
* **SJFN queue ties.**  The engine stable-sorts the queue by per-name
  mean runtime; the scan orders by ``(estimate rank, promotion
  ordinal)``.  These coincide exactly for the structural tie cases
  (no-history +inf estimates, same-name tasks — the ordinal preserves
  queue order); two *different* names colliding on the exact same
  finite f64 mean is the one measure-zero case where the orders could
  differ.

Supported feature matrix (anything else raises ``NotImplementedError``
loudly at build time rather than silently diverging):

=====================  =========================================
fair/sjfn/fillnodes/   exact scheduler classes only — subclasses
roundrobin             may override semantics the scan hard-codes
delayed arrivals       ``Submission.at > 0`` (idle-engine jumps)
multi-submission       with unique instance ids (use ``prefix``)
speculation            NO  (``EngineConfig.speculation``)
fault injection        NO  (``EngineConfig.faults``)
memory sizing          NO  (``EngineConfig.sizing``)
tarema / wtarema       NO  (usage-feature dependent)
disabled/failed nodes  NO
=====================  =========================================
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from repro import tracing
from repro.core.monitor import TraceDB
from repro.core.scheduler import (FairScheduler, FillNodesScheduler,
                                  RoundRobinScheduler, SJFNScheduler)
from repro.core.seeding import stable_seed
from repro.workflow.dag import WorkflowSpec, instantiate
from repro.workflow.engine import Engine, EngineConfig, _NodeArrays

_SUPPORTED = (FairScheduler, SJFNScheduler, FillNodesScheduler,
              RoundRobinScheduler)
_BLOCK = 64          # two-level argmin block (tasks pad to a multiple)
# Relative tolerance on start/end times and makespans where the device's
# f64 is not IEEE binary64 (see "Equivalence contract"); decisions stay
# exact.  Measured on a TPU v5 lite at 256 nodes x 2,000 instances x 64
# replicas: largest relative error 1.06e-13 (fair) and 1.11e-13 (sjfn),
# with node assignment and finish order equal on every replica.  The bound
# sits two orders of magnitude above that.
TPU_TIME_RTOL = 1e-11
_INT_SENTINEL = 1 << 30


# --------------------------------------------------------------- submissions
@dataclasses.dataclass(frozen=True)
class Submission:
    """One ``Engine.submit`` call of the fixed topology."""
    spec: WorkflowSpec
    run_id: int = 0
    seed: int = 0
    at: float = 0.0
    input_scale: float = 1.0
    prefix: Optional[str] = None


@dataclasses.dataclass
class EnsembleResult:
    """Per-replica trajectories; all arrays lead with the replica axis."""
    instances: list                 # [T] instance ids (topology order)
    makespan: np.ndarray            # [R] f64
    node_idx: np.ndarray            # [R, T] int32 (index into specs)
    start_t: np.ndarray             # [R, T] f64
    end_t: np.ndarray               # [R, T] f64
    finish_order: np.ndarray        # [R, T] int32: task indices, finish order
    timings: dict = dataclasses.field(default_factory=dict)


# ------------------------------------------------------------ ordered ties
class OrderedTies:
    """Strictly increasing fake RNG stream for the oracle's tie-breaks.

    ``least_loaded_idx``-style picks do ``lexsort((ties, keys...))``;
    with draws that only ever increase, equal-key ties resolve to the
    lowest candidate index — the batched path's deterministic argmin.
    Implements exactly the surface the supported schedulers consume
    (scalar and sized ``random``)."""

    def __init__(self):
        self._i = 0

    def random(self, size=None):
        if size is None:
            self._i += 1
            return 1.0 - 1.0 / (1.0 + self._i)
        out = 1.0 - 1.0 / (1.0 + self._i + np.arange(1, int(size) + 1,
                                                     dtype=np.float64))
        self._i += int(size)
        return out


def _reset_scheduler_for_replica(sched) -> None:
    """Per-replica state reset so one (possibly expensive to construct)
    scheduler instance serves every oracle replica: tie RNG -> ordered
    stream, round-robin cursor -> 0.  Estimate/label memos key on
    ``db.uid`` and invalidate themselves when the fresh TraceDB arrives."""
    if isinstance(sched, (FairScheduler, SJFNScheduler)):
        sched.rng = OrderedTies()
    if isinstance(sched, RoundRobinScheduler):
        sched._i = 0


# ---------------------------------------------------------------- topology
class _Topology:
    """Static (replica-independent) arrays of the instantiated DAG."""

    def __init__(self, specs, submissions, scheduler, config, n_replicas,
                 seed_stride):
        if not submissions:
            raise ValueError("ensemble needs at least one Submission")
        if n_replicas < 1:
            raise ValueError("n_replicas must be >= 1")
        cfg = config if config is not None else EngineConfig()
        if cfg.speculation:
            raise NotImplementedError(
                "ensemble scan cannot express speculation yet "
                "(EngineConfig.speculation=True)")
        if cfg.sizing is not None:
            raise NotImplementedError(
                "ensemble scan cannot express memory sizing yet "
                "(EngineConfig.sizing)")
        if cfg.faults is not None:
            raise NotImplementedError(
                "ensemble scan cannot express fault injection yet "
                "(EngineConfig.faults)")
        if cfg.prediction is not None:
            raise NotImplementedError(
                "ensemble scan cannot express runtime prediction yet "
                "(EngineConfig.prediction)")
        if type(scheduler) not in _SUPPORTED:
            raise NotImplementedError(
                f"ensemble supports exactly {[c.name for c in _SUPPORTED]}; "
                f"got {type(scheduler).__name__}")
        self.cfg = cfg
        self.kind = type(scheduler).name
        self.n_replicas = int(n_replicas)
        self.seed_stride = int(seed_stride)
        self.submissions = list(submissions)

        # -- node statics (via _NodeArrays so derived columns — mem_static,
        #    bw_scale — share the engine's exact construction arithmetic)
        na = _NodeArrays(list(specs), cfg.bw_exp)
        self.node_names = list(na.names)
        self.N = len(self.node_names)
        slow = na.slow * na.app_factor            # na.slow == 1.0 everywhere
        self.cpu_base = na.cpu_speed * slow       # == engine's cpu_speed*slow
        self.mem_base = (na.mem_static * slow) * na.bw_scale
        self.io_seq = na.io_seq.copy()
        self.cores_f = na.cores.astype(np.float64)
        self.mem_gb = na.mem_gb.copy()
        self.cores_i = na.cores.copy()

        # -- instantiate once: ids/deps/req are seed-independent, and the
        #    per-replica jitter multiplies the *abstract* work columns
        #    (instantiate's work output already carries one seed's jitter,
        #    so abstract work is rebuilt from the spec in the same
        #    task x instance order)
        ids: list = []
        index: dict = {}
        name_keys: list = []
        name_of: dict = {}
        rows = []                  # (name_idx, abstract work3, rc, rm, deps)
        self._sub_slices = []
        for sub in self.submissions:
            insts = instantiate(sub.spec, sub.run_id, sub.seed,
                                sub.input_scale)
            abs_work = [(t.work["cpu"], t.work["mem"], t.work["io"])
                        for t in sub.spec.tasks
                        for _ in range(t.n_instances)]
            lo = len(ids)
            for inst, w3 in zip(insts, abs_work):
                iid = inst.instance if sub.prefix is None \
                    else f"{sub.prefix}/{inst.instance}"
                deps = inst.deps if sub.prefix is None \
                    else tuple(f"{sub.prefix}/{d}" for d in inst.deps)
                if iid in index:
                    raise NotImplementedError(
                        f"duplicate instance id {iid!r}: the engine's "
                        "overwrite semantics are not expressible in the "
                        "scan — namespace submissions with prefix=")
                if inst.req_cores < 1:
                    raise NotImplementedError(
                        f"{iid!r}: req_cores < 1 would unbound per-node "
                        "concurrency (no dense slot pool)")
                key = (inst.workflow, inst.name)
                if key not in name_of:
                    name_of[key] = len(name_keys)
                    name_keys.append(key)
                index[iid] = len(ids)
                ids.append(iid)
                rows.append((name_of[key], w3, inst.req_cores,
                             inst.req_mem_gb, deps))
            self._sub_slices.append((lo, len(ids)))
        self.instances = ids
        self.index = index
        self.name_keys = name_keys
        self.K = len(name_keys)
        T = len(ids)
        self.T = T
        # dummy row T absorbs masked scatters; pad to an argmin block multiple
        self.TT = ((T + 1 + _BLOCK - 1) // _BLOCK) * _BLOCK

        self.name_idx = np.zeros(self.TT, np.int32)
        self.base_work = np.zeros((T, 3), np.float64)
        self.req_cores = np.zeros(self.TT, np.float64)
        self.req_mem = np.zeros(self.TT, np.float64)
        self.submit_t = np.full(self.TT, np.inf)
        deps_n = np.zeros(self.TT, np.int32)
        deps_n[T:] = 1 << 20                      # dummy rows never promote
        dependents: list = [[] for _ in range(self.TT)]
        for j, (nk, w3, rc, rm, deps) in enumerate(rows):
            self.name_idx[j] = nk
            self.base_work[j] = w3
            self.req_cores[j] = rc
            self.req_mem[j] = rm
            deps_n[j] = len(deps)
            for d in deps:
                dependents[index[d]].append(j)
        for (lo, hi), sub in zip(self._sub_slices, self.submissions):
            self.submit_t[lo:hi] = sub.at
        self.deps_left0 = deps_n
        self.D = max(1, max(len(d) for d in dependents))
        self.dependents = np.full((self.TT, self.D), T, np.int32)  # pad=dummy
        for j, dl in enumerate(dependents):
            self.dependents[j, :len(dl)] = dl
        self.seq = np.arange(self.TT, dtype=np.int32)

        # -- feasibility: the engine raises "tasks stuck" at runtime; a
        #    fixed topology can be checked up front
        fit = (self.cores_i[None, :] >= self.req_cores[:T, None]) \
            & (self.mem_gb[None, :] >= self.req_mem[:T, None])
        if not fit.any(axis=1).all():
            bad = ids[int(np.flatnonzero(~fit.any(axis=1))[0])]
            raise ValueError(f"task {bad!r} fits no node in the cluster")

        # -- slot pool: node-major [N, CAP].  CAP bounds any node's
        #    concurrency (cores / smallest request), so a feasible node
        #    always has a free sub-slot.
        min_rc = int(self.req_cores[:T].min())
        self.CAP = int(self.cores_i.max()) // min_rc
        self.S = self.N * self.CAP

        # -- contention denominators as numpy-precomputed lookup tables.
        #    XLA:CPU contracts ``1.0 + gamma * k`` into an FMA (single
        #    rounding), which differs from numpy's two-rounding result for
        #    some running counts — tabulating the denominators on the host
        #    keeps the scan bit-for-bit with the engine by construction.
        k_io = np.arange(min(self.S, T) + 2, dtype=np.float64)
        self.io_denom_table = 1.0 + cfg.io_gamma * np.maximum(0.0, k_io - 1.0)
        k_mem = np.arange(self.CAP + 2, dtype=np.float64)
        self.mem_denom_table = np.minimum(
            1.0 + cfg.mem_beta * np.maximum(0.0, k_mem - 1.0), cfg.mem_cap)

        # -- step budget: one finish per step + one idle jump per distinct
        #    future arrival time + slack
        future = np.unique(self.submit_t[:T][self.submit_t[:T] > 0.0])
        self.has_arrivals = future.size > 0
        self.n_steps = T + int(future.size) + 2

        # -- int32 key capacity: qrank = step * TT + seq, sjfn packs an
        #    estimate rank on top
        self.qshift = (self.n_steps + 2) * self.TT
        kmax = self.K if self.kind == "sjfn" else 1
        if kmax * self.qshift >= _INT_SENTINEL:
            raise NotImplementedError(
                "topology too large for int32 placement keys "
                f"((names={kmax}) * (steps+2={self.n_steps + 2}) * "
                f"(tasks_padded={self.TT}) >= 2^30)")

        # -- scheduler statics (recomputed from constructor attributes, not
        #    _on_bind products, so the ensemble never mutates the caller's
        #    scheduler)
        if self.kind == "sjfn":
            self.negspeed = np.array(
                [-round(scheduler.speed[n], -1) for n in self.node_names])
        elif self.kind == "fillnodes":
            self.rank_arr = np.array(
                [scheduler._rank[n] for n in self.node_names], np.int32)
        elif self.kind == "roundrobin":
            self.perm = np.array([na.index[n] for n in scheduler.nodes],
                                 np.int32)
        self.uniform_demand = bool(
            np.unique(self.req_cores[:T]).size == 1
            and np.unique(self.req_mem[:T]).size == 1)
        # sjfn fast path: carry the packed extraction keys across steps and
        # rebuild only when the name-rank ordering moves (needs uniform
        # demand — at most one failed extraction per pass to restore — and
        # no delayed arrivals, whose promotions would dirty the panel)
        self.fastkey = (self.kind == "sjfn" and self.uniform_demand
                        and not self.has_arrivals)

    # -- per-replica inputs -------------------------------------------------
    def replica_work(self) -> np.ndarray:
        """[R, T, 3] f64 work arrays, bit-identical to ``instantiate`` with
        seed ``sub.seed + r * seed_stride``: numpy's vectorized lognormal
        yields the same stream as n sequential scalar draws."""
        R = self.n_replicas
        out = np.zeros((R, self.T, 3), np.float64)
        for r in range(R):
            for (lo, hi), sub in zip(self._sub_slices, self.submissions):
                rng = np.random.default_rng(
                    (stable_seed(sub.spec.name),
                     sub.seed + r * self.seed_stride, sub.run_id))
                run_scale = float(rng.lognormal(0.0, 0.05)) * sub.input_scale
                scales = rng.lognormal(0.0, 0.35, hi - lo) * run_scale
                out[r, lo:hi] = self.base_work[lo:hi] * scales[:, None]
        return out


# ------------------------------------------------------------------- scan
def _build_scan(top: _Topology):
    """Trace-time specialization: one jitted program per (topology shape,
    scheduler kind, has_arrivals, uniform_demand) combination.

    Returns ``(scan, args)``: the jitted program and its runtime arguments
    (initial carry, per-node cores, per-node memory); ``scan(*args)`` runs
    every replica to completion.  Build and call it under
    ``jax.enable_x64(True)``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.kernels import ensemble_step as ks

    R, N, CAP, TT, T = (top.n_replicas, top.N, top.CAP, top.TT, top.T)
    K, kind, cfg = top.K, top.kind, top.cfg
    SENT = jnp.int32(_INT_SENTINEL)
    rr_rows = jnp.arange(R, dtype=jnp.int32)

    # cores_f / mem_gb are deliberately NOT closed over as trace-time
    # constants: they feed divisions (``free / cores`` in node_load and the
    # occupancy term of node_rates), and XLA:CPU strength-reduces division
    # by a *constant* into multiply-by-reciprocal, then fuses ``1 - x*inv``
    # into an FMA — exact only for power-of-two core counts, a 1-ulp load
    # skew everywhere else that flips argmin placements on mixed clusters.
    # They enter ``scan`` as runtime arguments instead (see below), where
    # the division stays a true division.
    cpu_base = jnp.asarray(top.cpu_base)
    mem_base = jnp.asarray(top.mem_base)
    io_seq = jnp.asarray(top.io_seq)
    io_denom_table = jnp.asarray(top.io_denom_table)
    mem_denom_table = jnp.asarray(top.mem_denom_table)
    req_cores = jnp.asarray(top.req_cores)
    req_mem = jnp.asarray(top.req_mem)
    submit_t = jnp.asarray(top.submit_t)
    seq = jnp.asarray(top.seq)
    name_idx = jnp.asarray(top.name_idx)
    dependents = jnp.asarray(top.dependents)
    work_pad = np.zeros((R, TT, 3))
    work_pad[:, :T] = top.replica_work()
    work_cpu = jnp.asarray(work_pad[:, :, 0])
    work_mem = jnp.asarray(work_pad[:, :, 1])
    work_io = jnp.asarray(work_pad[:, :, 2])
    if kind == "sjfn":
        negspeed = jnp.asarray(top.negspeed)
    elif kind == "fillnodes":
        rank_arr = jnp.asarray(top.rank_arr)
    elif kind == "roundrobin":
        perm = jnp.asarray(top.perm)
        rr_pos = jnp.arange(N, dtype=jnp.int32)

    def select_node(feas, free_cores, free_mem, rr_i, cores_f, mem_gb):
        """Masked-argmin twin of ``select_node_idx`` under ordered ties:
        the first-min (lowest index) in the scheduler's key order."""
        if kind == "fair":
            loads = ks.node_load(free_cores, free_mem, cores_f[None, :],
                                 mem_gb[None, :])
            sel = jnp.argmin(jnp.where(feas, loads, jnp.inf), axis=1)
        elif kind == "sjfn":
            loads = ks.node_load(free_cores, free_mem, cores_f[None, :],
                                 mem_gb[None, :])
            m1 = jnp.min(jnp.where(feas, negspeed[None, :], jnp.inf), axis=1)
            tier = feas & (negspeed[None, :] == m1[:, None])
            sel = jnp.argmin(jnp.where(tier, loads, jnp.inf), axis=1)
        elif kind == "fillnodes":
            empty = free_cores == cores_f[None, :]
            ikey = jnp.where(empty, N, 0).astype(jnp.int32) \
                + rank_arr[None, :]
            sel = jnp.argmin(jnp.where(feas, ikey, SENT), axis=1)
        else:                                    # roundrobin: rotated probe
            feas_p = feas[:, perm]
            rel = (rr_pos[None, :] - rr_i[:, None]) % N
            pos = jnp.argmin(jnp.where(feas_p, rel, SENT), axis=1)
            return perm[pos].astype(jnp.int32), pos.astype(jnp.int32)
        return sel.astype(jnp.int32), jnp.zeros(R, jnp.int32)

    def step(carry, s, cores_f, mem_gb):
        (t, free_cores, free_mem, n_running, total_running,
         rem_cpu, rem_mem, rem_io, sord, task_of,
         qrank, deps_left, start_ctr, rr_i, cnt, sm,
         n_finished, node_of, start_t_task, end_t_task, finish_step,
         rank_prev, key_carry) = carry

        # ---- promote arrivals (engine: _promote_ready at loop top).
        # Finish-readied tasks were stamped by the previous step's
        # dependent scatter with this step's batch base, so the merged
        # batch orders by seq exactly like the engine's sorted() batch.
        if top.has_arrivals:
            prom = (deps_left == 0) & (submit_t[None, :] <= t[:, None])
            qrank = jnp.where(prom, s * TT + seq[None, :], qrank)
            deps_left = jnp.where(prom, -1, deps_left)

        # ---- placement pass (engine: scheduler.order + _place_array):
        # repeatedly extract the least-key untried queued task; place it on
        # the scheduler's argmin node, or mark it tried and stop once the
        # remaining per-dim minimum demand fits on no node.
        if kind == "sjfn":
            est = jnp.where(cnt > 0, sm / cnt, jnp.inf)            # [R, K]
            rank = jnp.sum(est[:, None, :] < est[:, :, None],
                           axis=2).astype(jnp.int32)               # [R, K]
            shift = jnp.int32(top.qshift)

        def pack_keys(qr):
            rank_task = jnp.take_along_axis(
                rank, jnp.broadcast_to(name_idx[None, :], (R, TT)), axis=1)
            return jnp.where(qr < SENT, rank_task * shift + qr, SENT)

        # The queue is static within one placement pass (promotions happen
        # at step start, finish-readied tasks are stamped for the *next*
        # step), so the packed extraction key is computed once per step and
        # kept current incrementally: placed tasks flip to SENT exactly
        # like qrank, and a *failed* extraction flips to SENT too — the
        # engine's append-to-``still`` — the key panel is restored from
        # qrank before the next pass.  This removes both the per-iteration
        # rank*shift+qrank pack (sjfn) and the per-iteration tried-epoch
        # compare that an explicit "already tried this step" array needs.
        #
        # sjfn fast path (uniform demand, no delayed arrivals — the fleet
        # bench shape): the name-rank ordering changes rarely once runtime
        # estimates separate, so the packed panel is carried across steps
        # and the full [R, TT] gather+pack re-runs only on steps where the
        # rank vector actually moved; placements/fails/readied dependents
        # are maintained as O(R)/O(R·D) point updates below.
        if kind != "sjfn":
            key_task0 = qrank
        elif top.fastkey:
            key_task0 = lax.cond(jnp.any(rank != rank_prev),
                                 lambda: pack_keys(qrank),
                                 lambda: key_carry)
        else:
            key_task0 = pack_keys(qrank)

        # Extraction is a two-level min: per-block minima (bmin, [R, NB])
        # are carried through the loop and only the winning block's 64-wide
        # row is rescanned after an update, so one iteration touches
        # O(R·(NB+B)) keys instead of the full [R, TT] panel — the flat
        # argmin was the single largest cost of the whole step.  First-min
        # semantics (lowest index wins ties) are preserved: argmin over
        # block minima picks the first block holding the global min, then
        # the first slot inside it — ``ks.blocked_argmin_i32`` exactly.
        NB = TT // _BLOCK

        def more_to_place(free_cores, free_mem, key_task, bmin):
            # Lookahead twin of the loop's own extract-and-test: True iff
            # the engine's placement pass would do further work — the min
            # task fits somewhere, or (non-uniform demand) the engine's
            # suffix-min check says some *other* queued task still might.
            # Evaluating this at the *end* of each iteration (instead of
            # ``cont = place | ...``) means the loop exits without the
            # steady-state extra body run whose only product was
            # discovering that the cluster is full — that run still paid
            # for a full select_node and every (dummy) placement scatter.
            b = jnp.argmin(bmin, axis=1).astype(jnp.int32)
            rows = jnp.take_along_axis(key_task.reshape(R, NB, _BLOCK),
                                       b[:, None, None], axis=1)[:, 0, :]
            within = jnp.argmin(rows, axis=1).astype(jnp.int32)
            j = b * _BLOCK + within
            has = rows[rr_rows, within] < SENT
            rc = req_cores[j]
            rm = req_mem[j]
            any_feas = ((free_cores >= rc[:, None])
                        & (free_mem >= rm[:, None])).any(axis=1)
            if top.uniform_demand:
                return has & any_feas
            left = key_task < SENT
            min_rc = jnp.min(jnp.where(left, req_cores[None, :], jnp.inf),
                             axis=1)
            min_rm = jnp.min(jnp.where(left, req_mem[None, :], jnp.inf),
                             axis=1)
            fitmin = ((free_cores >= min_rc[:, None])
                      & (free_mem >= min_rm[:, None])).any(axis=1)
            # a candidate that fails in-body is retired before the
            # engine's suffix check, so ``fitmin`` (which still includes
            # it) can trigger at most one extra no-op iteration — the
            # body's own lookahead then excludes it, exactly the engine.
            return has & (any_feas | fitmin)

        def place_body(st):
            (free_cores, free_mem, n_running, total_running, rem_cpu,
             rem_mem, rem_io, sord, task_of, qrank, key_task, bmin,
             start_ctr, rr_i, node_of, start_t_task, jf_last, cont, it) = st
            b = jnp.argmin(bmin, axis=1).astype(jnp.int32)
            rows = jnp.take_along_axis(key_task.reshape(R, NB, _BLOCK),
                                       b[:, None, None], axis=1)[:, 0, :]
            within = jnp.argmin(rows, axis=1).astype(jnp.int32)
            j = b * _BLOCK + within
            kmin = rows[rr_rows, within]
            has_task = (kmin < SENT) & cont
            rc = req_cores[j]
            rm = req_mem[j]
            feas = (free_cores >= rc[:, None]) & (free_mem >= rm[:, None])
            any_feas = feas.any(axis=1)
            place = has_task & any_feas
            fail = has_task & ~any_feas
            n_sel, rr_pos_sel = select_node(feas, free_cores, free_mem, rr_i,
                                            cores_f, mem_gb)
            # retire a failed extraction (the engine appends to `still`;
            # its suffix-min blocked check lives in ``more_to_place``)
            jf = jnp.where(fail, j, T)
            key_task = key_task.at[rr_rows, jf].set(
                jnp.where(fail, SENT, key_task[rr_rows, jf]))
            jf_last = jnp.where(fail, j, jf_last)
            # apply the placement (per-replica gated scatters; dummies
            # target task row T / node 0 and rewrite the existing value)
            jp = jnp.where(place, j, T)
            npl = jnp.where(place, n_sel, 0)
            c_sel = jnp.argmax(sord[rr_rows, npl] == SENT, axis=1)
            old_fc = free_cores[rr_rows, npl]
            old_fm = free_mem[rr_rows, npl]
            free_cores = free_cores.at[rr_rows, npl].set(
                jnp.where(place, old_fc - rc, old_fc))
            free_mem = free_mem.at[rr_rows, npl].set(
                jnp.where(place, old_fm - rm, old_fm))
            n_running = n_running.at[rr_rows, npl].add(
                place.astype(jnp.int32))
            total_running = total_running + place.astype(jnp.int32)
            old = lambda a: a[rr_rows, npl, c_sel]
            rem_cpu = rem_cpu.at[rr_rows, npl, c_sel].set(
                jnp.where(place, work_cpu[rr_rows, jp], old(rem_cpu)))
            rem_mem = rem_mem.at[rr_rows, npl, c_sel].set(
                jnp.where(place, work_mem[rr_rows, jp], old(rem_mem)))
            rem_io = rem_io.at[rr_rows, npl, c_sel].set(
                jnp.where(place, work_io[rr_rows, jp], old(rem_io)))
            sord = sord.at[rr_rows, npl, c_sel].set(
                jnp.where(place, start_ctr, old(sord)))
            task_of = task_of.at[rr_rows, npl, c_sel].set(
                jnp.where(place, j, old(task_of)))
            qrank = qrank.at[rr_rows, jp].set(
                jnp.where(place, SENT, qrank[rr_rows, jp]))
            key_task = key_task.at[rr_rows, jp].set(
                jnp.where(place, SENT, key_task[rr_rows, jp]))
            retired = place | fail
            rows = rows.at[rr_rows, within].set(
                jnp.where(retired, SENT, kmin))
            bmin = bmin.at[rr_rows, b].set(jnp.min(rows, axis=1))
            node_of = node_of.at[rr_rows, jp].set(
                jnp.where(place, n_sel, node_of[rr_rows, jp]))
            start_t_task = start_t_task.at[rr_rows, jp].set(
                jnp.where(place, t, start_t_task[rr_rows, jp]))
            start_ctr = start_ctr + place.astype(jnp.int32)
            if kind == "roundrobin":
                rr_i = jnp.where(place, (rr_pos_sel + 1) % N, rr_i)
            cont = more_to_place(free_cores, free_mem, key_task, bmin)
            return (free_cores, free_mem, n_running, total_running, rem_cpu,
                    rem_mem, rem_io, sord, task_of, qrank, key_task, bmin,
                    start_ctr, rr_i, node_of, start_t_task, jf_last,
                    cont, it + 1)

        cap_iter = TT + top.S + 2
        bmin0 = key_task0.reshape(R, NB, _BLOCK).min(axis=2)
        cont0 = ((n_finished < T)
                 & more_to_place(free_cores, free_mem, key_task0, bmin0))
        st = lax.while_loop(
            lambda st: jnp.any(st[-2]) & (st[-1] < cap_iter), place_body,
            (free_cores, free_mem, n_running, total_running, rem_cpu,
             rem_mem, rem_io, sord, task_of, qrank, key_task0, bmin0,
             start_ctr, rr_i, node_of, start_t_task,
             jnp.full(R, T, jnp.int32), cont0, 0))
        (free_cores, free_mem, n_running, total_running, rem_cpu, rem_mem,
         rem_io, sord, task_of, qrank, key_task, _, start_ctr, rr_i, node_of,
         start_t_task, jf_last, _, _) = st

        if top.fastkey:
            # restore the (single — uniform demand) failed extraction's key
            # from its untouched qrank; the dummy row T gather is gated out
            failedm = jf_last != T
            kold = (rank[rr_rows, name_idx[jf_last]] * shift
                    + qrank[rr_rows, jf_last])
            key_task = key_task.at[rr_rows, jf_last].set(
                jnp.where(failedm, kold, key_task[rr_rows, jf_last]))

        # ---- next event: earliest finish over active slots (first-min by
        # start ordinal == the engine's append-ordered dense-slot argmin)
        cpu, mem = ks.node_rates(free_cores, mem_denom_table[n_running],
                                 cpu_base[None, :], mem_base[None, :],
                                 cores_f[None, :], cfg.smt_penalty)
        io_eff = io_seq[None, :] / io_denom_table[total_running][:, None]
        tl = ks.time_left(rem_cpu, rem_mem, rem_io, cpu, mem, io_eff)
        active = sord < SENT
        dt, j_slot = ks.first_min_by_order(
            tl.reshape(R, top.S), sord.reshape(R, top.S),
            active.reshape(R, top.S))
        done = n_finished >= T
        idle = (total_running == 0) & ~done
        do_fin = ~done & ~idle

        if top.has_arrivals:
            next_arr = jnp.min(jnp.where(deps_left == 0, submit_t[None, :],
                                         jnp.inf), axis=1)
            t_new = jnp.where(done, t,
                              jnp.where(idle, jnp.maximum(t, next_arr),
                                        t + dt))
        else:
            t_new = jnp.where(do_fin, t + dt, t)

        adv = ks.advance(rem_cpu, rem_mem, rem_io, tl, dt)
        g = (do_fin & (dt > 0.0))[:, None, None]
        rem_cpu = jnp.where(g, adv[0], rem_cpu)
        rem_mem = jnp.where(g, adv[1], rem_mem)
        rem_io = jnp.where(g, adv[2], rem_io)

        # ---- finish processing: free resources, log end/runtime, ready
        # the dependents (engine: _finish + _on_done)
        n_fin = jnp.where(do_fin, j_slot // CAP, 0)
        c_fin = jnp.where(do_fin, j_slot % CAP, 0)
        j_task = jnp.where(do_fin, task_of[rr_rows, n_fin, c_fin], T)
        old_fc = free_cores[rr_rows, n_fin]
        old_fm = free_mem[rr_rows, n_fin]
        free_cores = free_cores.at[rr_rows, n_fin].set(
            jnp.where(do_fin, old_fc + req_cores[j_task], old_fc))
        free_mem = free_mem.at[rr_rows, n_fin].set(
            jnp.where(do_fin, old_fm + req_mem[j_task], old_fm))
        n_running = n_running.at[rr_rows, n_fin].add(
            -do_fin.astype(jnp.int32))
        total_running = total_running - do_fin.astype(jnp.int32)
        oldz = lambda a: a[rr_rows, n_fin, c_fin]
        rem_cpu = rem_cpu.at[rr_rows, n_fin, c_fin].set(
            jnp.where(do_fin, 0.0, oldz(rem_cpu)))
        rem_mem = rem_mem.at[rr_rows, n_fin, c_fin].set(
            jnp.where(do_fin, 0.0, oldz(rem_mem)))
        rem_io = rem_io.at[rr_rows, n_fin, c_fin].set(
            jnp.where(do_fin, 0.0, oldz(rem_io)))
        sord = sord.at[rr_rows, n_fin, c_fin].set(
            jnp.where(do_fin, SENT, oldz(sord)))
        end_t_task = end_t_task.at[rr_rows, j_task].set(
            jnp.where(do_fin, t_new, end_t_task[rr_rows, j_task]))
        finish_step = finish_step.at[rr_rows, j_task].set(
            jnp.where(do_fin, s, finish_step[rr_rows, j_task]))
        n_finished = n_finished + do_fin.astype(jnp.int32)

        if kind == "sjfn":            # TraceDB._runtime_agg, finish order
            kf = jnp.where(do_fin, name_idx[j_task], 0)
            runtime = t_new - start_t_task[rr_rows, j_task]
            cnt = cnt.at[rr_rows, kf].add(jnp.where(do_fin, 1.0, 0.0))
            sm = sm.at[rr_rows, kf].add(jnp.where(do_fin, runtime, 0.0))

        # ---- dependent scatter: decrement counters; newly-ready tasks get
        # next step's batch base (duplicate dummy targets all rewrite the
        # same gathered value, so the scatter stays deterministic)
        depi = dependents[j_task]                                # [R, D]
        real = depi != T
        dl = deps_left[rr_rows[:, None], depi] \
            - (do_fin[:, None] & real).astype(jnp.int32)
        if top.has_arrivals:
            ready_now = (dl == 0) & (submit_t[depi] <= t_new[:, None])
        else:
            ready_now = dl == 0
        qr = qrank[rr_rows[:, None], depi]
        qr = jnp.where(ready_now, (s + 1) * TT + seq[depi], qr)
        dl = jnp.where(ready_now, -1, dl)
        deps_left = deps_left.at[rr_rows[:, None], depi].set(dl)
        qrank = qrank.at[rr_rows[:, None], depi].set(qr)
        if top.fastkey:
            # stamp the carried key panel too, with this step's ranks — if
            # next step's ranks differ, the lax.cond above rebuilds anyway
            kd = rank[rr_rows[:, None], name_idx[depi]] * shift + qr
            key_carry = key_task.at[rr_rows[:, None], depi].set(
                jnp.where(ready_now, kd,
                          key_task[rr_rows[:, None], depi]))
        if kind == "sjfn":
            rank_prev = rank

        return ((t_new, free_cores, free_mem, n_running, total_running,
                 rem_cpu, rem_mem, rem_io, sord, task_of, qrank,
                 deps_left, start_ctr, rr_i, cnt, sm, n_finished, node_of,
                 start_t_task, end_t_task, finish_step,
                 rank_prev, key_carry), None)

    # ---- initial carry (numpy-built, converted inside the x64 context)
    qrank0 = np.full((R, TT), _INT_SENTINEL, np.int32)
    deps0 = np.broadcast_to(top.deps_left0, (R, TT)).copy()
    ready0 = (top.deps_left0 == 0) & (top.submit_t <= 0.0)
    ready0[T:] = False
    qrank0[:, ready0] = top.seq[ready0]
    deps0[:, ready0] = -1
    carry0 = (
        jnp.zeros(R),                                             # t
        jnp.tile(jnp.asarray(top.cores_f), (R, 1)),               # free_cores
        jnp.tile(jnp.asarray(top.mem_gb), (R, 1)),                # free_mem
        jnp.zeros((R, N), jnp.int32),                             # n_running
        jnp.zeros(R, jnp.int32),                                  # total
        jnp.zeros((R, N, CAP)), jnp.zeros((R, N, CAP)),
        jnp.zeros((R, N, CAP)),                                   # rem c/m/io
        jnp.full((R, N, CAP), _INT_SENTINEL, jnp.int32),          # sord
        jnp.zeros((R, N, CAP), jnp.int32),                        # task_of
        jnp.asarray(qrank0),                                      # qrank
        jnp.asarray(deps0),                                       # deps_left
        jnp.zeros(R, jnp.int32), jnp.zeros(R, jnp.int32),         # ctr, rr_i
        jnp.zeros((R, K)), jnp.zeros((R, K)),                     # cnt, sum
        jnp.zeros(R, jnp.int32),                                  # n_finished
        jnp.full((R, TT), -1, jnp.int32),                         # node_of
        jnp.zeros((R, TT)), jnp.zeros((R, TT)),                   # start/end
        jnp.full((R, TT), -1, jnp.int32),                         # finish_step
        (jnp.full((R, K), -1, jnp.int32) if kind == "sjfn"
         else jnp.zeros((R, 0), jnp.int32)),                      # rank_prev
        (jnp.asarray(qrank0) if top.fastkey
         else jnp.zeros((R, 0), jnp.int32)),                      # key_carry
    )

    @jax.jit
    def scan(carry, cores_f, mem_gb):
        carry, _ = lax.scan(lambda c, s: step(c, s, cores_f, mem_gb), carry,
                            jnp.arange(top.n_steps, dtype=jnp.int32))
        return carry

    return scan, (carry0, jnp.asarray(top.cores_f), jnp.asarray(top.mem_gb))


# ------------------------------------------------------------------ public
def run_ensemble(specs, submissions, scheduler, n_replicas, *,
                 config: Optional[EngineConfig] = None,
                 seed_stride: int = 1) -> EnsembleResult:
    """Run ``n_replicas`` Monte-Carlo replicas of the fixed topology as one
    jitted ``lax.scan`` program.  See the module docstring for the
    supported feature matrix and the RNG-stream mapping; unsupported
    configurations raise ``NotImplementedError`` at build time.

    The program is compiled and then run once.  ``timings`` is the call's
    ``tracing.Record``: the seconds of the spans ``ensemble.build``
    (topology, work draws, closure and uploads), ``ensemble.compile``
    (lowering and compiling), ``ensemble.run`` (the run, to
    ``block_until_ready``), ``ensemble.fetch`` (copies to the host and
    the result) and ``ensemble.release`` (dropping the program and its
    device buffers) as ``build_s``, ``compile_s``, ``run_s``, ``fetch_s``
    and ``release_s``; ``compiles``, and the scan's ``n_steps``."""
    import jax

    rec = tracing.Record()
    with jax.enable_x64(True):
        with rec.span("ensemble.build"):
            top = _Topology(specs, submissions, scheduler, config,
                            n_replicas, seed_stride)
            scan, args = _build_scan(top)
        with rec.span("ensemble.compile", count="compiles"):
            compiled = scan.lower(*args).compile()
        with rec.span("ensemble.run"):
            out = jax.block_until_ready(compiled(*args))

    with rec.span("ensemble.fetch"):
        T = top.T
        n_fin = np.asarray(out[16])
        if not (n_fin == T).all():
            raise RuntimeError(
                f"ensemble scan under-ran: {int(n_fin.min())}/{T} finishes "
                f"within {top.n_steps} steps — step budget bug")
        end_t = np.asarray(out[19])[:, :T]
        fstep = np.asarray(out[20])[:, :T]
        res = EnsembleResult(
            instances=top.instances, makespan=end_t.max(axis=1),
            node_idx=np.asarray(out[17])[:, :T].astype(np.int32),
            start_t=np.asarray(out[18])[:, :T], end_t=end_t,
            finish_order=np.argsort(fstep, axis=1,
                                    kind="stable").astype(np.int32))
    with rec.span("ensemble.release"):
        del scan, args, compiled, out
    res.timings = {**rec.as_dict(), "n_steps": top.n_steps}
    return res


def oracle_ensemble(specs, submissions, scheduler, n_replicas, *,
                    config: Optional[EngineConfig] = None,
                    seed_stride: int = 1) -> EnsembleResult:
    """Sequential numpy-``Engine`` twin of :func:`run_ensemble` under the
    documented RNG mapping (ordered tie-breaks).  One fresh Engine +
    TraceDB per replica; the scheduler instance is shared across replicas
    with its mutable state reset (tie RNG, round-robin cursor)."""
    top = _Topology(specs, submissions, scheduler, config, n_replicas,
                    seed_stride)
    specs = list(specs)
    R, T = top.n_replicas, top.T
    makespan = np.zeros(R)
    node_idx = np.full((R, T), -1, np.int32)
    start_t = np.zeros((R, T))
    end_t = np.zeros((R, T))
    finish_order = np.zeros((R, T), np.int32)
    wall = 0.0
    for r in range(R):
        _reset_scheduler_for_replica(scheduler)
        db = TraceDB()
        eng = Engine(specs, scheduler, db, top.cfg)
        for sub in top.submissions:
            eng.submit(sub.spec, run_id=sub.run_id,
                       seed=sub.seed + r * top.seed_stride, at=sub.at,
                       input_scale=sub.input_scale, prefix=sub.prefix)
        t_r = time.perf_counter()
        res = eng.run()
        wall += time.perf_counter() - t_r
        makespan[r] = res["makespan"]
        for k, rec in enumerate(eng.assignment_log):
            j = top.index[rec.instance]
            node_idx[r, j] = eng._na.index[rec.node]
            start_t[r, j] = rec.start
            end_t[r, j] = rec.end
            finish_order[r, k] = j
    return EnsembleResult(
        instances=top.instances, makespan=makespan, node_idx=node_idx,
        start_t=start_t, end_t=end_t, finish_order=finish_order,
        timings={"run_s": wall})


def compare_traces(jax_res: EnsembleResult, ref: EnsembleResult) -> dict:
    """How far a scan result is from the oracle's, for devices on which the
    times need not be bitwise (see the module docstring).

    ``decisions_equal``: node assignment and finish order equal on every
    replica; ``first_divergence`` names the first replica and finish
    position at which they differ (None if they do not); ``bitwise``: the
    start/end times and makespans are identical; ``max_rel_err``: the
    largest relative error over those times (exact zeros compared
    absolutely)."""
    same_nodes = (jax_res.node_idx == ref.node_idx).all(axis=1)
    same_order = (jax_res.finish_order == ref.finish_order).all(axis=1)
    first = None
    bad = np.flatnonzero(~(same_nodes & same_order))
    if bad.size:
        r = int(bad[0])
        pos = int(np.argmax(jax_res.finish_order[r] != ref.finish_order[r])) \
            if not same_order[r] else None
        j = int(np.argmax(jax_res.node_idx[r] != ref.node_idx[r])) \
            if not same_nodes[r] else None
        first = {"replica": r, "finish_position": pos,
                 "first_node_mismatch": None if j is None
                 else jax_res.instances[j]}
        if pos is not None:
            a, b = jax_res.finish_order[r, pos], ref.finish_order[r, pos]
            first["scan_task"] = (jax_res.instances[a],
                                  float(jax_res.end_t[r, a]))
            first["oracle_task"] = (ref.instances[b], float(ref.end_t[r, b]))
    pairs = [(jax_res.start_t, ref.start_t), (jax_res.end_t, ref.end_t),
             (jax_res.makespan, ref.makespan)]
    err = max(float(np.max(np.abs(a - b) / np.where(b == 0.0, 1.0, np.abs(b))))
              for a, b in pairs)
    return {"decisions_equal": first is None, "first_divergence": first,
            "bitwise": all(np.array_equal(a, b) for a, b in pairs),
            "max_rel_err": err}


def assert_equivalent(jax_res: EnsembleResult, ref: EnsembleResult) -> None:
    """Bit-for-bit trace comparison (AssertionError carries the context)."""
    np.testing.assert_array_equal(jax_res.node_idx, ref.node_idx,
                                  err_msg="node assignment diverged")
    np.testing.assert_array_equal(jax_res.start_t, ref.start_t,
                                  err_msg="start times diverged")
    np.testing.assert_array_equal(jax_res.end_t, ref.end_t,
                                  err_msg="end times diverged")
    np.testing.assert_array_equal(jax_res.finish_order, ref.finish_order,
                                  err_msg="finish order diverged")
    np.testing.assert_array_equal(jax_res.makespan, ref.makespan,
                                  err_msg="makespans diverged")
