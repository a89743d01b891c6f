"""JAX-native batched ensemble simulator (ROADMAP open item 1).

Lowers a *fixed-topology* engine run — dense task slots, time-left /
advance math, masked-argmin next-event selection, dependency-counter
ready promotion, and the fair / sjfn / fillnodes / roundrobin placement
rules as masked argmins — into a single ``lax.scan`` step function,
batched over a leading replica axis so hundreds of Monte-Carlo replicas
(same DAG + cluster, different per-replica work jitter) execute as ONE
jitted XLA program.  ``bench/run.py`` measures its replicas/s on the
chip, in the forecast cells of ``BENCHMARK.json``.

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

The call's two work counts (see :func:`run_ensemble`) have no twin in
the engine and lie outside this contract: ``place_iters`` is counted
inside the scan, and reading it changes no decision or time;
``key_rebuilds`` is ``n_steps``, set on the host, since every signature
builds its extraction keys afresh on every step.

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
import threading
import time
from collections import OrderedDict
from typing import NamedTuple, Optional

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
# Scan programs kept at once (one per static signature, with its compiled
# executable); enough for the four schedulers of one topology and a few more.
_PROGRAM_CACHE_SIZE = 8


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
        # pad to an argmin block multiple
        self.TT = -(-T // _BLOCK) * _BLOCK

        self.name_idx = np.zeros(self.TT, np.int32)
        self.base_work = np.zeros((T, 3), np.float64)
        self.req_cores = np.zeros(self.TT, np.float64)
        self.req_mem = np.zeros(self.TT, np.float64)
        self.submit_t = np.full(self.TT, np.inf)
        deps_n = np.zeros(self.TT, np.int32)
        deps_n[T:] = 1 << 20                      # pad rows never promote
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
        self.dependents = np.full((self.TT, self.D), -1, np.int32)  # pad -1
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
        #    scheduler): sjfn's negated speeds, fillnodes' ranks,
        #    roundrobin's node order; fair has none
        if self.kind == "sjfn":
            self.sched = np.array(
                [-round(scheduler.speed[n], -1) for n in self.node_names])
        elif self.kind == "fillnodes":
            self.sched = np.array(
                [scheduler._rank[n] for n in self.node_names], np.int32)
        elif self.kind == "roundrobin":
            self.sched = np.array([na.index[n] for n in scheduler.nodes],
                                  np.int32)
        else:
            self.sched = np.zeros(0, np.int32)
        self.uniform_demand = bool(
            np.unique(self.req_cores[:T]).size == 1
            and np.unique(self.req_mem[:T]).size == 1)

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
class _Inputs(NamedTuple):
    """The topology's arrays and the call's draws, passed to the scan as
    runtime arguments besides its carry.

    None of them is closed over as a trace-time constant, for two reasons.
    The draws change with every call and the arrays with the topology's
    values, and a constant that changes makes a new program, so one
    program per static signature could never be reused.  And ``cores_f``
    / ``mem_gb`` feed divisions (``free / cores`` in node_load and the
    occupancy term of node_rates): XLA:CPU strength-reduces division by a *constant* into multiply-by-reciprocal,
    then fuses ``1 - x*inv`` into an FMA — exact only for power-of-two
    core counts, a 1-ulp load skew everywhere else that flips argmin
    placements on mixed clusters.  As arguments the division stays a true
    division."""
    cores_f: object            # [N] f64
    mem_gb: object             # [N] f64
    cpu_base: object           # [N] f64
    mem_base: object           # [N] f64
    io_seq: object             # [N] f64
    req_cores: object          # [TT] f64
    req_mem: object            # [TT] f64
    submit_t: object           # [TT] f64
    name_idx: object           # [TT] int32
    dependents: object         # [TT, D] int32, -1 past a task's last
    work_cpu: object           # [R, TT] f64, this call's draws
    work_mem: object           # [R, TT] f64
    work_io: object            # [R, TT] f64
    sched: object              # _Topology.sched


class _Carry(NamedTuple):
    """The scan's carry: every replica's state between steps."""
    t: object                  # [R] f64 simulated time
    free_cores: object         # [R, N] f64
    free_mem: object           # [R, N] f64
    n_running: object          # [R, N] int32
    total_running: object      # [R] int32
    rem_cpu: object            # [R, N, CAP] f64 work left in each slot
    rem_mem: object            # [R, N, CAP] f64
    rem_io: object             # [R, N, CAP] f64
    sord: object               # [R, N, CAP] int32 start ordinal, SENT if free
    task_of: object            # [R, N, CAP] int32 task in the slot
    qrank: object              # [R, TT] int32 queue key, SENT if not queued
    deps_left: object          # [R, TT] int32, -1 once queued
    start_ctr: object          # [R] int32 next start ordinal
    rr_i: object               # [R] int32 roundrobin's cursor
    cnt: object                # [R, K] f64 sjfn's finishes per task name
    sm: object                 # [R, K] f64 sjfn's runtime sum per task name
    n_finished: object         # [R] int32
    node_of: object            # [R, TT] int32, -1 until placed
    start_t_task: object       # [R, TT] f64
    end_t_task: object         # [R, TT] f64
    finish_step: object        # [R, TT] int32, -1 until finished
    place_iters: object        # int32 placement-loop iterations so far


class _Place(NamedTuple):
    """The placement ``while_loop``'s state within one step: the carry's
    fields a placement writes, the pass's extraction keys and their block
    minima, and the loop's condition and iteration count."""
    free_cores: object
    free_mem: object
    n_running: object
    total_running: object
    rem_cpu: object
    rem_mem: object
    rem_io: object
    sord: object
    task_of: object
    qrank: object
    key_task: object           # [R, TT//_BLOCK, _BLOCK] int32 extraction
                               # key in blocks, SENT once tried
    bmin: object               # [R, TT // _BLOCK] int32 block minima
    start_ctr: object
    rr_i: object
    node_of: object
    start_t_task: object
    cont: object               # [R] bool: the pass has more to place
    it: object                 # int32 iterations so far


class _Signature(NamedTuple):
    """Everything the scan's trace reads as a Python value: two topologies
    with equal signatures run one program.  The ``EngineConfig`` scalars
    are the step's: ``smt_penalty`` and those of the two contention
    denominator tables."""
    kind: str
    R: int
    N: int
    CAP: int
    TT: int
    T: int
    K: int
    D: int
    S: int
    n_steps: int
    qshift: int
    has_arrivals: bool
    uniform_demand: bool
    smt_penalty: float
    io_gamma: float
    mem_beta: float
    mem_cap: float
    shapes: tuple              # (shape, dtype) of every _Inputs field
    device: object             # jax's default device where set, else None


class _Programs:
    """The scan programs of the most recently used signatures, each with
    the ``Compiled`` that ``run_ensemble`` made of it.  Bounded, so that a
    caller sweeping topologies does not hoard executables."""

    def __init__(self, size: int):
        self.size = size
        self._entries: OrderedDict = OrderedDict()  # sig -> [scan, compiled]
        self._lock = threading.Lock()

    def program(self, sig: _Signature):
        """The jitted scan of ``sig``, made on a miss."""
        with self._lock:
            entry = self._entries.get(sig)
            if entry is None:
                entry = self._entries[sig] = [_scan_program(sig), None]
                while len(self._entries) > self.size:
                    self._entries.popitem(last=False)
            self._entries.move_to_end(sig)
            return entry[0]

    def _entry(self, scan):
        for entry in self._entries.values():
            if entry[0] is scan:
                return entry
        return None

    def compiled(self, scan):
        """The kept ``Compiled`` of ``scan`` if it is a cached program that
        has been compiled, else None."""
        with self._lock:
            entry = self._entry(scan)
            return None if entry is None else entry[1]

    def keep(self, scan, compiled) -> None:
        """Keep ``compiled`` beside ``scan`` if ``scan`` is still cached."""
        with self._lock:
            entry = self._entry(scan)
            if entry is not None:
                entry[1] = compiled

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)


_PROGRAMS = _Programs(_PROGRAM_CACHE_SIZE)


def _scan_program(sig: _Signature):
    """The jitted scan of one static signature: ``scan(carry, x)`` runs
    every replica to completion, ``x`` an :class:`_Inputs` of arrays.  The
    four schedulers' placement rules and the arrival and non-uniform
    demand paths are chosen here at trace time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.kernels import ensemble_step as ks

    R, N, CAP, TT, T = sig.R, sig.N, sig.CAP, sig.TT, sig.T
    K, kind = sig.K, sig.kind
    SENT = jnp.int32(_INT_SENTINEL)
    take, put = ks.take_one_hot, ks.put_one_hot

    # Arrays that depend only on the signature are trace-time constants:
    # the task slots' promotion ordinals and the contention denominators.
    # The TPU's f64 emulation folds its handling of constants into the
    # program; with these as arguments the step took 265.6 us against
    # 256.3 us (TPU v5 lite, the fleet forecast cell's shapes).  The
    # denominators are numpy-precomputed lookup tables: XLA:CPU contracts
    # ``1.0 + gamma * k`` into an FMA (single rounding), which differs from
    # numpy's two-rounding result for some running counts, so tabulating
    # them on the host keeps the scan bit-for-bit with the engine.
    seq = jnp.asarray(np.arange(TT, dtype=np.int32))
    k_io = np.arange(min(sig.S, T) + 2, dtype=np.float64)
    io_denom_table = jnp.asarray(
        1.0 + sig.io_gamma * np.maximum(0.0, k_io - 1.0))
    k_mem = np.arange(CAP + 2, dtype=np.float64)
    mem_denom_table = jnp.asarray(np.minimum(
        1.0 + sig.mem_beta * np.maximum(0.0, k_mem - 1.0), sig.mem_cap))
    if kind == "roundrobin":
        rr_pos = jnp.arange(N, dtype=jnp.int32)

    def select_node(feas, free_cores, free_mem, rr_i, x):
        """Masked-argmin twin of ``select_node_idx`` under ordered ties:
        the first-min (lowest index) in the scheduler's key order."""
        cores_f, mem_gb = x.cores_f, x.mem_gb
        if kind == "fair":
            loads = ks.node_load(free_cores, free_mem, cores_f[None, :],
                                 mem_gb[None, :])
            sel = jnp.argmin(jnp.where(feas, loads, jnp.inf), axis=1)
        elif kind == "sjfn":
            negspeed = x.sched
            loads = ks.node_load(free_cores, free_mem, cores_f[None, :],
                                 mem_gb[None, :])
            m1 = jnp.min(jnp.where(feas, negspeed[None, :], jnp.inf), axis=1)
            tier = feas & (negspeed[None, :] == m1[:, None])
            sel = jnp.argmin(jnp.where(tier, loads, jnp.inf), axis=1)
        elif kind == "fillnodes":
            empty = free_cores == cores_f[None, :]
            ikey = jnp.where(empty, N, 0).astype(jnp.int32) \
                + x.sched[None, :]
            sel = jnp.argmin(jnp.where(feas, ikey, SENT), axis=1)
        else:                                    # roundrobin: rotated probe
            perm = x.sched
            feas_p = feas[:, perm]
            rel = (rr_pos[None, :] - rr_i[:, None]) % N
            pos = jnp.argmin(jnp.where(feas_p, rel, SENT), axis=1)
            return perm[pos].astype(jnp.int32), pos.astype(jnp.int32)
        return sel.astype(jnp.int32), jnp.zeros(R, jnp.int32)

    def step(c, s, x, name_onehot):
        (cores_f, mem_gb, cpu_base, mem_base, io_seq, req_cores, req_mem,
         submit_t, name_idx, dependents, work_cpu, work_mem, work_io, _) = x
        t, qrank, deps_left = c.t, c.qrank, c.deps_left

        # ---- promote arrivals (engine: _promote_ready at loop top).
        # Finish-readied tasks were stamped by the previous step's
        # dependent scatter with this step's batch base, so the merged
        # batch orders by seq exactly like the engine's sorted() batch.
        if sig.has_arrivals:
            prom = (deps_left == 0) & (submit_t[None, :] <= t[:, None])
            qrank = jnp.where(prom, s * TT + seq[None, :], qrank)
            deps_left = jnp.where(prom, -1, deps_left)

        # ---- placement pass (engine: scheduler.order + _place_array):
        # repeatedly extract the least-key untried queued task; place it on
        # the scheduler's argmin node, or mark it tried and stop once the
        # remaining per-dim minimum demand fits on no node.  The queue is
        # static within one pass (promotions happen at step start;
        # finish-readied tasks are stamped for the *next* step), so the
        # extraction key is built once per step: qrank, or for sjfn the
        # name rank packed above it.  The rank lookup selects from the name
        # one-hot (``ks.rank_of_names``), never gathers from the [R, K] rank
        # table (element by element on a TPU).  In the pass a placed task
        # and a *failed* extraction (the engine's append-to-``still``) flip
        # to SENT, so no "already tried this step" array is needed.
        if kind == "sjfn":
            est = jnp.where(c.cnt > 0, c.sm / c.cnt, jnp.inf)      # [R, K]
            rank = jnp.sum(est[:, None, :] < est[:, :, None],
                           axis=2).astype(jnp.int32)               # [R, K]
            rank_task = ks.rank_of_names(rank, name_onehot[None])  # [R, TT]
            key_task0 = jnp.where(
                qrank < SENT, rank_task * jnp.int32(sig.qshift) + qrank, SENT)
        else:
            key_task0 = qrank

        # Extraction is a two-level min: per-block minima (bmin, [R, NB])
        # are carried through the loop beside the keys in blocks
        # ([R, NB, _BLOCK]), and after an update only the winning block's
        # minimum is recomputed, from its 64-wide row.  First-min semantics
        # (lowest index wins ties) are preserved: argmin over block minima
        # picks the first block holding the global min, then the first slot
        # inside it — ``ks.blocked_argmin_i32`` exactly.
        #
        # Every per-replica read or write of a panel here is a one-hot
        # masked select (``take`` / ``put``), not a point gather or
        # scatter, which a TPU runs element by element, so that its cost
        # grew with R.  A gated-off replica changes nothing.
        NB = TT // _BLOCK

        def extract(key_task, bmin):
            """The least key's block ``b``, that block's row of keys, the
            slot ``within`` it and the task ``j``, with j's demand."""
            b = jnp.argmin(bmin, axis=1).astype(jnp.int32)
            rows = take(key_task, b)                  # [R, _BLOCK]
            within = jnp.argmin(rows, axis=1).astype(jnp.int32)
            j = b * _BLOCK + within
            return (b, rows, within, j, take(req_cores[None], j),
                    take(req_mem[None], j))

        def more_to_place(free_cores, free_mem, key_task, bmin):
            # Lookahead twin of the loop's own extract-and-test: True iff
            # the engine's placement pass would do further work — the min
            # task fits somewhere, or (non-uniform demand) the engine's
            # suffix-min check says some *other* queued task still might.
            # Evaluating this at the *end* of each iteration (instead of
            # ``cont = place | ...``) means the loop exits without the
            # steady-state extra body run whose only product was
            # discovering that the cluster is full.
            _, rows, _, _, rc, rm = extract(key_task, bmin)
            has = jnp.min(rows, axis=1) < SENT
            any_feas = ((free_cores >= rc[:, None])
                        & (free_mem >= rm[:, None])).any(axis=1)
            if sig.uniform_demand:
                return has & any_feas
            left = key_task < SENT
            min_rc = jnp.min(jnp.where(left, req_cores.reshape(NB, _BLOCK),
                                       jnp.inf), axis=(1, 2))
            min_rm = jnp.min(jnp.where(left, req_mem.reshape(NB, _BLOCK),
                                       jnp.inf), axis=(1, 2))
            fitmin = ((free_cores >= min_rc[:, None])
                      & (free_mem >= min_rm[:, None])).any(axis=1)
            # a candidate that fails in-body is retired before the
            # engine's suffix check, so ``fitmin`` (which still includes
            # it) can trigger at most one extra no-op iteration — the
            # body's own lookahead then excludes it, exactly the engine.
            return has & (any_feas | fitmin)

        def place_body(st):
            b, rows, within, j, rc, rm = extract(st.key_task, st.bmin)
            has_task = (jnp.min(rows, axis=1) < SENT) & st.cont
            feas = ((st.free_cores >= rc[:, None])
                    & (st.free_mem >= rm[:, None]))
            any_feas = feas.any(axis=1)
            place = has_task & any_feas
            fail = has_task & ~any_feas
            n_sel, rr_pos_sel = select_node(feas, st.free_cores, st.free_mem,
                                            st.rr_i, x)
            # retire the extracted key, placed or failed (a failed one is
            # the engine's append to `still`; its suffix-min blocked check
            # lives in ``more_to_place``)
            retired = place | fail
            key_task = put(st.key_task, (b, within), retired, SENT)
            rows = put(rows, within, retired, SENT)
            bmin = put(st.bmin, b, retired, jnp.min(rows, axis=1))
            # apply the placement to the chosen node's first free slot
            c_sel = jnp.argmax(take(st.sord, n_sel) == SENT,
                               axis=1).astype(jnp.int32)
            slot = (n_sel, c_sel)
            free_cores = put(st.free_cores, n_sel, place,
                             st.free_cores - rc[:, None])
            free_mem = put(st.free_mem, n_sel, place,
                           st.free_mem - rm[:, None])
            n_running = put(st.n_running, n_sel, place, st.n_running + 1)
            total_running = st.total_running + place.astype(jnp.int32)
            rem_cpu = put(st.rem_cpu, slot, place, take(work_cpu, j))
            rem_mem = put(st.rem_mem, slot, place, take(work_mem, j))
            rem_io = put(st.rem_io, slot, place, take(work_io, j))
            sord = put(st.sord, slot, place, st.start_ctr)
            task_of = put(st.task_of, slot, place, j)
            qrank = put(st.qrank, j, place, SENT)
            node_of = put(st.node_of, j, place, n_sel)
            start_t_task = put(st.start_t_task, j, place, t)
            start_ctr = st.start_ctr + place.astype(jnp.int32)
            rr_i = st.rr_i
            if kind == "roundrobin":
                rr_i = jnp.where(place, (rr_pos_sel + 1) % N, rr_i)
            cont = more_to_place(free_cores, free_mem, key_task, bmin)
            return _Place(
                free_cores=free_cores, free_mem=free_mem, n_running=n_running,
                total_running=total_running, rem_cpu=rem_cpu,
                rem_mem=rem_mem, rem_io=rem_io, sord=sord, task_of=task_of,
                qrank=qrank, key_task=key_task, bmin=bmin,
                start_ctr=start_ctr, rr_i=rr_i, node_of=node_of,
                start_t_task=start_t_task, cont=cont, it=st.it + 1)

        cap_iter = TT + sig.S + 2
        key_blocks0 = key_task0.reshape(R, NB, _BLOCK)
        bmin0 = key_blocks0.min(axis=2)
        cont0 = ((c.n_finished < T)
                 & more_to_place(c.free_cores, c.free_mem, key_blocks0, bmin0))
        p = lax.while_loop(
            lambda st: jnp.any(st.cont) & (st.it < cap_iter), place_body,
            _Place(free_cores=c.free_cores, free_mem=c.free_mem,
                   n_running=c.n_running, total_running=c.total_running,
                   rem_cpu=c.rem_cpu, rem_mem=c.rem_mem, rem_io=c.rem_io,
                   sord=c.sord, task_of=c.task_of, qrank=qrank,
                   key_task=key_blocks0, bmin=bmin0, start_ctr=c.start_ctr,
                   rr_i=c.rr_i, node_of=c.node_of,
                   start_t_task=c.start_t_task, cont=cont0, it=0))
        place_iters = c.place_iters + p.it.astype(jnp.int32)

        # ---- next event: earliest finish over active slots (first-min by
        # start ordinal == the engine's append-ordered dense-slot argmin)
        cpu, mem = ks.node_rates(p.free_cores, mem_denom_table[p.n_running],
                                 cpu_base[None, :], mem_base[None, :],
                                 cores_f[None, :], sig.smt_penalty)
        io_eff = io_seq[None, :] / io_denom_table[p.total_running][:, None]
        tl = ks.time_left(p.rem_cpu, p.rem_mem, p.rem_io, cpu, mem, io_eff)
        active = p.sord < SENT
        dt, j_slot = ks.first_min_by_order(
            tl.reshape(R, sig.S), p.sord.reshape(R, sig.S),
            active.reshape(R, sig.S))
        done = c.n_finished >= T
        idle = (p.total_running == 0) & ~done
        do_fin = ~done & ~idle

        if sig.has_arrivals:
            next_arr = jnp.min(jnp.where(deps_left == 0, submit_t[None, :],
                                         jnp.inf), axis=1)
            t_new = jnp.where(done, t,
                              jnp.where(idle, jnp.maximum(t, next_arr),
                                        t + dt))
        else:
            t_new = jnp.where(do_fin, t + dt, t)

        adv = ks.advance(p.rem_cpu, p.rem_mem, p.rem_io, tl, dt)
        g = (do_fin & (dt > 0.0))[:, None, None]
        rem_cpu = jnp.where(g, adv[0], p.rem_cpu)
        rem_mem = jnp.where(g, adv[1], p.rem_mem)
        rem_io = jnp.where(g, adv[2], p.rem_io)

        # ---- finish processing: free resources, log end/runtime, ready
        # the dependents (engine: _finish + _on_done); every write is gated
        # by do_fin, so a replica that finishes nothing reads a slot and a
        # task it leaves alone
        fin = (j_slot // CAP, j_slot % CAP)
        j_task = take(p.task_of, fin)
        free_cores = put(
            p.free_cores, fin[0], do_fin,
            p.free_cores + take(req_cores[None], j_task)[:, None])
        free_mem = put(
            p.free_mem, fin[0], do_fin,
            p.free_mem + take(req_mem[None], j_task)[:, None])
        n_running = put(p.n_running, fin[0], do_fin, p.n_running - 1)
        total_running = p.total_running - do_fin.astype(jnp.int32)
        rem_cpu = put(rem_cpu, fin, do_fin, 0.0)
        rem_mem = put(rem_mem, fin, do_fin, 0.0)
        rem_io = put(rem_io, fin, do_fin, 0.0)
        sord = put(p.sord, fin, do_fin, SENT)
        end_t_task = put(c.end_t_task, j_task, do_fin, t_new)
        finish_step = put(c.finish_step, j_task, do_fin, s)
        n_finished = c.n_finished + do_fin.astype(jnp.int32)

        cnt, sm = c.cnt, c.sm
        if kind == "sjfn":            # TraceDB._runtime_agg, finish order
            kf = take(name_idx[None], j_task)
            runtime = t_new - take(p.start_t_task, j_task)
            cnt = put(cnt, kf, do_fin, cnt + 1.0)
            sm = put(sm, kf, do_fin, sm + runtime[:, None])

        # ---- dependents: decrement the finished task's dependents'
        # counters; newly-ready tasks get next step's batch base.  A task
        # listed twice among one task's dependents is decremented once.
        depi = take(dependents[None], j_task)        # [R, D]
        hit = do_fin[:, None] & jnp.any(depi[:, :, None] == seq, axis=1)
        dl = deps_left - hit.astype(jnp.int32)
        ready_now = hit & (dl == 0)
        if sig.has_arrivals:
            ready_now = ready_now & (submit_t[None, :] <= t_new[:, None])
        qrank = jnp.where(ready_now, (s + 1) * TT + seq[None, :], p.qrank)
        deps_left = jnp.where(ready_now, -1, dl)
        return c._replace(
            t=t_new, free_cores=free_cores, free_mem=free_mem,
            n_running=n_running, total_running=total_running,
            rem_cpu=rem_cpu, rem_mem=rem_mem, rem_io=rem_io, sord=sord,
            task_of=p.task_of, qrank=qrank, deps_left=deps_left,
            start_ctr=p.start_ctr, rr_i=p.rr_i, cnt=cnt, sm=sm,
            n_finished=n_finished, node_of=p.node_of,
            start_t_task=p.start_t_task, end_t_task=end_t_task,
            finish_step=finish_step, place_iters=place_iters), None

    @jax.jit
    def scan(carry, x):
        # sjfn's [TT, K] name one-hot, built once per call for its rank
        # lookups; the other schedulers read no rank
        name_onehot = (x.name_idx[:, None] == jnp.arange(K, dtype=jnp.int32)
                       if kind == "sjfn" else None)
        carry, _ = lax.scan(lambda c, s: step(c, s, x, name_onehot), carry,
                            jnp.arange(sig.n_steps, dtype=jnp.int32))
        return carry

    return scan


def _build_scan(top: _Topology):
    """The scan program of ``top``'s static signature, looked up in the
    program cache (made on a miss), and this call's runtime arguments.

    Returns ``(scan, args)``: ``args`` is the initial carry and an
    :class:`_Inputs` of the topology's arrays and this call's work draws;
    ``scan(*args)`` runs every replica to completion.  Topologies with the
    same static signature get the same ``scan`` object.  Build and call it
    under ``jax.enable_x64(True)``."""
    import jax
    import jax.numpy as jnp

    R, N, CAP, TT, T, K = top.n_replicas, top.N, top.CAP, top.TT, top.T, top.K
    cfg = top.cfg
    work_pad = np.zeros((R, TT, 3))
    work_pad[:, :T] = top.replica_work()
    host = _Inputs(
        top.cores_f, top.mem_gb, top.cpu_base, top.mem_base, top.io_seq,
        top.req_cores, top.req_mem, top.submit_t, top.name_idx,
        top.dependents,
        work_pad[:, :, 0], work_pad[:, :, 1], work_pad[:, :, 2],
        top.sched)
    sig = _Signature(
        top.kind, R, N, CAP, TT, T, K, top.D, top.S, top.n_steps,
        top.qshift, top.has_arrivals, top.uniform_demand,
        float(cfg.smt_penalty), float(cfg.io_gamma), float(cfg.mem_beta),
        float(cfg.mem_cap),
        tuple((a.shape, a.dtype.str) for a in host),
        jax.config.jax_default_device)
    scan = _PROGRAMS.program(sig)

    # ---- initial carry (numpy-built, converted inside the x64 context)
    qrank0 = np.full((R, TT), _INT_SENTINEL, np.int32)
    deps0 = np.broadcast_to(top.deps_left0, (R, TT)).copy()
    ready0 = (top.deps_left0 == 0) & (top.submit_t <= 0.0)
    ready0[T:] = False
    qrank0[:, ready0] = top.seq[ready0]
    deps0[:, ready0] = -1
    carry0 = _Carry(
        t=jnp.zeros(R),
        free_cores=jnp.tile(jnp.asarray(top.cores_f), (R, 1)),
        free_mem=jnp.tile(jnp.asarray(top.mem_gb), (R, 1)),
        n_running=jnp.zeros((R, N), jnp.int32),
        total_running=jnp.zeros(R, jnp.int32),
        rem_cpu=jnp.zeros((R, N, CAP)), rem_mem=jnp.zeros((R, N, CAP)),
        rem_io=jnp.zeros((R, N, CAP)),
        sord=jnp.full((R, N, CAP), _INT_SENTINEL, jnp.int32),
        task_of=jnp.zeros((R, N, CAP), jnp.int32),
        qrank=jnp.asarray(qrank0),
        deps_left=jnp.asarray(deps0),
        start_ctr=jnp.zeros(R, jnp.int32), rr_i=jnp.zeros(R, jnp.int32),
        cnt=jnp.zeros((R, K)), sm=jnp.zeros((R, K)),
        n_finished=jnp.zeros(R, jnp.int32),
        node_of=jnp.full((R, TT), -1, jnp.int32),
        start_t_task=jnp.zeros((R, TT)), end_t_task=jnp.zeros((R, TT)),
        finish_step=jnp.full((R, TT), -1, jnp.int32),
        place_iters=jnp.int32(0))
    return scan, (carry0, _Inputs(*(jnp.asarray(a) for a in host)))


# ------------------------------------------------------------------ public
def run_ensemble(specs, submissions, scheduler, n_replicas, *,
                 config: Optional[EngineConfig] = None,
                 seed_stride: int = 1) -> EnsembleResult:
    """Run ``n_replicas`` Monte-Carlo replicas of the fixed topology as one
    jitted ``lax.scan`` program.  See the module docstring for the
    supported feature matrix and the RNG-stream mapping; unsupported
    configurations raise ``NotImplementedError`` at build time.

    The scan program is cached per static signature (:func:`_build_scan`)
    with its compiled executable: a call whose topology matches a cached
    program's signature, with fresh draws or not, runs the kept executable
    and does no tracing, lowering or compiling; any other call compiles
    its program once.  The program is then run once.  ``timings`` is the
    call's ``tracing.Record``: the seconds of the spans ``ensemble.build``
    (topology, work draws, program lookup and uploads),
    ``ensemble.compile`` (lowering and compiling, on a miss only),
    ``ensemble.run`` (the run, to ``block_until_ready``),
    ``ensemble.fetch`` (copies to the host and the result) and
    ``ensemble.release`` (dropping the call's device buffers; the program
    stays cached) as ``build_s``, ``compile_s``, ``run_s``, ``fetch_s``
    and ``release_s``; the counts ``compiles`` and ``program_hits`` (one
    of them 1, the other 0), and the scan's ``n_steps``.  Two more count
    the scan's work: ``key_rebuilds``, the steps on which the extraction
    keys were built, is ``n_steps``, since every signature builds them
    afresh on every step; ``place_iters``, counted inside the scan, is the
    placement ``while_loop``'s iterations summed over the steps (an
    iteration serves every replica at once)."""
    import jax

    rec = tracing.Record()
    with jax.enable_x64(True):
        with rec.span("ensemble.build"):
            top = _Topology(specs, submissions, scheduler, config,
                            n_replicas, seed_stride)
            scan, args = _build_scan(top)
        compiled = _PROGRAMS.compiled(scan)
        if compiled is None:
            with rec.span("ensemble.compile", count="compiles"):
                compiled = scan.lower(*args).compile()
            _PROGRAMS.keep(scan, compiled)
        else:
            rec.count("program_hits")
        with rec.span("ensemble.run"):
            out = jax.block_until_ready(compiled(*args))

    with rec.span("ensemble.fetch"):
        T = top.T
        n_fin = np.asarray(out.n_finished)
        if not (n_fin == T).all():
            raise RuntimeError(
                f"ensemble scan under-ran: {int(n_fin.min())}/{T} finishes "
                f"within {top.n_steps} steps — step budget bug")
        end_t = np.asarray(out.end_t_task)[:, :T]
        fstep = np.asarray(out.finish_step)[:, :T]
        res = EnsembleResult(
            instances=top.instances, makespan=end_t.max(axis=1),
            node_idx=np.asarray(out.node_of)[:, :T].astype(np.int32),
            start_t=np.asarray(out.start_t_task)[:, :T], end_t=end_t,
            finish_order=np.argsort(fstep, axis=1,
                                    kind="stable").astype(np.int32))
        rec.count("key_rebuilds", top.n_steps)
        rec.count("place_iters", int(out.place_iters))
    with rec.span("ensemble.release"):
        del scan, args, compiled, out
    res.timings = {"compiles": 0, "program_hits": 0, **rec.as_dict(),
                   "n_steps": top.n_steps}
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
