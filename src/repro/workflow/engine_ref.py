"""FROZEN seed engine — the reference implementation.

This is a verbatim copy of the discrete-event engine as it shipped in the
seed commit, kept for two purposes:

  * ``tests/test_engine_equivalence.py`` asserts that the vectorized engine
    in ``engine.py`` reproduces this implementation's makespans and
    assignment traces bit-for-bit on the paper clusters;
  * ``benchmarks/engine_bench.py`` uses it as the wall-clock baseline for
    the fleet-scale speedup trajectory.

Do NOT optimize or refactor this module; fix only what a comparison test
requires.  All behaviour changes belong in ``engine.py``.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
from typing import Callable, Optional

import numpy as np

from repro.core.monitor import TaskTrace, TraceDB
from repro.core.profiler import NodeSpec
from repro.workflow.dag import TaskInstance, WorkflowSpec, instantiate

# Contention defaults: calibrated against the paper's Fig. 4/5 gaps
# (see EXPERIMENTS.md §Calibration); overridable per EngineConfig.
MEM_SHARE_BETA = 0.62        # memory-bandwidth contention strength
MEM_SHARE_CAP = 8.0
IO_SHARE_GAMMA = 0.08        # shared-volume contention strength
SMT_PENALTY = 0.15           # CPU slowdown at full occupancy (vCPUs are SMT
                             # threads; single-threaded benchmarks miss this)
BW_EXP = 0.30                 # node bandwidth ~ (cores/8)**BW_EXP


@dataclasses.dataclass
class SimNode:
    spec: NodeSpec
    free_cores: int
    free_mem: float
    running: set = dataclasses.field(default_factory=set)
    disabled: bool = False
    slow_factor: float = 1.0   # straggler injection

    @property
    def name(self):
        return self.spec.name

    def load(self) -> float:
        cores = 1.0 - self.free_cores / self.spec.cores
        mem = 1.0 - self.free_mem / self.spec.mem_gb
        return 0.5 * (cores + mem)


@dataclasses.dataclass
class EngineConfig:
    speculation: bool = False
    speculation_factor: float = 1.8   # relaunch if runtime > factor * p95
    seed: int = 0
    usage_noise: float = 0.03
    mem_beta: float = MEM_SHARE_BETA
    mem_cap: float = MEM_SHARE_CAP
    io_gamma: float = IO_SHARE_GAMMA
    smt_penalty: float = SMT_PENALTY
    bw_exp: float = BW_EXP


class Engine:
    def __init__(self, specs: list[NodeSpec], scheduler, db: TraceDB,
                 config: EngineConfig = EngineConfig(),
                 disabled_nodes: Optional[set] = None):
        self.nodes = {s.name: SimNode(s, s.cores, s.mem_gb) for s in specs}
        for n in disabled_nodes or ():
            self.nodes[n].disabled = True
        self.scheduler = scheduler
        self.db = db
        self.cfg = config
        self.rng = np.random.default_rng(config.seed)
        self.t = 0.0
        self.queue: list[TaskInstance] = []
        self.running: dict[str, TaskInstance] = {}
        self.done: dict[str, TaskInstance] = {}
        self.all_tasks: dict[str, TaskInstance] = {}
        self.assignments: list[tuple] = []       # (task_name, node, start, end)
        self._failures: list[tuple] = []         # (time, node)
        self._spec_copies: dict[str, str] = {}   # primary id -> copy id
        self._uid = itertools.count()

    # ------------------------------------------------------------ submission
    def submit(self, spec: WorkflowSpec, run_id: int, seed: int = 0,
               at: float = 0.0, input_scale: float = 1.0):
        for inst in instantiate(spec, run_id, seed, input_scale):
            inst.submit_t = at
            self.all_tasks[inst.instance] = inst

    def fail_node_at(self, t: float, node: str):
        self._failures.append((t, node))

    # ------------------------------------------------------------- mechanics
    def _rates(self, task: TaskInstance) -> dict:
        node = self.nodes[task.node]
        mem_sharers = len(node.running)
        io_active = len(self.running)
        slow = node.slow_factor * node.spec.app_factor
        # total memory bandwidth scales sublinearly with the VM's core count
        # (bigger GCP shapes span more memory channels); benchmarks are
        # single-threaded so Table IV numbers are unaffected
        bw_scale = (node.spec.cores / 8.0) ** self.cfg.bw_exp
        # SMT/LLC contention: past 50% vCPU occupancy, co-runners share
        # physical cores and last-level cache
        occ = 1.0 - node.free_cores / node.spec.cores
        smt = 1.0 - self.cfg.smt_penalty * max(0.0, occ - 0.5) / 0.5
        return {
            "cpu": node.spec.cpu_speed * slow * smt,
            "mem": node.spec.mem_bw * 0.02 * slow * bw_scale
                   / min(1.0 + self.cfg.mem_beta * max(0, mem_sharers - 1),
                         self.cfg.mem_cap),
            "io": node.spec.io_seq / (1.0 + self.cfg.io_gamma * max(0, io_active - 1)),
        }

    def _time_left(self, task: TaskInstance) -> float:
        rates = self._rates(task)
        rem = task.remaining
        # plain left-to-right adds: from Python 3.12 on, sum() of floats is
        # compensated and would no longer give the seed's values
        return rem["cpu"] / rates["cpu"] + rem["mem"] / rates["mem"] \
            + rem["io"] / rates["io"]

    def _feasible(self, task: TaskInstance) -> dict:
        feas = {n.name: (not n.disabled and n.free_cores >= task.req_cores
                         and n.free_mem >= task.req_mem_gb)
                for n in self.nodes.values()}
        if task.speculative_of:
            # a speculative copy must not land beside its (straggling) original
            orig = self.all_tasks.get(task.speculative_of)
            if orig is not None and orig.node:
                feas[orig.node] = False
        return feas

    def _start(self, task: TaskInstance, node_name: str):
        node = self.nodes[node_name]
        node.free_cores -= task.req_cores
        node.free_mem -= task.req_mem_gb
        node.running.add(task.instance)
        task.state = "running"
        task.node = node_name
        task.start_t = self.t
        task.remaining = dict(task.work)
        self.running[task.instance] = task

    def _finish(self, task: TaskInstance, record: bool = True):
        node = self.nodes[task.node]
        node.free_cores += task.req_cores
        node.free_mem += task.req_mem_gb
        node.running.discard(task.instance)
        self.running.pop(task.instance, None)
        task.state = "done"
        task.end_t = self.t
        self.done[task.instance] = task
        self.assignments.append((task.name, task.node, task.start_t, task.end_t))
        if record and task.speculative_of is None:
            total = sum(task.work.values()) or 1.0
            noise = lambda: 1.0 + self.rng.normal(0, self.cfg.usage_noise)
            usage = {
                "cpu": 100.0 * task.req_cores * task.work["cpu"] / total * noise(),
                "mem": task.peak_mem_gb * noise(),
                "io": task.work["io"] * noise(),
            }
            self.db.add(TaskTrace(task.workflow, task.name, task.instance,
                                  task.run_id, task.node,
                                  self.t - task.start_t, usage))

    def _kill(self, task: TaskInstance, requeue: bool):
        node = self.nodes[task.node]
        node.free_cores += task.req_cores
        node.free_mem += task.req_mem_gb
        node.running.discard(task.instance)
        self.running.pop(task.instance, None)
        if requeue:
            task.state = "ready"
            task.node = None
            task.remaining = None
            self.queue.append(task)
        else:
            task.state = "killed"

    def _promote_ready(self):
        queued = {t.instance for t in self.queue}
        for t in self.all_tasks.values():
            if t.state == "pending" and t.submit_t <= self.t and \
                    all(d in self.done or d in self._finished_names()
                        for d in t.deps):
                t.state = "ready"
                if t.instance not in queued:
                    self.queue.append(t)

    def _finished_names(self):
        return self.done

    def _schedule(self):
        self.queue = self.scheduler.order(self.queue, self.db)
        still = []
        for task in self.queue:
            node = self.scheduler.select_node(
                task, self.nodes, self._feasible(task), self.db)
            if node is None:
                still.append(task)
            else:
                self._start(task, node)
        self.queue = still

    def _maybe_speculate(self):
        if not self.cfg.speculation:
            return
        for task in list(self.running.values()):
            if task.speculative_of or task.instance in self._spec_copies:
                continue
            p95 = self.db.runtime_quantile(task.workflow, task.name, 0.95)
            if p95 and (self.t - task.start_t) > self.cfg.speculation_factor * p95:
                copy = dataclasses.replace(
                    task, instance=f"{task.instance}~spec{next(self._uid)}",
                    state="ready", node=None, remaining=None,
                    speculative_of=task.instance)
                self.all_tasks[copy.instance] = copy
                self.queue.append(copy)
                self._spec_copies[task.instance] = copy.instance

    # ------------------------------------------------------------------ run
    def run(self, max_t: float = 10_000_000.0) -> dict:
        self._failures.sort()
        fail_i = 0
        while True:
            self._promote_ready()
            self._schedule()
            self._maybe_speculate()
            if not self.running:
                if any(t.state in ("pending", "ready") for t in self.all_tasks.values()):
                    # deadlock or all nodes disabled: advance past next failure
                    if fail_i < len(self._failures):
                        self.t = self._failures[fail_i][1]
                    else:
                        raise RuntimeError("tasks stuck with no runnable node")
                else:
                    break
            # next event: earliest finishing task, next failure, or the next
            # speculation check (without it the loop can jump straight past
            # the straggler threshold)
            finish_times = {tid: self._time_left(t) for tid, t in self.running.items()}
            tid_min, dt = min(finish_times.items(), key=lambda kv: kv[1])
            if self.cfg.speculation:
                for t_ in self.running.values():
                    if t_.speculative_of or t_.instance in self._spec_copies:
                        continue
                    p95 = self.db.runtime_quantile(t_.workflow, t_.name, 0.95)
                    if p95:
                        wake = (t_.start_t + self.cfg.speculation_factor * p95
                                + 1e-6) - self.t
                        if 0 < wake < dt:
                            tid_min, dt = None, wake
            t_next = self.t + dt
            if fail_i < len(self._failures) and self._failures[fail_i][0] < t_next:
                ft, fnode = self._failures[fail_i]
                dt = max(ft - self.t, 0.0)
                self._advance(dt)
                self.t = ft
                fail_i += 1
                node = self.nodes[fnode]
                node.disabled = True
                for tid in list(node.running):
                    self._kill(self.running[tid], requeue=True)
                continue
            self._advance(dt)
            self.t = t_next
            if tid_min is None:        # speculation wake-up, nothing finished
                continue
            task = self.running[tid_min]
            self._finish(task)
            # speculative pair resolution: first finisher wins
            other = self._spec_copies.pop(task.speculative_of or task.instance, None)
            if task.speculative_of and task.speculative_of in self.running:
                self._kill(self.running[task.speculative_of], requeue=False)
                self.done[task.speculative_of] = task  # result available
            elif other and other in self.running:
                self._kill(self.running[other], requeue=False)
            if self.t > max_t:
                raise RuntimeError("simulation exceeded max_t")
        makespan = max((t.end_t for t in self.done.values()), default=0.0)
        return {"makespan": makespan, "assignments": self.assignments}

    def _advance(self, dt: float):
        if dt <= 0:
            return
        for task in self.running.values():
            left = self._time_left(task)
            frac = min(dt / left, 1.0) if left > 0 else 1.0
            for f in task.remaining:
                task.remaining[f] *= (1.0 - frac)
