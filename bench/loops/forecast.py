"""Forecasts through the batched scan: one call is one ``run_ensemble`` of
the configuration's submissions with fresh work draws for every replica,
as a Monte-Carlo user asks for them.

The check re-simulates a sample of the window's replicas, drawn from the
seed and with the longest makespan among them, in the plain reference
engine, spread over child processes that never touch the chip.
"""
from __future__ import annotations

import multiprocessing
import os

import numpy as np

from bench import gen
from bench.reference import engine as ref_engine

WINDOW_STREAM, CHECK_STREAM, WARM_STREAM = 1, 2, 3


def _reference_job(job) -> dict:
    nodes, wfs, subs, seed, scheduler, negspeed, dtype = job
    insts = []
    for s in subs:
        insts += gen.instantiate(wfs[s["workflow"]], s["run_id"], seed,
                                 s["prefix"])
    top = ref_engine.topology(nodes, insts)
    work = np.array([i["work"] for i in insts], np.float64)
    return ref_engine.simulate(top, work, scheduler, negspeed,
                               dtype=np.dtype(dtype).type)


def _program_nodes(nodes):
    from repro.core.profiler import NodeSpec
    return [NodeSpec(n["name"], n["machine"], n["cores"], n["mem_gb"],
                     cpu_speed=n["cpu_speed"], mem_bw=n["mem_bw"],
                     io_seq=n["io_seq"], io_rand=n["io_rand"],
                     net_gbps=n["net_gbps"], app_factor=n["app_factor"])
            for n in nodes]


def _program_workflow(wf):
    from repro.workflow.dag import AbstractTask, WorkflowSpec
    return WorkflowSpec(wf["name"], [
        AbstractTask(t["name"], t["n_instances"], dict(t["work"]),
                     t["peak_mem_gb"], deps=tuple(t["deps"]),
                     req_cores=t["req_cores"], req_mem_gb=t["req_mem_gb"])
        for t in wf["tasks"]])


class Loop:
    span = "forecast"

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.seed = seed
        self.traffic = traffic
        self.scheduler_name = traffic["scheduler"]
        self.n_replicas = traffic["replicas"]
        self.nodes = gen.cluster(cfg["cluster"])
        self.wfs = {w["name"]: gen.workflow(w) for w in cfg["workflows"]}
        self.subs = cfg["submissions"]
        self.calls: list[dict] = []
        self.negspeed = None
        if self.scheduler_name == "sjfn":
            self.negspeed = np.array(
                [-round(gen.synthetic_cpu(n, traffic["scheduler_seed"]), -1)
                 for n in self.nodes])

    # ------------------------------------------------------------ program
    def setup(self):
        from repro.core.scheduler import make_scheduler
        self.specs = _program_nodes(self.nodes)
        self.specs_wf = {k: _program_workflow(w) for k, w in self.wfs.items()}
        self.scheduler = make_scheduler(self.scheduler_name, self.specs,
                                        seed=self.traffic["scheduler_seed"])
        # fresh draws, as every call of the window has: the scan's program
        # changes with its draws, so a warm call served from the compile
        # cache would leave the compiler's first use inside the window
        self._forecast(gen.derive_seed(self.seed, WARM_STREAM))

    def _forecast(self, seed: int):
        from repro.workflow import ensemble
        subs = [ensemble.Submission(self.specs_wf[s["workflow"]],
                                    run_id=s["run_id"], seed=seed,
                                    prefix=s["prefix"]) for s in self.subs]
        return ensemble.run_ensemble(self.specs, subs, self.scheduler,
                                     self.n_replicas)

    def call(self, i: int) -> dict:
        seed = gen.derive_seed(self.seed, WINDOW_STREAM, i)
        rec = {"seed": seed}
        self.calls.append(rec)
        res = self._forecast(seed)
        rec.update(replicas=len(res.makespan), timings=dict(res.timings),
                   node_idx=res.node_idx, finish_order=res.finish_order,
                   start_t=res.start_t, end_t=res.end_t,
                   makespan=res.makespan)
        return rec

    # -------------------------------------------------------------- check
    def sample(self) -> list[tuple[int, int]]:
        """``(call, replica)`` pairs to check: a seeded sample of the
        window's completed replicas, with the longest makespan among them."""
        done = [(i, r) for i, c in enumerate(self.calls) if "makespan" in c
                for r in range(self.n_replicas)]
        if not done:
            return []
        rng = np.random.default_rng(gen.derive_seed(self.seed, CHECK_STREAM))
        n = min(self.traffic["check_replicas"], len(done))
        picked = [done[j] for j in rng.choice(len(done), n, replace=False)]
        longest = max(((i, r) for i, c in enumerate(self.calls)
                       if "makespan" in c
                       for r in range(len(c["makespan"]))),
                      key=lambda ir: self.calls[ir[0]]["makespan"][ir[1]])
        if longest not in picked:
            picked[-1] = longest
        return sorted(picked)

    def _references(self, pairs, dtype):
        jobs = [(self.nodes, self.wfs, self.subs,
                 self.calls[i]["seed"] + r, self.scheduler_name,
                 self.negspeed, dtype) for i, r in pairs]
        workers = min(len(jobs), 8, max(1, (os.cpu_count() or 2) - 2))
        if workers <= 1:
            return [_reference_job(j) for j in jobs]
        prev = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"       # children never reach the chip
        try:
            with multiprocessing.get_context("spawn").Pool(workers) as pool:
                return pool.map(_reference_job, jobs)
        finally:
            if prev is None:
                del os.environ["JAX_PLATFORMS"]
            else:
                os.environ["JAX_PLATFORMS"] = prev

    def check(self, control: bool = False) -> dict:
        from bench import compare
        pairs = self.sample()
        refs = self._references(pairs, "float64")
        if control:
            progs = self._references(pairs, "float32")
        else:
            progs = []
            for i, r in pairs:
                c = self.calls[i]
                progs.append(None if r >= len(c["makespan"]) else
                             {k: c[k][r] for k in ("node_idx", "finish_order",
                                                   "start_t", "end_t",
                                                   "makespan")})
        self.checked = len(pairs)
        return compare.forecast_numbers(list(zip(progs, refs)))

    def release(self):
        """Drop the program's state before the reference runs."""
        self.scheduler = None
