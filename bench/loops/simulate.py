"""Tarema end to end on the host simulator: one call is one complete run
of the paper's scheduler through the entry points a user calls.
``make_scheduler`` does phase 1 (synthetic profiles, ``choose_k`` on the
default device, the groups' description); ``Engine.run`` does phases 2
and 3 online over the configuration's submissions.  Every call draws
fresh profile noise, work jitter and usage noise from its own seed.

The check runs a sample of the window's calls, drawn from the seed and
with the longest makespan among them, in the plain references, in child
processes that never touch the chip: ``bench/reference/kmeans.py`` groups
the call's profiles on the CPU, its group ids are renamed to the
program's by the one-to-one map that matches the two groupings best (the
numbering orders tied groups, and it is the only thing taken from the
program), and ``bench/reference/tarema.py`` simulates the run.
"""
from __future__ import annotations

import itertools
import multiprocessing
import os

import numpy as np

from bench import gen
from bench.gen import tarema as gen_tarema
from bench.loops import forecast

WINDOW_STREAM, CHECK_STREAM, WARM_STREAM = 1, 2, 3


def rename(ref_labels, prog_labels) -> np.ndarray:
    """``ref_labels`` with each group id replaced by the program's id under
    the one-to-one map that puts the most nodes in the same group; a group
    left without a partner takes an id the program does not use."""
    ref_ids, a = np.unique(ref_labels, return_inverse=True)
    prog_ids, b = np.unique(prog_labels, return_inverse=True)
    n = max(len(ref_ids), len(prog_ids))
    overlap = np.zeros((n, n), np.int64)
    np.add.at(overlap, (a, b), 1)
    perm = max(itertools.permutations(range(n)),
               key=lambda p: overlap[np.arange(n), list(p)].sum())
    target = np.arange(n) + int(prog_ids.max()) + 1 - len(prog_ids)
    target[:len(prog_ids)] = prog_ids
    return target[np.asarray(perm)][a]


def _in_children(fn, jobs) -> list:
    """``fn`` over ``jobs`` in spawned child processes held to the CPU, so
    that none of them reaches for the chip this process holds (inline
    where one worker would do)."""
    workers = min(len(jobs), 8, max(1, (os.cpu_count() or 2) - 2))
    if workers <= 1:
        return [fn(j) for j in jobs]
    prev = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        with multiprocessing.get_context("spawn").Pool(workers) as pool:
            return pool.map(fn, jobs)
    finally:
        if prev is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = prev


def _reference_job(job) -> dict:
    """Group and simulate one call with the plain references."""
    import jax

    from bench.reference import kmeans as ref_kmeans
    from bench.reference import tarema as ref_tarema

    nodes, wfs, subs, seed, prog_labels, k_max, restarts, dtype = job
    X = gen_tarema.profiles(nodes, seed)
    with jax.default_device(jax.devices("cpu")[0]):
        grp = ref_kmeans.choose_k(X, k_max=k_max, restarts=restarts)
    insts, peak = [], []
    for s in subs:
        wf = wfs[s["workflow"]]
        insts += gen.instantiate(wf, s["run_id"], seed, s["prefix"])
        peak += gen_tarema.peak_mem(wf, s["run_id"], seed)
    out = ref_tarema.simulate(nodes, insts, peak, X,
                              rename(grp["labels"], prog_labels), seed,
                              dtype=np.dtype(dtype).type)
    out.update(k=int(grp["k"]), labels=np.asarray(grp["labels"]))
    return out


class Loop:
    span = "simulate"

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        if traffic["scheduler"] != "tarema" or traffic["replicas"] != 1:
            raise ValueError("the simulate loop runs one Tarema run a call")
        self.seed = seed
        self.traffic = traffic
        self.nodes = gen.cluster(cfg["cluster"])
        self.wfs = {w["name"]: gen.workflow(w) for w in cfg["workflows"]}
        self.subs = cfg["submissions"]
        self.calls: list[dict] = []

    # ------------------------------------------------------------ program
    def setup(self):
        self.specs = forecast._program_nodes(self.nodes)
        self.specs_wf = {k: forecast._program_workflow(w)
                         for k, w in self.wfs.items()}
        self._run(gen.derive_seed(self.seed, WARM_STREAM))

    def _run(self, seed: int) -> dict:
        from repro.core.monitor import TraceDB
        from repro.core.scheduler import make_scheduler
        from repro.workflow.engine import Engine, EngineConfig

        sched = make_scheduler(self.traffic["scheduler"], self.specs,
                               seed=seed)
        eng = Engine(self.specs, sched, TraceDB(), EngineConfig(seed=seed))
        for s in self.subs:
            eng.submit(self.specs_wf[s["workflow"]], s["run_id"], seed=seed,
                       prefix=s["prefix"])
        res = eng.run()
        tasks = list(eng.all_tasks.values())
        task_idx = {t.instance: j for j, t in enumerate(tasks)}
        node_idx = {s.name: i for i, s in enumerate(self.specs)}
        return {"replicas": 1, "k": int(sched.grouping["k"]),
                "labels": np.asarray(sched.grouping["labels"], np.int8),
                "node_idx": np.array([node_idx[t.node] for t in tasks]),
                "start_t": np.array([t.start_t for t in tasks]),
                "end_t": np.array([t.end_t for t in tasks]),
                "finish_order": np.array([task_idx[a.instance]
                                          for a in eng.assignment_log
                                          if a.completed]),
                "makespan": res["makespan"],
                "timings": {"scheduler": getattr(sched, "timings", None),
                            "engine": res.get("timings")}}

    def call(self, i: int) -> dict:
        seed = gen.derive_seed(self.seed, WINDOW_STREAM, i)
        rec = {"seed": seed}
        self.calls.append(rec)
        rec.update(self._run(seed))
        return rec

    # -------------------------------------------------------------- check
    def sample(self) -> list[int]:
        """Calls to check: a seeded sample of the completed ones, with the
        longest makespan among them."""
        done = [i for i, c in enumerate(self.calls) if "makespan" in c]
        if not done:
            return []
        rng = np.random.default_rng(gen.derive_seed(self.seed, CHECK_STREAM))
        n = min(self.traffic["check_calls"], len(done))
        picked = [done[j] for j in rng.choice(len(done), n, replace=False)]
        longest = max(done, key=lambda i: self.calls[i]["makespan"])
        if longest not in picked:
            picked[-1] = longest
        return sorted(picked)

    def _references(self, picked, dtype):
        jobs = [(self.nodes, self.wfs, self.subs, self.calls[i]["seed"],
                 self.calls[i]["labels"], self.traffic["k_max"],
                 self.traffic["restarts"], dtype) for i in picked]
        return _in_children(_reference_job, jobs)

    def check(self, control: bool = False) -> dict:
        from bench import compare
        picked = self.sample()
        refs = self._references(picked, "float64")
        progs = self._references(picked, "float32") if control \
            else [self.calls[i] for i in picked]
        self.checked = len(picked)
        pairs = list(zip(progs, refs))
        return {**compare.grouping_numbers(pairs),
                **compare.forecast_numbers(pairs)}

    def release(self):
        pass
