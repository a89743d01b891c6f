"""Plain discrete-event reference of one simulated workflow run.

The semantics are those of the repository's frozen seed engine: one event
per finished task; at every event the ready queue is ordered by the
scheduler, every queued task is tried in turn on the scheduler's node among
those with room, and the next event is the running task with the least
time left, the earliest started winning a tie.  Rates follow the seed's
contention model, operand for operand, so that the same work gives the
same floats.  Ties between equally loaded nodes go to the lowest node
index (the ensemble's documented tie mapping).

Schedulers: ``fair`` (least loaded) and ``sjfn`` (queue by mean observed
runtime of the task's name, unknown names last; fastest node by the
profiled cpu score rounded to tens, then least loaded).

Imports numpy only, never the program: it runs in child processes that
must not reach for the chip.
"""
from __future__ import annotations

import numpy as np

# the seed engine's contention constants (EngineConfig defaults)
MEM_SHARE_BETA = 0.62
MEM_SHARE_CAP = 8.0
IO_SHARE_GAMMA = 0.08
SMT_PENALTY = 0.15
BW_EXP = 0.30


def topology(nodes: list[dict], instances: list[dict]) -> dict:
    """Index the instances of all submissions (in submission order) into
    the arrays :func:`simulate` takes; ``work`` stays per replica."""
    index = {inst["instance"]: j for j, inst in enumerate(instances)}
    names: dict = {}
    name_id = np.array([names.setdefault((i["workflow"], i["name"]), len(names))
                        for i in instances], np.int64)
    dependents = [[] for _ in instances]
    deps_n = np.zeros(len(instances), np.int64)
    for j, inst in enumerate(instances):
        deps_n[j] = len(inst["deps"])
        for d in inst["deps"]:
            dependents[index[d]].append(j)
    return {
        "cores": np.array([n["cores"] for n in nodes], np.int64),
        "mem_gb": np.array([n["mem_gb"] for n in nodes], np.float64),
        "cpu_speed": np.array([n["cpu_speed"] for n in nodes], np.float64),
        "mem_bw": np.array([n["mem_bw"] for n in nodes], np.float64),
        "io_seq": np.array([n["io_seq"] for n in nodes], np.float64),
        "app_factor": np.array([n["app_factor"] for n in nodes], np.float64),
        "req_cores": np.array([i["req_cores"] for i in instances], np.float64),
        "req_mem": np.array([i["req_mem_gb"] for i in instances], np.float64),
        "name_id": name_id, "n_names": len(names),
        "deps_n": deps_n, "dependents": dependents,
    }


def simulate(top: dict, work: np.ndarray, scheduler: str,
             negspeed: np.ndarray | None = None, dtype=np.float64) -> dict:
    """Run one replica.  ``work`` is [T, 3] (cpu, mem, io); ``negspeed`` is
    sjfn's per-node key (minus the profiled cpu score rounded to tens).
    ``dtype`` is the precision of every time, rate and work value.

    Returns node index, start and end time per task, the finish order (task
    indices) and the makespan."""
    f = dtype
    one = f(1.0)
    T = work.shape[0]
    cores = top["cores"].astype(f)
    mem_gb = top["mem_gb"].astype(f)
    slow = np.ones_like(cores) * top["app_factor"].astype(f)
    cpu_base = top["cpu_speed"].astype(f) * slow
    mem_base = top["mem_bw"].astype(f) * f(0.02) * slow \
        * (top["cores"] / 8.0).astype(f) ** f(BW_EXP)
    io_seq = top["io_seq"].astype(f)
    rc, rm = top["req_cores"].astype(f), top["req_mem"].astype(f)
    rem = work.astype(f).copy()
    free_c, free_m = cores.copy(), mem_gb.copy()
    n_on = np.zeros(len(cores), np.int64)
    deps_left = top["deps_n"].copy()
    dependents = top["dependents"]
    name_id = top["name_id"]
    rt_sum = np.zeros(top["n_names"], f)
    rt_cnt = np.zeros(top["n_names"], np.int64)
    node_of = np.full(T, -1, np.int64)
    start = np.zeros(T, f)
    end = np.zeros(T, f)
    finish_order = []
    running: list[int] = []              # start order
    queue = [j for j in range(T) if deps_left[j] == 0]
    t = f(0.0)
    while True:
        if scheduler == "sjfn" and len(queue) > 1:
            est = np.where(rt_cnt > 0, rt_sum / np.maximum(rt_cnt, 1), np.inf)
            queue = [queue[i] for i in
                     np.argsort(est[name_id[queue]], kind="stable")]
        still = []
        for j in queue:
            feas = (free_c >= rc[j]) & (free_m >= rm[j])
            if not feas.any():
                still.append(j)
                continue
            load = f(0.5) * ((one - free_c / cores) + (one - free_m / mem_gb))
            if scheduler == "sjfn":
                key = np.where(feas, negspeed, np.inf)
                feas = feas & (negspeed == key.min())
            elif scheduler != "fair":
                raise ValueError(f"unsupported scheduler {scheduler!r}")
            n = int(np.argmin(np.where(feas, load, np.inf)))
            free_c[n] -= rc[j]
            free_m[n] -= rm[j]
            n_on[n] += 1
            node_of[j] = n
            start[j] = t
            running.append(j)
        queue = still
        if not running:
            if queue or len(finish_order) < T:
                raise RuntimeError("tasks stuck with no runnable node")
            break
        r = np.array(running)
        nd = node_of[r]
        occ = one - free_c[nd] / cores[nd]
        smt = one - f(SMT_PENALTY) * np.maximum(f(0.0), occ - f(0.5)) / f(0.5)
        cpu = cpu_base[nd] * smt
        mem = mem_base[nd] / np.minimum(
            one + f(MEM_SHARE_BETA) * np.maximum(0, n_on[nd] - 1).astype(f),
            f(MEM_SHARE_CAP))
        io = io_seq[nd] / (one + f(IO_SHARE_GAMMA) * f(max(0, len(r) - 1)))
        left = rem[r, 0] / cpu + rem[r, 1] / mem + rem[r, 2] / io
        k = int(np.argmin(left))
        dt = left[k]
        if dt > 0:
            with np.errstate(divide="ignore", invalid="ignore"):
                frac = np.where(left > 0, np.minimum(dt / left, one), one)
            rem[r] *= (one - frac)[:, None]
        t = t + dt
        j = running.pop(k)
        n = node_of[j]
        free_c[n] += rc[j]
        free_m[n] += rm[j]
        n_on[n] -= 1
        end[j] = t
        finish_order.append(j)
        rt_sum[name_id[j]] += t - start[j]
        rt_cnt[name_id[j]] += 1
        for d in dependents[j]:
            deps_left[d] -= 1
        queue.extend(sorted(d for d in dependents[j] if deps_left[d] == 0))
    return {"node_idx": node_of, "start_t": start, "end_t": end,
            "finish_order": np.array(finish_order, np.int64),
            "makespan": end.max()}
