"""Plain reference of one Tarema run (Bader et al. 2021, Sec. IV) on the
simulated cluster, given the nodes' profiles and their groups.

Phase 1, the groups' description: the group ids are compacted to 0..n-1
in their order; for each task feature (cpu, memory, io against the
profile columns cpu, mem, io_seq_read) the groups are ranked by the mean
profile of their nodes, weakest first (a stable sort), and take the
labels 1..n in that order.  A group's power is the sum of its labels; its
capacity is its cores (cpu), its GB (memory) or its node count (io).

Phase 2, the labels: after every finished task the monitor records its
usage, the seed engine's model: the cpu share of its work times its
reserved cores times 100, its peak memory, its io work, each times
``1 + N(0, 0.03)``, three draws per finish from one stream seeded with
the run's seed, in that order.  A task with no finished instance has no
label.  Otherwise, per feature, the percentiles are ``p_i = p_{i-1} +
m_i / sum m`` over the groups' capacities in rank order; the cut points
are the values at ``min(int(p_i * len), len - 1)`` of the sorted mean
usages of the workflow's tasks that have history; the label is one plus
the number of cut points at or below the task's mean usage.

Phase 3, the placement: groups in order of (score, -power), the score
being ``sum_k |n_k - t_k|`` over the three features; the first group
with a node that has room; the least loaded of its nodes with room, one
tie draw per such node in node order from a stream seeded with the
run's seed + 1.  A task without a label goes to the least loaded of all
nodes with room, under the same tie rule.

The event loop is the seed engine's, as ``bench/reference/engine.py``
states it: at every event the ready queue is tried in order, and the
next event is the running task with the least time left, the earliest
started winning a tie.  Imports numpy only, never the program.
"""
from __future__ import annotations

import bisect

import numpy as np

from bench.reference import engine as ref_engine

PROFILE_COLUMNS = (0, 1, 2)      # cpu, mem, io_seq_read
USAGE_NOISE = 0.03


def group_info(profiles: np.ndarray, labels, cores, mem_gb) -> dict:
    """The groups of ``labels`` (one per node): ``members`` (node indices
    per group, ascending), ``node_label`` [n, 3], ``power`` [n] and
    ``cuts`` (per feature, the inner percentiles p_1..p_{n-1})."""
    labels = np.searchsorted(np.unique(labels), labels)
    n = int(labels.max()) + 1
    members = [np.flatnonzero(labels == g) for g in range(n)]
    # cpu: cores, memory: GB, io: nodes; summed one node after another
    capacity = (np.asarray(cores, np.float64), np.asarray(mem_gb, np.float64),
                np.ones(len(labels)))
    node_label = np.zeros((n, len(PROFILE_COLUMNS)), np.int64)
    cuts = []
    for f, col in enumerate(PROFILE_COLUMNS):
        means = np.array([np.mean(profiles[m, col]) for m in members])
        order = np.argsort(means, kind="stable")
        node_label[order, f] = np.arange(1, n + 1)
        caps = [sum(capacity[f][members[g]].tolist()) for g in order]
        total = sum(caps) or 1.0
        ps = [0.0]
        for c in caps[:-1]:
            ps.append(ps[-1] + c / total)
        cuts.append(ps[1:])
    power = node_label.sum(axis=1).astype(np.float64)
    return {"members": members, "node_label": node_label, "power": power,
            "cuts": cuts}


def task_label(mean: np.ndarray, peers: np.ndarray, cuts) -> list[int]:
    """Label vector of a task with mean usage ``mean`` [3] among its
    workflow's tasks' mean usages ``peers`` [m, 3]."""
    out = []
    for f, ps in enumerate(cuts):
        xs = sorted(peers[:, f])
        bounds = [xs[min(int(p * len(xs)), len(xs) - 1)] for p in ps]
        out.append(1 + bisect.bisect_right(bounds, mean[f]))
    return out


def priority(info: dict, label) -> list[int]:
    """Groups by (score, -power), ties in group order."""
    scores = np.abs(info["node_label"] - np.asarray(label)).sum(axis=1)
    return sorted(range(len(scores)),
                  key=lambda g: (scores[g], -info["power"][g]))


def simulate(nodes: list[dict], instances: list[dict], peak_mem: list[float],
             profiles: np.ndarray, labels, seed: int,
             dtype=np.float64) -> dict:
    """One Tarema run of the instances (all submitted at t = 0) on the
    nodes grouped by ``labels``.  ``dtype`` is the precision of every
    time, rate and work value.  Returns node index, start and end time per
    task, the finish order (task indices) and the makespan."""
    f = dtype
    one = f(1.0)
    top = ref_engine.topology(nodes, instances)
    info = group_info(profiles, labels, top["cores"], top["mem_gb"])
    keys: dict = {}
    name_id = [keys.setdefault((i["workflow"], i["name"]), len(keys))
               for i in instances]
    wf_names: dict = {}
    for (wf, _), k in keys.items():
        wf_names.setdefault(wf, []).append(k)
    wf_of = {k: wf for (wf, _), k in keys.items()}
    usage_sum = np.zeros((len(keys), 3), np.float64)
    usage_cnt = np.zeros(len(keys), np.int64)
    noise_rng = np.random.default_rng(seed)
    tie_rng = np.random.default_rng(seed + 1)

    T = len(instances)
    cores = top["cores"].astype(f)
    mem_gb = top["mem_gb"].astype(f)
    slow = np.ones_like(cores) * top["app_factor"].astype(f)
    cpu_base = top["cpu_speed"].astype(f) * slow
    mem_base = top["mem_bw"].astype(f) * f(0.02) * slow \
        * (top["cores"] / 8.0).astype(f) ** f(ref_engine.BW_EXP)
    io_seq = top["io_seq"].astype(f)
    rc, rm = top["req_cores"].astype(f), top["req_mem"].astype(f)
    rem = np.array([i["work"] for i in instances], np.float64).astype(f)
    free_c, free_m = cores.copy(), mem_gb.copy()
    n_on = np.zeros(len(cores), np.int64)
    deps_left = top["deps_n"].copy()
    dependents = top["dependents"]
    node_of = np.full(T, -1, np.int64)
    start = np.zeros(T, f)
    end = np.zeros(T, f)
    finish_order = []
    running: list[int] = []              # start order
    queue = [j for j in range(T) if deps_left[j] == 0]
    t = f(0.0)
    while True:
        still = []
        for j in queue:
            feas = (free_c >= rc[j]) & (free_m >= rm[j])
            if not feas.any():
                still.append(j)
                continue
            load = f(0.5) * ((one - free_c / cores) + (one - free_m / mem_gb))
            k = name_id[j]
            cands = np.flatnonzero(feas)
            if usage_cnt[k] > 0:
                peers = [p for p in wf_names[wf_of[k]] if usage_cnt[p] > 0]
                label = task_label(usage_sum[k] / usage_cnt[k],
                                   usage_sum[peers] / usage_cnt[peers, None],
                                   info["cuts"])
                for g in priority(info, label):
                    m = info["members"][g]
                    if feas[m].any():
                        cands = m[feas[m]]
                        break
            ties = tie_rng.random(cands.size)
            n = int(cands[min(range(cands.size),
                              key=lambda c: (load[cands[c]], ties[c]))])
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
        smt = one - f(ref_engine.SMT_PENALTY) \
            * np.maximum(f(0.0), occ - f(0.5)) / f(0.5)
        cpu = cpu_base[nd] * smt
        mem = mem_base[nd] / np.minimum(
            one + f(ref_engine.MEM_SHARE_BETA)
            * np.maximum(0, n_on[nd] - 1).astype(f),
            f(ref_engine.MEM_SHARE_CAP))
        io = io_seq[nd] / (one + f(ref_engine.IO_SHARE_GAMMA)
                           * f(max(0, len(r) - 1)))
        left = rem[r, 0] / cpu + rem[r, 1] / mem + rem[r, 2] / io
        i = int(np.argmin(left))
        dt = left[i]
        if dt > 0:
            with np.errstate(divide="ignore", invalid="ignore"):
                frac = np.where(left > 0, np.minimum(dt / left, one), one)
            rem[r] *= (one - frac)[:, None]
        t = t + dt
        j = running.pop(i)
        n = node_of[j]
        free_c[n] += rc[j]
        free_m[n] += rm[j]
        n_on[n] -= 1
        end[j] = t
        finish_order.append(j)
        # the monitor's record of the finished task
        work = instances[j]["work"]
        total = work[0] + work[1] + work[2]
        noise = (1.0 + noise_rng.normal(0, USAGE_NOISE, 3)).tolist()
        usage_sum[name_id[j]] += (
            100.0 * instances[j]["req_cores"] * work[0] / total * noise[0],
            peak_mem[j] * noise[1], work[2] * noise[2])
        usage_cnt[name_id[j]] += 1
        for d in dependents[j]:
            deps_left[d] -= 1
        queue.extend(sorted(d for d in dependents[j] if deps_left[d] == 0))
    return {"node_idx": node_of, "start_t": start, "end_t": end,
            "finish_order": np.array(finish_order, np.int64),
            "makespan": end.max()}
