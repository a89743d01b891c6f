"""The benchmark's own generators of deployments and inputs.

Plain data only: nodes and workflows are dicts and lists, never the
program's classes, so a later change to the program cannot move what the
benchmark feeds it.  The numbers come from the configuration files under
``bench/configs``; the arithmetic is copied from the generators the
repository had when the benchmark was written (``benchmarks/engine_bench``,
``workflow/cluster``, ``workflow/nfcore``, ``dag.instantiate``,
``profiler.profile_node_synthetic``), operand for operand, so that the same
seed gives the same floats.
"""
from __future__ import annotations

import zlib

import numpy as np

NODE_DEFAULTS = {"io_seq": 482.0, "io_rand": 105.0, "net_gbps": 16.0,
                 "app_factor": 1.0}


def stable_seed(name: str) -> int:
    """16-bit seed component of a name (crc32, not the salted ``hash``)."""
    return zlib.crc32(name.encode()) & 0xFFFF


def derive_seed(seed: int, *path: int) -> int:
    """A 63-bit seed for the ``path``-th stream of a run's ``seed``; any
    whole number is accepted, negative ones by their two's complement."""
    ss = np.random.SeedSequence([seed & (2 ** 64 - 1), *path])
    return int(ss.generate_state(1, np.uint64)[0] >> np.uint64(1))


# ------------------------------------------------------------------ clusters
def cluster(cfg: dict) -> list[dict]:
    """Node list of a configuration's ``cluster`` block."""
    kind = cfg["kind"]
    if kind == "tiers":
        # engine_bench.fleet_cluster: tiers in equal thirds, round-robin
        out = []
        for i in range(cfg["nodes"]):
            machine, cpu, membw, app = cfg["tiers"][i % len(cfg["tiers"])]
            out.append({**NODE_DEFAULTS,
                        "name": cfg["name_format"].format(machine=machine, i=i),
                        "machine": machine, "cores": cfg["cores"],
                        "mem_gb": float(cfg["mem_gb"]), "cpu_speed": cpu,
                        "mem_bw": membw, "app_factor": app})
        return out
    if kind == "groups":
        # workflow/cluster._mk: one block of identical machines per group
        out = []
        for g in cfg["groups"]:
            for i in range(g["n"]):
                out.append({**NODE_DEFAULTS,
                            "name": f"{g['prefix']}-{g['machine']}-{i}",
                            "machine": g["machine"], "cores": g["cores"],
                            "mem_gb": g["mem_gb"], "cpu_speed": g["cpu_speed"],
                            "mem_bw": g["mem_bw"], "net_gbps": g["net_gbps"],
                            "app_factor": g["app_factor"]})
        return out
    raise ValueError(f"unknown cluster kind {kind!r}")


def synthetic_cpu(node: dict, seed: int) -> float:
    """The cpu score that ``profile_node_synthetic`` measures for a node:
    the ground truth with one uniform +-2 % draw (the first of its stream)."""
    rng = np.random.default_rng((stable_seed(node["name"]), seed))
    return float(node["cpu_speed"] * (1.0 + rng.uniform(-0.02, 0.02)))


def fleet_profiles(n: int, seed: int, cfg: dict) -> np.ndarray:
    """[n, 3] synthetic node profiles (cpu, mem bandwidth, io) of a fleet in
    the configuration's tiers (``engine_bench.fleet_profiles`` with a seed)."""
    rng = np.random.default_rng(seed)
    centers = np.array([[cpu, membw] for _, cpu, membw, _ in cfg["tiers"]])
    tier = rng.integers(0, len(centers), n)
    noise, io_noise = cfg["noise"], cfg["io_noise"]
    return np.c_[centers[tier] * (1.0 + rng.normal(0, noise, (n, 2))),
                 np.full((n, 1), cfg["io"])
                 * (1.0 + rng.normal(0, io_noise, (n, 1)))]


# ----------------------------------------------------------------- workflows
def _task(name, n, work, peak, deps, req_cores, req_mem):
    return {"name": name, "n_instances": n, "work": work, "peak_mem_gb": peak,
            "deps": tuple(deps), "req_cores": req_cores,
            "req_mem_gb": req_mem}


def workflow(cfg: dict) -> dict:
    """Workflow ``{"name", "tasks"}`` of one entry of a configuration's
    ``workflows`` list."""
    kind = cfg["kind"]
    if kind == "tasks":
        # workflow/nfcore: work given in seconds on an n2 node
        cpu_u, membw, share, io_u = cfg["unit"]
        mem_u = membw * share
        tasks = [_task(name, n, {"cpu": c * cpu_u * 1.0, "mem": m * mem_u * 1.0,
                                 "io": i * io_u * 1.0},
                       peak, deps, cfg["req_cores"], cfg["req_mem_gb"])
                 for name, n, (c, m, i), peak, deps in cfg["tasks"]]
        return {"name": cfg["name"], "tasks": tasks}
    raise ValueError(f"unknown workflow kind {kind!r}")


def instantiate(wf: dict, run_id: int, seed: int, prefix=None) -> list[dict]:
    """Task instances of one submission, in the engine's order, with the
    per-run and per-instance lognormal work jitter of ``dag.instantiate``."""
    rng = np.random.default_rng((stable_seed(wf["name"]), seed, run_id))
    run_scale = float(rng.lognormal(0.0, 0.05))
    pre = (lambda s: s) if prefix is None else (lambda s: f"{prefix}/{s}")
    out, by_task = [], {}
    for t in wf["tasks"]:
        ids = []
        for i in range(t["n_instances"]):
            scale = float(rng.lognormal(0.0, 0.35)) * run_scale
            deps = []
            for dep in t["deps"]:
                parents = by_task[dep]
                if t["n_instances"] == 1 or len(parents) == 1:
                    deps.extend(parents)
                elif len(parents) == t["n_instances"]:
                    deps.append(parents[i])
                elif len(parents) > t["n_instances"]:
                    deps.extend(parents[i::t["n_instances"]])
                else:
                    deps.append(parents[i % len(parents)])
            iid = pre(f"{t['name']}[{i}]")
            out.append({"workflow": wf["name"], "name": t["name"],
                        "instance": iid,
                        "work": [t["work"][k] * scale for k in ("cpu", "mem", "io")],
                        "req_cores": t["req_cores"],
                        "req_mem_gb": t["req_mem_gb"], "deps": deps})
            ids.append(iid)
        by_task[t["name"]] = ids
    return out
