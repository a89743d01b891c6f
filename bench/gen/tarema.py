"""Phase 1's synthetic node profiles and the instances' peak memory, the
two inputs of a Tarema run that ``bench.gen`` does not make.

Copied operand for operand from the generators the repository had when
the simulator cell was written (``profiler.profile_node_synthetic`` and
the peak-memory line of ``dag.instantiate``), so that the same seed gives
the same floats.  Plain data in, plain data out.
"""
from __future__ import annotations

import numpy as np

from bench.gen import stable_seed

# profile columns (``profiler.FEATURES``): the ground-truth key each one
# measures and its relative uniform jitter, in the order of the draws
PROFILE_JITTER = (("cpu_speed", 0.02), ("mem_bw", 0.015), ("io_seq", 0.003),
                  ("io_seq", 0.003), ("io_rand", 0.01), ("io_rand", 0.01))


def profiles(nodes: list[dict], seed: int) -> np.ndarray:
    """[n, 6] profiles (cpu, mem, io_seq_read, io_seq_write, io_rand_read,
    io_rand_write) that synthetic profiling measures for the nodes: each
    a node's ground truth times one uniform draw, from a stream per node."""
    out = np.empty((len(nodes), len(PROFILE_JITTER)), np.float64)
    for i, n in enumerate(nodes):
        rng = np.random.default_rng((stable_seed(n["name"]), seed))
        out[i] = [float(n[key] * (1.0 + rng.uniform(-rel, rel)))
                  for key, rel in PROFILE_JITTER]
    return out


def peak_mem(wf: dict, run_id: int, seed: int) -> list[float]:
    """Peak memory (GB) of each instance of one submission, in the order of
    ``bench.gen.instantiate``: the task's peak times its instance's work
    scale, capped at 1.2, replaying that function's stream of scales."""
    rng = np.random.default_rng((stable_seed(wf["name"]), seed, run_id))
    run_scale = float(rng.lognormal(0.0, 0.05))
    out = []
    for t in wf["tasks"]:
        for _ in range(t["n_instances"]):
            scale = float(rng.lognormal(0.0, 0.35)) * run_scale
            out.append(t["peak_mem_gb"] * min(scale, 1.2))
    return out
