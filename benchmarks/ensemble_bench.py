"""Batched-ensemble benchmark: jitted lax.scan fleets vs the numpy engine.

Runs Monte-Carlo replica fleets of the fleet workload (``engine_bench``'s
saturated stage chain) through ``repro.workflow.ensemble`` — one jitted
``lax.scan`` program per scheduler — and the same replicas through the
sequential numpy ``Engine`` oracle.  Emits
``benchmarks/results/BENCH_ensemble.json`` with two result families:

* **throughput** — replicas/sec for the jitted program (its one run,
  compile excluded; best of ``repeats`` launches) vs the sequential numpy
  loop, and their ratio.  The full-mode ratio gates the ROADMAP >= 10x
  floor.
* **distribution** — makespan mean / std / 95% CI over the replica axis:
  the columns that turn ``tenancy_bench``-style point estimates into the
  distributional comparisons Tarema's claims actually need.

Every run is also an equivalence gate: the oracle re-runs *all* replicas
and the full traces (node assignment, start/end times, finish order,
makespans) must match the scan bit-for-bit; any divergence fails the
bench after writing the artifact (CI uploads it for the post-mortem).

    PYTHONPATH=src python -m benchmarks.ensemble_bench [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import time

import numpy as np

from benchmarks.engine_bench import fleet_cluster, fleet_workflow
from repro.core.scheduler import make_scheduler
from repro.workflow.ensemble import (Submission, assert_equivalent,
                                     oracle_ensemble, run_ensemble)

RESULTS = os.path.join(os.path.dirname(__file__), "results")
OUT_PATH = os.path.join(RESULTS, "BENCH_ensemble.json")
# quick (CI) runs write their own file so a local repro can never clobber
# the committed fleet-scale trajectory
QUICK_OUT_PATH = os.path.join(RESULTS, "BENCH_ensemble.quick.json")

# full-mode perf gate (ROADMAP open item 1 acceptance): the jitted fleet
# must clear >= 10x replicas/sec over the sequential numpy loop.  Quick
# mode doesn't gate throughput — at CI scale the scan's fixed per-step
# cost isn't amortized and the ratio is pure noise — but *always* gates
# bit-for-bit equivalence.
SPEEDUP_FLOOR = 10.0

_SCHEDS = ("fair", "sjfn")


def _stats(x: np.ndarray) -> dict:
    """Makespan distribution columns (95% normal CI on the mean)."""
    n = x.size
    std = float(x.std(ddof=1)) if n > 1 else 0.0
    return {"n": n, "mean": float(x.mean()), "std": std,
            "ci95": 1.96 * std / math.sqrt(n) if n > 1 else 0.0,
            "min": float(x.min()), "max": float(x.max())}


def _slice_replicas(res, r: int):
    """First-r-replicas view for equivalence against a smaller oracle."""
    return dataclasses.replace(
        res, makespan=res.makespan[:r], node_idx=res.node_idx[:r],
        start_t=res.start_t[:r], end_t=res.end_t[:r],
        finish_order=res.finish_order[:r])


def _bench_one(sched_name: str, n_nodes: int, n_instances: int,
               n_replicas: int, oracle_replicas: int, repeats: int) -> dict:
    specs = fleet_cluster(n_nodes)
    width = n_nodes * 2                      # 2 slots per 8-core node
    spec = fleet_workflow(n_instances, width)
    subs = [Submission(spec, seed=11)]

    res = None
    best_run, compile_s, build_s = math.inf, 0.0, 0.0
    for _ in range(repeats):
        # the first launch compiles the scan and later ones run the cached
        # program; throughput reads the run, which run_ensemble times
        # apart from the compile
        out = run_ensemble(specs, subs, make_scheduler(sched_name, specs,
                                                       seed=0), n_replicas)
        compile_s += out.timings.get("compile_s", 0.0)
        if out.timings["run_s"] < best_run:
            best_run = out.timings["run_s"]
            build_s = out.timings["build_s"]
        res = out

    ref = oracle_ensemble(specs, subs, make_scheduler(sched_name, specs,
                                                      seed=0),
                          oracle_replicas)
    divergence = None
    try:
        assert_equivalent(_slice_replicas(res, oracle_replicas), ref)
    except AssertionError as e:
        divergence = str(e).splitlines()[0] if str(e) else "trace mismatch"

    jax_rps = n_replicas / best_run
    numpy_rps = oracle_replicas / ref.timings["run_s"]
    return {
        "scheduler": sched_name, "n_nodes": n_nodes,
        "n_instances": n_instances, "n_replicas": n_replicas,
        "oracle_replicas": oracle_replicas,
        "jax_run_s": round(best_run, 3),
        "jax_compile_s": round(compile_s, 3),
        "jax_build_s": round(build_s, 3),
        "numpy_run_s": round(ref.timings["run_s"], 3),
        "jax_replicas_per_s": round(jax_rps, 3),
        "numpy_replicas_per_s": round(numpy_rps, 3),
        "speedup": round(jax_rps / numpy_rps, 2),
        "makespan": _stats(res.makespan),
        "bitwise_equal": divergence is None,
        "divergence": divergence,
    }


def main(quick: bool = False, out_path: str | None = None) -> dict:
    print("ensemble_bench")
    if out_path is None:
        out_path = QUICK_OUT_PATH if quick else OUT_PATH
    if quick:
        n_nodes, n_instances, n_replicas, repeats = 64, 500, 16, 2
    else:
        n_nodes, n_instances, n_replicas, repeats = 256, 2_000, 64, 3
    runs = []
    gate_failures: list[str] = []
    for sched_name in _SCHEDS:
        rec = _bench_one(sched_name, n_nodes, n_instances, n_replicas,
                         oracle_replicas=n_replicas, repeats=repeats)
        runs.append(rec)
        m = rec["makespan"]
        print(f"ensemble_bench/{n_nodes}x{n_instances}x{n_replicas}/"
              f"{sched_name},{rec['jax_run_s'] / n_replicas * 1e6:.0f},"
              f"speedup={rec['speedup']}x "
              f"makespan={m['mean']:.0f}+-{m['ci95']:.0f}")
        if not rec["bitwise_equal"]:
            gate_failures.append(
                f"{sched_name}: jitted scan diverged from the numpy engine "
                f"({rec['divergence']})")
        if not quick and rec["speedup"] < SPEEDUP_FLOOR:
            gate_failures.append(
                f"{sched_name}: speedup {rec['speedup']}x fell below the "
                f"{SPEEDUP_FLOOR}x floor")
    summary = {"meta": {"quick": quick, "generated_unix": int(time.time())},
               "runs": runs}
    if gate_failures:
        summary["gate_failures"] = gate_failures
    if os.path.dirname(out_path):
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=1)
    print(f"# wrote {out_path}")
    if gate_failures:
        # RuntimeError, not SystemExit: benchmarks/run.py's suite guard
        # records the failure and keeps the remaining suites running
        raise RuntimeError("; ".join(gate_failures))
    return summary


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    main(quick=args.quick, out_path=args.out)
