"""Fleet-scale engine benchmark: 10^3 nodes x 10^4..10^5 task instances.

Drives the vectorized engine (``repro.workflow.engine``) across all five
schedulers on a synthetic heterogeneous fleet, and times the frozen seed
engine (``repro.workflow.engine_ref``) on the same workload as the speedup
baseline.  Emits ``benchmarks/results/BENCH_engine.json`` — the perf
trajectory tracked across PRs (see ROADMAP.md §Perf methodology).

The fleet mirrors the paper's three hardware tiers (N1/Broadwell,
N2/Cascade-Lake, C2/compute-optimized) in equal thirds; the workload is a
chain of equal-width stages with per-sample Nextflow channel semantics and
cycling cpu-/mem-/io-heavy resource signatures, sized so the cluster runs
saturated (width == reservable task slots).

    PYTHONPATH=src python -m benchmarks.engine_bench [--quick]
        [--no-seed-baseline] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time

from repro.core.monitor import TraceDB
from repro.core.profiler import NodeSpec
from repro.core.scheduler import SCHEDULERS, make_scheduler
from repro.workflow import engine, engine_ref
from repro.workflow.dag import AbstractTask, WorkflowSpec

RESULTS = os.path.join(os.path.dirname(__file__), "results")
OUT_PATH = os.path.join(RESULTS, "BENCH_engine.json")
# quick-mode default: keep CI smoke output away from the committed file
QUICK_OUT_PATH = os.path.join(RESULTS, "BENCH_engine.quick.json")

# CI perf gate: the apples-to-apples speedup over the frozen seed engine
# must not regress below the floor — the bench *fails* instead of only
# uploading the artifact.  Quick (CI) mode also gates makespan parity; its
# floor is lower because at 64x2k the seed baseline is only a few seconds,
# so the ratio is noisier (historically ~15x there vs ~230x at fleet scale).
SPEEDUP_FLOOR = 5.0          # full mode, the ROADMAP floor
QUICK_SPEEDUP_FLOOR = 3.0    # CI smoke scale

# the paper's three 8-vCPU tiers (Table II ground truth), fleet-replicated
_TIERS = (
    ("n1", 375.0, 14050.0, 0.78),
    ("n2", 463.0, 17600.0, 1.0),
    ("c2", 524.0, 19850.0, 1.02),
)
_REQ_CORES = 4            # fleet tasks are 4-vCPU / 8 GB -> 2 slots per node
_REQ_MEM = 8.0

# stage resource signatures, cycled (cpu events, mem MiB, io IOPS-s)
_SIGNATURES = (
    ("cpu_heavy", 900.0 * 463.0, 40.0 * 352.0, 10.0 * 482.0),
    ("mem_heavy", 250.0 * 463.0, 300.0 * 352.0, 20.0 * 482.0),
    ("io_heavy", 200.0 * 463.0, 50.0 * 352.0, 60.0 * 482.0),
    ("balanced", 400.0 * 463.0, 120.0 * 352.0, 25.0 * 482.0),
)


def fleet_cluster(n_nodes: int) -> list[NodeSpec]:
    specs = []
    for i in range(n_nodes):
        machine, cpu, membw, app = _TIERS[i % len(_TIERS)]
        specs.append(NodeSpec(f"f-{machine}-{i:05d}", machine, 8, 32.0,
                              cpu_speed=cpu, mem_bw=membw, app_factor=app))
    return specs


def fleet_workflow(n_instances: int, width: int, name: str = "fleet") -> WorkflowSpec:
    """Equal-width stage chain totalling `n_instances` task instances.

    Equal widths give per-sample dependency chains (instance i of stage s+1
    waits only on instance i of stage s), so the pipeline keeps exactly
    `width` tasks runnable — a saturated fleet without an unbounded ready
    queue, which is the regime the paper's clusters operate in.
    """
    n_stages = max(1, math.ceil(n_instances / width))
    tasks = []
    for s in range(n_stages):
        w = width if s < n_stages - 1 else n_instances - width * (n_stages - 1)
        sig, cpu, mem, io = _SIGNATURES[s % len(_SIGNATURES)]
        tasks.append(AbstractTask(
            f"s{s:03d}_{sig}", max(w, 1),
            {"cpu": cpu, "mem": mem, "io": io},
            peak_mem_gb=4.0, deps=(tasks[-1].name,) if tasks else (),
            req_cores=_REQ_CORES, req_mem_gb=_REQ_MEM))
    return WorkflowSpec(name, tasks)


def _bench_once(engine_mod, sched_name: str, n_nodes: int, n_instances: int,
                warm_labels: bool = True) -> dict:
    specs = fleet_cluster(n_nodes)
    width = n_nodes * (8 // _REQ_CORES)          # reservable task slots
    db = TraceDB()
    if warm_labels:
        # one miniature run (1 instance per stage) seeds the monitor so the
        # history-driven schedulers (sjfn, tarema) exercise their label path
        warm = fleet_workflow(max(1, math.ceil(n_instances / width)), 1,
                              name="fleet")
        weng = engine_mod.Engine(specs, make_scheduler(sched_name, specs, seed=1),
                                 db, engine_mod.EngineConfig(seed=1))
        weng.submit(warm, run_id=0, seed=5)
        weng.run()
    sched = make_scheduler(sched_name, specs, seed=3)
    eng = engine_mod.Engine(specs, sched, db, engine_mod.EngineConfig(seed=0))
    eng.submit(fleet_workflow(n_instances, width), run_id=1, seed=7)
    t0 = time.perf_counter()
    res = eng.run()
    wall = time.perf_counter() - t0
    rec = {"engine": engine_mod.__name__.rsplit(".", 1)[-1],
           "scheduler": sched_name, "n_nodes": n_nodes,
           "n_instances": n_instances, "wall_s": round(wall, 3),
           "warm_labels": warm_labels,
           "makespan": res["makespan"],
           "tasks_completed": len(res["assignments"])}
    # per-phase attribution (vectorized engine only): scheduling wall vs
    # event-loop wall vs monitor-ingest wall, so a future regression is
    # attributable to the layer that caused it
    timings = res.get("timings")
    if timings:
        rec["phase_wall_s"] = {k: round(timings[k], 3)
                               for k in ("schedule_s", "monitor_s", "event_s")}
    return rec


def fleet_profiles(n_profiles: int):
    """[n, 3] synthetic node profiles (cpu, mem bandwidth, io) of a fleet in
    the paper's three tiers, 1 % noise per feature (0.3 % on the shared io)."""
    import numpy as np
    rng = np.random.default_rng(0)
    centers = np.array([[375.0, 14050.0], [463.0, 17600.0], [524.0, 19850.0]])
    tier = rng.integers(0, 3, n_profiles)
    return np.c_[centers[tier] * (1.0 + rng.normal(0, 0.01, (n_profiles, 2))),
                 np.full((n_profiles, 1), 482.0)
                 * (1.0 + rng.normal(0, 0.003, (n_profiles, 1)))]


def _kmeans_fleet_probe(n_profiles: int) -> dict:
    """choose_k at fleet scale: 10^5 synthetic profiles through the
    Lloyd step (the Pallas kernel on TPU, segment sums elsewhere) and the
    blocked/sampled silhouette — no (n, n) (or even (sample, sample))
    distance matrix is ever materialized."""
    from repro.core.clustering import choose_k
    X = fleet_profiles(n_profiles)
    t0 = time.perf_counter()
    res = choose_k(X, k_max=4, restarts=2)
    wall = time.perf_counter() - t0
    return {"n_profiles": n_profiles, "k": res["k"],
            "silhouette": round(res["silhouette"], 4),
            "wall_s": round(wall, 3)}


def main(quick: bool = False, seed_baseline: bool = True,
         out_path: str | None = None) -> dict:
    print("engine_bench")
    if out_path is None:
        # quick (CI/smoke) runs must not clobber the committed fleet-scale
        # trajectory file in a contributor's working tree
        out_path = QUICK_OUT_PATH if quick else OUT_PATH
    if quick:
        scales = [(64, 2_000)]
        head_scale = (64, 2_000)
        kmeans_n = 16_384
    else:
        scales = [(256, 10_000), (1_000, 50_000)]
        head_scale = (1_000, 50_000)
        kmeans_n = 100_000
    runs = []
    gate_failures: list[str] = []
    for n_nodes, n_instances in scales:
        for sched_name in SCHEDULERS:
            rec = _bench_once(engine, sched_name, n_nodes, n_instances)
            runs.append(rec)
            print(f"engine_bench/{n_nodes}x{n_instances}/{sched_name},"
                  f"{rec['wall_s'] * 1e6:.0f},makespan={rec['makespan']:.0f}")
    speedup = None
    if seed_baseline:
        # the frozen seed engine, timed on the headline scale (fair keeps
        # the scheduler itself cheap so the engine hot path dominates)
        new = next(r for r in runs
                   if (r["n_nodes"], r["n_instances"]) == head_scale
                   and r["scheduler"] == "fair")
        ref = _bench_once(engine_ref, "fair", *head_scale)
        runs.append(ref)
        print(f"engine_bench/seed/{head_scale[0]}x{head_scale[1]}/fair,"
              f"{ref['wall_s'] * 1e6:.0f},makespan={ref['makespan']:.0f}")
        if ref["makespan"] != new["makespan"]:
            gate_failures.append(
                "seed and vectorized engines diverged on the fleet workload "
                f"({ref['makespan']!r} != {new['makespan']!r})")
        # the speedup block reuses the exact runs[] measurements it names
        # (same-process, same warm-labels protocol) and cross-references
        # them by index so the trajectory number is unambiguous
        speedup = {"scale": f"{head_scale[0]}x{head_scale[1]}",
                   "scheduler": "fair",
                   "seed_wall_s": ref["wall_s"],
                   "vectorized_wall_s": new["wall_s"],
                   "vectorized_run_index": runs.index(new),
                   "seed_run_index": runs.index(ref),
                   "same_run_timing": True,
                   "speedup": round(ref["wall_s"] / new["wall_s"], 2)}
        print(f"# speedup vs seed engine at {speedup['scale']}: "
              f"{speedup['speedup']}x "
              f"({ref['wall_s']:.1f}s -> {new['wall_s']:.1f}s)")
        floor = QUICK_SPEEDUP_FLOOR if quick else SPEEDUP_FLOOR
        if speedup["speedup"] < floor:
            gate_failures.append(
                f"speedup_vs_seed {speedup['speedup']}x fell below the "
                f"floor of {floor}x ({'quick' if quick else 'full'} mode)")
    km = _kmeans_fleet_probe(kmeans_n)
    print(f"engine_bench/choose_k/{km['n_profiles']},{km['wall_s'] * 1e6:.0f},"
          f"k={km['k']} sil={km['silhouette']}")
    summary = {"meta": {"quick": quick, "generated_unix": int(time.time())},
               "runs": runs, "speedup_vs_seed": speedup,
               "choose_k_fleet": km}
    if gate_failures:
        summary["gate_failures"] = gate_failures
    # always write the artifact — on a gate failure the per-phase breakdown
    # is exactly the diagnostic a regression hunt needs — then fail the job
    if os.path.dirname(out_path):
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=1)
    print(f"# wrote {out_path}")
    if gate_failures:
        # RuntimeError, not SystemExit: benchmarks/run.py's suite guard
        # catches Exception and records the failure without killing the
        # other suites; standalone __main__ still exits non-zero
        raise RuntimeError("CI perf gate: " + "; ".join(gate_failures))
    return summary


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: 64 nodes / 2k instances")
    ap.add_argument("--no-seed-baseline", action="store_true",
                    help="skip the (slow) frozen seed engine baseline run")
    ap.add_argument("--out", default=None,
                    help="output JSON (default: BENCH_engine.json, or "
                         "BENCH_engine.quick.json with --quick)")
    args = ap.parse_args()
    main(quick=args.quick, seed_baseline=not args.no_seed_baseline,
         out_path=args.out)
