"""Crash-recovery benchmark: kill the control plane mid-run, recover from
the write-ahead journal, and prove the result indistinguishable from an
uninterrupted run (PR 10 acceptance artifact).

Three scenarios over a two-tier probe workflow whose tasks have
*engineered* usage vectors (cpu-heavy "cruncher" vs sleepy, RSS- and
io-heavy "stager" — far-apart bimodal usage makes the measured Tarema
task labels deterministic):

  * ``baseline`` — an uninterrupted journaled run in a sacrificial driver
    process (``python -m repro.workflow.recovery``); its WAL replay
    yields the reference makespan, assignment log and measured labels.
  * ``crash-recover`` — the same driver SIGKILLed at a fraction of the
    baseline makespan with real children in flight; this process then
    ``ControlPlane.recover()``s from the journal, adopts or charges the
    orphans, and finishes the DAG.
  * ``attempt-chaos`` — deterministic per-attempt chaos (SIGKILLs at a
    work fraction, duplicated + delayed deliveries) with the plane left
    alive: completion despite chaos, fault-budget (never OOM) accounting,
    and stale-duplicate drops.

``acceptance`` gates the ISSUE-10 criteria on the 50 %-kill scenario:
every instance completed, no duplicate completed AssignmentRecords
across the crash boundary, and task labels equal to the uninterrupted
run's.  Emits ``benchmarks/results/BENCH_recovery.json`` (committed full
run); ``--quick`` writes the ``.quick.json`` twin so CI never clobbers
the committed trajectory.

    PYTHONPATH=src python -m benchmarks.recovery_bench [--quick] [--out P]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

from repro.core import labeling
from repro.core.monitor import TASK_FEATURES, TraceDB
from repro.core.profiler import profile_node_synthetic
from repro.core.scheduler import make_scheduler
from repro.workflow.controlplane import ControlPlane, ControlPlaneConfig
from repro.workflow.dag import AbstractTask, WorkflowSpec
from repro.workflow.jobmanager import LocalNode, LocalProcessBackend
from repro.workflow.recovery import (ChaosBackend, ChaosConfig,
                                     WriteAheadLog, replay, spec_to_dict)
from repro.workflow.selfhost import make_probe_runner

RESULTS = os.path.join(os.path.dirname(__file__), "results")
OUT_PATH = os.path.join(RESULTS, "BENCH_recovery.json")


def recovery_workflow(width: int) -> WorkflowSpec:
    return WorkflowSpec("recwf", [
        AbstractTask("cruncher", width, {"cpu": 2.0, "mem": 0.2, "io": 0.1},
                     peak_mem_gb=0.1, req_cores=1, req_mem_gb=0.3),
        AbstractTask("stager", width, {"cpu": 0.2, "mem": 2.0, "io": 2.0},
                     peak_mem_gb=0.2, deps=("cruncher",), req_cores=1,
                     req_mem_gb=0.3),
    ])


def probe_table(spin_ms: float) -> dict:
    # bimodal on every feature: cpu via spin-vs-sleep, mem via ballast,
    # io via fsync'd scratch writes (reported as exact logical MB)
    return {
        "cruncher": {"spin_ms": spin_ms, "rss_mb": 5},
        "stager": {"spin_ms": 10, "sleep_ms": spin_ms, "rss_mb": 120,
                   "io_mb": 20},
    }


def node_dicts(workdir: str) -> list:
    return [{"name": f"rn{i}", "cpus": [], "mem_gb": 1.0,
             "scratch": os.path.join(workdir, f"s{i}"), "kind": "local"}
            for i in range(2)]


def build_nodes(dicts: list) -> list:
    nodes = [LocalNode(d["name"], cpus=tuple(d["cpus"]),
                       mem_gb=d["mem_gb"], scratch=d["scratch"],
                       kind=d["kind"]) for d in dicts]
    for n in nodes:
        os.makedirs(n.scratch, exist_ok=True)
    return nodes


def group_info(nodes: list) -> labeling.GroupInfo:
    # synthetic per-node profiles (crc32-deterministic across processes);
    # one group per node so the label machinery has real cut points
    profiles = [profile_node_synthetic(n.spec()) for n in nodes]
    return labeling.build_group_info(profiles, list(range(len(profiles))))


def labels_of(db: TraceDB, wf: WorkflowSpec, info) -> dict:
    return {t.name: labeling.label_task(db, info, wf.name, t.name)
            for t in wf.tasks}


def driver_spec(workdir: str, wf: WorkflowSpec, spin_ms: float,
                chaos: dict = None) -> dict:
    return {
        "wal": os.path.join(workdir, "run.wal"),
        "registry": os.path.join(workdir, "reg"),
        "nodes": node_dicts(workdir),
        "workflow": spec_to_dict(wf),
        "submits": [{"run_id": 0, "seed": 0}],
        "probe_table": probe_table(spin_ms),
        "chaos": chaos,
        "config": {"poll_interval_s": 0.02, "backoff_base_s": 0.1},
    }


def run_driver(spec: dict, timeout: float = 120.0):
    # the driver runs a CPU control plane; on an accelerator host it must
    # not reach for a chip that this (or another) process holds
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    pp = env.get("PYTHONPATH", "")
    if src not in pp.split(os.pathsep):
        env["PYTHONPATH"] = src + (os.pathsep + pp if pp else "")
    p = subprocess.Popen(
        [sys.executable, "-m", "repro.workflow.recovery", json.dumps(spec)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    out, err = p.communicate(timeout=timeout)
    return p.returncode, out, err


def dup_completed(log) -> list:
    seen, dups = set(), []
    for r in log:
        if r.completed:
            if r.instance in seen:
                dups.append(r.instance)
            seen.add(r.instance)
    return dups


def main(quick: bool = False, out_path: str = OUT_PATH) -> dict:
    print("recovery_bench")
    if quick and out_path == OUT_PATH:
        out_path = OUT_PATH.replace(".json", ".quick.json")
    width = 3 if quick else 4
    spin_ms = 200.0 if quick else 400.0
    crash_fracs = [0.5] if quick else [0.3, 0.5, 0.7]
    wf = recovery_workflow(width)
    n_inst = sum(t.n_instances for t in wf.tasks)
    workdir = tempfile.mkdtemp(prefix="recovery_bench_")
    out = {"meta": {"quick": quick, "width": width, "spin_ms": spin_ms,
                    "n_instances": n_inst, "crash_fracs": crash_fracs,
                    "generated_unix": int(time.time())}}
    try:
        info = group_info(build_nodes(node_dicts(workdir)))

        # ---- baseline: uninterrupted journaled run in a driver process
        spec = driver_spec(os.path.join(workdir, "base"), wf, spin_ms)
        os.makedirs(spec["registry"], exist_ok=True)
        t0 = time.perf_counter()
        rc, stdout, stderr = run_driver(spec)
        wall = time.perf_counter() - t0
        if rc != 0:
            raise RuntimeError(f"baseline driver failed rc={rc}: {stderr}")
        base_res = json.loads(
            [l for l in stdout.splitlines()
             if l.startswith("RECOVERY_RESULT ")][0].split(" ", 1)[1])
        st = replay(WriteAheadLog.read(spec["wal"]))
        base_db = TraceDB()
        for tr in st.traces:
            base_db.add(tr)
        base_labels = labels_of(base_db, wf, info)
        out["baseline"] = {
            "makespan_s": base_res["makespan"], "wall_s": wall,
            "completed": base_res["completed"], "labels": base_labels,
        }
        print(f"recovery_bench/baseline,{wall * 1e6:.0f},"
              f"makespan={base_res['makespan']:.2f}"
              f",completed={base_res['completed']}")

        # ---- crash-recover: SIGKILL the plane at a fraction of baseline
        scenarios = []
        for frac in crash_fracs:
            d = os.path.join(workdir, f"crash{int(frac * 100)}")
            spec = driver_spec(d, wf, spin_ms, chaos={
                "crash_plane_at_s": frac * base_res["makespan"],
                "crash_mode": "sigkill"})
            os.makedirs(spec["registry"], exist_ok=True)
            t0 = time.perf_counter()
            rc, stdout, stderr = run_driver(spec)
            killed = rc == -9 and "RECOVERY_RESULT" not in stdout
            pre = replay(WriteAheadLog.read(spec["wal"]))
            nodes = build_nodes(spec["nodes"])
            be = LocalProcessBackend(
                nodes, runner=make_probe_runner(spec["probe_table"]),
                registry_dir=spec["registry"])
            cp = ControlPlane.recover(
                spec["wal"], be,
                make_scheduler("fair", [n.spec() for n in nodes], seed=0))
            try:
                res = cp.run(max_wall_s=300.0)
            finally:
                be.close()
            wall = time.perf_counter() - t0
            dups = dup_completed(cp.assignment_log)
            labels = labels_of(cp.db, wf, info)
            scenarios.append({
                "crash_frac": frac, "plane_killed": killed,
                "in_flight_at_crash": len(pre.in_flight),
                "adopted": cp.retry_stats["adopted_attempts"],
                "lost": cp.retry_stats["lost_attempts"],
                "makespan_s": res["makespan"], "wall_s": wall,
                "all_done": all(t.state == "done"
                                for t in cp.all_tasks.values()),
                "completed": sum(1 for r in cp.assignment_log
                                 if r.completed),
                "duplicate_records": dups,
                "labels": labels,
                "labels_match_baseline": labels == base_labels,
            })
            s = scenarios[-1]
            print(f"recovery_bench/crash{int(frac * 100)},"
                  f"{wall * 1e6:.0f},adopted={s['adopted']}"
                  f",lost={s['lost']},completed={s['completed']}"
                  f",labels_match={s['labels_match_baseline']}")
        out["crash_recover"] = scenarios

        # ---- attempt-chaos: per-attempt kills + duplicate deliveries,
        # plane stays alive; fault budget (never OOM) absorbs the chaos
        d = os.path.join(workdir, "attempt")
        nodes = build_nodes(node_dicts(d))
        be = ChaosBackend(
            LocalProcessBackend(
                nodes, runner=make_probe_runner(probe_table(spin_ms)),
                registry_dir=os.path.join(d, "reg")),
            ChaosConfig(seed=2, kill_prob=0.4,
                        nominal_attempt_s=spin_ms / 1e3,
                        dup_prob=0.5, delay_prob=0.3,
                        delay_s=(0.02, 0.1)))
        cp = ControlPlane(
            be, make_scheduler("fair", [n.spec() for n in nodes], seed=0),
            TraceDB(), ControlPlaneConfig(poll_interval_s=0.02,
                                          backoff_base_s=0.1))
        cp.submit(wf, run_id=0, seed=0)
        t0 = time.perf_counter()
        try:
            res = cp.run(max_wall_s=300.0)
        finally:
            be.close()
        wall = time.perf_counter() - t0
        out["attempt_chaos"] = {
            "chaos": dict(be.stats),
            "retries": dict(cp.retry_stats),
            "makespan_s": res["makespan"], "wall_s": wall,
            "all_done": all(t.state == "done"
                            for t in cp.all_tasks.values()),
            "duplicate_records": dup_completed(cp.assignment_log),
        }
        ac = out["attempt_chaos"]
        print(f"recovery_bench/attempt_chaos,{wall * 1e6:.0f},"
              f"kills={ac['chaos']['kills']},dups={ac['chaos']['dups']}"
              f",stale={ac['retries']['stale_results']}"
              f",all_done={ac['all_done']}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    gate = next(s for s in out["crash_recover"]
                if s["crash_frac"] == 0.5)
    acceptance = {
        "plane_killed_mid_run": gate["plane_killed"],
        "all_instances_completed": gate["all_done"]
        and gate["completed"] == n_inst,
        "no_duplicate_records": not gate["duplicate_records"],
        "labels_equal_uninterrupted": gate["labels_match_baseline"],
        "attempt_chaos_clean": (out["attempt_chaos"]["all_done"]
                                and not out["attempt_chaos"]
                                ["duplicate_records"]
                                and out["attempt_chaos"]["retries"]
                                ["oom_retries"] == 0),
        "target": "kill plane at 50% + recover: all instances complete, "
                  "no duplicate AssignmentRecords, labels equal to the "
                  "uninterrupted run",
    }
    acceptance["pass"] = all(v for k, v in acceptance.items()
                             if isinstance(v, bool))
    out["acceptance"] = acceptance
    print(f"# acceptance: "
          f"{'PASS' if acceptance['pass'] else 'FAIL'} "
          f"(killed={acceptance['plane_killed_mid_run']}"
          f", complete={acceptance['all_instances_completed']}"
          f", no_dups={acceptance['no_duplicate_records']}"
          f", labels={acceptance['labels_equal_uninterrupted']})")
    if os.path.dirname(out_path):
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"# wrote {out_path}")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: narrower DAG, one crash point, writes "
                         "the .quick.json twin")
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args()
    main(quick=args.quick, out_path=args.out)
