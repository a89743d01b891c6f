"""Run the system's two device paths once on one TPU chip and check them.

    python chip_smoke.py

One process holds the chip for the whole run.  It exits non-zero before
any phase when JAX finds no TPU: nothing here carries on on the CPU.
Three phases, one line each, naming the device, the checks and the wall
time:

1. grouping -- ``choose_k`` on ``engine_bench``'s 10^5 synthetic fleet
   profiles (3 features, 3 tiers) through the Pallas Lloyd kernel (the
   compiled program must hold the ``tpu_custom_call``).  It must find
   k = 3, and at least 99.9 % of its labels must agree, up to a
   permutation of group ids, with the same call on the host's CPU device.
2. batched scan -- ``run_ensemble`` at ``ensemble_bench``'s full scale
   (256 nodes x 2,000 instances x 64 replicas) for fair and sjfn, against
   ``oracle_ensemble`` on all 64 replicas.  Node assignment and finish
   order must be exactly equal; start/end times and makespans bitwise
   equal or within ``ensemble.TPU_TIME_RTOL``.
3. tarema pipeline -- ``TaremaScheduler`` on both paper clusters, grouping
   on the chip, runs an nf-core workflow through ``Engine``.  Groups, task
   labels and makespans must equal those of the same run grouped on the
   host's CPU device.

A failed check fails its phase; the remaining phases still run and the
script exits 1.  An exception ends the run at once.  Only when every phase
passed is the last line the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

FLEET_PROFILES = 100_000           # engine_bench's full-mode choose_k probe
MIN_LABEL_AGREEMENT = 0.999
SCAN_SCALE = (256, 2_000, 64)      # ensemble_bench full mode
SCAN_SCHEDULERS = ("fair", "sjfn")
NFCORE_WORKFLOW = "eager"
TAREMA_RUNS = 2                    # the second run places on learned labels


def label_agreement(a, b) -> float:
    """Fraction of equal labels under the best one-to-one map of group ids."""
    ia, a = np.unique(a, return_inverse=True)
    ib, b = np.unique(b, return_inverse=True)
    m = np.zeros((ia.size, ib.size), np.int64)
    np.add.at(m, (a, b), 1)
    if m.shape[0] > m.shape[1]:
        m = m.T
    rows = np.arange(m.shape[0])
    best = max(m[rows, list(p)].sum()
               for p in itertools.permutations(range(m.shape[1]), m.shape[0]))
    return best / a.size


def phase_grouping(cpu):
    import jax
    from benchmarks.engine_bench import fleet_profiles
    from repro.core import clustering

    X = fleet_profiles(FLEET_PROFILES)
    Xs = clustering.standardize(X)          # what choose_k clusters
    on_kernel = clustering.uses_lloyd_kernel(Xs)
    res = clustering.choose_k(X, k_max=4, restarts=2)
    # the program each restart at the chosen k ran, lowered again: the
    # kernel is in it, not a fallback
    hlo = clustering._kmeans_pp.lower(
        Xs, k=res["k"], key=jax.random.key(0), iters=32,
        use_kernel=on_kernel).compile().as_text()
    kernel = "tpu_custom_call" in hlo
    with jax.default_device(cpu):
        ref = clustering.choose_k(X, k_max=4, restarts=2)
    agree = label_agreement(res["labels"], ref["labels"])
    ok = (on_kernel and kernel and res["k"] == 3 and ref["k"] == 3
          and agree >= MIN_LABEL_AGREEMENT)
    sil = lambda r: {k: round(v, 6) for k, v in r["per_k"].items()}
    return ok, (f"n={FLEET_PROFILES} k={res['k']} (cpu k={ref['k']}) "
                f"pallas_kernel={kernel} label_agreement={agree:.6f} "
                f"silhouette={sil(res)} cpu_silhouette={sil(ref)}")


def phase_scan(cpu):
    from benchmarks.engine_bench import fleet_cluster, fleet_workflow
    from repro.core.scheduler import make_scheduler
    from repro.workflow import ensemble

    n_nodes, n_instances, n_replicas = SCAN_SCALE
    specs = fleet_cluster(n_nodes)
    subs = [ensemble.Submission(fleet_workflow(n_instances, 2 * n_nodes),
                                seed=11)]
    ok, parts = True, []
    for name in SCAN_SCHEDULERS:
        res = ensemble.run_ensemble(specs, subs,
                                    make_scheduler(name, specs, seed=0),
                                    n_replicas)
        ref = ensemble.oracle_ensemble(specs, subs,
                                       make_scheduler(name, specs, seed=0),
                                       n_replicas)
        cmp = ensemble.compare_traces(res, ref)
        ok &= cmp["decisions_equal"] and (
            cmp["bitwise"] or cmp["max_rel_err"] <= ensemble.TPU_TIME_RTOL)
        t = res.timings
        parts.append(
            f"{name}: decisions_equal={cmp['decisions_equal']} "
            f"bitwise={cmp['bitwise']} max_rel_err={cmp['max_rel_err']!r} "
            f"first_divergence={cmp['first_divergence']} "
            f"compile_s={t['compile_s']!r} run_s={t['run_s']!r}")
    return ok, (f"{n_nodes}x{n_instances}x{n_replicas} "
                f"rtol={ensemble.TPU_TIME_RTOL!r}; " + "; ".join(parts))


def _tarema_run(specs):
    from repro.core.monitor import TraceDB
    from repro.core.scheduler import make_scheduler
    from repro.workflow.engine import Engine, EngineConfig
    from repro.workflow.nfcore import WORKFLOWS

    wf = WORKFLOWS[NFCORE_WORKFLOW]()
    db = TraceDB()
    makespans = []
    for idx in range(TAREMA_RUNS):
        sched = make_scheduler("tarema", specs, seed=idx * 7 + 3)
        eng = Engine(specs, sched, db, EngineConfig(seed=idx))
        eng.submit(wf, run_id=idx, seed=11)
        makespans.append(eng.run()["makespan"])
    nodes = [p.node for p in sched.profiles]
    groups = {frozenset(n for n, g in zip(nodes, sched.grouping["labels"])
                        if g == gid)
              for gid in set(sched.grouping["labels"].tolist())}
    labels = {t.name: sched.task_labels(db, wf.name, t.name)
              for t in wf.tasks}
    return groups, labels, makespans


def phase_tarema(cpu):
    import jax
    from repro.workflow.cluster import CLUSTERS

    ok, parts = True, []
    for cname, make_specs in CLUSTERS.items():
        specs = make_specs()
        groups, labels, spans = _tarema_run(specs)
        with jax.default_device(cpu):
            c_groups, c_labels, c_spans = _tarema_run(specs)
        same = (groups == c_groups, labels == c_labels, spans == c_spans)
        ok &= all(same)
        parts.append(f"{cname}: groups={len(groups)} same_groups={same[0]} "
                     f"same_task_labels={same[1]} same_makespans={same[2]} "
                     f"makespans={spans} cpu_makespans={c_spans}")
    return ok, f"{NFCORE_WORKFLOW} x{TAREMA_RUNS}; " + "; ".join(parts)


PHASES = (("grouping", phase_grouping), ("batched scan", phase_scan),
          ("tarema pipeline", phase_tarema))


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "no phase was run", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"chip_smoke on {dev.device_kind} ({dev.platform}, "
          f"{len(devices)} device(s)); compile cache {cache}", flush=True)
    cpu = jax.devices("cpu")[0]
    failed = []
    for i, (name, phase) in enumerate(PHASES, 1):
        t0 = time.perf_counter()
        ok, detail = phase(cpu)
        wall = time.perf_counter() - t0
        print(f"phase {i} {name} on {dev.device_kind} ({dev.platform}): "
              f"{'ok' if ok else 'FAILED'} | {detail} | wall_s={wall:.3f}",
              flush=True)
        if not ok:
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
