"""Control-plane / execution-backend split (repro.workflow.controlplane).

Covers the three contract layers of the refactor:

  * the sim path is *delegation*, not reimplementation: a ControlPlane
    over a SimBackend produces byte-identical results to driving the
    Engine directly, and the engine refuses configs written for a real
    backend;
  * the decision helpers that moved out of engine.py keep their exact
    semantics (array-path feature detection incl. the MRO-depth rule,
    suffix-min blocked-queue proof);
  * the real path: LocalProcessBackend runs actual subprocesses through
    the same scheduler seam, with OOM escalation and retry budgets
    mirroring the simulator's policy — and a TraceDB fed by real
    measurements satisfies the same CheckedEngine-style invariants
    (exactly-once completion, non-negative usage, label-ready features)
    as a simulated one (sim-vs-real trace-schema parity).

Real-backend tests use the pure-python ``probe`` payload, so each attempt
is a fast interpreter-only child; jax-flavoured payloads are exercised by
tests/test_profiler_local.py and benchmarks/realexec_bench.py.
"""
import os

import numpy as np
import pytest

from repro.core import labeling
from repro.core.clustering import choose_k
from repro.core.monitor import TASK_FEATURES, TraceDB
from repro.core.profiler import NodeProfile, NodeSpec
from repro.core.scheduler import make_scheduler
from repro.workflow.controlplane import (AttemptResult, ControlPlane,
                                         ControlPlaneConfig, ResourceRequest,
                                         SimBackend, detect_array_path,
                                         make_backend, suffix_min_demand)
from repro.workflow.dag import AbstractTask, WorkflowSpec
from repro.workflow.engine import Engine, EngineConfig
from repro.workflow.jobmanager import (LocalNode, LocalProcessBackend,
                                       _has_execd)
from repro.workflow.selfhost import selfhost_workflow

SPECS = [
    NodeSpec("n1-a", "n1", 8, 30.0, cpu_speed=880.0, mem_bw=18000.0),
    NodeSpec("n1-b", "n1", 8, 30.0, cpu_speed=880.0, mem_bw=18000.0),
    NodeSpec("c2-a", "c2", 16, 62.0, cpu_speed=1400.0, mem_bw=23000.0),
    NodeSpec("m1-a", "m1", 40, 240.0, cpu_speed=1100.0, mem_bw=30000.0),
]

WF = WorkflowSpec("wf", [
    AbstractTask("prep", 2, {"cpu": 300.0, "mem": 40.0, "io": 10.0},
                 peak_mem_gb=2.0, req_cores=2, req_mem_gb=4.0),
    AbstractTask("main", 4, {"cpu": 900.0, "mem": 120.0, "io": 5.0},
                 peak_mem_gb=6.0, deps=("prep",), req_cores=4,
                 req_mem_gb=8.0),
    AbstractTask("post", 1, {"cpu": 100.0, "mem": 20.0, "io": 30.0},
                 peak_mem_gb=1.0, deps=("main",), req_cores=1,
                 req_mem_gb=2.0),
])


# ------------------------------------------------------------- sim parity

@pytest.mark.parametrize("sched_name", ["fair", "tarema", "sjfn"])
def test_sim_backend_bit_for_bit(sched_name):
    """ControlPlane(SimBackend) == Engine, byte for byte."""
    def drive(via_cp: bool):
        db = TraceDB()
        sched = make_scheduler(sched_name, SPECS, seed=3)
        if via_cp:
            cp = ControlPlane(make_backend(
                "sim", specs=SPECS, scheduler=sched, db=db))
            cp.submit(WF, run_id=0, seed=1)
            cp.submit(WF, run_id=1, seed=2, at=5.0, prefix="b")
            res = cp.run()
            return res, cp.engine.assignments, cp.engine.assignment_log, db
        eng = Engine(SPECS, sched, db)
        eng.submit(WF, run_id=0, seed=1)
        eng.submit(WF, run_id=1, seed=2, at=5.0, prefix="b")
        res = eng.run()
        return res, eng.assignments, eng.assignment_log, db

    res_a, asg_a, log_a, db_a = drive(True)
    res_b, asg_b, log_b, db_b = drive(False)
    assert res_a["makespan"] == res_b["makespan"]
    assert asg_a == asg_b
    assert log_a == log_b
    assert db_a.records == db_b.records


def test_sim_backend_snapshot_delegates():
    db = TraceDB()
    be = make_backend("sim", specs=SPECS,
                      scheduler=make_scheduler("fair", SPECS, seed=0), db=db)
    cp = ControlPlane(be)
    cp.submit(WF, run_id=0)
    blob = cp.snapshot()
    assert Engine.restore(blob).all_tasks.keys() == \
        cp.engine.all_tasks.keys()


def test_engine_refuses_nonsim_backend():
    with pytest.raises(ValueError, match="backend"):
        Engine(SPECS, make_scheduler("fair", SPECS, seed=0), TraceDB(),
               EngineConfig(backend="local"))


def test_make_backend_unknown_kind():
    with pytest.raises(ValueError):
        make_backend("kubernetes")


# ------------------------------------------------- moved decision helpers

def test_detect_array_path_semantics():
    fair = make_scheduler("fair", SPECS, seed=0)
    assert detect_array_path(fair, "auto")
    assert not detect_array_path(fair, "dict")
    with pytest.raises(ValueError):
        detect_array_path(fair, "bogus")

    class DictOnly:
        def select_node(self, task, nodes, feasible, db):
            return None

    assert not detect_array_path(DictOnly(), "auto")
    with pytest.raises(ValueError):
        detect_array_path(DictOnly(), "array")

    # MRO rule: a subclass customizing select_node *without* an array twin
    # must fall back to the dict path, not have its override bypassed
    class Custom(type(fair)):
        def select_node(self, task, nodes, feasible, db):
            return None

    assert not detect_array_path(Custom(0), "auto")


def test_suffix_min_demand():
    class T:
        def __init__(self, c, m):
            self.req_cores, self.req_mem_gb = c, m

    rc, rm = suffix_min_demand([T(4, 8.0), T(2, 16.0), T(8, 1.0)])
    assert rc.tolist() == [2, 2, 8]
    assert rm.tolist() == [1.0, 1.0, 1.0]


# ------------------------------------------------------------ real backend

def probe_runner(spin_ms=15.0, rss_mb=0.0, fail_names=()):
    """Map every task to the pure-python probe payload."""
    def runner(task, node):
        return {"fn": "probe",
                "kwargs": {"spin_ms": spin_ms, "rss_mb": rss_mb,
                           "fail": task.name in fail_names}}
    return runner


def two_local_nodes(tmp_path):
    return [LocalNode("la", cpus=(), mem_gb=2.0,
                      scratch=str(tmp_path / "a"), kind="local-a"),
            LocalNode("lb", cpus=(), mem_gb=2.0,
                      scratch=str(tmp_path / "b"), kind="local-b")]


def make_local_cp(tmp_path, sched_name="fair", runner=None,
                  enforce=False, cfg=None):
    nodes = two_local_nodes(tmp_path)
    for n in nodes:
        __import__("os").makedirs(n.scratch, exist_ok=True)
    be = LocalProcessBackend(nodes, runner=runner or probe_runner(),
                             enforce_requests=enforce)
    db = TraceDB()
    sched = make_scheduler(sched_name, be.nodespecs(), seed=0)
    return ControlPlane(be, sched, db, cfg), db


SMALL = WorkflowSpec("small", [
    AbstractTask("a", 1, {"cpu": 5.0, "mem": 1.0, "io": 1.0},
                 peak_mem_gb=0.1, req_cores=1, req_mem_gb=0.2),
    AbstractTask("b", 2, {"cpu": 2.0, "mem": 4.0, "io": 1.0},
                 peak_mem_gb=0.1, deps=("a",), req_cores=1, req_mem_gb=0.2),
    AbstractTask("c", 1, {"cpu": 1.0, "mem": 1.0, "io": 4.0},
                 peak_mem_gb=0.1, deps=("b",), req_cores=1, req_mem_gb=0.2),
])


@pytest.mark.parametrize("path", ["array", "dict"])
def test_local_backend_runs_dag(tmp_path, path):
    """Real subprocesses, both placement paths of the scheduler seam."""
    cp, db = make_local_cp(tmp_path,
                           cfg=ControlPlaneConfig(placement_path=path))
    assert cp._use_array == (path == "array")
    cp.submit(SMALL, run_id=0, prefix="r0")
    res = cp.run(max_wall_s=120)
    assert res["makespan"] > 0
    done = [r for r in cp.assignment_log if r.completed]
    assert len(done) == 4 and len(res["assignments"]) == 4
    assert all(t.state == "done" for t in cp.all_tasks.values())
    # dependency order held under real concurrency
    ends = {r.instance: r.end for r in done}
    starts = {r.instance: r.start for r in done}
    assert starts["r0/b[0]"] >= ends["r0/a[0]"]
    assert starts["r0/c[0]"] >= max(ends["r0/b[0]"], ends["r0/b[1]"])


def check_trace_invariants(db, log, makespan, node_names, workflow,
                           task_names):
    """CheckedEngine-style post-run invariants, backend-agnostic: exactly-
    once completion, well-formed records, non-negative usage, label-ready
    features.  Applied verbatim to simulated and real runs."""
    completed = [r for r in log if r.completed]
    insts = [r.instance for r in completed]
    assert len(insts) == len(set(insts)), "instance completed twice"
    for r in completed:
        assert r.node in node_names
        assert 0.0 <= r.start <= r.end <= makespan + 1e-6
        assert r.used_mem_gb >= 0.0 and r.cores >= 1 and r.mem_gb > 0.0
        assert r.outcome == "done"
    for t in task_names:
        assert db.has_history(workflow, t)
        for f in TASK_FEATURES:
            mu = db.mean_usage(workflow, t, f)
            assert mu is not None and np.isfinite(mu) and mu >= 0.0
        rt = db.mean_runtime(workflow, t)
        assert rt is not None and rt > 0.0


def test_trace_schema_parity_sim_vs_real(tmp_path):
    """A TraceDB fed by LocalProcessBackend satisfies the same invariants
    (and is consumable by the same labeling code) as a simulated one."""
    # --- simulated run
    sim_db = TraceDB()
    eng = Engine(SPECS, make_scheduler("fair", SPECS, seed=0), sim_db)
    eng.submit(WF, run_id=0, seed=1)
    sim_res = eng.run()
    check_trace_invariants(sim_db, eng.assignment_log, sim_res["makespan"],
                           set(eng.nodes), "wf", ("prep", "main", "post"))
    # --- real run
    cp, real_db = make_local_cp(tmp_path)
    cp.submit(SMALL, run_id=0, prefix="r0")
    real_res = cp.run(max_wall_s=120)
    check_trace_invariants(real_db, cp.assignment_log, real_res["makespan"],
                           set(cp.nodes), "small", ("a", "b", "c"))
    # --- identical schema: same trace fields, same usage keys, JSON-plain
    import dataclasses
    import json
    sim_t, real_t = sim_db.records[0], real_db.records[0]
    fields = lambda t: {f.name for f in dataclasses.fields(t)}
    assert fields(sim_t) == fields(real_t)
    assert set(sim_t.usage) == set(real_t.usage) == set(TASK_FEATURES)
    json.dumps([real_t.usage, real_t.runtime_s])   # plain floats only
    # --- label-ready: the same labeling code labels both
    from repro.core.profiler import FEATURES
    profiles = [NodeProfile(n.name, n.kind,
                            {f: 1.0 + i for f in FEATURES},
                            {"cores": 1, "mem_gb": 2.0})
                for i, n in enumerate(cp.backend.nodes())]
    X = np.stack([p.vector() for p in profiles])
    labels = choose_k(X)["labels"]
    info = labeling.build_group_info(profiles, labels)
    for task in ("a", "b", "c"):
        lab = labeling.label_task(real_db, info, "small", task)
        assert lab is not None
        assert set(lab) == set(TASK_FEATURES)
        assert all(1 <= v <= info.n_groups for v in lab.values())


def test_oom_retry_escalates_and_completes(tmp_path):
    """An attempt whose measured peak RSS exceeds its request fails as OOM
    and is retried under an escalated request (simulator sizing semantics
    on real processes)."""
    wf = WorkflowSpec("oomy", [
        AbstractTask("hog", 1, {"cpu": 1.0, "mem": 9.0, "io": 1.0},
                     peak_mem_gb=0.15, req_cores=1, req_mem_gb=0.04)])
    cfg = ControlPlaneConfig(mem_escalation=8.0, max_oom_retries=2)
    cp, db = make_local_cp(
        tmp_path, runner=probe_runner(spin_ms=40.0, rss_mb=120.0),
        enforce=True, cfg=cfg)
    cp.submit(wf, run_id=0)
    res = cp.run(max_wall_s=120)
    task = cp.all_tasks["hog[0]"]
    assert task.state == "done", [
        (r.outcome, r.mem_gb, r.used_mem_gb) for r in cp.assignment_log]
    assert task.attempt >= 1 and task.req_mem_gb > 0.04
    outcomes = [r.outcome for r in cp.assignment_log]
    assert "oom" in outcomes and outcomes[-1] == "done"
    assert cp.retry_stats["oom_retries"] >= 1
    # the failed attempt's partial service is logged
    oom_rec = next(r for r in cp.assignment_log if r.outcome == "oom")
    assert not oom_rec.completed and oom_rec.used_mem_gb > 0.04


def test_sampler_ignores_preexec_window():
    """Regression: Popen with ``cwd=`` forks before exec, and in that window
    the child pid's /proc entries describe the PARENT — a VmHWM sample
    there read the control plane's own multi-GB RSS as the child's peak
    and OOM-killed every enforced attempt once the test process had jax
    loaded.  ``_has_execd`` gates sampling on the cmdline flip at exec."""
    with open(f"/proc/{os.getpid()}/cmdline", "rb") as f:
        own = tuple(c.decode("utf-8", "replace")
                    for c in f.read().split(b"\0") if c)
    assert _has_execd(os.getpid(), own)          # exec'd: cmdline matches
    assert not _has_execd(                       # pre-exec lookalike: the
        os.getpid(), ("python", "-m", "repro.workflow.selfhost", "{}"))
    assert not _has_execd(2 ** 22 + 1, own)      # vanished pid -> False


def test_child_peak_rss_not_fork_inherited():
    """Regression: Linux fork-inherits ru_maxrss, so a task child spawned
    by a multi-GB parent used to *report* the parent's peak as its own —
    enforcement then OOM-killed every attempt no matter how far the
    request escalated.  The child must report its own post-exec VmHWM:
    a tiny probe launched from a 0.5-GB parent stays tiny."""
    import subprocess
    import sys
    code = (
        "import json\n"
        "ballast = bytearray(500 * 10**6)\n"
        "for i in range(0, len(ballast), 4096): ballast[i] = 1\n"
        "import subprocess, sys\n"
        "payload = json.dumps({'fn': 'probe',"
        " 'kwargs': {'spin_ms': 5.0, 'rss_mb': 20.0}})\n"
        "out = subprocess.run([sys.executable, '-m',"
        " 'repro.workflow.selfhost', payload],"
        " capture_output=True, text=True).stdout\n"
        "sys.stdout.write(out.splitlines()[-1])\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=_selfhost_env())
    assert out.returncode == 0, out.stderr
    import json
    rep = json.loads(out.stdout[len("TAREMA_RESULT "):])
    # own footprint (interpreter + 20 MB ballast), NOT the 0.5-GB parent
    assert 0.0 < rep["peak_rss_gb"] < 0.3, rep


def _selfhost_env():
    import sys
    env = dict(os.environ)
    src = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                       os.pardir, "src"))
    pp = env.get("PYTHONPATH", "")
    if src not in pp.split(os.pathsep):
        env["PYTHONPATH"] = src + (os.pathsep + pp if pp else "")
    return env


def test_failure_retry_budget_and_cancellation(tmp_path):
    """Deterministic child failure: retries consume the fault budget, then
    the instance fails permanently and its downstream is cancelled."""
    wf = WorkflowSpec("faily", [
        AbstractTask("boom", 1, {"cpu": 1.0, "mem": 1.0, "io": 1.0},
                     peak_mem_gb=0.1, req_cores=1, req_mem_gb=0.2),
        AbstractTask("after", 2, {"cpu": 1.0, "mem": 1.0, "io": 1.0},
                     peak_mem_gb=0.1, deps=("boom",), req_cores=1,
                     req_mem_gb=0.2)])
    cfg = ControlPlaneConfig(max_task_retries=1)
    cp, db = make_local_cp(
        tmp_path, runner=probe_runner(fail_names={"boom"}), cfg=cfg)
    cp.submit(wf, run_id=0)
    res = cp.run(max_wall_s=120)
    assert cp.all_tasks["boom[0]"].state == "killed"
    assert all(cp.all_tasks[f"after[{i}]"].state == "killed"
               for i in range(2))
    outs = [r.outcome for r in cp.assignment_log]
    assert outs.count("task-failure") == 2      # initial + 1 retry
    assert outs.count("fault-fail") == 1
    assert outs.count("cancelled") == 2
    assert not db.has_history("faily", "boom")  # no fake completions
    assert res["assignments"] == []


def test_stuck_queue_raises(tmp_path):
    wf = WorkflowSpec("big", [
        AbstractTask("huge", 1, {"cpu": 1.0, "mem": 1.0, "io": 1.0},
                     peak_mem_gb=0.1, req_cores=64, req_mem_gb=999.0)])
    cp, _ = make_local_cp(tmp_path)
    cp.submit(wf, run_id=0)
    with pytest.raises(RuntimeError, match="stuck"):
        cp.run(max_wall_s=30)


def test_real_backend_requires_scheduler_and_db(tmp_path):
    be = LocalProcessBackend(two_local_nodes(tmp_path),
                             runner=probe_runner())
    with pytest.raises(ValueError, match="scheduler"):
        ControlPlane(be)
    with pytest.raises(ValueError, match="simulator"):
        ControlPlane(be, make_scheduler("fair", be.nodespecs(), seed=0),
                     TraceDB()).snapshot()


def test_selfhost_workflow_shape():
    wf = selfhost_workflow(quick=True)
    names = [t.name for t in wf.tasks]
    assert names == ["ingest", "transform", "compute", "report"]
    assert sum(t.n_instances for t in wf.tasks) <= 8   # CI smoke budget
    wf_t = selfhost_workflow(quick=False, include_train=True)
    assert "train" in [t.name for t in wf_t.tasks]
    report = next(t for t in wf_t.tasks if t.name == "report")
    assert "train" in report.deps


@pytest.mark.parametrize("caller_env", [{}, {"JAX_PLATFORMS": "tpu"}])
def test_local_backend_children_stay_off_the_accelerator(tmp_path,
                                                         caller_env):
    """Task children run on virtual CPU nodes: whatever the caller's env
    says, they get ``JAX_PLATFORMS=cpu``, so none reaches for a chip the
    parent process holds."""
    be = LocalProcessBackend(two_local_nodes(tmp_path),
                             runner=probe_runner(), env=dict(caller_env))
    assert be._env["JAX_PLATFORMS"] == "cpu"
