"""The jitted ensemble scan must reproduce the numpy Engine bit-for-bit.

Property suite: random DAGs x random heterogeneous clusters x every
supported scheduler, with fixed pre-drawn jitter — full traces (node
assignment, start/end floats, finish order, makespans) compared exactly,
under the RNG-stream mapping documented in ``repro.workflow.ensemble``
(ordered tie-breaks in the oracle).  Unsupported engine features must
refuse loudly at build time, never silently diverge.

Runs through the ``tests/_hyp.py`` shim (deterministic fallback when
hypothesis isn't installed).
"""
import dataclasses

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core.monitor import TraceDB
from repro.core.prediction import PredictionConfig
from repro.core.profiler import NodeSpec
from repro.core.scheduler import make_scheduler
from repro.core.sizing import SizingConfig
from repro.workflow.cluster import cluster_555, cluster_5442
from repro.workflow.dag import AbstractTask, WorkflowSpec
from repro.workflow.engine import Engine, EngineConfig
from repro.workflow.ensemble import (Submission, assert_equivalent,
                                     compare_traces, oracle_ensemble,
                                     run_ensemble)
from repro.workflow.faults import FaultConfig
from repro.workflow.nfcore import WORKFLOWS

_SCHEDS = ("fair", "sjfn", "fillnodes", "roundrobin")


def random_workflow(rng, name: str) -> WorkflowSpec:
    """Layered random DAG; demands stay within random_cluster's smallest
    node (4 cores / 16 GB) so every task is placeable somewhere."""
    n_stages = int(rng.integers(2, 5))
    tasks = []
    for s in range(n_stages):
        deps = ()
        if tasks:
            n_deps = int(rng.integers(1, len(tasks) + 1))
            deps = tuple(t.name for t in
                         rng.choice(tasks, size=n_deps, replace=False))
        tasks.append(AbstractTask(
            f"{name}_s{s}", int(rng.integers(1, 6)),
            {"cpu": float(rng.uniform(50, 2000)),
             "mem": float(rng.uniform(10, 300)),
             "io": float(rng.uniform(1, 50))},
            peak_mem_gb=float(rng.uniform(0.5, 4.0)),
            deps=deps,
            req_cores=int(rng.integers(1, 5)),
            req_mem_gb=float(rng.uniform(1.0, 8.0))))
    return WorkflowSpec(name, tasks)


def random_cluster(rng) -> list[NodeSpec]:
    n = int(rng.integers(3, 9))
    return [NodeSpec(f"r-m{int(rng.integers(0, 3))}-{i}", f"m{i % 3}",
                     cores=int(rng.choice([4, 8, 16])),
                     mem_gb=float(rng.choice([16.0, 32.0, 64.0])),
                     cpu_speed=float(rng.uniform(300, 600)),
                     mem_bw=float(rng.uniform(12000, 20000)),
                     app_factor=float(rng.uniform(0.7, 1.05)))
            for i in range(n)]


@given(st.integers(0, 10_000_000))
@settings(max_examples=8, deadline=None)
def test_scan_matches_engine_on_random_cases(seed):
    rng = np.random.default_rng(seed)
    specs = random_cluster(rng)
    sched_name = _SCHEDS[seed % len(_SCHEDS)]
    subs = [Submission(random_workflow(rng, "wfa"), seed=seed, prefix="a")]
    if rng.random() < 0.5:   # delayed-arrival second stream
        subs.append(Submission(random_workflow(rng, "wfb"), seed=seed + 1,
                               at=float(rng.uniform(0.0, 60.0)), prefix="b"))
    res = run_ensemble(specs, subs, make_scheduler(sched_name, specs, seed=0),
                       n_replicas=2, seed_stride=7)
    ref = oracle_ensemble(specs, subs,
                          make_scheduler(sched_name, specs, seed=0),
                          n_replicas=2, seed_stride=7)
    assert_equivalent(res, ref)


def test_scan_matches_engine_nfcore_multisubmission():
    """Fixed paper-cluster case: sjfn + two delayed submissions."""
    specs = cluster_555()
    subs = [Submission(WORKFLOWS["cageseq"](), run_id=0, seed=7, prefix="a"),
            Submission(WORKFLOWS["cageseq"](), run_id=1, seed=8, at=25.0,
                       prefix="b")]
    res = run_ensemble(specs, subs, make_scheduler("sjfn", specs, seed=0),
                       n_replicas=2)
    ref = oracle_ensemble(specs, subs, make_scheduler("sjfn", specs, seed=0),
                          n_replicas=2)
    assert_equivalent(res, ref)
    assert (res.makespan > 0).all()
    # replicas draw different jitter -> distinct trajectories
    assert res.makespan[0] != res.makespan[1]


def test_scan_replica_seeds_match_individual_engine_runs():
    """Replica r == a stock engine run submitted with seed + r*stride."""
    specs = cluster_5442()
    wf = WORKFLOWS["mag"]()
    res = run_ensemble(specs, [Submission(wf, seed=3)],
                       make_scheduler("fillnodes", specs, seed=0),
                       n_replicas=3, seed_stride=10)
    for r in range(3):
        eng = Engine(specs, make_scheduler("fillnodes", specs, seed=0),
                     TraceDB(), EngineConfig())
        eng.submit(wf, run_id=0, seed=3 + 10 * r)
        out = eng.run()
        assert out["makespan"] == res.makespan[r]


def test_compare_traces_separates_decisions_from_times():
    """The TPU contract's measure: decisions exact, times to a tolerance."""
    specs = cluster_555()
    subs = [Submission(WORKFLOWS["mag"](), seed=3)]
    ref = oracle_ensemble(specs, subs, make_scheduler("fair", specs, seed=0),
                          n_replicas=2)
    same = compare_traces(ref, ref)
    assert same["decisions_equal"] and same["bitwise"]
    assert same["max_rel_err"] == 0.0 and same["first_divergence"] is None
    drift = dataclasses.replace(ref, end_t=ref.end_t * (1.0 + 1e-12),
                                makespan=ref.makespan * (1.0 + 1e-12))
    out = compare_traces(drift, ref)
    assert out["decisions_equal"] and not out["bitwise"]
    assert 0.5e-12 < out["max_rel_err"] < 2e-12
    order = ref.finish_order.copy()
    order[1, [3, 4]] = order[1, [4, 3]]
    out = compare_traces(dataclasses.replace(ref, finish_order=order), ref)
    assert not out["decisions_equal"]
    assert out["first_divergence"]["replica"] == 1
    assert out["first_divergence"]["finish_position"] == 3


# ------------------------------------------------------- loud refusals
def _toy():
    return WorkflowSpec("toy", [AbstractTask(
        "t0", 2, {"cpu": 100.0, "mem": 10.0, "io": 1.0}, 1.0)])


def _specs():
    return [NodeSpec("n0", "m", 4, 16.0, cpu_speed=400.0, mem_bw=15000.0,
                     app_factor=1.0)]


# one parametrized loud-refusal suite: every engine feature and every
# scheduler the batched scan cannot express must raise at *build* time
# (match pins the message naming the culprit), never silently diverge
@pytest.mark.parametrize("cfg,match", [
    (EngineConfig(speculation=True), "speculation"),
    (EngineConfig(sizing=SizingConfig()), "sizing"),
    (EngineConfig(faults=FaultConfig()), "faults"),
    (EngineConfig(prediction=PredictionConfig()), "prediction"),
])
def test_unsupported_engine_features_refuse_loudly(cfg, match):
    specs = _specs()
    with pytest.raises(NotImplementedError, match=match):
        run_ensemble(specs, [Submission(_toy())],
                     make_scheduler("fair", specs, seed=0), 1, config=cfg)


@pytest.mark.parametrize("sched,match", [
    ("tarema", "TaremaScheduler"),
    ("weighted-tarema", "WeightedTaremaScheduler"),
    ("predictive", "PredictiveScheduler"),
])
def test_unsupported_scheduler_refuses_loudly(sched, match):
    specs = cluster_555()
    with pytest.raises(NotImplementedError, match=match):
        run_ensemble(specs, [Submission(_toy())],
                     make_scheduler(sched, specs, seed=0), 1)


def test_duplicate_instance_ids_refuse_loudly():
    specs = _specs()
    subs = [Submission(_toy(), seed=1), Submission(_toy(), seed=2)]
    with pytest.raises(NotImplementedError, match="prefix"):
        run_ensemble(specs, subs, make_scheduler("fair", specs, seed=0), 1)


def test_zero_core_requests_refuse_loudly():
    specs = _specs()
    wf = WorkflowSpec("z", [AbstractTask(
        "t0", 1, {"cpu": 100.0, "mem": 10.0, "io": 1.0}, 1.0, req_cores=0)])
    with pytest.raises(NotImplementedError, match="req_cores"):
        run_ensemble(specs, [Submission(wf)],
                     make_scheduler("fair", specs, seed=0), 1)


def test_degenerate_arguments_raise_value_error():
    specs = _specs()
    sched = make_scheduler("fair", specs, seed=0)
    with pytest.raises(ValueError):
        run_ensemble(specs, [], sched, 1)
    with pytest.raises(ValueError):
        run_ensemble(specs, [Submission(_toy())], sched, 0)


def test_run_ensemble_compiles_once_and_runs_once(monkeypatch):
    """One lowering, one compile and one run of the scan per call; the
    call's record holds the spans' seconds, the compile count and the step
    count, and no timing of a second run."""
    import jax

    from repro.workflow import ensemble

    calls = {"lower": 0, "jit_call": 0, "compiled_call": 0}

    class Counted:
        def __init__(self, fn):
            self.fn = fn

        def lower(self, *args):
            calls["lower"] += 1
            return self.fn.lower(*args)

        def __call__(self, *args):
            calls["jit_call"] += 1
            return self.fn(*args)

    build = ensemble._build_scan
    monkeypatch.setattr(ensemble, "_build_scan",
                        lambda top: (lambda s, a: (Counted(s), a))(*build(top)))
    run_compiled = jax.stages.Compiled.__call__

    def counted_call(self, *args, **kwargs):
        calls["compiled_call"] += 1
        return run_compiled(self, *args, **kwargs)

    monkeypatch.setattr(jax.stages.Compiled, "__call__", counted_call)
    specs = cluster_5442()
    res = run_ensemble(specs, [Submission(_toy(), seed=3)],
                       make_scheduler("fair", specs, seed=0), 2)
    assert calls == {"lower": 1, "jit_call": 0, "compiled_call": 1}
    t = res.timings
    assert t["compiles"] == 1 and t["n_steps"] > 0
    assert "compile_run_s" not in t
    for key in ("build_s", "draws_s", "compile_s", "run_s", "fetch_s",
                "release_s"):
        assert t[key] > 0.0
    assert [(name, parent) for name, _, _, parent in t["spans"]] == [
        ("ensemble.build", None), ("ensemble.draws", 0),
        ("ensemble.compile", None), ("ensemble.run", None),
        ("ensemble.fetch", None), ("ensemble.release", None)]


def test_scan_module_is_named_jit_scan():
    """The device trace finds the scan's program by this name
    (``bench/metrics/scan.step_us.py``)."""
    import jax

    from repro.workflow import ensemble

    specs = _specs()
    top = ensemble._Topology(specs, [Submission(_toy())],
                             make_scheduler("fair", specs, seed=0), None, 1, 1)
    with jax.enable_x64(True):
        scan, args = ensemble._build_scan(top)
        text = scan.lower(*args).compile().as_text()
    assert text.startswith("HloModule jit_scan,")


@pytest.mark.parametrize("uniform_demand", [True, False],
                         ids=["uniform-demand", "mixed-demand"])
def test_sjfn_scan_has_no_gather_on_the_rank_table(uniform_demand):
    """sjfn looks task names up in its [R, K] int32 rank table by a one-hot
    select (``ensemble_step.rank_of_names``): a gather from that table ran
    element by element on the TPU.  The gather's operand types end its
    line in the lowered text, the table's first."""
    import re

    import jax

    from repro.workflow import ensemble

    if uniform_demand:
        specs = cluster_5442()
        subs = [Submission(WORKFLOWS["cageseq"](), seed=4, prefix="c"),
                Submission(WORKFLOWS["eager"](), seed=5, prefix="e")]
    else:
        specs, wf = _reuse_case()
        subs = [Submission(wf, seed=1, prefix="a")]
    top = ensemble._Topology(specs, subs, make_scheduler("sjfn", specs, seed=0),
                             None, 3, 1)
    assert top.uniform_demand == uniform_demand
    with jax.enable_x64(True):
        scan, args = ensemble._build_scan(top)
        text = scan.lower(*args).as_text()
    operands = re.findall(r'"stablehlo\.gather".*: \((tensor<[^>]*>)', text)
    assert operands                       # the pattern reads the other gathers
    assert f"tensor<{top.n_replicas}x{top.K}xi32>" not in operands


_SCATTER_OPERAND = r'"stablehlo\.scatter"\(.*?\}\) : \((tensor<[^>]*>)'


@pytest.mark.parametrize("sched_name", ["fair", "sjfn"])
def test_scan_has_no_scatter_into_slot_or_task_panels(sched_name):
    """Every per-replica write of the scan's step into an [R, N, CAP] slot
    panel or an [R, TT] task panel is a one-hot masked select
    (``ensemble_step.put_one_hot``): a point scatter runs element by
    element on a TPU, so its cost grew with R.  A scatter's operand types
    follow its update region in the lowered text, the panel's first."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.workflow import ensemble

    specs = cluster_5442()
    subs = [Submission(WORKFLOWS["cageseq"](), seed=4, prefix="c"),
            Submission(WORKFLOWS["eager"](), seed=5, prefix="e")]
    top = ensemble._Topology(specs, subs,
                             make_scheduler(sched_name, specs, seed=0),
                             None, 3, 1)
    R, N, CAP, TT = top.n_replicas, top.N, top.CAP, top.TT
    with jax.enable_x64(True):
        scan, args = ensemble._build_scan(top)
        text = scan.lower(*args).as_text()
        # the pattern reads the panel of a point scatter
        point = jax.jit(lambda a, i: a.at[jnp.arange(R), i].set(1.0)).lower(
            jnp.zeros((R, TT)), jnp.zeros(R, jnp.int32)).as_text()
    assert re.findall(_SCATTER_OPERAND, point, re.S) == [f"tensor<{R}x{TT}xf64>"]
    panels = {f"tensor<{R}x{shape}x{t}>" for shape in (f"{N}x{CAP}", TT)
              for t in ("f64", "i32")}
    assert not panels & set(re.findall(_SCATTER_OPERAND, text, re.S))


def _uniform(wf):
    return WorkflowSpec(wf.name, [dataclasses.replace(t, req_cores=2,
                                                      req_mem_gb=4.0)
                                  for t in wf.tasks])


@pytest.mark.parametrize("case", ["uniform", "arrivals", "mixed"])
@pytest.mark.parametrize("sched_name", _SCHEDS)
def test_scan_matches_engine_with_replicas_gated_apart(sched_name, case):
    """Three replicas of a contended cluster, so that in one placement
    iteration some replicas place while others fail or are done, and in one
    step some finish while others idle: the gated writes must leave the
    gated-off replicas' panels exactly as the engine has them.  Every
    scheduler under uniform demand, under delayed arrivals and under mixed
    demand."""
    from repro.workflow import ensemble

    rng = np.random.default_rng(11)
    specs = random_cluster(rng)
    wfs = [random_workflow(rng, "wfa"), random_workflow(rng, "wfb")]
    if case != "mixed":
        wfs = [_uniform(wf) for wf in wfs]
    at = 30.0 if case == "arrivals" else 0.0
    subs = [Submission(wfs[0], seed=1, prefix="a"),
            Submission(wfs[1], seed=2, at=at, prefix="b")]
    top = ensemble._Topology(specs, subs,
                             make_scheduler(sched_name, specs, seed=0),
                             None, 3, 7)
    assert (top.uniform_demand, top.has_arrivals) == (
        case != "mixed", case == "arrivals")
    res = run_ensemble(specs, subs, make_scheduler(sched_name, specs, seed=0),
                       n_replicas=3, seed_stride=7)
    ref = oracle_ensemble(specs, subs,
                          make_scheduler(sched_name, specs, seed=0),
                          n_replicas=3, seed_stride=7)
    assert_equivalent(res, ref)
    assert len(set(res.makespan)) == 3


# ------------------------------------------------------- program reuse
def _reuse_case():
    """A mixed-core cluster (divisions by 4-, 8- and 16-core counts) and
    one random DAG."""
    rng = np.random.default_rng(5)
    return random_cluster(rng), random_workflow(rng, "wfa")


def _forecast(specs, subs, sched_name, n_replicas=2, config=None):
    return run_ensemble(specs, subs, make_scheduler(sched_name, specs, seed=0),
                        n_replicas, config=config)


def _oracle(specs, subs, sched_name, n_replicas=2, config=None):
    return oracle_ensemble(specs, subs,
                           make_scheduler(sched_name, specs, seed=0),
                           n_replicas, config=config)


@pytest.mark.parametrize("sched_name", _SCHEDS)
def test_repeat_topology_reuses_the_compiled_program(sched_name):
    """A second call on one topology with fresh draws runs the kept
    executable: no tracing, lowering or compiling, no compile span, and
    the results of a cold call on the same draws, bit for bit."""
    import jax

    from repro.workflow import ensemble

    specs, wf = _reuse_case()
    subs = lambda seed: [Submission(wf, seed=seed, prefix="a")]
    ensemble._PROGRAMS.clear()
    first = _forecast(specs, subs(1), sched_name)
    events = []

    def on_duration(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        warm = _forecast(specs, subs(2), sched_name)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert (first.timings["compiles"], first.timings["program_hits"]) == (1, 0)
    assert (warm.timings["compiles"], warm.timings["program_hits"]) == (0, 1)
    assert events == []
    assert "compile_s" not in warm.timings
    assert [(name, parent) for name, _, _, parent in warm.timings["spans"]] \
        == [("ensemble.build", None), ("ensemble.draws", 0),
            ("ensemble.run", None), ("ensemble.fetch", None),
            ("ensemble.release", None)]
    assert_equivalent(first, _oracle(specs, subs(1), sched_name))
    assert_equivalent(warm, _oracle(specs, subs(2), sched_name))
    ensemble._PROGRAMS.clear()
    cold = _forecast(specs, subs(2), sched_name)
    assert cold.timings["compiles"] == 1
    assert_equivalent(warm, cold)


@pytest.mark.parametrize("change", ["replicas", "scheduler", "smt_penalty",
                                    "io_gamma", "mem_beta", "mem_cap",
                                    "arrivals"])
def test_another_signature_compiles_anew(change):
    """Each part of the static signature that the trace reads makes a
    program of its own, and the program before it stays cached."""
    from repro.workflow import ensemble

    specs, wf = _reuse_case()
    base = {"subs": [Submission(wf, seed=1, prefix="a")],
            "sched_name": "fair", "n_replicas": 2, "config": None}
    other = dict(base)
    if change == "replicas":
        other["n_replicas"] = 3
    elif change == "scheduler":
        other["sched_name"] = "sjfn"
    elif change in ("smt_penalty", "io_gamma", "mem_beta", "mem_cap"):
        # the step's EngineConfig scalars, each changed alone
        scale = 0.5 if change == "mem_cap" else 2.0
        other["config"] = EngineConfig(
            **{change: getattr(EngineConfig(), change) * scale})
    else:
        other["subs"] = [Submission(wf, seed=1, prefix="a", at=5.0)]
    ensemble._PROGRAMS.clear()
    assert _forecast(specs, **base).timings["compiles"] == 1
    res = _forecast(specs, **other)
    assert (res.timings["compiles"], res.timings["program_hits"]) == (1, 0)
    assert_equivalent(res, _oracle(specs, **other))
    assert _forecast(specs, **base).timings["program_hits"] == 1


def test_program_cache_evicts_past_its_bound(monkeypatch):
    """The least recently used program goes once the cache is full; a
    lookup of a cached signature refreshes it."""
    import jax

    from repro.workflow import ensemble

    monkeypatch.setattr(ensemble, "_PROGRAMS", ensemble._Programs(2))
    specs = _specs()

    def build(n_replicas):
        top = ensemble._Topology(specs, [Submission(_toy())],
                                 make_scheduler("fair", specs, seed=0), None,
                                 n_replicas, 1)
        with jax.enable_x64(True):
            return ensemble._build_scan(top)[0]

    a, b = build(1), build(2)
    assert build(1) is a
    c = build(3)                              # evicts b, not a
    assert len(ensemble._PROGRAMS) == 2
    assert build(1) is a and build(3) is c
    assert build(2) is not b
    assert len(ensemble._PROGRAMS) == 2
