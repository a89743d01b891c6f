"""The call's two work counts, ``key_rebuilds`` and ``place_iters``:
the first is every step, on every signature; the second is held against
the placements the result holds."""
import pytest

from repro.core.scheduler import make_scheduler
from repro.workflow.cluster import cluster_5442
from repro.workflow.dag import AbstractTask, WorkflowSpec
from repro.workflow.ensemble import (Submission, _Topology, assert_equivalent,
                                     oracle_ensemble, run_ensemble)
from repro.workflow.nfcore import WORKFLOWS

_SCHEDS = ("fair", "sjfn", "fillnodes", "roundrobin")


def _subs(at=0.0):
    """Two of the paper's workflows on the paper's mixed-core cluster:
    every task asks for 2 cores and 5 GB (uniform demand)."""
    return [Submission(WORKFLOWS["cageseq"](), seed=4, prefix="c"),
            Submission(WORKFLOWS["eager"](), seed=5, at=at, prefix="e")]


def _mixed_demand():
    """One workflow whose stages ask for 1, 2 and 4 cores."""
    work = {"cpu": 300.0, "mem": 40.0, "io": 5.0}
    return [Submission(WorkflowSpec("mixed", [
        AbstractTask("a", 6, work, 1.0, req_cores=1, req_mem_gb=2.0),
        AbstractTask("b", 6, work, 1.0, deps=("a",), req_cores=4,
                     req_mem_gb=8.0),
        AbstractTask("c", 3, work, 1.0, deps=("b",), req_cores=2,
                     req_mem_gb=4.0)]), seed=9)]


def _run(subs, sched_name, n_replicas):
    specs = cluster_5442()
    res = run_ensemble(specs, subs, make_scheduler(sched_name, specs, seed=0),
                       n_replicas)
    top = _Topology(specs, subs, make_scheduler(sched_name, specs, seed=0),
                    None, n_replicas, 1)
    return res, top


@pytest.mark.parametrize("sched_name,subs", [
    ("fair", _subs()), ("fillnodes", _subs()), ("sjfn", _subs()),
    ("sjfn", _subs(at=30.0)), ("sjfn", _mixed_demand())],
    ids=["fair", "fillnodes", "sjfn-uniform", "sjfn-arrivals",
         "sjfn-mixed-demand"])
def test_key_rebuilds_is_every_step(sched_name, subs):
    res, top = _run(subs, sched_name, 3)
    assert res.timings["key_rebuilds"] == top.n_steps == res.timings["n_steps"]


@pytest.mark.parametrize("sched_name", _SCHEDS)
def test_place_iters_is_one_per_task_for_one_replica(sched_name):
    """Under uniform demand the lookahead in ``more_to_place`` ends every
    pass before a failed extraction, so each iteration places one task."""
    subs = _subs()
    res, top = _run(subs, sched_name, 1)
    assert top.uniform_demand
    assert res.timings["place_iters"] == top.T


@pytest.mark.parametrize("sched_name,subs,n_replicas", [
    ("fair", _subs(), 4), ("sjfn", _subs(), 4), ("roundrobin", _subs(), 3),
    ("fair", _mixed_demand(), 1), ("sjfn", _mixed_demand(), 3)],
    ids=["fair-r4", "sjfn-r4", "roundrobin-r3", "fair-mixed-r1",
         "sjfn-mixed-r3"])
def test_place_iters_lies_between_the_tasks_and_the_loop_cap(
        sched_name, subs, n_replicas):
    res, top = _run(subs, sched_name, n_replicas)
    cap = top.n_steps * (top.TT + top.S + 2)
    assert top.T <= res.timings["place_iters"] <= cap


def test_counters_change_no_decision_or_time():
    """The result with the counters is the engine's, bit for bit, and two
    calls on the same draws count alike."""
    specs = cluster_5442()
    subs = _subs()
    sched = lambda: make_scheduler("sjfn", specs, seed=0)
    a = run_ensemble(specs, subs, sched(), 2)
    b = run_ensemble(specs, subs, sched(), 2)
    assert_equivalent(a, oracle_ensemble(specs, subs, sched(), 2))
    assert_equivalent(a, b)
    keep = ("key_rebuilds", "place_iters", "n_steps")
    assert {k: a.timings[k] for k in keep} == {k: b.timings[k] for k in keep}
