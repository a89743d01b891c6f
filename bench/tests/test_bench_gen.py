"""The benchmark's copied generators give the same deployments and draws as
the program's own generators today."""
import json
import os

import numpy as np
import pytest

from bench import gen

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _node_fields(spec):
    return {k: getattr(spec, k) for k in
            ("name", "machine", "cores", "mem_gb", "cpu_speed", "mem_bw",
             "io_seq", "io_rand", "net_gbps", "app_factor")}


def _wf_fields(spec):
    return [{"name": t.name, "n_instances": t.n_instances, "work": t.work,
             "peak_mem_gb": t.peak_mem_gb, "deps": tuple(t.deps),
             "req_cores": t.req_cores, "req_mem_gb": t.req_mem_gb}
            for t in spec.tasks]


def test_fleet_cluster_and_workflow_match_engine_bench():
    from benchmarks import engine_bench
    from repro.workflow.nfcore import WORKFLOWS
    cfg = _cfg("fleet-3tier")
    nodes = gen.cluster(cfg["cluster"])
    assert nodes == [_node_fields(s) for s in engine_bench.fleet_cluster(256)]
    assert [w["name"] for w in cfg["workflows"]] == list(WORKFLOWS)
    for w in cfg["workflows"]:
        assert gen.workflow(w)["tasks"] == _wf_fields(WORKFLOWS[w["name"]]())
    assert cfg["workflows"] == _cfg("paper-5-4-4-2")["workflows"]


def test_fleet_profiles_match_engine_bench_at_its_seed():
    from benchmarks import engine_bench
    cfg = _cfg("fleet-3tier")["profiles"]
    np.testing.assert_array_equal(gen.fleet_profiles(5000, 0, cfg),
                                  engine_bench.fleet_profiles(5000))
    assert not np.array_equal(gen.fleet_profiles(50, 1, cfg),
                              gen.fleet_profiles(50, 2, cfg))


def test_paper_cluster_and_nfcore_workflows_match():
    from repro.workflow.cluster import cluster_5442
    from repro.workflow.nfcore import WORKFLOWS
    cfg = _cfg("paper-5-4-4-2")
    assert gen.cluster(cfg["cluster"]) == [_node_fields(s) for s in cluster_5442()]
    assert [w["name"] for w in cfg["workflows"]] == list(WORKFLOWS)
    for w in cfg["workflows"]:
        assert gen.workflow(w)["tasks"] == _wf_fields(WORKFLOWS[w["name"]]())


@pytest.mark.parametrize("name,seed", [("fleet-3tier", 7), ("paper-5-4-4-2", 2**33 + 5)])
def test_instantiate_draws_match_dag(name, seed):
    from repro.workflow.dag import instantiate
    from repro.workflow.nfcore import WORKFLOWS
    cfg = _cfg(name)
    w = cfg["workflows"][-1]
    mine = gen.instantiate(gen.workflow(w), 1, seed)
    theirs = instantiate(WORKFLOWS[w["name"]](), 1, seed)
    assert [m["instance"] for m in mine] == [t.instance for t in theirs]
    assert [m["deps"] for m in mine] == [list(t.deps) for t in theirs]
    assert [m["work"] for m in mine] == [[t.work[k] for k in ("cpu", "mem", "io")]
                                         for t in theirs]


def test_synthetic_cpu_matches_the_profiler():
    from repro.core.profiler import profile_node_synthetic
    from repro.workflow.cluster import cluster_5442
    for spec, node in zip(cluster_5442(), gen.cluster(_cfg("paper-5-4-4-2")["cluster"])):
        assert gen.synthetic_cpu(node, 3) == profile_node_synthetic(spec, 3).features["cpu"]


def test_derive_seed_is_stable_and_takes_any_whole_number():
    assert gen.derive_seed(5, 1, 0) == gen.derive_seed(5, 1, 0)
    assert gen.derive_seed(5, 1, 0) != gen.derive_seed(5, 1, 1)
    assert 0 <= gen.derive_seed(-3, 1) < 2**63
    assert 0 <= gen.derive_seed(2**40, 2) < 2**63
