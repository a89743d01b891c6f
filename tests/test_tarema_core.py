"""Unit + property tests for the paper's three phases: clustering, labeling,
scoring allocation — including hypothesis properties on the invariants."""
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import allocation, labeling
from repro.core.clustering import (choose_k, kmeans_pp, silhouette,
                                   silhouette_blocked, standardize)
from repro.core.monitor import TaskTrace, TraceDB
from repro.core.profiler import profile_cluster_synthetic
from repro.workflow.cluster import cluster_555, cluster_5442

import jax
import jax.numpy as jnp


# ------------------------------------------------------------- clustering

def test_profiling_finds_three_groups_both_clusters():
    for specs, merged in ((cluster_555(), False), (cluster_5442(), True)):
        profiles = profile_cluster_synthetic(specs, seed=0)
        X = np.stack([p.vector() for p in profiles])
        res = choose_k(X, k_max=6)
        assert res["k"] == 3
        info = labeling.build_group_info(profiles, res["labels"])
        sizes = sorted(len(v) for v in info.group_nodes.values())
        assert sizes == ([2, 4, 9] if merged else [5, 5, 5])


def test_silhouette_prefers_true_k():
    rng = np.random.default_rng(0)
    X = np.concatenate([rng.normal(c, 0.05, (20, 3)) for c in (0.0, 1.0, 2.0)])
    res = choose_k(X, k_max=6)
    assert res["k"] == 3
    assert res["silhouette"] > 0.8


@given(st.integers(2, 5), st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_kmeans_partitions_everything(k, seed):
    rng = np.random.default_rng(seed)
    X = standardize(rng.normal(size=(30, 4)))
    labels, C, inertia = kmeans_pp(X, k, jax.random.key(seed))
    labels = np.asarray(labels)
    assert labels.shape == (30,)
    assert set(labels.tolist()) <= set(range(k))
    assert float(inertia) >= 0.0


def test_silhouette_blocked_matches_dense():
    """The streamed silhouette must agree with the dense (n,n) one."""
    rng = np.random.default_rng(3)
    X = standardize(np.concatenate(
        [rng.normal(c, 0.2, (70, 4)) for c in (0.0, 1.0, 3.0)]))
    labels, _, _ = kmeans_pp(X, 3, jax.random.key(1))
    dense = float(silhouette(X, labels, 3))
    for block in (32, 64, 210):          # non-divisor blocks exercise padding
        blocked = float(silhouette_blocked(X, labels, 3, block=block))
        np.testing.assert_allclose(blocked, dense, atol=1e-5)


def test_choose_k_fleet_scale_sampled():
    """Above the sample threshold choose_k scores through the blocked path
    (never a dense (n,n)) and still recovers the true k."""
    rng = np.random.default_rng(5)
    X = np.concatenate([rng.normal(c, 0.05, (4000, 3)) for c in (0.0, 1.0, 2.0)])
    res = choose_k(X, k_max=5, restarts=2,
                   silhouette_sample=2048, silhouette_block=512)
    assert res["k"] == 3
    assert res["labels"].shape == (12000,)
    assert res["silhouette"] > 0.8


@pytest.mark.parametrize("sample", [4096, 64])    # dense and blocked scoring
def test_choose_k_records_its_runs_and_host_syncs(sample):
    """One ``grouping.kmeans`` span per (k, restart), one
    ``grouping.silhouette`` per k, and one ``grouping.sync`` per blocking
    read: each run's inertia, each k's score, and the labels and centres
    each time the best k improves."""
    rng = np.random.default_rng(2)
    X = np.concatenate([rng.normal(c, 0.05, (60, 3)) for c in (0.0, 1.0, 2.0)])
    k_max, restarts = 5, 3
    res = choose_k(X, k_max=k_max, restarts=restarts, silhouette_sample=sample)
    t = res["timings"]
    spans = t["spans"]
    names = [s[0] for s in spans]
    n_k = k_max - 1
    improvements = sum(
        1 for k, s in res["per_k"].items()
        if all(s > res["per_k"][j] for j in range(2, k)))
    assert names.count("grouping.kmeans") == t["kmeans_runs"] == n_k * restarts
    assert names.count("grouping.silhouette") == n_k
    assert names.count("grouping.standardize") == 1
    assert (names.count("grouping.sync") == t["host_syncs"]
            == n_k * restarts + n_k + 2 * improvements)
    children = {}
    for name, _, _, parent in spans:
        if name == "grouping.sync":
            children.setdefault(parent, []).append(parent)
    for i, (name, _, _, _) in enumerate(spans):
        if name in ("grouping.kmeans", "grouping.silhouette"):
            assert len(children.pop(i)) == 1
    assert len(children.pop(None)) == 2 * improvements and not children
    for key in ("standardize_s", "kmeans_s", "silhouette_s", "sync_s"):
        assert t[key] > 0.0


# ---------------------------------------------------------------- labeling

def _info(specs):
    profiles = profile_cluster_synthetic(specs, seed=0)
    res = choose_k(np.stack([p.vector() for p in profiles]), k_max=6)
    return labeling.build_group_info(profiles, res["labels"])


def test_percentiles_formula():
    info = _info(cluster_555())
    ps = labeling.percentiles(info, "cpu")
    # equal group sizes and cores -> thirds (paper's formula)
    np.testing.assert_allclose(ps, [0.0, 1 / 3, 2 / 3, 1.0], atol=1e-9)
    assert ps[0] == 0.0 and ps[-1] == 1.0


def test_label_task_uses_history_and_intervals():
    info = _info(cluster_555())
    db = TraceDB()
    assert labeling.label_task(db, info, "wf", "t0") is None  # unknown
    for i, cpu in enumerate([50, 120, 200]):
        db.add(TaskTrace("wf", f"t{i}", f"t{i}[0]", 0, "n", 10.0,
                         {"cpu": cpu, "mem": 1.0 + i, "io": 5.0}))
    lo = labeling.label_task(db, info, "wf", "t0")
    hi = labeling.label_task(db, info, "wf", "t2")
    assert lo["cpu"] == 1 and hi["cpu"] == info.n_groups
    assert lo["mem"] <= hi["mem"]


@given(st.lists(st.floats(0.0, 400.0), min_size=1, max_size=30),
       st.floats(0.0, 400.0))
@settings(max_examples=25, deadline=None)
def test_label_bounds_monotone(usages, value):
    info = _info(cluster_555())
    bounds = labeling.usage_intervals(info, "cpu", usages)
    lab = labeling.label_from_bounds(value, bounds)
    assert 1 <= lab <= info.n_groups
    lab2 = labeling.label_from_bounds(value + 1.0, bounds)
    assert lab2 >= lab      # monotone in usage


# -------------------------------------------------------------- allocation

def test_score_matrix_matches_paper_example():
    """Table I: task (3,3,2) against groups 1..4 -> sums of |diff|."""
    groups = jnp.asarray([[1, 1, 1], [2, 2, 3], [1, 1, 2], [3, 3, 3]], jnp.float32)
    task = jnp.asarray([[3, 3, 2]], jnp.float32)
    scores = np.asarray(allocation.score_matrix(groups, task))[0]
    np.testing.assert_allclose(scores, [5, 3, 4, 1])
    assert int(scores.argmin()) == 3   # group four wins, as in the paper


@given(st.integers(1, 3), st.integers(1, 3), st.integers(1, 3))
@settings(max_examples=20, deadline=None)
def test_allocation_prefers_matching_group(c, m, i):
    info = _info(cluster_555())
    labels = {"cpu": c, "mem": m, "io": i}
    order = allocation.priority_groups(info, labels)
    assert sorted(order) == list(range(info.n_groups))
    # the top group minimises the score
    t = np.array([c, m, i], float)
    g = np.stack([info.labels_vector(gi) for gi in range(info.n_groups)])
    scores = np.abs(g - t).sum(axis=1)
    assert scores[order[0]] == scores.min()


def test_pick_node_falls_back_when_group_full():
    info = _info(cluster_555())
    labels = {"cpu": 3, "mem": 3, "io": 3}
    best = allocation.priority_groups(info, labels)[0]
    feasible = {n: info.node_group[n] != best for n in info.node_group}
    load = {n: 0.0 for n in info.node_group}
    chosen = allocation.pick_node(info, labels, load, feasible)
    assert chosen is not None and info.node_group[chosen] != best


def test_unknown_task_goes_least_loaded():
    info = _info(cluster_555())
    load = {n: 1.0 for n in info.node_group}
    target = next(iter(info.node_group))
    load[target] = 0.0
    feasible = {n: True for n in info.node_group}
    assert allocation.pick_node(info, None, load, feasible) == target


# ------------------------------------------------------------------ monitor

def test_tracedb_aggregates_and_persistence(tmp_path):
    db = TraceDB()
    for r in range(4):
        db.add(TaskTrace("wf", "align", f"align[{r}]", r, "n1", 100.0 + r,
                         {"cpu": 150.0, "mem": 3.0, "io": 10.0}))
    assert db.has_history("wf", "align")
    assert abs(db.mean_runtime("wf", "align") - 101.5) < 1e-9
    assert abs(db.mean_usage("wf", "align", "cpu") - 150.0) < 1e-9
    assert db.runtime_quantile("wf", "align", 0.95) == 103.0
    p = tmp_path / "db.json"
    db.save(str(p))
    db2 = TraceDB.load(str(p))
    assert db2.mean_runtime("wf", "align") == db.mean_runtime("wf", "align")
