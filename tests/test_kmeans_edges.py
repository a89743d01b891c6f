"""choose_k / k-means edge cases: empty clusters mid-Lloyd and tiny (n < k)
profile sets, with the fused Pallas Lloyd step validated against the
kernels/ref.py oracle in interpret mode."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import labeling
from repro.core.clustering import choose_k, kmeans_pp, standardize
from repro.core.profiler import profile_cluster_synthetic
from repro.kernels import ref
from repro.kernels.kmeans import kmeans_lloyd_step
from repro.workflow.cluster import cluster_555


def test_kmeans_empty_cluster_during_lloyd():
    """More centers than distinct blobs: some clusters necessarily empty.
    The Lloyd update must keep those centers finite (no 0/0) and still
    partition every point."""
    rng = np.random.default_rng(0)
    X = standardize(np.concatenate([rng.normal(c, 0.01, (16, 3))
                                    for c in (0.0, 10.0)]))
    labels, C, inertia = kmeans_pp(X, 5, jax.random.key(0))
    labels = np.asarray(labels)
    assert labels.shape == (32,)
    assert set(labels.tolist()) <= set(range(5))
    assert np.isfinite(np.asarray(C)).all(), "empty cluster produced NaN/inf"
    assert np.isfinite(float(inertia)) and float(inertia) >= 0.0


def test_lloyd_kernel_empty_cluster_matches_ref():
    """Fused kernel vs oracle on a center set with a guaranteed-empty
    cluster (one center far from every point): identical labels and
    all-zero sums/counts for the empty cluster, in interpret mode."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0.0, 1.0, (64, 4)), jnp.float32)
    c = jnp.concatenate([jnp.asarray(rng.normal(0.0, 1.0, (3, 4)), jnp.float32),
                         jnp.full((1, 4), 1e4, jnp.float32)])   # never nearest
    lab_k, d_k, sums_k, cnt_k = kmeans_lloyd_step(x, c, block_n=16,
                                                  interpret=True)
    lab_r, d_r, sums_r, cnt_r = ref.kmeans_lloyd_step(x, c)
    np.testing.assert_array_equal(np.asarray(lab_k), np.asarray(lab_r))
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sums_k), np.asarray(sums_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_r))
    assert float(cnt_k[3]) == 0.0
    np.testing.assert_array_equal(np.asarray(sums_k[3]), np.zeros(4))


def test_kmeans_more_centers_than_points():
    """k > n: duplicated seeds leave clusters empty from iteration one."""
    rng = np.random.default_rng(2)
    X = standardize(rng.normal(size=(3, 4)))
    labels, C, inertia = kmeans_pp(X, 5, jax.random.key(2))
    labels = np.asarray(labels)
    assert set(labels.tolist()) <= set(range(5))
    assert np.isfinite(np.asarray(C)).all()


@pytest.mark.parametrize("n", [1, 2])
def test_choose_k_tiny_profile_sets(n):
    """n < 3 cannot sweep 2 <= k <= n-1: every node becomes its own group
    (the seed implementation crashed here)."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(n, 6)) + 10.0
    res = choose_k(X, k_max=6)
    assert res["k"] == n
    assert res["labels"].shape == (n,)
    assert sorted(set(res["labels"].tolist())) == list(range(n))
    assert res["silhouette"] == 0.0 and res["per_k"] == {}


def test_choose_k_tiny_cluster_feeds_labeling():
    """A 2-node cluster must flow through build_group_info (the profiled
    schedulers' phase-1 path) without crashing."""
    profiles = profile_cluster_synthetic(cluster_555()[:2], seed=0)
    X = np.stack([p.vector() for p in profiles])
    res = choose_k(X, k_max=6)
    info = labeling.build_group_info(profiles, res["labels"])
    assert info.n_groups == 2
    assert sorted(len(v) for v in info.group_nodes.values()) == [1, 1]
    for f in ("cpu", "mem", "io"):
        ps = labeling.percentiles(info, f)
        assert ps[0] == 0.0 and ps[-1] == 1.0


def test_build_group_info_non_contiguous_labels():
    """Regression: k-means can emit non-contiguous label ids (a Lloyd
    iteration empties a cluster) and build_group_info used to np.mean an
    empty list per feature — NaN + RuntimeWarning, then a corrupt rank
    order.  Ids must be compacted and ranks stay NaN-free."""
    import warnings

    profiles = profile_cluster_synthetic(cluster_555()[:4], seed=0)
    labels = np.array([0, 2, 2, 5])          # ids 1, 3, 4 empty
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # any RuntimeWarning -> failure
        info = labeling.build_group_info(profiles, labels)
    assert info.n_groups == 3                # compacted to 0..2
    assert sorted(info.group_nodes) == [0, 1, 2]
    assert sorted(len(v) for v in info.group_nodes.values()) == [1, 1, 2]
    assert set(info.node_group.values()) == {0, 1, 2}
    for f in ("cpu", "mem", "io"):
        ranks = sorted(info.node_labels[g][f] for g in range(3))
        assert ranks == [1, 2, 3]            # every rank assigned, no NaN
        assert sorted(info.group_rank_order[f]) == [0, 1, 2]
        ps = labeling.percentiles(info, f)
        assert ps[0] == 0.0 and ps[-1] == 1.0
        assert all(np.isfinite(ps))
    # identical grouping expressed contiguously gives the same structure
    info_c = labeling.build_group_info(profiles, np.array([0, 1, 1, 2]))
    assert info_c.node_group == info.node_group
    assert info_c.node_labels == info.node_labels


def test_non_contiguous_labels_feed_task_labeling():
    """The compacted grouping must flow through the full phase-2 task
    labeling path (usage intervals + label_from_bounds) unchanged."""
    from repro.core.monitor import TaskTrace, TraceDB

    profiles = profile_cluster_synthetic(cluster_555()[:4], seed=0)
    info = labeling.build_group_info(profiles, np.array([0, 3, 3, 1]))
    db = TraceDB()
    for i, mem in enumerate([1.0, 2.0, 8.0]):
        db.add(TaskTrace("wf", f"t{i}", f"t{i}[0]", 0, "a-n1-0", 10.0,
                         {"cpu": 40.0 * (i + 1), "mem": mem, "io": 5.0}))
    for i in range(3):
        lab = labeling.label_task(db, info, "wf", f"t{i}")
        assert lab is not None
        assert all(1 <= lab[f] <= info.n_groups for f in lab)


def test_choose_k_three_profiles_sweeps_k2_only():
    """n == 3 bounds the sweep at k == 2 (n-1) and still returns a valid
    grouping."""
    rng = np.random.default_rng(4)
    X = np.concatenate([rng.normal(0.0, 0.01, (2, 3)),
                        rng.normal(5.0, 0.01, (1, 3))])
    res = choose_k(X, k_max=6)
    assert res["k"] == 2
    assert list(res["per_k"]) == [2]


# ------------------------------------------ point counts off the block size
def _blobs(n, f, seed):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0.0, 5.0, (3, f))
    return jnp.asarray(centers[rng.integers(0, 3, n)]
                       + rng.normal(0.0, 0.3, (n, f)), jnp.float32)


@pytest.mark.parametrize("n,block_n", [(1500, 1024), (5000, 1024),
                                       (777, 256)])
def test_lloyd_kernel_masks_padded_rows(n, block_n):
    """n not a multiple of the block: the rows are padded, and the padded
    rows add nothing to any sum or count (interpret mode vs the oracle)."""
    x = _blobs(n, 3, n)
    c = x[:4] + 0.1
    lab_k, d_k, sums_k, cnt_k = kmeans_lloyd_step(x, c, block_n=block_n,
                                                  interpret=True)
    lab_r, d_r, sums_r, cnt_r = ref.kmeans_lloyd_step(x, c)
    assert lab_k.shape == (n,) and d_k.shape == (n,)
    np.testing.assert_array_equal(np.asarray(lab_k), np.asarray(lab_r))
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_r))
    assert float(jnp.sum(cnt_k)) == n
    np.testing.assert_allclose(np.asarray(sums_k), np.asarray(sums_r),
                               rtol=1e-5, atol=1e-3)


def test_assign_kernel_pads_rows():
    from repro.kernels.kmeans import kmeans_assign
    x = _blobs(2500, 3, 5)
    c = x[:3] + 0.1
    lab_k, d_k = kmeans_assign(x, c, block_n=1024, interpret=True)
    lab_r, d_r = ref.kmeans_assign(x, c)
    assert lab_k.shape == (2500,)
    np.testing.assert_array_equal(np.asarray(lab_k), np.asarray(lab_r))
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r),
                               rtol=1e-4, atol=1e-4)


def test_kmeans_pp_kernel_path_at_any_point_count():
    """The kernel path (interpret mode here) takes a point count that is
    not a multiple of 1024 and groups exactly like the segment-sum path."""
    X = standardize(np.asarray(_blobs(2500, 3, 9)) + 20.0)
    key = jax.random.key(4)
    lab_k, C_k, _ = kmeans_pp(X, 3, key, use_kernel=True)
    lab_s, C_s, _ = kmeans_pp(X, 3, key, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(lab_k), np.asarray(lab_s))
    np.testing.assert_allclose(np.asarray(C_k), np.asarray(C_s),
                               rtol=1e-4, atol=1e-5)
