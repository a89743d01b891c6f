"""The replicas' work draws, seeded for every stream at once, must equal
numpy's own seeding bit for bit.

``_Topology.replica_work`` draws replica ``r`` of submission ``sub`` from
``np.random.default_rng((stable_seed(name), sub.seed + r * stride,
sub.run_id))`` -- the mapping ``dag.instantiate`` and the benchmark's
check use -- but computes ``SeedSequence`` and ``PCG64`` seeding for all
streams in one pass (``repro.core.seeding``).  Each case is one call's
streams: seeds of one and two 32-bit words, seeds that cross 2^32 or
2^64 within the call, run ids past the pool's four words.
"""
import numpy as np
import pytest

from repro.core.profiler import NodeSpec
from repro.core.scheduler import make_scheduler
from repro.core.seeding import (pcg64_state, seed_sequence_state,
                                stable_seed)
from repro.workflow import ensemble
from repro.workflow.dag import AbstractTask, WorkflowSpec
from repro.workflow.ensemble import Submission

# (seed, seed_stride, run_id, replicas)
CALLS = {
    "zero": (0, 1, 0, 1),
    "one word": (12345, 1, 0, 8),
    "two words": (2 ** 40 + 3, 7, 0, 8),
    "near 2^63": (2 ** 63 - 5, 10, 0, 8),
    "crosses 2^32": (2 ** 32 - 20, 7, 0, 8),
    "run_id > 0": (99, 1, 3, 4),
    "past the pool": (2 ** 63, 1, 2 ** 33, 4),
    "crosses 2^64": (2 ** 64 - 3, 10, 1, 4),
}
NAMES = (0, 0xFFFF, stable_seed("rnaseq"))


def _entropy(seed, stride, run_id, replicas):
    return [(name, seed + r * stride, run_id)
            for r in range(replicas) for name in NAMES]


@pytest.mark.parametrize("call", CALLS)
def test_seeding_equals_numpys(call):
    entropy = _entropy(*CALLS[call])
    cols = [np.array(c, object) for c in zip(*entropy)]
    words = seed_sequence_state(*cols)
    assert words.shape == (len(entropy), 4) and words.dtype == np.uint64
    for e, w in zip(entropy, words):
        ss = np.random.SeedSequence(e)
        assert np.array_equal(w, ss.generate_state(4, np.uint64)), e
        assert pcg64_state(w.tolist()) == np.random.PCG64(ss).state, e


def _workflow(name):
    return WorkflowSpec(name, [
        AbstractTask("a", 3, {"cpu": 100.0, "mem": 10.0, "io": 1.0}, 1.0),
        AbstractTask("b", 2, {"cpu": 70.0, "mem": 30.0, "io": 4.0}, 1.0,
                     deps=("a",))])


def _topology(seed, stride, run_id, replicas):
    specs = [NodeSpec("n0", "m", 4, 16.0, cpu_speed=400.0, mem_bw=15000.0,
                      app_factor=1.0)]
    subs = [Submission(_workflow("wfa"), run_id=run_id, seed=seed,
                       input_scale=1.5, prefix="a"),
            Submission(_workflow("wfb"), run_id=run_id + 1, seed=seed,
                       input_scale=0.75, prefix="b")]
    return ensemble._Topology(specs, subs,
                              make_scheduler("fair", specs, seed=0), None,
                              replicas, stride)


def _former_replica_work(top):
    """One ``default_rng`` per (replica, submission) stream: the loop the
    vectorized seeding replaced, kept as its reference."""
    out = np.zeros((top.n_replicas, top.T, 3), np.float64)
    for r in range(top.n_replicas):
        for (lo, hi), sub in zip(top._sub_slices, top.submissions):
            rng = np.random.default_rng(
                (stable_seed(sub.spec.name), sub.seed + r * top.seed_stride,
                 sub.run_id))
            run_scale = float(rng.lognormal(0.0, 0.05)) * sub.input_scale
            scales = rng.lognormal(0.0, 0.35, hi - lo) * run_scale
            out[r, lo:hi] = top.base_work[lo:hi] * scales[:, None]
    return out


@pytest.mark.parametrize("call", CALLS)
def test_replica_work_equals_the_per_stream_loop(call):
    top = _topology(*CALLS[call])
    got = top.replica_work()
    assert np.array_equal(got, _former_replica_work(top))
    # the draws differ between replicas and between submissions
    assert len({row.tobytes() for row in got[:, :, 0]}) == top.n_replicas
    assert not np.array_equal(got[0, :5], got[0, 5:10])


@pytest.mark.parametrize("where", ["seed", "run_id", "replica seed"])
def test_negative_entropy_raises_value_error(where):
    if where == "replica seed":           # seed + r * stride < 0 at r = 1
        top = _topology(3, -5, 0, 2)
        with pytest.raises(ValueError):
            top.replica_work()
        return
    cols = [np.array([1, 2]), np.array([4, 5]), np.array([0, 0])]
    cols[1 if where == "seed" else 2][1] = -1
    with pytest.raises(ValueError):
        seed_sequence_state(*cols)
