"""The comparisons that decide ``correct``, on hand-made pairs."""
import numpy as np
import pytest

from bench import compare


def _replica():
    return {"node_idx": np.array([0, 1, 1]), "finish_order": np.array([2, 0, 1]),
            "start_t": np.array([0.0, 0.0, 1.0]),
            "end_t": np.array([3.0, 2.0, 4.0]), "makespan": 4.0}


def test_identical_replicas_pass():
    out = compare.forecast_numbers([(_replica(), _replica())])
    assert out == {"decision_mismatches": 0, "time_rel_err": 0.0}


@pytest.mark.parametrize("field,value,mismatch", [
    ("node_idx", np.array([0, 1, 0]), 1),
    ("finish_order", np.array([0, 2, 1]), 1),
    ("end_t", np.array([3.0, 2.0, 4.0 * (1 + 1e-9)]), 0),
])
def test_divergent_replica_is_seen(field, value, mismatch):
    prog = _replica()
    prog[field] = value
    out = compare.forecast_numbers([(prog, _replica()), (_replica(), _replica())])
    assert out["decision_mismatches"] == mismatch
    if field == "end_t":
        assert out["time_rel_err"] == pytest.approx(1e-9)


def test_missing_replica_fails():
    out = compare.forecast_numbers([(None, _replica())])
    assert out["decision_mismatches"] == 1 and out["time_rel_err"] == np.inf


def test_label_share_is_up_to_renaming():
    a = np.array([0, 0, 1, 1, 2, 2])
    assert compare.label_mismatch_share(a, (a + 1) % 3) == 0.0
    assert compare.label_mismatch_share(a, np.array([0, 0, 1, 1, 2, 1])) == pytest.approx(1 / 6)
    assert compare.label_mismatch_share(a, a[:3]) == 1.0


def test_verdict_needs_every_number_under_its_limit():
    assert compare.verdict({"a": 0, "b": 1e-12}, {"a": 0, "b": 1e-10})[0]
    assert not compare.verdict({"a": 1, "b": 0.0}, {"a": 0, "b": 1e-10})[0]
    assert not compare.verdict({"a": 0}, {"a": 0, "b": 1e-10})[0]
    assert not compare.verdict({"a": float("nan")}, {"a": 1.0})[0]
