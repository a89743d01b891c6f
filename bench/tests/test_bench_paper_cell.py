"""The paper's own cluster under sjfn: its cell resolves with the metrics
it reports, the program matches the plain reference at the deployment's
full size, and the readers of the scan's counters average over the
window's forecasts and read nothing from a program without them."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import run as bench_run

CELL = "paper-5-4-4-2.forecast-sjfn-r256"
SEED = 2**31 + 29


def test_cell_resolves_with_its_metrics():
    c = bench_run.load_cell(CELL)
    assert c.chips == 1 and c.cfg["name"] == "paper-5-4-4-2"
    assert c.cfg["reduced"] == []
    assert c.traffic["scheduler"] == "sjfn" and c.traffic["replicas"] == 256
    assert c.limits["decision_mismatches"] == 0
    assert {m["name"] for m in c.end_to_end} == {"replicas_per_s", "setup_s"}
    traced = {m["name"] for m in c.per_layer}
    assert {"scan.key_rebuild_share", "scan.place_iters_per_step"} <= traced
    for name in traced:
        assert callable(bench_run.metric_reader(name))


def test_full_deployment_matches_the_reference_bit_for_bit():
    """All 15 nodes and all five workflows (304 tasks), 8 replicas, through
    ``run_ensemble``; the reference in the same process, on the CPU."""
    from bench.loops import forecast

    c = bench_run.load_cell(CELL)
    c.traffic["replicas"] = 8
    drv = forecast.Loop(c.cfg, c.traffic, SEED)
    drv.setup()
    rec = drv.call(0)
    assert rec["replicas"] == 8 and len(rec["node_idx"][0]) == 304
    for r in range(8):
        ref = forecast._reference_job((drv.nodes, drv.wfs, drv.subs,
                                       rec["seed"] + r, "sjfn", drv.negspeed,
                                       "float64"))
        for key in ("node_idx", "finish_order", "start_t", "end_t",
                    "makespan"):
            np.testing.assert_array_equal(rec[key][r], ref[key], err_msg=key)
    t = rec["timings"]
    assert 0 < t["key_rebuilds"] <= t["n_steps"] == 306
    assert 304 <= t["place_iters"]


def test_sound_run_at_a_few_replicas_is_correct():
    c = bench_run.load_cell(CELL)
    c.traffic.update(replicas=4, check_replicas=3)
    out = bench_run.run_cell(c, SEED, 0.2, False, require_chip=False)
    assert out["correct"], out["checked"]
    assert out["checked"]["time_rel_err"]["value"] == 0.0
    assert set(out["metrics"]) == {"replicas_per_s", "setup_s"}


TIMINGS = [{"key_rebuilds": 305, "place_iters": 358, "n_steps": 306},
           {"key_rebuilds": 153, "place_iters": 612, "n_steps": 306}]
# the record of a program whose scan keeps no counters
OLD_TIMINGS = {"build_s": 0.1, "run_s": 0.5, "compiles": 0, "n_steps": 306}


@pytest.mark.parametrize("name,mean", [
    ("scan.key_rebuild_share", 100.0 * (305 + 153) / 2 / 306),
    ("scan.place_iters_per_step", (358 + 612) / 2 / 306)])
def test_counter_readers_average_over_forecasts(name, mean):
    read = bench_run.metric_reader(name)
    calls = [{"timings": t} for t in TIMINGS] + [{"seed": 7}]
    assert read(SimpleNamespace(calls=calls, trace=None)) == pytest.approx(mean)


@pytest.mark.parametrize("name", ["scan.key_rebuild_share",
                                  "scan.place_iters_per_step"])
def test_counter_readers_without_their_counter(name):
    read = bench_run.metric_reader(name)
    for calls in ([], [{"seed": 7}], [{"timings": OLD_TIMINGS}]):
        assert read(SimpleNamespace(calls=calls, trace=None)) is None
