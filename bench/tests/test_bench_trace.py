"""The trace reduction on small hand-made traces: busy union, idle gaps,
their attribution to the host span open during them, per-op time."""
import pytest

from bench import trace as tr

MS = 1_000_000


def _trace():
    # host: two calls of the benchmark's span; inside the second, a compile
    host = [("forecast", 0, 40 * MS), ("forecast", 50 * MS, 50 * MS),
            ("backend_compile", 55 * MS, 20 * MS)]
    ops = [("fusion.1", 5 * MS, 10 * MS), ("fusion.2", 10 * MS, 10 * MS),
           ("kmeans_lloyd_step.6", 30 * MS, 5 * MS), ("fusion.1", 80 * MS, 15 * MS),
           ("outside", 200 * MS, 5 * MS)]
    return {"ops": {"/device:TPU:0": ops},
            "modules": {"/device:TPU:0": [("jit_scan(1)", 5 * MS, 15 * MS),
                                          ("jit_scan(2)", 80 * MS, 15 * MS)]},
            "spans": [h for h in host if h[0] == "forecast"], "host": host}


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_gaps_cover_the_window_outside_busy():
    assert tr.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps([], 0, 3) == [(0, 3)]


def test_reduce_busy_idle_and_attribution():
    red = tr.reduce(_trace())
    assert red["window_s"] == pytest.approx(0.100)
    # busy: [5, 20] + [30, 35] + [80, 95] ms; the op past the window is out
    assert red["busy_s"] == pytest.approx(0.035)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(0.025)]
    # gaps: 35 -> 80 ms (midpoint in the compile), 20 -> 30, 0 -> 5, 95 -> 100
    assert red["idle_gaps"] == [["backend_compile", pytest.approx(0.045)],
                                ["forecast", pytest.approx(0.010)],
                                ["forecast", pytest.approx(0.005)],
                                ["forecast", pytest.approx(0.005)]]
    assert red["modules"]["jit_scan(1)"] == (pytest.approx(0.015), 1)


def test_op_time_matches_by_name_prefix():
    red = tr.reduce(_trace())
    assert tr.op_time(red, "kmeans_lloyd_step") == (pytest.approx(0.005), 1)
    assert tr.op_time(red, "fusion") == (pytest.approx(0.035), 3)
    assert tr.op_time(red, "lloyd") == (0.0, 0)


def test_op_name_drops_the_hlo_text():
    assert tr.op_name("%kmeans_lloyd_step.6 = (s32[100352]{0}, f32[2,3]) "
                      "custom-call(%jit_kmeans_lloyd_step.1)") == "kmeans_lloyd_step.6"
    assert tr.op_name("%broadcast_maximum_fusion.2 = f32[2] fusion(f32[2] "
                      "%jit_kmeans_lloyd_step)") == "broadcast_maximum_fusion.2"
    assert tr.op_name("jit_scan(123)") == "jit_scan(123)"


def test_reduce_needs_the_benchmarks_spans():
    t = _trace()
    t["spans"] = []
    with pytest.raises(ValueError):
        tr.reduce(t)
