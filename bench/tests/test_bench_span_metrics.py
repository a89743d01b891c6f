"""The readers of ``run_ensemble``'s spans on a hand-made run: the mean
over the window's forecasts, and nothing where the program's record does
not hold the span (a failed call, or a program without it)."""
from types import SimpleNamespace

import pytest

from bench import run as bench_run

TIMINGS = [{"build_s": 0.1, "compile_s": 3.0, "compiles": 1, "run_s": 0.5,
            "fetch_s": 0.02, "n_steps": 10},
           {"build_s": 0.1, "compile_s": 3.2, "compiles": 1, "run_s": 0.7,
            "fetch_s": 0.04, "n_steps": 10}]
# a record from before the spans: the compile and a second run in one key
OLD_TIMINGS = {"build_s": 0.1, "compile_run_s": 3.5, "run_s": 0.5,
               "n_steps": 10}


def _run(calls):
    return SimpleNamespace(calls=calls, trace=None)


@pytest.mark.parametrize("name,mean", [("scan.compiles", 1.0),
                                       ("scan.run_s", 0.6),
                                       ("scan.fetch_s", 0.03)])
def test_span_readers_average_over_forecasts(name, mean):
    read = bench_run.metric_reader(name)
    calls = [{"timings": t} for t in TIMINGS] + [{"seed": 7}]
    assert read(_run(calls)) == pytest.approx(mean)


@pytest.mark.parametrize("name,old", [("scan.compiles", None),
                                      ("scan.run_s", 0.5),
                                      ("scan.fetch_s", None)])
def test_span_readers_without_their_span(name, old):
    read = bench_run.metric_reader(name)
    assert read(_run([])) is None
    assert read(_run([{"seed": 7}])) is None
    assert read(_run([{"timings": OLD_TIMINGS}])) == old
