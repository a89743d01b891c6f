"""The reader of ``run_ensemble``'s ``ensemble.draws`` span on a hand-made
run: the mean of ``draws_s`` over the window's forecasts, and nothing where
no call's record holds the span (a failed call, or a program without it)."""
from types import SimpleNamespace

import pytest

from bench import run as bench_run

TIMINGS = [{"build_s": 0.1, "draws_s": 0.05, "compile_s": 3.0, "compiles": 1,
            "run_s": 0.5, "fetch_s": 0.02, "n_steps": 10},
           {"build_s": 0.1, "draws_s": 0.07, "compile_s": 3.2, "compiles": 1,
            "run_s": 0.7, "fetch_s": 0.04, "n_steps": 10}]
# a record from a program without the span
OLD_TIMINGS = {"build_s": 0.1, "compile_s": 3.0, "compiles": 1, "run_s": 0.5,
               "fetch_s": 0.02, "n_steps": 10}


def _run(calls):
    return SimpleNamespace(calls=calls, trace=None)


def test_draws_reader_averages_over_forecasts():
    read = bench_run.metric_reader("scan.draws_s")
    calls = [{"timings": t} for t in TIMINGS] + [{"seed": 7}]
    assert read(_run(calls)) == pytest.approx(0.06)


@pytest.mark.parametrize("calls", [[], [{"seed": 7}],
                                   [{"timings": OLD_TIMINGS}]],
                         ids=["no calls", "no timings", "no span"])
def test_draws_reader_without_its_span(calls):
    assert bench_run.metric_reader("scan.draws_s")(_run(calls)) is None
