"""The check that decides ``correct``, driven end to end on the CPU at a
size a test run holds, with the look for a chip skipped: sound runs pass,
the control (the reference in the next lower precision, in the program's
place) fails, and so does every fault that a cell can have when it is
planted in the timed path."""
import numpy as np
import pytest

from bench import compare
from bench import run as bench_run

FORECAST_CELL = "fleet-3tier.forecast-fair-r64"
REGROUP_CELL = "fleet-3tier.regroup-100k"
# the forecast cell as it is, and under sjfn, whose carried key panel the
# reference has to follow as well
SCHEDULERS = ("fair", "sjfn")
SEED = 2**31 + 11


def small(name, scheduler=None):
    """The cell with its sizes cut to what a test run holds; its widths
    and comparisons as they are, its scheduler as the cell's traffic says
    unless ``scheduler`` is given."""
    c = bench_run.load_cell(name)
    if scheduler is not None:
        c.traffic["scheduler"] = scheduler
    if c.cfg["cluster"]["kind"] == "tiers":
        c.cfg["cluster"]["nodes"] = 12
        c.cfg["submissions"] = c.cfg["submissions"][:2]
    if c.traffic["entry"] == "forecast":
        c.traffic.update(replicas=4, check_replicas=3)
    else:
        c.cfg["profiles"]["n"] = 6000
        c.traffic.update(k_max=4, restarts=2, check_calls=1)
    return c


def run(c, seconds=0.2):
    return bench_run.run_cell(c, SEED, seconds, False, require_chip=False)


@pytest.mark.parametrize("name,scheduler", [(FORECAST_CELL, s) for s in SCHEDULERS]
                         + [(REGROUP_CELL, None)])
def test_sound_run_is_correct(name, scheduler):
    out = run(small(name, scheduler))
    assert out["correct"], out["checked"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checked"
    assert {m["name"] for m in bench_run.load_cell(name).end_to_end} == set(out["metrics"])


@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_forecast_control_fails(scheduler):
    from bench.loops import forecast
    c = small(FORECAST_CELL, scheduler)
    drv = forecast.Loop(c.cfg, c.traffic, SEED)
    drv.setup()
    drv.call(0)
    ok, _ = compare.verdict(drv.check(control=True), c.limits)
    assert not ok


def test_regroup_control_fails():
    from bench.loops import regroup
    c = small(REGROUP_CELL)
    c.cfg["profiles"]["n"] = 20000
    c.traffic.update(k_max=6, restarts=4)
    drv = regroup.Loop(c.cfg, c.traffic, 5)
    drv.calls.append({"seed": 1001, "latency_s": 0.0, "k": 3})
    ok, _ = compare.verdict(drv.check(control=True), c.limits)
    assert not ok


def _wrap(monkeypatch, module, name, fault):
    orig = getattr(module, name)
    monkeypatch.setattr(module, name, lambda *a, **k: fault(orig, *a, **k))


def _altered_forecast(orig, *a, **k):
    res = orig(*a, **k)
    res.node_idx = res.node_idx.copy()
    res.node_idx[-1, -1] = (res.node_idx[-1, -1] + 1) % 12
    return res


def _half_forecast(orig, specs, subs, sched, n_replicas, **k):
    return orig(specs, subs, sched, max(1, n_replicas // 2), **k)


@pytest.mark.parametrize("fault", ["altered answer", "half the batch",
                                   "state unchanged"])
@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_forecast_faults_fail(monkeypatch, scheduler, fault):
    from repro.workflow import ensemble
    if fault == "altered answer":
        _wrap(monkeypatch, ensemble, "run_ensemble", _altered_forecast)
    elif fault == "half the batch":
        _wrap(monkeypatch, ensemble, "run_ensemble", _half_forecast)
    else:
        built = []

        def frozen(orig, top):                  # set-up's warm call is sound
            scan, args = orig(top)
            built.append(top)
            return (scan if len(built) == 1 else lambda c, *_: c), args
        _wrap(monkeypatch, ensemble, "_build_scan", frozen)
    out = run(small(FORECAST_CELL, scheduler))
    assert not out["correct"]


def _altered_groups(orig, X, **k):
    res = orig(X, **k)
    labels = np.array(res["labels"])
    labels[:7] = (labels[:7] + 1) % res["k"]
    return {**res, "labels": labels}


def _half_groups(orig, X, **k):
    return orig(X[: len(X) // 2], **k)


@pytest.mark.parametrize("fault", [_altered_groups, _half_groups])
def test_regroup_faults_fail(monkeypatch, fault):
    from repro.core import clustering
    _wrap(monkeypatch, clustering, "choose_k", fault)
    out = run(small(REGROUP_CELL))
    assert not out["correct"]


def test_no_chip_means_no_result(capsys):
    assert bench_run.main(["--workload", REGROUP_CELL, "--seed", "1",
                           "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""
