"""Tarema end to end on the host simulator: its cell resolves with the
metrics it reports, the program (``make_scheduler`` and ``Engine.run``)
matches the plain reference exactly on the paper's cluster and on a cut of
the fleet's tiers, the group renaming of the check decides placements
where groups tie on power, the check fails on a fault planted in every
call, and the cell's readers average over the window's runs and read
nothing from a program without its spans."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import compare
from bench import run as bench_run
from bench.loops import simulate
from bench.reference import tarema as ref_tarema

CELL = "fleet-3tier.simulate-tarema-2k"
PAPER_CELL = "paper-5-4-4-2.forecast-sjfn-r256"
FLEET_CELL = "fleet-3tier.forecast-fair-r64"
SEED = 2**31 + 23
# seeds of runs: in both deployments two groups tie on power at the first
# (powers 5, 8, 5 on the paper's cluster; 7, 4, 7 on the fleet's cut), and
# no two do at the others
RUN_SEEDS = {"paper": (4743016679282810881, 8520188947784614408,
                       356805172452232606),
             "fleet32": (8520188947784614408, 4743016679282810881,
                         356805172452232606)}
NEW_METRICS = ("sim.group_s", "sim.run_s", "sim.schedule_s",
               "sim.label_computes_per_placement",
               "device.idle_share.simulate")


def _loop(deployment):
    """The cell's loop over the paper's cluster and its five workflows, or
    over 32 nodes of the fleet's tiers and the cell's 35 submissions."""
    c = bench_run.load_cell(CELL)
    if deployment == "paper":
        c.cfg = bench_run.load_cell(PAPER_CELL).cfg
    else:
        c.cfg["cluster"]["nodes"] = 32
    drv = simulate.Loop(c.cfg, c.traffic, SEED)
    drv.setup()
    return drv


@pytest.fixture(scope="module")
def loops():
    return {d: _loop(d) for d in RUN_SEEDS}


def _reference(drv, seed, prog_labels, dtype="float64"):
    return simulate._reference_job((drv.nodes, drv.wfs, drv.subs, seed,
                                    prog_labels, drv.traffic["k_max"],
                                    drv.traffic["restarts"], dtype))


def test_cell_resolves_with_its_metrics():
    c = bench_run.load_cell(CELL)
    assert c.chips == 1 and c.cfg["name"] == "fleet-3tier-partition"
    assert c.traffic["entry"] == "simulate"
    assert c.traffic["scheduler"] == "tarema" and c.traffic["replicas"] == 1
    assert c.limits["decision_mismatches"] == 0
    assert c.limits["k_mismatches"] == 0
    assert c.limits["label_mismatch_share"] == 0
    assert {m["name"] for m in c.end_to_end} == {"replicas_per_s", "setup_s"}
    assert {m["name"] for m in c.per_layer} == set(NEW_METRICS)
    for m in c.per_layer:
        assert m["moves"] == "replicas_per_s"
        assert callable(bench_run.metric_reader(m["name"]))


@pytest.mark.parametrize("key", ["cluster", "workflows", "submissions"])
def test_partition_is_the_fleet_cells_partition_and_backlog(key):
    """The cell's deployment runs the forecast cells' 256 nodes, workflows
    and 35 submissions; it differs in grouping the partition itself."""
    mine = bench_run.load_cell(CELL).cfg
    assert mine[key] == bench_run.load_cell(FLEET_CELL).cfg[key]
    assert "profiles" not in mine


@pytest.mark.parametrize("deployment,seed",
                         [(d, s) for d, seeds in RUN_SEEDS.items()
                          for s in seeds])
def test_program_matches_the_reference_exactly(loops, deployment, seed):
    drv = loops[deployment]
    prog = drv._run(seed)
    ref = _reference(drv, seed, prog["labels"])
    assert len(prog["node_idx"]) == (304 if deployment == "paper" else 2128)
    assert compare.grouping_numbers([(prog, ref)]) == {
        "k_mismatches": 0, "label_mismatch_share": 0.0}
    for key in ("node_idx", "finish_order", "start_t", "end_t", "makespan"):
        np.testing.assert_array_equal(prog[key], ref[key], err_msg=key)
    eng = prog["timings"]["engine"]
    assert eng["placements"] == len(prog["node_idx"])
    assert 0 < eng["label_computes"] <= eng["placements"]


@pytest.mark.parametrize("deployment", RUN_SEEDS)
def test_group_numbering_decides_placements_under_a_power_tie(loops,
                                                               deployment):
    """The reference's groups with the ids of the two groups of equal power
    swapped are renamed back to the program's; left swapped, their ties
    on score are broken the other way and placements move."""
    drv = loops[deployment]
    seed = RUN_SEEDS[deployment][0]
    prog = drv._run(seed)
    labels = np.asarray(prog["labels"], np.int64)
    k = int(labels.max()) + 1
    info = ref_tarema.group_info(
        simulate.gen_tarema.profiles(drv.nodes, seed), labels,
        np.array([n["cores"] for n in drv.nodes]),
        np.array([n["mem_gb"] for n in drv.nodes]))
    tied = [g for g in range(k)
            if list(info["power"]).count(info["power"][g]) > 1]
    assert len(tied) == 2
    perm = np.arange(k)
    perm[tied] = perm[tied[::-1]]
    renumbered = perm[labels]
    np.testing.assert_array_equal(simulate.rename(renumbered, labels), labels)
    insts, peak = [], []
    for s in drv.subs:
        wf = drv.wfs[s["workflow"]]
        insts += simulate.gen.instantiate(wf, s["run_id"], seed, s["prefix"])
        peak += simulate.gen_tarema.peak_mem(wf, s["run_id"], seed)
    X = simulate.gen_tarema.profiles(drv.nodes, seed)
    same = ref_tarema.simulate(drv.nodes, insts, peak, X, labels, seed)
    swapped = ref_tarema.simulate(drv.nodes, insts, peak, X, renumbered, seed)
    assert compare.forecast_numbers([(prog, same)])["decision_mismatches"] == 0
    assert compare.forecast_numbers(
        [(prog, swapped)])["decision_mismatches"] == 1


def small():
    """The cell cut to what a test run holds: 12 nodes, two submissions."""
    c = bench_run.load_cell(CELL)
    c.cfg["cluster"]["nodes"] = 12
    c.cfg["submissions"] = c.cfg["submissions"][:2]
    c.traffic["check_calls"] = 1
    return c


def test_sound_run_is_correct():
    out = bench_run.run_cell(small(), SEED, 0.2, False, require_chip=False)
    assert out["correct"], out["checked"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["checked"]["time_rel_err"]["value"] == 0.0
    assert set(out["metrics"]) == {"replicas_per_s", "setup_s"}


def test_control_fails():
    c = small()
    drv = simulate.Loop(c.cfg, c.traffic, SEED)
    drv.setup()
    drv.call(0)
    ok, _ = compare.verdict(drv.check(control=True), c.limits)
    assert not ok


def _moved_task(orig, eng, *a, **k):
    res = orig(eng, *a, **k)
    t = list(eng.all_tasks.values())[-1]
    t.node = next(n for n in eng.nodes if n != t.node)
    return res


def _halved_makespan(orig, eng, *a, **k):
    res = orig(eng, *a, **k)
    return {**res, "makespan": res["makespan"] / 2}


@pytest.mark.parametrize("fault", [_moved_task, _halved_makespan])
def test_faults_in_every_call_fail(monkeypatch, fault):
    from repro.workflow.engine import Engine
    orig = Engine.run
    monkeypatch.setattr(Engine, "run",
                        lambda eng, *a, **k: fault(orig, eng, *a, **k))
    out = bench_run.run_cell(small(), SEED, 0.2, False, require_chip=False)
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert not out["correct"]


def _call(group_s, run_s, schedule_s, labels, placements):
    return {"replicas": 1, "timings": {
        "scheduler": {"group_s": group_s, "profile_s": 0.001},
        "engine": {"run_s": run_s, "schedule_s": schedule_s,
                   "label_computes": labels, "placements": placements}}}


CALLS = [_call(0.04, 0.4, 0.15, 1700, 2128), _call(0.06, 0.3, 0.13, 1800, 2128)]
# a call of a program whose scheduler and engine keep no record
OLD_CALL = {"replicas": 1, "timings": {"scheduler": None, "engine": None}}


@pytest.mark.parametrize("name,mean", [
    ("sim.group_s", 0.05), ("sim.run_s", 0.35), ("sim.schedule_s", 0.14),
    ("sim.label_computes_per_placement", 3500 / 4256)])
def test_readers_average_over_runs(name, mean):
    read = bench_run.metric_reader(name)
    run = SimpleNamespace(calls=CALLS + [OLD_CALL, {"seed": 7}], trace=None)
    assert read(run) == pytest.approx(mean)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_without_their_record(name):
    read = bench_run.metric_reader(name)
    for calls in ([], [{"seed": 7}], [OLD_CALL]):
        assert read(SimpleNamespace(calls=calls, trace=None)) is None


def test_idle_share_reads_the_trace():
    read = bench_run.metric_reader("device.idle_share.simulate")
    run = SimpleNamespace(calls=CALLS, trace={"busy_s": 0.5, "window_s": 4.0})
    assert read(run) == pytest.approx(87.5)
