"""Spans of ``repro.tracing``: what one call's record holds, and that the
spans lie in the profiler's trace on the caller's thread."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest

from repro import tracing


def test_nested_spans_record_parent_duration_and_counts():
    rec = tracing.Record()
    with rec.span("entry.outer"):
        with rec.span("entry.inner", count="inners"):
            time.sleep(0.002)
        with pytest.raises(RuntimeError):
            with rec.span("entry.inner", count="inners"):
                raise RuntimeError
        with rec.span("entry.other"):
            pass
    with rec.span("entry.outer"):
        pass
    out = rec.as_dict()
    assert [(name, parent) for name, _, _, parent in out["spans"]] == [
        ("entry.outer", None), ("entry.inner", 0), ("entry.inner", 0),
        ("entry.other", 0), ("entry.outer", None)]
    seconds = {}
    for name, start, end, parent in out["spans"]:
        assert start <= end
        if parent is not None:
            _, p_start, p_end, _ = out["spans"][parent]
            assert p_start <= start and end <= p_end
        key = name.split(".")[1] + "_s"
        seconds[key] = seconds.get(key, 0.0) + end - start
    assert out["inner_s"] >= 0.002
    for key, value in seconds.items():
        assert out[key] == pytest.approx(value)
    assert out["inners"] == 2 and "outers" not in out


def test_counts_add_an_amount():
    rec = tracing.Record()
    rec.count("steps", 306)
    rec.count("steps", 0)
    with rec.span("entry.phase", count="phases"):
        rec.count("steps", 5)
    out = rec.as_dict()
    assert out["steps"] == 311 and out["phases"] == 1


def test_spans_lie_in_the_profiler_trace_on_the_callers_thread(tmp_path):
    from jax.profiler import ProfileData

    rec = tracing.Record()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("caller"):
            with rec.span("entry.phase"):
                jnp.arange(8.0).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events]
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    mine = [evs for evs in lines if any(n == "caller" for n, _, _ in evs)]
    assert len(mine) == 1
    (_, c0, c1), = [e for e in mine[0] if e[0] == "caller"]
    (_, s0, s1), = [e for e in mine[0] if e[0] == "entry.phase"]
    assert c0 <= s0 <= s1 <= c1


def _tarema_engine(sched_name="tarema"):
    from repro.core.monitor import TraceDB
    from repro.core.scheduler import make_scheduler
    from repro.workflow.cluster import cluster_555
    from repro.workflow.engine import Engine, EngineConfig
    from repro.workflow.nfcore import WORKFLOWS

    specs = cluster_555()
    eng = Engine(specs, make_scheduler(sched_name, specs, seed=0), TraceDB(),
                 EngineConfig(seed=0))
    for i, name in enumerate(("viralrecon", "eager")):
        eng.submit(WORKFLOWS[name](), run_id=0, seed=i, prefix=name)
    return eng


def test_engine_run_record_splits_the_loop_and_counts_its_work():
    eng = _tarema_engine()
    t0 = time.perf_counter()
    res = eng.run()
    wall = time.perf_counter() - t0
    t = res["timings"]
    assert t is eng.timings
    assert [s[0] for s in t["spans"]] == ["engine.run"]
    assert 0 < t["run_s"] <= wall
    assert min(t["schedule_s"], t["monitor_s"], t["event_s"]) > 0
    assert t["schedule_s"] + t["monitor_s"] + t["event_s"] \
        == pytest.approx(t["run_s"])
    done = [a for a in eng.assignment_log if a.completed]
    assert t["placements"] == len(done) == len(eng.all_tasks)
    assert t["events"] >= len(done)
    assert 0 < t["score_dispatches"] <= t["label_computes"] <= t["placements"]


def test_engine_run_records_are_per_run():
    """A paused run and its resumption each report their own work; a
    scheduler that counts nothing adds no counts."""
    eng = _tarema_engine("fair")
    first = eng.run(until=300.0)
    assert first["paused"]
    second = eng.run()
    assert not second["paused"] and second["timings"] is eng.timings
    assert first["timings"]["placements"] > 0
    assert first["timings"]["placements"] + second["timings"]["placements"] \
        == len(eng.all_tasks)
    assert "label_computes" not in second["timings"]


def test_phase_one_record_nests_the_grouping():
    from repro.core.scheduler import make_scheduler
    from repro.workflow.cluster import cluster_555

    sched = make_scheduler("tarema", cluster_555(), seed=0)
    t = sched.timings
    assert [s[0] for s in t["spans"]] == ["tarema.profile", "tarema.group",
                                          "tarema.info"]
    assert min(t["profile_s"], t["info_s"]) > 0
    assert t["group_s"] >= t["grouping"]["kmeans_s"] > 0
    assert t["grouping"]["kmeans_runs"] == 5 * 4     # k 2..6, 4 restarts
    assert sched.counts == {"label_computes": 0, "score_dispatches": 0}
