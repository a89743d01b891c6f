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
