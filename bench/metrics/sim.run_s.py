"""Mean seconds per call of the simulator's event loop: the ``engine.run``
span of ``Engine.run``'s record (``timings["run_s"]``), phases 2 and 3."""


def read(run):
    v = [c["timings"]["engine"]["run_s"] for c in run.calls
         if "run_s" in ((c.get("timings") or {}).get("engine") or {})]
    return sum(v) / len(v) if v else None
