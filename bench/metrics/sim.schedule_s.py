"""Mean seconds per run in the simulator's scheduling passes (queue order
and placement, ``timings["schedule_s"]`` of ``Engine.run``'s record)."""


def read(run):
    v = [c["timings"]["engine"]["schedule_s"] for c in run.calls
         if "schedule_s" in ((c.get("timings") or {}).get("engine") or {})]
    return sum(v) / len(v) if v else None
