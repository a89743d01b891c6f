"""Mean seconds per call of phase 1's grouping: the ``tarema.group`` span
of the scheduler's record (``timings["group_s"]``), ``choose_k`` on the
default device."""


def read(run):
    v = [c["timings"]["scheduler"]["group_s"] for c in run.calls
         if "group_s" in ((c.get("timings") or {}).get("scheduler") or {})]
    return sum(v) / len(v) if v else None
