"""Mean compiles per forecast: the count of ``run_ensemble``'s
``ensemble.compile`` span (``timings["compiles"]``)."""


def read(run):
    v = [c["timings"]["compiles"] for c in run.calls
         if "compiles" in c.get("timings", {})]
    return sum(v) / len(v) if v else None
