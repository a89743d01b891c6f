"""Mean host time per forecast of the scan program's run, to
``block_until_ready`` (``run_ensemble``'s ``ensemble.run`` span,
``timings["run_s"]``)."""


def read(run):
    v = [c["timings"]["run_s"] for c in run.calls
         if "run_s" in c.get("timings", {})]
    return sum(v) / len(v) if v else None
