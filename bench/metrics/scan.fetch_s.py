"""Mean host time per forecast to copy the scan's results to the host and
assemble them (``run_ensemble``'s ``ensemble.fetch`` span,
``timings["fetch_s"]``)."""


def read(run):
    v = [c["timings"]["fetch_s"] for c in run.calls
         if "fetch_s" in c.get("timings", {})]
    return sum(v) / len(v) if v else None
