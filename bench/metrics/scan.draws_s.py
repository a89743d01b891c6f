"""Mean host time per forecast to seed and draw the replicas' work
(``run_ensemble``'s ``ensemble.draws`` span within ``ensemble.build``,
``timings["draws_s"]``)."""


def read(run):
    v = [c["timings"]["draws_s"] for c in run.calls
         if "draws_s" in c.get("timings", {})]
    return sum(v) / len(v) if v else None
