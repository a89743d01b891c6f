"""Iterations of the scan's placement loop per step
(``timings["place_iters"]`` over ``timings["n_steps"]``), mean over the
window's forecasts."""


def read(run):
    v = [c["timings"]["place_iters"] / c["timings"]["n_steps"]
         for c in run.calls if "place_iters" in c.get("timings", {})]
    return sum(v) / len(v) if v else None
