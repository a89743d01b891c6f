"""Share of the scan's steps on which sjfn's carried key panel was rebuilt
(``timings["key_rebuilds"]`` over ``timings["n_steps"]``), in percent,
mean over the window's forecasts."""


def read(run):
    v = [100.0 * c["timings"]["key_rebuilds"] / c["timings"]["n_steps"]
         for c in run.calls if "key_rebuilds" in c.get("timings", {})]
    return sum(v) / len(v) if v else None
