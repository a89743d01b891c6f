"""Label vectors computed per placement: the misses of the scheduler's
label memo (``timings["label_computes"]`` of ``Engine.run``'s record)
over the placements, summed over the window's runs."""


def read(run):
    recs = [(c.get("timings") or {}).get("engine") or {} for c in run.calls]
    recs = [r for r in recs if "label_computes" in r and "placements" in r]
    placed = sum(r["placements"] for r in recs)
    return sum(r["label_computes"] for r in recs) / placed if placed else None
