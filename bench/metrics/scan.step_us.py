"""Device time of the scan program per scan step: the program's device
time in the trace over its runs times the steps of one run."""

PROGRAM = "jit_scan"


def read(run):
    if run.trace is None:
        return None
    mods = [v for n, v in run.trace["modules"].items() if n.startswith(PROGRAM)]
    steps = [c["timings"]["n_steps"] for c in run.calls if "timings" in c]
    if not mods or not steps:
        return None
    seconds = sum(s for s, _ in mods)
    runs = sum(r for _, r in mods)
    return 1e6 * seconds / runs / steps[0]
