"""Share of the chip's roofline that the fused Lloyd kernel reaches: the
least time the traced calls' Lloyd steps need at the chip's peaks (from
n, k and f alone) over the kernel's device time in the trace.  Nothing is
read where the kernel's event count is not the calls' Lloyd steps."""
from bench import roofline, trace

KERNEL = "kmeans_lloyd_step"   # the Pallas call's operation


def read(run):
    if run.trace is None:
        return None
    seconds, cnt = trace.op_time(run.trace, KERNEL)
    t = run.traffic
    n, f = run.cfg["profiles"]["n"], run.cfg["profiles"]["features"]
    calls = sum(1 for c in run.calls if "k" in c)
    steps = {k: t["restarts"] * roofline.LLOYD_STEPS
             for k in range(2, t["k_max"] + 1)}
    if not cnt or cnt != calls * sum(steps.values()):
        return None
    flops = calls * sum(s * roofline.lloyd_step_flops(n, k, f)
                        for k, s in steps.items())
    nbytes = calls * sum(s * roofline.lloyd_step_bytes(n, k, f)
                         for k, s in steps.items())
    pct, bound = roofline.share(flops, nbytes, seconds, run.peaks)
    run.notes["kmeans_lloyd_roofline_bound"] = bound
    return pct
