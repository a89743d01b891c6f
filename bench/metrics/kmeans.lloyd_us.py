"""Device time of the fused Lloyd kernel per Lloyd step: the kernel's
events in the trace, summed, over their number."""
from bench import trace

KERNEL = "kmeans_lloyd_step"   # the Pallas call's operation


def read(run):
    if run.trace is None:
        return None
    seconds, cnt = trace.op_time(run.trace, KERNEL)
    return 1e6 * seconds / cnt if cnt else None
