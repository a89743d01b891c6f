"""Mean host time per forecast to build the topology and the replicas'
work draws (``timings["build_s"]`` of ``run_ensemble``)."""


def read(run):
    v = [c["timings"]["build_s"] for c in run.calls if "timings" in c]
    return sum(v) / len(v) if v else None
