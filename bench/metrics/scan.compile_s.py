"""Mean seconds per forecast that JAX spent tracing, lowering and
compiling, from its own duration events during the call."""


def read(run):
    v = [c["compile_s"] for c in run.calls if "timings" in c]
    return sum(v) / len(v) if v else None
