"""Replicas of completed forecasts over all the time of the window."""


def read(run):
    done = [c["replicas"] for c in run.calls if "replicas" in c]
    return sum(done) / run.elapsed_s if done else None
