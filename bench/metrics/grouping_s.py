"""The window's time over the number of completed ``choose_k`` calls."""


def read(run):
    done = [c for c in run.calls if "k" in c]
    return run.elapsed_s / len(done) if done else None
