"""Window time over the epochs completed in it: how often every active
tenant's answer improves (host clock)."""


def read(run):
    chunks = [c for c in run.window["chunks"] if c[3]]
    epochs = sum(c[2] for c in chunks)
    if not epochs:
        return None
    return (chunks[-1][1] - run.window["t0"]) * 1e3 / epochs
