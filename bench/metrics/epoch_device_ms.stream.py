"""Device-busy time inside the traced window's chunk dispatches
(``EngineSession.run``, the fused superstep), per epoch."""


def read(run):
    r = run.reduced
    epochs = sum(c[2] for c in run.window["chunks"])
    if r is None or not epochs:
        return None
    busy = r.busy_within("run")
    return busy * 1e3 / epochs if busy > 0 else None
