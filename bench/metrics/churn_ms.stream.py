"""Mean host time of one tenant admit or retire, run to completion
(``EngineSession.admit`` / ``retire``: mask update plus a full refresh)."""


def read(run):
    d = [e - s for n, s, e in run.spans if n in ("admit", "retire")]
    return sum(d) * 1e3 / len(d) if d else None
