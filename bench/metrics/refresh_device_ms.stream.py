"""Device-busy time of one refresh (the derived-state recomputation every
admit, retire and ring drain runs): the ops under ``pique/refresh`` over the
refresh program's executions in the window, both read from the trace by
``bench/scopes.py``."""

from bench import scopes


def read(run):
    sc = scopes.from_run(run)
    if sc is None:
        return None
    calls = sc.program_runs("refresh")
    busy = sc.scope_busy_s("refresh")
    return busy * 1e3 / calls if calls and busy > 0 else None
