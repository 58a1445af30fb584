"""Device time of the trunk's expert layers (router, sort, grouped matmuls,
combine: the program's ``pique/experts`` scope), per trunk run; the trunk's
runs are executions of the conditional branch holding ``pique/trunk``
(``bench/trunk_scopes.py``)."""

from bench import trunk_scopes


def read(run):
    return trunk_scopes.per_trunk_run_ms(run, "experts")
