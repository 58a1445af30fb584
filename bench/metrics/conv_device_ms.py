"""Device time of the trunk's gated short convolutions (the program's
``pique/conv`` scope), per trunk run (``bench/trunk_scopes.py``)."""

from bench import trunk_scopes


def read(run):
    return trunk_scopes.per_trunk_run_ms(run, "conv")
