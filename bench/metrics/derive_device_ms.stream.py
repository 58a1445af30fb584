"""Device-busy time past the bank, per epoch: the ops under ``pique/apply``
(charging, the substrate write, ledger attribution), ``pique/derive``
(recombination and the per-slot joint) and ``pique/select`` (answer
selection), as ``bench/scopes.py`` puts ops down to scopes."""

from bench import scopes


def read(run):
    return scopes.per_epoch_ms(run, "apply", "derive", "select")
