"""Device-busy time of the superstep's scoring phases, per epoch: the ops
under the program's ``pique/score`` scope (Eq. 11 scoring, the per-lane
cost gather, valid masking) and ``pique/candidates`` (the candidate mask and
its restriction), as ``bench/scopes.py`` puts ops down to scopes."""

from bench import scopes


def read(run):
    return scopes.per_epoch_ms(run, "score", "candidates")
