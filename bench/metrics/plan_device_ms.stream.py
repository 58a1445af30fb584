"""Device-busy time of plan selection and the merge, per epoch: the ops
under ``pique/topk`` (the per-slot top-k) and ``pique/merge`` (the dedup
merge and quarantine filter), as ``bench/scopes.py`` puts ops down to
scopes."""

from bench import scopes


def read(run):
    return scopes.per_epoch_ms(run, "topk", "merge")
