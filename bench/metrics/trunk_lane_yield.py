"""The share of the trunk's lanes that carry a bought triple: model-level
triples bought in the window (from the executed bits) over the merged lanes
the trunk forwards, ``merged_capacity`` on every epoch it runs.  The trunk's
runs are the executions of the conditional branch holding the program's
``pique/trunk`` scope, counted in the trace by ``bench/scopes.py``."""

from bench import scopes


def read(run):
    sc = scopes.from_run(run)
    if sc is None or not run.model_triples:
        return None
    runs = sc.branch_runs("trunk")
    cfg = run.bundle["session"].config
    lanes = cfg.merged_capacity or run.bundle["session"].max_tenants * cfg.plan_size
    return 100.0 * run.model_triples / (lanes * runs) if runs else None
