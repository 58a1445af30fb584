"""The one traffic generator: a traffic file's parameters -> a schedule.

Queries arrive open loop: each is due at a fixed time, whatever the session
does.  Every seed gets the same set of inter-arrival gaps, arities and
lifetimes (the quantiles of their distributions), in an order drawn from the
seed, so seeds change which work comes when, not how much work there is.

* arrivals: Poisson at ``rate_per_s`` (exponential gaps);
* shape: a conjunction of k predicates, k uniform in [arity_min, arity_max],
  drawn without replacement under Zipf(``zipf_s``) popularity;
* lifetime: exponential with mean ``lifetime_mean_s``, counted from the
  query's first refined answer;
* stream (optional): ``initial_rows`` landed before the window, then
  ``batch_rows``-row batches at ``rows_per_s``.
"""

from __future__ import annotations

import numpy as np


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def schedule(traffic: dict, num_predicates: int, seconds: float, rng) -> dict:
    q = traffic["queries"]
    n = max(1, int(round(q["rate_per_s"] * seconds)))
    gaps = -np.log1p(-_quantiles(n)) / q["rate_per_s"]
    due = np.cumsum(rng.permutation(gaps))
    arity = np.resize(np.arange(q["arity_min"], q["arity_max"] + 1), n)
    arity = rng.permutation(arity)
    lifetime = rng.permutation(-np.log1p(-_quantiles(n)) * q["lifetime_mean_s"])
    pop = 1.0 / np.arange(1, num_predicates + 1) ** q["zipf_s"]
    pop = pop / pop.sum()
    queries = [
        dict(due=float(t), cols=tuple(sorted(int(c) for c in rng.choice(
            num_predicates, size=int(k), replace=False, p=pop))),
            lifetime=float(life))
        for t, k, life in zip(due, arity, lifetime)
        if t < seconds
    ]
    out = dict(queries=queries, batches=[], batch_rows=0)
    st = traffic.get("stream")
    if st:
        gap = st["batch_rows"] / st["rows_per_s"]
        out["batches"] = [float(i * gap) for i in range(int(np.ceil(seconds / gap)))]
        out["batch_rows"] = int(st["batch_rows"])
    return out
