"""Session over pre-materialised tagging outputs (the paper's own method:
every function's output exists in advance and is charged at its cost when
the planner buys it), fed by a stream of arriving rows.

The corpus and the offline tables are made by the benchmark in one jitted
call from the seed (``bench/offline.py``); the session under test is opened
through the public ``EngineSession`` API.
"""

from __future__ import annotations

import numpy as np

from bench import offline


def build(cfg: dict, traffic: dict, key_seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import EngineSession, Predicate

    cap = cfg["capacity"]
    ntr = cfg["train_rows"]
    p, f = cfg["predicates"], cfg["functions"]
    aucs = np.broadcast_to(np.asarray(cfg["aucs"], np.float32), (p, f))
    costs = np.broadcast_to(np.asarray(cfg["costs"], np.float32), (p, f))
    store = jnp.dtype(cfg["substrate_dtype"])

    @jax.jit
    def make(key, table_key):
        corpus, _ = offline.calibrated_outputs(key, cap, aucs, cfg["selectivity"])
        train, truth = offline.calibrated_outputs(table_key, ntr, aucs, cfg["selectivity"])
        tab = offline.tables(train, truth, cfg["combine_steps"], cfg["table_bins"])
        return corpus.astype(store), tab

    # the tables are the deployment's offline artifacts, fixed by the
    # configuration; the corpus comes from the run's seed
    corpus, tab = make(jax.random.PRNGKey(key_seed), jax.random.PRNGKey(cfg["table_seed"]))
    combine, table, costs_j, engine = offline.session_inputs(tab, costs, cfg)
    preds = [Predicate(i, 1) for i in range(p)]
    session = EngineSession(
        [q.positive() for q in preds], table, combine, costs_j,
        capacity=cap, max_tenants=cfg["max_tenants"], config=engine,
    )
    n0 = traffic["stream"]["initial_rows"] if traffic.get("stream") else cap
    state = session.init_state(corpus[:n0])
    host = np.asarray(jax.device_get(corpus))
    return dict(
        session=session,
        state=state,
        predicates=preds,
        initial_rows=n0,
        state_capacity=cap,
        store_bytes=store.itemsize,
        stream_rows=host[n0:],
        corpus=host,
        reference=dict(
            bank="gather",
            tables={k: np.asarray(v) for k, v in jax.device_get(tab).items()},
            costs=np.asarray(costs, np.float32),
        ),
    )
