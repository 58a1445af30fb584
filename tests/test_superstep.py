"""Fused epoch superstep + sharded planning: scan-vs-loop driver parity,
byte-identical sharded plan selection, hierarchical dedup exactness, triple-key
overflow guards, baseline plan rank scores, and plan costs looked up on the
kept lanes only."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    EngineSession,
    MultiQueryConfig,
    MultiQueryEngine,
    OperatorConfig,
    Predicate,
    ProgressiveQueryOperator,
    build_query_set,
    conjunction,
    fallback_decision_table,
)
from repro.core.combine import default_combine_params
from repro.core.plan import (
    Plan,
    canonicalize_plan,
    merge_plans_dedup,
    merge_plans_dedup_sharded,
    merge_sharded_plans_exact,
    select_plan,
    static_plan_from_order,
)
from repro.data.synthetic import make_corpus
from repro.enrich.simulated import SimulatedBank

P_GLOBAL, F, N = 4, 4, 160


def _world(seed=0):
    preds = [Predicate(i, 1) for i in range(P_GLOBAL)]
    corpus = make_corpus(
        jax.random.PRNGKey(seed), N, [p.tag_type for p in preds],
        [p.tag for p in preds], selectivity=[0.3, 0.4, 0.25, 0.35],
    )
    bank = SimulatedBank(outputs=corpus.func_probs, costs=corpus.costs)
    combine = default_combine_params(corpus.aucs)
    table = fallback_decision_table(P_GLOBAL, F, corpus.aucs)
    return preds, corpus, bank, combine, table


def _engine(queries, preds, bank, combine, table, **cfg_kw):
    qset = build_query_set(queries, global_predicates=[p.positive() for p in preds])
    cfg = MultiQueryConfig(**{"plan_size": 32, **cfg_kw})
    return MultiQueryEngine(qset, table, combine, bank.costs, bank, cfg)


def _queries(preds):
    return [
        conjunction(preds[0], preds[1]),
        conjunction(preds[1], preds[2]),
        conjunction(preds[0], preds[1]),  # duplicate tenant (hot query)
    ]


class OpaqueBank:
    """A traceable bank with its ``supports_scan`` flag hidden: ``run()``
    must route it to the per-epoch loop driver (the model-cascade posture)."""

    def __init__(self, inner):
        self.inner = inner
        self.costs = inner.costs

    def execute(self, plan):
        return self.inner.execute(plan)


def _assert_plans_identical(a: Plan, b: Plan, msg=""):
    ca, cb = canonicalize_plan(a), canonicalize_plan(b)
    for field in Plan._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ca, field)), np.asarray(getattr(cb, field)),
            err_msg=f"{msg}.{field}",
        )


# ------------------------------------------------------ scan driver parity --


def test_scan_driver_matches_loop_driver():
    preds, corpus, bank, combine, table = _world()
    eng_l = _engine(_queries(preds), preds, OpaqueBank(bank), combine, table)
    eng = _engine(_queries(preds), preds, bank, combine, table)
    state_l, hist_l = eng_l.run(N, 6)  # opaque bank -> loop driver
    state_s, hist_s = eng.run_scan(N, 6, collect_masks=True)
    assert len(hist_l) == len(hist_s)
    for a, b in zip(hist_l, hist_s):
        # float aggregates to 1 ulp (fusion may reassociate reductions);
        # everything discrete — answer sets, plan sizes — must be EXACT
        assert a.cost_spent == pytest.approx(b.cost_spent, rel=1e-6)
        assert a.epoch_cost == pytest.approx(b.epoch_cost, rel=1e-6, abs=1e-4)
        assert a.requested_cost == pytest.approx(b.requested_cost, rel=1e-6)
        assert a.expected_f == pytest.approx(b.expected_f, rel=1e-6)
        assert a.answer_size == b.answer_size
        assert a.plan_valid == b.plan_valid
        assert a.merged_valid == b.merged_valid
    np.testing.assert_array_equal(
        np.asarray(state_l.per_query.in_answer),
        np.asarray(state_s.per_query.in_answer),
    )
    # per-epoch answer sets equal the loop driver's (collected via run_epoch)
    st = eng.init_state(N)
    for h in hist_s:
        st, sel, *_ = eng.run_epoch(st)
        np.testing.assert_array_equal(np.asarray(sel.mask), h.answer_mask)


def test_scan_driver_trims_after_exhaustion():
    """Fixed-length scan: post-exhaustion epochs are free no-ops, trimmed to
    match the loop driver's early break."""
    preds, corpus, bank, combine, table = _world()
    eng = _engine([conjunction(preds[0])], preds, bank, combine, table,
                  plan_size=256, candidate_strategy="all")
    state, hist = eng.run_scan(N, 40)
    state2, hist2 = _engine(
        [conjunction(preds[0])], preds, OpaqueBank(bank), combine, table,
        plan_size=256, candidate_strategy="all",
    ).run(N, 40)
    assert len(hist) == len(hist2) < 40
    assert hist[-1].merged_valid == 0
    assert hist[-1].cost_spent == pytest.approx(hist2[-1].cost_spent, rel=1e-6)


def test_run_auto_routes_by_bank():
    preds, corpus, bank, combine, table = _world()
    eng_scan = _engine(_queries(preds), preds, bank, combine, table)
    assert getattr(eng_scan.bank, "supports_scan", False)
    eng_loop = _engine(_queries(preds), preds, OpaqueBank(bank), combine, table)
    s1, h1 = eng_scan.run(N, 3)  # auto -> scan
    s2, h2 = eng_loop.run(N, 3)  # auto -> loop
    assert [h.cost_spent for h in h1] == [h.cost_spent for h in h2]
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError):
            eng_scan.run(N, 2, driver="bogus")


def test_run_driver_kwarg_is_a_deprecated_shim():
    """The old explicit driver routing survives as a warning shim with
    unchanged results; the repo itself no longer calls it (tier-1 runs with
    -W error::DeprecationWarning in CI)."""
    preds, corpus, bank, combine, table = _world()
    eng = _engine(_queries(preds), preds, bank, combine, table)
    base, hist = eng.run(N, 3)
    for forced in ("auto", "scan", "loop"):
        e2 = _engine(_queries(preds), preds, bank, combine, table)
        with pytest.warns(DeprecationWarning, match="driver=.*deprecated"):
            s2, h2 = e2.run(N, 3, driver=forced)
        assert [h.cost_spent for h in h2] == [h.cost_spent for h in hist]
        np.testing.assert_array_equal(
            np.asarray(base.per_query.in_answer),
            np.asarray(s2.per_query.in_answer),
        )


def test_single_query_scan_matches_loop():
    preds, corpus, bank, combine, table = _world()
    query = conjunction(preds[0], preds[1])
    truth = jnp.asarray(np.asarray(corpus.truth_pred[:, 0] & corpus.truth_pred[:, 1]))
    op = ProgressiveQueryOperator(
        query, table.subset([0, 1]), default_combine_params(corpus.aucs[:2]),
        corpus.costs[:2], SimulatedBank(outputs=bank.outputs[:, :2], costs=bank.costs[:2]),
        OperatorConfig(plan_size=32), truth_mask=truth,
    )
    op_l = ProgressiveQueryOperator(
        query, table.subset([0, 1]), default_combine_params(corpus.aucs[:2]),
        corpus.costs[:2],
        OpaqueBank(SimulatedBank(outputs=bank.outputs[:, :2], costs=bank.costs[:2])),
        OperatorConfig(plan_size=32), truth_mask=truth,
    )
    state_l, hist_l = op_l.run(N, 5)  # opaque bank -> loop driver
    state_s, hist_s = op.run(N, 5)  # traceable bank -> fused scan
    assert len(hist_l) == len(hist_s)
    for a, b in zip(hist_l, hist_s):
        # float aggregates may differ by one float32 ulp: the scan fuses the
        # whole epoch into one program, so XLA may reassociate reductions
        assert a.cost_spent == pytest.approx(b.cost_spent, rel=1e-6)
        assert a.expected_f == pytest.approx(b.expected_f, rel=1e-6)
        assert a.answer_size == b.answer_size
        assert a.plan_valid == b.plan_valid
        assert a.true_f1 == pytest.approx(b.true_f1, abs=1e-6)
    np.testing.assert_array_equal(
        np.asarray(state_l.in_answer), np.asarray(state_s.in_answer)
    )


def test_unique_query_dedup_bitwise_identical():
    """Duplicate tenants' selections come from the same U-group computation:
    identical rows, and identical to an engine seeing only distinct queries."""
    preds, corpus, bank, combine, table = _world()
    eng = _engine(_queries(preds), preds, bank, combine, table)
    assert eng.query_set.num_unique == 2
    state, hist = eng.run(N, 4)
    per = state.per_query.in_answer
    np.testing.assert_array_equal(np.asarray(per[0]), np.asarray(per[2]))
    eng2 = _engine(_queries(preds)[:2], preds, bank, combine, table)
    state2, _ = eng2.run(N, 4)
    np.testing.assert_array_equal(
        np.asarray(per[:2]), np.asarray(state2.per_query.in_answer)
    )


@pytest.mark.parametrize("function_selection", ["table", "best"])
def test_engine_pallas_backend_matches_jnp(function_selection):
    """The engine-level backend='pallas' wiring (not just the ops layer) must
    track the jnp backend through full scan-driver runs."""
    preds, corpus, bank, combine, table = _world()
    kw = dict(function_selection=function_selection)
    eng_j = _engine(_queries(preds), preds, bank, combine, table,
                    backend="jnp", **kw)
    eng_p = _engine(_queries(preds), preds, bank, combine, table,
                    backend="pallas", pallas_interpret=True, **kw)
    s_j, h_j = eng_j.run_scan(N, 3)
    s_p, h_p = eng_p.run_scan(N, 3)
    assert len(h_j) == len(h_p)
    for a, b in zip(h_j, h_p):
        # kernel LUT/one-hot gathers vs jnp gathers: equal to f32 tolerance
        assert a.cost_spent == pytest.approx(b.cost_spent, rel=1e-4)
        assert a.expected_f == pytest.approx(b.expected_f, rel=1e-3, abs=1e-3)
        assert a.merged_valid == b.merged_valid
    np.testing.assert_array_equal(
        np.asarray(s_j.per_query.in_answer), np.asarray(s_p.per_query.in_answer)
    )


# -------------------------------------------------------- sharded planning --


@pytest.mark.parametrize("function_selection", ["table", "best"])
def test_sharded_planning_byte_identical(function_selection):
    preds, corpus, bank, combine, table = _world()
    kw = dict(function_selection=function_selection)
    eng1 = _engine(_queries(preds), preds, bank, combine, table, **kw)
    eng2 = _engine(_queries(preds), preds, bank, combine, table,
                   num_shards=2, **kw)
    state = eng1.init_state(N)
    plans1, merged1 = jax.jit(eng1._plan_epoch)(state)
    plans2, merged2 = jax.jit(eng2._plan_epoch)(state)
    _assert_plans_identical(plans1, plans2, "plans")
    _assert_plans_identical(merged1, merged2, "merged")
    # and whole trajectories agree
    s1, h1 = eng1.run(N, 4)
    s2, h2 = eng2.run(N, 4)
    assert [h.cost_spent for h in h1] == [h.cost_spent for h in h2]
    np.testing.assert_array_equal(
        np.asarray(s1.per_query.in_answer), np.asarray(s2.per_query.in_answer)
    )


def test_sharded_planning_validates_divisibility():
    preds, corpus, bank, combine, table = _world()
    eng = _engine(_queries(preds), preds, bank, combine, table, num_shards=3)
    with pytest.raises(ValueError):
        eng.init_state(N)  # 160 % 3 != 0


def _random_plans(seed, *shape_k):
    rng = np.random.default_rng(seed)
    k = shape_k
    return Plan(
        object_idx=jnp.asarray(rng.integers(0, 40, size=k), jnp.int32),
        pred_idx=jnp.asarray(rng.integers(0, 3, size=k), jnp.int32),
        func_idx=jnp.asarray(rng.integers(0, 4, size=k), jnp.int32),
        benefit=jnp.asarray(rng.uniform(0, 5, size=k).astype(np.float32)),
        cost=jnp.asarray(rng.uniform(0.1, 1.0, size=k).astype(np.float32)),
        valid=jnp.asarray(rng.uniform(size=k) < 0.85),
    )


def test_merge_plans_dedup_sharded_matches_flat():
    """Hierarchical (per-shard lexsort + cross-shard unique) == one-shot dedup
    over the same entries, for any partition of entries across shards."""
    plans = _random_plans(3, 4, 6, 8)  # interpreted as [S=4, Q=6, K=8]
    flat = merge_plans_dedup(plans, num_predicates=3, num_functions=4,
                             num_objects=40)
    hier = merge_plans_dedup_sharded(plans, num_predicates=3, num_functions=4,
                                     num_objects=40)
    _assert_plans_identical(flat, hier, "dedup")
    # with a cost budget applied at the final pass
    flat_b = merge_plans_dedup(plans, 3, 4, cost_budget=3.0, num_objects=40)
    hier_b = merge_plans_dedup_sharded(plans, 3, 4, cost_budget=3.0,
                                       num_objects=40)
    _assert_plans_identical(flat_b, hier_b, "dedup_budget")


def test_merge_sharded_plans_exact_matches_select_plan():
    from repro.core.benefit import TripleBenefits

    n, p, shards, k = 128, 3, 4, 24
    rng = np.random.default_rng(5)
    ben = rng.uniform(0, 5, size=(n, p)).astype(np.float32)
    ben[rng.uniform(size=(n, p)) < 0.1] = -np.inf  # some exhausted lanes
    tb = TripleBenefits(
        benefit=jnp.asarray(ben),
        next_fn=jnp.asarray(
            np.where(np.isfinite(ben), rng.integers(0, 4, size=(n, p)), -1),
            jnp.int32,
        ),
        est_joint=jnp.asarray(rng.uniform(size=(n, p)).astype(np.float32)),
    )
    costs = jnp.asarray(rng.uniform(0.1, 1, size=(p, 4)).astype(np.float32))
    global_plan = select_plan(tb, plan_size=k, costs=costs)
    v = np.asarray(global_plan.valid)
    np.testing.assert_array_equal(
        np.asarray(global_plan.cost)[v],
        np.asarray(costs)[np.asarray(global_plan.pred_idx), np.asarray(global_plan.func_idx)][v],
    )
    per = n // shards
    locals_ = []
    for s in range(shards):
        sl = TripleBenefits(*(x[s * per:(s + 1) * per] for x in tb))
        lp = select_plan(sl, plan_size=k, costs=costs)
        locals_.append(lp._replace(object_idx=lp.object_idx + s * per))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *locals_)
    merged = merge_sharded_plans_exact(stacked, plan_size=k, num_predicates=p)
    _assert_plans_identical(global_plan, merged, "exact_reduce")


# ------------------------------------------------------------- plan guards --


def test_merge_plans_dedup_key_overflow_guard():
    plans = _random_plans(0, 2, 4)
    # N * P * F = 2**29 * 3 * 4 > 2**31 -> must raise, not wrap
    with pytest.raises(ValueError, match="overflows"):
        merge_plans_dedup(
            plans, num_predicates=3, num_functions=4, num_objects=2**29
        )
    # without num_objects (or under the bound) the int32 path still works
    ok = merge_plans_dedup(plans, num_predicates=3, num_functions=4,
                           num_objects=40)
    assert int(ok.num_valid()) > 0


def test_static_plan_benefit_is_descending_rank():
    m, plan_size = 20, 6
    order = jnp.arange(m, dtype=jnp.int32)
    preds = jnp.zeros((m,), jnp.int32)
    fns = jnp.zeros((m,), jnp.int32)
    costs = jnp.ones((1, 1), jnp.float32)
    windows = [
        static_plan_from_order(order, preds, fns, costs,
                               jnp.asarray(off, jnp.int32), plan_size)
        for off in (0, plan_size, 3 * plan_size)
    ]
    seen = []
    for w in windows:
        b = np.asarray(w.benefit)
        v = np.asarray(w.valid)
        assert np.all(np.diff(b[v]) < 0), "rank must strictly descend in-window"
        assert np.all(np.isfinite(b) == v), "invalid slots carry -inf"
        seen.extend(b[v].tolist())
    assert seen == sorted(seen, reverse=True), "rank descends across windows"
    # dedup keeps the EARLIER (higher-rank) copy of a duplicated triple
    dup = jax.tree.map(lambda *xs: jnp.stack(xs), windows[0], windows[0])
    merged = merge_plans_dedup(dup, num_predicates=1, num_functions=1,
                               num_objects=m)
    assert int(merged.num_valid()) == plan_size


# ------------------------------------------ plan costs looked up after top-k --


def _old_rule_select_plans_batched(benefits, plan_size, num_shards,
                                   num_predicates, costs):
    """Plan selection as it was when the scorers priced every lane: the
    [Q, N, P] cost ``max(costs[p, max(nf, 0)], 1e-9)`` built over all lanes,
    resharded beside the benefits and indexed at the top-k lanes."""
    q, n, p = benefits.benefit.shape
    pred_idx = jnp.arange(p, dtype=jnp.int32)[None, None, :]
    cost = jnp.maximum(costs[pred_idx, jnp.maximum(benefits.next_fn, 0)], 1e-9)
    leaves = (benefits.benefit, benefits.next_fn, cost)

    def sel(benefit, next_fn, cost):
        top_vals, top_idx = jax.lax.top_k(benefit.reshape(-1), plan_size)
        fn = next_fn.reshape(-1)[top_idx]
        return Plan(
            object_idx=(top_idx // p).astype(jnp.int32),
            pred_idx=(top_idx % p).astype(jnp.int32),
            func_idx=fn.astype(jnp.int32),
            benefit=top_vals,
            cost=cost.reshape(-1)[top_idx],
            valid=jnp.isfinite(top_vals) & (fn >= 0),
        )

    if num_shards <= 1:
        return jax.vmap(sel)(*leaves)
    s, per = num_shards, n // num_shards
    local = [x.reshape(q, s, per, p).transpose(1, 0, 2, 3) for x in leaves]
    plans = jax.vmap(jax.vmap(sel))(*local)
    plans = plans._replace(
        object_idx=plans.object_idx
        + (jnp.arange(s, dtype=jnp.int32) * per)[:, None, None]
    )
    return jax.vmap(functools.partial(
        merge_sharded_plans_exact, plan_size=plan_size,
        num_predicates=num_predicates,
    ))(jax.tree.map(lambda x: x.transpose(1, 0, 2), plans))


@pytest.mark.parametrize("budget", [None, 0.5])
@pytest.mark.parametrize("num_shards", [1, 2])
@pytest.mark.parametrize("function_selection", ["table", "best"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_plan_cost_lookup_matches_old_rule(
    monkeypatch, backend, function_selection, num_shards, budget
):
    """Plans that look their costs up on the K kept lanes are bitwise the
    plans indexed out of the scorers' old all-lane cost, and so are the
    superstep's spend, requested cost and ledger: with an exhausted
    predicate (every function quarantined, so nf = -1), a quarantined
    function, a cost under the 1e-9 floor and an inactive slot."""
    from repro.core import executor as executor_lib

    preds, corpus, _, combine, table = _world()
    costs = np.array(corpus.costs)
    costs[1, 3] = 1e-12  # floored to 1e-9 by both rules
    cfg = MultiQueryConfig(
        plan_size=16, backend=backend, pallas_interpret=True,
        function_selection=function_selection, num_shards=num_shards,
        epoch_cost_budget=budget,
    )

    def run():
        sess = EngineSession(
            [p.positive() for p in preds], table, combine, costs,
            capacity=N, max_tenants=4, config=cfg,
        )
        st = sess.init_state(corpus.func_probs)
        for q in (conjunction(preds[0], preds[1]),
                  conjunction(preds[1], preds[2], preds[3])):
            st, _ = sess.admit(st, q)
        st = sess.quarantine(st, 0, 1)
        for f in range(F):
            st = sess.quarantine(st, 3, f)
        prog = sess.program

        @jax.jit
        def superstep(st):  # EpochProgram._superstep, keeping its plans
            plans, merged, want_bits = prog._plan_part(st)
            outputs = prog._bank_part(st, merged, None)[0]
            st, stats = prog._apply_part(st, plans, merged, want_bits, outputs)
            return st, (plans, merged, want_bits, stats["requested_cost"],
                        stats["cost_spent"], st.ledger)

        epochs = []
        for _ in range(3):
            st, out = superstep(st)
            epochs.append(out)
        return epochs

    with monkeypatch.context() as m:
        m.setattr(executor_lib, "select_plans_batched",
                  _old_rule_select_plans_batched)
        old = run()
    new = run()

    plans = new[0][0]
    fn, prd = np.asarray(plans.func_idx), np.asarray(plans.pred_idx)
    v = np.asarray(plans.valid)
    assert (fn == -1).any(), "an exhausted lane among the kept lanes"
    assert (v & (prd == 1) & (fn == 3)).any(), "a planned sub-floor cost"
    assert not (v & (prd == 0) & (fn == 1)).any(), "quarantine planned"
    for o, n in zip(old, new, strict=True):
        leaves_o, leaves_n = jax.tree.leaves(o), jax.tree.leaves(n)
        assert len(leaves_o) == len(leaves_n)
        for a, b in zip(leaves_o, leaves_n):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _instruction_shapes(hlo: str) -> dict:
    """Instruction name -> (dtype, element count) over a module's text."""
    out = {}
    for name, dtype, dims in re.findall(
        r"%([\w.\-]+) = (\w+)\[([\d,]*)\]", hlo
    ):
        out[name] = (dtype, int(np.prod([int(d) for d in dims.split(",") if d])))
    return out


@pytest.mark.parametrize("function_selection", ["table", "best"])
def test_superstep_gathers_no_per_lane_cost(function_selection):
    """The compiled superstep gathers nothing S*C*P-sized out of the [P, F]
    cost table: a plan looks its K lanes' costs up after top-k."""
    s_, c_, p_, f_ = 4, 4096, 4, 4
    preds = [Predicate(i, 1) for i in range(p_)]
    corpus = make_corpus(jax.random.PRNGKey(0), c_, [p.tag_type for p in preds],
                         [p.tag for p in preds])
    sess = EngineSession(
        [p.positive() for p in preds], fallback_decision_table(p_, f_, corpus.aucs),
        default_combine_params(corpus.aucs), corpus.costs,
        capacity=c_, max_tenants=s_,
        config=MultiQueryConfig(plan_size=32, backend="pallas",
                                pallas_interpret=True,
                                function_selection=function_selection),
    )
    st = sess.init_state(corpus.func_probs)
    st, _ = sess.admit(st, conjunction(preds[0], preds[1]))
    sess.run(st, 1)
    hlo = dict(sess.program.compiled_hlo())["superstep"]
    shapes = _instruction_shapes(hlo)
    gathers = re.findall(r"%([\w.\-]+) = \w+\[[\d,]*\]\S* gather\(%([\w.\-]+)", hlo)
    assert gathers, "the superstep's gathers were not found"
    per_lane = [
        g for g, operand in gathers
        if shapes[operand] == ("f32", p_ * f_) and shapes[g][1] == s_ * c_ * p_
    ]
    assert not per_lane, f"per-lane cost gathers: {per_lane}"
