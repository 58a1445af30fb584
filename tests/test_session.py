"""EngineSession: capacity-padded substrate, dynamic tenant slots, streaming
ingestion, per-tenant cost ledger — parity with the static engine, churn
without retrace, and fair-share attribution reconciling with cost_spent."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    EngineSession,
    MultiQueryConfig,
    MultiQueryEngine,
    Or,
    Predicate,
    build_query_set,
    compile_query,
    conjunction,
    fallback_decision_table,
)
from repro.core.combine import default_combine_params
from repro.core.ledger import attribute_epoch, init_ledger, want_matrix
from repro.core.plan import Plan, merge_plans_dedup, merge_plans_dedup_wants
from repro.data.synthetic import make_corpus
from repro.enrich.simulated import SimulatedBank

P_GLOBAL, F, N = 4, 4, 160


def _world(seed=0, num_objects=N):
    preds = [Predicate(i, 1) for i in range(P_GLOBAL)]
    corpus = make_corpus(
        jax.random.PRNGKey(seed), num_objects, [p.tag_type for p in preds],
        [p.tag for p in preds], selectivity=[0.3, 0.4, 0.25, 0.35],
    )
    combine = default_combine_params(corpus.aucs)
    table = fallback_decision_table(P_GLOBAL, F, corpus.aucs)
    return preds, corpus, combine, table


def _session(preds, corpus, combine, table, capacity, max_tenants, **cfg_kw):
    cfg = MultiQueryConfig(**{"plan_size": 32, **cfg_kw})
    return EngineSession(
        [p.positive() for p in preds], table, combine, corpus.costs,
        capacity=capacity, max_tenants=max_tenants, config=cfg,
    )


def _queries(preds):
    return [
        conjunction(preds[0], preds[1]),
        conjunction(preds[1], preds[2]),
        conjunction(preds[0], preds[1]),  # duplicate tenant (hot query)
    ]


# ------------------------------------------------------------ no-churn parity --


@pytest.mark.parametrize("strategy", ["auto", "outside_answer", "all"])
def test_no_churn_parity_bitwise(strategy):
    """capacity == N + fixed tenants: per-epoch answer sets and cost_spent are
    BITWISE identical to MultiQueryEngine.run_scan (the refactor's exactness
    bar)."""
    preds, corpus, combine, table = _world()
    queries = _queries(preds)
    bank = SimulatedBank(outputs=corpus.func_probs, costs=corpus.costs)
    qset = build_query_set(queries, global_predicates=[p.positive() for p in preds])
    cfg = dict(candidate_strategy=strategy)
    eng = MultiQueryEngine(
        qset, table, combine, bank.costs, bank,
        MultiQueryConfig(plan_size=32, **cfg),
    )
    _, hist_e = eng.run_scan(N, 6, collect_masks=True)

    sess = _session(preds, corpus, combine, table, capacity=N, max_tenants=3, **cfg)
    st = sess.init_state(corpus.func_probs)
    for q in queries:
        st, _ = sess.admit(st, q)
    st, hist_s = sess.run(st, 6, collect_masks=True)

    assert len(hist_e) == len(hist_s)
    for a, b in zip(hist_e, hist_s):
        np.testing.assert_array_equal(np.asarray(a.answer_mask),
                                      np.asarray(b.answer_mask))
        assert a.cost_spent == b.cost_spent  # bitwise, not approx
        assert a.merged_valid == b.merged_valid
        assert a.plan_valid == b.plan_valid


def test_capacity_padding_is_inert():
    """Padded rows change nothing: a capacity-2N session produces the same
    real-row answers and identical spend as a capacity-N session."""
    preds, corpus, combine, table = _world()
    queries = _queries(preds)[:2]

    def run(capacity):
        sess = _session(preds, corpus, combine, table,
                        capacity=capacity, max_tenants=2)
        st = sess.init_state(corpus.func_probs)
        for q in queries:
            st, _ = sess.admit(st, q)
        return sess.run(st, 5, collect_masks=True)

    st1, h1 = run(N)
    st2, h2 = run(2 * N)
    assert len(h1) == len(h2)
    for a, b in zip(h1, h2):
        assert a.cost_spent == b.cost_spent
        np.testing.assert_array_equal(
            np.asarray(a.answer_mask), np.asarray(b.answer_mask)[:, :N]
        )
        # invalid rows never enter an answer set
        assert not np.asarray(b.answer_mask)[:, N:].any()
    np.testing.assert_array_equal(
        np.asarray(st1.derived.in_answer),
        np.asarray(st2.derived.in_answer)[:, :N],
    )


# -------------------------------------------------------- churn without retrace --


def test_churn_trace_compiles_superstep_once():
    """≥1 ingest + ≥1 admit + ≥1 retire, interleaved with scan runs: the
    jitted superstep traces exactly once, and the ledger's per-tenant totals
    reconcile with the substrate's cost_spent."""
    preds, corpus, combine, table = _world(num_objects=2 * N)
    sess = _session(preds, corpus, combine, table, capacity=2 * N, max_tenants=4)
    st = sess.init_state(corpus.func_probs[:N])
    st, s0 = sess.admit(st, conjunction(preds[0], preds[1]))
    st, s1 = sess.admit(st, conjunction(preds[1], preds[2]))
    st, _ = sess.run(st, 3)
    st = sess.ingest(st, corpus.func_probs[N:N + 64])  # ingest event
    st, _ = sess.run(st, 3)
    st, s2 = sess.admit(st, conjunction(preds[2], preds[3]))  # admit event
    st, _ = sess.run(st, 3)
    st = sess.retire(st, s0)  # retire event
    st, hist = sess.run(st, 3)

    assert sess.superstep_traces == 1, "superstep re-traced under churn"
    assert hist[-1].num_rows == N + 64
    assert hist[-1].active == [False, True, True, False]
    led = st.ledger
    total = float(jnp.sum(led.attributed) + led.unattributed)
    assert total == pytest.approx(float(st.cost_spent), rel=1e-5)
    assert float(led.unattributed) == 0.0
    # retired slot keeps its final bill; never-used slot owes nothing
    assert float(led.attributed[s0]) > 0.0
    assert float(led.attributed[3]) == 0.0


def test_ingested_rows_become_candidates_and_invalid_rows_never_plan():
    preds, corpus, combine, table = _world(num_objects=2 * N)
    sess = _session(preds, corpus, combine, table, capacity=2 * N, max_tenants=2,
                    candidate_strategy="all")
    st = sess.init_state(corpus.func_probs[:N])
    st, _ = sess.admit(st, conjunction(preds[0], preds[1]))
    st, _ = sess.run(st, 2)

    def valid_plan_objects(state):
        benefits = sess.program._benefits(state, state.row_valid())
        from repro.core.executor import select_plans_batched

        plans = select_plans_batched(
            benefits, plan_size=sess.config.plan_size,
            num_shards=1, num_predicates=sess.num_predicates,
            costs=sess.costs,
        )
        v = np.asarray(plans.valid)
        return np.asarray(plans.object_idx)[v]

    objs = valid_plan_objects(st)
    assert objs.size and objs.max() < N, "plan referenced an invalid row"

    st = sess.ingest(st, corpus.func_probs[N:N + 32])
    objs2 = valid_plan_objects(st)
    assert objs2.max() < N + 32
    # run until the original rows exhaust; ingested rows must get planned
    st, hist = sess.run(st, 60)
    assert hist[-1].num_rows == N + 32
    enriched_new = np.asarray(st.substrate.exec_mask[N:N + 32].any(axis=(1, 2)))
    assert enriched_new.any(), "ingested objects never received enrichment"


def test_retire_last_tenant_idles_and_admission_resumes():
    preds, corpus, combine, table = _world()
    sess = _session(preds, corpus, combine, table, capacity=N, max_tenants=2)
    st = sess.init_state(corpus.func_probs)
    st, slot = sess.admit(st, conjunction(preds[0]))
    st, _ = sess.run(st, 2)
    spent = float(st.cost_spent)
    st = sess.retire(st, slot)
    st, hist = sess.run(st, 2)  # idles: plans empty, nothing charged
    assert [h.merged_valid for h in hist] == [0]
    assert float(st.cost_spent) == spent
    assert hist[-1].mean_expected_f == 0.0
    # admission brings the session back to life, warm-started
    st, _ = sess.admit(st, conjunction(preds[0], preds[1]))
    st, hist2 = sess.run(st, 2)
    assert hist2[-1].merged_valid > 0
    # one scan length in play -> churn never re-traced the superstep
    assert sess.superstep_traces == 1


# ------------------------------------------------------------------- guards --


def test_session_event_validation():
    preds, corpus, combine, table = _world()
    sess = _session(preds, corpus, combine, table, capacity=N, max_tenants=1)
    st = sess.init_state(corpus.func_probs)
    with pytest.raises(ValueError, match="outside the session's global space"):
        sess.admit(st, conjunction(Predicate(7, 1)))
    with pytest.raises(NotImplementedError):
        sess.admit(st, compile_query(Or(preds[0], preds[1])))
    st, slot = sess.admit(st, conjunction(preds[0]))
    with pytest.raises(RuntimeError, match="no free tenant slots"):
        sess.admit(st, conjunction(preds[1]))
    with pytest.raises(ValueError, match="already occupied"):
        sess.admit(st, conjunction(preds[1]), slot=slot)
    with pytest.raises(ValueError, match="not active"):
        sess.retire(sess.retire(st, slot), slot)
    with pytest.raises(ValueError, match="overflows capacity"):
        sess.ingest(st, jnp.full((1, P_GLOBAL, F), 0.5))
    with pytest.raises(ValueError, match="must be \\[M"):
        sess.ingest(st, jnp.full((1, P_GLOBAL + 1, F), 0.5))
    with pytest.raises(ValueError, match="exceeds capacity"):
        sess.init_state(jnp.full((N + 1, P_GLOBAL, F), 0.5))


# ----------------------------------------------------- want-bitmask dedup merge --


def _random_plans(seed, q, k, num_objects=40):
    rng = np.random.default_rng(seed)
    return Plan(
        object_idx=jnp.asarray(rng.integers(0, num_objects, size=(q, k)), jnp.int32),
        pred_idx=jnp.asarray(rng.integers(0, 3, size=(q, k)), jnp.int32),
        func_idx=jnp.asarray(rng.integers(0, 4, size=(q, k)), jnp.int32),
        benefit=jnp.asarray(rng.uniform(0, 5, size=(q, k)).astype(np.float32)),
        cost=jnp.asarray(rng.uniform(0.1, 1.0, size=(q, k)).astype(np.float32)),
        valid=jnp.asarray(rng.uniform(size=(q, k)) < 0.85),
    )


@pytest.mark.parametrize("num_slots", [6, 40])  # 40 exercises two bitmask words
def test_merge_plans_dedup_wants_matches_membership(num_slots):
    q, k = num_slots, 8
    plans = _random_plans(1, q, k)
    # a slot's plan never repeats a triple (select_plan contract): dedup rows
    keys = (
        np.asarray(plans.object_idx) * 3 + np.asarray(plans.pred_idx)
    ) * 4 + np.asarray(plans.func_idx)
    valid = np.asarray(plans.valid).copy()
    for i in range(q):
        seen = set()
        for j in range(k):
            if valid[i, j]:
                if keys[i, j] in seen:
                    valid[i, j] = False
                seen.add(keys[i, j])
    plans = plans._replace(valid=jnp.asarray(valid))

    merged, want_bits = merge_plans_dedup_wants(
        plans, num_predicates=3, num_functions=4, num_slots=num_slots,
        num_objects=40,
    )
    baseline = merge_plans_dedup(plans, num_predicates=3, num_functions=4,
                                 num_objects=40)
    for field in Plan._fields:  # merged plan identical to the plain merge
        np.testing.assert_array_equal(
            np.asarray(getattr(merged, field)), np.asarray(getattr(baseline, field))
        )
    want = np.asarray(want_matrix(want_bits, num_slots))  # [M, S]
    mv = np.asarray(merged.valid)
    mkeys = (
        np.asarray(merged.object_idx) * 3 + np.asarray(merged.pred_idx)
    ) * 4 + np.asarray(merged.func_idx)
    for m in range(mkeys.shape[0]):
        if not mv[m]:
            assert not want[m].any(), "invalid lane carries want bits"
            continue
        expect = np.array(
            [bool((valid[s] & (keys[s] == mkeys[m])).any()) for s in range(q)]
        )
        np.testing.assert_array_equal(want[m], expect, err_msg=f"lane {m}")
    assert want[mv].sum(axis=1).min() >= 1, "valid merged lane with no wanter"


def test_merge_plans_dedup_wants_requires_slot_major():
    plans = _random_plans(2, 3, 4)
    flat = jax.tree.map(lambda x: x.reshape(-1), plans)
    with pytest.raises(ValueError, match="requires \\[Q, K\\]"):
        merge_plans_dedup_wants(flat, 3, 4)


# ------------------------------------------------------------------- ledger --


def test_ledger_fair_share_exact_with_dyadic_costs():
    """Two identical tenants, power-of-two costs: each pays exactly half and
    the totals reconcile with cost_spent to the last bit."""
    preds = [Predicate(i, 1) for i in range(P_GLOBAL)]
    corpus = make_corpus(
        jax.random.PRNGKey(3), N, [p.tag_type for p in preds],
        [p.tag for p in preds], selectivity=[0.3, 0.4, 0.25, 0.35],
        costs=[0.5, 0.25, 0.125, 0.0625],
    )
    combine = default_combine_params(corpus.aucs)
    table = fallback_decision_table(P_GLOBAL, F, corpus.aucs)
    sess = _session(preds, corpus, combine, table, capacity=N, max_tenants=2)
    st = sess.init_state(corpus.func_probs)
    q = conjunction(preds[0], preds[1])
    st, a = sess.admit(st, q)
    st, b = sess.admit(st, q)
    st, _ = sess.run(st, 5)
    led = st.ledger
    assert float(st.cost_spent) > 0
    assert float(led.attributed[a]) == float(led.attributed[b])
    assert float(led.attributed[a] + led.attributed[b]) == float(st.cost_spent)
    assert float(led.unattributed) == 0.0
    assert float(led.reconcile(st.cost_spent)) == 0.0


@pytest.mark.parametrize("n_want", [3, 5, 7])
def test_attribute_epoch_exact_for_non_dyadic_splits(n_want):
    """Regression: fair-share splits used to be exact only under dyadic
    (power-of-two) splits — ``n * fl(cost/n)`` drifts from ``cost`` under 3-,
    5-, 7-way wants.  The rank-based cumulative split decomposes every lane's
    cost EXACTLY (f64 fsum of the f32 bills recovers the cost to the last
    bit) while keeping every bill within an ulp of ``cost/n``."""
    import math

    num_slots = 40  # two want-bitmask words
    rng = np.random.default_rng(n_want)
    for _ in range(8):
        cost = np.float32(rng.uniform(0.001, 1.7))  # arbitrary, non-dyadic
        slots = rng.choice(num_slots, size=n_want, replace=False)
        words = np.zeros((1, 2), np.uint32)
        for s in slots:
            words[0, s // 32] |= np.uint32(1) << np.uint32(s % 32)
        merged = Plan(
            object_idx=jnp.zeros((1,), jnp.int32),
            pred_idx=jnp.zeros((1,), jnp.int32),
            func_idx=jnp.zeros((1,), jnp.int32),
            benefit=jnp.ones((1,), jnp.float32),
            cost=jnp.asarray([cost]),
            valid=jnp.ones((1,), bool),
        )
        led = attribute_epoch(
            init_ledger(num_slots), merged, jnp.asarray(words),
            jnp.ones((1,), bool),
        )
        bills = np.asarray(led.attributed, np.float64)
        want = np.asarray(want_matrix(jnp.asarray(words), num_slots))[0]
        # f64 fsum of f32 bills is exact: the decomposition identity is
        # bitwise — the naive n * fl(cost/n) split fails this for these n
        assert math.fsum(bills) == float(cost)
        assert (bills[~want] == 0).all()
        # fairness: every bill within float noise of the ideal equal share
        np.testing.assert_allclose(bills[want], float(cost) / n_want, rtol=1e-5)
        assert float(led.unattributed) == 0.0


def test_padded_plan_lanes_inert_at_num_rows_equals_capacity():
    """Regression (ISSUE 4): ``_superstep`` used to clip ``merged.object_idx``
    to ``[0, capacity-1]``, so invalid/padded plan lanes gathered row
    ``capacity-1`` — a VALID row once the session fills up.  Prove that
    invalid merged lanes can never contribute to the chargeable mask, bank
    application, or ledger want-bits, even when poisoned with huge costs and
    aliased onto the last real row."""
    from repro.core import state as state_lib
    from repro.core.executor import select_plans_batched
    from repro.core.plan import gather_object_idx

    preds, corpus, combine, table = _world()
    sess = _session(preds, corpus, combine, table, capacity=N, max_tenants=2)
    st = sess.init_state(corpus.func_probs)  # num_rows == capacity: FULL
    st, _ = sess.admit(st, conjunction(preds[0], preds[1]))
    assert int(st.num_rows) == st.capacity

    benefits = sess.program._benefits(st, st.row_valid())
    plans = select_plans_batched(
        benefits, plan_size=sess.config.plan_size, num_shards=1,
        num_predicates=sess.num_predicates, costs=sess.costs,
    )
    merged, want_bits = merge_plans_dedup_wants(
        plans, sess.num_predicates, sess.num_functions,
        num_slots=sess.max_tenants, num_objects=st.capacity,
    )
    inv = ~np.asarray(merged.valid)
    assert inv.any(), "need invalid lanes to regression-test against"

    # 1. ledger: invalid lanes carry no want-bits -> no attribution possible
    assert not np.asarray(want_matrix(want_bits, sess.max_tenants))[inv].any()
    # 2. charging: the substrate's rule never charges an invalid lane
    ch = state_lib.chargeable_mask(
        st.substrate, merged.object_idx, merged.pred_idx, merged.func_idx,
        merged.valid,
    )
    assert not np.asarray(ch)[inv].any()
    # 3. bank gather: invalid lanes route to row 0, NOT the (valid!) last row
    obj = np.asarray(gather_object_idx(merged, st.capacity))
    assert (obj[inv] == 0).all()
    assert (obj[~inv] < int(st.num_rows)).all()
    # 4. end to end: poison invalid lanes (alias onto the last real row with
    # huge cost); substrate, spend, and ledger must be bitwise unaffected
    poisoned = merged._replace(
        object_idx=jnp.where(merged.valid, merged.object_idx, st.capacity - 1),
        cost=jnp.where(merged.valid, merged.cost, 1e6),
    )
    outputs = jnp.zeros((merged.object_idx.shape[0],), jnp.float32)
    sub_ref = state_lib.apply_outputs_to_substrate(
        st.substrate, merged.object_idx, merged.pred_idx, merged.func_idx,
        outputs, merged.cost, merged.valid,
    )
    sub_poi = state_lib.apply_outputs_to_substrate(
        st.substrate, poisoned.object_idx, poisoned.pred_idx, poisoned.func_idx,
        outputs, poisoned.cost, poisoned.valid,
    )
    assert float(sub_ref.cost_spent) == float(sub_poi.cost_spent)
    np.testing.assert_array_equal(np.asarray(sub_ref.exec_mask),
                                  np.asarray(sub_poi.exec_mask))
    np.testing.assert_array_equal(np.asarray(sub_ref.func_probs),
                                  np.asarray(sub_poi.func_probs))
    led_ref = attribute_epoch(init_ledger(sess.max_tenants), merged, want_bits, ch)
    led_poi = attribute_epoch(init_ledger(sess.max_tenants), poisoned, want_bits, ch)
    np.testing.assert_array_equal(np.asarray(led_ref.attributed),
                                  np.asarray(led_poi.attributed))
    assert float(led_poi.unattributed) == 0.0


def test_attribute_epoch_unattributed_bucket():
    """Defensive path: a chargeable triple nobody wanted lands in
    unattributed, never silently vanishing from the books."""
    merged = Plan(
        object_idx=jnp.asarray([0, 1], jnp.int32),
        pred_idx=jnp.zeros((2,), jnp.int32),
        func_idx=jnp.zeros((2,), jnp.int32),
        benefit=jnp.ones((2,), jnp.float32),
        cost=jnp.asarray([2.0, 3.0], jnp.float32),
        valid=jnp.asarray([True, True]),
    )
    want_bits = jnp.asarray([[1], [0]], jnp.uint32)  # lane 1: orphan
    led = attribute_epoch(
        init_ledger(2), merged, want_bits, jnp.asarray([True, True])
    )
    assert float(led.attributed[0]) == 2.0
    assert float(led.unattributed) == 3.0
    assert float(led.total()) == 5.0
