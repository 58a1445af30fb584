"""Benefit estimation (Eq. 11, Lemma 4, section 4.3) and plan selection."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline CI: fixed-example property testing
    from _hypothesis_fallback import given, settings, st

from repro.core import conjunction, Predicate
from repro.core.benefit import benefit_exact_slow, compute_benefits
from repro.core.decision_table import fallback_decision_table
from repro.core.entropy import binary_entropy, inverse_entropy_upper
from repro.core.plan import select_plan
from repro.core.state import init_state, refresh_derived
from repro.core.combine import default_combine_params


def _mk_state(seed=0, n=64, p=2, f=4):
    rng = np.random.default_rng(seed)
    query = conjunction(*[Predicate(i, 1) for i in range(p)])
    combine = default_combine_params(jnp.full((p, f), 0.8))
    stt = init_state(n, p, f)
    # random partial execution
    mask = rng.uniform(size=(n, p, f)) < 0.4
    probs = rng.uniform(0.02, 0.98, size=(n, p, f)).astype(np.float32)
    stt = dataclasses.replace(
        stt, exec_mask=jnp.asarray(mask), func_probs=jnp.asarray(probs)
    )
    stt = refresh_derived(stt, query, combine)
    return stt, query, combine


def test_benefit_matches_manual_eq11():
    stt, query, _ = _mk_state()
    p, f = 2, 4
    table = fallback_decision_table(p, f, jnp.asarray([0.6, 0.7, 0.8, 0.9]))
    costs = jnp.asarray(np.tile([0.02, 0.1, 0.4, 0.9], (p, 1)), jnp.float32)
    out = compute_benefits(stt, query, table, costs,
                           candidate_mask=jnp.ones((stt.num_objects,), bool))
    # pick a row and verify by hand
    i = 5
    for j in range(p):
        nf = int(out.next_fn[i, j])
        if nf < 0:
            assert not np.isfinite(float(out.benefit[i, j]))
            continue
        sid = int(stt.state_id()[i, j])
        h = float(stt.uncertainty[i, j])
        b = min(int(h * 10), 9)
        dh = float(table.delta_h[j, sid, b])
        h_hat = np.clip(h + dh, 0.0, 1.0)
        p_hat = float(inverse_entropy_upper(jnp.asarray(h_hat)))
        old_col = float(stt.pred_prob[i, j])
        joint = float(stt.joint_prob[i])
        est = joint / max(old_col, 1e-12) * p_hat if old_col > 0 else 0.0
        est = np.clip(est, 0.0, 1.0)
        expect = joint * est / max(float(costs[j, nf]), 1e-9)
        np.testing.assert_allclose(float(out.benefit[i, j]), expect, rtol=1e-4)


def test_exhausted_pairs_are_masked():
    stt, query, combine = _mk_state()
    stt = dataclasses.replace(stt, exec_mask=jnp.ones_like(stt.exec_mask))
    stt = refresh_derived(stt, query, combine)
    table = fallback_decision_table(2, 4, jnp.asarray([0.6, 0.7, 0.8, 0.9]))
    costs = jnp.full((2, 4), 0.1)
    out = compute_benefits(stt, query, table, costs,
                           candidate_mask=jnp.ones((stt.num_objects,), bool))
    assert not bool(jnp.any(jnp.isfinite(out.benefit)))
    assert bool(jnp.all(out.next_fn == -1))


def test_candidate_mask_excludes():
    stt, query, _ = _mk_state()
    table = fallback_decision_table(2, 4, jnp.asarray([0.6, 0.7, 0.8, 0.9]))
    costs = jnp.full((2, 4), 0.1)
    cand = jnp.zeros((stt.num_objects,), bool).at[:5].set(True)
    out = compute_benefits(stt, query, table, costs, candidate_mask=cand)
    assert not bool(jnp.any(jnp.isfinite(out.benefit[5:])))


def test_best_selection_dominates_table_selection():
    stt, query, _ = _mk_state(seed=3)
    table = fallback_decision_table(2, 4, jnp.asarray([0.6, 0.7, 0.8, 0.9]))
    costs = jnp.asarray(np.tile([0.02, 0.1, 0.4, 0.9], (2, 1)), jnp.float32)
    cand = jnp.ones((stt.num_objects,), bool)
    tab = compute_benefits(stt, query, table, costs, cand)
    best = compute_benefits(stt, query, table, costs, cand, function_selection="best")
    fin = jnp.isfinite(tab.benefit)
    assert bool(jnp.all(best.benefit[fin] >= tab.benefit[fin] - 1e-5))


def test_plan_selection_order_and_budget():
    stt, query, _ = _mk_state(seed=1)
    table = fallback_decision_table(2, 4, jnp.asarray([0.6, 0.7, 0.8, 0.9]))
    costs = jnp.asarray(np.tile([0.02, 0.1, 0.4, 0.9], (2, 1)), jnp.float32)
    out = compute_benefits(stt, query, table, costs,
                           candidate_mask=jnp.ones((stt.num_objects,), bool))
    plan = select_plan(out, plan_size=16, costs=costs, cost_budget=1.0)
    b = np.asarray(plan.benefit)
    assert np.all(np.diff(b) <= 1e-6)  # descending
    assert float(plan.total_cost()) <= 1.0 + 1e-5
    # valid triples point at real objects/functions, billed their cost
    v = np.asarray(plan.valid)
    assert np.all(np.asarray(plan.func_idx)[v] >= 0)
    np.testing.assert_array_equal(
        np.asarray(plan.cost)[v],
        np.asarray(costs)[np.asarray(plan.pred_idx), np.asarray(plan.func_idx)][v],
    )


def test_eq11_preserves_exact_benefit_order_lemma4():
    """Theorem 2 / Lemma 4: Eq. 11 ordering agrees with the literal Eq. 7
    ordering for the top choice (the one the plan actually takes)."""
    stt, query, _ = _mk_state(seed=5, n=24)
    table = fallback_decision_table(2, 4, jnp.asarray([0.6, 0.7, 0.8, 0.9]))
    costs = jnp.asarray(np.tile([0.02, 0.1, 0.4, 0.9], (2, 1)), jnp.float32)
    cand = jnp.ones((24,), bool)
    fast = compute_benefits(stt, query, table, costs, cand)
    slow = benefit_exact_slow(stt, query, table, costs, candidate_mask=cand)
    fb = np.asarray(fast.benefit).ravel()
    sb = np.asarray(slow.benefit).ravel()
    fin = np.isfinite(fb) & np.isfinite(sb)
    # rank correlation of top decile (what plan selection consumes)
    k = max(4, fin.sum() // 10)
    top_fast = set(np.argsort(-np.where(fin, fb, -np.inf))[:k])
    top_slow = set(np.argsort(-np.where(fin, sb, -np.inf))[:k])
    overlap = len(top_fast & top_slow) / k
    assert overlap >= 0.5


@given(st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_benefit_finite_and_nonnegative(seed):
    stt, query, _ = _mk_state(seed=seed, n=16)
    table = fallback_decision_table(2, 4, jnp.asarray([0.6, 0.7, 0.8, 0.9]))
    costs = jnp.full((2, 4), 0.25)
    out = compute_benefits(stt, query, table, costs,
                           candidate_mask=jnp.ones((16,), bool))
    b = np.asarray(out.benefit)
    fin = np.isfinite(b)
    assert np.all(b[fin] >= 0.0)
    assert np.all(np.asarray(out.est_joint) <= 1.0 + 1e-6)


@pytest.mark.parametrize("mode", ["table", "best"])
def test_batched_reference_recomputes_entropy_like_single_query(mode):
    """The batched reference adds the table's delta to H(pred_prob)
    recomputed in f32, as ``compute_benefits`` does; the stored uncertainty
    (rounded on a bf16 substrate) only picks the bin.  So with a bf16-rounded
    uncertainty the two paths still agree bit for bit."""
    from repro.core.benefit import compute_benefits_batched

    stt, query, _ = _mk_state(seed=3, n=96, p=2, f=4)
    unc_bf16 = stt.uncertainty.astype(jnp.bfloat16).astype(jnp.float32)
    assert (np.asarray(unc_bf16) != np.asarray(stt.uncertainty)).any()
    stt = dataclasses.replace(stt, uncertainty=unc_bf16)
    table = fallback_decision_table(2, 4, jnp.asarray([0.6, 0.7, 0.8, 0.9]))
    costs = jnp.asarray(np.tile([0.02, 0.1, 0.4, 0.9], (2, 1)), jnp.float32)
    one = compute_benefits(stt, query, table, costs, function_selection=mode,
                           candidate_mask=jnp.ones((96,), bool))
    batched = compute_benefits_batched(
        stt.pred_prob, stt.uncertainty, stt.state_id(), stt.joint_prob[None],
        table, costs, function_selection=mode,
    )
    valid = np.asarray(one.next_fn) >= 0
    assert valid.any()
    np.testing.assert_array_equal(np.asarray(batched.next_fn[0]), np.asarray(one.next_fn))
    for name in ("benefit", "est_joint"):
        np.testing.assert_array_equal(
            np.asarray(getattr(batched, name)[0])[valid],
            np.asarray(getattr(one, name))[valid],
        )
    # and the plans they give bill the same costs
    masked = lambda tb: tb._replace(benefit=jnp.where(valid, tb.benefit, -jnp.inf))
    plan_b = select_plan(masked(jax.tree.map(lambda x: x[0], batched)), 32, costs)
    plan_1 = select_plan(masked(one), 32, costs)
    for a, b in zip(plan_b, plan_1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_batched_best_mode_f32_unchanged_by_running_argmax():
    """Best mode's running max over F gives, at f32, exactly what the dense
    [Q, N, P, F] argmax formulation gives."""
    from repro.core.benefit import compute_benefits_batched, estimate_pred_prob_after
    from repro.core.query import conjunctive_joint_update

    p, f, q = 2, 4, 3
    stt, _, _ = _mk_state(seed=5, n=80, p=p, f=f)
    joint = jnp.asarray(np.random.default_rng(6).uniform(0.01, 1.0, (q, 80)), jnp.float32)
    table = fallback_decision_table(p, f, jnp.asarray([0.6, 0.7, 0.8, 0.9]))
    costs = jnp.asarray(np.tile([0.02, 0.1, 0.4, 0.9], (p, 1)), jnp.float32)
    out = compute_benefits_batched(
        stt.pred_prob, stt.uncertainty, stt.state_id(), joint, table, costs,
        function_selection="best",
    )
    pred_idx = jnp.broadcast_to(jnp.arange(p)[None], (80, p))
    dh_all = table.lookup_all(pred_idx, stt.state_id(), stt.uncertainty)
    _, p_hat = estimate_pred_prob_after(
        stt.pred_prob[..., None], jnp.where(jnp.isfinite(dh_all), dh_all, 0.0)
    )
    cost = jnp.maximum(jnp.broadcast_to(costs[None], dh_all.shape), 1e-9)
    est = jnp.clip(
        conjunctive_joint_update(
            joint[:, :, None, None], stt.pred_prob[None, :, :, None], p_hat[None]
        ),
        0.0, 1.0,
    )  # [Q, N, P, F]
    ben = jnp.where(jnp.isfinite(dh_all)[None],
                    joint[:, :, None, None] * est / cost[None], -1e30)
    nf = np.asarray(jnp.argmax(ben, axis=-1))
    valid = np.isfinite(np.asarray(dh_all)).any(-1)[None].repeat(q, 0)
    assert valid.any() and not valid.all()
    np.testing.assert_array_equal(np.asarray(out.next_fn)[valid], nf[valid])
    np.testing.assert_array_equal(np.asarray(out.next_fn)[~valid], -1)
    pick = lambda x: np.take_along_axis(np.asarray(x), nf[..., None], -1)[..., 0]
    np.testing.assert_array_equal(np.asarray(out.benefit)[valid], pick(ben)[valid])
    np.testing.assert_array_equal(np.asarray(out.est_joint)[valid], pick(est)[valid])
    # the plan bills each kept lane its argmax function's floored cost
    plans = jax.vmap(lambda b: select_plan(b, 32, costs))(out)
    pv = np.asarray(plans.valid)
    obj, prd = np.asarray(plans.object_idx), np.asarray(plans.pred_idx)
    lane = (np.arange(q)[:, None], obj, prd)
    np.testing.assert_array_equal(np.asarray(plans.func_idx)[pv], nf[lane][pv])
    dense_cost = pick(jnp.broadcast_to(cost[None], ben.shape))
    np.testing.assert_array_equal(np.asarray(plans.cost)[pv], dense_cost[lane][pv])
