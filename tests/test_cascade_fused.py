"""Fused traceable model-cascade bank: execute parity vs the host oracle,
ragged-cascade planning exclusion, and scan-driver routing.

Tolerance contract (documented in README "Real-model enrichment"): the fused
``execute`` and the host ``execute_host`` compute the same math, but the
stacked-parameter dispatch reassociates the probe/head contractions, so
probabilities agree to f32 rounding (atol 1e-5 here; observed ~1e-7 at these
shapes).  Answer sets and cost_spent between the fused scan driver and the
legacy per-epoch loop must agree exactly / to 1-ulp float aggregation.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import get_config
from repro.core import (
    MultiQueryConfig,
    MultiQueryEngine,
    OperatorConfig,
    Predicate,
    ProgressiveQueryOperator,
    build_query_set,
    conjunction,
    learn_decision_table,
)
from repro.core.combine import fit_combine_weights
from repro.core.executor import EpochProgram, scan_capable
from repro.core.plan import Plan
from repro.core.session import EngineSession
from repro.data.synthetic import make_corpus, split_corpus, truth_answer_mask
from repro.enrich.cascade import (
    SENTINEL_COST_S,
    ModelCascadeBank,
    build_cascade,
    build_cascade_suite,
)

PROB_ATOL = 1e-5  # fused-vs-host probability tolerance (f32 reassociation)

FEATURE_DIM = 8


def _probe_bank(num_preds=3, n=48, seed=0, ragged_pred=None):
    """Probe-only cascade bank (linear + MLP levels, no backbone).

    ``ragged_pred`` truncates that predicate's cascade to 1 level, making the
    bank ragged (F=2 with an unavailable (ragged_pred, 1) slot).
    """
    suite = build_cascade_suite(
        jax.random.PRNGKey(seed), num_preds, FEATURE_DIM
    )
    if ragged_pred is not None:
        suite[ragged_pred] = suite[ragged_pred][:1]
    feats = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, FEATURE_DIM))
    return ModelCascadeBank(cascades=suite, features=feats)


def _backbone_bank(num_preds=2, n=24, seed=0, arch="qwen3-1.7b"):
    cfg = get_config(arch, smoke=True)
    suite = build_cascade_suite(
        jax.random.PRNGKey(seed), num_preds, FEATURE_DIM, backbone_cfg=cfg
    )
    feats = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, FEATURE_DIM))
    return ModelCascadeBank(cascades=suite, features=feats)


def _random_plan(bank, m=32, seed=0, all_invalid=False):
    """A merged-plan-shaped Plan with duplicate lanes and partial validity,
    restricted to available (pred, level) pairs (the planner's guarantee)."""
    rng = np.random.default_rng(seed)
    n = bank.features.shape[0]
    p, f = bank.costs.shape
    avail = np.asarray(bank.available)
    pairs = np.argwhere(avail)
    pick = pairs[rng.integers(0, len(pairs), m)]
    valid = np.zeros(m, bool) if all_invalid else rng.random(m) < 0.75
    return Plan(
        object_idx=jnp.asarray(rng.integers(0, n, m), jnp.int32),
        pred_idx=jnp.asarray(pick[:, 0], jnp.int32),
        func_idx=jnp.asarray(pick[:, 1], jnp.int32),
        cost=jnp.zeros(m),
        benefit=jnp.zeros(m),
        valid=jnp.asarray(valid),
    )


def _operator_setup(bank, num_preds, n, seed=0, host_loop=False):
    """Operator over a planted corpus whose enrichment is the cascade bank."""
    rng = jax.random.PRNGKey(seed + 7)
    preds = [Predicate(i, 1) for i in range(num_preds)]
    corpus = make_corpus(
        rng, n + 128, [p.tag_type for p in preds], [p.tag for p in preds],
        selectivity=[0.3] * num_preds, feature_dim=FEATURE_DIM,
    )
    train, evalc = split_corpus(corpus, 128)
    # train outputs come from the bank's own levels over train features
    p, f = bank.costs.shape
    outs = np.full((train.features.shape[0], p, f), 0.5, np.float32)
    for i, casc in enumerate(bank.cascades):
        for j, lvl in enumerate(casc):
            outs[:, i, j] = np.asarray(lvl.apply_fn(lvl.params, train.features))
    combine = fit_combine_weights(
        jnp.asarray(outs), train.truth_pred[:, :p].astype(jnp.float32), steps=50
    )
    table = learn_decision_table(
        jnp.asarray(outs), combine, num_bins=8,
        costs=bank.costs, cost_normalized=True,
    )
    query = conjunction(*preds)
    served = _HostLoopBank(bank) if host_loop else bank
    op = ProgressiveQueryOperator(
        query, table, combine, bank.costs, served,
        OperatorConfig(plan_size=16, function_selection="best"),
    )
    return op


class _HostLoopBank:
    """Pre-fusion posture: hides ``supports_scan``, delegates to the host
    oracle — forces the facades' legacy per-epoch loop."""

    def __init__(self, inner):
        self.inner = inner
        self.costs = inner.costs
        self.available = inner.available

    def execute(self, plan):
        return self.inner.execute_host(plan)


# ------------------------------------------------------- execute parity ----


def test_cascade_bank_is_traceable():
    bank = _probe_bank()
    assert bank.supports_scan is True
    assert scan_capable(bank)
    assert not hasattr(bank, "outputs")  # no precomputed buffer to gather


@pytest.mark.parametrize("seed", [0, 3])
def test_execute_parity_probe_bank(seed):
    bank = _probe_bank(seed=seed)
    plan = _random_plan(bank, m=40, seed=seed)
    fused = np.asarray(bank.execute(plan))
    host = np.asarray(bank.execute_host(plan))
    np.testing.assert_allclose(fused, host, atol=PROB_ATOL, rtol=0)
    # invalid lanes return the 0.5 prior in both paths
    inv = ~np.asarray(plan.valid)
    assert np.all(fused[inv] == 0.5)


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "lfm2-24b-a2b"])
def test_execute_parity_backbone_bank(arch):
    bank = _backbone_bank(arch=arch)
    plan = _random_plan(bank, m=24, seed=1)
    fused = np.asarray(bank.execute(plan))
    host = np.asarray(bank.execute_host(plan))
    np.testing.assert_allclose(fused, host, atol=PROB_ATOL, rtol=0)


def test_execute_parity_under_jit():
    bank = _probe_bank()
    plan = _random_plan(bank, m=32, seed=2)
    eager = np.asarray(bank.execute(plan))
    jitted = np.asarray(jax.jit(bank.execute)(plan))
    np.testing.assert_allclose(jitted, eager, atol=1e-6, rtol=0)


def test_execute_empty_plan_returns_priors():
    bank = _probe_bank()
    plan = _random_plan(bank, m=16, all_invalid=True)
    np.testing.assert_array_equal(np.asarray(bank.execute(plan)), 0.5)
    np.testing.assert_array_equal(np.asarray(bank.execute_host(plan)), 0.5)


def test_execute_parity_merged_multi_query_plan():
    """Parity on a REAL merged deduplicated plan from the multi-query
    planner (not a synthetic one)."""
    num_preds, n, q = 3, 48, 3
    bank = _probe_bank(num_preds=num_preds, n=n)
    preds = [Predicate(i, 1) for i in range(num_preds)]
    queries = [
        conjunction(preds[0], preds[1]),
        conjunction(preds[1], preds[2]),
        conjunction(preds[0], preds[2]),
    ][:q]
    query_set = build_query_set(
        queries, global_predicates=[p.positive() for p in preds]
    )
    rng = jax.random.PRNGKey(11)
    corpus = make_corpus(
        rng, n + 96, [p.tag_type for p in preds], [p.tag for p in preds],
        selectivity=[0.3] * num_preds, feature_dim=FEATURE_DIM,
    )
    train, _ = split_corpus(corpus, 96)
    outs = np.full((96, num_preds, 2), 0.5, np.float32)
    for i, casc in enumerate(bank.cascades):
        for j, lvl in enumerate(casc):
            outs[:, i, j] = np.asarray(lvl.apply_fn(lvl.params, train.features))
    combine = fit_combine_weights(
        jnp.asarray(outs), train.truth_pred.astype(jnp.float32), steps=50
    )
    table = learn_decision_table(jnp.asarray(outs), combine, num_bins=8)
    engine = MultiQueryEngine(
        query_set, table, combine, bank.costs, bank,
        MultiQueryConfig(plan_size=16),
    )
    state = engine.init_state(n)
    _plans, merged = engine._plan_fn(state)
    assert int(merged.num_valid()) > 0
    fused = np.asarray(bank.execute(merged))
    host = np.asarray(bank.execute_host(merged))
    np.testing.assert_allclose(fused, host, atol=PROB_ATOL, rtol=0)


# ------------------------------------------------- ragged cascade planning --


def test_ragged_cascade_cost_padding_is_sentinel_not_zero():
    bank = _probe_bank(ragged_pred=1)
    costs = np.asarray(bank.costs)
    avail = np.asarray(bank.available)
    assert not avail[1, 1]
    assert costs[1, 1] == SENTINEL_COST_S
    assert (costs[avail] < 1.0).all()  # real levels: honest FLOP seconds


@pytest.mark.parametrize("host_loop", [False, True])
def test_ragged_cascade_never_plans_missing_level(host_loop):
    """A 1-level cascade next to 2-level ones: driving the operator to
    exhaustion through EITHER driver never executes (or bills) the missing
    level of the short cascade."""
    num_preds, n = 3, 48
    bank = _probe_bank(num_preds=num_preds, n=n, ragged_pred=1)
    op = _operator_setup(bank, num_preds, n, host_loop=host_loop)
    state, hist = op.run(n, num_epochs=40)
    exec_mask = np.asarray(state.exec_mask)
    assert exec_mask[:, 0, :].all() and exec_mask[:, 2, :].all(), (
        "full cascades should exhaust in 40 epochs"
    )
    assert exec_mask[:, 1, 0].all()
    assert not exec_mask[:, 1, 1].any(), (
        "planner selected the nonexistent level of the short cascade"
    )
    assert float(state.cost_spent) < SENTINEL_COST_S / 1e6, (
        "a sentinel-cost (missing) level was billed"
    )


# ------------------------------------------------------- driver routing ----


def test_scan_driver_selected_for_cascade_bank_and_loop_branch_gone():
    num_preds, n = 2, 32
    bank = _probe_bank(num_preds=num_preds, n=n)
    op = _operator_setup(bank, num_preds, n)
    state, hist = op.run(n, num_epochs=6)
    # the facade built a session around the bank: its program traces the
    # bank's execute inside the fused superstep
    assert op._session is not None
    session = op._session[1]
    assert session.bank is bank
    assert session.program.bank is bank
    assert session.superstep_traces >= 1
    # the legacy loop's cascade branch is gone: no run_loop anywhere
    assert not hasattr(EpochProgram, "run_loop")
    assert not hasattr(EngineSession, "run_loop")


def test_epoch_program_rejects_opaque_banks():
    bank = _probe_bank()
    opaque = _HostLoopBank(bank)
    assert not scan_capable(opaque)
    op = _operator_setup(bank, 3, 48)
    with pytest.raises(ValueError, match="supports_scan"):
        EpochProgram(
            op.table, op.combine_params, bank.costs, op._engine_config(),
            bank=opaque,
        )


def test_fused_scan_matches_host_loop_end_to_end():
    """Same workload, both postures: fused in-scan cascade vs the host-
    grouping per-epoch loop — answers exactly equal, spend to 1 ulp."""
    num_preds, n, epochs = 3, 48, 12
    bank = _probe_bank(num_preds=num_preds, n=n)
    op_scan = _operator_setup(bank, num_preds, n)
    op_loop = _operator_setup(bank, num_preds, n, host_loop=True)
    st_scan, hist_scan = op_scan.run(n, num_epochs=epochs)
    st_loop, hist_loop = op_loop.run(n, num_epochs=epochs)
    assert len(hist_scan) == len(hist_loop)
    for a, b in zip(hist_scan, hist_loop):
        assert np.isclose(a.cost_spent, b.cost_spent, rtol=1e-5)
        assert a.answer_size == b.answer_size
    np.testing.assert_array_equal(
        np.asarray(st_scan.in_answer), np.asarray(st_loop.in_answer)
    )


def test_session_quarantines_ragged_bank_missing_levels():
    """EngineSession(bank=ragged) opens with the missing (pred, level)
    pairs in the quarantine channel — structurally unplannable."""
    num_preds, n = 2, 24
    bank = _probe_bank(num_preds=num_preds, n=n, ragged_pred=0)
    op = _operator_setup(bank, num_preds, n)
    session = op._session_for(n)
    q = np.asarray(session._initial_quarantine())
    np.testing.assert_array_equal(q, ~np.asarray(bank.available))


def test_backbone_stack_requires_shared_trunk():
    """Per-predicate private trunks cannot stack — build_cascade_suite's
    shared-trunk layout is enforced at bank construction."""
    cfg = get_config("qwen3-1.7b", smoke=True)
    key = jax.random.PRNGKey(0)
    cascades = [
        build_cascade(jax.random.fold_in(key, i), FEATURE_DIM, backbone_cfg=cfg)
        for i in range(2)  # two PRIVATE trunks
    ]
    feats = jax.random.normal(jax.random.PRNGKey(1), (8, FEATURE_DIM))
    with pytest.raises(ValueError, match="shared trunk"):
        ModelCascadeBank(cascades=cascades, features=feats)


def test_superstep_takes_bank_arrays_as_arguments():
    """The trunk, heads, probes and features reach the compiled scan as jit
    ARGUMENTS.  Closed over, they would be baked into every executable as
    constants (gigabytes at published widths) and left out of its
    arguments."""
    from repro.launch.serve import build_cascade_session_server

    session, state, _, _ = build_cascade_session_server(
        num_objects=32, num_preds=2, max_tenants=2,
        backbone_arch="qwen3-1.7b", plan_size=8,
    )
    prog = session.program
    params = prog.bank_params
    trunk = params["levels"][-1]["trunk"]
    nbytes = lambda t: sum(x.nbytes for x in jax.tree.leaves(t))
    fn = prog._get_scan_fn(state.capacity, 2, False, False)
    mem = fn.lower(state, params).compile().memory_analysis()
    assert nbytes(trunk) > 0
    assert mem.argument_size_in_bytes >= nbytes(state) + nbytes(params)
