"""Distributed-PIQUE building blocks: hierarchical plan merge, sharded join,
histogram threshold as a sharding-friendly reduction, straggler cost model."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline CI: fixed-example property testing
    from _hypothesis_fallback import given, settings, st

from repro.core.benefit import TripleBenefits
from repro.core.join import join_predicate_probability
from repro.core.plan import Plan, merge_sharded_plans, select_plan
from repro.core.threshold import select_answer, select_answer_approx
from repro.enrich.simulated import LatencyModelBank


def _mk_benefits(seed, n, p):
    rng = np.random.default_rng(seed)
    b = rng.uniform(0, 5, size=(n, p)).astype(np.float32)
    return TripleBenefits(
        benefit=jnp.asarray(b),
        next_fn=jnp.zeros((n, p), jnp.int32),
        est_joint=jnp.asarray(rng.uniform(size=(n, p)).astype(np.float32)),
    )


def test_hierarchical_topk_equals_global_topk():
    """Per-shard top-k -> merge == global top-k (exactness of the hierarchy)."""
    n, p, shards, k = 256, 2, 4, 16
    ben = _mk_benefits(0, n, p)
    costs = jnp.full((p, 1), 0.1, jnp.float32)
    global_plan = select_plan(ben, plan_size=k, costs=costs)

    per = n // shards
    local_plans = []
    for s in range(shards):
        local = TripleBenefits(
            benefit=ben.benefit[s * per:(s + 1) * per],
            next_fn=ben.next_fn[s * per:(s + 1) * per],
            est_joint=ben.est_joint[s * per:(s + 1) * per],
        )
        lp = select_plan(local, plan_size=k, costs=costs)
        # re-index objects to global ids
        lp = lp._replace(object_idx=lp.object_idx + s * per)
        local_plans.append(lp)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *local_plans)
    merged = merge_sharded_plans(stacked, plan_size=k)

    np.testing.assert_allclose(
        np.sort(np.asarray(merged.benefit))[::-1],
        np.sort(np.asarray(global_plan.benefit))[::-1],
        rtol=1e-6,
    )
    assert set(np.asarray(merged.object_idx).tolist()) == set(
        np.asarray(global_plan.object_idx).tolist()
    )
    # every kept lane is billed its (predicate, function)'s cost
    np.testing.assert_array_equal(np.asarray(merged.cost), np.float32(0.1))


@given(st.integers(0, 500))
@settings(max_examples=25, deadline=None)
def test_histogram_threshold_close_to_exact(seed):
    rng = np.random.default_rng(seed)
    p = jnp.asarray(rng.beta(1.2, 3.0, size=1024).astype(np.float32))
    exact = select_answer(p)
    approx = select_answer_approx(p, bins=4096)
    assert abs(float(exact.expected_f) - float(approx.expected_f)) < 5e-3


def test_sharded_join_matches_unsharded():
    rng = np.random.default_rng(1)
    own = jnp.asarray(rng.uniform(size=64).astype(np.float32))
    partner = jnp.asarray(rng.uniform(size=100).astype(np.float32))
    ref = join_predicate_probability(own, partner)
    # simulate 4 partner shards: local sums + global count (the psum path)
    shards = np.array_split(np.asarray(partner), 4)
    total = sum(float(s.sum()) for s in shards)
    got = own * (total / 100)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), rtol=1e-5)


def test_latency_model_bank_bsp_epoch_time():
    """Bulk-synchronous epoch time = slowest shard's work (straggler model)."""
    n = 64
    outputs = jnp.full((n, 1, 2), 0.5)
    costs = jnp.asarray([[1.0, 2.0]])
    shard_of = jnp.asarray(np.repeat([0, 1], n // 2), jnp.int32)
    slow = jnp.asarray([1.0, 3.0])  # shard 1 is 3x slower
    bank = LatencyModelBank(
        outputs=outputs, costs=costs, shard_of_object=shard_of,
        shard_slowdown=slow,
    )
    plan = Plan(
        object_idx=jnp.asarray([0, 32], jnp.int32),  # one triple per shard
        pred_idx=jnp.zeros(2, jnp.int32),
        func_idx=jnp.zeros(2, jnp.int32),
        benefit=jnp.ones(2), cost=jnp.asarray([1.0, 1.0]),
        valid=jnp.ones(2, bool),
    )
    t = float(bank.modeled_plan_time(plan))
    assert t == pytest.approx(3.0)  # max(1*1, 1*3)


def test_rebalanced_partition_reduces_epoch_time():
    """Straggler-aware partitions lower the modeled BSP epoch time."""
    from repro.runtime.fault_tolerance import StragglerMonitor

    m = StragglerMonitor(num_shards=2)
    for _ in range(6):
        m.record(0, 1.0)
        m.record(1, 3.0)
    ranges = m.rebalance_objects(120)
    sizes = [e - s for s, e in ranges]
    # even split: epoch = max(60*1, 60*3) = 180 work-units
    # rebalanced:  epoch = max(sizes[0]*1, sizes[1]*3)
    even = max(60 * 1.0, 60 * 3.0)
    rebal = max(sizes[0] * 1.0, sizes[1] * 3.0)
    assert rebal < even
