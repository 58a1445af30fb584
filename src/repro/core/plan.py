"""Plan generation + selection (paper sections 4.4, 3.2).

The paper maintains a priority queue of triples and pops until the epoch's
time budget is exhausted.  TPU adaptation: a masked ``top_k`` over the dense
[N, P] benefit matrix, then a cost-cumsum mask enforcing the budget — all
shape-stable under jit.

Sharded operation (objects split over ("pod", "data")) uses hierarchical
selection: each shard takes its local top-k, the (k x shards) survivors are
all-gathered and reduced to the global top-k.  Exactness: benefit selection is
a global top-k, and the max over shards of per-shard top-k covers it.  The
exact variants below additionally reproduce the UNSHARDED tie-breaking order
(benefit descending, then ascending global flat index / triple key), so the
sharded planning path is byte-identical to the single-device path on every
valid lane — ``canonicalize_plan`` masks the don't-care invalid lanes so the
identity is testable with ``np.array_equal``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.benefit import TripleBenefits, function_cost


class Plan(NamedTuple):
    """A fixed-capacity epoch plan (paper Plan_i), sorted by descending benefit."""

    object_idx: jax.Array  # [K] int32
    pred_idx: jax.Array  # [K] int32
    func_idx: jax.Array  # [K] int32
    benefit: jax.Array  # [K] f32
    cost: jax.Array  # [K] f32
    valid: jax.Array  # [K] bool (within budget and finite benefit)

    @property
    def capacity(self) -> int:
        return self.object_idx.shape[0]

    def num_valid(self) -> jax.Array:
        return jnp.sum(self.valid)

    def total_cost(self) -> jax.Array:
        return jnp.sum(jnp.where(self.valid, self.cost, 0.0))


def canonicalize_plan(plan: Plan) -> Plan:
    """Mask don't-care invalid lanes to fixed sentinels.

    Invalid lanes carry whatever the selection machinery left behind (top-k
    fill, shard-local leftovers); execution never reads them.  Canonical form
    makes plans from different-but-equivalent selection paths (sharded vs
    unsharded, scan vs loop) comparable with ``np.array_equal``.
    """
    v = plan.valid

    def mask_i(x):
        return jnp.where(v, x, jnp.int32(-1))

    return Plan(
        object_idx=mask_i(plan.object_idx),
        pred_idx=mask_i(plan.pred_idx),
        func_idx=mask_i(plan.func_idx),
        benefit=jnp.where(v, plan.benefit, -jnp.inf),
        cost=jnp.where(v, plan.cost, 0.0),
        valid=v,
    )


def quarantine_filter(plan: Plan, quarantined: jax.Array) -> Plan:
    """Invalidate lanes whose (pred, func) is quarantined.

    The scoring path already excludes quarantined functions (their state-id
    bits read as executed), so on a healthy plan this is the identity; it
    exists so execution and ledger attribution — both keyed off ``valid`` —
    are *structurally* unable to run or bill a quarantined triple, whatever
    upstream selection produced.  ``quarantined`` is [P, F] bool.
    """
    dead = quarantined[plan.pred_idx, jnp.maximum(plan.func_idx, 0)]
    dead = dead & (plan.func_idx >= 0)
    return plan._replace(valid=plan.valid & ~dead)


def gather_object_idx(plan: Plan, num_objects: int) -> jax.Array:
    """[K] int32 object indices safe for bank/substrate row gathers.

    Invalid lanes carry whatever selection left behind (-1 sentinels after
    ``canonicalize_plan``, shard-local top-k fill otherwise).  Clipping to
    ``[0, num_objects - 1]`` alone aliases them onto row ``num_objects - 1``
    — a REAL row once a capacity-padded session fills up (num_rows ==
    capacity).  Routing invalid lanes to row 0 keeps the gather in-bounds
    while ``valid`` stays the single source of inertness: execution output
    for such lanes is gathered-then-dropped (``apply_outputs_to_substrate``
    scatters them out of range, ``chargeable_mask`` and the ledger's
    want-bits are masked by ``valid``), never applied.
    """
    safe = jnp.clip(plan.object_idx, 0, num_objects - 1)
    return jnp.where(plan.valid, safe, 0)


def select_plan(
    benefits: TripleBenefits,
    plan_size: int,
    costs: jax.Array,  # [P, F] per-(predicate, function) cost
    cost_budget: float | jax.Array | None = None,
) -> Plan:
    """Top-``plan_size`` triples by benefit, optionally cost-budget-masked.

    One triple per (object, predicate) pair exists (the decision table already
    picked the function), so the flattened matrix IS the candidate triple set
    Triples_i of §4.2.  Ordering contract: descending benefit, ties broken by
    ascending flat (object * P + predicate) index — ``merge_sharded_plans_exact``
    reproduces it across shards.

    Each kept lane's cost is looked up from ``costs`` by its (predicate,
    function), floored at 1e-9 (``benefit.function_cost``), on the K lanes
    only: the same floats the scorers divide Eq. 11 by.
    """
    n, p = benefits.benefit.shape
    flat = benefits.benefit.reshape(-1)
    k = min(plan_size, flat.shape[0])
    top_vals, top_idx = jax.lax.top_k(flat, k)
    obj = (top_idx // p).astype(jnp.int32)
    prd = (top_idx % p).astype(jnp.int32)
    fn = benefits.next_fn.reshape(-1)[top_idx]
    cost = function_cost(costs, prd, fn)
    valid = jnp.isfinite(top_vals) & (fn >= 0)
    if cost_budget is not None:
        # Triples are executed in benefit order until the budget is consumed
        # (paper §3.2 "until the allotted time for the epoch is consumed").
        csum = jnp.cumsum(jnp.where(valid, cost, 0.0))
        valid = valid & (csum <= cost_budget)
    return Plan(
        object_idx=obj,
        pred_idx=prd,
        func_idx=fn.astype(jnp.int32),
        benefit=top_vals,
        cost=cost,
        valid=valid,
    )


def merge_sharded_plans(plans: Plan, plan_size: int) -> Plan:
    """Reduce per-shard plans [S, K] -> global top-k plan (hierarchical top-k).

    ``plans`` leaves carry a leading shard axis (e.g. from shard_map +
    all_gather).  Used by the distributed operator; unit-testable on CPU by
    stacking local plans.  Top-k-equivalent but not order-identical to the
    unsharded plan on ties; use ``merge_sharded_plans_exact`` when downstream
    consumers (cross-query dedup) need byte-stable ordering.
    """
    flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), plans)
    score = jnp.where(flat.valid, flat.benefit, -jnp.inf)
    k = min(plan_size, score.shape[0])
    _, idx = jax.lax.top_k(score, k)
    return jax.tree.map(lambda x: x[idx], flat)


def merge_sharded_plans_exact(
    plans: Plan, plan_size: int, num_predicates: int
) -> Plan:
    """Reduce per-shard plans [S, K] -> the plan ``select_plan`` would produce
    on the unsharded benefit matrix, byte-identical on every valid lane.

    ``select_plan`` orders by (benefit desc, flat object*P+pred asc); a
    lexsort over the gathered shard survivors reproduces exactly that, so the
    hierarchy is not merely top-k-equivalent but order-identical — required
    for the downstream cross-query dedup (which top-ks in this order) to be
    byte-stable under sharding.  Object indices must already be global.
    """
    flat = jax.tree.map(lambda x: x.reshape(-1), plans)
    score = jnp.where(flat.valid, flat.benefit, -jnp.inf)
    tie = flat.object_idx * jnp.int32(num_predicates) + flat.pred_idx
    tie = jnp.where(flat.valid, tie, jnp.iinfo(jnp.int32).max)
    order = jnp.lexsort((tie, -score))
    k = min(plan_size, score.shape[0])
    return jax.tree.map(lambda x: x[order[:k]], flat)


def _triple_keys(
    plan: Plan,
    num_predicates: int,
    num_functions: int,
    num_objects: int | None = None,
):
    """Scalar (object, predicate, function) keys for flattened plan entries.

    Guards the key-space width: with int32 keys, callers need
    N * P * F < 2**31.  Passing ``num_objects`` makes the bound checked —
    promoting to int64 when the runtime allows it (jax_enable_x64) and
    raising a clear error instead of silently wrapping otherwise.
    """
    dtype = jnp.int32
    if num_objects is not None:
        key_space = int(num_objects) * int(num_predicates) * int(num_functions)
        if key_space >= 2**31:
            if jax.config.jax_enable_x64:
                dtype = jnp.int64
            else:
                raise ValueError(
                    f"triple key space N*P*F = {key_space} >= 2**31 overflows "
                    "the int32 dedup keys in merge_plans_dedup; enable "
                    "jax_enable_x64 for int64 keys or shard the object axis "
                    "(merge_plans_dedup_sharded) before merging"
                )
    key = (
        plan.object_idx.astype(dtype) * num_predicates + plan.pred_idx
    ) * num_functions + plan.func_idx
    sentinel = jnp.iinfo(dtype).max
    return jnp.where(plan.valid, key, sentinel), sentinel


def _dedup_merge_core(flat: Plan, key, sentinel, capacity, cost_budget):
    """Shared lexsort-dedup-compact pass over flattened plan entries.

    Returns (merged, order, first, top_idx): the merged plan plus the sort
    permutation, the first-occurrence mask (in sorted position), and the
    sorted positions selected into the merged plan — enough for callers to
    attach per-key aggregates (e.g. tenant want-bitmasks) to merged lanes.
    """
    # primary: key ascending; secondary: benefit descending, so the first
    # occurrence of each key is the max-benefit copy across queries
    order = jnp.lexsort((-flat.benefit, key))
    k_sorted = key[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), k_sorted[1:] != k_sorted[:-1]]
    )
    uniq = first & (k_sorted != sentinel)
    score = jnp.where(uniq, flat.benefit[order], -jnp.inf)
    top_vals, top_idx = jax.lax.top_k(score, capacity)
    sel = order[top_idx]
    merged = jax.tree.map(lambda x: x[sel], flat)
    valid = jnp.isfinite(top_vals)
    if cost_budget is not None:
        csum = jnp.cumsum(jnp.where(valid, merged.cost, 0.0))
        valid = valid & (csum <= cost_budget)
    return merged._replace(valid=valid), order, first, top_idx


def merge_plans_dedup(
    plans: Plan,
    num_predicates: int,
    num_functions: int,
    capacity: int | None = None,
    cost_budget: float | jax.Array | None = None,
    num_objects: int | None = None,
) -> Plan:
    """Merge per-query plans (any leading axes, e.g. [Q, K] or [S, Q, K]) into
    one deduplicated plan (§5 cache generalized to intra-epoch sharing across
    concurrent queries).

    Duplicate (object, predicate, function) triples — the same enrichment
    wanted by several queries this epoch — survive exactly once, keeping the
    highest benefit any query assigned them; the executed output fans back out
    to every requesting query through the shared substrate.  Shape-stable
    under jit: encode each triple as a scalar key, lexsort by (key, -benefit),
    keep first occurrences, compact by top-k benefit (ties broken by ascending
    key, an ordering independent of how entries were partitioned — the basis
    of the sharded variant's exactness).

    Keys are int32 by default: callers need N * P * F < 2**31.  Pass
    ``num_objects`` to have the bound enforced (int64 promotion under
    jax_enable_x64, a clear error otherwise).
    """
    flat = jax.tree.map(lambda x: x.reshape(-1), plans)
    total = flat.object_idx.shape[0]
    if capacity is None:
        capacity = total
    capacity = min(capacity, total)
    key, sentinel = _triple_keys(
        flat, num_predicates, num_functions, num_objects=num_objects
    )
    merged, _, _, _ = _dedup_merge_core(flat, key, sentinel, capacity, cost_budget)
    return merged


def merge_plans_dedup_wants(
    plans: Plan,  # [Q, K]: leading axis MUST be the tenant-slot axis
    num_predicates: int,
    num_functions: int,
    num_slots: int | None = None,
    capacity: int | None = None,
    cost_budget: float | jax.Array | None = None,
    num_objects: int | None = None,
) -> tuple[Plan, jax.Array]:
    """``merge_plans_dedup`` that also reports WHICH tenants wanted each triple.

    Returns ``(merged, want_bits)`` where ``want_bits`` is ``[M, W]`` uint32,
    ``W = ceil(num_slots / 32)``: bit ``q`` (little-endian across words) of
    row ``m`` is set iff slot ``q``'s plan contained merged triple ``m`` as a
    valid lane.  This is the ledger's raw material (``core.ledger``): the
    fair-share split of a deduped triple's cost needs the full wanter set, not
    just the max-benefit owner the merge keeps.

    The bitmask is built with a scatter-add over (key-group, word) — exact
    because a single slot's plan never contains the same triple twice
    (``select_plan`` top-ks distinct lanes), so add == bitwise OR.  The merged
    plan itself is bitwise identical to ``merge_plans_dedup`` on the same
    entries; lanes invalidated by the merge (top-k fill, cost budget) carry a
    zero bitmask.
    """
    if plans.object_idx.ndim != 2:
        raise ValueError(
            "merge_plans_dedup_wants requires [Q, K] plans (slot-major); got "
            f"shape {plans.object_idx.shape}"
        )
    q, k = plans.object_idx.shape
    if num_slots is None:
        num_slots = q
    if q > num_slots:
        raise ValueError(f"plans carry {q} slots > num_slots={num_slots}")
    flat = jax.tree.map(lambda x: x.reshape(-1), plans)
    total = flat.object_idx.shape[0]
    if capacity is None:
        capacity = total
    capacity = min(capacity, total)
    key, sentinel = _triple_keys(
        flat, num_predicates, num_functions, num_objects=num_objects
    )
    merged, order, first, top_idx = _dedup_merge_core(
        flat, key, sentinel, capacity, cost_budget
    )
    words = (num_slots + 31) // 32
    slot = (jnp.arange(total, dtype=jnp.uint32) // jnp.uint32(k))[order]
    valid_sorted = key[order] != sentinel
    bit = jnp.where(
        valid_sorted, jnp.uint32(1) << (slot % jnp.uint32(32)), jnp.uint32(0)
    )
    group = jnp.cumsum(first) - 1  # key-group id per sorted position
    acc = jnp.zeros((total, words), jnp.uint32).at[
        group, (slot // jnp.uint32(32)).astype(jnp.int32)
    ].add(bit)
    want_bits = jnp.where(merged.valid[:, None], acc[group[top_idx]], jnp.uint32(0))
    return merged, want_bits


def merge_plans_dedup_sharded(
    plans: Plan,
    num_predicates: int,
    num_functions: int,
    capacity: int | None = None,
    cost_budget: float | jax.Array | None = None,
    num_objects: int | None = None,
) -> Plan:
    """Hierarchical dedup merge: per-shard lexsort, then a cross-shard unique
    pass — the distributed form of ``merge_plans_dedup``.

    ``plans`` leaves carry a leading shard axis ([S, Q, K] or [S, K]).  Stage
    1 runs the lexsort-dedup independently inside every shard at full local
    capacity (lossless), which is all a device needs before the all-gather;
    stage 2 re-keys the gathered survivors and runs the same pass across
    shards.  Exact because dedup is associative (per-shard max benefit then
    cross-shard max = global max per key) and the output ordering (benefit
    desc, key asc) never depends on how entries were partitioned — so with
    ``capacity`` equal to the flat entry count the result is byte-identical
    (valid lanes) to ``merge_plans_dedup`` over the same entries flattened.
    """
    stage1 = jax.vmap(
        functools.partial(
            merge_plans_dedup,
            num_predicates=num_predicates,
            num_functions=num_functions,
            num_objects=num_objects,
        )
    )(plans)  # [S, K_local] per-shard unique survivors
    if capacity is None:
        capacity = plans.object_idx.size
    return merge_plans_dedup(
        stage1,
        num_predicates,
        num_functions,
        capacity=capacity,
        cost_budget=cost_budget,
        num_objects=num_objects,
    )


def static_plan_from_order(
    object_order: jax.Array,  # [M] object indices in execution order
    pred_of_slot: jax.Array,  # [M]
    func_of_slot: jax.Array,  # [M]
    costs: jax.Array,  # [P, F]
    offset: jax.Array,  # [] int32: how many triples were already executed
    plan_size: int,
) -> Plan:
    """A window of a precomputed static execution order (Baseline1/Baseline2).

    The benefit field carries a descending global rank score (M - slot): the
    baseline's execution order IS its priority, so earlier slots must outrank
    later ones if these plans ever feed ``merge_plans_dedup``, whose dedup
    keeps the max-benefit copy (a constant 0 would corrupt that ordering).
    """
    m = object_order.shape[0]
    sl = offset + jnp.arange(plan_size)
    in_range = sl < m
    rank = (m - sl).astype(jnp.float32)  # descending across and within windows
    sl = jnp.minimum(sl, m - 1)
    obj = object_order[sl]
    prd = pred_of_slot[sl]
    fn = func_of_slot[sl]
    cost = costs[prd, jnp.maximum(fn, 0)]
    valid = in_range & (fn >= 0)
    return Plan(
        object_idx=obj.astype(jnp.int32),
        pred_idx=prd.astype(jnp.int32),
        func_idx=fn.astype(jnp.int32),
        benefit=jnp.where(valid, rank, -jnp.inf),
        cost=cost,
        valid=valid,
    )
