"""Benefit estimation (paper section 4.3, Lemma 4 / Theorem 2 / Eq. 11).

For every candidate (object, predicate) pair we:
  1. look up the decision table with (predicate, state bitmask, uncertainty
     bin) -> (next function f*, expected delta-uncertainty u)        (§4.2)
  2. form the estimated uncertainty  h_hat = clip(h + u, 0, 1)        (§4.3.1)
  3. invert binary entropy, keeping the optimistic upper root p_hat   (Eq. 8)
  4. estimate the new joint probability P_hat (conjunctive O(1) path
     or general column-substitution re-evaluation)                   (§4.3.1)
  5. Benefit = P * P_hat / cost(f*)                                   (Eq. 11)

This module is the *reference* (pure jnp) implementation; the fused Pallas
kernel in ``repro.kernels.enrich_score`` computes steps 1-5 in a single HBM
pass and is numerically checked against this code.

The "default strategy" the paper compares against in §6.3.3 — re-running the
full threshold-selection per candidate triple — is also provided
(``benefit_exact_slow``) for the Fig. 8 benchmark.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import entropy as entropy_lib
from repro.core import threshold as threshold_lib
from repro.core.decision_table import DecisionTable
from repro.core.query import CompiledQuery, conjunctive_joint_update
from repro.core.state import EnrichmentState

NEG_INF = -jnp.inf


def candidate_mask(
    uncertainty: jax.Array,  # [N, P]
    in_answer: jax.Array,  # [N] bool
    strategy: str,
    pred_mask: jax.Array | None = None,  # [P] bool: predicates the query uses
    row_valid: jax.Array | None = None,  # [N] bool: rows holding real objects
) -> jax.Array:
    """[N] bool candidate restriction (§4.1 + the beyond-paper "auto" widening).

    ``pred_mask`` restricts the uncertainty aggregate to the query's own
    predicate columns — required in the multi-query setting where ``P`` spans
    the global predicate space and a query must not let other tenants'
    columns drag its entropy statistics around.

    ``row_valid`` restricts the "auto" median to rows holding real objects —
    required by the capacity-padded session state (``core.executor``) where
    invalid rows carry cold prior entropy that would drag the corpus median
    toward the prior.  With every row valid the masked median is the plain
    median bitwise (same sort, same middle-pair mean), so the padded path
    degenerates exactly to this one at capacity == N.
    """
    if strategy == "all":
        return jnp.ones(in_answer.shape, bool)
    if strategy == "auto":
        # Beyond-paper hardening (DESIGN.md section 8): the paper's
        # outside-answer restriction (section 4.1) assumes the answer set is
        # small/precise.  With diffuse early probabilities, Theorem-1
        # selection admits most of the corpus and the restriction would
        # refine only the hopeless tail.  "auto" additionally admits
        # inside-answer objects that are still uncertain (entropy above
        # the corpus median) so precision errors inside the set can be
        # fixed; it reduces to the paper rule once the set sharpens.
        if pred_mask is None:
            mean_h = jnp.mean(uncertainty, axis=-1)  # [N]
        else:
            denom = jnp.maximum(jnp.sum(pred_mask), 1)
            mean_h = jnp.sum(jnp.where(pred_mask[None, :], uncertainty, 0.0), -1) / denom
        if row_valid is None:
            med = jnp.median(mean_h)
        else:
            med = _masked_median(mean_h, row_valid)
        return (~in_answer) | (mean_h >= jnp.maximum(med, 0.35))
    return ~in_answer  # "outside_answer" — paper section 4.1 (Fig. 7 benchmarks)


def _masked_median(values: jax.Array, valid: jax.Array) -> jax.Array:
    """Median over the valid entries of ``values`` (shape-stable under jit).

    Invalid entries sort to +inf; the median indices come from the valid
    count.  Matches ``jnp.median`` bitwise when every entry is valid: same
    ascending sort, same (lo + hi) / 2 middle-pair mean.
    """
    s = jnp.sort(jnp.where(valid, values, jnp.inf))
    nv = jnp.maximum(jnp.sum(valid), 1)
    lo = (nv - 1) // 2
    hi = nv // 2
    return (s[lo] + s[hi]) / 2


def restrict_benefits(
    benefit: jax.Array,  # [N, P]
    cand: jax.Array,  # [N] bool
    plan_size: int,
) -> jax.Array:
    """Apply the candidate restriction with a starvation guard: never leave
    fewer valid triples than one plan; widen back to all objects when the
    restriction would."""
    restricted = jnp.where(cand[:, None], benefit, -jnp.inf)
    n_valid = jnp.sum(jnp.isfinite(restricted))
    use_restricted = n_valid >= jnp.minimum(
        plan_size, jnp.sum(jnp.isfinite(benefit))
    )
    return jnp.where(use_restricted, restricted, benefit)


class TripleBenefits(NamedTuple):
    """Eq. 11 over every (object, predicate) lane.

    A triple's cost is a function of its (predicate, function) alone, so
    no per-lane cost is carried: the plan looks it up on the lanes it keeps
    (``function_cost``, called by ``plan.select_plan``).
    """

    benefit: jax.Array  # [N, P] f32; -inf where no candidate triple exists
    next_fn: jax.Array  # [N, P] int32; -1 where exhausted
    est_joint: jax.Array  # [N, P] f32; estimated joint prob if executed


def function_cost(
    costs: jax.Array,  # [P, F]
    pred_idx: jax.Array,  # int32, any shape
    next_fn: jax.Array,  # int32, pred_idx's shape; -1 where exhausted
) -> jax.Array:
    """Cost of each triple's function, floored at 1e-9 (Eq. 11 divides by
    it); an exhausted lane (``next_fn`` -1) reads function 0's cost."""
    return jnp.maximum(costs[pred_idx, jnp.maximum(next_fn, 0)], 1e-9)


def estimate_pred_prob_after(
    pred_prob: jax.Array, delta_h: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Steps 2-3: (h_hat, p_hat) with the optimistic (upper) entropy root."""
    h = entropy_lib.binary_entropy(pred_prob)
    h_hat = jnp.clip(h + delta_h, 0.0, 1.0)
    p_hat = entropy_lib.inverse_entropy_upper(h_hat)
    return h_hat, p_hat


def compute_benefits(
    state: EnrichmentState,
    query: CompiledQuery,
    table: DecisionTable,
    costs: jax.Array,  # [P, F] per-(predicate, function) cost
    candidate_mask: jax.Array | None = None,  # [N] bool; default: ~in_answer (§4.1)
    function_selection: str = "table",  # "table" (paper §4.2) | "best" (beyond-paper)
) -> TripleBenefits:
    """Vectorized Eq. 11 over all candidate (object, predicate) pairs.

    ``function_selection="best"`` replaces the decision table's argmax-delta-h
    function choice with a direct argmax of Eq. 11 over every *remaining*
    function — the benefit metric prices the function, not just the object.
    A strict superset of the paper's behavior (ablated in EXPERIMENTS.md).
    """
    n, p = state.pred_prob.shape
    state_id = state.state_id()  # [N, P]
    pred_idx = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32)[None, :], (n, p))

    if function_selection == "best" and table.delta_h_all is not None:
        dh_all = table.lookup_all(pred_idx, state_id, state.uncertainty)  # [N,P,F]
        _, p_hat_all = estimate_pred_prob_after(
            state.pred_prob[..., None], jnp.where(jnp.isfinite(dh_all), dh_all, 0.0)
        )
        cost = jnp.maximum(jnp.broadcast_to(costs[None], dh_all.shape), 1e-9)
        if query.is_conjunctive:
            est_joint_all = query.conjunctive_update(
                state.joint_prob[:, None, None], state.pred_prob[..., None], p_hat_all
            )
        else:
            est_joint_all = jnp.stack(
                [
                    jnp.stack(
                        [
                            query.evaluate_with_column(
                                state.pred_prob, c, p_hat_all[:, c, f]
                            )
                            for f in range(dh_all.shape[-1])
                        ],
                        axis=-1,
                    )
                    for c in range(p)
                ],
                axis=1,
            )  # [N, P, F]
        est_joint_all = jnp.clip(est_joint_all, 0.0, 1.0)
        ben_all = state.joint_prob[:, None, None] * est_joint_all / cost  # Eq. 11 per f
        ben_all = jnp.where(jnp.isfinite(dh_all), ben_all, NEG_INF)
        nf = jnp.argmax(ben_all, axis=-1).astype(jnp.int32)  # [N, P]
        benefit = jnp.max(ben_all, axis=-1)
        est_joint = jnp.take_along_axis(est_joint_all, nf[..., None], axis=-1)[..., 0]
        nf = jnp.where(jnp.isfinite(benefit), nf, -1)
        valid = nf >= 0
        if candidate_mask is None:
            candidate_mask = ~state.in_answer
        valid = valid & candidate_mask[:, None]
        benefit = jnp.where(valid, benefit, NEG_INF)
        return TripleBenefits(benefit=benefit, next_fn=nf, est_joint=est_joint)

    nf, dh = table.lookup(pred_idx, state_id, state.uncertainty)  # [N, P] each

    _, p_hat = estimate_pred_prob_after(state.pred_prob, dh)

    if query.is_conjunctive:
        est_joint = query.conjunctive_update(
            state.joint_prob[:, None], state.pred_prob, p_hat
        )
    else:
        def sub_col(c):
            return query.evaluate_with_column(state.pred_prob, c, p_hat[:, c])

        est_joint = jnp.stack([sub_col(c) for c in range(p)], axis=-1)

    est_joint = jnp.clip(est_joint, 0.0, 1.0)

    cost = function_cost(costs, pred_idx, nf)  # [N, P]
    benefit = state.joint_prob[:, None] * est_joint / cost  # Eq. 11

    valid = nf >= 0
    if candidate_mask is None:
        candidate_mask = ~state.in_answer  # §4.1 Candidate = O - Answer_{i-1}
    valid = valid & candidate_mask[:, None]
    benefit = jnp.where(valid, benefit, NEG_INF)
    return TripleBenefits(benefit=benefit, next_fn=nf, est_joint=est_joint)


def compute_benefits_batched(
    pred_prob: jax.Array,  # [N, P] shared predicate probabilities
    uncertainty: jax.Array,  # [N, P] shared binary entropy of pred_prob
    state_id: jax.Array,  # [N, P] int32 shared decision-table key
    joint_prob: jax.Array,  # [Q, N] per-query joint probabilities
    table: DecisionTable,
    costs: jax.Array,  # [P, F]
    function_selection: str = "table",  # "table" | "best"
) -> TripleBenefits:
    """Multi-query Eq. 11 over a shared substrate: [Q, N, P] leaves.

    The conjunctive fast path of the multi-query engine.  Everything keyed on
    the substrate alone — table lookup, p_hat inversion, per-function costs —
    is computed ONCE at [N, P(, F)] and broadcast onto the Q axis; only the
    joint-probability update is per-query.  This is the jnp oracle the
    batched Pallas kernel (``repro.kernels.enrich_score``) is checked
    against; the kernel additionally fuses each lane's ``"best"``-mode
    argmax over F into the same pass.  Step 2's h is recomputed in f32 from
    ``pred_prob`` (as in ``compute_benefits``); ``uncertainty``, which a
    bf16 substrate stores rounded, only picks the table bin.

    Validity/candidate masking (pred_mask, §4.1 restriction) is the caller's
    job: returned benefits are unmasked except for exhausted triples.
    """
    n, p = pred_prob.shape
    q = joint_prob.shape[0]
    pred_idx = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32)[None, :], (n, p))
    # Under jit, costs closed over as constants would let XLA rewrite Eq. 11's
    # division as a multiply by the reciprocal (1 ulp off on ~1/5 of lanes);
    # the barrier keeps it a true division, whatever the caller passes.
    costs = jax.lax.optimization_barrier(jnp.asarray(costs, jnp.float32))

    if function_selection == "best":
        assert table.delta_h_all is not None, "table learned without delta_h_all"
        dh_all = table.lookup_all(pred_idx, state_id, uncertainty)  # [N, P, F]
        h = entropy_lib.binary_entropy(pred_prob)  # step 2's h, once for all F
        # Eq. 11 argmax over F as a running max over a static F loop: nothing
        # [Q, N, P, F]-shaped exists (on a TPU an F-minor f32 tensor is tiled
        # 32x larger than its data).  Strict ">" keeps the FIRST maximum, so
        # ties resolve exactly as ``argmax`` would.
        benefit = jnp.full((q, n, p), NEG_INF, jnp.float32)
        nf = jnp.full((q, n, p), -1, jnp.int32)
        # lanes with no finite benefit keep est 0, as the fused kernel
        # reports them
        est_joint = jnp.zeros((q, n, p), jnp.float32)
        for fi in range(dh_all.shape[-1]):
            dh = dh_all[..., fi]
            h_hat = jnp.clip(h + jnp.where(jnp.isfinite(dh), dh, 0.0), 0.0, 1.0)
            p_hat = entropy_lib.inverse_entropy_upper(h_hat)
            cost = jnp.maximum(costs[:, fi], 1e-9)[None, None, :]
            est = jnp.clip(
                conjunctive_joint_update(
                    joint_prob[:, :, None], pred_prob[None], p_hat[None]
                ),
                0.0,
                1.0,
            )  # [Q, N, P]
            ben = joint_prob[:, :, None] * est / cost
            ben = jnp.where(jnp.isfinite(dh)[None], ben, NEG_INF)
            better = ben > benefit
            benefit = jnp.where(better, ben, benefit)
            nf = jnp.where(better, fi, nf)
            est_joint = jnp.where(better, est, est_joint)
        return TripleBenefits(benefit=benefit, next_fn=nf, est_joint=est_joint)

    nf, dh = table.lookup(pred_idx, state_id, uncertainty)  # [N, P] each
    _, p_hat = estimate_pred_prob_after(pred_prob, dh)
    est_joint = jnp.clip(
        conjunctive_joint_update(
            joint_prob[:, :, None], pred_prob[None], p_hat[None]
        ),
        0.0,
        1.0,
    )  # [Q, N, P]
    cost = function_cost(costs, pred_idx, nf)  # [N, P], Eq. 11's divisor
    benefit = joint_prob[:, :, None] * est_joint / cost[None]
    return TripleBenefits(
        benefit=benefit,
        next_fn=jnp.broadcast_to(nf[None], (q, n, p)),
        est_joint=est_joint,
    )


def benefit_exact_slow(
    state: EnrichmentState,
    query: CompiledQuery,
    table: DecisionTable,
    costs: jax.Array,
    alpha: float = 1.0,
    candidate_mask: jax.Array | None = None,
) -> TripleBenefits:
    """The paper's §6.3.3 "default strategy": per-triple threshold re-selection.

    Benefit = (E(F_a) after re-running Theorem-1 selection with the estimated
    joint probability of this one object - E(F_a) of Answer_{i-1}) / cost
    (Eq. 7 computed literally).  O(N^2 P log N) — implemented with vmap for
    the Fig. 8 comparison at small N; do not use in production paths.
    """
    base = threshold_lib.select_answer(state.joint_prob, alpha)
    fast = compute_benefits(state, query, table, costs, candidate_mask)
    n, p = state.pred_prob.shape

    def ef_with(obj_idx, col):
        jp = state.joint_prob.at[obj_idx].set(fast.est_joint[obj_idx, col])
        return threshold_lib.select_answer(jp, alpha).expected_f

    obj_grid = jnp.arange(n)
    ef = jax.vmap(
        lambda o: jax.vmap(lambda c: ef_with(o, c))(jnp.arange(p))
    )(obj_grid)  # [N, P]
    pred_idx = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32)[None, :], (n, p))
    benefit = (ef - base.expected_f) / function_cost(costs, pred_idx, fast.next_fn)
    benefit = jnp.where(jnp.isfinite(fast.benefit), benefit, NEG_INF)
    return TripleBenefits(
        benefit=benefit, next_fn=fast.next_fn, est_joint=fast.est_joint
    )
