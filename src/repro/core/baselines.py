"""Baseline evaluation strategies (paper section 6.1 "Approaches" + Fig. 1).

* ``Baseline1`` (function-based): functions ordered by quality/cost descending;
  each function runs over all objects ordered by initial joint probability.
* ``Baseline2`` (object-based): objects ordered by initial joint probability;
  all required functions run per object before moving on.
* ``Traditional``: same execution order as Baseline1 but the answer set is
  withheld until every triple has executed (Fig. 1 left).
* ``Incremental``: cheapest-function-first sweeps over all objects — uniform
  quality refinement (Fig. 1 middle).

All are *static* orders fixed at t=0 (the paper stresses this is what the
progressive approach beats); they reuse the operator's plan-execution and
answer-selection machinery so the comparison isolates scheduling policy.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_lib
from repro.core import state as state_lib
from repro.core import threshold as threshold_lib
from repro.core.combine import CombineParams
from repro.core.metrics import true_f_alpha
from repro.core.operator import EpochStats, OperatorConfig
from repro.core.query import CompiledQuery


def _initial_joint_order(operator_state, query, combine_params) -> np.ndarray:
    joint = np.asarray(operator_state.joint_prob)
    return np.argsort(-joint, kind="stable")


def build_static_order(
    strategy: str,
    init_state: state_lib.EnrichmentState,
    query: CompiledQuery,
    combine_params: CombineParams,
    costs: np.ndarray,  # [P, F]
    quality: np.ndarray,  # [P, F] (AUC)
    exclude_pairs: set | None = None,  # (pred, fn) already pre-executed
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """-> (object_order, pred_of_slot, func_of_slot), each [N * pairs]."""
    n = init_state.num_objects
    p, f = costs.shape
    obj_order = _initial_joint_order(init_state, query, combine_params)  # [N]

    exclude_pairs = exclude_pairs or set()
    pairs = [
        (pi, fi)
        for pi in range(p)
        for fi in range(f)
        if (pi, fi) not in exclude_pairs
    ]
    if strategy in ("baseline1", "traditional"):
        # functions by quality/cost descending (paper Baseline1)
        pairs.sort(key=lambda t: -(quality[t[0], t[1]] / max(costs[t[0], t[1]], 1e-9)))
        slots_obj, slots_pred, slots_fn = [], [], []
        for pi, fi in pairs:
            slots_obj.append(obj_order)
            slots_pred.append(np.full(n, pi, np.int32))
            slots_fn.append(np.full(n, fi, np.int32))
    elif strategy == "incremental":
        # cheapest first, sweeping everything uniformly (Fig. 1 incremental)
        pairs.sort(key=lambda t: costs[t[0], t[1]])
        slots_obj, slots_pred, slots_fn = [], [], []
        for pi, fi in pairs:
            slots_obj.append(obj_order)
            slots_pred.append(np.full(n, pi, np.int32))
            slots_fn.append(np.full(n, fi, np.int32))
    elif strategy == "baseline2":
        # object-major: all (pred, fn) per object, functions best-quality first
        pairs.sort(key=lambda t: -quality[t[0], t[1]])
        per_obj_pred = np.array([pi for pi, _ in pairs], np.int32)
        per_obj_fn = np.array([fi for _, fi in pairs], np.int32)
        slots_obj = [np.repeat(obj_order, len(pairs))]
        slots_pred = [np.tile(per_obj_pred, n)]
        slots_fn = [np.tile(per_obj_fn, n)]
    else:
        raise ValueError(f"unknown baseline strategy: {strategy}")

    return (
        np.concatenate(slots_obj).astype(np.int32),
        np.concatenate(slots_pred).astype(np.int32),
        np.concatenate(slots_fn).astype(np.int32),
    )


class StaticOrderEvaluator:
    """Runs a static execution order through the same epoch machinery."""

    def __init__(
        self,
        strategy: str,
        query: CompiledQuery,
        combine_params: CombineParams,
        costs,
        quality,
        bank,
        config: OperatorConfig = OperatorConfig(),
        truth_mask: Optional[jax.Array] = None,
    ):
        self.strategy = strategy
        self.query = query
        self.combine_params = combine_params
        self.costs = jnp.asarray(costs, jnp.float32)
        self.quality = np.asarray(quality)
        self.bank = bank
        self.config = config
        self.truth_mask = truth_mask
        self._update = jax.jit(self._apply_and_select)

    def _apply_and_select(self, state, plan, outputs):
        state = state_lib.apply_function_outputs(
            state,
            self.query,
            self.combine_params,
            plan.object_idx,
            plan.pred_idx,
            plan.func_idx,
            outputs,
            plan.cost,
            plan.valid,
        )
        sel = (
            threshold_lib.select_answer_approx(state.joint_prob, self.config.alpha)
            if self.config.answer_mode == "approx"
            else threshold_lib.select_answer(state.joint_prob, self.config.alpha)
        )
        state = dataclasses.replace(state, in_answer=sel.mask)
        return state, sel

    def run(
        self,
        num_objects: int,
        num_epochs: int,
        cached_probs=None,
        cached_mask=None,
    ):
        st = state_lib.init_state(
            num_objects, self.query.num_predicates, self.costs.shape[1],
            prior=self.config.prior,
        )
        st = state_lib.refresh_derived(st, self.query, self.combine_params,
                                       prior=self.config.prior)
        exclude: set = set()
        if cached_probs is not None and cached_mask is not None:
            st = state_lib.with_cached_state(
                st, self.query, self.combine_params, cached_probs, cached_mask
            )
            # Pairs pre-executed on ALL objects need not be re-run.
            full = np.asarray(jnp.all(cached_mask, axis=0))  # [P, F]
            exclude = {(pi, fi) for pi, fi in zip(*np.nonzero(full))}
        order, preds, fns = build_static_order(
            "baseline1" if self.strategy == "traditional" else self.strategy,
            st, self.query, self.combine_params,
            np.asarray(self.costs), self.quality, exclude_pairs=exclude,
        )
        order_j = jnp.asarray(order)
        preds_j = jnp.asarray(preds)
        fns_j = jnp.asarray(fns)
        total = order.shape[0]
        history: list[EpochStats] = []
        offset = 0
        for e in range(num_epochs):
            if offset >= total:
                break
            plan = plan_lib.static_plan_from_order(
                order_j, preds_j, fns_j, self.costs,
                jnp.asarray(offset, jnp.int32), self.config.plan_size,
            )
            outputs = self.bank.execute(plan)
            st, sel = self._update(st, plan, outputs)
            offset += self.config.plan_size
            done = offset >= total
            # Traditional withholds any useful answer until fully enriched.
            if self.strategy == "traditional" and not done:
                ef, size, mask = 0.0, 0, jnp.zeros_like(sel.mask)
            else:
                ef, size, mask = float(sel.expected_f), int(sel.size), sel.mask
            tf1 = (
                float(true_f_alpha(mask, self.truth_mask, self.config.alpha))
                if self.truth_mask is not None
                else None
            )
            history.append(
                EpochStats(
                    epoch=e,
                    cost_spent=float(st.cost_spent),
                    expected_f=ef,
                    answer_size=size,
                    true_f1=tf1,
                    plan_cost=float(plan.total_cost()),
                    plan_valid=int(plan.num_valid()),
                )
            )
        return st, history
