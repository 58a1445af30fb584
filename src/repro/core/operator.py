"""The progressive integrated query operator (paper section 3), as a thin
facade over the unified session executor.

``ProgressiveQueryOperator`` keeps its paper-era API (EnrichmentState in,
EpochStats out) but no longer owns a scan driver: a conjunctive query is ONE
tenant slot of an ``EngineSession`` at ``capacity == N``, so ``run`` /
``run_scan`` convert the state at the boundary and delegate to the shared
``core.executor.EpochProgram`` (chunked fused-scan superstep for traceable
banks, the split-at-the-bank loop driver for model cascades).  A legacy
per-epoch path (``run_epoch`` + the jitted ``_plan_epoch`` /
``_apply_and_select`` stages) survives for the query shapes the session's
data-masked slots cannot express: non-conjunctive queries (general ASTs
evaluate Python query structure), ``benefit_mode="exact_slow"`` (the
paper's §6.3.3 default strategy), and custom ``benefit_fn`` overrides.

``candidate_mask`` / ``restrict_benefits`` moved to ``core.benefit`` (they
are scoring policy, shared by every engine); re-exported for back-compat.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import benefit as benefit_lib
from repro.core import ledger as ledger_lib
from repro.core import plan as plan_lib
from repro.core import state as state_lib
from repro.core import threshold as threshold_lib
from repro.core.benefit import candidate_mask, restrict_benefits  # noqa: F401
from repro.core.combine import CombineParams
from repro.core.decision_table import DecisionTable
from repro.core.executor import EngineConfig, resolve_deprecated_driver, scan_capable
from repro.core.metrics import true_f_alpha
from repro.core.query import CompiledQuery


@dataclasses.dataclass(frozen=True)
class OperatorConfig:
    plan_size: int = 256
    epoch_cost_budget: Optional[float] = None  # None: plan_size alone bounds epochs
    alpha: float = 1.0
    answer_mode: str = "exact"  # "exact" | "approx"  (threshold selection)
    candidate_strategy: str = "auto"  # "outside_answer" (§4.1) | "all" | "auto"
    use_fused_kernel: bool = False  # route benefit through the Pallas kernel
    benefit_mode: str = "fast"  # "fast" (Eq. 11) | "exact_slow" (§6.3.3 default)
    function_selection: str = "table"  # "table" (paper) | "best" (beyond-paper)
    prior: float = 0.5
    chunk_size: Optional[int] = None  # scan dispatch granularity (see executor)


@dataclasses.dataclass
class EpochStats:
    epoch: int
    cost_spent: float
    expected_f: float
    answer_size: int
    true_f1: Optional[float]
    plan_cost: float
    plan_valid: int


class ProgressiveQueryOperator:
    """Drives progressive evaluation of one query over one object corpus."""

    def __init__(
        self,
        query: CompiledQuery,
        table: DecisionTable,
        combine_params: CombineParams,
        costs: jax.Array,  # [P, F]
        bank,  # TaggingBank: .execute(plan) -> [K] probs  (see repro.enrich)
        config: OperatorConfig = OperatorConfig(),
        truth_mask: Optional[jax.Array] = None,  # [N] bool ground truth (metrics only)
        benefit_fn: Optional[Callable] = None,  # override (e.g. Pallas fused kernel)
    ):
        self.query = query
        self.table = table
        self.combine_params = combine_params
        self.costs = jnp.asarray(costs, jnp.float32)
        self.bank = bank
        self.config = config
        self.truth_mask = truth_mask
        self._benefit_fn = benefit_fn
        self._plan_fn = jax.jit(self._plan_epoch)
        self._update_fn = jax.jit(self._apply_and_select)
        self._session = None  # lazily built (num_objects, EngineSession)

    # ---- session facade ------------------------------------------------------

    @property
    def _legacy_only(self) -> bool:
        """Query shapes the session's data-masked slots cannot express."""
        return (
            self._benefit_fn is not None
            or self.config.benefit_mode == "exact_slow"
            or not self.query.is_conjunctive
        )

    def _engine_config(self) -> EngineConfig:
        cfg = self.config
        return EngineConfig(
            plan_size=cfg.plan_size,
            epoch_cost_budget=cfg.epoch_cost_budget,
            alpha=cfg.alpha,
            answer_mode=cfg.answer_mode,
            candidate_strategy=cfg.candidate_strategy,
            function_selection=cfg.function_selection,
            prior=cfg.prior,
            chunk_size=cfg.chunk_size,
        )

    def _session_for(self, num_objects: int):
        from repro.core.session import EngineSession

        if self._session is None or self._session[0] != num_objects:
            # A traceable bank with no precomputed ``.outputs`` buffer (the
            # model-cascade bank) runs its forwards inside the fused superstep.
            traced_bank = (
                self.bank
                if scan_capable(self.bank) and not hasattr(self.bank, "outputs")
                else None
            )
            self._session = (
                num_objects,
                EngineSession(
                    self.query.predicates,
                    self.table,
                    self.combine_params,
                    self.costs,
                    capacity=num_objects,
                    max_tenants=1,
                    config=self._engine_config(),
                    truth_masks=(
                        None
                        if self.truth_mask is None
                        else jnp.asarray(self.truth_mask)[None]
                    ),
                    bank=traced_bank,
                ),
            )
        return self._session[1]

    def _to_session_state(self, st: state_lib.EnrichmentState, for_donation=False):
        """EnrichmentState -> one-tenant SessionState (pure re-labelling:
        capacity == N, the single slot covers every predicate column).  A
        state headed into a donating dispatch copies the bank-owned output
        buffer so donation can never invalidate it."""
        from repro.core.executor import SessionDerived, SessionState

        n, p = st.pred_prob.shape
        if hasattr(self.bank, "outputs"):
            outputs = jnp.asarray(self.bank.outputs, jnp.float32)
            if for_donation:
                outputs = jnp.array(outputs, copy=True)
        else:  # in-scan bank.execute: the buffer is never gathered
            outputs = jnp.full((n, p, self.costs.shape[1]), self.config.prior)
        quarantined = None
        avail = getattr(self.bank, "available", None)
        if avail is not None:  # ragged cascade: missing levels unplannable
            quarantined = ~jnp.asarray(avail, bool)
        return SessionState(
            substrate=st.substrate,
            derived=SessionDerived(
                pred_prob=st.pred_prob,
                uncertainty=st.uncertainty,
                joint_prob=st.joint_prob[None],
                in_answer=st.in_answer[None],
            ),
            bank_outputs=outputs,
            pred_mask=jnp.ones((1, p), bool),
            active=jnp.ones((1,), bool),
            num_rows=jnp.asarray(n, jnp.int32),
            ledger=ledger_lib.init_ledger(1),
            quarantined=quarantined,
        )

    def _from_session_state(self, sst) -> state_lib.EnrichmentState:
        sub = sst.substrate
        return state_lib.EnrichmentState(
            func_probs=sub.func_probs,
            exec_mask=sub.exec_mask,
            pred_prob=sst.derived.pred_prob,
            uncertainty=sst.derived.uncertainty,
            joint_prob=sst.derived.joint_prob[0],
            in_answer=sst.derived.in_answer[0],
            cost_spent=sub.cost_spent,
        )

    def _stats_from_session(self, hist) -> list:
        """SessionEpochStats [S=1] -> the operator's scalar EpochStats.
        ``plan_cost`` / ``plan_valid`` map to the charged cost / merged lane
        count: for one tenant every planned triple is new, so the budgeted
        request equals the charge — the pre-facade numbers."""
        out = []
        for h in hist:
            tf1 = h.true_f[0] if h.true_f is not None else None
            out.append(
                EpochStats(
                    epoch=h.epoch,
                    cost_spent=h.cost_spent,
                    expected_f=h.expected_f[0],
                    answer_size=h.answer_size[0],
                    true_f1=tf1,
                    plan_cost=h.epoch_cost,
                    plan_valid=h.merged_valid,
                )
            )
        return out

    # ---- legacy jitted stages (general ASTs / exact_slow / benefit_fn) -------

    def _select_answer(self, joint_prob: jax.Array) -> threshold_lib.AnswerSelection:
        if self.config.answer_mode == "approx":
            return threshold_lib.select_answer_approx(joint_prob, self.config.alpha)
        return threshold_lib.select_answer(joint_prob, self.config.alpha)

    def _plan_epoch(self, state: state_lib.EnrichmentState) -> plan_lib.Plan:
        cfg = self.config
        every = jnp.ones((state.num_objects,), bool)
        if self._benefit_fn is not None:
            benefits = self._benefit_fn(
                state, self.query, self.table, self.costs, candidate_mask=every
            )
        elif cfg.benefit_mode == "exact_slow":
            benefits = benefit_lib.benefit_exact_slow(
                state, self.query, self.table, self.costs, cfg.alpha, every
            )
        else:
            benefits = benefit_lib.compute_benefits(
                state, self.query, self.table, self.costs, every,
                function_selection=cfg.function_selection,
            )
        avail = getattr(self.bank, "available", None)
        if avail is not None:
            # Ragged cascade bank: missing (pred, level) pairs carry a
            # sentinel cost, but benefit/cost stays finite — mask them out.
            pi = jnp.arange(benefits.next_fn.shape[-1], dtype=jnp.int32)
            ok = jnp.asarray(avail, bool)[pi, jnp.maximum(benefits.next_fn, 0)]
            benefits = benefits._replace(
                benefit=jnp.where(ok, benefits.benefit, benefit_lib.NEG_INF)
            )
        cand = candidate_mask(state.uncertainty, state.in_answer, cfg.candidate_strategy)
        benefits = benefits._replace(
            benefit=restrict_benefits(benefits.benefit, cand, cfg.plan_size)
        )
        return plan_lib.select_plan(
            benefits, cfg.plan_size, self.costs, cfg.epoch_cost_budget
        )

    def _apply_and_select(
        self,
        state: state_lib.EnrichmentState,
        plan: plan_lib.Plan,
        outputs: jax.Array,  # [K] raw probabilities from the bank
    ):
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
        sel = self._select_answer(state.joint_prob)
        state = dataclasses.replace(state, in_answer=sel.mask)
        return state, sel

    # ---- public driver ------------------------------------------------------

    def init_state(self, num_objects: int) -> state_lib.EnrichmentState:
        st = state_lib.init_state(
            num_objects,
            self.query.num_predicates,
            self.costs.shape[1],
            prior=self.config.prior,
        )
        return state_lib.refresh_derived(st, self.query, self.combine_params,
                                         prior=self.config.prior)

    def warm_start(self, state, cached_probs, cached_mask):
        """Apply a previous query's cache (paper section 5 / Fig. 11)."""
        st = state_lib.with_cached_state(
            state, self.query, self.combine_params, cached_probs, cached_mask,
            prior=self.config.prior,
        )
        sel = self._select_answer(st.joint_prob)
        return dataclasses.replace(st, in_answer=sel.mask)

    def run_epoch(self, state: state_lib.EnrichmentState):
        t0 = time.perf_counter()
        plan = self._plan_fn(state)
        outputs = self.bank.execute(plan)
        state, sel = self._update_fn(state, plan, outputs)
        wall = time.perf_counter() - t0
        return state, sel, plan, wall

    def run_scan(
        self,
        num_objects: int,
        num_epochs: int,
        state: Optional[state_lib.EnrichmentState] = None,
        stop_when_exhausted: bool = True,
        chunk_size: Optional[int] = None,
    ) -> tuple[state_lib.EnrichmentState, list[EpochStats]]:
        """All epochs through the unified chunked-scan superstep (one
        ``EngineSession`` tenant at capacity == N; no per-epoch host syncs).
        Query shapes outside the session's scope (general ASTs, exact_slow,
        custom benefit_fn) fall back to the per-epoch loop with identical
        results.  Post-exhaustion epochs are no-ops trimmed from the
        history."""
        created_here = state is None
        if state is None:
            state = self.init_state(num_objects)
        if self._legacy_only or not scan_capable(self.bank):
            # General ASTs / exact_slow / custom benefit_fn — or an opaque
            # bank with no traceable execute — keep the per-epoch loop.
            return self._run_legacy_loop(state, num_epochs, stop_when_exhausted)
        session = self._session_for(num_objects)
        # donate states this run created (the pre-facade policy)
        donate = created_here
        sst, hist = session.program.run_scan(
            self._to_session_state(state, for_donation=donate),
            num_epochs,
            stop_when_exhausted=stop_when_exhausted,
            chunk_size=chunk_size,
            donate=donate,
        )
        return self._from_session_state(sst), self._stats_from_session(hist)

    def _run_legacy_loop(
        self, state, num_epochs: int, stop_when_exhausted: bool
    ) -> tuple[state_lib.EnrichmentState, list[EpochStats]]:
        history: list[EpochStats] = []
        for e in range(num_epochs):
            state, sel, plan, _ = self.run_epoch(state)
            tf1 = None
            if self.truth_mask is not None:
                tf1 = float(true_f_alpha(sel.mask, self.truth_mask, self.config.alpha))
            n_valid = int(plan.num_valid())
            history.append(
                EpochStats(
                    epoch=e,
                    cost_spent=float(state.cost_spent),
                    expected_f=float(sel.expected_f),
                    answer_size=int(sel.size),
                    true_f1=tf1,
                    plan_cost=float(plan.total_cost()),
                    plan_valid=n_valid,
                )
            )
            if stop_when_exhausted and n_valid == 0:
                break
        return state, history

    def run(
        self,
        num_objects: int,
        num_epochs: int,
        state: Optional[state_lib.EnrichmentState] = None,
        stop_when_exhausted: bool = True,
        driver: Optional[str] = None,  # DEPRECATED: run() routes itself
        chunk_size: Optional[int] = None,
    ) -> tuple[state_lib.EnrichmentState, list[EpochStats]]:
        """Progressive evaluation for ``num_epochs`` epochs: the unified
        scan superstep whenever the session facade can serve the query
        (conjunctive, default scoring) — with the loop driver substituted
        inside it for non-traceable banks — and the legacy per-epoch loop
        otherwise.  ``driver`` is a deprecated shim."""
        forced = resolve_deprecated_driver(driver)
        if forced == "loop" or self._legacy_only:
            if state is None:
                state = self.init_state(num_objects)
            return self._run_legacy_loop(state, num_epochs, stop_when_exhausted)
        return self.run_scan(
            num_objects, num_epochs, state=state,
            stop_when_exhausted=stop_when_exhausted, chunk_size=chunk_size,
        )
