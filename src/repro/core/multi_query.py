"""Multi-query batched PIQUE engine: Q concurrent queries, one shared corpus.

At serving scale the win comes from sharing enrichment across *concurrent*
consumers (IDEA, Wang & Carey 2019): most tenants' queries overlap on popular
predicates, so the same (object, predicate, function) triples keep getting
requested.  This engine runs Q queries in lockstep epochs over one
``SharedSubstrate`` with cross-query plan dedup — a triple is executed and
charged once no matter how many queries want it.

Since the executor unification, ``MultiQueryEngine`` is a thin facade over
``EngineSession`` at ``capacity == N`` with ``max_tenants == Q``: each
conjunctive query is one tenant slot (a predicate-column mask), and
``run`` / ``run_scan`` convert ``MultiQueryState`` at the boundary and
delegate to the shared ``core.executor.EpochProgram`` — the chunked
fused-scan superstep for traceable banks, the split-at-the-bank loop driver
for model cascades.  A legacy per-epoch path (``run_epoch`` + the jitted
``_plan_epoch`` / ``_apply_and_select`` stages) survives for general
(non-conjunctive) ASTs, which evaluate Python query structure the session's
data-masked slots cannot express, and as the serving layer's per-epoch
control-point API.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import benefit as benefit_lib
from repro.core import ledger as ledger_lib
from repro.core import plan as plan_lib
from repro.core import query as query_lib
from repro.core import state as state_lib
from repro.core import threshold as threshold_lib
from repro.core.benefit import (
    NEG_INF,
    TripleBenefits,
    candidate_mask,
    estimate_pred_prob_after,
    restrict_benefits,
)
from repro.core.combine import CombineParams, combine_probabilities
from repro.core.decision_table import DecisionTable
from repro.core.entropy import binary_entropy
from repro.core.executor import (  # noqa: F401  (select_plans_batched re-export)
    EngineConfig,
    resolve_deprecated_driver,
    scan_capable,
    select_plans_batched,
)
from repro.core.metrics import true_f_alpha
from repro.core.query import CompiledQuery
from repro.core.state import PerQueryState, SharedSubstrate

# Back-compat alias: one config type for every engine (see core.executor).
MultiQueryConfig = EngineConfig


# --------------------------------------------------------------- query set --


@dataclasses.dataclass(frozen=True)
class QuerySet:
    """Q compiled queries re-homed onto one global predicate space.

    ``pred_mask[q, j]`` says query q references global predicate column j;
    columns outside the mask never earn benefit for q and never contribute to
    its entropy statistics.  ``evaluate_batched`` maps ``[Q, ..., P]``
    predicate probabilities to ``[Q, ...]`` joint probabilities — a closed-form
    masked product when every query is conjunctive (the paper's Q1-Q5 shape),
    an unrolled per-query evaluation otherwise.

    ``unique_rows`` / ``unique_index`` group tenants whose reindexed query is
    IDENTICAL (multi-tenant traffic concentrates on hot queries, so U <<< Q
    at scale): derived per-query compute whose inputs are query + substrate
    only — Theorem-1 answer selection, candidate restriction — runs once per
    distinct query at [U, ...] and fans out by gather, bitwise identical to
    the Q-fold computation.
    """

    queries: tuple  # tuple[CompiledQuery] — original, local predicate spaces
    reindexed: tuple  # tuple[CompiledQuery] — global predicate space
    global_predicates: tuple  # tuple[Predicate]
    pred_mask: jax.Array  # [Q, P] bool
    all_conjunctive: bool
    unique_rows: jax.Array  # [U] int32: first tenant row of each distinct query
    unique_index: jax.Array  # [Q] int32: tenant row -> distinct-query group

    @property
    def num_queries(self) -> int:
        return len(self.queries)

    @property
    def num_predicates(self) -> int:
        return len(self.global_predicates)

    @property
    def num_unique(self) -> int:
        return self.unique_rows.shape[0]

    def evaluate_batched(self, pred_prob: jax.Array) -> jax.Array:
        """[Q, ..., P] predicate probabilities -> [Q, ...] joint probabilities."""
        if self.all_conjunctive:
            shape = (self.num_queries,) + (1,) * (pred_prob.ndim - 2) + (-1,)
            mask = self.pred_mask.reshape(shape)
            return jnp.prod(jnp.where(mask, pred_prob, 1.0), axis=-1)
        return jnp.stack(
            [q.evaluate(pred_prob[i]) for i, q in enumerate(self.reindexed)]
        )

    def add(self, query: CompiledQuery) -> "QuerySet":
        """Extend with one query whose predicates already exist in the space.

        The substrate's P axis is fixed at engine construction, so admission
        cannot grow the global space — build the initial set with every
        predicate the corpus supports (the corpus schema, not the current
        tenants) when late admission is expected.
        """
        self.check_admissible(query)
        return build_query_set(
            self.queries + (query,), global_predicates=self.global_predicates
        )

    def check_admissible(self, query: CompiledQuery) -> None:
        """Reject queries the compiled predicate space cannot serve, loudly.

        The substrate and every jitted stage are compiled at
        ``num_predicates`` columns; a query referencing predicates outside
        the space would otherwise surface as a shape/index error deep inside
        ``evaluate_batched``.  Raises ValueError naming the offending
        predicates and the fix (rebuild with the corpus schema).
        """
        missing = [p for p in query.predicates if p not in self.global_predicates]
        if missing:
            raise ValueError(
                f"query references {len(missing)} predicate(s) outside the "
                f"compiled global space (num_predicates={self.num_predicates}): "
                f"{missing}; the substrate's P axis is fixed at engine "
                "construction — build the initial QuerySet over the full "
                "corpus schema (global_predicates=...) to admit this query"
            )


def build_query_set(
    queries: Sequence[CompiledQuery],
    global_predicates: Optional[Sequence] = None,
) -> QuerySet:
    queries = tuple(queries)
    if global_predicates is None:
        global_predicates = query_lib.global_predicate_space(queries)
    global_predicates = tuple(global_predicates)
    reindexed = tuple(
        query_lib.reindex_query(q, global_predicates) for q in queries
    )
    p = len(global_predicates)
    index = {pred: j for j, pred in enumerate(global_predicates)}
    mask = jnp.zeros((len(queries), p), bool)
    for i, q in enumerate(queries):
        cols = jnp.asarray([index[pred] for pred in q.predicates], jnp.int32)
        mask = mask.at[i, cols].set(True)
    # group tenants by reindexed AST (frozen dataclasses: hashable, by-value)
    groups: dict = {}
    unique_rows: list = []
    unique_index: list = []
    for i, rq in enumerate(reindexed):
        g = groups.get(rq.ast)
        if g is None:
            g = groups[rq.ast] = len(unique_rows)
            unique_rows.append(i)
        unique_index.append(g)
    return QuerySet(
        queries=queries,
        reindexed=reindexed,
        global_predicates=global_predicates,
        pred_mask=mask,
        all_conjunctive=all(q.is_conjunctive for q in queries),
        unique_rows=jnp.asarray(unique_rows, jnp.int32),
        unique_index=jnp.asarray(unique_index, jnp.int32),
    )


# ------------------------------------------------------------ engine state --


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MultiQueryState:
    substrate: SharedSubstrate
    per_query: PerQueryState

    @property
    def num_queries(self) -> int:
        return self.per_query.num_queries

    @property
    def cost_spent(self) -> jax.Array:
        return self.substrate.cost_spent


@dataclasses.dataclass
class MultiEpochStats:
    epoch: int
    cost_spent: float  # cumulative substrate spend (shared across queries)
    epoch_cost: float  # cost newly charged this epoch (post-dedup)
    requested_cost: float  # sum of per-query plan costs before dedup
    expected_f: list  # [Q] per-query E(F_alpha)
    answer_size: list  # [Q]
    true_f: Optional[list]  # [Q] against ground truth, when available
    plan_valid: list  # [Q] valid triples each query requested
    merged_valid: int  # unique triples actually executed
    answer_mask: Optional[np.ndarray] = None  # [Q, N] when collect_masks

    @property
    def dedup_savings(self) -> float:
        """Cost the cross-query merge avoided this epoch."""
        return self.requested_cost - self.epoch_cost

    @property
    def mean_expected_f(self) -> float:
        return sum(self.expected_f) / max(len(self.expected_f), 1)


# ------------------------------------------------------------------ engine --


class MultiQueryEngine:
    """Lockstep progressive evaluation of Q queries over one shared corpus."""

    def __init__(
        self,
        query_set: QuerySet,
        table: DecisionTable,
        combine_params: CombineParams,
        costs: jax.Array,  # [P, F] over the GLOBAL predicate space
        bank,  # TaggingBank: .execute(plan) -> [K] probs
        config: EngineConfig = EngineConfig(),
        truth_masks: Optional[jax.Array] = None,  # [Q, N] bool (metrics only)
    ):
        if config.function_selection == "best" and not query_set.all_conjunctive:
            raise NotImplementedError(
                "function_selection='best' requires an all-conjunctive query set"
            )
        if config.backend == "pallas" and not query_set.all_conjunctive:
            raise NotImplementedError(
                "backend='pallas' covers the conjunctive fast path only"
            )
        if config.backend not in ("jnp", "pallas"):
            raise ValueError(f"unknown backend: {config.backend!r}")
        if config.num_shards < 1:
            raise ValueError("num_shards must be >= 1")
        self.query_set = query_set
        self.table = table
        self.combine_params = combine_params
        self.costs = jnp.asarray(costs, jnp.float32)
        self.bank = bank
        self.config = config
        self.truth_masks = truth_masks
        self._plan_fn = jax.jit(self._plan_epoch)
        self._update_fn = jax.jit(self._apply_and_select)
        self._session = None  # lazily built (num_objects, EngineSession)

    # ---- session facade ------------------------------------------------------

    def _session_for(self, num_objects: int):
        from repro.core.session import EngineSession

        if self._session is None or self._session[0] != num_objects:
            # A traceable bank with no precomputed ``.outputs`` buffer (the
            # model-cascade bank) is wired into the session so its forwards
            # run inside the fused superstep.
            traced_bank = (
                self.bank
                if scan_capable(self.bank) and not hasattr(self.bank, "outputs")
                else None
            )
            self._session = (
                num_objects,
                EngineSession(
                    self.query_set.global_predicates,
                    self.table,
                    self.combine_params,
                    self.costs,
                    capacity=num_objects,
                    max_tenants=self.query_set.num_queries,
                    config=self.config,
                    truth_masks=self.truth_masks,  # per-slot true-F on device
                    bank=traced_bank,
                ),
            )
        return self._session[1]

    def _to_session_state(self, state: MultiQueryState, for_donation: bool = False):
        """MultiQueryState -> SessionState at capacity == N, every slot active.

        Pure re-labelling: the substrate passes through, the Q-broadcast
        derived leaves collapse to their shared [N, P] row, and the query
        set's predicate masks become the tenant-slot masks.  A state headed
        into a donating dispatch copies the leaves that alias engine-owned
        buffers (bank outputs, query-set masks) so donation can never
        invalidate them.
        """
        from repro.core.executor import SessionDerived, SessionState

        q = self.query_set.num_queries
        n = state.substrate.num_objects
        if hasattr(self.bank, "outputs"):
            outputs = jnp.asarray(self.bank.outputs, jnp.float32)
        else:  # in-scan bank.execute: the buffer is never gathered
            outputs = jnp.full(
                (n, self.query_set.num_predicates, self.costs.shape[1]),
                self.config.prior,
                jnp.float32,
            )
        quarantined = None
        avail = getattr(self.bank, "available", None)
        if avail is not None:  # ragged cascade: missing levels unplannable
            quarantined = ~jnp.asarray(avail, bool)
        pred_mask = self.query_set.pred_mask
        if for_donation:
            outputs = jnp.array(outputs, copy=True)
            pred_mask = jnp.array(pred_mask, copy=True)
        return SessionState(
            substrate=state.substrate,
            derived=SessionDerived(
                pred_prob=state.per_query.pred_prob[0],
                uncertainty=state.per_query.uncertainty[0],
                joint_prob=state.per_query.joint_prob,
                in_answer=state.per_query.in_answer,
            ),
            bank_outputs=outputs,
            pred_mask=pred_mask,
            active=jnp.ones((q,), bool),
            num_rows=jnp.asarray(n, jnp.int32),
            ledger=ledger_lib.init_ledger(q),
            quarantined=quarantined,
        )

    def _from_session_state(self, sst) -> MultiQueryState:
        q = self.query_set.num_queries
        shape = (q,) + sst.derived.pred_prob.shape
        return MultiQueryState(
            substrate=sst.substrate,
            per_query=PerQueryState(
                pred_prob=jnp.broadcast_to(sst.derived.pred_prob[None], shape),
                uncertainty=jnp.broadcast_to(sst.derived.uncertainty[None], shape),
                joint_prob=sst.derived.joint_prob,
                in_answer=sst.derived.in_answer,
            ),
        )

    def _stats_from_session(self, hist, collect_masks: bool) -> list:
        out = []
        for h in hist:
            tf = h.true_f  # computed on-device by the superstep, [S] floats
            out.append(
                MultiEpochStats(
                    epoch=h.epoch,
                    cost_spent=h.cost_spent,
                    epoch_cost=h.epoch_cost,
                    requested_cost=h.requested_cost,
                    expected_f=h.expected_f,
                    answer_size=h.answer_size,
                    true_f=tf,
                    plan_valid=h.plan_valid,
                    merged_valid=h.merged_valid,
                    answer_mask=h.answer_mask if collect_masks else None,
                )
            )
        return out

    # ---- derived-state maintenance (legacy per-epoch path) -------------------

    def _derive(self, substrate: SharedSubstrate) -> tuple[jax.Array, ...]:
        """Shared recombination + batched joint: the fan-out step.

        ``pred_prob`` / ``uncertainty`` are query-independent under shared
        combine params, so they are computed once and broadcast onto the Q
        axis; only the joint probability differs per query.
        """
        q = self.query_set.num_queries
        pred_prob = combine_probabilities(
            self.combine_params,
            substrate.func_probs,
            substrate.exec_mask,
            prior=self.config.prior,
        )  # [N, P]
        pp_q = jnp.broadcast_to(pred_prob[None], (q,) + pred_prob.shape)
        unc_q = jnp.broadcast_to(binary_entropy(pred_prob)[None], pp_q.shape)
        joint = self.query_set.evaluate_batched(pp_q)  # [Q, N]
        return pp_q, unc_q, joint

    def _select_answers(self, joint_prob: jax.Array) -> threshold_lib.AnswerSelection:
        """Theorem-1 selection per DISTINCT query, fanned out to tenants.

        Selection depends only on the query's joint probabilities, which are
        identical for duplicate tenants, so the per-query sort (the epoch's
        costliest reduction) runs U times, not Q times — bitwise identical to
        the Q-fold vmap by construction.
        """
        if self.config.answer_mode == "approx":
            fn = functools.partial(
                threshold_lib.select_answer_approx, alpha=self.config.alpha
            )
        else:
            fn = functools.partial(threshold_lib.select_answer, alpha=self.config.alpha)
        qs = self.query_set
        sel_u = jax.vmap(fn)(joint_prob[qs.unique_rows])
        return jax.tree.map(lambda x: x[qs.unique_index], sel_u)

    def init_state(self, num_objects: int) -> MultiQueryState:
        if self.config.num_shards > 1 and num_objects % self.config.num_shards:
            raise ValueError(
                f"num_objects={num_objects} must divide evenly over "
                f"num_shards={self.config.num_shards}"
            )
        sub = state_lib.init_substrate(
            num_objects,
            self.query_set.num_predicates,
            self.costs.shape[1],
            prior=self.config.prior,
        )
        pp, unc, joint = self._derive(sub)
        sel = self._select_answers(joint)
        return MultiQueryState(
            substrate=sub,
            per_query=PerQueryState(
                pred_prob=pp, uncertainty=unc, joint_prob=joint, in_answer=sel.mask
            ),
        )

    def warm_start(
        self,
        state: MultiQueryState,
        cached_probs: jax.Array,  # [N, P, F]
        cached_mask: jax.Array,  # [N, P, F] bool
    ) -> MultiQueryState:
        """Merge a pre-executed cache into the substrate (paper §6.1
        Initialization Step / §5 caching) and re-derive every query's state."""
        sub = state.substrate
        merged_mask = sub.exec_mask | cached_mask
        merged_probs = jnp.where(cached_mask, cached_probs, sub.func_probs)
        sub = SharedSubstrate(
            func_probs=merged_probs, exec_mask=merged_mask, cost_spent=sub.cost_spent
        )
        pp, unc, joint = self._derive(sub)
        sel = self._select_answers(joint)
        return MultiQueryState(
            substrate=sub,
            per_query=PerQueryState(
                pred_prob=pp, uncertainty=unc, joint_prob=joint, in_answer=sel.mask
            ),
        )

    def admit(
        self,
        state: MultiQueryState,
        query: CompiledQuery,
        truth_mask: Optional[jax.Array] = None,
    ) -> MultiQueryState:
        """Admit a new tenant mid-flight, warm-started from the substrate.

        Routes through ``state.with_cached_state`` with the substrate as the
        cache (paper §5): the query's first answer set already reflects every
        enrichment earlier tenants paid for.  Q grows by one, which re-traces
        the jitted stages at the new shape (``core.session.EngineSession``
        admits into pre-allocated slots without retracing).
        """
        self.query_set.check_admissible(query)
        if (
            self.config.function_selection == "best"
            or self.config.backend == "pallas"
        ) and not query.is_conjunctive:
            raise NotImplementedError(
                "function_selection='best' / backend='pallas' require an "
                "all-conjunctive query set"
            )
        if (self.truth_masks is not None) != (truth_mask is not None):
            raise ValueError(
                "admit(): truth_mask must be provided iff the engine tracks "
                "truth_masks (construct the engine without them to opt out)"
            )
        rq = query_lib.reindex_query(query, self.query_set.global_predicates)
        sub = state.substrate
        fresh = state_lib.init_state(
            sub.num_objects,
            self.query_set.num_predicates,
            sub.num_functions,
            prior=self.config.prior,
        )
        warm = state_lib.with_cached_state(
            fresh, rq, self.combine_params, sub.func_probs, sub.exec_mask,
            prior=self.config.prior,
        )
        if self.config.answer_mode == "approx":
            sel = threshold_lib.select_answer_approx(warm.joint_prob, self.config.alpha)
        else:
            sel = threshold_lib.select_answer(warm.joint_prob, self.config.alpha)
        self.query_set = self.query_set.add(query)
        per = state.per_query
        new_per = PerQueryState(
            pred_prob=jnp.concatenate([per.pred_prob, warm.pred_prob[None]]),
            uncertainty=jnp.concatenate([per.uncertainty, warm.uncertainty[None]]),
            joint_prob=jnp.concatenate([per.joint_prob, warm.joint_prob[None]]),
            in_answer=jnp.concatenate([per.in_answer, sel.mask[None]]),
        )
        if self.truth_masks is not None:
            self.truth_masks = jnp.concatenate([self.truth_masks, truth_mask[None]])
        self._plan_fn = jax.jit(self._plan_epoch)
        self._update_fn = jax.jit(self._apply_and_select)
        self._session = None  # stale Q-shaped facade session dropped
        return MultiQueryState(substrate=sub, per_query=new_per)

    # ---- legacy jitted stages (general ASTs + per-epoch serving API) ---------

    def _benefits_batched(self, state: MultiQueryState) -> TripleBenefits:
        """Vectorized Eq. 11 with [Q, N, P] leaves over the global space.

        The decision-table lookup keys on the *shared* exec bitmask — a triple
        executed for query A is "already run" for query B (write-once
        semantics surfacing in planning).  Columns outside a query's
        ``pred_mask`` earn -inf so no tenant pays for predicates it never
        asked about.

        Conjunctive query sets route through the shared-substrate fast path
        (``benefit.compute_benefits_batched`` or the fused Pallas kernel per
        ``config.backend``); general ASTs re-evaluate per query with one
        substituted column.
        """
        cfg = self.config
        sub = state.substrate
        per = state.per_query
        n, p = sub.num_objects, sub.num_predicates
        state_id = sub.state_id()  # [N, P] shared
        pred_mask = self.query_set.pred_mask  # [Q, P]

        if self.query_set.all_conjunctive:
            mode = (
                "best"
                if cfg.function_selection == "best"
                and self.table.delta_h_all is not None
                else "table"
            )
            if cfg.backend == "pallas":
                from repro.kernels.enrich_score import ops as es_ops

                tb = es_ops.fused_benefits_batched(
                    per.pred_prob[0], per.uncertainty[0], state_id,
                    per.joint_prob, self.table, self.costs,
                    function_selection=mode,
                    interpret=cfg.pallas_interpret,
                )
            else:
                tb = benefit_lib.compute_benefits_batched(
                    per.pred_prob[0], per.uncertainty[0], state_id,
                    per.joint_prob, self.table, self.costs,
                    function_selection=mode,
                )
            benefit, nf, est_joint = tb
        else:
            # General ASTs: per-query column-substitution re-evaluation.
            pred_idx = jnp.broadcast_to(
                jnp.arange(p, dtype=jnp.int32)[None], (n, p)
            )
            nf, dh = self.table.lookup(pred_idx, state_id, per.uncertainty)
            _, p_hat = estimate_pred_prob_after(per.pred_prob, dh)
            est_joint = jnp.stack(
                [
                    jnp.stack(
                        [
                            rq.evaluate_with_column(
                                per.pred_prob[i], c, p_hat[i, :, c]
                            )
                            for c in range(p)
                        ],
                        axis=-1,
                    )
                    for i, rq in enumerate(self.query_set.reindexed)
                ]
            )
            est_joint = jnp.clip(est_joint, 0.0, 1.0)
            cost = benefit_lib.function_cost(self.costs, pred_idx, nf)  # [Q, N, P]
            benefit = per.joint_prob[..., None] * est_joint / cost  # Eq. 11

        valid = (nf >= 0) & pred_mask[:, None, :]
        avail = getattr(self.bank, "available", None)
        if avail is not None:
            # Ragged cascade bank: a missing (pred, level) pair carries a
            # sentinel cost, but benefit/cost is still finite — mask it out
            # so the short cascade can never plan a level it does not have.
            pi = jnp.arange(p, dtype=jnp.int32)
            valid = valid & jnp.asarray(avail, bool)[pi, jnp.maximum(nf, 0)]
        benefit = jnp.where(valid, benefit, NEG_INF)

        # Candidate restriction per DISTINCT query (its inputs — uncertainty,
        # answer membership, pred_mask — are identical for duplicate tenants),
        # fanned back out by gather; kills the per-tenant median sorts of the
        # "auto" strategy under hot-query traffic.
        ui, inv = self.query_set.unique_rows, self.query_set.unique_index
        cand_u = jax.vmap(
            lambda u, a, m: candidate_mask(
                u, a, cfg.candidate_strategy, pred_mask=m
            )
        )(per.uncertainty[ui], per.in_answer[ui], pred_mask[ui])  # [U, N]
        cand = cand_u[inv]  # [Q, N]
        benefit = jax.vmap(
            lambda b, c: restrict_benefits(b, c, cfg.plan_size)
        )(benefit, cand)
        return TripleBenefits(benefit=benefit, next_fn=nf, est_joint=est_joint)

    def _plan_epoch(self, state: MultiQueryState) -> tuple[plan_lib.Plan, plan_lib.Plan]:
        """-> (per-query plans [Q, K], merged deduplicated plan [M])."""
        cfg = self.config
        benefits = self._benefits_batched(state)
        plans = select_plans_batched(
            benefits,
            plan_size=cfg.plan_size,
            num_shards=cfg.num_shards,
            num_predicates=self.query_set.num_predicates,
            costs=self.costs,
        )
        merged = plan_lib.merge_plans_dedup(
            plans,
            self.query_set.num_predicates,
            self.costs.shape[1],
            capacity=cfg.merged_capacity,
            cost_budget=cfg.epoch_cost_budget,
            num_objects=state.substrate.num_objects,
        )
        return plans, merged

    def _apply_and_select(
        self,
        state: MultiQueryState,
        merged: plan_lib.Plan,
        outputs: jax.Array,  # [M] raw probabilities from the bank
    ):
        sub = state_lib.apply_outputs_to_substrate(
            state.substrate,
            merged.object_idx,
            merged.pred_idx,
            merged.func_idx,
            outputs,
            merged.cost,
            merged.valid,
        )
        pp, unc, joint = self._derive(sub)
        sel = self._select_answers(joint)
        per = PerQueryState(
            pred_prob=pp, uncertainty=unc, joint_prob=joint, in_answer=sel.mask
        )
        return MultiQueryState(substrate=sub, per_query=per), sel

    # ---- public drivers ------------------------------------------------------

    def run_epoch(self, state: MultiQueryState):
        t0 = time.perf_counter()
        plans, merged = self._plan_fn(state)
        outputs = self.bank.execute(merged)
        prev_cost = float(state.substrate.cost_spent)
        state, sel = self._update_fn(state, merged, outputs)
        wall = time.perf_counter() - t0
        return state, sel, plans, merged, wall, prev_cost

    def run_scan(
        self,
        num_objects: int,
        num_epochs: int,
        state: Optional[MultiQueryState] = None,
        stop_when_exhausted: bool = True,
        collect_masks: bool = False,
        chunk_size: Optional[int] = None,
    ) -> tuple[MultiQueryState, list]:
        """Run ``num_epochs`` epochs through the unified chunked-scan
        superstep (an ``EngineSession`` at capacity == N; per-epoch stats
        accumulate on-device, one host sync at the end).

        Non-conjunctive query sets fall back to the legacy per-epoch loop
        with identical results (general ASTs are outside the session's
        data-masked slot model).  Post-exhaustion epochs are no-ops trimmed
        from the history.
        """
        created_here = state is None
        if state is None:
            state = self.init_state(num_objects)
        if not self.query_set.all_conjunctive:
            return self._run_legacy_loop(
                state, num_epochs, stop_when_exhausted
            )
        if not scan_capable(self.bank):
            # Opaque banks (no traceable execute, no outputs buffer) keep the
            # pre-facade per-epoch loop: jitted plan half, host bank.execute,
            # jitted apply half.
            return self._run_legacy_loop(
                state, num_epochs, stop_when_exhausted,
                collect_masks=collect_masks,
            )
        session = self._session_for(num_objects)
        # donate states this run created (the pre-facade policy): XLA
        # updates the [N, P, F] tensors in place across the run
        donate = created_here
        sst, hist = session.program.run_scan(
            self._to_session_state(state, for_donation=donate),
            num_epochs, collect_masks=collect_masks,
            stop_when_exhausted=stop_when_exhausted, chunk_size=chunk_size,
            donate=donate,
        )
        return (
            self._from_session_state(sst),
            self._stats_from_session(hist, collect_masks),
        )

    def _run_legacy_loop(
        self,
        state: MultiQueryState,
        num_epochs: int,
        stop_when_exhausted: bool,
        collect_masks: bool = False,
    ) -> tuple[MultiQueryState, list]:
        history: list[MultiEpochStats] = []
        for e in range(num_epochs):
            state, sel, plans, merged, _, prev_cost = self.run_epoch(state)
            tf = None
            if self.truth_masks is not None:
                tf = [
                    float(true_f_alpha(sel.mask[i], self.truth_masks[i], self.config.alpha))
                    for i in range(state.num_queries)
                ]
            merged_valid = int(merged.num_valid())
            history.append(
                MultiEpochStats(
                    epoch=e,
                    cost_spent=float(state.substrate.cost_spent),
                    epoch_cost=float(state.substrate.cost_spent) - prev_cost,
                    requested_cost=float(
                        jnp.sum(jnp.where(plans.valid, plans.cost, 0.0))
                    ),
                    expected_f=[float(x) for x in sel.expected_f],
                    answer_size=[int(x) for x in sel.size],
                    true_f=tf,
                    plan_valid=[int(x) for x in jnp.sum(plans.valid, axis=1)],
                    merged_valid=merged_valid,
                    answer_mask=(
                        np.asarray(sel.mask) if collect_masks else None
                    ),
                )
            )
            if stop_when_exhausted and merged_valid == 0:
                break
        return state, history

    def run(
        self,
        num_objects: int,
        num_epochs: int,
        state: Optional[MultiQueryState] = None,
        stop_when_exhausted: bool = True,
        driver: Optional[str] = None,  # DEPRECATED: run() routes itself
        chunk_size: Optional[int] = None,
    ) -> tuple[MultiQueryState, list]:
        """Progressive evaluation for ``num_epochs`` epochs.

        Routes to the unified scan superstep whenever the session facade can
        serve the query set (all-conjunctive) — with the loop driver
        substituted inside it for non-traceable banks — and to the legacy
        per-epoch loop otherwise.  ``driver`` is a deprecated shim.
        """
        forced = resolve_deprecated_driver(driver)
        if forced == "loop" or not self.query_set.all_conjunctive:
            if state is None:
                state = self.init_state(num_objects)
            return self._run_legacy_loop(state, num_epochs, stop_when_exhausted)
        return self.run_scan(
            num_objects, num_epochs, state=state,
            stop_when_exhausted=stop_when_exhausted, chunk_size=chunk_size,
        )
