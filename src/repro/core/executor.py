"""The unified epoch executor: ONE superstep for every engine generation.

This repo grew three incarnations of the paper's progressive integrated
operator — ``ProgressiveQueryOperator`` (one query), ``MultiQueryEngine``
(Q lockstep queries), ``EngineSession`` (long-lived churn-stable serving) —
whose plan -> execute -> apply drivers were duplicated per engine and held
equivalent only by parity tests.  ``EpochProgram`` is the collapse: it owns
the fused scan superstep over the session-shaped state (capacity-padded
substrate, tenant slots, ledger update, sharded plan merge) and BOTH drivers:

* **chunked scan** — ``run_scan`` dispatches the jitted ``lax.scan``
  superstep in ``chunk_size``-epoch chunks instead of one monolithic scan.
  Chunking is bitwise inert (the scan carry crosses chunk boundaries
  unchanged, each chunk runs the same compiled body) and makes the compiled
  program length-stable: every run length amortizes onto the same
  chunk-length program instead of tracing one scan per distinct epoch
  count, and chunk boundaries are where a host can apply staged churn
  events while the previous chunk is still in flight
  (``session.SessionPipeline``).  Dispatch never blocks; the single host
  sync happens at history materialization.
The bank boundary inside the superstep takes one of two traceable forms:
banks publishing a precomputed ``.outputs`` tensor (the simulated bank) are
gathered from the session-carried capacity-padded buffer, and banks passed
to the program as ``bank=`` (the model-cascade bank) have their pure-JAX
``execute_with_stats(plan, params) -> (probabilities, stats)`` traced
straight into the scan body — real model forwards with zero host
round-trips per epoch.  The old per-epoch loop driver
(``run_loop``: jitted plan half, host ``bank.execute``, jitted apply half)
is GONE; after the cascade bank became traceable nothing needed it.

``ProgressiveQueryOperator`` and ``MultiQueryEngine`` are now thin facades
over ``EngineSession`` (one tenant / capacity == N respectively), which owns
an ``EpochProgram``; their legacy per-epoch paths survive only for query
shapes the session's data-masked slots cannot express (general ASTs,
``benefit_mode="exact_slow"``, custom benefit overrides) and for opaque
banks that hide ``supports_scan``.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import benefit as benefit_lib
from repro.core import ledger as ledger_lib
from repro.core import plan as plan_lib
from repro.core import state as state_lib
from repro.core import threshold as threshold_lib
from repro.core import tracing
from repro.core.benefit import NEG_INF, TripleBenefits
from repro.core.combine import combine_probabilities
from repro.core.entropy import binary_entropy
from repro.core.ledger import CostLedger
from repro.core.metrics import true_f_alpha
from repro.core.state import SharedSubstrate


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Shared engine configuration (the former ``MultiQueryConfig``)."""

    plan_size: int = 256  # per-query plan capacity
    merged_capacity: Optional[int] = None  # None: Q * plan_size (lossless merge)
    epoch_cost_budget: Optional[float] = None  # applied to the merged plan
    alpha: float = 1.0
    answer_mode: str = "exact"  # "exact" | "approx"
    candidate_strategy: str = "auto"  # "outside_answer" | "all" | "auto"
    function_selection: str = "table"  # "table" (paper) | "best" (beyond-paper)
    prior: float = 0.5
    backend: str = "jnp"  # "jnp" | "pallas" (fused batched scoring kernel)
    # True runs the Pallas kernels in the interpreter (hosts without a TPU);
    # the default compiles them for the device, never silently falling back
    pallas_interpret: bool = False
    # >1: plan selection runs hierarchically over this many object shards
    # (per-shard top-k + exact cross-shard merge), byte-identical to the
    # unsharded path; the emulated-shard program is what each ("pod", "data")
    # mesh device runs under shard_map at pod scale.
    num_shards: int = 1
    # scan dispatch granularity: run_scan scans chunk_size epochs per device
    # dispatch (None: the whole run in one scan).  Bitwise inert; chunk
    # boundaries are where staged churn events overlap in-flight compute.
    chunk_size: Optional[int] = None
    # storage dtype of func_probs / bank_outputs / derived state ("float32" |
    # "bfloat16").  bf16 halves substrate HBM and ingest transfer bytes at
    # million-row capacity; ALL arithmetic (combine, entropy, Eq. 11 scoring,
    # answer selection) still runs in f32 — storage is upcast at the consumer
    # (in-register inside the Pallas tiles), so a bf16 session is exact w.r.t.
    # its stored values, and the f32 default is bitwise-identical to before
    # this knob existed.  cost_spent / ledger stay f32 unconditionally.
    substrate_dtype: str = "float32"


# Back-compat alias: every engine now shares one config type.
MultiQueryConfig = EngineConfig

_SUBSTRATE_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def resolve_substrate_dtype(name: str):
    """Map ``EngineConfig.substrate_dtype`` to a jnp dtype (typed rejection).

    The config field is a *string* so ``EngineConfig`` stays hashable /
    serializable (checkpoint meta, scan-cache keys); this is the one place
    the string becomes a dtype.
    """
    try:
        return _SUBSTRATE_DTYPES[name]
    except KeyError:
        raise ValueError(
            f"substrate_dtype must be one of {sorted(_SUBSTRATE_DTYPES)}, got {name!r}"
        ) from None


def scan_capable(bank) -> bool:
    """Can this bank's ``execute`` be traced into the fused scan superstep?"""
    return bool(getattr(bank, "supports_scan", False))


def resolve_deprecated_driver(driver: Optional[str]) -> Optional[str]:
    """The old ``run(driver=...)`` kwarg, kept as a warning shim.

    ``run()`` now routes by bank traceability and query shape in one place;
    passing ``driver`` explicitly is deprecated.  Returns the normalized
    driver ("scan" | "loop" | None for auto) or raises on unknown values.
    """
    if driver is None:
        return None
    warnings.warn(
        "run(driver=...) is deprecated: run() routes to the fused scan "
        "superstep when the bank is traceable and to the per-epoch loop "
        "otherwise; call run_scan() directly for an explicit scan",
        DeprecationWarning,
        stacklevel=3,
    )
    if driver == "auto":
        return None
    if driver in ("scan", "loop"):
        return driver
    raise ValueError(f"unknown driver: {driver!r}")


def select_plans_batched(
    benefits: TripleBenefits,  # [Q, N, P] leaves
    plan_size: int,
    num_shards: int,
    num_predicates: int,
    costs: jax.Array,  # [P, F]
) -> plan_lib.Plan:
    """Per-query plan selection, optionally sharded over the object axis.

    With ``num_shards=S``: every shard top-ks its own [N/S, P] slice (the
    per-device program under a ("pod", "data") shard_map — emulated here
    with a reshape + vmap, which lowers to the identical local compute),
    then the survivors reduce through the EXACT cross-shard merge, so the
    result is byte-identical to the unsharded top-k on every valid lane.
    """
    sel = functools.partial(plan_lib.select_plan, plan_size=plan_size, costs=costs)
    if num_shards <= 1:
        return jax.vmap(sel)(benefits)
    s = num_shards
    q, n, p = benefits.benefit.shape
    per_shard = n // s

    def reshard(x):  # [Q, N, P] -> [S, Q, N/S, P]
        return x.reshape(q, s, per_shard, p).transpose(1, 0, 2, 3)

    local = TripleBenefits(*(reshard(x) for x in benefits))
    local_plans = jax.vmap(jax.vmap(sel))(local)  # [S, Q, K]
    offsets = (jnp.arange(s, dtype=jnp.int32) * per_shard)[:, None, None]
    local_plans = local_plans._replace(
        object_idx=local_plans.object_idx + offsets
    )
    by_query = jax.tree.map(
        lambda x: x.transpose(1, 0, 2), local_plans
    )  # [Q, S, K]
    return jax.vmap(
        functools.partial(
            plan_lib.merge_sharded_plans_exact,
            plan_size=plan_size,
            num_predicates=num_predicates,
        )
    )(by_query)


# --------------------------------------------------------- session state --


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SessionDerived:
    """Derived state with the slot-independent half stored ONCE.

    Under shared combine params ``pred_prob`` / ``uncertainty`` are facts
    about the substrate, identical for every slot; the state stores the
    [C, P] half once and broadcasts only at use sites.  Only the joint
    probability and answer membership actually vary per slot.
    """

    pred_prob: jax.Array  # [C, P] substrate dtype, shared across slots
    uncertainty: jax.Array  # [C, P] substrate dtype, shared across slots
    joint_prob: jax.Array  # [S, C] substrate dtype
    in_answer: jax.Array  # [S, C] bool


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SessionState:
    """Everything churn can touch, as fixed-shape arrays (the scan carry)."""

    substrate: SharedSubstrate  # [C, P, F] capacity-padded
    derived: SessionDerived  # [C, P] shared + [S, C] per-slot derived state
    bank_outputs: jax.Array  # [C, P, F] capacity-padded tagging outputs
    pred_mask: jax.Array  # [S, P] bool: slot s's conjunctive predicate columns
    active: jax.Array  # [S] bool: slot occupancy
    num_rows: jax.Array  # [] int32: rows [0, num_rows) hold real objects
    ledger: CostLedger  # [S] per-tenant attributed cost
    # [P, F] bool: quarantined enrichment functions are OR-ed into the
    # decision-table state id, so plan selection skips their triples exactly
    # like already-executed ones — a pure data update (no retrace), the same
    # mechanism as tenant-slot masks.  None (the facades) means no quarantine
    # channel at all; the session layer always carries the array.
    quarantined: Optional[jax.Array] = None

    @property
    def capacity(self) -> int:
        return self.substrate.num_objects

    @property
    def num_slots(self) -> int:
        return self.pred_mask.shape[0]

    @property
    def cost_spent(self) -> jax.Array:
        return self.substrate.cost_spent

    def row_valid(self) -> jax.Array:
        return state_lib.row_validity(self.capacity, self.num_rows)


@dataclasses.dataclass
class SessionEpochStats:
    epoch: int
    cost_spent: float  # cumulative substrate spend
    epoch_cost: float  # newly charged this epoch (post-dedup)
    requested_cost: float  # sum of per-slot plan costs before dedup
    expected_f: list  # [S] per-slot E(F_alpha) (inactive slots: 0)
    answer_size: list  # [S]
    plan_valid: list  # [S]
    merged_valid: int
    active: list  # [S] bool snapshot
    num_rows: int
    attributed: list  # [S] cumulative ledger attribution snapshot
    level_lanes: list  # [F] valid merged lanes per function level
    expert_load: float  # the trunk's busiest expert over the mean; 0 where no expert ran
    answer_mask: Optional[np.ndarray] = None  # [S, C] when collect_masks
    true_f: Optional[list] = None  # [S] when the program carries truth_masks

    @property
    def active_tenants(self) -> int:
        return int(sum(self.active))

    @property
    def mean_expected_f(self) -> float:
        """Mean E(F) over ACTIVE slots (0 when the session idles)."""
        vals = [f for f, a in zip(self.expected_f, self.active) if a]
        return sum(vals) / len(vals) if vals else 0.0


# ----------------------------------------------------------- the program --


class EpochProgram:
    """The fused plan -> execute -> apply superstep and both its drivers.

    Operates on ``SessionState`` — the one state layout every engine
    generation now shares (capacity-padded substrate + tenant-slot masks).
    Shapes are read off the state arrays, never off ``self``, so one program
    serves every capacity tier of a growing session; the scan cache is keyed
    on (tier capacity, chunk length, collect_masks) and ``superstep_traces``
    counts body traces — the churn-stability and bounded-recompile witness.
    """

    def __init__(
        self,
        table,
        combine_params,
        costs: jax.Array,
        config: EngineConfig,
        truth_masks: Optional[jax.Array] = None,  # [S, C] bool (metrics only)
        bank=None,  # traceable bank whose execute_with_stats runs INSIDE the superstep
    ):
        self.table = table
        self.combine_params = combine_params
        self.costs = jnp.asarray(costs, jnp.float32)
        self.config = config
        # When a bank is attached, the superstep calls
        # ``bank.execute_with_stats(merged, params)`` in-trace with the
        # bank's ``params`` pytree (features, model weights) passed to the
        # compiled scan as an ARGUMENT, never baked in as constants; when
        # absent, outputs gather from the state-carried ``bank_outputs``
        # buffer (banks publishing a precomputed ``.outputs`` tensor).
        self.bank = bank
        if bank is not None and not scan_capable(bank):
            raise ValueError(
                "EpochProgram(bank=...) requires a traceable bank "
                "(supports_scan == True); opaque banks go through the "
                "facades' legacy per-epoch loop"
            )
        # ground-truth answer masks, one row per slot: when present the
        # superstep reports per-slot true F-alpha ON DEVICE ([S] floats per
        # epoch), so truth tracking never forces answer-mask collection.
        # Shapes must match (num_slots, capacity) — the facades' fixed-
        # capacity regime; growing sessions don't carry truth.
        self.truth_masks = None if truth_masks is None else jnp.asarray(truth_masks)
        self._trace_count = 0  # superstep (re)traces
        self._scan_cache: dict = {}
        # a jitted function's name is its module's name, which keys JAX's
        # persistent compilation cache beside the computation, while op
        # metadata does not: a program whose scopes change and whose
        # computation does not takes a new name, or the cache serves it an
        # executable that carries the old names (see compiled_hlo)
        self._refresh_fn = jax.jit(self._refresh_program)
        # argument shapes of each program's first dispatch, for compiled_hlo
        self._scan_args: dict = {}  # scan-cache key -> (state, bank_params)
        self._refresh_args: dict = {}  # capacity -> (state,)

    @property
    def num_predicates(self) -> int:
        return self.costs.shape[0]

    @property
    def num_functions(self) -> int:
        return self.costs.shape[1]

    @property
    def bank_params(self):
        """The attached bank's arrays, a scan argument (None without a bank)."""
        return None if self.bank is None else self.bank.params

    @property
    def superstep_traces(self) -> int:
        """How many times the scan superstep body has been traced."""
        return self._trace_count

    # ---- derived-state maintenance ----------------------------------------

    def _derive(self, substrate, pred_mask, active, row_valid):
        """Shared recombination + per-slot masked-conjunction joint.

        ``pred_prob`` / ``uncertainty`` are slot-independent under shared
        combine params (computed and stored once at [C, P]); the joint is the
        masked product over each slot's predicate columns, with the mask as
        *data* so admit/retire never retrace.  Joint probability is zeroed on
        invalid rows and inactive slots so they can never enter an answer set
        or earn benefit.

        Storage-dtype contract: arithmetic runs in f32 regardless of the
        substrate dtype (bf16 upcasts exactly), results are stored back at
        the substrate dtype.  Under the f32 default every cast is a no-op,
        so this path is bitwise-identical to the pre-dtype-knob executor.
        """
        store_dt = substrate.func_probs.dtype
        with tracing.scope(tracing.DERIVE):
            pred32 = combine_probabilities(
                self.combine_params,
                substrate.func_probs.astype(jnp.float32),
                substrate.exec_mask,
                prior=self.config.prior,
            )  # [C, P] f32
            joint32 = jnp.prod(
                jnp.where(pred_mask[:, None, :], pred32[None], 1.0), axis=-1
            )  # [S, C] f32
            joint32 = jnp.where(active[:, None] & row_valid[None, :], joint32, 0.0)
            return (
                pred32.astype(store_dt),
                binary_entropy(pred32).astype(store_dt),
                joint32.astype(store_dt),
            )

    def _select_answers(self, joint_prob: jax.Array) -> threshold_lib.AnswerSelection:
        # Selection consumes the STORED joint (upcast exactly to f32), so
        # answer membership is always derivable from a checkpointed state
        # regardless of the storage dtype; no-op under the f32 default.
        if self.config.answer_mode == "approx":
            fn = functools.partial(
                threshold_lib.select_answer_approx, alpha=self.config.alpha
            )
        else:
            fn = functools.partial(threshold_lib.select_answer, alpha=self.config.alpha)
        with tracing.scope(tracing.SELECT):
            return jax.vmap(fn)(joint_prob.astype(jnp.float32))

    def _refresh_program(self, state: SessionState) -> SessionState:
        """Recompute all derived state from the substrate + masks.

        The warm-start path for every churn event: an admitted slot's first
        derived state already reflects every enrichment the substrate has
        accumulated (paper §5 caching), ingested rows surface with cold prior
        state, retired slots drop out of answers.
        """
        with tracing.scope(tracing.REFRESH):
            row_valid = state.row_valid()
            pp, unc, joint = self._derive(
                state.substrate, state.pred_mask, state.active, row_valid
            )
            sel = self._select_answers(joint)
            mask = sel.mask & state.active[:, None] & row_valid[None, :]
            derived = SessionDerived(
                pred_prob=pp, uncertainty=unc, joint_prob=joint, in_answer=mask
            )
            return dataclasses.replace(state, derived=derived)

    def refresh(self, state: SessionState) -> SessionState:
        """Jitted public entry for state-adoption paths."""
        with tracing.span(tracing.REFRESH):
            if state.capacity not in self._refresh_args:
                self._refresh_args[state.capacity] = _abstract((state,))
            return self._refresh_fn(state)

    # ---- scoring + planning ------------------------------------------------

    def _benefits(self, state: SessionState, row_valid: jax.Array) -> TripleBenefits:
        """Masked Eq. 11 over [S, C, P]: the conjunctive fast path plus the
        slot/row masks — inactive slots and invalid rows get -inf, so they
        never win top-k."""
        cfg = self.config
        der = state.derived
        mode = (
            "best"
            if cfg.function_selection == "best" and self.table.delta_h_all is not None
            else "table"
        )
        with tracing.scope(tracing.SCORE):
            state_id = state.substrate.state_id()  # [C, P]
            if state.quarantined is not None:
                # quarantined functions look "already executed" to the table
                # lookup (both modes, both backends route through state_id),
                # so they can never be planned; pred_prob is untouched —
                # enrichment already applied keeps contributing to answers.
                state_id = state_id | state_lib.pack_function_bits(state.quarantined)[None, :]
            if cfg.backend == "pallas":
                from repro.kernels.enrich_score import ops as es_ops

                # raw storage dtype straight into the kernel: bf16 rows are
                # upcast to f32 in-register inside each tile (dequant-in-
                # tile), so no f32 copy of the substrate-derived rows ever
                # hits HBM.
                tb = es_ops.fused_benefits_batched(
                    der.pred_prob, der.uncertainty, state_id,
                    der.joint_prob, self.table, self.costs,
                    function_selection=mode,
                    interpret=cfg.pallas_interpret,
                )
            else:
                # the jnp backend has no tile boundary to hide the upcast
                # in; dequantize at the input (exact, no-op under f32)
                tb = benefit_lib.compute_benefits_batched(
                    der.pred_prob.astype(jnp.float32),
                    der.uncertainty.astype(jnp.float32),
                    state_id,
                    der.joint_prob.astype(jnp.float32),
                    self.table, self.costs,
                    function_selection=mode,
                )
            benefit, nf, est_joint = tb
            valid = (
                (nf >= 0)
                & state.pred_mask[:, None, :]
                & state.active[:, None, None]
                & row_valid[None, :, None]
            )
            benefit = jnp.where(valid, benefit, NEG_INF)
        with tracing.scope(tracing.CANDIDATES):
            unc32 = der.uncertainty.astype(jnp.float32)
            cand = jax.vmap(
                lambda a, m: benefit_lib.candidate_mask(
                    unc32, a, cfg.candidate_strategy,
                    pred_mask=m, row_valid=row_valid,
                )
            )(der.in_answer, state.pred_mask)  # [S, C]
            benefit = jax.vmap(
                lambda b, c: benefit_lib.restrict_benefits(b, c, cfg.plan_size)
            )(benefit, cand)
        return TripleBenefits(benefit=benefit, next_fn=nf, est_joint=est_joint)

    def _plan_part(self, state: SessionState):
        """The superstep up to the bank boundary: score, select, dedup-merge."""
        cfg = self.config
        row_valid = state.row_valid()
        benefits = self._benefits(state, row_valid)
        with tracing.scope(tracing.TOPK):
            plans = select_plans_batched(
                benefits,
                plan_size=cfg.plan_size,
                num_shards=cfg.num_shards,
                num_predicates=self.num_predicates,
                costs=self.costs,
            )
        with tracing.scope(tracing.MERGE):
            merged, want_bits = plan_lib.merge_plans_dedup_wants(
                plans,
                self.num_predicates,
                self.num_functions,
                num_slots=state.num_slots,
                capacity=cfg.merged_capacity,
                cost_budget=cfg.epoch_cost_budget,
                num_objects=state.capacity,
            )
            if state.quarantined is not None:
                # defense in depth: even if a quarantined lane survived
                # scoring (it cannot, by the state-id OR above), it must
                # neither execute nor bill — apply and ledger attribution
                # both key off ``merged.valid``.
                merged = plan_lib.quarantine_filter(merged, state.quarantined)
        return plans, merged, want_bits

    def _bank_part(self, state: SessionState, merged: plan_lib.Plan, bank_params):
        """The bank boundary, fully inside the trace.

        With an attached bank, the merged plan runs through the bank's pure
        ``execute_with_stats`` (real model forwards for the cascade bank);
        its f32 probabilities are quantized to the substrate storage dtype HERE —
        the same boundary ``ingest`` quantizes at — so ``apply`` only ever
        sees conforming writes.  Otherwise outputs gather from the
        capacity-padded ``state.bank_outputs`` buffer; invalid merged lanes
        route to row 0 (NOT clipped onto row capacity-1, a real row once the
        session fills) and stay inert: apply drops them, chargeable/want-bits
        are valid-masked.  -> (outputs, expert_load): the attached bank's
        ``expert_load`` stat, 0 for the gather.
        """
        with tracing.scope(tracing.BANK):
            if self.bank is not None:
                probs, bank_stats = self.bank.execute_with_stats(merged, bank_params)
                return (probs.astype(state.substrate.func_probs.dtype),
                        bank_stats["expert_load"])
            obj = plan_lib.gather_object_idx(merged, state.capacity)
            outputs = state.bank_outputs[obj, merged.pred_idx, jnp.maximum(merged.func_idx, 0)]
            return outputs, jnp.zeros((), jnp.float32)

    def _apply_part(self, state, plans, merged, want_bits, outputs):
        """The superstep past the bank boundary: charge, apply, attribute,
        re-derive, select.  Stats always carry the answer mask; drivers drop
        it when masks were not requested (dead code under jit)."""
        row_valid = state.row_valid()
        with tracing.scope(tracing.APPLY):
            # the SAME charging rule apply_outputs_to_substrate bills
            # cost_spent with, so ledger attribution reconciles by
            # construction
            chargeable = state_lib.chargeable_mask(
                state.substrate, merged.object_idx, merged.pred_idx,
                merged.func_idx, merged.valid,
            )
            sub = state_lib.apply_outputs_to_substrate(
                state.substrate,
                merged.object_idx,
                merged.pred_idx,
                merged.func_idx,
                outputs,
                merged.cost,
                merged.valid,
            )
            ledger = ledger_lib.attribute_epoch(state.ledger, merged, want_bits, chargeable)
        prev_cost = state.substrate.cost_spent
        pp, unc, joint = self._derive(sub, state.pred_mask, state.active, row_valid)
        sel = self._select_answers(joint)
        with tracing.scope(tracing.SELECT):
            mask = sel.mask & state.active[:, None] & row_valid[None, :]
        new_state = dataclasses.replace(
            state,
            substrate=sub,
            derived=SessionDerived(
                pred_prob=pp, uncertainty=unc, joint_prob=joint, in_answer=mask
            ),
            ledger=ledger,
        )
        stats = dict(
            cost_spent=sub.cost_spent,
            epoch_cost=sub.cost_spent - prev_cost,
            requested_cost=jnp.sum(jnp.where(plans.valid, plans.cost, 0.0)),
            expected_f=jnp.where(state.active, sel.expected_f, 0.0),
            answer_size=jnp.sum(mask, axis=1),
            plan_valid=jnp.sum(plans.valid, axis=1),
            merged_valid=merged.num_valid(),
            # valid merged lanes per function level; sums to merged_valid
            level_lanes=jnp.sum(
                merged.valid[:, None]
                & (merged.func_idx[:, None] == jnp.arange(self.num_functions)),
                axis=0, dtype=jnp.int32,
            ),
            active=state.active,
            num_rows=state.num_rows,
            attributed=ledger.attributed,
            answer_mask=mask,
        )
        if self.truth_masks is not None:
            stats["true_f"] = jax.vmap(
                lambda m, t: true_f_alpha(m, t, self.config.alpha)
            )(mask, self.truth_masks)
        return new_state, stats

    def _superstep(self, state: SessionState, bank_params, collect_masks: bool):
        """One plan -> execute -> apply -> attribute epoch as a pure scan body."""
        self._trace_count += 1  # Python side effect: fires per TRACE, not per step
        plans, merged, want_bits = self._plan_part(state)
        outputs, expert_load = self._bank_part(state, merged, bank_params)
        new_state, stats = self._apply_part(state, plans, merged, want_bits, outputs)
        stats["expert_load"] = expert_load
        if not collect_masks:
            stats = {k: v for k, v in stats.items() if k != "answer_mask"}
        return new_state, stats

    # ---- drivers -----------------------------------------------------------

    def _get_scan_fn(
        self, capacity: int, num_epochs: int, collect_masks: bool, donate: bool
    ):
        # keyed on the tier capacity: each tier owns ONE compiled superstep
        # per scan length, which is what bounds total retraces over any event
        # trace by the session's tier count (retrace_bound) per length.
        key = (capacity, num_epochs, collect_masks, donate)
        if key not in self._scan_cache:

            def run_fn(state, bank_params):
                return jax.lax.scan(
                    lambda s, _: self._superstep(s, bank_params, collect_masks),
                    state,
                    None,
                    length=num_epochs,
                )

            # donation lets XLA update the [C, P, F] state in place across
            # the dispatch instead of holding the pre-run copy alive.  The
            # session never donates (its state is a long-lived caller
            # handle); the facades donate states their own run created,
            # copying any leaves that alias engine-owned buffers first.
            argnums = (0,) if donate else ()
            self._scan_cache[key] = jax.jit(run_fn, donate_argnums=argnums)
        return self._scan_cache[key]

    @staticmethod
    def chunk_lengths(num_epochs: int, chunk_size: Optional[int]) -> list:
        """Split a run into scan-dispatch chunks (last chunk takes the rest)."""
        if num_epochs < 0:
            raise ValueError(f"num_epochs must be >= 0, got {num_epochs}")
        if chunk_size is not None and chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        if not num_epochs:
            return []
        if chunk_size is None or chunk_size >= num_epochs:
            return [num_epochs]
        k, r = divmod(num_epochs, chunk_size)
        return [chunk_size] * k + ([r] if r else [])

    def dispatch_scan(
        self,
        state: SessionState,
        length: int,
        collect_masks: bool,
        donate: bool = False,
    ):
        """Dispatch ONE scan chunk without blocking; returns state + stats
        futures.  The building block of the async event pipeline."""
        fn = self._get_scan_fn(state.capacity, length, collect_masks, donate)
        key = (state.capacity, length, collect_masks, donate)
        if key not in self._scan_args:
            self._scan_args[key] = _abstract((state, self.bank_params))
        with tracing.span(tracing.DISPATCH):
            return fn(state, self.bank_params)

    def compiled_hlo(self) -> list:
        """-> ``[(kind, text)]``: the optimized HLO of every program this
        executor has dispatched, ``kind`` "superstep" (one per scan shape)
        or "refresh" (one per capacity).

        Each is lowered again from the argument shapes of its first
        dispatch; JAX's trace cache serves that without a retrace
        (``superstep_traces`` does not move), and a configured persistent
        compilation cache serves the compile.  A profiler trace names each
        device op after an instruction of these programs, whose ``op_name``
        metadata carries the ``pique/`` scopes (``core.tracing``).  That
        cache keys a program by its computation and module name, never by
        its metadata, so the text carries the names of whichever program
        first compiled the same computation under the same name.
        """
        out = [
            ("superstep", self._scan_cache[key].lower(*args).compile().as_text())
            for key, args in self._scan_args.items()
        ]
        out += [
            ("refresh", self._refresh_fn.lower(*args).compile().as_text())
            for args in self._refresh_args.values()
        ]
        return out

    def run_scan(
        self,
        state: SessionState,
        num_epochs: int,
        chunk_size: Optional[int] = None,
        collect_masks: bool = False,
        stop_when_exhausted: bool = True,
        donate: bool = False,
        on_chunk=None,
    ):
        """Run ``num_epochs`` supersteps as chunked fused-scan dispatches.

        ``chunk_size=None`` (default, falling back to ``config.chunk_size``)
        keeps the pre-chunking behavior: one scan per run.  Chunked runs are
        bitwise identical to monolithic ones — the carry crosses chunk
        boundaries untouched — and reuse one compiled chunk program across
        run lengths.  Dispatch is async; the single host sync is the history
        materialization at the end.  ``donate=True`` (callers owning every
        buffer of ``state``, e.g. a facade that just created it) lets XLA
        reuse the input buffers in place; each chunk's input is then either
        the donated original or a previous chunk's output, both driver-owned.

        ``on_chunk(carry, epochs_dispatched)`` fires after each chunk
        dispatch with the in-flight carry and the cumulative epoch count of
        this run; returning truthy stops dispatching FURTHER chunks (the
        already-dispatched ones complete and appear in the history).  Chunk
        boundaries are superstep boundaries, so this is the one legal hook
        for durability snapshots and cooperative preemption
        (``core.durability``) — the carry handed to the callback is exactly
        what the next superstep would consume.
        """
        if chunk_size is None:
            chunk_size = self.config.chunk_size
        with tracing.span(tracing.RUN) as span:
            chunks = []
            dispatched = 0
            for length in self.chunk_lengths(num_epochs, chunk_size):
                state, stats = self.dispatch_scan(
                    state, length, collect_masks, donate=donate
                )
                chunks.append((length, stats))
                dispatched += length
                if on_chunk is not None and on_chunk(state, dispatched):
                    break
            span.set_metadata(epochs=dispatched, traces=self.superstep_traces)
            with tracing.span(tracing.WAIT):
                hosts = [(length, jax.device_get(s)) for length, s in chunks]
                state = jax.block_until_ready(state)
            history = self.materialize_history(
                hosts,
                collect_masks=collect_masks,
                stop_when_exhausted=stop_when_exhausted,
            )
        return state, history

    @staticmethod
    def materialize_history(
        hosts,  # [(chunk_len, host_stats_dict)] with leading [L] on leaves
        collect_masks: bool,
        stop_when_exhausted: bool,
        epoch_base: int = 0,
    ) -> list:
        """Build ``SessionEpochStats`` from chunked host-side scan stats,
        trimming post-exhaustion no-op epochs to match the loop driver.
        The ``pique.history`` span carries the merged lanes per function
        level and the epochs' ``expert_load``, each summed over every epoch
        of ``hosts``."""
        counts = {}
        if hosts:
            lanes = sum(np.sum(s["level_lanes"], axis=0) for _, s in hosts)
            counts = {f"lanes_{i}": int(n) for i, n in enumerate(lanes)}
            counts["expert_load"] = float(sum(np.sum(s["expert_load"]) for _, s in hosts))
        with tracing.span(tracing.HISTORY, **counts):
            history: list[SessionEpochStats] = []
            e = epoch_base
            for length, stats in hosts:
                for i in range(length):
                    merged_valid = int(stats["merged_valid"][i])
                    history.append(
                        SessionEpochStats(
                            epoch=e,
                            cost_spent=float(stats["cost_spent"][i]),
                            epoch_cost=float(stats["epoch_cost"][i]),
                            requested_cost=float(stats["requested_cost"][i]),
                            expected_f=[float(x) for x in stats["expected_f"][i]],
                            answer_size=[int(x) for x in stats["answer_size"][i]],
                            plan_valid=[int(x) for x in stats["plan_valid"][i]],
                            merged_valid=merged_valid,
                            active=[bool(x) for x in stats["active"][i]],
                            num_rows=int(stats["num_rows"][i]),
                            attributed=[float(x) for x in stats["attributed"][i]],
                            level_lanes=[int(x) for x in stats["level_lanes"][i]],
                            expert_load=float(stats["expert_load"][i]),
                            answer_mask=(
                                np.asarray(stats["answer_mask"][i])
                                if collect_masks
                                else None
                            ),
                            true_f=(
                                [float(x) for x in stats["true_f"][i]]
                                if "true_f" in stats
                                else None
                            ),
                        )
                    )
                    e += 1
                    if stop_when_exhausted and merged_valid == 0:
                        return history
            return history


def _abstract(args):
    """Shapes, dtypes and placements of a dispatch's arguments (what
    ``EpochProgram.compiled_hlo`` lowers again)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None),
            weak_type=getattr(x, "weak_type", False),
        ) if hasattr(x, "shape") else x,
        args,
    )
