"""Session-oriented engine core: jit-stable serving under tenant + corpus churn.

Production pay-as-you-go serving (the IDEA ingestion framework, Wang & Carey
2019) needs tenant admission and corpus ingestion to be cheap *data* updates.
``EngineSession`` makes every churn axis a masked, pre-allocated dimension so
the fused epoch superstep — owned by ``core.executor.EpochProgram``, the one
executor every engine generation now shares — compiles once per capacity tier
for the life of the session:

* **capacity-padded substrate** — state tensors are allocated at
  ``[capacity, P, F]``; a row-validity prefix mask (one traced ``num_rows``
  scalar) says which rows hold real objects.  ``ingest(outputs)`` writes new
  objects' tagging outputs into the next free rows and bumps the scalar.
* **tenant slots** — ``max_tenants`` slots are allocated up front; a slot is
  its conjunctive query's predicate-column mask plus an ``active`` bit.
  ``admit(query)`` fills a free slot (resetting its ledger accumulator — a
  recycled slot must not inherit the previous occupant's bill) and
  warm-starts its derived state from whatever the substrate has accumulated;
  ``retire(slot)`` clears the bits.
* **masked planning** — invalid rows and inactive slots earn ``-inf``
  benefit, so they never win plan top-k and never enter answer sets.
* **cost ledger** — the dedup merge carries per-tenant want-bitmasks and
  ``core.ledger`` splits every newly charged triple's cost fairly across the
  tenants whose plans wanted it, inside the superstep.
* **capacity tiers** — with ``max_capacity > capacity`` the session owns a
  geometric tier schedule; an overflowing ``ingest`` migrates the full
  ``SessionState`` to the next tier via ``pad_session_state`` (padded rows
  bitwise inert).  Each tier compiles one superstep per scan length, so
  total retraces over ANY event trace are bounded by ``1 +
  ceil(log2(max_capacity / capacity))`` per length — ``retrace_bound``,
  observable via ``superstep_traces``.
* **async event overlap** — ``SessionPipeline`` stages ingest/admit/retire
  events host-side and applies them between scan chunks while the previous
  chunk is still in flight: every event method takes the host-shadowed
  ``num_rows`` / ``active`` it needs, so the pipeline never blocks on device
  data and ``jax.block_until_ready`` happens only at ``finish()``.  Zero
  extra retraces: the pipeline dispatches the same chunk programs the
  lockstep path uses.

Exactness bars (tested): with ``capacity == num_objects`` and a fixed tenant
set, per-epoch answer sets and ``cost_spent`` are bitwise identical to
``MultiQueryEngine.run_scan`` (now a facade over this class); chunked and
pipelined runs are bitwise identical to lockstep ones; a session grown
``capacity -> max_capacity`` across a churn trace is bitwise identical to one
pre-allocated at ``max_capacity``.

Scope: tenants must be pure conjunctions (the paper's Q1-Q5 shape and the
multi-tenant fast path); general ASTs stay on ``MultiQueryEngine``'s legacy
loop.  The scan-driver execution bank is either the session-owned
capacity-padded output buffer (the simulated-bank gather) or — when the
session is opened with ``bank=`` — a traceable bank (the model-cascade
bank) whose real model forwards run inside the fused superstep.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ledger as ledger_lib
from repro.core import state as state_lib
from repro.core import tracing
from repro.core.errors import CapacityError, SlotActiveError, SlotsExhaustedError
from repro.core.executor import (
    EngineConfig,
    EpochProgram,
    SessionDerived,
    SessionEpochStats,
    SessionState,
    resolve_substrate_dtype,
)
from repro.core.query import CompiledQuery
from repro.core.state import SharedSubstrate

# Back-compat alias (the config moved to core.executor with the superstep).
MultiQueryConfig = EngineConfig


def tier_schedule(
    capacity: int, max_capacity: int, num_shards: int = 1
) -> tuple[int, ...]:
    """Geometric capacity tiers ``capacity, 2c, 4c, ...`` covering
    ``max_capacity``.

    Each tier is rounded UP to a multiple of ``num_shards`` so sharded plan
    selection keeps its divisibility invariant at every tier (the last tier
    may therefore slightly exceed ``max_capacity``; it never falls short).
    Doubling guarantees ``len(tiers) <= 1 + ceil(log2(max_capacity /
    capacity))`` — the session's retrace bound, since each tier compiles its
    superstep exactly once per scan shape.
    """
    if capacity < 1:
        raise ValueError("capacity must be >= 1")
    if max_capacity < capacity:
        raise ValueError(
            f"max_capacity={max_capacity} < capacity={capacity}"
        )

    def up(c: int) -> int:
        return -(-c // num_shards) * num_shards

    tiers = [up(capacity)]
    while tiers[-1] < max_capacity:
        tiers.append(up(min(2 * tiers[-1], max_capacity)))
    return tuple(tiers)


def pad_session_state(
    state: SessionState, capacity: int, prior: float
) -> SessionState:
    """Migrate a full ``SessionState`` onto a larger row capacity.

    Pure data movement, no arithmetic: every row-indexed leaf pads with the
    SAME inert fill its allocator uses (substrate and bank outputs with the
    prior, exec bits False, per-slot derived rows zero/False), and the
    row-validity prefix scalar is untouched — so padded rows are bitwise
    indistinguishable from rows a ``max_capacity``-sized session would have
    pre-allocated and never touched.  That is the growth-exactness bar: a
    grown session replays bitwise identically to a pre-allocated one.
    Callers refresh derived state afterwards (``EngineSession.grow`` does);
    the ledger has no row axis and crosses via ``ledger.migrate_ledger``.
    """
    if capacity < state.capacity:
        raise ValueError(
            f"cannot shrink a session from {state.capacity} to {capacity} rows"
        )
    if capacity == state.capacity:
        return state
    sub = state.substrate
    der = state.derived
    return dataclasses.replace(
        state,
        substrate=SharedSubstrate(
            func_probs=state_lib.pad_rows(sub.func_probs, capacity, prior),
            exec_mask=state_lib.pad_rows(sub.exec_mask, capacity, False),
            cost_spent=sub.cost_spent,
        ),
        derived=SessionDerived(
            pred_prob=state_lib.pad_rows(der.pred_prob, capacity, 0.0),
            uncertainty=state_lib.pad_rows(der.uncertainty, capacity, 0.0),
            joint_prob=state_lib.pad_axis(der.joint_prob, capacity, 0.0, axis=1),
            in_answer=state_lib.pad_axis(der.in_answer, capacity, False, axis=1),
        ),
        bank_outputs=state_lib.pad_rows(state.bank_outputs, capacity, prior),
        ledger=ledger_lib.migrate_ledger(state.ledger, state.num_slots),
    )


class EngineSession:
    """Long-lived multi-tenant PIQUE engine with churn-stable jitted shapes."""

    def __init__(
        self,
        global_predicates: Sequence,  # the corpus schema (fixes the P axis)
        table,
        combine_params,
        costs: jax.Array,  # [P, F] over the global predicate space
        capacity: int,
        max_tenants: int,
        config: EngineConfig = EngineConfig(),
        max_capacity: Optional[int] = None,
        truth_masks: Optional[jax.Array] = None,  # [S, capacity] bool, metrics only
        bank=None,  # traceable bank executed INSIDE the superstep (see executor)
    ):
        if config.backend not in ("jnp", "pallas"):
            raise ValueError(f"unknown backend: {config.backend!r}")
        if config.num_shards < 1:
            raise ValueError("num_shards must be >= 1")
        if config.num_shards > 1 and capacity % config.num_shards:
            raise ValueError(
                f"capacity={capacity} must divide evenly over "
                f"num_shards={config.num_shards}"
            )
        if max_tenants < 1:
            raise ValueError("max_tenants must be >= 1")
        self.global_predicates = tuple(global_predicates)
        self.table = table
        self.combine_params = combine_params
        self.costs = jnp.asarray(costs, jnp.float32)
        self.capacity = int(capacity)
        self.max_tenants = int(max_tenants)
        self.config = config
        # storage dtype of func_probs / bank_outputs / derived state;
        # resolve_substrate_dtype raises on unknown names at construction,
        # not deep inside the first allocation.
        self.substrate_dtype = resolve_substrate_dtype(config.substrate_dtype)
        # capacity tiers: default max_capacity == capacity (no growth; the
        # pre-tier contract).  Each tier is shard-divisible, so sharded
        # planning survives growth unchanged.
        self._tiers = tier_schedule(
            self.capacity,
            self.capacity if max_capacity is None else int(max_capacity),
            config.num_shards,
        )
        self.growths = 0  # tier migrations performed (any state this session owns)
        if self.costs.shape[0] != len(self.global_predicates):
            raise ValueError(
                f"costs rows ({self.costs.shape[0]}) != global predicates "
                f"({len(self.global_predicates)})"
            )
        self._pred_index = {p: i for i, p in enumerate(self.global_predicates)}
        if truth_masks is not None and self.max_capacity != self.capacity:
            raise ValueError(
                "truth_masks require a fixed-capacity session (the [S, C] "
                "truth rows cannot follow tier growth)"
            )
        # the unified executor: one superstep + drivers for the session's life
        self.bank = bank
        self.program = EpochProgram(
            table, combine_params, self.costs, config, truth_masks=truth_masks,
            bank=bank,
        )

    @property
    def num_predicates(self) -> int:
        return len(self.global_predicates)

    @property
    def num_functions(self) -> int:
        return self.costs.shape[1]

    @property
    def superstep_traces(self) -> int:
        """How many times the epoch superstep has been traced (churn-stability
        witness: stays 1 across any sequence of ingest/admit/retire events
        within a tier, and <= ``retrace_bound`` across tier growth)."""
        return self.program.superstep_traces

    @property
    def tier_capacities(self) -> tuple[int, ...]:
        """The geometric capacity tiers this session may occupy."""
        return self._tiers

    @property
    def max_capacity(self) -> int:
        """The last tier's capacity (requested ``max_capacity`` rounded up to
        the shard count); rows beyond this can never be ingested."""
        return self._tiers[-1]

    @property
    def retrace_bound(self) -> int:
        """Max supersteps traced per distinct scan shape over ANY event
        trace: one per tier, ``<= 1 + ceil(log2(max_capacity / capacity))``
        by the doubling schedule."""
        return len(self._tiers)

    # ---- session lifecycle ---------------------------------------------------

    def _tier_for(self, rows: int, used: int = 0, requested: int = None) -> int:
        """Smallest tier capacity holding ``rows`` (CapacityError past max).

        ``used``/``requested`` flow into the error's machine-readable triple:
        rows already occupied and the increment that failed (defaulting to
        ``rows`` when the request IS the total, e.g. an initial corpus).
        """
        for t in self._tiers:
            if rows <= t:
                return t
        raise CapacityError(
            f"{rows} rows exceeds capacity: the session's last tier holds "
            f"{self.max_capacity} (tiers {self._tiers}); open the session "
            "with a larger max_capacity for the expected arrival volume",
            used=used,
            capacity=self.max_capacity,
            requested=rows if requested is None else requested,
        )

    def init_state(self, bank_outputs: jax.Array) -> SessionState:
        """Open a session over an initial corpus of ``bank_outputs`` [N0, P, F].

        N0 may be anything up to ``max_capacity``; the session opens at the
        smallest tier that holds it, leaving the remaining rows pre-allocated
        for ``ingest``.  No tenants are active yet — ``admit`` fills slots.

        Outputs are quantized HERE to ``config.substrate_dtype`` — the one
        documented cast of the ingest path (everything downstream is
        dtype-strict, see ``state.ingest_rows``).
        """
        bank_outputs = jnp.asarray(bank_outputs)
        if bank_outputs.dtype != self.substrate_dtype:
            bank_outputs = bank_outputs.astype(self.substrate_dtype)
        n0, p, f = bank_outputs.shape
        if p != self.num_predicates or f != self.num_functions:
            raise ValueError(
                f"bank outputs [{n0}, {p}, {f}] do not match the compiled "
                f"space [P={self.num_predicates}, F={self.num_functions}]"
            )
        if n0 > self.max_capacity:
            raise CapacityError(
                f"initial corpus {n0} exceeds capacity {self.max_capacity} "
                f"(tiers {self._tiers})",
                used=0,
                capacity=self.max_capacity,
                requested=n0,
            )
        cap = self._tier_for(n0)
        substrate = state_lib.init_substrate(
            n0,
            self.num_predicates,
            self.num_functions,
            prior=self.config.prior,
            dtype=self.substrate_dtype,
            capacity=cap,
        )
        dt = self.substrate_dtype
        state = SessionState(
            substrate=substrate,
            derived=SessionDerived(  # placeholder; refresh fills it
                pred_prob=jnp.zeros((cap, self.num_predicates), dt),
                uncertainty=jnp.zeros((cap, self.num_predicates), dt),
                joint_prob=jnp.zeros((self.max_tenants, cap), dt),
                in_answer=jnp.zeros((self.max_tenants, cap), bool),
            ),
            bank_outputs=state_lib.pad_rows(bank_outputs, cap, self.config.prior),
            pred_mask=jnp.zeros((self.max_tenants, self.num_predicates), bool),
            active=jnp.zeros((self.max_tenants,), bool),
            num_rows=jnp.asarray(n0, jnp.int32),
            ledger=ledger_lib.init_ledger(self.max_tenants),
            quarantined=self._initial_quarantine(),
        )
        return self.program.refresh(state)

    def _initial_quarantine(self) -> jax.Array:
        """(pred, fn) pairs dead from birth: a ragged bank's missing levels
        (``bank.available == False``) enter the quarantine channel, so beyond
        their sentinel cost they are STRUCTURALLY unplannable — the same
        state-id exclusion a fault quarantine uses."""
        q = jnp.zeros((self.num_predicates, self.num_functions), bool)
        avail = getattr(self.bank, "available", None)
        if avail is not None:
            q = q | ~jnp.asarray(avail, bool)
        return q

    def _query_columns(self, query: CompiledQuery) -> list:
        if not query.is_conjunctive:
            raise NotImplementedError(
                "EngineSession slots are conjunctive predicate masks; general "
                "ASTs stay on MultiQueryEngine"
            )
        missing = [p for p in query.predicates if p not in self._pred_index]
        if missing:
            raise ValueError(
                f"query references {len(missing)} predicate(s) outside the "
                f"session's global space (num_predicates={self.num_predicates}): "
                f"{missing}; sessions are compiled over the corpus schema "
                "passed at construction"
            )
        return [self._pred_index[p] for p in query.predicates]

    def admit(
        self,
        state: SessionState,
        query: CompiledQuery,
        slot: Optional[int] = None,
        *,
        active=None,
    ) -> tuple[SessionState, int]:
        """Admit a tenant into a free slot between supersteps.

        Pure data update (mask bits + a ledger-slot reset) + derived-state
        warm start from the substrate; the compiled superstep is untouched.
        The slot's ledger accumulator resets so a recycled slot starts from a
        zero bill (the previous occupant's spend moves to the ledger's
        ``archived`` bucket — invoiced at retirement, never inherited).
        Admitting into a still-occupied slot raises ``SlotActiveError``.

        ``active`` may carry a host-side shadow of ``state.active`` (the
        async event pipeline's no-sync path); by default it is read from the
        device.  Returns the new state and the slot index (the tenant's
        ledger/billing handle).
        """
        cols = self._query_columns(query)
        with tracing.span(tracing.ADMIT) as span:
            if active is None:
                with tracing.span(tracing.SYNC):
                    active = jax.device_get(state.active)
            active_np = np.asarray(active)
            if slot is None:
                free = np.flatnonzero(~active_np)
                if free.size == 0:
                    raise SlotsExhaustedError(
                        f"no free tenant slots (max_tenants={self.max_tenants}); "
                        "retire a tenant or open the session with more slots",
                        used=int(active_np.sum()),
                        capacity=self.max_tenants,
                        requested=1,
                    )
                slot = int(free[0])
            else:
                if not 0 <= slot < self.max_tenants:
                    raise ValueError(f"slot {slot} out of range [0, {self.max_tenants})")
                if active_np[slot]:
                    raise SlotActiveError(
                        f"slot {slot} is already occupied; retire it first",
                        slot=slot,
                    )
            span.set_metadata(slot=slot)
            row = jnp.zeros((self.num_predicates,), bool).at[
                jnp.asarray(cols, jnp.int32)
            ].set(True)
            state = dataclasses.replace(
                state,
                pred_mask=state.pred_mask.at[slot].set(row),
                active=state.active.at[slot].set(True),
                ledger=ledger_lib.reset_slot(state.ledger, slot),
            )
            return self.program.refresh(state), slot

    def retire(
        self, state: SessionState, slot: int, *, active=None
    ) -> SessionState:
        """Retire a tenant slot between supersteps (mask bits off).

        The slot's enrichment stays in the substrate — it was shared property
        the moment it executed — and its ledger row keeps the final bill
        until the slot is recycled by a later ``admit`` (which archives it).
        Retiring the last active tenant is fine: the session idles (plans
        empty, nothing charged) until the next ``admit``.  ``active`` may
        carry a host-side shadow (the async pipeline's no-sync path).
        """
        if not 0 <= slot < self.max_tenants:
            raise ValueError(f"slot {slot} out of range [0, {self.max_tenants})")
        with tracing.span(tracing.RETIRE, slot=slot):
            if active is None:
                with tracing.span(tracing.SYNC):
                    occupied = bool(jax.device_get(state.active[slot]))
            else:
                occupied = bool(np.asarray(active)[slot])
            if not occupied:
                raise ValueError(f"slot {slot} is not active")
            state = dataclasses.replace(
                state,
                pred_mask=state.pred_mask.at[slot].set(
                    jnp.zeros((self.num_predicates,), bool)
                ),
                active=state.active.at[slot].set(False),
            )
            return self.program.refresh(state)

    def refresh(self, state: SessionState) -> SessionState:
        """Recompute all derived state from the substrate + masks (jitted).

        Public entry for state-adoption paths — e.g. a torn-down session's
        state migrated into a freshly built one (the rebuild baseline in
        ``benchmarks.growth``); normal churn events call it internally.
        """
        return self.program.refresh(state)

    # ---- degraded-mode enrichment (quarantine) -------------------------------

    def set_quarantine(self, state: SessionState, quarantined) -> SessionState:
        """Replace the [P, F] enrichment-function quarantine mask.

        A pure data update on the scan carry — no retrace, no refresh: the
        mask only gates *future* plan selection (its bits read as "already
        executed" to the decision table), while enrichment a function already
        delivered stays in the substrate and keeps contributing to answers.
        The ledger bills nothing for quarantined work because quarantined
        triples never enter a merged plan (and ``plan.quarantine_filter``
        makes that structural).
        """
        q = jnp.asarray(quarantined, bool)
        want = (self.num_predicates, self.num_functions)
        if q.shape != want:
            raise ValueError(f"quarantine mask must be {want}; got {q.shape}")
        return dataclasses.replace(state, quarantined=q)

    def quarantine(self, state: SessionState, pred: int, func: int) -> SessionState:
        """Mask enrichment function ``func`` of predicate ``pred`` out of
        plan selection (see ``set_quarantine``)."""
        self._check_pf(pred, func)
        return dataclasses.replace(
            state, quarantined=state.quarantined.at[pred, func].set(True)
        )

    def unquarantine(self, state: SessionState, pred: int, func: int) -> SessionState:
        """Re-admit a recovered enrichment function into plan selection."""
        self._check_pf(pred, func)
        return dataclasses.replace(
            state, quarantined=state.quarantined.at[pred, func].set(False)
        )

    def _check_pf(self, pred: int, func: int):
        if not (0 <= pred < self.num_predicates and 0 <= func < self.num_functions):
            raise ValueError(
                f"(pred={pred}, func={func}) outside "
                f"[P={self.num_predicates}, F={self.num_functions}]"
            )

    def reshard(self, num_shards: int) -> "EngineSession":
        """A new session over the same world, planning across ``num_shards``.

        The elastic-restart building block: after ``ElasticPolicy`` shrinks
        the data axis, the supervisor opens the resharded session and
        restores the newest checkpoint onto it — bitwise-identical answers
        are guaranteed because sharded plan selection is exact
        (``plan.merge_plans_dedup_sharded``) and restore re-pads inertly.
        The new session shares the table/params/costs but compiles its own
        superstep (a legitimate, bounded recompile per mesh change).
        """
        cfg = dataclasses.replace(self.config, num_shards=int(num_shards))
        return EngineSession(
            self.global_predicates,
            self.table,
            self.combine_params,
            self.costs,
            capacity=self.capacity,
            max_tenants=self.max_tenants,
            config=cfg,
            max_capacity=self._tiers[-1],
            truth_masks=self.program.truth_masks,
        )

    def _grow_padded(
        self, state: SessionState, min_rows: int, used: int
    ) -> SessionState:
        """Tier migration WITHOUT the derived-state refresh — for callers
        whose own tail refreshes anyway (``ingest``), sparing a second
        full-width device pass per growth event.  ``used`` is the host-known
        occupied row count (no device read here — the async pipeline relies
        on growth being sync-free)."""
        if min_rows <= state.capacity:
            return state
        target = self._tier_for(min_rows, used=used, requested=min_rows - used)
        state = pad_session_state(state, target, self.config.prior)
        self.growths += 1
        return state

    def grow(
        self, state: SessionState, min_rows: int, *, num_rows: Optional[int] = None
    ) -> SessionState:
        """Migrate a live session to the smallest capacity tier holding
        ``min_rows`` (no-op when the current tier already does).

        Pure data movement (``pad_session_state``) + a derived-state refresh:
        padded rows are bitwise inert, every accumulator (substrate spend,
        ledger bills, answer prefixes) carries over unchanged, and the next
        ``run`` compiles the superstep ONCE for the new tier — the bounded-
        recompile contract (``retrace_bound``).  Raises ``CapacityError``
        when ``min_rows`` exceeds the last tier.

        ``num_rows`` may carry the host-shadowed occupied row count (it only
        feeds the error payload); without it the count is read from the
        device — the one blocking sync of this path, which shadow-holding
        callers (the pipeline, the ingest ring) should never pay.
        """
        if min_rows <= state.capacity:
            return state
        used = (
            int(jax.device_get(state.num_rows)) if num_rows is None else int(num_rows)
        )
        grown = self._grow_padded(state, min_rows, used)
        return self.program.refresh(grown)

    def ingest(
        self,
        state: SessionState,
        outputs: jax.Array,
        *,
        num_rows: Optional[int] = None,
        refresh: bool = True,
    ) -> SessionState:
        """Stream new objects into pre-allocated rows between supersteps.

        ``outputs`` is [M, P, F] tagging-function outputs for the new objects
        (the simulated-bank contract: functions are pre-materialized, the
        bank gathers).  Their substrate rows start cold — prior probabilities,
        empty exec mask — and become planning candidates in the next epoch
        because the row-validity prefix now covers them.  An ingest that
        overflows the current tier grows the session to the next tier that
        holds it when ``max_capacity`` allows; past the last tier it raises
        ``CapacityError``.

        ``num_rows`` may carry the host-shadowed occupied row count (the
        async pipeline's no-sync path); by default it is read from the
        device.  ``refresh=False`` skips the derived-state recomputation —
        for callers applying a BURST of ingests (the pending-row ring drain)
        who refresh once at the end: refresh is idempotent w.r.t. the
        substrate, so burst-then-refresh is bitwise identical to
        refresh-per-batch at a fraction of the work.  A state whose last
        ingest skipped the refresh must not run a superstep until refreshed.

        Outputs are cast to the substrate dtype here — THE quantization
        boundary.  The old unconditional ``asarray(outputs, float32)``
        silently widened bf16 input (doubling H2D transfer bytes); now
        already-conforming input passes through untouched.
        """
        outputs = jnp.asarray(outputs)
        if outputs.dtype != self.substrate_dtype:
            outputs = outputs.astype(self.substrate_dtype)
        if outputs.ndim != 3 or outputs.shape[1:] != (
            self.num_predicates,
            self.num_functions,
        ):
            raise ValueError(
                f"ingest outputs must be [M, {self.num_predicates}, "
                f"{self.num_functions}]; got {outputs.shape}"
            )
        nr = (
            int(jax.device_get(state.num_rows))
            if num_rows is None
            else int(num_rows)
        )
        m = outputs.shape[0]
        if nr + m > self.max_capacity:
            raise CapacityError(
                f"ingest of {m} objects overflows capacity "
                f"({nr} rows used of {state.capacity}, max_capacity="
                f"{self.max_capacity}); open the session with a larger "
                "max_capacity for the expected arrival volume",
                used=nr,
                capacity=self.max_capacity,
                requested=m,
            )
        state = self._grow_padded(state, nr + m, nr)  # the tail refresh covers it
        bank, new_rows = state_lib.ingest_rows(
            state.bank_outputs, state.num_rows, outputs
        )
        state = dataclasses.replace(state, bank_outputs=bank, num_rows=new_rows)
        return self.program.refresh(state) if refresh else state

    # ---- drivers (delegating to the unified executor) ------------------------

    def run(
        self,
        state: SessionState,
        num_epochs: int,
        collect_masks: bool = False,
        stop_when_exhausted: bool = True,
        chunk_size: Optional[int] = None,
        on_chunk=None,
    ) -> tuple[SessionState, list]:
        """Run ``num_epochs`` supersteps as chunked fused-scan dispatches.

        Between calls the caller may ``ingest`` / ``admit`` / ``retire``
        freely — the compiled program is reused because every churn axis is
        data, and an ingest-driven tier migration switches to the target
        tier's own compiled program (at most ``retrace_bound`` per scan
        length).  With zero active tenants the session idles.  See
        ``EpochProgram.run_scan`` for chunking semantics (including the
        ``on_chunk`` superstep-boundary hook durability and preemption use)
        and ``SessionPipeline`` for overlapping events with in-flight chunks.
        """
        return self.program.run_scan(
            state,
            num_epochs,
            chunk_size=chunk_size,
            collect_masks=collect_masks,
            stop_when_exhausted=stop_when_exhausted,
            on_chunk=on_chunk,
        )

    def pipeline(
        self,
        state: SessionState,
        chunk_size: Optional[int] = None,
        preemption=None,
        heartbeat=None,
        boundary_hook=None,
    ) -> "SessionPipeline":
        """Open an async event pipeline over this session (one sync here —
        the shadow snapshot — then none until ``finish()``).  ``preemption``
        (a ``runtime.fault_tolerance.PreemptionHandler``) is polled at chunk
        boundaries so SIGTERM stops dispatch cooperatively; ``heartbeat``
        beats worker 0 per dispatched chunk; ``boundary_hook`` (no-arg
        callable) fires once per dispatched chunk — the supervisor's fault
        clock."""
        return SessionPipeline(
            self, state, chunk_size=chunk_size,
            preemption=preemption, heartbeat=heartbeat,
            boundary_hook=boundary_hook,
        )


class SessionPipeline:
    """Overlap churn-event application with in-flight scan chunks.

    The lockstep serving loop blocks at every boundary: ``run`` materializes
    its stats (a device sync) before the host even *looks* at the next
    event, and each event reads ``num_rows`` / ``active`` back from the
    device.  The pipeline removes every one of those barriers:

    * scan chunks are DISPATCHED, never waited on — JAX's async dispatch
      queues them on the device stream and hands back futures;
    * events validate against host-side shadows of ``num_rows`` and
      ``active`` (maintained here, exactly; every event's effect on them is
      host-computable) and apply as enqueued jitted data updates on the
      in-flight carry;
    * stats futures accumulate per chunk and materialize once, in
      ``finish()`` — the only ``jax.block_until_ready`` in the pipeline.

    So event latency hides behind device compute, with ZERO extra retraces:
    the pipeline dispatches the same compiled chunk programs the lockstep
    path uses (``superstep_traces`` is identical per tier), and the result —
    answer sets, ``cost_spent``, ledger — is bitwise identical to applying
    the same events lockstep, because the dispatch ORDER is identical; only
    the waiting moved.
    """

    def __init__(
        self,
        session: EngineSession,
        state: SessionState,
        chunk_size: Optional[int] = None,
        preemption=None,
        heartbeat=None,
        boundary_hook=None,
    ):
        self.session = session
        self.state = state
        self.chunk_size = (
            chunk_size if chunk_size is not None else session.config.chunk_size
        )
        self.preemption = preemption  # polled at chunk boundaries
        self.heartbeat = heartbeat  # beaten per dispatched chunk
        self.boundary_hook = boundary_hook  # fires once per dispatched chunk
        self.preempted = False  # a chunk-boundary poll saw should_stop
        # the pipeline's ONE upfront sync: snapshot the host shadows
        self.num_rows = int(jax.device_get(state.num_rows))
        self.active = np.asarray(jax.device_get(state.active)).copy()
        self._chunks = []  # (epoch_base_within_run, length, stats, collect)
        self.epochs_dispatched = 0
        self.events_staged = 0  # churn events only (ingest/admit/retire)
        self.stamps: list = []  # (wall_s, mean_active_expected_f) per epoch
        self._t0 = time.perf_counter()

    def run(self, num_epochs: int, collect_masks: bool = False) -> None:
        """Dispatch ``num_epochs`` supersteps as chunked scans (non-blocking).

        With a ``preemption`` handler attached, each chunk boundary polls
        ``should_stop``: on preemption no FURTHER chunks are dispatched
        (``preempted`` latches, ``epochs_dispatched`` counts only what was
        actually dispatched) — in-flight chunks drain normally at
        ``finish()``/``checkpoint()``, so the stop is always at a superstep
        boundary.
        """
        prog = self.session.program
        base = 0
        for length in prog.chunk_lengths(num_epochs, self.chunk_size):
            if self.preemption is not None and self.preemption.should_stop:
                self.preempted = True
                break
            self.state, stats = prog.dispatch_scan(
                self.state, length, collect_masks
            )
            self._chunks.append((base, length, stats, collect_masks))
            base += length
            if self.heartbeat is not None:
                self.heartbeat.beat(0)
            if self.boundary_hook is not None:
                # the supervisor's fault clock: may trip ``preemption`` so
                # the NEXT boundary poll stops dispatch at this superstep
                self.boundary_hook()
        self.epochs_dispatched += base

    def checkpoint(self, checkpointer, step: int, host_meta=None, force=True):
        """Drain in-flight chunks and snapshot the carry (superstep boundary
        by construction — dispatches only happen whole-chunk).  The pipeline
        keeps running afterwards: stats futures stay queued for ``finish()``,
        host shadows are untouched.  Returns the checkpoint path (or None if
        the cadence said skip and ``force`` is False)."""
        return checkpointer.maybe_save(
            self.state, step, host_meta=host_meta, force=force
        )

    def ingest(self, outputs: jax.Array) -> None:
        """Stage an ingest against the in-flight carry (no device sync;
        bounds-checked and tier-grown from the host shadow)."""
        self.state = self.session.ingest(
            self.state, outputs, num_rows=self.num_rows
        )
        self.num_rows += int(jnp.asarray(outputs).shape[0])
        self.events_staged += 1

    def drain_ring(self, ring) -> int:
        """Drain a ``repro.ingest.PendingRing`` into the in-flight carry.

        Every pending slot applies as a refresh-free ingest and derived
        state recomputes ONCE at the end — bitwise identical to ingesting
        each batch directly (refresh is idempotent w.r.t. the substrate) at
        a fraction of the work, and sync-free end to end: bounds checks and
        tier growth run off the pipeline's host shadow.  Returns the number
        of rows drained (0 when the ring was empty)."""
        self.state, self.num_rows, drained = ring.drain_into(
            self.session, self.state, self.num_rows
        )
        if drained:
            self.events_staged += 1
        return drained

    def admit(self, query: CompiledQuery, slot: Optional[int] = None) -> int:
        """Stage a tenant admission (slot chosen from the host shadow)."""
        self.state, slot = self.session.admit(
            self.state, query, slot=slot, active=self.active
        )
        self.active[slot] = True
        self.events_staged += 1
        return slot

    def retire(self, slot: int) -> None:
        """Stage a tenant retirement (validated against the host shadow)."""
        self.state = self.session.retire(self.state, slot, active=self.active)
        self.active[slot] = False
        self.events_staged += 1

    def finish(self) -> tuple[SessionState, list]:
        """Drain the pipeline: materialize every chunk's stats (in dispatch
        order, so each ``device_get`` stamps that chunk's true completion
        time while later chunks keep running) and return the final state +
        concatenated history.  The only blocking point of the pipeline."""
        prog = self.session.program
        history: list[SessionEpochStats] = []
        for base, length, stats, collect in self._chunks:
            host = jax.device_get(stats)  # blocks until THIS chunk completes
            t_done = time.perf_counter() - self._t0
            chunk_hist = prog.materialize_history(
                [(length, host)],
                collect_masks=collect,
                stop_when_exhausted=False,
                epoch_base=base,
            )
            for h in chunk_hist:
                self.stamps.append((t_done, h.mean_expected_f))
            history.extend(chunk_hist)
        self.state = jax.block_until_ready(self.state)
        self._chunks = []
        return self.state, history
