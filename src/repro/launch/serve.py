"""The progressive query server (the paper's system, end to end).

Serves PIQUE queries over an object corpus with a model-cascade tagging
bank: per request, runs epochs of plan-generation -> batched model
inference -> answer selection, streaming progressively better answer sets.
Integrates the runtime fault-tolerance pieces: straggler-aware object
partitions and cooperative preemption.

Two serving modes:

* single-tenant (``--queries 1``, the paper's operator): one
  ``ProgressiveQueryOperator`` per request;
* multi-tenant (``--queries Q``): Q concurrent queries over one shared
  enrichment substrate via ``core.multi_query.MultiQueryEngine`` — duplicate
  (object, predicate, function) work across tenants executes once per epoch
  and fans out, reporting per-query and aggregate F-alpha trajectories plus
  the cost the cross-query dedup avoided.

CPU-scale usage (examples/serve_progressive.py drives this):
    python -m repro.launch.serve --objects 512 --epochs 40
    python -m repro.launch.serve --objects 256 --preds 3 --queries 8

On a TPU, ``chip_smoke.py`` drives ``main`` at deployment scale, including a
cascade session with ``--backbone-size published``.  Costs print in the
planner's units (``enrich.cascade.PEAK_FLOPS``), never as device time.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.archs import get_config
from repro.core import (
    EngineSession,
    MultiQueryConfig,
    MultiQueryEngine,
    OperatorConfig,
    Predicate,
    ProgressiveQueryOperator,
    SessionCheckpointer,
    build_query_set,
    conjunction,
    learn_decision_table,
    restore_session_checkpoint,
)
from repro.core.combine import auc_score, fit_combine_weights
from repro.data.synthetic import make_corpus, split_corpus, truth_answer_mask
from repro.enrich.cascade import (
    ModelCascadeBank,
    build_cascade_suite,
    train_level,
)
from repro.runtime.fault_tolerance import (
    Heartbeat,
    PreemptionHandler,
    StragglerMonitor,
)


@dataclasses.dataclass
class ServeReport:
    epochs: int
    cost_spent: float
    expected_f: float
    true_f1: Optional[float]
    wall_s: float
    history: list


def use_compile_cache(default_dir=None) -> Optional[str]:
    """Turn on JAX's persistent compilation cache for this process.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is left
    alone, and so is a cache directory the calling program already chose.
    Otherwise the cache goes to ``default_dir`` or, when this package runs
    from a source checkout's ``src/``, to ``<checkout>/.jax_cache``: a fixed
    path, since the path is part of the cache key.  Installed elsewhere with
    no directory given, no persistent cache is turned on.  Call it from an
    entry point before the first compile, never at import.  -> the cache
    directory in use, or None.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.config.jax_compilation_cache_dir:
        return jax.config.jax_compilation_cache_dir
    if default_dir is None:
        src = Path(__file__).resolve().parents[2]
        if src.name != "src" or not (src.parent / "pyproject.toml").is_file():
            return None
        default_dir = src.parent / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(default_dir))
    return str(default_dir)


def backbone_config(arch: Optional[str], size: str = "smoke"):
    """``size="published"``: the architecture's published widths and depth;
    ``"smoke"``: its reduced same-family config (CPU-scale)."""
    if not arch:
        return None
    if size not in ("smoke", "published"):
        raise ValueError(f"backbone size must be smoke|published, got {size!r}")
    return get_config(arch, smoke=size == "smoke")


def _offline_phase(
    num_objects: int,
    num_preds: int,
    backbone_arch: Optional[str],
    seed: int,
    train_size: int = 512,
    backbone_size: str = "smoke",
):
    """Corpus + cascade training + combine/table learning over the GLOBAL
    predicate space (shared by single- and multi-tenant serving).

    -> (preds, evalc, bank, combine, table, qualities)
    """
    rng = jax.random.PRNGKey(seed)
    preds = [Predicate(i, 1) for i in range(num_preds)]
    corpus = make_corpus(
        rng, num_objects + train_size, [p.tag_type for p in preds],
        [p.tag for p in preds], selectivity=[0.3] * num_preds,
        feature_dim=64,
    )
    train, evalc = split_corpus(corpus, train_size)

    backbone_cfg = backbone_config(backbone_arch, backbone_size)
    # one SHARED backbone trunk with per-predicate heads — the stacked
    # layout the fused traceable bank requires
    suite = build_cascade_suite(rng, num_preds, 64, backbone_cfg)
    cascades = []
    qualities = []
    for i in range(num_preds):
        levels = [
            train_level(lvl, train.features, train.truth_pred[:, i])
            for lvl in suite[i]
        ]
        cascades.append(levels)
        qualities.append(
            [
                float(auc_score(lvl.apply_fn(lvl.params, evalc.features),
                                evalc.truth_pred[:, i]))
                for lvl in levels
            ]
        )
    bank = ModelCascadeBank(cascades=cascades, features=evalc.features)

    # offline artifacts: combine weights + decision table from TRAIN outputs
    f = len(cascades[0])
    train_outputs = np.zeros((train.features.shape[0], num_preds, f), np.float32)
    for i in range(num_preds):
        for j, lvl in enumerate(cascades[i]):
            train_outputs[:, i, j] = np.asarray(
                lvl.apply_fn(lvl.params, train.features)
            )
    train_outputs = jnp.asarray(train_outputs)
    combine = fit_combine_weights(
        train_outputs, train.truth_pred.astype(jnp.float32), steps=150
    )
    table = learn_decision_table(train_outputs, combine, num_bins=10,
                                 costs=bank.costs, cost_normalized=True)
    return preds, evalc, bank, combine, table, qualities


def build_server(
    num_objects: int = 512,
    num_preds: int = 1,
    backbone_arch: Optional[str] = "qwen3-1.7b",
    seed: int = 0,
    backbone_size: str = "smoke",
):
    """-> (operator, corpus, truth).  Trains the cascade probes offline."""
    preds, evalc, bank, combine, table, qualities = _offline_phase(
        num_objects, num_preds, backbone_arch, seed,
        backbone_size=backbone_size,
    )
    query = conjunction(*preds)
    truth = truth_answer_mask(evalc, query)
    cfg = OperatorConfig(plan_size=64, function_selection="best")
    op = ProgressiveQueryOperator(
        query, table, combine, bank.costs, bank, cfg, truth_mask=truth
    )
    return op, evalc, truth, qualities


def build_multi_server(
    num_objects: int = 512,
    num_preds: int = 3,
    num_queries: int = 8,
    backbone_arch: Optional[str] = "qwen3-1.7b",
    seed: int = 0,
    preds_per_query: int = 2,
    plan_shards: int = 1,
    backend: str = "jnp",
    backbone_size: str = "smoke",
    pallas_interpret: bool = False,
):
    """Multi-tenant server: Q overlapping conjunctive queries, one substrate.

    Tenants draw random predicate subsets from the corpus schema, so popular
    predicates are requested by many queries — the workload shape where
    cross-query dedup pays.  -> (engine, corpus, truths, qualities, queries)
    """
    preds, evalc, bank, combine, table, qualities = _offline_phase(
        num_objects, num_preds, backbone_arch, seed,
        backbone_size=backbone_size,
    )
    rng = np.random.default_rng(seed + 1)
    queries = []
    for _ in range(num_queries):
        k = min(max(1, preds_per_query), num_preds)
        cols = rng.choice(num_preds, size=k, replace=False)
        queries.append(conjunction(*[preds[c] for c in sorted(cols)]))
    query_set = build_query_set(
        queries, global_predicates=[p.positive() for p in preds]
    )
    # truth_pred columns are the GLOBAL predicate columns — evaluate the
    # reindexed queries, not the local-space originals
    truths = jnp.stack(
        [truth_answer_mask(evalc, rq) for rq in query_set.reindexed]
    )
    cfg = MultiQueryConfig(
        plan_size=64, function_selection="best",
        num_shards=plan_shards, backend=backend,
        pallas_interpret=pallas_interpret,
    )
    engine = MultiQueryEngine(
        query_set, table, combine, bank.costs, bank, cfg, truth_masks=truths
    )
    return engine, evalc, truths, qualities, queries


def serve_query(
    op: ProgressiveQueryOperator,
    num_objects: int,
    epochs: int = 40,
    preemption: Optional[PreemptionHandler] = None,
    target_expected_f: Optional[float] = None,
) -> ServeReport:
    """Progressive evaluation with early termination (pay-as-you-go)."""
    monitor = StragglerMonitor(num_shards=1)
    state = op.init_state(num_objects)
    t0 = time.perf_counter()
    history = []
    sel = None
    for e in range(epochs):
        if preemption is not None and preemption.should_stop:
            break
        te = time.perf_counter()
        state, sel, plan, _ = op.run_epoch(state)
        monitor.record(0, time.perf_counter() - te)
        history.append(
            dict(epoch=e, cost=float(state.cost_spent),
                 expected_f=float(sel.expected_f), size=int(sel.size))
        )
        if int(plan.num_valid()) == 0:
            break
        if target_expected_f is not None and float(sel.expected_f) >= target_expected_f:
            break
    tf1 = None
    if op.truth_mask is not None and sel is not None:
        from repro.core.metrics import true_f_alpha

        tf1 = float(true_f_alpha(sel.mask, op.truth_mask))
    return ServeReport(
        epochs=len(history),
        cost_spent=float(state.cost_spent),
        expected_f=history[-1]["expected_f"] if history else 0.0,
        true_f1=tf1,
        wall_s=time.perf_counter() - t0,
        history=history,
    )


@dataclasses.dataclass
class MultiServeReport:
    epochs: int
    num_queries: int
    cost_spent: float  # shared substrate spend
    requested_cost: float  # what the tenants would have paid without dedup
    expected_f: list  # [Q] final per-query E(F_alpha)
    true_f: Optional[list]  # [Q]
    wall_s: float
    history: list  # per-epoch dicts with per-query + aggregate trajectories

    @property
    def dedup_savings(self) -> float:
        return self.requested_cost - self.cost_spent

    @property
    def mean_expected_f(self) -> float:
        return sum(self.expected_f) / max(len(self.expected_f), 1)


def serve_queries(
    engine: MultiQueryEngine,
    num_objects: int,
    epochs: int = 40,
    preemption: Optional[PreemptionHandler] = None,
    target_expected_f: Optional[float] = None,
) -> MultiServeReport:
    """Multi-tenant progressive evaluation: lockstep epochs over Q queries.

    ``target_expected_f`` terminates early once the *mean* per-query E(F)
    reaches the target (each tenant still gets its own trajectory in the
    history for per-query SLO accounting).
    """
    state = engine.init_state(num_objects)
    t0 = time.perf_counter()
    history = []
    requested = 0.0
    for e in range(epochs):
        if preemption is not None and preemption.should_stop:
            break
        state, sel, plans, merged, wall, prev_cost = engine.run_epoch(state)
        requested += float(jnp.sum(jnp.where(plans.valid, plans.cost, 0.0)))
        per_query_f = [float(x) for x in sel.expected_f]
        mean_f = sum(per_query_f) / len(per_query_f)
        history.append(
            dict(
                epoch=e,
                cost=float(state.cost_spent),
                requested_cost=requested,
                expected_f=per_query_f,
                mean_expected_f=mean_f,
                sizes=[int(x) for x in sel.size],
                merged_valid=int(merged.num_valid()),
            )
        )
        if int(merged.num_valid()) == 0:
            break
        if target_expected_f is not None and mean_f >= target_expected_f:
            break
    tf = None
    if engine.truth_masks is not None and history:
        from repro.core.metrics import true_f_alpha

        tf = [
            float(true_f_alpha(state.per_query.in_answer[i], engine.truth_masks[i],
                               engine.config.alpha))
            for i in range(state.num_queries)
        ]
    return MultiServeReport(
        epochs=len(history),
        num_queries=engine.query_set.num_queries,
        cost_spent=float(state.cost_spent),
        requested_cost=requested,
        expected_f=history[-1]["expected_f"] if history else [],
        true_f=tf,
        wall_s=time.perf_counter() - t0,
        history=history,
    )


# ------------------------------------------------------------ session serving --


def build_session_server(
    num_objects: int = 256,
    capacity: Optional[int] = None,
    num_preds: int = 4,
    max_tenants: int = 8,
    seed: int = 0,
    train_size: int = 512,
    plan_size: int = 64,
    plan_shards: int = 1,
    backend: str = "jnp",
    max_capacity: Optional[int] = None,
    substrate_dtype: str = "float32",
    pallas_interpret: bool = False,
):
    """Long-lived serving session over a simulated (AUC-calibrated) corpus.

    The session owns a capacity-padded output buffer, so its execution bank is
    traceable inside the fused superstep — that is what makes ingest/admit/
    retire pure data events (``core.session``).  The model-cascade bank stays
    on the per-request ``MultiQueryEngine`` loop path above.

    With ``max_capacity > capacity`` the session grows through geometric
    capacity tiers as ingest events overflow the current tier (bounded
    recompiles, ``EngineSession.retrace_bound``); the ingest pool then covers
    ``max_capacity - num_objects`` objects so trace events can force growth.

    -> (session, state, ingest_pool, preds): ``ingest_pool`` holds the
    remaining pre-materialized outputs, streamed in by ``ingest`` trace
    events.
    """
    if capacity is None:
        capacity = 2 * num_objects
    limit = max(capacity, max_capacity or capacity)
    preds = [Predicate(i, 1) for i in range(num_preds)]
    corpus = make_corpus(
        jax.random.PRNGKey(seed), limit + train_size,
        [p.tag_type for p in preds], [p.tag for p in preds],
        selectivity=[0.3] * num_preds,
        aucs=[0.60, 0.88, 0.93, 0.97], costs=[0.01, 0.05, 0.2, 0.5],
    )
    train, evalc = split_corpus(corpus, train_size)
    combine = fit_combine_weights(
        train.func_probs, train.truth_pred.astype(jnp.float32), steps=150
    )
    table = learn_decision_table(train.func_probs, combine, num_bins=10)
    session = EngineSession(
        [p.positive() for p in preds], table, combine, evalc.costs,
        capacity=capacity, max_tenants=max_tenants,
        config=MultiQueryConfig(
            plan_size=plan_size, function_selection="best",
            num_shards=plan_shards, backend=backend,
            substrate_dtype=substrate_dtype, pallas_interpret=pallas_interpret,
        ),
        max_capacity=max_capacity,
    )
    state = session.init_state(evalc.func_probs[:num_objects])
    pool = evalc.func_probs[num_objects:limit]
    return session, state, pool, preds


def build_cascade_session_server(
    num_objects: int = 256,
    num_preds: int = 3,
    max_tenants: int = 8,
    seed: int = 0,
    backbone_arch: Optional[str] = None,
    plan_size: int = 64,
    plan_shards: int = 1,
    backend: str = "jnp",
    substrate_dtype: str = "float32",
    backbone_size: str = "smoke",
    pallas_interpret: bool = False,
):
    """Long-lived serving session whose enrichment is the REAL model-cascade
    bank, traced into the fused scan superstep (``EngineSession(bank=...)``).

    Every epoch's probe/backbone forwards run inside the compiled superstep —
    zero host round-trips — so admit/retire/run churn keeps
    ``superstep_traces == 1`` exactly like the simulated-bank session.  The
    bank's feature table IS the corpus, so the session is fixed-capacity
    (capacity == num_objects) and ingest events are out of scope here.

    -> (session, state, preds, qualities)
    """
    preds, evalc, bank, combine, table, qualities = _offline_phase(
        num_objects, num_preds, backbone_arch, seed,
        backbone_size=backbone_size,
    )
    session = EngineSession(
        [p.positive() for p in preds], table, combine, bank.costs,
        capacity=num_objects, max_tenants=max_tenants,
        config=MultiQueryConfig(
            plan_size=plan_size, function_selection="best",
            num_shards=plan_shards, backend=backend,
            substrate_dtype=substrate_dtype, pallas_interpret=pallas_interpret,
        ),
        bank=bank,
    )
    # no precomputed outputs to seed — the bank computes probabilities inside
    # the superstep; the buffer opens at the prior and is never gathered
    placeholder = jnp.full(
        (num_objects, len(preds), bank.costs.shape[1]),
        session.config.prior, jnp.float32,
    )
    state = session.init_state(placeholder)
    return session, state, preds, qualities


class StreamingIngest:
    """Routes ``ingest`` trace events through the staging/ring front-end.

    Owns a ``PendingRing`` sized by the ``--ingest-*`` flags and an
    ``IngestStream`` whose backpressure callback drains the ring back into
    the serve loop — lockstep drains through a host ``num_rows`` shadow
    (one device sync at attach, none per event), overlap drains through
    ``SessionPipeline.drain_ring`` against the in-flight carry — so a full
    ring under the ``block`` policy resolves itself instead of deadlocking.

    Lockstep backpressure drains land rows without a refresh and leave it
    owed; the next explicit ``drain`` pays it once.  So a ``state`` taken
    after ``feed`` may hold rows whose derived state is stale: settle with
    ``drain`` before running a superstep, sampling or checkpointing it
    (``admit`` and ``retire`` refresh in full on their own).
    """

    def __init__(
        self,
        session: EngineSession,
        *,
        batch_rows: int,
        num_slots: int = 4,
        policy: str = "block",
        rate_rows_per_s: Optional[float] = None,
    ):
        from repro.ingest import IngestStream, PendingRing

        self.ring = PendingRing(
            session, slot_rows=batch_rows, num_slots=num_slots, policy=policy
        )
        self.stream = IngestStream(
            self.ring, batch_rows=batch_rows,
            rate_rows_per_s=rate_rows_per_s, on_pressure=self._on_pressure,
        )
        self._session = session
        self._pipe = None
        self._state = None
        self._num_rows: Optional[int] = None
        self.drains = 0

    def attach_pipeline(self, pipe) -> None:
        self._pipe = pipe

    def attach_lockstep(self, state) -> None:
        self._state = state
        self._num_rows = int(state.num_rows)  # one sync, at attach time

    def begin(self, state) -> None:
        """Lockstep only: adopt the loop's current state before feed/drain
        (run/admit/retire events advanced it since the last ingest)."""
        self._state = state

    @property
    def state(self):
        """Lockstep only: the state after the last feed/drain.  After a
        ``feed`` it may hold rows landed by a backpressure drain whose
        refresh is still owed; only the state after ``drain`` is settled."""
        return self._state

    def feed(self, rows) -> int:
        return self.stream.feed(rows)

    def _on_pressure(self) -> None:
        """A full ring: land its rows and, in lockstep, leave the refresh
        owed — the settling ``drain`` recomputes derived state once for
        every row landed since."""
        self._drain(refresh=False)

    def drain(self) -> None:
        """Settle: land every pending row and refresh once if rows landed
        or a backpressure drain left the refresh owed.  Call it before every
        ``run`` and at the end of the trace."""
        self._drain(refresh=True)

    def _drain(self, *, refresh: bool) -> None:
        if self._pipe is not None:  # refreshes at every drain
            if self._pipe.drain_ring(self.ring):
                self.drains += 1
            return
        self._state, self._num_rows, drained = self.ring.drain_into(
            self._session, self._state, self._num_rows, refresh=refresh
        )
        if drained:
            self.drains += 1

    def counters(self) -> dict:
        return self.stream.counters()


def parse_trace(spec: str) -> list:
    """``"admit:2;run:4;ingest:64;retire:0;run:4"`` -> [(kind, int_arg), ...].

    Kinds: ``run:<epochs>`` scan epochs, ``admit:<k>`` admit a random
    conjunction of k schema predicates, ``ingest:<m>`` stream m pooled
    objects, ``retire:<slot>`` retire a tenant slot.
    """
    events = []
    for tok in spec.replace(",", ";").split(";"):
        tok = tok.strip()
        if not tok:
            continue
        kind, _, arg = tok.partition(":")
        if kind not in ("run", "admit", "ingest", "retire"):
            raise ValueError(f"unknown trace event {tok!r}")
        arg = int(arg)
        # negative/zero args would silently corrupt the serve loop (e.g. a
        # negative ingest rewinds the pool cursor, duplicating objects)
        if kind in ("run", "ingest", "admit") and arg < 1:
            raise ValueError(f"trace event {tok!r}: arg must be >= 1")
        if kind == "retire" and arg < 0:
            raise ValueError(f"trace event {tok!r}: slot must be >= 0")
        events.append((kind, arg))
    return events


@dataclasses.dataclass
class SessionServeReport:
    epochs: int
    events: list
    cost_spent: float
    mean_expected_f: float  # over active tenants at the end
    active_tenants: int
    num_rows: int
    attributed: list  # [S] per-tenant ledger totals
    unattributed: float
    superstep_traces: int
    wall_s: float
    history: list
    capacity: int = 0  # the tier the session ended on
    max_capacity: int = 0
    growths: int = 0  # tier migrations the trace forced
    retrace_bound: int = 1  # max traces per scan shape (1 + ceil(log2(max/cap)))
    overlap: bool = False  # events applied against in-flight chunks
    chunk_size: Optional[int] = None
    num_events: int = 0
    events_per_sec: float = 0.0
    # ---- durability (checkpoint/restore/preemption) ----
    preempted: bool = False  # the trace stopped at a preemption drain
    epochs_total: int = 0  # cumulative epochs INCLUDING pre-restore progress
    events_done: int = 0  # trace events fully completed (cumulative)
    restored_step: Optional[int] = None  # checkpoint step this run resumed from
    cost_hex: str = ""  # float.hex of cost_spent (bitwise-diffable in CI)
    bills_hex: list = dataclasses.field(default_factory=list)  # [S] invoice hex
    answer_digest: str = ""  # sha256 over in_answer[:, :num_rows] (tier-free)
    scan_lengths: list = dataclasses.field(default_factory=list)  # distinct dispatched
    checkpoint_saves: int = 0
    checkpoint_seconds: float = 0.0
    # ---- degraded-mode enrichment (quarantine) ----
    quarantined: list = dataclasses.field(default_factory=list)  # [[pred, func]]
    degraded: bool = False  # any enrichment function quarantined at the end
    # ---- streaming ingestion (staging + pending-row ring) ----
    streaming: bool = False  # ingest events routed through the ring front-end
    substrate_dtype: str = "float32"  # storage dtype of the session substrate
    ring_drains: int = 0  # times the ring flushed into the session
    ingest_counters: dict = dataclasses.field(default_factory=dict)
    # executed (object, predicate) triples per tagging function, live rows
    # only: which cascade levels the planner actually bought
    executed_per_function: list = dataclasses.field(default_factory=list)


HOST_META_FORMAT = 1  # driver-shadow block version inside extra["host"]


def serve_session_trace(
    session: EngineSession,
    state,
    events: list,  # [(kind, arg)] from parse_trace
    pool=None,  # [R, P, F] outputs available to ingest events
    preds=None,  # schema predicates, for admit events
    seed: int = 0,
    preemption: Optional[PreemptionHandler] = None,
    overlap: bool = False,
    chunk_size: Optional[int] = None,
    checkpointer: Optional[SessionCheckpointer] = None,
    resume: Optional[dict] = None,
    heartbeat: Optional[Heartbeat] = None,
    boundary_hook=None,
    streaming: Optional[StreamingIngest] = None,
) -> SessionServeReport:
    """Drive a scripted arrival trace through one long-lived session.

    Every event between runs is a masked data update; the report's
    ``superstep_traces`` staying within the retrace bound is the
    churn-without-retrace witness.

    ``overlap=True`` drives the trace through ``SessionPipeline``: scan
    chunks are dispatched without waiting, events validate against host
    shadows and apply to the in-flight carry, and the single device sync is
    the final drain — bitwise-identical results, with event latency hidden
    behind device compute.  ``chunk_size`` sets the scan dispatch
    granularity for both modes (lockstep still blocks at every run/event
    boundary, which is exactly the overhead ``overlap`` removes).

    **Durability.**  With a ``checkpointer``, snapshots land ONLY at scan-
    chunk boundaries (superstep boundaries — the ``core.durability``
    invariant): lockstep runs snapshot on the checkpointer's cadence via the
    ``on_chunk`` hook; overlap mode snapshots at event boundaries (a cadence
    snapshot there would force the drain the pipeline exists to avoid).  A
    ``preemption`` request stops dispatch at the next boundary, force-saves,
    and returns a ``preempted=True`` report — the SIGTERM -> drain ->
    checkpoint -> exit-0 path.  A clean completion force-saves a final
    checkpoint (event cursor past the end).  ``resume`` takes the
    ``extra["host"]`` block of a checkpoint (see ``main`` ``--restore``):
    the trace re-enters at the saved event cursor, skipping already-run
    epochs of a partially-complete run event, with the ingest-pool cursor
    and the admit RNG's bit-generator state restored — so the resumed
    process replays the uninterrupted run bitwise (``cost_hex``,
    ``bills_hex``, ``answer_digest`` in the report are the CI diff surface).

    ``boundary_hook`` (no-arg callable) fires once per dispatched scan
    chunk, BEFORE the preemption poll of that boundary — the supervisor's
    fault clock: a hook that trips the preemption handler stops dispatch
    and force-saves at that same superstep boundary
    (``runtime.supervisor``).

    With ``streaming`` (``--ingest-batch``), ingest events stage their rows
    through the double-buffered transfer path into the pending-row ring
    instead of applying directly; the ring drains into the session before
    every run event, before overlap-mode event-boundary checkpoints (ring
    contents are not part of a snapshot — drain-then-save keeps restores
    exact), and once at the end.  Results are bitwise identical to direct
    ingest; only the transfer/backpressure schedule differs.
    """
    rng = np.random.default_rng(seed)
    pool_off = 0
    start_event = 0
    start_into = 0  # epochs already run of the resumed-into run event
    epochs_total = 0  # cumulative across restarts (the checkpoint step)
    restored_step = None
    if resume is not None:
        if resume.get("format") != HOST_META_FORMAT:
            raise ValueError(
                f"resume host-meta format {resume.get('format')!r} != "
                f"{HOST_META_FORMAT}"
            )
        rng.bit_generator.state = resume["rng_state"]
        pool_off = int(resume["pool_offset"])
        start_event = int(resume["event_cursor"])
        start_into = int(resume["epochs_into_event"])
        epochs_total = int(resume["epochs_total"])
        restored_step = epochs_total

    def host_meta(cursor: int, into: int, total: int) -> dict:
        # everything the restarted driver needs BEFORE touching array data;
        # rng state must be captured at snapshot time (admits mutate it)
        return dict(
            format=HOST_META_FORMAT,
            event_cursor=cursor,
            epochs_into_event=into,
            epochs_total=total,
            pool_offset=pool_off,
            rng_state=rng.bit_generator.state,
        )

    history = []
    scan_lengths: set = set()
    pipe = (
        session.pipeline(
            state, chunk_size=chunk_size,
            preemption=preemption, heartbeat=heartbeat,
            boundary_hook=boundary_hook,
        )
        if overlap
        else None
    )
    if streaming is not None:
        if pipe is not None:
            streaming.attach_pipeline(pipe)
        else:
            streaming.attach_lockstep(state)
    preempted = False
    events_done = start_event
    t0 = time.perf_counter()
    for idx in range(start_event, len(events)):
        kind, arg = events[idx]
        if preemption is not None and preemption.should_stop:
            preempted = True
            break
        into0 = start_into if idx == start_event else 0
        if kind == "run":
            run_epochs = arg - into0
            if run_epochs <= 0:
                events_done = idx + 1
                continue
            if streaming is not None:
                # pending ring rows join planning before these epochs run
                if pipe is None:
                    streaming.begin(state)
                streaming.drain()
                if pipe is None:
                    state = streaming.state
            if pipe is not None:
                n_chunks = len(pipe._chunks)
                pipe.run(run_epochs)
                scan_lengths.update(
                    length for _, length, _, _ in pipe._chunks[n_chunks:]
                )
                this_run = sum(
                    length for _, length, _, _ in pipe._chunks[n_chunks:]
                )
                epochs_total += this_run
                if pipe.preempted:
                    preempted = True
                    if checkpointer is not None:
                        done = into0 + this_run
                        cursor, into = (
                            (idx + 1, 0) if done >= arg else (idx, done)
                        )
                        pipe.checkpoint(
                            checkpointer, epochs_total,
                            host_meta=host_meta(cursor, into, epochs_total),
                        )
                    break
            else:
                base_total = epochs_total
                stop_box = {"stop": False}
                prev_done = [0]

                def on_chunk(carry, done, _idx=idx, _arg=arg, _into0=into0,
                             _base=base_total, _stop=stop_box, _prev=prev_done):
                    scan_lengths.add(done - _prev[0])
                    _prev[0] = done
                    if heartbeat is not None:
                        heartbeat.beat(0)
                    if boundary_hook is not None:
                        boundary_hook()
                    stop = preemption is not None and preemption.should_stop
                    if checkpointer is not None:
                        into = _into0 + done
                        cursor, rem = (
                            (_idx + 1, 0) if into >= _arg else (_idx, into)
                        )
                        checkpointer.maybe_save(
                            carry, _base + done,
                            host_meta=host_meta(cursor, rem, _base + done),
                            force=stop,
                        )
                    if stop:
                        _stop["stop"] = True
                    return stop

                state, h = session.run(
                    state, run_epochs, stop_when_exhausted=False,
                    chunk_size=chunk_size, on_chunk=on_chunk,
                )
                history.extend(h)
                epochs_total = base_total + prev_done[0]
                if stop_box["stop"]:
                    preempted = True
                    break
        elif kind == "admit":
            if preds is None:
                raise ValueError("admit events need the schema predicates")
            k = min(max(1, arg), len(preds))
            cols = sorted(rng.choice(len(preds), size=k, replace=False))
            query = conjunction(*[preds[c] for c in cols])
            if pipe is not None:
                pipe.admit(query)
            else:
                state, slot = session.admit(state, query)
        elif kind == "ingest":
            if pool is None or pool_off + arg > pool.shape[0]:
                raise ValueError(
                    f"ingest of {arg} exceeds the remaining pool "
                    f"({0 if pool is None else pool.shape[0] - pool_off})"
                )
            batch = pool[pool_off:pool_off + arg]
            if streaming is not None:
                if pipe is None:
                    streaming.begin(state)
                streaming.feed(batch)
                if pipe is None:
                    state = streaming.state
            elif pipe is not None:
                pipe.ingest(batch)
            else:
                state = session.ingest(state, batch)
            pool_off += arg
        else:  # retire
            if pipe is not None:
                pipe.retire(arg)
            else:
                state = session.retire(state, arg)
        events_done = idx + 1
        if pipe is not None and checkpointer is not None:
            if streaming is not None:
                streaming.drain()  # ring rows are not part of a snapshot
            # overlap cadence: event boundaries (drains the in-flight chunks)
            pipe.checkpoint(
                checkpointer, epochs_total,
                host_meta=host_meta(idx + 1, 0, epochs_total),
                force=False,
            )
    if streaming is not None:
        # rows still parked in the ring (trace ended on ingest, or shed/spill
        # holdover) land before the final answers are read
        if pipe is None:
            streaming.begin(state)
        streaming.drain()
        if pipe is None:
            state = streaming.state
    if pipe is not None:
        state, history = pipe.finish()  # the pipeline's single sync point
    if preempted and checkpointer is not None:
        # preemption seen BETWEEN events (the in-run paths force-saved
        # already, leaving last_step == epochs_total): snapshot at the event
        # cursor so the restart replays any later churn events untouched
        if checkpointer.last_step != epochs_total:
            checkpointer.save(
                state, epochs_total,
                host_meta=host_meta(events_done, 0, epochs_total),
            )
    if not preempted and checkpointer is not None:
        # clean completion: a final restore point past the last event
        checkpointer.save(
            state, epochs_total,
            host_meta=host_meta(len(events), 0, epochs_total),
        )
    wall = time.perf_counter() - t0
    last = history[-1] if history else None
    num_rows = int(state.num_rows)
    answers = np.ascontiguousarray(
        np.asarray(state.derived.in_answer)[:, :num_rows]
    )
    bills = state.ledger.bills(state.cost_spent)
    quarantined = []
    if state.quarantined is not None:
        qm = np.asarray(jax.device_get(state.quarantined))
        quarantined = [[int(i), int(j)] for i, j in zip(*np.nonzero(qm))]
    return SessionServeReport(
        epochs=len(history),
        events=[dict(kind=k, arg=a) for k, a in events],
        cost_spent=float(state.cost_spent),
        mean_expected_f=last.mean_expected_f if last else 0.0,
        active_tenants=int(np.asarray(state.active).sum()),
        num_rows=num_rows,
        attributed=[float(x) for x in np.asarray(state.ledger.attributed)],
        unattributed=float(state.ledger.unattributed),
        superstep_traces=session.superstep_traces,
        wall_s=wall,
        history=history,
        capacity=int(state.capacity),
        max_capacity=session.max_capacity,
        growths=session.growths,
        retrace_bound=session.retrace_bound,
        overlap=overlap,
        chunk_size=chunk_size,
        num_events=len(events),
        events_per_sec=len(events) / max(wall, 1e-9),
        preempted=preempted,
        epochs_total=epochs_total,
        events_done=events_done,
        restored_step=restored_step,
        cost_hex=float(state.cost_spent).hex(),
        bills_hex=[float(b).hex() for b in bills],
        answer_digest=hashlib.sha256(answers.tobytes()).hexdigest(),
        scan_lengths=sorted(scan_lengths),
        checkpoint_saves=0 if checkpointer is None else checkpointer.saves,
        checkpoint_seconds=(
            0.0 if checkpointer is None else checkpointer.save_seconds
        ),
        quarantined=quarantined,
        degraded=bool(quarantined),
        streaming=streaming is not None,
        substrate_dtype=session.config.substrate_dtype,
        ring_drains=0 if streaming is None else streaming.drains,
        ingest_counters={} if streaming is None else streaming.counters(),
        executed_per_function=[
            int(c) for c in np.asarray(
                state.substrate.exec_mask[:num_rows]
            ).sum(axis=(0, 1))
        ],
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--objects", type=int, default=512)
    ap.add_argument("--preds", type=int, default=1)
    ap.add_argument("--epochs", type=int, default=40)
    ap.add_argument("--backbone", default="qwen3-1.7b")
    ap.add_argument("--backbone-size", default="smoke",
                    choices=("smoke", "published"),
                    help="cascade backbone at its reduced smoke config or at "
                         "the architecture's published widths and depth "
                         "(weights random from --seed)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus, the cascade weights and the "
                         "trace's admit draws")
    ap.add_argument("--queries", type=int, default=1,
                    help=">1 serves Q concurrent queries over one shared substrate")
    ap.add_argument("--preds-per-query", type=int, default=2)
    ap.add_argument("--plan-shards", type=int, default=1,
                    help="hierarchical plan selection over this many object "
                         "shards (byte-identical to unsharded planning)")
    ap.add_argument("--backend", default="jnp", choices=("jnp", "pallas"),
                    help="benefit-scoring backend for the multi-tenant engine")
    ap.add_argument("--pallas-interpret", action="store_true",
                    help="run the Pallas kernels in the interpreter (hosts "
                         "without a TPU); by default they compile for the "
                         "device")
    ap.add_argument("--session", action="store_true",
                    help="serve a long-lived EngineSession driven by a "
                         "scripted ingest/admit/retire arrival trace")
    ap.add_argument("--bank", default="simulated",
                    choices=("simulated", "cascade"),
                    help="session enrichment bank: 'simulated' (precomputed "
                         "AUC-calibrated outputs, ingest-capable) or "
                         "'cascade' (REAL model-cascade forwards traced into "
                         "the fused superstep; fixed corpus, no ingest)")
    ap.add_argument("--capacity", type=int, default=None,
                    help="session row capacity (default 2x --objects)")
    ap.add_argument("--max-capacity", type=int, default=None,
                    help="grow the session past --capacity through geometric "
                         "capacity tiers up to this bound when ingest events "
                         "overflow (at most 1 + ceil(log2(max/cap)) superstep "
                         "recompiles per scan shape; default: no growth)")
    ap.add_argument("--max-tenants", type=int, default=8,
                    help="pre-allocated session tenant slots")
    ap.add_argument("--trace", default=None,
                    help="session arrival trace, e.g. "
                         "'admit:2;run:4;ingest:64;admit:3;run:4;retire:0;run:4'")
    ap.add_argument("--substrate-dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="storage dtype of the session substrate (func_probs "
                         "and derived probabilities; bfloat16 halves HBM at "
                         "unchanged f32 scoring math — dequant-in-tile)")
    ap.add_argument("--ingest-batch", type=int, default=None, metavar="ROWS",
                    help="stream ingest trace events through the staging + "
                         "pending-row-ring front-end in micro-batches of this "
                         "many rows (enables streaming ingestion; results "
                         "stay bitwise identical to direct ingest)")
    ap.add_argument("--ring-capacity", type=int, default=4, metavar="SLOTS",
                    help="pending-row ring slots; arrivals beyond "
                         "ring + drain rate hit --ingest-policy")
    ap.add_argument("--ingest-rate", type=float, default=None,
                    metavar="ROWS_PER_S",
                    help="throttle staged arrivals to this many rows/s "
                         "(default: unthrottled)")
    ap.add_argument("--ingest-policy", default="block",
                    choices=("block", "shed", "spill"),
                    help="full-ring behavior: block (drain then retry), shed "
                         "(drop + count), spill (host-side FIFO overflow)")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="scan dispatch granularity: run events scan this many "
                         "epochs per device dispatch (bitwise inert; the unit "
                         "of event overlap)")
    ap.add_argument("--overlap", action="store_true",
                    help="apply trace events against in-flight scan chunks "
                         "(async pipeline: no device syncs until the final "
                         "drain) instead of lockstep between runs")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="durable sessions: snapshot the full session state "
                         "here at scan-chunk boundaries (atomic step_N dirs); "
                         "SIGTERM drains in-flight chunks, checkpoints, and "
                         "exits 0")
    ap.add_argument("--checkpoint-every", type=int, default=4,
                    help="snapshot cadence in scan-chunk boundaries "
                         "(lockstep mode; overlap snapshots at event "
                         "boundaries)")
    ap.add_argument("--checkpoint-keep", type=int, default=3,
                    help="checkpoints retained after each save")
    ap.add_argument("--restore", action="store_true",
                    help="resume the trace from the latest checkpoint in "
                         "--checkpoint-dir (bitwise-identical to an "
                         "uninterrupted run; works onto a different "
                         "--plan-shards or capacity tier)")
    ap.add_argument("--restore-step", type=int, default=None,
                    help="restore this checkpoint step instead of the latest")
    ap.add_argument("--supervise", action="store_true",
                    help="run the session trace under runtime.supervisor: "
                         "heartbeat-driven failure detection, elastic shrink "
                         "(ElasticPolicy), restore-on-the-shrunken-mesh, and "
                         "enrichment-function quarantine with backoff probes "
                         "(requires --checkpoint-dir)")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="deterministic chaos schedule at named chunk "
                         "boundaries, e.g. 'kill:w1@chunk:6;"
                         "raise:p2.f1@chunk:5+3;slow:w0*4@chunk:3+8;"
                         "silence:w1@chunk:4+2' (see runtime.chaos; "
                         "requires --supervise)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for 'auto' fault boundaries in --inject-faults")
    ap.add_argument("--heartbeat-timeout", type=float, default=2.0,
                    help="supervised mode: chunk boundaries of silence before "
                         "a worker is declared failed")
    ap.add_argument("--report", default=None,
                    help="write the session serve report as JSON (the CI "
                         "kill-and-resume job's bitwise diff surface)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="record the serve loop with jax.profiler into DIR "
                         "(README: Tracing a session)")
    args = ap.parse_args(argv)

    use_compile_cache()
    if args.profile_dir is None:
        return _serve(ap, args)
    jax.profiler.start_trace(args.profile_dir)
    try:
        return _serve(ap, args)
    finally:
        jax.profiler.stop_trace()


def _serve(ap, args) -> int:
    handler = PreemptionHandler().install()
    if args.session:
        if args.bank == "cascade":
            if args.ingest_batch is not None or args.max_capacity is not None:
                ap.error("--bank cascade serves a fixed corpus: no "
                         "--ingest-batch / --max-capacity growth")
            if args.supervise:
                ap.error("--bank cascade is not wired into --supervise yet")
            session, state, preds, qualities = build_cascade_session_server(
                num_objects=args.objects, num_preds=max(args.preds, 2),
                max_tenants=args.max_tenants, seed=args.seed,
                backbone_arch=args.backbone, backbone_size=args.backbone_size,
                plan_shards=args.plan_shards, backend=args.backend,
                substrate_dtype=args.substrate_dtype,
                pallas_interpret=args.pallas_interpret,
            )
            pool = None
            print(f"[serve] cascade qualities (AUC): {qualities}")
        else:
            session, state, pool, preds = build_session_server(
                num_objects=args.objects, capacity=args.capacity,
                num_preds=max(args.preds, 2), max_tenants=args.max_tenants,
                seed=args.seed, plan_shards=args.plan_shards,
                backend=args.backend, max_capacity=args.max_capacity,
                substrate_dtype=args.substrate_dtype,
                pallas_interpret=args.pallas_interpret,
            )
        streaming = None
        if args.ingest_batch is not None:
            if args.supervise:
                ap.error("--ingest-batch is not wired into --supervise yet")
            streaming = StreamingIngest(
                session, batch_rows=args.ingest_batch,
                num_slots=args.ring_capacity, policy=args.ingest_policy,
                rate_rows_per_s=args.ingest_rate,
            )
        checkpointer = None
        if args.checkpoint_dir:
            checkpointer = SessionCheckpointer(
                session, args.checkpoint_dir,
                every=args.checkpoint_every, keep=args.checkpoint_keep,
            )
        resume = None
        if args.restore:
            if not args.checkpoint_dir:
                ap.error("--restore requires --checkpoint-dir")
            # build_session_server is deterministic given (args, seed), so
            # the restored state drops into an identically-schema'd session;
            # the restore re-pads onto THIS session's tiers and shard count
            state, step, extra = restore_session_checkpoint(
                session, args.checkpoint_dir, step=args.restore_step
            )
            resume = extra.get("host")
            if resume is None:
                ap.error("checkpoint has no serve host metadata to resume")
            print(
                f"[serve] restored step {step} (event cursor "
                f"{resume['event_cursor']}, {resume['epochs_total']} epochs "
                f"done, {extra['num_rows']} rows) onto tier "
                f"{state.capacity} x {args.plan_shards} shard(s)"
            )
        e = max(args.epochs // 4, 1)
        # the default trace's big ingest forces tier growth when
        # --max-capacity extends the pool past the base capacity; the
        # cascade bank serves its fixed corpus, so its default churns
        # tenants only
        if pool is None:
            spec = args.trace or (
                f"admit:2;run:{e};admit:2;run:{e};retire:0;run:{e}"
            )
        else:
            spec = args.trace or (
                f"admit:2;admit:2;run:{e};ingest:{pool.shape[0] // 2};run:{e};"
                f"admit:3;run:{e};retire:0;run:{e}"
            )
        events = parse_trace(spec)
        if pool is None and any(k == "ingest" for k, _ in events):
            ap.error("--bank cascade serves a fixed corpus; drop ingest "
                     "events from --trace")
        supervision = None
        if args.inject_faults and not args.supervise:
            ap.error("--inject-faults requires --supervise")
        if args.supervise:
            if not args.checkpoint_dir:
                ap.error("--supervise requires --checkpoint-dir")
            if args.restore:
                ap.error("--supervise owns restore; drop --restore")
            from repro.runtime.chaos import parse_fault_spec
            from repro.runtime.supervisor import Supervisor, SupervisorConfig

            plan = (
                parse_fault_spec(args.inject_faults, seed=args.fault_seed)
                if args.inject_faults
                else None
            )
            sup = Supervisor(
                session, state, events, pool=pool, preds=preds,
                checkpoint_dir=args.checkpoint_dir, fault_plan=plan,
                external=handler, chunk_size=args.chunk_size,
                overlap=args.overlap,
                config=SupervisorConfig(
                    heartbeat_timeout=args.heartbeat_timeout,
                    checkpoint_every=args.checkpoint_every,
                    checkpoint_keep=args.checkpoint_keep,
                ),
            )
            report = sup.serve()
            supervision = sup.summary()
            print(
                f"[serve] supervised: state={supervision['final_state']}, "
                f"{supervision['restarts']} restarts, "
                f"shrinks={supervision['shrinks']}, "
                f"quarantined={supervision['quarantined']}, "
                f"recovered={supervision['recovered']}, "
                f"transitions={supervision['transitions']}"
            )
        else:
            report = serve_session_trace(
                session, state, events, pool=pool, preds=preds,
                seed=args.seed, preemption=handler, overlap=args.overlap,
                chunk_size=args.chunk_size,
                checkpointer=checkpointer, resume=resume,
                streaming=streaming,
            )
        eps = report.epochs / max(report.wall_s, 1e-9)
        bills = {i: f"{c:.3f}" for i, c in enumerate(report.attributed) if c > 0}
        mode = "overlap" if args.overlap else "lockstep"
        print(
            f"[serve] session trace {spec!r} ({mode}, chunk="
            f"{args.chunk_size}): {report.epochs} epochs "
            f"({report.epochs_total} total), "
            f"{report.num_rows} rows (tier {report.capacity} of "
            f"{report.max_capacity} max, {report.growths} growths), "
            f"{report.active_tenants} active tenants, "
            f"cost={report.cost_spent:.4f} (planner units), "
            f"mean E(F1)={report.mean_expected_f:.3f}, "
            f"ledger={bills} (+{report.unattributed:.4f} unattributed), "
            f"superstep traces={report.superstep_traces}, "
            f"wall={report.wall_s:.1f}s ({eps:.2f} epochs/s, "
            f"{report.events_per_sec:.2f} events/s)"
            + (f", {report.checkpoint_saves} checkpoints"
               if checkpointer is not None else "")
            + (" [PREEMPTED: drained + checkpointed]"
               if report.preempted else "")
        )
        if report.streaming:
            c = report.ingest_counters
            print(
                f"[serve] streaming ingest ({args.substrate_dtype} substrate, "
                f"batch={args.ingest_batch} x {args.ring_capacity} slots, "
                f"policy={args.ingest_policy}): "
                f"{c.get('pushed_rows', 0)} rows staged, "
                f"{report.ring_drains} drains, "
                f"blocked={c.get('blocked', 0)}, "
                f"shed={c.get('shed_rows', 0)}, "
                f"spilled={c.get('spilled_rows', 0)}"
            )
        if args.report:
            payload = {
                k: getattr(report, k)
                for k in (
                    "epochs", "epochs_total", "events_done", "num_events",
                    "cost_spent", "cost_hex", "bills_hex", "answer_digest",
                    "attributed", "unattributed", "num_rows", "capacity",
                    "growths", "superstep_traces", "retrace_bound",
                    "preempted", "restored_step", "scan_lengths",
                    "checkpoint_saves", "active_tenants", "mean_expected_f",
                    "quarantined", "degraded",
                    "streaming", "substrate_dtype", "ring_drains",
                    "ingest_counters", "executed_per_function", "wall_s",
                )
            }
            cfg = backbone_config(args.backbone, args.backbone_size)
            if args.bank == "cascade" and cfg is not None:
                payload["backbone"] = dict(
                    name=cfg.name, num_layers=cfg.num_layers,
                    d_model=cfg.d_model, num_heads=cfg.num_heads,
                    num_kv_heads=cfg.num_kv_heads, d_ff=cfg.d_ff,
                )
            payload["function_costs"] = np.asarray(session.program.costs).tolist()
            # the bank's arrays reach the compiled superstep as arguments
            payload["bank_param_bytes"] = sum(
                x.nbytes for x in jax.tree.leaves(session.program.bank_params)
            )
            if supervision is not None:
                payload["supervision"] = supervision
            with open(args.report, "w") as fh:
                json.dump(payload, fh, indent=1, sort_keys=True)
        # each DISTINCT dispatched scan length (with chunking: chunk length +
        # tail remainders, not run length) legitimately compiles its own scan
        # program once per capacity tier the trace actually VISITED
        # (growths + 1); anything beyond means a churn event re-traced the
        # superstep
        # (supervised runs recompile legitimately across restarts/reshards —
        # the final pass's session only saw its own scan lengths, so the
        # accounting below still holds per pass)
        expected = max(len(report.scan_lengths), 1) * (report.growths + 1)
        if not args.supervise and report.superstep_traces > expected:
            print(
                f"[serve] WARNING: superstep re-traced under churn "
                f"({report.superstep_traces} traces for {expected} scan "
                "shape x visited-tier combinations)"
            )
            return 1
        return 0
    if args.queries > 1:
        engine, corpus, truths, qualities, queries = build_multi_server(
            args.objects, args.preds, args.queries, args.backbone,
            seed=args.seed, preds_per_query=args.preds_per_query,
            plan_shards=args.plan_shards, backend=args.backend,
            backbone_size=args.backbone_size,
            pallas_interpret=args.pallas_interpret,
        )
        print(f"[serve] cascade qualities (AUC): {qualities}")
        report = serve_queries(engine, args.objects, args.epochs, handler)
        tf = ([f"{x:.3f}" for x in report.true_f] if report.true_f else "n/a")
        eps = report.epochs / max(report.wall_s, 1e-9)
        print(
            f"[serve] {report.num_queries} queries x {report.epochs} epochs, "
            f"cost={report.cost_spent:.4f} (planner units) "
            f"(requested {report.requested_cost:.4f}, dedup saved "
            f"{report.dedup_savings:.4f}), mean E(F1)={report.mean_expected_f:.3f}, "
            f"per-query E(F1)={[f'{x:.3f}' for x in report.expected_f]}, "
            f"true F1={tf}, wall={report.wall_s:.1f}s ({eps:.2f} epochs/s)"
        )
        return 0

    op, corpus, truth, qualities = build_server(
        args.objects, args.preds, args.backbone, seed=args.seed,
        backbone_size=args.backbone_size,
    )
    print(f"[serve] cascade qualities (AUC): {qualities}")
    report = serve_query(op, args.objects, args.epochs, handler)
    eps = report.epochs / max(report.wall_s, 1e-9)
    print(
        f"[serve] {report.epochs} epochs, cost={report.cost_spent:.4f} (planner units), "
        f"E(F1)={report.expected_f:.3f}, true F1={report.true_f1:.3f}, "
        f"wall={report.wall_s:.1f}s ({eps:.2f} epochs/s)"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
