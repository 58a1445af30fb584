"""Streaming-ingestion benchmark: staged double-buffered transfers + the
pending-row ring vs synchronous per-batch ingest, on a bf16 substrate.

The serving sessions made ingest a pure data update; this benchmark measures
the remaining cost of GETTING rows there — the host->device transfer and the
per-call derived-state refresh.  The SAME arrival schedule (an initial admit +
run burst, then rounds of an ingest wave followed by a short scan) runs
through two ingestion postures over one shared million-row-capacity session:

* **sync** — the pre-ring posture: every micro-batch calls
  ``EngineSession.ingest`` directly (per-call derived refresh) and blocks on
  the device before the next batch, the way a naive driver polls its updates;
* **overlap** — the ``repro.ingest`` front-end: ``IngestStream`` quantizes
  each micro-batch into pinned staging memory (double-buffered, so staging
  buffer ``i % 2`` is reused only after the transfer two pushes back was
  consumed), ships it with async ``device_put``, and parks it in the donated
  ``PendingRing``; the ring drains into ``SessionPipeline``'s in-flight carry
  (one derived refresh per drain, no host sync anywhere) under the ``block``
  backpressure policy.

Both postures apply identical row data at identical run boundaries, so final
spend / answers / ledger are bitwise identical (asserted) — the gap is pure
transfer/sync/refresh overhead, reported as sustained events/sec and rows/sec
plus the ingest-to-first-answer latency (first staged row of the first wave ->
completion of the first epoch that could answer over it).  The substrate is
**bfloat16** end to end: rows quantize host-side in the staging buffers, ride
the ring at storage dtype, and dequantize in-register inside the scoring tile
(``kernels/enrich_score``); ``parity`` in the payload re-checks the bf16
dequant-in-tile exactness contract on a small Pallas fixture.  Results land
in ``BENCH_ingest.json`` with the shared ``meta`` block carrying
``substrate_dtype`` / ``substrate_hbm_bytes``.

    PYTHONPATH=src python -m benchmarks.ingest [--full] [--out BENCH_ingest.json]

``--full`` is the headline configuration: capacity 2^20 rows (the million-row
floor) with ~122k-row waves in 8192-row micro-batches.  The default (CI) run
keeps the identical structure at 4096-row capacity.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_meta
from repro.core import conjunction
from repro.core.state import substrate_hbm_bytes
from repro.ingest import IngestStream, PendingRing
from repro.launch.serve import build_session_server


def _schedule(rounds: int, wave_rows: int, warm_epochs: int, run_epochs: int):
    """[admit, run:warm, (ingest:wave, run:E) x rounds] — the arrival shape
    where ingestion cost is visible: every wave must land before the next
    scan burst plans over it."""
    ev = [("admit", 2), ("run", warm_epochs)]
    for _ in range(rounds):
        ev.append(("ingest", wave_rows))
        ev.append(("run", run_epochs))
    return ev


def _drive(session, state0, preds, pool_np, schedule, batch, slots, chunk,
           overlap: bool):
    """Run the schedule in one posture -> (stats, answers, num_rows).

    ``overlap=False`` is the synchronous baseline: each ``batch``-row
    micro-batch is a direct ``session.ingest`` (per-call refresh) followed by
    a host sync — one round-trip per micro-batch.  ``overlap=True`` feeds the
    same micro-batches through ``IngestStream`` -> ``PendingRing`` ->
    ``SessionPipeline.drain_ring`` with zero host syncs until the final
    drain.  Both postures drain all pending rows before every run event, so
    the scans plan over identical substrates.
    """
    state = state0
    pool_off = 0
    query = conjunction(*[p.positive() for p in preds[:2]])
    events = 0
    ingested = 0
    t_first_feed = None
    first_epoch_after_wave = None  # epoch index of the run after wave 1
    latency_s = None
    epochs = 0

    pipe = session.pipeline(state, chunk_size=chunk) if overlap else None
    stream = None
    drains = [0]
    if overlap:
        ring = PendingRing(
            session, slot_rows=batch, num_slots=slots, policy="block"
        )

        def on_pressure():
            if pipe.drain_ring(ring):
                drains[0] += 1

        stream = IngestStream(ring, batch_rows=batch, on_pressure=on_pressure)
    t0 = time.perf_counter()
    for kind, arg in schedule:
        if kind == "admit":
            if pipe is not None:
                pipe.admit(query)
            else:
                state, _slot = session.admit(state, query)
            events += 1
        elif kind == "run":
            if pipe is not None:
                if stream is not None and pipe.drain_ring(ring):
                    drains[0] += 1
                pipe.run(arg)
            else:
                state, hist = session.run(
                    state, arg, stop_when_exhausted=False, chunk_size=chunk
                )
                if latency_s is None and t_first_feed is not None:
                    latency_s = time.perf_counter() - t_first_feed
            if first_epoch_after_wave is None and t_first_feed is not None:
                first_epoch_after_wave = epochs
            epochs += arg
            events += 1
        else:  # ingest wave, fed as micro-batches of `batch` rows
            for lo in range(pool_off, pool_off + arg, batch):
                rows = pool_np[lo:min(lo + batch, pool_off + arg)]
                if t_first_feed is None:
                    t_first_feed = time.perf_counter()
                if stream is not None:
                    stream.feed(rows)
                else:
                    state = session.ingest(state, rows)
                    # the sync posture: a device round-trip per micro-batch
                    jax.block_until_ready(state.num_rows)
                events += 1
                ingested += rows.shape[0]
            pool_off += arg
    if pipe is not None:
        if stream is not None and pipe.drain_ring(ring):
            drains[0] += 1
        state, _history = pipe.finish()
        if first_epoch_after_wave is not None and pipe.stamps:
            # stamps share the pipeline's clock: epoch completion wall minus
            # the moment the wave's first row entered staging
            latency_s = (
                pipe.stamps[first_epoch_after_wave][0]
                - (t_first_feed - pipe._t0)
            )
    wall = time.perf_counter() - t0
    led = state.ledger
    stats = dict(
        overlap=overlap,
        wall_s=wall,
        epochs=epochs,
        events=events,
        ingested_rows=ingested,
        events_per_sec=events / max(wall, 1e-9),
        rows_per_sec=ingested / max(wall, 1e-9),
        ingest_to_first_answer_s=latency_s,
        cost_spent=float(state.cost_spent),
        cost_hex=float(state.cost_spent).hex(),
        superstep_traces=session.superstep_traces,
        ring_drains=drains[0],
        ingest_counters=None if stream is None else stream.counters(),
        ledger=dict(
            attributed=[float(x) for x in np.asarray(led.attributed)],
            unattributed=float(led.unattributed),
            reconcile_abs=abs(float(led.reconcile(state.cost_spent))),
        ),
    )
    num_rows = int(state.num_rows)
    answers = np.asarray(state.derived.in_answer)[:, :num_rows].copy()
    return stats, answers, num_rows


def _pallas_bf16_parity(seed: int = 0):
    """Re-check the dequant-in-tile exactness contract on a small fixture.

    Planning-driving outputs (benefit / next_fn, and the plans they give,
    costs included) must be BITWISE between the bf16-fed kernel and its
    f32-upcast reference in both function-selection modes; best-mode
    ``est_joint`` is 1-ulp-stable (XLA output-fusion contraction — see the
    kernel module docstring).
    """
    from repro.core.decision_table import fallback_decision_table
    from repro.core.entropy import binary_entropy
    from repro.core.plan import select_plan
    from repro.kernels.enrich_score import ops as es_ops

    p_, f_, n_, q_ = 3, 4, 512, 3
    table = fallback_decision_table(
        p_, f_, auc=jnp.full((p_, f_), 0.85), num_bins=10
    )
    rng = np.random.default_rng(seed)
    costs = jnp.asarray(rng.uniform(0.05, 1.0, (p_, f_)), jnp.float32)
    pp = jnp.asarray(rng.uniform(0.01, 0.99, (n_, p_)), jnp.bfloat16)
    unc = binary_entropy(pp.astype(jnp.float32)).astype(jnp.bfloat16)
    sid = jnp.asarray(rng.integers(0, 2 ** f_, (n_, p_)), jnp.int32)
    joint = jnp.asarray(rng.uniform(0.0, 1.0, (q_, n_)), jnp.bfloat16)

    out = {}
    for mode in ("table", "best"):
        lo = es_ops.fused_benefits_batched(
            pp, unc, sid, joint, table, costs,
            function_selection=mode, interpret=True,
        )
        hi = es_ops.fused_benefits_batched(
            pp.astype(jnp.float32), unc.astype(jnp.float32), sid,
            joint.astype(jnp.float32), table, costs,
            function_selection=mode, interpret=True,
        )
        bit = lambda a, b: bool(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
        )
        plan_lo, plan_hi = (
            jax.vmap(lambda b: select_plan(b, 64, costs))(x) for x in (lo, hi)
        )
        ej_lo = np.asarray(lo.est_joint).view(np.int32).astype(np.int64)
        ej_hi = np.asarray(hi.est_joint).view(np.int32).astype(np.int64)
        out[mode] = dict(
            benefit_bitwise=bit(lo.benefit, hi.benefit),
            next_fn_bitwise=bit(lo.next_fn, hi.next_fn),
            plan_bitwise=all(map(bit, plan_lo, plan_hi)),
            est_joint_max_ulp=int(np.abs(ej_lo - ej_hi).max()),
        )
    out["planning_outputs_bitwise"] = all(
        out[m][k]
        for m in ("table", "best")
        for k in ("benefit_bitwise", "next_fn_bitwise", "plan_bitwise")
    )
    return out


def bench_ingest(small: bool = True, out_path: str = "BENCH_ingest.json"):
    if small:
        capacity, n0 = 1 << 12, 1 << 10
        rounds, batch, slots = 4, 256, 2  # 3-batch waves overflow a 2-slot ring
        warm_epochs, run_epochs, chunk = 2, 1, 1
    else:
        capacity, n0 = 1 << 20, 1 << 16  # the million-row floor
        rounds, batch, slots = 8, 8192, 4
        warm_epochs, run_epochs, chunk = 1, 1, 1
    num_preds = 4
    wave_rows = (capacity - n0) // rounds
    dtype = "bfloat16"

    session, state0, pool, preds = build_session_server(
        num_objects=n0, capacity=capacity, num_preds=num_preds,
        max_tenants=4, substrate_dtype=dtype,
    )
    pool_np = np.asarray(pool)  # arrivals are HOST data; staging quantizes
    schedule = _schedule(rounds, wave_rows, warm_epochs, run_epochs)

    # warm the chunk program + refresh/update jits on a scratch lineage so
    # both postures time steady-state serving, not XLA compilation
    scratch, _ = session.admit(state0, conjunction(preds[0].positive()))
    scratch, _h = session.run(
        scratch, chunk, stop_when_exhausted=False, chunk_size=chunk
    )
    scratch = session.ingest(scratch, pool_np[:batch])
    jax.block_until_ready(scratch.num_rows)

    sync_stats, sync_ans, sync_rows = _drive(
        session, state0, preds, pool_np, schedule, batch, slots, chunk,
        overlap=False,
    )
    over_stats, over_ans, over_rows = _drive(
        session, state0, preds, pool_np, schedule, batch, slots, chunk,
        overlap=True,
    )

    spend_identical = sync_stats["cost_hex"] == over_stats["cost_hex"]
    answers_identical = bool(
        sync_rows == over_rows and np.array_equal(sync_ans, over_ans)
    )
    ledger_identical = (
        sync_stats["ledger"]["attributed"]
        == over_stats["ledger"]["attributed"]
    )
    speedup = over_stats["events_per_sec"] / max(
        sync_stats["events_per_sec"], 1e-9
    )
    parity = _pallas_bf16_parity()

    payload = dict(
        benchmark="ingest",
        meta=bench_meta(
            capacity=capacity,
            active_tenants=1,
            events=schedule,
            chunk_size=chunk,
            backend="jnp",
            num_shards=1,
            substrate_dtype=dtype,
            substrate_hbm_bytes=substrate_hbm_bytes(
                capacity, num_preds, 4, dtype=dtype
            ),
        ),
        config=dict(
            num_objects=n0, capacity=capacity, num_preds=num_preds,
            rounds=rounds, wave_rows=wave_rows, batch_rows=batch,
            ring_slots=slots, policy="block", chunk_size=chunk,
            warm_epochs=warm_epochs, run_epochs=run_epochs, small=small,
        ),
        sync=sync_stats,
        overlap=over_stats,
        speedup_events_per_sec=speedup,
        spend_identical=bool(spend_identical),
        answers_identical=answers_identical,
        ledger_identical=bool(ledger_identical),
        parity=parity,
    )
    with open(out_path, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return [
        dict(
            name=f"ingest_C{capacity}_{dtype}_batch{batch}",
            us_per_call=1e6 / max(over_stats["rows_per_sec"], 1e-9),
            derived=(
                f"speedup={speedup:.2f}x"
                f";overlap_rows_ps={over_stats['rows_per_sec']:.0f}"
                f";sync_rows_ps={sync_stats['rows_per_sec']:.0f}"
                f";latency_s={over_stats['ingest_to_first_answer_s']:.3f}"
                f";blocked={over_stats['ingest_counters']['blocked']}"
                f";spend_identical={spend_identical}"
                f";answers_identical={answers_identical}"
                f";parity={parity['planning_outputs_bitwise']}"
            ),
        )
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="million-row capacity (2^20); default is CI scale")
    ap.add_argument("--out", default="BENCH_ingest.json")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    for r in bench_ingest(small=not args.full, out_path=args.out):
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
