"""Streaming ingestion front-end (``repro.ingest``) and the dtype-
parameterized substrate: ring semantics under every backpressure policy,
bitwise ring-vs-direct parity, staged transfers, bf16 sessions end to end,
checkpoint dtype strictness, and the dequant-in-tile exactness contract."""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CapacityError,
    EngineSession,
    IngestBackpressure,
    MultiQueryConfig,
    Predicate,
    SubstrateDtypeError,
    conjunction,
    fallback_decision_table,
)
from repro.core.combine import default_combine_params
from repro.core.durability import (
    restore_session_checkpoint,
    save_session_checkpoint,
)
from repro.core.state import (
    apply_outputs_to_substrate,
    ingest_rows,
    init_substrate,
)
from repro.data.synthetic import make_corpus
from repro.ingest import IngestStream, PendingRing
from repro.launch.serve import StreamingIngest, parse_trace, serve_session_trace

P_GLOBAL, F, N = 4, 4, 96


def _world(seed=0, num_objects=N):
    preds = [Predicate(i, 1) for i in range(P_GLOBAL)]
    corpus = make_corpus(
        jax.random.PRNGKey(seed), num_objects, [p.tag_type for p in preds],
        [p.tag for p in preds], selectivity=[0.3, 0.4, 0.25, 0.35],
    )
    combine = default_combine_params(corpus.aucs)
    table = fallback_decision_table(P_GLOBAL, F, corpus.aucs)
    return preds, corpus, combine, table


def _session(capacity=N, max_tenants=2, dtype="float32", seed=0,
             num_objects=N, max_capacity=None, **cfg_kw):
    preds, corpus, combine, table = _world(seed, num_objects)
    cfg = MultiQueryConfig(
        **{"plan_size": 16, "substrate_dtype": dtype, **cfg_kw}
    )
    sess = EngineSession(
        [p.positive() for p in preds], table, combine, corpus.costs,
        capacity=capacity, max_tenants=max_tenants, config=cfg,
        max_capacity=max_capacity,
    )
    return sess, corpus, preds


def _rows(m, seed=1, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(0.05, 0.95, (m, P_GLOBAL, F)), dtype)


def _assert_states_bitwise(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(jax.device_get(la), jax.device_get(lb)):
        x, y = np.asarray(x), np.asarray(y)
        assert (x.dtype, x.shape) == (y.dtype, y.shape)
        assert x.tobytes() == y.tobytes()


def _admitted(dtype):
    sess, corpus, preds = _session(capacity=N, dtype=dtype)
    st = sess.init_state(corpus.func_probs[:32])
    st, _ = sess.admit(st, conjunction(preds[0], preds[1]))
    return sess, st


# ------------------------------------------------------------- ring basics --


def test_ring_wraparound_preserves_rows():
    """Head wraps past the end across repeated push/drain cycles; every
    drained row lands in the bank in arrival order, bitwise."""
    sess, corpus, _ = _session(capacity=N)
    state = sess.init_state(corpus.func_probs[:16])
    ring = PendingRing(sess, slot_rows=4, num_slots=2)
    num_rows = 16
    fed = []
    for cycle in range(3):  # 2-slot ring -> head wraps every cycle
        for j in range(2):
            batch = _rows(4, seed=10 * cycle + j)
            assert ring.push(batch)
            fed.append(np.asarray(batch))
        assert ring.occupied == 2 and ring.free_slots == 0
        assert ring.pending_rows == 8
        state, num_rows, drained = ring.drain_into(sess, state, num_rows)
        assert drained == 8
        assert ring.occupied == 0
    assert num_rows == 16 + 24
    got = np.asarray(state.bank_outputs[16:40])
    np.testing.assert_array_equal(got, np.concatenate(fed))
    c = ring.counters
    assert c["pushed_batches"] == c["drained_batches"] == 6
    assert c["pushed_rows"] == c["drained_rows"] == 24
    assert c["blocked"] == c["shed_rows"] == c["spilled_rows"] == 0


def test_ring_partial_batch_fill_counts():
    """A trailing partial batch drains only its real rows — zero padding in
    the slot never reaches the bank."""
    sess, corpus, _ = _session(capacity=N)
    state = sess.init_state(corpus.func_probs[:8])
    ring = PendingRing(sess, slot_rows=8, num_slots=2)
    batch = _rows(3, seed=7)
    assert ring.push(batch)
    assert ring.pending_rows == 3
    state, num_rows, drained = ring.drain_into(sess, state, 8)
    assert (drained, num_rows) == (3, 11)
    np.testing.assert_array_equal(
        np.asarray(state.bank_outputs[8:11]), np.asarray(batch)
    )


def test_ring_push_bad_shape_raises():
    sess, _, _ = _session()
    ring = PendingRing(sess, slot_rows=4, num_slots=2)
    with pytest.raises(ValueError, match=r"\[1\.\.4, 4, 4\]"):
        ring.push(_rows(5))  # longer than a slot
    with pytest.raises(ValueError, match="ring batch"):
        ring.push(jnp.zeros((2, P_GLOBAL + 1, F)))  # wrong P
    with pytest.raises(ValueError, match="ring batch"):
        ring.push(jnp.zeros((P_GLOBAL, F)))  # missing batch axis
    with pytest.raises(ValueError, match="policy"):
        PendingRing(sess, slot_rows=4, num_slots=2, policy="drop")
    with pytest.raises(ValueError, match="slot_rows"):
        PendingRing(sess, slot_rows=0, num_slots=2)


def test_ring_push_mixed_dtype_raises():
    sess, _, _ = _session(dtype="bfloat16")
    ring = PendingRing(sess, slot_rows=4, num_slots=2)
    with pytest.raises(SubstrateDtypeError) as ei:
        ring.push(_rows(2, dtype=jnp.float32))
    assert ei.value.expected == "bfloat16"
    assert ei.value.got == "float32"
    assert ei.value.where == "PendingRing.push"
    assert ring.push(_rows(2, dtype=jnp.bfloat16))  # conforming input lands


# --------------------------------------------------- backpressure policies --


def test_block_policy_raises_typed_signal():
    sess, corpus, _ = _session()
    ring = PendingRing(sess, slot_rows=4, num_slots=2, policy="block")
    assert ring.push(_rows(4)) and ring.push(_rows(4))
    with pytest.raises(IngestBackpressure) as ei:
        ring.push(_rows(3))
    e = ei.value
    assert (e.occupied, e.capacity, e.requested, e.policy) == (2, 2, 3, "block")
    assert ring.counters["blocked"] == 1
    # drain frees every slot; the SAME batch then lands
    state = sess.init_state(corpus.func_probs[:8])
    state, num_rows, drained = ring.drain_into(sess, state, 8)
    assert drained == 8
    assert ring.push(_rows(3))
    assert ring.pending_rows == 3


def test_shed_policy_drops_and_counts():
    sess, corpus, _ = _session()
    ring = PendingRing(sess, slot_rows=4, num_slots=2, policy="shed")
    assert ring.push(_rows(4, seed=1)) and ring.push(_rows(4, seed=2))
    assert not ring.push(_rows(4, seed=3))  # full: overboard
    assert ring.counters["shed_batches"] == 1
    assert ring.counters["shed_rows"] == 4
    state = sess.init_state(corpus.func_probs[:8])
    state, num_rows, drained = ring.drain_into(sess, state, 8)
    assert drained == 8  # only the two batches that landed
    # the shed batch is GONE: what survived is batches 1 and 2
    np.testing.assert_array_equal(
        np.asarray(state.bank_outputs[8:16]),
        np.concatenate([np.asarray(_rows(4, seed=1)),
                        np.asarray(_rows(4, seed=2))]),
    )


def test_spill_policy_preserves_arrival_order():
    """Overflow spills host-side; once spilled, EVERYTHING spills until the
    queue drains — so rows re-enter in exact arrival order."""
    sess, corpus, _ = _session()
    ring = PendingRing(sess, slot_rows=4, num_slots=2, policy="spill")
    batches = [_rows(4, seed=s) for s in range(5)]
    for b in batches:
        assert ring.push(b)  # never blocks, never sheds
    assert ring.occupied == 2
    assert ring.spilled_pending == 3
    assert ring.counters["spilled_batches"] == 3
    assert ring.counters["spilled_rows"] == 12
    state = sess.init_state(corpus.func_probs[:8])
    state, num_rows, drained = ring.drain_into(sess, state, 8)
    assert drained == 20 and num_rows == 28
    assert ring.occupied == 0 and ring.spilled_pending == 0
    np.testing.assert_array_equal(
        np.asarray(state.bank_outputs[8:28]),
        np.concatenate([np.asarray(b) for b in batches]),
    )


# --------------------------------------------------------- ring-vs-direct --


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("policy", ["block", "shed", "spill"])
def test_ring_fed_bitwise_matches_direct(dtype, policy):
    """Ring-fed ingestion (refresh-free burst + one refresh) is bitwise
    identical to direct per-batch ingest, for every policy x dtype — with
    the shed comparison feeding only the batches that survived."""
    batches = [_rows(8, seed=s, dtype=jnp.dtype(dtype)) for s in range(4)]

    sess_r, st_r = _admitted(dtype)
    ring = PendingRing(sess_r, slot_rows=8, num_slots=2, policy=policy)
    num_rows, landed = 32, []
    for b in batches:
        try:
            ok = ring.push(b)
        except IngestBackpressure:
            st_r, num_rows, _ = ring.drain_into(sess_r, st_r, num_rows)
            ok = ring.push(b)
        if ok:
            landed.append(b)
    st_r, num_rows, _ = ring.drain_into(sess_r, st_r, num_rows)
    st_r, hist_r = sess_r.run(st_r, 3, stop_when_exhausted=False)

    sess_d, st_d = _admitted(dtype)
    for b in landed:
        st_d = sess_d.ingest(st_d, b)
    st_d, hist_d = sess_d.run(st_d, 3, stop_when_exhausted=False)

    if policy == "shed":
        assert len(landed) == 2  # the ring really did drop arrivals
    assert num_rows == 32 + 8 * len(landed)
    assert float(st_r.cost_spent).hex() == float(st_d.cost_spent).hex()
    np.testing.assert_array_equal(
        np.asarray(st_r.derived.in_answer), np.asarray(st_d.derived.in_answer)
    )
    np.testing.assert_array_equal(
        np.asarray(st_r.bank_outputs), np.asarray(st_d.bank_outputs)
    )
    for a, b in zip(hist_r, hist_d):
        assert a.cost_spent == b.cost_spent


# -------------------------------------------------------------- the stream --


def test_stream_feed_micro_batches_and_partial_tail():
    sess, corpus, _ = _session(capacity=N)
    state = sess.init_state(corpus.func_probs[:16])
    ring = PendingRing(sess, slot_rows=8, num_slots=4)
    stream = IngestStream(ring, batch_rows=8)
    wave = np.asarray(_rows(19, seed=3))  # 8 + 8 + 3
    assert stream.feed(wave) == 19
    assert stream.batches_fed == 3 and stream.rows_fed == 19
    assert ring.pending_rows == 19
    state, num_rows, drained = ring.drain_into(sess, state, 16)
    assert drained == 19
    np.testing.assert_array_equal(np.asarray(state.bank_outputs[16:35]), wave)


def test_stream_reuse_tokens_never_hold_ring_versions():
    """The safe-reuse gate is a sentinel resolved against the LIVE ring
    buffer at stage time.  Storing a ring-buffer VERSION instead would block
    on a buffer the next donated push deletes — an XlaRuntimeError on every
    platform that selects the donating write path (GPU/TPU), invisible to
    the CPU fallback."""
    from repro.ingest.stream import _RING_WRITE

    sess, corpus, _ = _session(capacity=N)
    state = sess.init_state(corpus.func_probs[:8])
    ring = PendingRing(sess, slot_rows=4, num_slots=8)
    stream = IngestStream(ring, batch_rows=4)
    first = np.asarray(_rows(20, seed=9))  # 5 micro-batches: both staging
    assert stream.feed(first) == 20  # buffers recycle through the gate
    assert all(t is None or t is _RING_WRITE for t in stream._consumed)
    second = np.asarray(_rows(8, seed=10))  # re-stages via the blocked path
    assert stream.feed(second) == 8
    state, num_rows, drained = ring.drain_into(sess, state, 8)
    assert (drained, num_rows) == (28, 36)
    np.testing.assert_array_equal(
        np.asarray(state.bank_outputs[8:36]),
        np.concatenate([first, second]),
    )


def test_stream_backpressure_callback_drains_and_retries():
    """A blocked push invokes on_pressure (which drains) and retries the
    SAME device batch — every row lands despite a ring smaller than the
    wave."""
    sess, corpus, _ = _session(capacity=N)
    holder = {"state": sess.init_state(corpus.func_probs[:16]), "rows": 16}
    ring = PendingRing(sess, slot_rows=8, num_slots=2, policy="block")

    def on_pressure():
        holder["state"], holder["rows"], _ = ring.drain_into(
            sess, holder["state"], holder["rows"]
        )

    stream = IngestStream(ring, batch_rows=8, on_pressure=on_pressure)
    wave = np.asarray(_rows(40, seed=4))  # 5 micro-batches through 2 slots
    assert stream.feed(wave) == 40
    assert ring.counters["blocked"] >= 1
    on_pressure()  # final drain
    assert holder["rows"] == 56
    np.testing.assert_array_equal(
        np.asarray(holder["state"].bank_outputs[16:56]), wave
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_lockstep_pressure_drains_defer_refresh_bitwise(dtype):
    """Lockstep backpressure drains land rows without a refresh; the
    settling ``drain`` refreshes once, and the settled state is bitwise the
    state of a path that refreshes at every drain."""
    wave = np.asarray(_rows(56, seed=5, dtype=jnp.dtype(dtype)))  # 7 batches

    sess_l, st_l = _admitted(dtype)
    ing = StreamingIngest(sess_l, batch_rows=8, num_slots=2)
    ing.attach_lockstep(st_l)
    ing.begin(st_l)
    assert ing.feed(wave) == 56  # pushes 3, 5 and 7 find the ring full
    ing.drain()
    c = ing.counters()
    assert c["refreshes"] == 1 and c["refreshes_deferred"] >= 3
    assert c["blocked"] == c["refreshes_deferred"] and ing.drains == 4

    sess_p, st_p = _admitted(dtype)
    ring = PendingRing(sess_p, slot_rows=8, num_slots=2)
    held = {"state": st_p, "rows": 32}

    def refresh_every_drain():
        held["state"], held["rows"], _ = ring.drain_into(
            sess_p, held["state"], held["rows"], refresh=True
        )

    IngestStream(ring, batch_rows=8, on_pressure=refresh_every_drain).feed(wave)
    refresh_every_drain()
    assert ring.counters["refreshes"] == 4
    assert ring.counters["refreshes_deferred"] == 0
    _assert_states_bitwise(ing.state, held["state"])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_settling_drain_pays_owed_refresh_on_empty_ring(dtype):
    """A debt left by pressure drains is paid by ``drain`` even when no row
    is left to land, and paid only once."""
    sess, st = _admitted(dtype)
    ing = StreamingIngest(sess, batch_rows=8, num_slots=2)
    ing.attach_lockstep(st)
    ing.begin(st)
    ing.feed(np.asarray(_rows(24, seed=6, dtype=jnp.dtype(dtype))))
    ing.stream.on_pressure()  # the pressure path empties the ring
    assert ing.ring.occupied == 0
    c = ing.counters()
    assert (c["refreshes"], c["refreshes_deferred"]) == (0, 2)
    unsettled = ing.state
    ing.drain()
    assert ing.counters()["refreshes"] == 1
    _assert_states_bitwise(ing.state, sess.refresh(unsettled))
    settled = ing.state
    ing.drain()  # nothing landed, nothing owed: no refresh
    assert ing.counters()["refreshes"] == 1
    assert ing.state is settled


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_serve_lockstep_streaming_matches_direct_ingest(dtype):
    """Serve's lockstep trace with an ingest over two ring fills reads the
    same answers and spend as the trace that ingests each batch directly."""
    def serve(spec, streaming):
        sess, corpus, preds = _session(capacity=N, dtype=dtype)
        ing = None
        if streaming:
            ing = StreamingIngest(sess, batch_rows=8, num_slots=2)
        return serve_session_trace(
            sess, sess.init_state(corpus.func_probs[:32]), parse_trace(spec),
            pool=corpus.func_probs[32:], preds=preds, seed=3, chunk_size=2,
            streaming=ing,
        )

    tail = "admit:2;run:4;retire:0;run:2"
    rep_s = serve(f"ingest:40;{tail}", True)
    rep_d = serve(";".join(["ingest:8"] * 5) + f";{tail}", False)
    assert rep_s.ingest_counters["refreshes_deferred"] == 2
    assert rep_s.ingest_counters["refreshes"] == 1
    assert rep_s.num_rows == rep_d.num_rows == 72
    assert rep_s.answer_digest == rep_d.answer_digest
    assert rep_s.cost_hex == rep_d.cost_hex
    assert rep_s.bills_hex == rep_d.bills_hex


def test_stream_without_callback_propagates_backpressure():
    sess, _, _ = _session()
    ring = PendingRing(sess, slot_rows=4, num_slots=1, policy="block")
    stream = IngestStream(ring, batch_rows=4)
    with pytest.raises(IngestBackpressure):
        stream.feed(np.asarray(_rows(8, seed=5)))


def test_stream_throttle_counts_waits():
    sess, _, _ = _session()
    ring = PendingRing(sess, slot_rows=4, num_slots=4)
    # 40ms per 4-row batch — far above push overhead, so pacing must engage
    stream = IngestStream(ring, batch_rows=4, rate_rows_per_s=100.0)
    stream.feed(np.asarray(_rows(12, seed=6)))
    assert stream.throttle_waits >= 1  # pacing engaged after batch 1
    assert stream.counters()["throttle_waits"] == stream.throttle_waits
    with pytest.raises(ValueError, match="rate_rows_per_s"):
        IngestStream(ring, rate_rows_per_s=0.0)
    with pytest.raises(ValueError, match="batch_rows"):
        IngestStream(ring, batch_rows=9)  # > slot_rows


def test_stream_quantizes_to_substrate_dtype():
    """f32 host arrivals quantize in the staging buffer of a bf16 session —
    the ring only ever sees storage dtype."""
    sess, corpus, _ = _session(dtype="bfloat16")
    state = sess.init_state(corpus.func_probs[:8])
    ring = PendingRing(sess, slot_rows=4, num_slots=2)
    stream = IngestStream(ring, batch_rows=4)
    wave = np.random.default_rng(0).uniform(0, 1, (4, P_GLOBAL, F))
    assert stream.feed(wave.astype(np.float32)) == 4
    state, _, _ = ring.drain_into(sess, state, 8)
    got = np.asarray(state.bank_outputs[8:12])
    assert state.bank_outputs.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        got, np.asarray(jnp.asarray(wave, jnp.float32).astype(jnp.bfloat16))
    )


# ---------------------------------------------------------- capacity errors --


def test_ingest_capacity_error_payload():
    sess, corpus, _ = _session(capacity=32)
    state = sess.init_state(corpus.func_probs[:30])
    with pytest.raises(CapacityError) as ei:
        sess.ingest(state, _rows(5))
    e = ei.value
    assert (e.used, e.capacity, e.requested) == (30, 32, 5)
    # the ring surfaces the same payload from a shadow-held drain
    ring = PendingRing(sess, slot_rows=5, num_slots=1)
    ring.push(_rows(5))
    with pytest.raises(CapacityError) as ei2:
        ring.drain_into(sess, state, 30)
    assert (ei2.value.used, ei2.value.requested) == (30, 5)


def test_drain_capacity_precheck_is_all_or_nothing():
    """A drain that cannot fit raises BEFORE applying any slot: ring
    shadows, spill queue, and counters stay intact, so a caller that frees
    capacity retries without losing a row."""
    sess, corpus, _ = _session(capacity=32)
    state = sess.init_state(corpus.func_probs[:30])
    ring = PendingRing(sess, slot_rows=4, num_slots=2, policy="spill")
    fed = [np.asarray(_rows(4, seed=40 + s)) for s in range(3)]
    for batch in fed:  # 2 ring slots + 1 spilled batch = 12 pending rows
        assert ring.push(jnp.asarray(batch))
    before = dict(ring.counters)
    with pytest.raises(CapacityError) as ei:
        ring.drain_into(sess, state, 30)
    e = ei.value
    assert (e.used, e.capacity, e.requested) == (30, 32, 12)
    assert ring.occupied == 2 and ring.pending_rows == 8
    assert ring.spilled_pending == 1
    assert ring.counters == before
    # retry against freed capacity: every pending row lands, in order
    state2 = sess.init_state(corpus.func_probs[:16])
    state2, num_rows, drained = ring.drain_into(sess, state2, 16)
    assert (drained, num_rows) == (12, 28)
    np.testing.assert_array_equal(
        np.asarray(state2.bank_outputs[16:28]), np.concatenate(fed)
    )


# ---------------------------------------------------- dtype-parameterized --


def test_bf16_session_end_to_end():
    """A bf16 session serves admit/ingest/run with bf16 storage leaves and
    an f32 spend ledger (the dtype contract's two halves)."""
    sess, corpus, preds = _session(capacity=N, dtype="bfloat16")
    st = sess.init_state(corpus.func_probs[:48])
    st, _ = sess.admit(st, conjunction(preds[0], preds[2]))
    st = sess.ingest(st, _rows(8, dtype=jnp.bfloat16))
    st, hist = sess.run(st, 3, stop_when_exhausted=False)
    for leaf in (st.substrate.func_probs, st.bank_outputs,
                 st.derived.pred_prob, st.derived.uncertainty,
                 st.derived.joint_prob):
        assert leaf.dtype == jnp.bfloat16
    assert st.cost_spent.dtype == jnp.float32  # spend identity stays f32
    assert float(st.cost_spent) > 0.0
    assert len(hist) == 3


def test_f32_default_unchanged():
    """The default config is f32 end to end — the dtype parameterization is
    invisible to existing sessions."""
    sess, corpus, _ = _session(capacity=N)
    st = sess.init_state(corpus.func_probs[:48])
    assert st.substrate.func_probs.dtype == jnp.float32
    assert st.derived.pred_prob.dtype == jnp.float32
    assert sess.config.substrate_dtype == "float32"


def test_grow_preserves_substrate_dtype():
    sess, corpus, _ = _session(
        capacity=32, dtype="bfloat16", max_capacity=128
    )
    st = sess.init_state(corpus.func_probs[:30])
    st = sess.ingest(st, _rows(20, dtype=jnp.bfloat16))  # forces a tier jump
    assert st.capacity > 32
    assert st.substrate.func_probs.dtype == jnp.bfloat16
    assert st.bank_outputs.dtype == jnp.bfloat16
    assert st.cost_spent.dtype == jnp.float32
    assert int(st.num_rows) == 50


def test_mixed_dtype_merge_raises():
    buf = jnp.zeros((16, P_GLOBAL, F), jnp.bfloat16)
    with pytest.raises(SubstrateDtypeError) as ei:
        ingest_rows(buf, jnp.int32(4), jnp.zeros((2, P_GLOBAL, F), jnp.float32))
    assert ei.value.where == "ingest_rows"
    assert ei.value.expected == "bfloat16"

    sub = init_substrate(16, P_GLOBAL, F, dtype=jnp.bfloat16)
    k = 4
    idx = jnp.arange(k, dtype=jnp.int32)
    with pytest.raises(SubstrateDtypeError) as ei2:
        apply_outputs_to_substrate(
            sub, idx, idx % P_GLOBAL, idx % F,
            jnp.full((k,), 0.5, jnp.float32),  # f32 probs into bf16 store
            jnp.ones((k,), jnp.float32),
            jnp.ones((k,), bool),
        )
    assert ei2.value.where == "apply_outputs_to_substrate"


def test_invalid_substrate_dtype_rejected():
    with pytest.raises(ValueError, match="substrate_dtype"):
        _session(capacity=32, dtype="float16")


# -------------------------------------------------------- checkpoint dtype --


def test_checkpoint_roundtrip_bf16_bitwise(tmp_path):
    sess, corpus, preds = _session(capacity=N, dtype="bfloat16")
    st = sess.init_state(corpus.func_probs[:48])
    st, _ = sess.admit(st, conjunction(preds[0], preds[1]))
    st, _ = sess.run(st, 2, stop_when_exhausted=False)
    save_session_checkpoint(tmp_path, 2, sess, st)

    sess2, _, _ = _session(capacity=N, dtype="bfloat16")
    st2, step, extra = restore_session_checkpoint(sess2, tmp_path)
    assert step == 2
    assert extra["substrate_dtype"] == "bfloat16"
    assert st2.substrate.func_probs.dtype == jnp.bfloat16
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the restored lineage keeps serving bitwise-identically
    st, _ = sess.run(st, 2, stop_when_exhausted=False)
    st2, _ = sess2.run(st2, 2, stop_when_exhausted=False)
    assert float(st.cost_spent).hex() == float(st2.cost_spent).hex()


def test_checkpoint_dtype_mismatch_refused(tmp_path):
    sess, corpus, _ = _session(capacity=N, dtype="bfloat16")
    st = sess.init_state(corpus.func_probs[:48])
    save_session_checkpoint(tmp_path, 1, sess, st)
    sess_f32, _, _ = _session(capacity=N, dtype="float32")
    with pytest.raises(ValueError, match="substrate_dtype"):
        restore_session_checkpoint(sess_f32, tmp_path)


# -------------------------------------------------- pallas dequant-in-tile --


def _parity_fixture(seed=0, n=512, q=3, p=3, f=4):
    from repro.core.entropy import binary_entropy

    table = fallback_decision_table(p, f, auc=jnp.full((p, f), 0.85),
                                    num_bins=10)
    rng = np.random.default_rng(seed)
    costs = jnp.asarray(rng.uniform(0.05, 1.0, (p, f)), jnp.float32)
    pp = jnp.asarray(rng.uniform(0.01, 0.99, (n, p)), jnp.bfloat16)
    unc = binary_entropy(pp.astype(jnp.float32)).astype(jnp.bfloat16)
    sid = jnp.asarray(rng.integers(0, 2 ** f, (n, p)), jnp.int32)
    joint = jnp.asarray(rng.uniform(0.0, 1.0, (q, n)), jnp.bfloat16)
    return table, costs, pp, unc, sid, joint


@pytest.mark.parametrize("mode", ["table", "best"])
def test_pallas_bf16_dequant_in_tile_parity(mode):
    """The exactness contract: bf16-fed kernels match the f32-upcast
    reference BITWISE on every planning-driving output (benefit / next_fn,
    and the plans they give, costs included); table-mode est_joint is
    bitwise too, best-mode est_joint is 1-ulp-stable (XLA output-fusion
    contraction — kernel docstring)."""
    from repro.kernels.enrich_score import ops as es_ops

    table, costs, pp, unc, sid, joint = _parity_fixture()
    lo = es_ops.fused_benefits_batched(
        pp, unc, sid, joint, table, costs,
        function_selection=mode, interpret=True,
    )
    hi = es_ops.fused_benefits_batched(
        pp.astype(jnp.float32), unc.astype(jnp.float32), sid,
        joint.astype(jnp.float32), table, costs,
        function_selection=mode, interpret=True,
    )
    for name in ("benefit", "next_fn"):
        a, b = np.asarray(getattr(lo, name)), np.asarray(getattr(hi, name))
        assert a.tobytes() == b.tobytes(), f"{mode}.{name} not bitwise"
    # the plans they give, costs included
    from repro.core.plan import select_plan

    plans = [jax.vmap(lambda b: select_plan(b, 64, costs))(x) for x in (lo, hi)]
    for name, a, b in zip(plans[0]._fields, *plans):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), f"{mode}.plan.{name}"
    ej_lo = np.asarray(lo.est_joint).view(np.int32).astype(np.int64)
    ej_hi = np.asarray(hi.est_joint).view(np.int32).astype(np.int64)
    max_ulp = int(np.abs(ej_lo - ej_hi).max())
    assert max_ulp <= (0 if mode == "table" else 1)


def test_ingest_benchmark_parity_block_holds(monkeypatch):
    """``benchmarks/ingest.py`` reports the same contract in its JSON (CI
    asserts ``planning_outputs_bitwise``); it must run on the scorers'
    current outputs."""
    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parents[1]))
    from benchmarks.ingest import _pallas_bf16_parity

    out = _pallas_bf16_parity()
    assert out["planning_outputs_bitwise"] is True
    assert out["table"]["est_joint_max_ulp"] == 0
    assert out["best"]["est_joint_max_ulp"] <= 1


def test_pallas_mixed_probability_dtypes_raise():
    from repro.kernels.enrich_score import ops as es_ops

    table, costs, pp, unc, sid, joint = _parity_fixture()
    with pytest.raises(SubstrateDtypeError) as ei:
        es_ops.fused_benefits_batched(
            pp, unc.astype(jnp.float32), sid, joint, table, costs,
            interpret=True,
        )
    assert ei.value.where == "fused_benefits_batched"


def test_pallas_backend_bf16_session_runs():
    """A bf16 session on the pallas backend serves end to end — derived
    rows reach the kernel at storage dtype (dequant-in-tile) and planning
    proceeds normally."""
    sess, corpus, preds = _session(
        capacity=N, dtype="bfloat16", backend="pallas", pallas_interpret=True,
    )
    st = sess.init_state(corpus.func_probs[:48])
    st, _ = sess.admit(st, conjunction(preds[0], preds[1]))
    st, hist = sess.run(st, 2, stop_when_exhausted=False)
    assert len(hist) == 2
    assert float(st.cost_spent) > 0.0
