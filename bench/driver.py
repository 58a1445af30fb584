"""The benchmark's client: drives one session through its traffic, lockstep.

At each chunk boundary the client, in this order: retires queries whose
lifetime (counted from their first refined answer) is over; admits due
queries into free tenant slots, oldest first (a query that finds every slot
full waits, and the wait counts); feeds the stream batches that are due
through the pending-row ring; drains the ring; and runs one chunk of
``chunk_size`` epochs through ``EngineSession.run``.  Answers reach tenants
when the chunk returns, which is when the host holds its epoch stats.

Every call into the session runs to completion inside a host span named for
it (run / admit / retire / ingest / bookkeeping); with tracing on, each span
is also a ``jax.profiler.TraceAnnotation`` in the device trace.
"""

from __future__ import annotations

import contextlib
import time
from collections import deque


class Spans:
    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.items: list = []  # (name, start, end) on time.perf_counter

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.items.append((name, t0, time.perf_counter()))


def _query(predicates, cols):
    from repro.core import conjunction

    return conjunction(*[predicates[c] for c in cols])


def _streaming(session, cfg, batch_rows):
    from repro.launch.serve import StreamingIngest

    return StreamingIngest(
        session, batch_rows=batch_rows, num_slots=cfg["ring_slots"],
        policy=cfg["ring_policy"],
    )


def warm_up(bundle: dict, cfg: dict, sched: dict) -> None:
    """Compile every program the window uses, on a throwaway copy of the
    state and a throwaway ring: the admit of each arity, a chunk, a retire,
    and (with a stream) a ring filled past capacity twice, so every ring
    slot is written, blocks once and drains."""
    import jax

    session, state = bundle["session"], bundle["state"]
    for k in sorted({len(q["cols"]) for q in sched["queries"]}):
        state, slot = session.admit(state, _query(bundle["predicates"], tuple(range(k))))
        state, _ = session.run(state, cfg["chunk_size"], stop_when_exhausted=False)
        state = session.retire(state, slot)
    if sched["batches"]:
        rows = bundle["stream_rows"]
        b = sched["batch_rows"]
        ing = _streaming(session, cfg, b)
        ing.attach_lockstep(state)
        for i in range(2 * cfg["ring_slots"] + 1):
            ing.begin(state)
            ing.feed(rows[i * b:(i + 1) * b])
            state = ing.state
        ing.begin(state)
        ing.drain()
        state = ing.state
    jax.block_until_ready(state)


def run_window(bundle: dict, cfg: dict, sched: dict, seconds: float,
               sample_at: list, spans: Spans, grace_s: float = 60.0) -> dict:
    """Drive the cell's traffic for ``seconds`` of wall time, then keep
    serving (no new arrivals) until every query and batch due in the window
    has been answered or made visible, at most ``grace_s`` more.

    ``sample_at``: fractions of the window; the chunk dispatched at the first
    boundary past each keeps its starting and returned state for the check.
    """
    import jax

    session, state = bundle["session"], bundle["state"]
    chunk = cfg["chunk_size"]
    queries = sched["queries"]
    batches = sched["batches"]
    b_rows = sched["batch_rows"]
    stream = bundle["stream_rows"]

    ing = None
    if batches:
        ing = _streaming(session, cfg, b_rows)
        ing.attach_lockstep(state)

    q_admit = [None] * len(queries)
    q_queued = [False] * len(queries)  # found every slot full at a boundary
    q_first = [None] * len(queries)
    b_visible = [None] * len(batches)
    waiting: deque = deque()
    q_seen = [None] * len(queries)  # the boundary at which a query was due
    active: dict = {}  # slot -> query index
    next_q = next_b = rows_fed = 0
    pending_b: list = []  # fed, drained, waiting for a chunk to run with them
    chunks: list = []  # (dispatch, return, epochs, history)
    samples: list = []
    to_sample = sorted(sample_at)
    queue_len: list = []

    t0 = time.perf_counter()
    t_end = t0 + seconds
    while True:
        now = time.perf_counter()
        el = now - t0
        with spans("bookkeeping"):
            expired = [s for s, qi in active.items()
                       if q_first[qi] is not None
                       and now >= q_first[qi] + queries[qi]["lifetime"]]
            while next_q < len(queries) and queries[next_q]["due"] <= el:
                waiting.append(next_q)
                q_seen[next_q] = now
                next_q += 1
        for slot in expired:
            with spans("retire"):
                state = session.retire(state, slot)
                jax.block_until_ready(state)
            del active[slot]
        while waiting and len(active) < session.max_tenants:
            qi = waiting.popleft()
            with spans("admit"):
                state, slot = session.admit(state, _query(bundle["predicates"], queries[qi]["cols"]))
                jax.block_until_ready(state)
            active[slot] = qi
            q_admit[qi] = time.perf_counter()
        for qi in waiting:
            q_queued[qi] = True
        if ing is not None:
            with spans("ingest"):
                while next_b < len(batches) and batches[next_b] <= el:
                    ing.begin(state)
                    rows = stream[next_b * b_rows:(next_b + 1) * b_rows]
                    ing.feed(rows)
                    rows_fed += rows.shape[0]
                    state = ing.state
                    pending_b.append(next_b)
                    next_b += 1
                ing.begin(state)
                ing.drain()
                state = jax.block_until_ready(ing.state)
        queue_len.append((el, len(waiting)))

        in_window = time.perf_counter() < t_end
        if not in_window:
            owed = (any(f is None for f in q_first)
                    or any(v is None for v in b_visible[:next_b]))
            if not owed or time.perf_counter() > t_end + grace_s:
                break
        sample = bool(to_sample) and in_window and el >= to_sample[0] * seconds
        while to_sample and el >= to_sample[0] * seconds:
            to_sample.pop(0)
        if sample:
            pre = state
        with spans("run"):
            t_disp = time.perf_counter()
            state, hist = session.run(state, chunk, stop_when_exhausted=False,
                                      chunk_size=chunk)
            t_ret = time.perf_counter()
        chunks.append((t_disp, t_ret, len(hist), in_window))
        if sample:
            samples.append(dict(pre=pre, post=state, ef=hist[-1].expected_f))
        for qi in active.values():
            if q_first[qi] is None:
                q_first[qi] = t_ret
        for bi in pending_b:
            b_visible[bi] = t_ret
        pending_b = []

    if ing is not None:
        ing.begin(state)
        ing.drain()
        state = jax.block_until_ready(ing.state)
    return dict(
        t0=t0, t_end=t_end, t_cut=time.perf_counter(), state=state, chunks=chunks, samples=samples,
        queries=queries, q_admit=q_admit, q_seen=q_seen, q_queued=q_queued, q_first=q_first,
        batches=batches, b_visible=b_visible,
        rows_fed=rows_fed, batch_rows=b_rows, queue_len=queue_len,
        unanswered=sum(f is None for f in q_first),
        invisible=sum(v is None for v in b_visible[:next_b]),
    )
