"""Donated pending-row ring: device-resident staging between arrival and drain.

The ingest hot loop must never trade a host sync for a row.  ``PendingRing``
holds arriving micro-batches in a pre-allocated ``[K, B, P, F]`` device
buffer at the session's substrate dtype:

* ``push`` writes one micro-batch into the next free slot as a jitted
  ``dynamic_update_slice`` with the ring buffer DONATED, so XLA
  updates it in place — no copy of K slots per arrival, no host sync (the
  slot index is a traced scalar; occupancy lives in host shadows).
* ``drain_into`` replays every pending slot into an ``EngineSession`` as
  refresh-free ingests and refreshes derived state once — bitwise identical
  to ingesting each batch directly (refresh is idempotent w.r.t. the
  substrate), minus the per-batch full-width refreshes and device reads.
  ``refresh=False`` lands the slots and leaves that refresh owed; the next
  drain with ``refresh=True`` pays it, even on an empty ring.

Backpressure — enrichment falling behind arrivals — is a full ring at
``push`` time, resolved by policy:

* ``"block"``  raise the typed ``IngestBackpressure`` signal; the caller
  drains (freeing every slot) and retries.  Lossless, ordered; arrival
  stalls for one drain.
* ``"shed"``   drop the INCOMING batch and count it.  Lossy; arrival never
  stalls (load-shedding frontends).
* ``"spill"``  queue the batch host-side and count it; drains move spilled
  batches into freed slots FIFO before new pushes land, so arrival order is
  preserved end-to-end.  Lossless; overflow pays host memory + a second
  transfer instead of a stall.

Every counter (pushes, drains, sheds, spills, blocks, refreshes run and
deferred) is host-side bookkeeping — reading them never touches the device.
"""

from __future__ import annotations

from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.errors import CapacityError, IngestBackpressure

_POLICIES = ("block", "shed", "spill")


@partial(jax.jit, donate_argnums=(0,))
def _write_slot_donated(buf, batch, slot):
    """buf[slot] = batch, in place via donation.  ``slot`` is a traced
    scalar, so every slot index reuses ONE compiled program (batches are
    padded to full slot width before the write, so there is exactly one
    trace per ring shape)."""
    return jax.lax.dynamic_update_slice(
        buf, batch[None], (slot,) + (0,) * batch.ndim
    )


class PendingRing:
    """Bounded FIFO of pending ingest micro-batches on the device.

    ``slot_rows`` is the micro-batch capacity B of each of ``num_slots``
    slots; a pushed batch may be SHORTER than B (the trailing partial batch
    of a stream) — the slot's host-side fill count remembers how many rows
    are real.  Shapes (P, F) and dtype come from the session so a drained
    slot is dtype-strict by construction.
    """

    def __init__(
        self,
        session,
        *,
        slot_rows: int,
        num_slots: int,
        policy: str = "block",
    ):
        if policy not in _POLICIES:
            raise ValueError(f"policy must be one of {_POLICIES}, got {policy!r}")
        if slot_rows < 1 or num_slots < 1:
            raise ValueError(
                f"need slot_rows >= 1 and num_slots >= 1, got "
                f"({slot_rows}, {num_slots})"
            )
        self.session = session
        self.slot_rows = int(slot_rows)
        self.num_slots = int(num_slots)
        self.policy = policy
        p, f = session.num_predicates, session.num_functions
        self._buf = jnp.zeros(
            (self.num_slots, self.slot_rows, p, f), session.substrate_dtype
        )
        # host shadows of occupancy: FIFO position + per-slot fill counts
        self._head = 0  # oldest pending slot
        self._count = 0  # pending slots
        self._fill = [0] * self.num_slots  # real rows per slot
        self._spilled: deque = deque()  # host-side overflow (policy="spill")
        self.counters = {
            "pushed_batches": 0,
            "pushed_rows": 0,
            "drained_batches": 0,
            "drained_rows": 0,
            "shed_batches": 0,
            "shed_rows": 0,
            "spilled_batches": 0,
            "spilled_rows": 0,
            "blocked": 0,
            "refreshes": 0,
            "refreshes_deferred": 0,
        }
        # rows landed by a refresh=False drain whose refresh is still owed
        self._refresh_owed = False

    # ---- occupancy (host shadows, never a device read) ----------------------

    @property
    def occupied(self) -> int:
        """Pending slots awaiting a drain."""
        return self._count

    @property
    def free_slots(self) -> int:
        return self.num_slots - self._count

    @property
    def pending_rows(self) -> int:
        """Rows parked on the device (spilled host-side rows not included)."""
        return sum(
            self._fill[(self._head + i) % self.num_slots]
            for i in range(self._count)
        )

    @property
    def spilled_pending(self) -> int:
        """Host-side batches waiting for freed slots (policy="spill")."""
        return len(self._spilled)

    # ---- producer side -------------------------------------------------------

    def _validate(self, batch) -> tuple:
        shape = tuple(batch.shape)
        p, f = self.session.num_predicates, self.session.num_functions
        if len(shape) != 3 or shape[1:] != (p, f) or not 1 <= shape[0] <= self.slot_rows:
            raise ValueError(
                f"ring batch must be [1..{self.slot_rows}, {p}, {f}]; got "
                f"{list(shape)}"
            )
        return shape

    def _enqueue(self, batch) -> None:
        """Write into the next free slot (caller guarantees one exists)."""
        m = batch.shape[0]
        slot = (self._head + self._count) % self.num_slots
        if m < self.slot_rows:
            # partial trailing batch: the write needs full slot width; the
            # fill shadow keeps the padding out of every drain
            pad = jnp.zeros(
                (self.slot_rows - m,) + batch.shape[1:], self._buf.dtype
            )
            batch = jnp.concatenate([batch, pad], axis=0)
        self._buf = _write_slot_donated(self._buf, batch, jnp.int32(slot))
        self._fill[slot] = m
        self._count += 1
        self.counters["pushed_batches"] += 1
        self.counters["pushed_rows"] += m

    def push(self, batch) -> bool:
        """Stage one micro-batch; True if it landed in the ring (or spilled),
        False if the shed policy dropped it.

        ``batch`` is [m <= slot_rows, P, F] at the substrate dtype (host
        arrays are fine — ``device_put`` them yourself, e.g. via
        ``IngestStream``, to overlap the transfer).  Mixed-float input
        raises at the slot write (``SubstrateDtypeError`` semantics are
        enforced by the session on drain; here the concatenate/update would
        silently promote, so we check eagerly).
        """
        batch = jnp.asarray(batch)
        self._validate(batch)
        if (
            jnp.issubdtype(batch.dtype, jnp.inexact)
            and batch.dtype != self._buf.dtype
        ):
            from repro.core.errors import SubstrateDtypeError

            raise SubstrateDtypeError(
                f"ring stores {self._buf.dtype} but push got {batch.dtype}; "
                "quantize at the staging buffer (IngestStream does)",
                expected=str(self._buf.dtype),
                got=str(batch.dtype),
                where="PendingRing.push",
            )
        blocked = self._count == self.num_slots and self.policy == "block"
        with tracing.span(tracing.PUSH, blocked=int(blocked)):
            if self.policy == "spill" and (self._count == self.num_slots or self._spilled):
                # order preservation: once anything is spilled, EVERYTHING
                # spills until the queue has drained back into slots
                self._spilled.append(np.asarray(batch))
                self.counters["spilled_batches"] += 1
                self.counters["spilled_rows"] += int(batch.shape[0])
                return True
            if self._count == self.num_slots:
                if self.policy == "shed":
                    self.counters["shed_batches"] += 1
                    self.counters["shed_rows"] += int(batch.shape[0])
                    return False
                self.counters["blocked"] += 1
                raise IngestBackpressure(
                    f"pending-row ring is full ({self._count}/{self.num_slots} "
                    f"slots); drain into the session and retry",
                    occupied=self._count,
                    capacity=self.num_slots,
                    requested=int(batch.shape[0]),
                    policy=self.policy,
                )
            self._enqueue(batch)
            return True

    # ---- consumer side -------------------------------------------------------

    def drain_into(self, session, state, num_rows: int, *, refresh: bool = True):
        """Apply every pending slot to ``state`` in arrival order.

        -> ``(state, num_rows, drained_rows)``.  Each slot lands as a
        refresh-free ``session.ingest`` (pure ``dynamic_update_slice`` on
        the bank buffer + row-count bump); ONE refresh recomputes derived
        state at the end.  No host sync anywhere: bounds checks and tier
        growth run off the ``num_rows`` shadow, slot reads are static
        indices into the ring buffer.  Spilled batches (policy="spill")
        re-enter freed slots FIFO and drain in the same pass, so a drain
        leaves the ring truly empty unless the spill queue outruns the ring
        again.

        ``refresh=False`` lands the slots the same way and skips that
        refresh, leaving it owed: the returned state holds rows whose
        derived state is stale, and must not run a superstep, be sampled or
        be checkpointed until a later drain with ``refresh=True`` settles
        it.  That drain refreshes once if it landed rows or a refresh is
        owed, so deferred drains followed by one settling drain give
        bitwise the state of refreshing at every drain: the refresh is a
        pure function of substrate, slot masks and row count, and the
        refresh-free ingests never read derived state.  The ``pique.drain``
        span's ``refreshed`` (0 or 1) and the ``refreshes`` /
        ``refreshes_deferred`` counters record which drains paid.

        All-or-nothing: capacity is checked against the TOTAL pending rows
        (ring + spill queue) before any slot is applied, so a
        ``CapacityError`` raises with the ring shadows, the spill queue, and
        ``state`` all untouched — a caller that catches it (e.g. to shrink
        load and retry) loses nothing.  A mid-drain raise would instead pop
        applied slots from the shadows while the accumulated state/num_rows
        die with the exception.
        """
        total = self.pending_rows + sum(
            int(b.shape[0]) for b in self._spilled
        )
        if num_rows + total > session.max_capacity:
            raise CapacityError(
                f"draining {total} pending rows overflows capacity "
                f"({num_rows} rows used, max_capacity="
                f"{session.max_capacity}); nothing was applied — shrink the "
                "backlog or open the session with a larger max_capacity",
                used=num_rows,
                capacity=session.max_capacity,
                requested=total,
            )
        drained = 0
        refreshed = refresh and (total > 0 or self._refresh_owed)
        with tracing.span(
            tracing.DRAIN, slots=self._count + len(self._spilled), rows=total,
            refreshed=int(refreshed),
        ):
            while self._count or self._spilled:
                while self._count:
                    slot = self._head
                    m = self._fill[slot]
                    rows = self._buf[slot, :m]
                    state = session.ingest(
                        state, rows, num_rows=num_rows, refresh=False
                    )
                    num_rows += m
                    drained += m
                    self._fill[slot] = 0
                    self._head = (self._head + 1) % self.num_slots
                    self._count -= 1
                    self.counters["drained_batches"] += 1
                    self.counters["drained_rows"] += m
                # refill from the spill queue (preserving arrival order); the
                # outer loop drains these freshly filled slots on its next
                # pass
                while self._spilled and self._count < self.num_slots:
                    self._enqueue(jnp.asarray(self._spilled.popleft()))
            if refreshed:
                state = session.program.refresh(state)
                self._refresh_owed = False
                self.counters["refreshes"] += 1
            elif drained:
                self._refresh_owed = True
                self.counters["refreshes_deferred"] += 1
        return state, num_rows, drained
