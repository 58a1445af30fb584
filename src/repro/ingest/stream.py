"""Double-buffered async host->device ingestion feeding a ``PendingRing``.

The transfer path the ROADMAP's streaming front-end calls for:

1. arriving rows are QUANTIZED on the host into one of two pre-allocated
   staging buffers at the substrate dtype (bf16 staging halves H2D bytes —
   the cast costs host cycles once instead of device bandwidth forever);
2. ``jax.device_put`` ships the staged view asynchronously;
3. the device array goes straight into the ring's donated slot write, which
   is itself async — so transfer N overlaps both the slot write of batch
   N-1 and whatever scan chunks the session pipeline has in flight;
4. a staging buffer is reused only after the RING WRITE that consumed it is
   done (``block_until_ready`` on the LIVE ring buffer — not on the
   transfer, because ``device_put`` of a numpy view may alias on CPU
   backends, and "transfer complete" would not mean "safe to overwrite";
   and not on a stored buffer version, because the donated write path
   deletes every superseded version on the very next push).

With two buffers the steady state is the classic overlap-by-one: the host
quantizes batch N+1 while the device absorbs batch N.  Throttling
(``rate_rows_per_s``) and blocked-ring handling (``on_pressure`` drains,
then the push retries) both live here so the serving loop stays a dumb
event loop.

A pressure drain may land rows without refreshing derived state
(``PendingRing.drain_into(..., refresh=False)``): the session state after
``feed`` is then not settled, and the caller settles it with a refreshing
drain before it runs a superstep, samples or checkpoints it.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.core import tracing
from repro.core.errors import IngestBackpressure
from repro.ingest.ring import PendingRing

# Reuse-gate sentinel: "the ring write that consumed this staging buffer".
# We must NOT store the ring-buffer version itself — under the donated
# write path the very next push donates that version away, and blocking on
# a donated/deleted buffer raises on GPU/TPU.  Blocking on the LIVE ring
# buffer is equivalent: single-device dispatch is in-order, so the live
# version being ready implies every earlier slot write has completed.
_RING_WRITE = object()


class IngestStream:
    """Micro-batching producer: host rows -> staging -> async H2D -> ring.

    ``on_pressure`` is required for ``policy="block"`` rings under real
    load: when a push raises ``IngestBackpressure`` the stream invokes it
    (the callback drains the ring into the session — e.g.
    ``pipeline.drain_ring``) and retries the SAME device batch, so nothing
    is re-staged or re-transferred.  Without a callback the signal
    propagates to the caller.  The callback only has to free the ring: it
    may drain with ``refresh=False`` and leave the refresh owed, as the
    lockstep ``launch.serve.StreamingIngest`` does, as long as its caller
    settles the state with a refreshing drain before the next superstep.
    """

    def __init__(
        self,
        ring: PendingRing,
        *,
        batch_rows: Optional[int] = None,
        rate_rows_per_s: Optional[float] = None,
        on_pressure: Optional[Callable[[], object]] = None,
    ):
        self.ring = ring
        self.batch_rows = int(batch_rows or ring.slot_rows)
        if not 1 <= self.batch_rows <= ring.slot_rows:
            raise ValueError(
                f"batch_rows must be in [1, slot_rows={ring.slot_rows}]; "
                f"got {self.batch_rows}"
            )
        if rate_rows_per_s is not None and rate_rows_per_s <= 0:
            raise ValueError(f"rate_rows_per_s must be > 0, got {rate_rows_per_s}")
        self.rate_rows_per_s = rate_rows_per_s
        self.on_pressure = on_pressure
        p, f = ring.session.num_predicates, ring.session.num_functions
        dt = np.dtype(ring.session.substrate_dtype)
        # the two pinned staging buffers (numpy holds bf16 via ml_dtypes)
        self._staging = [
            np.zeros((self.batch_rows, p, f), dt),
            np.zeros((self.batch_rows, p, f), dt),
        ]
        # per-buffer consumption token: what must settle before the buffer
        # is safe to overwrite — ``_RING_WRITE`` (gate on the live ring
        # buffer) after a landed push, or the orphaned transfer after a shed
        self._consumed: list = [None, None]
        self._next = 0
        self._t_next_send = 0.0  # rate-limit horizon (monotonic seconds)
        self.rows_fed = 0
        self.batches_fed = 0
        self.throttle_waits = 0

    def _stage(self, rows: np.ndarray):
        """Quantize ``rows`` into the next free staging buffer and start the
        async transfer.  Blocks only if BOTH buffers' consumers are still in
        flight — the double-buffer backstop, not the steady state."""
        i = self._next
        token = self._consumed[i]
        gate = None
        if token is not None:
            gate = self.ring._buf if token is _RING_WRITE else token
            self._consumed[i] = None
        m = rows.shape[0]
        with tracing.span(
            tracing.STAGE, rows=m, waited=int(gate is not None and not gate.is_ready())
        ):
            if gate is not None:
                jax.block_until_ready(gate)
            buf = self._staging[i]
            np.copyto(buf[:m], rows, casting="unsafe")  # host-side quantization
            self._next = 1 - i
            return i, jax.device_put(buf[:m])

    def _throttle(self, m: int) -> None:
        if self.rate_rows_per_s is None:
            return
        now = time.monotonic()
        if now < self._t_next_send:
            self.throttle_waits += 1
            time.sleep(self._t_next_send - now)
            now = time.monotonic()
        self._t_next_send = max(self._t_next_send, now) + m / self.rate_rows_per_s

    def feed(self, rows) -> int:
        """Split ``rows`` [M, P, F] into micro-batches and push each through
        staging -> async transfer -> ring.  Returns the number of rows that
        LANDED (ring or spill queue); under a shed-policy ring the
        difference went overboard and is visible in ``ring.counters``."""
        rows = np.asarray(rows)
        if rows.ndim != 3:
            raise ValueError(f"feed expects [M, P, F] rows; got {list(rows.shape)}")
        landed = 0
        for off in range(0, rows.shape[0], self.batch_rows):
            chunk = rows[off : off + self.batch_rows]
            self._throttle(chunk.shape[0])
            i, dev = self._stage(chunk)
            while True:
                try:
                    ok = self.ring.push(dev)
                    break
                except IngestBackpressure:
                    if self.on_pressure is None:
                        raise
                    self.on_pressure()  # drain; the retry reuses `dev`
            if ok:
                # safe-reuse gate: the slot write that consumed `dev` (hence
                # staging buffer i) — resolved against the LIVE ring buffer
                # at _stage time, never a version the next push may donate
                self._consumed[i] = _RING_WRITE
                landed += chunk.shape[0]
            else:  # shed: nothing consumed the transfer; buffer reusable when
                self._consumed[i] = dev  # the (now pointless) H2D settles
            self.batches_fed += 1
            self.rows_fed += chunk.shape[0]
        return landed

    def counters(self) -> dict:
        """Stream + ring counters in one host-side dict (for reports)."""
        out = dict(self.ring.counters)
        out.update(
            rows_fed=self.rows_fed,
            batches_fed=self.batches_fed,
            throttle_waits=self.throttle_waits,
        )
        return out
