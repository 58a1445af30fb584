"""Names the program puts on the profiler's clock.

Two kinds, both read from a ``jax.profiler`` trace and nowhere else:

* **host spans** — ``span(name, **counts)`` is a
  ``jax.profiler.TraceAnnotation`` named ``pique.<name>``, whose keyword
  arguments (small integers: a slot, rows, lanes per level) travel with the
  event.  Outside a trace it costs one flag check in C++.
* **device scopes** — ``scope(name)`` is a ``jax.named_scope``
  ``pique/<name>``: compile-time metadata that reaches the compiled HLO's
  ``op_name`` (``jit(run_fn)/while/body/.../pique/score/...``) and nothing
  else.  A device op belongs to the FIRST ``pique/`` scope of its op name,
  so work traced inside ``refresh`` counts there, and ``trunk`` is a
  sub-scope of ``bank``; ``experts`` and ``conv`` are sub-scopes of
  ``trunk``.  XLA's TPU lowering of ``jax.lax.ragged_dot`` (the experts'
  grouped matmuls) replaces that instruction's op name with its own,
  ``ragged-dot-<n>``: the benchmark puts those ops down to ``experts`` by
  that name.

Tests, the benchmark's trace reduction and the README cite the names below.
"""

from __future__ import annotations

import jax

SPAN_PREFIX = "pique."
SCOPE_PREFIX = "pique/"

# host spans
ADMIT = "admit"  # EngineSession.admit; slot
RETIRE = "retire"  # EngineSession.retire; slot
SYNC = "sync"  # the blocking read of state.active inside admit / retire
REFRESH = "refresh"  # EpochProgram.refresh; also the scope of its program
STAGE = "stage"  # IngestStream._stage; rows, waited
PUSH = "push"  # PendingRing.push; blocked
DRAIN = "drain"  # PendingRing.drain_into; slots, rows, refreshed
RUN = "run"  # EpochProgram.run_scan; epochs, traces
DISPATCH = "dispatch"  # EpochProgram.dispatch_scan
WAIT = "wait"  # run_scan's device_get and block_until_ready
HISTORY = "history"  # materialize_history; lanes_0 .. lanes_{F-1}, expert_load

# device scopes, one per superstep phase, plus the model trunk inside the
# bank (with its expert and conv layers inside it) and REFRESH, the whole
# refresh program
SCORE = "score"  # _benefits: scoring, the cost gather, valid masking
CANDIDATES = "candidates"  # candidate_mask and restrict_benefits
TOPK = "topk"  # select_plans_batched
MERGE = "merge"  # merge_plans_dedup_wants, quarantine_filter
BANK = "bank"  # _bank_part
TRUNK = "trunk"  # the backbone branch of ModelCascadeBank.execute
EXPERTS = "experts"  # models/moe.moe_apply: router, sort, grouped matmuls, combine
CONV = "conv"  # models/short_conv.conv_apply: the gated short convolution
APPLY = "apply"  # chargeable_mask, apply_outputs_to_substrate, attribute_epoch
DERIVE = "derive"  # _derive
SELECT = "select"  # _select_answers

SUPERSTEP_SCOPES = (SCORE, CANDIDATES, TOPK, MERGE, BANK, APPLY, DERIVE, SELECT)


def span(name: str, **counts):
    """Host span ``pique.<name>`` carrying ``counts`` as its arguments."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **counts)


def scope(name: str):
    """Device scope ``pique/<name>`` on every op traced inside it."""
    return jax.named_scope(SCOPE_PREFIX + name)
