"""Jit'd wrappers: enrichment state -> TripleBenefits via the fused kernels.

``fused_benefits`` is a drop-in replacement for
``repro.core.benefit.compute_benefits`` on conjunctive queries
(``OperatorConfig.use_fused_kernel``); ``fused_benefits_batched`` is the
multi-query analogue of ``repro.core.benefit.compute_benefits_batched``
(``MultiQueryConfig.backend="pallas"``), including the fused ``"best"``-mode
argmax that never materializes [Q, N, P, F] in HBM.  Neither returns a
per-lane cost: the kernel prices Eq. 11 in-tile, and the plan looks up the
cost of the lanes it keeps (``repro.core.benefit.function_cost``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.benefit import TripleBenefits
from repro.core.decision_table import DecisionTable
from repro.core.entropy import _inverse_entropy_table, binary_entropy
from repro.core.query import CompiledQuery
from repro.core.state import EnrichmentState
from repro.kernels.enrich_score.kernel import (
    BIG_INVALID,
    BLOCK_ROWS,
    LANES,
    enrich_score_best_tiles_batched,
    enrich_score_tiles,
    enrich_score_tiles_batched,
    gather_table,
)


def _tile_layout(n: int, p: int):
    """Shared [N*P] -> [R, LANES] padding scheme of both wrappers.

    R is padded to a multiple of the kernel's ``BLOCK_ROWS``.  Returns
    (flatten, unflatten): ``flatten`` lays any
    [..., N, P]-shaped operand out as LANES-wide rows (leading axes
    preserved), ``unflatten`` strips the pad and restores [..., N, P].

    ``flatten`` casts to ``dtype`` — f32 by default (index-like operands:
    state ids, predicate indices, masks), but probability rows from a bf16
    substrate pass ``dtype=x.dtype`` so the STORAGE dtype reaches the
    kernel and the f32 upcast happens in-register inside the tile
    (dequant-in-tile: no f32 copy of the substrate rows ever lands in HBM).
    """
    m = n * p
    block = BLOCK_ROWS * LANES
    rows = -(-m // block) * BLOCK_ROWS
    pad = rows * LANES - m

    def flatten(x, fill=0.0, dtype=jnp.float32):
        lead = x.shape[:-2]
        x = x.reshape(lead + (-1,)).astype(dtype)
        widths = [(0, 0)] * len(lead) + [(0, pad)]
        x = jnp.pad(x, widths, constant_values=fill)
        return x.reshape(lead + (rows, LANES))

    def unflatten(x):
        lead = x.shape[:-2]
        return x.reshape(lead + (-1,))[..., :m].reshape(lead + (n, p))

    return flatten, unflatten


def _staged_tables(table: DecisionTable, costs, mode: str, lut_bins: int):
    """The kernel's three staged tables: (delta + next fn | per-function
    deltas), costs, and the inverse-entropy LUT with its one-bin-shifted
    copy (both lerp neighbours from one gather at the lower index).
    """
    lut = _inverse_entropy_table(lut_bins)
    f = costs.shape[1]
    if mode == "best":
        delta_all = table.delta_h_all.reshape(-1, f).astype(jnp.float32)
        delta_all = jnp.where(jnp.isfinite(delta_all), delta_all, BIG_INVALID)
        first = gather_table(delta_all.T)
        cost_tab = gather_table(jnp.asarray(costs, jnp.float32).T)
    else:
        first = gather_table(jnp.stack([
            table.delta_h.reshape(-1).astype(jnp.float32),
            table.next_fn.reshape(-1).astype(jnp.float32),
        ]))
        cost_tab = gather_table(jnp.asarray(costs, jnp.float32).reshape(1, -1))
    lut_tab = gather_table(jnp.asarray(np.stack([lut, np.append(lut[1:], lut[-1])])))
    return first, cost_tab, lut_tab


def fused_benefits(
    state: EnrichmentState,
    query: CompiledQuery,
    table: DecisionTable,
    costs: jax.Array,  # [P, F]
    candidate_mask: jax.Array | None = None,
    interpret: bool = False,
    lut_bins: int = 4096,
) -> TripleBenefits:
    assert query.is_conjunctive, "fused kernel covers the conjunctive fast path"
    n, p = state.pred_prob.shape
    f = costs.shape[1]
    if candidate_mask is None:
        candidate_mask = ~state.in_answer

    flat, unflat = _tile_layout(n, p)

    pred_idx = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32)[None], (n, p))
    out = enrich_score_tiles(
        flat(state.pred_prob),
        flat(state.uncertainty),
        flat(binary_entropy(state.pred_prob.astype(jnp.float32))),
        flat(state.state_id().astype(jnp.float32)),
        flat(pred_idx.astype(jnp.float32)),
        flat(jnp.broadcast_to(state.joint_prob[:, None], (n, p))),
        flat(jnp.broadcast_to(candidate_mask[:, None], (n, p)).astype(jnp.float32)),
        *_staged_tables(table, costs, "table", lut_bins),
        num_bins=table.num_bins,
        num_states=table.num_states,
        num_functions=f,
        lut_bins=lut_bins,
        interpret=interpret,
    )
    benefit, next_fn, est_joint = (unflat(x) for x in out)
    benefit = jnp.where(benefit <= -1e29, -jnp.inf, benefit)
    return TripleBenefits(
        benefit=benefit, next_fn=next_fn.astype(jnp.int32), est_joint=est_joint
    )


def fused_benefits_batched(
    pred_prob: jax.Array,  # [N, P] shared predicate probabilities
    uncertainty: jax.Array,  # [N, P]
    state_id: jax.Array,  # [N, P] int32
    joint_prob: jax.Array,  # [Q, N] per-query joint probabilities
    table: DecisionTable,
    costs: jax.Array,  # [P, F]
    function_selection: str = "table",  # "table" | "best"
    interpret: bool = False,
    lut_bins: int = 4096,
) -> TripleBenefits:
    """Multi-query fused scoring over a shared substrate -> [Q, N, P] leaves.

    The substrate-derived rows (pred_prob / uncertainty / state_id, and the
    f32 entropy row H(pred_prob) that step 2 adds the delta to, computed
    here exactly as ``compute_benefits_batched`` computes it) are laid out
    once at [R, LANES] and shared by every grid row via the kernel's index
    map; only ``joint`` and the output tensors carry the Q axis.  In
    ``"best"`` mode the per-function Eq. 11 argmax runs inside the tile, so
    nothing F-shaped reaches HBM.

    Validity/candidate masking beyond exhausted triples (pred_mask, §4.1) is
    the caller's job, mirroring ``compute_benefits_batched``.

    Probability inputs may be bf16 (the bf16 substrate's derived rows):
    they ship to the kernel AT storage dtype and dequantize to f32
    in-register inside each tile, where every Eq. 11 term — entropy deltas,
    benefit ratio, best-mode argmax — runs in f32 exactly as if the caller
    had upcast first (bf16 -> f32 is exact; see the kernel module docstring
    for the exactness contract the parity tests pin).  Mixed probability
    dtypes raise
    ``SubstrateDtypeError`` — a silent promotion here would materialize the
    f32 copy the tile path exists to avoid.

    ``interpret=True`` runs the kernel in the Pallas interpreter (hosts
    without a TPU); the default compiles it for the device.
    """
    if not (pred_prob.dtype == uncertainty.dtype == joint_prob.dtype):
        from repro.core.errors import SubstrateDtypeError

        raise SubstrateDtypeError(
            f"fused scoring needs one probability dtype; got pred_prob="
            f"{pred_prob.dtype}, uncertainty={uncertainty.dtype}, "
            f"joint_prob={joint_prob.dtype}",
            expected=str(pred_prob.dtype),
            got=f"{uncertainty.dtype}/{joint_prob.dtype}",
            where="fused_benefits_batched",
        )
    row_dt = pred_prob.dtype
    n, p = pred_prob.shape
    q = joint_prob.shape[0]
    f = costs.shape[1]

    flat, unflat = _tile_layout(n, p)

    pred_idx = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32)[None], (n, p))
    shared = (
        flat(pred_prob, dtype=row_dt),
        flat(uncertainty, dtype=row_dt),
        flat(binary_entropy(pred_prob.astype(jnp.float32))),
        flat(state_id.astype(jnp.float32)),
        flat(pred_idx.astype(jnp.float32)),
    )
    joint_b = flat(jnp.broadcast_to(joint_prob[:, :, None], (q, n, p)), dtype=row_dt)
    consts = dict(
        num_bins=table.num_bins, num_states=table.num_states,
        num_functions=f, lut_bins=lut_bins, interpret=interpret,
    )

    if function_selection == "best":
        assert table.delta_h_all is not None, "table learned without delta_h_all"
        out = enrich_score_best_tiles_batched(
            *shared, joint_b, *_staged_tables(table, costs, "best", lut_bins),
            **consts,
        )
    else:
        out = enrich_score_tiles_batched(
            *shared, joint_b, *_staged_tables(table, costs, "table", lut_bins),
            **consts,
        )

    benefit, next_fn, est_joint = (unflat(x) for x in out)
    benefit = jnp.where(benefit <= -1e29, -jnp.inf, benefit)
    return TripleBenefits(
        benefit=benefit, next_fn=next_fn.astype(jnp.int32), est_joint=est_joint
    )
