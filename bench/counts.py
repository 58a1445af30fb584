"""Operations and bytes the algorithms need, counted from shapes.

These are the yardstick of the roofline and utilisation metrics: the work
the algorithm requires, whatever implements it, never what a particular
kernel happens to move or compute.
"""

from __future__ import annotations

# Eq. 11 per (slot, row, predicate) lane and per function: entropy step
# (add, clip x2), inverse-entropy lerp (scale, floor, frac, 1 - frac, two
# multiplies, add), joint update (divide, multiply, clip x2, select), benefit
# (multiply, divide), running argmax (compare, three selects) = 20; plus the
# table bin of the lane (clip, scale, floor, index) = 4, once per lane.
SCORE_OPS_PER_FUNCTION = 20
SCORE_OPS_PER_LANE = 4


def score_work(slots: int, rows: int, preds: int, functions: int,
               store_bytes: int) -> tuple[float, float]:
    """(operations, bytes) of one Eq. 11 scoring pass over [S, C, P].

    Bytes: each shared [C, P] operand read once (predicate probability and
    entropy at the storage width, the int32 state id), the [S, C] joint read
    once at the storage width, and the three [S, C, P] f32 outputs (benefit,
    next function, estimated joint) written once.
    """
    lanes = slots * rows * preds
    ops = lanes * (SCORE_OPS_PER_LANE + SCORE_OPS_PER_FUNCTION * functions)
    read = rows * preds * (2 * store_bytes + 4) + slots * rows * store_bytes
    written = 3 * lanes * 4
    return float(ops), float(read + written)


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peak["flops"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def transformer_active_params(layers: int, hidden: int, heads: int,
                              kv_heads: int, head_dim: int,
                              intermediate: int) -> int:
    """Parameters a token passes through in a dense GQA transformer with a
    gated (SwiGLU) MLP, from its published widths: q/k/v/o projections and
    three MLP matrices per layer.  Norm weights and embeddings excluded."""
    attn = hidden * head_dim * (heads + 2 * kv_heads) + heads * head_dim * hidden
    mlp = 3 * hidden * intermediate
    return layers * (attn + mlp)


def backbone_flops(triples: int, positions: int, active_params: int) -> float:
    """Forward FLOPs of ``triples`` objects through the trunk: 2 per active
    parameter per position."""
    return 2.0 * triples * positions * active_params
