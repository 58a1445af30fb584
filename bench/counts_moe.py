"""Work of the dropless expert layer, counted from its shapes: the
yardstick of ``moe_expert_roofline``, whatever implements the layer."""

from __future__ import annotations


def expert_work(tokens: int, top_k: int, experts: int, d_model: int, d_ff: int,
                weight_bytes: int, act_bytes: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one SwiGLU expert layer's grouped matmuls over
    ``tokens`` tokens, each routed to ``top_k`` experts.

    FLOPs: 2 x routed rows x 3 matrices x d_model x d_ff.  Bytes: every
    expert's three matrices read once, plus the routed rows in (d_model wide)
    and out (d_model wide) at the activation width.
    """
    rows = tokens * top_k
    flops = 2.0 * rows * 3 * d_model * d_ff
    nbytes = 3.0 * experts * d_model * d_ff * weight_bytes + 2.0 * rows * d_model * act_bytes
    return flops, nbytes
