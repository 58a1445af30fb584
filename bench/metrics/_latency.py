"""Shared arithmetic of the latency metrics (a tail over every item due in
the window, from its due time; an item never served counts as the grace
limit it ran out at)."""

from __future__ import annotations

import numpy as np

from bench.common import percentile


def tail_ms(due_s, done, t0: float, t_cut: float, q: float):
    if not due_s:
        return None
    lat = [((d if d is not None else t_cut) - (t0 + s)) * 1e3 for s, d in zip(due_s, done)]
    return percentile(np.asarray(lat), q)
