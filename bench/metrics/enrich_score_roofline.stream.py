"""Eq. 11 scoring kernel (``kernels/enrich_score``) against its roofline:
the least time the chip needs for the work the algorithm requires
(``bench/counts.score_work``), times the calls in the traced window, over
the kernel's summed device time from the trace."""

from bench import common, counts

KERNEL = "enrich_score"


def read(run):
    r = run.reduced
    if r is None:
        return None
    t = r.op_seconds(lambda n: KERNEL in n)
    if t <= 0:
        return None
    cfg = run.cfg
    ops, nbytes = counts.score_work(
        cfg["max_tenants"], run.capacity, cfg["predicates"], cfg["functions"],
        run.store_bytes,
    )
    t_min, _ = counts.roofline_seconds(ops, nbytes, common.peak_of(run.device_kind))
    calls = sum(c[2] for c in run.window["chunks"])
    return 100.0 * t_min * calls / t
