"""80th percentile over the queries due in the window: due time to the
return of the first chunk its tenant slot was active in (host clock)."""

from bench.metrics._latency import tail_ms


def read(run):
    w = run.window
    return tail_ms([q["due"] for q in w["queries"]], w["q_first"], w["t0"],
                   w["t_cut"], 80.0)
