"""95th percentile over the stream's batches due in the window: due time to
the return of the first chunk whose epochs ran with its rows valid (host
clock)."""

from bench.metrics._latency import tail_ms


def read(run):
    w = run.window
    return tail_ms(w["batches"], w["b_visible"], w["t0"], w["t_cut"], 95.0)
