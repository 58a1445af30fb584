"""Host time of the ingest front end per stream batch: every feed through
the staging buffers and pending-row ring, and every drain into the session,
run to completion, over the batches fed."""


def read(run):
    n = run.window["rows_fed"] // max(run.window.get("batch_rows", 0) or 1, 1)
    d = [e - s for name, s, e in run.spans if name == "ingest"]
    return sum(d) * 1e3 / n if n and d else None
