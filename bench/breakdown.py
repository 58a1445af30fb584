"""Where a cell's device time and idle time go, by the program's own names.

    python bench/breakdown.py --workload <cell> --seed <n> --seconds <s> [--out DIR]

Builds the cell and runs one traced window as ``bench/run.py --trace 1``
does, without the correctness check, then prints as its last line one JSON
object: every metric of the cell read from that window (the end-to-end ones
too, to set beside an untraced run: the cost of tracing), the device time
per epoch of each superstep scope beside ``epoch_device_ms``, the unscoped
remainder inside the chunk dispatches by op, the top device ops prefixed by
their scope, the longest idle gaps named down to the program's host spans,
and per host span its count, seconds and summed arguments.  ``--out`` keeps
the ``.xplane.pb`` and the session's compiled HLO there.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common, driver, scopes  # noqa: E402
from bench import trace as trace_lib  # noqa: E402
from bench import traffic as traffic_lib  # noqa: E402
from bench.run import use_compile_cache  # noqa: E402
from repro.core.tracing import SUPERSTEP_SCOPES  # noqa: E402


def span_summary(program_spans, w0: int, w1: int) -> dict:
    """Per program span in the window: count, seconds, summed arguments."""
    out: dict = defaultdict(lambda: dict(count=0, seconds=0.0, args=defaultdict(int)))
    for name, s, e, args in program_spans:
        if w0 <= s < w1:
            o = out[name]
            o["count"] += 1
            o["seconds"] += (e - s) / 1e9
            for k, v in args.items():
                if isinstance(v, (int, float)):
                    o["args"][k] += v
    return {n: dict(o, args=dict(o["args"])) for n, o in sorted(out.items())}


def unscoped(sc, k: int = 10) -> list:
    """Inside the ``run`` spans, the device time of ops under no scope that
    no scoped op overlaps, by op (first device): the remainder of
    ``epoch_device_ms`` the scopes leave."""
    if not sc.ops:
        return []
    runs = trace_lib.union([(s, e) for n, s, e in sc.r.spans if n == "run"])
    ops = next(iter(sc.ops.values()))
    scoped = trace_lib.union([(s, e) for _, s, e, _, _, scope in ops if scope])
    totals: dict = defaultdict(int)
    for n, s, e, _, _, scope in ops:
        if not scope:
            for a, b in trace_lib.clip(runs, s, e):
                totals[n] += (b - a) - trace_lib.length(trace_lib.clip(scoped, a, b))
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
    return [[n[: trace_lib.NAME_CHARS], t / 1e9] for n, t in top if t > 0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None, help="keep the trace and compiled HLO here")
    args = ap.parse_args(argv)

    import jax

    use_compile_cache(ROOT)
    spec = common.load_benchmark()
    cell, cfg, traffic = common.resolve_cell(spec, args.workload)
    key_seed, rng = common.seeds(args.seed)
    bundle = common.load_module("builders", cfg["builder"]).build(cfg, traffic, key_seed)
    session = bundle["session"]
    sched = traffic_lib.schedule(traffic, cfg["predicates"], args.seconds, rng)
    driver.warm_up(bundle, cfg, sched)
    triples0 = bundle["model_triples"](bundle["state"]) if "model_triples" in bundle else None
    setup_s = time.perf_counter() - T_START

    trace_dir = tempfile.mkdtemp(prefix="bench_breakdown_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    spans = driver.Spans(annotate=True)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    window = driver.run_window(bundle, cfg, sched, args.seconds, [], spans)
    jax.profiler.stop_trace()
    try:
        path = trace_lib.newest_xplane(trace_dir)
        reduced = trace_lib.Reduced(trace_lib.read_xplane(path))
        program_spans = scopes.read_program_spans(path)
        hlo = session.program.compiled_hlo()
        if args.out:
            out_dir = Path(args.out)
            out_dir.mkdir(parents=True, exist_ok=True)
            shutil.copy(path, out_dir / f"{args.workload}.xplane.pb")
            for i, (kind, text) in enumerate(hlo):
                (out_dir / f"{args.workload}.{i}.{kind}.hlo.txt").write_text(text)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    final = window.pop("state")
    model_triples = None if triples0 is None else bundle["model_triples"](final) - triples0
    run = types.SimpleNamespace(
        window=window, spans=spans.items, setup_s=setup_s, reduced=reduced,
        model_triples=model_triples, cfg=cfg, capacity=bundle["state_capacity"],
        store_bytes=bundle["store_bytes"], device_kind=jax.devices()[0].device_kind,
        bundle=bundle, scoped=scopes.Scoped(reduced, hlo),
    )
    metrics = {}
    for group in ("end_to_end", "per_layer"):
        for m in common.cell_metrics(spec, args.workload, group):
            v = common.load_module("metrics", m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = float(v)
    sc, n = run.scoped, scopes.epochs(run)
    by_scope = {s: sc.scope_busy_s(s) * 1e3 / max(n, 1) for s in SUPERSTEP_SCOPES}
    out = dict(
        device=common.device_record(),
        epochs=n,
        window_s=reduced.window_s,
        busy_s=reduced.busy_s,
        metrics=metrics,
        epoch_device_ms=reduced.busy_within("run") * 1e3 / max(n, 1),
        scope_ms_per_epoch=by_scope,
        superstep_scopes_ms=sum(by_scope.values()),
        refresh_runs=sc.program_runs("refresh"),
        trunk_runs=sc.branch_runs("trunk"),
        unscoped_in_run_s=unscoped(sc),
        device_ops=sc.top_ops(15),
        idle_gaps=scopes.gap_labels(reduced, program_spans, 15),
        program_spans=span_summary(program_spans, reduced.w0, reduced.w1),
    )
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
