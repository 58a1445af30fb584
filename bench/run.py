"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the session from the cell's configuration (corpus, tables and
weights made on the device from the seed) and warms up the cell's own
programs; the window drives the cell's open-loop traffic for ``--seconds``;
the report checks the sampled chunks against the plain reference and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks`` (each number compared beside its limit).

The run refuses (non-zero exit, no result) where JAX finds no TPU, or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import check, common, driver  # noqa: E402
from bench import traffic as traffic_lib  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache in the checkout (a fixed path:
    the path is part of the cache key), for every program however fast it
    compiles; ``JAX_COMPILATION_CACHE_DIR`` wins where it is set."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chip_check(chips: int) -> str | None:
    """None if JAX sees at least ``chips`` TPU devices, else the reason."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        return f"JAX finds no accelerator: {e}"
    if devs[0].platform != "tpu":
        return f"JAX finds no TPU (platform {devs[0].platform!r})"
    if len(devs) < chips:
        return f"the cell needs {chips} chips, JAX finds {len(devs)}"
    return None


def run_cell(args, root: Path = ROOT, overrides: dict | None = None,
             control: bool = False) -> dict:
    """Set-up, window and report of one cell -> the result dict.
    ``overrides`` replaces configuration or traffic entries (tests run
    cells at CPU sizes this way).  ``control`` also reads the control, the
    reference computed one precision below the configuration's (bfloat16
    for float32), in the program's place on the same sampled chunks, into
    ``control_numbers`` (``bench/control.py``; the benchmark's runs never
    do)."""
    import jax

    spec = common.load_benchmark(root)
    cell, cfg, traffic = common.resolve_cell(spec, args.workload, root)
    for k, v in (overrides or {}).items():
        (traffic if k in traffic else cfg)[k] = v
    key_seed, rng = common.seeds(args.seed)
    clock = common.CompileClock()

    builder = common.load_module("builders", cfg["builder"])
    bundle = builder.build(cfg, traffic, key_seed)
    session = bundle["session"]
    sched = traffic_lib.schedule(traffic, cfg["predicates"], args.seconds, rng)
    need = len(sched["batches"]) * sched["batch_rows"]
    if need and need > len(bundle["stream_rows"]):
        raise ValueError(f"the stream needs {need} rows, the corpus holds "
                         f"{len(bundle['stream_rows'])} beyond the initial rows")
    # sampled chunks start in the first 60% of the window, so each has a
    # boundary to start at while the window is open
    sample_at = sorted(rng.uniform(0.05, 0.6, size=cfg["check_chunks"]).tolist())
    driver.warm_up(bundle, cfg, sched)
    traces_before = session.superstep_traces
    triples0 = bundle["model_triples"](bundle["state"]) if "model_triples" in bundle else None
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s, {clock.compiles} compiles "
        f"({clock.seconds:.3f} s), {clock.cache_hits} persistent-cache hits; "
        f"{len(sched['queries'])} queries and {len(sched['batches'])} stream "
        f"batches due in the window")

    spans = driver.Spans(annotate=bool(args.trace))
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0 = clock.mark()
    window = driver.run_window(bundle, cfg, sched, args.seconds, sample_at, spans)
    c1 = clock.mark()
    reduced = None
    if args.trace:
        jax.profiler.stop_trace()
        from bench import trace as trace_lib

        try:
            raw = trace_lib.read_xplane(trace_lib.newest_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        reduced = trace_lib.Reduced(raw)
        log(f"trace: {len(raw['spans'])} host spans, "
            f"{ {d: len(o) for d, o in raw['devices'].items()} } device ops; window "
            f"{reduced.window_s:.6f} s, device busy {reduced.busy_s:.6f} s")
    peak_bytes = common.memory_peak_bytes()
    chunks = window["chunks"]
    log(f"window: {len(chunks)} chunks, {sum(c[2] for c in chunks)} epochs "
        f"({sum(c[2] for c in chunks if c[3])} in the window), "
        f"{c1[1] - c0[1]} compiles in the window, superstep traces "
        f"{traces_before} -> {session.superstep_traces}, "
        f"{window['unanswered']} queries and {window['invisible']} batches unserved, "
        f"queue at close {window['queue_len'][-1][1] if window['queue_len'] else 0}")
    model_triples = None
    if triples0 is not None:
        model_triples = bundle["model_triples"](window["state"]) - triples0
        log(f"model-level triples bought: {model_triples} in {len(chunks)} chunks")

    # ---- correctness: after the window, with the program's state freed ----
    t_ref = time.perf_counter()
    numbers = {}
    final = window.pop("state")
    if window["batches"]:
        rows = np.asarray(jax.device_get(final.bank_outputs[: bundle["initial_rows"] + window["rows_fed"]]))
        numbers["rows_wrong"] = float(check.rows_wrong(
            rows, int(final.num_rows), bundle["corpus"],
            bundle["initial_rows"] + window["rows_fed"]))
    samples = [dict(pre=check.host_state(s["pre"]), post=check.host_state(s["post"]), ef=s["ef"])
               for s in window.pop("samples")]
    del final, bundle["state"]
    gc.collect()
    readings, ctrl = check.check_samples(bundle, cfg, samples, key_seed, control)
    numbers.update(readings)
    correct, table = check.verdict(numbers, cfg["limits"])
    log(f"reference check of {len(samples)} chunk(s): {time.perf_counter() - t_ref:.3f} s")

    group = "per_layer" if args.trace else "end_to_end"
    # what the metric readers see of the run
    run = types.SimpleNamespace(
        window=window, spans=spans.items, setup_s=setup_s, reduced=reduced,
        model_triples=model_triples,
        cfg=cfg, capacity=bundle["state_capacity"], store_bytes=bundle["store_bytes"],
        device_kind=jax.devices()[0].device_kind,
        bundle=bundle,
    )
    metrics = {}
    for m in common.cell_metrics(spec, args.workload, group):
        v = common.load_module("metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = common.device_record()
    device["memory_peak_bytes"] = peak_bytes
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    out = dict(
        correct=bool(correct),
        attempted=len(window["queries"]) + len(window["batches"]),
        failed=window["unanswered"] + window["invisible"],
        metrics=metrics,
        device=device,
    )
    if reduced is not None:
        out["breakdown"] = dict(device_ops=reduced.top_ops(), idle_gaps=reduced.idle_gaps())
    out["program_numbers"] = numbers
    if control:
        out["control_numbers"] = ctrl
    out["checks"] = table
    return out


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "BENCHMARK.json").is_file() or not (ROOT / "src" / "repro").is_dir():
        log(f"no BENCHMARK.json and program under {ROOT}: nothing to run")
        return 2
    cell = {w["name"]: w for w in common.load_benchmark()["workloads"]}.get(args.workload)
    if cell is None:
        log(f"unknown workload {args.workload!r}")
        return 2
    reason = chip_check(cell["chips"])
    if reason:
        log(f"refusing to run: {reason}")
        return 1
    use_compile_cache(ROOT)
    out = run_cell(args)
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
