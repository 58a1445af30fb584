"""Bring-up smoke: drive the PIQUE serving session end to end on one TPU.

Runs the served path in this one process, through ``repro.launch.serve.main``
(a chip belongs to one process, so nothing here starts another):

  A   simulated-bank session at deployment scale: 1<<20-row bf16 substrate,
      4 predicates x 4 functions, 16 tenant slots, chunked scans with
      ``--overlap``, admits / runs / a retire, ingest streamed through the
      donated pending-row ring, Pallas scoring kernels compiled for the chip,
      checkpoints at event boundaries;
  A'  ``--restore`` from A's earliest retained mid-trace checkpoint: must
      reproduce A's ``answer_digest`` and ``cost_hex`` bit for bit;
  B   A's trace on the jnp scoring backend: the reference for A's kernels,
      which must reproduce A's ``answer_digest`` and ``cost_hex`` bit for
      bit;
  C   model-cascade session whose expensive level is the qwen3-1.7b backbone
      at its published widths (28 layers, d_model 2048, 16/8 heads, d_ff
      6144; random weights from the seed), traced into the superstep; the
      trace runs long enough that the planner exhausts the probe levels and
      buys backbone triples, and the phase fails unless it did.

Each phase prints epochs, wall and compile seconds, superstep traces, mean
E(F) and the device's peak bytes in use.  The last line of standard output
is ``{"ok": true, "device": {...}}`` only if every check passed on a TPU;
otherwise the script exits non-zero and prints no result.

    python chip_smoke.py                 # on the chip
    python chip_smoke.py --tiny          # tiny shapes, Pallas interpreter:
                                         # exercises every phase on a CPU
                                         # host, then refuses (not a TPU)
"""

from __future__ import annotations

import argparse
import json
import math
import re
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0  # corpus, cascade weights and admit draws of every phase
FULL = dict(
    capacity=1 << 20, objects=786432, ingest=131072, batch=32768, tenants=16,
    cascade_objects=512, cascade_tenants=8, backbone_size="published",
)
TINY = dict(
    capacity=1024, objects=768, ingest=128, batch=64, tenants=4,
    cascade_objects=128, cascade_tenants=4, backbone_size="smoke",
)
# 18 epochs over 2 predicates: the planner runs out of probe triples on its
# candidates (512 objects x 2 predicates x 2 probe levels) and buys the
# backbone level
CASCADE_TRACE = "admit:2;admit:1;admit:2;run:6;admit:2;run:6;retire:0;run:6"


def session_trace(ingest: int) -> str:
    return (
        f"admit:2;admit:3;admit:4;run:4;ingest:{ingest};admit:2;run:4;"
        f"retire:1;ingest:{ingest};admit:3;run:4"
    )


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit's retrieval counts as its compile)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return self.seconds, self.cache_hits


def run_phase(name, argv, report_path, clock, failures):
    """serve.main(argv) in-process -> its JSON report (None on failure)."""
    import jax

    from repro.launch import serve

    s0, h0 = clock.mark()
    t0 = time.perf_counter()
    try:
        rc = serve.main(argv + ["--report", str(report_path)])
    except Exception as e:  # a phase failure is reported, never swallowed
        rc = f"{type(e).__name__}: {e}"
    wall = time.perf_counter() - t0
    s1, h1 = clock.mark()
    if rc != 0:
        failures.append(f"phase {name}: serve.main returned {rc}")
        print(f"[smoke] phase {name}: FAILED ({rc})", flush=True)
        return None
    rep = json.loads(Path(report_path).read_text())
    stats = jax.devices()[0].memory_stats() or {}
    print(
        f"[smoke] phase {name}: epochs={rep['epochs']} "
        f"wall_s={wall} serve_wall_s={rep['wall_s']} "
        f"compile_s={s1 - s0} cache_hits={h1 - h0} "
        f"superstep_traces={rep['superstep_traces']} "
        f"mean_ef={rep['mean_expected_f']} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"answer_digest={rep['answer_digest'][:16]} cost_hex={rep['cost_hex']}",
        flush=True,
    )
    if not (rep["epochs"] > 0 and math.isfinite(rep["mean_expected_f"])):
        failures.append(f"phase {name}: no epochs or non-finite E(F)")
    return rep


def earliest_mid_trace_step(ckpt_dir: Path, final_step: int):
    steps = sorted(
        int(m.group(1))
        for p in ckpt_dir.iterdir()
        if (m := re.fullmatch(r"step_(\d+)", p.name))
    )
    mid = [s for s in steps if s < final_step]
    return mid[0] if mid else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="tiny shapes and the Pallas interpreter (CPU hosts); "
                         "the script still refuses to report success off a TPU")
    ap.add_argument("--work-dir", default=None,
                    help="checkpoints and phase reports (default: a fresh "
                         "temporary directory)")
    ap.add_argument("--compile-cache", default=str(ROOT / ".jax_cache"),
                    help="persistent compile cache directory, used unless "
                         "JAX_COMPILATION_CACHE_DIR is set")
    args = ap.parse_args(argv)

    try:
        import jax

        from repro.launch.serve import use_compile_cache
    except ImportError as e:
        print(f"[smoke] cannot import the PIQUE package from {ROOT}/src: {e}",
              file=sys.stderr)
        return 2

    dev = jax.devices()[0]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()))
    if dev.platform != "tpu" and not args.tiny:
        print(f"[smoke] no TPU: JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    cache_dir = use_compile_cache(args.compile_cache)
    print(f"[smoke] device {device}, compile cache {cache_dir}", flush=True)

    size = TINY if args.tiny else FULL
    interp = ["--pallas-interpret"] if args.tiny else []
    clock = CompileClock()
    failures: list = []
    with tempfile.TemporaryDirectory(dir=args.work_dir) as work:
        work = Path(work)
        ckpt = work / "ckpt_a"
        base = [
            "--session", "--seed", str(SEED), "--preds", "4",
            "--objects", str(size["objects"]),
            "--capacity", str(size["capacity"]),
            "--max-tenants", str(size["tenants"]),
            "--substrate-dtype", "bfloat16", "--chunk-size", "2", "--overlap",
            "--ingest-batch", str(size["batch"]),
            "--trace", session_trace(size["ingest"]),
        ]
        a = run_phase(
            "A", base + ["--backend", "pallas", "--checkpoint-dir", str(ckpt),
                         "--checkpoint-every", "2"] + interp,
            work / "a.json", clock, failures,
        )
        if a is not None:
            step = earliest_mid_trace_step(ckpt, a["epochs_total"])
            if step is None:
                failures.append("phase A left no mid-trace checkpoint")
            else:
                print(f"[smoke] phase A': restoring step {step}", flush=True)
                r = run_phase(
                    "A'", base + ["--backend", "pallas", "--checkpoint-dir",
                                  str(ckpt), "--restore", "--restore-step",
                                  str(step)] + interp,
                    work / "a_restore.json", clock, failures,
                )
                if r is not None and (
                    r["answer_digest"], r["cost_hex"]
                ) != (a["answer_digest"], a["cost_hex"]):
                    failures.append("restore did not reproduce phase A bitwise")
        b = run_phase("B", base + ["--backend", "jnp"], work / "b.json",
                      clock, failures)
        if a is not None and b is not None:
            exact = (a["answer_digest"], a["cost_hex"]) == (
                b["answer_digest"], b["cost_hex"])
            print(f"[smoke] pallas vs jnp: bitwise={exact} "
                  f"cost_hex {a['cost_hex']} vs {b['cost_hex']}", flush=True)
            if not exact:
                failures.append("pallas session is not bitwise the jnp session")
        c = run_phase(
            "C",
            ["--session", "--bank", "cascade", "--seed", str(SEED),
             "--backbone", "qwen3-1.7b", "--backbone-size", size["backbone_size"],
             "--objects", str(size["cascade_objects"]), "--preds", "2",
             "--max-tenants", str(size["cascade_tenants"]),
             "--trace", CASCADE_TRACE],
            work / "c.json", clock, failures,
        )
        if c is not None:
            executed = c["executed_per_function"]
            backbone_cost = min(row[-1] for row in c["function_costs"])
            print(f"[smoke] phase C backbone {c.get('backbone')}, "
                  f"bank arrays passed to the superstep: "
                  f"{c['bank_param_bytes']} bytes, executed triples per "
                  f"level {executed}, spend {c['cost_spent']} (one backbone "
                  f"triple costs {backbone_cost})", flush=True)
            want = dict(num_layers=28, d_model=2048, num_heads=16,
                        num_kv_heads=8, d_ff=6144)
            got = {k: c.get("backbone", {}).get(k) for k in want}
            if not args.tiny and got != want:
                failures.append(f"phase C backbone widths {got} != {want}")
            if executed[-1] < 1 or c["cost_spent"] < backbone_cost:
                failures.append("phase C never ran the backbone level")

    for f in failures:
        print(f"[smoke] FAILED: {f}", file=sys.stderr)
    if failures:
        return 1
    if dev.platform != "tpu":
        print(f"[smoke] every phase passed, but on {dev.platform!r}: no TPU, "
              "no result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
