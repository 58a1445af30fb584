"""Find the highest query rate a cell sustains, once, on the chip.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1.5,2,2.5,3

One process builds and warms the cell once, then drives its traffic at each
rate from the same starting state, lowest rate first.  A query's wait runs
from its due time to its admission; part of it is the lockstep cadence (the
next chunk boundary), and part, its slot wait, is time spent queued at
boundaries where every tenant slot was full.  A rate is sustained when the
admission queue does not grow: no query is queued at the window's last
boundary, and the mean slot wait of the queries due in the window's second
half exceeds the first half's by at most a tenth of the first half's mean
wait.  (The cadence part has no trend; left in, its noise alone would swing
a test this tight.)  The traffic file takes four fifths of the highest rate
that is sustained together with every lower rate swept.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import common, driver, run  # noqa: E402
from bench import traffic as traffic_lib  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = common.load_benchmark()
    cell, cfg, traffic = common.resolve_cell(spec, args.workload)
    reason = run.chip_check(cell["chips"])
    if reason:
        print(f"[sweep] refusing to run: {reason}", file=sys.stderr)
        return 1
    run.use_compile_cache(ROOT)
    key_seed, rng = common.seeds(args.seed)
    bundle = common.load_module("builders", cfg["builder"]).build(cfg, traffic, key_seed)
    rates = sorted(float(r) for r in args.rates.split(","))
    traffic["queries"]["rate_per_s"] = max(rates)
    driver.warm_up(bundle, cfg, traffic_lib.schedule(traffic, cfg["predicates"], args.seconds, rng))
    held, best = True, None
    for rate in rates:
        traffic["queries"]["rate_per_s"] = rate
        sched = traffic_lib.schedule(traffic, cfg["predicates"], args.seconds, rng)
        w = driver.run_window(bundle, cfg, sched, args.seconds, [], driver.Spans(False))
        due = np.asarray([q["due"] for q in w["queries"]])
        admit = np.asarray([np.nan if a is None else a for a in w["q_admit"]])
        seen = np.asarray([np.nan if a is None else a for a in w["q_seen"]])
        wait = admit - (w["t0"] + due)
        slot_wait = np.where(np.asarray(w["q_queued"]), admit - seen, 0.0)
        half = due < args.seconds / 2
        chunk_s = np.mean([c[1] - c[0] for c in w["chunks"]])
        in_window = [n for t, n in w["queue_len"] if t <= args.seconds]
        q_end = in_window[-1]
        growth = float(np.nanmean(slot_wait[~half]) - np.nanmean(slot_wait[half]))
        ok = q_end == 0 and growth <= 0.1 * float(np.nanmean(wait[half]))
        held = held and ok
        if held:
            best = rate
        print(json.dumps(dict(
            rate=rate, queries=len(due), sustained=bool(ok), queue_at_close=q_end,
            queue_max=max(in_window), queued=int(np.sum(w["q_queued"])),
            wait_first_half_s=float(np.nanmean(wait[half])),
            wait_second_half_s=float(np.nanmean(wait[~half])),
            slot_wait_first_half_s=float(np.nanmean(slot_wait[half])),
            slot_wait_second_half_s=float(np.nanmean(slot_wait[~half])),
            chunk_s=float(chunk_s),
            first_answer_p90_ms=float(np.percentile(
                [(f - (w["t0"] + d)) * 1e3 for d, f in zip(due, w["q_first"]) if f is not None], 90)),
        )), flush=True)
        w.pop("state")
    print(json.dumps(dict(highest_sustained=best,
                          cell_rate=None if best is None else 0.8 * best)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
