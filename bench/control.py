"""Readings that set the limits of ``correct``: the program's numbers over
many seeds, and the control's (the plain reference one precision below the
configuration's, in the program's place) on the same sampled chunks.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

One process, one line of JSON per seed: ``program`` and ``control`` numbers.
The benchmark's own runs never run the control.
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

from bench import common, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--no-control", action="store_true",
                    help="read the program's numbers only")
    args = ap.parse_args(argv)
    cell = {w["name"]: w for w in common.load_benchmark()["workloads"]}[args.workload]
    reason = run.chip_check(cell["chips"])
    if reason:
        print(f"[control] refusing to run: {reason}", file=sys.stderr)
        return 1
    run.use_compile_cache(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        a = argparse.Namespace(workload=args.workload, seed=seed, seconds=args.seconds, trace=0)
        out = run.run_cell(a, control=not args.no_control)
        print(json.dumps(dict(seed=seed, correct=out["correct"],
                              program=out["program_numbers"],
                              control=out.get("control_numbers"),
                              metrics=out["metrics"])), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
