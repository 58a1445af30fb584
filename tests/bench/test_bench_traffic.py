"""The traffic generator: deterministic per seed, and every seed gets the
same work in another order."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import common, traffic  # noqa: E402

MIX = json.loads((ROOT / "bench" / "traffic" / "analysts-stream.json").read_text())


def sched(seed, seconds=51.0):
    _, rng = common.seeds(seed)
    return traffic.schedule(MIX, 4, seconds, rng)


def test_same_seed_same_schedule():
    assert sched(4_000_000_007) == sched(4_000_000_007)


def test_seeds_reorder_the_same_work():
    a, b = sched(1), sched(2)
    assert [q["due"] for q in a["queries"]] != [q["due"] for q in b["queries"]]
    n = len(a["queries"])
    assert abs(n - len(b["queries"])) <= 1
    la = sorted(q["lifetime"] for q in a["queries"])
    lb = sorted(q["lifetime"] for q in b["queries"])
    k = min(len(la), len(lb)) - 1
    assert np.allclose(la[:k], lb[:k])
    ka = sorted(len(q["cols"]) for q in a["queries"])
    kb = sorted(len(q["cols"]) for q in b["queries"])
    assert abs(sum(ka) - sum(kb)) <= 3


def test_query_shape_and_stream():
    s = sched(3)
    rate = MIX["queries"]["rate_per_s"]
    assert abs(len(s["queries"]) - rate * 51.0) <= 1
    for q in s["queries"]:
        assert 1 <= len(q["cols"]) <= 3 and len(set(q["cols"])) == len(q["cols"])
        assert 0 <= q["due"] < 51.0 and q["lifetime"] > 0
    st = MIX["stream"]
    gap = st["batch_rows"] / st["rows_per_s"]
    assert s["batch_rows"] == st["batch_rows"]
    assert np.allclose(np.diff(s["batches"]), gap)
    assert s["batches"][-1] < 51.0 <= s["batches"][-1] + gap


def test_seed_maps_beyond_32_bits():
    k1, _ = common.seeds(2**31 + 5)
    k2, _ = common.seeds(2**33 + 5)
    assert 0 <= k1 < 2**31 and 0 <= k2 < 2**31 and k1 != k2
