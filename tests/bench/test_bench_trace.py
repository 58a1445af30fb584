"""The trace reduction, on a synthetic trace whose answers are counted by
hand."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace  # noqa: E402

MS = 1_000_000  # ns


def synthetic():
    # window: first span at 0 ms, last run ends at 100 ms
    spans = [
        ("bookkeeping", 0 * MS, 2 * MS),
        ("admit", 2 * MS, 10 * MS),
        ("run", 10 * MS, 50 * MS),
        ("ingest", 50 * MS, 60 * MS),
        ("run", 60 * MS, 100 * MS),
    ]
    ops = [
        ("refresh", 4 * MS, 8 * MS),
        ("enrich_score_best_tiles_batched.1", 12 * MS, 20 * MS),
        ("sort.6", 20 * MS, 45 * MS),
        ("fusion.2", 30 * MS, 40 * MS),  # nested inside sort's interval
        ("dynamic_update_slice", 52 * MS, 54 * MS),
        ("enrich_score_best_tiles_batched.1", 62 * MS, 70 * MS),
        ("sort.6", 70 * MS, 95 * MS),
        ("late", 100 * MS, 120 * MS),  # after the window
    ]
    return dict(devices={"/device:TPU:0": ops}, spans=spans)


def test_union_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert trace.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert trace.length([(0, 3), (5, 9)]) == 7


def test_busy_idle_and_window():
    r = trace.Reduced(synthetic())
    assert r.window_s == pytest.approx(0.100)
    # busy: 4-8, 12-45, 52-54, 62-95 -> 4 + 33 + 2 + 33 = 72 ms
    assert r.busy_s == pytest.approx(0.072)
    assert r.busy_within("run") == pytest.approx(0.066)  # 12-45, 62-95


def test_kernel_seconds_and_top_ops():
    r = trace.Reduced(synthetic())
    assert r.op_seconds(lambda n: "enrich_score" in n) == pytest.approx(0.016)
    top = r.top_ops(2)
    assert top[0][0] == "sort.6" and top[0][1] == pytest.approx(0.050)
    assert top[1][0] == "enrich_score_best_tiles_batched.1"


def test_idle_gaps_named_by_host_span():
    gaps = trace.Reduced(synthetic()).idle_gaps(3)
    # gaps: 0-4 (bookkeeping/admit), 8-12 (admit/run), 45-52 (run->ingest),
    # 54-62 (ingest/run), 95-100 (run)
    assert gaps[0] == ["ingest", pytest.approx(0.008)]  # 54-62, mid 58
    assert gaps[1] == ["run", pytest.approx(0.007)]  # 45-52, mid 48.5
    assert gaps[2][1] == pytest.approx(0.005)


def test_window_needs_a_run_span():
    with pytest.raises(ValueError):
        trace.Reduced(dict(devices={}, spans=[("admit", 0, 1)]))
