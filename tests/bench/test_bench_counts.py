"""Operation and byte counts of the yardstick, against hand counts."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import common, counts  # noqa: E402


def test_score_work_hand_count():
    # 2 slots x 3 rows x 4 predicates, 4 functions, bf16 storage
    ops, nbytes = counts.score_work(2, 3, 4, 4, 2)
    lanes = 2 * 3 * 4
    assert ops == lanes * (4 + 20 * 4)
    # shared: 12 lanes x (2 + 2 + 4) bytes; joint: 2 x 3 x 2; out: 3 x 24 x 4
    assert nbytes == 12 * 8 + 12 + 288


def test_roofline_picks_the_binding_bound():
    peak = {"flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_seconds(1000.0, 10.0, peak) == (10.0, "compute")
    assert counts.roofline_seconds(10.0, 1000.0, peak) == (100.0, "memory")


def test_qwen3_1_7b_active_params_from_published_widths():
    # per layer: q 2048x2048, k and v 2048x1024 each, o 2048x2048,
    # MLP 3 x 2048 x 6144
    per_layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 6144
    n = counts.transformer_active_params(28, 2048, 16, 8, 128, 6144)
    assert n == 28 * per_layer == 1_409_286_144
    assert counts.backbone_flops(10, 8, n) == 2.0 * 10 * 8 * n


def test_peaks_table_names_its_source_and_refuses_unknown_devices():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    v5e = common.peak_of("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert all("source" in p for p in peaks.values())
    with pytest.raises(KeyError):
        common.peak_of("cpu")
