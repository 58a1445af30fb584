"""Pieces every part of the benchmark shares: where its files are, seeds,
percentiles, the compile clock, the device record.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
workload, its configuration file and its traffic mix; the configuration names
its builder (``bench/builders/<builder>.py``); each metric is
``bench/metrics/<name>.py``.  A later cell or metric is new files and new
entries, never an edit here.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve_cell(spec: dict, workload: str, root: Path = ROOT):
    """-> (workload entry, configuration dict, traffic dict) for one cell."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, cfg, traffic


def cell_metrics(spec: dict, workload: str, group: str) -> list:
    """The metrics of ``group`` ("end_to_end" | "per_layer") this cell reports:
    those listing it, and those with no ``workloads`` key."""
    return [
        m for m in spec[group]
        if "workloads" not in m or workload in m["workloads"]
    ]


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold '-' and '.')."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seeds(seed: int) -> tuple[int, np.random.Generator]:
    """A run's ``--seed`` (any non-negative int, beyond 32 bits too) -> a
    31-bit JAX key seed and a numpy generator, both fixed by the seed."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    ss = np.random.SeedSequence(seed)
    key_seed = int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)
    return key_seed, np.random.default_rng(ss.spawn(1)[0])


# ---- percentiles (copied from the arithmetic of benchmarks/common.py) ------


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) with linear interpolation between order
    statistics (numpy's default method)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(v, q))


# ---- compile clock (copied from chip_smoke.CompileClock) --------------------


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit's retrieval counts as its compile)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


def device_record() -> dict:
    import jax

    devs = jax.devices()
    return dict(platform=devs[0].platform, kind=devs[0].device_kind, count=len(devs))


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


def peak_of(kind: str) -> dict:
    """The chip's published peaks, keyed by JAX's ``device_kind``; a device
    missing from ``bench/peaks.json`` is an error, never a default."""
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return peaks[kind]
