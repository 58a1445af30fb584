"""``correct`` on the CPU at a tiny size: the harness's whole run minus its
look for a chip, with the Pallas kernels in the interpreter.

A sound run is correct; the control (the plain reference one precision
below the configuration's, in the program's place) is not; nor is a run
whose timed path is broken underneath: a chunk that returns its state
unchanged, half of the executed triples left out, a tagging output altered
where it is written, an answer set altered where it is selected.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

QUERIES = {"rate_per_s": 2.0, "arity_min": 1, "arity_max": 3, "zipf_s": 1.0,
           "lifetime_mean_s": 1.5}
TINY = {
    "tweets-live": {
        "capacity": 1024, "max_tenants": 3, "plan_size": 16, "train_rows": 256,
        "combine_steps": 20, "pallas_interpret": True,
        "stream": {"initial_rows": 640, "rows_per_s": 48, "batch_rows": 16},
        "queries": QUERIES,
    },
    "multipie-backbone": {
        "objects": 1024, "backbone_size": "smoke", "max_tenants": 2, "plan_size": 16,
        "pallas_interpret": True, "trunk_sample": 4, "head_scale": [0.05, 0.05],
        "backbone": {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
                     "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
                     "rms_norm_eps": 1e-6, "rope_theta": 1000000, "torch_dtype": "bfloat16"},
        "queries": QUERIES,
    },
}
CELLS = sorted(TINY)


@pytest.fixture(scope="module")
def harness():
    """``run_cell`` with the cell built once: later runs reuse the session
    and its compiled programs."""
    from bench import common, run

    real = common.load_module
    cache = {}

    def load_module(kind, name):
        mod = real(kind, name)
        if kind != "builders":
            return mod

        def build(cfg, traffic, key_seed):
            if cfg["name"] not in cache:
                cache[cfg["name"]] = mod.build(cfg, traffic, key_seed)
            return dict(cache[cfg["name"]])

        return types.SimpleNamespace(build=build)

    common.load_module = load_module
    try:
        yield run, cache
    finally:
        common.load_module = real


def _run(harness, cell, control=False):
    run, _ = harness
    args = argparse.Namespace(workload=cell, seed=3_000_000_001, seconds=3.0, trace=0)
    return run.run_cell(args, overrides={k: dict(v) if isinstance(v, dict) else v
                                         for k, v in TINY[cell].items()}, control=control)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_the_control_is_not(harness, cell):
    from bench import check, common

    out = _run(harness, cell, control=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    _, cfg, _ = common.resolve_cell(common.load_benchmark(), cell)
    ok, _ = check.verdict(dict(out["control_numbers"], rows_wrong=0.0), cfg["limits"])
    assert not ok, out["control_numbers"]


def _broken(session, mutate):
    """``session.run`` with its result passed through ``mutate``."""
    real = session.run

    def run(state, *a, **k):
        new, hist = real(state, *a, **k)
        return mutate(session, state, new), hist

    return real, run


def _unchanged(session, pre, post):
    return pre


def _new_bits(pre, post):
    return np.asarray(post.substrate.exec_mask) & ~np.asarray(pre.substrate.exec_mask)


def _half_left_out(session, pre, post):
    import jax.numpy as jnp

    new = np.flatnonzero(_new_bits(pre, post))[::2]
    shape = post.substrate.exec_mask.shape
    idx = np.unravel_index(new, shape)
    sub = post.substrate
    sub = dataclasses.replace(
        sub,
        exec_mask=sub.exec_mask.at[idx].set(False),
        func_probs=sub.func_probs.at[idx].set(jnp.asarray(pre.substrate.func_probs)[idx]),
    )
    return session.refresh(dataclasses.replace(post, substrate=sub))


def _output_altered(session, pre, post):
    idx = np.unravel_index(np.flatnonzero(_new_bits(pre, post)), post.substrate.exec_mask.shape)
    sub = post.substrate
    sub = dataclasses.replace(sub, func_probs=sub.func_probs.at[idx].set(1.0 - sub.func_probs[idx]))
    return session.refresh(dataclasses.replace(post, substrate=sub))


def _answer_altered(session, pre, post):
    act = np.flatnonzero(np.asarray(post.active))
    if not act.size:
        return post
    der = post.derived
    flipped = der.in_answer.at[act[0], :8].set(~der.in_answer[act[0], :8])
    return dataclasses.replace(post, derived=dataclasses.replace(der, in_answer=flipped))


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out, _output_altered, _answer_altered],
                         ids=["state_unchanged", "half_left_out", "output_altered", "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(harness, cell, fault):
    from bench import common

    _, cache = harness
    name = common.resolve_cell(common.load_benchmark(), cell)[1]["name"]
    if name not in cache:
        _run(harness, cell)  # builds the cell (cached) on a sound path first
    session = cache[name]["session"]
    real, broken = _broken(session, fault)
    session.run = broken
    try:
        out = _run(harness, cell)
    finally:
        session.run = real
    assert not out["correct"], out["checks"]
