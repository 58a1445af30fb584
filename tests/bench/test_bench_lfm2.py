"""The LFM2-MoE cell at the smoke size of its architecture, on the CPU: a
``bench/run.py`` run with overrides is correct and its control is not, and
the trunk's new metric readers read synthetic traces."""

from __future__ import annotations

import argparse
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "multipie-lfm2-moe"
LFM2_SMOKE = {
    "objects": 1024, "backbone_size": "smoke", "max_tenants": 2, "plan_size": 16,
    "pallas_interpret": True, "trunk_sample": 16, "head_scale": [0.05, 0.02],
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "num_hidden_layers": 6,
    "queries": {"rate_per_s": 2.0, "arity_min": 1, "arity_max": 3, "zipf_s": 1.0,
                "lifetime_mean_s": 1.5},
}


def _overrides():
    from bench import common

    _, cfg, _ = common.resolve_cell(common.load_benchmark(), CELL)
    out = dict(LFM2_SMOKE, queries=dict(LFM2_SMOKE["queries"]))
    out["layer_types"] = cfg["layer_types"][: out["num_hidden_layers"]]
    return out


def test_smoke_run_is_correct_and_the_control_is_not():
    from bench import check, common, run

    args = argparse.Namespace(workload=CELL, seed=3_000_000_019, seconds=3.0, trace=0)
    out = run.run_cell(args, overrides=_overrides(), control=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["program_numbers"]["trunk_gap"] > 0.0  # model triples were checked
    _, cfg, _ = common.resolve_cell(common.load_benchmark(), CELL)
    ok, _ = check.verdict(out["control_numbers"], cfg["limits"])
    assert not ok, out["control_numbers"]


# ---- the trunk's metric readers, on a synthetic trace ------------------------

MS = 1_000_000  # ns

TRUNK_HLO = """HloModule jit_run_fn, is_scheduled=true

%branch_skip (arg: (f32[8])) -> (f32[8]) {
  %arg = (f32[8]{0}) parameter(0)
  ROOT %tuple.9 = (f32[8]{0}) tuple(%arg)
}

%layer_body (q: (s32[], bf16[64,64])) -> (s32[], bf16[64,64]) {
  %q = (s32[], bf16[64,64]{1,0}) parameter(0)
  %gte.5 = bf16[64,64]{1,0} get-tuple-element(%q), index=1
  %ragged-dot-none = bf16[128,32]{1,0} custom-call(%gte.5), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.5 = bf16[64,64]{1,0} fusion(%gte.5), kind=kLoop, calls=%fc, metadata={op_name="jit(run_fn)/while/body/pique/bank/cond/branch_1_fun/pique/trunk/while/body/pique/experts/mul"}
  %fusion.6 = bf16[64,64]{1,0} fusion(%gte.5), kind=kLoop, calls=%fc, metadata={op_name="checkpoint/pique/conv/add"}
  %dot.7 = bf16[64,64]{1,0} dot(%gte.5, %gte.5), metadata={op_name="jit(run_fn)/while/body/pique/bank/cond/branch_1_fun/pique/trunk/while/body/dot_general"}
  ROOT %tuple.5 = (s32[], bf16[64,64]{1,0}) tuple(%gte.5, %dot.7)
}

%fc (param_0: bf16[64,64]) -> bf16[64,64] {
  %param_0 = bf16[64,64]{1,0} parameter(0)
  ROOT %multiply.1 = bf16[64,64]{1,0} multiply(%param_0, %param_0)
}

%layer_cond (q.1: (s32[], bf16[64,64])) -> pred[] {
  %q.1 = (s32[], bf16[64,64]{1,0}) parameter(0)
  ROOT %lt.2 = pred[] constant(true)
}

%branch_trunk (arg.1: (f32[8])) -> (f32[8]) {
  %arg.1 = (f32[8]{0}) parameter(0)
  %t.1 = (s32[], bf16[64,64]{1,0}) tuple(%arg.1)
  %while.20 = (s32[], bf16[64,64]{1,0}) while(%t.1), condition=%layer_cond, body=%layer_body, metadata={op_name="jit(run_fn)/while/body/pique/bank/cond/branch_1_fun/pique/trunk/while"}
  ROOT %tuple.10 = (f32[8]{0}) tuple(%arg.1)
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.3 = f32[8]{0} get-tuple-element(%p), index=1
  %pred.1 = pred[] constant(true)
  %t = (f32[8]{0}) tuple(%gte.3)
  %cond.2 = (f32[8]{0}) conditional(%pred.1, %t, %t), branch_computations={%branch_skip, %branch_trunk}, metadata={op_name="jit(run_fn)/while/body/pique/bank/cond"}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%pred.1, %gte.3)
}

%cond_comp (p.1: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %tuple.1 = (s32[], f32[8]{0}) tuple(%Arg_0.1, %Arg_0.1)
  %while.10 = (s32[], f32[8]{0}) while(%tuple.1), condition=%cond_comp, body=%body, metadata={op_name="jit(run_fn)/while"}
  ROOT %gte.9 = f32[8]{0} get-tuple-element(%while.10), index=1
}
"""

OUTER = "%while.10 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.1), condition=%cond_comp, body=%body"
COND = "%cond.2 = (f32[8]{0}) conditional(pred[] %pred.1, (f32[8]{0}) %t, (f32[8]{0}) %t)"
LAYERS = "%while.20 = (s32[], bf16[64,64]{1,0}) while((s32[], bf16[64,64]{1,0}) %t.1), condition=%layer_cond, body=%layer_body"
RAGGED = "%ragged-dot-none = bf16[128,32]{1,0:T(8,128)(2,1)} custom-call(bf16[64,64]{1,0} %gte.5), custom_call_target=\"tpu_custom_call\""
EXPERTS = "%fusion.5 = bf16[64,64]{1,0} fusion(bf16[64,64]{1,0} %gte.5), kind=kLoop, calls=%fc"
CONV = "%fusion.6 = bf16[64,64]{1,0} fusion(bf16[64,64]{1,0} %gte.5), kind=kLoop, calls=%fc"
ATTN = "%dot.7 = bf16[64,64]{1,0} dot(bf16[64,64]{1,0} %gte.5, bf16[64,64]{1,0} %gte.5)"
SHAPE = dict(layers=2, top_k=2, experts=8, d_model=64, d_ff_expert=32, weight_bytes=2)


def _trunk_run(programs, moe_shape=SHAPE):
    """Two chunk dispatches; the trunk runs in the first: grouped matmuls
    4 ms, other expert ops 2 ms, conv 3 ms, attention 3 ms."""
    from bench import trace as trace_lib

    spans = [("run", 10 * MS, 50 * MS), ("run", 60 * MS, 100 * MS)]
    ops = [(OUTER, 10 * MS, 48 * MS), (COND, 30 * MS, 45 * MS), (LAYERS, 31 * MS, 44 * MS),
           (RAGGED, 31 * MS, 35 * MS), (EXPERTS, 35 * MS, 37 * MS), (CONV, 37 * MS, 40 * MS),
           (ATTN, 40 * MS, 43 * MS), (OUTER, 60 * MS, 98 * MS), (COND, 80 * MS, 82 * MS)]
    prog = types.SimpleNamespace(compiled_hlo=lambda: programs)
    session = types.SimpleNamespace(program=prog, max_tenants=2,
                                    config=types.SimpleNamespace(merged_capacity=None, plan_size=4))
    bundle = dict(session=session)
    if moe_shape is not None:
        bundle["moe_shape"] = moe_shape
    return types.SimpleNamespace(
        reduced=trace_lib.Reduced(dict(devices={"/device:TPU:0": ops}, spans=spans)),
        window=dict(chunks=[(0.0, 0.1, 2, True), (0.1, 0.2, 2, True)]),
        model_triples=6, bundle=bundle, cfg=dict(backbone_tokens=8),
        device_kind="TPU v5 lite")


def _read(run, name):
    from bench import common

    return common.load_module("metrics", name).read(run)


def test_trunk_readers_read_the_expert_and_conv_layers():
    from bench import common, trunk_scopes

    run = _trunk_run([("superstep", TRUNK_HLO)])
    # the trunk's branch ran once: experts 31-37 (the grouped matmul counts
    # though XLA renamed it), conv 37-40 (a rematerialised op's short name)
    assert _read(run, "moe_device_ms") == pytest.approx(6.0)
    assert _read(run, "conv_device_ms") == pytest.approx(3.0)
    sc = run.scoped
    assert trunk_scopes.busy_s(sc, ("experts", "conv", "trunk")) == pytest.approx(0.013)
    assert trunk_scopes.grouped_matmul_s(sc) == pytest.approx(0.004)
    # roofline: 2 x 4 lanes x 8 positions x top-2 rows through 2 expert layers
    rows = 2 * 4 * 8 * 2
    flops = 2.0 * rows * 3 * 64 * 32
    nbytes = 3.0 * 8 * 64 * 32 * 2 + 2.0 * rows * 64 * 2
    peak = common.peak_of("TPU v5 lite")
    t_min = max(flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])
    assert _read(run, "moe_expert_roofline") == pytest.approx(100.0 * t_min * 2 / 0.004)


@pytest.mark.parametrize("name", ["moe_device_ms", "conv_device_ms", "moe_expert_roofline"])
def test_trunk_readers_leave_out_a_trunk_without_these_layers(name):
    dense = TRUNK_HLO.replace("ragged-dot", "custom-dot").replace("pique/experts", "x") \
        .replace("pique/conv", "x")
    assert _read(_trunk_run([("superstep", dense)]), name) is None
    assert _read(_trunk_run([]), name) is None
    untraced = _trunk_run([("superstep", TRUNK_HLO)])
    untraced.reduced = None
    assert _read(untraced, name) is None
    if name == "moe_expert_roofline":
        assert _read(_trunk_run([("superstep", TRUNK_HLO)], moe_shape=None), name) is None


def test_new_cells_and_metrics_are_declared():
    import json

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    assert cells[CELL]["config"] == "multipie-lfm2-24b-a2b" and cells[CELL]["chips"] == 1
    per = {m["name"]: m for m in spec["per_layer"]}
    for name in ("moe_device_ms", "moe_expert_roofline", "conv_device_ms"):
        assert per[name]["workloads"] == [CELL] and per[name]["moves"] == "refresh_ms.static"
    assert CELL in per["enrich_mfu"]["workloads"]
