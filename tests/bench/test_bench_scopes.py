"""Device ops put down to the program's scopes, on synthetic traces and HLO
text whose answers are counted by hand, and on HLO that XLA compiled."""

from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common, scopes  # noqa: E402
from bench import trace as trace_lib  # noqa: E402

MS = 1_000_000  # ns

SUPERSTEP_HLO = """HloModule jit_run_fn, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(run_fn)/while/body/pique/score/mul"}
}

%cmp (a: f32[], b: f32[]) -> pred[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %lt.1 = pred[] compare(%a, %b), direction=LT, metadata={op_name="jit(run_fn)/while/body/pique/select/sort"}
}

%branch_skip (arg: (f32[8])) -> (f32[8]) {
  %arg = (f32[8]{0}) parameter(0)
  ROOT %tuple.9 = (f32[8]{0}) tuple(%arg)
}

%branch_trunk (arg.1: (f32[8])) -> (f32[8]) {
  %arg.1 = (f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg.1), index=0
  %dot.9 = f32[8]{0} dot(%gte.1, %gte.1), metadata={op_name="jit(run_fn)/while/body/pique/bank/cond/branch_1_fun/pique/trunk/dot_general"}
  ROOT %tuple.10 = (f32[8]{0}) tuple(%dot.9)
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.3 = f32[8]{0} get-tuple-element(%p), index=1
  %fusion.147 = f32[8]{0:T(256)} fusion(%gte.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(run_fn)/while/body/closed_call/pique/score/mul"}
  %sort.6 = f32[16,8]{1,0} sort(%fusion.147), dimensions={1}, to_apply=%cmp, metadata={op_name="jit(run_fn)/while/body/closed_call/pique/select/sort"}
  %pred.1 = pred[] constant(true)
  %t = (f32[8]{0}) tuple(%gte.3)
  %cond.2 = (f32[8]{0}) conditional(%pred.1, %t, %t), branch_computations={%branch_skip, %branch_trunk}, metadata={op_name="jit(run_fn)/while/body/closed_call/pique/bank/cond"}
  %copy.240 = f32[8]{0} copy(%gte.3)
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%pred.1, %copy.240)
}

%cond_comp (p.1: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(true), metadata={op_name="jit(run_fn)/while/cond/lt"}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %tuple.1 = (s32[], f32[8]{0}) tuple(%Arg_0.1, %Arg_0.1)
  %while.10 = (s32[], f32[8]{0}) while(%tuple.1), condition=%cond_comp, body=%body, metadata={op_name="jit(run_fn)/while"}
  ROOT %gte.9 = f32[8]{0} get-tuple-element(%while.10), index=1
}
"""

REFRESH_HLO = """HloModule jit__refresh, is_scheduled=true

%fc (param_0.1: f32[16,8]) -> f32[16,8] {
  %param_0.1 = f32[16,8]{1,0} parameter(0)
  ROOT %neg = f32[16,8]{1,0} negate(%param_0.1), metadata={op_name="jit(_refresh)/pique/refresh/pique/derive/neg"}
}

ENTRY %main.2 (p.2: f32[16,8]) -> f32[16,8] {
  %p.2 = f32[16,8]{1,0} parameter(0)
  %fusion.3 = f32[16,8]{1,0} fusion(%p.2), kind=kLoop, calls=%fc, metadata={op_name="jit(_refresh)/pique/refresh/pique/derive/neg"}
  ROOT %sort.6 = f32[16,8]{1,0} sort(%fusion.3), dimensions={1}, to_apply=%fc, metadata={op_name="jit(_refresh)/pique/refresh/pique/select/sort"}
}
"""

PROGRAMS = [("superstep", SUPERSTEP_HLO), ("refresh", REFRESH_HLO)]

# device op names as the TPU trace gives them: the instruction with its
# operands' shapes, no metadata
WHILE = "%while.10 = (s32[], f32[8]{0:T(256)}) while((s32[], f32[8]{0}) %tuple.1), condition=%cond_comp, body=%body"
SCORE = "%fusion.147 = f32[8]{0:T(256)} fusion(f32[8]{0:T(256)} %gte.3), kind=kLoop, calls=%fused_computation"
SORT = "%sort.6 = f32[16,8]{1,0:T(8,128)} sort(f32[16,8]{1,0} %fusion.147), dimensions={1}"
COND = "%cond.2 = (f32[8]{0}) conditional(pred[] %pred.1, (f32[8]{0}) %t, (f32[8]{0}) %t)"
TRUNK = "%dot.9 = f32[8]{0} dot(f32[8]{0} %gte.1, f32[8]{0} %gte.1)"
COPY = "%copy.240 = f32[8]{0} copy(f32[8]{0} %gte.3)"
DERIVE = "%fusion.3 = f32[16,8]{1,0} fusion(f32[16,8]{1,0} %p.2), kind=kLoop, calls=%fc"
EAGER = "%dynamic-update-slice.1 = pred[16]{0} dynamic-update-slice(pred[16]{0} %p, pred[1]{0} %u, s32[] %i)"


def synthetic():
    """One admit (a refresh), two chunk dispatches (the trunk runs in the
    first), one ingest (a ring write and a refresh)."""
    spans = [
        ("admit", 0 * MS, 10 * MS),
        ("run", 10 * MS, 50 * MS),
        ("ingest", 50 * MS, 60 * MS),
        ("run", 60 * MS, 100 * MS),
    ]
    ops = [
        (EAGER, 1 * MS, 2 * MS),
        (DERIVE, 2 * MS, 4 * MS),
        (SORT, 4 * MS, 8 * MS),  # the refresh's sort
        (WHILE, 10 * MS, 48 * MS),
        (SCORE, 11 * MS, 20 * MS),
        (SORT, 20 * MS, 30 * MS),  # the superstep's sort: same instruction text
        (COND, 30 * MS, 45 * MS),
        (TRUNK, 31 * MS, 44 * MS),
        (COPY, 45 * MS, 47 * MS),
        (EAGER, 50 * MS, 51 * MS),
        (DERIVE, 52 * MS, 53 * MS),
        (SORT, 53 * MS, 56 * MS),
        (WHILE, 60 * MS, 98 * MS),
        (SCORE, 61 * MS, 70 * MS),
        (SORT, 70 * MS, 80 * MS),
        (COND, 80 * MS, 82 * MS),
        (COPY, 90 * MS, 95 * MS),
    ]
    return dict(devices={"/device:TPU:0": ops}, spans=spans)


def _scoped():
    return scopes.Scoped(trace_lib.Reduced(synthetic()), PROGRAMS)


def test_op_key_strips_layouts_and_operands():
    assert scopes.op_key(SCORE) == ("fusion.147", "f32[8]", "fusion")
    assert scopes.op_key(WHILE) == ("while.10", "(s32[], f32[8])", "while")
    assert scopes.op_key("  ROOT %sort.6 = f32[16,8]{1,0} sort(%x), dimensions={1}") == (
        "sort.6", "f32[16,8]", "sort")
    assert scopes.op_key("enrich_score_best_tiles_batched.1") is None
    assert scopes.first_scope("jit(f)/while/body/pique/refresh/pique/derive/mul") == "refresh"
    assert scopes.first_scope("jit(f)/while") is None


def test_module_keeps_device_ops_and_finds_branches():
    m = scopes.Module("superstep", SUPERSTEP_HLO)
    assert m.entry == "main.9"
    assert m.branches == {"branch_skip", "branch_trunk"}
    names = {k[0] for k in m.instrs}
    assert {"while.10", "fusion.147", "sort.6", "cond.2", "dot.9", "copy.240", "lt"} <= names
    # instructions inside fused computations and comparators run as no op
    assert "multiply.1" not in names and "lt.1" not in names
    assert m.instrs[scopes.op_key(TRUNK)][1] == "branch_trunk"
    assert m.lookup(TRUNK) == ("dot.9", "f32[8]", "dot")
    assert m.lookup(EAGER) is None and m.lookup("dot.9") is None


def test_scope_attribution_counts_nested_ops_once_and_unscoped_nowhere():
    sc = _scoped()
    # score: 11-20, 61-70
    assert sc.scope_busy_s("score") == pytest.approx(0.018)
    # select (the superstep's sort, inside run spans): 20-30, 70-80
    assert sc.scope_busy_s("select") == pytest.approx(0.020)
    # bank: the conditional 30-45 holds the trunk 31-44 (counted once), 80-82
    assert sc.scope_busy_s("bank") == pytest.approx(0.017)
    # derive and select inside the refresh program count under refresh:
    # 2-4, 4-8, 52-53, 53-56
    assert sc.scope_busy_s("refresh") == pytest.approx(0.010)
    assert sc.scope_busy_s("derive") == 0.0
    assert sc.scope_busy_s("trunk") == 0.0  # a sub-scope of bank
    assert sc.scope_busy_s("score", "select", "bank") == pytest.approx(0.055)
    by_name = {n: scope for n, _, _, _, _, scope in sc.ops["/device:TPU:0"]}
    # the container, the copy XLA inserted and the eager update: no scope
    assert by_name[WHILE] is None and by_name[COPY] is None and by_name[EAGER] is None


def test_executions_of_the_refresh_program_and_the_trunk_branch():
    sc = _scoped()
    assert sc.program_runs("refresh") == 2
    assert sc.branch_runs("trunk") == 1
    assert sc.program_runs("superstep") == 2  # the while, once a chunk


def test_without_scopes_nothing_is_attributed():
    r = trace_lib.Reduced(synthetic())
    for programs in ([], [("superstep", SUPERSTEP_HLO.replace("pique/", "x/"))]):
        sc = scopes.Scoped(r, programs)
        assert not sc.any_scoped
        assert sc.scope_busy_s("score") == 0.0


def test_op_labels_carry_the_scope():
    top = _scoped().top_ops(3)
    assert top[0][0] == WHILE[: trace_lib.NAME_CHARS]  # unscoped: the old label
    assert top[0][1] == pytest.approx(0.076)
    assert top[1][0] == f"select | {SORT}"[: trace_lib.NAME_CHARS]
    assert top[1][1] == pytest.approx(0.020)
    assert top[2][0] == f"score | {SCORE}"[: trace_lib.NAME_CHARS]


PROGRAM_SPANS = [
    ("pique.admit", 0 * MS, 9 * MS, {"slot": 0}),
    ("pique.refresh", 1 * MS, 2 * MS, {}),
    ("pique.drain", 52 * MS, 59 * MS, {"slots": 2, "rows": 1024}),
    ("pique.refresh", 52 * MS, 54 * MS, {}),
    ("pique.run", 60 * MS, 99 * MS, {"epochs": 2, "traces": 1}),
    ("pique.wait", 62 * MS, 99 * MS, {}),
]


@pytest.mark.parametrize("with_spans", [False, True], ids=["bench_only", "program_spans"])
def test_gap_labels(with_spans):
    r = trace_lib.Reduced(synthetic())
    gaps = scopes.gap_labels(r, PROGRAM_SPANS if with_spans else [], 3)
    # device idle: 0-1, 8-10, 48-50, 56-60, 98-100
    assert gaps[0][1] == pytest.approx(0.004)  # 56-60, mid 58
    assert gaps[0][0] == ("ingest/pique.drain" if with_spans else "ingest")
    assert gaps[1][1] == pytest.approx(0.002)
    # where no program span covers the midpoint, the benchmark's label alone
    assert [g[0] for g in gaps] == (
        ["ingest/pique.drain", "admit", "run"] if with_spans else ["ingest", "admit", "run"])
    # the old labels, where there are no program spans
    assert [g[0] for g in scopes.gap_labels(r, [], 3)] == [g[0] for g in r.idle_gaps(3)]


def test_program_spans_are_read_with_their_arguments(tmp_path):
    import jax

    from repro.core import tracing

    jax.profiler.start_trace(str(tmp_path))
    with tracing.span(tracing.DRAIN, slots=3, rows=1536):
        with tracing.span(tracing.REFRESH):
            pass
    with tracing.span(tracing.RUN) as span:
        span.set_metadata(epochs=2, traces=1)
    jax.profiler.stop_trace()
    spans = scopes.read_program_spans(trace_lib.newest_xplane(str(tmp_path)))
    got = [(n, a) for n, _, _, a in spans]
    assert got == [("pique.drain", {"slots": 3, "rows": 1536}), ("pique.refresh", {}),
                   ("pique.run", {"epochs": 2, "traces": 1})]
    assert all(s <= e for _, s, e, _ in spans)


# ---- the metric readers ---------------------------------------------------

NEW = ["score_device_ms", "plan_device_ms", "derive_device_ms", "refresh_device_ms"]
OLD = ["device_idle_share.stream", "epoch_device_ms.stream", "enrich_score_roofline.stream"]


def _run(programs=PROGRAMS, model_triples=12):
    """What a traced benchmark run hands its readers, over the synthetic
    trace: two chunks of two epochs, a session whose program names its
    compiled programs (or, with ``programs=None``, a program that cannot)."""
    prog = types.SimpleNamespace()
    if programs is not None:
        prog.compiled_hlo = lambda: programs
    session = types.SimpleNamespace(
        program=prog, max_tenants=2,
        config=types.SimpleNamespace(merged_capacity=None, plan_size=4))
    return types.SimpleNamespace(
        reduced=trace_lib.Reduced(synthetic()),
        window=dict(chunks=[(0.0, 0.1, 2, True), (0.1, 0.2, 2, True)]),
        model_triples=model_triples,
        bundle=dict(session=session),
        cfg=dict(capacity=16, predicates=1, functions=1, max_tenants=2),
        capacity=16, store_bytes=2, device_kind="TPU v5 lite",
    )


def _read(run, name):
    return common.load_module("metrics", name).read(run)


@pytest.mark.parametrize("family", ["stream", "static"])
def test_new_readers_on_the_synthetic_trace(family):
    run = _run()
    got = {n: _read(run, f"{n}.{family}") for n in NEW}
    assert got["score_device_ms"] == pytest.approx(18.0 / 4)  # score, candidates
    assert got["plan_device_ms"] is None  # no topk or merge op in the trace
    assert got["derive_device_ms"] == pytest.approx(20.0 / 4)  # apply, derive, select
    assert got["refresh_device_ms"] == pytest.approx(10.0 / 2)  # two refreshes


def test_trunk_lane_yield_counts_lanes_over_trunk_runs():
    # 6 model triples bought; the trunk ran once over 2 x 4 merged lanes
    assert _read(_run(model_triples=6), "trunk_lane_yield") == pytest.approx(75.0)
    assert _read(_run(model_triples=0), "trunk_lane_yield") is None


@pytest.mark.parametrize("name", [f"{n}.{f}" for n in NEW for f in ("stream", "static")]
                         + ["trunk_lane_yield"])
def test_new_readers_leave_out_what_a_program_without_scopes_cannot_give(name):
    assert _read(_run(programs=None), name) is None  # no compiled_hlo
    assert _read(_run(programs=[]), name) is None  # nothing scoped
    untraced = _run()
    untraced.reduced = None
    assert _read(untraced, name) is None


@pytest.mark.parametrize("name", OLD)
def test_existing_readers_read_the_same_beside_the_new_ones(name):
    plain = _run()
    before = _read(plain, name)
    both = _run()
    for n in NEW:
        _read(both, f"{n}.stream")
    assert both.scoped is not None
    assert _read(both, name) == before
    r0, r1 = plain.reduced, both.reduced
    assert (r1.busy_s, r1.window_s, r1.busy_within("run")) == (
        r0.busy_s, r0.window_s, r0.busy_within("run"))
    assert r1.op_seconds(lambda n: "sort" in n) == r0.op_seconds(lambda n: "sort" in n)


def test_new_metrics_are_declared_with_their_cells():
    from repro.core import tracing

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    per = {m["name"]: m for m in spec["per_layer"]}
    cells = {"stream": ["tweets-live"], "static": ["multipie-backbone"]}
    for n in NEW:
        for fam, wl in cells.items():
            m = per[f"{n}.{fam}"]
            assert m["workloads"] == wl and m["source"] == "device_trace"
            assert (ROOT / "bench" / "metrics" / f"{n}.{fam}.py").is_file()
    assert per["trunk_lane_yield"]["workloads"] == ["multipie-backbone"]
    # the scopes the readers name are the program's own
    for name in ("score", "candidates", "topk", "merge", "apply", "derive", "select"):
        assert name in tracing.SUPERSTEP_SCOPES
    assert tracing.REFRESH == "refresh" and tracing.TRUNK == "trunk"
    assert (scopes.SCOPE_PREFIX, scopes.SPAN_PREFIX) == (tracing.SCOPE_PREFIX, tracing.SPAN_PREFIX)


# ---- on HLO that XLA compiled ------------------------------------------------


def test_compiled_hlo_resolves_trace_names():
    """A scan whose body holds scoped ops and a conditional branch: every
    device-op instruction of the compiled text, named as a trace names it,
    resolves to the scope its op_name starts with."""
    import jax
    import jax.numpy as jnp

    from repro.core import tracing

    def body(c, _):
        with tracing.scope(tracing.SCORE):
            y = jnp.sort(c * 2.0, axis=-1)
        with tracing.scope(tracing.BANK):
            z = jax.lax.cond(
                y.sum() > 0, tracing.scope(tracing.TRUNK)(lambda v: jnp.tanh(v) @ v.T @ v),
                lambda v: v, y)
        return c + z, None

    text = jax.jit(lambda x: jax.lax.scan(body, x, None, length=3)[0]).lower(
        jnp.ones((4, 8))).compile().as_text()
    m = scopes.Module("superstep", text)
    assert m.branches
    scoped = {k: scopes.first_scope(op) for k, (op, _) in m.instrs.items()}
    assert {"score", "bank"} <= set(scoped.values())
    assert any(c in m.branches and "pique/trunk" in op for op, c in m.instrs.values())
    for key in scoped:
        name, shape, opcode = key
        assert m.lookup(f"%{name} = {shape} {opcode}(f32[4,8]{{1,0}} %x)") == key
