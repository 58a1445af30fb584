"""The program's names on the profiler's clock: device scopes in the compiled
superstep and refresh, host spans with their counts in a CPU trace, and the
per-level lane counter of the epoch stats."""

import dataclasses
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import conjunction, tracing
from repro.core.executor import SessionEpochStats
from repro.core.multi_query import MultiEpochStats
from repro.core.operator import EpochStats
from repro.launch.serve import (
    StreamingIngest,
    build_cascade_session_server,
    build_session_server,
    main,
)


def _scopes(text: str) -> set:
    """Every ``pique/<x>`` scope named in ``text``'s op_names."""
    return set(re.findall(r"pique/(\w+)", text))


def _first_scopes(text: str) -> set:
    """The first ``pique/<x>`` of each op_name: the scope an op counts under."""
    return set(re.findall(r'op_name="[^"]*?pique/(\w+)', text))


def _simulated(**kw):
    session, state, _, preds = build_session_server(
        num_objects=96, capacity=160, num_preds=4, max_tenants=3, plan_size=16, **kw
    )
    return session, state, preds


@pytest.fixture(scope="module")
def cascade():
    session, state, preds, _ = build_cascade_session_server(
        num_objects=32, num_preds=2, max_tenants=2, backbone_arch="qwen3-1.7b",
        plan_size=8,
    )
    state, _ = session.admit(state, conjunction(preds[0]))
    state, hist = session.run(state, 2, chunk_size=2, stop_when_exhausted=False)
    return session, state, hist


@pytest.mark.parametrize("bank", ["simulated", "cascade"])
def test_compiled_superstep_and_refresh_carry_every_scope(bank, cascade):
    if bank == "simulated":
        session, state, preds = _simulated()
        state, _ = session.admit(state, conjunction(preds[0], preds[1]))
        session.run(state, 2, chunk_size=2, stop_when_exhausted=False)
    else:
        session = cascade[0]
    traces = session.superstep_traces
    programs = dict(session.program.compiled_hlo())
    assert session.superstep_traces == traces  # served from the trace cache
    assert set(programs) == {"superstep", "refresh"}
    step = programs["superstep"]
    assert set(tracing.SUPERSTEP_SCOPES) <= _first_scopes(step)
    if bank == "cascade":
        # the trunk's ops sit under the bank: a sub-scope, never the first
        assert "pique/bank/" in step and "/pique/trunk/" in step
        assert tracing.TRUNK not in _first_scopes(step)
    # refresh: derive and select nested under the refresh scope
    assert _first_scopes(programs["refresh"]) == {tracing.REFRESH}
    assert {tracing.DERIVE, tracing.SELECT} <= _scopes(programs["refresh"])


def _trace(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*", "*.xplane.pb"))[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(tracing.SPAN_PREFIX):
                    spans.append((ev.name[len(tracing.SPAN_PREFIX):], dict(ev.stats)))
    return out, spans


def test_session_host_spans_carry_their_counts(tmp_path):
    session, state, preds = _simulated()
    pool = np.full((64, 4, 4), 0.5, np.float32)
    ing = StreamingIngest(session, batch_rows=16, num_slots=2)

    def serve():
        st, slot = session.admit(state, conjunction(preds[0], preds[1]))
        ing.attach_lockstep(st)
        ing.begin(st)
        ing.feed(pool[:48])  # three batches into two slots: one drain on pressure
        ing.begin(ing.state)
        ing.drain()
        st, hist = session.run(ing.state, 4, chunk_size=2, stop_when_exhausted=False)
        st = session.retire(st, slot)
        return hist

    hist, spans = _trace(tmp_path, serve)
    names = [n for n, _ in spans]
    for name in (tracing.ADMIT, tracing.RETIRE, tracing.SYNC, tracing.REFRESH, tracing.STAGE,
                 tracing.PUSH, tracing.DRAIN, tracing.RUN, tracing.DISPATCH, tracing.WAIT,
                 tracing.HISTORY):
        assert name in names, name
    args = {}
    for n, a in spans:
        args.setdefault(n, []).append(a)
    assert args["admit"] == [{"slot": 0}] and args["retire"] == [{"slot": 0}]
    assert sum(a["rows"] for a in args["drain"]) == 48
    assert sum(a["slots"] for a in args["drain"]) == 3
    assert [a["rows"] for a in args["stage"]] == [16, 16, 16]
    assert sum(a["blocked"] for a in args["push"]) == 1  # the third push found the ring full
    assert args["run"] == [{"epochs": 4, "traces": 1}]
    # the pressure drain leaves its refresh to the settling drain: one
    # refresh per admit, retire and settling drain
    assert [a["refreshed"] for a in args["drain"]] == [0, 1]
    assert names.count("refresh") == 2 + sum(a["refreshed"] for a in args["drain"])
    history = args["history"][0]
    lanes = {k: v for k, v in history.items() if k.startswith("lanes_")}
    assert sorted(lanes) == [f"lanes_{i}" for i in range(session.num_functions)]
    assert sum(lanes.values()) == sum(h.merged_valid for h in hist)
    assert set(history) - set(lanes) == {"expert_load"} and history["expert_load"] == 0


@pytest.fixture(scope="module")
def lfm2():
    """A session served through ``build_cascade_session_server`` whose model
    level is the LFM2-MoE smoke trunk (conv, attention and expert layers)."""
    session, state, preds, _ = build_cascade_session_server(
        num_objects=32, num_preds=2, max_tenants=2, backbone_arch="lfm2-24b-a2b",
        plan_size=8,
    )
    return session, state, preds


def test_trunk_branch_holds_the_expert_and_conv_scopes(lfm2):
    session, state, preds = lfm2
    st, _ = session.admit(state, conjunction(preds[0]))
    session.run(st, 2, chunk_size=2, stop_when_exhausted=False)
    step = dict(session.program.compiled_hlo())["superstep"]
    names = re.findall(r'op_name="([^"]*)"', step)
    branch = "pique/bank/cond/branch_1_fun/pique/trunk/"
    for sub in (tracing.EXPERTS, tracing.CONV):
        inside = [n for n in names if branch in n and f"pique/{sub}/" in n]
        assert inside, sub
        # nested inside the trunk, inside the bank: never an op's first scope
        # where the whole path is named
        assert all(n.index("pique/bank/") < n.index(f"pique/{sub}/") for n in inside)
    assert tracing.TRUNK not in _first_scopes(step)


def test_expert_load_is_summed_on_the_history_span(lfm2, tmp_path):
    session, state, preds = lfm2

    def serve():
        st, _ = session.admit(state, conjunction(preds[0]))
        return session.run(st, 16, chunk_size=2, stop_when_exhausted=False)[1]

    hist, spans = _trace(tmp_path, serve)
    history = [a for n, a in spans if n == tracing.HISTORY]
    assert len(history) == 1
    trunk = [h for h in hist if h.level_lanes[2] > 0]
    assert trunk, "the planner bought no model-level triple"
    # the busiest of 8 experts holds at least its top-2 share, at most every token
    assert all(1.0 <= h.expert_load <= 4.0 for h in trunk)
    assert all(h.expert_load == 0.0 for h in hist if h.level_lanes[2] == 0)
    assert history[0]["expert_load"] == pytest.approx(sum(h.expert_load for h in hist))


def test_level_lanes_sum_to_merged_valid_and_count_the_executed_bits(cascade):
    session, state, preds = _simulated()
    state, _ = session.admit(state, conjunction(preds[0], preds[1]))
    before = np.asarray(state.substrate.exec_mask).sum(axis=(0, 1))
    state, hist = session.run(state, 6, chunk_size=2, stop_when_exhausted=False)
    after = np.asarray(state.substrate.exec_mask).sum(axis=(0, 1))
    for h in hist:
        assert len(h.level_lanes) == session.num_functions
        assert sum(h.level_lanes) == h.merged_valid
    np.testing.assert_array_equal(np.sum([h.level_lanes for h in hist], axis=0), after - before)
    for h in cascade[2]:  # the cascade bank: levels are the cascade's
        assert sum(h.level_lanes) == h.merged_valid


def test_stats_carry_no_wall_clock():
    for cls in (SessionEpochStats, MultiEpochStats, EpochStats):
        assert "wall_time_s" not in {f.name for f in dataclasses.fields(cls)}


def test_serve_profile_dir_writes_a_trace_with_the_program_spans(tmp_path):
    rc = main(["--session", "--objects", "96", "--preds", "2", "--epochs", "4",
               "--backbone", "", "--profile-dir", str(tmp_path)])
    assert rc == 0
    paths = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*", "*.xplane.pb"))
    assert len(paths) == 1
    names = {ev.name for plane in ProfileData.from_file(paths[0]).planes
             for ln in plane.lines for ev in ln.events}
    assert {"pique.admit", "pique.run", "pique.refresh"} <= names
