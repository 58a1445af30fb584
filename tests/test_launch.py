"""Launch-layer tests: mesh construction, sharding rules, small-mesh AOT
lowering of every step kind (the 512-device run lives in launch/dryrun.py),
end-to-end smoke training, and the progressive serve driver."""

import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import ARCHS, get_config
from repro.configs.shapes import SHAPES, ShapeSpec, all_cells, shape_applicable
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.launch.rules import rules_for_cell


def test_all_cells_inventory():
    cells = all_cells()
    assert len(cells) == 44
    runnable = [c for c in cells if c["runnable"]]
    skipped = [c for c in cells if not c["runnable"]]
    assert len(runnable) == 37
    assert len(skipped) == 7
    assert all(c["shape"] == "long_500k" for c in skipped)


def test_long500k_applicability_matches_design():
    runs = {"gemma2-9b", "h2o-danube-1.8b", "hymba-1.5b", "mamba2-370m"}
    for arch in ARCHS:
        cfg = get_config(arch)
        ok, _ = shape_applicable(cfg, "long_500k")
        assert ok == (arch in runs), arch


def test_rules_divisibility_fallbacks():
    mesh = make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    # arctic: 56 heads not divisible by 16 -> shard head_dim instead
    r = rules_for_cell(get_config("arctic-480b"), FakeMesh(), "train", 256)
    assert r.rules["heads"] is None and r.rules["head_dim"] == "model"
    # seamless: vocab 256206 not divisible -> unsharded vocab
    r = rules_for_cell(get_config("seamless-m4t-large-v2"), FakeMesh(), "train", 256)
    assert r.rules["vocab"] is None
    # arctic experts 128 divisible by data 16 -> expert parallel
    r = rules_for_cell(get_config("arctic-480b"), FakeMesh(), "train", 256)
    assert r.rules["experts"] == "data"
    # grok experts 8 not divisible -> replicated expert dim
    r = rules_for_cell(get_config("grok-1-314b"), FakeMesh(), "train", 256)
    assert r.rules["experts"] is None
    # decode with batch 1: kv_seq spreads over everything
    r = rules_for_cell(get_config("gemma2-9b"), FakeMesh(), "decode", 1)
    assert r.rules["batch"] is None
    assert "model" in tuple(r.rules["kv_seq"])


def test_spec_never_reuses_mesh_axis():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    r = rules_for_cell(get_config("gemma2-9b"), FakeMesh(), "decode", 128)
    spec = r.spec(("layers", "batch", "kv_seq", "kv_heads", "head_dim"))
    used = []
    for entry in spec:
        if entry is None:
            continue
        entries = entry if isinstance(entry, tuple) else (entry,)
        used.extend(entries)
    assert len(used) == len(set(used))


@pytest.mark.parametrize("shape_name", ["train_4k", "prefill_32k", "decode_32k"])
@pytest.mark.slow
def test_small_mesh_lower_compile(shape_name):
    """Every step kind lowers+compiles on an 8-device mesh in a subprocess
    (keeps this process single-device).  The MoE archs cover experts
    replicated (grok: 8 experts) and expert-parallel (arctic, LFM2: experts
    over "data"); in their train and prefill steps each data shard
    dispatches its own tokens, so no collective carries the call's
    (token, expert) pairs."""
    code = textwrap.dedent(f"""
        import os, re
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, dataclasses
        from repro.configs.archs import get_config
        from repro.configs.shapes import SHAPES
        from repro.launch.steps import build_step
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        spec = dataclasses.replace(
            SHAPES["{shape_name}"],
            seq_len=128 if "{shape_name}" != "train_4k" else 64,
            global_batch=8,
        )
        for arch in ("qwen3-1.7b", "grok-1-314b", "arctic-480b", "lfm2-24b-a2b",
                     "mamba2-370m", "seamless-m4t-large-v2", "hymba-1.5b"):
            cfg = get_config(arch, smoke=True)
            built = build_step(cfg, spec, mesh)
            text = built.fn.lower(*built.args).compile().as_text()
            if cfg.moe is not None and spec.kind != "decode":
                pairs = spec.global_batch * spec.seq_len * cfg.moe.top_k
                moved = re.findall(
                    rf"= ([^ ]*[[,]{{pairs}}[],][^ ]*) "
                    "(?:all-gather|all-reduce|all-to-all|collective-permute)", text)
                assert not moved, (arch, moved)
            print(arch, "OK")
        print("ALL_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=str(Path.cwd() / "src")),
        timeout=900,
    )
    assert "ALL_OK" in out.stdout, out.stderr[-3000:]


@pytest.mark.slow
def test_train_loop_descends_and_checkpoints(tmp_path):
    from repro.launch.train import train_loop

    cfg = get_config("qwen3-1.7b", smoke=True)
    shape = ShapeSpec("t", "train", 32, 4)
    mesh = make_host_mesh()
    with mesh:
        params, opt_state, hist = train_loop(
            cfg, shape, mesh, steps=12, ckpt_dir=str(tmp_path), ckpt_every=5,
            log_every=100,
        )
    assert hist[-1]["loss"] < hist[0]["loss"]
    from repro.checkpoint.store import latest_step

    assert latest_step(tmp_path) == 10
    # resume continues from the checkpoint
    with mesh:
        _, _, hist2 = train_loop(
            cfg, shape, mesh, steps=14, ckpt_dir=str(tmp_path), log_every=100,
        )
    assert hist2[0]["step"] == 10


@pytest.mark.slow
def test_serve_driver_end_to_end():
    from repro.launch.serve import build_server, serve_query

    op, corpus, truth, qualities = build_server(
        num_objects=192, num_preds=1, backbone_arch="qwen3-1.7b", seed=0
    )
    # cascade quality must increase with level cost (Table-1 property)
    q = qualities[0]
    assert q[-1] > 0.6
    report = serve_query(op, 192, epochs=25)
    assert report.epochs > 0
    assert report.expected_f > 0
    assert report.true_f1 is not None and report.true_f1 > 0.2


@pytest.mark.slow
def test_serve_early_termination_budget():
    from repro.launch.serve import build_server, serve_query

    op, *_ = build_server(num_objects=128, num_preds=1,
                          backbone_arch=None, seed=1)
    full = serve_query(op, 128, epochs=40)
    early = serve_query(op, 128, epochs=40,
                        target_expected_f=full.expected_f * 0.6)
    assert early.cost_spent <= full.cost_spent
    assert early.epochs <= full.epochs
