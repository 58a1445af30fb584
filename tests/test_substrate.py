"""Substrate tests: checkpointing (incl. elastic restore), fault tolerance,
gradient compression, data pipeline, optimizers."""

import dataclasses
import os
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import (
    latest_step,
    prune_old,
    restore_checkpoint,
    save_checkpoint,
)
from repro.data.pipeline import (
    PrefetchIterator,
    SyntheticTokenStream,
    TokenStreamConfig,
    shard_object_ranges,
)
from repro.optim.adamw import AdamW, clip_by_global_norm, cosine_schedule, global_norm
from repro.optim.adafactor import Adafactor
from repro.optim.compress import (
    init_error_feedback,
    int8_compress,
    topk_compress,
)
from repro.runtime.fault_tolerance import (
    ElasticPolicy,
    Heartbeat,
    PreemptionHandler,
    StragglerMonitor,
)


# ------------------------------------------------------------- checkpoint ---

def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "layers": (jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32),),
        "embed": jnp.asarray(rng.normal(size=(32, 16)), jnp.bfloat16),
        "step": jnp.asarray(7, jnp.int32),
    }


def test_checkpoint_roundtrip(tmp_path):
    tree = _tree()
    save_checkpoint(tmp_path, 10, tree)
    restored, step = restore_checkpoint(tmp_path, None, tree)
    assert step == 10
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_latest_and_prune(tmp_path):
    tree = _tree()
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(tmp_path, s, tree)
    assert latest_step(tmp_path) == 5
    prune_old(tmp_path, keep=2)
    assert latest_step(tmp_path) == 5
    kept = sorted(p.name for p in Path(tmp_path).iterdir())
    assert len(kept) == 2


def test_checkpoint_atomicity(tmp_path):
    tree = _tree()
    save_checkpoint(tmp_path, 1, tree)
    # a stale tmp dir from a crashed save must not be visible
    (Path(tmp_path) / "step_00000099.tmp").mkdir()
    assert latest_step(tmp_path) == 1


def test_elastic_restore_different_mesh(tmp_path):
    """Save on a (4,)-device mesh, restore on (2,) — subprocess with 8 fake
    devices so the main test process keeps 1 CPU device."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.store import save_checkpoint, restore_checkpoint

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        from repro.launch.mesh import make_mesh
        mesh4 = make_mesh((4,), ("data",))
        sh4 = {"w": NamedSharding(mesh4, P("data"))}
        placed = jax.device_put(tree["w"], sh4["w"])
        save_checkpoint("CKPT", 3, {"w": placed})

        mesh2 = make_mesh((2,), ("data",))
        sh2 = {"w": NamedSharding(mesh2, P("data"))}
        restored, step = restore_checkpoint("CKPT", None, tree, sh2)
        assert step == 3
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
        assert restored["w"].sharding.mesh.shape["data"] == 2
        print("ELASTIC_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=tmp_path, env=dict(env, PYTHONPATH=str(Path.cwd() / "src")),
    )
    assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]


# -------------------------------------------------------- fault tolerance ---

def test_preemption_handler_cooperative():
    h = PreemptionHandler()
    assert not h.should_stop
    h.request()
    assert h.should_stop


def test_heartbeat_failure_detection():
    t = [0.0]
    hb = Heartbeat(num_workers=3, timeout_s=10.0, clock=lambda: t[0])
    t[0] = 5.0
    hb.beat(0)
    hb.beat(1)
    t[0] = 12.0
    assert hb.failed_workers() == [2]
    assert not hb.healthy()


def test_straggler_monitor_rebalances():
    m = StragglerMonitor(num_shards=4)
    for _ in range(8):
        for s, dt in enumerate((1.0, 1.0, 1.0, 3.0)):
            m.record(s, dt)
    assert m.stragglers(factor=1.5) == [3]
    ranges = m.rebalance_objects(1000)
    sizes = [e - s for s, e in ranges]
    assert sum(sizes) == 1000
    assert sizes[3] < sizes[0]  # slow shard gets fewer objects


def test_elastic_policy_shrinks_data_axis():
    p = ElasticPolicy(data_axis=16, model_axis=16)
    assert p.shrink_for_failures(512) == (16, 16)
    assert p.shrink_for_failures(300) == (16, 16)
    assert p.shrink_for_failures(255) == (8, 16)
    assert p.shrink_for_failures(129) == (8, 16)
    with pytest.raises(RuntimeError):
        p.shrink_for_failures(10)


# ----------------------------------------------------------- compression ----

def test_topk_compress_error_feedback():
    rng = np.random.default_rng(0)
    grads = {"a": jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)}
    state = init_error_feedback(grads)
    comp, state = topk_compress(grads, state, fraction=0.1)
    # sparsity
    nz = float(jnp.mean((comp["a"] != 0).astype(jnp.float32)))
    assert nz <= 0.11
    # compressed + error == original (nothing lost)
    recon = comp["a"] + state.error["a"]
    np.testing.assert_allclose(np.asarray(recon), np.asarray(grads["a"]), rtol=1e-6)


def test_topk_error_reinjected_next_round():
    grads = {"a": jnp.asarray([1.0, 0.01, 0.0, 0.0])}
    state = init_error_feedback(grads)
    comp1, state = topk_compress(grads, state, fraction=0.25)
    assert float(comp1["a"][0]) == 1.0 and float(comp1["a"][1]) == 0.0
    # zero new gradient: the residual 0.01 must surface now
    zeros = {"a": jnp.zeros(4)}
    comp2, state = topk_compress(zeros, state, fraction=0.25)
    assert float(comp2["a"][1]) == pytest.approx(0.01)


def test_int8_compress_bounded_error():
    rng = np.random.default_rng(1)
    grads = {"a": jnp.asarray(rng.normal(size=(128,)), jnp.float32)}
    state = init_error_feedback(grads)
    comp, state = int8_compress(grads, state, jax.random.PRNGKey(0))
    scale = float(jnp.max(jnp.abs(grads["a"]))) / 127.0
    err = np.abs(np.asarray(comp["a"] - grads["a"]))
    assert err.max() <= scale * 1.01


# -------------------------------------------------------------- pipeline ----

def test_token_stream_deterministic_and_learnable():
    cfg = TokenStreamConfig(vocab_size=97, seq_len=32, global_batch=4, seed=3)
    s = SyntheticTokenStream(cfg)
    b1, b2 = s.batch(5), s.batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (4, 32)
    # targets are next-token shifted
    np.testing.assert_array_equal(b1["targets"][:, :-1], b1["tokens"][:, 1:])


def test_prefetch_iterator():
    cfg = TokenStreamConfig(vocab_size=17, seq_len=8, global_batch=2)
    s = SyntheticTokenStream(cfg)

    def gen():
        for i in range(5):
            yield s.batch(i)

    it = PrefetchIterator(gen())
    batches = list(it)
    assert len(batches) == 5
    assert batches[0]["tokens"].shape == (2, 8)


def test_shard_object_ranges():
    r = shard_object_ranges(10, 3)
    assert r == [(0, 4), (4, 7), (7, 10)]
    assert shard_object_ranges(4, 4) == [(0, 1), (1, 2), (2, 3), (3, 4)]


# -------------------------------------------------------------- optimizers --

def test_adamw_converges_quadratic():
    opt = AdamW(lr=0.1, weight_decay=0.0)
    params = {"x": jnp.asarray([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = jax.tree.map(lambda p: 2 * p, params)  # d/dx x^2
        params, state = opt.update(grads, state, params)
    assert float(jnp.abs(params["x"]).max()) < 0.1


def test_adafactor_converges_quadratic():
    opt = Adafactor(lr=0.3)
    params = {"w": jnp.full((8, 8), 4.0)}
    state = opt.init(params)
    for _ in range(200):
        grads = jax.tree.map(lambda p: 2 * p, params)
        params, state = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.3
    # factored state is small
    assert state.v_row["w"].shape == (8,)
    assert state.v_col["w"].shape == (8,)


def test_clip_by_global_norm():
    tree = {"a": jnp.asarray([3.0, 4.0])}  # norm 5
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(norm) == pytest.approx(5.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-3)


def test_cosine_schedule_shape():
    s0 = float(cosine_schedule(jnp.asarray(0), 1.0, warmup=10, total=100))
    s10 = float(cosine_schedule(jnp.asarray(10), 1.0, warmup=10, total=100))
    s100 = float(cosine_schedule(jnp.asarray(100), 1.0, warmup=10, total=100))
    assert s0 == 0.0 and s10 == pytest.approx(1.0) and s100 == pytest.approx(0.1)
