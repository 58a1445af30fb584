"""Multi-device sharded path smoke (ROADMAP leftover from PR 2).

The sharded planning program (``num_shards=2``) was exactness-tested under
shard EMULATION (reshape + vmap on one device); this runs the same fused
session superstep on a REAL 2-device host-platform mesh — substrate placed
via ``state.shard_substrate`` — and asserts parity with the single-device
program across a run/ingest/grow/run trace.  A subprocess sets
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` so the main test
process keeps its single CPU device.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path


def test_sharded_superstep_on_two_device_mesh_matches_single_device():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "").replace(
                "--xla_force_host_platform_device_count=2", ""
            )
            + " --xla_force_host_platform_device_count=2"
        )
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import EngineSession, MultiQueryConfig, Predicate, conjunction
        from repro.core import fallback_decision_table
        from repro.core import state as state_lib
        from repro.core.combine import default_combine_params
        from repro.data.synthetic import make_corpus
        from repro.launch.mesh import make_mesh

        assert jax.device_count() == 2, jax.devices()
        P, F, N = 4, 4, 128
        preds = [Predicate(i, 1) for i in range(P)]
        corpus = make_corpus(
            jax.random.PRNGKey(0), N, [p.tag_type for p in preds],
            [p.tag for p in preds], selectivity=[0.3, 0.4, 0.25, 0.35],
        )
        combine = default_combine_params(corpus.aucs)
        table = fallback_decision_table(P, F, corpus.aucs)

        def run(place_on_mesh):
            sess = EngineSession(
                [p.positive() for p in preds], table, combine, corpus.costs,
                capacity=64, max_tenants=2, max_capacity=N,
                config=MultiQueryConfig(plan_size=32, num_shards=2),
            )
            st = sess.init_state(corpus.func_probs[:64])
            if place_on_mesh:
                mesh = make_mesh((2,), ("data",))
                st = dataclasses.replace(
                    st, substrate=state_lib.shard_substrate(st.substrate, mesh)
                )
                shards = st.substrate.func_probs.sharding.device_set
                assert len(shards) == 2, shards
            st, _ = sess.admit(st, conjunction(preds[0], preds[1]))
            st, _ = sess.admit(st, conjunction(preds[1], preds[2]))
            st, h1 = sess.run(st, 4)
            st = sess.ingest(st, corpus.func_probs[64:N])  # forces tier growth
            st, h2 = sess.run(st, 4)
            assert st.capacity == N and sess.superstep_traces <= sess.retrace_bound
            return st, h1 + h2

        st1, h1 = run(False)
        st2, h2 = run(True)
        for a, b in zip(h1, h2):
            assert a.cost_spent == b.cost_spent, (a.epoch, a.cost_spent, b.cost_spent)
            assert a.answer_size == b.answer_size, a.epoch
        np.testing.assert_array_equal(
            np.asarray(st1.derived.in_answer), np.asarray(st2.derived.in_answer)
        )
        np.testing.assert_array_equal(
            np.asarray(st1.substrate.exec_mask), np.asarray(st2.substrate.exec_mask)
        )
        np.testing.assert_allclose(
            np.asarray(st1.substrate.func_probs),
            np.asarray(st2.substrate.func_probs), rtol=0, atol=0,
        )
        print("SHARDED_MESH_OK", jax.device_count())
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert "SHARDED_MESH_OK" in out.stdout, (out.stdout[-2000:], out.stderr[-4000:])
