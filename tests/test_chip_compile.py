"""Compile the served path's kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed wherever libtpu is, and it compiles for a
chip that is described rather than attached.  It refuses what the Pallas
interpreter accepts: block shapes the TPU tiling rejects, relayouts Mosaic
cannot express, more fast memory than a kernel may use.  So every scoring
kernel ``--backend pallas`` serves is compiled here at deployment scale
(1<<20 rows x 4 predicates, 16 tenant slots), in f32 and bf16, and the
flash-attention kernel at the cascade backbone's lane shape.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and the test workers all import
this file.  The persistent compilation cache is off around these compiles
(an entry written for a described chip cannot be read back without one).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.decision_table import fallback_decision_table
from repro.kernels.enrich_score import ops as es_ops

ROWS = 1 << 20  # session capacity of the deployment-scale smoke
P, F, S = 4, 4, 16  # predicates, functions, tenant slots


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled, name: str) -> bool:
    text = compiled.as_text()
    return "tpu_custom_call" in text and name in text


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mode", ["table", "best"])
def test_batched_scoring_kernels_compile_for_v5e(one_chip, mode, dtype):
    """Both batched entry points (table mode -> enrich_score_tiles_batched,
    best mode -> enrich_score_best_tiles_batched) at deployment scale."""
    table = fallback_decision_table(P, F, jnp.linspace(0.6, 0.9, F))
    costs = jnp.asarray(np.tile(np.linspace(0.05, 0.9, F), (P, 1)), jnp.float32)
    dt = jnp.dtype(dtype)

    def score(pp, unc, sid, joint):
        return es_ops.fused_benefits_batched(
            pp, unc, sid, joint, table, costs, function_selection=mode,
        )

    compiled = jax.jit(score).lower(
        _spec((ROWS, P), dt, one_chip), _spec((ROWS, P), dt, one_chip),
        _spec((ROWS, P), jnp.int32, one_chip), _spec((S, ROWS), dt, one_chip),
    ).compile()
    name = "enrich_score_best_tiles_batched" if mode == "best" else "enrich_score_tiles_batched"
    assert _has_kernel(compiled, name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_single_query_scoring_kernel_compiles_for_v5e(one_chip, dtype):
    from repro.kernels.enrich_score import kernel as es_kernel

    table = fallback_decision_table(P, F, jnp.linspace(0.6, 0.9, F))
    costs = jnp.asarray(np.tile(np.linspace(0.05, 0.9, F), (P, 1)), jnp.float32)
    tables = es_ops._staged_tables(table, costs, "table", 4096)
    rows = ROWS * P // es_kernel.LANES
    dt = jnp.dtype(dtype)
    prob = _spec((rows, es_kernel.LANES), dt, one_chip)
    index = _spec((rows, es_kernel.LANES), jnp.float32, one_chip)

    def score(pp, unc, ent, sid, pidx, joint, cand):
        return es_kernel.enrich_score_tiles(
            pp, unc, ent, sid, pidx, joint, cand, *tables,
            num_bins=table.num_bins, num_states=table.num_states,
            num_functions=F, lut_bins=4096,
        )

    compiled = jax.jit(score).lower(
        prob, prob, index, index, index, prob, index
    ).compile()
    assert _has_kernel(compiled, "enrich_score_tiles")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_attention_compiles_at_backbone_lane_shape(one_chip, dtype):
    """qwen3-1.7b heads (16 query / 8 kv, head_dim 128) over the cascade
    bank's lanes: 512 merged lanes x 8 token positions each."""
    from repro.kernels.flash_attention.ops import flash_attention

    dt = jnp.dtype(dtype)
    q = _spec((512, 8, 16, 128), dt, one_chip)
    kv = _spec((512, 8, 8, 128), dt, one_chip)
    fn = lambda q, k, v: flash_attention(
        q, k, v, causal=False, block_q=8, block_kv=8, interpret=False
    )
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
