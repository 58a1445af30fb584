"""Pallas kernel validation (interpret mode) vs pure-jnp oracles, swept over
shapes and dtypes (assignment deliverable c)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.decode_attention import ref as da_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan import ref as ssd_ref
from repro.kernels.enrich_score import ops as es_ops
from repro.core import Predicate, conjunction
from repro.core.benefit import compute_benefits
from repro.core.combine import default_combine_params
from repro.core.decision_table import fallback_decision_table, learn_decision_table
from repro.core.plan import select_plan
from repro.core.state import init_state, refresh_derived


# ------------------------------------------------------------ flash attn ---

def _fa_inputs(seed, b, sq, skv, h, kv, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, skv, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, skv, kv, d), dtype)
    return q, k, v


FA_CASES = [
    # b, sq, skv, h, kv, d, causal, window, softcap, dtype
    (1, 128, 128, 4, 2, 32, True, None, None, jnp.float32),
    (2, 256, 256, 4, 4, 64, True, None, 50.0, jnp.float32),
    (1, 128, 128, 8, 2, 32, True, 48, None, jnp.float32),
    (2, 128, 128, 4, 1, 64, False, None, None, jnp.float32),
    (1, 256, 256, 4, 2, 32, True, None, None, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention_matches_ref(case):
    b, sq, skv, h, kv, d, causal, window, cap, dtype = case
    q, k, v = _fa_inputs(0, b, sq, skv, h, kv, d, dtype)
    kv_len = jnp.asarray([skv], jnp.int32)
    out = fa_ops.flash_attention(
        q, k, v, kv_len, causal=causal, window=window, logit_softcap=cap,
        block_q=64, block_kv=64, interpret=True,
    )
    qm = jnp.transpose(q, (0, 2, 1, 3)).reshape(b * h, sq, d)
    km = jnp.transpose(k, (0, 2, 1, 3)).reshape(b * kv, skv, d)
    vm = jnp.transpose(v, (0, 2, 1, 3)).reshape(b * kv, skv, d)
    ref = fa_ref.reference_bhsd(
        qm, km, vm, kv_len, num_q_heads=h, num_kv_heads=kv,
        causal=causal, window=window, softcap=cap,
    )
    ref = jnp.transpose(ref.reshape(b, h, sq, d), (0, 2, 1, 3))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_attention_partial_kv_len():
    b, sq, skv, h, kv, d = 1, 64, 256, 4, 2, 32
    q, k, v = _fa_inputs(1, b, sq, skv, h, kv, d, jnp.float32)
    kv_len = jnp.asarray([100], jnp.int32)
    out = fa_ops.flash_attention(
        q, k, v, kv_len, causal=True, q_offset_from_kv_len=True,
        block_q=64, block_kv=64, interpret=True,
    )
    qm = jnp.transpose(q, (0, 2, 1, 3)).reshape(b * h, sq, d)
    km = jnp.transpose(k, (0, 2, 1, 3)).reshape(b * kv, skv, d)
    vm = jnp.transpose(v, (0, 2, 1, 3)).reshape(b * kv, skv, d)
    ref = fa_ref.reference_bhsd(
        qm, km, vm, kv_len, num_q_heads=h, num_kv_heads=kv,
        causal=True, q_offset_from_kv_len=True,
    )
    ref = jnp.transpose(ref.reshape(b, h, sq, d), (0, 2, 1, 3))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ----------------------------------- flash attn vs models/attention --------
# Backbone parity fixtures for the fused cascade bank: the trunk routes its
# attention through this kernel when ``cfg.attn_impl == "pallas"``, so the
# kernel is pinned against the models/attention engines at the REDUCED
# backbone shapes the bank actually runs (lanes x 8 tokens, non-causal).

BACKBONE_FA_DTYPES = [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)]


@pytest.mark.parametrize("dtype,tol", BACKBONE_FA_DTYPES)
def test_attention_engine_pallas_matches_dense_backbone_shapes(dtype, tol):
    from repro.models.attention import attention_engine

    b, s, h, kv, d = 16, 8, 4, 2, 16  # 16 lanes x N_BACKBONE_TOKENS
    q, k, v = _fa_inputs(2, b, s, s, h, kv, d, dtype)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    kwargs = dict(causal=False, window=None, kv_len=None, cap=None)
    out_pl = attention_engine(q, k, v, pos, pos, impl="pallas", **kwargs)
    out_dn = attention_engine(q, k, v, pos, pos, impl="dense", **kwargs)
    np.testing.assert_allclose(
        np.asarray(out_pl, np.float32), np.asarray(out_dn, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("dtype_name,tol", [("float32", 2e-4), ("bfloat16", 4e-2)])
def test_backbone_trunk_pallas_matches_default_impl(dtype_name, tol):
    """The cascade-bank trunk, end to end: stack_apply with attn_impl
    "pallas" must match the default (dense/chunked) engines at the reduced
    backbone config."""
    from repro.configs.archs import get_config
    from repro.models import transformer as tf
    from repro.models.model import Model

    cfg = get_config("qwen3-1.7b", smoke=True)
    cfg = dataclasses.replace(cfg, dtype=dtype_name)
    params, _ = Model(cfg).init_params(jax.random.PRNGKey(0))
    b, s = 16, 8
    x = jax.random.normal(
        jax.random.PRNGKey(1), (b, s, cfg.d_model), cfg.activation_dtype
    )
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def run(impl):
        c = dataclasses.replace(cfg, attn_impl=impl)
        h, _, _ = tf.stack_apply(
            params["layers"], c, x, pos, c.num_layers, causal=False
        )
        return np.asarray(h, np.float32)

    np.testing.assert_allclose(run("pallas"), run("auto"), rtol=tol, atol=tol)


# ------------------------------------------------------------ decode attn ---

DA_CASES = [
    (2, 256, 4, 2, 32, None, None, 4, jnp.float32),
    (1, 512, 8, 2, 64, 30.0, None, 8, jnp.float32),
    (2, 256, 4, 4, 32, None, 128, 4, jnp.float32),
    (1, 256, 4, 2, 32, None, None, 4, jnp.bfloat16),
]


@pytest.mark.parametrize("case", DA_CASES)
def test_decode_attention_matches_ref(case):
    b, skv, h, kv, d, cap, window, ns, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), dtype)
    k = jax.random.normal(ks[1], (b, skv, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, skv, kv, d), dtype)
    kv_len = jnp.asarray([skv * 3 // 4], jnp.int32)
    out = da_ops.decode_attention(
        q, k, v, kv_len, softcap=cap, window=window, num_splits=ns,
        interpret=True,
    )
    ref = da_ref.reference_decode(q, k, v, kv_len, softcap=cap, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_decode_combine_partials_algebra():
    """Split-combine must be exact regardless of split count."""
    b, skv, h, kv, d = 1, 512, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    k = jax.random.normal(ks[1], (b, skv, kv, d))
    v = jax.random.normal(ks[2], (b, skv, kv, d))
    kv_len = jnp.asarray([skv], jnp.int32)
    outs = [
        np.asarray(da_ops.decode_attention(q, k, v, kv_len, num_splits=ns,
                                           interpret=True))
        for ns in (1, 2, 8)
    ]
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[0], outs[2], rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ SSD ----

SSD_CASES = [
    (2, 128, 32, 16, 32, jnp.float32),  # bh, s, p, n, chunk
    (4, 256, 64, 16, 64, jnp.float32),
    (1, 64, 32, 8, 64, jnp.float32),
    (2, 128, 32, 16, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_matches_recurrence(case):
    bh, s, p, n, chunk, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (bh, s, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bh, s))) * 0.1
    a = -jnp.exp(jax.random.normal(ks[2], (bh,)) * 0.3)
    b_mat = jax.random.normal(ks[3], (bh, s, n), dtype)
    c_mat = jax.random.normal(ks[4], (bh, s, n), dtype)
    y, h = ssd_ops.ssd_scan(x, dt, a, b_mat, c_mat, chunk=chunk, interpret=True)
    y_ref, h_ref = ssd_ref.reference_ssd(x, dt, a, b_mat, c_mat)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=tol, atol=tol)


def test_ssd_scan_with_initial_state():
    bh, s, p, n = 2, 64, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    x = jax.random.normal(ks[0], (bh, s, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bh, s))) * 0.1
    a = -jnp.exp(jax.random.normal(ks[2], (bh,)) * 0.3)
    b_mat = jax.random.normal(ks[3], (bh, s, n))
    c_mat = jax.random.normal(ks[4], (bh, s, n))
    h0 = jax.random.normal(ks[5], (bh, p, n))
    y, h = ssd_ops.ssd_scan(x, dt, a, b_mat, c_mat, h0, chunk=32, interpret=True)
    y_ref, h_ref = ssd_ref.reference_ssd(x, dt, a, b_mat, c_mat, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------- enrich score ---

def _mk_state(seed, n, p, f, query):
    rng = np.random.default_rng(seed)
    combine = default_combine_params(jnp.full((p, f), 0.8))
    stt = init_state(n, p, f)
    mask = rng.uniform(size=(n, p, f)) < 0.5
    probs = rng.uniform(0.02, 0.98, size=(n, p, f)).astype(np.float32)
    stt = dataclasses.replace(
        stt, exec_mask=jnp.asarray(mask), func_probs=jnp.asarray(probs)
    )
    return refresh_derived(stt, query, combine)


@pytest.mark.parametrize("n,p,f", [(64, 2, 4), (200, 3, 4), (33, 1, 3)])
def test_enrich_score_matches_reference(n, p, f):
    query = conjunction(*[Predicate(i, 1) for i in range(p)])
    stt = _mk_state(0, n, p, f, query)
    table = fallback_decision_table(p, f, jnp.linspace(0.6, 0.9, f))
    costs = jnp.asarray(
        np.tile(np.linspace(0.05, 0.9, f), (p, 1)), jnp.float32
    )
    cand = jnp.asarray(np.random.default_rng(1).uniform(size=n) < 0.7)
    ref = compute_benefits(stt, query, table, costs, candidate_mask=cand)
    out = es_ops.fused_benefits(stt, query, table, costs, candidate_mask=cand,
                                interpret=True)
    fin = np.isfinite(np.asarray(ref.benefit))
    assert (fin == np.isfinite(np.asarray(out.benefit))).all()
    np.testing.assert_allclose(
        np.asarray(out.benefit)[fin], np.asarray(ref.benefit)[fin],
        rtol=5e-3, atol=5e-3,
    )
    np.testing.assert_array_equal(
        np.asarray(out.next_fn)[fin], np.asarray(ref.next_fn)[fin]
    )
    np.testing.assert_allclose(
        np.asarray(out.est_joint)[fin], np.asarray(ref.est_joint)[fin],
        rtol=5e-3, atol=5e-3,
    )


def _batched_inputs(seed, n, p, f, q):
    """Shared-substrate rows + per-query joints for the batched kernels."""
    query = conjunction(*[Predicate(i, 1) for i in range(p)])
    stt = _mk_state(seed, n, p, f, query)
    rng = np.random.default_rng(seed + 100)
    joint = jnp.asarray(rng.uniform(0.01, 1.0, size=(q, n)).astype(np.float32))
    return stt, joint


def _assert_batched_parity(stt, joint, table, costs, mode):
    from repro.core.benefit import compute_benefits_batched

    ref = compute_benefits_batched(
        stt.pred_prob, stt.uncertainty, stt.state_id(), joint, table, costs,
        function_selection=mode,
    )
    out = es_ops.fused_benefits_batched(
        stt.pred_prob, stt.uncertainty, stt.state_id(), joint, table, costs,
        function_selection=mode, interpret=True,
    )
    # mask the engine way: a lane only matters where a next function exists
    rv = np.asarray(ref.next_fn) >= 0
    ov = np.asarray(out.next_fn) >= 0
    np.testing.assert_array_equal(ov, rv)
    rb = np.where(rv, np.asarray(ref.benefit), -np.inf)
    ob = np.where(ov, np.asarray(out.benefit), -np.inf)
    fin = np.isfinite(rb)
    assert (fin == np.isfinite(ob)).all()
    np.testing.assert_allclose(ob[fin], rb[fin], rtol=5e-3, atol=5e-3)
    np.testing.assert_array_equal(
        np.asarray(out.next_fn)[fin], np.asarray(ref.next_fn)[fin]
    )
    np.testing.assert_allclose(
        np.asarray(out.est_joint)[fin], np.asarray(ref.est_joint)[fin],
        rtol=5e-3, atol=5e-3,
    )
    # the plan bills each kept lane the cost the reference priced it at
    plans = jax.vmap(lambda b: select_plan(b, 16, costs))(out)
    pv = np.asarray(plans.valid)
    obj, prd = np.asarray(plans.object_idx), np.asarray(plans.pred_idx)
    ref_fn = np.asarray(ref.next_fn)[np.arange(len(obj))[:, None], obj, prd]
    np.testing.assert_array_equal(np.asarray(plans.func_idx)[pv], ref_fn[pv])
    ref_cost = np.maximum(np.asarray(costs)[prd, np.maximum(ref_fn, 0)], 1e-9)
    np.testing.assert_array_equal(np.asarray(plans.cost)[pv], ref_cost[pv])
    return fin


@pytest.mark.parametrize("mode", ["table", "best"])
@pytest.mark.parametrize("n,p,f,q", [(64, 2, 4, 3), (130, 3, 4, 5), (40, 1, 3, 1)])
def test_enrich_score_batched_matches_reference(mode, n, p, f, q):
    stt, joint = _batched_inputs(0, n, p, f, q)
    table = fallback_decision_table(p, f, jnp.linspace(0.6, 0.9, f))
    costs = jnp.asarray(np.tile(np.linspace(0.05, 0.9, f), (p, 1)), jnp.float32)
    fin = _assert_batched_parity(stt, joint, table, costs, mode)
    assert fin.any()


@pytest.mark.parametrize("mode", ["table", "best"])
def test_enrich_score_batched_edge_bins(mode):
    """h ~ 0 (saturated probs), h ~ 1 (coin-flip probs), exhausted triples."""
    n, p, f, q = 96, 2, 4, 3
    query = conjunction(*[Predicate(i, 1) for i in range(p)])
    combine = default_combine_params(jnp.full((p, f), 0.8))
    rng = np.random.default_rng(7)
    probs = np.empty((n, p, f), np.float32)
    probs[: n // 3] = rng.uniform(1e-6, 1e-4, size=(n // 3, p, f))  # h ~ 0
    probs[n // 3 : 2 * n // 3] = 0.5 + rng.uniform(  # h ~ 1
        -1e-5, 1e-5, size=(n // 3, p, f)
    )
    probs[2 * n // 3 :] = rng.uniform(0.02, 0.98, size=(n - 2 * (n // 3), p, f))
    mask = rng.uniform(size=(n, p, f)) < 0.5
    mask[2 * n // 3 :] = True  # exhausted: every function already executed
    stt = init_state(n, p, f)
    stt = dataclasses.replace(
        stt, exec_mask=jnp.asarray(mask), func_probs=jnp.asarray(probs)
    )
    stt = refresh_derived(stt, query, combine)
    joint = jnp.asarray(rng.uniform(0.0, 1.0, size=(q, n)).astype(np.float32))
    table = fallback_decision_table(p, f, jnp.linspace(0.6, 0.9, f))
    costs = jnp.asarray(np.tile(np.linspace(0.05, 0.9, f), (p, 1)), jnp.float32)
    _assert_batched_parity(stt, joint, table, costs, mode)
    # exhausted rows must be invalid in both implementations
    out = es_ops.fused_benefits_batched(
        stt.pred_prob, stt.uncertainty, stt.state_id(), joint, table, costs,
        function_selection=mode, interpret=True,
    )
    assert (np.asarray(out.next_fn)[:, 2 * n // 3 :, :] == -1).all()


@pytest.mark.parametrize("mode", ["table", "best"])
def test_enrich_score_batched_jitted_with_constant_tables(mode):
    """Inside a jitted program (how the session superstep calls it) the
    decision table and costs are compile-time constants.  The kernel must
    give exactly what the same program gives with them passed as arguments,
    and stay within KERNEL_RTOL of the reference: a table staged through
    XLA's constant folding once came out with entries permuted."""
    from repro.core.benefit import compute_benefits_batched
    from repro.core.entropy import binary_entropy
    from repro.kernels.enrich_score.kernel import KERNEL_RTOL

    p, f, n, q = 4, 4, 1024, 3
    table = fallback_decision_table(p, f, jnp.full((p, f), 0.85), num_bins=10)
    rng = np.random.default_rng(0)
    costs = jnp.asarray(rng.uniform(0.05, 1.0, (p, f)), jnp.float32)
    pp = jnp.asarray(rng.uniform(0.01, 0.99, (n, p)), jnp.float32)
    sid = jnp.asarray(rng.integers(0, 2 ** f, (n, p)), jnp.int32)
    joint = jnp.asarray(rng.uniform(0.0, 1.0, (q, n)), jnp.float32)
    args = (pp, binary_entropy(pp), sid, joint)

    def fused(*a):
        return es_ops.fused_benefits_batched(
            *a, table, costs, function_selection=mode, interpret=True
        )

    def fused_args(tab, cst, *a):
        return es_ops.fused_benefits_batched(
            *a, tab, cst, function_selection=mode, interpret=True
        )

    staged_at_run = jax.jit(fused_args)(table, costs, *args)
    jitted = jax.jit(fused)(*args)
    for name in staged_at_run._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(jitted, name)),
            np.asarray(getattr(staged_at_run, name)),
        )
    ref = compute_benefits_batched(*args, table, costs, function_selection=mode)
    valid = np.asarray(ref.next_fn) >= 0
    np.testing.assert_array_equal(np.asarray(jitted.next_fn), np.asarray(ref.next_fn))
    for name in ("benefit", "est_joint"):
        np.testing.assert_allclose(
            np.asarray(getattr(jitted, name))[valid],
            np.asarray(getattr(ref, name))[valid],
            rtol=KERNEL_RTOL, atol=KERNEL_RTOL,
        )



@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mode", ["table", "best"])
def test_enrich_score_batched_bitwise_with_reference_under_jit(mode, dtype):
    """As the session superstep runs them (jitted, table and costs closed
    over), the interpreted kernel and the jnp reference agree bit for bit on
    every output of every lane that has a next function."""
    from repro.core.benefit import compute_benefits_batched
    from repro.core.entropy import binary_entropy

    p, f, n, q = 4, 4, 1024, 3
    dt = jnp.dtype(dtype)
    table = fallback_decision_table(p, f, jnp.full((p, f), 0.85), num_bins=10)
    rng = np.random.default_rng(1)
    costs = jnp.asarray(rng.uniform(0.05, 1.0, (p, f)), jnp.float32)
    pp = jnp.asarray(rng.uniform(0.01, 0.99, (n, p)), jnp.float32).astype(dt)
    unc = binary_entropy(pp.astype(jnp.float32)).astype(dt)
    sid = jnp.asarray(rng.integers(0, 2 ** f, (n, p)), jnp.int32)
    joint = jnp.asarray(rng.uniform(0.0, 1.0, (q, n)), jnp.float32).astype(dt)

    out = jax.jit(lambda *a: es_ops.fused_benefits_batched(
        *a, table, costs, function_selection=mode, interpret=True
    ))(pp, unc, sid, joint)
    ref = jax.jit(lambda *a: compute_benefits_batched(
        *(x if x.dtype == jnp.int32 else x.astype(jnp.float32) for x in a),
        table, costs, function_selection=mode,
    ))(pp, unc, sid, joint)
    valid = np.asarray(ref.next_fn) >= 0
    assert valid.any() and not valid.all()
    np.testing.assert_array_equal(np.asarray(out.next_fn), np.asarray(ref.next_fn))
    for name in ("benefit", "est_joint"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out, name))[valid], np.asarray(getattr(ref, name))[valid]
        )
    # the plans they give, costs included, as the superstep selects them
    masked = lambda tb: tb._replace(benefit=jnp.where(valid, tb.benefit, -jnp.inf))
    plan = jax.jit(jax.vmap(lambda b: select_plan(b, 128, costs)))
    for a, b in zip(plan(masked(out)), plan(masked(ref))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_enrich_score_batched_with_learned_table():
    from repro.data.synthetic import make_corpus

    rng = jax.random.PRNGKey(11)
    p, f, n, q = 2, 4, 128, 4
    query = conjunction(Predicate(0, 1), Predicate(1, 2))
    corpus = make_corpus(rng, 512, [0, 1], [1, 2], aucs=[0.6, 0.8, 0.9, 0.95])
    combine = default_combine_params(corpus.aucs)
    table = learn_decision_table(corpus.func_probs, combine)
    stt = _mk_state(3, n, p, f, query)
    joint = jnp.asarray(
        np.random.default_rng(4).uniform(0.01, 1.0, size=(q, n)).astype(np.float32)
    )
    for mode in ("table", "best"):
        _assert_batched_parity(stt, joint, table, corpus.costs, mode)


def test_enrich_score_with_learned_table():
    from repro.data.synthetic import make_corpus
    rng = jax.random.PRNGKey(5)
    query = conjunction(Predicate(0, 1), Predicate(1, 2))
    corpus = make_corpus(rng, 512, [0, 1], [1, 2], aucs=[0.6, 0.8, 0.9, 0.95])
    combine = default_combine_params(corpus.aucs)
    table = learn_decision_table(corpus.func_probs, combine)
    stt = _mk_state(2, 256, 2, 4, query)
    costs = corpus.costs
    ref = compute_benefits(stt, query, table, costs,
                           candidate_mask=jnp.ones(256, bool))
    out = es_ops.fused_benefits(stt, query, table, costs,
                                candidate_mask=jnp.ones(256, bool),
                                interpret=True)
    fin = np.isfinite(np.asarray(ref.benefit))
    np.testing.assert_allclose(
        np.asarray(out.benefit)[fin], np.asarray(ref.benefit)[fin],
        rtol=5e-3, atol=5e-3,
    )
