"""Decision table learning, combine functions, joins, blocks, metrics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Predicate, conjunction, learn_decision_table
from repro.core.blocks import (
    block_benefits,
    make_block_state,
    per_object_load_cost,
    swap_best_block,
)
from repro.core.combine import (
    auc_score,
    calibrate_platt,
    apply_platt,
    combine_probabilities,
    default_combine_params,
    fit_combine_weights,
)
from repro.core.decision_table import enumerate_states, fallback_decision_table
from repro.core.join import join_predicate_probability
from repro.core.metrics import (
    gain_curve,
    progressive_qty,
    true_precision_recall_f,
)
from repro.data.synthetic import make_corpus


def test_enumerate_states():
    s = enumerate_states(3)
    assert s.shape == (8, 3)
    assert not s[0].any() and s[7].all()
    # little-endian: state 5 = 0b101 -> functions 0 and 2
    assert list(s[5]) == [True, False, True]


def test_auc_score_on_planted_data():
    rng = jax.random.PRNGKey(0)
    corpus = make_corpus(rng, 8192, [0], [1], aucs=[0.6, 0.75, 0.9, 0.97],
                         selectivity=0.3)
    for f, target in enumerate([0.6, 0.75, 0.9, 0.97]):
        got = float(auc_score(corpus.func_scores[:, 0, f], corpus.truth_pred[:, 0]))
        assert abs(got - target) < 0.03, (f, got, target)


def test_calibration_probs_are_calibrated():
    """Planted posteriors should match empirical frequencies (paper section 6.1)."""
    rng = jax.random.PRNGKey(1)
    corpus = make_corpus(rng, 16384, [0], [1], aucs=[0.6, 0.8, 0.9, 0.95],
                         selectivity=0.25)
    p = np.asarray(corpus.func_probs[:, 0, 2])
    y = np.asarray(corpus.truth_pred[:, 0])
    for lo, hi in [(0.1, 0.3), (0.3, 0.5), (0.5, 0.7), (0.7, 0.9)]:
        m = (p >= lo) & (p < hi)
        if m.sum() > 200:
            assert abs(y[m].mean() - p[m].mean()) < 0.08


def test_platt_improves_calibration():
    rng = jax.random.PRNGKey(2)
    n = 4096
    y = jax.random.bernoulli(rng, 0.4, (n,)).astype(jnp.float32)
    # miscalibrated overconfident scores
    raw = jax.nn.sigmoid(6.0 * (y * 2 - 1) + 3.0 * jax.random.normal(rng, (n,)))
    a, b = calibrate_platt(raw, y)
    cal = apply_platt(raw, a, b)
    def nll(p):
        p = jnp.clip(p, 1e-6, 1 - 1e-6)
        return float(-jnp.mean(y * jnp.log(p) + (1 - y) * jnp.log(1 - p)))
    assert nll(cal) <= nll(raw) + 1e-6


def test_combine_empty_state_returns_prior():
    params = default_combine_params(jnp.full((2, 3), 0.8))
    probs = jnp.full((4, 2, 3), 0.9)
    mask = jnp.zeros((4, 2, 3), bool)
    out = combine_probabilities(params, probs, mask, prior=0.5)
    np.testing.assert_allclose(np.asarray(out), 0.5)


def test_combine_more_evidence_sharper():
    params = default_combine_params(jnp.full((1, 4), 0.85))
    probs = jnp.full((1, 1, 4), 0.8)
    one = combine_probabilities(params, probs, jnp.asarray([[[1, 0, 0, 0]]], bool))
    all4 = combine_probabilities(params, probs, jnp.ones((1, 1, 4), bool))
    assert float(all4[0, 0]) > float(one[0, 0])


def test_fit_combine_beats_single_function_auc():
    rng = jax.random.PRNGKey(3)
    corpus = make_corpus(rng, 8192, [0], [1], aucs=[0.6, 0.7, 0.8, 0.9],
                         selectivity=0.3)
    params = fit_combine_weights(
        corpus.func_probs, corpus.truth_pred.astype(jnp.float32), steps=150
    )
    combined = combine_probabilities(
        params, corpus.func_probs, jnp.ones_like(corpus.func_probs, bool)
    )
    auc_comb = float(auc_score(combined[:, 0], corpus.truth_pred[:, 0]))
    assert auc_comb > 0.9  # ensemble beats best single function (paper intro)


def test_learned_decision_table_is_consistent():
    rng = jax.random.PRNGKey(4)
    corpus = make_corpus(rng, 2048, [0], [1], aucs=[0.6, 0.8, 0.9, 0.95],
                         selectivity=0.3)
    params = default_combine_params(corpus.aucs)
    table = learn_decision_table(corpus.func_probs, params, num_bins=10)
    nf = np.asarray(table.next_fn)
    dh = np.asarray(table.delta_h)
    assert nf.shape == (1, 16, 10)
    # exhausted state (15) has no next function
    assert np.all(nf[:, 15, :] == -1)
    # a chosen function is never already in the state
    states = enumerate_states(4)
    for s in range(15):
        for b in range(10):
            f = nf[0, s, b]
            if f >= 0:
                assert not states[s, f]
    assert np.all(dh <= 0.0)


def test_join_eq13():
    own = jnp.asarray([0.5, 1.0, 0.0])
    partner = jnp.asarray([0.2, 0.4, 0.6, 0.8])
    out = join_predicate_probability(own, partner)
    np.testing.assert_allclose(np.asarray(out), [0.25, 0.5, 0.0], rtol=1e-6)


def test_blocks_load_cost_and_swap():
    bs = make_block_state(num_objects=100, num_blocks=10, resident_blocks=3,
                          load_cost=5.0)
    lc = per_object_load_cost(bs, 100)
    assert float(lc[0]) == 0.0  # block 0 resident
    assert float(lc[99]) == pytest.approx(0.5)  # 5.0 / 10 objects per block
    # fake benefits concentrated in block 7
    from repro.core.benefit import TripleBenefits
    ben = np.zeros((100, 1), np.float32)
    ben[70:80] = 10.0
    tb = TripleBenefits(
        benefit=jnp.asarray(ben), next_fn=jnp.zeros((100, 1), jnp.int32),
        est_joint=jnp.zeros((100, 1)),
    )
    bb = block_benefits(bs, tb)
    assert int(jnp.argmax(bb)) == 7
    bs2 = swap_best_block(bs, tb)
    assert bool(bs2.resident[7])
    assert int(bs2.resident.sum()) == 3


def test_metrics_gain_and_qty():
    f = [0.1, 0.4, 0.6, 0.6, 0.8]
    g = gain_curve(np.asarray(f))
    assert g[0] == 0.0 and g[-1] == 1.0
    q = progressive_qty([1, 2, 3, 4, 5], f, budget=5.0)
    assert 0.0 < q <= 1.0
    # front-loaded improvement scores higher
    q_front = progressive_qty([1, 2, 3, 4, 5], [0.1, 0.7, 0.8, 0.8, 0.8], budget=5.0)
    q_back = progressive_qty([1, 2, 3, 4, 5], [0.1, 0.1, 0.1, 0.1, 0.8], budget=5.0)
    assert q_front > q_back


def test_true_f_alpha():
    a = jnp.asarray([True, True, False, False])
    g = jnp.asarray([True, False, True, False])
    pre, rec, f1 = true_precision_recall_f(a, g)
    assert float(pre) == 0.5 and float(rec) == 0.5 and float(f1) == 0.5
