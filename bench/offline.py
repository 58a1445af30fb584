"""The benchmark's own inputs, made on the device from the seed.

The corpus of tagging outputs, the combine weights (paper Eq. 1) and the
decision table (paper section 4.2) are configuration inputs: the session under
test and the plain reference both read them as data.  They are made here, by
the benchmark, never by the program: the arithmetic is copied from the
repository's offline phase (``data/synthetic.make_corpus``,
``core/combine.fit_combine_weights``, ``core/decision_table.
learn_decision_table``) so a later change to the program cannot move them.
"""

from __future__ import annotations

import numpy as np


def _jnp():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def calibrated_outputs(key, n: int, aucs, selectivity: float):
    """AUC-calibrated outputs of every tagging function, [n, P, F] f32, and
    the planted truth [n, P] bool.  Scores are two unit Gaussians separated
    by mu = Phi^-1(AUC) / sqrt(2); outputs are their exact posterior."""
    jax, jnp = _jnp()
    from jax.scipy.stats import norm

    aucs = jnp.asarray(aucs, jnp.float32)  # [P, F]
    p, f = aucs.shape
    k_truth, k_noise = jax.random.split(key)
    truth = jax.random.uniform(k_truth, (n, p)) < selectivity
    mu = norm.ppf(jnp.clip(aucs, 0.5 + 1e-4, 1 - 1e-4)) / jnp.sqrt(2.0)
    y = truth.astype(jnp.float32)
    scores = mu[None] * (2.0 * y[:, :, None] - 1.0) + jax.random.normal(k_noise, (n, p, f))
    prior_logit = np.log(selectivity) - np.log1p(-selectivity)
    return jax.nn.sigmoid(2.0 * mu[None] * scores + prior_logit), truth


def _logit(p, eps=1e-6):
    _, jnp = _jnp()
    p = jnp.clip(p, eps, 1.0 - eps)
    return jnp.log(p) - jnp.log1p(-p)


def _combine(w, b, rho, probs, mask, prior=0.5):
    jax, jnp = _jnp()
    m = mask.astype(jnp.float32)
    logits = _logit(probs) * m * w
    denom = jnp.maximum(jnp.sum(m * w, axis=-1), 1e-9)
    n_exec = jnp.sum(m, axis=-1)
    pooled = jnp.sum(logits, axis=-1) / denom
    out = jax.nn.sigmoid(pooled * jnp.power(jnp.maximum(n_exec, 1.0), rho) + b)
    return jnp.where(n_exec > 0, out, prior)


def _entropy(p):
    _, jnp = _jnp()
    p = jnp.clip(p, 0.0, 1.0)

    def xlog2x(x):
        return jnp.where(x > 0, x * jnp.log(jnp.maximum(x, 1e-38)) / 0.6931471805599453, 0.0)

    return -(xlog2x(p) + xlog2x(1.0 - p))


def fit_combine(probs, labels, steps: int, lr: float = 0.05):
    """Masked logistic pooling fitted by NLL descent on fully executed
    training rows -> (weights [P, F], bias [P], rho [P])."""
    jax, jnp = _jnp()
    n, p, f = probs.shape
    full = jnp.ones((n, p, f), bool)

    def unpack(t):
        return jax.nn.softplus(t["w"]) + 1e-3, t["b"], jax.nn.sigmoid(t["r"])

    def loss(t):
        pred = jnp.clip(_combine(*unpack(t), probs, full), 1e-6, 1 - 1e-6)
        return jnp.mean(-(labels * jnp.log(pred) + (1 - labels) * jnp.log(1 - pred)))

    theta = {"w": jnp.zeros((p, f)), "b": jnp.zeros((p,)), "r": jnp.zeros((p,))}
    grad = jax.grad(loss)
    theta, _ = jax.lax.scan(
        lambda t, _: (jax.tree.map(lambda a, g: a - lr * g, t, grad(t)), None),
        theta, None, length=steps,
    )
    return unpack(theta)


def learn_table(probs, w, b, rho, num_bins: int):
    """Per-function expected entropy change [P, 2^F, bins, F] (+inf where the
    function already ran or the bin saw no training row), plus the argmax
    function and its delta [P, 2^F, bins]."""
    jax, jnp = _jnp()
    ntr, p, f = probs.shape
    states = jnp.asarray(
        [[bool((s >> j) & 1) for j in range(f)] for s in range(2**f)]
    )

    def bins_of(h):
        return jnp.clip(
            jnp.floor(jnp.clip(h, 0.0, 1.0 - 1e-7) * num_bins).astype(jnp.int32),
            0, num_bins - 1,
        )

    def per_state(row):
        mask = jnp.broadcast_to(row[None, None, :], (ntr, p, f))
        h_s = _entropy(_combine(w, b, rho, probs, mask))
        onehot = jax.nn.one_hot(bins_of(h_s), num_bins, dtype=jnp.float32)
        cnts = jnp.sum(onehot, axis=0)

        def per_fn(j):
            add = jnp.zeros((f,), bool).at[j].set(True)
            mask2 = jnp.broadcast_to((row | add)[None, None, :], (ntr, p, f))
            dh = _entropy(_combine(w, b, rho, probs, mask2)) - h_s
            mean = jnp.einsum("np,npb->pb", dh, onehot) / jnp.maximum(cnts, 1.0)
            mean = jnp.where(row[j], jnp.inf, mean)
            return jnp.where(cnts >= 1, mean, jnp.inf)

        deltas = jax.vmap(per_fn)(jnp.arange(f))  # [F, P, B]
        best = jnp.argmin(deltas, axis=0)
        best_delta = jnp.take_along_axis(deltas, best[None], axis=0)[0]
        no_data = ~jnp.isfinite(jnp.min(deltas, axis=0))
        best = jnp.where(no_data, jnp.argmax(~row).astype(best.dtype), best)
        best = jnp.where(jnp.all(row), -1, best)
        best_delta = jnp.where(jnp.isfinite(best_delta), jnp.minimum(best_delta, 0.0), 0.0)
        best_delta = jnp.where(jnp.all(row), 0.0, best_delta)
        clean = jnp.where(jnp.isfinite(deltas), jnp.minimum(deltas, 0.0), jnp.inf)
        return best.astype(jnp.int32), best_delta, clean

    nf, dh, dall = jax.lax.map(per_state, states)
    return (
        jnp.transpose(nf, (1, 0, 2)),
        jnp.transpose(dh, (1, 0, 2)),
        jnp.transpose(dall, (2, 0, 3, 1)),
    )


def tables(train_probs, train_truth, combine_steps: int, num_bins: int) -> dict:
    """Combine weights and decision table learned from fully executed
    training rows (jittable)."""
    _, jnp = _jnp()
    w, b, rho = fit_combine(train_probs, train_truth.astype(jnp.float32), combine_steps)
    next_fn, delta_h, delta_all = learn_table(train_probs, w, b, rho, num_bins)
    return dict(weights=w, bias=b, rho=rho, next_fn=next_fn, delta_h=delta_h,
                delta_h_all=delta_all)


def session_inputs(tab: dict, costs, cfg: dict):
    """The program's input types built from the benchmark's arrays."""
    import jax.numpy as jnp

    from repro.core import EngineConfig
    from repro.core.combine import CombineParams
    from repro.core.decision_table import DecisionTable

    combine = CombineParams(weights=tab["weights"], bias=tab["bias"], rho=tab["rho"])
    table = DecisionTable(
        next_fn=tab["next_fn"], delta_h=tab["delta_h"],
        delta_h_all=tab["delta_h_all"], num_bins=cfg["table_bins"],
    )
    engine = EngineConfig(
        plan_size=cfg["plan_size"], function_selection=cfg["function_selection"],
        candidate_strategy=cfg["candidate_strategy"], answer_mode=cfg["answer_mode"],
        backend=cfg["backend"], substrate_dtype=cfg["substrate_dtype"],
        prior=cfg["prior"], alpha=cfg["alpha"],
        pallas_interpret=bool(cfg.get("pallas_interpret", False)),
    )
    return combine, table, jnp.asarray(costs, jnp.float32), engine


def analytic_tables(aucs, num_bins: int) -> dict:
    """Combine weights and decision table from declared function qualities
    alone (no training corpus): weight logit(AUC) per function, bias 0,
    rho 0.5; the next function is the best remaining by AUC, with an
    expected entropy change of -2 (AUC - 0.5) times the bin's midpoint.
    The arithmetic of the repository's analytic prior table."""
    aucs = np.asarray(aucs, np.float64)  # [P, F]
    p, f = aucs.shape
    clipped = np.clip(aucs, 0.5 + 1e-3, 1 - 1e-3)
    weights = np.maximum(np.log(clipped) - np.log1p(-clipped), 1e-3)
    states = np.asarray([[bool((s >> j) & 1) for j in range(f)] for s in range(2**f)])
    mid = (np.arange(num_bins) + 0.5) / num_bins
    q = np.where(states[None], -np.inf, aucs[:, None, :])  # [P, S, F]
    best = np.argmax(q, axis=-1)
    frac = np.clip(2.0 * (np.max(q, axis=-1) - 0.5), 0.0, 1.0)
    done = states.all(axis=-1)[None, :]
    delta = np.where(done[..., None], 0.0, -frac[..., None] * mid)
    frac_all = np.clip(2.0 * (aucs - 0.5), 0.0, 1.0)  # [P, F]
    delta_all = -frac_all[:, None, None, :] * mid[None, None, :, None]
    delta_all = np.broadcast_to(delta_all, (p, 2**f, num_bins, f))
    delta_all = np.where(states[None, :, None, :], np.inf, delta_all)
    return dict(
        weights=weights.astype(np.float32),
        bias=np.zeros(p, np.float32),
        rho=np.full(p, 0.5, np.float32),
        next_fn=np.broadcast_to(np.where(done, -1, best)[..., None], (p, 2**f, num_bins)).astype(np.int32),
        delta_h=delta.astype(np.float32),
        delta_h_all=delta_all.astype(np.float32),
    )
